"""Span tracer and the naming contract of the compiled step (DESIGN.md §11).

**Host spans** — ``Tracer.span`` and the module-level ``span`` around
training-loop phases (``superstep`` with its children ``dispatch`` and
``loss_readback``; ``feed/wait``, ``feed/build``, ``feed/put``;
``checkpoint``, ``resize``, ``prefill``, ``decode``, ``autotune``), and
``Tracer.open``/``Tracer.complete`` for a lifecycle that ends in a later
call (a serve request, submit to evict).  Every span enters a
``jax.profiler.TraceAnnotation``, installed tracer or not, so under
``jax.profiler.start_trace`` it sits on the profiler's host plane beside
the device operations (an annotation costs well under a microsecond when
no profiler runs).  An installed ``Tracer`` also keeps its own copy for a
Chrome-trace/Perfetto ``trace.json``, stamped on the clock the profiler's
host events use (``time.time_ns``), so the two overlay.

Track layout (Perfetto): pid per subsystem (``train`` / ``serve`` /
``bench``), tid 0 = the host thread (``driver`` / ``engine``), further
tids per thread name (``feed``, ``slot0..S``).

**Device scopes** — the compiled step names its work with
``jax.named_scope``; the HLO keeps the scope in each instruction's
``op_name``:

======================  ==================================================
``{kind}{i}``           a Table-2 layer (``conv0``, ``pool1``, ... ``fc7``,
                        the names ``bucket_spec`` uses); forward under
                        autodiff reads ``jvp(conv2)/...``
``stem``, ``down{i}``,  a ConvNeXt layer (``models/convnext.py``, the names
``s{i}b{j}``, ``head``  ``bucket_spec`` uses): the stem, the downsample
                        before stage i, block j of stage i, the head
``s{i}b{j}/{sub}``      a ConvNeXt block's sublayer ``dwconv``, ``norm``
                        or ``mlp`` (``jvp(s1b2)/dwconv/...``); what a
                        block runs outside them is the block's own
backward of a layer     ``transpose(jvp(conv2))/...`` (autodiff, custom
                        VJPs included) or ``conv2/bwd/...`` (the explicit
                        saved-activation tape)
``loss``                the softmax cross-entropy and the error rate
``update``              what a sync strategy does once the gradients
                        exist: exchange, stale mixing, optimizer, boundary
``exchange/{bucket}``   one bucket's gradient exchange (inside ``update``,
                        or mid-backward on the interleaved schedule)
======================  ==================================================

``scope_of`` reads one ``op_name``; ``hlo_scopes`` a compiled module's
text.  Nothing here runs inside a compiled step: no host callback is ever
inserted, installed tracer or not.
"""
from __future__ import annotations

import json
import re
import threading
import time
from contextlib import contextmanager
from typing import Optional

from jax.profiler import TraceAnnotation

_LAYER = re.compile(r"(?:conv|pool|fc|down)\d+|stem|head")
_BLOCK = re.compile(r"s\d+b\d+")
_SUBLAYERS = ("dwconv", "norm", "mlp")
_WRAPPED = re.compile(r"(\w+)\((.*)\)")


def _unwrap(part: str) -> tuple[str, bool]:
    """``transpose(jvp(conv2))`` -> ``("conv2", True)``: the scope inside
    JAX's transformation wrappers, and whether one of them is a
    transpose (the backward pass)."""
    transposed = False
    m = _WRAPPED.fullmatch(part)
    while m:
        transposed |= m.group(1) == "transpose"
        part = m.group(2)
        m = _WRAPPED.fullmatch(part)
    return part, transposed


def scope_of(op_name: str) -> Optional[tuple[str, str]]:
    """``(scope, "fwd" | "bwd")`` of an HLO ``op_name``, or None when it
    carries no scope of the contract above.  The innermost scope wins
    (``update/exchange/conv2/...`` is ``exchange/conv2``, and
    ``s1b2/dwconv/...`` is ``s1b2/dwconv``); of a fused ``a;b`` name, the
    first part that has one."""
    for name in op_name.split(";"):
        parts = [_unwrap(p) for p in name.split("/")]
        found, transposed, i = None, False, 0
        while i < len(parts):
            part, t = parts[i]
            transposed |= t
            nxt = parts[i + 1][0] if i + 1 < len(parts) else None
            if part == "exchange" and nxt is not None:
                found = (f"exchange/{nxt}", transposed)
                i += 1
            elif _BLOCK.fullmatch(part) and nxt in _SUBLAYERS:
                found = (f"{part}/{nxt}", transposed or parts[i + 1][1])
                i += 1
            elif (_LAYER.fullmatch(part) or _BLOCK.fullmatch(part)
                  or part in ("loss", "update")):
                found = (part, transposed or nxt == "bwd")
            i += 1
        if found is not None:
            return found[0], "bwd" if found[1] else "fwd"
    return None


_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> dict[str, Optional[tuple[str, str]]]:
    """{instruction name: ``scope_of`` its op_name} for every instruction
    of a compiled HLO module's text.  A fusion takes the scope of its
    root; where the root has none (a ``lax.map`` writing a weight gradient
    into its stack), of its convolution or dot member, or else of its
    first scoped member."""
    own, calls, opcode = {}, {}, {}
    members: dict[str, list[str]] = {}
    root: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and " = " not in line:
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            members[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        is_root, name, rest = m.groups()
        op = _OPCODE.search(" " + rest.split(", metadata=")[0])
        opcode[name] = op.group(1) if op else ""
        on = _OP_NAME.search(rest)
        own[name] = scope_of(on.group(1)) if on else None
        members[comp].append(name)
        if is_root:
            root[comp] = name
        c = _CALLS.search(rest) if opcode[name] == "fusion" else None
        if c:
            calls[name] = c.group(1)

    memo: dict = {}

    def resolved(n):
        if n not in memo:
            memo[n] = own[n]
            body = calls.get(n)
            if body is not None:
                inner = members.get(body, [])
                heavy = [x for x in inner
                         if opcode[x] in ("convolution", "dot")]
                first = [root[body]] if body in root else []
                memo[n] = next((s for s in map(resolved, first + heavy
                                               + inner) if s), own[n])
        return memo[n]

    return {n: resolved(n) for n in own}


def _now_us() -> float:
    """Microseconds on the clock of the profiler's host events (since the
    Unix epoch), so ``trace.json`` overlays the ``.xplane.pb``."""
    return time.time_ns() * 1e-3


class Tracer:
    """Collects events in memory; ``write()`` exports trace.json + .jsonl.

    Thread-safe: spans come from the driver thread, the feed's producer
    thread and the serve engine loop."""

    def __init__(self, process: str = "train"):
        self.default_process = process
        self._lock = threading.Lock()
        self._events: list = []          # chrome "X"/"i"/"C" dicts
        self._pids: dict = {}            # process name -> pid
        self._tids: dict = {}            # (pid, thread name) -> tid

    # -- track bookkeeping --------------------------------------------------
    def _track(self, process: Optional[str], thread: str):
        process = process or self.default_process
        with self._lock:
            pid = self._pids.setdefault(process, len(self._pids) + 1)
            key = (pid, thread)
            if key not in self._tids:
                used = [t for (p, _), t in self._tids.items() if p == pid]
                self._tids[key] = (max(used) + 1) if used else 0
            return pid, self._tids[key]

    def _record(self, ev: dict, process, thread, args):
        ev["pid"], ev["tid"] = self._track(process, thread)
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    # -- host spans ---------------------------------------------------------
    @contextmanager
    def span(self, name: str, *, process: Optional[str] = None,
             thread: str = "driver", cat: str = "host", **args):
        with TraceAnnotation(name, **args):
            t0 = _now_us()
            try:
                yield self
            finally:
                t1 = _now_us()
                self._record({"name": name, "ph": "X", "ts": t0,
                              "dur": t1 - t0, "cat": cat},
                             process, thread, args)

    def open(self, name: str, **args) -> dict:
        """Begin a span that ``complete`` ends in a later call (a serve
        request's submit→evict window); its annotation starts now."""
        return {"name": name, "ts": _now_us(), "args": args,
                "annotation": TraceAnnotation(name, **args)}

    def complete(self, opened: dict, *, process: Optional[str] = None,
                 thread: str = "driver", cat: str = "host", **args):
        """End a span begun by ``open``; ``args`` join the opening ones."""
        opened["annotation"].__exit__(None, None, None)
        self._record({"name": opened["name"], "ph": "X",
                      "ts": opened["ts"], "dur": _now_us() - opened["ts"],
                      "cat": cat}, process, thread,
                     {**opened["args"], **args})

    def instant(self, name: str, *, process: Optional[str] = None,
                thread: str = "driver", cat: str = "host", **args):
        self._record({"name": name, "ph": "i", "s": "t", "ts": _now_us(),
                      "cat": cat}, process, thread, args)

    def counter(self, name: str, value: float, *,
                process: Optional[str] = None, thread: str = "driver"):
        """Chrome counter event — renders as a value track in Perfetto
        (e.g. per-superstep wall time, so a straggler is visible as a spike
        before any eviction fires)."""
        self._record({"name": name, "ph": "C", "ts": _now_us()},
                     process, thread, {"value": float(value)})

    # -- export -------------------------------------------------------------
    def to_chrome(self) -> dict:
        events = []
        with self._lock:
            pids = dict(self._pids)
            tids = dict(self._tids)
            spans = list(self._events)
        for name, pid in pids.items():
            events.append({"ph": "M", "name": "process_name", "pid": pid,
                           "tid": 0, "args": {"name": name}})
        for (pid, tname), tid in tids.items():
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        return {"traceEvents": events + spans, "displayTimeUnit": "ms"}

    def write(self, path: str):
        """Write Chrome-trace JSON to ``path`` and a flat JSONL (one event
        per line, the log-pipeline-friendly form) next to it."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f)
        jsonl = path + "l" if path.endswith(".json") else path + ".jsonl"
        with open(jsonl, "w") as f:
            for ev in doc["traceEvents"]:
                f.write(json.dumps(ev) + "\n")
        print(f"[obs] wrote {len(doc['traceEvents'])} trace events to "
              f"{path} (+ {jsonl})", flush=True)


# -- module-global active tracer --------------------------------------------
_ACTIVE: Optional[Tracer] = None


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or clear, with None) the process-wide tracer that the
    module-level ``span`` records into.  Returns the previous tracer."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, tracer
    return prev


def get_tracer() -> Optional[Tracer]:
    return _ACTIVE


@contextmanager
def span(name: str, **kw):
    """A profiler annotation always; ``Tracer.span`` on the installed
    tracer too, when there is one (yields it, else None)."""
    t = _ACTIVE
    if t is None:
        args = {k: v for k, v in kw.items()
                if k not in ("process", "thread", "cat")}
        with TraceAnnotation(name, **args):
            yield None
    else:
        with t.span(name, **kw):
            yield t
