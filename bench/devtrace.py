"""Reduce a JAX profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` file a traced run writes into a small
record: for each chip, its device operations as ``[name, category, start,
end]`` (nanoseconds), and the host's phases as ``[phase, start, end]``,
from the harness's own annotations.  The functions below work on that
record alone, so a test can feed them a recorded one.

A device operation's class comes from the compiled program's HLO text
(``hlo_classes``), which names every operation the trace shows:

* ``collective``: an exchange between chips (all-gather, all-reduce,
  reduce-scatter, collective-permute, all-to-all, send/recv), or a
  fusion holding one;
* ``conv``: a convolution with a spatial window, a fusion holding one, or
  a Pallas kernel (``tpu_custom_call``) that reads or writes a tensor of a
  conv layer's weight shape (the kernels carry no stable name yet);
* ``other``: everything else (tanh, pooling, the FC products, which XLA
  writes as window-less convolutions, the loss, the optimizer, loops).

An operation the HLO text does not name is classified by its name alone.
"""
from __future__ import annotations

import re

#: the harness's host annotations, in the order a superstep runs them
PHASES = ("feed_wait", "dispatch", "loss_readback")

_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce|reducescatter|\bsend\b|\brecv\b|send-done"
    r"|recv-done|\bcollective\b", re.I)
_CONV = re.compile(r"convolution|conv2d|conv_general|\bconv\b", re.I)


def classify(name: str, category: str = "") -> str:
    """The class of an operation from its name (and category) alone."""
    text = f"{name} {category}"
    if _COLLECTIVE.search(text):
        return "collective"
    if _CONV.search(text):
        return "conv"
    return "other"


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


_COLLECTIVE_OPS = {
    "all-gather", "all-gather-start", "all-gather-done", "all-reduce",
    "all-reduce-start", "all-reduce-done", "reduce-scatter",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "all-to-all", "send", "send-done", "recv",
    "recv-done"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_RANK = {"collective": 2, "conv": 1, "other": 0}


def hlo_classes(hlo_text: str, conv_weight_shapes=()) -> dict[str, str]:
    """{operation name: class} for every instruction of a compiled HLO
    module's text.  ``conv_weight_shapes`` are the conv layers' weight
    shapes ``(k, k, c_in, c_out)``, which mark a Pallas conv kernel."""
    weights = [re.compile(r"\[" + ",".join(map(str, w)) + r"\]")
               for w in conv_weight_shapes]
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    members: dict[str, list[str]] = {}
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
        name, rest = m.groups()
        op = _OPCODE.search(" " + rest.split(", metadata=")[0])
        opcode = op.group(1) if op else ""
        cls = "other"
        if opcode in _COLLECTIVE_OPS:
            cls = "collective"
        elif opcode == "convolution" and "window={" in rest:
            cls = "conv"
        elif (opcode == "custom-call" and "tpu_custom_call" in rest
              and any(w.search(rest) for w in weights)):
            cls = "conv"
        own[name] = cls
        members[comp].append(name)
        if opcode == "fusion":
            c = _CALLS.search(rest)
            if c:
                calls[name] = c.group(1)

    def comp_class(c: str) -> str:
        best = "other"
        for n in members.get(c, ()):
            k = resolved(n)
            if _RANK[k] > _RANK[best]:
                best = k
        return best

    memo: dict[str, str] = {}

    def resolved(n: str) -> str:
        if n not in memo:
            memo[n] = own[n]
            if n in calls:
                memo[n] = comp_class(calls[n])
        return memo[n]

    return {n: resolved(n) for n in own}


def load(path: str) -> dict:
    """Read an ``.xplane.pb``: device operations per chip from each TPU
    plane's "XLA Ops" line (the chip's trace carries no category, so it is
    left empty), and the harness's host phases."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append([ev.name, "", ev.start_ns,
                                ev.start_ns + ev.duration_ns])
            devices[m.group(1)] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PHASES:
                        host.append([ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns])
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end] intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    b = union(b)
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def window(rec: dict) -> tuple[float, float]:
    """The traced window: from the first host phase to the last."""
    starts = [s for _, s, _ in rec["host"]]
    ends = [e for _, _, e in rec["host"]]
    return min(starts), max(ends)


def leaves(ops) -> list[bool]:
    """For each operation, whether it is a leaf: it holds no other
    operation of its chip inside its interval (a loop that the trace shows
    around its body's operations is not a leaf)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    leaf = [True] * len(ops)
    stack: list[int] = []
    for i in order:
        s, e = ops[i][2], ops[i][3]
        while stack and ops[stack[-1]][3] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][3]:
            leaf[stack[-1]] = False
        stack.append(i)
    return leaf


class Reduced:
    """Sums over the traced window, per chip and averaged over chips.

    A class's time is the union of its leaf operations' intervals; the
    ``other`` class is the busy time that no convolution or collective
    leaf covers, which includes the self time of loops around them."""

    def __init__(self, rec: dict, lo: float | None = None,
                 hi: float | None = None, classes: dict | None = None):
        if lo is None or hi is None:
            lo, hi = window(rec)
        classes = classes or {}

        def cls(name, category):
            return classes.get(short_name(name)) or classify(name, category)

        self.lo, self.hi = lo, hi
        self.chips = sorted(rec["devices"], key=int)
        self.ops, self.leaf = {}, {}
        for d in self.chips:
            ops = rec["devices"][d]
            kept = [(short_name(n), cls(n, c), max(s, lo), min(e, hi),
                     leaf)
                    for (n, c, s, e), leaf in zip(ops, leaves(ops))
                    if min(e, hi) > max(s, lo)]
            self.ops[d] = [k[:4] for k in kept]
            self.leaf[d] = [k[4] for k in kept]
        self.host = sorted((s, e, p) for p, s, e in rec["host"])

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def _mean(self, per_chip) -> float:
        vals = [per_chip(d) for d in self.chips]
        return sum(vals) / len(vals) if vals else 0.0

    def _leaf_union(self, d, classes):
        return union((s, e) for (_, c, s, e), leaf in
                     zip(self.ops[d], self.leaf[d]) if leaf and c in classes)

    def busy_ns(self) -> float:
        """Union of device operation intervals, averaged over chips."""
        return self._mean(lambda d: length(union(
            (s, e) for _, _, s, e in self.ops[d])))

    def class_ns(self, cls: str) -> float:
        """Device time of one class, averaged over chips."""
        if cls != "other":
            return self._mean(lambda d: length(self._leaf_union(d, {cls})))
        return self._mean(lambda d: length(subtract(
            union((s, e) for _, _, s, e in self.ops[d]),
            self._leaf_union(d, {"conv", "collective"}))))

    def exposed_collective_ns(self) -> float:
        """Collective time during which no other leaf operation ran on
        that chip, averaged over chips."""
        return self._mean(lambda d: length(subtract(
            self._leaf_union(d, {"collective"}),
            self._leaf_union(d, {"conv", "other"}))))

    def top_ops(self, n: int = 10) -> list[list]:
        """[name, seconds] of the leaf operations that took most device
        time, summed over the window and averaged over chips."""
        tot: dict[str, float] = {}
        for d in self.chips:
            for (name, _, s, e), leaf in zip(self.ops[d], self.leaf[d]):
                if leaf:
                    tot[name] = tot.get(name, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        k = max(len(self.chips), 1)
        return [[name, t / k * 1e-9] for name, t in top]

    def phase_at(self, t: float) -> str:
        """The innermost host phase running at time ``t``."""
        best = None
        for s, e, p in self.host:
            if s > t:
                break
            if e >= t and (best is None or s >= best[0]):
                best = (s, p)
        return best[1] if best else "other"

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[host phase, seconds]: device idle time (on the first chip)
        summed by what the host was doing at each gap's midpoint,
        largest first."""
        if not self.chips:
            return []
        busy = union((s, e) for _, _, s, e in self.ops[self.chips[0]])
        gaps = subtract([(self.lo, self.hi)], busy)
        tot: dict[str, float] = {}
        for s, e in gaps:
            p = self.phase_at((s + e) / 2)
            tot[p] = tot.get(p, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[p, t * 1e-9] for p, t in top]
