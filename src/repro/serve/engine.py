"""Continuous-batching serve engine (DESIGN.md §9).

The CHAOS mapping: training kept every lane busy with thread+vector
parallelism; serving keeps the device busy by stepping ALL occupied cache
slots in one fixed-shape compiled dispatch per token, admitting queued
requests into free slots mid-flight (batched prefill) and evicting
finished sequences without recompiling anything.

Scheduler loop (one ``step()``):
  1. admit  — pop every arrived request that fits a free slot, prefill the
     group in ONE dispatch (whole right-padded prompts; ``q_offset`` keeps
     the causal mask honest), scatter the sub-cache into the slots, and
     take each row's first sampled token from the prefill logits at
     ``lengths-1`` — the prefill dispatch IS that token's decode.
  2. decode — one compiled dispatch over the whole slot batch with the
     per-slot cursor vector as ``cache_len``; greedy sampling is fused
     into the dispatch (no eager host-side argmax), so a request that
     generates ``gen`` tokens costs exactly 1 prefill + (gen-1) decode
     dispatches — the old per-token loop paid one extra trailing decode
     whose logits were discarded, plus a host sync per token.
  3. evict  — slots whose request hit ``max_new`` go back to the free
     list; idle slots keep decoding junk (harmless: causal rows are never
     fully masked, and admission overwrites the whole slot row).

Determinism: admission time is VIRTUAL (``step_dt`` seconds of clock per
decode step), sampling is greedy by default, and every per-row computation
is independent of its batch neighbours — so a (seed, trace) pair generates
identical tokens regardless of slot count or admission interleaving.
``temperature`` > 0 enables seeded sampling (optionally top-p nucleus)
fused into the same dispatches; its keys fold (request id, token position),
never the slot index, so the determinism contract survives sampling: same
(seed, trace) ⇒ same tokens, still slot-count-invariant.

Observability (DESIGN.md §11): with a ``tracer``/``bus`` attached the
engine emits the full admit→prefill→decode→evict lifecycle — a
``request/<rid>`` span per request on its slot's track, ``prefill``/
``decode`` dispatch spans on the engine track, slot-occupancy and
queue-depth gauges, TTFT/TPOT histograms, and dispatch/token counters.
Without them, no obs code runs at all.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.models.api import get_ops


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray          # (prompt_len,) int32
    max_new: int
    arrival: float = 0.0        # virtual seconds


@dataclasses.dataclass
class Finished:
    rid: int
    prompt_len: int
    tokens: np.ndarray          # (n_generated,) int32
    admit_step: int
    finish_step: int


def poisson_trace(seed: int, n: int, rate: float, vocab: int,
                  prompt_lens=(8, 32), max_new: int = 8) -> list:
    """Seeded Poisson request trace: exponential inter-arrivals at ``rate``
    requests per virtual second, uniform prompt lengths in ``prompt_lens``
    (inclusive), random token ids.  Deterministic in ``seed``."""
    rng = np.random.default_rng(seed)
    t, reqs = 0.0, []
    lo, hi = prompt_lens
    for i in range(n):
        t += float(rng.exponential(1.0 / rate))
        ln = int(rng.integers(lo, hi + 1))
        toks = rng.integers(0, vocab, size=(ln,)).astype(np.int32)
        reqs.append(Request(rid=i, tokens=toks, max_new=max_new, arrival=t))
    return reqs


class RequestFeed(threading.Thread):
    """Producer side of the feed/compute split (the superstep PrefetchFeed
    idiom from launch/train.py): replays a trace into a bounded queue so
    request ingest (tokenize/IO stand-in) overlaps the device loop.  With
    ``realtime=True`` it sleeps until each request's (scaled) arrival."""

    def __init__(self, trace, depth: int = 64, realtime: bool = False,
                 time_scale: float = 0.0):
        super().__init__(daemon=True)
        self.q = queue.Queue(maxsize=depth)
        self._trace = list(trace)
        self._realtime = realtime
        self._scale = time_scale
        self._stop = threading.Event()

    def run(self):
        t0 = time.time()
        for req in self._trace:
            if self._stop.is_set():
                return
            if self._realtime:
                lag = req.arrival * self._scale - (time.time() - t0)
                if lag > 0:
                    time.sleep(lag)
            self.q.put(req)
        self.q.put(None)                     # sentinel: trace exhausted

    def stop(self):
        self._stop.set()

    def drain(self) -> list:
        """Non-blocking: every request available right now."""
        out = []
        while True:
            try:
                item = self.q.get_nowait()
            except queue.Empty:
                return out
            if item is None:
                return out
            out.append(item)


def _pow2_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _make_sampler(temperature: float, top_p: float):
    """Seeded per-row sampler fused into the decode/prefill dispatches, or
    None for greedy (``temperature <= 0``).  Each row's key folds
    (request id, generated-token position) — never the slot index or the
    batch composition — so sampled tokens are deterministic in (seed,
    trace) and invariant to slot count, exactly like the greedy path."""
    if temperature <= 0.0:
        return None

    def sample_row(key, logits):
        l = logits.astype(jnp.float32) / jnp.float32(temperature)
        if top_p < 1.0:
            order = jnp.argsort(-l)
            ls = l[order]
            ps = jax.nn.softmax(ls)
            # nucleus: keep tokens whose PRECEDING cumulative mass < top_p
            # (the head token always survives, so the mask can't be empty)
            mass_before = jnp.cumsum(ps) - ps
            ls = jnp.where(mass_before < top_p, ls, -jnp.inf)
            return order[jax.random.categorical(key, ls)]
        return jax.random.categorical(key, l)

    def sample(base_key, rids, positions, logits):
        def one(rid, pos, lg):
            k = jax.random.fold_in(jax.random.fold_in(base_key, rid), pos)
            return sample_row(k, lg)
        return jax.vmap(one)(rids, positions, logits)

    return sample


class ServeEngine:
    """Continuous-batching engine over one model family.

    ``prefill_mode``: 'batched' (whole prompts, one dispatch — the fast
    path) or 'loop' (token-at-a-time reference, the pre-§9 serve loop,
    kept as the benchmark baseline).  ``use_kernel`` routes GQA prefill
    attention through the Pallas flash kernel (interpret-mode on CPU)."""

    def __init__(self, arch: str, *, slots: int = 4, max_seq: int = 128,
                 smoke: bool = True, seed: int = 0, step_dt: float = 1.0,
                 prefill_mode: str = "batched", use_kernel: bool = False,
                 params=None, temperature: float = 0.0, top_p: float = 1.0,
                 sample_seed: Optional[int] = None, tracer=None, bus=None):
        from repro.serve.cache import SlotKVCache
        if prefill_mode not in ("batched", "loop"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        self.cfg = C.smoke(arch) if smoke else C.get(arch)
        self.ops = get_ops(self.cfg)
        if self.ops.decode is None or self.ops.prefill is None:
            raise ValueError(f"{arch} ({self.cfg.family}) is not servable")
        self.params = (params if params is not None
                       else self.ops.init(jax.random.key(seed)))
        self.kv = SlotKVCache(self.ops, slots, max_seq)
        self.prefill_mode = prefill_mode
        self.use_kernel = use_kernel
        self.step_dt = step_dt
        self.clock = 0.0
        self.step_idx = 0
        self.pending: list = []              # sorted by arrival
        self.active: dict = {}               # slot -> state dict
        self.counters = {"prefill_dispatch": 0, "decode_dispatch": 0,
                         "prefill_tokens": 0, "decode_tokens": 0}
        self.last_tok = np.zeros((slots, 1), np.int32)
        self.slot_rid = np.zeros((slots,), np.int32)
        self._prefill_jit: dict = {}
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self._sampler = _make_sampler(self.temperature, self.top_p)
        self._sample_key = jax.random.key(
            seed if sample_seed is None else sample_seed)
        self.tracer = tracer
        self.bus = bus
        self._submit_span: dict = {}         # rid -> open request span
        self._submit_t: dict = {}            # rid -> submit time.monotonic()
        vocab = self.cfg.vocab_size
        sampler, skey = self._sampler, self._sample_key

        def _decode(params, cache, toks, cursors, rids, poss):
            logits, cache = self.ops.decode(params, cache, toks, cursors)
            lg = logits[:, -1, :vocab]
            nxt = (jnp.argmax(lg, axis=-1) if sampler is None
                   else sampler(skey, rids, poss, lg))
            return nxt.astype(jnp.int32)[:, None], cache

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        # single-row position-0 sampler for the loop-mode reference prefill
        # (parity with the batched path's fused first-token sampling)
        if sampler is not None:
            self._sample1 = jax.jit(lambda rid, lg: sampler(
                skey, rid[None], jnp.zeros((1,), jnp.int32), lg[None])[0])
        # token-at-a-time reference prefill step (cache_len as a traced
        # scalar so one program serves every position)
        self._decode_t1 = jax.jit(
            lambda p, c, t, cl: self.ops.decode(p, c, t, cl))

    # -- prefill ------------------------------------------------------------
    def _prefill_fn(self, A: int, T: int):
        key = (A, T)
        if key in self._prefill_jit:
            return self._prefill_jit[key]
        ops, vocab = self.ops, self.cfg.vocab_size
        sampler, skey = self._sampler, self._sample_key
        kw = ({"use_kernel": True} if self.use_kernel
              and self.cfg.family == "dense" else {})

        def fn(params, tokens, lengths, rids):
            sub = self.kv.zeros_like_sub(ops, A)
            logits, sub = ops.prefill(params, sub, tokens, lengths, 0, **kw)
            rows = jnp.arange(A)
            lg = logits[rows, lengths - 1, :vocab]
            nxt = (jnp.argmax(lg, axis=-1) if sampler is None
                   else sampler(skey, rids, jnp.zeros_like(rids), lg))
            return nxt.astype(jnp.int32)[:, None], sub

        self._prefill_jit[key] = jax.jit(fn)
        return self._prefill_jit[key]

    def _admit(self, reqs) -> None:
        tr, bus = self.tracer, self.bus
        slots = self.kv.alloc(len(reqs))
        lens = np.array([len(r.tokens) for r in reqs], np.int32)
        rids = np.array([r.rid for r in reqs], np.int32)
        ctx = (tr.span("prefill", thread="engine", cat="serve",
                       batch=len(reqs), tokens=int(lens.sum()),
                       mode=self.prefill_mode)
               if tr is not None else contextlib.nullcontext())
        with ctx:
            if self.prefill_mode == "batched":
                T = _pow2_bucket(int(lens.max()))
                if not self.kv.stateful:
                    # bucket padding writes [0, T) into every row's KV slot,
                    # so the bucket itself must fit (admitted rows already do)
                    T = min(T, self.kv.max_seq)
                toks = np.zeros((len(reqs), T), np.int32)
                for i, r in enumerate(reqs):
                    toks[i, :lens[i]] = r.tokens
                first, sub = self._prefill_fn(len(reqs), T)(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    jnp.asarray(rids))
                self.counters["prefill_dispatch"] += 1
                self.kv.adopt(sub, slots, lens)
                first = np.asarray(first)
            else:                            # token-at-a-time reference loop
                first = np.zeros((len(reqs), 1), np.int32)
                sub_rows = []
                for i, r in enumerate(reqs):
                    logits = None
                    row = self.kv.zeros_like_sub(self.ops, 1)
                    for t in range(lens[i]):
                        tok = jnp.asarray(r.tokens[t:t + 1][None])
                        logits, row = self._decode_t1(
                            self.params, row, tok, jnp.int32(t))
                        self.counters["prefill_dispatch"] += 1
                    lg = logits[0, -1, :self.cfg.vocab_size]
                    first[i, 0] = (int(jnp.argmax(lg))
                                   if self._sampler is None
                                   else int(self._sample1(
                                       jnp.int32(r.rid), lg)))
                    sub_rows.append(row)
                sub = jax.tree.map(lambda *xs: jnp.concatenate(xs, 1),
                                   *sub_rows)
                self.kv.adopt(sub, slots, lens)
        self.counters["prefill_tokens"] += int(lens.sum())
        if bus is not None:
            bus.counter("serve/prefill_dispatch")
            bus.counter("serve/prefill_tokens", int(lens.sum()))
        now = time.monotonic()
        for i, (r, s) in enumerate(zip(reqs, slots)):
            self.last_tok[s, 0] = first[i, 0]
            self.slot_rid[s] = r.rid
            st = {"req": r, "out": [int(first[i, 0])],
                  "admit_step": self.step_idx, "t_first": now}
            if tr is not None:
                st["span"] = (self._submit_span.pop(r.rid, None)
                              or tr.open(f"request/{r.rid}", rid=r.rid))
            if bus is not None:
                t_sub = self._submit_t.pop(r.rid, now)
                bus.observe("serve/ttft_s", now - t_sub)
            self.active[s] = st

    # -- scheduler ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.kv.validate_admit(len(req.tokens), req.max_new)
        if self.tracer is not None:
            self._submit_span[req.rid] = self.tracer.open(
                f"request/{req.rid}", rid=req.rid)
        if self.bus is not None:
            self._submit_t[req.rid] = time.monotonic()
        self.pending.append(req)
        self.pending.sort(key=lambda r: (r.arrival, r.rid))

    def _evict_done(self) -> list:
        tr, bus = self.tracer, self.bus
        done = []
        for slot in sorted(self.active):
            st = self.active[slot]
            if len(st["out"]) >= st["req"].max_new:
                done.append(Finished(
                    rid=st["req"].rid, prompt_len=len(st["req"].tokens),
                    tokens=np.array(st["out"], np.int32),
                    admit_step=st["admit_step"], finish_step=self.step_idx))
                if tr is not None:
                    tr.complete(st["span"], thread=f"slot{slot}",
                                cat="serve",
                                prompt_len=len(st["req"].tokens),
                                generated=len(st["out"]))
                if bus is not None:
                    n = len(st["out"])
                    if n > 1:
                        bus.observe("serve/tpot_s",
                                    (time.monotonic() - st["t_first"])
                                    / (n - 1))
                    bus.counter("serve/requests_done")
                del self.active[slot]
                self.kv.release(slot)
        return done

    def step(self) -> list:
        """One scheduler step: admit -> (maybe) decode -> evict.  Returns
        requests finished during this step."""
        if not self.active and self.pending:
            # idle engine: jump the virtual clock to the next arrival
            self.clock = max(self.clock, self.pending[0].arrival)
        grab = []
        while (self.pending and self.kv.free_count() > len(grab)
               and self.pending[0].arrival <= self.clock):
            grab.append(self.pending.pop(0))
        if grab:
            self._admit(grab)
        tr, bus = self.tracer, self.bus
        if bus is not None:
            bus.gauge("serve/slot_occupancy",
                      len(self.active) / self.kv.slots)
            bus.gauge("serve/queue_depth", len(self.pending))
        done = self._evict_done()            # max_new == 1 finishes here
        if not self.active:
            self.clock += self.step_dt
            self.step_idx += 1
            return done
        # the token being sampled is at position len(out): position 0 was
        # the prefill-fused first token, decode k samples position k
        poss = np.zeros((self.kv.slots,), np.int32)
        for slot, st in self.active.items():
            poss[slot] = len(st["out"])
        ctx = (tr.span("decode", thread="engine", cat="serve",
                       active=len(self.active), step=self.step_idx)
               if tr is not None else contextlib.nullcontext())
        with ctx:
            nxt, self.kv.tree = self._decode(
                self.params, self.kv.tree, jnp.asarray(self.last_tok),
                jnp.asarray(self.kv.cursors), jnp.asarray(self.slot_rid),
                jnp.asarray(poss))
            nxt = np.asarray(nxt)            # sync point (sampled on-device)
        self.counters["decode_dispatch"] += 1
        if bus is not None:
            bus.counter("serve/decode_dispatch")
            bus.counter("serve/decode_tokens", len(self.active))
        for slot, st in self.active.items():
            self.kv.cursors[slot] += 1
            st["out"].append(int(nxt[slot, 0]))
            self.last_tok[slot, 0] = nxt[slot, 0]
        self.counters["decode_tokens"] += len(self.active)
        done += self._evict_done()
        self.clock += self.step_dt
        self.step_idx += 1
        return done

    def run(self, trace=None) -> list:
        """Drive until every submitted/traced request finishes."""
        for r in (trace or []):
            self.submit(r)
        finished = []
        while self.pending or self.active:
            finished.extend(self.step())
        return sorted(finished, key=lambda f: f.rid)
