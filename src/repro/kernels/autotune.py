"""Block-size autotuner for the Pallas kernels (DESIGN.md §Kernels).

The paper hand-picks its vectorisation widths for one machine (Listing 1 is
written for 512-bit Xeon-Phi SIMD); the TPU analogue — how many batch rows,
output rows, and output channels each grid step keeps in VMEM — is
shape-dependent, so we search instead of hard-coding.

Two-phase design, because timing is impossible under ``jit`` tracing:

* ``tune_*`` entry points (called by ``benchmarks/run.py --only kernels``
  and tests) measure every candidate on real arrays, pick the fastest, and
  persist the result to an on-disk JSON cache keyed by
  ``op|shapes|dtype|backend|interpret``.
* ``get_conv_fwd_config`` / ``get_conv_bwd_config`` (called from
  ``kernels/ops.py`` on the training hot path, possibly inside a trace)
  return the cached winner when present, else a VMEM-budget heuristic.

Candidates are TPU-legal divisor block sizes pruned by a VMEM-footprint
estimate over padded (8, 128) tiles, and the heuristic default (what an
untuned run uses) is ALWAYS the first candidate, so the tuned pick is never
slower than the untuned one on the measurements it was chosen from.

Cache format (one JSON object)::

    {"<key>": {"config": {"batch_block": 8, "row_block": 13, ...},
               "us": 123.4,
               "candidates": {"<config-json>": us, ...},
               "timestamp": 1690000000.0}, ...}
"""
from __future__ import annotations

import functools
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import conv2d as K
from repro.kernels import fc as FCK
from repro.obs.trace import span as _obs_span


def _traced(fn):
    """Wrap a tune entry point in an ``autotune`` obs span (DESIGN.md §11)
    so kernel-tuning time lands on the trace timeline; no-op without an
    installed tracer."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with _obs_span("autotune", target=fn.__name__):
            return fn(*args, **kwargs)
    return wrapped

_MEM: dict[str, dict] = {}
# one-shot disk snapshot so cache misses on the eager hot path don't
# re-open the JSON file per conv call; reloaded when the path changes
_DISK: dict = {"path": None, "data": {}}

#: Mosaic's default scoped-VMEM limit is 16 MiB per core; leave headroom
#: for the compiler's own spills.  Estimates below count padded tiles.
VMEM_BUDGET_BYTES = int(os.environ.get("REPRO_VMEM_BUDGET", 12 * 2 ** 20))

FLASH_BASELINE = {"block_q": 512, "block_k": 512}


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save_disk(entries: dict) -> None:
    """Concurrent-writer-safe persist: re-merge against the file, write to a
    tmp file UNIQUE to this process (mkstemp), then atomically rename.  Two
    processes tuning the same net may each win some last-writer races on
    individual keys, but the cache file itself can never be torn/corrupt —
    a shared ``path + ".tmp"`` name would let two writers interleave bytes
    in one tmp file before the rename (tests/test_autotune_cache.py)."""
    import tempfile

    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    merged = _load_disk()
    merged.update(entries)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".autotune.", suffix=".tmp")
    try:
        # mkstemp creates 0600 scratch files; restore umask-based perms so
        # a shared cache path stays readable to other users/CI stages like
        # the plain open() it replaced
        umask = os.umask(0)
        os.umask(umask)
        os.fchmod(fd, 0o666 & ~umask)
        with os.fdopen(fd, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def key_for(op: str, shapes, dtype, *, interpret: bool,
            variant: str = "plain") -> str:
    """``variant`` distinguishes kernel flavours with different VMEM /
    compute profiles under the same shapes: the bias+tanh forward epilogue
    and the dtanh-fused backward (which carries an extra y slab)."""
    shp = "x".join("_".join(map(str, s)) for s in shapes)
    return (f"{op}|{variant}|{shp}|{jnp.dtype(dtype).name}"
            f"|{jax.default_backend()}|interp={int(interpret)}")


def lookup(key: str) -> dict | None:
    if key in _MEM:
        return _MEM[key]
    if _DISK["path"] != cache_path():
        _DISK["path"] = cache_path()
        _DISK["data"] = _load_disk()
    entry = _DISK["data"].get(key)
    if entry is not None:
        _MEM[key] = entry
    return entry


def record(key: str, config: dict, us: float, candidates: dict,
           iters: int = 1) -> dict:
    """Persist a tuning result.  A result measured with fewer timing
    iterations never overwrites one measured with more (so a --quick
    iters=1 run can't clobber a careful iters=3 tune with noise)."""
    existing = lookup(key)
    if existing is not None and existing.get("iters", 1) > iters:
        return existing
    entry = {"config": config, "us": us, "candidates": candidates,
             "iters": iters, "timestamp": time.time()}
    _MEM[key] = entry
    if _DISK["path"] == cache_path():
        _DISK["data"][key] = entry
    _save_disk({key: entry})
    return entry


def clear_memory_cache() -> None:
    _MEM.clear()
    _DISK["path"], _DISK["data"] = None, {}


# ---------------------------------------------------------------------------
# Candidate generation + VMEM footprint estimates
# ---------------------------------------------------------------------------
def _divisors(n: int, cap: int | None = None) -> list[int]:
    cap = n if cap is None else min(cap, n)
    return [d for d in range(1, cap + 1) if n % d == 0]


def _lane_blocks(n: int, cap: int | None = None) -> list[int]:
    """Block sizes a TPU accepts for a minor (lane) dim of extent ``n``:
    divisors up to ``cap`` that are multiples of 128 lanes, and the whole
    dim."""
    return sorted({d for d in _divisors(n, cap) if d % K.LANES == 0} | {n})


def _sublane_blocks(n: int, cap: int | None = None) -> list[int]:
    """Block sizes a TPU accepts for a second-minor (sublane) dim of extent
    ``n``: divisors up to ``cap`` that are multiples of 8, else the whole
    dim."""
    return ([d for d in _divisors(n, cap) if d % K.SUBLANES == 0]
            or [n])


def tiled_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of an array whose two minor dims are padded to whole
    (8 * 4 / itemsize, 128) tiles — a Cin=1 slab costs 128x its logical
    size."""
    *lead, sub, lane = (1,) * (2 - len(shape)) + tuple(shape)
    sub_tile = K.SUBLANES * 4 // itemsize
    return (math.prod(lead) * (-(-sub // sub_tile) * sub_tile)
            * (-(-lane // K.LANES) * K.LANES) * itemsize)


def conv_fwd_vmem_bytes(cfg, x_shape, w_shape, itemsize: int = 4) -> int:
    """Bytes per grid step: the double-buffered x slab, weight, bias and
    out blocks, plus the loaded slab, a tap patch and the fp32
    accumulator the kernel body keeps live."""
    B, H, W, Cin = x_shape
    Kk, _, _, Cout = w_shape
    Ho, Wo = H - Kk + 1, W - Kk + 1
    bb = K._divisor_block(B, cfg["batch_block"])
    rb = K._divisor_block(Ho, cfg["row_block"])
    cb = K._divisor_block(Cout, cfg["cout_block"])
    Wa = K.aligned(Wo)
    x = tiled_bytes((bb, rb + Kk - 1, Wa + Kk - 1, Cin), itemsize)
    blocks = (x + tiled_bytes((Kk, Kk, Cin, cb), itemsize)
              + tiled_bytes((1, cb), itemsize)
              + tiled_bytes((bb, rb, Wa, cb), itemsize))
    rows = bb * rb * Wa
    return (2 * blocks + x + tiled_bytes((rows, Cin), itemsize)
            + tiled_bytes((rows, cb)))


def conv_bwd_vmem_bytes(cfg, x_shape, w_shape, itemsize: int = 4,
                        fused_tanh: bool = True) -> int:
    """Bytes per grid step of the fused backward: double-buffered x, dy
    (+ y), w, dx, dw and db blocks, the dw/db scratch, and the loaded
    slabs, dz, tap patches and fp32 dx accumulator of the kernel body."""
    B, H, W, Cin = x_shape
    Kk, _, _, Cout = w_shape
    Wo = W - Kk + 1
    bb = K._divisor_block(B, cfg["batch_block"])
    rb = K._divisor_block(H, cfg["row_block"])
    Wa, Woa = K.aligned(W), K.aligned(Wo)
    n_dz = 2 if fused_tanh else 1
    x = tiled_bytes((bb, rb + Kk - 1, Woa + Kk - 1, Cin), itemsize)
    dz = tiled_bytes((bb, rb + Kk - 1, Wa + Kk - 1, Cout), itemsize)
    dw = tiled_bytes((Kk, Kk, Cin, Cout))
    db = tiled_bytes((1, Cout))
    blocks = (x + n_dz * dz + tiled_bytes((Kk, Kk, Cin, Cout), itemsize)
              + tiled_bytes((bb, rb, Wa, Cin), itemsize) + dw + db)
    dx_rows, dw_rows = bb * rb * Wa, bb * rb * Woa
    values = (x + (n_dz + 1) * dz + tiled_bytes((dx_rows, Cin))
              + tiled_bytes((dw_rows, Cout)) + tiled_bytes((dw_rows, Cin))
              + tiled_bytes((Cin, dw_rows)))
    return 2 * blocks + dw + db + values


def conv_fwd_candidates(x_shape, w_shape, itemsize: int = 4) -> list[dict]:
    """The heuristic default first, then every TPU-legal (bb, rb, cb) that
    fits the VMEM budget."""
    B, H, W, Cin = x_shape
    Kk, _, _, Cout = w_shape
    Ho = H - Kk + 1
    cands = [default_conv_fwd(x_shape, w_shape, itemsize)]
    for bb in _divisors(B, 16):
        for rb in _divisors(Ho):
            if rb < Kk and rb != Ho:      # halo would dominate the slab
                continue
            for cb in _lane_blocks(Cout):
                cfg = {"batch_block": bb, "row_block": rb, "cout_block": cb}
                if conv_fwd_vmem_bytes(cfg, x_shape, w_shape,
                                       itemsize) <= VMEM_BUDGET_BYTES:
                    cands.append(cfg)
    return _dedup(cands)


def conv_bwd_candidates(x_shape, w_shape, itemsize: int = 4) -> list[dict]:
    B, H, W, Cin = x_shape
    Kk = w_shape[0]
    cands = [default_conv_bwd(x_shape, w_shape, itemsize)]
    for bb in _divisors(B, 16):
        for rb in _divisors(H):
            if rb < Kk and rb != H:
                continue
            cfg = {"batch_block": bb, "row_block": rb}
            if conv_bwd_vmem_bytes(cfg, x_shape, w_shape,
                                   itemsize) <= VMEM_BUDGET_BYTES:
                cands.append(cfg)
    return _dedup(cands)


def fc_fwd_vmem_bytes(cfg, x_shape, w_shape, itemsize: int = 4) -> int:
    """Bytes per grid step: double-buffered x row block, w column block,
    bias block and output tile, plus the fp32 accumulator."""
    B, Din = x_shape
    _, Dout = w_shape
    bb = K._divisor_block(B, cfg["batch_block"])
    db = K._divisor_block(Dout, cfg["dout_block"])
    blocks = (tiled_bytes((bb, Din), itemsize)
              + tiled_bytes((Din, db), itemsize)
              + tiled_bytes((1, db), itemsize)
              + tiled_bytes((bb, db), itemsize))
    return 2 * blocks + tiled_bytes((bb, db))


def fc_bwd_vmem_bytes(cfg, x_shape, w_shape, itemsize: int = 4,
                      fused_tanh: bool = True) -> int:
    """Bytes per grid step of the fused backward: double-buffered x, dy
    (+ y), w, dx, dw and db blocks, the dw/db scratch, and the dz and
    transposed operands of the kernel body."""
    B, Din = x_shape
    _, Dout = w_shape
    bb = K._divisor_block(B, cfg["batch_block"])
    n_dy = 2 if fused_tanh else 1
    dw, db = tiled_bytes((Din, Dout)), tiled_bytes((1, Dout))
    blocks = (2 * tiled_bytes((bb, Din), itemsize)           # x, dx
              + n_dy * tiled_bytes((bb, Dout), itemsize)      # dy (+ y)
              + tiled_bytes((Din, Dout), itemsize) + dw + db)
    values = (tiled_bytes((bb, Dout)) + tiled_bytes((Dout, Din))
              + tiled_bytes((Din, bb)))
    return 2 * blocks + dw + db + values


def fc_fwd_candidates(x_shape, w_shape, itemsize: int = 4) -> list[dict]:
    B, _ = x_shape
    _, Dout = w_shape
    cands = [default_fc_fwd(x_shape, w_shape, itemsize)]
    for bb in _sublane_blocks(B, 64):
        for db in _lane_blocks(Dout, 512):
            cfg = {"batch_block": bb, "dout_block": db}
            if fc_fwd_vmem_bytes(cfg, x_shape, w_shape,
                                 itemsize) <= VMEM_BUDGET_BYTES:
                cands.append(cfg)
    return _dedup(cands)


def fc_bwd_candidates(x_shape, w_shape, itemsize: int = 4) -> list[dict]:
    B, _ = x_shape
    cands = [default_fc_bwd(x_shape, w_shape, itemsize)]
    for bb in _sublane_blocks(B, 64):
        cfg = {"batch_block": bb}
        if fc_bwd_vmem_bytes(cfg, x_shape, w_shape,
                             itemsize) <= VMEM_BUDGET_BYTES:
            cands.append(cfg)
    return _dedup(cands)


def flash_vmem_bytes(cfg, q_shape, k_shape) -> int:
    """Bytes per grid step of the flash forward: q/k/v tiles, the (bq, bk)
    score tile, and the fp32 (m, l, acc) scratch."""
    D, Dv = q_shape[3], k_shape[3]
    bq = min(cfg["block_q"], q_shape[2])
    bk = min(cfg["block_k"], k_shape[2])
    return 4 * (bq * D + bk * D + bk * Dv + bq * bk + bq * (Dv + 2))


def flash_candidates(q_shape, k_shape) -> list[dict]:
    """(block_q, block_k) candidates: power-of-two tiles up to the sequence
    lengths (the kernel clamps to Tq/Tk and pads non-divisors), pruned by
    VMEM footprint; the 512x512 baseline is always included."""
    Tq, Tk = q_shape[2], k_shape[2]
    sizes_q = sorted({min(s, Tq) for s in (64, 128, 256, 512)})
    sizes_k = sorted({min(s, Tk) for s in (64, 128, 256, 512)})
    cands = [dict(FLASH_BASELINE)]
    for bq in sizes_q:
        for bk in sizes_k:
            cfg = {"block_q": bq, "block_k": bk}
            if flash_vmem_bytes(cfg, q_shape, k_shape) <= VMEM_BUDGET_BYTES:
                cands.append(cfg)
    return _dedup(cands)


def get_flash_config(q_shape, k_shape, dtype, *, interpret: bool) -> dict:
    """Tuned (block_q, block_k) for the flash forward at kernel-layout
    shapes q (B, Hq, Tq, D) / k (B, Hkv, Tk, Dv); baseline when untuned."""
    entry = lookup(key_for("flash_fwd", (q_shape, k_shape), dtype,
                           interpret=interpret))
    if entry is not None:
        return entry["config"]
    return dict(FLASH_BASELINE)


def _dedup(cands: list[dict]) -> list[dict]:
    seen, out = set(), []
    for c in cands:
        key = json.dumps(c, sort_keys=True)
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


# ---------------------------------------------------------------------------
# Heuristic defaults (used when nothing has been tuned yet)
# ---------------------------------------------------------------------------
def _first_fit(configs, fits) -> dict:
    """The first config that ``fits``, else the last (smallest) one."""
    for cfg in configs:
        if fits(cfg):
            return cfg
    return cfg


def default_conv_fwd(x_shape, w_shape, itemsize: int = 4) -> dict:
    """Largest block that fits VMEM: start from the whole output map, all
    output channels and 8 images, and shrink the batch block first, then
    rows, then (lane-legal) output channels."""
    B, H, _, _ = x_shape
    Kk, _, _, Cout = w_shape
    Ho = H - Kk + 1
    return _first_fit(
        ({"batch_block": bb, "row_block": rb, "cout_block": cb}
         for cb in reversed(_lane_blocks(Cout))
         for rb in reversed(_divisors(Ho))
         for bb in reversed(_divisors(B, 8))),
        lambda c: conv_fwd_vmem_bytes(c, x_shape, w_shape,
                                      itemsize) <= VMEM_BUDGET_BYTES)


def default_conv_bwd(x_shape, w_shape, itemsize: int = 4) -> dict:
    """As ``default_conv_fwd``: whole input map and 8 images, shrinking the
    batch block first, then rows."""
    B, H, _, _ = x_shape
    return _first_fit(
        ({"batch_block": bb, "row_block": rb}
         for rb in reversed(_divisors(H))
         for bb in reversed(_divisors(B, 8))),
        lambda c: conv_bwd_vmem_bytes(c, x_shape, w_shape,
                                      itemsize) <= VMEM_BUDGET_BYTES)


def default_fc_fwd(x_shape, w_shape, itemsize: int = 4) -> dict:
    """Largest whole-row block that fits VMEM: shrink the (lane-legal)
    output column block first, then the (sublane-legal) batch block."""
    B, _ = x_shape
    _, Dout = w_shape
    return _first_fit(
        ({"batch_block": bb, "dout_block": db}
         for bb in reversed(_sublane_blocks(B, 8))
         for db in reversed(_lane_blocks(Dout))),
        lambda c: fc_fwd_vmem_bytes(c, x_shape, w_shape,
                                    itemsize) <= VMEM_BUDGET_BYTES)


def default_fc_bwd(x_shape, w_shape, itemsize: int = 4) -> dict:
    B, _ = x_shape
    return _first_fit(
        ({"batch_block": bb} for bb in reversed(_sublane_blocks(B, 8))),
        lambda c: fc_bwd_vmem_bytes(c, x_shape, w_shape,
                                    itemsize) <= VMEM_BUDGET_BYTES)


def get_conv_fwd_config(x_shape, w_shape, dtype, *, interpret: bool,
                        variant: str = "plain") -> dict:
    entry = lookup(key_for("conv_fwd", (x_shape, w_shape), dtype,
                           interpret=interpret, variant=variant))
    if entry is not None:
        return entry["config"]
    return default_conv_fwd(x_shape, w_shape, jnp.dtype(dtype).itemsize)


def get_conv_bwd_config(x_shape, w_shape, dtype, *, interpret: bool,
                        variant: str = "plain") -> dict:
    entry = lookup(key_for("conv_bwd", (x_shape, w_shape), dtype,
                           interpret=interpret, variant=variant))
    if entry is not None:
        return entry["config"]
    return default_conv_bwd(x_shape, w_shape, jnp.dtype(dtype).itemsize)


def get_fc_fwd_config(x_shape, w_shape, dtype, *, interpret: bool,
                      variant: str = "plain") -> dict:
    entry = lookup(key_for("fc_fwd", (x_shape, w_shape), dtype,
                           interpret=interpret, variant=variant))
    if entry is not None:
        return entry["config"]
    return default_fc_fwd(x_shape, w_shape, jnp.dtype(dtype).itemsize)


def get_fc_bwd_config(x_shape, w_shape, dtype, *, interpret: bool,
                      variant: str = "plain") -> dict:
    entry = lookup(key_for("fc_bwd", (x_shape, w_shape), dtype,
                           interpret=interpret, variant=variant))
    if entry is not None:
        return entry["config"]
    return default_fc_bwd(x_shape, w_shape, jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------
def _time_us(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


@_traced
def tune_conv_fwd(x, w, bias=None, *, activation: str | None = None,
                  interpret: bool, iters: int = 3,
                  max_candidates: int | None = None):
    """Measure all pruned candidates for the forward kernel; cache + return
    ``(best_config, report)``.  The baseline is always measured, so
    ``best_us <= baseline_us`` by construction."""
    variant = "bias_tanh" if activation == "tanh" else "plain"
    key = key_for("conv_fwd", (x.shape, w.shape), x.dtype,
                  interpret=interpret, variant=variant)
    cands = conv_fwd_candidates(x.shape, w.shape, x.dtype.itemsize)
    if max_candidates:
        cands = cands[:max_candidates]
    measured = {}
    for cfg in cands:
        fn = jax.jit(lambda x, w, cfg=cfg: K.conv2d_fwd(
            x, w, bias, activation=activation, interpret=interpret, **cfg))
        measured[json.dumps(cfg, sort_keys=True)] = _time_us(
            fn, x, w, iters=iters)
    best_key = min(measured, key=measured.get)
    best = json.loads(best_key)
    record(key, best, measured[best_key], measured, iters=iters)
    return best, {"key": key, "best_us": measured[best_key],
                  "baseline_us": measured[json.dumps(cands[0],
                                                     sort_keys=True)],
                  "candidates": measured}


@_traced
def tune_conv_bwd(x, dy, w, y=None, *, interpret: bool,
                  iters: int = 3, max_candidates: int | None = None):
    """Measure candidates for the fused backward kernel (dtanh-fused when
    ``y`` is given); cache + return ``(best_config, report)``."""
    variant = "dtanh" if y is not None else "plain"
    key = key_for("conv_bwd", (x.shape, w.shape), x.dtype,
                  interpret=interpret, variant=variant)
    cands = conv_bwd_candidates(x.shape, w.shape, x.dtype.itemsize)
    if max_candidates:
        cands = cands[:max_candidates]
    measured = {}
    for cfg in cands:
        fn = jax.jit(lambda x, dy, w, cfg=cfg: K.conv2d_bwd_fused(
            x, dy, w, y, interpret=interpret, **cfg))
        measured[json.dumps(cfg, sort_keys=True)] = _time_us(
            fn, x, dy, w, iters=iters)
    best_key = min(measured, key=measured.get)
    best = json.loads(best_key)
    record(key, best, measured[best_key], measured, iters=iters)
    return best, {"key": key, "best_us": measured[best_key],
                  "baseline_us": measured[json.dumps(cands[0],
                                                     sort_keys=True)],
                  "candidates": measured}


@_traced
def tune_fc_fwd(x, w, bias=None, *, activation: str | None = None,
                interpret: bool, iters: int = 3,
                max_candidates: int | None = None):
    """Measure all pruned candidates for the fused FC forward; cache +
    return ``(best_config, report)``.  Same contract as the conv tuners:
    the batch_block=8 whole-row baseline is always measured."""
    variant = "bias_tanh" if activation == "tanh" else "plain"
    key = key_for("fc_fwd", (x.shape, w.shape), x.dtype,
                  interpret=interpret, variant=variant)
    cands = fc_fwd_candidates(x.shape, w.shape, x.dtype.itemsize)
    if max_candidates:
        cands = cands[:max_candidates]
    measured = {}
    for cfg in cands:
        fn = jax.jit(lambda x, w, cfg=cfg: FCK.fc_fwd(
            x, w, bias, activation=activation, interpret=interpret, **cfg))
        measured[json.dumps(cfg, sort_keys=True)] = _time_us(
            fn, x, w, iters=iters)
    best_key = min(measured, key=measured.get)
    best = json.loads(best_key)
    record(key, best, measured[best_key], measured, iters=iters)
    return best, {"key": key, "best_us": measured[best_key],
                  "baseline_us": measured[json.dumps(cands[0],
                                                     sort_keys=True)],
                  "candidates": measured}


@_traced
def tune_fc_bwd(x, dy, w, y=None, *, interpret: bool, iters: int = 3,
                max_candidates: int | None = None):
    """Measure candidates for the fused FC backward (dtanh-fused when ``y``
    is given); cache + return ``(best_config, report)``."""
    variant = "dtanh" if y is not None else "plain"
    key = key_for("fc_bwd", (x.shape, w.shape), x.dtype,
                  interpret=interpret, variant=variant)
    cands = fc_bwd_candidates(x.shape, w.shape, x.dtype.itemsize)
    if max_candidates:
        cands = cands[:max_candidates]
    measured = {}
    for cfg in cands:
        fn = jax.jit(lambda x, dy, w, cfg=cfg: FCK.fc_bwd_fused(
            x, dy, w, y, interpret=interpret, **cfg))
        measured[json.dumps(cfg, sort_keys=True)] = _time_us(
            fn, x, dy, w, iters=iters)
    best_key = min(measured, key=measured.get)
    best = json.loads(best_key)
    record(key, best, measured[best_key], measured, iters=iters)
    return best, {"key": key, "best_us": measured[best_key],
                  "baseline_us": measured[json.dumps(cands[0],
                                                     sort_keys=True)],
                  "candidates": measured}


@_traced
def tune_flash_attention(q, k, v, *, causal: bool = True,
                         interpret: bool, iters: int = 3,
                         max_candidates: int | None = None):
    """Measure (block_q, block_k) candidates for the Pallas flash forward
    (q, k, v in kernel layout (B, H, T, D)); cache + return
    ``(best_config, report)``.  Same contract as the conv/FC tuners: the
    512x512 baseline is always measured, so ``best_us <= baseline_us``."""
    from repro.kernels import flash_attention as FA

    key = key_for("flash_fwd", (q.shape, k.shape), q.dtype,
                  interpret=interpret)
    cands = flash_candidates(q.shape, k.shape)
    if max_candidates:
        cands = cands[:max_candidates]
    measured = {}
    for cfg in cands:
        fn = jax.jit(lambda q, k, v, cfg=cfg: FA.flash_attention_fwd(
            q, k, v, causal=causal, interpret=interpret, **cfg))
        measured[json.dumps(cfg, sort_keys=True)] = _time_us(
            fn, q, k, v, iters=iters)
    best_key = min(measured, key=measured.get)
    best = json.loads(best_key)
    record(key, best, measured[best_key], measured, iters=iters)
    return best, {"key": key, "best_us": measured[best_key],
                  "baseline_us": measured[json.dumps(dict(FLASH_BASELINE),
                                                     sort_keys=True)],
                  "candidates": measured}


def tune_lm_attention(cfg, batch: int, seq: int, *, interpret: bool,
                      iters: int = 1):
    """Tune the flash forward at an LM config's training attention shape —
    exactly the cache key ``flash_attention_train`` looks up (q is
    (batch, n_heads, seq, d_head) after the BTHD -> BHTD transpose).  The
    worker mesh runs per-shard batches, so callers pass the per-shard
    batch.  Returns the list of cache keys written."""
    dtype = jnp.dtype(cfg.param_dtype)
    kk = jax.random.key(0)
    q = jax.random.normal(kk, (batch, cfg.n_heads, seq, cfg.d_head), dtype)
    k = jax.random.normal(kk, (batch, cfg.n_kv_heads, seq, cfg.d_head),
                          dtype)
    v = jax.random.normal(kk, (batch, cfg.n_kv_heads, seq, cfg.d_head),
                          dtype)
    _, rep = tune_flash_attention(q, k, v, causal=True, iters=iters,
                                  interpret=interpret)
    return [rep["key"]]


def tune_cnn_net(cfg, batch: int, *, interpret: bool, iters: int = 1):
    """Tune every fused conv/FC kernel of a Table-2 CNN at the given batch
    size, populating exactly the cache keys the training path looks up.

    The worker-mesh route (DESIGN.md §4) shards the global batch into
    ``WorkerConfig.logical_shards`` micro-shards, so its kernels run at a
    per-shard batch (e.g. 1) whose autotune keys differ from the full-batch
    keys ``benchmarks/run.py --only kernels`` populates — scaling runs call
    this first so kernel-on cells measure tuned configs, not the heuristic
    fallback.  Returns the list of cache keys written."""
    from repro.models.cnn import _trace_shapes  # local: avoid import cycle

    keys = []
    h = cfg.cnn_input[0]  # input spatial size of the NEXT layer
    kk = jax.random.key(0)
    shapes = _trace_shapes(cfg)
    for i, (kind, k, h_out, cin, cout) in enumerate(shapes):
        if kind == "conv":
            x = jax.random.normal(kk, (batch, h, h, cin), jnp.float32)
            w = jax.random.normal(kk, (k, k, cin, cout), jnp.float32) * 0.1
            b = jnp.zeros((cout,), jnp.float32)
            dy = jax.random.normal(kk, (batch, h_out, h_out, cout),
                                   jnp.float32)
            y = jnp.tanh(dy)
            _, rep = tune_conv_fwd(x, w, b, activation="tanh", iters=iters,
                                   interpret=interpret)
            keys.append(rep["key"])
            _, rep = tune_conv_bwd(x, dy, w, y, iters=iters,
                                   interpret=interpret)
            keys.append(rep["key"])
            h = h_out
        elif kind == "pool":
            h = h_out
        else:  # fc — tanh epilogue on hidden layers, plain on the head
            x = jax.random.normal(kk, (batch, cin), jnp.float32)
            w = jax.random.normal(kk, (cin, cout), jnp.float32) * 0.1
            b = jnp.zeros((cout,), jnp.float32)
            dy = jax.random.normal(kk, (batch, cout), jnp.float32)
            # positional, matching models/cnn.py::forward's head test —
            # a hidden fc as wide as n_classes must still tune the tanh
            # variants the model actually launches
            last = i == len(shapes) - 1
            act = None if last else "tanh"
            _, rep = tune_fc_fwd(x, w, b, activation=act, iters=iters,
                                 interpret=interpret)
            keys.append(rep["key"])
            _, rep = tune_fc_bwd(x, dy, w, None if last else jnp.tanh(dy),
                                 iters=iters, interpret=interpret)
            keys.append(rep["key"])
            h = 1
    return keys
