"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``; a kind
that is not there is an error, never a default.  A caller that models a
chip it is not running on (the CPU tests and dry runs model a v5e) names
the kind explicitly.

Terms (per device — the post-SPMD HLO module is the per-device program):
    compute term    = HLO_FLOPs / peak_FLOPs
    memory term     = HLO_bytes_accessed / HBM_bw
    collective term = effective_collective_bytes / link_bw
        where effective bytes = sum over collective ops of
        max(operand, result) local bytes, x2 for all-reduce (ring costs
        2(n-1)/n ~ 2 shard-volumes; others ~ 1).

collective bytes are parsed from the *optimized* HLO text since
cost_analysis does not expose them.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float     # peak bf16 FLOP/s per chip
    hbm_bw: float    # HBM bytes/s per chip
    ici_bw: float    # interconnect bytes/s per link


#: ``device_kind`` JAX reports for a TPU v5e chip.
V5E = "TPU v5 lite"

#: Published per-chip peaks.  TPU v5e: Google Cloud documentation, "TPU
#: v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
#: interconnect per chip (4 links of ~50 GB/s).
PEAKS = {
    V5E: Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to core/roofline.py::PEAKS with their source") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    """Bytes of the first shape literal in `text` (tuples: sum all)."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]
    effective_bytes: float
    ops: List[str]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts = {k: 0 for k in _COLLECTIVES}
    bytes_by_kind = {k: 0 for k in _COLLECTIVES}
    effective = 0.0
    ops = []
    for line in hlo_text.splitlines():
        ls = line.strip()
        # result-side instruction like:  %x = f32[..] all-reduce(...)
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        kind = m.group(2)
        if kind.rstrip("-start").rstrip("-done") in _COLLECTIVES:
            kind = kind.replace("-start", "").replace("-done", "")
        if kind not in _COLLECTIVES:
            continue
        if "-done" in ls.split("(")[0]:
            continue  # avoid double counting start/done pairs
        result_b = _shape_bytes(m.group(1))
        # operand shapes are inside the parens
        inner = ls[ls.index("(") + 1:]
        operand_b = _shape_bytes(inner)
        b = max(result_b, operand_b)
        counts[kind] += 1
        bytes_by_kind[kind] += b
        effective += b * (2.0 if kind == "all-reduce" else 1.0)
        ops.append(ls[:160])
    return CollectiveStats(counts, bytes_by_kind, effective, ops)


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    collective: CollectiveStats
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float = 0.0
    per_device_hbm_bytes: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """max(terms)/sum(terms): 1.0 = perfectly bound by one roof (ideal
        overlap); the dominant term alone is the achievable lower bound."""
        s = self.compute_s + self.memory_s + self.collective_s
        return self.bound_s / s if s else 0.0

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def to_dict(self):
        return {
            "flops_per_dev": self.flops,
            "bytes_per_dev": self.bytes_accessed,
            "collective_bytes_per_dev": self.collective.total_bytes,
            "collective_effective_bytes": self.collective.effective_bytes,
            "collective_counts": self.collective.counts,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "roofline_fraction": self.roofline_fraction,
            "model_flops_total": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "per_device_hbm_bytes": self.per_device_hbm_bytes,
        }


def analyze(compiled, *, n_devices: int, device_kind: str,
            model_flops_total: float = 0.0):
    """Build Roofline terms from a compiled executable, against the peaks
    of ``device_kind``.

    The partitioned HLO module is the per-device program, so cost_analysis
    FLOPs/bytes are per-device quantities already.
    """
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):    # older jax: one dict per program
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    mem = compiled.memory_analysis()
    hbm = 0.0
    if mem is not None:
        hbm = (getattr(mem, "argument_size_in_bytes", 0)
               + getattr(mem, "temp_size_in_bytes", 0)
               + getattr(mem, "output_size_in_bytes", 0)
               - getattr(mem, "alias_size_in_bytes", 0))
    model_flops_dev = model_flops_total / n_devices if n_devices else 0.0
    pk = peaks(device_kind)
    return Roofline(
        flops=flops, bytes_accessed=bytes_acc, collective=coll,
        compute_s=flops / pk.flops,
        memory_s=bytes_acc / pk.hbm_bw,
        collective_s=coll.effective_bytes / pk.ici_bw,
        model_flops=model_flops_dev,
        per_device_hbm_bytes=hbm,
    )


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D for training (N = active params),
    2*N per token for decode/prefill forward-only."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    if shape.kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


# ---------------------------------------------------------------------------
# Fused-attention (Pallas flash kernel) accounting — §Perf hypothesis H1.
#
# The pure-jnp blockwise attention materialises score/prob matrices through
# HBM (XLA cannot fuse across the two dots, on CPU *or* TPU).  The Pallas
# kernel (kernels/flash_attention.py) keeps them in VMEM.  Because XLA cost
# analysis cannot see inside a pallas_call, the optimized cell's terms are
# the measured baseline minus this analytic overhead:
#
#   passes_fwd  = 4   (s write + s read + p write + p read)
#   passes_bwd  = 10  (s w/r, p w + 2 reads, dp w/r, ds w + 2 reads)
#   score_bytes = passes * B * Hq * Tq * Tk * 4 / n_dev
#
# and, for causal attention, the kernel skips ~half the kv blocks that the
# jnp version computes-and-masks:
#
#   skipped_flops ~= 0.5 * attn_dot_flops   (fwd: 2 dots, bwd: 5 dots)
# ---------------------------------------------------------------------------
def attention_call_shapes(cfg, shape):
    """Yield (Hq, Tq, Tk, D, Dv, causal, n_calls) per attention site."""
    T = shape.seq_len
    if cfg.family in ("dense", "moe"):
        yield (cfg.n_heads, T, T, cfg.d_head, cfg.d_head, True, cfg.n_layers)
    elif cfg.family == "mla":
        d = cfg.qk_nope_dim + cfg.qk_rope_dim
        yield (cfg.n_heads, T, T, d, cfg.v_head_dim, True, cfg.n_layers)
    elif cfg.family == "vlm":
        Tt = T + cfg.n_patches
        yield (cfg.n_heads, Tt, Tt, cfg.d_head, cfg.d_head, True,
               cfg.n_layers)
    elif cfg.family == "hybrid":
        n_attn = cfg.n_layers // max(cfg.attn_every, 1)
        yield (cfg.n_heads, T, T, cfg.d_head, cfg.d_head, True, n_attn)
    elif cfg.family == "encdec":
        F = cfg.enc_frames
        yield (cfg.n_heads, F, F, cfg.d_head, cfg.d_head, False,
               cfg.n_enc_layers)               # encoder self
        yield (cfg.n_heads, T, T, cfg.d_head, cfg.d_head, True, cfg.n_layers)
        yield (cfg.n_heads, T, F, cfg.d_head, cfg.d_head, False,
               cfg.n_layers)                    # cross
    # ssm: no attention


def unfused_attention_overhead(cfg, shape, n_dev: int, train: bool):
    """Per-device (bytes, flops) that the Pallas flash kernel removes."""
    B = shape.global_batch
    passes = 4 + (10 if train else 0)
    dots = 2 + (5 if train else 0)
    bytes_total = 0.0
    flops_skip = 0.0
    for Hq, Tq, Tk, D, Dv, causal, n in attention_call_shapes(cfg, shape):
        elems = float(B) * Hq * Tq * Tk * n
        bytes_total += passes * elems * 4
        if causal:
            dot_flops = dots * 2.0 * elems * (D + Dv) / 2
            flops_skip += 0.5 * dot_flops
    return bytes_total / n_dev, flops_skip / n_dev
