"""Three-term roofline analysis from a compiled dry-run artifact.

    compute    = HLO_FLOPs        / (chips * Peaks.flops)
    memory     = HLO_bytes        / (chips * Peaks.hbm_bw)
    collective = collective_bytes / (chips * Peaks.link_bw)

``compiled.cost_analysis()`` supplies FLOPs and bytes accessed for the
*partitioned per-device* module (GSPMD compiles one per-device program), so
the terms below divide by per-chip peaks directly and treat the analysis as
per-chip.  collective_bytes is not in cost_analysis — we parse the
post-partitioning HLO text and sum *operand* sizes of every collective op
(operand size reconstructed from the result size and the op's semantics +
replica group size).

Hardware constants: per-chip peaks keyed by ``jax.Device.device_kind``
(:data:`PEAKS`); a device kind missing from the table is an error, never a
default.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM B/s per chip
    link_bw: float    # B/s per ICI link


#: ``device_kind`` of a TPU v5e chip, as JAX reports it
V5E = "TPU v5 lite"

#: Published per-chip peaks.  TPU v5e — Google Cloud documentation,
#: "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s
#: of inter-chip interconnect per chip over its 4 ICI links (50 GB/s each).
PEAKS: Dict[str, Peaks] = {
    V5E: Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for a device the
    table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

def dtype_bytes(dtype) -> int:
    """Bytes per element, accepting an HLO dtype name ("bf16", "f32"), a
    repro.core.precision policy-name ("bf16"/"f32" share HLO spelling), or
    anything jnp/np can make a dtype of.  Roofline consumers derive
    feature-plane byte counts from the precision policy through this
    instead of assuming 4 bytes/element."""
    if isinstance(dtype, str):
        if dtype in _DTYPE_BYTES:
            return _DTYPE_BYTES[dtype]
        raise ValueError(f"unknown dtype name {dtype!r}; "
                         f"known: {sorted(_DTYPE_BYTES)}")
    import numpy as np
    return int(np.dtype(dtype).itemsize)


# result shapes: one or a tuple of `dtype[d0,d1,...]`
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota v2: [num_groups,group_size]
        return int(m.group(2))
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-collective-type *operand* bytes per device, summed over the module.

    all-gather      : operand = result / group_size
    reduce-scatter  : operand = result * group_size
    all-reduce / all-to-all / collective-permute : operand = result
    ``-done`` ops are skipped (their ``-start`` pair was already counted).
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done(" in line:
            continue
        shapes, op = m.group(1), m.group(2)
        size = _shape_bytes(shapes)
        if size == 0:
            continue
        g = _group_size(line)
        if op == "all-gather" and g > 1:
            size = size // g
        elif op == "reduce-scatter":
            size = size * g
        out[op] = out.get(op, 0) + size
    return out


@dataclasses.dataclass(frozen=True)
class Roofline:
    name: str
    chips: int
    device_kind: str                  # key of PEAKS
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_by_type: Dict[str, float]
    model_flops: float = 0.0          # 6*N*D (active) — global, all chips
    peak_memory_bytes: float = 0.0    # per device, from memory_analysis

    @property
    def peaks(self) -> Peaks:
        return peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / self.peaks.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Roofline-optimal step time = max of the three terms (perfect
        overlap assumption)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS over all chips — catches remat and
        redundancy waste."""
        hlo_total = self.flops_per_device * self.chips
        return self.model_flops / hlo_total if hlo_total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline bound."""
        if self.t_bound == 0:
            return 0.0
        return (self.model_flops / self.chips / self.t_bound) / \
            self.peaks.flops

    def row(self) -> Dict:
        return {
            "name": self.name, "chips": self.chips,
            "device_kind": self.device_kind,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops_per_dev": self.flops_per_device,
            "useful_flops_frac": self.useful_flops_fraction,
            "mfu_bound": self.mfu_bound,
            "peak_memory_gb": self.peak_memory_bytes / 2 ** 30,
            "coll_by_type": self.coll_by_type,
        }


def from_dryrun(name: str, chips: int, cost: Dict, hlo_text: str,
                model_flops: float = 0.0,
                peak_memory_bytes: float = 0.0, *,
                device_kind: str) -> Roofline:
    coll = collective_bytes(hlo_text)
    return from_costs(name, chips, cost, coll, model_flops,
                      peak_memory_bytes, device_kind=device_kind)


def from_costs(name: str, chips: int, cost: Dict, coll_by_type: Dict,
               model_flops: float = 0.0,
               peak_memory_bytes: float = 0.0, *,
               device_kind: str) -> Roofline:
    peaks_for(device_kind)          # an unknown device fails here, loudly
    return Roofline(
        name=name, chips=chips, device_kind=device_kind,
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_device=float(sum(coll_by_type.values())),
        coll_by_type=dict(coll_by_type),
        model_flops=model_flops,
        peak_memory_bytes=peak_memory_bytes)


def extrapolate_costs(cost_1g: Dict, cost_2g: Dict, coll_1g: Dict,
                      coll_2g: Dict, n_groups: int):
    """Per-layer-group linear extrapolation of cost_analysis numbers.

    XLA's ``cost_analysis()`` counts a ``lax.scan`` body ONCE, so the
    scanned full-depth program under-reports flops/bytes/collectives by
    ~n_groups.  We instead lower UNROLLED 1-group and 2-group variants of
    the same config (identical remat policy) and extrapolate:

        total(G) = cost(1g) + (G - 1) * (cost(2g) - cost(1g))

    which is exact for homogeneous layer groups (all assigned archs) —
    the constant part (embed / logits / loss / their optimizer update)
    lives in cost(1g) and the per-group part in the delta.
    """
    def _extr(a, b):
        keys = set(a) | set(b)
        return {k: float(a.get(k, 0.0)) +
                (n_groups - 1) * (float(b.get(k, 0.0)) - float(a.get(k, 0.0)))
                for k in keys}
    return (_extr({k: v for k, v in cost_1g.items()
                   if isinstance(v, (int, float))},
                  {k: v for k, v in cost_2g.items()
                   if isinstance(v, (int, float))}),
            _extr(coll_1g, coll_2g))


def model_flops_for(cfg, shape) -> float:
    """6*N_active*D per trained token; 2*N_active*D per generated/prefilled
    token (fwd only).  D = tokens processed in the step.

    Prefill computes logits only for the LAST position, so the lm-head's
    2*V*d_model flops are charged once per sequence, not per token —
    without this the 'useful' flops exceed the compiled flops."""
    n = cfg.active_param_count()
    # the head matmul costs 2*V*D per scored position whether or not its
    # weights are tied to the embedding table
    head = cfg.d_model * cfg.vocab_size
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * (n - head) * toks + 2.0 * head * shape.global_batch
    toks = shape.global_batch  # decode: one token per sequence
    return 2.0 * n * toks
