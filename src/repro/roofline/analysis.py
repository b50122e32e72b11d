"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh), all in seconds (TPU v5e constants):

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / ICI_bw

``cost_analysis()`` on an SPMD-partitioned module reports per-partition
FLOPs/bytes (verified empirically).  Collective bytes are NOT in
cost_analysis — we parse the optimized HLO text: every
all-gather/all-reduce/reduce-scatter/all-to-all/collective-permute line
contributes ``wire_bytes(op) × loop_multiplier``, where the multiplier
accounts for collectives living inside scan bodies (the layer scan runs
L times; the SSD chunk scan seq/chunk times) — XLA prints the loop body
once but executes it per trip.

Wire-bytes model per device (ring algorithms, group size g):
  all-gather       result_bytes × (g-1)/g      (received)
  reduce-scatter   result_bytes × (g-1)        (≈ input×(g-1)/g)
  all-reduce       2 × result_bytes × (g-1)/g
  all-to-all       result_bytes × (g-1)/g
  collective-permute  result_bytes
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?"                       # optional tuple result
    r"((?:[a-z0-9]+\[[0-9,]*\][^ ]*\s*)+)?"        # (unused) shapes blob
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(line: str) -> int:
    """Bytes of the result shape(s) — the text before the op name."""
    # result shapes appear between '=' and the op name
    m = re.search(r"=\s*(.*?)\s(all-gather|all-reduce|reduce-scatter|"
                  r"all-to-all|collective-permute)", line)
    if not m:
        return 0
    total = 0
    for dt, dims in _SHAPE_RE.findall(m.group(1)):
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
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def _wire_bytes(kind: str, result_bytes: int, g: int) -> float:
    if kind == "all-gather":
        return result_bytes * (g - 1) / g
    if kind == "reduce-scatter":
        return result_bytes * (g - 1)
    if kind == "all-reduce":
        return 2 * result_bytes * (g - 1) / g
    if kind == "all-to-all":
        return result_bytes * (g - 1) / g
    return float(result_bytes)          # collective-permute


def _loop_multiplier(line: str, trip_counts: List[int]) -> int:
    m = re.search(r'op_name="([^"]*)"', line)
    depth = m.group(1).count("/while/") if m else 0
    mult = 1
    for d in range(min(depth, len(trip_counts))):
        mult *= trip_counts[d]
    return mult


@dataclasses.dataclass
class CollectiveStats:
    total_bytes: float
    by_kind: Dict[str, float]
    op_count: int


def collective_bytes(hlo_text: str, trip_counts: List[int]) -> CollectiveStats:
    total, by_kind, count = 0.0, {}, 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        if kind + "-done" in line:
            continue
        rb = _shape_bytes(line)
        g = _group_size(line)
        wb = _wire_bytes(kind, rb, g) * _loop_multiplier(line, trip_counts)
        total += wb
        by_kind[kind] = by_kind.get(kind, 0.0) + wb
        count += 1
    return CollectiveStats(total, by_kind, count)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator generation."""
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # bytes/s
    ici_bw: float               # bytes/s per interconnect link
    source: str


# Keyed by ``jax.Device.device_kind``.  A device that is not here has no
# roofline: :func:`peaks` raises rather than borrowing another chip's.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e" (system '
               'architecture: per-chip peak compute, HBM, ICI)'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks for a device kind (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add them to "
            f"repro.roofline.analysis.PEAKS with their source") from None


# the dry run compiles for a v5e pod (repro.launch.mesh), so its terms
# use that chip's peaks
DRYRUN_DEVICE_KIND = "TPU v5 lite"


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    step: str
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    model_flops_global: float          # 6·N·D (dense) / 6·N_active·D (MoE)
    chips: int
    coll_by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)
    memory_per_device: Optional[dict] = None

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / peaks(DRYRUN_DEVICE_KIND).flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / peaks(DRYRUN_DEVICE_KIND).hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / peaks(DRYRUN_DEVICE_KIND).ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips): remat/redundancy waste probe."""
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "step": self.step, "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "coll_by_kind": self.coll_by_kind,
            "model_flops_global": self.model_flops_global,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "memory_per_device": self.memory_per_device,
        }


def model_flops(cfg, shape, kind: str) -> float:
    """Analytic 6ND model FLOPs for the step (per the roofline spec)."""
    n = cfg.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if kind in ("train", "prefill")
                                   else 1)
    if kind == "mpic_prefill":
        tokens = shape.global_batch * shape.seq_len // 8
    f = 2.0 * n * tokens                 # fwd matmuls
    if kind == "train":
        f *= 3.0                         # fwd + bwd ≈ 6ND
    return f
