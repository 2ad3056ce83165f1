"""Three-term roofline model (TPU v5e-class target; CPU container derives
all terms from the compiled dry-run artifact, never from wall time).

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth
    collective = per-device link bytes / (links x link_bw)

`cost_analysis()` of an SPMD module reports per-device FLOPs/bytes, so the
'chips x' in the task formulas is already divided out.  Collective bytes
come from `perf.hlo.collective_bytes` (per-device operand bytes; all-reduce
counted 2x for its two ring phases).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

# hardware constants (task spec): 197 TFLOP/s bf16; 819 GB/s HBM; ~50 GB/s/link
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9
LINKS_PER_CHIP = 1  # conservative: one effective ICI link per chip
DCN_BW = 6.25e9     # cross-pod (multi-pod 'pod' axis) per-chip bandwidth
HBM_PER_CHIP = 16e9


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops_per_dev: float
    hbm_bytes_per_dev: float           # HLO-counted (no fusion model)
    coll_bytes_per_dev: float
    model_flops_per_dev: float     # 6*N*D (train) or 2*N*D (serve), / chips
    n_chips: int
    hbm_bytes_model_per_dev: float = 0.0   # analytic fused model (perf.hbm_model)
    per_kind: Dict[str, Dict[str, float]] = field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        """memory term used for bottleneck classification: the analytic
        fused model when available, else the raw HLO count."""
        b = self.hbm_bytes_model_per_dev or self.hbm_bytes_per_dev
        return b / HBM_BW

    @property
    def t_memory_hlo(self) -> float:
        return self.hbm_bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_dev / (LINKS_PER_CHIP * LINK_BW)

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def step_time(self) -> float:
        """perfect-overlap model: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute fraction of the modeled step: MODEL_FLOPS at peak
        vs modeled step time.  ==1 when compute-bound with zero waste."""
        ideal = self.model_flops_per_dev / PEAK_FLOPS
        return ideal / max(self.step_time, 1e-30)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops_per_dev / max(self.flops_per_dev, 1e-30)

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "n_chips": self.n_chips,
            "flops_per_dev": self.flops_per_dev,
            "hbm_bytes_per_dev": self.hbm_bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "model_flops_per_dev": self.model_flops_per_dev,
            "hbm_bytes_model_per_dev": self.hbm_bytes_model_per_dev,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_memory_hlo": self.t_memory_hlo,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "step_time_model": self.step_time,
            "roofline_fraction": self.roofline_fraction,
            "useful_flops_ratio": self.useful_flops_ratio,
            "per_kind_collectives": self.per_kind,
        }


def model_flops(n_active_params: float, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (forward-only serve)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens
