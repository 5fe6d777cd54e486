"""Roofline terms from the compiled dry-run artifact, against per-chip peaks.

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_traffic_per_device / link_bw

plus MODEL_FLOPS = 6·N·D (train) / 2·N_active·D (inference) and the
usefulness ratio MODEL_FLOPS / (HLO_FLOPs × n_devices)."""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, with where they were published."""

    flops_bf16: float    # FLOP/s
    hbm_bw: float        # B/s
    ici_link_bw: float   # B/s per interconnect link
    hbm_bytes: float     # B of HBM per chip
    source: str


#: Peaks keyed by ``jax.Device.device_kind``. A kind missing here is an
#: error (:func:`chip_peaks`), never a default.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        ici_link_bw=50e9,
        hbm_bytes=16e9,
        source=(
            "Google Cloud TPU docs, 'TPU v5e': 197 TFLOP/s bf16, 16 GB HBM "
            "at 819 GB/s, 1,600 Gbit/s ICI over 4 links"
        ),
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peak table entry for ``device_kind``; raises for unknown kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"add it to repro.launch.roofline.PEAKS (known: {tuple(PEAKS)})"
        ) from None


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    n_devices: int
    model_flops_global: float
    peaks: ChipPeaks

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_link_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_device * self.n_devices
        return self.model_flops_global / total if total else 0.0

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilisation at the roofline step time."""
        t = self.step_time_s
        if t == 0:
            return 0.0
        return self.model_flops_global / (t * self.n_devices * self.peaks.flops_bf16)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "n_devices": self.n_devices,
            "model_flops_global": self.model_flops_global,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_step_s": self.step_time_s,
            "mfu_at_roofline": self.mfu,
        }


def expert_param_count(skeleton) -> int:
    """Parameters living on an 'experts' logical axis."""
    import jax
    from repro.models.param import ParamDef

    total = 0
    for leaf in jax.tree.leaves(skeleton, is_leaf=lambda x: isinstance(x, ParamDef)):
        if "experts" in leaf.logical_axes:
            total += int(np.prod(leaf.shape))
    return total


def model_flops(cfg, skeleton, kind: str, seq: int, batch: int) -> float:
    """6·N·D (train) / 2·N_active·D (prefill) / 2·N_active·B (decode)."""
    from repro.models.param import param_count

    n = param_count(skeleton)
    if cfg.moe is not None:
        e_params = expert_param_count(skeleton)
        active_frac = cfg.moe.top_k / cfg.moe.n_experts
        n = n - e_params + e_params * active_frac
    if kind == "train":
        return 6.0 * n * seq * batch
    if kind == "prefill":
        return 2.0 * n * seq * batch
    return 2.0 * n * batch  # decode: one token per request
