"""Hardware peak table — the single source of truth for roofline math.

Keyed by the ``device_kind`` string JAX reports (``jax.devices()[0]
.device_kind``), one dict per chip: peak_flops (FLOP/s), hbm_bw (B/s), and
link_bw (B/s, one interconnect link, conservative).  Both the model
roofline (``analysis/roofline.py``) and the transaction-engine cost model
(``analysis/txn_cost.py``) read THESE numbers — a chip is added or
corrected in exactly one place.  A device that is not in the table is an
error (``peaks_for``), never a stand-in.

``ridge(kind)`` is the chip's arithmetic-intensity ridge point
(FLOP/byte): kernels below it are memory-bound, above it compute-bound.
"""
from __future__ import annotations

#: device_kind of a TPU v5e chip.
V5E = "TPU v5 lite"

HW_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s, 1,600 Gbit/s of interchip interconnect over 4 links
    # (50 GB/s per link).
    V5E: {"peak_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
    # A100 SXM 80G: bf16 tensor-core peak, HBM2e, one NVLink3 direction.
    "NVIDIA A100-SXM4-80GB": {"peak_flops": 312e12, "hbm_bw": 2039e9,
                              "link_bw": 300e9},
    # H100 SXM: bf16 tensor-core peak (dense), HBM3, one NVLink4 direction.
    "NVIDIA H100 80GB HBM3": {"peak_flops": 989e12, "hbm_bw": 3350e9,
                              "link_bw": 450e9},
}

# The analytic model roofline (analysis/roofline.py) is stated for a v5e.
PEAK_FLOPS = HW_PEAKS[V5E]["peak_flops"]
HBM_BW = HW_PEAKS[V5E]["hbm_bw"]
LINK_BW = HW_PEAKS[V5E]["link_bw"]


def peaks_for(kind: str) -> dict:
    """The peak row of device kind ``kind``; unknown kinds are an error."""
    try:
        return HW_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no peak table entry for device kind {kind!r} "
                         f"(known: {sorted(HW_PEAKS)})") from None


def ridge(kind: str = V5E) -> float:
    """Arithmetic-intensity ridge point (FLOP/byte) of ``kind``."""
    p = peaks_for(kind)
    return p["peak_flops"] / p["hbm_bw"]
