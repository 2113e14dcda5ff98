"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

A copy of ``repro.core.roofline.PEAKS``.  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
chip-to-chip interconnect.  A kind that is not in the table is an error.
"""

from __future__ import annotations

PEAKS = {
    # device_kind: (bf16 FLOP/s, HBM bytes/s, ICI bytes/s)
    "TPU v5 lite": (197e12, 819e9, 1600e9 / 8),
}


def peaks(device_kind: str) -> tuple[float, float, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """Percent of the roofline a kernel reached in ``seconds`` of device
    time for ``flops`` operations and ``nbytes`` of HBM traffic, and which
    of the two bounds it ("compute" or "memory")."""
    flop_s, bw, _ = peaks(device_kind)
    t_c, t_m = flops / flop_s, nbytes / bw
    return 100.0 * max(t_c, t_m) / seconds, "compute" if t_c >= t_m else "memory"
