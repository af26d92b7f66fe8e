"""Published per-chip peaks: the denominators of every roofline share and
of ``train_mfu``. Copied from ``rlo_tpu/utils/device.py`` (PR 21) so that a
later PR to the program cannot move the yardstick. A device kind that is
not in the table is an error, never a default."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float        # bytes
    source: str


#: keyed by ``jax.devices()[0].device_kind``
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2e at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> DevicePeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add a sourced row in a new benchmark PR "
            f"before measuring on it") from None


def least_time_s(flops: float, nbytes: float, pk: DevicePeaks):
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` bytes of HBM traffic, and which of the two bounds it."""
    t_c, t_m = flops / pk.bf16_flops, nbytes / pk.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
