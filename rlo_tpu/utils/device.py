"""The device the program runs on: what it is, what it can do at peak,
and where its compiled programs are kept.

One rule (docs/DESIGN.md §4): the program never chooses a platform in
code. The CPU is selected only from outside, by the test harness
(``JAX_PLATFORMS=cpu`` plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; tests/conftest.py
is the single in-tree place that does it). Entry points that print a
device metric call ``require_tpu`` and fail on anything else; multi-device
entry points call ``require_devices`` and fail on a shortfall instead of
retargeting.

Nothing here runs at ``import rlo_tpu``; every function touches JAX only
when called.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks; the denominators of every utilization
    and roofline figure the benchmarks print."""
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float        # bytes
    source: str


#: keyed by ``jax.devices()[0].device_kind``. A device that is not in the
#: table is an error, never a default.
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2e at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; KeyError-free: an unknown kind raises a
    ValueError that names the kinds the table has."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add a sourced row to "
            f"rlo_tpu/utils/device.py before printing a utilization for "
            f"it") from None


def require_tpu() -> DevicePeaks:
    """Fail unless the live backend is a TPU of a known kind. Returns
    its peaks. Called by every entry point that prints a device metric,
    before its first compile."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"this entry point measures the accelerator and the live JAX "
            f"backend is {backend!r} ({jax.devices()[0].device_kind}); it "
            f"does not fall back. Run it on the chip, or use the entry "
            f"point's test-shape flag for a CPU check")
    return peaks(jax.devices()[0].device_kind)


def bench_device(test_shapes: bool = False):
    """What a benchmark entry point calls before its first jit: places
    the compile cache and returns ``(label, peaks)`` — the device_kind
    for metric strings and its table row. A non-TPU backend is refused
    unless the run uses test shapes (``--tiny``): then the label says so
    and peaks is None, so no utilization can be printed against it."""
    import jax
    enable_compile_cache()
    backend = jax.default_backend()
    if test_shapes and backend != "tpu":
        return f"{backend}, test shapes", None
    return jax.devices()[0].device_kind, require_tpu()


def require_devices(n: int) -> None:
    """Fail when fewer than ``n`` devices are live — never retarget."""
    import jax
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"need {n} devices, the {jax.default_backend()!r} backend has "
            f"{have}. For a CPU-mesh run start the process with "
            f"JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")


def describe() -> dict:
    """Platform, kind, count and coords of the live devices plus the
    jax/jaxlib/libtpu versions — the header every chip run prints."""
    import importlib.metadata as md

    import jax
    import jaxlib
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "coords": [list(getattr(d, "coords", ())) for d in devs],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": md.version("libtpu"),
    }


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere that outlives
    the process, and return the directory in use. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing
    is set in code; otherwise the cache lives at ``<checkout>/.jax_cache``
    — a fixed path, because a directory that moves never hits. Call once
    from a main, before the first jit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


if __name__ == "__main__":
    import json
    print(json.dumps(describe()))
