"""The chip: its published peaks, the check that it is there, its memory.

The peaks are the benchmark's own table, keyed by ``device_kind`` as JAX
reports it. A device that is not in the table is an error, not a default:
a utilization against a guessed denominator is not a measurement.
"""

from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

EXIT_NO_CHIP = 3


class NoChip(Exception):
    """No TPU, or fewer chips than the cell asks for."""


def peaks_for(kind):
    if kind not in PEAKS:
        raise KeyError(
            f"device kind {kind!r} is not in perfbench/harness/devices.py "
            "PEAKS; add the chip there with the source of its peaks")
    return PEAKS[kind]


def require_chips(n, rehearse=False):
    """The ``n`` devices the cell runs on. Without a TPU, or with fewer
    chips than the cell asks for, the run ends here with a non-zero code
    and prints no result. ``rehearse`` takes whatever backend there is."""
    import jax
    devs = jax.devices()
    if not rehearse and jax.default_backend() != "tpu":
        raise NoChip(
            f"perfbench: JAX's default backend is {jax.default_backend()!r},"
            " not 'tpu'. The benchmark measures the chip and does not fall "
            "back to a CPU (use --rehearse to debug the harness).")
    if len(devs) < n:
        raise NoChip(f"perfbench: the cell needs {n} chip(s), JAX sees "
                     f"{len(devs)}")
    return devs[:n]


def device_report(devs):
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs):
    """Peak bytes on the fullest of ``devs``.

    This runtime books a program's temporaries as *reserved*, not *in
    use* (PERF.md, PR 21: 0.26 GB in use beside 3.39 GB reserved), so the
    chip's high-water mark is the sum of the two peaks. Returns
    ``(peak, per_device_stats)``; 0 where the backend reports nothing."""
    peaks, stats = [], []
    for d in devs:
        s = d.memory_stats() or {}
        stats.append({k: s.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit", "largest_alloc_size")})
        peaks.append(int(s.get("peak_bytes_in_use", 0) or 0)
                     + int(s.get("peak_bytes_reserved", 0) or 0))
    return max(peaks), stats
