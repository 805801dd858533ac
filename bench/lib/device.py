"""The device guard and the table of peaks, keyed by ``device_kind``."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": per chip, bf16 and int8 peak
# operations per second, HBM bandwidth and capacity
PEAKS = {
    "TPU v5 lite": dict(bf16_flops=197e12, int8_ops=393e12,
                        hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


class NoChip(Exception):
    """The machine has no accelerator this benchmark measures."""


def peaks(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown kind is an error."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise NoChip(f"no peaks for device kind {kind!r}; known: "
                     f"{sorted(PEAKS)}") from None


def require_chips(jax, chips: int):
    """The first ``chips`` TPUs JAX sees, or :class:`NoChip`."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPUs, JAX found "
                     f"{len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:chips]


def describe(devices, trace: dict | None = None) -> dict:
    """The result's ``device``: as JAX reports it, with the peak memory of
    the fullest chip and, from a trace, busy and traced seconds."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out
