"""The chip a run holds: the look for it, its published peaks, its memory."""

from __future__ import annotations

# Published peaks of one chip, keyed by JAX's ``device_kind``.  A kind that
# is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int):
    """The first ``chips`` TPU devices; raises ``NoAccelerator`` otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX's first device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[kind]


def describe(devices) -> dict:
    """The result line's ``device``: as JAX reports it."""
    import jax
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no statistics)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
