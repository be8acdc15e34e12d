"""The device a run uses: its stamp, its published peaks, its memory peak,
and a counter of backend compiles.

Importing this module touches no device; ``require_tpu`` does.
"""
from __future__ import annotations

import threading
from typing import Dict

#: Published peaks of one chip, keyed by JAX's ``device_kind``.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, 'TPU v5e'",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}

#: The event JAX records around each backend compile (a persistent-cache
#: hit included: both hand the process a new executable).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> Dict:
    """Published peaks of ``kind``; an unknown kind is an error."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(PEAKS)})") from None


def require_tpu(chips: int) -> Dict:
    """Stamp of the devices; raises ``NoAccelerator`` without the chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX's first device is {devs[0].platform!r}, "
                            f"not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return stamp(chips)


def stamp(chips: int) -> Dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts backend compiles from JAX's monitoring events."""

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1

    def read(self) -> int:
        with self._lock:
            return self.count

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)
