"""The chip: the table of peaks, the device check, compile and
persistent-cache counters, peak memory."""

from __future__ import annotations

import os
from pathlib import Path

#: Published peaks per chip, keyed by JAX's ``device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoChipError(RuntimeError):
    """JAX found no TPU, too few chips, or a chip without a peak entry."""


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise NoChipError(f"device kind {kind!r} has no entry in the peaks "
                          f"table {sorted(PEAKS)}")
    return PEAKS[kind]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins where it is set); every
    program is written, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root).resolve() / "bench" / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> dict:
    """The device description, or :class:`NoChipError` without a TPU or
    with fewer than ``chips`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChipError(f"cell needs {chips} chips, JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int | None:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compiles and persistent-cache hits and misses
    through JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, duration, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
