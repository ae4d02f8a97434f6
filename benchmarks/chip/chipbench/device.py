"""The chip a run needs, its peaks, its memory, and the compiler's clock."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

from chipbench.spec import BENCH_DIR

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> List:
    """The first ``chips`` TPU devices, or ``NoChip``.  Nothing is compiled
    or placed before this check."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX reports "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict:
    """Published peaks of one chip of ``device_kind``.  A kind that is not
    in ``peaks.json`` is an error, never a default."""
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(table)})")
    return table[device_kind]


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Counts XLA compilations (``compiles``; a persistent-cache load counts
    too) and persistent-cache misses, with the seconds they took, from the
    moment it is installed.  ``snapshot()`` marks a point to count from."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles, "cache_misses": self.misses,
                "compile_s": self.seconds}

    def since(self, snap: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)
