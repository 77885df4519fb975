"""What the run knows of the device: the chip check, the compile clock
(copied from ``chip_smoke.py``), peak memory and the table of peaks."""
from __future__ import annotations

import json
import os


class CompileClock:
    """Seconds JAX spent compiling (XLA backend compiles, from
    ``jax.monitoring``) since construction; ``count`` is the number of
    such compiles.  Copied from ``chip_smoke.py``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.live = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if self.live and event == self.EVENT:
            self.seconds += secs
            self.count += 1


def require_chips(chips: int) -> dict:
    """The result line's device record; exits (non-zero, no result) unless
    JAX runs on a TPU with at least ``chips`` devices.  Never falls back."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: JAX found no backend: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX runs on "
                     f"{devs[0].platform}: {devs}")
    if len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def peaks_for(kind: str, path: str) -> dict:
    """The row of ``peaks.json`` for a ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    rows = table["devices"]
    if kind not in rows:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(rows)}")
    return rows[kind]


def set_compile_cache(directory: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
