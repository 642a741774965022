"""The chip: the check that one is there, the compile cache, the compile
counter and the device's own readings.

Nothing here imports JAX at module level, so a test can import the
benchmark without loading an accelerator library.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List

from .spec import ROOT


class NoChip(RuntimeError):
    """JAX found no TPU, too few of them, or kernels in interpret mode."""


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``.  Every program is kept,
    however short its compile, so that a second run of a cell compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax

    from repro.kernels.backend import resolve_interpret
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX backend is {backend!r}, not a TPU")
    if resolve_interpret():
        raise NoChip("Pallas kernels would run in interpret mode "
                     f"(DAE_PALLAS_INTERPRET="
                     f"{os.environ.get('DAE_PALLAS_INTERPRET')!r})")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class CompileCounter:
    """Programs lowered or compiled while armed, from ``jax.monitoring``
    events (persistent-cache hits included: they are lowered too)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {e: 0 for e in self.EVENTS}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self.counts:
            with self._lock:
                self.counts[event] += 1

    @property
    def programs(self) -> int:
        """Lowerings, or backend compiles where more (a compile without a
        lowering is still a program built in the window)."""
        return max(self.counts.values())


def start_trace(trace_dir: str) -> None:
    """Start the profiler with device and runtime events only: the
    Python function tracer would slow the host several-fold and fill the
    trace with millions of events."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def trace_files(trace_dir: str) -> List[str]:
    out = []
    for dirpath, _, names in os.walk(trace_dir):
        out += [os.path.join(dirpath, n) for n in names
                if n.endswith(".xplane.pb")]
    return sorted(out)
