"""What every driver does the same way: the compile cache, the compile
counter, the profiler slice, the device's memory peak, set-up time."""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Optional

from benchmark.harness import xplane
from benchmark.harness.manifest import ROOT


def configure_jax(run: Any) -> None:
    """The persistent compilation cache where the program's helper puts it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    with every program written to it, however quick its compile."""
    import jax

    from trustworthy_dl_tpu.utils.compile_cache import configure_compile_cache

    run.counters["compile_cache_dir"] = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Backend compilations and their seconds, through the program's
    ``obs.compilewatch.CompileRegistry``; ``mark_window`` splits the count
    into before and inside the window."""

    def __init__(self) -> None:
        from trustworthy_dl_tpu.obs.compilewatch import CompileRegistry

        self.registry = CompileRegistry().install()
        self._at_open: Optional[int] = None
        self._s_at_open = 0.0
        self._at_close: Optional[int] = None

    def mark_open(self) -> None:
        self._at_open = self.registry.total
        self._s_at_open = self.registry.total_seconds

    def mark_close(self) -> None:
        self._at_close = self.registry.total

    def into(self, run: Any) -> None:
        run.counters["compile_s"] = self._s_at_open
        run.counters["compiles_before_window"] = self._at_open
        run.counters["compiles_in_window"] = self._at_close - self._at_open
        self.registry.uninstall()


def setup_seconds(run: Any) -> float:
    """Process start to now (the window's opening)."""
    return time.time() - run.counters["process_start"]


def memory_peak_bytes(run: Any) -> int:
    """The peak on the fullest chip the cell uses: the allocator's
    ``peak_bytes_in_use`` plus ``peak_bytes_reserved``, the scratch that the
    TPU runtime reserves for a running program apart from the allocator's
    buffers (a step's temporaries live there).  The two peaks need not fall
    together, so this is an upper bound on the bytes held at one moment."""
    def peak(device: Any) -> int:
        # A backend with no memory statistics (the CPU of the tests) reads 0.
        stats = device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in run.devices)


def trace_dir() -> str:
    """Inside the checkout, fixed, emptied on every call."""
    path = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


class TracedSlice:
    """The profiler round a short slice of steady work, with the
    ``bench.traced`` annotation that bounds the traced window."""

    def __init__(self, run: Any):
        import jax

        self.run = run
        self.dir = trace_dir()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._ann = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._ann.__enter__()

    def finish(self) -> None:
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        trace = xplane.load(xplane.newest_xplane(self.dir))
        self.run.trace = xplane.summarize(trace)
        shutil.rmtree(self.dir, ignore_errors=True)
