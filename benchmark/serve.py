"""The coordinator, started as an operator starts it.

    python benchmark/serve.py -c <config.toml>

calls the same ``xaynet_tpu.server.runner.main()`` that ``python -m
xaynet_tpu.server.runner -c <config.toml>`` calls, and adds nothing but a
profiler window for the traced run: with ``BENCH_TRACE_DIR`` set, SIGUSR1
starts ``jax.profiler.start_trace`` there and SIGUSR2 (or
``BENCH_TRACE_MAX_S`` seconds) stops it, from a thread of its own so that
the event loop serving the API never waits for the profiler. The window's
ends, on this process's clocks, are written beside the trace.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
WINDOW_SPAN = "bench.window"  # benchmark/harness/xtrace.py looks for this name


def arm_profiler(trace_dir: str, max_seconds: float) -> None:
    start, stop = threading.Event(), threading.Event()
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())

    def window() -> None:
        start.wait()
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.monotonic()
        # one host span over the whole window, so that the reduction knows
        # where the window lies even while nothing else is being traced
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            stop.wait(max_seconds)
        seconds = time.monotonic() - t0
        jax.profiler.stop_trace()
        with open(os.path.join(trace_dir, "window.json"), "w", encoding="utf-8") as f:
            json.dump({"window_s": seconds, "written_s": time.monotonic() - t0 - seconds}, f)

    threading.Thread(target=window, name="bench-profiler", daemon=True).start()


if __name__ == "__main__":
    if os.environ.get("BENCH_TRACE_DIR"):
        arm_profiler(os.environ["BENCH_TRACE_DIR"],
                     float(os.environ.get("BENCH_TRACE_MAX_S", "10")))
    from xaynet_tpu.server import runner

    runner.main()
