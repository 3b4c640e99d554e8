"""Launcher of the traced run: the same server ``main()`` in the
process that owns the chip, with the profiler switched on and off
around the measured window by lines on stdin.

    python benchmarks/trace_launch.py <module> <the module's argv...>

``start <dir>``  jax.profiler.start_trace(dir); answers
                 ``trace started <wall ns>`` — the wall clock read
                 inside a ``bench_clock_mark`` annotation, which ties
                 the trace's clock to the client's.
``stop``         jax.profiler.stop_trace(); answers ``trace stopped``.
                 The raw ``.xplane.pb`` stays on disk for
                 trace_reduce.py.

Only the process that holds the chip can trace it, which is why this
is a launcher and not a call from the benchmark's own process.
"""

from __future__ import annotations

import runpy
import sys
import threading
import time


def control() -> None:
    import jax.profiler as jp

    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "start":
            opts = jp.ProfileOptions()
            opts.python_tracer_level = 0  # no Python call stacks
            opts.host_tracer_level = 1    # annotations only
            jp.start_trace(cmd[1], profiler_options=opts)
            with jp.TraceAnnotation("bench_clock_mark"):
                wall_ns = time.time_ns()
            print(f"trace started {wall_ns}", flush=True)
        elif cmd[0] == "stop":
            jp.stop_trace()
            print("trace stopped", flush=True)


def main() -> None:
    module, args = sys.argv[1], sys.argv[2:]
    threading.Thread(target=control, daemon=True).start()
    sys.argv = [module, *args]
    runpy.run_module(module, run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
