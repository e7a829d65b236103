"""The model server's own main(), in its main thread, plus a profiler
window that the benchmark's parent opens and closes through two files.

    python benchmarks/serve_child.py <control dir> <server arguments...>

Only the process that holds the chip can trace it, and the server has no
switch for a profiler window. A side thread waits for `<control
dir>/trace.start`, starts jax.profiler into `<control dir>/profile`, waits
for `trace.stop`, and stops it. Used by the traced run only; the timed run
starts `python -m kubeflow_tpu.serve.server` itself.
"""

from __future__ import annotations

import os
import sys
import threading
import time


def trace_on_request(control: str) -> None:
    start = os.path.join(control, "trace.start")
    stop = os.path.join(control, "trace.stop")
    while not os.path.exists(start):
        time.sleep(0.05)
    import jax

    # The device's ops are what the reduction reads; tracing every Python
    # call of 32 request handlers would slow the host it is measuring.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(control, "profile"),
                             profiler_options=options)
    while not os.path.exists(stop):
        time.sleep(0.05)
    jax.profiler.stop_trace()


def main(argv: list[str]) -> int:
    control, rest = argv[0], argv[1:]
    threading.Thread(target=trace_on_request, args=(control,), daemon=True,
                     name="bench-trace").start()
    from kubeflow_tpu.serve import server

    return server.main(rest)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
