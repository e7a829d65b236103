"""A number from `host_trace.json`, the second reduction of the traced
window (xplane_host.py): the program's spans, the executables and the
device's operations off one clock. `what` says which:

  module_share      device seconds of the executables whose name matches
                    `pattern`, as a percentage of device busy seconds
  span_share        seconds of the program span `span`, the spans nested
                    in it included, as a percentage of the traced window
  idle_named_share  device idle seconds that fall inside some program span,
                    as a percentage of device idle seconds

The reduction runs once a run, in a short CPU child started only now, after
the chip's owner has exited (the kind has returned); every reader shares the
pass through `ctx.facts`. A program without the spans (a parent commit), a
trace without a device plane, or a reduction that failed gives None: the
metric is left out of the line.
"""

import os
import re
import subprocess
import sys

import common

NO_SPAN = "(no span)"


def summary(ctx) -> dict | None:
    if "host_trace" not in ctx.facts:
        ctx.facts["host_trace"] = reduce_once(ctx)
    return ctx.facts["host_trace"]


def reduce_once(ctx) -> dict | None:
    trace_dir = os.path.join(ctx.out, "profile")
    if not ctx.trace or not os.path.isdir(trace_dir):
        return None
    out = os.path.join(ctx.out, "host_trace.json")
    env = dict(ctx.env)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(common.BENCH, "xplane_host.py"),
             trace_dir, out], cwd=common.ROOT, env=env,
            capture_output=True, text=True, timeout=max(ctx.left(), 30.0))
        if proc.returncode != 0:
            raise common.BenchError(proc.stderr[-2000:])
        return common.load_json(out)
    except (OSError, subprocess.TimeoutExpired, common.BenchError) as e:
        print(f"host_trace: no reduction: {e}", file=sys.stderr)
        return None


def module_runs(ht: dict, pattern: str) -> tuple[float, float]:
    """(device seconds, runs) of the executables matching `pattern`."""
    rx = re.compile(pattern)
    hit = [v for name, v in ht["modules"].items() if rx.search(name)]
    return sum(v[0] for v in hit), sum(v[1] for v in hit)


def read(ctx, what: str, pattern: str = "", span: str = ""):
    ht = summary(ctx)
    if not ht:
        return None
    if what == "module_share":
        seconds, _ = module_runs(ht, pattern)
        if not ht["planes"] or not ht["busy_s"] or not ht["modules"]:
            return None
        return 100.0 * seconds / ht["busy_s"]
    if what == "span_share":
        if span not in ht["spans"] or not ht["window_s"]:
            return None
        return 100.0 * ht["spans"][span][0] / ht["window_s"]
    if what == "idle_named_share":
        if not ht["planes"] or not ht["spans"] or not ht["idle_s"]:
            return None
        unnamed = ht["idle_by_span"].get(NO_SPAN, 0.0)
        return 100.0 * (ht["idle_s"] - unnamed) / ht["idle_s"]
    raise common.BenchError(f"host_trace: no such reading {what!r}")
