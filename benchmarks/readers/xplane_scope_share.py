"""Device self time of the operations made under a `jax.named_scope` (or a
flax module) whose path matches `scope`, as a percentage of device busy
time, from the traced window. `scope` is a regular expression searched in
each operation's op_name path (`.../layer_0/kda/kda_scan/while`).

The trace is reduced once a run by `xplane_scopes.py`, in a short CPU child
started after the chip's owner has exited; every reader of scopes shares the
pass through `ctx.facts`. No trace, no device plane, events without a path
stat (another profiler), or no operation under the scope (a program that
lacks the scope, as a parent commit does) give None.
"""

import os
import re
import subprocess
import sys

import common


def summary(ctx) -> dict | None:
    if "scopes" not in ctx.facts:
        ctx.facts["scopes"] = reduce_once(ctx)
    return ctx.facts["scopes"]


def reduce_once(ctx) -> dict | None:
    trace_dir = os.path.join(ctx.out, "profile")
    if not ctx.trace or not os.path.isdir(trace_dir):
        return None
    out = os.path.join(ctx.out, "scopes.json")
    env = dict(ctx.env)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(common.BENCH, "xplane_scopes.py"),
             trace_dir, out], cwd=common.ROOT, env=env,
            capture_output=True, text=True, timeout=max(ctx.left(), 30.0))
        if proc.returncode != 0:
            raise common.BenchError(proc.stderr[-2000:])
        return common.load_json(out)
    except (OSError, subprocess.TimeoutExpired, common.BenchError) as e:
        print(f"xplane_scopes: no reduction: {e}", file=sys.stderr)
        return None


def seconds_under(ctx, scope: str) -> float | None:
    """Device self seconds (a chip, over the traced window) under `scope`."""
    s = summary(ctx)
    if not s or not s["planes"] or not s["paths"]:
        return None
    rx = re.compile(scope)
    hit = [sec for path, sec in s["paths"] if rx.search(path)]
    return sum(hit) if hit else None


def read(ctx, scope: str):
    s = summary(ctx)
    seconds = seconds_under(ctx, scope)
    if seconds is None or not s["busy_s"]:
        return None
    return 100.0 * seconds / s["busy_s"]
