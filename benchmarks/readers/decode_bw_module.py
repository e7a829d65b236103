"""The decode executables' share of the chip's memory bandwidth, %, for a
configuration whose state is not a row of K and V a token: the bytes their
steps *require* by `required/<module>.py` over the device seconds they took,
over the published peak. `readers/decode_bw.py` with another yardstick.

  bytes a step  `decode_bytes_per_step(config, rows)`: every matmul weight
                once in bf16 and every state row the batch's rows read.
                `rows` is the mean over the dispatches of the sum of
                `row_counters` (each summed by the engine at each decode
                dispatch over its rows): over the traced span where the
                kind read the counters at both its ends
                (`facts["trace_counters"]`), else over the measured window
  steps         `chunk` a run, times the runs of the executables matching
                `pattern` in the traced window (host_trace.json `modules`)
  seconds       those runs' device seconds, from the same trace

Nothing to read (no counter in a parent commit, no device plane, a CPU)
gives None.
"""

import os

import common
import shapes

host_trace = common.load_module(
    os.path.join(common.BENCH, "readers", "host_trace.py"))


def rows_per_dispatch(ctx, row_counters: list):
    for facts in ("trace_counters", "counters"):
        deltas = ctx.facts.get(facts) or {}
        if (deltas.get("decode_dispatches")
                and all(k in deltas for k in row_counters)):
            return (sum(deltas[k] for k in row_counters)
                    / deltas["decode_dispatches"])
    return None


def read(ctx, pattern: str, module: str, row_counters: list):
    rows = rows_per_dispatch(ctx, row_counters)
    if rows is None or ctx.facts["device"]["platform"] == "cpu":
        return None
    ht = host_trace.summary(ctx)
    if not ht or not ht["planes"]:
        return None
    seconds, runs = host_trace.module_runs(ht, pattern)
    if not seconds or not runs:
        return None
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    moved = (req.decode_bytes_per_step(ctx.config, rows)
             * ctx.facts["engine"]["chunk"] * runs)
    return 100.0 * moved / seconds / shapes.peak(
        ctx.facts["device"]["kind"], "hbm_bytes_per_s")
