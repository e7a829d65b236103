"""The decode executables' share of the chip's memory bandwidth, %, for a
configuration whose step reads only the experts it routes to: the bytes its
steps *require* by `required/<module>.py` over the device seconds they took,
over the published peak. `readers/decode_bw_module.py` with a second counter.

  bytes a step  `decode_bytes_per_step(config, rows, touched)`: what every
                step reads (attention, dense and shared FFNs, routers, head),
                the touched experts' matrices and the batch's state rows.
                `rows` and `touched` are the means over the dispatches of the
                sums of `row_counters` and of `expert_counter`, each counted
                at a dispatch's first step: over the traced span where the
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


def per_dispatch(ctx, names: list):
    for facts in ("trace_counters", "counters"):
        deltas = ctx.facts.get(facts) or {}
        if (deltas.get("decode_dispatches")
                and all(k in deltas for k in names)):
            return sum(deltas[k] for k in names) / deltas["decode_dispatches"]
    return None


def read(ctx, pattern: str, module: str, row_counters: list,
         expert_counter: str):
    rows = per_dispatch(ctx, row_counters)
    touched = per_dispatch(ctx, [expert_counter])
    if (rows is None or touched is None
            or ctx.facts["device"]["platform"] == "cpu"):
        return None
    ht = host_trace.summary(ctx)
    if not ht or not ht["planes"]:
        return None
    seconds, runs = host_trace.module_runs(ht, pattern)
    if not seconds or not runs:
        return None
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    moved = (req.decode_bytes_per_step(ctx.config, rows, touched)
             * ctx.facts["engine"]["chunk"] * runs)
    return 100.0 * moved / seconds / shapes.peak(
        ctx.facts["device"]["kind"], "hbm_bytes_per_s")
