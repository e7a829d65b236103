"""The decode executables' share of the chip's memory bandwidth, %: the
bytes their steps *require* over the device seconds they took, over the
published peak.

  bytes a step  shapes.decode_bytes_per_step: every matmul weight once at
                `weight_bytes` a parameter (the batch shares the read) and
                the K and V of every token of the batch's contexts at
                `kv_bytes` a value. The contexts are the engine's own count:
                decode_context_tokens sums, at each dispatch, its rows'
                context lengths, so its change over the window divided by
                the change of decode_dispatches (which counts speculative
                dispatches too) is the mean batch context
  steps         `chunk` a run, times the runs of the executables matching
                `pattern` in the traced window (host_trace.json `modules`)
  seconds       those runs' device seconds, from the same trace

Required bytes, not moved bytes: the program's bf16 copy of the fp32
weights and its contiguous view of the paged pool are traffic it chooses,
and count nothing. Nothing to read (no counter in a parent commit, no
device plane, a CPU) gives None.
"""

import os

import common
import shapes

host_trace = common.load_module(
    os.path.join(common.BENCH, "readers", "host_trace.py"))


def read(ctx, pattern: str, weight_bytes: int = 2, kv_bytes: int = 2):
    deltas = ctx.facts.get("counters") or {}
    dispatches = deltas.get("decode_dispatches")
    if "decode_context_tokens" not in deltas or not dispatches:
        return None
    if ctx.facts["device"]["platform"] == "cpu":
        return None  # the CPU has no peak to hold a rehearsal against
    ht = host_trace.summary(ctx)
    if not ht or not ht["planes"]:
        return None
    seconds, runs = host_trace.module_runs(ht, pattern)
    if not seconds or not runs:
        return None
    per_step = shapes.decode_bytes_per_step(
        ctx.config, weight_bytes, kv_bytes,
        deltas["decode_context_tokens"] / dispatches)
    moved = per_step * ctx.facts["engine"]["chunk"] * runs
    return 100.0 * moved / seconds / shapes.peak(
        ctx.facts["device"]["kind"], "hbm_bytes_per_s")
