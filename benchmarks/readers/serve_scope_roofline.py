"""A serving kernel's share of its roofline, %: the least time the chip
could take for the work a scope *requires* over the device seconds spent
under the scope in the traced span, whatever implements it.

  required   by `required/<module>.py`, over the traced span, from the
             engine's counters read at both its ends by the kind
             (`facts["trace_counters"]`; a counter ticks when the host
             dispatches, the device runs it up to a round later, so the two
             ends are a round off the trace's):
             form "decode"   `chunk` steps a decode dispatch, each reading
                             the state rows `row_counters` count a dispatch:
                             `core_flops` and `core_bytes_decode` of them
             form "prefill"  the prompts admitted in the span at their mean
                             length: `core_flops` of `prompt_rows`, and
                             `core_bytes_prefill`. The attention's part
                             grows faster than linearly in the length, so
                             the mean gives no more than the true sum
  least time the larger of FLOPs over the bf16 peak and bytes over the HBM
             peak: the binding one
  seconds    device self time under `scope` (readers/xplane_scope_share.py)

Nothing to read (no scope or counter in the program, no path stat, a CPU)
gives None.
"""

import os

import common
import shapes

scopes = common.load_module(
    os.path.join(common.BENCH, "readers", "xplane_scope_share.py"))


def read(ctx, scope: str, module: str, form: str, row_counters: list = ()):
    deltas = ctx.facts.get("trace_counters")
    if not deltas or ctx.facts["device"]["platform"] == "cpu":
        return None
    seconds = scopes.seconds_under(ctx, scope)
    if not seconds:
        return None
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    cfg = ctx.config
    if form == "decode":
        if any(k not in deltas for k in row_counters):
            return None
        rows = ctx.facts["engine"]["chunk"] * sum(
            deltas[k] for k in row_counters)
        flops, moved = req.core_flops(cfg, rows), req.core_bytes_decode(
            cfg, rows)
    elif form == "prefill":
        if not deltas.get("requests"):
            return None
        n = round(deltas["prompt_tokens"] / deltas["requests"])
        flops = deltas["requests"] * req.core_flops(
            cfg, req.prompt_rows(cfg, n))
        moved = req.core_bytes_prefill(cfg, deltas["prompt_tokens"])
    else:
        raise common.BenchError(f"serve_scope_roofline: no form {form!r}")
    kind = ctx.facts["device"]["kind"]
    least = max(flops / shapes.peak(kind, "bf16_flops"),
                moved / shapes.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least / seconds
