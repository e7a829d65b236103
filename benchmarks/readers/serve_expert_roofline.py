"""The routed experts' share of their roofline at decode, %: the least time
the chip could take to read the experts the traced span's steps were routed
to, over the device seconds spent under the scope that does their work,
whatever implements it.

  required   `expert_bytes(config)` (`required/<module>.py`) for each expert
             `expert_counter` counts: distinct experts a layer a dispatch's
             first step was routed to, summed over layers; `chunk` steps a
             dispatch. From the engine's counters read at both ends of the
             traced span by the kind (`facts["trace_counters"]`; a counter
             ticks when the host fetches the dispatch, a round or two after
             the device ran it, so the two ends are that far off the trace's)
  least time bytes over the HBM peak: a decode step's experts see one or two
             rows each, so their matmuls are bound by the weights' read
  seconds    device self time under `scope` (readers/xplane_scope_share.py)

Nothing to read (no scope or counter in the program, no path stat, a CPU)
gives None.
"""

import os

import common
import shapes

scopes = common.load_module(
    os.path.join(common.BENCH, "readers", "xplane_scope_share.py"))


def read(ctx, scope: str, module: str, expert_counter: str):
    deltas = ctx.facts.get("trace_counters")
    if (not deltas or not deltas.get(expert_counter)
            or ctx.facts["device"]["platform"] == "cpu"):
        return None
    seconds = scopes.seconds_under(ctx, scope)
    if not seconds:
        return None
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    moved = (deltas[expert_counter] * ctx.facts["engine"]["chunk"]
             * req.expert_bytes(ctx.config))
    least = moved / shapes.peak(ctx.facts["device"]["kind"],
                                "hbm_bytes_per_s")
    return 100.0 * least / seconds
