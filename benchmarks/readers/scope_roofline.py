"""A kernel's share of its roofline, %: the least time the chip could take
for the work a scope *requires* over the device seconds spent under the
scope in the traced window.

  required   `flops` and `bytes` name functions of `required/<module>.py`
             taking the configuration; each gives the amount for one token
             of one layer, multiplied here by the tokens of a step, by the
             layers `layers` counts (a key of the configuration's
             `linear_attn_config`) and by the steps traced
  least time the larger of FLOPs over the bf16 peak and bytes over the HBM
             peak: the binding one (for the KDA recurrence the bytes)
  seconds    device self time under `scope` (readers/xplane_scope_share.py)

Nothing to read (no scope in the program, no path stat, a CPU) gives None.
"""

import os

import common
import shapes

scopes = common.load_module(
    os.path.join(common.BENCH, "readers", "xplane_scope_share.py"))


def read(ctx, scope: str, module: str, flops: str, bytes: str, layers: str):
    if ctx.facts["device"]["platform"] == "cpu":
        return None  # the CPU has no peak to hold a rehearsal against
    seconds = scopes.seconds_under(ctx, scope)
    if not seconds:
        return None
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    spec = ctx.facts["spec"]
    first, stop = ctx.mix["profile_steps"]
    tokens = spec["batch_size"] / ctx.chips * spec["seq_len"] * (stop - first)
    n = tokens * req.layers_of(ctx.config, layers)
    kind = ctx.facts["device"]["kind"]
    least = max(
        n * getattr(req, flops)(ctx.config) / shapes.peak(kind, "bf16_flops"),
        n * getattr(req, bytes)(ctx.config)
        / shapes.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least / seconds
