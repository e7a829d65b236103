"""Required serving FLOPs over the chip's peak, %, for a configuration whose
required work is a function of `required/<module>.py`: the whole step's
share, prompts and outputs together, over the measured window.

  prompts   `prompt_flops(config, n)` at the window's mean prompt length
            (the change of `prompt_tokens` over the change of `requests`),
            times the prompts admitted in the window. The attention's part
            grows faster than linearly in n, so the mean length gives no
            more than the prompts' true sum
  outputs   `decode_flops(config, tokens, rows)`: the change of
            `decode_tokens`, and the state rows their steps read: each
            name of `row_counters` is summed by the engine at each decode
            dispatch over its rows as the chunk's first step reads them, so
            a dispatch of `chunk` steps reads `chunk` times that (the rows a
            later step of the chunk adds are left out)
  seconds   the measured window, on the harness's clock

Nothing to read (no such counter in a parent commit, a CPU) gives None.
"""

import os

import common
import shapes


def read(ctx, module: str, row_counters: list, peak: str = "bf16_flops"):
    deltas = ctx.facts.get("counters") or {}
    names = ["prompt_tokens", "decode_tokens", "requests"] + list(row_counters)
    if any(k not in deltas for k in names) or not deltas["requests"]:
        return None
    if ctx.facts["device"]["platform"] == "cpu":
        return None  # the CPU has no peak to hold a rehearsal against
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    mean_prompt = round(deltas["prompt_tokens"] / deltas["requests"])
    rows = ctx.facts["engine"]["chunk"] * sum(
        deltas[k] for k in row_counters)
    need = (deltas["requests"] * req.prompt_flops(ctx.config, mean_prompt)
            + req.decode_flops(ctx.config, deltas["decode_tokens"], rows))
    return 100.0 * need / ctx.facts["window_s"] / shapes.peak(
        ctx.facts["device"]["kind"], peak)
