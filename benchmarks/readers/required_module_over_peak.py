"""Required training FLOPs over the chip's peak, %, for a configuration
whose required work `shapes.train_flops_per_token` cannot describe: the
function `train_flops_per_token(config, seq_len)` of `required/<module>.py`,
times the tokens a chip trains a second, over the peak of the device the run
reported.

The rate is the step's tokens over the median `step_time_s` of the measured
log rows (the trainer's clock, closed by one `block_until_ready` a row), not
the run's end-to-end `train_tok_s`: per-layer metrics are read in the traced
run, whose window also holds the profiler writing its trace (for this model
≈ 70 MB: a traced run read 6.5% by `train_tok_s` where the timed rate gives
12.0%, my chip runs, PR 28), and the median row does not."""

import os
import statistics

import common
import shapes


def read(ctx, module: str, peak: str = "bf16_flops"):
    times = [r["step_time_s"] for r in ctx.facts.get("rows", [])
             if "step_time_s" in r]
    if not times or ctx.facts["device"]["platform"] == "cpu":
        return None  # the CPU has no peak to hold a rehearsal against
    req = common.load_module(
        os.path.join(common.BENCH, "required", module + ".py"))
    spec = ctx.facts["spec"]
    per_chip = (spec["batch_size"] / ctx.chips * spec["seq_len"]
                / statistics.median(times))
    need = req.train_flops_per_token(ctx.config, spec["seq_len"])
    return 100.0 * need * per_chip / shapes.peak(
        ctx.facts["device"]["kind"], peak)
