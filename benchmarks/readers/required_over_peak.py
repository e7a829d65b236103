"""Required training FLOPs over the chip's peak, as a percentage: what
`shapes.train_flops_per_token` says a token needs, times the end-to-end
rate the run measured (tokens/s/chip), over the peak of the device the run
reported."""

import shapes


def read(ctx, rate: str, peak: str = "bf16_flops"):
    per_chip = ctx.facts.get("e2e", {}).get(rate)
    if per_chip is None or ctx.facts["device"]["platform"] == "cpu":
        return None  # the CPU has no peak to hold a rehearsal against
    need = shapes.train_flops_per_token(ctx.config,
                                        ctx.facts["spec"]["seq_len"])
    return 100.0 * need * per_chip / shapes.peak(
        ctx.facts["device"]["kind"], peak)
