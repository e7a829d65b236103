"""A value the run measured end to end (the kind's `e2e`), reported in the
traced run as a per-layer metric: for a statistic that is worth watching
but spreads too widely to carry a bound."""


def read(ctx, name: str):
    return ctx.facts.get("e2e", {}).get(name)
