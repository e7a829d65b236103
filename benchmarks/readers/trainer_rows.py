"""A field of the trainer's measured log rows (those after the warm-up),
reduced by median or mean and scaled."""

import statistics


def read(ctx, field: str, reduce: str = "median", scale: float = 1.0):
    values = [r[field] for r in ctx.facts.get("rows", []) if field in r]
    if not values:
        return None
    fn = {"median": statistics.median, "mean": statistics.fmean}[reduce]
    return fn(values) * scale
