"""The sum of a field over the trainer's measured log rows (those after the
warm-up): for a count a row reports for its own window. No row with the
field (a parent commit's trainer) gives None."""


def read(ctx, field: str):
    values = [r[field] for r in ctx.facts.get("rows", []) if field in r]
    return sum(values) if values else None
