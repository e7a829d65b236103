"""The change over the measured window of a sum of the engine's counters
(`GET /v2/models/<m>` -> `stats`): for a count that is a number by itself,
where counter_delta gives a ratio. A counter the program does not have (a
parent commit's engine) gives None."""


def read(ctx, names: list):
    deltas = ctx.facts.get("counters")
    if deltas is None or any(k not in deltas for k in names):
        return None
    return sum(deltas[k] for k in names)
