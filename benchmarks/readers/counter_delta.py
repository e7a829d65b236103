"""A ratio of sums of the engine's counters (`GET /v2/models/<m>` ->
`stats`), each taken as its change over the measured window.

`num` and `den` list counter names; `den` may instead be the word
"window_s" (the window's length). `den_times` lists keys of the
configuration's `engine` block whose values multiply the denominator
(a dispatch decodes `chunk` tokens for each of `slots` rows)."""


def read(ctx, num: list, den, den_times: list = (), scale: float = 1.0):
    deltas = ctx.facts.get("counters")
    if deltas is None or any(k not in deltas for k in num):
        return None
    top = sum(deltas[k] for k in num)
    if den == "window_s":
        bottom = ctx.facts["window_s"]
    else:
        if any(k not in deltas for k in den):
            return None
        bottom = sum(deltas[k] for k in den)
    for key in den_times:
        bottom *= ctx.facts["engine"][key]
    if not bottom:
        return None
    return scale * top / bottom
