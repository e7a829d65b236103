"""Device idle share over the traced window, %: 1 - busy / window, where
busy is the union of the intervals in which an operation ran on the
device, averaged over the chips (xplane.py)."""


def read(ctx):
    x = ctx.facts.get("xplane")
    if not x or not x["window_s"]:
        return None
    return 100.0 * (1.0 - x["busy_s"] / x["window_s"])
