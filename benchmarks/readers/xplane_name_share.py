"""Device time of the operations whose name matches `pattern`, as a
percentage of device busy time, from the traced window."""

import re


def read(ctx, pattern: str):
    x = ctx.facts.get("xplane")
    if not x or not x["busy_s"]:
        return None
    rx = re.compile(pattern)
    hit = sum(s for name, s in x["ops"] if rx.search(name))
    return 100.0 * hit / x["busy_s"]
