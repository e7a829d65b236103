"""Reduction of a profiler trace (`*.xplane.pb`) to what the per-layer
readers need, with nothing but `jax.profiler.ProfileData`.

    python benchmarks/xplane.py <trace dir or file> <out.json>

For each device plane (`/device:TPU:<n>`) the op line (`XLA Ops`) holds one
event per executed HLO operation, start and duration in ns on the device's
clock. From it:

  busy_s    the union of the op intervals: seconds in which some operation
            ran on that device
  window_s  last end - first start on that line: the traced window as the
            device saw it
  ops       seconds by op name, as *self* time: an event nested inside
            another (the body of a `while`) is taken out of its parent, so
            the names add up to busy_s
  gaps      the longest stretches inside the window in which nothing ran,
            each named by the ops on either side of it (no host span is on
            the device's clock yet, so what the host did there has no name)

All four are averaged over the device planes found, i.e. over the chips
used. Run as a short process of its own with JAX_PLATFORMS=cpu: importing
jax.profiler in the benchmark's parent would be the first step towards a
parent that holds the chip.
"""

from __future__ import annotations

import json
import os
import re
import sys

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"


def find_trace(path: str) -> str | None:
    """The newest `*.xplane.pb` under `path` (a file is returned as is)."""
    if os.path.isfile(path):
        return path
    found = [os.path.join(root, f) for root, _, files in os.walk(path)
             for f in files if f.endswith(".xplane.pb")]
    found = [p for p in found if os.path.getsize(p) > 0]
    return max(found, key=os.path.getmtime) if found else None


def reduce_events(events: list[tuple[str, float, float]]) -> dict:
    """`events` are (name, start_ns, duration_ns) of one line. Pure
    arithmetic, so the tests can feed it numbers worked out by hand."""
    # An event with no duration is a marker, not an operation: it covers
    # no time and splits no gap.
    events = sorted((e for e in events if e[2] > 0),
                    key=lambda e: (e[1], -e[2]))
    if not events:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "gaps": []}
    ops: dict[str, float] = {}
    stack: list[tuple[str, float]] = []  # (name, end) of the open events
    busy = 0.0
    gaps: list[tuple[str, float]] = []
    first = events[0][1]
    reach, reach_name = first, events[0][0]  # how far the union extends
    for name, start, dur in events:
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:  # nested: its time is not its parent's own
            ops[stack[-1][0]] -= min(end, stack[-1][1]) - start
        ops[name] = ops.get(name, 0.0) + dur
        stack.append((name, end))
        if start > reach:
            # An op's name may be its whole HLO text: keep the left side.
            gaps.append((f"after {reach_name.split(' = ')[0]} | before "
                         f"{name.split(' = ')[0]}", (start - reach) / 1e9))
            busy += end - start
            reach, reach_name = end, name
        elif end > reach:
            busy += end - reach
            reach, reach_name = end, name
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy / 1e9, "window_s": (reach - first) / 1e9,
            "ops": {k: v / 1e9 for k, v in ops.items()}, "gaps": gaps}


def summarize(path: str, plane_rx: str = DEVICE_PLANE,
              line_rx: str = OP_LINE) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    per_plane = []
    for plane in data.planes:
        if not re.search(plane_rx, plane.name):
            continue
        events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                  for line in plane.lines if re.search(line_rx, line.name)
                  for ev in line.events]
        if events:
            per_plane.append(reduce_events(events))
    n = len(per_plane)
    if not n:
        return {"planes": 0, "busy_s": 0.0, "window_s": 0.0, "ops": [],
                "gaps": []}
    ops: dict[str, float] = {}
    for r in per_plane:
        for name, s in r["ops"].items():
            ops[name] = ops.get(name, 0.0) + s / n
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    gaps = sorted((g for r in per_plane for g in r["gaps"]),
                  key=lambda g: -g[1])
    return {"planes": n,
            "busy_s": sum(r["busy_s"] for r in per_plane) / n,
            "window_s": sum(r["window_s"] for r in per_plane) / n,
            "ops": [[k, v] for k, v in ranked],
            "gaps": [[k[:160], v] for k, v in gaps[:5]]}


def describe(path: str) -> list:
    """Planes, lines and event counts: what to look at by hand before
    trusting a regex."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [[plane.name, [[line.name, sum(1 for _ in line.events)]
                          for line in plane.lines]]
            for plane in data.planes]


def main(argv: list[str]) -> int:
    trace = find_trace(argv[0])
    if trace is None:
        print(f"xplane: no *.xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    if len(argv) > 1 and argv[1] == "--describe":
        print(json.dumps(describe(trace), indent=1))
        return 0
    out = summarize(trace)
    out["trace"] = os.path.relpath(trace)
    text = json.dumps(out)
    if len(argv) > 1:
        with open(argv[1], "w") as fh:
            fh.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
