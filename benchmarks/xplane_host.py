"""Second reduction of a profiler trace (`*.xplane.pb`): the program's own
spans, the executables and the device's operations, off one clock.

    python benchmarks/xplane_host.py <trace dir or file> <out.json>

The program's `obs.span(...)` blocks are `jax.profiler.TraceAnnotation`s
(kubeflow_tpu/utils/obs.py), so while a profiler session is open they land
in the host plane of the same file as the device's operations, on the same
timeline. From the trace:

  modules       device seconds and runs per executable. A device plane
                (`/device:TPU:<n>`) has an `XLA Modules` line with one event
                per executable run, named `jit_<function>(<id>)`; the id is
                dropped, so the sixteen decode buckets are one
                `jit_decode_chunk`. A CPU rehearsal has no device plane:
                there the op events of the host plane carry an `hlo_module`
                stat, and an op's seconds go to its module (no runs).
  spans         seconds, *self* seconds and count by name of the host-plane
                events named `engine.*`, `serve.*` or `train.*`, on any
                thread line. Self time is the span's duration minus what the
                spans nested in it on its own thread cover (the
                `choosing-metrics` guide's definition). Spans are cut to the
                device's window, which is what the shares are taken over.
  threads       per thread line that has such spans: its window (first span
                start to last span end), the seconds its spans cover, and
                the self seconds by name.
  idle_by_span  the device's idle seconds (the gaps in the union of its op
                intervals: xplane.reduce_events' arithmetic, with the gaps'
                places kept) put down to the innermost program span that
                covers each piece of them, or to `(no span)`. Innermost
                across threads means the covering span that started last.
  gaps          the ten longest gaps: start (s after the window's start),
                seconds, and the span that covers most of each.
  fetch_check   `engine.fetch` against the counter it doubles, over one
                interval. Each such span carries `stalled_s`, the engine's
                `host_stall_seconds` as it stood before that fetch. From
                the first of the trace's fetches to the last: `counter_s`,
                the counter's change; `span_s`, the seconds of the spans it
                rose by (all but the last); `interval_s`, first start to
                last start. Absent where the spans carry no such mark.

Device numbers are averaged over the device planes found, like xplane.py's.
Run as a short process of its own under JAX_PLATFORMS=cpu, after the chip's
owner has exited (`readers/host_trace.py` does, once a run).
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xplane  # noqa: E402

HOST_PLANE = r"^/host:CPU$"
MODULE_LINE = r"^XLA Modules$"
SPAN_NAME = r"^(engine|serve|train)\."
NO_SPAN = "(no span)"
FETCH_SPAN = "engine.fetch"
FETCH_MARK = "stalled_s"


def module_name(event_name: str) -> str:
    """`jit_decode_chunk(1234)` -> `jit_decode_chunk`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def busy_and_gaps(events: list[tuple[float, float]]) -> dict:
    """`events` are (start_ns, duration_ns) of one device's operations.
    The union of their intervals, as in xplane.reduce_events, but the gaps
    keep their places: [(start_ns, end_ns)], in time order."""
    events = sorted(e for e in events if e[1] > 0)
    if not events:
        return {"first": 0.0, "last": 0.0, "busy_ns": 0.0, "gaps": []}
    first = reach = events[0][0]
    busy, gaps = 0.0, []
    for start, dur in events:
        end = start + dur
        if start > reach:
            gaps.append((reach, start))
            busy += dur
            reach = end
        elif end > reach:
            busy += end - reach
            reach = end
    return {"first": first, "last": reach, "busy_ns": busy, "gaps": gaps}


def span_times(spans: list[tuple[str, float, float]],
               window: tuple[float, float] | None = None) -> dict:
    """`spans` are (name, start_ns, duration_ns) of ONE thread line. By
    name: [seconds, self seconds, count], a span's self time being its
    duration less what the spans nested in it cover. With `window`
    (start_ns, end_ns) every span is first cut to it, and one that lies
    outside counts nothing."""
    cut = []
    for name, start, dur in spans:
        end = start + dur
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
        if end > start:
            cut.append((name, start, end - start))
    # Self time is xplane.reduce_events' own arithmetic (an event nested in
    # another is taken out of its parent), on spans instead of ops.
    own = xplane.reduce_events(cut)["ops"]
    out: dict[str, list] = {}
    for name, _, dur in cut:
        row = out.setdefault(name, [0.0, own[name], 0])
        row[0] += dur / 1e9
        row[2] += 1
    return out


class Cover:
    """All program spans of all threads, to ask which one covers a moment.
    The innermost is the covering span that started last: on one thread
    that is the deepest of the nest, across threads the newest."""

    def __init__(self, spans: list[tuple[str, float, float]]):
        self.spans = sorted((start, start + dur, name)
                            for name, start, dur in spans if dur > 0)
        self.starts = [s[0] for s in self.spans]
        self.reach = []  # the furthest end among spans[0..i]
        far = float("-inf")
        for _, end, _ in self.spans:
            far = max(far, end)
            self.reach.append(far)
        self.cuts = sorted({t for s in self.spans for t in s[:2]})

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] > t:
            if self.spans[i][1] > t:
                return self.spans[i][2]
            i -= 1
        return NO_SPAN

    def split(self, lo: float, hi: float) -> dict[str, float]:
        """The stretch [lo, hi) by the innermost span over each piece."""
        a = bisect.bisect_right(self.cuts, lo)
        b = bisect.bisect_left(self.cuts, hi)
        edges = [lo] + self.cuts[a:b] + [hi]
        out: dict[str, float] = {}
        for x, y in zip(edges, edges[1:]):
            if y > x:
                name = self.at((x + y) / 2)
                out[name] = out.get(name, 0.0) + (y - x)
        return out


def attribute(gaps: list[tuple[float, float]], cover: Cover) -> dict:
    """Each gap's nanoseconds to the spans that cover it. Returns the sum
    by span name and, for each gap, (start_ns, ns, the span over most of
    it)."""
    by_span: dict[str, float] = {}
    named = []
    for lo, hi in gaps:
        parts = cover.split(lo, hi)
        for name, ns in parts.items():
            by_span[name] = by_span.get(name, 0.0) + ns
        named.append((lo, hi - lo, max(parts, key=parts.get)))
    return {"by_span": by_span, "gaps": named}


def module_seconds(events: list[tuple[str, float]]) -> dict:
    """(executable name, duration_ns) -> {name: [seconds, runs]}."""
    out: dict[str, list] = {}
    for name, dur in events:
        row = out.setdefault(module_name(name), [0.0, 0])
        row[0] += dur / 1e9
        row[1] += 1
    return out


def fetch_check(marks: list[tuple[float, float, float]]) -> dict | None:
    """`marks` are (start_ns, duration_ns, the counter before it) of the
    trace's engine.fetch spans."""
    marks = sorted(marks)
    if len(marks) < 2:
        return None
    return {"fetches": len(marks) - 1,
            "interval_s": (marks[-1][0] - marks[0][0]) / 1e9,
            "span_s": sum(dur for _, dur, _ in marks[:-1]) / 1e9,
            "counter_s": marks[-1][2] - marks[0][2]}


def read_planes(path: str) -> dict:
    """What the reduction needs of the file, as plain lists: per device
    plane its op and module events, the host plane's program spans by
    thread line with the engine.fetch spans' marks, and (only for a trace
    with no device plane) the host plane's op events that name their
    `hlo_module`."""
    from jax.profiler import ProfileData

    span_rx = re.compile(SPAN_NAME)
    planes = list(ProfileData.from_file(path).planes)
    devices = []
    for plane in planes:
        if not re.search(xplane.DEVICE_PLANE, plane.name):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if re.search(xplane.OP_LINE, line.name):
                ops += [(float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events]
            elif re.search(MODULE_LINE, line.name):
                mods += [(ev.name, float(ev.duration_ns))
                         for ev in line.events]
        if ops:
            devices.append({"ops": ops, "modules": mods})
    threads, host_ops, marks = [], [], []
    for plane in planes:
        if not re.search(HOST_PLANE, plane.name):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if span_rx.search(ev.name):
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)))
                    if ev.name == FETCH_SPAN:
                        mark = dict(ev.stats).get(FETCH_MARK)
                        if mark is not None:
                            marks.append((*spans[-1][1:], float(mark)))
                elif not devices and ev.duration_ns > 0:
                    module = dict(ev.stats).get("hlo_module")
                    if module:
                        host_ops.append((str(module), float(ev.start_ns),
                                         float(ev.duration_ns)))
            if spans:
                threads.append(spans)
    return {"devices": devices, "threads": threads, "host_ops": host_ops,
            "fetch_marks": marks}


def reduce_trace(planes: dict) -> dict:
    """The summary, from read_planes' lists. Pure arithmetic."""
    devices = planes["devices"]
    rehearsal = not devices
    if rehearsal and planes["host_ops"]:
        # No device plane (the CPU): the op events stand in for the device,
        # and their modules have seconds but no runs.
        devices = [{"ops": [(s, d) for _, s, d in planes["host_ops"]],
                    "modules": []}]
    all_spans = [s for line in planes["threads"] for s in line]
    cover = Cover(all_spans)
    n = len(devices)
    out = {"planes": 0 if rehearsal else n, "busy_s": 0.0, "window_s": 0.0,
           "idle_s": 0.0, "modules": {}, "spans": {}, "threads": [],
           "idle_by_span": {}, "gaps": []}
    longest = []
    window = None
    for dev in devices:
        u = busy_and_gaps(dev["ops"])
        window = (u["first"], u["last"]) if window is None else (
            min(window[0], u["first"]), max(window[1], u["last"]))
        out["busy_s"] += u["busy_ns"] / 1e9 / n
        out["window_s"] += (u["last"] - u["first"]) / 1e9 / n
        idle = attribute(u["gaps"], cover)
        for name, ns in idle["by_span"].items():
            out["idle_by_span"][name] = (out["idle_by_span"].get(name, 0.0)
                                         + ns / 1e9 / n)
        longest += [(ns / 1e9, (lo - u["first"]) / 1e9, name)
                    for lo, ns, name in idle["gaps"]]
        for name, (s, runs) in module_seconds(dev["modules"]).items():
            row = out["modules"].setdefault(name, [0.0, 0.0])
            row[0] += s / n
            row[1] += runs / n
    if rehearsal:
        for module, _, dur in planes["host_ops"]:
            row = out["modules"].setdefault(module, [0.0, 0.0])
            row[0] += dur / 1e9
    out["idle_s"] = sum(out["idle_by_span"].values())
    out["gaps"] = [[round(at, 6), s, name]
                   for s, at, name in sorted(longest, reverse=True)[:10]]
    for line in planes["threads"]:
        times = span_times(line, window)
        for name, (s, self_s, count) in times.items():
            row = out["spans"].setdefault(name, [0.0, 0.0, 0])
            row[0] += s
            row[1] += self_s
            row[2] += count
        if times:
            lo = min(s[1] for s in line)
            hi = max(s[1] + s[2] for s in line)
            if window is not None:
                lo, hi = max(lo, window[0]), min(hi, window[1])
            out["threads"].append({
                "window_s": max(hi - lo, 0.0) / 1e9,
                "covered_s": sum(v[1] for v in times.values()),
                "self_s": {k: v[1] for k, v in sorted(
                    times.items(), key=lambda kv: -kv[1][1])}})
    # The busiest threads first: the engine's or the trainer's loop, then
    # the request handlers.
    out["threads"] = sorted(out["threads"],
                            key=lambda t: -t["covered_s"])[:8]
    check = fetch_check(planes.get("fetch_marks", []))
    if check:
        out["fetch_check"] = check
    return out


def main(argv: list[str]) -> int:
    trace = xplane.find_trace(argv[0])
    if trace is None:
        print(f"xplane_host: no *.xplane.pb under {argv[0]}",
              file=sys.stderr)
        return 1
    out = reduce_trace(read_planes(trace))
    out["trace"] = os.path.relpath(trace)
    text = json.dumps(out, indent=1)
    if len(argv) > 1:
        with open(argv[1], "w") as fh:
            fh.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
