"""Device self time by `jax.named_scope` path, from a profiler trace.

    python benchmarks/xplane_scopes.py <trace dir or file> <out.json>

`xplane.py` names an operation by its HLO text, which holds shapes but not
where in the program it came from. The compiler also keeps each operation's
`op_name` metadata (`jit(step)/.../layer_0/kda/kda_scan/while`), the path of
flax modules and `jax.named_scope`s around the call that made it, and the
TPU's profiler writes it as the stat `tf_op` of the event's *metadata* (seen
in a chip trace, PR 28: `jax.profiler.ProfileData` shows an event's own stats
only, `device_offset_ps` and the like, so this file reads the protobuf itself,
with the `xplane_pb2` that the installed TensorFlow or tsl brings). This
reduction sums *self* time (an event nested in another, the body of a
`while`, is taken out of its parent, as in xplane.py) by that path:

    {"planes": n, "busy_s": ..., "stat": "tf_op",
     "paths": [[path, seconds], ...]}

averaged over the device planes. A fusion carries one path, its root's, so an
elementwise operation fused into its consumer is counted under the consumer's
scope; an operation the compiler made itself (most of the optimizer's) has
none and is keyed "(no path) <its name>". Where no `xplane_pb2` can be
imported, or no event carries the stat, the result says `"stat": null` with
no paths, and the readers return None. Run as a short process of its own
under JAX_PLATFORMS=cpu, like xplane.py.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xplane  # noqa: E402

#: The stat of an event's metadata that carries the op_name path.
PATH_STAT = "tf_op"
#: Where an `xplane_pb2` has been found.
PROTO_MODULES = ("tsl.profiler.protobuf.xplane_pb2",
                 "tensorflow.tsl.profiler.protobuf.xplane_pb2",
                 "xprof.protobuf.xplane_pb2")


def xplane_pb2():
    for name in PROTO_MODULES:
        try:
            return importlib.import_module(name)
        except ImportError:
            continue
    return None


def reduce_plane(raw: list[tuple[str, float, float, str | None]]) -> dict:
    """`raw` are (name, start_ns, duration_ns, path or None) of one device's
    op line. Pure arithmetic: `xplane.reduce_events` with each event keyed
    by its path (an event without one by "(no path) " + its name)."""
    return xplane.reduce_events(
        [(path or "(no path) " + name.split(" = ")[0][:60], start, dur)
         for name, start, dur, path in raw])


def plane_events(plane) -> list[tuple[str, float, float, str | None]]:
    """(name, start_ns, duration_ns, path) of a device plane's op line,
    the path from the event metadata's `tf_op` stat."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    paths = {}
    for key, meta in plane.event_metadata.items():
        for st in meta.stats:
            if stat_names.get(st.metadata_id) == PATH_STAT:
                paths[key] = (st.str_value
                              or stat_names.get(st.ref_value) or None)
    out = []
    for line in plane.lines:
        if not re.search(xplane.OP_LINE, line.name):
            continue
        t0 = float(line.timestamp_ns)
        for ev in line.events:
            meta = plane.event_metadata[ev.metadata_id]
            out.append((meta.name, t0 + ev.offset_ps / 1e3,
                        ev.duration_ps / 1e3, paths.get(ev.metadata_id)))
    return out


def summarize(path: str) -> dict:
    pb2 = xplane_pb2()
    if pb2 is None:
        return merge([], None)
    space = pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    per_plane, seen = [], False
    for plane in space.planes:
        if not re.search(xplane.DEVICE_PLANE, plane.name):
            continue
        raw = plane_events(plane)
        if raw:
            seen = seen or any(r[3] for r in raw)
            per_plane.append(reduce_plane(raw))
    return merge(per_plane, PATH_STAT if seen else None)


def merge(per_plane: list[dict], stat: str | None) -> dict:
    n = len(per_plane)
    if not n:
        return {"planes": 0, "busy_s": 0.0, "stat": None, "paths": []}
    paths: dict[str, float] = {}
    for red in per_plane:
        for key, s in red["ops"].items():
            paths[key] = paths.get(key, 0.0) + s / n
    ranked = sorted(paths.items(), key=lambda kv: -kv[1])
    return {"planes": n, "busy_s": sum(r["busy_s"] for r in per_plane) / n,
            "stat": stat, "paths": [[k, v] for k, v in ranked] if stat else []}


def main(argv: list[str]) -> int:
    trace = xplane.find_trace(argv[0])
    if trace is None:
        print(f"xplane_scopes: no *.xplane.pb under {argv[0]}",
              file=sys.stderr)
        return 1
    text = json.dumps(summarize(trace))
    if len(argv) > 1:
        with open(argv[1], "w") as fh:
            fh.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
