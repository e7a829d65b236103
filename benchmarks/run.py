#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A stdlib parent that never imports JAX (a chip belongs to one process).
It finds everything that belongs to the cell by name:

    BENCHMARK.json                 the cell, its configuration's file, the metrics
    traffic/<traffic>.json         the mix or job; its `kind` names the cell kind
    kinds/<kind>.py                starts the program's entry point, measures, checks
    layer_metrics/<metric>.json    one per-layer metric: its reader and arguments
    readers/<reader>.py            read(ctx, **args) -> number | None

runs the cell, and prints the contract's one JSON object as its last line:
with `--trace 0` the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics, `device.busy_s` / `device.window_s` and a `breakdown`.
Whatever it writes goes under `benchmarks/out/<cell>/`, wiped per run, and
into the compile cache that `kubeflow_tpu.utils.devices` places.

`--rehearse-cpu` runs the same code under JAX_PLATFORMS=cpu with the
`rehearsal` blocks of the configuration and the mix (toy sizes). It says
`platform: cpu`, puts no metric into `metrics`, and is never a result: it
is for finding every bug that is not about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402
from common import BENCH, ROOT, BenchError  # noqa: E402


def applies(metric: dict, cell_name: str) -> bool:
    """A metric with no `workloads` key is reported in every cell."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(bench: dict, cell_name: str, rehearse: bool) -> tuple:
    """(cell, configuration, mix, kind module) for a cell's name."""
    cell = next((w for w in bench["workloads"] if w["name"] == cell_name),
                None)
    if cell is None:
        raise BenchError(f"no workload {cell_name!r} in BENCHMARK.json; "
                         f"have {[w['name'] for w in bench['workloads']]}")
    entry = next((c for c in bench["configs"] if c["name"] == cell["config"]),
                 None)
    if entry is None:
        raise BenchError(f"workload {cell_name!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")
    config = common.load_json(os.path.join(ROOT, entry["file"]))
    mix = common.load_json(
        os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        for block in (config, mix):
            block.update(block.get("rehearsal", {}))
    kind = common.load_module(
        os.path.join(BENCH, "kinds", mix["kind"] + ".py"))
    return cell, config, mix, kind


def end_to_end(bench: dict, cell_name: str, found: dict) -> dict:
    out = {}
    for m in bench["end_to_end"]:
        if not applies(m, cell_name):
            continue
        if m["name"] not in found:
            raise BenchError(f"the run did not measure {m['name']!r}")
        out[m["name"]] = {"value": found[m["name"]], "unit": m["unit"]}
    return out


def per_layer(bench: dict, cell_name: str, ctx) -> dict:
    """Each per-layer metric is a file of its own naming a reader of its
    own. A reader that finds nothing to read returns None and the metric
    is left out of the line."""
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell_name):
            continue
        spec = common.load_json(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".json"))
        reader = common.load_module(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str]) -> int:
    t0 = time.monotonic()
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    ctx = None
    try:
        if not os.path.isdir(os.path.join(ROOT, "kubeflow_tpu")):
            raise BenchError("no kubeflow_tpu/ beside benchmarks/: there "
                             "is no program here to measure")
        bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell, config, mix, kind = resolve(bench, args.workload,
                                          args.rehearse_cpu)
        seconds = (args.seconds if args.seconds is not None
                   else float(bench["run_seconds"]))
        ctx = common.Ctx(cell=cell, config=config, mix=mix, seed=args.seed,
                         seconds=seconds, trace=bool(args.trace),
                         rehearse=args.rehearse_cpu, t0=t0)
        shutil.rmtree(ctx.out, ignore_errors=True)
        os.makedirs(ctx.out)
        res = kind.run(ctx)
        ctx.facts["e2e"] = res["e2e"]
        metrics = (per_layer(bench, cell["name"], ctx) if ctx.trace
                   else end_to_end(bench, cell["name"], res["e2e"]))
        line = {"correct": bool(res["correct"]),
                "attempted": int(res["attempted"]),
                "failed": int(res["failed"]),
                "metrics": metrics, "device": res["device"]}
        if ctx.trace and ctx.facts.get("xplane"):
            x = ctx.facts["xplane"]
            line["device"]["busy_s"] = x["busy_s"]
            line["device"]["window_s"] = x["window_s"]
            line["breakdown"] = {
                "device_ops": [[n[:120], s] for n, s in x["ops"][:10]],
                "idle_gaps": x["gaps"][:10]}
        if ctx.rehearse:
            # A CPU number never goes out under a device metric's name.
            print(json.dumps({"rehearsal": True,
                              "metrics_computed": sorted(metrics)}))
            line["metrics"] = {}  # (its trace has no device plane either)
        print(json.dumps({"parts": res.get("parts", {})}))
        print(json.dumps(line), flush=True)
        return 0
    except BenchError as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 1
    except Exception:  # the boundary: report, stop the children, fail
        traceback.print_exc()
        return 1
    finally:
        if ctx is not None:
            ctx.stop_all()


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
