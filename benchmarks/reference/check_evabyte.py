#!/usr/bin/env python3
"""The reference side of the "serve_ref" kind for EvaByte, a process of its
own (it takes the chip after the server has left it):

    check_evabyte.py --bundle <dir with model.json> --config <reference cfg>
                     --probes <json> --out <json>
                     [--control no_summary,sliding,...]

Weights are rebuilt from the bundle's seed through the program's own
initialiser, exactly as the jax-registry runtime made them inside the server
(`registry.build_model(...).init(jax.random.key(seed), ...)`): data, not code
under test. `--probes` holds what the server answered: for each request (the
probes sent before the loop and the sample of requests the window finished)
its prompt ids, the ids it emitted (greedy) and their streamed
`output_logprobs`. The reference (`benchmarks/reference/evabyte.py`) runs
over prompt + emitted ids, teacher-forced, twice: in fp32 at `highest` and
in the precision the configuration states (`precision="stated"`). Head 0's
log-softmax at the same positions for the same ids is held against the
server's: the logits' values, not sampled ids. Written out: for each request
its largest and mean absolute gap to the fp32 reference; over all requests

  `logprob_gap_max`, `logprob_gap_mean`  the largest and the mean |server -
      fp32 reference|: what a wrong program moves;
  `stated_gap_mean`  the mean |server - stated reference|: the server's
      distance from a plain forward of its own stated precision. The
      weights' rounding to bf16, which the two share and which is most of
      either's gap to fp32 (one draw a seed, so that gap swings from seed
      to seed), cancels; what is left is the rounding of activations, which
      averages over many draws and reads alike at every seed. A program
      that rounds more than the configuration states (its residual stream,
      its scores or its logits in bf16) lies twice as far off.

`--control` is never the check. It makes the readings the limits in the
configuration's file were set between: a program that is wrong in one named
way (`evabyte.CONTROLS`; its logprobs are the reference's computed that way,
at the ids the server emitted), held exactly as the server's are, each under
`controls` in the shape of the check's own result, so that
`serve_ref.compare` can be fed it whole. `arrays` holds the compared
logprobs themselves, position by position (the server's, each reference's,
each control's).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def logprobs(ref, params, cfg: dict, probes: list, **how):
    """Head 0's log-softmax of each emitted id at its position, teacher-
    forced over prompt + emitted ids, one array a request. `how`: the
    reference's `control` or `precision`."""
    import jax
    import numpy as np

    out = []
    for pr in probes:
        ids, emitted = pr["input_ids"], pr["output_ids"]
        logits = ref.forward(params, ids + emitted, cfg, **how)
        lp = jax.nn.log_softmax(
            logits[len(ids) - 1:len(ids) - 1 + len(emitted), 0], axis=-1)
        out.append(np.asarray(lp, np.float64)[np.arange(len(emitted)),
                                              np.asarray(emitted)])
    return out


def held(got: list, fp32: list, stated: list, probes: list) -> dict:
    """A program's logprobs `got` against the two references'."""
    import numpy as np

    rows, every, off = [], [], []
    for pr, g, want, near in zip(probes, got, fp32, stated):
        g = np.asarray(g, np.float64)
        gap = np.abs(g - want)
        rows.append({"group": pr.get("group", "probe"),
                     "prompt": len(pr["input_ids"]), "output": len(g),
                     "gap_max": float(gap.max()),
                     "gap_mean": float(gap.mean()),
                     "stated_gap_mean": float(np.abs(g - near).mean())})
        every.append(gap)
        off.append(np.abs(g - near))
    every = np.concatenate(every)
    return {"probes": rows, "compared_bytes": int(every.size),
            "logprob_gap_max": float(every.max()),
            "logprob_gap_mean": float(every.mean()),
            "stated_gap_mean": float(np.concatenate(off).mean())}


def main(argv: list[str]) -> int:
    t0 = time.monotonic()
    p = argparse.ArgumentParser()
    p.add_argument("--bundle", required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--probes", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--control", default="")
    args = p.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    # The reference beside this file; the program at the checkout's root
    # (the kind passes it in PYTHONPATH, a run by hand need not).
    sys.path[:0] = [here, os.path.dirname(os.path.dirname(here))]

    import flax.linen as nn
    import jax
    import numpy as np

    import evabyte as ref
    from kubeflow_tpu.utils import registry
    from kubeflow_tpu.utils.devices import enable_compile_cache

    controls = [c for c in args.control.split(",") if c]
    if set(controls) - set(ref.CONTROLS):
        raise SystemExit(f"--control takes {list(ref.CONTROLS)}")
    enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"event": "device", "platform": dev.platform,
                      "kind": dev.device_kind,
                      "count": jax.device_count()}), flush=True)
    with open(os.path.join(args.bundle, "model.json")) as fh:
        spec = json.load(fh)
    with open(args.config) as fh:
        cfg = json.load(fh)
    with open(args.probes) as fh:
        probes = json.load(fh)
    module, info = registry.build_model(spec["model"],
                                        **spec.get("model_kwargs", {}))
    example = np.zeros((1, *info["example_shape"][1:]),
                       info["example_dtype"])
    params = nn.meta.unbox(
        module.init(jax.random.key(spec.get("seed", 0)), example)["params"])
    t1 = time.monotonic()
    fp32 = logprobs(ref, params, cfg, probes)
    stated = logprobs(ref, params, cfg, probes, precision="stated")
    out = held([pr["output_logprobs"] for pr in probes], fp32, stated,
               probes)
    out.update(init_s=t1 - t0, reference_pass_s=time.monotonic() - t1)
    arrays = {"server": [pr["output_logprobs"] for pr in probes],
              "fp32": fp32, "stated": stated}
    if controls:
        out["controls"] = {}
        for c in controls:
            arrays[c] = logprobs(ref, params, cfg, probes, control=c)
            out["controls"][c] = held(arrays[c], fp32, stated, probes)
    out["arrays"] = {k: [np.asarray(a).tolist() for a in v]
                     for k, v in arrays.items()}
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
