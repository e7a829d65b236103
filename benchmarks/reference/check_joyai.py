#!/usr/bin/env python3
"""The reference side of the "serve_ref" kind for JoyAI-LLM-Flash, a process
of its own (it takes the chip after the server has left it):

    check_joyai.py --bundle <dir with model.json> --config <reference cfg>
                   --probes <json> --out <json>
                   [--control rope_half,raw_gates,...]

Weights are rebuilt from the bundle's seed through the program's own
initialiser, as the jax-registry runtime made them inside the server
(`serve/weights.py:Seeded` over `registry.build_model(...).init(
jax.random.key(seed), ...)`: a leaf has the bits the whole `init` gives it):
data, not code under test. The whole fp32 tree (22.2 GB) does not fit the chip,
so the reference goes layer by layer: one top-level group of the tree is made
in fp32, every sequence of every variant is taken through it, and it is
dropped before the next is made.

`--probes` holds what the server answered: for each request (the probes sent
before the loop and the sample of requests the window finished) its prompt
ids, the ids it emitted (greedy) and their streamed `output_logprobs`. The
reference (`benchmarks/reference/joyai.py`) runs over prompt + emitted ids,
teacher-forced, in fp32 at `highest` and in the precision the configuration
states (`precision="stated"`). Its log-softmax at the same positions for the
same ids is held against the server's: the logits' values, not sampled ids.
Written out, as `check_evabyte.py` does: for each request its largest and mean
absolute gap to the fp32 reference; over all requests `logprob_gap_max`,
`logprob_gap_mean` (|server - fp32 reference|: what a wrong program moves) and
`stated_gap_mean` (|server - stated reference|: the weights' rounding to bf16,
which the two share, cancels; a program that rounds more than the configuration
states lies further off).

A top-8 of 256 near-equal scores flips on a rounding, and a flipped token
lies a whole expert away from the reference's: against fp32 a few percent of
the tokens do, at every seed alike, which is why the largest gap's limit is
wide and the mean's is what a wrong program moves. The stated reference shares
the server's roundings before the router (the same bf16 products in another
order), so far fewer tokens flip against it.

`--control` is never the check. It makes the readings the limits in the
configuration's file were set between: a program that is wrong in one named
way (`joyai.CONTROLS`; its logprobs are the reference's computed that way, at
the ids the server emitted), held exactly as the server's are, each under
`controls` in the shape of the check's own result, so that `serve_ref.compare`
can be fed it whole. `arrays` holds the compared logprobs themselves.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def logprobs(ref, get, cfg: dict, probes: list, variants: dict) -> dict:
    """For each variant (a name -> the reference's `precision` / `control`)
    the log-softmax of each emitted id at its position, teacher-forced over
    prompt + emitted ids, one array a request. A group of the tree is made
    once and serves every sequence of every variant."""
    import jax
    import numpy as np

    seqs = [pr["input_ids"] + pr["output_ids"] for pr in probes]
    table = get("embed")
    x = {v: [ref.embed(table, s, **how) for s in seqs]
         for v, how in variants.items()}
    del table
    for i in range(cfg["num_hidden_layers"]):
        group = get(f"layer_{i}")
        for v, how in variants.items():
            x[v] = [jax.block_until_ready(ref.layer(group, xs, cfg, **how))
                    for xs in x[v]]
        del group
    norm, w_head = get("final_norm"), get("lm_head")
    out = {}
    for v, how in variants.items():
        out[v] = []
        for pr, xs in zip(probes, x[v]):
            first, emitted = len(pr["input_ids"]) - 1, pr["output_ids"]
            lp = jax.nn.log_softmax(ref.head(
                norm, w_head, xs[first:first + len(emitted)], cfg, **how),
                axis=-1)
            out[v].append(np.asarray(lp, np.float64)[
                np.arange(len(emitted)), np.asarray(emitted)])
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
    every, off = np.concatenate(every), np.concatenate(off)
    return {"probes": rows, "compared_bytes": int(every.size),
            "logprob_gap_max": float(every.max()),
            "logprob_gap_mean": float(every.mean()),
            "logprob_gap_median": float(np.median(every)),
            "stated_gap_mean": float(off.mean()),
            "stated_gap_median": float(np.median(off)),
            "stated_gap_p90": float(np.percentile(off, 90))}


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

    import jax
    import numpy as np

    import joyai as ref
    from kubeflow_tpu.serve.weights import Seeded
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
    seeded = Seeded(module, jax.random.key(spec.get("seed", 0)),
                    np.zeros((1, *info["example_shape"][1:]),
                             info["example_dtype"]))
    t1 = time.monotonic()
    variants = {"fp32": {}, "stated": {"precision": "stated"},
                **{c: {"control": c} for c in controls}}
    arrays = logprobs(ref, seeded.group, cfg, probes, variants)
    arrays["server"] = [pr["output_logprobs"] for pr in probes]
    out = held(arrays["server"], arrays["fp32"], arrays["stated"], probes)
    out.update(init_s=t1 - t0, reference_pass_s=time.monotonic() - t1)
    if controls:
        out["controls"] = {c: held(arrays[c], arrays["fp32"],
                                   arrays["stated"], probes)
                           for c in controls}
    out["arrays"] = {k: [np.asarray(a).tolist() for a in v]
                     for k, v in arrays.items()}
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
