"""Cell kind "train_ref": the "train" kind's timed run, then `correct` held
against a plain reference.

The timed window is `kinds/train.py`'s, unchanged (its `run` is called). After
it, outside every metric:

  1. one more trainer child through the same entry point with the same spec,
     `steps` 1 and `checkpoint: {dir, interval: 1}`: the trainer's own first
     step, its `loss` and `grad_norm` on the log row, its first Adam moment
     in the checkpoint (mu = (1 - b1) * g after one step, so g = mu / (1 - b1)
     is the gradient the step applied, to fp32 rounding);
  2. the configuration's `reference.script` in a child of its own (the chip
     is free again): it rebuilds the weights and the first batch from the seed
     through the program's initialiser and dataset (data, not code under
     test), computes loss and gradients with `benchmarks/reference/`, and
     writes what it compared.

`correct` then also needs (a) |loss - loss_ref| <= loss_abs, (b) |grad_norm -
grad_norm_ref| / grad_norm_ref <= grad_norm_rel, (c) of the per-tensor errors
||g - g_ref|| / ||g_ref||, the median and the largest within their limits,
for the routed experts' and routers' tensors and for all the others apart:
the limits the configuration's `reference` block gives with their reasons
(the job's file names no model, so another configuration can run it). The compared numbers go
out beside their limits under `parts.compared`, the line before the result.
No switch of the trainer is involved.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import time

import common
from common import BenchError

train = common.load_module(os.path.join(common.BENCH, "kinds", "train.py"))


def first_step(ctx) -> tuple[dict, str, str]:
    """Run the trainer for one step with a checkpoint. Returns (the row of
    step 1, the spec's path, the checkpoint's directory)."""
    spec = dict(ctx.facts["spec"])
    ckpt = os.path.join(ctx.out, "ref_ckpt")
    spec.update(steps=1, log_every=1,
                checkpoint={"dir": ckpt, "interval": 1, "async_save": False},
                metrics_path=os.path.join(ctx.out, "ref_metrics.jsonl"))
    for key in ("profile_start_step", "profile_stop_step"):
        spec.pop(key, None)
    path = os.path.join(ctx.out, "ref_spec.json")
    with open(path, "w") as fh:
        json.dump(spec, fh, indent=1)
    log = os.path.join(ctx.out, "ref_trainer.log")
    proc = ctx.spawn(["-m", "kubeflow_tpu.train.trainer", "--spec", path],
                     log, stdout=subprocess.PIPE)
    try:
        out, _ = proc.communicate(timeout=max(ctx.left(), 1.0))
    except subprocess.TimeoutExpired:
        ctx.stop(proc)
        raise BenchError("reference step: the trainer did not finish\n"
                         + common.tail(log)) from None
    if proc.returncode != 0:
        raise BenchError(f"reference step: trainer exit code "
                         f"{proc.returncode}\n" + common.tail(log))
    rows = [r for r in map(common.parse_json_line, out.splitlines())
            if r and "loss" in r and "event" not in r and r.get("step") == 1]
    if not rows:
        raise BenchError("reference step: no row for step 1")
    return rows[0], path, ckpt


def reference(ctx, spec_path: str, ckpt: str) -> dict:
    ref = ctx.config["reference"]
    out = os.path.join(ctx.out, "ref_check.json")
    cfg_path = os.path.join(ctx.out, "ref_config.json")
    with open(cfg_path, "w") as fh:
        json.dump(ref["rehearsal_config" if ctx.rehearse else "config"], fh)
    log = os.path.join(ctx.out, "ref_check.log")
    proc = ctx.spawn([os.path.join(common.BENCH, ref["script"]),
                      "--spec", spec_path, "--config", cfg_path,
                      "--checkpoint", ckpt, "--out", out], log)
    try:
        rc = proc.wait(timeout=max(ctx.left(), 1.0))
    except subprocess.TimeoutExpired:
        ctx.stop(proc)
        raise BenchError("reference: did not finish\n"
                         + common.tail(log)) from None
    if rc != 0:
        raise BenchError(f"reference: exit code {rc}\n" + common.tail(log))
    return common.load_json(out)


def compare(row: dict, ref: dict, limits: dict) -> tuple[bool, dict]:
    """(every comparison inside its limit, the numbers beside the limits).
    The per-tensor errors are held twice for each of the two classes of
    tensor (`is_expert`: the routed experts' weights and the routers): their
    median, which a change of precision moves and a seed hardly does, and
    their largest, which one wrong tensor moves."""
    compared = {
        "loss": {"trainer": row["loss"], "reference": ref["loss"],
                 "err": abs(row["loss"] - ref["loss"]),
                 "limit": limits["loss_abs"]},
        "grad_norm": {"trainer": row["grad_norm"],
                      "reference": ref["grad_norm"],
                      "err": abs(row["grad_norm"] - ref["grad_norm"])
                      / ref["grad_norm"],
                      "limit": limits["grad_norm_rel"]},
    }
    for which, expert in (("grad_rel", False), ("grad_rel_experts", True)):
        errs = sorted((err, name) for name, err in ref["grad_rel_err"].items()
                      if ref["is_expert"][name] == expert)
        compared[which + "_median"] = {
            "tensors": len(errs), "err": statistics.median(e for e, _ in errs),
            "limit": limits[which + "_median"]}
        compared[which + "_max"] = {
            "worst_tensor": errs[-1][1], "err": errs[-1][0],
            "limit": limits[which + "_max"]}
    ok = all(c["err"] <= c["limit"] for c in compared.values())
    return ok, compared


def run(ctx) -> dict:
    res = train.run(ctx)
    t0 = time.monotonic()
    row, spec_path, ckpt = first_step(ctx)
    t1 = time.monotonic()
    ref = reference(ctx, spec_path, ckpt)
    ok, compared = compare(row, ref, ctx.config["reference"]["limits"])
    res["correct"] = bool(res["correct"] and ok)
    # The check's own seconds, outside every metric: the one-step trainer
    # child, then the reference child (of which `restore_s` reading the
    # first moment and `reference_pass_s` the pass itself).
    res["parts"]["check_s"] = {
        "first_step": t1 - t0, "reference": time.monotonic() - t1,
        **{k: ref.get(k) for k in ("init_s", "restore_s",
                                   "reference_pass_s")}}
    res["parts"]["compared"] = compared
    return res
