"""Cell kind "train": one trainer child through the entry point the JAXJob
executor launches (`python -m kubeflow_tpu.train.trainer --spec <json>`).

The spec is made from the configuration's `registry_model` / `model_kwargs`
and the job's file; `--seed` seeds the weights and the data. The trainer
blocks on the device once per `log_every` steps and prints a row; this
parent reads the rows as they arrive and stamps them with its own clock.
Set-up ends when the row of step `warmup_steps` arrives (it holds the
compile or the cache load and two warm windows); the measured window runs
from there to the last row, over a number of steps fixed by the job's
`step_s_nominal`, so both sides of a later comparison run the same steps
and the trainer exits by itself, its trace flushed.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import threading
import time

import common
from common import BenchError


def plan_steps(job: dict, seconds: float) -> tuple[int, int]:
    """(warm-up steps, total steps): the measured steps are the least
    multiple of `log_every` that fills `seconds` at the nominal step time,
    and never fewer than the traced run's profiler window needs."""
    warm, every = int(job["warmup_steps"]), int(job["log_every"])
    if warm % every:
        raise BenchError("warmup_steps must be a multiple of log_every")
    want = max(math.ceil(seconds / float(job["step_s_nominal"])),
               int(job["profile_steps"][1]) - warm)
    return warm, warm + every * math.ceil(want / every)


def make_spec(ctx, steps: int) -> dict:
    job, cfg = ctx.mix, ctx.config
    seed = ctx.seed % (2 ** 31 - 1)  # jax.random.key takes 32 signed bits
    spec = {
        "model": cfg["registry_model"],
        "model_kwargs": cfg["model_kwargs"],
        "dataset": job["dataset"],
        "dataset_kwargs": {"vocab_size": cfg["model_kwargs"]["vocab_size"],
                           **job.get("dataset_kwargs", {})},
        "seq_len": job["seq_len"],
        "batch_size": job["batch_per_chip"] * ctx.chips,
        "steps": steps,
        "seed": seed,
        "metrics_path": os.path.join(ctx.out, "metrics.jsonl"),
    }
    for key in ("learning_rate", "log_every", "loss_impl", "loss_chunk",
                "prefetch", "fsdp", "mesh", "param_dtype"):
        if key in job:
            spec[key] = job[key]
    if ctx.trace:
        spec["profile_start_step"], spec["profile_stop_step"] = (
            job["profile_steps"])
    return spec


def run(ctx) -> dict:
    job = ctx.mix
    warm, steps = plan_steps(job, ctx.seconds)
    spec = make_spec(ctx, steps)
    spec_path = os.path.join(ctx.out, "spec.json")
    with open(spec_path, "w") as fh:
        json.dump(spec, fh, indent=1)
    log_path = os.path.join(ctx.out, "trainer.log")
    proc = ctx.spawn(["-m", "kubeflow_tpu.train.trainer", "--spec",
                      spec_path], log_path, stdout=subprocess.PIPE)
    watchdog = threading.Timer(max(ctx.left(), 1.0), ctx.stop, (proc,))
    watchdog.daemon = True
    watchdog.start()
    rows: list[tuple[float, dict]] = []  # (arrival on this clock, row)
    others: list[dict] = []
    try:
        with open(os.path.join(ctx.out, "trainer.out"), "w") as keep:
            for line in proc.stdout:
                now = time.monotonic()
                keep.write(line)
                row = common.parse_json_line(line)
                if row is None:
                    continue
                if "loss" in row and "event" not in row:
                    rows.append((now, row))
                else:
                    others.append(row)
                    if row.get("event") == "device":
                        ctx.check_device(row, "trainer")
        rc = proc.wait()
    finally:
        watchdog.cancel()
    dev = ctx.check_device(common.event(others, "device"), "trainer")
    if rc != 0:
        raise BenchError(f"trainer: exit code {rc}\n"
                         + common.tail(log_path))
    by_step = {int(r["step"]): (t, r) for t, r in rows}
    if warm not in by_step or steps not in by_step:
        raise BenchError(f"trainer: no row for step {warm} or {steps}; "
                         f"got {sorted(by_step)}")
    t_warm, t_last = by_step[warm][0], by_step[steps][0]
    measured = [r for _, r in rows if int(r["step"]) > warm]
    tokens = (steps - warm) * spec["batch_size"] * spec["seq_len"]
    end = common.event(others, "device_end")

    losses = [r["loss"] for _, r in rows]
    target = math.log(spec["dataset_kwargs"]["vocab_size"])
    # Random tokens: the loss starts near ln(vocab) and cannot leave it in
    # a few dozen steps at this learning rate. NaN, 0 or something far off
    # means a wrong mask or scale; the pinned step-`warm` loss catches a
    # step that computes something else than it did when the cell was made.
    correct = all(math.isfinite(x) and abs(x - target) < 1.5
                  for x in losses)
    expect = job.get("expect", {}).get("loss_at_warmup")
    if expect is not None and not ctx.rehearse:
        correct = correct and abs(by_step[warm][1]["loss"] - expect) <= \
            job["expect"]["tolerance"]

    ctx.facts.update(rows=measured, spec=spec, tokens=tokens,
                     window_s=t_last - t_warm)
    if ctx.trace:
        ctx.facts["xplane"] = common.summarize_trace(
            ctx, os.path.join(ctx.out, "profile"))
    return {
        "correct": correct,
        "attempted": steps - warm,
        "failed": 0,
        "e2e": {"setup_s": t_warm - ctx.t0,
                "train_tok_s": tokens / (t_last - t_warm) / ctx.chips},
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"],
                   "memory_peak_bytes": common.peak_bytes(end)},
        "parts": {**({} if ctx.rehearse else
                     {"compile_s": (end or {}).get("compile_s")}),
                  "compile_cache_hits": (end or {}).get(
                      "compile_cache_hits"),
                  "peak_bytes_in_use": (end or {}).get("peak_bytes_in_use"),
                  "steps": steps, "warmup_steps": warm,
                  "loss_at_warmup": by_step[warm][1]["loss"],
                  "loss_last": by_step[steps][1]["loss"]},
    }
