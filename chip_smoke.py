#!/usr/bin/env python3
"""chip_smoke.py — does tpukit still start on the chip?

Drives the two loops users pay TPU time for, through the entry points the
control plane itself launches, at the full width and depth of `llama_1b()`
(random weights from a seed), and checks what comes out:

  kernels  one child: `flash_attention(interpret=False)` forward+backward at
           the model's attention shape — causal, packed (`segment_ids`) and
           sliding-window — against `ops/reference.py` in fp32.
  serve    `python -m kubeflow_tpu.serve.server --model-dir <bundle>` (paged
           pool, prefix cache, depth-2 pipelining), eight `:generate`
           requests incl. a streamed one, an OpenAI one and a repeat that
           must hit the prefix cache; stopped with SIGTERM.
  train    `python -m kubeflow_tpu.train.trainer --spec <json>` (the
           controller's argv): 8 steps of batch 8/chip, seq 1024, with the
           spec's profiler window over two steady steps.

This parent is plain stdlib and never imports JAX: a chip belongs to one
process at a time, so each phase is a child that has fully exited before the
next starts (the train phase running after the server is the proof that the
server's SIGTERM shutdown released the chip).

    python chip_smoke.py              the chip run; fails anywhere a phase
                                      does not report platform "tpu"
    python chip_smoke.py --cpu-tiny   the same phase code at llama_tiny on
                                      the CPU (interpret-mode kernels) — the
                                      test suite's guard against rot; it
                                      refuses to run on a TPU

Every phase prints one JSON line (ok, platform, device_kind, device_count,
wall_s and, when it passed, compile_s, ...); a failed phase's reason and
the tail of its log go to stderr. The last stdout line is the verdict:
`{"ok": true, "device": {...}}` with exit code 0 only if all phases passed,
else `{"ok": false, "failed": [...], "device": {...}}` and exit code 1.
Where no child comes up on the platform the run is for (no accelerator),
nothing at all is printed to stdout and the exit code is non-zero.
Everything written lands in `chip_smoke_out/` (git-ignored); compiled
programs go where kubeflow_tpu.utils.devices.enable_compile_cache() puts
them.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")

#: The driver allows 1200 s; leave room to stop children and report.
DEADLINE_S = 1140.0

#: Kernel-vs-reference tolerance, as max|kernel - ref| / max|ref| per
#: tensor. The inputs are bf16 and the kernel writes bf16 outputs and
#: gradients, so every element carries a rounding of up to 2^-9 relative
#: (~2e-3), the softmax weights are rounded once more before the P·V and
#: dS·K products, and dk/dv sum ~1e3 such terms. 2e-2 of the tensor's
#: range covers that with margin and is still two orders below what a
#: wrong mask or a misread block does (errors of the order of the range).
KERNEL_TOL = 2e-2

CHIP = {
    "platform": "tpu",
    "model": "llama_1b", "vocab": 32768,
    "train": {"seq_len": 1024, "batch_per_chip": 8, "steps": 8,
              "profile": (5, 7)},
    "serve": {"slots": 4, "max_len": 1024, "chunk": 16,
              "prefill_buckets": [128, 512], "kv_block_size": 16,
              # 2048 blocks x 16 tokens x 64 KiB/token (16 layers, 8 KV
              # heads of 128, K+V, bf16) = 2 GiB of pool.
              "kv_blocks": 2048, "prefix_cache": 16,
              # both buckets, one past the largest (chunked prefill), and
              # more requests than slots
              "prompt_lens": [40, 300, 100, 400, 90, 120, 600],
              "max_tokens": 48},
    "kernels": {"batch": 2, "seq": 1024, "heads": 16, "kv_heads": 8,
                "head_dim": 128, "block": 512, "window": 256,
                "interpret": False},
}

CPU_TINY = {
    "platform": "cpu",
    "model": "llama_tiny", "vocab": 512,
    "train": {"seq_len": 64, "batch_per_chip": 8, "steps": 6,
              "profile": (3, 5)},
    "serve": {"slots": 2, "max_len": 128, "chunk": 4,
              "prefill_buckets": [16, 32], "kv_block_size": 8,
              "kv_blocks": 64, "prefix_cache": 16,
              "prompt_lens": [6, 20, 10, 28, 12, 14, 40],
              "max_tokens": 12},
    "kernels": {"batch": 1, "seq": 128, "heads": 4, "kv_heads": 2,
                "head_dim": 16, "block": 64, "window": 48,
                "interpret": True},
}


class PhaseFailed(Exception):
    pass


# -- the kernels child (the only code in this file that imports JAX) ---------


def kernels_child(cfg: dict) -> int:
    sys.path.insert(0, HERE)
    from kubeflow_tpu.utils import devices

    devices.enable_compile_cache()
    clock = devices.compile_clock()
    dev = devices.device_summary()
    # Like both mains: the device first, before anything compiles, so a
    # kernel Mosaic refuses still leaves the parent knowing where it ran.
    print(json.dumps({"event": "device", **dev}), flush=True)
    if dev["platform"] != cfg["platform"]:
        return 3

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.flash_attention import MaskSpec, flash_attention
    from kubeflow_tpu.ops.reference import naive_attention

    k = cfg["kernels"]
    b, s, h, kh, d = (k["batch"], k["seq"], k["heads"], k["kv_heads"],
                      k["head_dim"])
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
    kk = jax.random.normal(keys[1], (b, s, kh, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, s, kh, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b, s, h, d), jnp.float32)
    # Packed rows: documents of unequal length, boundaries off the block
    # grid, a different packing in every batch row.
    seg = np.zeros((b, s), np.int32)
    for row in range(b):
        cuts = [s // 5 + 3 * row, s // 2 + 7, (7 * s) // 8 - row]
        seg[row] = np.searchsorted(np.asarray(cuts), np.arange(s),
                                   side="right")
    seg = jnp.asarray(seg)
    cases = {
        "causal": {},
        "segment_ids": {"segment_ids": seg},
        "sliding_window": {"mask": MaskSpec("sliding_window",
                                            window=k["window"])},
    }

    def scalar(fn, **kw):
        def f(q, kk, v):
            return jnp.sum(fn(q, kk, v, **kw).astype(jnp.float32) * w)
        return f

    def rel_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    def kernel(q, kk, v, **kw):
        return flash_attention(q, kk, v, True, k["block"], k["block"],
                               k["interpret"], **kw)

    def reference(q, kk, v, **kw):
        return naive_attention(q, kk, v, causal=True, **kw)

    worst = {}
    ok = True
    for name, kw in cases.items():
        out = jax.jit(lambda q, kk, v: kernel(q, kk, v, **kw))(q, kk, v)
        grads = jax.jit(jax.grad(scalar(kernel, **kw), argnums=(0, 1, 2)))(
            q, kk, v)
        f32 = [x.astype(jnp.float32) for x in (q, kk, v)]
        with jax.default_matmul_precision("highest"):
            ref_out = jax.jit(
                lambda q, kk, v: reference(q, kk, v, **kw))(*f32)
            ref_grads = jax.jit(
                jax.grad(scalar(reference, **kw), argnums=(0, 1, 2)))(*f32)
        errs = {"out": rel_err(out, ref_out)}
        for label, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
            errs[label] = rel_err(g, rg)
        worst[name] = {n: round(e, 5) for n, e in errs.items()}
        if not all(math.isfinite(e) and e <= KERNEL_TOL
                   for e in errs.values()):
            ok = False
    print(json.dumps({"event": "kernels", "interpret": k["interpret"],
                      "tolerance": KERNEL_TOL, "max_rel_err": worst,
                      **clock.snapshot()}), flush=True)
    return 0 if ok else 1


# -- parent-side plumbing ----------------------------------------------------


class Run:
    """One smoke run: the children it started (all stopped on the way
    out) and the clock every wait is bounded by."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.t0 = time.monotonic()
        self.children: list[subprocess.Popen] = []
        #: {"platform", "kind", "count"} as the last child reported it.
        self.device: dict | None = None
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [HERE] + [p for p in self.env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        if cfg["platform"] == "cpu":
            self.env["JAX_PLATFORMS"] = "cpu"

    def left(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t0)

    def spawn(self, argv: list[str], log_path: str) -> subprocess.Popen:
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=HERE, env=self.env,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        finally:
            log.close()  # the child holds its own descriptor
        self.children.append(proc)
        return proc

    def wait(self, proc: subprocess.Popen, what: str) -> int:
        try:
            return proc.wait(timeout=max(self.left(), 1.0))
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise PhaseFailed(f"{what}: still running at the smoke's "
                              f"{DEADLINE_S:.0f}s limit; stopped") from None

    def stop(self, proc: subprocess.Popen, grace_s: float = 60.0) -> None:
        """SIGTERM and wait — a SIGKILLed libtpu process can leave the
        chip locked, so the kill is the last resort only."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def stop_all(self) -> None:
        for proc in self.children:
            self.stop(proc, grace_s=20.0)


def _json_lines(path: str) -> list[dict]:
    rows = []
    with open(path, errors="replace") as fh:
        for line in fh:
            line = line.strip()
            if line.startswith("{"):
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    pass
    return rows


def _tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, errors="replace") as fh:
            return fh.read()[-n:]
    except OSError as e:
        return f"<{e}>"


def _event(rows: list[dict], name: str) -> dict | None:
    return next((r for r in rows if r.get("event") == name), None)


def _check_device(run: Run, log: str, who: str) -> dict:
    """The child's own `device` line (its first, printed before anything
    compiles) must name the platform this run is for. Records it on the
    run; returns the phase line's device fields."""
    row = _event(_json_lines(log), "device")
    if row is None:
        raise PhaseFailed(f"{who}: never reported its device\n"
                          + _tail(log))
    dev = {key: row.get(key) for key in ("platform", "kind", "count")}
    if dev["platform"] != run.cfg["platform"]:
        raise PhaseFailed(f"{who}: came up on {dev}, this run needs "
                          f"platform {run.cfg['platform']!r}")
    run.device = dev
    return {"platform": dev["platform"], "device_kind": dev["kind"],
            "device_count": dev["count"]}


# -- phases ------------------------------------------------------------------


def phase_kernels(run: Run) -> dict:
    log = os.path.join(OUT, "kernels.log")
    argv = [os.path.abspath(__file__), "--phase", "kernels"]
    if run.cfg is CPU_TINY:
        argv.append("--cpu-tiny")
    rc = run.wait(run.spawn(argv, log), "kernels")
    dev = _check_device(run, log, "kernels")
    row = _event(_json_lines(log), "kernels")
    if rc != 0 or row is None:
        # No result row: a kernel did not lower or run (the compiler's
        # message is in the log's tail). A row: it ran and disagreed.
        raise PhaseFailed(
            f"kernels: exit code {rc}; max_rel_err="
            f"{row and row['max_rel_err']} (tolerance {KERNEL_TOL})\n"
            + _tail(log))
    return {**dev, "compile_s": row["compile_s"],
            "compile_cache_hits": row["compile_cache_hits"],
            "interpret": row["interpret"],
            "max_rel_err": row["max_rel_err"]}


def phase_train(run: Run) -> dict:
    t = run.cfg["train"]
    work = os.path.join(OUT, "train")
    # Nothing an earlier run left may pass this one's checks
    # (MetricsLogger appends; the profiler adds a directory per run).
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    metrics = os.path.join(work, "metrics.jsonl")
    spec = {
        "model": run.cfg["model"],
        "dataset": "synthetic_lm",
        "dataset_kwargs": {"vocab_size": run.cfg["vocab"]},
        "seq_len": t["seq_len"],
        "batch_size": t["batch_per_chip"] * run.device["count"],
        "steps": t["steps"],
        "learning_rate": 1e-4,
        "log_every": 1,
        "metrics_path": metrics,
        "profile_start_step": t["profile"][0],
        "profile_stop_step": t["profile"][1],
    }
    spec_path = os.path.join(work, "spec.json")
    with open(spec_path, "w") as fh:
        json.dump(spec, fh, indent=1)
    prof_dir = os.path.join(work, "profile")  # next to metrics_path
    log = os.path.join(OUT, "train.log")
    rc = run.wait(run.spawn(
        ["-m", "kubeflow_tpu.train.trainer", "--spec", spec_path], log),
        "train")
    dev = _check_device(run, log, "train")
    rows = _json_lines(log)
    if rc != 0:
        raise PhaseFailed(f"train: exit code {rc}\n" + _tail(log))
    losses = [r["loss"] for r in rows if "loss" in r and "event" not in r]
    target = math.log(run.cfg["vocab"])
    if len(losses) != t["steps"]:
        raise PhaseFailed(f"train: {len(losses)} loss lines for "
                          f"{t['steps']} steps\n" + _tail(log))
    # Random tokens: the loss starts near ln(vocab) and cannot leave it in
    # a few steps. 0, NaN or something far off means the step is not
    # computing what it computes on the CPU.
    bad = [x for x in losses
           if not (math.isfinite(x) and abs(x - target) < 1.5)]
    if bad:
        raise PhaseFailed(f"train: losses {losses} not all finite and "
                          f"within 1.5 of ln(vocab)={target:.2f}")
    traces = [os.path.join(root, f)
              for root, _, files in os.walk(prof_dir)
              for f in files if f.endswith(".xplane.pb")]
    if not any(os.path.getsize(p) > 0 for p in traces):
        raise PhaseFailed(f"train: no non-empty *.xplane.pb under "
                          f"{prof_dir}")
    end = _event(rows, "device_end")
    if end is None:
        raise PhaseFailed("train: no device_end line\n" + _tail(log))
    sharding = _event(rows, "state_sharding") or {}
    return {**dev, "compile_s": end["compile_s"],
            "compile_cache_hits": end["compile_cache_hits"],
            "steps": len(losses), "batch_size": spec["batch_size"],
            "first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4),
            "opt_state_bytes_per_chip":
                sharding.get("opt_state_bytes_per_chip"),
            "peak_bytes_in_use": end["peak_bytes_in_use"],
            "trace_bytes": sum(os.path.getsize(p) for p in traces)}


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _get_json(url: str, timeout: float = 30.0) -> dict:
    with _http("GET", url, timeout=timeout) as resp:
        return json.loads(resp.read())


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _check_generated(what: str, n_out, logprobs, want: int) -> None:
    if n_out != want:
        raise PhaseFailed(f"serve: {what}: {n_out} output tokens, asked "
                          f"for {want}")
    if (len(logprobs) != want
            or not all(isinstance(x, (int, float)) and math.isfinite(x)
                       and x <= 1e-6 for x in logprobs)):
        raise PhaseFailed(f"serve: {what}: logprobs not {want} finite "
                          f"values <= 0: {logprobs}")


def _generate(base: str, name: str, kind: str, ids: list[int],
              max_tokens: int) -> None:
    """One request through the surface `kind` names; raises PhaseFailed
    unless it returns 200 with max_tokens tokens and finite logprobs."""
    what = f"{kind} request (prompt {len(ids)})"
    try:
        if kind == "openai":
            with _http("POST", f"{base}/openai/v1/completions", {
                    "model": name, "prompt": ids, "max_tokens": max_tokens,
                    "temperature": 0.0, "logprobs": 0}) as resp:
                out = json.loads(resp.read())
            _check_generated(
                what, out["usage"]["completion_tokens"],
                out["choices"][0]["logprobs"]["token_logprobs"], max_tokens)
            return
        body = {"input_ids": ids, "max_tokens": max_tokens}
        if kind == "stream":
            body["stream"] = True
        with _http("POST", f"{base}/v1/models/{name}:generate",
                   body) as resp:
            if kind != "stream":
                out = json.loads(resp.read())
            else:
                events = [json.loads(line) for line in resp if line.strip()]
                out = events[-1]
                streamed = [t for ev in events[:-1]
                            for t in ev.get("tokens", [])]
                if not out.get("done") or streamed != out["output_ids"]:
                    raise PhaseFailed(
                        f"serve: {what}: streamed tokens {streamed} do not "
                        f"add up to the done event's {out}")
        _check_generated(what, out["num_output_tokens"],
                         out["output_logprobs"], max_tokens)
    except urllib.error.HTTPError as e:
        raise PhaseFailed(f"serve: {what}: HTTP {e.code} {e.reason}: "
                          f"{e.read()[:500]!r}") from None
    except (OSError, KeyError, ValueError) as e:
        raise PhaseFailed(f"serve: {what}: {type(e).__name__}: {e}") from None


def _idle_blocks(base: str, name: str) -> int:
    """paged_kv.blocks_used once it has stopped moving (retired slots
    return their blocks at the next fetch boundary, not with the
    response)."""
    last, stable = None, 0
    for _ in range(60):
        used = _get_json(f"{base}/v2/models/{name}")["paged_kv"][
            "blocks_used"]
        stable = stable + 1 if used == last else 0
        if stable >= 2:
            return used
        last = used
        time.sleep(0.5)
    raise PhaseFailed("serve: paged_kv.blocks_used never settled")


def phase_serve(run: Run) -> dict:
    s = run.cfg["serve"]
    name = "smoke"
    bundle = os.path.join(OUT, "serve", "bundle")
    os.makedirs(bundle, exist_ok=True)
    # No params/ directory: the jax-registry runtime initialises the
    # weights from the seed inside the server process.
    with open(os.path.join(bundle, "model.json"), "w") as fh:
        json.dump({
            "format": "jax-registry", "model": run.cfg["model"],
            "model_kwargs": {}, "seed": 0,
            "generative": {k: s[k] for k in (
                "slots", "max_len", "chunk", "prefill_buckets",
                "kv_block_size", "kv_blocks", "prefix_cache")},
        }, fh, indent=1)
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    log = os.path.join(OUT, "serve.log")
    proc = run.spawn(["-m", "kubeflow_tpu.serve.server", "--model-dir",
                      bundle, "--name", name, "--port", str(port)], log)
    try:
        # The port opens only after load (weights + every AOT compile).
        while True:
            if proc.poll() is not None:
                _check_device(run, log, "serve")
                raise PhaseFailed(f"serve: server exited with code "
                                  f"{proc.returncode} before it was ready\n"
                                  + _tail(log))
            if run.left() < 120:
                raise PhaseFailed("serve: not ready in time\n" + _tail(log))
            try:
                if _get_json(f"{base}/v2/health/ready", 5.0).get("ready"):
                    break
            except (OSError, ValueError):
                time.sleep(1.0)
        dev = _check_device(run, log, "serve")

        # Deterministic prompts; distinct first tokens so no prompt is a
        # prefix of another by accident.
        prompts = [[(7 * i + 13 * j + 1) % (run.cfg["vocab"] - 1) + 1
                    for j in range(n)]
                   for i, n in enumerate(s["prompt_lens"])]
        kinds = ["generate"] * len(prompts)
        kinds[2], kinds[4] = "stream", "openai"
        errors: list[str] = []

        def one(kind, ids):
            try:
                _generate(base, name, kind, ids, s["max_tokens"])
            except PhaseFailed as e:
                errors.append(str(e))

        # Wave 1: all at once — more requests than slots.
        threads = [threading.Thread(target=one, args=a)
                   for a in zip(kinds, prompts)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=max(run.left() - 60, 1.0))
        if any(th.is_alive() for th in threads):
            errors.append("serve: requests still in flight at the limit")
        if errors:
            raise PhaseFailed("\n".join(errors) + "\n" + _tail(log))
        idle = _idle_blocks(base, name)
        bs = s["kv_block_size"]
        held = sum(-(-len(p) // bs) for p in prompts)
        if idle != held:
            raise PhaseFailed(
                f"serve: {idle} KV blocks in use when idle; the prefix "
                f"cache's entries account for {held} — blocks leaked or "
                f"lost")
        # Wave 2: the longest prompt again. Its first chunk boundary is
        # cached, so admission must resume from it (a prefix hit), and
        # afterwards the pool must be back exactly where it idled.
        _generate(base, name, "generate", prompts[-1], s["max_tokens"])
        if _idle_blocks(base, name) != idle:
            raise PhaseFailed("serve: paged_kv.blocks_used did not return "
                              f"to its idle value {idle} after the repeat")
        md = _get_json(f"{base}/v2/models/{name}")
        stats = md["stats"]
        for key, least in (("decode_dispatches", 1), ("prefix_hits", 1),
                           ("decode_fetch_overlapped", 1)):
            if stats.get(key, 0) < least:
                raise PhaseFailed(f"serve: stats.{key}={stats.get(key)} "
                                  f"(< {least}); stats={stats}")
        if md.get("device") != run.device:
            raise PhaseFailed(
                f"serve: /v2/models/{name} reports device "
                f"{md.get('device')}, the process said {run.device}")
    finally:
        run.stop(proc)
    if proc.returncode != 0:
        raise PhaseFailed(f"serve: exit code {proc.returncode} after "
                          "SIGTERM (0 = clean shutdown)\n" + _tail(log))
    rows = _json_lines(log)
    end = _event(rows, "device_end")
    if end is None:
        raise PhaseFailed("serve: no device_end line after SIGTERM\n"
                          + _tail(log))
    return {**dev, "compile_s": end["compile_s"],
            "compile_cache_hits": end["compile_cache_hits"],
            "load_time_s": (_event(rows, "model_loaded") or {}).get(
                "load_time_s"),
            "requests": len(prompts) + 1,
            "decode_dispatches": stats["decode_dispatches"],
            "decode_fetch_overlapped": stats["decode_fetch_overlapped"],
            "prefix_hits": stats["prefix_hits"],
            "idle_blocks_used": idle,
            "peak_bytes_in_use": end["peak_bytes_in_use"]}


# -- main --------------------------------------------------------------------


def main(argv: list[str]) -> int:
    cfg = CPU_TINY if "--cpu-tiny" in argv else CHIP
    if "--phase" in argv:  # internal: the kernels child
        return kernels_child(cfg)
    if not os.path.isdir(os.path.join(HERE, "kubeflow_tpu")):
        print("chip_smoke: no kubeflow_tpu/ beside this script — run it "
              "from a checkout of the repo", file=sys.stderr)
        return 2
    os.makedirs(OUT, exist_ok=True)
    run = Run(cfg)
    phases = {"kernels": phase_kernels, "serve": phase_serve,
              "train": phase_train}
    failed = []
    try:
        for name, phase in phases.items():
            t0 = time.monotonic()
            try:
                row = {"ok": True, **phase(run)}
            except Exception as e:
                # PhaseFailed, or a check tripping over a reply it did not
                # expect: either way the phase failed and the run goes on.
                failed.append(name)
                print("chip_smoke: FAILED " + (
                    str(e) if isinstance(e, PhaseFailed)
                    else f"{name}: {traceback.format_exc()}"),
                    file=sys.stderr)
                if run.device is None:
                    # Wrong platform, or not even the kernels child could
                    # say where it ran: no later phase can pass, and there
                    # is no result to print.
                    break
                row = {"ok": False, "platform": run.device["platform"],
                       "device_kind": run.device["kind"],
                       "device_count": run.device["count"]}
            print(json.dumps({
                "phase": name, **row,
                "wall_s": round(time.monotonic() - t0, 1)}), flush=True)
    finally:
        run.stop_all()
    if run.device is None:
        return 1
    verdict = {"ok": not failed, "device": run.device}
    if failed:
        verdict["failed"] = failed
    print(json.dumps(verdict), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
