"""Cell kind "serve": one model-server child through the entry point the
InferenceService controller launches (`python -m kubeflow_tpu.serve.server
--model-dir <bundle>`), and streamed `:generate` traffic against it.

The bundle has no `params/`: the jax-registry runtime makes the weights
from the bundle's seed inside the server, which is `--seed`. Requests come
from `requests_for()` below, the one general generator: sizes from the
mix's file, contents from the seed. The mix's `loop` names the traffic loop
(`loops/<loop>.py`). The loop runs before, through and after the measured
window without a break; set-up ends and the window starts `warm_s` seconds
after the first request, and the engine's counters are read at both ends
of the window.
"""

from __future__ import annotations

import json
import math
import os
import random
import socket
import time

import client
import common
from common import BenchError

MODEL = "bench"


def size_pool(mix: dict) -> list[tuple[int, int]]:
    """The (prompt, output) lengths of one round of requests: lognormal,
    clipped, drawn from the mix's own fixed `sizes_seed`."""
    rng = random.Random(mix["sizes_seed"])

    def draw(spec):
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
        return int(min(max(round(x), spec["min"]), spec["max"]))

    return [(draw(mix["prompt_tokens"]), draw(mix["output_tokens"]))
            for _ in range(int(mix["round"]))]


def requests_for(mix: dict, vocab: int, seed: int):
    """request_at(i) -> (ids, max_tokens). Request i takes the sizes at
    its place in a permutation of its round that the mix's `sizes_seed`
    fixes, so every seed sends the same sizes in the same order and only
    the tokens differ (greedy decoding to `max_tokens` costs the same
    whatever they are). Its first token is i's own, so no prompt is a
    prefix of another and the prefix cache has nothing to find (`sharing:
    none`); the rest are random tokens from the seed."""
    if mix.get("sharing", "none") != "none":
        raise BenchError(f"sharing {mix['sharing']!r}: this generator knows "
                         "'none' so far")
    pool = size_pool(mix)
    n = len(pool)

    def request_at(i: int):
        order = list(range(n))
        random.Random(mix["sizes_seed"] * 1000003 + i // n).shuffle(order)
        plen, olen = pool[order[i % n]]
        rng = random.Random(seed * 7919 + i)
        ids = [1 + i % (vocab - 1)] + [rng.randrange(1, vocab)
                                       for _ in range(plen - 1)]
        return ids, olen

    return request_at


def probe_ids(mix: dict, vocab: int) -> list[int]:
    """The fixed probe: the same tokens in every run of every seed. Its
    first token is the vocabulary's last, which no request of the loop
    uses before request vocab - 2."""
    return [vocab - 1] + [(7 * j + 13) % (vocab - 1) + 1
                          for j in range(int(mix["probe_tokens"]) - 1)]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_bundle(ctx) -> str:
    bundle = os.path.join(ctx.out, "bundle")
    os.makedirs(bundle, exist_ok=True)
    with open(os.path.join(bundle, "model.json"), "w") as fh:
        json.dump({"format": "jax-registry",
                   "model": ctx.config["registry_model"],
                   "model_kwargs": ctx.config["model_kwargs"],
                   "seed": ctx.seed % (2 ** 31 - 1),
                   "generative": ctx.config["engine"]}, fh, indent=1)
    return bundle


def wait_ready(ctx, proc, base: str, log_path: str) -> None:
    """The port opens only after load: weights and every AOT compile."""
    while True:
        if proc.poll() is not None:
            raise BenchError(f"server: exited with code {proc.returncode} "
                             "before it was ready\n" + common.tail(log_path))
        if ctx.left() < 200:
            raise BenchError("server: not ready in time\n"
                             + common.tail(log_path))
        try:
            if common.get_json(f"{base}/v2/health/ready", 5.0).get("ready"):
                return
        except (OSError, ValueError):
            time.sleep(0.5)


def idle_blocks(base: str) -> int | None:
    """paged_kv.blocks_used once it has stopped moving (retired slots
    return their blocks at the next fetch boundary, not with the reply)."""
    last, stable = None, 0
    for _ in range(40):
        used = common.get_json(f"{base}/v2/models/{MODEL}")["paged_kv"][
            "blocks_used"]
        stable = stable + 1 if used == last else 0
        if stable >= 2:
            return used
        last = used
        time.sleep(0.25)
    return None


def sleep_until(t: float) -> None:
    time.sleep(max(t - time.monotonic(), 0.0))


def run(ctx) -> dict:
    mix, engine = ctx.mix, ctx.config["engine"]
    vocab = ctx.config["model_kwargs"]["vocab_size"]
    bundle = write_bundle(ctx)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    log_path = os.path.join(ctx.out, "server.log")
    argv = ["--model-dir", bundle, "--name", MODEL, "--port", str(port)]
    if ctx.trace:
        # The same main(), in a child that can also open a profiler window
        # when this parent asks through a control file.
        argv = [os.path.join(common.BENCH, "serve_child.py"), ctx.out] + argv
    else:
        argv = ["-m", "kubeflow_tpu.serve.server"] + argv
    proc = ctx.spawn(argv, log_path)
    wait_ready(ctx, proc, base, log_path)
    dev = ctx.check_device(
        common.event(common.json_lines(log_path), "device"), "server")
    t_ready = time.monotonic()

    def send(req):
        ids, olen = req
        return client.generate("127.0.0.1", port, MODEL, ids, olen,
                               timeout=float(mix["request_timeout_s"]))

    probe = (probe_ids(mix, vocab), int(mix["probe_output_tokens"]))
    probe_before = send(probe)
    loop_mod = common.load_module(
        os.path.join(common.BENCH, "loops", mix["loop"] + ".py"))
    loop = loop_mod.Loop(mix, send, requests_for(mix, vocab, ctx.seed))
    t_loop = time.monotonic()
    loop.start()
    w0 = t_loop + float(mix["warm_s"])
    w1 = w0 + ctx.seconds
    sleep_until(w0)
    before = common.get_json(f"{base}/v2/models/{MODEL}")
    if ctx.trace:
        span = min(float(mix["trace_s"]), ctx.seconds / 2)
        sleep_until(w0 + (ctx.seconds - span) / 2)
        open(os.path.join(ctx.out, "trace.start"), "w").close()
        sleep_until(w0 + (ctx.seconds + span) / 2)
        open(os.path.join(ctx.out, "trace.stop"), "w").close()
    sleep_until(w1)
    after = common.get_json(f"{base}/v2/models/{MODEL}")
    unfinished = loop.stop(float(mix["drain_s"]))
    records = loop.snapshot()

    probe_after = send(probe)
    blocks = idle_blocks(base)
    ctx.stop(proc)
    rows = common.json_lines(log_path)
    end = common.event(rows, "device_end")
    if proc.returncode != 0 or end is None:
        raise BenchError(f"server: exit code {proc.returncode} after "
                         "SIGTERM, or no device_end line\n"
                         + common.tail(log_path))

    sent = [r for r in records if w0 <= r["t_send"] < w1]
    bad = [r for r in sent if not client.ok(r)]
    ttfts = [x for x in map(client.ttft_s, sent) if x is not None]
    tpots = [x for x in map(client.tpot_s, sent) if x is not None]
    if not ttfts or not tpots:
        raise BenchError(f"server: no request sent in the window produced "
                         f"tokens ({len(sent)} sent)\n"
                         + common.tail(log_path))
    out_tokens = client.tokens_between(records, w0, w1)

    # The probe, alone before the window and beside nothing after it (then
    # a prefix hit if its entry survived): the same first-token logprob.
    probes_ok = client.ok(probe_before) and client.ok(probe_after)
    lp_gap = None
    if probes_ok:
        lp_gap = abs(probe_before["done"]["output_logprobs"][0]
                     - probe_after["done"]["output_logprobs"][0])
    bs = engine.get("kv_block_size", 0)
    held_most = (engine.get("prefix_cache", 0)
                 * -(-mix["prompt_tokens"]["max"] // bs) if bs else None)
    blocks_ok = (not bs) or (blocks is not None and blocks <= held_most)
    correct = (not bad and not unfinished and probes_ok
               and lp_gap <= float(mix["probe_logprob_tol"]) and blocks_ok)

    deltas = {k: after["stats"][k] - before["stats"][k]
              for k, v in after["stats"].items()
              if isinstance(v, (int, float)) and not isinstance(v, bool)
              and k in before["stats"]}
    ctx.facts.update(counters=deltas, window_s=ctx.seconds, engine=engine,
                     records=sent)
    if ctx.trace:
        ctx.facts["xplane"] = common.summarize_trace(
            ctx, os.path.join(ctx.out, "profile"))
    parts = {"requests_sent_in_window": len(sent),
             "requests_total": len(records), "unfinished": unfinished,
             "bad": len(bad),
             "first_error": next((r["error"] or r["status"] for r in bad),
                                 None),
             "probe_logprob_gap": lp_gap,
             "probe_prefix_hit": (probe_after.get("done") or {}).get(
                 "prefix_hit"),
             "idle_blocks_used": blocks,
             "compile_cache_hits": end.get("compile_cache_hits"),
             "peak_bytes_in_use": end.get("peak_bytes_in_use")}
    if not ctx.rehearse:  # times, which a CPU run never reports
        parts.update(
            compile_s=end.get("compile_s"),
            load_time_s=(common.event(rows, "model_loaded") or {}).get(
                "load_time_s"),
            ready_s=t_ready - ctx.t0,
            ttft_p50_ms=1e3 * client.percentile(ttfts, 50),
            tpot_p50_ms=1e3 * client.percentile(tpots, 50))
    return {
        "correct": correct,
        "attempted": len(sent) + unfinished,
        "failed": len(bad) + unfinished,
        "e2e": {"setup_s": w0 - ctx.t0,
                "out_tok_s": out_tokens / ctx.seconds,
                "ttft_p95_ms": 1e3 * client.percentile(ttfts, 95),
                "tpot_p95_ms": 1e3 * client.percentile(tpots, 95)},
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"],
                   "memory_peak_bytes": common.peak_bytes(end)},
        "parts": parts,
    }
