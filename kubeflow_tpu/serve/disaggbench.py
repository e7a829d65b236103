"""Disaggregated-vs-unified serving benchmark (ISSUE 13) → DISAGGBENCH.json.

The claim under test (ROADMAP item 3 / PROFILE §8): under heavy MIXED
traffic, long-prompt chunked prefill steals decode dispatches from
in-flight streams because both share one engine loop — so splitting the
fleet into prefill-only and decode-only replicas (KV blocks shipped
through the router) isolates TTFT and the decode tail at EQUAL total
engines.

Harness discipline (PROFILE §11, the ROUTERBENCH rules):

  * **Open loop.** Seeded Poisson arrivals FIRE AT SCHEDULE — a closed
    loop would slow offered load to whatever the server survives and
    hide exactly the queueing this bench exists to expose.
  * **Real engines, honest labels.** Replicas run the REAL
    GenerationEngine on the tiny CPU model behind real ModelServers and
    the real router — the mechanism counters (prefill chunks, shipped/
    received blocks) are the engine's own, not simulated. Absolute
    tok/s numbers are CPU-tiny-model numbers and say nothing about
    chips; the ARM DELTAS (TTFT/tail isolation at equal engines) are
    the artifact.
  * **Equal resources.** Both arms run exactly two engines with
    identical pools/slots; the disagg arm splits them by role, the
    unified arm load-balances mixed traffic across both.

Per request the harness records TTFT (first streamed token frame) and
total latency; the summary reports goodput, p50/p99 TTFT (overall and
for the short-decode class the interference claim is about), and the
decode-tail p99 (total − TTFT over short requests).
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from kubeflow_tpu.serve.loadgen import summarize  # noqa: F401 (doc link)


def _build_tiny():
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.llama import Llama, llama_tiny

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32,
                              num_layers=2)
    model = Llama(cfg)
    params = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.key(0))
    return model, params, cfg


def _make_replica(model, params, cfg, *, role: str, gen_kw: dict,
                  name: str = "m"):
    from kubeflow_tpu.serve.generation import GenerativeJAXModel
    from kubeflow_tpu.serve.server import ModelServer

    m = GenerativeJAXModel(name, model, params, cfg,
                           generation=dict(gen_kw, role=role))
    srv = ModelServer(max_inflight=128, executor_workers=128)
    srv.repo.register(m)
    port = srv.start_background()
    return srv, f"http://127.0.0.1:{port}", m


def _stream_generate(base_url: str, model: str, payload: dict,
                     timeout_s: float = 60.0) -> dict:
    """POST a streaming :generate and record TTFT (first token frame)
    + total wall. Returns {status, ttft_ms, total_ms, tokens}."""
    req = urllib.request.Request(
        f"{base_url}/v1/models/{model}:generate",
        data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    t0 = time.monotonic()
    ttft = None
    tokens = 0
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            buf = b""
            while True:
                chunk = r.read1(65536)
                if not chunk:
                    break
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if not line.strip():
                        continue
                    ev = json.loads(line)
                    if ev.get("tokens") and ttft is None:
                        ttft = (time.monotonic() - t0) * 1e3
                    tokens += len(ev.get("tokens", ()))
        return {"status": 200, "ttft_ms": ttft,
                "total_ms": (time.monotonic() - t0) * 1e3,
                "tokens": tokens}
    except urllib.error.HTTPError as e:
        return {"status": e.code, "ttft_ms": None,
                "total_ms": (time.monotonic() - t0) * 1e3, "tokens": 0}
    except Exception as e:
        return {"status": -1, "ttft_ms": None,
                "total_ms": (time.monotonic() - t0) * 1e3, "tokens": 0,
                "error": f"{type(e).__name__}: {e}"}


def _mixed_open_loop(base: str, *, rate_rps: float, duration_s: float,
                     long_frac: float, cfg, long_prompt: int,
                     short_prompt: int, long_max_tokens: int,
                     short_max_tokens: int, seed: int) -> list[dict]:
    """Seeded Poisson mixed long-prompt/short-decode arrivals, fired at
    schedule (open loop); one record per request."""
    rng = np.random.default_rng(seed)
    arrivals = []
    t = 0.0
    while t < duration_s:
        t += float(rng.exponential(1.0 / rate_rps))
        if t < duration_s:
            arrivals.append((t, bool(rng.random() < long_frac)))
    records: list[dict] = []
    lock = threading.Lock()
    threads = []

    def fire(i: int, is_long: bool):
        g = np.random.default_rng(seed * 100003 + i)
        n = long_prompt if is_long else short_prompt
        payload = {
            "input_ids": [int(x) for x in
                          g.integers(1, cfg.vocab_size, n)],
            "max_tokens": (long_max_tokens if is_long
                           else short_max_tokens),
        }
        rec = _stream_generate(base, "m", payload)
        rec["kind"] = "long" if is_long else "short"
        with lock:
            records.append(rec)

    start = time.monotonic()
    for i, (sched, is_long) in enumerate(arrivals):
        delay = start + sched - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(i, is_long),
                              daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=120.0)
    return records


def _pct(vals, p):
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return None
    return round(vals[min(int(len(vals) * p), len(vals) - 1)], 2)


def _summarize_mixed(records: list[dict], duration_s: float) -> dict:
    ok = [r for r in records if r["status"] == 200]
    shorts = [r for r in ok if r["kind"] == "short"]
    longs = [r for r in ok if r["kind"] == "long"]
    tails = [r["total_ms"] - r["ttft_ms"] for r in shorts
             if r["ttft_ms"] is not None]
    return {
        "requests": len(records),
        "completed_ok": len(ok),
        "goodput_rps": round(len(ok) / duration_s, 2),
        "shed_rate": round(sum(1 for r in records
                               if r["status"] == 503)
                           / max(len(records), 1), 4),
        "errors": sum(1 for r in records
                      if r["status"] not in (200, 503)),
        "ttft_p50_ms": _pct([r["ttft_ms"] for r in ok], 0.50),
        "ttft_p99_ms": _pct([r["ttft_ms"] for r in ok], 0.99),
        "short_ttft_p50_ms": _pct([r["ttft_ms"] for r in shorts], 0.50),
        "short_ttft_p99_ms": _pct([r["ttft_ms"] for r in shorts], 0.99),
        "long_ttft_p99_ms": _pct([r["ttft_ms"] for r in longs], 0.99),
        "decode_tail_p99_ms": _pct(tails, 0.99),
        "tokens": sum(r["tokens"] for r in ok),
    }


def run_disaggbench(quick: bool = False, seed: int = 0) -> dict:
    """The DISAGGBENCH.json payload: unified vs disaggregated fleets at
    equal engines under identical seeded mixed traffic."""
    import jax

    from kubeflow_tpu.serve.router import RouterServer

    model, params, cfg = _build_tiny()
    gen_kw = dict(slots=4, max_len=120, chunk=8,
                  prefill_buckets=(16, 32), kv_block_size=8,
                  kv_blocks=0, pipeline_depth=2, seed=seed)
    duration = 6.0 if quick else 16.0
    # Mixed traffic: long prompts chunk-prefill (4 chunks of 32) with a
    # short decode; short prompts decode long enough to have a tail.
    traffic = dict(long_frac=0.35, long_prompt=96, short_prompt=12,
                   long_max_tokens=8, short_max_tokens=32)
    rate = 10.0 if quick else 14.0

    result: dict = {
        "metric": "disaggbench",
        "mode": "real-tiny-engines-cpu",
        "note": ("both arms run the REAL GenerationEngine (tiny model, "
                 "CPU) behind real ModelServers and the real router at "
                 "EQUAL total engines; absolute latencies are CPU-tiny "
                 "numbers — the artifact is the arm DELTA (TTFT/tail "
                 "isolation) and the mechanism counters"),
        "device_kind": jax.devices()[0].device_kind,
        "params": {"gen_kw": {k: v for k, v in gen_kw.items()},
                   "traffic": traffic, "rate_rps": rate,
                   "duration_s": duration, "seed": seed,
                   "quick": bool(quick)},
        "arms": {},
    }

    def one_arm(disagg: bool) -> dict:
        servers = []
        router = None
        try:
            if disagg:
                roles = (("pre", "prefill"), ("dec", "decode"))
            else:
                roles = (("u0", "unified"), ("u1", "unified"))
            reps = []
            for name, role in roles:
                srv, url, m = _make_replica(model, params, cfg,
                                            role=role, gen_kw=gen_kw)
                servers.append(srv)
                reps.append((name, url, m, role))
            router = RouterServer()
            router.fleet.poll_interval_s = 0.15
            for name, url, _m, role in reps:
                router.fleet.add(name, url,
                                 role=("any" if role == "unified"
                                       else role))
            base = f"http://127.0.0.1:{router.start_background()}"
            time.sleep(0.4)  # first scrape
            records = _mixed_open_loop(
                base, rate_rps=rate, duration_s=duration, cfg=cfg,
                seed=seed, **traffic)
            arm = _summarize_mixed(records, duration)
            arm["replicas"] = {}
            for name, _url, m, role in reps:
                s = m.engine.stats_snapshot()
                arm["replicas"][name] = {
                    "role": m.engine.role,
                    "prefill_chunks": s["prefill_chunks"],
                    "decode_dispatches": s["decode_dispatches"],
                    "kv_blocks_shipped": s["kv_blocks_shipped"],
                    "kv_blocks_received": s["kv_blocks_received"],
                    "kv_spilled_blocks": s["kv_spilled_blocks"],
                    "kv_restored_blocks": s["kv_restored_blocks"],
                    "remote_admits": s["remote_admits"],
                    "requests": s["requests"],
                }
            arm["router"] = {
                k: v for k, v in router.router.stats_snapshot().items()
                if k in ("placed", "handoffs", "handoff_retries",
                         "decode_pool", "sheds_forwarded", "errors",
                         "no_replica")}
            return arm
        finally:
            if router is not None:
                router.stop()
            for srv in servers:
                srv.stop()

    result["arms"]["unified"] = one_arm(disagg=False)
    result["arms"]["disagg"] = one_arm(disagg=True)
    uni, dis = result["arms"]["unified"], result["arms"]["disagg"]
    if uni["ttft_p99_ms"] and dis["ttft_p99_ms"]:
        result["ttft_p99_ratio"] = round(
            dis["ttft_p99_ms"] / uni["ttft_p99_ms"], 3)
    if uni["short_ttft_p99_ms"] and dis["short_ttft_p99_ms"]:
        result["short_ttft_p99_ratio"] = round(
            dis["short_ttft_p99_ms"] / uni["short_ttft_p99_ms"], 3)
    if uni["decode_tail_p99_ms"] and dis["decode_tail_p99_ms"]:
        result["decode_tail_p99_ratio"] = round(
            dis["decode_tail_p99_ms"] / uni["decode_tail_p99_ms"], 3)
    result["goodput_ratio"] = round(
        dis["goodput_rps"] / max(uni["goodput_rps"], 1e-9), 3)
    return result
