"""Cell kind "serve_ref": the "serve" kind's run, with `correct` also held
against a plain reference.

It is `kinds/serve.py` (loaded by name; its `write_bundle`, `wait_ready`,
`requests_for`, `probe_ids`, `idle_blocks` and its arithmetic are used as
they are) plus three things, and what of `serve.run` has to be repeated to
place them is repeated below (the next `benchmark` PR folds this file into
`serve.py`, PERF.md section 7):

  1. Before the loop starts, with the server otherwise idle, the
     configuration's `reference.probes`: fixed prompts (`[prompt ids,
     output ids]` lengths; the ids are `probe_ids`' at that length, the same
     in every run of every seed, the weights are the seed's), greedy,
     streamed, one at a time: the boundaries of the state, each alone.
  2. A sample of the requests that the timed window itself finished
     (`window_sample` below), with every slot live around them: blocks
     given back by one request are taken by another while both decode.
     What the server answered to both (ids and `output_logprobs`) is
     written to `probes.json`.
  3. After the server has exited (the chip is free again), the
     configuration's `reference.script` in a child of its own: it rebuilds
     the weights from the bundle's seed, runs `benchmarks/reference/` over
     prompt + emitted ids and holds the server's logprobs against the
     reference's log-softmax at the same positions for the same ids.

`correct` then also needs every number the configuration's `reference.limits`
names (the check script's: gaps to the fp32 reference, and the distance from
a reference in the stated precision) inside its limit, over the probes and
the window's sample together, and,
for a pool of several kinds of block, every kind's `<kind>_blocks_used` at 0
once the loop has drained. The compared numbers go out beside their limits
under `parts.compared`, the check's own seconds under `parts.check_s`.
"""

from __future__ import annotations

import json
import os
import time

import client
import common
from common import BenchError

serve = common.load_module(os.path.join(common.BENCH, "kinds", "serve.py"))
MODEL = serve.MODEL


def probe_request(mix: dict, vocab: int, prompt: int, output: int):
    return serve.probe_ids({**mix, "probe_tokens": prompt}, vocab), output


def reference(ctx, bundle: str, probes_path: str) -> dict:
    ref = ctx.config["reference"]
    out = os.path.join(ctx.out, "ref_check.json")
    cfg_path = os.path.join(ctx.out, "ref_config.json")
    with open(cfg_path, "w") as fh:
        json.dump({k: ctx.config[k] for k in ref["config_keys"]}, fh)
    log = os.path.join(ctx.out, "ref_check.log")
    proc = ctx.spawn([os.path.join(common.BENCH, ref["script"]),
                      "--bundle", bundle, "--config", cfg_path,
                      "--probes", probes_path, "--out", out], log)
    try:
        rc = proc.wait(timeout=max(ctx.left(), 1.0))
    except Exception:
        ctx.stop(proc)
        raise BenchError("reference: did not finish\n"
                         + common.tail(log)) from None
    if rc != 0:
        raise BenchError(f"reference: exit code {rc}\n" + common.tail(log))
    ctx.check_device(common.event(common.json_lines(log), "device"),
                     "reference")
    return common.load_json(out)


def compare(found: dict, limits: dict) -> tuple[bool, dict]:
    """(every comparison inside its limit, the numbers beside the limits).
    `found` is the check script's result (or one of its `controls`)."""
    compared = {name: {"err": found[name], "limit": limit}
                for name, limit in limits.items()}
    return all(c["err"] <= c["limit"] for c in compared.values()), compared


def crosses(rec: dict, window: int) -> bool:
    """Whether the request's decode steps close a window of its state."""
    first = rec["prompt_tokens"]
    return (first + rec["max_tokens"] - 1) // window > (first - 1) // window


def window_sample(asked: list, w0: float, w1: float, take: dict,
                  window: int) -> list:
    """Of `asked` ([(record, prompt ids)] as sent), the requests sent and
    finished inside [w0, w1) that answered in full: those whose decode
    closed a window first, then those with the most served bytes; as many
    as `take["requests"]`, of at most `take["tokens"]` prompt + output
    tokens together (the reference runs over every one of them)."""
    inside = [(r, ids) for r, ids in asked
              if client.ok(r) and w0 <= r["t_send"] and r["t_done"] < w1]
    inside.sort(key=lambda x: (not crosses(x[0], window),
                               -x[0]["max_tokens"], x[0]["t_send"]))
    out, left = [], int(take["tokens"])
    for r, ids in inside:
        n = len(ids) + r["max_tokens"]
        if len(out) < int(take["requests"]) and n <= left:
            out.append((r, ids))
            left -= n
    return out


def changes(before: dict, after: dict) -> dict:
    """Each numeric counter's change between two reads of `stats`."""
    return {k: v - before[k] for k, v in after.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
            and k in before}


def run(ctx) -> dict:
    mix, engine = ctx.mix, ctx.config["engine"]
    vocab = ctx.config["model_kwargs"]["vocab_size"]
    bundle = serve.write_bundle(ctx)
    port = serve.free_port()
    base = f"http://127.0.0.1:{port}"
    log_path = os.path.join(ctx.out, "server.log")
    argv = ["--model-dir", bundle, "--name", MODEL, "--port", str(port)]
    if ctx.trace:
        argv = [os.path.join(common.BENCH, "serve_child.py"), ctx.out] + argv
    else:
        argv = ["-m", "kubeflow_tpu.serve.server"] + argv
    proc = ctx.spawn(argv, log_path)
    serve.wait_ready(ctx, proc, base, log_path)
    dev = ctx.check_device(
        common.event(common.json_lines(log_path), "device"), "server")
    t_ready = time.monotonic()

    asked = []  # (record, prompt ids) of every request, as they end

    def send(req):
        ids, olen = req
        rec = client.generate("127.0.0.1", port, MODEL, ids, olen,
                              timeout=float(mix["request_timeout_s"]))
        asked.append((rec, ids))
        return rec

    # The reference probes, one at a time on an idle server.
    ref = ctx.config["reference"]
    answered = [send(probe_request(mix, vocab, p, o))
                for p, o in ref["probes"]]
    held = [(x, "probe") for x in asked]
    t_probed = time.monotonic()

    probe = (serve.probe_ids(mix, vocab), int(mix["probe_output_tokens"]))
    probe_before = send(probe)
    loop_mod = common.load_module(
        os.path.join(common.BENCH, "loops", mix["loop"] + ".py"))
    loop = loop_mod.Loop(mix, send, serve.requests_for(mix, vocab, ctx.seed))
    t_loop = time.monotonic()
    loop.start()
    w0 = t_loop + float(mix["warm_s"])
    w1 = w0 + ctx.seconds
    serve.sleep_until(w0)
    before = common.get_json(f"{base}/v2/models/{MODEL}")
    if ctx.trace:
        span = min(float(mix["trace_s"]), ctx.seconds / 2)
        serve.sleep_until(w0 + (ctx.seconds - span) / 2)
        open(os.path.join(ctx.out, "trace.start"), "w").close()
        t_before = common.get_json(f"{base}/v2/models/{MODEL}")["stats"]
        serve.sleep_until(w0 + (ctx.seconds + span) / 2)
        open(os.path.join(ctx.out, "trace.stop"), "w").close()
        t_after = common.get_json(f"{base}/v2/models/{MODEL}")["stats"]
        # The engine's counters over the traced span, for the readers that
        # hold device seconds of the trace against work counted by the host.
        ctx.facts["trace_counters"] = changes(t_before, t_after)
    serve.sleep_until(w1)
    after = common.get_json(f"{base}/v2/models/{MODEL}")
    unfinished = loop.stop(float(mix["drain_s"]))
    records = loop.snapshot()

    probe_after = send(probe)
    blocks = serve.idle_blocks(base)
    paged = common.get_json(f"{base}/v2/models/{MODEL}").get("paged_kv") or {}
    kinds_used = {k: v for k, v in paged.items()
                  if k.endswith("_blocks_used")}
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

    probes_ok = client.ok(probe_before) and client.ok(probe_after)
    lp_gap = None
    if probes_ok:
        lp_gap = abs(probe_before["done"]["output_logprobs"][0]
                     - probe_after["done"]["output_logprobs"][0])
    blocks_ok = (blocks == 0 and bool(kinds_used)
                 and not any(kinds_used.values()))

    # The reference check, outside every metric: the probes and a sample
    # of what the window finished under load.
    t_check = time.monotonic()
    ref_ok, compared = False, None
    answered_ok = all(client.ok(r) for r in answered)
    held += [(x, "window") for x in window_sample(
        asked, w0, w1, ref["window_sample"], ctx.config["window_size"])]
    sampled = len(held) - len(answered)
    if answered_ok and sampled:
        probes_path = os.path.join(ctx.out, "probes.json")
        with open(probes_path, "w") as fh:
            json.dump([{"group": group, "input_ids": ids,
                        "output_ids": r["done"]["output_ids"],
                        "output_logprobs": r["done"]["output_logprobs"]}
                       for (r, ids), group in held], fh)
        found = reference(ctx, bundle, probes_path)
        ref_ok, compared = compare(found, ref["limits"])
        compared.update(probes=found["probes"],
                        compared_bytes=found["compared_bytes"])
    check_s = time.monotonic() - t_check
    correct = (not bad and not unfinished and probes_ok
               and lp_gap <= float(mix["probe_logprob_tol"]) and blocks_ok
               and answered_ok and sampled and ref_ok)

    ctx.facts.update(counters=changes(before["stats"], after["stats"]), window_s=ctx.seconds, engine=engine,
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
             "idle_blocks_used": blocks, "idle_blocks_by_kind": kinds_used,
             "reference_probes_ok": answered_ok,
             "window_requests_compared": sampled, "compared": compared,
             "compile_cache_hits": end.get("compile_cache_hits"),
             "peak_bytes_in_use": end.get("peak_bytes_in_use")}
    if not ctx.rehearse:  # times, which a CPU run never reports
        parts.update(
            compile_s=end.get("compile_s"),
            load_time_s=(common.event(rows, "model_loaded") or {}).get(
                "load_time_s"),
            ready_s=t_ready - ctx.t0, reference_probes_s=t_probed - t_ready,
            check_s=check_s,
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
