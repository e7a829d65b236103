"""Serving benchmark harness: measured numbers for the
TPU-native serving stack — decode throughput vs slot count, TTFT per
prefill bucket, chunked-prefill admission cost, length-aware decode-bucket
speedup, int8-weight-only vs bf16 delta, and batcher latency percentiles.

`python bench.py --serve` runs it on the chip (and fails without one) and
writes `SERVEBENCH.json`; the regression test pins the harness by handing
it a tiny config on the CPU.
The reference inherits vLLM's numbers for its huggingfaceserver
⟨kserve: python/huggingfaceserver⟩ — this is the artifact that lets the
TPU stack's claims be checked instead of asserted.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _build_model(cfg):
    from kubeflow_tpu.models.llama import Llama

    model = Llama(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda r: model.init(r, toks)["params"])(
        jax.random.key(0))
    return model, params


def _drain(engine, prompts, max_tokens, per_prompt_kwargs=None):
    """Submit all prompts concurrently; return wall seconds start→last.
    `per_prompt_kwargs` (optional, one dict per prompt) rides into each
    submit — e.g. per-request adapter selection."""
    done = []
    errs = []
    kws = per_prompt_kwargs or [{}] * len(prompts)

    def run(p, kw):
        try:
            done.append(engine.submit(p, max_tokens=max_tokens, **kw))
        except Exception as e:  # pragma: no cover - surfaced in result
            errs.append(str(e))

    threads = [threading.Thread(target=run, args=(p, kw))
               for p, kw in zip(prompts, kws)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    dt = time.monotonic() - t0
    if errs:
        raise RuntimeError(f"servebench requests failed: {errs[:3]}")
    return dt, done


def bench_decode_slots(model, params, cfg, *, slots_list: Sequence[int],
                      max_len: int, chunk: int, buckets, decode_tokens: int,
                      rng: np.random.Generator) -> dict:
    """Decode tok/s at each concurrency: N greedy requests on N slots."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    out = {}
    for slots in slots_list:
        eng = GenerationEngine(model, params, cfg, slots=slots,
                               max_len=max_len, chunk=chunk,
                               prefill_buckets=buckets, prefix_cache=0)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 16))
                       for _ in range(slots)]
            _drain(eng, prompts, decode_tokens)
            s = eng.stats
            out[f"slots_{slots}"] = {
                "decode_tok_s": round(s["decode_tokens"]
                                      / max(s["decode_seconds"], 1e-9), 1),
                "decode_dispatches": s["decode_dispatches"],
            }
        finally:
            eng.close()
    return out


def bench_decode_buckets(model, params, cfg, *, max_len: int, chunk: int,
                         buckets, decode_tokens: int,
                         rng: np.random.Generator) -> dict:
    """Length-aware decode win: short conversations on bucketed vs flat
    (max_len-wide) decode."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    res = {}
    for label, dbuckets in (("bucketed", None), ("flat", [max_len])):
        eng = GenerationEngine(model, params, cfg, slots=4, max_len=max_len,
                               chunk=chunk, prefill_buckets=buckets,
                               decode_buckets=dbuckets, prefix_cache=0)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 8))
                       for _ in range(4)]
            _drain(eng, prompts, decode_tokens)
            s = eng.stats
            res[label] = s["decode_tokens"] / max(s["decode_seconds"], 1e-9)
        finally:
            eng.close()
    return {
        "bucketed_tok_s": round(res["bucketed"], 1),
        "flat_tok_s": round(res["flat"], 1),
        "speedup": round(res["bucketed"] / max(res["flat"], 1e-9), 3),
    }


def bench_ttft(model, params, cfg, *, max_len: int, chunk: int, buckets,
               rng: np.random.Generator) -> dict:
    """Time-to-first-token per prefill bucket (1 generated token), plus
    the chunked-admission cost of a prompt past the largest bucket."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    eng = GenerationEngine(model, params, cfg, slots=1, max_len=max_len,
                           chunk=chunk, prefill_buckets=buckets,
                           prefix_cache=0)
    ttft = {}
    try:
        for b in eng.prefill_buckets:
            n = max(b - 1, 1)
            lat = []
            for _ in range(3):
                r = eng.submit(list(rng.integers(1, cfg.vocab_size, n)),
                               max_tokens=1)
                lat.append(r["latency_s"])
            ttft[str(b)] = round(min(lat), 4)
        chunked = {}
        big = eng.prefill_buckets[-1]
        if big < max_len - 1:  # chunked-prefill reachable
            n = min(2 * big + big // 2, max_len - 1)
            lat = []
            for _ in range(3):
                r = eng.submit(list(rng.integers(1, cfg.vocab_size, n)),
                               max_tokens=1)
                lat.append(r["latency_s"])
            chunked = {"prompt_len": n, "admission_s": round(min(lat), 4)}
    finally:
        eng.close()
    return {"ttft_s": ttft, "chunked_prefill": chunked}


def bench_quant(model, params, cfg, *, max_len: int, chunk: int, buckets,
                decode_tokens: int, rng: np.random.Generator) -> dict:
    """Weight-only int8 vs bf16 decode throughput + HBM saving.

    Three arms since the dequant-placement fix (ROADMAP item 4 first
    half): `int8` is the FIXED path (Int8DenseGeneral — raw-int8 matmul
    operand, output-side scale, no full-weight dequant anywhere in the
    program), `int8_legacy` the old dequantize-per-apply wrapper that
    SERVEBENCH pinned at 0.747x bf16 (the per-step full-weight multiply
    inside the decode scan). The HLO-shape guard in
    tests/test_quant_dequant.py pins the mechanism on CPU; this row
    records the throughput outcome whenever a chip window runs it."""
    from kubeflow_tpu.serve.generation import GenerationEngine
    from kubeflow_tpu.serve.quant import (QuantizedModule, quantize_tree,
                                          quantized_bytes)

    res = {}
    qparams = quantize_tree(params)
    sizes = quantized_bytes(qparams)
    for label, m, p in (
            ("bf16", model, params),
            ("int8", QuantizedModule(model, cfg.dtype), qparams),
            ("int8_legacy",
             QuantizedModule(model, cfg.dtype, legacy_dequant=True),
             qparams)):
        eng = GenerationEngine(m, p, cfg, slots=4, max_len=max_len,
                               chunk=chunk, prefill_buckets=buckets,
                               prefix_cache=0)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 16))
                       for _ in range(4)]
            _drain(eng, prompts, decode_tokens)
            s = eng.stats
            res[label] = s["decode_tokens"] / max(s["decode_seconds"], 1e-9)
        finally:
            eng.close()
    return {
        "bf16_tok_s": round(res["bf16"], 1),
        "int8_tok_s": round(res["int8"], 1),
        "int8_legacy_tok_s": round(res["int8_legacy"], 1),
        "int8_vs_bf16": round(res["int8"] / max(res["bf16"], 1e-9), 3),
        "int8_legacy_vs_bf16": round(
            res["int8_legacy"] / max(res["bf16"], 1e-9), 3),
        "fixed_vs_legacy": round(
            res["int8"] / max(res["int8_legacy"], 1e-9), 3),
        "param_bytes": sizes,
    }


def bench_decode_buckets_long(model, params, cfg, *, max_len: int,
                              chunk: int, decode_tokens: int,
                              rng: np.random.Generator) -> dict:
    """The bucketed-decode row at a length where the feature can show
    value (at max_len 512 the 1.03x reading was
    non-evidence): short conversations on a LONG-max_len engine — flat
    decode pays max_len-wide attention for every token, bucketed pays
    only the smallest bucket covering the active sequences."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    res = {}
    for label, dbuckets in (("bucketed", None), ("flat", [max_len])):
        eng = GenerationEngine(model, params, cfg, slots=4, max_len=max_len,
                               chunk=chunk, prefill_buckets=(32,),
                               decode_buckets=dbuckets, prefix_cache=0)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 8))
                       for _ in range(4)]
            _drain(eng, prompts, decode_tokens)
            s = eng.stats
            res[label] = s["decode_tokens"] / max(s["decode_seconds"], 1e-9)
        finally:
            eng.close()
    return {
        "max_len": max_len,
        "bucketed_tok_s": round(res["bucketed"], 1),
        "flat_tok_s": round(res["flat"], 1),
        "speedup": round(res["bucketed"] / max(res["flat"], 1e-9), 3),
    }


def bench_spec_decode(model, params, cfg, *, max_len: int, chunk: int,
                      buckets, decode_tokens: int,
                      rng: np.random.Generator, draft_layers: int = 2,
                      draft_hidden: int = 256) -> dict:
    """Speculative decoding measured, not asserted:
    greedy decode tok/s for vanilla, a SELF-draft (draft == target —
    acceptance ~= 1, the mechanism's speedup ceiling at gamma), and a
    small random-weight draft (acceptance ~= chance — the floor; real
    draft checkpoints land between). Acceptance rates reported so the
    reader can weigh both."""
    import dataclasses

    from kubeflow_tpu.models.llama import Llama
    from kubeflow_tpu.serve.generation import GenerationEngine

    dcfg = dataclasses.replace(
        cfg, hidden_size=draft_hidden,
        intermediate_size=int(draft_hidden * 2.75) // 2 * 2,
        num_layers=draft_layers, num_heads=4, num_kv_heads=2,
        head_dim=draft_hidden // 4)
    dmodel = Llama(dcfg)
    dparams = jax.jit(lambda r: dmodel.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.key(3))

    out: dict[str, Any] = {"gamma": 4, "draft_params": dcfg.num_params}
    variants = (
        ("vanilla", None),
        ("self_draft", {"model": model, "params": params, "cfg": cfg,
                        "gamma": 4}),
        ("small_draft", {"model": dmodel, "params": dparams, "cfg": dcfg,
                         "gamma": 4}),
    )
    for label, draft in variants:
        eng = GenerationEngine(model, params, cfg, slots=2, max_len=max_len,
                               chunk=chunk, prefill_buckets=buckets,
                               prefix_cache=0, draft=draft)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 16))
                       for _ in range(2)]
            _drain(eng, prompts, decode_tokens)
            s = eng.stats
            row = {"tok_s": round(s["decode_tokens"]
                                  / max(s["decode_seconds"], 1e-9), 1)}
            if draft is not None:
                row["acceptance"] = round(
                    s["spec_accepted"] / max(s["spec_proposed"], 1), 3)
                row["spec_dispatches"] = s["spec_dispatches"]
            out[label] = row
        finally:
            eng.close()
    out["self_draft_speedup"] = round(
        out["self_draft"]["tok_s"] / max(out["vanilla"]["tok_s"], 1e-9), 3)
    out["small_draft_speedup"] = round(
        out["small_draft"]["tok_s"] / max(out["vanilla"]["tok_s"], 1e-9), 3)
    return out


def _synth_adapter_dir(cfg, path: str, seed: int, r: int = 8) -> str:
    """Write a synthetic PEFT-format LoRA adapter (q/v targets) for the
    bench model — torch-free, so the chip bench never pays a 0.9B torch
    materialization just to exercise the multi-LoRA path."""
    import json
    import os

    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    g = np.random.default_rng(seed)
    tensors = {}
    for i in range(cfg.num_layers):
        for mod, out_dim in (("q_proj", cfg.num_heads * cfg.head_dim),
                             ("v_proj", cfg.num_kv_heads * cfg.head_dim)):
            pre = f"base_model.model.model.layers.{i}.self_attn.{mod}"
            tensors[f"{pre}.lora_A.weight"] = (
                g.normal(0, 0.02, (r, cfg.hidden_size)).astype(np.float32))
            tensors[f"{pre}.lora_B.weight"] = (
                g.normal(0, 0.02, (out_dim, r)).astype(np.float32))
    save_file(tensors, os.path.join(path, "adapter_model.safetensors"))
    with open(os.path.join(path, "adapter_config.json"), "w") as f:
        json.dump({"peft_type": "LORA", "r": r, "lora_alpha": 2 * r,
                   "target_modules": ["q_proj", "v_proj"],
                   "bias": "none"}, f)
    return path


def bench_multilora(model, params, cfg, *, max_len: int, chunk: int,
                    buckets, decode_tokens: int, rng: np.random.Generator,
                    workdir: str) -> dict:
    """Mixed-adapter batch throughput vs base-only:
    4 concurrent requests — 2 base, 1 each on two rank-8 adapters —
    against the same 4 requests on a no-adapter engine. The delta is the
    cost of the per-row gather + rank-r delta einsums riding every
    dispatch."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    a1 = _synth_adapter_dir(cfg, f"{workdir}/ml_a", 11)
    a2 = _synth_adapter_dir(cfg, f"{workdir}/ml_b", 12)
    res = {}
    for label, adapters in (("base", None),
                            ("multilora", {"a": a1, "b": a2})):
        eng = GenerationEngine(model, params, cfg, slots=4, max_len=max_len,
                               chunk=chunk, prefill_buckets=buckets,
                               prefix_cache=0, adapters=adapters)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 16))
                       for _ in range(4)]
            names = [None, None, "a", "b"] if adapters else [None] * 4
            _drain(eng, prompts, decode_tokens,
                   per_prompt_kwargs=[{"adapter": ad} for ad in names])
            s = eng.stats
            res[label] = s["decode_tokens"] / max(s["decode_seconds"], 1e-9)
        finally:
            eng.close()
    return {
        "base_tok_s": round(res["base"], 1),
        "mixed_adapter_tok_s": round(res["multilora"], 1),
        "multilora_vs_base": round(
            res["multilora"] / max(res["base"], 1e-9), 3),
    }


def bench_pipelined_vs_sync(model, params, cfg, *, slots: int,
                            max_len: int, chunk: int, buckets,
                            decode_tokens: int,
                            rng: np.random.Generator) -> dict:
    """ISSUE 3 tentpole A/B: the overlapped engine (in-flight decode
    pipelining + off-critical-path admission, `pipeline_depth=2`) against
    the synchronous loop (`pipeline_depth=1`, the escape hatch that IS
    the old engine) on identical traffic — 2 waves of requests so
    admission overlaps in-flight decode. Every synchronous chunk fetch
    stalls the device's dispatch queue for a host round trip; depth 2
    hides it behind the next in-flight chunk. `host_stall_s` and
    the blocking/overlapped fetch split prove the MECHANISM (the stall
    left the loop), `wall_s`/`tok_s_e2e` the outcome. Measurement is
    fetch-synced: the wall clock closes when the last request's final
    tokens have been fetched to the host."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    res: dict[str, Any] = {}
    for label, depth in (("sync_depth1", 1), ("pipelined_depth2", 2)):
        eng = GenerationEngine(model, params, cfg, slots=slots,
                               max_len=max_len, chunk=chunk,
                               prefill_buckets=buckets, prefix_cache=0,
                               pipeline_depth=depth)
        try:
            prompts = [list(rng.integers(1, cfg.vocab_size, 16))
                       for _ in range(2 * slots)]
            dt, done = _drain(eng, prompts, decode_tokens)
            s = eng.stats
            emitted = sum(r["num_output_tokens"] for r in done)
            res[label] = {
                "pipeline_depth": depth,
                "wall_s": round(dt, 4),
                # Wall-anchored: under overlap the engine-busy clock
                # (decode_seconds) absorbs admission time the sync loop
                # spends elsewhere, so emitted/wall is the only tok/s
                # comparable across the two modes.
                "tok_s_e2e": round(emitted / max(dt, 1e-9), 1),
                "host_stall_s": round(s["host_stall_seconds"], 4),
                "decode_dispatches": s["decode_dispatches"],
                "blocking_fetches": s["decode_fetch_blocking"],
                "overlapped_fetches": s["decode_fetch_overlapped"],
                "admit_overlap": s["admit_overlap"],
                "wasted_tokens": s["decode_wasted_tokens"],
            }
        finally:
            eng.close()
    res["speedup_wall"] = round(
        res["sync_depth1"]["wall_s"]
        / max(res["pipelined_depth2"]["wall_s"], 1e-9), 3)
    res["host_stall_removed_s"] = round(
        res["sync_depth1"]["host_stall_s"]
        - res["pipelined_depth2"]["host_stall_s"], 4)
    return res


def bench_paged_vs_flat(model, params, cfg, *, slots: int, max_len: int,
                        chunk: int, buckets, decode_tokens: int,
                        rng: np.random.Generator) -> dict:
    """ISSUE 6 tentpole A/B: block-paged KV cache against the flat
    slot-contiguous cache on a mixed-length request set, at EQUAL pool
    memory (the paged pool holds exactly `slots x max_len` tokens, the
    flat engine's footprint) but double the decode width — the paged
    engine admits by free-block accounting, so short requests coexist
    where flat mode pins worst-case rows. `peak_inflight_requests` is
    the mechanism proof (more concurrent rows than flat slots in the
    same memory); wall/tok_s the outcome. Fetch-synced per PROFILE §1:
    _drain returns when every request's tokens are host-side."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    bs = 16  # divides max_len and every power-of-two decode bucket
    pool_blocks = slots * max_len // bs
    n_req = 4 * slots
    prompts = [list(rng.integers(
        1, cfg.vocab_size, int(rng.integers(8, max(10, max_len // 8)))))
        for _ in range(n_req)]
    res: dict[str, Any] = {}
    for label, kw, width in (
            ("flat", {}, slots),
            ("paged", {"kv_block_size": bs, "kv_blocks": pool_blocks},
             2 * slots)):
        eng = GenerationEngine(model, params, cfg, slots=width,
                               max_len=max_len, chunk=chunk,
                               prefill_buckets=buckets, prefix_cache=0,
                               pipeline_depth=2, **kw)
        peak = [0]
        orig = eng._dispatch_chunk

        def spy(active, carry=None, _orig=orig, _peak=peak):
            _peak[0] = max(_peak[0], len(active))
            return _orig(active, carry)

        eng._dispatch_chunk = spy
        try:
            dt, done = _drain(eng, prompts, decode_tokens)
            s = eng.stats
            emitted = sum(r["num_output_tokens"] for r in done)
            res[label] = {
                "slots": width,
                "pool_tokens": slots * max_len,
                "requests": n_req,
                "wall_s": round(dt, 4),
                "tok_s_e2e": round(emitted / max(dt, 1e-9), 1),
                "decode_dispatches": s["decode_dispatches"],
                "peak_inflight_requests": peak[0],
            }
            if label == "paged":
                res[label]["kv_block_size"] = bs
                res[label]["kv_blocks"] = pool_blocks
        finally:
            eng.close()
    res["speedup_wall"] = round(
        res["flat"]["wall_s"] / max(res["paged"]["wall_s"], 1e-9), 3)
    res["concurrency_gain"] = round(
        res["paged"]["peak_inflight_requests"]
        / max(res["flat"]["peak_inflight_requests"], 1), 3)
    return res


def bench_quant_paged(model, params, cfg, *, slots: int, max_len: int,
                      chunk: int, buckets, decode_tokens: int,
                      rng: np.random.Generator) -> dict:
    """ISSUE 19 tentpole A/B: int8 KV pool against the full-precision
    paged pool at EQUAL pool HBM — the quantized arm's block count is
    scaled by the per-token byte ratio (D·itemsize vs D+4 with the f32
    scale, ≈2x at bf16/D=64), floor-rounded so its pool never exceeds
    the full arm's bytes, and its decode width doubled again so the
    extra blocks can become extra concurrent requests.
    `peak_inflight_requests` is the mechanism proof (the quant pool
    RUNS more requests in the same memory); wall/tok_s the outcome.
    Two side rows make the rest of the claim: the same greedy probe
    through both arms (quality delta = max per-token |Δlogprob| —
    measured, not asserted) and one prefill handoff per arm (fmt-3
    wire bytes vs fmt-1 for the identical prompt). Fetch-synced per
    PROFILE §1: _drain returns when every token is host-side."""
    from kubeflow_tpu.serve.generation import GenerationEngine
    from kubeflow_tpu.serve.kv_transfer import peek_meta

    bs = 16  # divides max_len and every power-of-two decode bucket
    d = int(cfg.head_dim)
    fitem = int(jnp.dtype(cfg.dtype).itemsize)
    pool_blocks = slots * max_len // bs
    # Equal HBM: int8 rows cost D bytes + one f32 scale per row-head.
    q_blocks = pool_blocks * (d * fitem) // (d + 4)
    n_req = 8 * slots
    prompts = [list(rng.integers(
        1, cfg.vocab_size, int(rng.integers(8, max(10, max_len // 8)))))
        for _ in range(n_req)]
    probe = list(rng.integers(1, cfg.vocab_size, 16))
    res: dict[str, Any] = {}
    ident: dict[str, Any] = {}
    for label, kw, width, blocks in (
            ("full_paged", {}, 2 * slots, pool_blocks),
            ("quant_paged", {"kv_quant": "int8"}, 4 * slots, q_blocks)):
        eng = GenerationEngine(model, params, cfg, slots=width,
                               max_len=max_len, chunk=chunk,
                               prefill_buckets=buckets, prefix_cache=0,
                               pipeline_depth=2, kv_block_size=bs,
                               kv_blocks=blocks, **kw)
        peak = [0]
        orig = eng._dispatch_chunk

        def spy(active, carry=None, _orig=orig, _peak=peak):
            _peak[0] = max(_peak[0], len(active))
            return _orig(active, carry)

        eng._dispatch_chunk = spy
        try:
            dt, done = _drain(eng, prompts, decode_tokens)
            s = eng.stats
            emitted = sum(r["num_output_tokens"] for r in done)
            res[label] = {
                "slots": width,
                "kv_block_size": bs,
                "kv_blocks": blocks,
                # Measured, not derived: the device pool's actual bytes
                # (values + scale planes + the reserved garbage block).
                "pool_bytes": int(sum(np.asarray(a).nbytes
                                      for a in eng._cache.values())),
                "requests": n_req,
                "wall_s": round(dt, 4),
                "tok_s_e2e": round(emitted / max(dt, 1e-9), 1),
                "decode_dispatches": s["decode_dispatches"],
                "peak_inflight_requests": peak[0],
            }
            out = eng.submit(probe, max_tokens=decode_tokens,
                             temperature=0.0)
            ident[label] = (out["output_ids"], out["output_logprobs"])
        finally:
            eng.close()
    res["kv_blocks_ratio"] = round(q_blocks / max(pool_blocks, 1), 3)
    res["concurrency_gain"] = round(
        res["quant_paged"]["peak_inflight_requests"]
        / max(res["full_paged"]["peak_inflight_requests"], 1), 3)
    res["speedup_wall"] = round(
        res["full_paged"]["wall_s"]
        / max(res["quant_paged"]["wall_s"], 1e-9), 3)
    ids_f, lps_f = ident["full_paged"]
    ids_q, lps_q = ident["quant_paged"]
    res["quality"] = {
        "probe_tokens": len(ids_f),
        "greedy_ids_identical": bool(ids_f == ids_q),
        "max_logprob_delta": round(max(
            abs(a - b) for a, b in zip(lps_f, lps_q)), 5),
    }
    # Wire row: one prefill handoff per arm, identical prompt — the
    # quantized shipment (fmt 3) against the full-precision fmt 1.
    wire: dict[str, Any] = {}
    ship_prompt = list(rng.integers(1, cfg.vocab_size, 24))
    for label, kw in (("fmt1_bytes", {}),
                      ("fmt3_bytes", {"kv_quant": "int8"})):
        eng = GenerationEngine(model, params, cfg, slots=1,
                               max_len=max_len, chunk=chunk,
                               prefill_buckets=buckets, prefix_cache=0,
                               role="prefill", kv_block_size=bs,
                               kv_blocks=pool_blocks, **kw)
        try:
            ship = eng.prefill_ship(ship_prompt,
                                    max_tokens=decode_tokens)
            wire[label] = len(ship["shipment"])
            wire[label.replace("bytes", "fmt")] = peek_meta(
                ship["shipment"])["fmt"]
        finally:
            eng.close()
    wire["fmt3_vs_fmt1"] = round(
        wire["fmt3_bytes"] / max(wire["fmt1_bytes"], 1), 3)
    res["wire"] = wire
    return res


def bench_spec_paged(model, params, cfg, *, slots: int, max_len: int,
                     chunk: int, buckets, decode_tokens: int,
                     rng: np.random.Generator) -> dict:
    """ISSUE 18 tentpole A/B: speculative decoding composed with the
    paged engine at pipeline_depth=2 — vanilla-paged vs spec-paged
    (self-draft: the acceptance≈1 mechanism ceiling) on identical
    seeded MIXED traffic, greedy rows plus one top-p row per wave, so
    the per-sub-batch dispatch is what's measured (the old batch-wide
    gate would zero speculation on exactly this traffic). The pool
    carries both footprints (target + per-slot draft rows).
    Fetch-synced per PROFILE §1: _drain returns when every request's
    tokens are host-side. After the timed waves the SAME greedy prompt
    runs through both engines — the spec output must be token+logprob-
    identical, the lossless claim measured on the composed path."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    bs = 16  # divides max_len and every power-of-two decode bucket
    # Worst-case admission reserve doubles under speculation (the
    # draft's per-slot rows live in the same pool) — size it so `slots`
    # spec-able requests still fit concurrently.
    pool_blocks = 2 * slots * max_len // bs
    n_req = 2 * slots
    prompts = [list(rng.integers(1, cfg.vocab_size, 16))
               for _ in range(n_req)]
    kws: list[dict] = [{"temperature": 0.0}] * (n_req - 1)
    kws.append({"temperature": 0.9, "top_p": 0.9})
    probe = list(rng.integers(1, cfg.vocab_size, 16))
    res: dict[str, Any] = {}
    ident: dict[str, Any] = {}
    for label, draft in (
            ("vanilla_paged", None),
            ("spec_paged", {"model": model, "params": params,
                            "cfg": cfg, "gamma": 4})):
        eng = GenerationEngine(model, params, cfg, slots=slots,
                               max_len=max_len, chunk=chunk,
                               prefill_buckets=buckets, prefix_cache=0,
                               pipeline_depth=2, kv_block_size=bs,
                               kv_blocks=pool_blocks, draft=draft)
        try:
            dt, done = _drain(eng, prompts, decode_tokens,
                              per_prompt_kwargs=kws)
            s = eng.stats
            emitted = sum(r["num_output_tokens"] for r in done)
            row: dict[str, Any] = {
                "pipeline_depth": 2,
                "kv_block_size": bs,
                "kv_blocks": pool_blocks,
                "requests": n_req,
                "wall_s": round(dt, 4),
                "tok_s_e2e": round(emitted / max(dt, 1e-9), 1),
                "decode_dispatches": s["decode_dispatches"],
            }
            if draft is not None:
                row["spec_dispatches"] = s["spec_dispatches"]
                row["spec_proposed"] = s["spec_proposed"]
                row["spec_accepted"] = s["spec_accepted"]
                row["spec_stale_rides"] = s["spec_stale_rides"]
                row["acceptance"] = round(
                    s["spec_accepted"] / max(s["spec_proposed"], 1), 3)
            res[label] = row
            out = eng.submit(probe, max_tokens=decode_tokens,
                             temperature=0.0)
            ident[label] = (out["output_ids"], out["output_logprobs"])
        finally:
            eng.close()
    res["speedup_wall"] = round(
        res["vanilla_paged"]["wall_s"]
        / max(res["spec_paged"]["wall_s"], 1e-9), 3)
    ids_v, lps_v = ident["vanilla_paged"]
    ids_s, lps_s = ident["spec_paged"]
    res["greedy_identical"] = bool(
        ids_v == ids_s and np.allclose(lps_v, lps_s, rtol=1e-4,
                                       atol=1e-5))
    # The sub-batch split proof: a top-p row rode every wave, yet the
    # greedy rows still proposed and accepted draft tokens.
    res["mixed_traffic_speculated"] = bool(
        res["spec_paged"]["spec_dispatches"] > 0
        and res["spec_paged"]["spec_accepted"] > 0)
    return res


def bench_batcher(*, requests: int = 200, threads: int = 8,
                  max_batch_size: int = 32,
                  max_latency_ms: float = 2.0) -> dict:
    """Adaptive-batcher latency distribution under concurrent load, with a
    jitted matmul predictor (the BERT-predictor shape of config 3).

    Requests are [1, 256] — ONE example each, the server's request shape.
    (The r4 harness submitted rank-1 (256,) arrays; the batcher read the
    feature dim as a 256-row batch and every request took the oversized
    BYPASS — 8 threads contending on inline full predicts, zero
    coalescing. THAT was the 13x p99 tail: PROFILE.md §5.) The predictor pads coalesced batches to power-of-two
    buckets and warms them, like the server's AOT predictors — jit
    recompiles per distinct batch size would otherwise ride the tail."""
    from kubeflow_tpu.serve.batcher import Batcher

    w = jax.random.normal(jax.random.key(1), (256, 256), jnp.float32)

    @jax.jit
    def fwd(x):
        return jnp.tanh(x @ w) @ w

    def predict(inputs):
        x = np.asarray(inputs[0])
        n = x.shape[0]
        b = 1
        while b < n:
            b *= 2
        xp = np.zeros((b,) + x.shape[1:], x.dtype)
        xp[:n] = x
        return [np.asarray(fwd(jnp.asarray(xp)))[:n]]

    b = 1
    while b <= max_batch_size:  # warm the bucket set (AOT-load analog)
        predict([np.zeros((b, 256), np.float32)])
        b *= 2

    batcher = Batcher(predict, max_batch_size=max_batch_size,
                      max_latency_ms=max_latency_ms)
    lat: list[float] = []
    lock = threading.Lock()
    x = np.zeros((1, 256), np.float32)

    def worker(n):
        for _ in range(n):
            t0 = time.monotonic()
            batcher.submit([x]).result(timeout=60)
            dt = time.monotonic() - t0
            with lock:
                lat.append(dt)

    ths = [threading.Thread(target=worker, args=(requests // threads,))
           for _ in range(threads)]
    t0 = time.monotonic()
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=120)
    wall = time.monotonic() - t0
    stats = dict(batcher.stats)
    batcher.close()
    arr = np.asarray(lat) * 1e3
    return {
        "requests": len(lat),
        "throughput_rps": round(len(lat) / wall, 1),
        "p50_ms": round(float(np.percentile(arr, 50)), 3),
        "p99_ms": round(float(np.percentile(arr, 99)), 3),
        "coalesced_batches": stats["batches"],
        "examples_per_batch": round(
            stats["examples"] / max(stats["batches"], 1), 2),
    }


def run_servebench(*, cfg=None, quick: bool = False,
                   workdir: str = "/tmp/tpk_servebench") -> dict:
    """The full serving benchmark on `cfg` (default: the 0.9B bench model
    `llama_1b()`). The regression test passes a tiny config with `quick`;
    nothing here chooses a smaller model by itself.

    The chip config is deliberately lean on AOT surface — every engine
    pays its full warmup compile set, so buckets/slots variants are the
    compile-time budget (each engine ≈ prefill+extend+2·decode-buckets
    executables)."""
    import sys

    from kubeflow_tpu.models.llama import llama_1b

    cfg = cfg or llama_1b()

    if quick:
        max_len, chunk, buckets = 96, 4, (8, 16)
        slots_list: Sequence[int] = (1, 2)
        decode_tokens = 12
        batcher_reqs = 64
        long_max_len = 256
    else:
        max_len, chunk, buckets = 512, 16, (32, 128)
        slots_list = (1, 4)
        decode_tokens = 96
        batcher_reqs = 200
        long_max_len = 2048

    def log(stage):
        print(f"servebench: {stage}", file=sys.stderr, flush=True)

    log(f"building model ({cfg.num_params} params)")
    model, params = _build_model(cfg)
    rng = np.random.default_rng(0)

    result: dict[str, Any] = {
        "metric": "serving",
        "model_params": cfg.num_params,
        "device_kind": jax.devices()[0].device_kind,
        "max_len": max_len,
        "chunk": chunk,
        "prefill_buckets": list(buckets),
    }
    log("pipelined vs sync engine (overlapped scheduling A/B)")
    result["pipelined_vs_sync"] = bench_pipelined_vs_sync(
        model, params, cfg, slots=2 if quick else 4, max_len=max_len,
        chunk=chunk, buckets=buckets, decode_tokens=decode_tokens, rng=rng)
    log("paged vs flat KV cache (block-table memory A/B)")
    result["paged_vs_flat"] = bench_paged_vs_flat(
        model, params, cfg, slots=2 if quick else 4, max_len=max_len,
        chunk=chunk, buckets=buckets, decode_tokens=decode_tokens, rng=rng)
    log("quantized vs full-precision KV pool (equal-HBM A/B)")
    result["quant_paged"] = bench_quant_paged(
        model, params, cfg, slots=2 if quick else 4, max_len=max_len,
        chunk=chunk, buckets=buckets, decode_tokens=decode_tokens, rng=rng)
    log("spec x paged at depth 2 (speculation composition A/B)")
    result["spec_paged"] = bench_spec_paged(
        model, params, cfg, slots=2 if quick else 4, max_len=max_len,
        chunk=chunk, buckets=buckets, decode_tokens=decode_tokens, rng=rng)
    log("decode throughput vs slots")
    result["decode"] = bench_decode_slots(
        model, params, cfg, slots_list=slots_list, max_len=max_len,
        chunk=chunk, buckets=buckets, decode_tokens=decode_tokens, rng=rng)
    log("length-aware decode buckets")
    result["decode_buckets"] = bench_decode_buckets(
        model, params, cfg, max_len=max_len, chunk=chunk, buckets=buckets,
        decode_tokens=decode_tokens, rng=rng)
    log("ttft per prefill bucket")
    result.update(bench_ttft(model, params, cfg, max_len=max_len,
                             chunk=chunk, buckets=buckets, rng=rng))
    long_max_len = min(long_max_len, cfg.max_seq_len)
    log(f"length-aware decode at max_len {long_max_len}")
    result["decode_buckets_long"] = bench_decode_buckets_long(
        model, params, cfg, max_len=long_max_len, chunk=chunk,
        decode_tokens=decode_tokens, rng=rng)
    log("speculative decoding (vanilla / self-draft / small-draft)")
    result["spec_decode"] = bench_spec_decode(
        model, params, cfg, max_len=max_len, chunk=chunk, buckets=buckets,
        decode_tokens=decode_tokens, rng=rng)
    log("multi-LoRA mixed-adapter batch")
    result["multilora"] = bench_multilora(
        model, params, cfg, max_len=max_len, chunk=chunk, buckets=buckets,
        decode_tokens=decode_tokens, rng=rng, workdir=workdir)
    log("int8 vs bf16")
    result["quant"] = bench_quant(
        model, params, cfg, max_len=max_len, chunk=chunk, buckets=buckets,
        decode_tokens=decode_tokens, rng=rng)
    log("batcher percentiles")
    result["batcher"] = bench_batcher(requests=batcher_reqs)
    return result
