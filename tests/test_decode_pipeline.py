"""Overlapped serving engine (ISSUE 3 tentpole): in-flight decode
pipelining must never change WHAT is emitted — only when the host blocks.

Covers: greedy token-identity at every depth, the depth-1 escape hatch's
seeded-sampling determinism, the CPU dispatch-count guard (pipelined mode
issues ~O(1) host-blocking fetches where sync mode issues one per chunk —
the overlap can't silently regress without a TPU), EOS reconciliation of
speculatively dead chunks, and off-critical-path admission accounting.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.llama import Llama, llama_tiny
from kubeflow_tpu.serve.generation import GenerationEngine
from tests.test_generate import ref_greedy

CFG = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=2)


@pytest.fixture(scope="module")
def tiny():
    model = Llama(CFG)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefill_buckets", (8,))
    return GenerationEngine(model, params, CFG, **kw)


def test_dispatch_count_guard_pipelined_vs_sync(tiny):
    """THE CI guard (ISSUE 3 satellite): for an M-chunk generation the
    sync engine blocks the host on every one of its M fetches; the
    pipelined engine must overlap all but the pipe-drain tail. A
    regression that quietly re-serializes the loop flips these counters
    long before anyone can measure it on a chip."""
    model, params = tiny
    prompt = [5, 9, 2]
    chunks = 6
    budget = chunks * 4  # chunk=4 → exactly M=6 decode dispatches
    want = ref_greedy(model, params, prompt, budget)
    counts = {}
    for depth in (1, 2):
        eng = _engine(tiny, slots=1, pipeline_depth=depth)
        try:
            out = eng.submit(prompt, max_tokens=budget)
            assert out["output_ids"] == want, depth
            counts[depth] = dict(eng.stats)
        finally:
            eng.close()
    sync, piped = counts[1], counts[2]
    assert sync["decode_fetch_blocking"] == chunks
    assert sync["decode_fetch_overlapped"] == 0
    # Pipe fill + drain leave at most 2 non-overlapped fetches (first
    # fill and final drain); steady state must be overlapped.
    assert piped["decode_fetch_blocking"] <= 2, piped
    assert piped["decode_fetch_overlapped"] >= chunks - 2, piped
    # Budget gating: no runaway speculation past max_tokens.
    assert piped["decode_dispatches"] <= chunks + 1, piped


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_pipelined_greedy_matches_sync_multi_request(tiny):
    """3 concurrent requests on 2 slots through the pipelined loop: slot
    reuse with speculation in flight must keep every stream identical to
    the uncached reference."""
    model, params = tiny
    prompts = [[5, 9, 2], [17, 3, 3, 8, 1], [40, 7, 11, 2, 2, 6, 30]]
    budgets = [6, 9, 5]
    eng = _engine(tiny, prefill_buckets=(8, 16), pipeline_depth=2)
    try:
        results = [None] * 3

        def run(i):
            results[i] = eng.submit(prompts[i], max_tokens=budgets[i])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i in range(3):
            assert results[i] is not None, f"request {i} did not finish"
            assert results[i]["output_ids"] == ref_greedy(
                model, params, prompts[i], budgets[i]), i
    finally:
        eng.close()


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_depth1_seeded_sampling_deterministic_and_depth2_single_stream(
        tiny):
    """pipeline_depth=1 is the bit-exact escape hatch: same seed → same
    sampled stream across engine instances (the synchronous RNG-split
    order). A single budget-bounded request consumes identical splits at
    depth 2 (no EOS surprises → no extra speculative dispatches), so its
    stream matches too — the sampling law survives pipelining."""
    streams = {}
    for label, depth in (("d1a", 1), ("d1b", 1), ("d2", 2)):
        eng = _engine(tiny, slots=1, pipeline_depth=depth, seed=7)
        try:
            out = eng.submit([5, 9, 2], max_tokens=8, temperature=0.8,
                             top_p=0.9)
            streams[label] = out["output_ids"]
            assert len(streams[label]) == 8
        finally:
            eng.close()
    assert streams["d1a"] == streams["d1b"]
    assert streams["d2"] == streams["d1a"]


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_eos_reconciles_dead_speculation_and_slot_reuse(tiny):
    """EOS lands mid-chunk while chunk k+1 is already in flight: the
    request must stop exactly at EOS (dead rows dropped, accounted in
    decode_wasted_tokens) and the freed slot must serve a new request
    correctly even though its stale speculative chunk was still in
    flight at admission time."""
    model, params = tiny
    eng = _engine(tiny, slots=1, pipeline_depth=2)
    try:
        free = ref_greedy(model, params, [5, 9, 2], 12)
        eos = free[5]  # retires mid-chunk-2 with chunk 3 in flight
        out = eng.submit([5, 9, 2], max_tokens=12, eos_id=eos)
        assert out["output_ids"] == free[:6]
        deadline = time.monotonic() + 5.0
        while (eng.stats["decode_dead_slot_chunks"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)  # loop drains the dead chunk asynchronously
        assert eng.stats["decode_dead_slot_chunks"] >= 1
        assert eng.stats["decode_wasted_tokens"] >= eng.chunk
        out2 = eng.submit([7, 7, 1], max_tokens=6)
        assert out2["output_ids"] == ref_greedy(model, params, [7, 7, 1],
                                                6)
    finally:
        eng.close()


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_admission_overlaps_inflight_decode(tiny):
    """Off-critical-path admission: request B admitted while A's decode
    chunks are in flight must (a) be correct and (b) be counted as an
    overlapped admission — the prefill rode the device stream behind
    in-flight chunks instead of stopping the world."""
    model, params = tiny
    eng = _engine(tiny, pipeline_depth=2)
    try:
        results = {}

        def run_a():
            results["a"] = eng.submit([5, 9, 2], max_tokens=40)

        ta = threading.Thread(target=run_a)
        ta.start()
        # Wait until A is decoding (pipe non-empty in steady state),
        # then admit B mid-flight.
        deadline = time.monotonic() + 10.0
        while (eng.stats["decode_dispatches"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.002)
        results["b"] = eng.submit([8, 1, 4], max_tokens=8)
        ta.join(timeout=120)
        assert results["a"]["output_ids"] == ref_greedy(
            model, params, [5, 9, 2], 40)
        assert results["b"]["output_ids"] == ref_greedy(
            model, params, [8, 1, 4], 8)
        assert eng.stats["admit_overlap"] >= 1, eng.stats
    finally:
        eng.close()


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_max_tokens_1_finishes_without_decode_fetch(tiny):
    """A 1-token request at depth 2 finishes off the deferred first
    token — TTFT must not wait for a decode-chunk fetch boundary."""
    model, params = tiny
    eng = _engine(tiny, slots=1, pipeline_depth=2)
    try:
        out = eng.submit([5, 9, 2], max_tokens=1)
        assert out["output_ids"] == ref_greedy(model, params, [5, 9, 2], 1)
    finally:
        eng.close()


def test_pipeline_depth_validation(tiny):
    with pytest.raises(ValueError, match="pipeline_depth"):
        _engine(tiny, pipeline_depth=0)
