"""Rolling sliding-window KV cache: serving Mistral-class checkpoints
PAST the window (the vLLM/huggingfaceserver capability; SURVEY.md §2.2
runtimes row).

Oracle: step-by-step FULL-FORWARD greedy decode under the sliding-window
MaskSpec — no cache at all, so any rolling-cache bookkeeping bug (modular
write collisions, pad-row eviction, spec-decode rewind clobber, stale-row
reads) shows up as a token mismatch. Torch parity for the same path lives
in test_mistral_import.py (slow tier).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.llama import Llama, LlamaConfig, init_cache
from kubeflow_tpu.serve.generation import GenerationEngine

WINDOW = 8


def _cfg(**kw):
    base = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                max_seq_len=64, remat=False, mask_kind="sliding_window",
                mask_window=WINDOW, dtype=jnp.float32,
                param_dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig(**base)


@pytest.fixture(scope="module")
def windowed_model():
    cfg = _cfg()
    model = Llama(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


def _oracle(model, params, prompt, n):
    """Greedy continuation via full forwards (sliding-window mask, no
    cache) — the exactness reference for every engine path below."""
    seq = list(prompt)
    for _ in range(n):
        logits = model.apply({"params": params},
                             jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def test_rolling_cache_layout():
    """Sliding cfg past the window allocates window rows + a pos plane."""
    cfg = _cfg()
    cache = init_cache(cfg, 2, 32)
    assert cache["k"].shape == (2, 2, WINDOW, 2, 8)
    assert cache["pos"].shape == (2, 2, WINDOW)
    assert int(cache["pos"][0, 0, 0]) == -(WINDOW + 1)
    # Within the window: plain causal layout, no pos plane.
    within = init_cache(cfg, 2, WINDOW)
    assert "pos" not in within and within["k"].shape[2] == WINDOW


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_engine_rolls_past_window(windowed_model):
    """Long prompt (chunked admission) + decode across the wrap boundary,
    token-identical to the full-forward oracle."""
    model, params, cfg = windowed_model
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, 128, 13)]
    eng = GenerationEngine(model, params, cfg, slots=2, max_len=32,
                           chunk=4, prefill_buckets=(4, 16))
    try:
        assert eng._rolling == WINDOW
        # Buckets clamp to the window (wider chunks would wrap onto
        # themselves); decode has the single window-sized bucket.
        assert eng.prefill_buckets == [4, WINDOW]
        assert eng.decode_buckets == [WINDOW]
        out = eng.submit(prompt, max_tokens=10, temperature=0.0)
        assert out["output_ids"] == _oracle(model, params, prompt, 10)
        # Short prompt, generation alone outgrows the window.
        p2 = [int(t) for t in rng.integers(0, 128, 3)]
        got = eng.submit(p2, max_tokens=16, temperature=0.0)["output_ids"]
        assert got == _oracle(model, params, p2, 16)
    finally:
        eng.close()


def test_rolling_concurrent_slots(windowed_model):
    """Two in-flight requests share the slot-batched rolling cache
    without cross-talk (per-row modular indices)."""
    import threading

    model, params, cfg = windowed_model
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(0, 128, n)] for n in (11, 5)]
    want = [_oracle(model, params, p, 9) for p in prompts]
    eng = GenerationEngine(model, params, cfg, slots=2, max_len=32,
                           chunk=4, prefill_buckets=(8,))
    try:
        got = [None, None]

        def run(i):
            got[i] = eng.submit(prompts[i], max_tokens=9,
                                temperature=0.0)["output_ids"]

        ts = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert got[0] == want[0] and got[1] == want[1]
    finally:
        eng.close()


def test_rolling_spec_decode_exact(windowed_model):
    """Speculative decoding x rolling: rejected candidate writes are
    reverted (they evict live in-window rows otherwise), keeping greedy
    output token-identical to the oracle."""
    model, params, cfg = windowed_model
    dcfg = LlamaConfig(vocab_size=128, hidden_size=16, intermediate_size=32,
                       num_layers=1, num_heads=2, num_kv_heads=1, head_dim=8,
                       max_seq_len=64, remat=False, dtype=jnp.float32,
                       param_dtype=jnp.float32)
    dmodel = Llama(dcfg)
    dparams = dmodel.init(jax.random.key(1),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(0, 128, 11)]
    eng = GenerationEngine(
        model, params, cfg, slots=1, max_len=32, chunk=8,
        prefill_buckets=(8,),
        draft={"model": dmodel, "params": dparams, "cfg": dcfg, "gamma": 3})
    try:
        out = eng.submit(prompt, max_tokens=12, temperature=0.0)
        assert out["output_ids"] == _oracle(model, params, prompt, 12)
        assert eng.stats["spec_dispatches"] > 0
    finally:
        eng.close()


def test_rolling_prefix_cache(windowed_model):
    """Prefix-cache fragments carry the pos plane; a hit resumes exactly."""
    model, params, cfg = windowed_model
    rng = np.random.default_rng(11)
    p = [int(t) for t in rng.integers(0, 128, 9)]
    want = _oracle(model, params, p, 8)
    eng = GenerationEngine(model, params, cfg, slots=1, max_len=32,
                           chunk=4, prefill_buckets=(4,), prefix_cache=4)
    try:
        assert eng.submit(p, max_tokens=8,
                          temperature=0.0)["output_ids"] == want
        assert eng.submit(p, max_tokens=8,
                          temperature=0.0)["output_ids"] == want
        assert eng.stats["prefix_hits"] >= 1
    finally:
        eng.close()


def test_rolling_gamma_exceeding_window_refused(windowed_model):
    model, params, cfg = windowed_model
    dcfg = LlamaConfig(vocab_size=128, hidden_size=16, intermediate_size=32,
                       num_layers=1, num_heads=2, num_kv_heads=1, head_dim=8,
                       max_seq_len=64, remat=False, dtype=jnp.float32,
                       param_dtype=jnp.float32)
    dmodel = Llama(dcfg)
    dparams = dmodel.init(jax.random.key(1),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="rolling window"):
        GenerationEngine(
            model, params, cfg, slots=1, max_len=32, chunk=16,
            prefill_buckets=(8,),
            draft={"model": dmodel, "params": dparams, "cfg": dcfg,
                   "gamma": WINDOW})
