"""Mistral-family import: sliding-window attention vs the torch reference.

The HF `sliding_window` config field maps onto the flash kernel's banded
MaskSpec (kind="sliding_window") instead of being refused; the serving
engine accepts windowed checkpoints only while max_len <= window, where
causal KV-cache decode is exact (serve/generation.py).
"""

from __future__ import annotations

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

pytestmark = pytest.mark.slow  # torch-reference tier


def _mistral_cfg(window):
    return transformers.MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, rope_theta=10000.0,
        rms_norm_eps=1e-5, sliding_window=window,
        attn_implementation="eager")


@pytest.fixture(scope="module")
def hf_mistral_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("hf_mistral")
    torch.manual_seed(9)
    model = transformers.MistralForCausalLM(_mistral_cfg(window=8))
    model.eval()
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


def test_mistral_windowed_logits_match_torch(hf_mistral_dir):
    """seq 16 > window 8: the band actually clips, so this checks the
    sliding-window MaskSpec against HF's eager window mask, not just
    causal agreement."""
    path, tmodel = hf_mistral_dir
    from kubeflow_tpu.models.hf_import import import_llama
    from kubeflow_tpu.models.llama import Llama

    cfg, params = import_llama(path, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    assert cfg.mask_kind == "sliding_window" and cfg.mask_window == 8
    model = Llama(cfg)
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, (2, 16), dtype=np.int64)
    with torch.no_grad():
        ref = tmodel(torch.from_numpy(toks)).logits.numpy()
    got = model.apply({"params": params}, jnp.asarray(toks, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), ref, atol=3e-3, rtol=2e-2)
    # Sanity: a causal (no-window) forward must DISAGREE at positions
    # past the window, or this test proves nothing.
    import dataclasses
    causal = Llama(dataclasses.replace(cfg, mask_kind="causal",
                                       mask_window=0))
    got_causal = causal.apply({"params": params},
                              jnp.asarray(toks, jnp.int32))
    assert not np.allclose(np.asarray(got_causal)[:, 12:],
                           ref[:, 12:], atol=3e-3, rtol=2e-2)


def test_windowed_serving_exact_within_window(hf_mistral_dir):
    """Engine accepts max_len <= window and its greedy decode matches the
    torch model's (windowed attention never clips inside the window)."""
    path, tmodel = hf_mistral_dir
    from kubeflow_tpu.models.hf_import import import_llama
    from kubeflow_tpu.models.llama import Llama
    from kubeflow_tpu.serve.generation import GenerationEngine

    cfg, params = import_llama(path, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    eng = GenerationEngine(Llama(cfg), params, cfg, slots=1, max_len=8,
                           chunk=4, prefill_buckets=(4,))
    try:
        prompt = [5, 9, 2]
        out = eng.submit(prompt, max_tokens=5, temperature=0.0)
        ids = torch.tensor([prompt])
        with torch.no_grad():
            ref = tmodel.generate(
                ids, max_new_tokens=5, do_sample=False,
                pad_token_id=0).numpy()[0, len(prompt):]
        assert list(out["output_ids"]) == list(ref)
    finally:
        eng.close()


def test_windowed_serving_composes_with_int8(hf_mistral_dir):
    """The causal rebuild must reconstruct the INNER module of a quantized
    wrapper, not call the wrapper's constructor with a config."""
    path, _ = hf_mistral_dir
    from kubeflow_tpu.models.hf_import import import_llama
    from kubeflow_tpu.models.llama import Llama
    from kubeflow_tpu.serve.generation import GenerationEngine
    from kubeflow_tpu.serve.quant import QuantizedModule, quantize_tree

    cfg, params = import_llama(path, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    eng = GenerationEngine(QuantizedModule(Llama(cfg), jnp.float32),
                           quantize_tree(params), cfg, slots=1, max_len=8,
                           chunk=4, prefill_buckets=(4,))
    try:
        assert isinstance(eng.model, QuantizedModule)
        assert eng.model.module.cfg.mask_kind == "causal"
        out = eng.submit([5, 9, 2], max_tokens=3, temperature=0.0)
        assert len(out["output_ids"]) == 3
    finally:
        eng.close()


def test_windowed_serving_rolls_past_window(hf_mistral_dir):
    """max_len > window switches to the ROLLING cache (window rows,
    modular writes) and greedy decode stays token-identical to torch even
    when prompt + generation outgrow the window — the vLLM capability the
    engine used to refuse."""
    path, tmodel = hf_mistral_dir
    from kubeflow_tpu.models.hf_import import import_llama
    from kubeflow_tpu.models.llama import Llama
    from kubeflow_tpu.serve.generation import GenerationEngine

    cfg, params = import_llama(path, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    eng = GenerationEngine(Llama(cfg), params, cfg, slots=1, max_len=32,
                           chunk=4, prefill_buckets=(4,))
    try:
        assert eng._rolling == 8 and eng.cfg.mask_kind == "sliding_window"
        rng = np.random.default_rng(4)
        # Prompt 12 > window 8 (chunked admission through the rolling
        # cache), decode 10 more — the band clips throughout.
        prompt = [int(t) for t in rng.integers(0, 256, 12)]
        out = eng.submit(prompt, max_tokens=10, temperature=0.0)
        with torch.no_grad():
            ref = tmodel.generate(
                torch.tensor([prompt]), max_new_tokens=10, do_sample=False,
                pad_token_id=0).numpy()[0, len(prompt):]
        assert list(out["output_ids"]) == list(ref)
    finally:
        eng.close()
