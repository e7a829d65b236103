"""Block-sparse mask specs (ops/ROADMAP.md item 2):
prefix-LM, sliding-window, and full masks through all three fused flash
kernels (fwd, bwd-dq, bwd-dkv), composed with segments, and through Llama.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.flash_attention import MaskSpec, flash_attention
from kubeflow_tpu.ops.reference import naive_attention


def _qkv(b, s, h, kh, d, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    return q, k, v


SPECS = [
    MaskSpec("causal"),
    MaskSpec("full"),
    MaskSpec("prefix_lm", prefix=24),
    MaskSpec("prefix_lm", prefix=64),  # exceeds one kv block
    MaskSpec("sliding_window", window=16),
    MaskSpec("sliding_window", window=50),  # crosses block boundaries
]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"{s.kind}-w{s.window}-p{s.prefix}")
def test_mask_spec_forward_matches_naive(spec):
    q, k, v = _qkv(b=2, s=96, h=4, kh=2, d=16, seed=31)
    ref = naive_attention(q, k, v, mask=spec)
    out = flash_attention(q, k, v, block_q=32, block_kv=32, mask=spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"{s.kind}-w{s.window}-p{s.prefix}")
def test_mask_spec_grads_match_naive(spec):
    q, k, v = _qkv(b=1, s=64, h=2, kh=2, d=8, seed=33)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, block_q=16, block_kv=16,
                            mask=spec) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, mask=spec) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_document_window_mask_composes_with_segments():
    """Sliding window + segment ids = document-window mask: the window
    never crosses a packed-document boundary."""
    q, k, v = _qkv(b=1, s=64, h=2, kh=2, d=8, seed=35)
    seg = jnp.concatenate([jnp.zeros((1, 40), jnp.int32),
                           jnp.ones((1, 24), jnp.int32)], axis=1)
    spec = MaskSpec("sliding_window", window=12)
    ref = naive_attention(q, k, v, mask=spec, segment_ids=seg)
    out = flash_attention(q, k, v, block_q=16, block_kv=16, mask=spec,
                          segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_prefix_lm_refuses_segments():
    """prefix_lm's boundary is an absolute position; packed rows restart
    positions per document, so composing them would silently give only
    the first document a bidirectional prefix — refused loudly."""
    q, k, v = _qkv(b=1, s=32, h=2, kh=2, d=8, seed=9)
    seg = jnp.zeros((1, 32), jnp.int32)
    with pytest.raises(ValueError, match="prefix_lm"):
        flash_attention(q, k, v, mask=MaskSpec("prefix_lm", prefix=8),
                        segment_ids=seg)
    # The portable fallback must refuse identically — otherwise
    # attention_impl='naive' runs semantics the fused path rejects.
    with pytest.raises(ValueError, match="prefix_lm"):
        naive_attention(q, k, v, mask=MaskSpec("prefix_lm", prefix=8),
                        segment_ids=seg)


def test_mask_spec_validation():
    with pytest.raises(ValueError, match="mask kind"):
        MaskSpec("triangular")
    with pytest.raises(ValueError, match="window"):
        MaskSpec("sliding_window", window=0)
    out_kind = flash_attention(
        *_qkv(b=1, s=32, h=2, kh=2, d=8, seed=1), mask="full")
    assert out_kind.shape == (1, 32, 2, 8)  # string shorthand accepted


def test_llama_accepts_mask_spec():
    """mask_kind on the config flows into the kernels; sliding-window
    logits differ from causal exactly where the window truncates."""
    from kubeflow_tpu.models.llama import Llama, llama_tiny

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=2)
    wcfg = dataclasses.replace(cfg, mask_kind="sliding_window",
                               mask_window=8)
    toks = jax.random.randint(jax.random.key(3), (1, 32), 0, cfg.vocab_size)
    params = Llama(cfg).init(jax.random.key(0), toks)["params"]
    full = Llama(cfg).apply({"params": params}, toks)
    windowed = Llama(wcfg).apply({"params": params}, toks)
    # Rows inside the window see identical context; later rows diverge.
    np.testing.assert_allclose(np.asarray(windowed[0, :8]),
                               np.asarray(full[0, :8]), rtol=2e-4,
                               atol=2e-4)
    assert not np.allclose(np.asarray(windowed[0, 16:]),
                           np.asarray(full[0, 16:]), atol=1e-3)


def test_llama_mask_spec_rejects_ring():
    from kubeflow_tpu.models.llama import Llama, llama_tiny

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=1,
                              attention_impl="ring",
                              mask_kind="sliding_window", mask_window=8)
    toks = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="causal-only"):
        Llama(cfg).init(jax.random.key(0), toks)
