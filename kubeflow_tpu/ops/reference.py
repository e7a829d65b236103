"""Reference (einsum) attention — the numerics golden for every fused path.

Single source of truth for GQA softmax attention: models call it as the
portable fallback, flash_attention's VJP differentiates through it, and the
kernel tests compare against it. O(S·T) score materialization — correct at
any size, only efficient at small ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def naive_attention(q, k, v, *, causal: bool = True,
                    positions_q=None, positions_kv=None,
                    segment_ids=None, segment_ids_kv=None,
                    mask=None, softcap: float = 0.0,
                    windowed=None, k_scale=None, v_scale=None) -> jax.Array:
    """q: [B,S,H,D]; k: [B,T,KH,D]; v: [B,T,KH,Dv] with H % KH == 0 (Dv may
    differ from D: the result is [B,S,H,Dv]); fp32 softmax.
    Causality is masked by absolute positions when given (packed/offset
    sequences), else by array index. `segment_ids` [B,S] (and optionally a
    separate kv set) additionally confine attention within equal-id spans
    — the packed-sequence mask. `mask` (a flash_attention.MaskSpec)
    selects causal/full/prefix_lm/sliding_window, overriding `causal`.

    `softcap` > 0 applies Gemma-2's attention-logit soft-cap
    tanh(s/cap)*cap after scaling, before masking. `windowed` (traced
    scalar bool, Gemma-2's alternating layers) gates a sliding_window
    mask's band per call: where False the mask degrades to plain causal
    — dynamic, so one scanned trunk serves both layer types.

    `k_scale`/`v_scale` [B,T,KH] f32 are per-row dequant scales for a
    QUANTIZED cache (serve/quant.py KV helpers): k/v arrive as the raw
    quantized values through a bare convert, and the scales land on the
    score/prob tensors — `scores * k_scale` after Q·Kᵀ, `probs *
    v_scale` before probs·V (the scale varies along the contraction
    axis, so pre-contraction on probs is the output-side placement). No
    cache-width `[..., T, KH, D]` multiply ever exists; the HLO guard
    in tests/test_kv_quant.py pins this."""
    if (mask is not None and mask.kind == "prefix_lm"
            and segment_ids is not None):
        # Same refusal as flash_attention: a global prefix boundary is
        # ill-defined over packed documents whose positions restart per
        # segment — accepting it here would let attention_impl='naive'
        # run semantics the fused path deliberately rejects.
        raise ValueError(
            "prefix_lm mask is incompatible with packed segment_ids: "
            "the prefix boundary is global but packed positions restart "
            "per document")
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    group = h // kh
    qg = q.reshape(b, s, kh, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if k_scale is not None:
        scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        pq = positions_q if positions_q is not None else jnp.arange(s)[None]
        pk = positions_kv if positions_kv is not None else jnp.arange(t)[None]
        rows = pq[:, None, None, :, None]
        cols = pk[:, None, None, None, :]
        if mask.kind == "causal":
            m = rows >= cols
        elif mask.kind == "prefix_lm":
            m = (rows >= cols) | (cols < mask.prefix)
        elif mask.kind == "sliding_window":
            band = rows - cols < mask.window
            if windowed is not None:
                band = band | jnp.logical_not(windowed)
            m = (rows >= cols) & band
        else:  # full
            m = None
        if m is not None:
            scores = jnp.where(m, scores, -1e30)
    elif causal:
        pq = positions_q if positions_q is not None else jnp.arange(s)[None]
        pk = positions_kv if positions_kv is not None else jnp.arange(t)[None]
        mask = pq[:, None, None, :, None] >= pk[:, None, None, None, :]
        scores = jnp.where(mask, scores, -1e30)
    if segment_ids is not None:
        sk = segment_ids_kv if segment_ids_kv is not None else segment_ids
        seg = (segment_ids[:, None, None, :, None]
               == sk[:, None, None, None, :])
        scores = jnp.where(seg, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    probs = probs.astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, v.shape[-1])
