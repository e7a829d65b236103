"""T5 encoder-decoder — the text2text model family.

The reference serves T5-class checkpoints through huggingfaceserver's
text2text_generation task (SURVEY.md §2.2 ⟨kserve:
python/huggingfaceserver⟩). This is a native flax implementation with the
T5 specifics that silently break naive ports: RMS layer norm in fp32 with
no mean subtraction, NO sqrt(d) attention scaling, bucketed relative
position bias owned by the first block of each stack (bidirectional for
the encoder, causal-asymmetric for the decoder, none for cross
attention), pre-LN residual blocks, and — when embeddings are tied — the
d_model**-0.5 logits rescale.

Generation is one XLA program end to end (`greedy_generate`): encoder,
per-layer cross K/V precompute, then a `lax.scan` over decoder steps with
a self-attention KV cache — no per-token host round trip (each one
stalls the device's dispatch queue).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 2048
    num_layers: int = 6          # encoder
    num_decoder_layers: int = 6
    num_heads: int = 8
    rel_buckets: int = 32
    rel_max_distance: int = 128
    layer_norm_eps: float = 1e-6
    feed_forward_proj: str = "relu"   # "relu" (v1.0) | "gated-gelu" (v1.1)
    # UMT5: EVERY layer owns its relative-position bias table (classic
    # T5/MT5 share block 0's across the stack).
    per_layer_rel_bias: bool = False
    tie_embeddings: bool = True
    decoder_start_id: int = 0
    eos_id: int = 1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def gated(self) -> bool:
        return self.feed_forward_proj.startswith("gated")

    @property
    def num_params(self) -> int:
        e = self.vocab_size * self.d_model
        att = 4 * self.d_model * self.num_heads * self.d_kv
        ff = (3 if self.gated else 2) * self.d_model * self.d_ff
        enc = self.num_layers * (att + ff)
        dec = self.num_decoder_layers * (2 * att + ff)
        return e * (1 if self.tie_embeddings else 2) + enc + dec


def t5_small() -> T5Config:
    return T5Config()


def t5_tiny() -> T5Config:
    return T5Config(vocab_size=64, d_model=32, d_kv=8, d_ff=64,
                    num_layers=2, num_decoder_layers=2, num_heads=4,
                    rel_buckets=8, rel_max_distance=16)


class T5LayerNorm(nn.Module):
    """RMS norm, fp32 accumulation, no bias, no mean subtraction."""

    eps: float
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],), self.param_dtype)
        dt = x.dtype
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                + self.eps)
        return (xf * scale).astype(dt)


def relative_position_bucket(rel_pos, *, bidirectional: bool,
                             num_buckets: int, max_distance: int):
    """The T5 log-bucketed relative position → bucket index map
    (vectorized; matches the reference bucketing exactly, asserted by the
    torch-parity tests)."""
    ret = jnp.zeros_like(rel_pos)
    n = num_buckets
    if bidirectional:
        n = n // 2
        ret = ret + jnp.where(rel_pos > 0, n, 0)
        rel_pos = jnp.abs(rel_pos)
    else:
        rel_pos = -jnp.minimum(rel_pos, 0)
    max_exact = n // 2
    is_small = rel_pos < max_exact
    large = max_exact + (
        jnp.log(jnp.maximum(rel_pos, 1).astype(jnp.float32) / max_exact)
        / jnp.log(max_distance / max_exact) * (n - max_exact)
    ).astype(rel_pos.dtype)
    large = jnp.minimum(large, n - 1)
    return ret + jnp.where(is_small, rel_pos, large)


class RelPosBias(nn.Module):
    """[heads, q_len, kv_len] additive bias from bucketed offsets."""

    cfg: T5Config
    bidirectional: bool

    @nn.compact
    def __call__(self, q_pos, kv_pos):
        cfg = self.cfg
        table = self.param("rel_embedding", nn.with_logical_partitioning(
            nn.initializers.normal(1.0), (None, "heads")),
            (cfg.rel_buckets, cfg.num_heads), cfg.param_dtype)
        rel = kv_pos[None, :] - q_pos[:, None]  # [Q, KV]
        bucket = relative_position_bucket(
            rel, bidirectional=self.bidirectional,
            num_buckets=cfg.rel_buckets, max_distance=cfg.rel_max_distance)
        return table[bucket].transpose(2, 0, 1).astype(cfg.dtype)


class T5Attention(nn.Module):
    """q @ k with NO sqrt(d) scaling; optional additive position bias.

    Projections live in setup so the cached decode path can call them
    individually (q/k/v on different tensors) outside a compact trace.
    """

    cfg: T5Config

    def setup(self):
        cfg = self.cfg
        proj = partial(
            nn.DenseGeneral, features=(cfg.num_heads, cfg.d_kv),
            use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(),
                ("qkv_embed", "heads", "kv")))
        self.q, self.k, self.v = proj(name="q"), proj(name="k"), proj(name="v")
        self.o = nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "kv", "embed")),
            name="o")

    def __call__(self, x, kv, mask, bias=None):
        return self.finish(self.q(x), self.k(kv), self.v(kv), mask, bias)

    def finish(self, q, k, v, mask, bias=None):
        """Score/softmax/project half — shared by the cached decode path,
        which computes k/v against the cache instead."""
        cfg = self.cfg
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        if bias is not None:
            scores = scores + bias.astype(jnp.float32)
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return self.o(out)


class T5FFN(nn.Module):
    cfg: T5Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        up = dict(kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "mlp")))
        if cfg.gated:
            h = (nn.gelu(dense(cfg.d_ff, **up, name="wi_0")(x),
                         approximate=True)
                 * dense(cfg.d_ff, **up, name="wi_1")(x))
        else:
            h = nn.relu(dense(cfg.d_ff, **up, name="wi")(x))
        return dense(cfg.d_model, kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("mlp", "embed")),
            name="wo")(h)


class T5(nn.Module):
    """Teacher-forced forward: `__call__(input_ids, decoder_input_ids)` →
    logits [B, T, V]. Generation goes through `greedy_generate` (module
    methods `encode` / `cross_kv` / `decode_step` compose the one-program
    decode loop)."""

    cfg: T5Config

    def setup(self):
        cfg = self.cfg
        self.shared = self.param(
            "shared_embedding", nn.with_logical_partitioning(
                nn.initializers.normal(1.0), ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        ln = partial(T5LayerNorm, eps=cfg.layer_norm_eps,
                     param_dtype=cfg.param_dtype)
        if cfg.per_layer_rel_bias:  # UMT5: one table per layer
            self.enc_rels = [RelPosBias(cfg, bidirectional=True,
                                        name=f"enc_{i}_rel")
                             for i in range(cfg.num_layers)]
            self.dec_rels = [RelPosBias(cfg, bidirectional=False,
                                        name=f"dec_{i}_rel")
                             for i in range(cfg.num_decoder_layers)]
        else:
            self.enc_rel = RelPosBias(cfg, bidirectional=True,
                                      name="enc_rel")
            self.dec_rel = RelPosBias(cfg, bidirectional=False,
                                      name="dec_rel")
        self.enc_attn = [T5Attention(cfg, name=f"enc_{i}_attn")
                         for i in range(cfg.num_layers)]
        self.enc_attn_ln = [ln(name=f"enc_{i}_attn_ln")
                            for i in range(cfg.num_layers)]
        self.enc_ffn = [T5FFN(cfg, name=f"enc_{i}_ffn")
                        for i in range(cfg.num_layers)]
        self.enc_ffn_ln = [ln(name=f"enc_{i}_ffn_ln")
                           for i in range(cfg.num_layers)]
        self.enc_final_ln = ln(name="enc_final_ln")
        d = cfg.num_decoder_layers
        self.dec_self = [T5Attention(cfg, name=f"dec_{i}_self")
                         for i in range(d)]
        self.dec_self_ln = [ln(name=f"dec_{i}_self_ln") for i in range(d)]
        self.dec_cross = [T5Attention(cfg, name=f"dec_{i}_cross")
                          for i in range(d)]
        self.dec_cross_ln = [ln(name=f"dec_{i}_cross_ln") for i in range(d)]
        self.dec_ffn = [T5FFN(cfg, name=f"dec_{i}_ffn") for i in range(d)]
        self.dec_ffn_ln = [ln(name=f"dec_{i}_ffn_ln") for i in range(d)]
        self.dec_final_ln = ln(name="dec_final_ln")
        if not cfg.tie_embeddings:
            self.lm_head = self.param(
                "lm_head", nn.with_logical_partitioning(
                    nn.initializers.normal(1.0), ("embed", "vocab")),
                (cfg.d_model, cfg.vocab_size), cfg.param_dtype)

    # -- encoder ------------------------------------------------------------

    def encode(self, input_ids, enc_mask=None):
        cfg = self.cfg
        b, s = input_ids.shape
        if enc_mask is None:
            enc_mask = jnp.ones((b, s), jnp.bool_)
        x = self.shared[input_ids].astype(cfg.dtype)
        pos = jnp.arange(s)
        bias = (None if cfg.per_layer_rel_bias
                else self.enc_rel(pos, pos)[None])   # [1, H, S, S]
        mask = enc_mask[:, None, None, :]            # [B, 1, 1, S]
        for i in range(cfg.num_layers):
            b_i = (self.enc_rels[i](pos, pos)[None]
                   if cfg.per_layer_rel_bias else bias)
            h = self.enc_attn_ln[i](x)
            x = x + self.enc_attn[i](h, h, mask, b_i)
            x = x + self.enc_ffn[i](self.enc_ffn_ln[i](x))
        return self.enc_final_ln(x)

    # -- decoder ------------------------------------------------------------

    def _logits(self, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            # The tied head includes the T5 d_model**-0.5 rescale.
            x = x * (cfg.d_model ** -0.5)
            return jnp.einsum("btd,vd->btv", x,
                              self.shared.astype(cfg.dtype)
                              ).astype(jnp.float32)
        return jnp.einsum("btd,dv->btv", x,
                          self.lm_head.astype(cfg.dtype)
                          ).astype(jnp.float32)

    def decode(self, decoder_input_ids, enc_out, enc_mask):
        """Teacher-forced decoder pass → logits [B, T, V]."""
        cfg = self.cfg
        b, t = decoder_input_ids.shape
        x = self.shared[decoder_input_ids].astype(cfg.dtype)
        pos = jnp.arange(t)
        bias = (None if cfg.per_layer_rel_bias
                else self.dec_rel(pos, pos)[None])
        causal = (pos[:, None] >= pos[None, :])[None, None]
        cross_mask = enc_mask[:, None, None, :]
        for i in range(cfg.num_decoder_layers):
            b_i = (self.dec_rels[i](pos, pos)[None]
                   if cfg.per_layer_rel_bias else bias)
            h = self.dec_self_ln[i](x)
            x = x + self.dec_self[i](h, h, causal, b_i)
            x = x + self.dec_cross[i](self.dec_cross_ln[i](x), enc_out,
                                      cross_mask)
            x = x + self.dec_ffn[i](self.dec_ffn_ln[i](x))
        return self._logits(self.dec_final_ln(x))

    def __call__(self, input_ids, decoder_input_ids, enc_mask=None):
        b, s = input_ids.shape
        if enc_mask is None:
            enc_mask = jnp.ones((b, s), jnp.bool_)
        return self.decode(decoder_input_ids,
                           self.encode(input_ids, enc_mask), enc_mask)

    # -- one-program greedy decode parts ------------------------------------

    def cross_kv(self, enc_out):
        """Per-layer cross-attention K/V, computed once per request."""
        return [(self.dec_cross[i].k(enc_out), self.dec_cross[i].v(enc_out))
                for i in range(self.cfg.num_decoder_layers)]

    def decode_step(self, tok, cache_k, cache_v, pos, enc_mask, cross):
        """One decoder step at position `pos` (scalar): tok [B, 1] →
        (logits [B, V], updated caches). cache_k/v: [L, B, T_max, H, Dk]."""
        cfg = self.cfg
        x = self.shared[tok].astype(cfg.dtype)     # [B, 1, D]
        t_max = cache_k.shape[2]
        kv_pos = jnp.arange(t_max)
        bias = (None if cfg.per_layer_rel_bias
                else self.dec_rel(pos[None], kv_pos)[None])  # [1,H,1,T]
        self_mask = (kv_pos <= pos)[None, None, None, :]
        cross_mask = enc_mask[:, None, None, :]
        for i in range(cfg.num_decoder_layers):
            b_i = (self.dec_rels[i](pos[None], kv_pos)[None]
                   if cfg.per_layer_rel_bias else bias)
            attn = self.dec_self[i]
            h = self.dec_self_ln[i](x)
            q, k1, v1 = attn.q(h), attn.k(h), attn.v(h)
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k1[None].astype(cache_k.dtype), (i, 0, pos, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v1[None].astype(cache_v.dtype), (i, 0, pos, 0, 0))
            x = x + attn.finish(q, cache_k[i].astype(cfg.dtype),
                                cache_v[i].astype(cfg.dtype),
                                self_mask, b_i)
            cattn = self.dec_cross[i]
            cq = cattn.q(self.dec_cross_ln[i](x))
            ckk, cvv = cross[i]
            x = x + cattn.finish(cq, ckk, cvv, cross_mask)
            x = x + self.dec_ffn[i](self.dec_ffn_ln[i](x))
        logits = self._logits(self.dec_final_ln(x))[:, 0]
        return logits, cache_k, cache_v


def greedy_generate(model: T5, params, input_ids, enc_mask=None, *,
                    max_tokens: int):
    """Whole greedy decode as ONE jittable program: encoder + cross-KV
    precompute + a lax.scan over `max_tokens` decoder steps with a
    self-attention KV cache. Emission stops advancing at EOS (tokens after
    are padded with eos_id); returns (tokens [B, max_tokens],
    n_valid [B])."""
    cfg = model.cfg
    b, s = input_ids.shape
    if enc_mask is None:
        enc_mask = jnp.ones((b, s), jnp.bool_)

    enc_out = model.apply({"params": params}, input_ids, enc_mask,
                          method=T5.encode)
    cross = model.apply({"params": params}, enc_out, method=T5.cross_kv)
    L, H, Dk = cfg.num_decoder_layers, cfg.num_heads, cfg.d_kv
    cache_k = jnp.zeros((L, b, max_tokens, H, Dk), cfg.dtype)
    cache_v = jnp.zeros_like(cache_k)

    def step(carry, pos):
        tok, ck, cv, done = carry
        logits, ck, cv = model.apply(
            {"params": params}, tok, ck, cv, pos, enc_mask, cross,
            method=T5.decode_step)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        nxt = jnp.where(done, jnp.int32(cfg.eos_id), nxt)
        done = jnp.logical_or(done, nxt == cfg.eos_id)
        return (nxt[:, None], ck, cv, done), nxt

    start = jnp.full((b, 1), cfg.decoder_start_id, jnp.int32)
    (_, _, _, done), toks = jax.lax.scan(
        step, (start, cache_k, cache_v, jnp.zeros((b,), jnp.bool_)),
        jnp.arange(max_tokens))
    toks = toks.T  # [B, max_tokens]
    n_valid = jnp.where(
        (toks == cfg.eos_id).any(1),
        jnp.argmax(toks == cfg.eos_id, 1), max_tokens)
    return toks, n_valid
