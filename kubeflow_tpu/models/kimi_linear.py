"""Kimi-Linear: a decoder whose layers differ in kind — Kimi Delta Attention
(a gated delta-rule linear attention, ops/kda.py) on three layers of four,
latent attention without positions (MLA, NoPE) on the fourth, a dense SwiGLU
on the leading layers and routed experts (models/moe.HeldExpertsBlock) on
the rest. After the Kimi Linear report (arXiv 2510.26692) and the published
`config.json` of moonshotai/Kimi-Linear-48B-A3B-Instruct.

Pre-norm residual blocks, `x += Mix(RMSNorm(x)); x += FFN(RMSNorm(x))`, a
final RMSNorm and an untied head. Per layer, from the config's 1-indexed
lists: `kda_layers` / `full_attn_layers` name the mixer, layers up to
`first_k_dense_replace` the dense FFN.

One chip of an expert-parallel job holds `experts_held` of every expert
layer's `num_experts` and a slice of the vocabulary: the router keeps its
width and its top-k, the layer computes the chosen held experts' part, and
what the absent experts would add is left out here and in the reference
alike (benchmarks/reference/kimi_linear.py): that partial result goes on to
the next layer. Nothing here stands in for the absent chips.

The trunk is unrolled (the layers' parameter trees differ, so no `nn.scan`)
with each layer under `jax.checkpoint` (`_RematLayer`: a KDA layer keeps what
its forward kernel wrote). Activations and matmul operands are
`cfg.dtype` (bf16) with fp32 accumulation; norms, the softmax, the router,
the gates and the whole KDA core are fp32 (the KDA mixer's inside the two
kernels of ops/kda.py, see `KDAMixer`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.models.llama import LlamaConfig, MLPBlock, RMSNorm
from kubeflow_tpu.models.moe import HeldExpertsBlock
from kubeflow_tpu.ops.flash_attention import flash_attention
from kubeflow_tpu.ops.kda import kda_mixer


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_layers: int = 27
    # 1-indexed, as the source lists them (linear_attn_config).
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216        # the dense layers' SwiGLU
    # KDA (linear_attn_config: num_heads, head_dim, short_conv_kernel_size)
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_lowrank: int = 128               # decay and output-gate bottleneck
    kda_chunk: int = 64
    kda_norm_eps: float = 1e-6           # L2 norm of q and k per head
    # MLA, no positions (mla_use_nope)
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64           # the shared key part; no rotary
    v_head_dim: int = 128
    # Routed experts
    num_experts: int = 256
    experts_per_token: int = 8
    experts_held: tuple = (0, 256)       # (first, count) held on this chip
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    flash_block_q: int = 512
    flash_block_kv: int = 512

    def __post_init__(self):
        # JSON specs hand lists over; the dataclass must stay hashable.
        for name in ("kda_layers", "full_attn_layers", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        kinds = set(self.kda_layers) | set(self.full_attn_layers)
        missing = set(range(1, self.num_layers + 1)) - kinds
        if missing or set(self.kda_layers) & set(self.full_attn_layers):
            raise ValueError(
                f"every layer 1..{self.num_layers} needs one mixer; "
                f"kda_layers {self.kda_layers} and full_attn_layers "
                f"{self.full_attn_layers} leave {sorted(missing)} without")

    def mixer(self, layer: int) -> str:
        """'kda' or 'mla' for the 0-indexed layer."""
        return "kda" if layer + 1 in self.kda_layers else "mla"

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def ffn_cfg(self, width: int) -> LlamaConfig:
        """What `MLPBlock` reads of a LlamaConfig, for a SwiGLU of `width`."""
        return LlamaConfig(hidden_size=self.hidden_size,
                           intermediate_size=width, dtype=self.dtype,
                           param_dtype=self.param_dtype)

    # -- parameter counts: held here, multiplied per token, published -----

    @property
    def _kda_params(self) -> int:
        h, w, r = (self.hidden_size, self.kda_heads * self.kda_head_dim,
                   self.kda_lowrank)
        return (4 * h * w + 2 * (h * r + r * w) + h * self.kda_heads
                + 3 * self.kda_conv * w)

    @property
    def _mla_params(self) -> int:
        h, n = self.hidden_size, self.num_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (h * n * qk + h * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * n * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + n * self.v_head_dim * h)

    def _count(self, experts: float) -> int:
        """Matmul weights with `experts` routed experts counted a layer."""
        h = self.hidden_size
        one = 3 * h * self.moe_intermediate_size
        total = 2 * self.vocab_size * h
        for i in range(self.num_layers):
            total += (self._kda_params if self.mixer(i) == "kda"
                      else self._mla_params)
            if self.is_moe(i):
                total += int((experts + self.num_shared_experts) * one
                             + h * self.num_experts)
            else:
                total += 3 * h * self.intermediate_size
        return total

    @property
    def held_params(self) -> int:
        """Weights this chip stores (norms and biases aside)."""
        return self._count(self.experts_held[1])

    @property
    def active_params(self) -> int:
        """Weights a token is multiplied by *here*, in expectation: of its
        `experts_per_token` choices the held share, held / num_experts;
        the embedding row is a gather and counts nothing."""
        share = self.experts_per_token * self.experts_held[1] \
            / self.num_experts
        return self._count(share) - self.vocab_size * self.hidden_size

    @property
    def published_params(self) -> int:
        """The same count with every expert of every layer held."""
        return self._count(self.num_experts)


def kimi_linear_48b() -> KimiLinearConfig:
    return KimiLinearConfig()


def kimi_linear_tiny(vocab: int = 512) -> KimiLinearConfig:
    """Test-size config — the same kinds of layer, toy widths."""
    return KimiLinearConfig(
        vocab_size=vocab, hidden_size=64, num_layers=5,
        kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
        intermediate_size=128, kda_heads=2, kda_head_dim=16, kda_lowrank=8,
        kda_chunk=16, num_heads=2, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
        experts_per_token=4, experts_held=(4, 4), moe_intermediate_size=32,
        remat=False, flash_block_q=64, flash_block_kv=64)


def _dense(cfg: KimiLinearConfig, features, axes, name: str, **kw):
    return nn.DenseGeneral(
        features=features, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), axes), **kw)


def _a_log_init(key, shape, dtype=jnp.float32):
    """log of A ~ U(1, 16) per head, as the gated-delta-rule family draws
    it (assumed: the config does not give it)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of dt ~ logU(1e-3, 1e-1) per channel (assumed, as
    above)."""
    lo, hi = jnp.log(1e-3), jnp.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)),
                     1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class _NormScale(nn.Module):
    """`RMSNorm`'s parameter (`scale`, ones, fp32) without its arithmetic,
    for a norm that a kernel applies."""

    width: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.with_logical_partitioning(
            nn.initializers.ones, ("norm",)), (self.width,), jnp.float32)


class KDAMixer(nn.Module):
    """Six projections of x in `cfg.dtype` (q, k, v; the decay's and the
    output gate's low-rank pairs; beta), `ops/kda.py:kda_mixer`, `o_proj`.
    What lies between the matmuls runs inside the two KDA kernels, fp32 in
    VMEM: the short convolutions of q, k and v with their SiLU, the per-head
    L2 norms of q and k, the decay g = -exp(A_log) softplus(f + dt_bias), the
    recurrence, the per-head RMS norm of o (`o_norm`) and the sigmoid gate. So
    the kernels read q, k, v, f and the gate as the matmuls round them
    (`cfg.dtype`), in place as [B, T, H * d], and write o in the dtype
    `o_proj` multiplies: each crosses HBM once. Those two roundings are the
    only ones; the backward rounds the five cotangents once, on the way out
    of `kda_bwd`. (At a kernel boundary the rounding of the matmuls' results
    is real. While XLA fused them into this fp32 work it kept them unrounded
    on the chip: PERF.md, section 6, PR 31.) beta's sigmoid (one value a head
    a step) stays here."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):  # [B, T, hidden]
        cfg = self.cfg
        heads, dk = cfg.kda_heads, cfg.kda_head_dim
        width = heads * dk

        def head_proj(name):  # the projection, and its convolution's taps
            y = _dense(cfg, width, ("embed", "mlp"), name)(x)
            return y, self.param(
                name.replace("proj", "conv"), nn.with_logical_partitioning(
                    nn.initializers.variance_scaling(
                        1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
                    (None, "mlp")), (cfg.kda_conv, width), cfg.param_dtype)

        (q, q_conv), (k, k_conv), (v, v_conv) = (
            head_proj("q_proj"), head_proj("k_proj"), head_proj("v_proj"))

        def low_rank(name):
            y = _dense(cfg, cfg.kda_lowrank, ("embed", None), name + "_a")(x)
            return _dense(cfg, width, (None, "mlp"), name + "_b")(y)

        a_log = self.param("A_log", nn.with_logical_partitioning(
            _a_log_init, (None,)), (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.with_logical_partitioning(
            _dt_bias_init, ("mlp",)), (width,), jnp.float32)
        f, gate = low_rank("f"), low_rank("g")
        beta = jax.nn.sigmoid(_dense(
            cfg, heads, ("embed", None), "b_proj")(x).astype(jnp.float32))
        with jax.named_scope("kda_scan"):
            o = kda_mixer(
                q, k, v, f, gate, beta,
                convs=(q_conv, k_conv, v_conv), dt_bias=dt_bias, a_log=a_log,
                o_scale=_NormScale(dk, name="o_norm")(),
                l2_eps=cfg.kda_norm_eps, rms_eps=cfg.rms_eps,
                out_dtype=cfg.dtype, chunk=cfg.kda_chunk,
                sub=min(16, cfg.kda_chunk))
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "o_proj")(o)


class MLAMixer(nn.Module):
    """Latent attention with no positions: the keys' 64-wide second part is
    one vector a token, shared by all heads, and neither part is rotated."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, _ = x.shape
        n, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        q = _dense(cfg, (n, dn + dr), ("qkv_embed", "heads", "kv"),
                   "q_proj")(x)
        kva = _dense(cfg, cfg.kv_lora_rank + dr, ("embed", None),
                     "kv_a_proj")(x)
        c = RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_a_norm")(
            kva[..., :cfg.kv_lora_rank])
        k_r = kva[..., cfg.kv_lora_rank:]
        kvb = _dense(cfg, (n, dn + dv), (None, "heads", "kv"),
                     "kv_b_proj")(c)
        k = jnp.concatenate(
            [kvb[..., :dn],
             jnp.broadcast_to(k_r[:, :, None, :], (b, t, n, dr))], axis=-1)
        out = flash_attention(q, k, kvb[..., dn:], True, cfg.flash_block_q,
                              cfg.flash_block_kv)
        return _dense(cfg, cfg.hidden_size, ("heads", "kv", "embed"),
                      "o_proj", axis=(-2, -1))(out)


class KimiLayer(nn.Module):
    cfg: KimiLinearConfig
    layer: int  # 0-indexed

    @nn.compact
    def __call__(self, x):
        """Returns (x, local pair share, load max / mean): the two routing
        counters are 0 on a dense layer."""
        cfg = self.cfg
        kind = cfg.mixer(self.layer)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        with jax.named_scope(kind):
            mix = (KDAMixer if kind == "kda" else MLAMixer)(
                cfg, name=kind)(h)
        x = x + mix
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        zero = jnp.zeros((), jnp.float32)
        if cfg.is_moe(self.layer):
            y, share, load, _ = HeldExpertsBlock(
                hidden_size=cfg.hidden_size,
                expert_width=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts,
                experts_per_token=cfg.experts_per_token,
                experts_held=cfg.experts_held,
                routed_scale=cfg.routed_scaling_factor,
                shared_width=(cfg.num_shared_experts
                              * cfg.moe_intermediate_size),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="moe")(h)
        else:
            y = MLPBlock(cfg.ffn_cfg(cfg.intermediate_size), name="mlp")(h)
            share = load = zero
        x = nn.with_logical_constraint(
            x + y, ("batch", "act_seq", "act_embed"))
        return x, share, load


#: A layer under `jax.checkpoint`: its backward recomputes the layer but for
#: what a KDA layer's `kda_fwd` wrote, o and the chunks' start states (named
#: in ops/kda.py), which are kept from the forward; so that kernel runs once a
#: layer, not twice. An MLA layer names nothing and keeps nothing. 0.67 GB a
#: KDA layer at the cell's 2 x 8,192 tokens.
_RematLayer = nn.remat(KimiLayer, policy=jax.checkpoint_policies
                       .save_only_these_names("kda_o", "kda_starts"))


class KimiLinear(nn.Module):
    """Causal LM. __call__ returns logits [B, S, V], or the post-norm hidden
    states with `return_hidden` (the chunked loss). Sows the step's routing
    counters into the `counters` collection (train/step.py puts them on the
    log rows): `moe_local_pair_share`, the mean over the expert layers of
    pairs routed to held experts over all pairs, and
    `moe_load_max_over_mean`, the largest over the layers of the busiest
    held expert over the mean held expert."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, return_hidden: bool = False):
        cfg = self.cfg
        embed = self.param(
            "embed", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = embed.astype(cfg.dtype)[tokens]
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        layer_cls = _RematLayer if cfg.remat else KimiLayer
        shares, loads = [], []
        for i in range(cfg.num_layers):
            x, share, load = layer_cls(cfg, i, name=f"layer_{i}")(x)
            if cfg.is_moe(i):
                shares.append(share)
                loads.append(load)
        if shares:
            self.sow("counters", "moe_local_pair_share",
                     jnp.mean(jnp.stack(shares)))
            self.sow("counters", "moe_load_max_over_mean",
                     jnp.max(jnp.stack(loads)))
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="final_norm")(x)
        if return_hidden:
            return x
        return _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
