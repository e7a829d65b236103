"""Mixture-of-experts decoder — expert parallelism (EP) as a first-class
strategy (SURVEY.md §2.6: the reference launches DeepSpeed-MoE inside user
containers; here EP is native).

TPU-first design: GShard/Switch-style *capacity-based dense dispatch* —
routing becomes two einsums against one-hot dispatch/combine tensors, which
XLA maps onto the MXU and, when the `expert` mesh axis is sharded, lowers
the dispatch contraction into the expert all-to-all automatically. No
ragged/dynamic shapes anywhere (XLA requirement), tokens over capacity are
dropped (Switch semantics), and a Switch-style load-balancing auxiliary
loss (sown into the `aux_loss` collection, picked up by the train-step
factory) keeps routing uniform so drops stay rare.

Architecture mirrors Mixtral: the Llama trunk with every layer's FFN
replaced by top-k routed SwiGLU experts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.models.llama import Llama, LlamaConfig
from kubeflow_tpu.utils.devices import on_tpu


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2     # top-k routing (Mixtral: 2)
    capacity_factor: float = 1.25  # buffer slack over perfect balance
    router_aux_coef: float = 0.01  # Switch load-balance loss weight
    # Mixtral renormalizes the top-k gate values to sum 1; Qwen2-MoE's
    # default (norm_topk_prob=false) keeps the raw softmax mass.
    norm_topk_prob: bool = True
    # Qwen2-MoE shared expert: an always-on SwiGLU FFN of this width
    # whose output is scaled by a learned sigmoid gate (0 = none).
    shared_expert_size: int = 0

    def _shared_params(self) -> int:
        if not self.shared_expert_size:
            return 0
        return 3 * self.hidden_size * self.shared_expert_size \
            + self.hidden_size  # + the [H, 1] sigmoid gate

    @property
    def num_params(self) -> int:
        h, m, v = self.hidden_size, self.intermediate_size, self.vocab_size
        qkv = (h * self.num_heads * self.head_dim
               + 2 * h * self.num_kv_heads * self.head_dim)
        attn = qkv + self.num_heads * self.head_dim * h
        if self.attention_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        experts = self.num_experts * 3 * h * m
        router = h * self.num_experts
        per_layer = attn + experts + router + 2 * h + self._shared_params()
        emb = v * h * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + emb + h

    @property
    def active_params(self) -> int:
        """Params touched per token (for MFU accounting of sparse models)."""
        h, m, v = self.hidden_size, self.intermediate_size, self.vocab_size
        qkv = (h * self.num_heads * self.head_dim
               + 2 * h * self.num_kv_heads * self.head_dim)
        attn = qkv + self.num_heads * self.head_dim * h
        if self.attention_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        experts = self.experts_per_token * 3 * h * m
        per_layer = (attn + experts + h * self.num_experts + 2 * h
                     + self._shared_params())
        emb = v * h * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + emb + h


def mixtral_8x7b() -> MoEConfig:
    return MoEConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=8192, rope_theta=1e6, num_experts=8,
        experts_per_token=2)


def moe_tiny(vocab: int = 512) -> MoEConfig:
    """Test-size config — same routing topology, toy dims."""
    return MoEConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=128, remat=False, num_experts=4, experts_per_token=2,
        flash_block_q=64, flash_block_kv=64)


def expert_capacity(cfg: MoEConfig, seq_len: int) -> int:
    """Per-(batch-row) expert buffer: perfect balance needs K*S/E slots;
    capacity_factor adds slack before tokens drop."""
    return max(1, int(math.ceil(
        seq_len * cfg.experts_per_token / cfg.num_experts
        * cfg.capacity_factor)))


def gshard_route(x: jax.Array, w_router: jax.Array, K: int, C: int,
                 renormalize: bool = True):
    """GShard/Switch capacity routing, pure jnp — shared by the flax
    MoEBlock and the pipeline stage body (models/llama_pp.py MoE-PP), so
    the two paths cannot drift.

    x [B, S, H] (any dtype; router runs fp32), w_router [H, E] fp32.
    Returns (dispatch [B,S,E,C], combine [B,S,E,C], aux scalar) where aux
    is the UNWEIGHTED Switch load-balance term E * Σ_e frac_e · mean_prob_e
    (caller applies router_aux_coef). `renormalize` scales the top-k gate
    values to sum 1 (Mixtral); Qwen2-MoE's norm_topk_prob=false keeps the
    raw softmax mass."""
    E = w_router.shape[-1]
    logits = jnp.einsum("bsh,he->bse", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)            # [B,S,E]
    gate_vals, expert_idx = jax.lax.top_k(probs, K)    # [B,S,K]
    if renormalize:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    B, S = x.shape[0], x.shape[1]
    # Capacity assignment, slot-major (GShard): slot-0 choices claim
    # buffer positions first, then slot-1, each in sequence order.
    dispatch = jnp.zeros((B, S, E, C), jnp.float32)
    combine = jnp.zeros((B, S, E, C), jnp.float32)
    count = jnp.zeros((B, 1, E), jnp.float32)  # claimed so far
    for k in range(K):
        mask_e = jax.nn.one_hot(expert_idx[:, :, k], E)       # [B,S,E]
        pos = jnp.cumsum(mask_e, axis=1) - mask_e + count     # [B,S,E]
        count = count + jnp.sum(mask_e, axis=1, keepdims=True)
        keep = mask_e * (pos < C)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), C) * keep[..., None]
        dispatch = dispatch + slot                            # [B,S,E,C]
        combine = combine + gate_vals[:, :, k, None, None] * slot

    # Switch aux loss: E * Σ_e (token fraction to e) · (mean prob of e).
    frac = jnp.mean(jax.nn.one_hot(expert_idx[:, :, 0], E), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def shared_expert_ffn(x, w_gate, w_up, w_down, gate_w, dtype):
    """Qwen2-MoE's always-on shared expert, pure jnp — one definition
    shared by the flax MoEBlock and the pipeline stage body
    (models/llama_pp.py _moe_ffn) so the two paths cannot drift (same
    contract as gshard_route): dense SwiGLU scaled by a learned
    per-token sigmoid gate (fp32 sigmoid). x [.., H]; w_gate/w_up
    [H, Ms]; w_down [Ms, H]; gate_w [H, 1]."""
    xd = x.astype(dtype)
    sh = (jax.nn.silu(xd @ w_gate.astype(dtype))
          * (xd @ w_up.astype(dtype))) @ w_down.astype(dtype)
    gate = jax.nn.sigmoid((xd @ gate_w.astype(dtype)).astype(jnp.float32))
    return sh * gate.astype(dtype)


class MoEBlock(nn.Module):
    """Top-k routed SwiGLU experts with capacity-based dispatch."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array, adapter=None,
                 adapter_ids=None) -> jax.Array:  # [B, S, H]
        cfg = self.cfg
        if adapter is not None:
            raise ValueError(
                "multi-LoRA adapters don't apply to routed-expert FFNs "
                "(use attention-only adapters with MoE models)")
        B, S, H = x.shape
        E, K = cfg.num_experts, cfg.experts_per_token
        C = expert_capacity(cfg, S)

        # Router in fp32 (small matmul; numerics matter more than MXU).
        w_router = self.param(
            "router", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)),
            (H, E), jnp.float32)
        dispatch, combine, aux = gshard_route(
            x, w_router, K, C, renormalize=cfg.norm_topk_prob)
        self.sow("aux_loss", "router", cfg.router_aux_coef * aux)

        # Dispatch → per-expert batches [E,B,C,H]; with `expert` sharded
        # this contraction IS the all-to-all (GSPMD inserts it).
        xin = jnp.einsum("bsec,bsh->ebch", dispatch.astype(cfg.dtype),
                         x.astype(cfg.dtype))
        xin = nn.with_logical_constraint(
            xin, ("expert", "batch", None, None))

        dense_init = nn.initializers.lecun_normal()
        w_gate = self.param(
            "w_gate", nn.with_logical_partitioning(
                dense_init, ("expert", "embed", "expert_mlp")),
            (E, H, cfg.intermediate_size), cfg.param_dtype)
        w_up = self.param(
            "w_up", nn.with_logical_partitioning(
                dense_init, ("expert", "embed", "expert_mlp")),
            (E, H, cfg.intermediate_size), cfg.param_dtype)
        w_down = self.param(
            "w_down", nn.with_logical_partitioning(
                dense_init, ("expert", "expert_mlp", "embed")),
            (E, cfg.intermediate_size, H), cfg.param_dtype)

        g = jnp.einsum("ebch,ehm->ebcm", xin, w_gate.astype(cfg.dtype))
        u = jnp.einsum("ebch,ehm->ebcm", xin, w_up.astype(cfg.dtype))
        h = nn.silu(g) * u
        h = nn.with_logical_constraint(
            h, ("expert", "batch", None, "expert_mlp"))
        out = jnp.einsum("ebcm,emh->ebch", h, w_down.astype(cfg.dtype))

        # Combine back to token order (the return all-to-all).
        y = jnp.einsum("bsec,ebch->bsh", combine.astype(cfg.dtype), out)

        if cfg.shared_expert_size:
            # Qwen2-MoE shared expert: an always-on dense SwiGLU whose
            # output is scaled by a learned per-token sigmoid gate —
            # replicated over `expert` (every rank computes it; it's the
            # dense fraction of the FLOPs), sharded like a dense MLP.
            ms = cfg.shared_expert_size
            ws_gate = self.param(
                "w_shared_gate", nn.with_logical_partitioning(
                    dense_init, ("embed", "mlp")), (H, ms), cfg.param_dtype)
            ws_up = self.param(
                "w_shared_up", nn.with_logical_partitioning(
                    dense_init, ("embed", "mlp")), (H, ms), cfg.param_dtype)
            ws_down = self.param(
                "w_shared_down", nn.with_logical_partitioning(
                    dense_init, ("mlp", "embed")), (ms, H), cfg.param_dtype)
            w_sgate = self.param(
                "shared_gate", nn.with_logical_partitioning(
                    dense_init, ("embed", None)), (H, 1), cfg.param_dtype)
            y = y + shared_expert_ffn(x, ws_gate, ws_up, ws_down, w_sgate,
                                      cfg.dtype)
        return y.astype(cfg.dtype)


@jax.custom_vjp
def permute_rows(x: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """x[perm] for a permutation `perm` of the rows with inverse `inv`. Its
    transpose is the gather g[inv], stated here because autodiff would
    write it as a scatter-add, which a TPU serialises."""
    return x[perm]


def _permute_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


def sigmoid_topk_route(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                       k: int, scale: float):
    """DeepSeek-V3-style routing over all experts, fp32 at full precision
    (a top-k over 256 near-equal scores flips on a bf16 rounding): scores
    s = sigmoid(x W_r); the k experts with the largest s + bias (the
    score-correction bias steers the choice only and takes no gradient);
    weights scale * s_i / sum of the chosen s. x [N, H]. Returns
    (expert ids [N, k] int32, weights [N, k] fp32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scale * gates / jnp.sum(gates, axis=-1, keepdims=True)


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down, *, start: int,
                     num_experts: int, dtype, interpret: bool | None = None):
    """The part of a routed-expert layer that the experts held here give:
    sum over the chosen experts e in [start, start + held) of weight_e *
    SwiGLU_e(x). Dropless and without capacity: the token-expert pairs are
    sorted by expert and the three products run as grouped matmuls over the
    held slice only (`megablox.gmm` with `group_offset`: tiles of absent
    experts are neither read nor computed, their rows come back zero). On
    an `expert` mesh axis this is one shard's work, its result summed over
    the axis by the caller; on one chip it is the whole layer's routed part
    as far as this chip can know it.

    x [N, H]; idx, weights [N, K] from the router over all `num_experts`;
    w_gate, w_up [held, H, M], w_down [held, M, H]. Returns (y [N, H],
    pairs routed here [scalar], tokens of each held expert [held])."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    if interpret is None:
        interpret = not on_tpu()
    n, hidden = x.shape
    k = idx.shape[1]
    held = w_gate.shape[0]
    pairs = n * k
    flat = idx.reshape(pairs)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32))
    sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    tm = min(128, pairs)
    pad = -pairs % tm
    xs = permute_rows(jnp.repeat(x.astype(dtype), k, axis=0), order, inv)
    if pad:  # rows past every group: never read, returned as zeros
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    offset = jnp.asarray(start, jnp.int32)

    def grouped(lhs, rhs):
        tiling = (tm, min(1024, lhs.shape[1]), min(1024, rhs.shape[2]))
        return megablox.gmm(lhs, rhs.astype(dtype), sizes, dtype, tiling,
                            offset, None, False, interpret)

    h = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
    out = grouped(h, w_down)[:pairs]
    out = permute_rows(out, inv, order).reshape(n, k, hidden)
    local = jnp.logical_and(idx >= start, idx < start + held)
    w_local = jnp.where(local, weights, 0.0).astype(dtype)
    y = jnp.einsum("nk,nkh->nh", w_local, out)
    return y, jnp.sum(local), jax.lax.dynamic_slice(sizes, (start,), (held,))


#: Tokens the routed part of `HeldExpertsBlock` handles at a time.
_ROUTED_BLOCK_TOKENS = 4096


class HeldExpertsBlock(nn.Module):
    """A routed-expert FFN that is told which experts it holds: the router
    keeps its published width (`num_experts`) and top-k, the parameters are
    those of experts [start, start + held) only, and the result is the
    chosen *held* experts' part plus the shared expert. What the absent
    experts would add is left out (models/kimi_linear.py says why).

    Sows nothing; returns (y, pairs routed here / all pairs, busiest held
    expert / mean held expert, held experts that got a pair) for the trunk
    to report."""

    hidden_size: int
    expert_width: int
    num_experts: int
    experts_per_token: int
    experts_held: tuple  # (start, count)
    routed_scale: float
    shared_width: int = 0  # the always-on dense SwiGLU beside them; 0: none
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: The score-correction bias at a fresh start. Zeros is where balancing
    #: starts from in training; a model that is served from a seed gives it
    #: a spread, so that a check against a reference is not blind to it.
    bias_init: Any = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x: jax.Array):
        b, s, hidden = x.shape
        start, held = self.experts_held
        if not 0 <= start <= start + held <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"0..{self.num_experts}")
        init = nn.initializers.lecun_normal()
        w_router = self.param(
            "router", nn.with_logical_partitioning(init, ("embed", None)),
            (hidden, self.num_experts), jnp.float32)
        bias = self.param(
            "e_score_correction_bias", nn.with_logical_partitioning(
                self.bias_init, (None,)),
            (self.num_experts,), jnp.float32)

        def expert_w(name, shape, axes):
            # lecun_normal over [in, out]; the leading dim is a batch of
            # independent experts.
            return self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.variance_scaling(
                        1.0, "fan_in", "truncated_normal", in_axis=-2,
                        out_axis=-1, batch_axis=(0,)), axes),
                shape, self.param_dtype)

        m = self.expert_width
        w_gate = expert_w("w_gate", (held, hidden, m),
                          ("expert", "embed", "expert_mlp"))
        w_up = expert_w("w_up", (held, hidden, m),
                        ("expert", "embed", "expert_mlp"))
        w_down = expert_w("w_down", (held, m, hidden),
                          ("expert", "expert_mlp", "embed"))
        # The sorted token-expert pairs are K rows a token, nearly all of
        # them other chips' and empty here, so the routed part walks the
        # tokens in blocks (recomputed in the backward) and its buffers
        # stay a block's size. Routing is per token: blocks change nothing.
        n = b * s
        blocks = max(1, n // _ROUTED_BLOCK_TOKENS)
        while n % blocks:
            blocks -= 1

        def routed(xb):
            with jax.named_scope("moe_route"):
                idx, weights = sigmoid_topk_route(
                    xb, w_router, bias, self.experts_per_token,
                    self.routed_scale)
            with jax.named_scope("moe_experts"):
                return held_experts_ffn(
                    xb, idx, weights, w_gate, w_up, w_down, start=start,
                    num_experts=self.num_experts, dtype=self.dtype)

        y, here, load = jax.lax.map(
            jax.checkpoint(routed), x.reshape(blocks, n // blocks, hidden))
        here, load = jnp.sum(here), jnp.sum(load, axis=0)
        y = y.reshape(b, s, hidden)
        if self.shared_width:
            from kubeflow_tpu.models.llama import MLPBlock

            with jax.named_scope("moe_shared"):
                y = y + MLPBlock(LlamaConfig(
                    hidden_size=hidden, intermediate_size=self.shared_width,
                    dtype=self.dtype, param_dtype=self.param_dtype),
                    name="shared_expert")(x)
        touched = jnp.sum(load > 0).astype(jnp.int32)
        load = load.astype(jnp.float32)
        return (y.astype(self.dtype),
                here.astype(jnp.float32) / (b * s * self.experts_per_token),
                jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9), touched)


def MoELlama(cfg: MoEConfig, **kwargs: Any) -> Llama:
    """Mixtral-family causal LM: Llama trunk + routed-expert FFNs."""
    return Llama(cfg, mlp_cls=MoEBlock, **kwargs)
