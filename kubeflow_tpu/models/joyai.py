"""JoyAI-LLM-Flash: a DeepSeek-V3-shaped decoder (`model_type:
joyai_llm_flash`, 48B-A2.7B) whose attention caches one latent row a token
and whose FFNs, after a leading dense layer, are 256-way sigmoid top-8 routed
experts with one shared expert. After the published `config.json` of
jdopensource/JoyAI-LLM-Flash and DeepSeek-V3's report (arXiv:2412.19437,
sections 2.1 and 2.2), whose equations every key of that file names.

Pre-norm residual blocks, `x += Mix(RMSNorm(x)); x += FFN(RMSNorm(x))`, a
final RMSNorm and an untied head. Per layer (H hidden, n heads):

  c_q = RMSNorm(x W_qa); q = c_q W_qb -> n x (nope ‖ rope)
  [c ‖ k_r] = x W_kva; c <- RMSNorm(c); [k_nope ‖ v] = c W_kvb -> n x (nope ‖ v)
  rotary (interleaved pairs (2i, 2i + 1), theta, no scaling) on q's rope part
  and on k_r, one vector a token shared by all heads
  score_h(t, s) = (q_nope,h(t).k_nope,h(s) + q_rope,h(t).k_r(s)) (nope + rope)^-1/2
  causal softmax; o = concat_h(sum p v_h) W_o

  layer < first_k_dense_replace: a SwiGLU of `intermediate_size`; after it
  `models/moe.py:HeldExpertsBlock` (sigmoid scores, the 8 largest s + b, gates
  scale * s_i / sum of the chosen s, dropless, + the shared expert).

What a request of n tokens holds (serving): n rows of `[c after its norm ‖ k_r
after its rotation]` a layer, nothing a head (`LatentState`: one kind of block
in the paged pool, taken as the request's rows are dispatched). The multi-token
prediction module of the release is not part of the served model (DeepSeek-V3
section 2.2: discarded at inference).

With `cache` the forward is one of two programs of the same attention: a piece
of a prompt (S > 1: the cache is a one-request fragment, `cache_index` the
piece's first position, `attend_full_cache` says rows came before it) runs the
*unabsorbed* form through the flash kernel, keys of nope + rope and values of v
expanded from the latent rows (the piece causally, the rows before it a piece's
length at a time, joined by the kernel's row log-sum-exp); one decode step a
row (S == 1: the cache is the pool with the rows' block tables beside it, read
and written in place) runs the *absorbed* form, `ops/mla.py`. Without a cache,
the whole sequence from position 0 (the piece's program, nothing before it).

The trunk is unrolled (the layers' trees differ). Activations, the residual
stream and matmul operands are `cfg.dtype` (bf16) with fp32 accumulation;
norms, rotary, scores and the softmax's statistics, the router, its gates and
the logits are fp32. Under `mutable=["counters"]` the forward sows
`moe_experts_touched` (distinct held experts routed to, summed over the
expert layers).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.models.llama import LlamaConfig, MLPBlock, RMSNorm
from kubeflow_tpu.models.moe import HeldExpertsBlock
from kubeflow_tpu.ops import mla
from kubeflow_tpu.ops.flash_attention import flash_attention_lse

#: Spread of the score-correction bias b at a fresh start (assumed: the
#: release's values come with its weights). The chosen experts' scores lie
#: about 0.006 apart, so this decides many choices without making the load
#: uneven.
ROUTER_BIAS_STD = 0.02
#: Spread of the embedding at a fresh start (assumed likewise). At the zoo's
#: usual 0.02 the stream after layer 0 is one direction common to every token
#: (what near-uniform attention adds), the router sees almost the same vector
#: for all of them and 16 rows touch 30-34% of the experts, another share at
#: every seed; a trained router is balanced (what the bias is trained for),
#: and with a unit embedding the seeded one is: 38.2-38.4%, where a fair one
#: touches 39.4%.
EMBED_STD = 1.0


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_layers: int = 40
    first_k_dense_replace: int = 1
    intermediate_size: int = 7168        # the dense layers' SwiGLU
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    num_experts: int = 256
    experts_per_token: int = 8
    experts_held: tuple = (0, 256)       # (first, count) held on this chip
    moe_intermediate_size: int = 768
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    flash_block: int = 512
    #: Blocks of the paged pool that the decode core copies at a time.
    step_group: int = 16

    def __post_init__(self):
        # JSON specs hand lists over; the dataclass must stay hashable.
        object.__setattr__(self, "experts_held", tuple(self.experts_held))

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def ffn_cfg(self, width: int) -> LlamaConfig:
        """What `MLPBlock` reads of a LlamaConfig, for a SwiGLU of `width`."""
        return LlamaConfig(hidden_size=self.hidden_size,
                           intermediate_size=width, dtype=self.dtype,
                           param_dtype=self.param_dtype)

    @property
    def row_width(self) -> int:
        """Values a cached row takes in the pool (ops/mla.py)."""
        return mla.row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    # -- parameter counts: held here, multiplied per token ------------------

    @property
    def mla_params(self) -> int:
        h, n = self.hidden_size, self.num_heads
        return (h * self.q_lora_rank
                + self.q_lora_rank * n * (self.qk_nope_head_dim
                                          + self.qk_rope_head_dim)
                + h * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * n * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + n * self.v_head_dim * h)

    def _count(self, experts: float) -> int:
        """Matmul weights with `experts` routed experts counted a layer."""
        h = self.hidden_size
        one = 3 * h * self.moe_intermediate_size
        total = 2 * self.vocab_size * h
        for i in range(self.num_layers):
            total += self.mla_params
            if self.is_moe(i):
                total += int((experts + self.num_shared_experts) * one
                             + h * self.num_experts)
            else:
                total += 3 * h * self.intermediate_size
        return total

    @property
    def held_params(self) -> int:
        """Weights this chip stores (norms and the bias aside)."""
        return self._count(self.experts_held[1])

    @property
    def active_params(self) -> int:
        """Weights a token is multiplied by here, in expectation; the
        embedding row is a gather and counts nothing."""
        share = self.experts_per_token * self.experts_held[1] \
            / self.num_experts
        return self._count(share) - self.vocab_size * self.hidden_size

    def serving_state(self, block_size: int, max_len: int) -> "LatentState":
        return LatentState(self, block_size, max_len)


def joyai_llm_flash() -> JoyAIConfig:
    return JoyAIConfig()


def joyai_tiny(vocab: int = 512) -> JoyAIConfig:
    """Test size: the same kinds of layer, toy widths."""
    return JoyAIConfig(
        vocab_size=vocab, hidden_size=64, num_layers=3, intermediate_size=128,
        num_heads=2, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
        experts_per_token=4, experts_held=(0, 16), moe_intermediate_size=32,
        max_seq_len=512, flash_block=32, step_group=2)


class LatentState:
    """What the serving engine asks of this model's decode state (serve/
    paging.py `serving_state`): one kind of block, `block_size` rows of
    `[c ‖ k_r]` a layer, shared by all heads. A request takes its blocks as
    its rows are dispatched and holds them until it retires; the decode step
    reads and writes the pool in place through the row's table."""

    kinds = ("latent",)
    #: Blocks are taken as rows are dispatched (none comes back before the
    #: request retires), and the pool is read in place, not through a copy.
    grows = True
    #: Counters the engine keeps for this state, live in its `stats`.
    #: `latent_rows`: at each decode dispatch the rows its first step reads,
    #: summed over its rows as `decode_context_tokens` is. The other comes
    #: from the device with the dispatch's tokens (the forward's `counters`
    #: collection), of the dispatch's first step.
    counters = ("latent_rows", "moe_experts_touched")

    def __init__(self, cfg: JoyAIConfig, block_size: int, max_len: int):
        if block_size < 1:
            raise ValueError(
                f"{type(self).__name__} lives in the paged pool: set "
                "kv_block_size > 0")
        self.cfg, self.bs = cfg, int(block_size)
        #: Table width of the compiled programs.
        self.widths = (-(-int(max_len) // self.bs),)

    def check(self, prefill_buckets: list) -> None:
        """Refuse an engine whose prompt pieces this state cannot take: a
        later piece walks the rows before it a piece's length at a time."""
        bad = [b for b in prefill_buckets if prefill_buckets[-1] % b]
        if bad:
            raise ValueError(
                f"prefill_buckets {prefill_buckets}: every bucket must "
                f"divide the largest; {bad} do not")

    def read(self, written: list) -> dict:
        """Counted at a decode dispatch over rows that have `written` rows
        each: what the chunk's first step reads (its own row among them)."""
        return {"latent_rows": sum(n + 1 for n in written)}

    def released(self, gone: tuple) -> dict:
        return {}

    def held(self, n: int) -> tuple[int]:
        return (-(-max(int(n), 0) // self.bs),)

    def peak(self, n: int) -> int:
        return self.held(n)[0]

    def pool(self, n_blocks: int, kv_quant: str = "none") -> dict:
        """The paged pool, block 0 the reserved NULL block."""
        cfg = self.cfg
        return {"c": jnp.zeros((cfg.num_layers, n_blocks + 1, self.bs,
                                cfg.row_width), cfg.dtype)}

    def fragment(self, length: int) -> dict:
        """One request's rows as prefill builds them, contiguous."""
        cfg = self.cfg
        return {"c": jnp.zeros((cfg.num_layers, 1, length, cfg.row_width),
                               cfg.dtype)}

    def insert(self, pool: dict, frag: dict, tables: dict) -> dict:
        """Scatter a fragment's rows into a request's blocks:
        `tables["latent"]` [widths[0]], entries the request does not hold
        (yet) at the NULL block."""
        rows = frag["c"][:, 0, :self.widths[0] * self.bs]
        rows = rows.reshape(rows.shape[0], -1, self.bs, rows.shape[-1])
        return {"c": pool["c"].at[:, tables["latent"]].set(rows)}


def rotate_pairs(x: jax.Array, positions: jax.Array,
                 theta: float) -> jax.Array:
    """Rotary over interleaved pairs (2i, 2i + 1) of the last axis, fp32.
    x [B, S, ..., d]; positions [B, S] absolute."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv       # [B, S, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _dense(cfg: JoyAIConfig, features, axes, name: str, **kw):
    return nn.DenseGeneral(
        features=features, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), axes), **kw)


def _join(o_a, lse_a, o_b, lse_b):
    """Two softmaxes over disjoint keys as one: outputs [B, S, H, D] fp32
    with their row log-sum-exps [B, S, H, 1]."""
    m = jnp.maximum(lse_a, lse_b)
    w_a, w_b = jnp.exp(lse_a - m), jnp.exp(lse_b - m)
    return (w_a * o_a + w_b * o_b) / (w_a + w_b), m + jnp.log(w_a + w_b)


class LatentAttention(nn.Module):
    cfg: JoyAIConfig
    layer: int  # 0-indexed: this layer's plane of the cache

    @nn.compact
    def __call__(self, x, positions, cache, cache_index, after: bool):
        cfg = self.cfg
        n, dn, dr, dv, rank = (cfg.num_heads, cfg.qk_nope_head_dim,
                               cfg.qk_rope_head_dim, cfg.v_head_dim,
                               cfg.kv_lora_rank)
        c_q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_a_norm")(
            _dense(cfg, cfg.q_lora_rank, ("embed", None), "q_a_proj")(x))
        q = _dense(cfg, (n, dn + dr), (None, "heads", "kv"), "q_b_proj")(c_q)
        kva = _dense(cfg, rank + dr, ("embed", None), "kv_a_proj")(x)
        c = RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_a_norm")(
            kva[..., :rank])
        k_r = rotate_pairs(kva[..., rank:], positions, cfg.rope_theta)
        q = jnp.concatenate(
            [q[..., :dn], rotate_pairs(q[..., dn:], positions,
                                       cfg.rope_theta)], axis=-1)
        # One up-projection, used whole (keys and values of the unabsorbed
        # form) or by its halves (around the absorbed core).
        w_kvb = self.param(
            "kv_b_proj", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
                (None, "heads", "kv")),
            (rank, n, dn + dv), cfg.param_dtype).astype(cfg.dtype)
        pad = cfg.row_width - rank - dr
        row = jnp.pad(jnp.concatenate([c, k_r], axis=-1),
                      ((0, 0), (0, 0), (0, pad)))

        def expand(rows):
            """Per-head keys and values of latent rows [B, T, >= rank + dr]."""
            kvb = jnp.einsum("btc,chd->bthd", rows[..., :rank], w_kvb,
                             preferred_element_type=jnp.float32
                             ).astype(cfg.dtype)
            shared = jnp.broadcast_to(
                rows[:, :, None, rank:rank + dr], (*kvb.shape[:3], dr))
            return (jnp.concatenate([kvb[..., :dn], shared], axis=-1),
                    kvb[..., dn:])

        if cache is not None and x.shape[1] == 1:
            o, cache = self._step(q[:, 0], row[:, 0], w_kvb, cache,
                                  cache_index)
            o = o[:, None]
        else:
            o, cache = self._piece(q, row, expand, cache, cache_index, after)
        y = _dense(cfg, cfg.hidden_size, ("heads", "kv", "embed"), "o_proj",
                   axis=(-2, -1))(o)
        return y, cache

    def _piece(self, q, row, expand, frag, index, after: bool):
        """A piece of one request's prompt that starts at row `index`
        (or a whole batch from row 0, `frag` None): unabsorbed, through the
        flash kernel; the rows before the piece come off the fragment a
        piece's length at a time."""
        cfg = self.cfg
        s = q.shape[1]
        blk = min(cfg.flash_block, s)
        with jax.named_scope("mla_core_prefill"):
            k, v = expand(row)
            o, lse = flash_attention_lse(q, k, v, True, blk, blk)
        if frag is None:
            return o, None
        if q.shape[0] != 1:
            raise ValueError("a fragment is one request's state")
        rows = jax.lax.dynamic_update_slice(
            frag["c"][self.layer], row.astype(frag["c"].dtype),
            (0, index[0], 0))
        if after:
            def before(j, carry):
                kj, vj = expand(jax.lax.dynamic_slice_in_dim(
                    rows, j * s, s, axis=1))
                oj, lsej = flash_attention_lse(q, kj, vj, False, blk, blk)
                return _join(*carry, oj.astype(jnp.float32), lsej)

            with jax.named_scope("mla_core_prefill"):
                o, _ = jax.lax.fori_loop(
                    0, index[0] // s, before, (o.astype(jnp.float32), lse))
                o = o.astype(cfg.dtype)
        return o, {"c": frag["c"].at[self.layer].set(rows)}

    def _step(self, q, row, w_kvb, cache, index):
        """One decode step a row at absolute position `index` [B], through
        the pool `cache["c"]` [L, N, bs, W] and the rows' tables
        `cache["latent"]` [B, nb]: the new row is written into its block,
        then the absorbed core reads the row's blocks where they lie."""
        cfg = self.cfg
        dn, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        pool, tables = cache["c"], cache["latent"]
        bs = pool.shape[2]
        base = self.layer * pool.shape[1]
        flat = pool.reshape(-1, *pool.shape[2:])
        block = base + jnp.take_along_axis(
            tables, (index // bs)[:, None], axis=1)[:, 0]
        flat = flat.at[block, index % bs].set(row.astype(flat.dtype))
        q_abs = jnp.einsum("bhd,chd->bhc", q[..., :dn], w_kvb[..., :dn],
                           preferred_element_type=jnp.float32
                           ).astype(cfg.dtype)
        q_row = jnp.pad(jnp.concatenate([q_abs, q[..., dn:]], axis=-1),
                        ((0, 0), (0, 0), (0, row.shape[-1] - rank
                                          - cfg.qk_rope_head_dim)))
        u = mla.absorbed_step(
            q_row, flat, base + tables, index + 1, rank=rank,
            scale=q.shape[-1] ** -0.5, group=cfg.step_group)
        o = jnp.einsum("bhc,chd->bhd", u, w_kvb[..., dn:],
                       preferred_element_type=jnp.float32).astype(cfg.dtype)
        return o, {**cache, "c": flat.reshape(pool.shape)}


class JoyAILayer(nn.Module):
    cfg: JoyAIConfig
    layer: int  # 0-indexed

    @nn.compact
    def __call__(self, x, positions, cache, cache_index, after: bool):
        """Returns (x, cache, pairs routed to held experts, distinct held
        experts routed to): the two counters are 0 on a dense layer."""
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        with jax.named_scope("mla"):
            mix, cache = LatentAttention(cfg, self.layer, name="mla")(
                h, positions, cache, cache_index, after)
        x = x + mix
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        touched = jnp.zeros((), jnp.int32)
        if cfg.is_moe(self.layer):
            with jax.named_scope("moe"):
                y, _, _, touched = HeldExpertsBlock(
                    hidden_size=cfg.hidden_size,
                    expert_width=cfg.moe_intermediate_size,
                    num_experts=cfg.num_experts,
                    experts_per_token=cfg.experts_per_token,
                    experts_held=cfg.experts_held,
                    routed_scale=cfg.routed_scaling_factor,
                    shared_width=(cfg.num_shared_experts
                                  * cfg.moe_intermediate_size),
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    bias_init=nn.initializers.normal(ROUTER_BIAS_STD),
                    name="moe")(h)
        else:
            y = MLPBlock(cfg.ffn_cfg(cfg.intermediate_size), name="mlp")(h)
        return x + y, cache, touched


class JoyAI(nn.Module):
    """Causal LM. See the module's text for the cached forms. Returns logits
    [B, S, V] (fp32), or the post-norm hidden states with `return_hidden`;
    with a cache, `(that, cache)`."""

    cfg: JoyAIConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, positions: jax.Array | None = None,
                 cache: dict | None = None,
                 cache_index: jax.Array | None = None,
                 return_hidden: bool = False,
                 attend_full_cache: bool = False):
        cfg = self.cfg
        b, s = tokens.shape
        if cache is not None and cache_index is None:
            cache_index = jnp.zeros((b,), jnp.int32)
        if positions is None:
            start = 0 if cache is None else cache_index[:, None]
            positions = start + jnp.broadcast_to(jnp.arange(s), (b, s))
        embed = self.param(
            "embed", nn.with_logical_partitioning(
                nn.initializers.normal(EMBED_STD), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = embed.astype(cfg.dtype)[tokens]
        touched = jnp.zeros((), jnp.int32)
        # The program's form, on every operation's path: a decode step's
        # expert work apart from a piece's.
        form = ("full" if cache is None else
                "step" if s == 1 else "piece")
        with jax.named_scope(form):
            for i in range(cfg.num_layers):
                x, cache, t = JoyAILayer(cfg, i, name=f"layer_{i}")(
                    x, positions, cache, cache_index, attend_full_cache)
                touched = touched + t
        self.sow("counters", "moe_experts_touched", touched)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="final_norm")(x)
        if not return_hidden:
            head = self.param(
                "lm_head", nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")),
                (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
            x = jnp.einsum("bsh,hv->bsv", x, head.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        return (x, cache) if cache is not None else x
