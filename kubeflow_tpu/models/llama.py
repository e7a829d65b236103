"""Llama-class decoder-only transformer, TPU-first.

This is the flagship training model (north star: Llama-3-8B fine-tune via
JAXJob; BASELINE.md). The reference platform never owned a model — PyTorchJob
launched user containers holding HF/Megatron code (SURVEY.md §2.6). Here the
model is part of the framework, designed for XLA/TPU:

  * params annotated with logical axes (parallel/sharding.py rules engine)
    so DP/FSDP/TP/SP compose via GSPMD instead of NCCL process groups;
  * layers rolled into one `nn.scan` — O(1) HLO size in depth, fast compiles;
  * bfloat16 activations/matmuls (MXU-native), fp32 RMSNorm/softmax/rope;
  * selectable attention impl: naive einsum, Pallas flash kernel, or ring
    attention over the `seq` mesh axis for long context (SURVEY.md §5.7);
  * `jax.checkpoint` (remat) policy per block to trade FLOPs for HBM.

GQA, RoPE, SwiGLU, RMSNorm match the Llama-3 architecture family.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.parallel.sharding import logical_to_spec
from kubeflow_tpu.utils.devices import on_tpu


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # Llama-3.1-style rope scaling (the "llama3" rope_type): a one-time
    # remap of the inverse frequencies. factor == 1.0 disables it. Scalars
    # (not a dict) so the config stays hashable for jit-static use.
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_len: int = 8192
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # QKV projection biases (Qwen2-family checkpoints; o_proj stays
    # bias-free, matching HF).
    attention_bias: bool = False
    # Gemma-family conventions (models/hf_import.py import_gemma): RMSNorm
    # applies (1 + w); token embeddings scale by sqrt(hidden) at input;
    # the MLP gate activation is tanh-approximate GeLU instead of SiLU.
    norm_plus_one: bool = False
    embed_scale: bool = False
    mlp_act: str = "silu"  # silu | gelu_tanh
    # Gemma-2 conventions (import_gemma2): sandwich norms add a norm on
    # the attention/MLP OUTPUTS before the residual add (HF
    # post_attention_layernorm / post_feedforward_layernorm; our
    # post_attn_norm then plays HF's pre_feedforward_layernorm role);
    # attention scores and final logits pass through tanh soft-caps; the
    # score scale is query_pre_attn_scalar^-0.5 instead of head_dim^-0.5.
    sandwich_norms: bool = False
    attn_softcap: float = 0.0    # 0 = off
    final_softcap: float = 0.0   # 0 = off
    query_pre_attn_scalar: float = 0.0  # 0 = use head_dim
    # Which layers the sliding_window mask applies to: "all" (Mistral),
    # "even" (Gemma-2: layers 0,2,4,... sliding), or "5to1" (Gemma-3:
    # every 6th layer full, the rest sliding — HF layer_types). Non-"all"
    # patterns thread a per-layer traced flag through the scanned trunk,
    # so they run on the einsum attention path only.
    sliding_pattern: str = "all"
    # Gemma-3 conventions (import_gemma3): RMSNorm ((1+w), fp32) on the
    # projected q/k heads before RoPE; TWO rope bases — sliding layers
    # use rope_theta_local (0 = single-table models), full layers use
    # rope_theta with an optional LINEAR position scaling.
    qk_norm: bool = False
    rope_theta_local: float = 0.0
    rope_global_scaling_factor: float = 1.0
    # LoRA fine-tuning (the reference SDK's PEFT LoraConfig): rank 0 = off.
    # Adapters add (x @ A) @ B * alpha/rank to the target projections —
    # q/v (PEFT's Llama default) for "attn", plus gate/up/down for
    # "attn_mlp". B starts at zero, so step 0 equals the base model; the
    # train step freezes everything but *_lora_* leaves (train/lora.py).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: str = "attn"  # attn | attn_mlp
    # auto | naive | flash | ring | ring_flash | zigzag | zigzag_flash
    # (*_flash = fused Pallas inner block per ring step)
    attention_impl: str = "auto"
    remat: bool = True
    # Which residuals the remat'd backward may keep: "nothing" (recompute
    # the whole block — minimum memory, ~2 extra fwd FLOP-shares), "dots"
    # (save matmul outputs — recompute only elementwise, costs activation
    # memory), "dots_no_batch" (save only weight-stationary dots),
    # "save_attn" (keep attention outputs so bwd skips re-running the
    # attention kernel — wins only on HBM-rich parts; PROFILE.md §4).
    remat_policy: str = "nothing"
    scan_layers: bool = True
    # flash-kernel block sizes (tuned for v5e/v5p VMEM; ops/flash_attention.py)
    flash_block_q: int = 512
    flash_block_kv: int = 512
    # Block-sparse attention mask family (ops/flash_attention.MaskSpec):
    # causal | full | prefix_lm | sliding_window. Scalars (not a MaskSpec)
    # so the config stays hashable/serializable; see mask_spec below.
    mask_kind: str = "causal"
    mask_window: int = 0
    mask_prefix: int = 0
    # Weight-only int8 serving (serve/quant.py): dense/embed sites
    # consume Int8Leaf params natively — raw-int8 matmul operands with
    # the per-channel scale applied OUTPUT-side, so no full-size
    # dequantized weight is ever materialized (the SERVEBENCH 0.747x
    # fix). Only QuantizedModule sets this; the default False path
    # constructs exactly the historical modules.
    quantized_dense: bool = False

    @property
    def mask_spec(self):
        """MaskSpec for non-default masks, None for plain causal (the
        fast path keeps its historical call signatures)."""
        if self.mask_kind == "causal":
            return None
        from kubeflow_tpu.ops.flash_attention import MaskSpec
        return MaskSpec(self.mask_kind, window=self.mask_window,
                        prefix=self.mask_prefix)

    @property
    def num_params(self) -> int:
        """Parameter count (for MFU accounting; BASELINE.md formula)."""
        h, m, v = self.hidden_size, self.intermediate_size, self.vocab_size
        qkv = h * self.num_heads * self.head_dim + 2 * h * self.num_kv_heads * self.head_dim
        attn = qkv + self.num_heads * self.head_dim * h
        if self.attention_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        mlp = 3 * h * m
        norms = 2 * h
        per_layer = attn + mlp + norms
        emb = v * h * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + emb + h


def _dense_cls(cfg: LlamaConfig):
    """The projection layer class: `nn.DenseGeneral` normally, its
    Int8Leaf-aware twin under quantized serving (cfg.quantized_dense —
    see serve/quant.py Int8DenseGeneral: raw-int8 matmul operand,
    output-side scale). Resolved per call so the default path has zero
    import-time coupling to the serve package."""
    if not cfg.quantized_dense:
        return nn.DenseGeneral
    from kubeflow_tpu.serve.quant import Int8DenseGeneral
    return Int8DenseGeneral


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def llama_tiny(vocab: int = 512) -> LlamaConfig:
    """Test-size config — same topology, toy dims."""
    return LlamaConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        remat=False, flash_block_q=64, flash_block_kv=64)


def llama_1b() -> LlamaConfig:
    """Bench-size config that fits a single emulated v5e chip."""
    return LlamaConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=5632,
        num_layers=16, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=2048)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    # Gemma convention: the learned scale is zero-centered and applied as
    # (1 + w) — checkpoints store w, init stays ones-equivalent via zeros.
    plus_one: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.with_logical_partitioning(
                (nn.initializers.zeros_init() if self.plus_one
                 else nn.initializers.ones), ("norm",)),
            (x.shape[-1],), jnp.float32)
        if self.plus_one:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def rope_table(head_dim: int, max_len: int, theta: float,
               cfg: "LlamaConfig | None" = None) -> tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if cfg is not None and getattr(cfg, "rope_global_scaling_factor",
                                   1.0) != 1.0:
        # HF "linear" rope scaling: positions divided by the factor —
        # identically, frequencies divided. Read from cfg so EVERY
        # cfg-passing call site (scanned trunk, pipeline stage) scales
        # identically; Gemma-3's LOCAL table passes cfg=None and stays
        # unscaled (HF scales the global rope only).
        inv = inv / cfg.rope_global_scaling_factor
    if cfg is not None and cfg.rope_scaling_factor != 1.0:
        # Llama-3.1 "llama3" rope scaling: leave high-frequency components
        # alone, divide low-frequency ones by `factor`, and interpolate
        # smoothly in between (matches HF modeling_rope_utils).
        factor = cfg.rope_scaling_factor
        low = cfg.rope_scaling_low_freq_factor
        high = cfg.rope_scaling_high_freq_factor
        old_len = cfg.rope_scaling_original_max_len
        wavelen = 2 * jnp.pi / inv
        low_wl, high_wl = old_len / low, old_len / high
        smooth = (old_len / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = (1 - smooth) * inv / factor + smooth * inv
        inv = jnp.where(wavelen > low_wl, inv / factor,
                        jnp.where(wavelen < high_wl, inv, scaled))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] absolute positions (for decode)."""
    cos = cos[positions][:, :, None, :]  # [B,S,1,D/2]
    sin = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# Re-exported for compatibility; canonical home is ops/reference.py (ops/
# must not depend on models/).
from kubeflow_tpu.ops.reference import naive_attention  # noqa: E402,F401


def init_cache(cfg: LlamaConfig, batch: int, max_len: int | None = None,
               dtype: Any = None, kv_quant: str = "none") -> dict:
    """Decode KV cache: {"k","v"} of [L, B, T, KH, D] (layer-stacked so the
    scanned trunk consumes it as a per-layer scan input). Functional — the
    cache is passed into and returned from `Llama.__call__`, never stored as
    a flax variable, so serving can AOT-compile prefill/decode as pure fns
    (the TPU answer to vLLM's mutable paged cache; SURVEY.md §2.2
    huggingfaceserver row).

    Sliding-window checkpoints (Mistral-class) whose window is shorter
    than the requested length get a ROLLING cache instead: T = window
    rows, writes wrap modularly, and a "pos" plane [L, B, T] records each
    row's absolute position (sentinel -(window+1) = never written) so
    attention can mask reads exactly — the vLLM/HF rolling-buffer
    capability, XLA-shaped (static shapes, pure fns).

    `kv_quant` != "none" (ISSUE 19) stores K/V as int8/fp8 with per-row
    f32 scale planes "ks"/"vs" of [L, B, T, KH] — the paged pool's
    quantized layout (serve/quant.py KV helpers). Rolling caches never
    quantize (the engine refuses the combination upstream: quantization
    requires the paged pool, rolling requires the flat layout)."""
    t = max_len or cfg.max_seq_len
    dt = dtype or cfg.dtype
    window = int(getattr(cfg, "mask_window", 0) or 0)
    cache = {}
    if (getattr(cfg, "mask_kind", "causal") == "sliding_window"
            and 0 < window < t
            and getattr(cfg, "sliding_pattern", "all") == "all"):
        # Alternating patterns (Gemma-2/3) have FULL-attention layers
        # that need the whole history — nothing rolls; they serve past
        # the window on the plain full-length layout with per-layer
        # banded decode reads (Attention's decode branch).
        t = window
        cache["pos"] = jnp.full((cfg.num_layers, batch, t),
                                -(window + 1), jnp.int32)
    shape = (cfg.num_layers, batch, t, cfg.num_kv_heads, cfg.head_dim)
    if kv_quant != "none":
        from kubeflow_tpu.serve.quant import kv_qdtype

        if "pos" in cache:
            raise ValueError("kv_quant does not compose with a rolling "
                             "sliding-window cache")
        qdt = kv_qdtype(kv_quant)
        cache.update({"k": jnp.zeros(shape, qdt),
                      "v": jnp.zeros(shape, qdt),
                      "ks": jnp.zeros(shape[:-1], jnp.float32),
                      "vs": jnp.zeros(shape[:-1], jnp.float32)})
        return cache
    cache.update({"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)})
    return cache


class RowState:
    """What the serving engine asks of a model's decode state
    (serve/paging.py `serving_state`), for the cache above: one K row and
    one V row a token a layer, so one kind of block, and a request holds
    `ceil(n / block_size)` of them for its n rows until it retires."""

    kinds = ("rows",)
    #: Nothing comes or goes while a request decodes: its whole worst case
    #: is taken at admission.
    grows = False
    #: Names of the counters the engine keeps for this state: none.
    counters = ()

    def __init__(self, cfg, block_size: int, max_len: int):
        self.cfg, self.bs = cfg, int(block_size)

    def held(self, n: int) -> tuple[int]:
        return (-(-max(int(n), 0) // self.bs),)

    def peak(self, n: int) -> int:
        return self.held(n)[0]

    def slots(self, n_slots: int, max_len: int) -> dict:
        """The flat engine's cache: `max_len` rows a slot."""
        return init_cache(self.cfg, n_slots, max_len)

    def pool(self, n_blocks: int, kv_quant: str = "none") -> dict:
        """The paged pool, block 0 the reserved NULL block."""
        return init_cache(self.cfg, n_blocks + 1, self.bs,
                          kv_quant=kv_quant)

    def fragment(self, length: int) -> dict:
        """One request's rows as prefill builds them, contiguous."""
        return init_cache(self.cfg, 1, length)


def _update_cache(cache_k, cache_v, k, v, index):
    """Write new k/v [B,S,KH,D] into per-layer cache [B,T,KH,D] at per-row
    sequence offsets index [B] (rows advance independently under continuous
    batching)."""
    def row(ck, cv, kk, vv, i):
        return (jax.lax.dynamic_update_slice(ck, kk, (i, 0, 0)),
                jax.lax.dynamic_update_slice(cv, vv, (i, 0, 0)))
    return jax.vmap(row)(cache_k, cache_v, k.astype(cache_k.dtype),
                         v.astype(cache_v.dtype), index)


def _update_rows(cache_leaf, new_rows, index):
    """`_update_cache` generalized over trailing rank: writes `new_rows`
    [B, S, ...] into a per-layer plane [B, T, ...] at per-row offsets —
    the quantized cache's f32 scale planes [B, T, KH] ride next to the
    value planes [B, T, KH, D] through the same per-row write."""
    def row(c, n, i):
        return jax.lax.dynamic_update_slice(c, n, (i,) + (0,) * (c.ndim - 1))
    return jax.vmap(row)(cache_leaf, new_rows.astype(cache_leaf.dtype),
                         index)


def _update_cache_rolling(cache, k, v, positions, index, window):
    """Modular writes into a per-layer rolling cache {"k","v","pos"}:
    chunk token j lands in row (index + j) % window with its absolute
    position recorded. Rows whose `positions` entry is negative (the
    engine marks prompt-bucket padding with a sentinel) keep their OLD
    contents — a padded write must never evict a real in-window row.
    Callers guarantee S <= window (the engine clamps prefill buckets), so
    the target rows are distinct and gather-then-set is well-defined."""
    s = k.shape[1]

    def row(ck, cv, cp, kk, vv, pos, i):
        rows = (i + jnp.arange(s)) % window
        valid = pos >= 0
        kk = jnp.where(valid[:, None, None], kk.astype(ck.dtype), ck[rows])
        vv = jnp.where(valid[:, None, None], vv.astype(cv.dtype), cv[rows])
        pp = jnp.where(valid, pos, cp[rows])
        return ck.at[rows].set(kk), cv.at[rows].set(vv), cp.at[rows].set(pp)

    ck, cv, cp = jax.vmap(row)(cache["k"], cache["v"], cache["pos"],
                               k, v, positions, index)
    return {"k": ck, "v": cv, "pos": cp}


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions, ring_axis: str | None = None,
                 standard_positions: bool = True, cache: dict | None = None,
                 cache_index: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 attend_full_cache: bool = False,
                 adapter: dict | None = None,
                 adapter_ids: jax.Array | None = None,
                 sliding: jax.Array | None = None,
                 rope_local: tuple | None = None):
        cfg = self.cfg
        dense = partial(
            _dense_cls(cfg), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype)
        qkv_bias = dict()
        if cfg.attention_bias:
            # Qwen2-style QKV biases; [heads, head_dim] shards like the
            # kernel's output dims.
            qkv_bias = dict(
                use_bias=True,
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("heads", "kv")))
        q = dense(features=(cfg.num_heads, cfg.head_dim),
                  kernel_init=nn.with_logical_partitioning(
                      nn.initializers.lecun_normal(), ("qkv_embed", "heads", "kv")),
                  name="q_proj", **qkv_bias)(x)
        k = dense(features=(cfg.num_kv_heads, cfg.head_dim),
                  kernel_init=nn.with_logical_partitioning(
                      nn.initializers.lecun_normal(), ("qkv_embed", "heads", "kv")),
                  name="k_proj", **qkv_bias)(x)
        v = dense(features=(cfg.num_kv_heads, cfg.head_dim),
                  kernel_init=nn.with_logical_partitioning(
                      nn.initializers.lecun_normal(), ("qkv_embed", "heads", "kv")),
                  name="v_proj", **qkv_bias)(x)
        if cfg.lora_rank > 0:
            # PEFT's Llama default targets: q_proj + v_proj.
            h_in = (cfg.hidden_size,)
            q = q + _lora_delta(self, cfg, "q_proj", x, h_in,
                                (cfg.num_heads, cfg.head_dim),
                                ("heads", "kv"))
            v = v + _lora_delta(self, cfg, "v_proj", x, h_in,
                                (cfg.num_kv_heads, cfg.head_dim),
                                ("heads", "kv"))
        if adapter is not None:
            # Multi-LoRA serving: per-row adapter selection.
            q = q + _multi_lora_delta(x, adapter_ids, adapter["q_proj"],
                                      (cfg.num_heads, cfg.head_dim))
            v = v + _multi_lora_delta(x, adapter_ids, adapter["v_proj"],
                                      (cfg.num_kv_heads, cfg.head_dim))
        if cfg.qk_norm:
            # Gemma-3: per-head RMSNorm on q/k BEFORE the score scale and
            # RoPE (the norm would erase a pre-applied scalar).
            q = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                        name="q_norm")(q)
            k = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                        name="k_norm")(k)
        if cfg.query_pre_attn_scalar:
            # Gemma-2 scales scores by query_pre_attn_scalar^-0.5; every
            # attention impl here divides by sqrt(head_dim), so fold the
            # ratio into q (AFTER adapter deltas — HF scales the full
            # projected query at score time).
            q = q * jnp.asarray(
                (cfg.head_dim ** 0.5) / (cfg.query_pre_attn_scalar ** 0.5),
                q.dtype)
        if rope_local is not None and sliding is not None:
            # Gemma-3 dual rope bases: this layer's table picked by the
            # traced sliding flag (local base on sliding layers, global —
            # possibly linear-scaled — on full layers).
            cos = jnp.where(sliding, rope_local[0], cos)
            sin = jnp.where(sliding, rope_local[1], sin)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        q = nn.with_logical_constraint(q, ("batch", "act_seq", "act_heads", "act_kv"))
        k = nn.with_logical_constraint(k, ("batch", "act_seq", None, "act_kv"))
        v = nn.with_logical_constraint(v, ("batch", "act_seq", None, "act_kv"))

        def o_proj(out):
            return dense(features=cfg.hidden_size, axis=(-2, -1),
                         kernel_init=nn.with_logical_partitioning(
                             nn.initializers.lecun_normal(),
                             ("heads", "kv", "embed")),
                         name="o_proj")(out)

        mask_spec = cfg.mask_spec
        if cache is not None and "pos" in cache:
            # Rolling sliding-window decode (vLLM/HF rolling-buffer
            # parity for Mistral-class serving past the window). Attend
            # BEFORE writing: a chunk's own modular writes may evict rows
            # its earliest queries are still entitled to see. Stale rows
            # (a spec-decode rewind leaves rows holding positions >= the
            # current write index) are masked to the sentinel first; the
            # fresh chunk's own K/V ride alongside the cache in the read.
            window = int(cfg.mask_window)
            sentinel = jnp.int32(-(window + 1))
            cpos = jnp.where(cache["pos"] >= cache_index[:, None],
                             sentinel, cache["pos"])
            keys = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=1)
            vals = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=1)
            pos_kv = jnp.concatenate([cpos, positions], axis=1)
            out = naive_attention(q, keys, vals, causal=True,
                                  positions_q=positions, positions_kv=pos_kv,
                                  mask=mask_spec, softcap=cfg.attn_softcap)
            new_cache = _update_cache_rolling(cache, k, v, positions,
                                              cache_index, window)
            return o_proj(out), new_cache
        if (mask_spec is not None and cache is not None
                and not (mask_spec.kind == "sliding_window"
                         and sliding is not None)):
            raise ValueError(
                "attention mask specs don't compose with KV-cache decode "
                "(v1): serve masked models with full-forward predict "
                "(sliding_window checkpoints roll automatically when the "
                "cache is built with max_len > window)")

        new_cache = None
        k_scale = v_scale = None
        if cache is not None:
            if "ks" in cache:
                # Quantized pool view (ISSUE 19): quantize ONLY the
                # newly written rows, write values + scales through the
                # generic per-row updater, and hand attention the RAW
                # quantized cache plus the scale planes — dequant is
                # output-side inside naive_attention (scores × k_scale,
                # probs × v_scale), so no full-width fp cache exists in
                # the scan carry and committed rows' bytes never change.
                from kubeflow_tpu.serve.quant import kv_quantize_rows

                qmode = ("int8" if cache["k"].dtype == jnp.int8
                         else "fp8")
                kq, ks = kv_quantize_rows(k, qmode)
                vq, vs = kv_quantize_rows(v, qmode)
                new_cache = {
                    "k": _update_rows(cache["k"], kq, cache_index),
                    "v": _update_rows(cache["v"], vq, cache_index),
                    "ks": _update_rows(cache["ks"], ks, cache_index),
                    "vs": _update_rows(cache["vs"], vs, cache_index)}
                ck = new_cache["k"].astype(k.dtype)  # bare convert
                cv = new_cache["v"].astype(v.dtype)
                k_scale, v_scale = new_cache["ks"], new_cache["vs"]
            else:
                ck, cv = _update_cache(cache["k"], cache["v"], k, v,
                                       cache_index)
                new_cache = {"k": ck, "v": cv}
            if x.shape[1] == 1 or attend_full_cache:
                # Single-token decode — or a continuation chunk
                # (attend_full_cache: S new tokens at a nonzero offset,
                # the chunked-prefill path): attend over the whole cache;
                # causality and the not-yet-written tail (incl. stale
                # entries from a previous slot occupant) are both masked
                # by absolute positions (positions_kv > positions_q).
                # Alternating-window models (Gemma-2/3 past the window)
                # keep the FULL-length cache — the full-attention layers
                # need all history, so there is nothing to roll — and
                # the sliding layers band their reads per the traced
                # flag, exactly as in the full forward.
                t = ck.shape[1]
                out = naive_attention(
                    q, ck, cv, causal=True, positions_q=positions,
                    positions_kv=jnp.broadcast_to(jnp.arange(t), (ck.shape[0], t)),
                    softcap=cfg.attn_softcap,
                    mask=(mask_spec if sliding is not None else None),
                    windowed=sliding, k_scale=k_scale, v_scale=v_scale)
                return o_proj(out), new_cache
            # Prefill (cache_index must be 0): nothing precedes the new
            # tokens, so attention over just k/v is exact — the fast flash
            # path below serves it; the cache write above is the only extra.

        if cfg.attn_softcap or (sliding is not None
                                and mask_spec is not None):
            # Gemma-2's tanh score cap / per-layer traced window flag are
            # not implemented in the fused kernels — the einsum path is
            # the only correct impl; silently running flash would serve
            # wrong logits. NB `sliding` alone doesn't force this path:
            # after the serving engine's within-window causal rebuild the
            # flags stay alive for Gemma-3's dual rope selection, and
            # with the mask gone flash prefill is exact again.
            if cfg.attention_impl not in ("auto", "naive"):
                raise ValueError(
                    f"attn_softcap / alternating sliding layers need "
                    f"attention_impl 'naive', not "
                    f"{cfg.attention_impl!r}")
            out = naive_attention(q, k, v, causal=True,
                                  positions_q=positions,
                                  positions_kv=positions,
                                  segment_ids=segment_ids, mask=mask_spec,
                                  softcap=cfg.attn_softcap,
                                  windowed=sliding)
            return o_proj(out), new_cache

        impl = cfg.attention_impl
        if impl == "auto":
            if ring_axis is not None:
                impl = "ring"
            elif ((standard_positions or segment_ids is not None)
                  and on_tpu()):
                impl = "flash"
            else:
                impl = "naive"
        if impl == "flash" and not standard_positions and segment_ids is None:
            # The flash kernel masks causality by array index; custom
            # positions (packed/offset sequences) need the segment mask
            # (pass segment_ids) or a position-aware impl.
            raise ValueError(
                "attention_impl='flash' with custom positions needs "
                "segment_ids (packed sequences); use 'naive' or 'ring' "
                "otherwise")
        if segment_ids is not None and impl not in ("flash", "naive"):
            raise ValueError(
                f"segment_ids (packed sequences) need attention_impl "
                f"'flash' or 'naive', not {impl!r}")
        if mask_spec is not None and impl not in ("flash", "naive"):
            raise ValueError(
                f"mask_kind={cfg.mask_kind!r} needs attention_impl 'flash' "
                f"or 'naive' (ring/zigzag schedules are causal-only), "
                f"not {impl!r}")
        if impl in ("ring", "ring_flash"):
            from kubeflow_tpu.ops.ring_attention import ring_attention
            if impl == "ring_flash":
                if not standard_positions:
                    raise ValueError(
                        "attention_impl='ring_flash' derives causality from "
                        "the contiguous layout; custom positions need 'ring'")
                out = ring_attention(q, k, v, axis_name=ring_axis or "seq",
                                     inner="flash",
                                     block_q=cfg.flash_block_q,
                                     block_kv=cfg.flash_block_kv)
            else:
                out = ring_attention(q, k, v, axis_name=ring_axis or "seq",
                                     positions=positions)
        elif impl in ("zigzag", "zigzag_flash"):
            # Balanced causal ring schedule: the CALLER must feed tokens in
            # zigzag order (ops.ring_attention.zigzag_indices) and pass the
            # matching absolute `positions` for RoPE — the trainer does both
            # when spec.ring_attention == "zigzag" (train/trainer.py).
            if standard_positions:
                # Default arange positions mean the data was NOT permuted:
                # the kernel would mask by zigzag positions on straight
                # data — silently corrupt attention. Refuse loudly.
                raise ValueError(
                    "attention_impl='zigzag' needs zigzag-permuted tokens "
                    "and their explicit absolute positions (the trainer's "
                    "ring_attention='zigzag' mode supplies both)")
            from kubeflow_tpu.ops.ring_attention import zigzag_ring_attention
            out = zigzag_ring_attention(
                q, k, v, axis_name=ring_axis or "seq", pre_permuted=True,
                inner="flash" if impl == "zigzag_flash" else "einsum",
                block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv)
        elif impl == "flash":
            from kubeflow_tpu.ops.flash_attention import \
                flash_attention_on_mesh
            from kubeflow_tpu.parallel.mesh import current_mesh
            out = flash_attention_on_mesh(
                q, k, v, current_mesh(), block_q=cfg.flash_block_q,
                block_kv=cfg.flash_block_kv, segment_ids=segment_ids,
                mask=mask_spec)
        else:
            out = naive_attention(q, k, v, causal=True, positions_q=positions,
                                  positions_kv=positions,
                                  segment_ids=segment_ids, mask=mask_spec)
        return o_proj(out), new_cache


def _multi_lora_delta(x: jax.Array, ids: jax.Array, ab: dict,
                      out_shape: tuple) -> jax.Array:
    """Per-ROW adapter delta for multi-LoRA serving: each batch row
    selects its own adapter from stacked weights. ab = {"a": [N, in, r],
    "b": [N, r, *out]} where entry 0 is all-zeros ("no adapter") and B is
    PRE-SCALED by alpha/r at load time (serve/multilora.py), so the
    delta is just (x @ a[id]) @ b[id]. x [B, S, in]."""
    a = ab["a"][ids].astype(x.dtype)              # [B, in, r]
    b = ab["b"][ids].astype(x.dtype)              # [B, r, *out]
    low = jnp.einsum("bsh,bhr->bsr", x, a)
    bflat = b.reshape(b.shape[0], b.shape[1], -1)
    d = jnp.einsum("bsr,brf->bsf", low, bflat)
    return d.reshape(d.shape[0], d.shape[1], *out_shape)


def _lora_delta(mod: nn.Module, cfg: LlamaConfig, name: str, x: jax.Array,
                in_shape: tuple, out_shape: tuple,
                out_axes: tuple) -> jax.Array:
    """(x @ A) @ B * alpha/rank for one target projection. A
    [*in_shape, r] (small init), B [r, *out_shape] (ZERO init — the
    adapted model equals the base at step 0, the standard LoRA start).
    The rank dim is tiny and never sharded; B's output dims follow the
    base kernel's logical axes so TP shards the delta like the weight."""
    r = cfg.lora_rank
    a = mod.param(
        f"{name}_lora_a",
        nn.with_logical_partitioning(
            nn.initializers.normal(0.02),
            tuple([None] * len(in_shape)) + (None,)),
        tuple(in_shape) + (r,), cfg.param_dtype)
    b = mod.param(
        f"{name}_lora_b",
        nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (None,) + tuple(out_axes)),
        (r,) + tuple(out_shape), cfg.param_dtype)
    dt = cfg.dtype
    n_in = len(in_shape)
    low = jax.lax.dot_general(
        x.astype(dt), a.astype(dt),
        (((tuple(range(x.ndim - n_in, x.ndim))), tuple(range(n_in))),
         ((), ())))
    delta = jax.lax.dot_general(
        low, b.astype(dt), (((low.ndim - 1,), (0,)), ((), ())))
    return delta * (cfg.lora_alpha / r)


class MLPBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, adapter: dict | None = None,
                 adapter_ids: jax.Array | None = None):
        cfg = self.cfg
        dense = partial(_dense_cls(cfg), use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        lora_mlp = cfg.lora_rank > 0 and cfg.lora_targets == "attn_mlp"
        multi_mlp = adapter is not None and "gate_proj" in adapter
        gate = dense(features=cfg.intermediate_size,
                     kernel_init=nn.with_logical_partitioning(
                         nn.initializers.lecun_normal(), ("embed", "mlp")),
                     name="gate_proj")(x)
        up = dense(features=cfg.intermediate_size,
                   kernel_init=nn.with_logical_partitioning(
                       nn.initializers.lecun_normal(), ("embed", "mlp")),
                   name="up_proj")(x)
        if lora_mlp:
            h = cfg.hidden_size
            gate = gate + _lora_delta(self, cfg, "gate_proj", x, (h,),
                                      (cfg.intermediate_size,), ("mlp",))
            up = up + _lora_delta(self, cfg, "up_proj", x, (h,),
                                  (cfg.intermediate_size,), ("mlp",))
        if multi_mlp:
            gate = gate + _multi_lora_delta(
                x, adapter_ids, adapter["gate_proj"],
                (cfg.intermediate_size,))
            up = up + _multi_lora_delta(
                x, adapter_ids, adapter["up_proj"],
                (cfg.intermediate_size,))
        if cfg.mlp_act == "silu":
            act = nn.silu(gate)
        elif cfg.mlp_act == "gelu_tanh":  # Gemma's GeGLU gate
            act = nn.gelu(gate, approximate=True)
        else:
            raise ValueError(f"mlp_act {cfg.mlp_act!r}: silu | gelu_tanh")
        h = act * up
        h = nn.with_logical_constraint(h, ("batch", "act_seq", "mlp"))
        down = dense(features=cfg.hidden_size,
                     kernel_init=nn.with_logical_partitioning(
                         nn.initializers.lecun_normal(), ("mlp", "embed")),
                     name="down_proj")(h)
        if lora_mlp:
            down = down + _lora_delta(
                self, cfg, "down_proj", h, (cfg.intermediate_size,),
                (cfg.hidden_size,), ("embed",))
        if multi_mlp:
            down = down + _multi_lora_delta(
                h, adapter_ids, adapter["down_proj"], (cfg.hidden_size,))
        return down


class DecoderLayer(nn.Module):
    cfg: LlamaConfig
    mlp_cls: Any = None  # defaults to MLPBlock; models/moe.py swaps in MoE

    @nn.compact
    def __call__(self, x, cos, sin, positions, ring_axis=None,
                 standard_positions=True, cache=None, cache_index=None,
                 segment_ids=None, attend_full_cache=False,
                 adapter=None, adapter_ids=None, sliding=None,
                 rope_local=None):
        cfg = self.cfg
        attn_ad = None
        mlp_ad = None
        if adapter is not None:
            attn_ad = {k: adapter[k] for k in ("q_proj", "v_proj")
                       if k in adapter} or None
            mlp_ad = {k: adapter[k]
                      for k in ("gate_proj", "up_proj", "down_proj")
                      if k in adapter} or None
        h = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                    name="input_norm")(x)
        attn_out, new_cache = Attention(cfg, name="attn")(
            h, cos, sin, positions, ring_axis, standard_positions, cache,
            cache_index, segment_ids, attend_full_cache,
            adapter=attn_ad, adapter_ids=adapter_ids, sliding=sliding,
            rope_local=rope_local)
        if cfg.sandwich_norms:
            # Gemma-2: norm the attention OUTPUT before the residual add
            # (HF post_attention_layernorm).
            attn_out = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                               name="attn_out_norm")(attn_out)
        # Remat landmark: policy "save_attn" keeps this tensor so the
        # backward skips re-running the attention kernel (small residual:
        # [B,S,H·D] bf16 per layer vs the full block internals).
        from jax.ad_checkpoint import checkpoint_name
        attn_out = checkpoint_name(attn_out, "attn_out")
        x = x + attn_out
        # In sandwich mode this plays HF's pre_feedforward_layernorm role
        # (same position: normed input to the MLP).
        h = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                    name="post_attn_norm")(x)
        mlp_out = (self.mlp_cls or MLPBlock)(cfg, name="mlp")(
            h, adapter=mlp_ad, adapter_ids=adapter_ids)
        if cfg.sandwich_norms:
            mlp_out = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                              name="mlp_out_norm")(mlp_out)
        x = x + mlp_out
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        return x, new_cache


class Llama(nn.Module):
    """Causal LM. __call__ returns logits [B, S, V]."""

    cfg: LlamaConfig
    mlp_cls: Any = None  # per-layer FFN class (None = dense MLPBlock)

    @nn.compact
    def __call__(self, tokens: jax.Array, positions: jax.Array | None = None,
                 ring_axis: str | None = None, cache: dict | None = None,
                 cache_index: jax.Array | None = None,
                 return_hidden: bool = False,
                 segment_ids: jax.Array | None = None,
                 attend_full_cache: bool = False,
                 adapter: dict | None = None,
                 adapter_ids: jax.Array | None = None):
        """Returns logits [B,S,V]; with `cache` (see init_cache) returns
        (logits, updated_cache) — prefill when S>1 at cache_index 0,
        single-token decode when S==1 (positions default to cache_index),
        and CONTINUATION when S>1 with `attend_full_cache=True`: the new
        tokens write at cache_index>0 and attend over the whole cache
        (chunked prefill of long prompts; pass absolute `positions`).
        `return_hidden` skips the unembedding and returns the post-norm
        hidden states [B,S,H] (chunked-CE training path). `segment_ids`
        [B,S] enables packed-sequence training: attention is confined
        within equal-id spans (pass the matching per-segment restarting
        `positions` for RoPE).

        Multi-LoRA serving (`adapter` + `adapter_ids`): `adapter` maps
        target module names to stacked adapter pairs {"a": [L, N, in, r],
        "b": [L, N, r, *out]} (entry 0 zeros = base, B pre-scaled by
        alpha/r — serve/multilora.py), and `adapter_ids` [B] selects one
        per batch row; the stacks ride the layer scan like the cache."""
        cfg = self.cfg
        if adapter is not None and adapter_ids is None:
            adapter_ids = jnp.zeros((tokens.shape[0],), jnp.int32)
        if cache is not None:
            if cache_index is None:
                cache_index = jnp.zeros((tokens.shape[0],), jnp.int32)
            if positions is None and tokens.shape[1] == 1:
                positions = cache_index[:, None]
        standard_positions = positions is None
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        embed = self.param(
            "embed", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        if cfg.quantized_dense:
            # Int8-aware gather: rows dequantize AFTER the lookup
            # ([B,S,D] work, not [V,D] per call — see serve/quant.py).
            from kubeflow_tpu.serve.quant import quant_embed_lookup
            x = quant_embed_lookup(embed, tokens, cfg.dtype)
        else:
            x = embed.astype(cfg.dtype)[tokens]
        if cfg.embed_scale:
            # Gemma scales token embeddings by sqrt(hidden) at input; the
            # multiplier is cast to the activation dtype first (HF rounds
            # the normalizer to the model dtype before multiplying).
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        cos, sin = rope_table(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta,
                              cfg)
        # Per-layer kind flags (HF layer_types): needed by the alternating
        # MASK (while the config still carries it — the serving engine's
        # within-window rebuild drops the mask) AND by Gemma-3's dual
        # rope bases (which survive the rebuild, so the flags must not
        # depend on the mask being present).
        sliding = None
        if cfg.sliding_pattern != "all" and (
                cfg.mask_kind == "sliding_window" or cfg.rope_theta_local):
            idx = jnp.arange(cfg.num_layers)
            if cfg.sliding_pattern == "even":
                sliding = idx % 2 == 0       # Gemma-2
            elif cfg.sliding_pattern == "5to1":
                sliding = (idx + 1) % 6 != 0  # Gemma-3: every 6th full
            else:
                raise ValueError(
                    f"sliding_pattern {cfg.sliding_pattern!r}: "
                    "all | even | 5to1")
        rope_local = None
        if cfg.rope_theta_local:
            rope_local = rope_table(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta_local)

        layer_cls = DecoderLayer
        if cfg.remat:
            policies = {
                "nothing": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.checkpoint_dots,
                "dots_no_batch":
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                "save_attn": jax.checkpoint_policies.save_only_these_names(
                    "attn_out"),
            }
            try:
                policy = policies[cfg.remat_policy]
            except KeyError:
                raise ValueError(
                    f"remat_policy {cfg.remat_policy!r}: "
                    f"{sorted(policies)}") from None
            # Static argnums are SELF-BASED in nn.remat (the scope rides at
            # index 0, user args start at 1): ring_axis(5) and
            # standard_positions(6) and attend_full_cache(10) are python
            # values steering control flow and must not be traced;
            # cache/cache_index/segment_ids are arrays and must stay
            # dynamic (serving prefill passes a real cache through the
            # remat'd layers).
            layer_cls = nn.remat(layer_cls, policy=policy,
                                 static_argnums=(5, 6, 10))
        new_cache = None
        if cfg.scan_layers:
            # `cache` (leading layer dim) rides as the scan's per-layer input
            # and the updated cache comes back as its per-layer output.
            x, new_cache = nn.scan(
                lambda mdl, carry, layer_cache, ad, sl: mdl(
                    carry, cos, sin, positions, ring_axis,
                    standard_positions, layer_cache, cache_index,
                    segment_ids, attend_full_cache, ad, adapter_ids, sl,
                    rope_local),
                variable_axes={"params": 0, "aux_loss": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(layer_cls(cfg, self.mlp_cls, name="layers"), x, cache,
              adapter, sliding)
        else:
            layer_caches = []
            for i in range(cfg.num_layers):
                layer_cache = None if cache is None else jax.tree.map(
                    lambda c: c[i], cache)
                layer_ad = None if adapter is None else jax.tree.map(
                    lambda a: a[i], adapter)
                x, lc = layer_cls(cfg, self.mlp_cls, name=f"layer_{i}")(
                    x, cos, sin, positions, ring_axis, standard_positions,
                    layer_cache, cache_index, segment_ids,
                    attend_full_cache, layer_ad, adapter_ids,
                    None if sliding is None else sliding[i], rope_local)
                layer_caches.append(lc)
            if cache is not None:
                new_cache = jax.tree.map(
                    lambda *ls: jnp.stack(ls), *layer_caches)

        x = RMSNorm(cfg.rms_eps, cfg.dtype, cfg.norm_plus_one,
                    name="final_norm")(x)
        if return_hidden:
            # Chunked-CE training path (train/step.py): the caller computes
            # logits blockwise against the unembedding so the [B·S, V] fp32
            # logits buffer is never materialized (ops/ROADMAP.md item 1).
            return (x, new_cache) if cache is not None else x
        if cfg.tie_embeddings:
            if cfg.quantized_dense:
                from kubeflow_tpu.serve.quant import quant_unembed
                logits = quant_unembed(x, embed, cfg.dtype)
            else:
                logits = jnp.einsum("bsh,vh->bsv", x,
                                    embed.astype(cfg.dtype))
        else:
            logits = _dense_cls(cfg)(
                features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")),
                name="lm_head")(x)
        if cfg.final_softcap:
            # Gemma-2 final-logit soft-cap. NB the chunked-CE training
            # path exits above via return_hidden — train/step.py applies
            # the same cap inside each logits chunk.
            logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
        if cache is not None:
            return logits, new_cache
        return logits
