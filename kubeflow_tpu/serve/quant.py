"""Weight-only int8 quantization for serving.

The reference's LLM runtime leans on vLLM's GPU quantization back ends
(⟨kserve: python/huggingfaceserver — vLLM engine args⟩, SURVEY.md §2.2).
The TPU-native equivalent for a serving-side win is *weight-only* int8:
weights sit in HBM at half the bf16 footprint and the dequantize (a
per-channel multiply) fuses into the consuming matmul's operand read under
XLA — decode steps are HBM-bandwidth-bound, so halving weight bytes is a
direct throughput lever (ops/ROADMAP.md item: quantized serving).

Scheme: symmetric per-channel (max-abs over the largest axis — the
contraction/in dim for 2-D kernels and scanned layer stacks alike) int8,
fp32 scales with that axis kept at 1 for broadcast. Quantized leaves are a
registered pytree node (`Int8Leaf`), so the quantized tree flows through
jit / device_put / AOT lowering like any params tree, and `QuantizedModule`
makes it transparent to every consumer that calls `model.apply` (the
generation engine, AOT-bucketed predictors, graph nodes).

Dequant placement (the SERVEBENCH 0.747x defect, ROADMAP item 4): the
original wrapper dequantized the WHOLE tree per `apply` — `(q * scale)`
is a full-weight-shaped multiply, and a multiply feeding a dot operand
does not fuse into the matmul's operand read, so every decode step
inside the chunk scan materialized every weight at full bf16 width
(verified in the compiled HLO: the convert+multiply fusions carry
`while/body` metadata). Per step that is int8 + bf16 weight traffic —
~1.5x the bf16 baseline's bytes, which is exactly the measured 0.747x
throughput. The fix moves the scale to the OTHER side of the matmul:
`x @ (q * s) == (x @ q) * s` when `s` is per-output-channel (the
contraction dims of the scale are 1), so `Int8DenseGeneral` feeds the
dot the RAW int8 kernel through a bare convert — which XLA does fuse
into the operand read — and applies the scale to the `[B, S, out]`
output, a bandwidth-trivial multiply. No full-size dequantized weight
tensor exists anywhere in the program; the HLO-shape guard test pins
this (tests/test_kv_transfer.py is the serving suite; the guard lives
in tests/test_quant_dequant.py).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
class Int8Leaf:
    """int8 values + fp32 per-channel scales; w ≈ q * scale."""

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    def dequantize(self, dtype=jnp.bfloat16):
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def _is_quant_leaf(x: Any) -> bool:
    return isinstance(x, Int8Leaf)


def _contraction_axes(path_names: list[str], ndim: int) -> tuple[int, ...]:
    """Axes to max-abs over = the matmul CONTRACTION axes, so scales are
    per-output-channel (the standard weight-only scheme) and tiny. Known
    kernel families by name; a leading scan/layers dim (ndim >= 3) is
    never reduced — scales stay per-layer. Reducing over axis 0
    unconditionally (the old scheme) maxed over LAYERS on scanned stacks
    and stored a near-full-size fp32 scale tensor."""
    if any(n in path_names for n in ("o_proj", "o")) and ndim >= 3:
        return (ndim - 3, ndim - 2)  # [..., heads, head_dim, out]
    if any(n in path_names
           for n in ("q_proj", "k_proj", "v_proj", "q", "k", "v")) \
            and ndim >= 3:
        return (ndim - 3,)           # [..., in, heads, head_dim]
    if path_names and path_names[-1] in ("embed", "wte",
                                         "shared_embedding"):
        # Tied embeddings across families (Llama "embed", GPT-2 "wte",
        # T5 "shared_embedding"): [vocab, D], the unembed contracts D.
        return (ndim - 1,)
    return (ndim - 2,)               # [..., in, out]


def quantize_tree(params: Any, *, min_size: int = 4096) -> Any:
    """Replace large float leaves with Int8Leaf.

    Leaves smaller than `min_size` elements (norm scales, biases) stay in
    full precision — they are bandwidth-irrelevant and precision-critical.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def quant(path, leaf):
        if not hasattr(leaf, "dtype") or not jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            return leaf
        arr = jnp.asarray(leaf)
        if arr.ndim < 2 or arr.size < min_size:
            return leaf
        # Dict keys only: boxed params (nn.Partitioned) append attr keys
        # like `.value` that would shadow the trailing param name.
        names = [str(k.key) for k in path if hasattr(k, "key")]
        if "router" in names:
            # MoE router: int8 noise can FLIP top-k expert assignment —
            # a discrete routing change, not a smooth dequant error. The
            # tensor is bandwidth-trivial next to the experts it gates.
            return leaf
        if names and names[-1] == "bias":
            # Additive biases (Qwen2 QKV): bandwidth-trivial, and the
            # name-based contraction-axis table below is kernel-shaped —
            # it would pick a nonsense scale axis for a bias tensor.
            return leaf
        a32 = arr.astype(jnp.float32)
        amax = jnp.max(jnp.abs(a32),
                       axis=_contraction_axes(names, arr.ndim),
                       keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(a32 / scale), -127, 127).astype(jnp.int8)
        return Int8Leaf(q, scale)

    return jax.tree_util.tree_unflatten(
        treedef, [quant(p, l) for p, l in flat])


def dequantize_tree(params: Any, dtype: Any = jnp.bfloat16) -> Any:
    """Inverse of quantize_tree; runs inside jit so XLA fuses the multiply
    into the consuming matmul's operand read."""
    return jax.tree.map(
        lambda leaf: leaf.dequantize(dtype) if _is_quant_leaf(leaf) else leaf,
        params, is_leaf=_is_quant_leaf)


def quantized_bytes(params: Any) -> dict:
    """{"quantized": n, "full": n} parameter byte counts for metadata.
    `full` is the bf16 baseline (what the server would otherwise hold),
    so full/quantized is the honest HBM saving — about 2×."""
    qb = fb = 0
    for leaf in jax.tree.leaves(params, is_leaf=_is_quant_leaf):
        if _is_quant_leaf(leaf):
            qb += leaf.q.size + leaf.scale.size * 4
            fb += leaf.q.size * 2  # bf16
        elif hasattr(leaf, "nbytes"):
            qb += leaf.nbytes
            fb += leaf.nbytes
    return {"quantized": int(qb), "full": int(fb)}


# --- KV-cache block quantization (ISSUE 19) ---------------------------
#
# The paged KV pool's blocks become int8/fp8 payloads with f32 scales in
# a parallel pool, addressed by the SAME block ids ("ks"/"vs" next to
# "k"/"v") — so every layer that trades in block ids (prefix refs, CoW
# forks, host-tier spills, TPKV1 shipments) carries scales by carrying
# ids, and `BlockAllocator` never learns about quantization. Scales are
# per-row-per-head (amax over head_dim): a coarser per-block scale could
# not honor "scatter-back re-quantizes only newly written rows" — the
# new row would either move the shared scale (silently re-encoding every
# committed row in the block) or clip against the old one. Row scales
# make each row's encoding independent, so committed rows are immutable
# bytes exactly like the unquantized pool.
#
# Dequant placement mirrors Int8DenseGeneral (the ISSUE 13 lesson),
# lifted to attention: Q·Kᵀ and probs·V read the RAW quantized cache
# through a bare convert, and the row scales land on the score/prob
# tensors ([B, KH, G, S, T]-shaped — no [..., T, KH, D] cache-width
# multiply anywhere in the decode scan). See ops/reference.py
# `naive_attention(k_scale=, v_scale=)`.

#: Legal `kv_quant` knob values. "none" is the bit-exact escape hatch.
KV_QUANT_MODES = ("none", "int8", "fp8")


def require_kv_planes(state) -> None:
    """A quantized pool keeps a scale a row a head beside planes of K and V:
    a model that keeps another state is refused when its engine is made."""
    from kubeflow_tpu.serve.paging import require_rows

    require_rows(state, "kv_quant: the scale planes follow rows of per-head "
                 "K and V")


def kv_qdtype(mode: str):
    """Storage dtype of a quantized KV pool."""
    return {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[mode]


def kv_qmax(mode: str) -> float:
    """Largest representable magnitude the scale normalizes amax onto."""
    return {"int8": 127.0, "fp8": 448.0}[mode]


def kv_quantize_rows(rows, mode: str):
    """Quantize `[..., D]` float rows → (q `[..., D]`, scale `[...]` f32).

    Symmetric per-row max-abs over the head_dim axis; int8 rounds to
    nearest, fp8 relies on the cast's RNE. All-zero rows get the eps
    scale and encode to exact zeros, so NULL-block garbage stays inert.
    """
    r32 = rows.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(r32), axis=-1),
                        1e-12) / kv_qmax(mode)
    q = r32 / scale[..., None]
    if mode == "int8":
        q = jnp.clip(jnp.round(q), -127, 127)
    return q.astype(kv_qdtype(mode)), scale


def kv_dequantize_rows(q, scale, dtype=jnp.bfloat16):
    """Inverse of kv_quantize_rows — ADMISSION-side only (fragment
    reconstruction for prefix reuse / shipment import). The decode scan
    never calls this: it would be exactly the full-width materialization
    the HLO guard forbids."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


class Int8DenseGeneral(nn.Module):
    """`nn.DenseGeneral` twin that understands `Int8Leaf` kernels.

    Same constructor surface as the subset the model families use
    (features tuple, `axis`, optional bias, dtype/param_dtype, inits)
    and the same param names/shapes, so a quantized tree produced from
    an `nn.DenseGeneral` init slots straight in. With a plain-array
    kernel it reproduces DenseGeneral's math (promote + dot_general) —
    but the plain path only ever runs at init: the class is selected by
    `cfg.quantized_dense`, which only `QuantizedModule` sets, so
    unquantized serving never constructs it.

    The Int8 path is the dequant-placement fix (module docstring): the
    dot reads the int8 kernel through a bare convert (fusable into the
    operand read — no full-size weight temp), and the per-output-channel
    scale lands on the `[..., out]` OUTPUT in f32 before the cast back,
    which is also where the legacy scheme's precision lived (f32
    multiply, then cast)."""

    features: Union[int, Sequence[int]]
    axis: Union[int, Sequence[int]] = -1
    use_bias: bool = True
    dtype: Any = None
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, inputs):
        feats = ((self.features,) if isinstance(self.features, int)
                 else tuple(self.features))
        axes = ((self.axis,) if isinstance(self.axis, int)
                else tuple(self.axis))
        axes = tuple(a % inputs.ndim for a in axes)
        kshape = tuple(inputs.shape[a] for a in axes) + feats
        kernel = self.param("kernel", self.kernel_init, kshape,
                            self.param_dtype)
        bias = (self.param("bias", self.bias_init, feats,
                           self.param_dtype) if self.use_bias else None)
        contract = ((axes, tuple(range(len(axes)))), ((), ()))
        if isinstance(kernel, Int8Leaf):
            out_dtype = self.dtype or inputs.dtype
            # f32 accumulation: int8 dots natively accumulate wide (the
            # MXU does this for free), and the f32 partials + f32 scale
            # make this path strictly MORE precise than the legacy
            # dequantize-then-bf16-matmul, not just cheaper.
            y = jax.lax.dot_general(inputs.astype(out_dtype),
                                    kernel.q.astype(out_dtype), contract,
                                    preferred_element_type=jnp.float32)
            scale = kernel.scale.reshape(feats)  # contraction dims are 1
            y = (y * scale).astype(out_dtype)
        else:
            inputs, kernel = nn.dtypes.promote_dtype(inputs, kernel,
                                                     dtype=self.dtype)
            y = jax.lax.dot_general(inputs, kernel, contract)
        if bias is not None:
            bias = jnp.asarray(bias, y.dtype)
            y = y + bias.reshape((1,) * (y.ndim - len(feats)) + feats)
        return y


def quant_embed_lookup(embed: Any, tokens, dtype):
    """Token-embedding gather with Int8Leaf awareness: gather the int8
    rows and the matching per-row scales, multiply AFTER the gather —
    `[B, S, D]` work instead of dequantizing the whole `[V, D]` table
    per call (which the decode scan would otherwise pay per step)."""
    if not isinstance(embed, Int8Leaf):
        return embed.astype(dtype)[tokens]
    rows = embed.q[tokens].astype(jnp.float32)
    return (rows * embed.scale[tokens]).astype(dtype)


def quant_unembed(x, embed: Any, dtype):
    """Tied-embedding unembed `x @ embed.T` with the scale applied to
    the logits (per-vocab-row scale = per-output-channel of the
    transposed matmul) — the same output-side placement as
    Int8DenseGeneral."""
    if not isinstance(embed, Int8Leaf):
        return jnp.einsum("bsh,vh->bsv", x, embed.astype(dtype))
    logits = jnp.einsum("bsh,vh->bsv", x, embed.q.astype(dtype))
    return (logits.astype(jnp.float32)
            * embed.scale.reshape(1, 1, -1)).astype(dtype)


class QuantizedModule:
    """Wraps a flax module so `apply` serves a quantized params tree —
    quantization stays a storage detail invisible to every serving path
    that holds a (module, params) pair.

    Modules whose config carries a `quantized_dense` field (the Llama
    family — llama/mistral/qwen/gemma configs) are REBUILT with the flag
    set: their dense/embed sites consume `Int8Leaf` leaves natively
    (`Int8DenseGeneral` — output-side scale, no full-weight dequant), so
    `apply` passes `kernel`/`embed` leaves through raw and dequantizes
    only the rest (MoE expert stacks, other families' tensors).
    `legacy_dequant=True` restores the old dequantize-everything wrapper
    — the A/B control for the SERVEBENCH `quant` row."""

    def __init__(self, module: Any, dtype: Any = jnp.bfloat16,
                 legacy_dequant: bool = False):
        self.dtype = dtype
        self.legacy_dequant = bool(legacy_dequant)
        cfg = getattr(module, "cfg", None)
        self._native_quant = (not legacy_dequant and cfg is not None
                              and hasattr(cfg, "quantized_dense"))
        if self._native_quant and not cfg.quantized_dense:
            import dataclasses

            # Rebuild by REPLACING the module's cfg field, never by
            # re-constructing `type(module)(cfg)`: flax modules are
            # dataclasses, and reconstruction would drop every other
            # field (MoELlama's mlp_cls=MoEBlock — the routed-expert
            # trunk would silently become a dense MLPBlock whose params
            # don't exist).
            module = dataclasses.replace(
                module,
                cfg=dataclasses.replace(cfg, quantized_dense=True))
        self.module = module

    def _prepare(self, params: Any) -> Any:
        if not self._native_quant:
            return dequantize_tree(params, self.dtype)

        flat, treedef = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=_is_quant_leaf)

        def prep(path, leaf):
            if not _is_quant_leaf(leaf):
                return leaf
            names = [str(k.key) for k in path if hasattr(k, "key")]
            tail = names[-1] if names else ""
            # Handled natively by the quant-aware sites; everything else
            # (MoE expert stacks etc.) keeps the legacy dequant.
            if tail in ("kernel", "embed"):
                return leaf
            return leaf.dequantize(self.dtype)

        return jax.tree_util.tree_unflatten(
            treedef, [prep(p, l) for p, l in flat])

    def apply(self, variables: dict, *args, **kwargs):
        variables = dict(variables)
        variables["params"] = self._prepare(variables["params"])
        return self.module.apply(variables, *args, **kwargs)

    def __getattr__(self, name):  # cfg etc. pass through
        return getattr(self.module, name)
