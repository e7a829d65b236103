"""Pallas TPU flash attention (fused forward + fused two-pass backward).

The hot op of the flagship model. The reference platform has no kernels at
all (GPU attention lived in user containers: flash-attn/vLLM; SURVEY.md
§2.6) — this is the TPU-native equivalent, written against the Pallas TPU
model (/opt/skills/guides/pallas_guide.md): online-softmax blockwise
attention; Q blocks in VMEM stream over K/V blocks; fp32 accumulators;
causal upper blocks skipped entirely (not masked) so the causal speedup is
real wall-clock, not just masking.

Backward is the standard two-pass flash recipe with saved row stats:
the forward additionally writes LSE (logsumexp per q row); the backward
precomputes delta = rowsum(dO·O), then
  * a dq kernel over (batch·head, q blocks) streaming visible kv blocks,
  * a dk/dv kernel over (batch·kv-head, kv blocks, group · q blocks): the
    q, dO, LSE and delta blocks of every q head in the GQA group stream
    through the innermost grid axis (zero-copy: the grouped views are
    reshapes, never materialized per-head copies) into fp32 dk/dv
    accumulators in VMEM, so the kernel's footprint does not grow with
    the sequence; blocks a kv block cannot see are neither fetched nor
    computed.
Neither pass materializes an O(S·T) score matrix in HBM.

Layout: q [B, S, H, D], k [B, T, KH, D], v [B, T, KH, Dv] with GQA
(H % KH == 0); Dv may differ from D (latent attention: keys of 192, values
of 128) and the output is [B, S, H, Dv]. The forward and dq grids are
(B*H, Q_blocks); each program owns one q block and loops over its visible
kv blocks. K/V stay sequence-complete in VMEM per (batch, head) program —
fine through ~8k tokens in bf16 (the scoped-VMEM limit is raised to what
the whole rows need when that passes the default); ring attention
(ring_attention.py) is the path past that.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import dp_like_axes
from kubeflow_tpu.utils.devices import on_tpu

_LOG = logging.getLogger(__name__)

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Block-sparse attention mask families (the splash-attention mask-spec
    surface, ops/ROADMAP.md item 2). Static per compile; fully-masked
    blocks are SKIPPED by the kernels' visible-block ranges, not masked —
    the sparsity is wall-clock, not cosmetic.

    kind:
      - "causal": rows attend cols <= row (the default).
      - "full": bidirectional (encoder-style).
      - "prefix_lm": bidirectional over the first `prefix` positions,
        causal after (T5/PaLM2-style prefix LM fine-tuning).
      - "sliding_window": causal, but each row sees only the trailing
        `window` keys (Mistral-style local attention).

    Document confinement composes orthogonally via `segment_ids` — a
    window never crosses a segment boundary when both are given (the
    "document-window" mask). Exception: prefix_lm is refused with
    segment_ids (its boundary is an absolute position; packed rows
    restart positions per document).
    """

    kind: str = "causal"
    window: int = 0
    prefix: int = 0

    def __post_init__(self):
        kinds = ("causal", "full", "prefix_lm", "sliding_window")
        if self.kind not in kinds:
            raise ValueError(f"mask kind {self.kind!r}: one of {kinds}")
        if self.kind == "sliding_window" and self.window < 1:
            raise ValueError("sliding_window needs window >= 1")
        if self.kind == "prefix_lm" and self.prefix < 0:
            raise ValueError("prefix_lm needs prefix >= 0")


def _norm_mask(causal: bool, mask) -> MaskSpec:
    if mask is None:
        return MaskSpec("causal" if causal else "full")
    if isinstance(mask, str):
        return MaskSpec(mask)
    return mask


def _apply_mask(valid, rows, cols, mask: MaskSpec):
    """Fold the spec's in-block predicate into `valid` (static dispatch)."""
    if mask.kind == "causal":
        return jnp.logical_and(valid, rows >= cols)
    if mask.kind == "prefix_lm":
        return jnp.logical_and(
            valid, jnp.logical_or(rows >= cols, cols < mask.prefix))
    if mask.kind == "sliding_window":
        return jnp.logical_and(
            valid, jnp.logical_and(rows >= cols,
                                   rows - cols < mask.window))
    return valid  # full


def _q_visible(qi, block_q, block_kv, seq_kv, mask: MaskSpec):
    """(first, bound) kv-block range a q block must visit — blocks outside
    are fully masked and never touched. qi may be traced."""
    num_kv = pl.cdiv(seq_kv, block_kv)
    if mask.kind == "full":
        return 0, num_kv
    last = (qi + 1) * block_q - 1
    causal_bound = jnp.minimum(last // block_kv + 1, num_kv)
    if mask.kind == "causal":
        return 0, causal_bound
    if mask.kind == "prefix_lm":
        # Rows below the prefix see every prefix block (bidirectional).
        prefix_bound = jnp.where(
            qi * block_q < mask.prefix,
            jnp.minimum(pl.cdiv(mask.prefix, block_kv), num_kv), 0)
        return 0, jnp.maximum(causal_bound, prefix_bound)
    # sliding_window: the earliest col any row sees is first_row-window+1.
    first = jnp.maximum((qi * block_q - mask.window + 1) // block_kv, 0)
    return first, causal_bound


def _kv_visible(j, block_q, block_kv, seq_q_pad, mask: MaskSpec):
    """(first, bound) q-block range a kv block contributes gradients to."""
    num_q = seq_q_pad // block_q
    if mask.kind == "full":
        return 0, num_q
    causal_first = jnp.minimum((j * block_kv) // block_q, num_q)
    if mask.kind == "causal":
        return causal_first, num_q
    if mask.kind == "prefix_lm":
        return jnp.where(j * block_kv < mask.prefix, 0, causal_first), num_q
    # sliding_window: the last row that sees col c is c + window - 1.
    bound = jnp.minimum(
        ((j + 1) * block_kv - 1 + mask.window - 1) // block_q + 1, num_q)
    return causal_first, bound


#: Mosaic's default scoped-VMEM limit on the chips this runs on (16 MiB), and
#: how much of it the whole-row K/V buffers may take before the forward and
#: dq calls ask for more.
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_ROWS_SHARE = 0.6


def _whole_rows_vmem(t: int, d: int, dv: int, dtype, interpret: bool) -> dict:
    """`pallas_call` keywords for a kernel that keeps K [t, d] and V [t, dv]
    whole in VMEM: nothing while their double-buffered, lane-padded copies
    leave the default limit room for the rest (every shape the kernels ran
    at before latent attention, so those calls are unchanged), else a
    scoped-VMEM limit of what the rows take plus the default."""
    if interpret:
        return {}
    lanes = lambda n: -(-n // 128) * 128
    rows = 2 * t * (lanes(d) + lanes(dv)) * jnp.dtype(dtype).itemsize
    if rows <= _VMEM_ROWS_SHARE * _VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=rows + _VMEM_DEFAULT)}


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_q: int,
                      block_kv: int, seq_kv: int, mask: MaskSpec,
                      sm_scale: float, segments: bool = False):
    if segments:
        qs_ref, ks_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [block_q, D]

    first_visible, num_visible = _q_visible(qi, block_q, block_kv, seq_kv,
                                            mask)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [block_q, block_kv]
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1) + j * block_kv
        # Mask padded keys (inputs are padded up to a block multiple by the
        # wrapper; without this the pad keys would attend in non-causal mode).
        valid = cols < seq_kv
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0) + qi * block_q
        valid = _apply_mask(valid, rows, cols, mask)
        if segments:
            # Packed sequences: attention confined within equal-id spans
            # (padding carries -1 on the kv side, never equal to real ids).
            qseg = qs_ref[0, :, 0][:, None]
            kseg = ks_ref[0, pl.ds(j * block_kv, block_kv), 0][None, :]
            valid = jnp.logical_and(valid, qseg == kseg)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(first_visible, num_visible, body,
                                  (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # Row logsumexp of the scaled scores — the backward's softmax residual.
    lse_ref[0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_fwd(q3, k3, v3, seg_q3, seg_kv3, *, group: int, heads: int,
               mask: MaskSpec, block_q: int, block_kv: int, seq_kv: int,
               sm_scale: float, interpret: bool):
    """q3 [B*H, S, D]; k3/v3 [B*KH, T, D], padded to block multiples; GQA is
    served zero-copy by the K/V index_map (q program bh reads kv row
    bh // group, since bh = batch*H + qh and H = KH*group). seq_kv is the
    pre-padding key length used for masking. seg_q3/seg_kv3 [B, *, 1] (or
    None) carry packed-sequence segment ids, read zero-copy per batch row
    via b // heads index_maps. Returns (o3, lse [B*H, S])."""
    bh, s, d = q3.shape
    t, dv = k3.shape[1], v3.shape[2]
    grid = (bh, pl.cdiv(s, block_q))
    segments = seg_q3 is not None
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_kv=block_kv, seq_kv=seq_kv,
        mask=mask, sm_scale=sm_scale, segments=segments)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, t, d), lambda b, i: (b // group, 0, 0)),
        pl.BlockSpec((1, t, dv), lambda b, i: (b // group, 0, 0)),
    ]
    args = [q3, k3, v3]
    if segments:
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b // heads, i, 0)),
            pl.BlockSpec((1, t, 1), lambda b, i: (b // heads, 0, 0)),
        ]
        args += [seg_q3, seg_kv3]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        interpret=interpret,
        **_whole_rows_vmem(t, d, dv, k3.dtype, interpret),
    )(*args)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, block_q: int, block_kv: int, seq_q: int,
                         seq_kv: int, mask: MaskSpec, sm_scale: float,
                         segments: bool = False):
    if segments:
        qs_ref, ks_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale      # [bq, D]
    do = do_ref[0].astype(jnp.float32)               # [bq, D]
    lse = lse_ref[0]                                 # [bq, 1]
    delta = delta_ref[0]                             # [bq, 1]
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + qi * block_q

    first_visible, num_visible = _q_visible(qi, block_q, block_kv, seq_kv,
                                            mask)

    def body(j, acc):
        k = k_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1) + j * block_kv
        valid = jnp.logical_and(cols < seq_kv, rows < seq_q)
        valid = _apply_mask(valid, rows, cols, mask)
        if segments:
            valid = jnp.logical_and(
                valid,
                qs_ref[0, :, 0][:, None]
                == ks_ref[0, pl.ds(j * block_kv, block_kv), 0][None, :])
        # p from saved row stats; masked (incl. padded q rows, whose lse is
        # garbage) to exactly zero so no NaN/inf leaks into the matmuls.
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    d = q_ref.shape[-1]
    acc = jax.lax.fori_loop(first_visible, num_visible, body,
                            jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (acc * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                          *rest, block_q: int, block_kv: int,
                          seq_q: int, seq_kv: int, seq_q_pad: int, group: int,
                          mask: MaskSpec, sm_scale: float,
                          segments: bool = False):
    """One (kv block, q block of one head of the group) step: the innermost
    grid axis walks the group's heads and, within a head, the q blocks in
    order; dk and dv accumulate in fp32 scratch from its first step to its
    last, where they are written out."""
    if segments:
        qs_ref, ks_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    j = pl.program_id(1)
    step = pl.program_id(2)
    num_q = seq_q_pad // block_q
    qi = step % num_q

    @pl.when(step == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    first, bound = _kv_visible(j, block_q, block_kv, seq_q_pad, mask)

    @pl.when(jnp.logical_and(qi >= first, qi < bound))
    def _():
        k = k_ref[0].astype(jnp.float32)             # [bkv, D]
        v = v_ref[0].astype(jnp.float32)             # [bkv, Dv]
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1) + j * block_kv
        q = q_ref[0].astype(jnp.float32) * sm_scale
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0) + qi * block_q
        valid = jnp.logical_and(cols < seq_kv, rows < seq_q)
        valid = _apply_mask(valid, rows, cols, mask)
        if segments:
            valid = jnp.logical_and(
                valid, qs_ref[0, :, 0][:, None] == ks_ref[0, :, 0][None, :])
        p = jnp.where(valid, jnp.exp(s - lse_ref[0]), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        # q in the score matmul carried sm_scale, so ds . q is d/dk of
        # (q·k·scale) already.
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == group * num_q - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flatten_heads(q, k, v):
    """[B,S,H,D] → q3 [B*H, S, D], k3 [B*KH, T, D], v3 [B*KH, T, Dv] — no
    GQA repetition; the kernel's index_map maps q heads onto shared kv
    heads."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * kh, t, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * kh, t, v.shape[3])
    return q3, k3, v3


def _pad_seq(x3, block):
    pad = -x3.shape[1] % block
    if pad:
        x3 = jnp.pad(x3, ((0, 0), (0, pad), (0, 0)))
    return x3


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 8))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, interpret: bool | None = None,
                    segment_ids: jax.Array | None = None,
                    mask: MaskSpec | str | None = None):
    """Flash attention. q [B,S,H,D]; k [B,T,KH,D]; v [B,T,KH,Dv]; returns
    [B,S,H,Dv]. Scores scale by D^-½.

    Forward and backward both run fused Pallas kernels (O(S) memory); the
    backward uses the saved LSE row stats (two-pass dq then dk/dv).

    `segment_ids` [B,S] int (self-attention only) confines attention
    within equal-id spans — packed-sequence training with the fused
    kernels (the splash-style mask, ops/ROADMAP.md item 3).

    `mask` (a MaskSpec or kind string) selects the block-sparse mask
    family — causal / full / prefix_lm / sliding_window — overriding
    `causal`; fully-masked blocks are skipped in all three kernels.
    causal/full/sliding_window compose with `segment_ids` (document-window
    masks: in-document index distance equals position distance, so the
    window is per-document automatically). prefix_lm does NOT — its
    boundary is an absolute position, which packed rows restart per
    document — and is refused with segment_ids rather than silently
    masking only the first document's prefix."""
    out, _ = _attn_impl(q, k, v, causal, block_q, block_kv, interpret,
                        segment_ids, mask)
    return out


def flash_attention_on_mesh(q, k, v, mesh, *, block_q: int = 512,
                            block_kv: int = 512,
                            segment_ids: jax.Array | None = None,
                            mask: MaskSpec | str | None = None):
    """Causal `flash_attention` for a caller traced under `mesh` (None or a
    one-device mesh: the plain call). Mosaic kernels cannot be partitioned
    by GSPMD — under jit with sharded operands the compiled kernel refuses
    to lower ("wrap the call in a shard_map") — so on a multi-device mesh
    the call runs in a shard_map: batch over the dp-like axes, heads over
    `tensor`. A dimension whose size does not divide is gathered instead
    (every chip then computes all of it), with a warning at trace time.
    Attention is independent across batch rows and (kv-)head groups, so
    the body needs no collective. Interpret mode takes the same route so
    the CPU-mesh tests run the path the chip runs."""
    def attend(q, k, v, seg=None):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_kv=block_kv, segment_ids=seg, mask=mask)

    if mesh is None or mesh.size == 1:
        return attend(q, k, v, segment_ids)
    batch = dp_like_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in batch)
    tp = mesh.shape["tensor"] if "tensor" in mesh.axis_names else 1
    heads = "tensor" if tp > 1 else None
    if q.shape[0] % dp:
        _LOG.warning("flash_attention_on_mesh: batch %d does not divide "
                     "%s=%d; gathered, every chip computes all rows",
                     q.shape[0], "x".join(batch), dp)
        batch = ()
    if q.shape[2] % tp or k.shape[2] % tp:
        _LOG.warning("flash_attention_on_mesh: heads %d/%d do not divide "
                     "tensor=%d; gathered, every chip computes all heads",
                     q.shape[2], k.shape[2], tp)
        heads = None
    spec = P(batch or None, None, heads, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if segment_ids is not None:
        args += (segment_ids,)
        in_specs += (P(batch or None, None),)
    return shard_map(attend, mesh=mesh, in_specs=in_specs, out_specs=spec,
                     check_vma=False)(*args)


def _resolve(q, k, block_q, block_kv, interpret):
    if interpret is None:
        interpret = not on_tpu()
    s, t = q.shape[1], k.shape[1]
    block_q = min(block_q, max(s, 1))
    block_kv = min(block_kv, max(t, 1))
    return block_q, block_kv, interpret


def _seg3(segment_ids, block, b, s, t):
    """[B,S] segment ids → padded [B, S_pad, 1]. NOT replicated per head —
    the BlockSpec index_maps (b // heads) read the shared batch row
    zero-copy. Trailing unit dim: Mosaic needs the last two block dims to
    be (8k, 128k)-divisible or array-equal; (block, 1) satisfies that."""
    if segment_ids is None:
        return None
    if segment_ids.shape != (b, s) or t != s:
        raise ValueError(
            f"segment_ids must be [B,S]={b, s} for self-attention "
            f"(got {segment_ids.shape}, T={t})")
    seg = jnp.asarray(segment_ids, jnp.int32)
    pad = -seg.shape[1] % block
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    return seg[:, :, None]


def _attn_impl(q, k, v, causal, block_q, block_kv, interpret,
               segment_ids=None, mask=None):
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    if h % kh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kh}")
    block_q, block_kv, interpret = _resolve(q, k, block_q, block_kv,
                                            interpret)
    spec = _norm_mask(causal, mask)
    if spec.kind == "prefix_lm" and segment_ids is not None:
        raise ValueError(
            "prefix_lm does not compose with segment_ids: packed rows "
            "restart positions per document, but the prefix boundary is "
            "an absolute index — only the first document would get a "
            "bidirectional prefix. Pack prefix-LM data unsegmented.")
    sm_scale = 1.0 / (d ** 0.5)
    q3, k3, v3 = _flatten_heads(q, k, v)
    # Pad sequences to block multiples: unpadded dynamic slices would clamp
    # at the boundary and silently misalign kv columns. The kernel masks
    # padded keys via its seq_kv bound; padded q rows are sliced off here.
    q3 = _pad_seq(q3, block_q)
    k3 = _pad_seq(k3, block_kv)
    v3 = _pad_seq(v3, block_kv)
    sq3 = _seg3(segment_ids, block_q, b, s, t)
    skv3 = _seg3(segment_ids, block_kv, b, s, t)
    o3, lse = _flash_fwd(q3, k3, v3, sq3, skv3, group=h // kh, heads=h,
                         mask=spec, block_q=block_q, block_kv=block_kv,
                         seq_kv=t, sm_scale=sm_scale, interpret=interpret)
    out = o3[:, :s].reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)
    return out, (o3, lse)


def _float0_like(x):
    """Cotangent for integer-dtype primals (segment ids)."""
    if x is None:
        return None
    import numpy as np
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _flash_fwd_rule(q, k, v, causal, block_q, block_kv, interpret,
                    segment_ids=None, mask=None):
    out, (o3, lse) = _attn_impl(q, k, v, causal, block_q, block_kv,
                                interpret, segment_ids, mask)
    return out, (q, k, v, o3, lse, segment_ids)


def _flash_bwd_rule(causal, block_q, block_kv, interpret, mask, res, g):
    q, k, v, o3, lse, segment_ids = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o3, lse, g, None, causal, block_q,
                                 block_kv, interpret, segment_ids, mask)
    return dq, dk, dv, _float0_like(segment_ids)


def _flash_bwd_impl(q, k, v, o3, lse, g, g_lse, causal, block_q, block_kv,
                    interpret, segment_ids=None, mask=None):
    """Shared two-pass backward. `g_lse` [B,S,H,1] (or None) is the LSE
    cotangent: d lse_i/d s_ij = p_ij, so it folds into the delta term —
    ds = p·(dp - (delta - g_lse)) — at zero extra kernel cost."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    group = h // kh
    block_q, block_kv, interpret = _resolve(q, k, block_q, block_kv,
                                            interpret)
    spec = _norm_mask(causal, mask)
    sm_scale = 1.0 / (d ** 0.5)

    d_v = v.shape[3]
    q3, k3, v3 = _flatten_heads(q, k, v)
    q3 = _pad_seq(q3, block_q)
    k3 = _pad_seq(k3, block_kv)
    v3 = _pad_seq(v3, block_kv)
    do3 = _pad_seq(g.transpose(0, 2, 1, 3).reshape(b * h, s, d_v), block_q)
    s_pad, t_pad = q3.shape[1], k3.shape[1]
    bh, bkh = b * h, b * kh

    # delta_i = rowsum(dO_i · O_i) — the softmax-normalization term.
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if g_lse is not None:
        gl3 = _pad_seq(
            g_lse.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
                b * h, s, 1), block_q)
        delta = delta - gl3

    segments = segment_ids is not None
    sq3 = _seg3(segment_ids, block_q, b, s, t)
    skv3 = _seg3(segment_ids, block_kv, b, s, t)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_q=block_q, block_kv=block_kv, seq_q=s,
        seq_kv=t, mask=spec, sm_scale=sm_scale, segments=segments)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, t_pad, d), lambda bi, i: (bi // group, 0, 0)),
        pl.BlockSpec((1, t_pad, d_v), lambda bi, i: (bi // group, 0, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bi, i: (bi, i, 0)),
    ]
    dq_args = [q3, k3, v3, do3, lse, delta]
    if segments:
        dq_specs += [
            pl.BlockSpec((1, block_q, 1), lambda bi, i: (bi // h, i, 0)),
            pl.BlockSpec((1, t_pad, 1), lambda bi, i: (bi // h, 0, 0)),
        ]
        dq_args += [sq3, skv3]
    dq3 = pl.pallas_call(
        dq_kernel,
        grid=(bh, s_pad // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        interpret=interpret,
        **_whole_rows_vmem(t_pad, d, d_v, k3.dtype, interpret),
    )(*dq_args)

    # Grouped (per kv head) views of the q-side tensors: pure reshapes of the
    # [B*H, ...] layout since q head h serves kv head h // group. Row block
    # g * num_q + qi of a view is q block qi of the group's head g.
    qg = q3.reshape(bkh, group * s_pad, d)
    dog = do3.reshape(bkh, group * s_pad, d_v)
    lseg = lse.reshape(bkh, group * s_pad, 1)
    deltag = delta.reshape(bkh, group * s_pad, 1)
    num_q = s_pad // block_q

    def q_block(j, step):
        """The q block a grid step reads. A step whose block this kv block
        cannot see is skipped by the kernel; pointing it at a visible block
        keeps the pipeline from fetching rows nobody reads."""
        first, bound = _kv_visible(j, block_q, block_kv, s_pad, spec)
        return jnp.clip(step % num_q, first, jnp.maximum(bound - 1, first))

    def q_side(bi, j, step):
        return (bi, (step // num_q) * num_q + q_block(j, step), 0)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_q=block_q, block_kv=block_kv, seq_q=s,
        seq_kv=t, seq_q_pad=s_pad, group=group, mask=spec,
        sm_scale=sm_scale, segments=segments)
    dkv_specs = [
        pl.BlockSpec((1, block_q, d), q_side),
        pl.BlockSpec((1, block_q, d_v), q_side),
        pl.BlockSpec((1, block_q, 1), q_side),
        pl.BlockSpec((1, block_q, 1), q_side),
        pl.BlockSpec((1, block_kv, d), lambda bi, j, step: (bi, j, 0)),
        pl.BlockSpec((1, block_kv, d_v), lambda bi, j, step: (bi, j, 0)),
    ]
    dkv_args = [qg, dog, lseg, deltag, k3, v3]
    if segments:
        dkv_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda bi, j, step: (bi // kh, q_block(j, step), 0)),
            pl.BlockSpec((1, block_kv, 1),
                         lambda bi, j, step: (bi // kh, j, 0)),
        ]
        dkv_args += [sq3, skv3]
    dk3, dv3 = pl.pallas_call(
        dkv_kernel,
        grid=(bkh, t_pad // block_kv, group * num_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda bi, j, step: (bi, j, 0)),
            pl.BlockSpec((1, block_kv, d_v), lambda bi, j, step: (bi, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkh, t_pad, d), k.dtype),
            jax.ShapeDtypeStruct((bkh, t_pad, d_v), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dkv_args)

    dq = dq3[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)
    dk = dk3[:, :t].reshape(b, kh, t, d).transpose(0, 2, 1, 3)
    dv = dv3[:, :t].reshape(b, kh, t, d_v).transpose(0, 2, 1, 3)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# -- (out, lse) variant: the ring-attention inner block ----------------------
# Ring attention merges per-step partial results by their row logsumexp, so
# the inner op must EXPOSE lse and be differentiable in it. The backward is
# the same two kernels with delta := delta - g_lse (see _flash_bwd_impl).


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse(q, k, v, causal: bool = True, block_q: int = 512,
                        block_kv: int = 512, interpret: bool | None = None):
    """Flash attention returning (out [B,S,H,D], lse [B,S,H,1] fp32).

    lse is the per-row logsumexp of the scaled scores — the online-softmax
    merge statistic. Both outputs are differentiable."""
    out, (o3, lse) = _attn_impl(q, k, v, causal, block_q, block_kv,
                                interpret)
    return out, _lse_bshl(lse, q.shape)


def _lse_bshl(lse3, qshape):
    b, s, h, d = qshape
    return lse3[:, :s].reshape(b, h, s, 1).transpose(0, 2, 1, 3)


def _flash_lse_fwd_rule(q, k, v, causal, block_q, block_kv, interpret):
    out, (o3, lse) = _attn_impl(q, k, v, causal, block_q, block_kv,
                                interpret)
    return (out, _lse_bshl(lse, q.shape)), (q, k, v, o3, lse)


def _flash_lse_bwd_rule(causal, block_q, block_kv, interpret, res, g):
    q, k, v, o3, lse = res
    g_out, g_lse = g
    return _flash_bwd_impl(q, k, v, o3, lse, g_out, g_lse, causal, block_q,
                           block_kv, interpret)


flash_attention_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)
