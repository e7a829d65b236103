"""Ring attention: causal attention over a sequence-sharded mesh axis.

First-class sequence/context parallelism (SURVEY.md §5.7) — the capability
the reference could only *host* (DeepSpeed-Ulysses / Megatron-CP ran inside
user containers; the platform just provided pods + NCCL env). Here it is an
op: K/V shards rotate around the `seq` mesh-axis ring via
`jax.lax.ppermute` while each device accumulates online-softmax partial
results for its resident Q shard, so peak memory is O(S/n) per device and
the permute overlaps with the block compute under XLA's async collectives.

Works under `jit` by nesting a `shard_map` over the seq axis; differentiable
(each ring step is rematerialized). The all-to-all "Ulysses" alternative is
`ulysses_attention` below: resharding seq↔heads around a local attention so
existing per-head kernels apply — preferable when heads ≥ ring size and
context is moderate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from kubeflow_tpu.parallel.mesh import current_mesh, dp_like_axes

NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, kv_pos, q_seg=None, kv_seg=None):
    """One blockwise attention contribution with causal masking by absolute
    positions. q [b,s,h,d] (local shard), k/v [b,t,kh,d]. Returns fp32
    (acc [b,s,h,d], m [b,s,h,1], l [b,s,h,1]) partials. `q_seg`/`kv_seg`
    [b,s]/[b,t] additionally confine attention within equal-id spans (the
    packed-sequence mask, matching ops/reference.py semantics)."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    group = h // kh
    qg = q.reshape(b, s, kh, group, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bskgt", qg, k.astype(jnp.float32))
    scores = scores / (d ** 0.5)
    mask = q_pos[:, :, None, None, None] >= kv_pos[:, None, None, None, :]
    if q_seg is not None:
        mask &= (q_seg[:, :, None, None, None]
                 == kv_seg[:, None, None, None, :])
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)  # [b,s,kh,g,1]
    # Rows with no visible keys: exp(NEG_INF - NEG_INF) would be 1; zero them
    # via l and guard m so downstream exp() stays finite.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(scores - m_safe) * (m > NEG_INF / 2)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bskgt,btkd->bskgd", p, v.astype(jnp.float32))
    return (acc.reshape(b, s, h, d), m_safe.reshape(b, s, h, 1),
            l.reshape(b, s, h, 1))


def _batch_spec(mesh, axis_name):
    """Shard batch over whichever dp-like axes the mesh actually has
    (never the ring axis itself) — a dedicated single-axis ring mesh
    (kernel tests, standalone CP) leaves batch replicated."""
    return dp_like_axes(mesh, exclude=axis_name) or None


def _merge(carry, update):
    """Merge two online-softmax partials."""
    acc, m, l = carry
    acc_u, m_u, l_u = update
    m_new = jnp.maximum(m, m_u)
    a1 = jnp.exp(m - m_new)
    a2 = jnp.exp(m_u - m_new)
    return acc * a1 + acc_u * a2, m_new, l * a1 + l_u * a2


def _rotate_if(more, operand, axis_name, n):
    """ppermute `operand` one step around the ring when `more` (skipped on
    the final step, whose rotation would be discarded)."""
    def rotate(o):
        perm = [(j, (j + 1) % n) for j in range(n)]
        return jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), o)

    return jax.lax.cond(more, rotate, lambda o: o, operand)


def _merge_lse(carry, update):
    """Merge two NORMALIZED partials (o, lse): o fp32 [b,s,h,d], lse fp32
    [b,s,h,1]. o·exp(lse) recovers the unnormalized accumulator, so the
    stable combine is a weighted average with weights exp(lse - max)."""
    o, lse = carry
    o_u, lse_u = update
    m = jnp.maximum(jnp.maximum(lse, lse_u), NEG_INF / 2)
    w1 = jnp.exp(lse - m)
    w2 = jnp.exp(lse_u - m)
    denom = jnp.maximum(w1 + w2, 1e-30)
    return (o * w1 + o_u * w2) / denom, m + jnp.log(denom)


def _flash_case_block(q, k, v, case, block_q, block_kv):
    """Fused inner block for ring schedules. `case` (traced int32): 0 = the
    causal mask kills the whole block (skip — zero partials), 1 = diagonal
    block (aligned causal flash), 2 = fully visible (non-causal flash).
    Returns fp32 (o, lse). Offset-ordered layouts (contiguous ring shards,
    zigzag chunks) make every block one of these three cases, so the fused
    kernel needs no position-aware masking."""
    from kubeflow_tpu.ops.flash_attention import flash_attention_lse

    b, s, h, d = q.shape

    def skip(_):
        return (jnp.zeros((b, s, h, d), jnp.float32),
                jnp.full((b, s, h, 1), NEG_INF, jnp.float32))

    def diag(_):
        o, l = flash_attention_lse(q, k, v, True, block_q, block_kv)
        return o.astype(jnp.float32), l

    def full(_):
        o, l = flash_attention_lse(q, k, v, False, block_q, block_kv)
        return o.astype(jnp.float32), l

    return jax.lax.switch(case, (skip, diag, full), None)


def ring_attention_manual(q, k, v, pos, axis_name: str, n: int,
                          segment_ids=None) -> jax.Array:
    """Einsum-inner causal ring body for callers ALREADY inside a manual
    (`shard_map`) region whose mesh includes `axis_name` — context
    parallelism composed inside another manually-partitioned schedule, e.g.
    the pipeline stage region (models/llama_pp.py, CP-inside-PP).

    All shapes are per-shard: q [b_loc, s_loc, H, D], k/v [b_loc, s_loc,
    KH, D], pos [b_loc, s_loc] GLOBAL positions of the resident shard
    (causality is masked by absolute position, so any contiguous or
    permuted layout works). `segment_ids` [b_loc, s_loc] (packed
    documents) rotate around the ring with K/V so every step masks
    within-document exactly. Differentiable (each ring step
    rematerializes)."""
    h, d = q.shape[2], q.shape[3]
    packed = segment_ids is not None

    def step(i, carry):
        acc_m_l, kv, kv_pos, kv_seg = carry
        k_i, v_i = kv
        update = _block_attn(q, k_i, v_i, pos, kv_pos,
                             segment_ids if packed else None, kv_seg)
        acc_m_l = _merge(acc_m_l, update)
        kv, kv_pos, kv_seg = _rotate_if(
            i < n - 1, (kv, kv_pos, kv_seg), axis_name, n)
        return acc_m_l, kv, kv_pos, kv_seg

    b_loc, s_loc = q.shape[0], q.shape[1]
    init = (jnp.zeros((b_loc, s_loc, h, d), jnp.float32),
            jnp.full((b_loc, s_loc, h, 1), NEG_INF, jnp.float32),
            jnp.zeros((b_loc, s_loc, h, 1), jnp.float32))
    # None is a leaf-less pytree node: unpacked callers carry (and
    # ppermute) nothing extra.
    (acc, _, l), _, _, _ = jax.lax.fori_loop(
        0, n, jax.checkpoint(step), (init, (k, v), pos, segment_ids))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention_flash_manual(q, k, v, axis_name: str, n: int,
                                block_q: int = 512,
                                block_kv: int = 512) -> jax.Array:
    """Fused-inner contiguous-layout ring body for manual-region callers
    (see ring_attention_manual). Requires the CONTIGUOUS layout — shard r
    of the ring owns global positions [r*s_loc, (r+1)*s_loc) — because
    causality is derived from ring offsets, not positions."""
    me = jax.lax.axis_index(axis_name)
    b_loc, s_loc, h, d = q.shape

    def step(i, carry):
        (o, lse), kv = carry
        k_i, v_i = kv
        src = jnp.mod(me - i, n)  # origin shard of the resident KV
        case = jnp.where(src == me, 1,
                         jnp.where(src < me, 2, 0)).astype(jnp.int32)
        update = _flash_case_block(q, k_i, v_i, case, block_q, block_kv)
        o, lse = _merge_lse((o, lse), update)
        kv = _rotate_if(i < n - 1, kv, axis_name, n)
        return (o, lse), kv

    init = (jnp.zeros((b_loc, s_loc, h, d), jnp.float32),
            jnp.full((b_loc, s_loc, h, 1), NEG_INF, jnp.float32))
    (o, _), _ = jax.lax.fori_loop(
        0, n, jax.checkpoint(step), (init, (k, v)))
    return o.astype(q.dtype)


def ring_attention(q, k, v, axis_name: str = "seq",
                   positions: jax.Array | None = None,
                   mesh=None, inner: str = "einsum",
                   block_q: int = 512, block_kv: int = 512) -> jax.Array:
    """Causal ring attention. q [B,S,H,D], k/v [B,S,KH,D] — S is the GLOBAL
    sequence; arrays may be traced under jit with any sharding, the inner
    shard_map forces P(axis_name) on dim 1. `positions` defaults to
    arange(S) broadcast over batch (standard packing comes later).

    inner="flash" runs the fused Pallas kernel per ring step (ops/ROADMAP
    item: no O(s_loc·t_loc) score materialization): with the contiguous
    layout each incoming KV shard is entirely before/at/after the resident
    Q shard, so the step is a skip / causal / full flash call selected by
    ring offset. Requires default positions (the layout IS the mask)."""
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ring_attention needs a mesh (with mesh: ...)")
    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    if inner == "flash":
        if positions is not None:
            raise ValueError(
                "inner='flash' derives causality from the contiguous ring "
                "layout; custom positions need inner='einsum'")
        return _ring_attention_flash(q, k, v, axis_name, mesh, n,
                                     block_q, block_kv)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
    if n == 1:
        from kubeflow_tpu.ops.reference import naive_attention
        return naive_attention(q, k, v, causal=True, positions_q=positions,
                               positions_kv=positions)

    # Batch stays sharded over the dp-like axes — replicating it here would
    # all-gather the global batch onto every seq-ring member.
    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)
    pos_spec = P(_batch_spec(mesh, axis_name), axis_name)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec, spec, spec, pos_spec),
        out_specs=spec, check_vma=False)
    def _ring(q, k, v, pos):
        # All shapes here are per-shard: s_loc = S / n, b_loc = B / dp.
        return ring_attention_manual(q, k, v, pos, axis_name, n)

    return _ring(q, k, v, positions)


def _ring_attention_flash(q, k, v, axis_name, mesh, n, block_q, block_kv):
    """Contiguous-layout ring with the fused flash inner block. Shard r of
    the ring owns positions [r·s_loc, (r+1)·s_loc); after i rotations the
    resident KV originates from shard (me - i) mod n, so the whole step is
    before/at/after the Q shard — see _flash_case_block."""
    if n == 1:
        from kubeflow_tpu.ops.flash_attention import flash_attention_on_mesh
        return flash_attention_on_mesh(q, k, v, mesh, block_q=block_q,
                                       block_kv=block_kv)

    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def _ring(q, k, v):
        return ring_attention_flash_manual(q, k, v, axis_name, n,
                                           block_q, block_kv)

    return _ring(q, k, v)


def zigzag_indices(s: int, n: int) -> jax.Array:
    """Zigzag sequence layout for a ring of n devices (SURVEY.md §5.7
    "causal load-balance"): the sequence splits into 2n chunks and shard i
    holds chunks (i, 2n-1-i) — one early, one late — so every ring member
    owns the same amount of causally-visible work: sum over its chunks of
    (chunk_id+1) = (i+1) + (2n-i) = 2n+1, constant in i. Contiguous
    layout instead gives member i work ∝ i+1: the last member does n× the
    first's, and under lockstep SPMD the ring runs at the slowest
    member's pace.

    Returns the permutation `idx` such that `x[:, idx]` is zigzag-ordered;
    invert with jnp.argsort(idx)."""
    if s % (2 * n):
        raise ValueError(f"seq len {s} must divide 2*ring ({2 * n})")
    c = s // (2 * n)
    chunks = jnp.arange(s, dtype=jnp.int32).reshape(2 * n, c)
    order = []
    for i in range(n):
        order += [i, 2 * n - 1 - i]
    return chunks[jnp.asarray(order)].reshape(-1)


def _maybe_block_attn(q, k, v, q_pos, kv_pos):
    """_block_attn, skipped entirely (zero partials) when the causal mask
    kills the whole block — the predicate comes from absolute positions, so
    skipping can never change numerics, only save the dense FLOPs."""
    b, s, h, d = q.shape

    def compute(_):
        return _block_attn(q, k, v, q_pos, kv_pos)

    def skip(_):
        return (jnp.zeros((b, s, h, d), jnp.float32),
                jnp.full((b, s, h, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, s, h, 1), jnp.float32))

    visible = jnp.max(q_pos) >= jnp.min(kv_pos)
    return jax.lax.cond(visible, compute, skip, None)


def zigzag_ring_attention(q, k, v, axis_name: str = "seq", mesh=None,
                          pre_permuted: bool = False,
                          inner: str = "einsum",
                          block_q: int = 512,
                          block_kv: int = 512) -> jax.Array:
    """Causal ring attention with the zigzag layout. Inputs/outputs are in
    NORMAL sequence order unless `pre_permuted` (the efficient path: lay
    the batch out with zigzag_indices in the input pipeline and skip the
    runtime gather). Each ring step splits the resident Q and incoming KV
    into their two chunks and computes only the causally-visible
    sub-blocks — ~2× less dense work at the lockstep pace vs the
    contiguous schedule.

    inner="flash": zigzag chunks are contiguous position ranges, so every
    (q chunk, kv chunk) sub-block is skip / aligned-causal / full — the
    fused Pallas kernel serves all of them (_flash_case_block)."""
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("zigzag_ring_attention needs a mesh")
    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    if n == 1:
        from kubeflow_tpu.ops.reference import naive_attention
        return naive_attention(q, k, v, causal=True)

    idx = zigzag_indices(s, n)
    if not pre_permuted:
        q, k, v = (x[:, idx] for x in (q, k, v))
    if inner == "flash":
        out = _zigzag_ring_flash(q, k, v, axis_name, mesh, n,
                                 block_q, block_kv)
        return out if pre_permuted else out[:, jnp.argsort(idx)]
    positions = jnp.broadcast_to(idx[None].astype(jnp.int32), (b, s))

    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)
    pos_spec = P(_batch_spec(mesh, axis_name), axis_name)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec, spec, spec, pos_spec),
        out_specs=spec, check_vma=False)
    def _ring(q, k, v, pos):
        b_loc, s_loc = q.shape[0], q.shape[1]
        half = s_loc // 2  # chunk boundary inside the zigzag shard

        def split(x):
            return x[:, :half], x[:, half:]

        q_lo, q_hi = split(q)
        p_lo, p_hi = split(pos)

        def step(i, carry):
            (lo_part, hi_part), kv, kv_pos = carry
            k_i, v_i = kv
            k_lo, k_hi = split(k_i)
            v_lo, v_hi = split(v_i)
            kp_lo, kp_hi = split(kv_pos)
            # 4 sub-blocks; fully-masked ones cost ~nothing (lax.cond).
            for kk, vv, kp in ((k_lo, v_lo, kp_lo), (k_hi, v_hi, kp_hi)):
                lo_part = _merge(lo_part,
                                 _maybe_block_attn(q_lo, kk, vv, p_lo, kp))
                hi_part = _merge(hi_part,
                                 _maybe_block_attn(q_hi, kk, vv, p_hi, kp))

            kv, kv_pos = _rotate_if(i < n - 1, (kv, kv_pos), axis_name, n)
            return (lo_part, hi_part), kv, kv_pos

        def zero_part(width):
            return (jnp.zeros((b_loc, width, h, d), jnp.float32),
                    jnp.full((b_loc, width, h, 1), NEG_INF, jnp.float32),
                    jnp.zeros((b_loc, width, h, 1), jnp.float32))

        init = (zero_part(half), zero_part(s_loc - half))
        (lo, hi), _, _ = jax.lax.fori_loop(
            0, n, jax.checkpoint(step), (init, (k, v), pos))

        def finish(part):
            acc, _, l = part
            return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

        return jnp.concatenate([finish(lo), finish(hi)], axis=1)

    out = _ring(q, k, v, positions)
    if pre_permuted:
        return out
    return out[:, jnp.argsort(idx)]


def _zigzag_ring_flash(q, k, v, axis_name, mesh, n, block_q, block_kv):
    """Zigzag schedule with the fused flash inner block. Shard i holds
    chunks (i, 2n-1-i); chunk c covers positions [c·cs, (c+1)·cs), so
    chunk-id comparison decides each sub-block's case."""
    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def _ring(q, k, v):
        me = jax.lax.axis_index(axis_name)
        b_loc, s_loc, h, d = q.shape
        half = s_loc // 2

        def split(x):
            return x[:, :half], x[:, half:]

        q_lo, q_hi = split(q)
        qc_lo, qc_hi = me, 2 * n - 1 - me  # chunk ids of the two q halves

        def case(qc, kc):
            return jnp.where(qc == kc, 1,
                             jnp.where(qc > kc, 2, 0)).astype(jnp.int32)

        def step(i, carry):
            (lo, hi), kv = carry
            k_i, v_i = kv
            src = jnp.mod(me - i, n)
            k_lo, k_hi = split(k_i)
            v_lo, v_hi = split(v_i)
            for kk, vv, kc in ((k_lo, v_lo, src), (k_hi, v_hi, 2 * n - 1 - src)):
                lo = _merge_lse(lo, _flash_case_block(
                    q_lo, kk, vv, case(qc_lo, kc), block_q, block_kv))
                hi = _merge_lse(hi, _flash_case_block(
                    q_hi, kk, vv, case(qc_hi, kc), block_q, block_kv))

            kv = _rotate_if(i < n - 1, kv, axis_name, n)
            return (lo, hi), kv

        def zero_part(width):
            return (jnp.zeros((b_loc, width, h, d), jnp.float32),
                    jnp.full((b_loc, width, h, 1), NEG_INF, jnp.float32))

        init = (zero_part(half), zero_part(s_loc - half))
        ((o_lo, _), (o_hi, _)), _ = jax.lax.fori_loop(
            0, n, jax.checkpoint(step), (init, (k, v)))
        return jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype)

    return _ring(q, k, v)


def ulysses_attention(q, k, v, axis_name: str = "seq",
                      mesh=None) -> jax.Array:
    """DeepSpeed-Ulysses-style context parallelism: all_to_all seq↔heads so
    each device holds full sequence for H/n heads, runs local (flash)
    attention, then all_to_all back. Requires H % n == 0 and KH % n == 0."""
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ulysses_attention needs a mesh")
    n = mesh.shape[axis_name]
    if n == 1:
        from kubeflow_tpu.ops.reference import naive_attention
        return naive_attention(q, k, v, causal=True)

    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def _ulysses(q, k, v):
        # [b, s/n, h, d] -> all_to_all -> [b, s, h/n, d]
        def scatter_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)

        def gather_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                      concat_axis=2, tiled=True)

        ql, kl, vl = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        # Forward AND backward run the fused Pallas kernels (O(S) memory;
        # flash_attention's custom VJP is the two-pass dq/dkv recipe).
        from kubeflow_tpu.ops.flash_attention import flash_attention
        out = flash_attention(ql, kl, vl, True)
        return gather_heads(out)

    return _ulysses(q, k, v)
