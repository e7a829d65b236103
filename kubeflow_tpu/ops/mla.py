"""Latent attention's decode core, absorbed: one query a row against that
row's cached latent rows, read in place through the block table of the paged
pool.

A cached row of a token is `[c ‖ k_r]`: the `rank` values of its normed key
and value latent and the `rope` values of its rotated shared key part
(DeepSeek-V3, arXiv:2412.19437 section 2.1; models/joyai.py writes them),
followed by zeros up to a whole number of 128-lane tiles (`row_width`: the
chip's tiled HBM layout pads the row so whatever its logical width, and a
copy of a block can only be cut on a tile's edge). With the key half of the
up-projection folded into the query (`q~_h = q_nope,h W_k,h^T`, the caller's
matmul) every head scores the same row,

    score_h(s) = scale * (q~_h . c(s) + q_rope,h . k_r(s)),
    u_h = sum_s softmax_s(score_h) c(s)          [rank values a head]

and the value half is applied to `u` afterwards (the caller's, again). So a
row's state crosses HBM once a step for all heads together: `absorbed_step`
(scope `mla_core_decode`) is a Pallas kernel (`mla_step`) that walks the row's
blocks a group at a time, each group copied from the pool into one of two VMEM
slots while the one before it is multiplied, under a running softmax. The
query (padded with zeros as the rows are) scores a group in one matmul over
the whole row; the probabilities then multiply the rows' first `rank` columns
as they lie. Operands stay in the pool's dtype with fp32 accumulation; scores
and the softmax's statistics are fp32.

`absorbed_rows` is the same arithmetic in plain `jax.numpy` over rows that
are already contiguous: what the tests hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.utils.devices import on_tpu

NEG_INF = -1e30
LANES = 128


def row_width(rank: int, rope: int) -> int:
    """Values a cached row takes: `rank + rope`, up to whole lane tiles."""
    return -(-(rank + rope) // LANES) * LANES


def absorbed_rows(q: jax.Array, rows: jax.Array, n_rows: jax.Array, *,
                  rank: int, scale: float) -> jax.Array:
    """q [B, H, W]; rows [B, T, W] (W = `row_width`, both zero past rank +
    rope), of which row b reads the first n_rows[b]. Returns u [B, H, rank]
    in q's dtype."""
    st = scale * jnp.einsum("bhw,btw->bht", q, rows,
                            preferred_element_type=jnp.float32)
    seen = jnp.arange(rows.shape[1])[None] < n_rows[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], st, NEG_INF), axis=-1)
    return jnp.einsum("bht,btc->bhc", p.astype(rows.dtype),
                      rows[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _step_kernel(tables_ref, n_ref, q_ref, pool_hbm, o_ref, buf, sem, *,
                 group: int, rank: int, scale: float):
    """One row of the decode batch: its blocks `group` at a time, the copy
    of group i + 1 started before group i is waited for."""
    b = pl.program_id(0)
    _, _, bs, width = buf.shape
    rows = group * bs
    n = n_ref[b]
    total = (n + rows - 1) // rows

    def copies(i, slot):
        for g in range(group):
            yield pltpu.make_async_copy(
                pool_hbm.at[tables_ref[b, i * group + g]], buf.at[slot, g],
                sem.at[slot])

    for c in copies(0, 0):
        c.start()
    q = q_ref[0]
    h = q.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)

    def body(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < total)
        def _():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        kv = buf[slot].reshape(rows, width)
        c_rows = kv[:, :rank]
        st = scale * jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [H, rows]
        seen = col < n - i * rows
        st = jnp.where(seen, st, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(st, axis=1, keepdims=True))
        p = jnp.where(seen, jnp.exp(st - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(c_rows.dtype), c_rows,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, total, body,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, rank), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def absorbed_step(q: jax.Array, pool: jax.Array, tables: jax.Array,
                  n_rows: jax.Array, *, rank: int, scale: float,
                  group: int = 16,
                  interpret: bool | None = None) -> jax.Array:
    """One query a row. q [B, H, W] (W = `row_width`), the nope part already
    multiplied by the key half of the up-projection, zeros past rank + rope;
    pool [N, bs, W], the paged pool with layers and blocks on one axis and
    `rank` a multiple of the lane tile on a TPU; tables [B, nb]
    the row's blocks in that pool in position order. Row b reads its first
    n_rows[b] >= 1 rows in place through the table: one Pallas kernel, no
    copy of the state outside it, and no read of a group of `group` blocks
    that holds none of the row's. Returns u [B, H, rank]. `interpret` as
    the flash kernel's: None compiles on a TPU, interprets elsewhere."""
    with jax.named_scope("mla_core_decode"):
        b, h, width = q.shape
        bs = pool.shape[1]
        tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % group)))
        return pl.pallas_call(
            functools.partial(_step_kernel, group=group, rank=rank,
                              scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b,),
                in_specs=[pl.BlockSpec((1, h, width),
                                       lambda i, *_: (i, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, h, rank),
                                       lambda i, *_: (i, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, group, bs, width), pool.dtype),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
            interpret=(not on_tpu()) if interpret is None else interpret,
            name="mla_step",
        )(tables.astype(jnp.int32), n_rows.astype(jnp.int32), q, pool)
