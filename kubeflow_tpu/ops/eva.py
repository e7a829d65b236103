"""EVA attention as EvaByte specialises it: exact keys and values for the
open window, one summary key and value per closed chunk.

After Zheng et al., *Efficient Attention via Control Variates*
(arXiv:2302.04542), with the sampled random feature replaced by two learned
vectors a head (`mu`, `phi`), as EvaByte's release has them. With `s = d^-1/2`,
window `W`, chunk `C`, a chunk `c` of 16 consecutive rotated keys `k_j` and
values `v_j`:

    k~_c = sum_j softmax_j(s mu.k_j) k_j
    v~_c = sum_j softmax_j(s phi.k_j - (s/2) |k_j|^2) v_j

and a query at position t attends, under ONE softmax, to the exact rows of
its own window up to t (the window is block-local, it does not slide) and to
the summaries of every chunk of every window before it.

Three forms, each under its own `jax.named_scope`:

  `pool_chunks`    (`eva_pool`)          chunks of rows -> summary rows
  `attend_piece`   (`eva_core_prefill`)  one piece of at most one window:
                   flash attention inside the piece, joined with the given
                   summary rows through the kernel's row log-sum-exp
  `attend_step`    (`eva_core_decode`)   one query a row against that row's
                   open-window rows and summary rows, read through the
                   block tables of the paged pool: a Pallas kernel
                   (`eva_step`) that copies the row's blocks from HBM a
                   group at a time, two groups in flight

`attend_sequence` strings `attend_piece` and `pool_chunks` over a whole
sequence (the model's full forward; serving goes piece by piece). Matmul
operands stay in the inputs' dtype with fp32 accumulation; the softmax's
statistics and the pooling weights are fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.flash_attention import flash_attention_lse
from kubeflow_tpu.utils.devices import on_tpu

NEG_INF = -1e30


def pool_chunks(k: jax.Array, v: jax.Array, mu: jax.Array,
                phi: jax.Array) -> tuple[jax.Array, jax.Array]:
    """k, v [..., C, H, D], the rows of one chunk on axis -3; mu, phi
    [H, D]. Returns the chunk's summary key and value [..., H, D] in the
    inputs' dtypes. Elementwise fp32 throughout (no matmul unit: the
    weights would otherwise be rounded to bf16 on a TPU)."""
    with jax.named_scope("eva_pool"):
        s = k.shape[-1] ** -0.5
        k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
        wk = s * jnp.sum(k32 * mu.astype(jnp.float32), axis=-1)
        wv = (s * jnp.sum(k32 * phi.astype(jnp.float32), axis=-1)
              - 0.5 * s * jnp.sum(k32 * k32, axis=-1))
        pk = jax.nn.softmax(wk, axis=-2)[..., None]      # over the C rows
        pv = jax.nn.softmax(wv, axis=-2)[..., None]
        return (jnp.sum(pk * k32, axis=-3).astype(k.dtype),
                jnp.sum(pv * v32, axis=-3).astype(v.dtype))


def attend_piece(q: jax.Array, k: jax.Array, v: jax.Array, sk: jax.Array,
                 sv: jax.Array, n_summary: jax.Array, *,
                 block: int = 512, interpret: bool | None = None) -> jax.Array:
    """One piece of at most one window. q, k, v [B, S, H, D], rotated, the
    piece starting on a window boundary; sk, sv [B, R, H, D] summary rows of
    which row b reads the first n_summary[b]. Causal attention inside the
    piece and the summaries under one softmax: the flash kernel's output is
    re-weighted by its row log-sum-exp against the summaries' own.
    `interpret` is the flash kernel's: None compiles on a TPU and interprets
    anywhere else."""
    with jax.named_scope("eva_core_prefill"):
        s = q.shape[-1] ** -0.5
        o_loc, lse = flash_attention_lse(q, k, v, True, block, block,
                                         interpret)
        lse = lse[..., 0].transpose(0, 2, 1)                 # [B, H, S]
        ls = s * jnp.einsum("bshd,brhd->bhsr", q, sk,
                            preferred_element_type=jnp.float32)
        seen = jnp.arange(sk.shape[1])[None] < n_summary[:, None]
        ls = jnp.where(seen[:, None, None, :], ls, NEG_INF)
        m = jnp.maximum(lse, jnp.max(ls, axis=-1))
        p = jnp.where(seen[:, None, None, :], jnp.exp(ls - m[..., None]), 0.0)
        w_loc = jnp.exp(lse - m)
        num = jnp.einsum("bhsr,brhd->bshd", p.astype(sv.dtype), sv,
                         preferred_element_type=jnp.float32)
        w_loc = w_loc.transpose(0, 2, 1)[..., None]          # [B, S, H, 1]
        den = w_loc + jnp.sum(p, axis=-1).transpose(0, 2, 1)[..., None]
        out = (w_loc * o_loc.astype(jnp.float32) + num) / den
        return out.astype(q.dtype)


def _step_kernel(tables_ref, ne_ref, ns_ref, q_ref, k_hbm, v_hbm, o_ref,
                 kbuf, vbuf, sem, *, group: int, exact_width: int,
                 scale: float):
    """One row of the decode batch: walk its blocks `group` at a time,
    the open window's first and then the summaries', each group copied
    from the pool in HBM into one of two VMEM slots while the one before
    it is multiplied (the copy of group i + 1 starts before group i is
    waited for), under a running softmax.

    A group's rows of all heads lie on one axis, `rows * H` wide. The
    scores are one matmul of all H queries against it, of which query h
    keeps the columns of its own head (H times the work the scores need,
    on a matmul unit that would otherwise idle: the step is bound by the
    copies); the probabilities, zero off a query's own head, then multiply
    the values as they lie."""
    b = pl.program_id(0)
    _, _, bs, h, d = kbuf.shape
    rows = group * bs
    ne, ns = ne_ref[b], ns_ref[b]
    ge = (ne + rows - 1) // rows
    total = ge + (ns + rows - 1) // rows

    def place(i):
        """(first table entry, valid rows) of group i."""
        exact = i < ge
        return (jnp.where(exact, i * group, exact_width + (i - ge) * group),
                jnp.where(exact, ne - i * rows, ns - (i - ge) * rows))

    def copies(i, slot):
        first, _ = place(i)
        for g in range(group):
            block = tables_ref[b, first + g]
            yield pltpu.make_async_copy(k_hbm.at[block], kbuf.at[slot, g],
                                        sem.at[slot, 0])
            yield pltpu.make_async_copy(v_hbm.at[block], vbuf.at[slot, g],
                                        sem.at[slot, 1])

    for c in copies(0, 0):
        c.start()
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows * h), 1)
    own = (col % h) == jax.lax.broadcasted_iota(jnp.int32, (h, rows * h), 0)
    q = q_ref[0]

    def body(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < total)
        def _():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k2 = kbuf[slot].reshape(rows * h, d)
        v2 = vbuf[slot].reshape(rows * h, d)
        st = scale * jax.lax.dot_general(
            q, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [H, rows * H]
        seen = own & (col // h < place(i)[1])
        st = jnp.where(seen, st, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(st, axis=1, keepdims=True))
        p = jnp.where(seen, jnp.exp(st - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v2.dtype), v2,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, total, body,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def attend_step(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                blocks: jax.Array, n_exact: jax.Array,
                n_summary: jax.Array, exact_blocks: int, *, group: int = 8,
                interpret: bool | None = None) -> jax.Array:
    """One query a row. q [B, H, D]; pool_k, pool_v [N, bs, H, D], the
    paged pool with layers and blocks on one axis; blocks [B, nb] the
    row's blocks in that pool, the first `exact_blocks` its open window in
    position order, the rest its summary blocks. Row b reads the first
    n_exact[b] >= 1 rows of the former and the first n_summary[b] of the
    latter, in place through the table: one Pallas kernel
    (`_step_kernel`), no copy of the state outside it, and no read of a
    group of `group` blocks that holds none of the row's. `interpret` as
    the flash kernel's: None compiles on a TPU, interprets elsewhere."""
    with jax.named_scope("eva_core_decode"):
        b, h, d = q.shape
        bs = pool_k.shape[1]

        def whole_groups(t):
            return jnp.pad(t, ((0, 0), (0, -t.shape[1] % group)))

        exact = whole_groups(blocks[:, :exact_blocks])
        tables = jnp.concatenate(
            [exact, whole_groups(blocks[:, exact_blocks:])], axis=1)
        row = pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0))
        return pl.pallas_call(
            functools.partial(_step_kernel, group=group,
                              exact_width=exact.shape[1], scale=d ** -0.5),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b,),
                in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=row,
                scratch_shapes=[
                    pltpu.VMEM((2, group, bs, h, d), pool_k.dtype),
                    pltpu.VMEM((2, group, bs, h, d), pool_v.dtype),
                    pltpu.SemaphoreType.DMA((2, 2))]),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=(not on_tpu()) if interpret is None else interpret,
            name="eva_step",
        )(tables.astype(jnp.int32), n_exact.astype(jnp.int32),
          n_summary.astype(jnp.int32), q, pool_k, pool_v)


def attend_sequence(q: jax.Array, k: jax.Array, v: jax.Array, mu: jax.Array,
                    phi: jax.Array, *, window: int, chunk: int) -> jax.Array:
    """A whole sequence from position 0, q, k, v [B, T, H, D] rotated:
    window by window, each a piece that reads the summaries of the windows
    before it; a window is pooled once it is full."""
    b, t, h, d = k.shape
    per = window // chunk
    sk = jnp.zeros((b, 0, h, d), k.dtype)
    sv = jnp.zeros((b, 0, h, d), v.dtype)
    outs = []
    for w0 in range(0, t, window):
        kw, vw = k[:, w0:w0 + window], v[:, w0:w0 + window]
        n = jnp.full((b,), sk.shape[1], jnp.int32)
        # A first window has no summaries; one masked row keeps the shapes.
        outs.append(attend_piece(
            q[:, w0:w0 + window], kw, vw,
            sk if sk.shape[1] else jnp.zeros((b, 1, h, d), k.dtype),
            sv if sv.shape[1] else jnp.zeros((b, 1, h, d), v.dtype), n))
        if kw.shape[1] == window and w0 + window < t:
            ks, vs = pool_chunks(kw.reshape(b, per, chunk, h, d),
                                 vw.reshape(b, per, chunk, h, d), mu, phi)
            sk = jnp.concatenate([sk, ks], axis=1)
            sv = jnp.concatenate([sv, vs], axis=1)
    return jnp.concatenate(outs, axis=1)
