"""Kimi Delta Attention core: the gated delta rule with a per-channel decay,
in its chunked (WY) form, as two Pallas TPU kernels behind one custom_vjp.

Per head, with state S in R^{dk x dv}, S_0 = 0:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                      a_t = exp(g_t) in (0, 1]^{dk}

Within a chunk of C steps write G_i = sum_{j<=i} g_j (so exp(G_i) is the
decay from the chunk's start to step i) and u_i = b_i (v_i - (Diag(a_i)
S_{i-1})^T k_i), the "pseudo-value" that makes the update rank one:
S_i = Diag(a_i) S_{i-1} + k_i u_i^T. Unrolled from the chunk's first state
S_0 this gives, with A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc) (j < i)
and B_ij = sum_c q_ic k_jc exp(G_ic - G_jc) (j <= i):

    (I + A) U = Diag(b) (V - (K * exp(G)) S_0)
    O         = (Q * exp(G)) S_0 + B U
    S_C       = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

so a chunk costs a unit-lower-triangular solve and a few small matmuls, and
only the chunk-to-chunk state is sequential.

**Every exponent here is non-positive.** With A_log drawn as the published
models draw it, g reaches -10 a step, and the textbook factorisation
(q * e^{G_i}) (k * e^{-G_j})^T overflows fp32 inside one chunk. Instead the
chunk is cut into sub-chunks of 16: a pair (i, j) in the same sub-chunk gets
its exp(G_i - G_j) computed pairwise; a pair in different sub-chunks is
split at the *row's* sub-chunk start s, exp(G_i - G_s) * exp(G_s - G_j),
both factors at most one, which is again a matmul. Masked entries clamp the
exponent at 0 before the exp, so nothing above the diagonal is ever inf.

The triangular system is solved by blocks of the same 16: each diagonal
block's inverse is the finite Neumann product (I - L)(I + L^2)(I + L^4)
(I + L^8) (L is strictly lower, L^16 = 0), then block forward substitution
with that inverse spread over it: u_a = (inv rhs)_a - sum_{b<a} (inv A)_ab
u_b.

**What runs where.** `kda_fwd` walks the grid (batch, head group, chunk),
the chunks in turn ("arbitrary") with the group's states [dv, dk] fp32 in
VMEM scratch; a step reads the chunk's rows of q, k, g, v in place from the
[B, T, H * d] arrays (a block of C rows by the group's lanes: no transpose
on either side of the call) and beta, computes everything above in VMEM and
registers, and writes o and the state the chunk started from. `kda_bwd`
walks the same grid from the last chunk with dS in the scratch: it reads
the chunk's inputs, its start state and dO, takes `jax.vjp` of the same
chunk function inside the kernel body (so the chunk is recomputed there,
never stored) and writes dq, dk, dv, dg, dbeta. Only those arrays and the
start states (dk * dv * 4 bytes a chunk a head, live while the layer's
backward runs) cross HBM. A head group is 128 / C heads (two at C = 64),
stacked: time runs along all 128 lanes in the pairwise part, and the
matmuls that do not involve a head's state are shared, block-diagonal.

**Inside a chunk.** The pairs of a sub-chunk are taken by diagonals with
time along the lanes and the channels down the rows: the d-th diagonal is a
lane rotation by d, its sum over the channels adds rows (vector adds, not
lane reductions). The Neumann products stay in that storage by diagonals,
(xy)[d] = sum_e x[e] * roll(y[d - e], e): multiplies and sums on the vector
unit. Nothing of a chunk that is larger than its inputs exists outside the
kernel.

**Precision**, as `assumed.precision` of the Kimi configuration states it or
finer: everything is held in fp32. The matmuls (`_dot`: the cross-sub-chunk
products, the products with the state, inv A, inv rhs, the substitution, B U,
the state's update, and the same in the backward) are three bf16 passes with
fp32 accumulation, hi*hi + hi*lo + lo*hi, what `Precision.HIGH` is, written
out because Mosaic in jax 0.9.0 lowers only DEFAULT and HIGHEST (interpreted
they are the host's fp32 product, what HIGH is on a CPU). G's
cumulative sum is a 0/1 triangular matrix times g cut into *three* bf16
parts: every product exact, the sums fp32 (G passes -600 in a chunk; two
parts would put 1e-4 on a decay factor, tests pin it). The pairwise
products, the Neumann products and beta's placement are fp32 multiplies and
sums on the vector unit. Against the recurrence at `highest` on the chip
the outputs and all five gradients read 5e-6 (the `jnp` form this replaced:
1.3e-5; my chip run, PR 29).

On anything but a TPU the same kernels run interpreted (`on_tpu()`, as in
ops/flash_attention.py). Compiled, the group's lanes must be whole tiles:
dk and dv multiples of 128 / (heads in a group), or every head in one group;
the toy widths of the tests other than (3 heads, dv 8) compile too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.utils.devices import on_tpu

_F32, _BF16 = jnp.float32, jnp.bfloat16
#: Contraction dimensions of a @ b, a @ b.T and a.T @ b.
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


# -- pieces of a chunk, each differentiable inside a kernel body -------------

def _bf16_parts(x, n: int):
    """x (fp32) as a sum of `n` bf16 arrays, largest first: two parts hold 16
    bits of the mantissa, three all 24."""
    parts = []
    for _ in range(n):
        p = x.astype(_BF16)
        parts.append(p)
        x = x - p.astype(_F32)
    return parts


def _pass(a, b, dims):
    """One pass of the matrix unit: bf16 operands, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dot(a, b, dims, interpret: bool):
    """A 2-D matmul of fp32 operands as `Precision.HIGH` does it. Compiled,
    that is three bf16 passes (hi*hi + hi*lo + lo*hi, about 2^-17 relative),
    written out because Mosaic in jax 0.9.0 takes DEFAULT or HIGHEST only;
    interpreted, the host's own fp32 product, as HIGH is on a CPU. With a
    VJP of its own, so that cotangents are multiplied the same way and never
    rounded to the bf16 of a part."""
    if interpret:
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=_F32)
    (a0, a1), (b0, b1) = _bf16_parts(a, 2), _bf16_parts(b, 2)
    return _pass(a0, b0, dims) + (_pass(a0, b1, dims) + _pass(a1, b0, dims))


def _dot_fwd(a, b, dims, interpret):
    return _dot(a, b, dims, interpret), (a, b)


def _dot_bwd(dims, interpret, res, ct):
    a, b = res
    dot = functools.partial(_dot, interpret=interpret)
    if dims == _NN:      # c = a b:   da = ct b^T, db = a^T ct
        return dot(ct, b, _NT), dot(a, ct, _TN)
    if dims == _NT:      # c = a b^T: da = ct b,   db = ct^T a
        return dot(ct, b, _NN), dot(ct, a, _TN)
    return dot(b, ct, _NT), dot(a, ct, _NN)  # c = a^T b


_dot.defvjp(_dot_fwd, _dot_bwd)


def _ones_dot(ones, x, dims):
    """`ones` (0s and 1s, exact in bf16) times an fp32 x split three ways:
    every product is exact and the sums are fp32, as on the vector unit."""
    return sum(_pass(ones, p, dims) for p in _bf16_parts(x, 3))


@jax.custom_vjp
def _cumsum(tri, x):
    """Cumulative sum down the rows of x, within the blocks that the 0/1
    matrix `tri` marks (tri[i, j] = 1 where row j is summed into row i)."""
    return _ones_dot(tri, x, _NN)


_cumsum.defvjp(lambda tri, x: (_ones_dot(tri, x, _NN), tri),
               lambda tri, ct: (None, _ones_dot(tri, ct, _TN)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _roll(x, shift: int, axis: int, interpret: bool):
    """x rolled towards higher indices (`jnp.roll`), as the TPU's rotate
    where the kernel is compiled; its VJP rolls back."""
    if not shift:
        return x
    if interpret:
        return jnp.roll(x, shift, axis)
    return pltpu.roll(x, shift, axis)


_roll.defvjp(
    lambda x, shift, axis, interpret: (_roll(x, shift, axis, interpret), None),
    lambda shift, axis, interpret, _, ct: (
        _roll(ct, (ct.shape[axis] - shift) % ct.shape[axis], axis,
              interpret),))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _diag_product(x, y, roll):
    """Product of two block-lower-triangular matrices held by diagonals:
    x[d, n] is the entry (n, n - d) of the block that n lies in, 0 where
    n - d falls before the block's start. (xy)[d, n] = sum_{e <= d} x[e, n]
    y[d - e, n - e]: multiplies and sums on the vector unit, exact fp32."""
    sub = x.shape[0]
    drow = _iota(x.shape, 0)
    acc = x[0:1] * y
    for e in range(1, sub):
        ye = roll(roll(y, e, 1), e, 0)
        acc = acc + x[e:e + 1] * jnp.where(drow >= e, ye, 0.0)
    return acc


def _from_diagonals(diag):
    """The block-diagonal matrix that `diag` [sub, n] holds by diagonals (see
    `_diag_product`): out[i, j] = diag[i - j, i], 0 off the blocks."""
    sub, n = diag.shape
    diff = _iota((n, n), 1) - _iota((n, n), 0)
    out = jnp.zeros((n, n), _F32)
    for d in range(sub):
        out = out + jnp.where(diff == d, diag[d:d + 1], 0.0)
    return out.T  # built with i along the lanes, as `diag` has it


def _chunk(q, k, v, g, beta, st, *, pack: int, sub: int, interpret: bool):
    """One chunk of `pack` heads. q, k, g [C, pack * dk]; v [C, pack * dv];
    beta [1, pack * C]; st [pack * dv, dk], each head's state transposed.
    Returns (o [C, pack * dv], the next st).

    The heads are stacked: n = pack * C rows (head, step), the chunk's
    matrices [n, n] and block-diagonal over the heads, so that both heads
    share every matmul whose other operand is not the head's state."""
    roll = functools.partial(_roll, interpret=interpret)

    def dot(a, b, dims=_NN):
        return _dot(a, b, dims, interpret)

    c = q.shape[0]
    dk, dv = q.shape[1] // pack, v.shape[1] // pack
    nb, n = c // sub, pack * c

    def stack(x, d):  # [C, pack * d] -> [pack * C, d], head after head
        return jnp.concatenate(
            [x[:, h * d:(h + 1) * d] for h in range(pack)], axis=0)

    def rows_of(x, a):  # the rows of sub-chunk a, of every head
        return jnp.concatenate(
            [x[h * c + a * sub:h * c + (a + 1) * sub] for h in range(pack)],
            axis=0)

    qs, ks, vs, gs = stack(q, dk), stack(k, dk), stack(v, dv), stack(g, dk)
    r_i, c_i = _iota((n, n), 0), _iota((n, n), 1)
    same_head = r_i // c == c_i // c
    G = _cumsum(((r_i >= c_i) & same_head).astype(_BF16), gs)  # [n, dk], <= 0
    # beta down the rows, as it multiplies a row of A and of the right side.
    bcol = jnp.sum(jnp.where(r_i == c_i, beta, 0.0), axis=1, keepdims=True)

    # Pairs inside a sub-chunk, by diagonals, time along the lanes and the
    # channels down the rows: a diagonal is a roll, the sum over channels
    # adds rows. Every exponent is clamped at 0, so that what the roll brings
    # in from another block (masked below) is never inf.
    qt, kt, gt = qs.T, ks.T, G.T                      # [dk, n]
    valid = _iota((sub, n), 1) % sub >= _iota((sub, n), 0)
    dk_rows, dq_rows = [], []
    for d in range(sub):
        kj = kt if not d else roll(kt, d, 1) * jnp.exp(
            jnp.minimum(gt - roll(gt, d, 1), 0.0))
        dk_rows.append(jnp.sum(kt * kj, axis=0, keepdims=True))
        dq_rows.append(jnp.sum(qt * kj, axis=0, keepdims=True))
    diag_k = jnp.where(valid, jnp.concatenate(dk_rows, axis=0), 0.0)
    diag_q = jnp.where(valid, jnp.concatenate(dq_rows, axis=0), 0.0)

    # (I + L)^-1 of the diagonal blocks, L strictly lower and L^sub = 0:
    # the finite Neumann product (I - L)(I + L^2)(I + L^4)...
    drow = _iota((sub, n), 0)
    eye = (drow == 0).astype(_F32)
    power = jnp.where(drow >= 1, beta * diag_k, 0.0)
    inv = eye - power
    for _ in range(max(sub.bit_length() - 2, 0)):
        power = _diag_product(power, power, roll)
        inv = _diag_product(inv, eye + power, roll)
    inv = _from_diagonals(inv)                        # [n, n]

    # Pairs in different sub-chunks, split at the row's sub-chunk start s:
    # exp(G_i - G_s) exp(G_s - G_j), both factors at most one.
    zeros = jnp.zeros((pack * sub, n), _F32)
    off_k, off_q = [zeros], [zeros]
    for a in range(1, nb):
        start = jnp.concatenate(  # G_s: each head's G through the row before
            [jnp.broadcast_to(G[h * c + a * sub - 1:h * c + a * sub], (c, dk))
             for h in range(pack)], axis=0)
        row = jnp.exp(rows_of(G - start, a))
        col = jnp.exp(jnp.minimum(start - G, 0.0))
        off = dot(jnp.concatenate(
            [rows_of(ks, a) * row, rows_of(qs, a) * row], axis=0),
            ks * col, _NT)                            # [2 pack sub, n]
        before = rows_of(same_head & (c_i % c < a * sub), a)
        off_k.append(jnp.where(before, off[:pack * sub], 0.0))
        off_q.append(jnp.where(before, off[pack * sub:], 0.0))

    def unstack(blocks):  # nb blocks of (head, row) -> [n, .] (head, step)
        return jnp.concatenate(
            [x[h * sub:(h + 1) * sub] for h in range(pack) for x in blocks],
            axis=0)

    b_m = unstack(off_q) + _from_diagonals(diag_q)    # [n, n]
    # Block forward substitution for (I + A) u = rhs with inv spread over
    # it: u_a = (inv rhs)_a - sum_{b < a} (inv A_off)_ab u_b.
    inv_a = dot(inv, unstack(off_k) * bcol)

    gam = jnp.exp(G)                                  # decay from the start
    kg, qg = ks * gam, qs * gam
    on_state = [dot(jnp.concatenate(                  # [2 C, dv] a head
        [kg[h * c:(h + 1) * c], qg[h * c:(h + 1) * c]], axis=0),
        st[h * dv:(h + 1) * dv], _NT) for h in range(pack)]
    y = dot(inv, bcol * (vs - jnp.concatenate(
        [x[:c] for x in on_state], axis=0)))
    us = [rows_of(y, 0)]
    for a in range(1, nb):
        done = unstack(us + [jnp.zeros((pack * sub, dv), _F32)] * (nb - a))
        us.append(rows_of(y, a) - dot(rows_of(inv_a, a), done))
    u = unstack(us)                                   # [n, dv]
    o = jnp.concatenate([x[c:] for x in on_state], axis=0) + dot(b_m, u)

    states = []
    for h in range(pack):
        rows = slice(h * c, (h + 1) * c)
        last = G[(h + 1) * c - 1:(h + 1) * c]         # [1, dk]
        states.append(st[h * dv:(h + 1) * dv] * jnp.exp(last)
                      + dot(u[rows], ks[rows] * jnp.exp(last - G[rows]),
                             _TN))
    return (jnp.concatenate([o[h * c:(h + 1) * c] for h in range(pack)],
                            axis=1), jnp.concatenate(states, axis=0))


# -- the two kernels ----------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, st_ref, *,
                chunk_fn):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st = st_ref[...]
    s_ref[...] = st
    o, st_ref[...] = chunk_fn(q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                              b_ref[...], st)
    o_ref[...] = o


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dst_ref, *, chunk_fn):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    _, vjp = jax.vjp(chunk_fn, q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                     b_ref[...], s_ref[...])
    (dq_ref[...], dk_ref[...], dv_ref[...], dg_ref[...], db_ref[...],
     dst_ref[...]) = vjp((do_ref[...], dst_ref[...]))


def _specs(n: int, chunk: int, pack: int, dk: int, dv: int, reverse: bool):
    """Block specs on the grid (batch, head group, chunk): [B, T, H * d]
    arrays read in place, a head group's `pack * d` lanes of a chunk's rows;
    beta [B, H / pack, N, 1, pack * C]; states [B, H / pack, N, pack * dv,
    dk]. `reverse` walks the chunks from the last."""
    def at(i):
        return n - 1 - i if reverse else i

    def wide(d):
        return pl.BlockSpec((None, chunk, pack * d),
                            lambda b, h, i: (b, at(i), h))

    beta = pl.BlockSpec((None, None, None, 1, pack * chunk),
                        lambda b, h, i: (b, h, at(i), 0, 0))
    state = pl.BlockSpec((None, None, None, pack * dv, dk),
                         lambda b, h, i: (b, h, at(i), 0, 0))
    return wide(dk), wide(dv), beta, state


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=[scratch], name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


# The two calls are jitted on their own: tracing a chunk and lowering it for
# Mosaic takes seconds, and every layer of a model, its rematerialised
# forward included, then shares one trace of each kernel.

@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _forward(q, k, v, g, beta, chunk, sub, pack, interpret):
    """q, k, g [B, T, H * dk], v [B, T, H * dv], beta [B, H / pack, N, 1,
    pack * C], T = N * C. Returns o [B, T, H * dv] and the state every
    chunk started from, [B, H / pack, N, pack * dv, dk]."""
    b = q.shape[0]
    groups, n = beta.shape[1], beta.shape[2]
    dk, dv = q.shape[2] // (groups * pack), v.shape[2] // (groups * pack)
    wide_k, wide_v, beta_s, state_s = _specs(n, chunk, pack, dk, dv, False)
    chunk_fn = functools.partial(_chunk, pack=pack, sub=sub,
                                 interpret=interpret)
    return _call(
        functools.partial(_fwd_kernel, chunk_fn=chunk_fn), "kda_fwd",
        (b, groups, n), [wide_k, wide_k, wide_v, wide_k, beta_s],
        [wide_v, state_s],
        [jax.ShapeDtypeStruct(v.shape, _F32),
         jax.ShapeDtypeStruct((b, groups, n, pack * dv, dk), _F32)],
        pltpu.VMEM((pack * dv, dk), _F32), interpret)(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _backward(q, k, v, g, beta, starts, do, chunk, sub, pack, interpret):
    b, groups, n = starts.shape[:3]
    dk, dv = starts.shape[4], starts.shape[3] // pack
    wide_k, wide_v, beta_s, state_s = _specs(n, chunk, pack, dk, dv, True)
    chunk_fn = functools.partial(_chunk, pack=pack, sub=sub,
                                 interpret=interpret)
    return tuple(_call(
        functools.partial(_bwd_kernel, chunk_fn=chunk_fn), "kda_bwd",
        (b, groups, n),
        [wide_k, wide_k, wide_v, wide_k, beta_s, state_s, wide_v],
        [wide_k, wide_k, wide_v, wide_k, beta_s],
        [jax.ShapeDtypeStruct(x.shape, _F32) for x in (q, k, v, g, beta)],
        pltpu.VMEM((pack * dv, dk), _F32), interpret)(
            q, k, v, g, beta, starts, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kda(q, k, v, g, beta, chunk, sub, pack, interpret):
    return _forward(q, k, v, g, beta, chunk, sub, pack, interpret)[0]


def _kda_fwd(q, k, v, g, beta, chunk, sub, pack, interpret):
    o, starts = _forward(q, k, v, g, beta, chunk, sub, pack, interpret)
    return o, (q, k, v, g, beta, starts)


def _kda_bwd(chunk, sub, pack, interpret, res, do):
    return _backward(*res, do, chunk, sub, pack, interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16,
                interpret: bool | None = None):
    """The recurrence above over a whole sequence.

    q, k [B, T, H, dk] (already normalised and scaled), v [B, T, H, dv],
    g [B, T, H, dk] the log-decay (<= 0), beta [B, T, H]. Returns o
    [B, T, H, dv] in fp32.
    T need not be a multiple of `chunk`: the tail is padded with steps that
    leave the state as it is (g = 0, beta = 0) and is cut off again.
    `interpret` None: compiled on a TPU, interpreted anywhere else."""
    if chunk % sub or sub & (sub - 1):
        raise ValueError(f"chunk {chunk} must be a multiple of sub {sub}, "
                         "a power of two")
    if interpret is None:
        interpret = not on_tpu()
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk
    # Heads side by side along the 128 lanes of the pairwise part; all of
    # them where a narrower block would not be whole 128-lane tiles.
    pack = max(1, 128 // chunk)
    while h % pack:
        pack -= 1
    if pack * dk % 128 or pack * dv % 128:
        pack = h

    def flat(x):  # [B, T, H, d] -> [B, N * C, H * d] fp32: a reshape
        x = x.astype(_F32).reshape(b, t, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    # beta is the one small array: [B, H / pack, N, 1, pack * C].
    bt = flat(beta).reshape(b, n, chunk, h // pack, pack)
    bt = bt.transpose(0, 3, 1, 4, 2).reshape(b, h // pack, n, 1, pack * chunk)
    o = _kda(flat(q), flat(k), flat(v), flat(g), bt, chunk, sub, pack,
             interpret)
    return o[:, :t].reshape(b, t, h, dv)
