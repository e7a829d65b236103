"""Kimi Delta Attention: the gated delta rule with a per-channel decay, in
its chunked (WY) form, as two Pallas TPU kernels behind one custom_vjp, with
the mixer's elementwise work around the rule inside the same two kernels.

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
u_b. The solve (`_solve`) brings its own VJP: dR = (I + A)^-T dU, by block
back substitution through the same inv A and one product with inv^T, and
dA = -dR U^T read back on A's pattern.

**What runs where.** `kda_fwd` walks the grid (batch, head group, chunk),
the chunks in turn ("arbitrary") with the group's states [dv, dk] fp32 in
VMEM scratch; a step reads the chunk's rows of its wide operands in place
from the [B, T, H * d] arrays (a block of C rows by the group's lanes: no
transpose on either side of the call) and beta, computes everything in VMEM
and registers, and writes o and the state the chunk started from. `kda_bwd`
walks the same grid from the last chunk with dS in the scratch: it reads the
chunk's inputs, its start state and dO, takes `jax.vjp` of the same chunk
function inside the kernel body (so the chunk is recomputed there, never
stored) and writes the operands' cotangents. That `jax.vjp` stops at the
solve and takes its VJP above, so the Neumann product and the substitution
are never linearised; the cotangents of beta and the pairwise diagonals
come from autodiff of the elementwise work around it. Only those arrays and
the start states (dk * dv * 4 bytes a chunk a head) cross HBM. The start
states and o leave the forward named `kda_starts` and `kda_o`
(`checkpoint_name`): a layer under `jax.checkpoint` that saves those names
keeps them from its forward to its backward, and `kda_fwd` runs once a layer
instead of again in the recomputation. A head group is 128 / C heads (two at
C = 64), stacked: time runs along all 128 lanes in the pairwise part, and the
matmuls that do not involve a head's state are shared, block-diagonal.

The chunk function is one of two. `kda_chunked` runs `_chunk`, the rule
alone, on q, k, v, g as given (fp32). `kda_mixer` runs `_mixer_chunk`: a
prologue, `_chunk`, an epilogue, which is everything `KDAMixer` does between
its matmuls. The kernels then read what the five projections put out (q, k,
v, the decay's f, the output gate), and per chunk: the causal depthwise
convolutions of q, k, v and their SiLU; the per-head L2 norms of q and k and
q's d^-1/2; g = -exp(A_log) softplus(f + dt_bias); the rule; the per-head
RMS norm of o with its learned scale; times sigmoid(gate). The small
parameters ride in as rows of one [16, H * d] table (`_channel_table`), a
channel a lane; `kda_bwd` adds their gradients up over the chunks in an
output block that stays in VMEM, and what leaves the call is [B, 16, H * d].

**The convolution's history.** A chunk's first K - 1 rows need the rows
before the chunk. Both kernels get them through one more block spec a
convolved operand: the last 16 rows of the chunk before (whole bf16 tiles;
the first chunk's block is zeroed in the kernel, which is `jnp.pad`'s zeros).
In `kda_fwd` and in `kda_bwd`'s recomputation alike that is the *earlier*
chunk in time. The cotangent of those rows belongs to the earlier chunk,
which `kda_bwd` visits next: it waits in VMEM scratch beside dS and is added
to that chunk's cotangent before the write. A padded tail (T not a multiple
of C) leaves the state alone after the prologue: beta is padded with 0 and f
with a value whose softplus is 0, so g = 0 there.

**Inside a chunk.** The pairs of a sub-chunk are taken by diagonals with
time along the lanes and the channels down the rows: the d-th diagonal is a
lane rotation by d, its sum over the channels adds rows (vector adds, not
lane reductions). The Neumann products stay in that storage by diagonals,
(xy)[d] = sum_e x[e] * roll(y[d - e], e): multiplies and sums on the vector
unit. Nothing of a chunk that is larger than its inputs exists outside the
kernel.

**Precision**, as `assumed.precision` of the Kimi configuration states it or
finer: between a kernel's reads and its writes everything is fp32. Blocks are
converted as they are loaded, so the operands may be bf16: `KDAMixer` hands
over the projections' outputs as the matmuls round them and gets o in the
dtype `o_proj` multiplies, and those are the only roundings to bf16 on the
way forward. On the way back dO arrives in o's dtype and each operand's
cotangent is rounded to the operand's dtype once, at the write, after the
chunk's own part and the history's part have been added in fp32; the small
parameters' gradients never leave fp32. The matmuls (`_dot`: the
cross-sub-chunk products, the products with the state, inv A, inv rhs, the
substitution, B U, the state's update, and the same in the backward) are
three bf16 passes with fp32 accumulation, hi*hi + hi*lo + lo*hi, what
`Precision.HIGH` is, written out because Mosaic in jax 0.9.0 lowers only
DEFAULT and HIGHEST (interpreted they are the host's fp32 product, what HIGH
is on a CPU). G's cumulative sum is a 0/1 triangular matrix times g cut into
*three* bf16 parts: every product exact, the sums fp32 (G passes -600 in a
chunk; two parts would put 1e-4 on a decay factor, tests pin it). The
pairwise products, the Neumann products, beta's placement, the convolutions,
norms and gates are fp32 on the vector unit. Against the recurrence at
`highest` on the chip the core's outputs and all five gradients read 5e-6
(the `jnp` form this replaced: 1.3e-5; my chip run, PR 29).

On anything but a TPU the same kernels run interpreted (`on_tpu()`, as in
ops/flash_attention.py). Compiled, the group's lanes must be whole tiles:
dk and dv multiples of 128 / (heads in a group), or every head in one group;
the toy widths of the tests other than (3 heads, dv 8) compile too.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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


def _to_diagonals(x, sub: int):
    """The diagonals of x [n, n] that `_from_diagonals` fills, read back (its
    transpose): out[d, i] = x[i, i - d], 0 where i < d."""
    n = x.shape[0]
    xt = x.T                                          # i along the lanes
    diff = _iota((n, n), 1) - _iota((n, n), 0)
    return jnp.concatenate(
        [jnp.sum(jnp.where(diff == d, xt, 0.0), axis=0, keepdims=True)
         for d in range(sub)], axis=0)


def _sub_rows(x, a: int, c: int, sub: int):
    """The rows of sub-chunk a of every head of x [pack * C, .]."""
    return jnp.concatenate(
        [x[h * c + a * sub:h * c + (a + 1) * sub]
         for h in range(x.shape[0] // c)], axis=0)


def _unstack(blocks, sub: int):
    """nb blocks of (head, row) [pack * sub, .] -> [n, .] (head, step)."""
    return jnp.concatenate(
        [x[h * sub:(h + 1) * sub]
         for h in range(blocks[0].shape[0] // sub) for x in blocks], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _solve(power, a_off, rhs, c: int, sub: int, interpret: bool):
    """u with (I + A) u = rhs for a chunk's n = pack * C rows, the heads
    stacked, A = L + a_off strictly lower. L lies in the diagonal sub x sub
    blocks and is given by diagonals, power [sub, n] (see `_diag_product`;
    0 in row 0 and where n - d falls before the block); a_off [n, n] is the
    rest, a head's rows reading its earlier sub-chunks; rhs [n, dv].

    Its VJP is written out, so that autodiff never linearises the Neumann
    product or the substitution: with M = I + A, dR = M^-T dU and dA = -dR
    u^T on A's pattern, where M^-T = inv^T (I + inv a_off)^-T is a block
    back substitution and one product with inv^T."""
    return _solve_fwd(power, a_off, rhs, c, sub, interpret)[0]


def _solve_fwd(power, a_off, rhs, c, sub, interpret):
    roll = functools.partial(_roll, interpret=interpret)
    nb = c // sub
    # (I + L)^-1 of the diagonal blocks, L strictly lower and L^sub = 0:
    # the finite Neumann product (I - L)(I + L^2)(I + L^4)...
    eye = (_iota(power.shape, 0) == 0).astype(_F32)
    inv = eye - power
    for _ in range(max(sub.bit_length() - 2, 0)):
        power = _diag_product(power, power, roll)
        inv = _diag_product(inv, eye + power, roll)
    inv = _from_diagonals(inv)                        # [n, n]
    # Block forward substitution for (I + A) u = rhs with inv spread over
    # it: u_a = (inv rhs)_a - sum_{b < a} (inv a_off)_ab u_b.
    inv_a = _dot(inv, a_off, _NN, interpret)
    y = _dot(inv, rhs, _NN, interpret)
    us = [_sub_rows(y, 0, c, sub)]
    for a in range(1, nb):
        done = _unstack(us + [jnp.zeros_like(us[0])] * (nb - a), sub)
        us.append(_sub_rows(y, a, c, sub)
                  - _dot(_sub_rows(inv_a, a, c, sub), done, _NN, interpret))
    u = _unstack(us, sub)                             # [n, dv]
    return u, (inv, inv_a, u)


def _solve_bwd(c, sub, interpret, res, du):
    inv, inv_a, u = res
    n, nb = inv.shape[0], c // sub
    # w = (I + inv a_off)^-T dU from the last sub-chunk back:
    # w_a = dU_a - sum_{b > a} (inv a_off)_ba^T w_b.
    inv_at = inv_a.T
    ws = [_sub_rows(du, nb - 1, c, sub)]
    for a in range(nb - 2, -1, -1):
        done = _unstack([jnp.zeros_like(ws[0])] * (a + 1) + ws, sub)
        ws.insert(0, _sub_rows(du, a, c, sub)
                  - _dot(_sub_rows(inv_at, a, c, sub), done, _NN, interpret))
    d_rhs = _dot(inv, _unstack(ws, sub), _TN, interpret)   # inv^T w
    d_m = -_dot(d_rhs, u, _NT, interpret)             # [n, n]: -dR u^T
    r_i, c_i = _iota((n, n), 0), _iota((n, n), 1)
    before = (r_i // c == c_i // c) & (c_i // sub < r_i // sub)
    drow = _iota((sub, n), 0)
    in_block = (drow >= 1) & (_iota((sub, n), 1) % sub >= drow)
    return (jnp.where(in_block, _to_diagonals(d_m, sub), 0.0),
            jnp.where(before, d_m, 0.0), d_rhs)


_solve.defvjp(_solve_fwd, _solve_bwd)


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
        return _sub_rows(x, a, c, sub)

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

    b_m = _unstack(off_q, sub) + _from_diagonals(diag_q)  # [n, n]

    gam = jnp.exp(G)                                  # decay from the start
    kg, qg = ks * gam, qs * gam
    on_state = [dot(jnp.concatenate(                  # [2 C, dv] a head
        [kg[h * c:(h + 1) * c], qg[h * c:(h + 1) * c]], axis=0),
        st[h * dv:(h + 1) * dv], _NT) for h in range(pack)]
    # (I + A) u = rhs: L within the sub-chunks by diagonals, the rest [n, n].
    rhs = bcol * (vs - jnp.concatenate([x[:c] for x in on_state], axis=0))
    u = _solve(jnp.where(_iota((sub, n), 0) >= 1, beta * diag_k, 0.0),
               _unstack(off_k, sub) * bcol, rhs, c, sub, interpret)  # [n, dv]
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


# -- the mixer's elementwise work around a chunk ------------------------------

def _per_head(x, pack: int, fn):
    """`fn` over each head's lanes of x [C, pack * d], side by side again."""
    d = x.shape[1] // pack
    return jnp.concatenate(
        [fn(x[:, h * d:(h + 1) * d]) for h in range(pack)], axis=1)


def _conv_silu(x, before, w, roll):
    """SiLU of the causal depthwise convolution y_t = sum_i w_i x_{t-K+1+i}.
    x [C, n] the chunk's rows, `before` [R, n] the R >= K - 1 rows that
    precede them (zeros before the sequence starts), w [K, n]. A tap is a
    roll down the sublanes of the two stacked."""
    taps, lead = w.shape[0], before.shape[0]
    rows = jnp.concatenate([before, x], axis=0)
    y = w[taps - 1:taps] * x
    for s in range(1, taps):
        y = y + w[taps - 1 - s:taps - s] * roll(rows, s, 0)[lead:]
    return y * jax.nn.sigmoid(y)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _mixer_chunk(q, k, v, f, gate, beta, q_before, k_before, v_before, table,
                 st, *, taps: int, l2_eps: float, rms_eps: float, pack: int,
                 sub: int, interpret: bool):
    """`_chunk` between the KDA mixer's prologue and epilogue, all fp32.
    q, k, v, f, gate [C, pack * d]: what the five projections put out for the
    chunk's rows; *_before [R, pack * d]: the rows before the chunk, for the
    convolutions; table: the group's lanes of `_channel_table`."""
    roll = functools.partial(_roll, interpret=interpret)
    d = q.shape[1] // pack

    def conv(x, before, which):
        return _conv_silu(x, before, table[which * taps:(which + 1) * taps],
                          roll)

    def l2(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True)
                                 + l2_eps)

    def rms(y):
        return y * jax.lax.rsqrt(jnp.mean(y * y, axis=1, keepdims=True)
                                 + rms_eps)

    dt_bias, a_log, o_scale = (table[3 * taps + r:3 * taps + r + 1]
                               for r in range(3))
    q = _per_head(conv(q, q_before, 0), pack, l2) * d ** -0.5
    k = _per_head(conv(k, k_before, 1), pack, l2)
    g = -jnp.exp(a_log) * _softplus(f + dt_bias)
    o, st = _chunk(q, k, conv(v, v_before, 2), g, beta, st, pack=pack,
                   sub=sub, interpret=interpret)
    return _per_head(o, pack, rms) * o_scale * jax.nn.sigmoid(gate), st


def _channel_table(convs, dt_bias, a_log, o_scale):
    """The mixer's small parameters as rows of one fp32 [R, H * d] array, a
    channel a lane: the taps of the q, k and v convolutions, dt_bias, A_log
    (a head's value on each of its lanes), the output norm's scale (the same
    d values under every head); zero rows up to a multiple of 8. Built in
    `jnp` outside the kernels, so that autodiff spreads the table's cotangent
    back over the parameters."""
    heads, d = a_log.shape[0], o_scale.shape[0]
    table = jnp.concatenate(
        [*convs, dt_bias[None], jnp.repeat(a_log, d)[None],
         jnp.tile(o_scale, heads)[None]]).astype(_F32)
    return jnp.pad(table, ((0, -table.shape[0] % 8), (0, 0)))


# -- the two kernels ----------------------------------------------------------
#
# Both take a chunk function `fn(*rows, *before, *consts, st) -> (o, st)` and
# its operands in three kinds: `rows`, a block a chunk (the wide arrays and
# beta); `before`, the last rows of the chunk before, one for each of the first
# len(before) of `rows`; `consts`, blocks that every chunk of a head group
# shares. The core alone has rows only.

def _operands(refs, n_rows: int, n_before: int, has_before):
    """The blocks in fp32, whatever dtype the arrays have, `before` zeroed
    where the chunk is the sequence's first (its block spec then points at
    rows that are not before it)."""
    live = jnp.where(has_before, 1.0, 0.0).astype(_F32)
    return [r[...].astype(_F32) * live if n_rows <= j < n_rows + n_before
            else r[...].astype(_F32) for j, r in enumerate(refs)]


def _fwd_kernel(*refs, chunk_fn, n_rows, n_before):
    *ins, o_ref, s_ref, st_ref = refs
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st = st_ref[...]
    s_ref[...] = st
    o, st_ref[...] = chunk_fn(*_operands(ins, n_rows, n_before, i > 0), st)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(*refs, chunk_fn, n_rows, n_before, n_consts):
    """Walks the chunks from the last. Cotangents of `rows` are written a
    block a chunk, rounded to the array's dtype once everything has been added
    in fp32; those of `before` belong to the chunk visited next and wait in
    scratch beside dS; those of `consts` add up over the chunks in an output
    block that stays in VMEM."""
    n_in = n_rows + n_before + n_consts
    ins, (s_ref, do_ref) = refs[:n_in], refs[n_in:n_in + 2]
    outs = refs[n_in + 2:]
    d_rows, d_consts = outs[:n_rows], outs[n_rows:n_rows + n_consts]
    dst_ref, *d_before = outs[n_rows + n_consts:]
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        for ref in (dst_ref, *d_before, *d_consts):
            ref[...] = jnp.zeros_like(ref)

    _, vjp = jax.vjp(chunk_fn, *_operands(
        ins, n_rows, n_before, step < pl.num_programs(2) - 1), s_ref[...])
    *cts, dst_ref[...] = vjp((do_ref[...].astype(_F32), dst_ref[...]))
    for j, ref in enumerate(d_rows):
        ct = cts[j]
        if j < n_before:  # what the chunk after this one left for its tail
            lead = ct.shape[0] - d_before[j].shape[0]
            tail = ct[lead:] + d_before[j][...]
            ct = jnp.concatenate([ct[:lead], tail]) if lead else tail
        ref[...] = ct.astype(ref.dtype)
    for ref, ct in zip(d_before, cts[n_rows:]):
        ref[...] = ct
    for ref, ct in zip(d_consts, cts[n_rows + n_before:]):
        ref[...] += ct


class _Plan(NamedTuple):
    """What a pair of kernels is traced for; hashable, a static argument."""
    chunk: int
    sub: int
    pack: int
    interpret: bool
    out_dtype: Any
    #: (taps, l2_eps, rms_eps) of the mixer around the chunk; None: the core
    #: alone.
    mixer: tuple | None

    @property
    def n_before(self) -> int:
        return 3 if self.mixer else 0  # q, k and v pass a convolution

    @property
    def before_rows(self) -> int:
        """Rows of the block that brings a chunk the rows before it: whole
        (16, 128) tiles of bf16, and a divisor of the chunk."""
        return 16 if self.chunk % 16 == 0 else self.chunk

    def chunk_fn(self):
        kw = dict(pack=self.pack, sub=self.sub, interpret=self.interpret)
        if not self.mixer:
            return functools.partial(_chunk, **kw)
        taps, l2_eps, rms_eps = self.mixer
        return functools.partial(_mixer_chunk, taps=taps, l2_eps=l2_eps,
                                 rms_eps=rms_eps, **kw)


class _Specs:
    """Block specs on the grid (batch, head group, chunk). [B, T, H * d]
    arrays are read in place, a head group's `pack * d` lanes of a chunk's
    rows; beta is [B, H / pack, N, 1, pack * C] and the states [B, H / pack,
    N, pack * dv, dk]. `reverse` walks the chunks from the last."""

    def __init__(self, plan: _Plan, groups: int, n: int, reverse: bool):
        self.plan, self.groups = plan, groups
        self.at = (lambda i: n - 1 - i) if reverse else (lambda i: i)

    def rows(self, x):
        return pl.BlockSpec(
            (None, self.plan.chunk, x.shape[2] // self.groups),
            lambda b, h, i: (b, self.at(i), h))

    def before(self, x):
        """The last rows of the chunk before this one. The first chunk has
        none: the index stays in the array and the kernel zeroes the block."""
        tail = self.plan.before_rows
        per_chunk = self.plan.chunk // tail
        return pl.BlockSpec(
            (None, tail, x.shape[2] // self.groups),
            lambda b, h, i: (b, jnp.maximum(self.at(i) * per_chunk - 1, 0), h))

    def per_chunk(self, *dims):  # beta, states
        return pl.BlockSpec((None, None, None) + dims,
                            lambda b, h, i: (b, h, self.at(i), 0, 0))

    def const(self, x):  # [R, H * d]: the group's lanes, the same every chunk
        return pl.BlockSpec((x.shape[0], x.shape[1] // self.groups),
                            lambda b, h, i: (0, h))

    def d_const(self, x):  # [B, R, H * d]: one block while the chunks run
        return pl.BlockSpec((None, x.shape[0], x.shape[1] // self.groups),
                            lambda b, h, i: (b, 0, h))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


def _layout(rows, plan: _Plan):
    """(batch, head groups, chunks), the state's shape [pack * dv, dk], and
    the arrays whose rows before a chunk the kernels also read."""
    q, _, v, *_, beta = rows
    b, groups, n = beta.shape[:3]
    heads = groups * plan.pack
    state = (v.shape[2] // heads * plan.pack, q.shape[2] // heads)
    return (b, groups, n), state, rows[:plan.n_before]


# The two calls are jitted on their own: tracing a chunk and lowering it for
# Mosaic takes seconds, and every layer of a model, its rematerialised
# forward included, then shares one trace of each kernel.

@functools.partial(jax.jit, static_argnums=(2,))
def _forward(rows, consts, plan: _Plan):
    """rows: the wide arrays [B, T, H * d] (q, k, v first), T = N * C, then
    beta; consts: arrays [R, H * d]. Returns o [B, T, H * dv] and the state
    every chunk started from."""
    grid, state, before = _layout(rows, plan)
    specs = _Specs(plan, grid[1], grid[2], False)
    *wide, beta = rows
    v = rows[2]
    return _call(
        functools.partial(_fwd_kernel, chunk_fn=plan.chunk_fn(),
                          n_rows=len(rows), n_before=len(before)),
        "kda_fwd", grid,
        [*map(specs.rows, wide), specs.per_chunk(1, beta.shape[-1]),
         *map(specs.before, before), *map(specs.const, consts)],
        [specs.rows(v), specs.per_chunk(*state)],
        [jax.ShapeDtypeStruct(v.shape, plan.out_dtype),
         jax.ShapeDtypeStruct(grid + state, _F32)],
        [pltpu.VMEM(state, _F32)], plan.interpret)(*rows, *before, *consts)


@functools.partial(jax.jit, static_argnums=(4,))
def _backward(rows, consts, starts, do, plan: _Plan):
    """Cotangents of `rows`, each in its array's dtype, and of `consts`."""
    grid, state, before = _layout(rows, plan)
    specs = _Specs(plan, grid[1], grid[2], True)
    *wide, beta = rows
    row_specs = [*map(specs.rows, wide), specs.per_chunk(1, beta.shape[-1])]
    outs = _call(
        functools.partial(_bwd_kernel, chunk_fn=plan.chunk_fn(),
                          n_rows=len(rows), n_before=len(before),
                          n_consts=len(consts)),
        "kda_bwd", grid,
        [*row_specs, *map(specs.before, before), *map(specs.const, consts),
         specs.per_chunk(*state), specs.rows(do)],
        [*row_specs, *map(specs.d_const, consts)],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in rows]
        + [jax.ShapeDtypeStruct(grid[:1] + c.shape, _F32) for c in consts],
        [pltpu.VMEM(state, _F32)]
        + [pltpu.VMEM((plan.before_rows, x.shape[2] // grid[1]), _F32)
           for x in before],
        plan.interpret)(*rows, *before, *consts, starts, do)
    return (tuple(outs[:len(rows)]),
            tuple(jnp.sum(d, axis=0) for d in outs[len(rows):]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _kda(rows, consts, plan):
    return _forward(rows, consts, plan)[0]


def _kda_fwd(rows, consts, plan):
    # The names are inert under any remat policy that does not save them.
    o, starts = _forward(rows, consts, plan)
    o = checkpoint_name(o, "kda_o")
    starts = checkpoint_name(starts, "kda_starts")
    return o, (rows, consts, starts)


def _kda_bwd(plan, res, do):
    return _backward(*res, do, plan)


_kda.defvjp(_kda_fwd, _kda_bwd)

#: A decay pre-activation whose softplus is exactly 0 in fp32 and in bf16.
_NO_DECAY = -1e30


def _scan(wide, fills, beta, consts, *, chunk, sub, mixer, out_dtype,
          interpret):
    """`_kda` over a whole sequence: the wide arrays [B, T, H * d] and beta
    [B, T, H] padded to whole chunks (each wide array with its `fills`), beta
    laid out by head group, the result cut to T again."""
    if chunk % sub or sub & (sub - 1):
        raise ValueError(f"chunk {chunk} must be a multiple of sub {sub}, "
                         "a power of two")
    if interpret is None:
        interpret = not on_tpu()
    b, t, h = beta.shape
    dk, dv = wide[0].shape[2] // h, wide[2].shape[2] // h
    pad = -t % chunk
    n = (t + pad) // chunk
    # Heads side by side along the 128 lanes of the pairwise part; all of
    # them where a narrower block would not be whole 128-lane tiles.
    pack = max(1, 128 // chunk)
    while h % pack:
        pack -= 1
    if pack * dk % 128 or pack * dv % 128:
        pack = h

    def whole_chunks(x, fill=0.0):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0)),
                       constant_values=fill) if pad else x

    # beta is the one small array: [B, H / pack, N, 1, pack * C].
    bt = whole_chunks(beta.astype(_F32)).reshape(b, n, chunk, h // pack, pack)
    bt = bt.transpose(0, 3, 1, 4, 2).reshape(b, h // pack, n, 1, pack * chunk)
    o = _kda((*map(whole_chunks, wide, fills), bt), tuple(consts),
             _Plan(chunk, sub, pack, interpret, out_dtype, mixer))
    return o[:, :t]


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16,
                interpret: bool | None = None):
    """The recurrence above over a whole sequence: the core alone.

    q, k [B, T, H, dk] (already normalised and scaled), v [B, T, H, dv],
    g [B, T, H, dk] the log-decay (<= 0), beta [B, T, H]. Returns o
    [B, T, H, dv] in fp32.
    T need not be a multiple of `chunk`: the tail is padded with steps that
    leave the state as it is (g = 0, beta = 0) and is cut off again.
    `interpret` None: compiled on a TPU, interpreted anywhere else."""
    b, t, h, _ = q.shape
    o = _scan([x.astype(_F32).reshape(b, t, -1) for x in (q, k, v, g)],
              (0.0,) * 4, beta, (), chunk=chunk, sub=sub, mixer=None,
              out_dtype=_F32, interpret=interpret)
    return o.reshape(b, t, h, -1)


def kda_mixer(q, k, v, f, gate, beta, *, convs, dt_bias, a_log, o_scale,
              l2_eps: float, rms_eps: float, out_dtype=None, chunk: int = 64,
              sub: int = 16, interpret: bool | None = None):
    """The KDA mixer between its projections, in the two kernels:

        q, k, v <- SiLU(causal depthwise conv(.))      convs: three [K, H * d]
        q <- q / sqrt(sum_head q^2 + l2_eps) * d^-1/2,  k likewise, unscaled
        g  = -exp(A_log) * softplus(f + dt_bias)        a_log [H], dt_bias [H * d]
        o  = the recurrence above
        o <- o / sqrt(mean_head o^2 + rms_eps) * o_scale * sigmoid(gate)

    q, k, v, f, gate [B, T, H * d] are what the five projections put out, in
    their own dtype; beta [B, T, H] (after its sigmoid); o_scale [d]. Returns
    o [B, T, H * d] in `out_dtype` (None: q's). Everything between the reads
    and the write is fp32. T need not be a multiple of `chunk`: the padded
    tail gets beta = 0 and an f whose softplus is 0, so g = 0 there."""
    mixer = (convs[0].shape[0], float(l2_eps), float(rms_eps))
    if mixer[0] - 1 > min(chunk, 16):
        raise ValueError(f"a convolution of {mixer[0]} taps reaches past the "
                         f"{min(chunk, 16)} rows a chunk is given of the one "
                         "before it")
    return _scan([q, k, v, f, gate], (0.0, 0.0, 0.0, _NO_DECAY, 0.0), beta,
                 [_channel_table(convs, dt_bias, a_log, o_scale)],
                 chunk=chunk, sub=sub, mixer=mixer,
                 out_dtype=jnp.dtype(out_dtype or q.dtype),
                 interpret=interpret)
