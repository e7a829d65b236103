"""Kimi Delta Attention core: the gated delta rule with a per-channel decay,
in its chunked (WY) form. Plain `jnp`, fp32 inside, autodiff backward.

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
(I + L^8) (L is strictly lower, L^16 = 0), then four steps of block forward
substitution. The operands of every matmul here are fp32 and must stay so on
a TPU, whose default rounds them to bf16: the 16 x 16 Neumann products are
multiplies and sums on the vector unit (exact fp32, and faster there than on
the matrix unit), everything else runs at `Precision.HIGH` (three bf16
passes, about 2^-17 relative: forty times finer than the bf16 activations
around the core, at half the matmul time of `HIGHEST`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGH)


def _tiny_matmul(a, b):
    """a @ b for [..., s, s] blocks of s <= 16, as multiplies and a sum on
    the vector unit, in fp32: the matrix unit runs a 16 x 16 product at an
    eighth of its rows and, for fp32 operands, six times over (88 ms a step
    of the Neumann products in a chip trace, PR 28)."""
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)

#: Bytes the pairwise sub-chunk tensor [.., sub, sub, dk] may take at once;
#: the chunks are walked in groups small enough to stay under it.
_PAIRWISE_BYTES = 128 * 2 ** 20


def _chunk_terms(q, k, v, g, beta, sub: int):
    """Everything of a chunk that does not depend on the incoming state.

    q, k, g [..., C, dk]; v [..., C, dv]; beta [..., C]; all fp32.
    Returns (Uv [..., C, dv], W [..., C, dk], B [..., C, C],
    qg [..., C, dk], kd [..., C, dk], gc [..., dk])."""
    c, dk = q.shape[-2], q.shape[-1]
    dv = v.shape[-1]
    nb = c // sub
    lead = q.shape[:-2]
    G = jnp.cumsum(g, axis=-2)                        # [..., C, dk], <= 0

    def blocks(x):
        return x.reshape(lead + (nb, sub, x.shape[-1]))

    Gb, kb, qb = blocks(G), blocks(k), blocks(q)
    # G at each sub-chunk's start: the cumulative sum through the end of
    # the sub-chunk before it, 0 for the first.
    Gs = jnp.concatenate(
        [jnp.zeros_like(Gb[..., :1, -1, :]), Gb[..., :-1, -1, :]], axis=-2)
    row = jnp.exp(Gb - Gs[..., :, None, :])           # exp(G_i - G_s) <= 1
    # exp(G_s(a) - G_j) for j in sub-chunk b < a; clamped where b >= a.
    col = jnp.exp(jnp.minimum(
        Gs[..., :, None, None, :] - Gb[..., None, :, :, :], 0.0))
    kc = kb[..., None, :, :, :] * col                 # [..., a, b, j, dk]
    off_k = _einsum("...aic,...abjc->...aibj", kb * row, kc)
    off_q = _einsum("...aic,...abjc->...aibj", qb * row, kc)
    # Same sub-chunk: pairwise, exponent clamped above the diagonal.
    pair = jnp.exp(jnp.minimum(
        Gb[..., :, None, :] - Gb[..., None, :, :], 0.0))  # [..., a, i, j, dk]
    kj = kb[..., None, :, :] * pair
    dia_k = jnp.sum(kb[..., :, None, :] * kj, axis=-1)    # [..., a, i, j]
    dia_q = jnp.sum(qb[..., :, None, :] * kj, axis=-1)

    a_idx = jnp.arange(nb)
    below = (a_idx[:, None] > a_idx[None, :])[:, None, :, None]  # b < a
    same = (a_idx[:, None] == a_idx[None, :])[:, None, :, None]

    def assemble(off, dia):
        full = jnp.where(below, off, 0.0) + jnp.where(
            same, dia[..., :, :, None, :], 0.0)
        return full.reshape(lead + (c, c))

    i_idx = jnp.arange(c)
    Mk, Mq = assemble(off_k, dia_k), assemble(off_q, dia_q)
    A = jnp.where(i_idx[:, None] > i_idx[None, :],
                  Mk * beta[..., :, None], 0.0)       # strictly lower
    B = jnp.where(i_idx[:, None] >= i_idx[None, :], Mq, 0.0)

    gam = jnp.exp(G)                                  # decay from the start
    rhs = jnp.concatenate([v, k * gam], axis=-1) * beta[..., None]
    X = _solve_unit_lower(A, rhs, sub)
    gc = G[..., -1, :]
    kd = k * jnp.exp(gc[..., None, :] - G)
    return X[..., :dv], X[..., dv:], B, q * gam, kd, jnp.exp(gc)


def _solve_unit_lower(A, rhs, sub: int):
    """X with (I + A) X = rhs, A [..., C, C] strictly lower triangular."""
    c = A.shape[-1]
    nb = c // sub
    lead = A.shape[:-2]
    Ab = A.reshape(lead + (nb, sub, nb, sub))
    rb = rhs.reshape(lead + (nb, sub, rhs.shape[-1]))
    eye = jnp.eye(sub, dtype=A.dtype)
    L = jnp.stack([Ab[..., a, :, a, :] for a in range(nb)], axis=-3)
    inv = eye - L                      # (I + L)^-1 = prod (I + (-L)^(2^p))
    power = L
    for _ in range(max(sub.bit_length() - 2, 0)):     # L^2, L^4, ... L^(sub/2)
        power = _tiny_matmul(power, power)
        inv = _tiny_matmul(inv, eye + power)
    xs = []
    for a in range(nb):
        r = rb[..., a, :, :]
        for b in range(a):
            r = r - _einsum("...ij,...jd->...id", Ab[..., a, :, b, :], xs[b])
        xs.append(_einsum("...ij,...jd->...id", inv[..., a, :, :], r))
    return jnp.stack(xs, axis=-3).reshape(rhs.shape)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16):
    """The recurrence above over a whole sequence.

    q, k [B, T, H, dk] (already normalised and scaled), v [B, T, H, dv],
    g [B, T, H, dk] the log-decay (<= 0), beta [B, T, H]. Returns o
    [B, T, H, dv] in fp32.
    T need not be a multiple of `chunk`: the tail is padded with steps that
    leave the state as it is (g = 0, beta = 0) and is cut off again."""
    if chunk % sub or sub & (sub - 1):
        raise ValueError(f"chunk {chunk} must be a multiple of sub {sub}, "
                         "a power of two")
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def prep(x):  # [B, T, H, d] -> [N, B, H, C, d], fp32
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    qc, kc, vc, gc = prep(q), prep(k), prep(v), prep(g)
    bc = prep(beta[..., None])[..., 0]

    # A few chunks at a time: their state-free terms together (the pairwise
    # tensor bounds how many), then the chunks' states in turn. Each group
    # is rematerialised in the backward, so what outlives it is the state
    # between groups, not the terms of every chunk of the sequence.
    per_chunk = b * h * (chunk // sub) * sub * sub * dk * 4
    group = max(1, min(n, _PAIRWISE_BYTES // per_chunk))
    while n % group:
        group -= 1

    def step(S, xs):
        uv, w, bm, qg, kd, gl = xs
        u = uv - _einsum("...ck,...kv->...cv", w, S)
        o = (_einsum("...ck,...kv->...cv", qg, S)
             + _einsum("...ij,...jv->...iv", bm, u))
        S = gl[..., :, None] * S + _einsum("...ck,...cv->...kv", kd, u)
        return S, o

    @jax.checkpoint
    def chunks(S, xs):
        return jax.lax.scan(step, S, _chunk_terms(*xs, sub=sub))

    _, o = jax.lax.scan(chunks, jnp.zeros((b, h, dk, dv), jnp.float32), tuple(
        x.reshape((n // group, group) + x.shape[1:])
        for x in (qc, kc, vc, gc, bc)))
    o = o.reshape((n,) + o.shape[2:])
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)  # [B,H,T,dv]
    return jnp.moveaxis(o, 1, 2)[:, :t]
