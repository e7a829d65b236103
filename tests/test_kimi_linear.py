"""Kimi-Linear (models/kimi_linear.py) against its plain reference
(benchmarks/reference/kimi_linear.py) on seeded random weights at toy widths:
the chunked KDA core against the recurrence, latent attention through the
flash kernels against a masked softmax, the held-experts layer against a loop
over experts, the shares of an expert-parallel layer adding up to the uncut
layer, the whole model's loss and gradients; and the flash backward this model
forced (value width != key width, q-side rows streamed through the grid).
"""

import dataclasses
import functools
import importlib.util
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from kubeflow_tpu.models import kimi_linear as kl
from kubeflow_tpu.models import moe
from kubeflow_tpu.models.moe import HeldExpertsBlock
from kubeflow_tpu.ops import flash_attention as fa
from kubeflow_tpu.ops.kda import kda_chunked, kda_mixer
from kubeflow_tpu.ops.reference import naive_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_reference",
        os.path.join(ROOT, "benchmarks", "reference", "kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def ref_cfg(cfg: kl.KimiLinearConfig) -> dict:
    return {k: getattr(cfg, k) for k in (
        "hidden_size", "num_layers", "kda_layers", "full_attn_layers",
        "first_k_dense_replace", "kda_heads", "kda_head_dim", "kda_norm_eps",
        "num_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "num_experts", "experts_per_token", "experts_held",
        "routed_scaling_factor", "rms_eps")}


# -- KDA: chunked against the recurrence -------------------------------------

def kda_inputs(t, decay, seed=0, b=2, h=3, dk=16, dv=8):
    r = np.random.default_rng(seed)
    q = r.normal(size=(b, t, h, dk))
    k = r.normal(size=(b, t, h, dk))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(b, t, h, dv))
    g = -decay * np.abs(r.normal(size=(b, t, h, dk)))
    beta = 1 / (1 + np.exp(-r.normal(size=(b, t, h))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


#: Toy heads, and the cell's (dk = dv = 128, chunks of 64), two side by side.
KDA_SHAPES = {"toy": {}, "cell": dict(b=1, h=2, dk=128, dv=128)}


@pytest.mark.parametrize("t,decay,shape", [
    (128, 0.1, "toy"),   # chunk-aligned
    (100, 0.1, "toy"),   # ragged: the tail is padded with steps that do nothing
    (128, 10.0, "toy"),  # sum of g over a chunk far below -300: the textbook
    (70, 10.0, "toy"),   # factorisation's exp(-G) is inf in fp32 here
    (256, 1.0, "cell"),
    (200, 1.0, "cell"),  # ragged at the cell's widths
])
def test_kda_chunked_matches_recurrence(t, decay, shape):
    args = kda_inputs(t, decay, **KDA_SHAPES[shape])
    if decay > 1:
        assert float(jnp.min(jnp.sum(args[3][:, :64], axis=1))) < -300

    def scalar(fn):
        weights = jnp.cos(jnp.arange(args[2].shape[-1]))
        return lambda *a: jnp.sum(fn(*a) * weights)

    out, want = kda_chunked(*args), ref.kda_recurrence(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    got = jax.grad(scalar(kda_chunked), argnums=(0, 1, 2, 3, 4))(*args)
    exp = jax.grad(scalar(ref.kda_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, e in zip("q k v g beta".split(), got, exp):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, e) < 1e-4, name


def test_kda_decay_factors_keep_fp32_under_a_large_cumulative_sum():
    """g in [-14, -6] a step through whole chunks, so that G = cumsum(g) passes
    -600 while the decay between neighbouring steps, exp(G_i - G_j), is a
    difference of two such sums. Each (writer j, reader i) pair below has a
    channel of its own: k_j = q_i = that unit vector, beta_j = 1 and 0
    elsewhere, so o_i = exp(sum_{j < s <= i} g_s) v_j, the bare factor. g is
    drawn on a grid of 2^-14 (up to 18 bits a value, every partial sum exact
    in fp32): an exact fp32 sum leaves the matmuls' 2^-17 and exp's own
    rounding; a sum over g cut to two bf16 parts (16 bits), or one, does
    not pass."""
    t, dk, dv = 128, 16, 8
    pairs = [(60, 61), (60, 63), (47, 48), (45, 50), (30, 33), (62, 64),
             (63, 66), (120, 122), (2, 7), (10, 15), (17, 21), (23, 28),
             (70, 75), (81, 85), (97, 102), (106, 111)]  # inside a sub-chunk,
    # across sub-chunks, into the next chunk: a channel each
    r = np.random.default_rng(0)
    g = -(6 + r.integers(0, 2 ** 17, (1, t, 2, dk)) / 2.0 ** 14)
    v = r.normal(size=(1, t, 2, dv))
    q, k, beta = np.zeros_like(g), np.zeros_like(g), np.zeros((1, t, 2))
    for c, (j, i) in enumerate(pairs):
        k[0, j, :, c], beta[0, j, :], q[0, i, :, c] = 1.0, 1.0, 1.0
    # The recurrence in float64, head by head.
    want = np.zeros((1, t, 2, dv))
    for h in range(2):
        S = np.zeros((dk, dv))
        for s in range(t):
            S = np.exp(g[0, s, h])[:, None] * S
            S += np.outer(k[0, s, h], beta[0, s, h] * (v[0, s, h]
                                                       - S.T @ k[0, s, h]))
            want[0, s, h] = S.T @ q[0, s, h]
    out = np.asarray(kda_chunked(*(jnp.asarray(x, jnp.float32)
                                   for x in (q, k, v, g, beta))), np.float64)
    assert float(np.sum(g[0, :64, 0, 0])) < -600
    for j, i in pairs:
        err = np.linalg.norm(out[0, i] - want[0, i]) / np.linalg.norm(
            want[0, i])
        assert err < 3e-5, (j, i, err)


@pytest.mark.parametrize("dims", ["nn", "nt", "tn"])
def test_kda_compiled_matmul_is_three_bf16_passes(dims):
    """What the compiled kernels multiply with (the interpreted ones take the
    host's fp32 product): hi*hi + hi*lo + lo*hi, within 2^-15 of the fp32
    product where one bf16 pass is 2^-10 and more off; its cotangents too."""
    from kubeflow_tpu.ops import kda

    contract = {"nn": kda._NN, "nt": kda._NT, "tn": kda._TN}[dims]
    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.normal(ka, (64, 48) if dims == "tn" else (48, 64))
    b = jax.random.normal(kb, (32, 64) if dims == "nt" else (64, 32))
    probe = jnp.sin(jnp.arange(32.0))

    def value_and_cotangents(product):
        out, vjp = jax.vjp(product, a, b)
        return (out,) + vjp(jnp.broadcast_to(probe, out.shape))

    three = value_and_cotangents(lambda a, b: kda._dot(a, b, contract, False))
    exact = value_and_cotangents(lambda a, b: jax.lax.dot_general(
        a, b, (contract, ((), ())), precision="highest"))
    one = value_and_cotangents(lambda a, b: kda._pass(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), contract))
    for got, want, coarse in zip(three, exact, one):
        assert rel(got, want) < 2.0 ** -15
        assert rel(coarse.astype(jnp.float32), want) > 2.0 ** -10


@pytest.mark.parametrize("chunk,sub", [(64, 24), (40, 16)])
def test_kda_refuses_a_chunk_its_blocks_do_not_tile(chunk, sub):
    """The Neumann product needs a power-of-two block, the block
    substitution whole blocks in a chunk."""
    with pytest.raises(ValueError, match="multiple of sub"):
        kda_chunked(*kda_inputs(64, 0.1), chunk=chunk, sub=sub)


def plain_solve(power, a_off, rhs, c, sub, interpret):
    """`ops/kda.py:_solve`'s forward as `_chunk` had it inline, with no rule of
    its own: what autodiff linearises whole."""
    from kubeflow_tpu.ops import kda

    roll = functools.partial(kda._roll, interpret=interpret)
    dot = functools.partial(kda._dot, dims=kda._NN, interpret=interpret)
    n, nb = power.shape[1], c // sub
    pack = n // c

    def rows_of(x, a):
        return jnp.concatenate(
            [x[h * c + a * sub:h * c + (a + 1) * sub] for h in range(pack)],
            axis=0)

    def unstack(blocks):
        return jnp.concatenate(
            [x[h * sub:(h + 1) * sub] for h in range(pack) for x in blocks],
            axis=0)

    eye = (jnp.arange(sub)[:, None] == 0).astype(jnp.float32)
    inv = eye - power
    for _ in range(max(sub.bit_length() - 2, 0)):
        power = kda._diag_product(power, power, roll)
        inv = kda._diag_product(inv, eye + power, roll)
    inv = kda._from_diagonals(inv)
    inv_a, y = dot(inv, a_off), dot(inv, rhs)
    us = [rows_of(y, 0)]
    for a in range(1, nb):
        done = unstack(us + [jnp.zeros((pack * sub, rhs.shape[1]))] * (nb - a))
        us.append(rows_of(y, a) - dot(rows_of(inv_a, a), done))
    return unstack(us)


def solve_inputs(pack, c, dv, sub=16, seed=0):
    """power, a_off, rhs and a cotangent for u, each 0 off its pattern, and
    the patterns' masks (as `_chunk` builds them)."""
    n = pack * c
    r = np.random.default_rng(seed)
    d, i = np.arange(sub)[:, None], np.arange(n)[None]
    in_block = (d >= 1) & (i % sub >= d)
    rows, cols = np.arange(n)[:, None], np.arange(n)[None]
    before = (rows // c == cols // c) & (cols // sub < rows // sub)
    power = np.where(in_block, r.uniform(-0.5, 0.5, (sub, n)), 0.0)
    a_off = np.where(before, r.uniform(-0.5, 0.5, (n, n)), 0.0)
    rhs, du = r.normal(size=(n, dv)), r.normal(size=(n, dv))
    return ([jnp.asarray(x, jnp.float32) for x in (power, a_off, rhs, du)],
            (in_block, before))


@pytest.mark.parametrize("pack,c,dv", [
    (2, 64, 128),  # the cell's head group: dk = dv = 128, two heads, nb 4
    (2, 16, 128),  # one sub-chunk: no substitution at all
    (3, 64, 8),    # toy widths
    (1, 16, 8),
])
def test_kda_solve_vjp_matches_autodiff_of_the_plain_form(pack, c, dv):
    """The solve's own VJP (a transposed block substitution, inv^T, -dR u^T
    read back on A's pattern) against autodiff of the same forward written
    out, interpreted in fp32; the primal is that forward to the bit."""
    from kubeflow_tpu.ops import kda

    (power, a_off, rhs, du), (in_block, before) = solve_inputs(pack, c, dv)

    def plain(p, a, r):  # masked, so that the cotangents are 0 off A's pattern
        return plain_solve(jnp.where(in_block, p, 0.0),
                           jnp.where(before, a, 0.0), r, c, 16, True)

    def value_and_cotangents(fn):  # op by op: faster than compiling here
        out, pullback = jax.vjp(fn, power, a_off, rhs)
        return out, pullback(du)

    got, got_cts = value_and_cotangents(
        lambda p, a, r: kda._solve(p, a, r, c, 16, True))
    want, want_cts = value_and_cotangents(plain)
    assert bool(jnp.array_equal(got, want))
    for name, a, e in zip(("power", "a_off", "rhs"), got_cts, want_cts):
        assert rel(a, e) < 1e-5, name


def test_kda_backward_never_linearises_the_neumann_product(monkeypatch):
    """`_diag_product` counted while `jax.vjp` of a chunk and its pullback are
    traced: the forward's six products (sub 16: three squarings, three
    factors), none of them differentiated. The same probe on the plain
    forward sees all six linearised."""
    from kubeflow_tpu.ops import kda

    calls = {"primal": 0, "linearised": 0}
    inner = kda._diag_product

    @functools.partial(jax.custom_jvp, nondiff_argnums=(2,))
    def counted(x, y, roll):
        calls["primal"] += 1
        return inner(x, y, roll)

    @counted.defjvp
    def _(roll, primals, tangents):
        calls["linearised"] += 1
        return jax.jvp(lambda x, y: inner(x, y, roll), primals, tangents)

    monkeypatch.setattr(kda, "_diag_product", counted)
    pack, c, dk, dv = 2, 32, 16, 8
    r = np.random.default_rng(0)
    args = [jnp.asarray(x, jnp.float32) for x in (
        r.normal(size=(c, pack * dk)), r.normal(size=(c, pack * dk)),
        r.normal(size=(c, pack * dv)), -np.abs(r.normal(size=(c, pack * dk))),
        r.uniform(size=(1, pack * c)), r.normal(size=(pack * dv, dk)))]

    def traced(fn, *args):  # the forward and its pullback, traced once
        def both(*a):
            out, pullback = jax.vjp(fn, *a)
            return pullback(out)
        calls.update(primal=0, linearised=0)
        jax.make_jaxpr(both)(*args)
        return dict(calls)

    chunk = functools.partial(kda._chunk, pack=pack, sub=16, interpret=True)
    assert traced(chunk, *args) == {"primal": 6, "linearised": 0}
    (power, a_off, rhs, _), _ = solve_inputs(pack, c, dv)
    assert traced(lambda p: plain_solve(p, a_off, rhs, c, 16, True),
                  power) == {"primal": 0, "linearised": 6}


# -- KDA: the fused mixer call against the reference's pieces -----------------

L2_EPS, RMS_EPS = 1e-6, 1e-5
#: As KDA_SHAPES, one width a head: the mixer's q, k, v, f and gate share it.
MIXER_SHAPES = {"toy": {}, "cell": dict(b=1, h=2, d=128)}


def mixer_inputs(t, seed=0, b=2, h=3, d=16, taps=4):
    """(the five pre-activations, beta, the three convolutions' taps, dt_bias,
    A_log, the output norm's scale), fp32, drawn as the model draws them."""
    r = np.random.default_rng(seed)
    w = h * d
    pre = tuple(r.normal(size=(b, t, w)) for _ in range(5))
    beta = 1 / (1 + np.exp(-r.normal(size=(b, t, h))))
    convs = tuple(r.uniform(-0.5, 0.5, size=(taps, w)) for _ in range(3))
    small = (r.normal(size=(w,)) - 3, np.log(r.uniform(1, 16, size=(h,))),
             1 + 0.1 * r.normal(size=(d,)))
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                        (pre, beta, convs) + small)


def mixer_fused(pre, beta, convs, dt_bias, a_log, o_scale):
    return kda_mixer(*pre, beta, convs=convs, dt_bias=dt_bias, a_log=a_log,
                     o_scale=o_scale, l2_eps=L2_EPS, rms_eps=RMS_EPS)


def mixer_plain(pre, beta, convs, dt_bias, a_log, o_scale):
    """`ref.kda_mixer` between its projections, from its own pieces."""
    q, k, v, f, gate = pre
    (b, t, w), h = q.shape, a_log.shape[0]
    d = w // h

    def head(x, taps):
        return jax.nn.silu(ref.causal_conv(x, taps)).reshape(b, t, h, d)

    def l2(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + L2_EPS)

    g = (-jnp.exp(a_log)[:, None]
         * jax.nn.softplus(f + dt_bias).reshape(b, t, h, d))
    o = ref.kda_recurrence(l2(head(q, convs[0])) * d ** -0.5,
                           l2(head(k, convs[1])), head(v, convs[2]), g, beta)
    o = ref.rms_norm(o, o_scale, RMS_EPS)
    return (o * jax.nn.sigmoid(gate).reshape(b, t, h, d)).reshape(b, t, w)


def mixer_grads(fn, args, weights):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                    argnums=tuple(range(len(args))))(*args)


def assert_grads_match(got, want, limit=1e-4):
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == 12  # 5 pre-activations, beta, 3 x taps, 3 small
    for (path, a), e in zip(flat, jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, e) < limit, name


@pytest.mark.parametrize("t,shape", [  # four chunks each: a shape's two
    (256, "toy"), (200, "toy"),        # lengths share one trace of the kernels
    (256, "cell"), (200, "cell"),      # chunk-aligned; ragged: a padded tail
])
def test_kda_mixer_matches_reference_pieces(t, shape):
    """Convolutions, SiLU, L2 norms, the decay, the recurrence, the output
    norm and the gate in the two kernels, against the same in plain `jnp`:
    the output, and the gradient with respect to every input and parameter."""
    args = mixer_inputs(t, **MIXER_SHAPES[shape])
    out, want = mixer_fused(*args), mixer_plain(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert rel(out, want) < 1e-5
    weights = jnp.cos(jnp.arange(out.shape[-1]))
    assert_grads_match(mixer_grads(mixer_fused, args, weights),
                       mixer_grads(mixer_plain, args, weights))


@pytest.mark.parametrize("t,row", [
    (256, 63),   # the last row of chunk 0, into chunk 1
    (200, 191),  # the last row of chunk 2, into a chunk with 8 real rows
    (256, 15),   # the row a first chunk would take for its history, unmasked
])
def test_kda_convolution_history_crosses_chunks(t, row):
    """v's pre-activation is one impulse at `row`, where beta is 0: the state
    learns nothing of it, and all that o can hold of it comes from the three
    rows after it, through the convolution's history. The loss reads only the
    rows after `row`: the impulse's gradient is what comes back that way."""
    pre, beta, *rest = mixer_inputs(t)
    impulse = jnp.zeros_like(pre[2]).at[:, row].set(1.0)
    args = ((*pre[:2], impulse, *pre[3:]), beta.at[:, row].set(0.0), *rest)
    out, want = mixer_fused(*args), mixer_plain(*args)
    assert not float(jnp.max(jnp.abs(out[:, :row + 1])))  # nothing before it
    after = out[:, row + 1:row + 4]
    assert float(jnp.min(jnp.linalg.norm(after, axis=(0, 2)))) > 1e-2
    assert rel(after, want[:, row + 1:row + 4]) < 1e-5
    assert rel(out, want) < 1e-5
    weights = (jnp.arange(t) > row)[:, None] * jnp.cos(
        jnp.arange(out.shape[-1]))
    got = mixer_grads(mixer_fused, args, weights)
    exp = mixer_grads(mixer_plain, args, weights)
    assert float(jnp.linalg.norm(exp[0][2][:, row])) > 1e-3
    assert rel(got[0][2][:, row], exp[0][2][:, row]) < 1e-4
    assert_grads_match(got, exp)


def test_kda_mixer_refuses_a_convolution_longer_than_its_history():
    """A chunk is handed 16 rows of the one before it, so 17 taps at most."""
    pre, beta, convs, *small = mixer_inputs(32, taps=18)
    with pytest.raises(ValueError, match="18 taps"):
        mixer_fused(pre, beta, convs, *small)


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation in a jaxpr, through the calls it nests
    (but not into a kernel's body)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


def test_kda_kernels_take_and_return_the_activation_dtype():
    """`KDAMixer` at bf16, forward and backward, read off the jaxpr: the two
    kernels take the five pre-activations as the projections round them and
    hand o back as `o_proj` multiplies it, and nothing fp32 of [B, T, H * d]
    goes into or comes out of either."""
    cfg = kl.kimi_linear_tiny()
    assert cfg.dtype == jnp.bfloat16
    mixer = kl.KDAMixer(cfg)
    b, t, width = 2, 32, cfg.kda_heads * cfg.kda_head_dim
    assert width != cfg.hidden_size
    x = jnp.ones((b, t, cfg.hidden_size), cfg.dtype)
    params = nn.meta.unbox(mixer.init(jax.random.key(0), x)["params"])
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
        mixer.apply({"params": p}, x).astype(jnp.float32)),
        argnums=(0, 1)))(params, x)
    calls = {eqn.params["name"]: eqn for eqn in _pallas_calls(jaxpr.jaxpr)}
    assert sorted(calls) == ["kda_bwd", "kda_fwd"]

    def wide(variables, dtype):
        return [v for v in variables if v.aval.shape == (b, t, width)
                and v.aval.dtype == dtype]

    fwd, bwd = calls["kda_fwd"], calls["kda_bwd"]
    # q, k, v, f, gate, and q, k, v again for the rows before a chunk
    assert len(wide(fwd.invars, jnp.bfloat16)) == 8
    assert len(wide(fwd.outvars, jnp.bfloat16)) == 1           # o
    assert len(wide(bwd.invars, jnp.bfloat16)) == 9            # and dO
    assert len(wide(bwd.outvars, jnp.bfloat16)) == 5
    for eqn in (fwd, bwd):
        assert not wide(eqn.invars + eqn.outvars, jnp.float32)


# -- MLA through the flash kernels against the masked softmax ----------------

@pytest.mark.parametrize("t", [64, 50])
def test_mla_matches_masked_softmax(t):
    cfg = dataclasses.replace(kl.kimi_linear_tiny(), dtype=jnp.float32,
                              flash_block_q=32, flash_block_kv=32)
    x = jax.random.normal(jax.random.key(1), (2, t, cfg.hidden_size))
    mixer = kl.MLAMixer(cfg)
    params = nn.meta.unbox(mixer.init(jax.random.key(2), x)["params"])

    def prog(p, x):
        return mixer.apply({"params": p}, x)

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return ref.mla_mixer(x, p, {**ref.DEFAULTS, **ref_cfg(cfg)},
                                 q_block=16)

    assert rel(prog(params, x), plain(params, x)) < 1e-5
    probe = jnp.sin(jnp.arange(cfg.hidden_size))
    got = jax.grad(lambda p, x: jnp.sum(prog(p, x) * probe),
                   argnums=(0, 1))(params, x)
    exp = jax.grad(lambda p, x: jnp.sum(plain(p, x) * probe),
                   argnums=(0, 1))(params, x)
    for a, e in zip(jax.tree.leaves(got), jax.tree.leaves(exp)):
        assert rel(a, e) < 1e-4


# -- the held-experts layer ---------------------------------------------------

@pytest.fixture
def two_blocks(monkeypatch):
    """The expert tests' 128 tokens walked as two blocks of 64."""
    monkeypatch.setattr(moe, "_ROUTED_BLOCK_TOKENS", 64)


def expert_block(num_experts, held, top, shared=32):
    return HeldExpertsBlock(
        hidden_size=48, expert_width=32, num_experts=num_experts,
        experts_per_token=top, experts_held=held, routed_scale=2.446,
        shared_width=shared, dtype=jnp.float32)


def expert_ref_cfg(block):
    return {**ref.DEFAULTS, "experts_held": block.experts_held,
            "experts_per_token": block.experts_per_token,
            "routed_scaling_factor": block.routed_scale}


@pytest.mark.parametrize("biased", [True, False])
def test_expert_layer_matches_loop_reference(biased, two_blocks):
    block = expert_block(32, (8, 8), 4)
    x = jax.random.normal(jax.random.key(3), (2, 64, 48))
    params = nn.meta.unbox(block.init(jax.random.key(4), x)["params"])
    if biased:  # every token also picks held expert 10
        params["e_score_correction_bias"] = (
            jnp.zeros((32,)).at[10].set(5.0))

    def prog(p, x):
        return block.apply({"params": p}, x)

    def plain(p, x):
        with jax.default_matmul_precision("highest"):
            return ref.moe_ffn(x, p, expert_ref_cfg(block))

    (y, share, load, touched), (want, want_share, counts) = prog(
        params, x), plain(params, x)
    assert int(touched) == int(jnp.sum(counts > 0))
    counts = counts.astype(jnp.float32)
    assert rel(y, want) < 1e-5
    assert float(share) == pytest.approx(float(want_share))
    assert float(load) == pytest.approx(
        float(jnp.max(counts) / jnp.mean(counts)))
    if biased:
        assert float(load) >= 4.0  # one held expert at 4x the mean and more
        assert int(counts[2]) == 128  # dropless: all 128 tokens reached it
    probe = jnp.cos(jnp.arange(48))
    got = jax.grad(lambda p, x: jnp.sum(prog(p, x)[0] * probe),
                   argnums=(0, 1))(params, x)
    exp = jax.grad(lambda p, x: jnp.sum(plain(p, x)[0] * probe),
                   argnums=(0, 1))(params, x)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    for (path, a), e in zip(flat_got, jax.tree.leaves(exp)):
        if not float(jnp.linalg.norm(e)):  # the bias takes no gradient
            assert not float(jnp.linalg.norm(a)), path
        else:
            assert rel(a, e) < 1e-4, path


@pytest.mark.parametrize("num_experts,held", [(16, 4), (32, 1)])
def test_the_shares_add_up_to_the_uncut_layer(num_experts, held):
    """What every chip of the expert-parallel job computes alike (the shared
    expert) counted once, the routed parts of all the shares summed: the
    uncut layer."""
    top = 4
    whole = expert_block(num_experts, (0, num_experts), top)
    x = jax.random.normal(jax.random.key(5), (1, 64, 48))
    params = nn.meta.unbox(whole.init(jax.random.key(6), x)["params"])
    params["e_score_correction_bias"] = 0.1 * jax.random.normal(
        jax.random.key(7), (num_experts,))
    uncut, share_all, *_ = whole.apply({"params": params}, x)
    assert float(share_all) == pytest.approx(1.0)
    routed = {k: v for k, v in params.items() if k != "shared_expert"}
    uncut_routed, *_ = expert_block(
        num_experts, (0, num_experts), top, shared=0).apply(
            {"params": routed}, x)
    shared_part = uncut - uncut_routed
    total, shares = jnp.zeros_like(uncut), 0.0
    for start in range(0, num_experts, held):
        mine = dict(routed, **{k: routed[k][start:start + held]
                               for k in ("w_gate", "w_up", "w_down")})
        y, share, *_ = expert_block(
            num_experts, (start, held), top, shared=0).apply(
                {"params": mine}, x)
        total, shares = total + y, shares + float(share)
    assert shares == pytest.approx(1.0)
    assert rel(total, uncut_routed) < 1e-5
    assert rel(total + shared_part, uncut) < 1e-5


# -- the whole model ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_model_loss_and_gradients_match_reference(seed):
    cfg = dataclasses.replace(kl.kimi_linear_tiny(), dtype=jnp.float32)
    model = kl.KimiLinear(cfg)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, 41)), jnp.int32)
    inputs, targets = toks[:, :-1], toks[:, 1:]
    params = nn.meta.unbox(
        model.init(jax.random.key(seed), inputs)["params"])
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: (0.1 * jax.random.normal(jax.random.key(9), p.shape)
                         if "e_score_correction_bias" in str(path) else p),
        params)

    from kubeflow_tpu.train.step import cross_entropy_loss

    def prog(p):
        out, sown = model.apply({"params": p}, inputs, mutable=["counters"])
        return cross_entropy_loss(out, targets), sown["counters"]

    (loss, counters), grads = jax.value_and_grad(prog, has_aux=True)(params)
    (want, want_counters), want_grads = ref.loss_and_grads(
        params, inputs, targets, ref_cfg(cfg))
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    for name, value in want_counters.items():
        assert float(counters[name][0]) == pytest.approx(float(value))
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) > 100
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        if "e_score_correction_bias" in str(path):
            assert not float(jnp.linalg.norm(g)), path
        else:
            assert rel(g, w) < 2e-4, path


def test_registry_reports_held_and_published_parameters_apart():
    from kubeflow_tpu.utils import registry

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-linear-48b-a3b-d5e8.json")) as fh:
        entry = json.load(fh)
    _, info = registry.build_model(entry["registry_model"],
                                   **entry["model_kwargs"])
    # ISSUE 28's arithmetic: 602M held, 336M multiplied a token, and the
    # published model's 48B with every expert of every layer.
    assert info["held_params"] == pytest.approx(602e6, rel=0.005)
    assert info["num_params"] == pytest.approx(336e6, rel=0.005)
    assert info["num_params"] < info["held_params"]
    _, full = registry.build_model("kimi_linear_48b")
    assert full["published_params"] == pytest.approx(49.1e9, rel=0.01)


#: One layer of each mixer, the second with experts: enough for the step's
#: plumbing, a third of the compile.
TWO_LAYERS = {"num_layers": 2, "kda_layers": [1], "full_attn_layers": [2]}


def test_trainer_rows_carry_the_routing_counters(tmp_path):
    from kubeflow_tpu.train.trainer import Trainer, TrainJobSpec

    path = tmp_path / "metrics.jsonl"
    Trainer(TrainJobSpec(
        model="kimi_linear_tiny", model_kwargs=TWO_LAYERS, steps=2,
        batch_size=2, seq_len=32,
        log_every=1, loss_impl="chunked", loss_chunk=32, prefetch=0,
        metrics_path=str(path))).run()
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    rows = [r for r in rows if "loss" in r and "event" not in r]
    assert len(rows) == 2
    for row in rows:
        # 4 of 16 experts held: a quarter of the pairs under a fair router.
        assert 0.1 < row["moe_local_pair_share"] < 0.45
        assert row["moe_load_max_over_mean"] >= 1.0
        assert np.isfinite(row["loss"]) and np.isfinite(row["grad_norm"])


def test_counters_survive_gradient_accumulation():
    import optax

    from kubeflow_tpu.parallel.mesh import single_device_mesh
    from kubeflow_tpu.train.step import init_train_state, make_train_step

    model = kl.KimiLinear(dataclasses.replace(kl.kimi_linear_tiny(),
                                              **TWO_LAYERS))
    mesh = single_device_mesh()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (4, 33)),
                       jnp.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    metrics = {}
    for accum in (1, 2):
        state = init_train_state(model, optax.adamw(1e-3),
                                 jax.random.key(0), (batch["inputs"],), mesh)
        _, metrics[accum] = make_train_step(
            model, mesh, accum_steps=accum)(state, batch)
    for name in ("moe_local_pair_share", "loss"):
        assert float(metrics[2][name]) == pytest.approx(
            float(metrics[1][name]), rel=2e-2)


# -- the flash backward: value width of its own, q rows streamed -------------

def qkv(b, s, h, kh, d, dv, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d)),
            jax.random.normal(ks[1], (b, s, kh, d)),
            jax.random.normal(ks[2], (b, s, kh, dv)))


@pytest.mark.parametrize("h,kh,s", [(4, 4, 96), (4, 2, 80)])
def test_flash_value_width_differs_from_key_width(h, kh, s):
    q, k, v = qkv(1, s, h, kh, 24, 16)
    probe = jnp.cos(jnp.arange(16))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * probe)

    flash = functools.partial(fa.flash_attention, causal=True, block_q=32,
                              block_kv=32)
    out = flash(q, k, v)
    assert out.shape == (1, s, h, 16)
    assert rel(out, naive_attention(q, k, v)) < 1e-5
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    exp = jax.grad(loss(naive_attention), argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(got, exp):
        assert a.shape == e.shape and rel(a, e) < 1e-4


def test_flash_backward_past_the_old_row_wall():
    """6 q heads on one kv head at 1,024 rows: 6,144 grouped rows, which the
    dk/dv kernel used to hold whole (and could not, compiled, past ~5,000)."""
    q, k, v = qkv(1, 1024, 6, 1, 16, 16, seed=2)
    flash = functools.partial(fa.flash_attention, causal=True, block_q=512,
                              block_kv=512)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                   argnums=(0, 1, 2))(q, k, v)
    exp = jax.grad(lambda *a: jnp.sum(jnp.sin(naive_attention(*a))),
                   argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(got, exp):
        assert rel(a, e) < 1e-4


def _whole_rows_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                           dk_ref, dv_ref, *, block_q, block_kv, seq_q,
                           seq_kv, seq_q_pad, group, mask, sm_scale):
    """The dk/dv kernel as it was before the rows were streamed (PR 27's
    tree), kept here as the oracle for "unchanged to the bit": grouped q, dO,
    LSE and delta as whole rows, one fori_loop a head of the group."""
    j = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1) + j * block_kv
    kv_valid = cols < seq_kv
    first, num_q_blocks = fa._kv_visible(j, block_q, block_kv, seq_q_pad,
                                         mask)
    d = q_ref.shape[-1]

    def make_body(g):
        base = g * seq_q_pad

        def body(qi, carry):
            dk, dv = carry
            off = base + qi * block_q
            q = q_ref[0, pl.ds(off, block_q), :].astype(
                jnp.float32) * sm_scale
            do = do_ref[0, pl.ds(off, block_q), :].astype(jnp.float32)
            lse = lse_ref[0, pl.ds(off, block_q), :]
            delta = delta_ref[0, pl.ds(off, block_q), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0) + qi * block_q
            valid = fa._apply_mask(
                jnp.logical_and(kv_valid, rows < seq_q), rows, cols, mask)
            p = jnp.where(valid, jnp.exp(s - lse), 0.0)
            dv = dv + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                p * (dp - delta), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        return body

    dk = jnp.zeros((block_kv, d), jnp.float32)
    dv = jnp.zeros((block_kv, d), jnp.float32)
    for g in range(group):
        dk, dv = jax.lax.fori_loop(first, num_q_blocks, make_body(g),
                                   (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@pytest.mark.parametrize("kind", ["causal", "full"])
def test_flash_grouped_backward_unchanged_to_the_bit(kind):
    """6:1 groups (the Qwen cell's shape at toy sizes): dk and dv of the
    streamed kernel equal the whole-row kernel's bit for bit — the same
    products, added in the same order."""
    b, s, h, kh, d, blk = 2, 128, 6, 1, 16, 64
    q, k, v = qkv(b, s, h, kh, d, d, seed=4)
    g = jax.random.normal(jax.random.key(5), (b, s, h, d))
    causal = kind == "causal"
    _, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal, blk, blk), q, k, v)
    _, dk, dv = vjp(g)

    _, (o3, lse) = fa._attn_impl(q, k, v, causal, blk, blk, True)
    q3, k3, v3 = fa._flatten_heads(q, k, v)
    do3 = g.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    delta = jnp.sum(do3 * o3, axis=-1, keepdims=True)
    group, bkh = h // kh, b * kh
    rows = lambda x: x.reshape(bkh, group * s, x.shape[-1])
    whole = lambda w: pl.BlockSpec((1, group * s, w), lambda bi, j: (bi, 0, 0))
    block = pl.BlockSpec((1, blk, d), lambda bi, j: (bi, j, 0))
    dk3, dv3 = pl.pallas_call(
        functools.partial(
            _whole_rows_dkv_kernel, block_q=blk, block_kv=blk, seq_q=s,
            seq_kv=s, seq_q_pad=s, group=group, mask=fa.MaskSpec(kind),
            sm_scale=1.0 / d ** 0.5),
        grid=(bkh, s // blk),
        in_specs=[whole(d), whole(d), whole(1), whole(1), block, block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((bkh, s, d), k.dtype)] * 2,
        interpret=True,
    )(rows(q3), rows(do3), rows(lse), rows(delta), k3, v3)
    unflat = lambda x: x.reshape(b, kh, s, d).transpose(0, 2, 1, 3)
    assert np.array_equal(np.asarray(dk), np.asarray(unflat(dk3)))
    assert np.array_equal(np.asarray(dv), np.asarray(unflat(dv3)))


# -- the kernels at the cell's widths, compiled for the chip ------------------
# (the TPU's compiler is installed; the chip is described, not attached)

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,s,h,kh,d,dv", [
    (2, 8192, 32, 32, 192, 128),  # this model's latent attention at 8k
    (8, 1024, 12, 2, 128, 128),   # 6:1 groups at s1024: PR 24's VMEM wall
])
def test_flash_compiles_for_the_chip_at_real_widths(one_chip, b, s, h, kh, d,
                                                    dv):
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, True, 512, 512, False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(
        shape(b, s, h, d), shape(b, s, kh, d), shape(b, s, kh, dv)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # forward, dq, dk/dv


@pytest.mark.parametrize("call", ["core", "mixer"])
def test_kda_compiles_for_the_chip_at_real_widths(one_chip, call):
    """The cell's KDA layer, forward and backward, the core alone (fp32) and
    with the mixer's elementwise work around it (bf16 in and out): two Mosaic
    kernels, and nothing of a chunk's pairwise products [.., 16, 16, 128]
    outside them."""
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    b, t, h, d = 2, 8192, 32, 128
    if call == "core":
        def loss(*a):
            return jnp.sum(kda_chunked(*a, interpret=False))

        args = (shape(b, t, h, d),) * 4 + (shape(b, t, h),)
    else:
        def loss(pre, beta, convs, dt_bias, a_log, o_scale):
            return jnp.sum(kda_mixer(
                *pre, beta, convs=convs, dt_bias=dt_bias, a_log=a_log,
                o_scale=o_scale, l2_eps=L2_EPS, rms_eps=RMS_EPS,
                interpret=False).astype(jnp.float32))

        args = ((shape(b, t, h * d, dtype=jnp.bfloat16),) * 5, shape(b, t, h),
                (shape(4, h * d),) * 3, shape(h * d), shape(h), shape(d))
    text = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # kda_fwd, kda_bwd
    assert not re.search(r"\[[0-9,]*16,16,128\]", text)


@pytest.mark.parametrize("form", ["prefill", "decode"])
def test_eva_cores_compile_for_the_chip_at_real_widths(one_chip, form):
    """EvaByte's two attention cores (ops/eva.py) at the served cell's
    widths: a window piece of 2,048 through the flash kernel joined with 768
    summary rows, and the decode kernel's 16 rows reading 128 + 48 blocks
    each through their tables out of the 4.43 GB pool. Here, not in tests/test_evabyte.py:
    the described chip belongs to one test file (one worker loads the TPU's
    library)."""
    from kubeflow_tpu.ops import eva

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    h, d = 32, 128
    if form == "prefill":
        text = jax.jit(functools.partial(
            eva.attend_piece, interpret=False)).lower(
            *(shape(1, 2048, h, d),) * 3, *(shape(1, 768, h, d),) * 2,
            shape(1, dtype=jnp.int32)).compile().as_text()
        assert text.count("tpu_custom_call") >= 1  # the flash forward
    else:
        pool = shape(6 * 2817, 16, h, d)
        text = jax.jit(functools.partial(
            eva.attend_step, exact_blocks=128, interpret=False)).lower(
            shape(16, h, d), pool, pool, shape(16, 176, dtype=jnp.int32),
            shape(16, dtype=jnp.int32),
            shape(16, dtype=jnp.int32)).compile().as_text()
        # One kernel reads the rows through the tables; nothing of the
        # state ([16, 2816, 32, 128] of K and of V) is copied outside it.
        assert text.count("tpu_custom_call") == 1
        assert not re.search(r"\[16,(2816|176),", text)


@pytest.mark.parametrize("form", ["decode_core", "later_piece",
                                  "decode_experts"])
def test_latent_serving_compiles_for_the_chip_at_real_widths(one_chip, form):
    """What the served latent-attention expert model (models/joyai.py) asks
    of Mosaic at its published widths: the absorbed decode core's 16 rows
    reading up to 512 blocks of 16 latent rows (576 values, 640 as stored)
    each through their tables out of the pool; a later prompt piece's
    queries against the rows before it (no causal mask, key 192 / value 128,
    the kernel's row log-sum-exp returned); and the dropless expert layer at
    a decode step's size (128 pairs over 256 experts of 2048 x 768). Here,
    not in tests/test_joyai.py: the described chip belongs to one test
    file."""
    from kubeflow_tpu.models.moe import held_experts_ffn
    from kubeflow_tpu.ops import mla

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    if form == "decode_core":
        text = jax.jit(functools.partial(
            mla.absorbed_step, rank=512, scale=192 ** -0.5, group=16,
            interpret=False)).lower(
            shape(16, 32, 640), shape(5 * 8193, 16, 640),
            shape(16, 512, dtype=jnp.int32),
            shape(16, dtype=jnp.int32)).compile().as_text()
        # One kernel reads the rows through the tables; nothing of the
        # state ([16, 8192, 640]) is copied outside it.
        assert text.count("tpu_custom_call") == 1
        assert not re.search(r"\[16,(8192|512),(16,)?640\]", text)
    elif form == "later_piece":
        text = jax.jit(lambda q, k, v: fa.flash_attention_lse(
            q, k, v, False, 512, 512, False)).lower(
            shape(1, 2048, 32, 192), shape(1, 2048, 32, 192),
            shape(1, 2048, 32, 128)).compile().as_text()
        assert text.count("tpu_custom_call") == 1
    else:
        text = jax.jit(functools.partial(
            held_experts_ffn, start=0, num_experts=256, dtype=jnp.bfloat16,
            interpret=False)).lower(
            shape(16, 2048), shape(16, 8, dtype=jnp.int32),
            shape(16, 8, dtype=jnp.float32), shape(256, 2048, 768),
            shape(256, 2048, 768), shape(256, 768, 2048)
        ).compile().as_text()
        assert text.count("tpu_custom_call") == 3  # gate, up, down
