"""How the serving engine holds weights (serve/weights.py): a leaf the
model's forward only rounds to `cfg.dtype` is rounded once, when the engine
takes the tree, and everything the forward reads as fp32 stays fp32. Held
here, at toy widths on the CPU, for both model families the benchmark
serves: the rule's outcome leaf by leaf, answers bit-equal to the fp32-held
tree through prefill, piecewise extend and decode, lowered programs with no
weight convert left in them, int8 leaves untouched, one tree between the
wrapper and the engine, and the two gauges in `stats`."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import evabyte
from kubeflow_tpu.models.llama import Llama, llama_tiny
from kubeflow_tpu.serve import weights
from kubeflow_tpu.serve.generation import GenerationEngine, GenerativeJAXModel
from kubeflow_tpu.serve.quant import Int8Leaf, QuantizedModule, quantize_tree

FAMILIES = {
    # model, configuration (bf16 compute, fp32 parameters), engine arguments
    "llama": (Llama, llama_tiny(),
              dict(slots=2, max_len=64, chunk=4, prefill_buckets=(8, 16))),
    "evabyte": (evabyte.EvaByte, evabyte.evabyte_tiny(),
                dict(slots=2, max_len=128, chunk=4, prefill_buckets=(8, 32),
                     kv_block_size=4, kv_blocks=64)),
}
#: What stays fp32 in the engine's tree; everything else is stored in bf16.
NORMS = {"final_norm/scale", "layers/input_norm/scale",
         "layers/post_attn_norm/scale"}
STAYS_FP32 = {
    "llama": NORMS,
    "evabyte": NORMS | {"embed", "layers/attn/adaptive_mu_k",
                        "layers/attn/adaptive_phi"},
}


def by_name(tree) -> dict:
    return {"/".join(str(k.key) for k in path if hasattr(k, "key")): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def built(request):
    """(family, model, cfg, the fp32 tree, an engine built from it)."""
    make, cfg, kw = FAMILIES[request.param]
    model = make(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = GenerationEngine(model, params, cfg, **kw)
    yield request.param, model, cfg, params, eng
    eng.close()


def test_the_rule_by_leaf(built):
    family, _, cfg, params, eng = built
    held, given = by_name(eng._params), by_name(params)
    assert list(held) == list(given)                 # the tree's structure
    assert {n for n, x in held.items()
            if x.dtype == jnp.float32} == STAYS_FP32[family]
    assert {x.dtype for x in held.values()} == {jnp.dtype(jnp.float32),
                                                jnp.dtype(cfg.dtype)}
    for name, x in held.items():
        # The caller's tree is the caller's still: nothing was donated.
        assert given[name].dtype == jnp.float32
        assert not given[name].is_deleted()
        np.testing.assert_array_equal(               # rounded, not redrawn
            np.asarray(x, np.float32),
            np.asarray(given[name].astype(x.dtype), np.float32))


def test_bit_equal_to_the_fp32_held_tree(built, monkeypatch):
    """The same requests through an engine that keeps every leaf as it
    came (the rule switched off for the comparison, which no option does):
    the same tokens and the same logprobs to the bit, over a prompt below
    one bucket, one cut into pieces, and the decode steps after each."""
    family, model, cfg, params, eng = built
    monkeypatch.setattr(
        weights, "stored_narrow",
        lambda model, params, state, dtype, **kw: [False] * len(
            jax.tree.leaves(params)))
    plain = GenerationEngine(model, params, cfg, **FAMILIES[family][2])
    try:
        assert all(x.dtype == jnp.float32
                   for x in jax.tree.leaves(plain._params))
        rng = np.random.RandomState(5)
        for n in (5, 41, 70 if family == "evabyte" else 30):
            ids = [int(t) for t in rng.randint(1, cfg.vocab_size, size=n)]
            got = eng.submit(ids, max_tokens=10)
            want = plain.submit(ids, max_tokens=10)
            assert got["output_ids"] == want["output_ids"]
            assert got["output_logprobs"] == want["output_logprobs"]
    finally:
        plain.close()


def _weight_converts(text: str, held) -> set:
    """Shapes that a lowered program rounds from fp32 to bf16 and that are
    those of a weight the engine stores rounded (whole, or one layer of a
    stacked one)."""
    shapes = set()
    for x in jax.tree.leaves(held):
        if x.ndim >= 2 and x.dtype == jnp.bfloat16:
            shapes |= {"x".join(map(str, x.shape)),
                       "x".join(map(str, x.shape[1:]))}
    found = re.findall(
        r"stablehlo\.convert .*\(tensor<([\dx]+)xf32>\) -> tensor<\1xbf16>",
        text)
    return {s for s in found if "x" in s and s in shapes}


def test_no_weight_convert_in_the_lowered_programs(built):
    family, _, cfg, params, eng = built
    b = eng.prefill_buckets[-1]
    one = jnp.ones((1,), jnp.int32)
    prefill = (jnp.zeros((1, b), jnp.int32), one, jnp.zeros((1,)), one * 0,
               jnp.ones((1,)), eng._key)
    n = eng.n_slots
    row = jnp.zeros((n,), jnp.int32)
    bucket = eng.decode_buckets[-1]
    tables = (eng._block_tables([], bucket // eng._kv_bs),) if eng._paged \
        else ()
    decode = (eng._cache, *tables, row, row, jnp.zeros((n,)), row,
              jnp.ones((n,)), eng._key)
    for fn, args in ((eng._prefill[b], prefill),
                     (eng._decode[(bucket, False)], decode)):
        text = fn.lower(eng._params, *args).as_text()
        assert _weight_converts(text, eng._params) == set()
        # The check can see one: the fp32 tree through the same function.
        assert _weight_converts(fn.lower(params, *args).as_text(),
                                eng._params)


def test_stats_carry_the_two_gauges(built):
    _, _, _, params, eng = built
    stats = eng.stats_snapshot()
    leaves = jax.tree.leaves(eng._params)
    assert stats["weight_bytes"] == sum(x.nbytes for x in leaves)
    assert stats["weight_bytes_fp32"] == sum(
        x.nbytes for x in leaves if x.dtype == jnp.float32)
    assert 0 < stats["weight_bytes_fp32"] < stats["weight_bytes"]
    assert stats["weight_bytes"] < sum(
        x.nbytes for x in jax.tree.leaves(params))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_wrapper_and_engine_hold_one_tree(family):
    """After load() `predict()` reads the engine's tree and gives the
    logits it gave; a wrapper told that the tree is its alone gives the
    fp32 leaves up as their rounded twins exist."""
    make, cfg, kw = FAMILIES[family]
    model = make(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.key(4), jnp.zeros((1, 8), jnp.int32))["params"])
    toks = np.arange(1, 13, dtype=np.int32)[None]
    gm = GenerativeJAXModel("m", model, params, cfg, generation=dict(kw),
                            donate_params=True)
    before = gm.predict([toks])[0]
    gm.load()
    try:
        assert gm._params is gm.engine._params
        np.testing.assert_array_equal(gm.predict([toks])[0], before)
        kept = {n for n, x in by_name(params).items() if not x.is_deleted()}
        assert kept == STAYS_FP32[family]
    finally:
        gm.unload()


def test_int8_leaves_pass_untouched():
    cfg = llama_tiny()
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])
    qtree = quantize_tree(params)
    eng = GenerationEngine(QuantizedModule(model, cfg.dtype), qtree, cfg,
                           **FAMILIES["llama"][2])
    try:
        is_q = lambda x: isinstance(x, Int8Leaf)
        went = jax.tree.leaves(qtree, is_leaf=is_q)
        came = jax.tree.leaves(eng._params, is_leaf=is_q)
        assert sum(map(is_q, went)) > 0
        for a, b in zip(went, came):
            assert is_q(a) == is_q(b)
            if is_q(a):
                assert (b.q.dtype, b.scale.dtype) == (jnp.int8, jnp.float32)
                np.testing.assert_array_equal(np.asarray(a.q),
                                              np.asarray(b.q))
                np.testing.assert_array_equal(np.asarray(a.scale),
                                              np.asarray(b.scale))
            else:  # what the quantiser left in floats: the norm scales
                assert b.dtype == jnp.float32
        stats = eng.stats_snapshot()
        assert stats["weight_bytes"] == sum(
            x.nbytes for x in jax.tree.leaves(eng._params))
        assert len(eng.submit([5, 9, 2], max_tokens=6)["output_ids"]) == 6
    finally:
        eng.close()


def test_a_sharded_tree_is_rounded_before_it_is_laid_out(devices8):
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

    make, cfg, kw = FAMILIES["llama"]
    model = make(cfg)
    params = model.init(jax.random.key(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]  # boxed
    mesh = build_mesh(MeshConfig(data=1, tensor=2), devices8[:2])
    ids = [5, 9, 2, 7, 11, 3, 8, 1, 4, 6, 12]
    one = GenerationEngine(model, params, cfg, **kw)
    try:
        want = one.submit(ids, max_tokens=6)["output_ids"]
        dtypes = [x.dtype for x in jax.tree.leaves(one._params)]
    finally:
        one.close()
    eng = GenerationEngine(model, params, cfg, mesh=mesh, **kw)
    try:
        held = jax.tree.leaves(eng._params)
        assert [x.dtype for x in held] == dtypes
        assert any(len(x.sharding.device_set) == 2
                   and not x.sharding.is_fully_replicated for x in held)
        assert eng.submit(ids, max_tokens=6)["output_ids"] == want
    finally:
        eng.close()


# -- the reading of a program -------------------------------------------------

def _double(x):
    return x * 2


@jax.custom_vjp
def _rounded(w):
    return w.astype(jnp.bfloat16)


_rounded.defvjp(lambda w: (_rounded(w), None), lambda _, g: (g,))

READS = {
    # name: (function of one fp32 [4, 4] leaf, what only_converted says)
    "convert": (lambda w: w.astype(jnp.bfloat16), True),
    "convert_to_another_dtype": (lambda w: w.astype(jnp.float16), False),
    "used_as_fp32": (lambda w: w * 2, False),
    "both": (lambda w: w.astype(jnp.bfloat16).sum() + w.sum(), False),
    "unread": (lambda w: jnp.zeros(()), None),
    "handed_on": (lambda w: w, False),
    "inside_jit": (jax.jit(lambda w: w.astype(jnp.bfloat16)), True),
    "inside_jit_as_fp32": (jax.jit(_double), False),
    "scanned_slices": (lambda w: jax.lax.scan(
        lambda c, row: (c + row.astype(jnp.bfloat16), None),
        jnp.zeros((4,), jnp.bfloat16), w)[0], True),
    "scanned_as_fp32": (lambda w: jax.lax.scan(
        lambda c, row: (c + row, None), jnp.zeros((4,)), w)[0], False),
    "scan_constant": (lambda w: jax.lax.scan(
        lambda c, _: (c + w.astype(jnp.bfloat16), None),
        jnp.zeros((4, 4), jnp.bfloat16), None, length=2)[0], True),
    "remat": (jax.checkpoint(lambda w: w.astype(jnp.bfloat16) * 2), True),
    "custom_vjp": (_rounded, True),
    "cond_branches": (lambda w: jax.lax.cond(
        w.astype(jnp.bfloat16)[0, 0] > 0,
        lambda: w.astype(jnp.bfloat16), lambda: -w.astype(jnp.bfloat16)),
        True),
    "cond_one_branch_fp32": (lambda w: jax.lax.cond(
        w.astype(jnp.bfloat16)[0, 0] > 0,
        lambda: w.astype(jnp.bfloat16).astype(jnp.float32), lambda: w),
        False),
    "while_constant": (lambda w: jax.lax.while_loop(
        lambda c: c[0] < 2,
        lambda c: (c[0] + 1, c[1] + w.astype(jnp.bfloat16)),
        (0, jnp.zeros((4, 4), jnp.bfloat16)))[1], True),
    "while_carry": (lambda w: jax.lax.while_loop(
        lambda c: c[0] < 2, lambda c: (c[0] + 1, c[1]), (0, w))[1], False),
}


@pytest.mark.parametrize("name", sorted(READS))
def test_only_converted_reads_a_program(name):
    fn, want = READS[name]
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4, 4), jnp.float32))
    assert weights.only_converted(jaxpr.jaxpr, jnp.bfloat16) == [want]


def test_nothing_is_traced_where_nothing_is_wider():
    """A model that computes in its parameters' dtype (most of this suite)
    has nothing to round, and pays no trace for it."""
    import dataclasses

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.eval_shape(
        lambda: nn.meta.unbox(model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    # `state` is never asked: a trace would fail on None.
    assert weights.stored_narrow(model, params, None, cfg.dtype, max_len=64,
                                 piece=8) == [False] * len(
                                     jax.tree.leaves(params))
