"""JoyAI-LLM-Flash (models/joyai.py, ops/mla.py, the decode-sized expert
layer of models/moe.py) against its plain reference (benchmarks/reference/
joyai.py) on seeded random weights at toy widths (3 layers, 2 heads, latent
rank 32 + 8, 16 experts top 4): the full forward; prefill in pieces and
decode through the paged pool of latent blocks, logits at every position;
the absorbed core against the unabsorbed attention; the expert layer at 1,
16 and 2,048 rows; what the engine stores of the weights and how it makes
them from a seed; the engine's answers, counters, drain and refusals; and the
reference's controls, each of which the comparison must refuse. The model
runs in fp32 here unless a test says otherwise, so that the sound path agrees
to rounding and a control cannot hide in bf16's.
"""

import dataclasses
import functools
import importlib.util
import os
import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import evabyte, joyai, llama
from kubeflow_tpu.models.moe import HeldExpertsBlock
from kubeflow_tpu.ops import mla
from kubeflow_tpu.serve import weights
from kubeflow_tpu.serve.generation import GenerationEngine, build_engine_fns

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, MAX_LEN = 4, 128
#: fp32 against fp32 at `highest`: the order of the sums is all that differs
#: (flash blocks and the absorbed products against one plain softmax).
TOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "joyai_reference",
        os.path.join(ROOT, "benchmarks", "reference", "joyai.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = dataclasses.replace(joyai.joyai_tiny(), dtype=jnp.float32)


def ref_cfg(cfg=CFG) -> dict:
    return {"num_hidden_layers": cfg.num_layers,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scaling_factor}


REF_CFG = ref_cfg()
EXAMPLE = np.zeros((1, 16), np.int32)


def ids_of(n: int, seed: int) -> list[int]:
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, CFG.vocab_size, size=n)]


def want_logits(params, ids, **how):
    return ref.forward(params.__getitem__, ids, REF_CFG, block=32, **how)


@pytest.fixture(scope="module")
def model():
    return joyai.JoyAI(CFG)


@pytest.fixture(scope="module")
def seeded(model):
    return weights.Seeded(model, jax.random.key(7), EXAMPLE)


@pytest.fixture(scope="module")
def params(seeded):
    return seeded.whole()


@pytest.fixture(scope="module")
def engine(model, seeded):
    """The one toy engine of this module, its weights made from the seed a
    group at a time."""
    eng = GenerationEngine(model, seeded, CFG, slots=2, max_len=MAX_LEN,
                           chunk=4, prefill_buckets=[8, 16, 32],
                           kv_block_size=BS, kv_blocks=64)
    yield eng
    eng.close()


# -- the model's full forward -------------------------------------------------

@pytest.mark.parametrize("length", [20, 50])
def test_full_forward_matches_the_reference(model, params, length):
    ids = ids_of(length, length)
    got = model.apply({"params": params}, jnp.asarray(ids)[None])[0]
    assert got.shape == (length, CFG.vocab_size)
    np.testing.assert_allclose(got, want_logits(params, ids), atol=TOL)


def test_rotary_is_over_interleaved_pairs():
    x = jax.random.normal(jax.random.key(0), (1, 5, 3, 8))
    got = joyai.rotate_pairs(x, jnp.arange(5)[None], 1e4)
    inv = 1.0 / (1e4 ** (np.arange(0, 8, 2) / 8))
    ang = np.arange(5)[:, None] * inv[None]
    z = (np.asarray(x[0, :, :, 0::2]) + 1j * np.asarray(x[0, :, :, 1::2])
         ) * np.exp(1j * ang)[:, None, :]
    np.testing.assert_allclose(got[0, :, :, 0::2], z.real, atol=1e-5)
    np.testing.assert_allclose(got[0, :, :, 1::2], z.imag, atol=1e-5)


# -- prefill in pieces, decode through the pool -------------------------------

def test_logits_at_every_position_through_the_pool(model, params):
    """Two rows of one batch: a prompt inside one piece, and one prefilled
    in three pieces (the last a smaller bucket). Every prompt position's
    logits and every decode step's, teacher-forced, against the reference's
    full forward; the rows' blocks are taken as `LatentState.held` says."""
    state = CFG.serving_state(BS, MAX_LEN)
    fns = build_engine_fns(model, CFG, max_len=MAX_LEN, chunk=4,
                           prefill_buckets=[8, 16, 32], offset_writes=True,
                           kv_block_size=BS)
    prompts, total = (30, 72), 10
    seqs = [ids_of(p + total, 100 + p) for p in prompts]
    want = [want_logits(params, s) for s in seqs]
    pool = state.pool(60)
    free = list(range(1, 61))
    tables = [[] for _ in prompts]

    @functools.lru_cache(maxsize=None)
    def piece_fn(after: bool):
        return jax.jit(lambda piece, frag, at: model.apply(
            {"params": params}, piece, cache=frag, cache_index=at,
            attend_full_cache=after))

    def take(row, n):
        while len(tables[row]) < state.held(n)[0]:
            tables[row].append(free.pop())

    def padded():
        out = np.zeros((len(prompts), state.widths[0]), np.int32)
        for row, t in enumerate(tables):
            out[row, :len(t)] = t
        return jnp.asarray(out)

    for row, (p, seq) in enumerate(zip(prompts, seqs)):
        frag = state.fragment(fns["frag_len"])
        for at in range(0, p, 32):
            n = min(32, p - at)
            width = next(b for b in (8, 16, 32) if b >= n)
            piece = np.zeros((1, width), np.int32)
            piece[0, :n] = seq[at:at + n]
            logits, frag = piece_fn(at > 0)(jnp.asarray(piece), frag,
                                            jnp.asarray([at]))
            np.testing.assert_allclose(logits[0, :n], want[row][at:at + n],
                                       atol=TOL)
        take(row, p)
        pool = fns["insert_paged"](pool, frag, {"latent": padded()[row]})
    step = jax.jit(lambda pool, tables, tok, idx: model.apply(
        {"params": params}, tok[:, None], cache={**pool, "latent": tables},
        cache_index=idx))
    for j in range(total):
        for row, p in enumerate(prompts):
            take(row, p + j + 1)
        logits, cache = step(
            pool, padded(),
            jnp.asarray([s[p + j] for p, s in zip(prompts, seqs)]),
            jnp.asarray([p + j for p in prompts]))
        pool = {"c": cache["c"]}
        for row, p in enumerate(prompts):
            np.testing.assert_allclose(logits[row, 0], want[row][p + j],
                                       atol=TOL)


@pytest.mark.parametrize("n, held", [(0, 0), (1, 1), (4, 1), (5, 2),
                                     (128, 32)])
def test_state_arithmetic(n, held):
    state = CFG.serving_state(BS, MAX_LEN)
    assert state.held(n) == (held,) and state.peak(n) == held
    assert state.widths == (32,)
    assert state.pool(3)["c"].shape == (CFG.num_layers, 4, BS, 128)
    assert state.read([n, 9]) == {"latent_rows": n + 11}


# -- the absorbed core --------------------------------------------------------

@pytest.mark.parametrize("rows", [(1, 7), (8, 16), (23, 40)])
def test_paged_kernel_matches_contiguous_rows(rows):
    """`mla_step` (interpreted) reading each row's blocks through its table
    out of a shuffled pool, against the same arithmetic over contiguous
    rows: one row inside its first block, whole groups, several groups with a
    ragged last block."""
    rank, rope, h, bs, group = 32, 8, 2, 4, 2
    width = mla.row_width(rank, rope)
    key = jax.random.key(sum(rows))
    kq, kr, kp = jax.random.split(key, 3)
    q = jnp.pad(jax.random.normal(kq, (2, h, rank + rope)),
                ((0, 0), (0, 0), (0, width - rank - rope)))
    flat = jnp.pad(jax.random.normal(kr, (2, 40, rank + rope)),
                   ((0, 0), (0, 0), (0, width - rank - rope)))
    order = np.asarray(jax.random.permutation(kp, 20)) + 1   # 0 is NULL
    tables = order.reshape(2, 10)
    pool = jnp.zeros((21, bs, width)).at[order].set(
        flat.reshape(20, bs, width))
    n = jnp.asarray(rows)
    got = mla.absorbed_step(q, pool, jnp.asarray(tables), n, rank=rank,
                            scale=0.2, group=group)
    want = mla.absorbed_rows(q, flat, n, rank=rank, scale=0.2)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_absorbed_is_unabsorbed_for_one_layer():
    """One attention layer: the last position of the whole sequence through
    the unabsorbed form, and the same position as a decode step through the
    pool after the rows before it were prefilled."""
    layer = joyai.LatentAttention(CFG, 0)
    t = 21
    x = jax.random.normal(jax.random.key(1), (1, t, CFG.hidden_size))
    pos = jnp.arange(t)[None]
    p = layer.init(jax.random.key(2), x, pos, None, None, False)
    whole, _ = layer.apply(p, x, pos, None, None, False)
    state = CFG.serving_state(BS, MAX_LEN)
    frag = {"c": state.fragment(64)["c"][:1]}
    _, frag = layer.apply(p, x[:, :t - 1], pos[:, :t - 1], frag,
                          jnp.zeros((1,), jnp.int32), False)
    table = jnp.arange(1, 33)[None]
    pool = {"c": jnp.zeros((1, 40, BS, CFG.row_width)).at[:, table[0, :16]]
            .set(frag["c"][:, 0].reshape(1, 16, BS, -1))}
    step, _ = layer.apply(p, x[:, t - 1:], pos[:, t - 1:],
                          {**pool, "latent": table},
                          jnp.asarray([t - 1]), False)
    np.testing.assert_allclose(step[0, 0], whole[0, -1], atol=1e-5)


# -- the expert layer at decode and prompt sizes ------------------------------

def expert_block(held=(0, 16), shared=32):
    return HeldExpertsBlock(
        hidden_size=48, expert_width=32, num_experts=16, experts_per_token=4,
        experts_held=held, routed_scale=2.5, shared_width=shared,
        dtype=jnp.float32, bias_init=nn.initializers.normal(0.02))


@pytest.fixture(scope="module")
def expert_params():
    return nn.meta.unbox(expert_block().init(
        jax.random.key(4), jnp.zeros((1, 4, 48)))["params"])


@pytest.mark.parametrize("rows", [1, 16, 2048])
def test_expert_layer_matches_all_experts_dense(expert_params, rows):
    """One decode row, a decode batch and a prompt piece against every
    expert computed for every token and weighed by its gate."""
    x = jax.random.normal(jax.random.key(rows), (1, rows, 48))
    y, share, _, touched = expert_block().apply({"params": expert_params}, x)
    with jax.default_matmul_precision("highest"):
        want, idx = ref.moe_ffn(
            x[0], expert_params, {"num_experts_per_tok": 4,
                                  "routed_scaling_factor": 2.5})
    assert float(share) == pytest.approx(1.0)
    assert int(touched) == len(np.unique(np.asarray(idx)))
    np.testing.assert_allclose(y[0], want, atol=1e-5)


def test_two_halves_add_up_to_the_whole(expert_params):
    """`experts_held` (0, 8) + (8, 8), the shared expert counted once."""
    x = jax.random.normal(jax.random.key(9), (1, 16, 48))
    whole, *_ = expert_block().apply({"params": expert_params}, x)
    routed = {k: v for k, v in expert_params.items() if k != "shared_expert"}
    total = whole - expert_block(shared=0).apply({"params": routed}, x)[0]
    touched = 0
    for start in (0, 8):
        mine = dict(routed, **{k: routed[k][start:start + 8]
                               for k in ("w_gate", "w_up", "w_down")})
        y, _, _, t = expert_block((start, 8), shared=0).apply(
            {"params": mine}, x)
        total, touched = total + y, touched + int(t)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert touched == int(expert_block().apply(
        {"params": expert_params}, x)[3])


# -- the weights: what is stored, and how a tree is made from a seed ----------

def test_the_engine_keeps_router_bias_and_norms_fp32():
    """serve/weights.py's outcome for this model, read off its programs:
    every matmul weight and the embedding in bf16; the router, its bias and
    every norm scale as they came."""
    cfg = joyai.joyai_tiny()
    model = joyai.JoyAI(cfg)
    seeded = weights.Seeded(model, jax.random.key(1), EXAMPLE)
    narrow = weights.stored_narrow(
        model, seeded, cfg.serving_state(BS, 64), cfg.dtype, max_len=64,
        piece=16)
    flat = jax.tree_util.tree_leaves_with_path(seeded.abstract)
    kept = {jax.tree_util.keystr(path) for (path, _), to
            in zip(flat, narrow) if not to}
    assert all(name.endswith(("['scale']", "['router']",
                              "['e_score_correction_bias']"))
               for name in kept)
    per_layer = 4                         # two block norms, two latent norms
    assert len(kept) == cfg.num_layers * per_layer + 1 + 2 * 2


def _llama_tiny():
    cfg = llama.llama_tiny()
    return llama.Llama(cfg), EXAMPLE


def _evabyte_tiny():
    return evabyte.EvaByte(evabyte.evabyte_tiny()), EXAMPLE


def _joyai_tiny():
    return joyai.JoyAI(dataclasses.replace(joyai.joyai_tiny(),
                                           num_layers=2)), EXAMPLE


@pytest.mark.parametrize("make", [_joyai_tiny, _llama_tiny, _evabyte_tiny])
def test_a_leaf_made_alone_has_the_whole_inits_bits(make):
    """`Seeded.make` (the equations of the traced `init` that the asked
    leaves depend on, run one by one) against `module.init` run whole:
    bit-equal, so a served tree is the one every check script rebuilds."""
    module, example = make()
    rng = jax.random.key(11)
    whole = nn.meta.unbox(module.init(rng, example)["params"])
    seeded = weights.Seeded(module, rng, example)
    assert jax.tree.structure(seeded.abstract) == jax.tree.structure(whole)
    want = jax.tree.leaves(whole)
    groups = seeded.together()
    assert sorted(i for g in groups for i in g) == list(range(len(want)))
    for group in groups:           # as `hold` makes them: a scan's together
        for i, got in zip(group, seeded.make(group)):
            assert got.dtype == want[i].dtype
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want[i]))
    alone = len(want) - 1          # and one leaf by itself
    np.testing.assert_array_equal(np.asarray(seeded.make([alone])[0]),
                                  np.asarray(want[alone]))


def test_held_tree_is_the_whole_tree_rounded(model, seeded, params, engine):
    for a, b in zip(jax.tree.leaves(engine._params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the engine ---------------------------------------------------------------

def gap_to_reference(params, prompt, out, **how):
    logits = want_logits(params, prompt + out["output_ids"], **how)
    lp = jax.nn.log_softmax(logits[len(prompt) - 1:-1], axis=-1)
    want = np.asarray(lp)[np.arange(len(out["output_ids"])),
                          np.asarray(out["output_ids"])]
    return np.abs(want - np.asarray(out["output_logprobs"]))


@pytest.mark.parametrize("prompt, output", [(5, 6), (32, 9), (33, 8),
                                            (70, 20)])
def test_engine_answers_match_the_reference(engine, params, prompt, output):
    """Through `GenerationEngine`: inside one piece, exactly one bucket, one
    token past a piece boundary, three pieces. The streamed logprobs
    against the reference's, teacher-forced; the blocks back afterwards."""
    ids = ids_of(prompt, prompt)
    out = engine.submit(ids, max_tokens=output)
    assert len(out["output_ids"]) == output
    assert gap_to_reference(params, ids, out).max() < TOL
    info = engine.kv_info()
    assert (info["blocks_used"], info["latent_blocks_used"]) == (0, 0)


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS
                                     if c not in ("router_bf16", "bfloat16")])
def test_each_control_fails_the_comparison(engine, params, control):
    """The same answers held against a reference that is wrong in one named
    way. (The two precision controls need bf16's roundings to differ; the
    benchmark's recorded readings hold those, benchmarks/tests.)"""
    ids = ids_of(70, 70)
    out = engine.submit(ids, max_tokens=20)
    assert gap_to_reference(params, ids, out).max() < TOL
    assert gap_to_reference(params, ids, out, control=control).max() \
        > 10 * TOL


def test_the_reference_in_the_stated_precision(params):
    ids = ids_of(60, 60)
    fp32, stated, below = (
        np.asarray(jax.nn.log_softmax(want_logits(params, ids, **how),
                                      axis=-1))
        for how in ({}, {"precision": "stated"}, {"control": "bfloat16"}))
    gap = np.abs(stated - fp32).mean()
    assert 1e-5 < gap < 0.1
    assert gap < np.abs(below - fp32).mean()
    with pytest.raises(ValueError, match="precision"):
        want_logits(params, ids, precision="fp16")


def test_counters_tick_and_blocks_come_back_after_a_drain(engine):
    """Two requests side by side: the host's `latent_rows` is what their
    dispatches' first steps read, the device's counter arrives with the
    tokens, every block is back once both have retired."""
    before = engine.stats_snapshot()
    outs = {}
    th = [threading.Thread(target=lambda p=p, o=o: outs.update(
        {p: engine.submit(ids_of(p, p), max_tokens=o)}))
        for p, o in ((30, 16), (70, 24))]
    for t in th:
        t.start()
    for t in th:
        t.join()
    assert {len(o["output_ids"]) for o in outs.values()} == {16, 24}
    after = engine.stats_snapshot()
    moved = {k: after[k] - before[k] for k in after
             if isinstance(after[k], (int, float))}
    n = moved["decode_dispatches"]
    # A row reads its context and its own new row, at each of its dispatches.
    own = moved["latent_rows"] - moved["decode_context_tokens"]
    assert n <= own <= 2 * n
    layers = CFG.num_layers - CFG.first_k_dense_replace
    # Both slots ride every dispatch (a dead row routes too): 2 rows x 4
    # choices a layer, onto at least 4 and at most 8 distinct experts.
    assert "moe_pairs" not in moved
    assert 4 * layers * n <= moved["moe_experts_touched"] <= 8 * layers * n
    info = engine.kv_info()
    assert (info["blocks_used"], info["latent_blocks_used"]) == (0, 0)


@pytest.mark.parametrize("kwargs, reason", [
    ({"prefix_cache": 4}, "prefix_cache"),
    ({"draft": {"model": None, "params": None, "cfg": None}}, "draft"),
    ({"kv_quant": "int8"}, "kv_quant"),
    ({"role": "prefill"}, "shipment"),
    ({"kv_host_tier_blocks": 8}, "host tier"),
    ({"kv_block_size": 0}, "paged pool"),
    ({"prefill_buckets": [12, 32]}, "divide the largest"),
])
def test_the_engine_refuses_what_it_cannot_do_with_latent_blocks(
        model, params, kwargs, reason):
    args = {"slots": 1, "max_len": 64, "chunk": 4, "prefill_buckets": [32],
            "kv_block_size": BS, "kv_blocks": 16}
    args.update(kwargs)
    with pytest.raises(ValueError, match=reason):
        GenerationEngine(model, params, CFG, **args)
