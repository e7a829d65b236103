"""EvaByte (models/evabyte.py, ops/eva.py) against its plain reference
(benchmarks/reference/evabyte.py) on seeded random weights at toy widths
(window 32, chunk 4, 2 layers, 4 heads of 16): the full forward with all
eight heads; prefill in window pieces and decode through the paged pool of
exact and summary blocks, logits at every position; the generation engine's
answers, its allocator's invariant at every fetch boundary and its refusals;
and the reference's four controls, each of which the comparison must refuse.
The model runs in fp32 here, so that the sound path agrees to rounding and a
control cannot hide in bf16's.
"""

import dataclasses
import importlib.util
import os
import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import evabyte
from kubeflow_tpu.serve.generation import (GenerationEngine,
                                           KVCapacityExceeded,
                                           build_engine_fns)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, CHUNK, MAX_LEN = 32, 4, 256
TOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "evabyte_reference",
        os.path.join(ROOT, "benchmarks", "reference", "evabyte.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = dataclasses.replace(evabyte.evabyte_tiny(), dtype=jnp.float32)
REF_CFG = {"num_hidden_layers": CFG.num_layers, "window_size": WINDOW,
           "chunk_size": CHUNK, "rope_theta": CFG.rope_theta,
           "rms_norm_eps": CFG.rms_eps}


def ids_of(n: int, seed: int) -> list[int]:
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, CFG.vocab_size, size=n)]


@pytest.fixture(scope="module")
def model():
    return evabyte.EvaByte(CFG)


@pytest.fixture(scope="module")
def params(model):
    return nn.meta.unbox(model.init(
        jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"])


@pytest.fixture(scope="module")
def engine(model, params):
    eng = GenerationEngine(model, params, CFG, slots=2, max_len=MAX_LEN,
                           chunk=4, prefill_buckets=[8, 16, 32],
                           kv_block_size=CHUNK, kv_blocks=26)
    yield eng
    eng.close()


# -- the model's full forward -------------------------------------------------

@pytest.mark.parametrize("length", [20, 32, 75])
def test_full_forward_matches_the_reference(model, params, length):
    """Below one window, exactly one, several with a ragged last chunk: all
    eight heads at every position."""
    ids = ids_of(length, length)
    got = model.apply({"params": params}, jnp.asarray(ids)[None])[0]
    want = ref.forward(params, ids, REF_CFG)
    assert got.shape == (length, CFG.num_pred_heads, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=TOL)


# -- what n rows hold ---------------------------------------------------------

@pytest.mark.parametrize("n, rows, held, peak", [
    (1, (1, 0), (1, 0), 1),
    (32, (32, 0), (8, 0), 8),       # a full window is still open
    (33, (1, 8), (1, 2), 10),       # its first successor pools it: the 8
    (100, (4, 24), (1, 6), 14),     # exact blocks beside their 2 summary
    (256, (32, 56), (8, 14), 22),   # blocks while that step is in flight
])
def test_state_arithmetic(n, rows, held, peak):
    state = CFG.serving_state(CHUNK, MAX_LEN)
    assert state.rows(n) == rows
    assert state.held(n) == held
    assert state.peak(n) == peak
    assert state.widths == (8, 14)


# -- prefill in pieces, decode through the pool -------------------------------

def test_logits_at_every_position_through_the_pool(model, params):
    """Two rows of one batch at different phases: a prompt inside the first
    window that decodes across its close, and one prefilled in three window
    pieces that decodes across the next. Every prompt position's logits and
    every decode step's, teacher-forced, against the reference's full
    forward; the rows' blocks are taken as `EvaState.held` says."""
    state = CFG.serving_state(CHUNK, MAX_LEN)
    fns = build_engine_fns(model, CFG, max_len=MAX_LEN, chunk=4,
                           prefill_buckets=[8, 16, 32], offset_writes=True,
                           kv_block_size=CHUNK)
    prompts, total = (30, 70), 44
    seqs = [ids_of(p + total, 100 + p) for p in prompts]
    want = [ref.forward(params, s, REF_CFG)[:, 0] for s in seqs]
    pool = state.pool(40)
    free = list(range(1, 41))
    tables = [{"exact": [], "summary": []} for _ in prompts]

    def take(row, n):
        for kind, need in zip(state.kinds, state.held(n)):
            while len(tables[row][kind]) < need:
                tables[row][kind].append(free.pop())

    def padded(kind, width):
        out = np.zeros((len(prompts), width), np.int32)
        for row, t in enumerate(tables):
            out[row, :len(t[kind])] = t[kind]
        return jnp.asarray(out)

    for row, (p, seq) in enumerate(zip(prompts, seqs)):
        frag = state.fragment()
        for at in range(0, p, WINDOW):
            piece = jnp.asarray(seq[at:min(at + WINDOW, p)])[None]
            logits, frag = model.apply(
                {"params": params}, piece, cache=frag,
                cache_index=jnp.asarray([at]), attend_full_cache=at > 0)
            np.testing.assert_allclose(
                logits[0], want[row][at:at + piece.shape[1]], atol=TOL)
        take(row, p)
        pool = fns["insert_paged"](pool, frag, {
            kind: padded(kind, w)[row]
            for kind, w in zip(state.kinds, state.widths)})
    step = jax.jit(lambda pool, tables, tok, idx: model.apply(
        {"params": params}, tok[:, None], cache={**pool, **tables},
        cache_index=idx))
    for j in range(total):
        for row, p in enumerate(prompts):
            take(row, p + j + 1)
        tabs = {kind: padded(kind, w)
                for kind, w in zip(state.kinds, state.widths)}
        logits, cache = step(
            pool, tabs, jnp.asarray([s[p + j] for p, s in zip(prompts, seqs)]),
            jnp.asarray([p + j for p in prompts]))
        pool = {name: cache[name] for name in pool}
        for row, p in enumerate(prompts):
            np.testing.assert_allclose(logits[row, 0], want[row][p + j],
                                       atol=TOL)
            # Once the close has run, the new window has taken the first
            # blocks over in place: what is past `held` is not read again.
            keep = state.held(p + j + 1)[0]
            free.extend(tables[row]["exact"][keep:])
            del tables[row]["exact"][keep:]


# -- the engine ---------------------------------------------------------------

def gap_to_reference(params, prompt, out, control=None):
    logits = ref.forward(params, prompt + out["output_ids"], REF_CFG,
                         control=control)
    lp = jax.nn.log_softmax(logits[len(prompt) - 1:-1, 0], axis=-1)
    want = np.asarray(lp)[np.arange(len(out["output_ids"])),
                          np.asarray(out["output_ids"])]
    return np.abs(want - np.asarray(out["output_logprobs"]))


@pytest.mark.parametrize("prompt, output", [(5, 6), (32, 9), (30, 12),
                                            (70, 40)])
def test_engine_answers_match_the_reference(engine, params, prompt, output):
    """Through `GenerationEngine`: inside a window, ending on its boundary,
    crossing it while decoding, and two closed windows with a third close
    mid-decode. The streamed logprobs against the reference's, teacher-
    forced; both kinds of block back in the pool afterwards."""
    ids = ids_of(prompt, prompt)
    out = engine.submit(ids, max_tokens=output)
    assert len(out["output_ids"]) == output
    assert gap_to_reference(params, ids, out).max() < TOL
    info = engine.kv_info()
    assert (info["blocks_used"], info["exact_blocks_used"],
            info["summary_blocks_used"]) == (0, 0, 0)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_each_control_fails_the_comparison(engine, params, control):
    """The same answers held against a reference that is wrong in one named
    way: no summary term, a sliding window, the (1 + g) offset off, bf16
    throughout. The sound path passes the limit each of them fails."""
    ids = ids_of(70, 70)
    out = engine.submit(ids, max_tokens=40)
    assert gap_to_reference(params, ids, out).max() < TOL
    assert gap_to_reference(params, ids, out, control).max() > 10 * TOL


def test_the_reference_in_the_stated_precision(params):
    """`precision="stated"` (bf16 matmuls accumulated in fp32; fp32 norms,
    scores, residual stream and logits) is the same forward: near the fp32
    one, nearer than bf16 throughout, and not equal to it."""
    ids = ids_of(75, 75)
    fp32, stated, below = (
        np.asarray(jax.nn.log_softmax(
            ref.forward(params, ids, REF_CFG, **how)[:, 0], axis=-1))
        for how in ({}, {"precision": "stated"}, {"control": "bfloat16"}))
    gap = np.abs(stated - fp32).mean()
    assert 1e-5 < gap < 0.05
    assert gap < np.abs(below - fp32).mean()
    with pytest.raises(ValueError, match="precision"):
        ref.forward(params, ids, REF_CFG, precision="fp16")


def test_allocator_invariant_at_every_fetch(engine):
    """Two requests side by side, each across window closes: at every fetch
    boundary a live request holds `held(rows dispatched)` of each kind
    (a whole window's exact blocks while the step that pools it is in
    flight), the pool never holds more than it has, the closed windows'
    blocks come back while the requests still decode, and nothing is held
    once they have retired."""
    state, seen = engine._state, []
    release = engine._release

    def watched(rec):
        release(rec)
        for st in engine._slots:
            if st is not None:
                seen.append((st["idx"], min(st["disp"], st["rows"]),
                             len(st["blocks"]), len(st["summary_blocks"]),
                             engine.kv_blocks_used))

    before = engine.stats_snapshot()
    engine._release = watched
    try:
        outs = {}
        th = [threading.Thread(target=lambda p=p, o=o: outs.update(
            {p: engine.submit(ids_of(p, p), max_tokens=o)}))
            for p, o in ((30, 40), (70, 56))]
        for t in th:
            t.start()
        for t in th:
            t.join()
    finally:
        engine._release = release
    assert {len(o["output_ids"]) for o in outs.values()} == {40, 56}
    assert seen
    for idx, disp, exact, summary, used in seen:
        held = [state.held(n) for n in range(idx, disp + 1)]
        assert summary == state.held(disp)[1]
        assert exact == max(h[0] for h in held)
        assert exact <= state.widths[0] and used <= 26
    after = engine.stats_snapshot()
    # 30 + 40 crosses 32 and 64, 70 + 56 crosses 96: each gives back the
    # window's 8 blocks less the one or two the new window took over.
    assert after["eva_windows_closed"] - before["eva_windows_closed"] == 3
    released = (after["eva_exact_blocks_released"]
                - before["eva_exact_blocks_released"])
    assert 3 * (state.widths[0] - 2) <= released <= 3 * (state.widths[0] - 1)
    assert after["eva_summary_rows"] > before["eva_summary_rows"]
    assert engine.kv_info()["blocks_used"] == 0


@pytest.mark.parametrize("kv_blocks, prompt, output, kind", [
    (6, 40, 8, "exact"),        # a window's 8 exact blocks never fit
    (9, 70, 40, "summary"),     # they do, the summaries beside them do not
])
def test_a_request_is_refused_when_either_kind_runs_out(model, params,
                                                        kv_blocks, prompt,
                                                        output, kind):
    eng = GenerationEngine(model, params, CFG, slots=1, max_len=128, chunk=4,
                           prefill_buckets=[32], kv_block_size=CHUNK,
                           kv_blocks=kv_blocks)
    try:
        with pytest.raises(KVCapacityExceeded, match="KV blocks"):
            eng.submit(ids_of(prompt, 1), max_tokens=output)
        # What fits is served, and the refusal held nothing back.
        out = eng.submit(ids_of(10, 2), max_tokens=8)
        assert len(out["output_ids"]) == 8
        assert eng.kv_info()["blocks_used"] == 0
    finally:
        eng.close()


@pytest.mark.parametrize("kwargs, reason", [
    ({"prefix_cache": 4}, "prefix_cache"),
    ({"draft": {"model": None, "params": None, "cfg": None}}, "draft"),
    ({"kv_quant": "int8"}, "kv_quant"),
    ({"role": "prefill"}, "shipment"),
    ({"kv_host_tier_blocks": 8}, "host tier"),
    ({"kv_block_size": 0}, "chunk_size"),        # the flat (rolling) layout
    ({"prefill_buckets": [8, 16]}, "one window at a time"),
])
def test_the_engine_refuses_what_it_cannot_do_with_two_kinds(model, params,
                                                             kwargs, reason):
    args = {"slots": 1, "max_len": 128, "chunk": 4,
            "prefill_buckets": [32], "kv_block_size": CHUNK, "kv_blocks": 16}
    args.update(kwargs)
    with pytest.raises(ValueError, match=reason):
        GenerationEngine(model, params, CFG, **args)
