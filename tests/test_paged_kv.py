"""Paged KV cache (ISSUE 6 tentpole): block-table decode memory.

Covers the host-side allocator (alloc/free/refcount), the paged engine's
token-identity with the flat engine (greedy AND seeded sampling — the
gathered view runs the exact flat computation), the flat escape hatch's
seeded determinism (`kv_block_size=0` IS the pre-paging engine),
admission by free-block accounting (more concurrent requests than the
same memory holds flat rows), zero-copy prefix sharing with
copy-on-write tail forks, exhaustion shedding (engine + HTTP 503), and
prefix-cache block reclaim under pressure.
"""

import dataclasses
import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.llama import Llama, llama_tiny
from kubeflow_tpu.serve.generation import (GenerationEngine,
                                           KVCapacityExceeded)
from kubeflow_tpu.serve.paging import BlockAllocator, blocks_for
from tests.test_generate import ref_greedy

CFG = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=2)


@pytest.fixture(scope="module")
def tiny():
    model = Llama(CFG)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk", 4)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("pipeline_depth", 1)
    return GenerationEngine(model, params, CFG, **kw)


# -- allocator (pure host) ----------------------------------------------------

def test_allocator_alloc_free_refcount():
    a = BlockAllocator(8, 16)
    assert a.free_blocks == 8 and a.used_blocks == 0
    ids = a.alloc(3)
    assert len(ids) == 3 and 0 not in ids  # NULL block never handed out
    assert a.free_blocks == 5 and a.used_blocks == 3
    assert all(a.refcount(b) == 1 for b in ids)
    # Sharing: incref keeps blocks alive through one decref.
    a.incref(ids[:2])
    assert a.decref(ids) == 1  # only the unshared block frees
    assert a.free_blocks == 6
    assert a.refcount(ids[0]) == 1 and a.refcount(ids[2]) == 0
    assert a.decref(ids[:2]) == 2
    assert a.free_blocks == 8 and a.used_blocks == 0


def test_allocator_exhaustion_all_or_nothing_and_errors():
    a = BlockAllocator(4, 8)
    assert a.alloc(5) is None          # all-or-nothing: nothing taken
    assert a.free_blocks == 4
    ids = a.alloc(4)
    assert a.alloc(1) is None and a.can_alloc(0)
    a.decref(ids)
    assert a.free_blocks == 4
    with pytest.raises(ValueError):
        a.decref([ids[0]])             # double free is loud
    with pytest.raises(ValueError):
        a.incref([99])                 # unallocated id
    assert blocks_for(0, 8) == 0
    assert blocks_for(1, 8) == 1
    assert blocks_for(17, 8) == 3


# -- flat/paged identity ------------------------------------------------------

PROMPTS = {
    "3_in_one_bucket": [5, 9, 2],
    "8_exactly_the_bucket": [11, 4, 7, 30, 2, 19, 6, 8],
    # prefill + extend_mid + extend at prefill_buckets=(8,)
    "19_three_chunks": [17, 3, 3, 8, 1, 12, 25, 9, 14, 6, 21, 2, 7, 31,
                        10, 4, 18, 5, 13],
}


@pytest.fixture(scope="module")
def twins(tiny):
    """A flat and a paged engine per pipeline depth, same seed, built on
    first use and shared by every case: each case submits the same
    sequence to both, so their keys stay in step whatever the order."""
    made = {}

    def get(depth):
        if depth not in made:
            kw = dict(seed=7, pipeline_depth=depth, prefix_cache=4)
            made[depth] = (_engine(tiny, **kw),
                           _engine(tiny, kv_block_size=8, **kw))
        return made[depth]

    yield get
    for flat, paged in made.values():
        flat.close()
        paged.close()


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.8}],
                         ids=["greedy", "t0.8"])
@pytest.mark.parametrize("shape", list(PROMPTS))
def test_flat_vs_paged_token_identical_greedy_and_seeded_sampling(
        twins, shape, sampling, depth):
    """Flat and paged decode run one scan over a cache view (view row t
    is logical position t) behind one admission, so paged output —
    greedy and temperature-sampled under the same seed, one chunk or
    three, pipelined or not — must match flat token for token, logprob
    for logprob; and again from a stored prefix boundary."""
    flat, paged = twins(depth)
    prompt = PROMPTS[shape]

    def both():
        before = [e.stats_snapshot() for e in (flat, paged)]
        a = flat.submit(prompt, max_tokens=12, **sampling)
        b = paged.submit(prompt, max_tokens=12, **sampling)
        assert a["output_ids"] == b["output_ids"]
        assert a["output_logprobs"] == b["output_logprobs"]
        after = [e.stats_snapshot() for e in (flat, paged)]
        moved = [{k: y[k] - x[k] for k in ("prefill_chunks",
                                           "prefix_hit_tokens")}
                 for x, y in zip(before, after)]
        assert moved[0] == moved[1]
        return a, moved[0]

    first, moved = both()
    assert len(first["output_ids"]) == 12
    # The second submission resumes from the stored 16-token boundary
    # where there is one; on both engines alike.
    again, moved = both()
    resumed = 16 if len(prompt) == 19 else 0
    assert moved == {"prefill_chunks": -(-(len(prompt) - resumed) // 8),
                     "prefix_hit_tokens": resumed}
    if not sampling:
        assert again["output_ids"] == first["output_ids"]
        assert again["output_logprobs"] == first["output_logprobs"]


# -- the dispatch snapshot and the reserve (host only) -------------------------

def _bare_engine(**attrs):
    """An engine object with no thread, no model and no device state:
    only what the host-side helper under test reads."""
    eng = GenerationEngine.__new__(GenerationEngine)
    for k, v in attrs.items():
        setattr(eng, k, v)
    return eng


def test_gather_rows_snapshots_dispatch_state():
    """One row with its first token still on the device, one with a
    host-known last token, an empty slot and a live row outside the
    sub-batch: the six arrays both dispatchers read."""
    slots = [
        {"req": {"temperature": 0.7, "top_k": 5, "top_p": 0.9},
         "disp": 11, "idx": 11, "last": None,
         "pending": (object(), object()), "aid": 2},
        {"req": {"temperature": 0.0}, "disp": 23, "idx": 19, "last": 42,
         "pending": None},
        None,
        {"req": {"temperature": 0.3, "top_k": 9}, "disp": 30, "idx": 30,
         "last": 7, "pending": None, "aid": 1},
    ]
    eng = _bare_engine(n_slots=4, _slots=slots, _paged=False)
    idx, temps, ks, ps, aids, last = eng._gather_rows([0, 1])
    # Flat: the rider parks its batch-wide write at its own disp.
    assert idx.tolist() == [11, 23, 0, 30]
    assert temps.tolist() == pytest.approx([0.7, 0.0, 0.0, 0.0])
    assert ks.tolist() == [5, 0, 0, 0]
    assert ps.tolist() == pytest.approx([0.9, 1.0, 1.0, 1.0])
    assert aids.tolist() == [2, 0, 0, 0]
    # The pending row's token is spliced in on the device, not here.
    assert last.tolist() == [0, 42, 0, 0]
    assert [a.dtype.name for a in (idx, temps, ks, ps, aids, last)] == [
        "int32", "float32", "int32", "float32", "int32", "int32"]
    # Paged riders write the NULL block: nothing is parked.
    eng._paged = True
    assert eng._gather_rows([0, 1])[0].tolist() == [11, 23, 0, 0]


def test_reserve_blocks_is_one_rule_for_local_ship_and_remote():
    """Local and remote admission of the same (prompt, max_tokens) hold
    the same number of pool blocks; ship mode holds the prompt's alone;
    a draft reserve that fails gives the target's blocks back."""
    from kubeflow_tpu.serve.generation import _NeedKVBlocks

    alloc = BlockAllocator(24, 8)
    eng = _bare_engine(_kv_alloc=alloc, _kv_bs=8, _paged=True, _spec=None,
                       max_len=64, chunk=4)
    req = {"input_ids": list(range(1, 18)), "max_tokens": 40}
    held = {}
    for mode in (None, "remote", "ship"):
        fresh, dtable = eng._reserve_blocks(dict(req, mode=mode))
        assert dtable is None and alloc.used_blocks == len(fresh)
        held[mode] = len(fresh)
        alloc.decref(fresh)
    # 17 + 40 tokens in whole chunks, 8 to a block; the prompt alone: 3.
    assert held == {None: 8, "remote": 8, "ship": 3}
    fresh, _ = eng._reserve_blocks(req, 2)  # two blocks shared by a hit
    assert len(fresh) == 6
    alloc.decref(fresh)
    # With a draft, a spec-able request reserves as much again; 24
    # blocks hold one such pair, and the second fails whole.
    eng._spec = {"gamma": 2}
    fresh, dtable = eng._reserve_blocks(req)
    assert len(fresh) == len(dtable) == 8 and alloc.used_blocks == 16
    extra = alloc.alloc(1)  # 7 free: the target fits, the draft cannot
    with pytest.raises(_NeedKVBlocks):
        eng._reserve_blocks(req)
    assert alloc.used_blocks == 17
    alloc.decref(extra)
    # A truncated-sampling request never speculates: no draft blocks.
    assert eng._reserve_blocks(dict(req, top_k=5))[1] is None
    # Submit sheds by the same count what an empty pool could never
    # hold: 16 of 15 blocks with the draft's, 6 of 5 for a shipment.
    for n_blocks, mode, draft, fits in ((15, None, False, True),
                                        (15, "remote", True, False),
                                        (6, "ship", True, True),
                                        (5, "ship", True, False)):
        eng._kv_alloc = BlockAllocator(n_blocks, 8)
        eng._spec = {"gamma": 2} if draft else None
        if fits:
            eng._refuse_oversized(dict(req, mode=mode))
        else:
            with pytest.raises(KVCapacityExceeded, match="KV blocks"):
                eng._refuse_oversized(dict(req, mode=mode))


def test_flat_escape_hatch_seeded_determinism(tiny):
    """`kv_block_size=0` (the default) must be the flat engine exactly:
    same seed, same sampled stream, with and without the knob spelled
    out — the paged code paths are inert."""
    outs = []
    for kw in ({}, {"kv_block_size": 0, "kv_blocks": 0}):
        eng = _engine(tiny, seed=11, **kw)
        try:
            assert not eng._paged
            outs.append(eng.submit([5, 9, 2], max_tokens=10,
                                   temperature=0.9)["output_ids"])
        finally:
            eng.close()
    assert outs[0] == outs[1]


@pytest.mark.slow  # compile-heavy engine builds; full tier covers it
def test_paged_pipelined_depth2_matches_reference(tiny):
    """Paging composes with overlapped scheduling: block allocation is
    host bookkeeping at admit, so chained dispatch needs no new syncs —
    and greedy output stays reference-identical."""
    model, params = tiny
    eng = _engine(tiny, pipeline_depth=2, kv_block_size=8)
    prompt = [17, 3, 3, 8, 1]
    try:
        out = eng.submit(prompt, max_tokens=12)
        assert out["output_ids"] == ref_greedy(model, params, prompt, 12)
        assert eng.stats["decode_fetch_overlapped"] > 0
    finally:
        eng.close()


# -- admission by free blocks -------------------------------------------------

@pytest.mark.slow  # compile-heavy engine builds; full tier covers it
def test_paged_concurrency_exceeds_static_row_equivalent(tiny):
    """THE acceptance criterion: with a pool worth 4 flat max_len rows,
    the paged engine must sustain strictly MORE concurrent in-flight
    requests than those 4 static rows — with every request's output
    token-identical to reference greedy."""
    model, params = tiny
    # pool = 32 blocks x 8 = 256 tokens = 4 flat rows of max_len 64.
    eng = _engine(tiny, slots=8, pipeline_depth=2, kv_block_size=8,
                  kv_blocks=32)
    peak = [0]
    orig = eng._dispatch_chunk

    def spy(active, carry=None):
        peak[0] = max(peak[0], len(active))
        return orig(active, carry)

    eng._dispatch_chunk = spy
    prompts = [[3 + i, 7, 11 + i] for i in range(8)]
    refs = [ref_greedy(model, params, p, 8) for p in prompts]
    outs = [None] * 8

    def run(i):
        outs[i] = eng.submit(prompts[i], max_tokens=8)

    try:
        ts = [threading.Thread(target=run, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        for i in range(8):
            assert outs[i] is not None and \
                outs[i]["output_ids"] == refs[i], i
        assert peak[0] > 4, peak  # static-row equivalent of the pool
        # Every block returned on retirement.
        assert eng.kv_blocks_free == 32 and eng.kv_blocks_used == 0
    finally:
        eng.close()


@pytest.mark.slow  # compile-heavy engine builds; full tier covers it
def test_exhaustion_sheds_never_fits_and_queues_transient(tiny):
    model, params = tiny
    # 4 blocks x 8 = 32 tokens of pool.
    eng = _engine(tiny, slots=4, kv_block_size=8, kv_blocks=4)
    try:
        # Worst case 7 blocks > 4-block pool: can NEVER fit -> shed now.
        with pytest.raises(KVCapacityExceeded, match="KV blocks"):
            eng.submit(list(range(1, 40)), max_tokens=16)
        # Transient pressure: three 2-block requests against a 4-block
        # pool — at most two fit at once; the third waits head-of-line
        # and completes correctly.
        prompts = [[5 + i, 9, 2] for i in range(3)]
        refs = [ref_greedy(model, params, p, 8) for p in prompts]
        outs = [None] * 3

        def run(i):
            outs[i] = eng.submit(prompts[i], max_tokens=8, timeout=180)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        for i in range(3):
            assert outs[i] is not None and \
                outs[i]["output_ids"] == refs[i], i
        assert eng.kv_blocks_free == 4
    finally:
        eng.close()


# -- zero-copy prefix sharing + CoW -------------------------------------------

@pytest.mark.slow  # compile-heavy engine builds; full tier covers it
def test_prefix_zero_copy_hit_and_cow_fork(tiny):
    """A prefix hit maps fully-committed blocks into the new table by
    reference (zero-copy) and forks only the partially-filled tail
    block; the continued request stays token-identical to reference."""
    model, params = tiny
    eng = _engine(tiny, slots=4, prefix_cache=4, seed=5,
                  kv_block_size=8, kv_blocks=24)
    base = list(range(2, 22))  # 20 tokens: 2 full blocks + 4-row tail
    try:
        r1 = eng.submit(base, max_tokens=6)
        assert r1["output_ids"] == ref_greedy(model, params, base, 6)
        # Stored prefixes hold block refs, not copies: pool usage is the
        # cache's refs only once the request retired.
        assert eng.kv_blocks_used > 0
        r2 = eng.submit(base + [31, 32], max_tokens=6)
        assert r2["output_ids"] == ref_greedy(model, params,
                                              base + [31, 32], 6)
        s = eng.stats
        assert s["prefix_hits"] == 1
        assert s["prefix_zero_copy_hits"] == 1  # 2 shared full blocks
        assert s["kv_cow_copies"] == 1          # the forked tail block
        # A hit on a block-ALIGNED stored prefix forks nothing.
        aligned = base[:16]
        r3 = eng.submit(aligned + [40], max_tokens=4)
        assert r3["output_ids"] == ref_greedy(model, params,
                                              aligned + [40], 4)
        assert eng.stats["kv_cow_copies"] == 1
    finally:
        eng.close()


@pytest.mark.slow  # compile-heavy engine builds; full tier covers it
def test_prefix_cache_blocks_reclaimed_under_pressure(tiny):
    """Cached prefix blocks must yield to live traffic: when the pool
    cannot cover an admission, LRU prefix entries are evicted (their
    blocks freed) instead of the admission waiting forever."""
    model, params = tiny
    eng = _engine(tiny, slots=2, prefix_cache=8, kv_block_size=8,
                  kv_blocks=6)  # 48 tokens of pool
    try:
        # Park ~3 blocks of pool in prefix-cache refs.
        p1 = list(range(2, 20))  # 18 tokens -> 3 blocks
        eng.submit(p1, max_tokens=4)
        assert eng.kv_blocks_used >= 3
        # This request needs 5 blocks (25 tokens prompt + 8 budget
        # rounded) — only possible if the cache gives blocks back.
        p2 = list(range(30, 55))
        out = eng.submit(p2, max_tokens=8, timeout=120)
        assert out["output_ids"] == ref_greedy(model, params, p2, 8)
        # p2's own boundary stores may hold refs now, but nothing leaks:
        # live tables are all retired, so every used block must be
        # accounted for by a prefix-cache reference — a refcount leak
        # (e.g. a regressed collision decref) would strand blocks
        # outside this set.
        cached = {b for _, bl in eng._prefix_lru.values() for b in bl}
        assert eng.kv_blocks_used == len(cached)
        p3 = list(range(60, 85))
        out = eng.submit(p3, max_tokens=8, timeout=120)
        assert out["output_ids"] == ref_greedy(model, params, p3, 8)
    finally:
        eng.close()


# -- serving surface ----------------------------------------------------------

def _http(method, url, body=None):
    req = urllib.request.Request(url, method=method,
                                 data=json.dumps(body).encode()
                                 if body is not None else None)
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, dict(r.headers), json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"{}")


@pytest.fixture(scope="module")
def paged_server(tmp_path_factory):
    from kubeflow_tpu.serve import ModelServer, export_for_serving, \
        load_model

    d = str(tmp_path_factory.mktemp("pagedbundle"))
    export_for_serving(
        d, model="llama_tiny",
        model_kwargs={"dtype": "float32", "num_layers": 2},
        extra={"generative": {"slots": 2, "max_len": 64, "chunk": 4,
                              "prefill_buckets": [8],
                              "kv_block_size": 8, "kv_blocks": 6}})
    srv = ModelServer()
    srv.repo.register(load_model(d, name="llm"), model_dir=d)
    port = srv.start_background()
    yield f"http://127.0.0.1:{port}", srv
    srv.stop()


def test_http_kv_exhaustion_503_and_pool_gauges(paged_server):
    """The 503-shed path (satellite): a request that can never fit the
    pool sheds with Retry-After and rides tpk_shed_total; the pool
    gauges and paging counters render on /metrics."""
    base, _ = paged_server
    code, _, body = _http("POST", f"{base}/v1/models/llm:generate",
                          {"input_ids": [5, 9, 2], "max_tokens": 6})
    assert code == 200, body
    code, headers, body = _http(
        "POST", f"{base}/v1/models/llm:generate",
        {"input_ids": list(range(1, 50)), "max_tokens": 14})
    assert code == 503, body
    assert "KV blocks" in body["error"]
    assert headers.get("Retry-After")
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        text = r.read().decode()
    assert 'tpk_kv_blocks_free{model="llm"} 6' in text, text
    assert 'tpk_kv_blocks_used{model="llm"} 0' in text
    assert 'tpk_kv_cow_copies_total{model="llm"}' in text
    assert 'tpk_prefix_zero_copy_hits_total{model="llm"}' in text
    assert "tpk_shed_total" in text
    # Flat engines must NOT emit the pool gauges (metadata still says
    # why: paged_kv is null).
    code, _, md = _http("GET", f"{base}/v2/models/llm")
    assert code == 200 and md["paged_kv"]["blocks"] == 6


def test_http_kv_exhaustion_503_on_streaming_path(paged_server):
    """The STREAMING surface must shed identically: a pre-stream
    KVCapacityExceeded is a 503 + Retry-After, never the 400 the
    generic RuntimeError mapping would produce (review finding)."""
    base, _ = paged_server
    code, headers, body = _http(
        "POST", f"{base}/v1/models/llm:generate",
        {"input_ids": list(range(1, 50)), "max_tokens": 14,
         "stream": True})
    assert code == 503, body
    assert "KV blocks" in body["error"]
    assert headers.get("Retry-After")


# -- construction guards ------------------------------------------------------

def test_paged_rejects_bad_compositions(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="divide max_len"):
        _engine(tiny, kv_block_size=7)
    # Spec x paged composes now that the draft's KV lives in pool
    # blocks (its own block-table rows, per-slot): construction must
    # succeed, not refuse. The degenerate-gamma guard still holds.
    eng = _engine(tiny, kv_block_size=8, kv_blocks=48,
                  draft={"model": model, "params": params, "cfg": CFG})
    try:
        assert eng._spec is not None
    finally:
        eng.close()
    with pytest.raises(ValueError, match="gamma"):
        _engine(tiny, kv_block_size=8, kv_blocks=48,
                draft={"model": model, "params": params, "cfg": CFG,
                       "gamma": 0})
