"""Length-aware decode buckets + prefix caching:
decode cost tracks the longest active sequence, shared prompt prefixes
skip recompute, and greedy outputs are bit-identical either way."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.llama import Llama, llama_tiny
from kubeflow_tpu.serve.generation import GenerationEngine
from tests.test_generate import ref_greedy

pytestmark = pytest.mark.slow  # multi-process/e2e/AOT tier

CFG = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=2)


@pytest.fixture(scope="module")
def tiny():
    model = Llama(CFG)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    return model, params


def test_bucketed_decode_matches_unbucketed(tiny):
    """Small decode buckets (forcing slice + write-back every chunk) give
    the same greedy tokens as the single max_len-wide decode."""
    model, params = tiny
    prompts = [[5, 9, 2], [17, 3, 3, 8, 1, 40, 7]]
    outs = {}
    for label, buckets in (("bucketed", [16, 32, 48]), ("flat", None)):
        eng = GenerationEngine(model, params, CFG, slots=2, max_len=64,
                               chunk=4, prefill_buckets=(8, 16),
                               decode_buckets=buckets, prefix_cache=0)
        try:
            outs[label] = [eng.submit(p, max_tokens=10)["output_ids"]
                           for p in prompts]
        finally:
            eng.close()
    assert outs["bucketed"] == outs["flat"]
    for p in prompts:
        assert outs["flat"].pop(0) == ref_greedy(model, params, p, 10)


def test_decode_bucket_selection(tiny):
    """The engine compiles one decode executable per bucket and the
    derived default ladder is powers of two capped at max_len."""
    model, params = tiny
    eng = GenerationEngine(model, params, CFG, slots=1, max_len=96,
                           chunk=4, prefill_buckets=(8,), prefix_cache=0)
    try:
        assert eng.decode_buckets == [64, 96]
        assert set(eng._decode) == {(64, False), (64, True),
                                    (96, False), (96, True)}
    finally:
        eng.close()


def test_prefix_cache_reuse_same_output(tiny):
    """A request sharing a long head with an earlier one admits via the
    prefix cache (fewer prompt chunks recomputed) and still produces the
    exact greedy continuation."""
    model, params = tiny
    head = [7, 3, 11, 2, 9, 1, 4, 4, 30, 8, 2, 5, 19, 6, 1, 3,
            22, 9, 9, 1, 7, 2, 13, 5]  # 24 tokens = 3 full 8-chunks
    suffix_a, suffix_b = [40, 2, 6], [12, 33]
    cold = GenerationEngine(model, params, CFG, slots=1, max_len=64,
                            chunk=4, prefill_buckets=(8,), prefix_cache=0)
    try:
        want_b = cold.submit(head + suffix_b, max_tokens=8)["output_ids"]
    finally:
        cold.close()
    warm = GenerationEngine(model, params, CFG, slots=1, max_len=64,
                            chunk=4, prefill_buckets=(8,), prefix_cache=8)
    try:
        warm.submit(head + suffix_a, max_tokens=4)
        assert warm.stats["prefix_hits"] == 0
        got_b = warm.submit(head + suffix_b, max_tokens=8)["output_ids"]
        assert warm.stats["prefix_hits"] == 1
        assert warm.stats["prefix_hit_tokens"] >= 24
    finally:
        warm.close()
    assert got_b == want_b
    assert got_b == ref_greedy(model, params, head + suffix_b, 8)


def test_prefix_cache_offset_write_headroom(tiny):
    """Regression: with the largest prefill bucket == max_len (chunked
    admission unreachable), a prefix-cache hit still makes _extend write a
    bucket-wide update at a nonzero offset — the fragment must carry pad
    headroom or dynamic_update_slice clamps the start and corrupts the
    cached prompt KV silently."""
    model, params = tiny
    head = [7, 3, 11, 2, 9, 1, 4, 4, 30, 8] * 4  # 40 tokens
    suffix = [40, 2, 6, 9, 1, 22, 5, 13, 2, 17]
    cold = GenerationEngine(model, params, CFG, slots=1, max_len=64,
                            chunk=4, prefill_buckets=(8, 64),
                            prefix_cache=0)
    try:
        want = cold.submit(head + suffix, max_tokens=8)["output_ids"]
    finally:
        cold.close()
    warm = GenerationEngine(model, params, CFG, slots=1, max_len=64,
                            chunk=4, prefill_buckets=(8, 64),
                            prefix_cache=8)
    try:
        warm.submit(head, max_tokens=2)  # seeds the 40-token prefix
        got = warm.submit(head + suffix, max_tokens=8)["output_ids"]
        assert warm.stats["prefix_hits"] == 1
    finally:
        warm.close()
    assert got == want == ref_greedy(model, params, head + suffix, 8)


def test_prefix_cache_lru_bounded(tiny):
    model, params = tiny
    eng = GenerationEngine(model, params, CFG, slots=1, max_len=64,
                           chunk=4, prefill_buckets=(8,), prefix_cache=2)
    try:
        for i in range(5):
            eng.submit([i + 1] * 10, max_tokens=2)
        assert len(eng._prefix_lru) <= 2
    finally:
        eng.close()
