"""Generative LLM serving: KV-cache decode, sampling, continuous batching.

The reference's flagship LLM runtime is the vLLM-backed huggingfaceserver
(⟨kserve: python/huggingfaceserver⟩, SURVEY.md §2.2/§3.3 rebuild note).
Its design — paged KV blocks, per-step GPU kernel launches, token-level
continuous batching — does not map to XLA. The TPU-native shape:

  * **Functional cache**: one global slot-batched cache [L, B_slots, T, KH,
    D] carried through pure jitted fns (models/llama.py `init_cache`);
    stale slot content needs no eviction — absolute-position masking hides
    anything past a slot's write index.
  * **AOT everything**: prefill compiled per prompt-length bucket,
    decode compiled once — the hot path never traces.
  * **Chunked decode**: one dispatch runs `lax.scan` over K decode steps
    with on-device sampling, returning K tokens/slot. A host sync stalls
    the device's dispatch queue for a host round trip, so per-token sync
    decoding leaves the chip idle once per token; chunking amortizes the
    stall K×.
  * **Continuous batching at chunk boundaries**: finished slots are
    re-admitted (prefill → cache insert at the slot index) between decode
    dispatches — the scheduling granularity is the chunk, not the token,
    which is the right trade under compiled static shapes.

Sampling: greedy (temperature 0), temperature, top-k, and nucleus
(top-p) sampling — all per-slot and on device.

Speculative decoding (`draft=`): a small draft model proposes gamma
tokens per step and the target verifies them in ONE forward — greedy
output stays token-identical to vanilla decode (the first mismatch emits
the target's own argmax), and plain-temperature requests use the
standard rejection scheme whose emitted marginal IS the tempered target
distribution (`spec_acceptance`) — the speedup is free of quality loss
either way; see `build_spec_decode`. Top-k/top-p requests fall back to
plain chunked decode.
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.serve import weights
from kubeflow_tpu.serve.kv_transfer import (HostKVTier, ShipmentError,
                                            pack_shipment,
                                            require_row_blocks,
                                            unpack_shipment)
from kubeflow_tpu.serve.model import Model
from kubeflow_tpu.serve.paging import (BlockAllocator, blocks_for,
                                       require_rows, serving_state)
from kubeflow_tpu.serve.quant import (KV_QUANT_MODES, kv_dequantize_rows,
                                      kv_qdtype, kv_quantize_rows,
                                      require_kv_planes)
from kubeflow_tpu.utils import devices, obs
from kubeflow_tpu.utils.resilience import (Deadline, DeadlineExceeded,
                                           metrics as res_metrics)

#: tpk_kv_shipment_bytes buckets — wire-payload-shaped (1 KiB tiny-model
#: handoffs to multi-MiB production blocks), NOT the latency-shaped
#: default. Quantified wire savings: fmt-3 shipments of the same blocks
#: land ≈2 buckets lower than fmt-1 (DISAGGBENCH reports only wall).
_SHIPMENT_BUCKETS = (1024.0, 4096.0, 16384.0, 65536.0, 262144.0,
                     1048576.0, 4194304.0, 16777216.0, 67108864.0)

#: Engine roles (disaggregated prefill/decode, ISSUE 13). "unified" is
#: the escape hatch — today's engine bit-for-bit, serving both phases
#: from one loop. A "prefill" engine only chunk-prefills and SHIPS
#: committed KV blocks (prefill_ship); a "decode" engine only admits
#: shipped blocks (submit_remote) and never runs a prefill chunk, so
#: long-prompt admission cannot steal decode dispatches from in-flight
#: streams.
ENGINE_ROLES = ("unified", "prefill", "decode")

NEG_INF = -1e30


class KVCapacityExceeded(RuntimeError):
    """The request's worst-case KV footprint exceeds the whole paged
    pool — it can NEVER be admitted, no matter how long it waits. The
    HTTP serving surfaces (native :generate, OpenAI facade, both
    streaming and not) map this to a shed — 503 + Retry-After, counted
    in tpk_shed_total — instead of a 400: the spec is valid, this
    replica's pool is just too small."""


class _NeedKVBlocks(Exception):
    """Internal admission signal: the pool cannot cover the request's
    worst-case block need RIGHT NOW (it fits the pool in principle).
    The scheduler keeps the request queued — head-of-line, so a large
    request cannot be starved by a stream of small ones — and retries
    as retirements free blocks."""


def _chosen_logprob(logits: jax.Array, tok: jax.Array) -> jax.Array:
    """log P(tok) under the UNTEMPERED distribution — the logprob surface
    OpenAI reports. logits [..., V], tok [...] -> [...] f32."""
    l32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(l32, axis=-1)
    gold = jnp.take_along_axis(l32, tok[..., None], axis=-1)[..., 0]
    return gold - lse


def sample_tokens(logits: jax.Array, temperature: jax.Array,
                  key: jax.Array, top_k: jax.Array | None = None,
                  top_p: jax.Array | None = None) -> jax.Array:
    """Per-row sampling: argmax where temperature<=0, else categorical at
    that temperature with optional per-row nucleus/top-k truncation.
    logits [B, V]; temperature/top_p [B] f32; top_k [B] int32 (<=0 means
    disabled) -> [B] int32. All on device — one fused dispatch per step."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperature, 1e-4)[:, None]
    scaled = logits.astype(jnp.float32) / safe_t
    if top_k is not None or top_p is not None:
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V]
        keep = jnp.ones_like(scaled, bool)
        if top_k is not None:
            idx = jnp.clip(top_k - 1, 0, v - 1)[:, None]
            kth = jnp.take_along_axis(sorted_desc, idx, axis=-1)  # [B,1]
            keep &= jnp.where(top_k[:, None] > 0, scaled >= kth, True)
        if top_p is not None:
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            # Keep the smallest prefix whose mass reaches p (top token
            # always survives): a token is kept iff the mass STRICTLY
            # before it is < p.
            cum_before = jnp.cumsum(probs, axis=-1) - probs
            # Cutoff value: the smallest sorted logit still kept.
            kept_sorted = cum_before < top_p[:, None]
            cutoff_idx = jnp.maximum(
                jnp.sum(kept_sorted, axis=-1, keepdims=True) - 1, 0)
            cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx, axis=-1)
            keep &= jnp.where(top_p[:, None] < 1.0, scaled >= cutoff, True)
        scaled = jnp.where(keep, scaled, NEG_INF)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def gather_view(pool, tables):
    """Paged pool -> contiguous per-row view, leaf by leaf:
    [L, NB, bs, ...] x [B, nb] -> [L, B, nb*bs, ...]. View row j*bs + r is
    block tables[b, j] row r, which is logical position j*bs + r because
    tables are position-ordered."""
    def leaf(p):
        g = jnp.take(p, tables, axis=1)  # [L, B, nb, bs, ...]
        return g.reshape(g.shape[0], g.shape[1],
                         g.shape[2] * g.shape[3], *g.shape[4:])
    return jax.tree.map(leaf, pool)


def scatter_view(pool, view, tables):
    """Write a `gather_view` view back to its blocks. Duplicate ids (shared
    prefix blocks across rows, NULL-block pads) are benign: a shared
    block is immutable, so every row writes its original values; the
    NULL block receives garbage nobody reads."""
    b, nb = tables.shape

    def leaf(p, v):
        bs = p.shape[2]
        v = v.reshape(v.shape[0], b, nb, bs, *v.shape[3:])
        v = v.reshape(v.shape[0], b * nb, bs, *v.shape[4:])
        return p.at[:, tables.reshape(-1)].set(v)
    return jax.tree.map(leaf, pool, view)


def build_engine_fns(model, cfg, *, max_len: int, chunk: int,
                     prefill_buckets: Sequence[int],
                     offset_writes: bool,
                     cache_sharding=None, adapters=None,
                     rolling_window: int = 0,
                     kv_block_size: int = 0,
                     kv_quant: str = "none") -> dict:
    """The engine's pure device functions, as unjitted closures.

    Single source of truth shared by the live `GenerationEngine` (which
    jits them) and the 8B serving scale proof (which AOT-lowers THESE
    functions with tensor-parallel shardings — proving the memory envelope
    of the actual product, not a hand-written stand-in;
    `utils/scaleproof.py` serve_8b_tp8). `cache_sharding` (a NamedSharding
    or None) pins fragment caches created inside prefill so GSPMD shards
    KV heads over `tensor` instead of guessing from use.

    `adapters` (serve/multilora.py stacks): every fn gains an optional
    trailing `aid` (adapter index per row, 0 = base) and the model call
    gathers per-row adapter deltas — multi-LoRA inside one compiled
    program. Callers that never pass `aid` keep base behavior exactly.

    `rolling_window` > 0 switches the cache to the rolling sliding-window
    layout (models/llama.py init_cache): caches hold `window` rows, every
    admission fn passes EXPLICIT positions whose padded tail is the
    sentinel (so modular writes skip pad rows), and decode passes the raw
    absolute index (the model wraps it; clamping would corrupt positions).

    `kv_block_size` > 0 additionally builds the PAGED variants (serve/
    paging.py design note): the persistent cache is a pool of fixed-size
    blocks `[L, n_blocks, block_size, KH, D]` and each decode row's
    history lives wherever its block table points. Flat and paged decode
    run the one `decode_scan` below over a cache VIEW: flat slices the
    first `bucket` rows and writes them back, paged gathers the block
    tables into a contiguous `[L, B, bucket, ...]` view (`gather_view`;
    view row t IS logical position t, so masking/positions/sampling
    need no paged case) and scatters it back block-by-block.
    Scatter-back rewrites shared (immutable) blocks with their own
    values and pads through the reserved NULL block 0, so duplicate
    scatter indices can only ever disagree on garbage nobody reads
    (absolute-position masking hides every row past a request's write
    index, exactly as it hides stale flat slots).

    `kv_quant` != "none" (ISSUE 19, paged only) stores the pool as
    int8/fp8 payloads with per-row f32 scale planes "ks"/"vs" addressed
    by the same block ids. Gather/scatter and the scan carry are
    tree-generic, so the quantized view (values + scales) flows through
    the same decode and the model applies scales output-side
    (models/llama.py decode branch) — no full-width dequantized cache
    ever exists in the scan. Only the admission boundary differs:
    `insert_paged_quant` quantizes the fragment's rows with
    `kv_quantize_rows`, the encode the scan's row writes call too, and
    `frag_from_pool_quant` dequantizes into the full-precision fragment
    (admission-side, outside any scan).
    """
    state = serving_state(cfg, kv_block_size, max_len)
    prefill_buckets = sorted(prefill_buckets)
    big = prefill_buckets[-1]
    frag_len = max_len + (big if offset_writes else 0)
    rolling = int(rolling_window) > 0
    cache_len = rolling_window if rolling else max_len
    sentinel = -(int(rolling_window) + 1)

    def _chunk_positions(index, length, width):
        """Absolute positions for a right-padded chunk, pad tail at the
        sentinel — rolling mode only (the sentinel both masks pad keys
        out of attention and stops their modular cache writes)."""
        ar = jnp.arange(width)[None]
        return jnp.where(ar < length[:, None], index[:, None] + ar,
                         sentinel)

    def apply_kw(aid) -> dict:
        if aid is None or adapters is None:
            return {}
        return {"adapter": adapters, "adapter_ids": aid}

    def _constrain_cache(cache):
        if cache_sharding is None:
            return cache
        return {k: (jax.lax.with_sharding_constraint(v, cache_sharding)
                    if k in ("k", "v") else v)
                for k, v in cache.items()}

    def prefill(params, tokens, length, temperature, top_k, top_p, key,
                aid=None):
        """tokens [1, S_bucket] right-padded; returns (frag_cache,
        first sampled token [1], its logprob [1])."""
        cache = _constrain_cache(state.fragment(frag_len))
        kw = apply_kw(aid)
        if rolling:
            kw["positions"] = _chunk_positions(
                jnp.zeros((1,), jnp.int32), length, tokens.shape[1])
        logits, cache = model.apply(
            {"params": params}, tokens, cache=cache,
            cache_index=jnp.zeros((1,), jnp.int32), **kw)
        last = jnp.take_along_axis(
            logits, (length - 1)[:, None, None], axis=1)[:, 0]  # [1, V]
        tok = sample_tokens(last, temperature, key, top_k, top_p)
        return cache, tok, _chosen_logprob(last, tok)

    def extend(params, cache, tokens, length, index, temperature,
               top_k, top_p, key, aid=None):
        """FINAL continuation chunk of a long prompt: tokens
        [1, S_bucket] right-padded, written at offset `index` [1],
        attending over the WHOLE fragment cache; samples the first
        generated token like prefill."""
        if rolling:
            positions = _chunk_positions(index, length, tokens.shape[1])
        else:
            positions = index[:, None] + jnp.arange(tokens.shape[1])[None]
        logits, cache = model.apply(
            {"params": params}, tokens, cache=cache, cache_index=index,
            positions=positions, attend_full_cache=True, **apply_kw(aid))
        last = jnp.take_along_axis(
            logits, (length - 1)[:, None, None], axis=1)[:, 0]
        tok = sample_tokens(last, temperature, key, top_k, top_p)
        return cache, tok, _chosen_logprob(last, tok)

    def extend_mid(params, cache, tokens, index, aid=None):
        """Intermediate continuation chunk: cache write + attention
        only — return_hidden skips the full-vocab unembedding whose
        sampled token would be discarded anyway. Intermediate chunks are
        always FULL (only the final piece of a prompt can be partial —
        _admit_inner), so rolling mode needs no pad sentinel here."""
        positions = index[:, None] + jnp.arange(tokens.shape[1])[None]
        _, cache = model.apply(
            {"params": params}, tokens, cache=cache, cache_index=index,
            positions=positions, attend_full_cache=True,
            return_hidden=True, **apply_kw(aid))
        return cache

    def insert(cache, frag, slot):
        """Write a prefill fragment (slot-batch 1) into slot `slot`,
        dropping the fragment's pad-headroom rows past max_len."""
        return jax.tree.map(
            lambda c, f: jax.lax.dynamic_update_slice(
                c,
                jax.lax.slice_in_dim(f, 0, c.shape[2], axis=2).astype(
                    c.dtype),
                (0, slot) + (0,) * (c.ndim - 2)), cache, frag)

    def decode_scan(truncate, bucket, params, view, last_tok, index,
                    temperature, top_k, top_p, key, aid):
        """`chunk` decode steps over a cache view whose row t is logical
        position t — the one scan behind flat and paged `decode_chunk`.
        The non-truncating variant skips the full-vocab sort/cumsum:
        all-greedy/plain-temperature traffic (the defaults) must not pay
        O(V log V) per token. Rolling mode passes the index through RAW
        — the model wraps it modularly and needs the absolute value for
        positions. Returns (view, tokens [K, B], logprobs [K, B], what the
        model counted on the device at the chunk's first step: the scalars
        it sows into its `counters` collection by name, {} for a model that
        sows none)."""
        def step(carry, _):
            view, tok, idx, key = carry
            key, sub = jax.random.split(key)
            (logits, view), sown = model.apply(
                {"params": params}, tok[:, None], cache=view,
                cache_index=(idx if rolling
                             else jnp.minimum(idx, bucket - 1)),
                mutable=["counters"], **apply_kw(aid))
            if truncate:
                nxt = sample_tokens(logits[:, 0], temperature, sub,
                                    top_k, top_p)
            else:
                nxt = sample_tokens(logits[:, 0], temperature, sub)
            lp = _chosen_logprob(logits[:, 0], nxt)
            counts = {name: sum(vals) for name, vals
                      in sown.get("counters", {}).items()}
            return (view, nxt, idx + 1, key), (nxt, lp, counts)

        (view, _, _, _), (toks, lps, counts) = jax.lax.scan(
            step, (view, last_tok, index, key), None, length=chunk)
        return view, toks, lps, jax.tree.map(lambda c: c[0], counts)

    def make_decode(truncate: bool, bucket: int):
        def decode_chunk(params, cache, last_tok, index, temperature,
                         top_k, top_p, key, aid=None):
            """K decode steps under one dispatch; on-device sampling.
            last_tok/index/temperature [B]; returns (cache,
            tokens [B, K], logprobs [B, K], the model's device counts).
            Attention runs over the
            first `bucket` cache rows only (the loop picks the smallest
            bucket covering every active sequence), then the slice is
            written back. A rolling cache is `window` rows, its one
            bucket, and is never sliced."""
            sliced = (cache if bucket == cache_len else jax.tree.map(
                lambda c: jax.lax.slice_in_dim(c, 0, bucket, axis=2),
                cache))
            sliced, toks, lps, counts = decode_scan(
                truncate, bucket, params, sliced, last_tok, index,
                temperature, top_k, top_p, key, aid)
            if bucket != cache_len:
                cache = jax.tree.map(
                    lambda c, s: jax.lax.dynamic_update_slice(
                        c, s, (0,) * c.ndim), cache, sliced)
            else:
                cache = sliced
            return cache, toks.T, lps.T, counts
        return decode_chunk

    fns = {"prefill": prefill, "extend": extend, "extend_mid": extend_mid,
           "insert": insert, "make_decode": make_decode,
           "frag_len": frag_len}
    if kv_block_size > 0:
        if rolling:
            raise ValueError(
                "paged KV does not compose with the rolling cache")
        bs = int(kv_block_size)
        mb = max_len // bs  # blocks covering one full-length request

        # What the scan runs over. Rows: a contiguous copy gathered
        # through the tables and scattered back. A state of several kinds
        # of block (`tables` a dict, one table a kind): the pool itself
        # with the tables beside it, which the model reads and writes in
        # place: no copy of the state a dispatch.
        if state.grows:
            def view_of(pool, tables):
                return {**pool, **tables}

            def write_back(pool, view, tables):
                return {name: view[name] for name in pool}
        else:
            view_of, write_back = gather_view, scatter_view

        def make_decode_paged(truncate: bool, bucket: int):
            def decode_chunk(params, pool, tables, last_tok, index,
                             temperature, top_k, top_p, key, aid=None):
                """Flat `decode_chunk` over a gathered block view:
                tables [B, bucket // bs] (pad entries 0 = NULL block)."""
                view, toks, lps, counts = decode_scan(
                    truncate, bucket, params, view_of(pool, tables),
                    last_tok, index, temperature, top_k, top_p, key, aid)
                return write_back(pool, view, tables), toks.T, lps.T, counts
            return decode_chunk

        def insert_paged(pool, frag, table):
            """Scatter an admission fragment's first max_len rows into
            the request's blocks. `table` [mb] is the SCATTER table:
            zero-copy shared prefix blocks are masked to the NULL block
            (they already hold these exact rows and must stay untouched
            by construction, not by luck), a freshly forked tail block
            receives its committed rows from the fragment — that write
            IS the copy-on-write copy — and entries past the allocation
            pad to NULL."""
            def leaf(p, f):
                rows = jax.lax.slice_in_dim(
                    f, 0, mb * bs, axis=2).astype(p.dtype)
                rows = rows.reshape(rows.shape[0], mb, bs,
                                    *rows.shape[3:])
                return p.at[:, table].set(rows)
            return jax.tree.map(leaf, pool, frag)

        def frag_from_pool(pool, table):
            """Rebuild a fragment cache [L, 1, frag_len, ...] from a
            block table — the admission-side gather that lets chunked
            prefill RESUME after a prefix-cache hit without the flat
            engine's stored full-length fragment copy. Rows past the
            stored prefix come back as garbage; safe for the same reason
            stale fragment rows always were (each is overwritten before
            any query position can attend it)."""
            empty = state.fragment(frag_len)

            def leaf(f, p):
                g = jnp.take(p, table, axis=1)  # [L, mb, bs, ...]
                g = g.reshape(g.shape[0], 1, mb * bs, *g.shape[3:])
                return jax.lax.dynamic_update_slice(
                    f, g.astype(f.dtype), (0,) * f.ndim)
            return jax.tree.map(leaf, empty, pool)

        def export_blocks(pool, table):
            """Gather `mb` whole blocks off the pool ([mb] table, NULL
            pads) — the device half of the KV wire format (serve/
            kv_transfer.py). Pad gathers return NULL-block garbage the
            host side slices away; committed rows come back exactly as
            the pool holds them, so pool → wire → pool round-trips
            byte-identically (test-pinned)."""
            return jax.tree.map(lambda p: jnp.take(p, table, axis=1),
                                pool)

        def import_blocks(pool, blocks, table):
            """Scatter shipped host blocks into the pool at `table` —
            the H2D half. Table entries masked to NULL absorb the
            shipment's pad blocks in the reserved garbage block; real
            entries land a remote prefill's committed rows without a
            single local prefill chunk."""
            return jax.tree.map(
                lambda p, b: p.at[:, table].set(b.astype(p.dtype)),
                pool, blocks)

        def insert_paged_quant(pool, frag, table):
            """`insert_paged` for the quantized pool: the fragment
            arrives at FULL precision (admission computes exact rows),
            and the scatter quantizes them with `kv_quantize_rows`, the
            encode the decode scan's per-row writes call
            (models/llama.py), so a row reaches the same bytes whether
            it was admitted or decoded. Shared prefix blocks are masked
            to NULL exactly as in the plain path — their committed
            bytes never change."""
            rows_k = jax.lax.slice_in_dim(frag["k"], 0, mb * bs, axis=2)
            rows_v = jax.lax.slice_in_dim(frag["v"], 0, mb * bs, axis=2)
            kq, ks = kv_quantize_rows(rows_k, kv_quant)
            vq, vs = kv_quantize_rows(rows_v, kv_quant)

            def blocked(r):
                return r.reshape(r.shape[0], mb, bs, *r.shape[3:])

            out = dict(pool)
            for name, arr in (("k", kq), ("v", vq), ("ks", ks),
                              ("vs", vs)):
                out[name] = out[name].at[:, table].set(blocked(arr))
            return out

        def frag_from_pool_quant(pool, table):
            """`frag_from_pool` for the quantized pool: gather blocks +
            scale blocks, dequantize into the full-precision fragment.
            This is the ONE place full-width dequantized rows may
            materialize — admission-side reconstruction for a prefix hit
            or continuation, outside any scan (each call is a dequant
            fallback; the engine counts them)."""
            empty = state.fragment(frag_len)

            def rowed(g):
                return g.reshape(g.shape[0], 1, mb * bs, *g.shape[3:])

            out = {}
            for name, sname in (("k", "ks"), ("v", "vs")):
                vals = rowed(jnp.take(pool[name], table, axis=1))
                scales = rowed(jnp.take(pool[sname], table, axis=1))
                rows = kv_dequantize_rows(vals, scales,
                                          empty[name].dtype)
                out[name] = jax.lax.dynamic_update_slice(
                    empty[name], rows, (0,) * empty[name].ndim)
            return out

        fns.update(make_decode_paged=make_decode_paged,
                   insert_paged=insert_paged,
                   frag_from_pool=frag_from_pool,
                   export_blocks=export_blocks,
                   import_blocks=import_blocks)
        if kv_quant != "none":
            # Quantized pools differ at the admission boundary only.
            fns.update(insert_paged=insert_paged_quant,
                       frag_from_pool=frag_from_pool_quant)
        if state.grows:
            # The model's own fragment goes into its own kinds of block.
            fns.update(insert_paged=state.insert)
    return fns


def spec_acceptance(drafts, dlogits, tlogits, temperature, key):
    """Per-row speculative acceptance — greedy rows exact-match, sampled
    rows the standard rejection scheme (Leviathan/Chen): the draft
    proposed d_j ~ p_d (its temperature-scaled softmax), the target
    accepts with prob min(1, p_t(d_j)/p_d(d_j)) and on first rejection
    emits a sample from the residual normalize(max(p_t - p_d, 0)); full
    acceptance emits a bonus sample from p_t[gamma]. The emitted marginal
    at every position is EXACTLY the target's tempered distribution — a
    weak draft costs acceptance rate, never the sampling law.

    drafts [B, gamma] (greedy rows: argmax proposals; sampled rows: draws
    from p_d), dlogits [B, gamma, V] draft logits per proposal position,
    tlogits [B, gamma+1, V] target logits, temperature [B] (<=0 greedy).
    Returns (out [B, gamma+1] emitted tokens incl. correction/bonus,
    k [B] accepted counts, next_tok [B])."""
    b, gamma = drafts.shape
    sampled = temperature > 0
    safe_t = jnp.maximum(temperature, 1e-4)[:, None, None]
    tprobs = jax.nn.softmax(tlogits.astype(jnp.float32) / safe_t, axis=-1)
    dprobs = jax.nn.softmax(dlogits.astype(jnp.float32) / safe_t, axis=-1)
    tgreedy = jnp.argmax(tlogits, -1).astype(jnp.int32)  # [B, gamma+1]

    pt_d = jnp.take_along_axis(tprobs[:, :gamma], drafts[..., None],
                               axis=-1)[..., 0]          # [B, gamma]
    pd_d = jnp.take_along_axis(dprobs, drafts[..., None],
                               axis=-1)[..., 0]
    ukey, rkey = jax.random.split(key)
    u = jax.random.uniform(ukey, (b, gamma))
    accept_sampled = u < pt_d / jnp.maximum(pd_d, 1e-30)
    accept_greedy = drafts == tgreedy[:, :gamma]
    accept = jnp.where(sampled[:, None], accept_sampled, accept_greedy)
    k = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

    # Correction at the rejection position (sampled rows): residual
    # distribution max(p_t - p_d, 0) renormalized; on full acceptance the
    # "residual" at position gamma is p_t itself (p_d defined 0 there).
    dprobs_pad = jnp.concatenate(
        [dprobs, jnp.zeros_like(dprobs[:, :1])], axis=1)  # [B, gamma+1, V]
    pt_k = jnp.take_along_axis(tprobs, k[:, None, None], axis=1)[:, 0]
    pd_k = jnp.take_along_axis(dprobs_pad, k[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(pt_k - pd_k, 0.0)
    resid_mass = jnp.sum(resid, axis=-1, keepdims=True)
    # Degenerate residual (identical distributions): fall back to p_t.
    resid = jnp.where(resid_mass > 1e-30, resid, pt_k)
    corr_sampled = jax.random.categorical(
        rkey, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1).astype(jnp.int32)
    corr_greedy = jnp.take_along_axis(tgreedy, k[:, None], axis=1)[:, 0]
    corr = jnp.where(sampled, corr_sampled, corr_greedy)

    j = jnp.arange(gamma + 1)[None]
    padded = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    out = jnp.where(j < k[:, None], padded,
                    jnp.where(j == k[:, None], corr[:, None], 0))
    return out, k, corr


def build_spec_decode(model, draft_model, *, gamma: int, n_spec: int,
                      max_len: int, rolling_window: int = 0,
                      adapters=None, kv_block_size: int = 0):
    """Speculative decoding step functions (vLLM's draft-model speedup,
    XLA-shaped): per spec step the DRAFT autoregressively proposes `gamma`
    tokens (gamma cheap forwards inside the scan), then the TARGET scores
    all gamma+1 positions in ONE forward — the chunked-prefill path
    (explicit positions + attend_full_cache), which writes the candidate
    K/V rows before attending, so rejected rows are simply overwritten by
    the next step's write at the rewound index. Acceptance per row
    (spec_acceptance): greedy rows exact-match against the target argmax
    (emitted stream TOKEN-IDENTICAL to vanilla greedy); tempered rows the
    rejection scheme (draft samples from p_d, accept w.p. min(1,
    p_t/p_d), residual sample on rejection) whose emitted marginal is
    exactly the tempered target distribution — per step, k accepted + 1
    correction/bonus, k in [0, gamma].

    `n_spec` steps ride one dispatch (the host-sync amortization that
    motivates chunked decode; worst case n_spec*(gamma+1) tokens, the
    caller sizes the cache bucket for it). Returns
    make(bucket) -> spec_chunk(params, dparams, cache, dcache, last_tok,
    index, temperature, key) -> (cache, dcache,
    tokens [B, n_spec, gamma+1], logprobs [B, n_spec, gamma+1],
    accepted [B, n_spec]).

    `rolling_window` > 0: the TARGET runs a rolling sliding-window cache
    (window rows, modular writes). The verify forward writes all gamma+1
    candidate rows, but a rejection rewinds — and in a rolling cache
    those rejected writes have EVICTED live in-window rows (in a causal
    cache they merely occupy not-yet-committed rows ahead of the index).
    After acceptance the step reverts rows past the accepted count to
    their pre-verify contents, so the cache always holds exactly the
    committed stream.

    `adapters` (multi-LoRA x spec-decode): the TARGET verifies under each
    row's adapter while the draft proposes from its own base weights — a
    base-model draft can only cost acceptance rate, never correctness,
    because every emitted token comes from the target's (adapted) logits
    via exact-match/rejection acceptance.

    `kv_block_size` > 0 (spec x paged, ISSUE 18): make(bucket) returns
    the PAGED signature instead — spec_chunk(params, dparams, pool,
    dpool, tables, dtables, last_tok, index, temperature, key) — which
    gathers per-row block views of the target AND draft pools (tables /
    dtables [B, bucket//bs], pad entries 0 = NULL block; `gather_view`),
    calls the flat `spec_chunk` on the views and scatters both back.
    The draft pool shares the target's block-id space but its tables
    are per-slot and never prefix-shared (a draft cache is private
    working state)."""
    rolling = int(rolling_window) > 0
    bs = int(kv_block_size)
    if bs and rolling:
        raise ValueError(
            "paged spec decode does not compose with the rolling cache")

    def make(bucket: int):
        def spec_chunk(params, dparams, cache, dcache, last_tok, index,
                       temperature, key, aid=None):
            t_kw = ({} if aid is None or adapters is None
                    else {"adapter": adapters, "adapter_ids": aid})
            def sl(c):
                # Rolling target (window rows) and its causal draft
                # (max_len rows) are never sliced — the window already
                # bounds the target's attention cost, and bucket is
                # sized for the causal layout only.
                if rolling or bucket == max_len:
                    return c
                return jax.tree.map(
                    lambda x: jax.lax.slice_in_dim(x, 0, bucket, axis=2), c)

            sliced, dsliced = sl(cache), sl(dcache)
            dcap = max_len if rolling else bucket

            def revert_rejected(cw, c0, idx, k):
                """Restore rolling-cache rows the rejected candidates
                clobbered: row (idx+j) % window keeps the verify's write
                for j <= k (committed tokens) and returns to its
                pre-verify contents otherwise."""
                j = jnp.arange(gamma + 1)
                rows = (idx[:, None] + j[None]) % rolling_window
                keep = j[None] <= k[:, None]

                def leaf(cw, c0):
                    def per_batch(cwb, c0b, r, kp):
                        old = jnp.take(c0b, r, axis=1)  # [L, gamma+1, ...]
                        new = jnp.take(cwb, r, axis=1)
                        sel = kp.reshape((1, -1) + (1,) * (cwb.ndim - 2))
                        vals = jnp.where(sel, new, old)
                        return jax.vmap(
                            lambda cl, vl: cl.at[r].set(vl))(cwb, vals)
                    return jax.vmap(per_batch, in_axes=(1, 1, 0, 0),
                                    out_axes=1)(cw, c0, rows, keep)

                return jax.tree.map(leaf, cw, c0)

            def spec_step(carry, _):
                c, dc, tok, idx, key = carry
                key, dkey, akey = jax.random.split(key, 3)

                def dstep(dcarry, skey):
                    dc, t, i = dcarry
                    dlogits, dc = draft_model.apply(
                        {"params": dparams}, t[:, None], cache=dc,
                        cache_index=jnp.minimum(i, dcap - 1))
                    row = dlogits[:, 0]
                    # Sampled rows draw from the draft's tempered softmax
                    # (the rejection scheme needs d ~ p_d); greedy rows
                    # take argmax — exactly sample_tokens' untruncated
                    # path, reused so proposal sampling can never drift
                    # from the engine's sampling semantics.
                    nxt = sample_tokens(row, temperature, skey)
                    return (dc, nxt, i + 1), (nxt, row)

                # gamma+1 iterations, gamma proposals: the extra step
                # writes the LAST proposal's K/V into the draft cache
                # (each iteration caches its INPUT, so d_{gamma-1} —
                # output-only in a gamma-length scan — would otherwise
                # leave a stale row after a fully-accepted step, and
                # every later draft forward would attend garbage there,
                # collapsing the acceptance rate).
                (dc, _, _), (drafts, dlogits) = jax.lax.scan(
                    dstep, (dc, tok, idx),
                    jax.random.split(dkey, gamma + 1))
                drafts = drafts.T[:, :gamma]           # [B, gamma]
                dlogits = dlogits.transpose(1, 0, 2)[:, :gamma]

                tokens_in = jnp.concatenate([tok[:, None], drafts], axis=1)
                positions = idx[:, None] + jnp.arange(gamma + 1)[None]
                c0 = c
                tlogits, c = model.apply(
                    {"params": params}, tokens_in, cache=c,
                    cache_index=(idx if rolling
                                 else jnp.minimum(idx, bucket - 1)),
                    positions=positions, attend_full_cache=True, **t_kw)
                out, k, nxt = spec_acceptance(
                    drafts, dlogits, tlogits, temperature, akey)
                if rolling:
                    c = revert_rejected(c, c0, idx, k)
                lps = _chosen_logprob(tlogits, out)
                return (c, dc, nxt, idx + k + 1, key), (out, lps, k)

            (sliced, dsliced, _, _, _), (outs, lps, ks) = jax.lax.scan(
                spec_step, (sliced, dsliced, last_tok, index, key), None,
                length=n_spec)

            def wb(full, s):
                if rolling or bucket == max_len:
                    return s
                return jax.tree.map(
                    lambda c, x: jax.lax.dynamic_update_slice(
                        c, x, (0,) * c.ndim), full, s)

            return (wb(cache, sliced), wb(dcache, dsliced),
                    outs.transpose(1, 0, 2), lps.transpose(1, 0, 2), ks.T)
        if not bs:
            return spec_chunk

        def spec_chunk_paged(params, dparams, pool, dpool, tables,
                             dtables, last_tok, index, temperature, key,
                             aid=None):
            cache, dcache, toks, lps, ks = spec_chunk(
                params, dparams, gather_view(pool, tables),
                gather_view(dpool, dtables), last_tok, index,
                temperature, key, aid)
            return (scatter_view(pool, cache, tables),
                    scatter_view(dpool, dcache, dtables), toks, lps, ks)
        return spec_chunk_paged
    return make


class GenerationEngine:
    """Slot-based continuous-batching decode loop over one global cache.

    `submit()` is thread-safe and blocks until the request completes; the
    worker thread multiplexes all in-flight requests onto the slot batch.

    **Overlapped scheduling** (`pipeline_depth`, default 2): the run loop
    keeps up to `pipeline_depth` decode chunks in flight — chunk k+1 is
    dispatched *chained through the on-device cache and last-token carry*
    before chunk k's tokens are fetched, so the device never idles a
    host round trip between chunks. Admission (prefill/extend/insert) is likewise dispatched
    *between* in-flight chunks without a host sync — the newly admitted
    request's first sampled token stays on device as the decode carry and
    its host value is collected lazily — so admitting request B no longer
    stalls every active slot for a whole prefill round-trip. When a
    fetched chunk reveals EOS/budget/deadline for a slot, the chunks
    already speculatively dispatched contain dead rows for it; the fetch
    path reconciles by dropping them (`decode_wasted_tokens` /
    `decode_dead_slot_chunks` account the waste, bounded by
    `pipeline_depth - 1` chunks per retirement) and the slot is freed at
    that boundary. `pipeline_depth=1` is the escape hatch: it reproduces
    the fully synchronous dispatch→fetch loop bit-for-bit (same RNG
    stream, same host-sync points). Engines with a speculative `draft`
    always run depth 1 — the spec path's advance is data-dependent
    (accepted counts), so its carry cannot be chained on device; the spec
    chunk already amortizes the RTT by n_spec·(gamma+1) tokens.

    **Tensor parallelism** (SURVEY.md §2.2 "tensor-parallel serving"):
    pass `mesh` (a jax.sharding.Mesh with a `tensor` axis) and the engine
    shards weights and KV caches over it — KV heads over `tensor` (each
    device holds its head group), mlp/vocab per the logical rules — and
    every prefill/decode dispatch runs SPMD with XLA-inserted collectives.
    An 8B bf16 model does not fit one chip; TP-8 is how the flagship
    serves. The public API is unchanged: submit() still takes one request.

    **The weights** are held the one way serve/weights.py says: a leaf
    the model's forward only rounds to `cfg.dtype` is rounded once, here,
    the rest stay as they came; `eng._params` keeps the tree's structure.
    `donate_params=True` is a caller's word that it gives its tree (and a
    draft's) up, as `jax.jit`'s donation is: each device leaf is deleted
    as its rounded twin exists, so loading never holds a tree twice. The
    caller's tree is dead afterwards, whether or not construction ended.
    """

    def __init__(self, model, params, cfg, *, slots: int = 4,
                 max_len: int = 256, chunk: int = 16,
                 prefill_buckets: Sequence[int] = (32, 128),
                 decode_buckets: Sequence[int] | None = None,
                 prefix_cache: int = 0, seed: int = 0,
                 mesh=None, rules=None, draft: dict | None = None,
                 adapters: dict | None = None, pipeline_depth: int = 2,
                 kv_block_size: int = 0, kv_blocks: int = 0,
                 role: str = "unified", kv_host_tier_blocks: int = 0,
                 kv_quant: str = "none", donate_params: bool = False):
        self.model, self.cfg = model, cfg
        self.max_len, self.chunk, self.n_slots = int(max_len), int(chunk), int(slots)
        msl = int(getattr(cfg, "max_seq_len", 0) or 0)
        if msl and self.max_len > msl:
            # Past the model's position range the wpe/RoPE-table gather
            # CLAMPS under jit — every later token reuses the last
            # position, silently diverging from the source model.
            raise ValueError(
                f"max_len {self.max_len} exceeds the model's position "
                f"range (max_seq_len={msl}); positions would silently "
                "clamp")
        mask_kind = getattr(cfg, "mask_kind", "causal")
        self._rolling = 0
        if mask_kind == "sliding_window":
            window = int(getattr(cfg, "mask_window", 0))
            if window < 1 and self.max_len > window:
                raise ValueError(
                    "sliding-window checkpoint with window=0 cannot be "
                    "served")
            if (self.max_len > window
                    and getattr(cfg, "sliding_pattern", "all") != "all"):
                # Alternating sliding/full layers (Gemma-2/3) past the
                # window: the full-attention layers need ALL history, so
                # nothing rolls — the cache stays full-length and the
                # sliding layers band their reads per the traced
                # per-layer flag (models/llama.py decode branch). The
                # config keeps its mask; decode runs the einsum path.
                pass
            elif self.max_len > window:
                # Serving PAST the window: rolling-buffer KV cache
                # (models/llama.py init_cache grows a "pos" plane; rows =
                # window, modular writes, position-masked reads) — the
                # vLLM/huggingfaceserver capability of serving
                # Mistral-class models at full context, exactly. (The
                # window >= 1 guard above already rejected degenerate
                # configs.)
                self._rolling = window
            else:
                # Within the window the band never clips, so causal decode
                # is exact — rebuild the module causal (params are
                # identical; the mask kind is config-only) to use the
                # faster causal KV-cache paths (bucketed decode, flash
                # prefill) instead of the rolling read.
                import dataclasses

                from kubeflow_tpu.serve.quant import QuantizedModule

                cfg = dataclasses.replace(cfg, mask_kind="causal",
                                          mask_window=0,
                                          attention_impl="auto")
                if isinstance(model, QuantizedModule):
                    # Rebuild the INNER module by replacing its cfg
                    # field (flax modules are dataclasses) — a
                    # type(module)(cfg) reconstruction would drop every
                    # other field, e.g. an MoE trunk's mlp_cls.
                    model = QuantizedModule(
                        dataclasses.replace(model.module, cfg=cfg),
                        model.dtype,
                        legacy_dequant=model.legacy_dequant)
                else:
                    model = dataclasses.replace(model, cfg=cfg)
                self.model, self.cfg = model, cfg
        elif mask_kind != "causal":
            raise ValueError(
                f"generative serving needs a causal-class model; got "
                f"mask_kind={mask_kind!r}")
        # Rolling mode clamps prompt buckets to the window: a chunk wider
        # than the window would wrap onto itself (duplicate modular write
        # rows — undefined scatter order).
        bucket_cap = min(self.max_len, self._rolling or self.max_len)
        self.prefill_buckets = sorted(
            {min(int(b), bucket_cap) for b in prefill_buckets})
        # Length-aware decode: decode compiles once PER
        # CACHE-LENGTH BUCKET over a time-sliced cache, so attention cost
        # tracks the longest ACTIVE sequence, not max_len. Default buckets:
        # powers of two from max(64, 2·chunk) up to max_len.
        # Rolling mode has ONE bucket — the window itself already bounds
        # attention cost, and rolling rows aren't prefix-ordered, so
        # time-slicing the cache would drop live in-window rows.
        if self._rolling:
            self.decode_buckets = [self._rolling]
        else:
            if decode_buckets is None:
                b, decode_buckets = max(64, 2 * self.chunk), []
                while b < self.max_len:
                    decode_buckets.append(b)
                    b *= 2
            self.decode_buckets = sorted(
                {int(b) for b in decode_buckets
                 if self.chunk < int(b) < self.max_len} | {self.max_len})
        # Paged KV cache (ROADMAP item 1, the vLLM PagedAttention design
        # TPU-shaped — serve/paging.py): `kv_block_size` > 0 swaps the
        # slot-contiguous cache [L, slots, max_len, ...] for a pool of
        # `kv_blocks` fixed-size blocks (+ the reserved NULL block).
        # `slots` becomes pure CONCURRENCY (the compiled decode width);
        # memory is the pool, so many short requests coexist where flat
        # mode would hold `slots` worst-case rows. kv_blocks=0 sizes the
        # pool to flat parity (slots*max_len tokens) — raise slots and
        # shrink kv_blocks to trade worst-case headroom for concurrency.
        # kv_block_size=0 (default) is the escape hatch: the flat engine,
        # bit-for-bit (same RNG splits, same sync points).
        self._paged = int(kv_block_size) > 0
        self._kv_bs = int(kv_block_size)
        self._kv_stash: deque = deque()  # admissions waiting for blocks
        # What a request holds between steps is the model's to say
        # (serve/paging.py `serving_state`): rows of K and V, one kind of
        # block, or a state of several kinds whose blocks come and go
        # while the request decodes (`grows`). One mechanism below; what
        # the engine cannot yet do with the latter it refuses here.
        # Three parts of the engine are written against rows of per-head
        # K and V (models/llama.py `RowState`) and say so themselves.
        if (kv_quant or "none") != "none":
            require_kv_planes(self._state)
        if role != "unified" or int(kv_host_tier_blocks) > 0:
            require_row_blocks(self._state)
        if draft is not None:
            require_rows(
                self._state,
                "a draft model: a rejected proposal rewinds rows of K and "
                "V, and the draft's cache is made of them")
        if self._state.grows:
            # (A row state never grows, so this refuses exactly the states
            # whose tables cannot map a stored prefix.)
            if int(prefix_cache) > 0:
                require_rows(
                    self._state,
                    "prefix_cache > 0: a stored prefix shares blocks by "
                    "reference, and no table here maps a prefix of such "
                    "blocks")
            self._state.check(self.prefill_buckets)
            # A row reads a bounded state, whatever its length: one
            # decode shape.
            self.decode_buckets = [self.max_len]
        # Disaggregated prefill/decode (ISSUE 13): KV blocks are the
        # wire format, so both split roles and the host-RAM spill tier
        # require the paged pool. role="unified" with no tier is the
        # escape hatch — bit-for-bit today's engine (same RNG splits,
        # same sync points, no extra compiles).
        if role not in ENGINE_ROLES:
            raise ValueError(
                f"role {role!r}: must be one of {ENGINE_ROLES}")
        if role != "unified" and not self._paged:
            raise ValueError(
                f"role={role!r} needs the paged KV cache (KV blocks are "
                "the prefill→decode wire unit); set kv_block_size > 0")
        if int(kv_host_tier_blocks) and not self._paged:
            raise ValueError(
                "kv_host_tier_blocks > 0 needs the paged KV cache (the "
                "host tier spills whole blocks); set kv_block_size > 0")
        self.role = role
        self._host_tier = (HostKVTier(int(kv_host_tier_blocks))
                           if self._paged and int(kv_host_tier_blocks)
                           else None)
        if self._paged:
            if self._rolling:
                raise ValueError(
                    "kv_block_size > 0 does not compose with rolling "
                    "sliding-window serving (rolling rows are not "
                    "prefix-ordered, so block tables cannot address "
                    "them); set kv_block_size=0")
            if self.max_len % self._kv_bs:
                raise ValueError(
                    f"kv_block_size {self._kv_bs} must divide max_len "
                    f"{self.max_len} (block tables address whole blocks)")
            bad = [b for b in self.decode_buckets if b % self._kv_bs]
            if bad:
                raise ValueError(
                    f"kv_block_size {self._kv_bs} must divide every "
                    f"decode bucket; offending: {bad} (pass explicit "
                    "decode_buckets or a power-of-two block size)")
            n_blocks = int(kv_blocks) or -(-self.n_slots * self.max_len
                                           // self._kv_bs)
            self._kv_alloc = BlockAllocator(n_blocks, self._kv_bs)
        # Quantized KV blocks (ISSUE 19): the pool stores int8/fp8
        # payloads + per-row f32 scale planes addressed by the same
        # block ids, so ≈2× kv_blocks fit the same HBM, host-tier
        # spills charge about half the block units, and TPKV1 fmt-3
        # ships quantized bytes. "none" (default) is the bit-exact
        # escape hatch: the unquantized pool.
        self.kv_quant = str(kv_quant or "none")
        if self.kv_quant not in KV_QUANT_MODES:
            raise ValueError(
                f"kv_quant {self.kv_quant!r}: must be one of "
                f"{KV_QUANT_MODES}")
        if self.kv_quant != "none":
            if not self._paged:
                raise ValueError(
                    "kv_quant requires the paged KV cache (quantization "
                    "is a property of pool blocks); set kv_block_size "
                    "> 0")
            if draft is not None:
                # Measured decision (bench.py quant A/B, PROFILE.md
                # §17): draft-assisted acceptance degrades measurably
                # when the verify forward reads a quantized cache, and
                # a spec rewind would re-quantize rows that were NOT
                # newly written (breaking the immutable-committed-rows
                # discipline CoW and shipments rely on). Refused loudly
                # — cpp/admission.h enforces the same cross-field rule
                # at submit time.
                raise ValueError(
                    "kv_quant does not compose with speculative "
                    "decoding (draft): a rejection rewind would "
                    "re-quantize committed rows; drop the draft or "
                    "set kv_quant='none'")
        # Prefix cache: LRU of prompt-chunk-boundary KV fragments keyed by
        # the exact token prefix; admission resumes chunked prefill after
        # the longest hit instead of recomputing it (the vLLM prefix-reuse
        # capability, at bucket granularity). Capacity in fragments —
        # OPT-IN (0 = off): each fragment is a full-length KV copy, so
        # the cache charges real HBM; enable it for shared-system-prompt
        # workloads where the recompute saving pays for the residency.
        self._prefix_cap = int(prefix_cache)
        # LRU keyed by (aid, prefix_len, hash(token_tuple)); each value is
        # (token_tuple, fragment) — the tuple verifies the hash, and the
        # (aid -> {len: count}) side index lets lookup probe by length
        # instead of scanning every entry (see _prefix_lookup).
        self._prefix_lru: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._prefix_lens: dict[int, dict[int, int]] = {}
        # Speculative decoding (vLLM draft-model speedup): draft =
        # {"model", "params", "cfg", "gamma"?} — greedy requests decode
        # speculatively (token-identical to vanilla greedy) and
        # plain-temperature requests via rejection sampling (exact
        # tempered-target marginal); top-k/top-p requests fall back to
        # the plain chunked decode.
        self._spec = None
        if draft is not None:
            dcfg = draft["cfg"]
            # Same windowed-checkpoint treatment the target gets above: a
            # Mistral-family draft is exact within its window (rebuild
            # causal), past it refuse with an actionable message instead
            # of crashing in the jit trace.
            dmask = getattr(dcfg, "mask_kind", "causal")
            if dmask == "sliding_window":
                dwindow = int(getattr(dcfg, "mask_window", 0))
                if self.max_len > dwindow:
                    raise ValueError(
                        f"sliding-window draft (window={dwindow}): serving "
                        f"max_len={self.max_len} exceeds the window; set "
                        "max_len <= window or use a causal draft")
                import dataclasses

                from kubeflow_tpu.serve.quant import QuantizedModule

                dcfg = dataclasses.replace(dcfg, mask_kind="causal",
                                           mask_window=0,
                                           attention_impl="auto")
                dmodel = draft["model"]
                if isinstance(dmodel, QuantizedModule):
                    # Replace the INNER module's cfg field (see the
                    # target rebuild above — reconstruction drops
                    # non-cfg module fields).
                    dmodel = QuantizedModule(
                        dataclasses.replace(dmodel.module, cfg=dcfg),
                        dmodel.dtype,
                        legacy_dequant=dmodel.legacy_dequant)
                else:
                    dmodel = dataclasses.replace(dmodel, cfg=dcfg)
                draft = dict(draft, cfg=dcfg, model=dmodel)
            elif dmask != "causal":
                raise ValueError(
                    f"speculative decoding needs a causal-class draft; "
                    f"got mask_kind={dmask!r}")
            if getattr(dcfg, "vocab_size", None) != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {getattr(dcfg, 'vocab_size', None)} != "
                    f"target vocab {cfg.vocab_size} — speculative "
                    "acceptance compares token ids, so the vocabularies "
                    "must be identical")
            dmsl = int(getattr(dcfg, "max_seq_len", 0) or 0)
            if dmsl and self.max_len > dmsl:
                raise ValueError(
                    f"max_len {self.max_len} exceeds the draft model's "
                    f"position range (max_seq_len={dmsl})")
            gamma = int(draft.get("gamma", 4))
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            if self._rolling and gamma + 1 > self._rolling:
                raise ValueError(
                    f"gamma={gamma} writes {gamma + 1} candidate rows per "
                    f"spec step, more than the rolling window "
                    f"({self._rolling}) holds")
            self._spec = {
                "model": draft["model"], "cfg": dcfg, "gamma": gamma,
                # Spec steps per dispatch: match the vanilla chunk's
                # best-case token budget so the host-sync amortization
                # carries over.
                "n_spec": max(1, self.chunk // (gamma + 1)),
            }
            # Device placement happens after mesh setup below — under TP
            # the draft shards over the same mesh as the target.
            self._dparams_src = draft["params"]
        # Multi-LoRA serving (serve/multilora.py): {name: PEFT adapter
        # dir} — all adapters stacked on device, selected per request by
        # index inside the compiled program.
        self._ml_stacks = None
        self._ml_ids: dict[str, int] = {}
        if adapters:
            from kubeflow_tpu.serve.multilora import build_adapter_stacks

            self._ml_stacks, self._ml_ids = build_adapter_stacks(
                dict(adapters), self.cfg)
            if mesh is None:
                self._ml_stacks = jax.device_put(self._ml_stacks)
            else:
                # multi-LoRA x TP: adapter stacks REPLICATE over the mesh
                # (rank-r factors are tiny next to the base weights); the
                # per-row delta lands on sharded activations and XLA
                # slices it at the logical constraint right after. Spec
                # compose note: the draft proposes from the BASE model —
                # acceptance may drop for heavily-adapted targets, but
                # emitted tokens always come from the target's (adapted)
                # logits, so the sampling law is untouched.
                from jax.sharding import NamedSharding, PartitionSpec
                self._ml_stacks = jax.device_put(
                    self._ml_stacks, NamedSharding(mesh, PartitionSpec()))
            self._ml_names = {i: n for n, i in self._ml_ids.items()}
        self._mesh = mesh
        if rules is None:
            from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
            rules = DEFAULT_RULES
        self._rules = tuple(rules)
        self._cache_sharding = None
        self._dcache_sharding = None
        # The engine's own tree (serve/weights.py): what the forward only
        # rounds to cfg.dtype is rounded once, here, and every executable
        # reads the stored leaf in place.
        self._params, self._cache_sharding = self._hold(
            params, self.model, self.cfg, self._state, donate_params)
        if self._spec is not None:
            # Spec-decode x TP: the draft shards over the SAME mesh by
            # the same logical rules (its KV heads must divide tensor
            # like the target's) — one SPMD program runs draft proposals
            # and target verify together.
            self._dstate = serving_state(self._spec["cfg"], self._kv_bs,
                                         self.max_len)
            self._dparams, self._dcache_sharding = self._hold(
                self._dparams_src, self._spec["model"], self._spec["cfg"],
                self._dstate, donate_params, role="draft")
            del self._dparams_src
        if int(pipeline_depth) < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        # Spec x pipelining (ISSUE 18 move 3): the spec chunk's advance is
        # data-dependent (accepted counts pick the next index), so depth>1
        # chains spec chunk k+1 on the WORST-CASE carry — the last bonus
        # token under full acceptance. Any rejection dooms the chained
        # in-flight chunks; the fetch reconciles them exactly like
        # speculatively-dead chunks (bounded waste: depth-1 chunks per
        # rejection event). pipeline_depth bounds each sub-batch chain
        # (spec and vanilla pipeline independently since move 2).
        self.pipeline_depth = int(pipeline_depth)
        #: Live in-flight dispatch count (worker-thread writes, metrics
        #: reads — a plain int store, GIL-atomic). 0 when idle/drained;
        #: a pipeline that silently re-serializes never reads above 1.
        self.inflight_depth = 0
        self._busy_mark: float | None = None
        self._key = jax.random.key(seed)
        self._queue: queue.Queue = queue.Queue()
        self._wake = threading.Event()
        self._stop = False
        # Worker-thread writes race metrics/metadata readers; the lock
        # makes snapshots tear-free AND keeps `dict(stats)` safe against
        # the first adapter-request key insertion (an unlocked dict copy
        # concurrent with a key insert can raise RuntimeError).
        self._stats_lock = threading.Lock()
        # guarded-by: _stats_lock
        self.stats = {"requests": 0, "prompt_tokens": 0, "decode_tokens": 0,
                      "decode_seconds": 0.0, "decode_dispatches": 0,
                      "prefix_hits": 0, "prefix_hit_tokens": 0,
                      "prefix_misses": 0, "prefix_stores": 0,
                      "host_stall_seconds": 0.0,
                      "decode_fetch_blocking": 0,
                      "decode_fetch_overlapped": 0,
                      "admit_overlap": 0, "decode_dead_slot_chunks": 0,
                      "decode_wasted_tokens": 0,
                      "spec_dispatches": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_demotions": 0,
                      "spec_readmissions": 0, "spec_stale_rides": 0,
                      "kv_cow_copies": 0, "prefix_zero_copy_hits": 0,
                      # Disaggregation + host tier (ISSUE 13):
                      # prefill_chunks counts prefill/extend dispatches
                      # (a decode-role engine must pin it at 0 —
                      # DISAGGBENCH mechanism assertion), shipped/
                      # received count wire blocks, spilled/restored
                      # the host-tier traffic.
                      "prefill_chunks": 0, "remote_admits": 0,
                      "kv_blocks_shipped": 0, "kv_blocks_received": 0,
                      "kv_spilled_blocks": 0, "kv_restored_blocks": 0,
                      # Quantized KV (ISSUE 19): admission-side
                      # full-width dequant events (prefix-hit fragment
                      # reconstruction / fmt-1 import) and shipped wire
                      # bytes (fmt-3 pays about half fmt-1's).
                      "kv_dequant_fallbacks": 0, "kv_shipment_bytes": 0,
                      # Where a request's first token went (ISSUE 26),
                      # each summed where the event happens:
                      # queue_wait_seconds over `admitted` is submit →
                      # slot (the serve.batch_gather interval),
                      # ttft_seconds over `first_tokens` submit → first
                      # token on the stream; decode_context_tokens sums
                      # each dispatch's rows' context lengths (the K/V a
                      # decode step has to read).
                      "queue_wait_seconds": 0.0, "admitted": 0,
                      "ttft_seconds": 0.0, "first_tokens": 0,
                      "decode_context_tokens": 0}
        # The state's own counters (what a step reads of each kind, what
        # was given back mid-request): named and computed by the state.
        self.stats.update(dict.fromkeys(self._state.counters, 0))
        # Two gauges, not counters: what the engine holds of weights, and
        # how much of that is still fp32 (serve/weights.py).
        self.stats.update(weights.weight_bytes(
            self._params, self._dparams if self._spec else None))
        # The pass number of the engine loop, on its `engine.*` spans.
        self._round = 0
        devices.compile_clock()  # counting before this engine's compiles
        self._compile()
        with self._scope():
            cache_sh = None
            if self._cache_sharding is not None:
                cache_sh = {"k": self._cache_sharding,
                            "v": self._cache_sharding}
                if self._rolling:
                    # The pos plane [L, B, W] is tiny i32 bookkeeping —
                    # replicate it.
                    from jax.sharding import NamedSharding, PartitionSpec
                    cache_sh["pos"] = NamedSharding(self._mesh,
                                                    PartitionSpec())
            if self._paged:
                if cache_sh is not None and self.kv_quant != "none":
                    # Scale planes [L, NB+1, bs, KH]: KH shards over
                    # `tensor` exactly like the value planes' head axis
                    # (the scale must be co-resident with its rows).
                    from jax.sharding import NamedSharding, PartitionSpec
                    cache_sh["ks"] = cache_sh["vs"] = NamedSharding(
                        self._mesh,
                        PartitionSpec(*self._cache_sharding.spec[:4]))
                # The pool: kv_blocks usable blocks + NULL block 0. Block
                # axis rides the slot axis's (replicated) spec; heads
                # still shard over `tensor` under TP.
                self._cache = jax.jit(
                    lambda: self._state.pool(self._kv_alloc.n_blocks,
                                             kv_quant=self.kv_quant),
                    out_shardings=cache_sh)()
            else:
                self._cache = jax.jit(
                    lambda: self._state.slots(self.n_slots, self.max_len),
                    out_shardings=cache_sh)()
            if self._spec is not None:
                dcache_sh = (None if self._dcache_sharding is None else
                             {"k": self._dcache_sharding,
                              "v": self._dcache_sharding})
                if self._paged:
                    # Paged draft KV (ISSUE 18 move 1): the draft gets
                    # its own pool in the SAME block-id space as the
                    # target's (one allocator governs both), so a slot's
                    # draft blocks are ordinary allocations — per-slot,
                    # never prefix-shared, freed with the slot.
                    self._dcache = jax.jit(
                        lambda: self._dstate.pool(self._kv_alloc.n_blocks),
                        out_shardings=dcache_sh)()
                else:
                    self._dcache = jax.jit(
                        lambda: self._dstate.slots(self.n_slots,
                                                   self.max_len),
                        out_shardings=dcache_sh)()
            self._warmup()
        self._slots = [None] * self.n_slots  # per-slot host state
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="tpk-generate")
        self._thread.start()

    @functools.cached_property
    def _state(self):
        """What the model keeps of a request between steps, as its
        configuration declares it (serve/paging.py `serving_state`). Made
        at first use and not in `__init__`: the host-only tests of the
        reserve (tests/test_paged_kv.py) make an engine object without a
        model, which then holds rows."""
        return serving_state(getattr(self, "cfg", None), self._kv_bs,
                             self.max_len)

    @functools.cached_property
    def _keys(self) -> tuple:
        """Slot-state keys of a paged request's block tables, one a kind
        of its state: `blocks`, then `<kind>_blocks`."""
        return ("blocks",) + tuple(f"{kind}_blocks"
                                   for kind in self._state.kinds[1:])

    def _count(self, counts: dict) -> None:
        """Add what the state counted to the engine's `stats`."""
        with self._stats_lock:
            for name, n in counts.items():
                self.stats[name] += n

    def _hold(self, params, model, cfg, state, donate: bool,
              role: str = "target"):
        """Take a weight tree (the target's or the draft's) as the engine
        keeps it: each leaf the model's forward only rounds to `cfg.dtype`
        stored so (the rule: serve/weights.py), every leaf on the device
        or on its shard of the mesh. Returns (tree, cache_sharding), the
        latter None off a mesh."""
        dtype = getattr(cfg, "dtype", None)
        narrow = weights.stored_narrow(
            model, params, state, dtype, max_len=self.max_len,
            piece=self.prefill_buckets[-1])
        shardings = cache_sharding = None
        if self._mesh is not None:
            import flax.linen as nn

            shardings, cache_sharding = self._shardings(model, cfg, role)
            shardings = jax.tree.leaves(shardings)
            # Callers hand over boxed (fresh init) or plain
            # (orbax-restored) trees; shardings are derived unboxed.
            params = nn.meta.unbox(params)
        return weights.hold(params, narrow, dtype, self._put, shardings,
                            donate=donate), cache_sharding

    # -- tensor parallelism --------------------------------------------------

    def _shardings(self, model, cfg, role: str):
        """How a weight tree lies over the mesh, by the model's logical
        axis annotations (the same rules engine training uses), and the
        matching KV-cache sharding: heads over `tensor`, everything else
        replicated. Each device ends up holding its head group / mlp
        shard; XLA inserts the collectives. Returns (param shardings,
        cache_sharding) — also for the DRAFT model under spec-decode x TP
        (role only flavors the error message)."""
        import flax.linen as nn

        from kubeflow_tpu.parallel.sharding import logical_to_spec
        from jax.sharding import NamedSharding

        mesh = self._mesh
        tp = mesh.shape.get("tensor", 1)
        if cfg.num_kv_heads % tp:
            raise ValueError(
                f"tensor parallelism {tp} must divide the {role} model's "
                f"num_kv_heads {cfg.num_kv_heads} (KV heads shard over "
                f"the tensor axis)")
        with mesh, nn.logical_axis_rules(self._rules):
            abstract = jax.eval_shape(
                lambda r: model.init(
                    r, jnp.zeros((1, 8), jnp.int32))["params"],
                jax.random.key(0))
        specs = nn.get_partition_spec(abstract)
        shardings = nn.logical_to_mesh_sharding(specs, mesh, self._rules)
        # Cache layout [L, B, T, KH, D]: KH rides the `heads` rule.
        cache_sharding = NamedSharding(
            mesh, logical_to_spec(("layers", None, None, "heads", "kv"),
                                  self._rules))
        return shardings, cache_sharding

    def _put(self, leaf, sh):
        """One leaf onto the default device (`sh` None), or onto its
        shard of the mesh."""
        from kubeflow_tpu.serve.quant import Int8Leaf

        if sh is not None and isinstance(leaf, Int8Leaf):
            from jax.sharding import NamedSharding, PartitionSpec

            # int8 x TP: the int8 payload shards exactly like the
            # weight it replaces; the fp32 per-output-channel scales
            # keep the weight's spec on their >1 dims (the size-1
            # contraction dims cannot shard, and the dequantize
            # broadcast needs the scale co-resident with its shard).
            spec = list(sh.spec) + [None] * (leaf.q.ndim - len(sh.spec))
            sspec = [ax if d > 1 else None
                     for ax, d in zip(spec, leaf.scale.shape)]
            return Int8Leaf(
                jax.device_put(leaf.q, sh),
                jax.device_put(
                    leaf.scale,
                    NamedSharding(self._mesh, PartitionSpec(*sspec))))
        return jax.device_put(leaf, sh)

    def _scope(self):
        """Mesh + logical-rules context for tracing/compiling — a no-op
        single-device. Every jit trace happens under this scope so
        in-model `nn.with_logical_constraint`s resolve to mesh axes."""
        import contextlib

        if self._mesh is None:
            return contextlib.nullcontext()
        import flax.linen as nn

        stack = contextlib.ExitStack()
        stack.enter_context(self._mesh)
        stack.enter_context(nn.logical_axis_rules(self._rules))
        return stack

    # -- compiled device functions ------------------------------------------

    def _compile(self):
        # Fragment caches carry headroom of one max bucket past max_len
        # WHEN offset writes can happen — chunked admission, or a prefix-
        # cache hit resuming mid-prompt (either makes _extend write a
        # bucket-wide update at a nonzero offset whose padding may extend
        # past max_len, and dynamic_update_slice would otherwise CLAMP the
        # start index, shifting the write backwards over real prompt rows:
        # silent KV corruption). Pad rows land in the slack and are
        # dropped at insert; real prompt rows never exceed max_len-1
        # (submit bound).
        big = self.prefill_buckets[-1]
        self._may_chunk = big < self.max_len - 1
        offset_writes = self._may_chunk or self._prefix_cap > 0
        fns = build_engine_fns(
            self.model, self.cfg, max_len=self.max_len, chunk=self.chunk,
            prefill_buckets=self.prefill_buckets,
            offset_writes=offset_writes,
            cache_sharding=self._cache_sharding,
            adapters=self._ml_stacks,
            rolling_window=self._rolling,
            kv_block_size=self._kv_bs if self._paged else 0,
            kv_quant=self.kv_quant)
        prefill_jit = jax.jit(fns["prefill"])
        self._prefill = {b: prefill_jit for b in self.prefill_buckets}
        self._extend = jax.jit(fns["extend"], donate_argnums=(1,))
        self._extend_mid = jax.jit(fns["extend_mid"], donate_argnums=(1,))
        if self._paged:
            # Same attribute names, paged signatures: _insert takes the
            # request's scatter table, _decode the per-row block tables
            # (call sites branch on self._paged). Admission fragments
            # (prefill/extend) are identical in both modes.
            self._insert = jax.jit(fns["insert_paged"],
                                   donate_argnums=(0,))
            self._frag_from_pool = jax.jit(fns["frag_from_pool"])
            # KV wire format halves (ISSUE 13): export gathers blocks
            # for a shipment/spill, import scatters shipped blocks in.
            # Jitted lazily on first use — a unified engine that never
            # ships pays nothing. DELIBERATE: one compiled shape each,
            # max_len-blocks wide — the device copy moves the full
            # width and the host slices/pads to the real block count
            # (the HTTP wire carries only committed blocks). Bucketing
            # the width like decode would shrink the D2H/H2D copies for
            # short prompts at the cost of a per-bucket executable
            # pair; revisit when a chip profile shows the copy, not the
            # handoff hop, dominating.
            self._export_blocks = jax.jit(fns["export_blocks"])
            self._import_blocks = jax.jit(fns["import_blocks"],
                                          donate_argnums=(0,))
            self._decode = {
                (b, trunc): jax.jit(fns["make_decode_paged"](trunc, b),
                                    donate_argnums=(1,))
                for b in self.decode_buckets for trunc in (False, True)}
        else:
            self._insert = jax.jit(fns["insert"], donate_argnums=(0,))
            self._decode = {
                (b, trunc): jax.jit(fns["make_decode"](trunc, b),
                                    donate_argnums=(1,))
                for b in self.decode_buckets for trunc in (False, True)}
        if self._spec is not None:
            # The draft runs the SAME admission recipe (chunked cache
            # writes, no sampling — extend_mid) over its own cache tree.
            dfns = build_engine_fns(
                self._spec["model"], self._spec["cfg"],
                max_len=self.max_len, chunk=self.chunk,
                prefill_buckets=self.prefill_buckets,
                offset_writes=True,
                cache_sharding=self._dcache_sharding,
                kv_block_size=self._kv_bs if self._paged else 0)
            self._dextend_mid = jax.jit(dfns["extend_mid"],
                                        donate_argnums=(1,))
            if self._paged:
                # Paged draft pool (ISSUE 18 move 1): insert scatters a
                # replayed draft fragment into the slot's draft blocks;
                # export/import are the wire halves for the shipment's
                # optional draft section (fmt 2) — compiled only on role
                # engines' warmup, like the target's.
                self._dinsert = jax.jit(dfns["insert_paged"],
                                        donate_argnums=(0,))
                self._dexport_blocks = jax.jit(dfns["export_blocks"])
                self._dimport_blocks = jax.jit(dfns["import_blocks"],
                                               donate_argnums=(0,))
            else:
                self._dinsert = jax.jit(dfns["insert"], donate_argnums=(0,))
            self._dfrag_len = dfns["frag_len"]
            self._dfrag_init = jax.jit(
                lambda: self._dstate.fragment(self._dfrag_len))
            spec_make = build_spec_decode(
                self.model, self._spec["model"],
                gamma=self._spec["gamma"], n_spec=self._spec["n_spec"],
                max_len=self.max_len, rolling_window=self._rolling,
                adapters=self._ml_stacks,
                kv_block_size=self._kv_bs if self._paged else 0)
            self._spec_decode = {
                b: jax.jit(spec_make(b), donate_argnums=(2, 3))
                for b in self.decode_buckets}

    def _warmup(self):
        """Pay every compile before serving: one prefill per bucket, one
        insert, one chunked decode (jit caches keyed on static shapes)."""
        zero_t = jnp.zeros((1,), jnp.float32)
        one_l = jnp.ones((1,), jnp.int32)
        zero_k = jnp.zeros((1,), jnp.int32)
        one_p = jnp.ones((1,), jnp.float32)
        aid1 = self._aid1(0)
        frag = None
        for b in self.prefill_buckets:
            frag, _, _ = self._prefill[b](
                self._params, jnp.zeros((1, b), jnp.int32), one_l, zero_t,
                zero_k, one_p, self._key, aid=aid1)
        if self._may_chunk or self._prefix_cap:  # offset-write paths
            # Intermediate chunks always use the largest bucket; the
            # final (sampling) chunk can land on any bucket.
            frag = self._extend_mid(
                self._params, frag,
                jnp.zeros((1, self.prefill_buckets[-1]), jnp.int32),
                zero_k, aid=aid1)
            for b in self.prefill_buckets:
                frag, _, _ = self._extend(
                    self._params, frag, jnp.zeros((1, b), jnp.int32),
                    one_l, zero_k, zero_t, zero_k, one_p, self._key,
                    aid=aid1)
        n = self.n_slots
        if self._paged:
            # All-NULL tables: the warmup writes land in the reserved
            # garbage block, never in allocatable pool blocks.
            mb = self.max_len // self._kv_bs
            self._cache = self._insert(self._cache, frag,
                                       self._scatter_tables({}))
            if self._prefix_cap:
                frag = self._frag_from_pool(self._cache,
                                            jnp.zeros((mb,), jnp.int32))
            if self.role != "unified" or self._host_tier is not None:
                # Warm the wire-format halves: a role engine's first
                # handoff (or first spill) must not pay a compile.
                mb = self.max_len // self._kv_bs
                gt = jnp.zeros((mb,), jnp.int32)
                gathered = self._export_blocks(self._cache, gt)
                # All-NULL table: the import lands in the reserved
                # garbage block, never in allocatable pool blocks.
                self._cache = self._import_blocks(self._cache, gathered,
                                                  gt)
            for (b, _), fn in self._decode.items():
                self._cache, *_ = fn(
                    self._params, self._cache,
                    self._block_tables([], b // self._kv_bs),
                    jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n,), jnp.float32),
                    jnp.zeros((n,), jnp.int32),
                    jnp.ones((n,), jnp.float32),
                    self._key, aid=self._aid_batch([0] * n))
        else:
            self._cache = self._insert(self._cache, frag, jnp.int32(0))
            for fn in self._decode.values():
                self._cache, *_ = fn(
                    self._params, self._cache, jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n,), jnp.float32),
                    jnp.zeros((n,), jnp.int32),
                    jnp.ones((n,), jnp.float32),
                    self._key, aid=self._aid_batch([0] * n))
        if self._spec is not None:
            dfrag = self._dfrag_init()
            for b in self.prefill_buckets:
                dfrag = self._dextend_mid(
                    self._dparams, dfrag, jnp.zeros((1, b), jnp.int32),
                    zero_k)
            if self._paged:
                mb = self.max_len // self._kv_bs
                # All-NULL scatter/gather tables, like the target's pool
                # warmup: nothing lands in allocatable blocks.
                self._dcache = self._dinsert(self._dcache, dfrag,
                                             jnp.zeros((mb,), jnp.int32))
                if self.role != "unified":
                    gt = jnp.zeros((mb,), jnp.int32)
                    gathered = self._dexport_blocks(self._dcache, gt)
                    self._dcache = self._dimport_blocks(self._dcache,
                                                        gathered, gt)
                for b, fn in self._spec_decode.items():
                    self._cache, self._dcache, _, _, _ = fn(
                        self._params, self._dparams, self._cache,
                        self._dcache,
                        jnp.zeros((n, b // self._kv_bs), jnp.int32),
                        jnp.zeros((n, b // self._kv_bs), jnp.int32),
                        jnp.zeros((n,), jnp.int32),
                        jnp.zeros((n,), jnp.int32),
                        jnp.zeros((n,), jnp.float32), self._key,
                        aid=self._aid_batch([0] * n))
            else:
                self._dcache = self._dinsert(self._dcache, dfrag,
                                             jnp.int32(0))
                for fn in self._spec_decode.values():
                    self._cache, self._dcache, _, _, _ = fn(
                        self._params, self._dparams, self._cache,
                        self._dcache,
                        jnp.zeros((n,), jnp.int32),
                        jnp.zeros((n,), jnp.int32),
                        jnp.zeros((n,), jnp.float32), self._key,
                        aid=self._aid_batch([0] * n))

    # -- multi-LoRA ----------------------------------------------------------

    def _aid1(self, aid: int):
        """[1]-shaped adapter index for admission fns — None when the
        engine has no adapter stacks (base-only trace)."""
        if self._ml_stacks is None:
            return None
        return jnp.asarray([aid], jnp.int32)

    def _aid_batch(self, aids):
        if self._ml_stacks is None:
            return None
        return jnp.asarray(aids, jnp.int32)

    def adapter_names(self) -> list:
        """Loaded multi-LoRA adapter names (the public surface — the
        OpenAI model-id routing and metadata() both read this)."""
        return sorted(self._ml_ids)

    def _resolve_adapter(self, name) -> int:
        if name is None:
            return 0
        if self._ml_stacks is None:
            raise ValueError(
                f"adapter {name!r} requested but the engine has no "
                "adapters configured")
        try:
            return self._ml_ids[name]
        except KeyError:
            raise ValueError(
                f"unknown adapter {name!r}; loaded: "
                f"{sorted(self._ml_ids)}") from None

    # -- public API ----------------------------------------------------------

    def submit(self, input_ids: Sequence[int], *, max_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, eos_id: int | None = None,
               timeout: float = 300.0, adapter: str | None = None,
               deadline: Deadline | None = None, on_tokens=None,
               trace_id: str = "") -> dict:
        """`on_tokens(tokens, done)` (optional) is invoked from the worker
        thread as tokens are emitted — chunk-granular streaming; the final
        call has done=True. Exceptions in the callback are swallowed (a
        slow/broken stream consumer must not stall the decode loop).

        `deadline` is the request's end-to-end budget (resilience.Deadline,
        propagated from the server's timeout header): the scheduler checks
        it at admission and every chunk boundary, and an expired request
        raises DeadlineExceeded AND frees its decode slot — it stops
        burning batch capacity the moment its 504 is decided."""
        if self.role != "unified":
            # Role discipline IS the isolation claim: a decode engine
            # that ran this path would chunk-prefill locally (stealing
            # decode dispatches), a prefill engine would decode.
            raise RuntimeError(
                f"{self.role}-role engine refuses a local generate: "
                "prefill engines take prefill_ship(), decode engines "
                "take submit_remote()")
        if not input_ids:
            raise ValueError("input_ids must be non-empty")
        if len(input_ids) > self.max_len - 1:
            raise ValueError(
                f"prompt of {len(input_ids)} tokens exceeds max_len "
                f"{self.max_len}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        req = {
            "input_ids": [int(t) for t in input_ids],
            "max_tokens": int(max_tokens),
            "temperature": float(temperature),
            "top_k": int(top_k),
            "top_p": float(top_p),
            "aid": self._resolve_adapter(adapter),
            "eos_id": eos_id,
            "out": [],
            "out_logprobs": [],
            "done": threading.Event(),
            "error": None,
            "deadline": deadline,
            "t0": time.monotonic(),
            # Trace identity + enqueue mark: the worker records this
            # request's batch-gather span (queue wait → slot admission)
            # and annotates its prefill/decode/fetch spans with the id.
            "trace": trace_id,
            "t_enq": time.perf_counter(),
            "cb": on_tokens,
        }
        self._refuse_oversized(req)
        self._queue.put(req)
        self._wake.set()
        wait_s = timeout
        if deadline is not None:
            # Wake as soon as the budget expires — the worker notices at
            # the next chunk boundary, but the caller's 504 must not wait
            # for it.
            wait_s = deadline.bound(timeout)
        if not req["done"].wait(wait_s):
            if deadline is not None and deadline.expired():
                req["error"] = DeadlineExceeded(
                    "request deadline expired during generation")
            else:
                req["error"] = f"generation timed out after {timeout}s"
        if isinstance(req["error"], BaseException):
            raise req["error"]
        if req["error"]:
            raise RuntimeError(req["error"])
        return {
            "output_ids": req["out"],
            "output_logprobs": req["out_logprobs"],
            "num_input_tokens": len(req["input_ids"]),
            "num_output_tokens": len(req["out"]),
            "latency_s": time.monotonic() - req["t0"],
        }

    def prefill_ship(self, input_ids: Sequence[int], *,
                     max_tokens: int = 32, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0,
                     eos_id: int | None = None, timeout: float = 300.0,
                     adapter: str | None = None,
                     deadline: Deadline | None = None,
                     trace_id: str = "", extra: dict | None = None) -> dict:
        """Chunk-prefill a prompt into pool blocks and return them as a
        WIRE SHIPMENT instead of decoding (the prefill half of
        disaggregation): committed KV blocks + the prompt tokens + the
        sampled first token/logprob + this engine's post-prefill RNG key
        state, packed by serve/kv_transfer.py. The blocks are released
        back to the pool the moment they are serialized — a prefill
        replica's pool only ever holds in-flight prefills (plus its
        prefix cache, which keeps sharing/spilling as usual).

        `extra` rides the shipment metadata verbatim (the server stashes
        the caller's stream flag there). Returns {"shipment": bytes,
        "num_input_tokens", "first_token", "latency_s"}."""
        if self.role == "decode":
            raise RuntimeError(
                "decode-role engine refuses prefill work (zero prefill "
                "chunks is the disaggregation invariant)")
        if not self._paged:
            raise RuntimeError(
                "prefill_ship needs the paged KV cache (KV blocks are "
                "the wire unit); set kv_block_size > 0")
        if not input_ids:
            raise ValueError("input_ids must be non-empty")
        if len(input_ids) > self.max_len - 1:
            raise ValueError(
                f"prompt of {len(input_ids)} tokens exceeds max_len "
                f"{self.max_len}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        req = {
            "mode": "ship",
            "input_ids": [int(t) for t in input_ids],
            "max_tokens": int(max_tokens),
            "temperature": float(temperature),
            "top_k": int(top_k),
            "top_p": float(top_p),
            "aid": self._resolve_adapter(adapter),
            "adapter": adapter,
            "eos_id": eos_id,
            "timeout": float(timeout),
            "extra": dict(extra or {}),
            "out": [], "out_logprobs": [],
            "done": threading.Event(),
            "error": None,
            "result": None,
            "deadline": deadline,
            "t0": time.monotonic(),
            "trace": trace_id,
            "t_enq": time.perf_counter(),
            "cb": None,
        }
        self._refuse_oversized(req)
        self._queue.put(req)
        self._wake.set()
        wait_s = deadline.bound(timeout) if deadline is not None else timeout
        if not req["done"].wait(wait_s):
            if deadline is not None and deadline.expired():
                req["error"] = DeadlineExceeded(
                    "request deadline expired during prefill")
            else:
                req["error"] = f"prefill timed out after {timeout}s"
        if isinstance(req["error"], BaseException):
            raise req["error"]
        if req["error"]:
            raise RuntimeError(req["error"])
        out = dict(req["result"])
        out["latency_s"] = time.monotonic() - req["t0"]
        return out

    def submit_remote(self, shipment, *, timeout: float | None = None,
                      deadline: Deadline | None = None, on_tokens=None,
                      trace_id: str = "") -> dict:
        """Admit a shipped prefill (prefill_ship bytes) straight into
        decode — the decode half of disaggregation. The shipped blocks
        scatter into this pool under a freshly reserved table (full
        decode-budget reservation, exactly the local admission
        discipline — transient exhaustion stashes head-of-line in
        `_kv_stash` like any admission), the shipped first token seeds
        the decode carry, and the shipped RNG key state is adopted so a
        single disaggregated stream is token+logprob-identical to the
        unified engine on the same seed. Sampling params AND the
        caller's request timeout travel IN the shipment (they were
        fixed at prefill; `timeout=None` adopts the shipped budget so
        a role split never silently shrinks it). Never runs a prefill
        chunk."""
        if self.role == "prefill":
            raise RuntimeError(
                "prefill-role engine refuses decode work; route "
                "shipments to a decode or unified replica")
        if not self._paged:
            raise RuntimeError(
                "submit_remote needs the paged KV cache; set "
                "kv_block_size > 0")
        meta, arrays = unpack_shipment(shipment)
        fmt = int(meta.get("fmt", 0))
        if fmt not in (1, 2, 3):
            raise ShipmentError(
                f"unknown shipment fmt {meta.get('fmt')!r}")
        if fmt == 3 and self.kv_quant == "none":
            # Never silently dequant-upcast: accepting quantized blocks
            # into a full-precision pool would make this stream's
            # numerics depend on WHICH replica prefilled it — the
            # fleet-skew failure mode the compat guard exists to refuse.
            raise ShipmentError(
                f"shipment fmt 3 carries {meta.get('kv_quant')!r}-"
                "quantized KV blocks but this engine runs "
                "kv_quant='none'; pair quantized prefill replicas with "
                "decode replicas running the same kv_quant (or drop "
                "generative.kv_quant fleet-wide)")
        if fmt == 3 and str(meta.get("kv_quant")) != self.kv_quant:
            raise ShipmentError(
                f"shipment kv_quant {meta.get('kv_quant')!r} != this "
                f"engine's {self.kv_quant!r} — mixed-precision fleets "
                "cannot exchange KV blocks (align generative.kv_quant)")
        if fmt == 2 and self._spec is None:
            # The versioned draft section is refused loudly, never
            # silently dropped: a fleet pairing draft-carrying prefill
            # replicas with draft-less decode replicas is misconfigured
            # (the decode side would re-pay the replay the shipment
            # exists to avoid) and must surface at submit.
            raise ShipmentError(
                "shipment fmt 2 carries a draft-KV section but this "
                "engine has no draft model; pair draft-carrying "
                "prefill replicas with draft-configured decode "
                "replicas (or drop generative.draft fleet-wide)")
        if int(meta.get("block_size", 0)) != self._kv_bs:
            raise ShipmentError(
                f"shipment block_size {meta.get('block_size')} != this "
                f"pool's {self._kv_bs} (pair replicas with identical "
                "kv_block_size)")
        if int(meta.get("vocab_size", 0)) != int(self.cfg.vocab_size):
            raise ShipmentError(
                f"shipment vocab {meta.get('vocab_size')} != model "
                f"vocab {self.cfg.vocab_size}")
        ids = [int(t) for t in meta["tokens"]]
        if not ids or len(ids) > self.max_len - 1:
            raise ShipmentError(
                f"shipped prompt of {len(ids)} tokens does not fit "
                f"max_len {self.max_len}")
        n_blocks = blocks_for(len(ids), self._kv_bs)
        mb = self.max_len // self._kv_bs
        ref = self._cache["k"].shape  # [L, NB+1, bs, KH, D]
        quantized = self.kv_quant != "none"
        if quantized and fmt != 3:
            # fmt-1 full-precision blocks into a quantized pool:
            # quantize at import, host-side at the admission boundary,
            # with the SAME encode decode writes and local admission use
            # — so a remotely prefilled row reaches the identical bytes
            # a local prefill of the same prompt would have written.
            for name in ("k", "v"):
                arr = arrays.get(name)
                if arr is None:
                    raise ShipmentError(
                        f"shipment missing {name!r} blocks")
                q, s = kv_quantize_rows(jnp.asarray(arr), self.kv_quant)
                arrays[name] = np.asarray(q)
                arrays[name + "s"] = np.asarray(s)
        blocks = {}
        for name in (("k", "v", "ks", "vs") if quantized
                     else ("k", "v")):
            arr = arrays.get(name)
            if arr is None:
                raise ShipmentError(f"shipment missing {name!r} blocks")
            lref = self._cache[name].shape  # scale planes drop the D axis
            want = (lref[0], n_blocks, *lref[2:])
            if tuple(arr.shape) != want:
                raise ShipmentError(
                    f"shipment {name} blocks shaped {tuple(arr.shape)}, "
                    f"this engine needs {want}")
            # Pad to the compiled [mb]-block import width; pads scatter
            # into the NULL block.
            pad = np.zeros((lref[0], mb, *lref[2:]), arr.dtype)
            pad[:, :n_blocks] = arr
            blocks[name] = pad
        draft_blocks = None
        dn_blocks = 0
        if fmt == 2:
            dmeta = dict(meta.get("draft") or {})
            dref = self._dcache["k"]
            if (int(dmeta.get("block_size", 0)) != self._kv_bs
                    or int(dmeta.get("vocab_size", 0))
                    != int(self._spec["cfg"].vocab_size)
                    or int(dmeta.get("num_layers", 0)) != int(dref.shape[0])
                    or list(dmeta.get("kv_shape", ()))
                    != list(dref.shape[2:])
                    or str(dmeta.get("dtype")) != str(dref.dtype)):
                raise ShipmentError(
                    f"shipment draft section {dmeta} does not match "
                    f"this engine's draft model (layers={dref.shape[0]}, "
                    f"kv_shape={list(dref.shape[2:])}, "
                    f"dtype={dref.dtype}, block_size={self._kv_bs}) — "
                    "mixed-precision or mixed-config fleets cannot "
                    "exchange draft KV")
            dn_blocks = int(dmeta.get("n_blocks", 0))
            if dn_blocks < 1 or dn_blocks > mb:
                raise ShipmentError(
                    f"shipment draft section claims {dn_blocks} blocks; "
                    f"this engine fits at most {mb}")
            draft_blocks = {}
            for name in ("k", "v"):
                arr = arrays.get("draft_" + name)
                if arr is None:
                    raise ShipmentError(
                        f"fmt 2 shipment missing draft_{name!r} blocks")
                want = (dref.shape[0], dn_blocks, *dref.shape[2:])
                if tuple(arr.shape) != want:
                    raise ShipmentError(
                        f"shipment draft_{name} blocks shaped "
                        f"{tuple(arr.shape)}, this engine needs {want}")
                pad = np.zeros((dref.shape[0], mb, *dref.shape[2:]),
                               arr.dtype)
                pad[:, :dn_blocks] = arr
                draft_blocks[name] = pad
        if timeout is None:
            timeout = float(meta.get("timeout", 300.0))
        max_tokens = int(meta.get("max_tokens", 32))
        req = {
            "mode": "remote",
            "input_ids": ids,
            "max_tokens": max_tokens,
            "temperature": float(meta.get("temperature", 0.0)),
            "top_k": int(meta.get("top_k", 0)),
            "top_p": float(meta.get("top_p", 1.0)),
            "aid": self._resolve_adapter(meta.get("adapter")),
            "eos_id": meta.get("eos_id"),
            "first_tok": int(meta["first_token"]),
            "first_lp": float(meta["first_logprob"]),
            "kv_blocks": blocks,
            "n_blocks": n_blocks,
            "draft_blocks": draft_blocks,
            "dn_blocks": dn_blocks,
            "rng_key": arrays.get("rng_key"),
            "out": [], "out_logprobs": [],
            "done": threading.Event(),
            "error": None,
            "deadline": deadline,
            "t0": time.monotonic(),
            # The trace id rides the shipment meta too (router-stamped
            # via rewrite_meta): a caller that didn't thread an explicit
            # id still joins the request's distributed trace.
            "trace": trace_id or str(meta.get("trace") or ""),
            "t_enq": time.perf_counter(),
            "cb": on_tokens,
        }
        self._refuse_oversized(req)
        self._queue.put(req)
        self._wake.set()
        wait_s = deadline.bound(timeout) if deadline is not None else timeout
        if not req["done"].wait(wait_s):
            if deadline is not None and deadline.expired():
                req["error"] = DeadlineExceeded(
                    "request deadline expired during generation")
            else:
                req["error"] = f"generation timed out after {timeout}s"
        if isinstance(req["error"], BaseException):
            raise req["error"]
        if req["error"]:
            raise RuntimeError(req["error"])
        return {
            "output_ids": req["out"],
            "output_logprobs": req["out_logprobs"],
            "num_input_tokens": len(ids),
            "num_output_tokens": len(req["out"]),
            "latency_s": time.monotonic() - req["t0"],
        }

    def close(self):
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=5.0)

    # -- worker --------------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _decode_bucket_for(self, need: int) -> int:
        """The smallest decode bucket holding `need` cache rows — short
        conversations never pay max_len-wide attention."""
        return next((b for b in self.decode_buckets if b >= need),
                    self.decode_buckets[-1])

    # -- prefix cache --------------------------------------------------------

    def _prefix_lookup(self, ids: list[int],
                       aid: int = 0) -> tuple[int, Any] | None:
        """Longest cached chunk-boundary prefix STRICTLY shorter than the
        prompt (the final token's logits must still be computed). Keys
        carry the ADAPTER index: a prefix computed under adapter X holds
        X's K/V deltas and must never serve a request under adapter Y.

        Fast path (ISSUE 3): entries are keyed `(aid, n, hash(tokens))`
        and a per-adapter length index drives the probe — one O(n) hash
        per DISTINCT cached length (longest first) instead of the seed's
        O(cap × len) scan with a full token-list compare per entry. The
        stored token tuple still verifies each hash hit, so a collision
        can only cost a miss, never a wrong fragment.
        Returns (matched_len, fresh fragment copy) or None."""
        lens = self._prefix_lens.get(aid)
        if not lens:
            return None
        for n in sorted(lens, reverse=True):
            if n >= len(ids):
                continue
            kt = tuple(ids[:n])
            key = (aid, n, hash(kt))
            entry = self._prefix_lru.get(key)
            if entry is None or entry[0] != kt:
                continue  # absent, or a same-hash different prefix
            self._prefix_lru.move_to_end(key)
            return n, jax.tree.map(jnp.copy, entry[1])
        return None

    def _prefix_store(self, aid: int, kt: tuple, frag, *,
                      copy: bool = True) -> None:
        """Snapshot a fragment at a prompt-chunk boundary. Rows past the
        keyed prefix may hold pad/stale K/V — safe, because any reader
        overwrites row i before its query positions can reach it (absolute-
        position masking hides rows above the current index).

        `copy=False` takes `frag` by reference — used for an admission's
        FINAL fragment, which nothing donates afterwards (`_insert`
        donates the slot cache, not the fragment) and which every lookup
        hit copies out of, so the stored tree is never mutated. A store
        whose key is already resident is a pure LRU touch (no device
        work)."""
        key = (aid, len(kt), hash(kt))
        existing = self._prefix_lru.get(key)
        if existing is not None and existing[0] == kt:
            self._prefix_lru.move_to_end(key)
            return
        if existing is None:
            per = self._prefix_lens.setdefault(aid, {})
            per[len(kt)] = per.get(len(kt), 0) + 1
        self._prefix_lru[key] = (kt, frag if not copy
                                 else jax.tree.map(jnp.copy, frag))
        self._prefix_lru.move_to_end(key)
        with self._stats_lock:
            self.stats["prefix_stores"] += 1
        while len(self._prefix_lru) > self._prefix_cap:
            self._prefix_evict_oldest()

    # -- paged KV (block-table) admission ------------------------------------

    def _paged_need_tokens(self, prompt: int, max_tokens: int) -> int:
        """Worst-case cache rows a request can ever WRITE: the prompt
        plus its decode budget rounded up to whole dispatch chunks (the
        retirement chunk still writes its full width), capped at max_len
        (decode indices clamp at bucket-1, so no write ever lands past
        row max_len-1). Blocks covering this are reserved whole at
        admission — allocation never happens on the decode critical
        path, which is what lets paging compose with pipeline_depth>1
        without new host syncs. Dead in-flight chunks past a retirement
        may write beyond this bound; those rows map to NULL-block table
        pads, never to another request's blocks."""
        chunks = -(-max(int(max_tokens), 1) // self.chunk)
        return min(self.max_len, prompt + chunks * self.chunk)

    def _need_blocks(self, req: dict) -> int:
        """The most target pool blocks a request ever holds, which
        admission has to see free: what the worst case of
        `_paged_need_tokens` holds (rows: reserved whole at admission) or
        peaks at on its way there (a state that grows), or the prompt
        alone in ship mode (the decode replica reserves the decode budget
        at submit_remote)."""
        n = len(req["input_ids"])
        if req.get("mode") != "ship":
            n = self._paged_need_tokens(n, req["max_tokens"])
        return self._state.peak(n)

    def _admit_blocks(self, req: dict) -> tuple:
        """Blocks of each kind a request takes at admission: all it will
        ever hold, or, where the state grows, what its prompt holds (the
        rest is taken as its rows are dispatched, `_grow`)."""
        n = len(req["input_ids"])
        if req.get("mode") != "ship" and not self._state.grows:
            n = self._paged_need_tokens(n, req["max_tokens"])
        return self._state.held(n)

    def _kv_owed(self) -> int:
        """Blocks that live requests may still take on their way to their
        peaks: free blocks that admission must not give away."""
        return sum(st["peak"] - sum(len(st[key]) for key in self._keys)
                   for st in self._slots
                   if st is not None and "peak" in st)

    def _refuse_oversized(self, req: dict) -> None:
        """Shed at submit (503) a request whose reserve even an empty
        pool can't cover: permanent, so it must not camp in the queue
        to a 504. Spec-able requests count the draft pool's equal
        footprint (ISSUE 18 move 1)."""
        if not self._paged:
            return
        need = self._need_blocks(req) + self._draft_need_blocks(req)
        if need > self._kv_alloc.n_blocks:
            budget = ("" if req.get("mode") == "ship"
                      else f" + max_tokens {req['max_tokens']}")
            raise KVCapacityExceeded(
                f"request needs {need} KV blocks worst-case (prompt "
                f"{len(req['input_ids'])}{budget}) but the pool has "
                f"{self._kv_alloc.n_blocks}")

    def _spec_able(self, req: dict) -> bool:
        """A request rides the spec sub-batch iff it has no truncated
        sampling: greedy and plain-temperature rows compose with the
        rejection scheme; top-k/top-p rows decode on the vanilla
        sub-batch (ISSUE 18 move 2 — per-request, not batch-wide)."""
        return (self._spec is not None
                and req.get("top_k", 0) == 0
                and req.get("top_p", 1.0) >= 1.0)

    def _draft_need_blocks(self, req: dict) -> int:
        """Worst-case DRAFT pool blocks a spec-able request reserves on
        top of the target's (ISSUE 18 move 1): the same bound as the
        target's, because the draft cache mirrors the committed index.
        Draft blocks are per-slot private working state — never
        prefix-shared, never discounted by a hit."""
        if not (self._paged and self._spec_able(req)):
            return 0
        return self._need_blocks(req)

    def _reserve_blocks(self, req: dict,
                        n_shared: int = 0) -> tuple[list, list | None]:
        """Take a request's whole worst-case block need off the pool,
        less `n_shared` prefix blocks it maps by reference — the one
        reserve behind local, ship-mode and remote admission, so a
        shipped request can neither out- nor under-reserve a local one.
        Returns (fresh target blocks, draft blocks or None); raises
        _NeedKVBlocks with nothing held. _admit_waiting's _kv_fits
        precheck (which counts both) makes the failure unreachable in
        the normal flow; defense against future reordering."""
        fresh = self._kv_alloc.alloc(
            max(0, sum(self._admit_blocks(req)) - n_shared))
        if fresh is None:
            raise _NeedKVBlocks()
        # Draft blocks ride the same pool, per-slot and never
        # prefix-shared (the draft cache holds draft-model activations —
        # a target prefix block would be garbage to it). Both or
        # neither, so _kv_fits stays the single admission gate.
        dtable = None
        dneed = self._draft_need_blocks(req)
        if dneed:
            dtable = self._kv_alloc.alloc(dneed)
            if dtable is None:
                self._kv_alloc.decref(fresh)
                raise _NeedKVBlocks()
        return fresh, dtable

    def _prefix_probe_paged(self, ids: list[int], aid: int, *,
                            touch: bool) -> tuple[int, tuple] | None:
        """Paged twin of `_prefix_lookup`: longest strictly-shorter
        cached prefix, returning its resident BLOCK IDS instead of a
        fragment copy. `touch=False` is the read-only peek the
        admission-fit check uses (no LRU reorder, no stats)."""
        lens = self._prefix_lens.get(aid)
        if not lens:
            return None
        for n in sorted(lens, reverse=True):
            if n >= len(ids):
                continue
            kt = tuple(ids[:n])
            key = (aid, n, hash(kt))
            entry = self._prefix_lru.get(key)
            if entry is None or entry[0] != kt:
                continue
            if touch:
                self._prefix_lru.move_to_end(key)
            return n, entry[1]
        return None

    def _prefix_store_paged(self, aid: int, kt: tuple,
                            blocks: list[int]) -> None:
        """Publish a prompt-boundary prefix as block REFERENCES
        (refcount bump — no fragment copy, no device work). The stored
        tail block may be partially filled; its owner keeps appending at
        rows >= len(kt), which never disturbs the committed rows a later
        hit reads, and the hit forks that block before writing (CoW)."""
        key = (aid, len(kt), hash(kt))
        existing = self._prefix_lru.get(key)
        if existing is not None and existing[0] == kt:
            self._prefix_lru.move_to_end(key)
            return
        if existing is None:
            per = self._prefix_lens.setdefault(aid, {})
            per[len(kt)] = per.get(len(kt), 0) + 1
        else:
            # Hash-collision overwrite: the displaced entry's block refs
            # must be dropped or they leak out of the pool forever (the
            # flat cache's displaced fragment was simply GC'd; the
            # refcounted twin needs the explicit release).
            self._kv_alloc.decref(existing[1])
        self._kv_alloc.incref(blocks)
        self._prefix_lru[key] = (kt, tuple(blocks))
        self._prefix_lru.move_to_end(key)
        with self._stats_lock:
            self.stats["prefix_stores"] += 1
        while len(self._prefix_lru) > self._prefix_cap:
            self._prefix_evict_oldest()

    def _prefix_evict_oldest(self) -> None:
        self._prefix_evict(next(iter(self._prefix_lru)))

    def _prefix_evict(self, key: tuple) -> None:
        """Drop one prefix entry + its length-index bookkeeping — shared
        by both cache flavors. The payload is a fragment tree (flat:
        Python GC reclaims it) or a block-id tuple (paged: the refs must
        be returned to the allocator explicitly). With a host tier
        configured, a paged eviction SPILLS the blocks first (cold
        blocks move down-tier instead of vanishing — restore-on-hit
        brings them back, lifting the effective pool beyond HBM)."""
        if self._paged and self._host_tier is not None:
            kt, blocks = self._prefix_lru[key]
            self._spill_prefix(key, kt, blocks)
        _, payload = self._prefix_lru.pop(key)
        eaid, en, _ = key
        per = self._prefix_lens.get(eaid, {})
        if per.get(en, 0) <= 1:
            per.pop(en, None)
            if not per:
                self._prefix_lens.pop(eaid, None)
        else:
            per[en] -= 1
        if self._paged:
            self._kv_alloc.decref(payload)

    def _kv_fits(self, req: dict) -> bool:
        """Admission-by-free-blocks (the paged replacement for "is a
        static slot free"): can the pool cover this request's worst-case
        need right now, counting zero-copy shared prefix blocks? Under
        pressure, LRU prefix-cache entries are reclaimed first — cached
        prefixes must yield to live traffic, or a pool fully pinned by
        cache references would deadlock an idle engine against a stashed
        admission.

        Reclaim discipline: the feasibility bound is computed ONCE (a
        block counts as reclaimable only when every ref on it is a
        cache ref — live tables pin the rest), for BOTH outcomes:
        keeping the peeked zero-copy hit (discounted need, hit's blocks
        unreclaimable) and sacrificing it (full need, everything
        reclaimable). If neither can ever fit, nothing is evicted at
        all. Otherwise non-hit entries go first, oldest-first, and the
        hit itself is evicted only when sacrificing its discount is the
        only way to fit — an admission can never wipe the cache while
        freeing nothing, and never destroys its own hit needlessly."""
        ids = req["input_ids"]
        mode = req.get("mode")
        # Spec-able requests also cover the draft pool's footprint —
        # fresh blocks only, so the prefix-hit discount never applies.
        total = self._need_blocks(req) + self._draft_need_blocks(req)
        aid = req.get("aid", 0)
        # Remote admissions never discount by a prefix hit: their blocks
        # arrive on the wire and the reserve below allocates the FULL
        # need — a discount here could pass a request the reserve can
        # never satisfy (permanent head-of-line stall).
        hit = (self._prefix_probe_paged(ids, aid, touch=False)
               if self._prefix_cap and mode != "remote" else None)
        shared = hit[0] // self._kv_bs if hit is not None else 0
        hit_key = ((aid, hit[0], hash(tuple(ids[:hit[0]])))
                   if hit is not None else None)
        if self._kv_alloc.can_alloc(total - shared + self._kv_owed()):
            return True
        if not self._prefix_lru:
            return False
        cache_refs: dict[int, int] = {}
        for _, eblocks in self._prefix_lru.values():
            for b in eblocks:
                cache_refs[b] = cache_refs.get(b, 0) + 1
        hit_blocks = set(hit[1]) if hit is not None else set()
        free = self._kv_alloc.free_blocks
        reclaim_all = sum(1 for b, c in cache_refs.items()
                          if self._kv_alloc.refcount(b) == c)
        reclaim_keep_hit = sum(1 for b, c in cache_refs.items()
                               if self._kv_alloc.refcount(b) == c
                               and b not in hit_blocks)
        keep_hit = (hit_key is not None
                    and free + reclaim_keep_hit >= total - shared)
        if not keep_hit and free + reclaim_all < total:
            return False
        protect = hit_key if keep_hit else None
        while True:
            resident = (hit_key is not None
                        and hit_key in self._prefix_lru)
            disc = shared if resident else 0
            if self._kv_alloc.can_alloc(total - disc):
                return True
            victim = next((k for k in self._prefix_lru if k != protect),
                          None)
            if victim is None:
                return False  # unreachable under the exact bounds above
            self._prefix_evict(victim)

    def _free_slot_blocks(self, st: dict) -> None:
        """Return a retired request's block references to the pool
        (idempotent — the pop guards double-retirement paths). Blocks
        still referenced by the prefix cache or by zero-copy sharers
        survive; in-flight dead chunks may still write to truly-freed
        blocks, which is safe because any re-admission's insert is
        dispatched AFTER them and rewrites every block it was handed
        (device stream order is dispatch order)."""
        if not self._paged:
            return
        for key in self._keys + ("dblocks",):
            blocks = st.pop(key, None)
            if blocks:
                self._kv_alloc.decref(blocks)

    @property
    def kv_blocks_free(self):
        return self._kv_alloc.free_blocks if self._paged else None

    @property
    def kv_blocks_used(self):
        return self._kv_alloc.used_blocks if self._paged else None

    def kv_info(self) -> dict | None:
        """Paged-pool snapshot for metadata()/debugging (None = flat)."""
        if not self._paged:
            return None
        info = {"block_size": self._kv_bs,
                "blocks": self._kv_alloc.n_blocks,
                "blocks_free": self._kv_alloc.free_blocks,
                "blocks_used": self._kv_alloc.used_blocks}
        if self._state.grows:
            # By kind, in the live requests' tables (a list's length is
            # one read; the worker thread owns the lists).
            for kind, _, key in self._kind_tables():
                info[f"{kind}_blocks_used"] = sum(
                    len(st.get(key, ())) for st in list(self._slots)
                    if st is not None)
        if self._host_tier is not None:
            info["host_tier"] = self._host_tier.stats_snapshot()
        return info

    @property
    def kv_spill_blocks(self):
        """Host-tier resident blocks (None = no tier) — the
        tpk_kv_spill_blocks gauge."""
        return (self._host_tier.resident_blocks
                if self._host_tier is not None else None)

    def _admit_inner_paged(self, slot: int, req: dict) -> None:
        """Paged admission: the fragment pipeline (`_prefill_chunks`
        over a contiguous fragment cache) and the slot state (`_seat`)
        are flat admission's — only where the fragment lands differs
        (scatter into this request's blocks instead of a slot row), plus
        the block-table bookkeeping:

          * zero-copy prefix hit: the stored prefix's fully-committed
            blocks map into this table by reference (refcount bump);
            only the partially-filled tail block is forked — its
            committed rows ride the fragment into a fresh block, which
            IS the copy-on-write copy (`kv_cow_copies`).
          * the whole worst-case block need is allocated here, off the
            decode critical path (see `_paged_need_tokens`).
        """
        ids = req["input_ids"]
        aid = req.get("aid", 0)
        bs = self._kv_bs
        mb = self.max_len // bs
        frag, done = None, 0
        shared: list[int] = []
        gather_tbl: tuple | None = None
        cow_fork = False
        hit = None
        if self._prefix_cap:
            hit = self._prefix_probe_paged(ids, aid, touch=True)
            if hit is None and self._host_tier is not None:
                # Host-tier restore-on-hit: a prefix spilled under pool
                # pressure comes back through the same wire format and
                # re-publishes as an HBM cache entry before this
                # admission consumes it like any zero-copy hit. The
                # request rides along so the restore can prove THIS
                # admission still fits afterwards (see the livelock
                # note in _restore_spilled).
                hit = self._restore_spilled(ids, aid, req)
            if hit is not None:
                done, hit_blocks = hit
                shared = list(hit_blocks[:done // bs])
                cow_fork = done % bs > 0
                gather_tbl = hit_blocks
        fresh, dtable = self._reserve_blocks(req, len(shared))
        if self._prefix_cap:
            with self._stats_lock:
                if hit is not None:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += done
                    if shared:
                        self.stats["prefix_zero_copy_hits"] += 1
                    if cow_fork:
                        self.stats["kv_cow_copies"] += 1
                else:
                    self.stats["prefix_misses"] += 1
        self._kv_alloc.incref(shared)
        table = shared + fresh
        # The request's tables by slot-state key: one of rows, or one a
        # kind cut from `fresh` in the state's order.
        owned = {"blocks": table}
        if self._state.grows:
            # `peak`: what admission saw free for it; `rows`: the rows
            # that bought. A chunk dispatched past them (its budget
            # already covered, the batch still going) takes nothing more.
            owned = {"peak": self._need_blocks(req),
                     "rows": self._paged_need_tokens(len(ids),
                                                     req["max_tokens"])}
            rest = table
            for key, n in zip(self._keys, self._admit_blocks(req)):
                owned[key], rest = rest[:n], rest[n:]
        boundaries: list[int] = []
        try:
            if gather_tbl is not None:
                # Resume chunked prefill mid-prompt: seed the fragment
                # from the hit's blocks (includes the partial tail —
                # read-only; its committed rows become the fork copy).
                gt = np.zeros((mb,), np.int32)
                gt[:len(gather_tbl)] = gather_tbl
                frag = self._frag_from_pool(self._cache, jnp.asarray(gt))
                if self.kv_quant != "none":
                    # The ONE full-width dequant materialization the
                    # quantized design permits (admission-side fragment
                    # rebuild, outside any scan) — counted so a fleet
                    # can see when prefix-hit traffic pays it.
                    with self._stats_lock:
                        self.stats["kv_dequant_fallbacks"] += 1
            # Boundaries are only noted here: the store is deferred
            # until the insert below has written the blocks.
            frag, tok0, lp0 = self._prefill_chunks(
                req, frag, done, lambda m, _: boundaries.append(m))
            # Scatter table: shared prefix blocks masked to NULL (their
            # rows are already resident and immutable), owned blocks
            # receive their fragment rows — including the CoW fork and
            # the pad/garbage tail that decode will overwrite in place.
            self._cache = self._insert(
                self._cache, frag, self._scatter_tables(owned, len(shared)))
            if dtable is not None:
                # The draft must hold the same prompt history (flat
                # admission's rule): chunked replay over the draft's own
                # fragment cache, scattered into this slot's draft
                # blocks. Never prefix-shared, so the whole table is a
                # fresh scatter target.
                dt = np.zeros((mb,), np.int32)
                dt[:len(dtable)] = dtable
                self._dcache = self._dinsert(self._dcache,
                                             self._draft_replay(ids),
                                             jnp.asarray(dt))
        except BaseException:
            self._kv_alloc.decref(table)
            if dtable is not None:
                self._kv_alloc.decref(dtable)
            raise
        for m in boundaries:
            self._prefix_store_paged(aid, tuple(ids[:m]),
                                     table[:blocks_for(m, bs)])
        if req.get("mode") == "ship":
            self._finish_ship(req, table, tok0, lp0, dtable)
            return
        self._seat(slot, req, tok0, lp0, draft_ok=dtable is not None,
                   dblocks=dtable, **owned)

    def _finish_ship(self, req: dict, table: list[int], tok0,
                     lp0, dtable: list[int] | None = None) -> None:
        """Serialize a ship-mode admission's committed blocks into the
        wire format and release them. Runs on the worker thread right
        after the fragment insert. The fetches here ARE device syncs:
        on a prefill-role engine there are never decode chunks in
        flight to stall, which is the design point. KNOWN COST on an
        "any"-role replica playing the prefill phase (the symmetric
        role_split fallback): each handoff's export fetch completes
        behind any in-flight decode dispatches — a per-handoff stall
        the dedicated prefill role exists to avoid. Prefer real
        prefill replicas under mixed load; the fallback trades tail
        latency for not stranding decode specialists."""
        ids = req["input_ids"]
        mb = self.max_len // self._kv_bs
        gt = np.zeros((mb,), np.int32)
        gt[:len(table)] = table
        gathered = self._export_blocks(self._cache, jnp.asarray(gt))
        arrays = {name: np.asarray(leaf)[:, :len(table)]
                  for name, leaf in gathered.items()}
        draft_meta = None
        if dtable is not None:
            # Optional draft-block section (fmt 2): the decode replica
            # speculates from position 0 without replaying the prompt
            # through its own draft. The section's config identity lets
            # a mismatched fleet refuse loudly at submit_remote instead
            # of decoding garbage.
            dgt = np.zeros((mb,), np.int32)
            dgt[:len(dtable)] = dtable
            dgathered = self._dexport_blocks(self._dcache,
                                             jnp.asarray(dgt))
            for name, leaf in dgathered.items():
                arrays["draft_" + name] = np.asarray(leaf)[:, :len(dtable)]
            self._kv_alloc.decref(dtable)
            dref = self._dcache["k"]
            draft_meta = {
                "block_size": self._kv_bs,
                "vocab_size": int(self._spec["cfg"].vocab_size),
                "n_blocks": len(dtable),
                "kv_shape": list(dref.shape[2:]),
                "num_layers": int(dref.shape[0]),
                "dtype": str(dref.dtype),
            }
        # Post-prefill RNG state: a decode engine adopting it continues
        # the exact key-split stream the unified engine would have used
        # (the disagg-vs-unified identity pin).
        arrays["rng_key"] = np.asarray(jax.random.key_data(self._key))
        first_tok = int(np.asarray(tok0)[0])
        # fmt 3: quantized blocks — the arrays dict already carries the
        # ks/vs scale planes (export is tree-generic over the pool), so
        # the wire ships quantized bytes + f32 scales, ≈2× smaller than
        # the same blocks at fmt 1. kv_quant in the meta lets the decode
        # side refuse a precision-skewed fleet loudly at submit_remote.
        # (fmt 2 never combines: kv_quant × draft is refused at init.)
        meta = {
            "fmt": (2 if draft_meta is not None
                    else 3 if self.kv_quant != "none" else 1),
            "block_size": self._kv_bs,
            "vocab_size": int(self.cfg.vocab_size),
            "tokens": list(ids),
            "committed": len(ids),
            "first_token": first_tok,
            "first_logprob": float(np.asarray(lp0)[0]),
            "max_tokens": req["max_tokens"],
            "temperature": req["temperature"],
            "top_k": req.get("top_k", 0),
            "top_p": req.get("top_p", 1.0),
            "eos_id": req.get("eos_id"),
            "adapter": req.get("adapter"),
            # The CALLER's request timeout rides the shipment so the
            # decode replica waits as long as the unified engine would
            # have — a role split must not silently shrink budgets.
            "timeout": req.get("timeout", 300.0),
            "extra": req.get("extra") or {},
        }
        if draft_meta is not None:
            meta["draft"] = draft_meta
        if self.kv_quant != "none":
            meta["kv_quant"] = self.kv_quant
        payload = pack_shipment(meta, arrays)
        res_metrics.observe("tpk_kv_shipment_bytes", len(payload),
                            buckets=_SHIPMENT_BUCKETS)
        self._kv_alloc.decref(table)
        with self._stats_lock:
            self.stats["requests"] += 1
            self.stats["prompt_tokens"] += len(ids)
            self.stats["kv_blocks_shipped"] += len(table)
            self.stats["kv_shipment_bytes"] += len(payload)
            aid = req.get("aid", 0)
            if aid:
                per = dict(self.stats.get("adapter_requests", {}))
                name = self._ml_names[aid]
                per[name] = per.get(name, 0) + 1
                self.stats["adapter_requests"] = per
        req["result"] = {"shipment": payload,
                         "num_input_tokens": len(ids),
                         "first_token": first_tok,
                         "kv_blocks": len(table)}
        req["done"].set()

    # Decode-side admission of a shipped prefill: import + bookkeeping
    # only — NO prefill chunk, no host fetch of device values (the
    # shipped first token/logprob are already host scalars), so remote
    # admission composes with pipeline_depth > 1 exactly like local
    # paged admission (allocation off the decode critical path).
    # tpk-hot: remote-admit
    def _admit_remote_paged(self, slot: int, req: dict) -> None:
        ids = req["input_ids"]
        aid = req.get("aid", 0)
        bs = self._kv_bs
        mb = self.max_len // bs
        table, dtable = self._reserve_blocks(req)
        n_blocks = req["n_blocks"]
        try:
            # Scatter the shipped blocks into the FIRST n_blocks table
            # entries; the reservation's decode-budget tail keeps its
            # stale contents (decode writes every row before any query
            # position can attend it, exactly as local admission does)
            # and the shipment's pad blocks land in the NULL block.
            st_tbl = np.zeros((mb,), np.int32)
            st_tbl[:n_blocks] = table[:n_blocks]
            dev_blocks = {name: jnp.asarray(arr)
                          for name, arr in req["kv_blocks"].items()}
            self._cache = self._import_blocks(self._cache, dev_blocks,
                                              jnp.asarray(st_tbl))
            if dtable is not None:
                dship = req.get("draft_blocks")
                if dship is not None:
                    # fmt 2: the prompt's draft KV rode the shipment —
                    # import into the first dn_blocks entries, exactly
                    # like the target import above.
                    dst = np.zeros((mb,), np.int32)
                    dn = min(req["dn_blocks"], len(dtable))
                    dst[:dn] = dtable[:dn]
                    ddev = {name: jnp.asarray(arr)
                            for name, arr in dship.items()}
                    self._dcache = self._dimport_blocks(
                        self._dcache, ddev, jnp.asarray(dst))
                else:
                    # fmt 1 from a draft-less prefill replica: rebuild
                    # the draft history locally (one replay — the cost
                    # fmt 2 shipments avoid), so this decode replica
                    # still speculates.
                    dt = np.zeros((mb,), np.int32)
                    dt[:len(dtable)] = dtable
                    self._dcache = self._dinsert(self._dcache,
                                                 self._draft_replay(ids),
                                                 jnp.asarray(dt))
        except BaseException:
            self._kv_alloc.decref(table)
            if dtable is not None:
                self._kv_alloc.decref(dtable)
            raise
        kd = req.get("rng_key")
        if kd is not None:
            # Adopt the prefill engine's post-admission key stream —
            # concurrent shipments multiplex this one key exactly as
            # concurrent local admissions always have (last admit
            # wins); per-stream identity is what the seeded test pins.
            self._key = jax.random.wrap_key_data(jnp.asarray(kd))
        st = {"req": req, "idx": len(ids), "disp": len(ids),
              "last": req["first_tok"], "pending": None,
              "draft_ok": dtable is not None, "aid": aid,
              "blocks": table, "dblocks": dtable}
        self._slots[slot] = st
        with self._stats_lock:
            self.stats["requests"] += 1
            self.stats["remote_admits"] += 1
            self.stats["kv_blocks_received"] += n_blocks
        self._emit(slot, st, [req["first_tok"]], [req["first_lp"]])

    def _restore_spilled(self, ids: list[int], aid: int,
                         req: dict) -> tuple[int, tuple] | None:
        """Restore the longest host-tier prefix covering `ids` back into
        pool blocks and re-publish it as an HBM prefix-cache entry —
        returns the (matched_len, block_ids) contract of
        `_prefix_probe_paged`, or None (no spill, no pool room, or a
        payload this engine cannot verify). The tier entry retires on
        take(); an un-restorable payload is simply dropped (the pool
        recomputes — never serves bytes it cannot validate).

        LIVELOCK GUARD: the restore must leave room for THIS
        admission's own reserve (full need minus the restored full
        blocks it maps zero-copy). Checking `can_alloc(n_blocks)` alone
        allowed a tight pool to ping-pong forever: _kv_fits sacrifices
        the hit (spill), the admission restores it (consuming the last
        headroom), its reserve then fails and stashes head-of-line, and
        the next pass spills/restores the same prefix again — so the
        restore is attempted only when restore + reserve provably fit
        together; otherwise the admission proceeds cold, which always
        terminates."""
        tier = self._host_tier
        n = tier.probe_longest(aid, ids)
        if n is None:
            return None
        n_blocks = blocks_for(n, self._kv_bs)
        total = self._need_blocks(req)
        shared_after = n // self._kv_bs  # full blocks mapped zero-copy
        if not self._kv_alloc.can_alloc(n_blocks + total - shared_after):
            return None  # leave it spilled; admission proceeds cold
        kt = tuple(ids[:n])
        taken = tier.take(aid, kt)
        if taken is None:
            return None
        _, payload = taken
        names = (("k", "v", "ks", "vs") if self.kv_quant != "none"
                 else ("k", "v"))
        try:
            meta, arrays = unpack_shipment(payload)
            # A quantized pool restores only payloads it spilled itself
            # (same kv_quant, scale planes present); anything else —
            # including a full-precision spill left over from a config
            # change — is un-verifiable here and drops to recompute.
            if (int(meta.get("block_size", 0)) != self._kv_bs
                    or list(meta.get("tokens", ())) != list(kt)
                    or str(meta.get("kv_quant", "none")) != self.kv_quant
                    or any(x not in arrays
                           or tuple(arrays[x].shape)
                           != (self._cache[x].shape[0], n_blocks,
                               *self._cache[x].shape[2:])
                           for x in names)):
                raise ShipmentError("spilled payload mismatch")
        except ShipmentError:
            return None
        blocks = self._kv_alloc.alloc(n_blocks)
        if blocks is None:
            return None
        mb = self.max_len // self._kv_bs
        st_tbl = np.zeros((mb,), np.int32)
        st_tbl[:n_blocks] = blocks
        dev = {}
        for name in names:
            lref = self._cache[name].shape
            pad = np.zeros((lref[0], mb, *lref[2:]),
                           arrays[name].dtype)
            pad[:, :n_blocks] = arrays[name]
            dev[name] = jnp.asarray(pad)
        self._cache = self._import_blocks(self._cache, dev,
                                          jnp.asarray(st_tbl))
        # Publish as a cache entry (its incref owns the blocks), then
        # drop our allocation ref — restore-on-hit leaves exactly the
        # refcounts an HBM-resident entry would have had.
        self._prefix_store_paged(aid, kt, blocks)
        self._kv_alloc.decref(blocks)
        with self._stats_lock:
            self.stats["kv_restored_blocks"] += n_blocks
        return n, tuple(blocks)

    def _spill_prefix(self, key: tuple, kt: tuple,
                      blocks: tuple) -> None:
        """Serialize one evicted prefix entry's blocks into the host
        tier (same wire format as a prefill shipment). Called just
        before the eviction decrefs — the gather must happen while the
        blocks still hold the committed rows."""
        aid, _, _ = key
        mb = self.max_len // self._kv_bs
        gt = np.zeros((mb,), np.int32)
        gt[:len(blocks)] = blocks
        gathered = self._export_blocks(self._cache, jnp.asarray(gt))
        arrays = {name: np.asarray(leaf)[:, :len(blocks)]
                  for name, leaf in gathered.items()}
        meta = {"fmt": 3 if self.kv_quant != "none" else 1,
                "block_size": self._kv_bs,
                "vocab_size": int(self.cfg.vocab_size),
                "tokens": list(kt), "committed": len(kt)}
        charge = len(blocks)
        if self.kv_quant != "none":
            meta["kv_quant"] = self.kv_quant
            # Charge the tier by actual payload weight, in full-
            # precision-block units: a quantized block is D bytes of
            # values + 4 bytes of f32 scale per row-head against
            # D·itemsize full-width — so an unchanged
            # kv_host_tier_blocks budget holds ≈2× the entries.
            d = int(self._cache["k"].shape[-1])
            fitem = jnp.dtype(self.cfg.dtype).itemsize
            charge = max(1, -(-len(blocks) * (d + 4) // (d * fitem)))
        payload = pack_shipment(meta, arrays)
        if self._host_tier.put(aid, kt, charge, payload):
            with self._stats_lock:
                self.stats["kv_spilled_blocks"] += len(blocks)

    def _admit(self, slot: int, req: dict) -> None:
        tracer = obs.get_tracer()
        now = time.perf_counter()
        t_enq = req.get("t_enq") or now
        if tracer.enabled:
            # Queue wait (submit enqueue → slot admission): the engine's
            # continuous batcher is this request's "batch gather".
            tracer.record("serve.batch_gather", t_enq, now,
                          req.get("trace", ""), slot=slot)
        with self._scope():
            with obs.span("serve.prefill", trace_id=req.get("trace", ""),
                          slot=slot,
                          prompt_tokens=len(req["input_ids"])):
                self._admit_inner(slot, req)
        # Counted once the admission held (a request stashed for want of
        # KV blocks comes through here again).
        with self._stats_lock:
            self.stats["queue_wait_seconds"] += now - t_enq
            self.stats["admitted"] += 1

    def _admit_inner(self, slot: int, req: dict) -> None:
        if req.get("mode") == "remote":
            return self._admit_remote_paged(slot, req)
        if self._paged:
            return self._admit_inner_paged(slot, req)
        ids = req["input_ids"]
        aid = req.get("aid", 0)
        frag, done = None, 0
        if self._prefix_cap:
            hit = self._prefix_lookup(ids, aid)
            if hit is not None:
                done, frag = hit
                with self._stats_lock:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += done
            else:
                with self._stats_lock:
                    self.stats["prefix_misses"] += 1
        # The final fragment is handed over by reference — nothing
        # donates it after the loop, so the full-fragment HBM copy the
        # seed paid on every admission is gone.
        frag, tok0, lp0 = self._prefill_chunks(
            req, frag, done,
            lambda m, f: self._prefix_store(aid, tuple(ids[:m]), f,
                                            copy=m < len(ids)))
        self._cache = self._insert(self._cache, frag, jnp.int32(slot))
        draft_ok = self._spec_able(req)
        if draft_ok:
            # The draft must hold the same prompt history: run the chunked
            # admission over its own cache (no sampling — the first
            # generated token reaches the draft as next decode input).
            # Greedy AND plain-temperature requests decode speculatively
            # (exact match / rejection sampling); top-k/top-p requests
            # skip this pass — they never take the spec path, so their
            # draft rows would be dead weight.
            self._dcache = self._dinsert(self._dcache,
                                         self._draft_replay(ids),
                                         jnp.int32(slot))
        self._seat(slot, req, tok0, lp0, draft_ok=draft_ok)

    def _pieces(self, ids: list[int], done: int = 0):
        """Cut `ids[done:]` into admission chunks of the largest prefill
        bucket, the last one possibly shorter: yields (offset, tokens
        [1, bucket] right-padded, piece length) — target and draft
        admission cut a prompt the same way."""
        big = self.prefill_buckets[-1]
        while done < len(ids):
            piece = ids[done:done + big]
            toks = np.zeros((1, self._bucket_for(len(piece))), np.int32)
            toks[0, :len(piece)] = piece
            yield done, jnp.asarray(toks), len(piece)
            done += len(piece)

    def _prefill_chunks(self, req: dict, frag, done: int, at_boundary):
        """Chunked prefill of `req`'s prompt from token `done` on (`frag`
        holds the rows before it: a prefix hit, else None) — the one
        loop behind flat and paged admission. Prompts longer than the
        largest bucket prefill in CHUNKS: the first is a plain prefill,
        the rest are continuation chunks attending over the whole
        fragment cache — no silent truncation (submit() already bounds
        the prompt by max_len). The engine key is split for the first
        chunk (a prefill samples even when its token is dropped) and for
        the final one, in that order: seeded streams depend on it.
        `at_boundary(done, frag)` is called after each chunk worth
        caching as a prefix. Returns (fragment, first sampled token [1],
        its logprob [1]), both still on the device."""
        ids = req["input_ids"]
        aid1 = self._aid1(req.get("aid", 0))
        sample_args = (
            jnp.asarray([req["temperature"]], jnp.float32),
            jnp.asarray([req.get("top_k", 0)], jnp.int32),
            jnp.asarray([req.get("top_p", 1.0)], jnp.float32),
        )
        big = self.prefill_buckets[-1]
        tok0 = lp0 = None
        chunks = 0
        for at, toks, n in self._pieces(ids, done):
            done = at + n
            first, final = at == 0, done >= len(ids)
            if first or final:
                self._key, sub = jax.random.split(self._key)
                length = jnp.asarray([n], jnp.int32)
            if first:
                frag, tok0, lp0 = self._prefill[toks.shape[1]](
                    self._params, toks, length, *sample_args, sub,
                    aid=aid1)
            elif final:
                frag, tok0, lp0 = self._extend(
                    self._params, frag, toks, length,
                    jnp.asarray([at], jnp.int32), *sample_args, sub,
                    aid=aid1)
            else:  # intermediate chunk: no sampling, no unembedding
                frag = self._extend_mid(
                    self._params, frag, toks,
                    jnp.asarray([at], jnp.int32), aid=aid1)
            chunks += 1
            if self._prefix_cap:
                # Skip boundaries a LATER boundary of this same admission
                # would immediately LRU-evict (cap < boundaries left: the
                # seed copied them only to pop them milliseconds later).
                chunks_left = -(-(len(ids) - done) // big)
                if chunks_left < self._prefix_cap:
                    at_boundary(done, frag)
        with self._stats_lock:
            self.stats["prefill_chunks"] += chunks
        return frag, tok0, lp0

    def _seat(self, slot: int, req: dict, tok0, lp0, *, draft_ok: bool,
              **tables) -> None:
        """Seat a locally prefilled request in `slot`: the slot state
        (`tables`: a paged request's `blocks` / `dblocks`), its first
        token, the admission counters."""
        ids = req["input_ids"]
        aid = req.get("aid", 0)
        st = {"req": req, "idx": len(ids), "disp": len(ids), "last": None,
              "pending": None, "draft_ok": draft_ok, "aid": aid, **tables}
        if self.pipeline_depth > 1:
            # Off-critical-path admission: do NOT fetch the first sampled
            # token here — that host sync would serialize the prefill
            # behind every in-flight decode chunk and stall the loop for
            # all slots. The token stays on device as the slot's decode
            # carry; its host value lands via the async copy and is
            # emitted at the next poll/fetch boundary.
            for arr in (tok0, lp0):
                getattr(arr, "copy_to_host_async", lambda: None)()
            st["pending"] = (tok0, lp0)
        else:
            st["last"] = int(tok0[0])
        self._slots[slot] = st
        with self._stats_lock:
            self.stats["requests"] += 1
            self.stats["prompt_tokens"] += len(ids)
            if aid:
                # Copy-on-write: stats_snapshot() copies stats SHALLOWLY
                # from another thread — swapping in a fresh dict keeps
                # any in-flight snapshot's inner reference immutable.
                per = dict(self.stats.get("adapter_requests", {}))
                name = self._ml_names[aid]
                per[name] = per.get(name, 0) + 1
                self.stats["adapter_requests"] = per
        if st["pending"] is None:
            self._emit(slot, st, [st["last"]], [float(lp0[0])])

    def _draft_replay(self, ids: list[int]) -> Any:
        """Chunked draft-cache build over a token sequence, shared by
        initial admission and re-admission (no sampling: _dextend_mid
        only)."""
        dfrag = self._dfrag_init()
        for at, toks, _ in self._pieces(ids):
            dfrag = self._dextend_mid(self._dparams, dfrag, toks,
                                      jnp.asarray([at], jnp.int32))
        return dfrag

    def _readmit_worthwhile(self, st: dict) -> bool:
        """Cost gate for draft re-admission: replaying the whole history
        to speculate a handful of remaining tokens (or a history vastly
        longer than the remainder) costs more than it saves. Checked
        PER SLOT (ADVICE r5 partial fix): an unworthy slot is simply not
        replayed — it rides the spec chunk with its stale draft rows —
        instead of keeping the whole batch vanilla for its lifetime."""
        req = st["req"]
        remaining = req["max_tokens"] - len(req["out"])
        history = len(req["input_ids"]) + len(req["out"]) - 1
        return remaining >= self.chunk and history <= 32 * remaining

    def _readmit_draft(self, slot: int, st: dict) -> None:
        """Rebuild a demoted slot's draft cache from its token history
        (prompt + all emitted but the pending last = positions
        0..idx-1), restoring speculative decoding after a vanilla chunk
        invalidated the draft rows — mixed traffic costs spec throughput
        only WHILE the truncated-sampling request is in flight, not for
        the rest of every concurrent request (r4 advisor finding)."""
        req = st["req"]
        ids = req["input_ids"] + req["out"][:-1]
        if self._paged:
            mb = self.max_len // self._kv_bs
            dt = np.zeros((mb,), np.int32)
            dblocks = st["dblocks"]
            dt[:len(dblocks)] = dblocks
            target = jnp.asarray(dt)
        else:
            target = jnp.int32(slot)
        self._dcache = self._dinsert(self._dcache, self._draft_replay(ids),
                                     target)
        st["draft_ok"] = True
        with self._stats_lock:
            self.stats["spec_readmissions"] += 1

    def _emit(self, slot: int, st: dict, tokens: list[int],
              logprobs: list[float] | None = None) -> None:
        """Append generated tokens to `st`'s request; retire on EOS /
        budget / context exhaustion. Streams newly appended tokens to the
        request's on_tokens callback when one is set. `st` is passed
        explicitly (not read from the slot) because in pipelined mode a
        fetched chunk may belong to a request that already retired and
        whose slot was re-admitted — the caller reconciles by identity."""
        req = st["req"]
        new: list[int] = []
        finished = req["done"].is_set()
        first = not req["out"]
        for j, t in enumerate(tokens):
            if finished:
                break
            req["out"].append(t)
            if logprobs is not None:
                req["out_logprobs"].append(logprobs[j])
            new.append(t)
            if ((req["eos_id"] is not None and t == req["eos_id"])
                    or len(req["out"]) >= req["max_tokens"]):
                finished = True
        if st["idx"] >= self.max_len - 1:
            finished = True
        # Stream BEFORE signalling completion: done.set() wakes submit()
        # in the caller's thread, and a final summary racing ahead of the
        # last token chunk would truncate the stream.
        if req["cb"] is not None and (new or finished):
            try:
                req["cb"](new, finished)
            except Exception:
                pass
        if first and new:
            # The request's first token is on its stream (or in `out`,
            # for a caller that waits for the whole reply).
            now = time.perf_counter()
            with self._stats_lock:
                self.stats["ttft_seconds"] += now - (req.get("t_enq") or now)
                self.stats["first_tokens"] += 1
        if finished:
            req["done"].set()
            if self._slots[slot] is st:
                self._slots[slot] = None
            self._free_slot_blocks(st)

    def _expire(self, req: dict) -> bool:
        """Finish `req` with DeadlineExceeded when its budget is gone.
        True means the request is done and must not (or no longer) hold a
        decode slot. No metrics here: the serving surface that returns
        the error counts each expired request exactly once."""
        if req["done"].is_set():
            return True  # already finished (e.g. EOS raced the sweep)
        dl = req.get("deadline")
        if dl is None or not dl.expired():
            return False
        req["error"] = DeadlineExceeded(
            "request deadline expired during generation")
        req["done"].set()
        return True

    def _admit_waiting(self, overlap: bool) -> None:
        """Admit waiting requests into free slots (chunk boundary).
        Each free slot keeps popping past already-expired entries
        (their callers were 504'd) and failed admissions, so a
        backlog of dead requests can't make live ones wait a chunk
        per corpse; one empty probe ends the whole scan (no
        per-slot queue.Empty churn on the idle hot loop). Queued
        admissions coalesce: every free slot fills in ONE pass, so a
        burst of arrivals costs one trip through the admission
        dispatches before the next decode chunk goes out.

        With `overlap` (decode chunks in flight), the prefill/extend/
        insert dispatches enqueue BEHIND them on the device stream and
        no host sync happens (`_admit_inner` defers the first-token
        fetch) — admission is off the critical path, counted by
        `admit_overlap`.

        Paged mode adds the free-block gate: a request whose worst-case
        block need the pool cannot cover yet is STASHED head-of-line
        (`_kv_fits` — which first reclaims LRU prefix-cache blocks) and
        the scan stops, so admission stays FIFO and a big request can't
        be starved by smaller ones slipping past it."""
        queue_empty = False
        for slot in range(self.n_slots):
            if queue_empty:
                break
            while self._slots[slot] is None:
                if self._kv_stash:
                    req = self._kv_stash.popleft()
                else:
                    try:
                        req = self._queue.get_nowait()
                    except queue.Empty:
                        queue_empty = True
                        break
                if self._expire(req):
                    continue  # never admitted; try the next waiter
                if self._paged and not self._kv_fits(req):
                    self._kv_stash.appendleft(req)
                    queue_empty = True  # FIFO: nothing jumps the line
                    break
                try:
                    self._admit(slot, req)
                except _NeedKVBlocks:
                    self._kv_stash.appendleft(req)
                    queue_empty = True
                    break
                except Exception as e:  # surface to the caller
                    req["error"] = f"{type(e).__name__}: {e}"
                    req["done"].set()
                    self._slots[slot] = None
                    continue  # slot still free; try the next waiter
                if overlap:
                    with self._stats_lock:
                        self.stats["admit_overlap"] += 1
                break

    def _emit_pending(self, slot: int, st: dict) -> None:
        """Deliver a deferred first token (deep-pipeline admission). By
        the time this runs the prefill has long completed (it precedes
        any decode chunk containing the slot in stream order) and the
        async host copy has usually landed — the fetch is a no-wait."""
        tok0, lp0 = st["pending"]
        st["pending"] = None
        first = int(np.asarray(tok0)[0])
        st["last"] = first
        self._emit(slot, st, [first], [float(np.asarray(lp0)[0])])

    def _poll_pending_first(self) -> None:
        """Emit deferred first tokens whose async host copy already
        landed — chunk-granular TTFT without waiting for the next fetch
        boundary, and an EOS / max_tokens=1 finish frees the slot before
        the next dispatch wastes a chunk on it (like the sync path)."""
        for slot, st in enumerate(self._slots):
            if st is None or st.get("pending") is None:
                continue
            if st["pending"][0].is_ready():
                self._emit_pending(slot, st)

    def _worth_speculating(self, active: list[int]) -> bool:
        """Gate for dispatching chunk k+1 before chunk k is fetched:
        never speculate past the context end (the write would clamp — or
        wrap, in rolling mode), and never when every active request's
        remaining budget is already covered by in-flight tokens (the
        chunk would be pure waste). EOS is unknowable on the host; that
        waste is the price of overlap, bounded by pipeline_depth-1
        chunks per retirement and accounted in decode_wasted_tokens."""
        if (max(self._slots[i]["disp"] for i in active) + self.chunk
                > self.max_len):
            return False
        return any(self._budget_uncovered(self._slots[i]) for i in active)

    @staticmethod
    def _budget_uncovered(st: dict) -> bool:
        """Whether tokens already emitted plus those in flight still fall
        short of the request's budget."""
        inflight = st["disp"] - st["idx"] + (1 if st["pending"] else 0)
        return len(st["req"]["out"]) + inflight < st["req"]["max_tokens"]

    def _van_riders_fit(self, van_batch: list[int]) -> bool:
        """Flat-mode guard for the vanilla sub-batch: live rows OUTSIDE
        the batch (spec rows) park their batch-wide write at their own
        disp — near the context end that write would clamp backwards
        over committed rows, so the dispatch waits the few chunks until
        those rows retire. Paged riders write the NULL block; nothing
        to check."""
        if self._paged:
            return True
        vb = set(van_batch)
        for j, stj in enumerate(self._slots):
            if (stj is not None and j not in vb
                    and stj["disp"] + self.chunk > self.max_len):
                return False
        return True

    def _spec_batch(self, active: list[int], van_covered: set,
                    spec_chain: list) -> tuple[list[int], list[int]]:
        """Plan this round's SPEC sub-batch (per-sub-batch dispatch):
        greedy + plain-temperature rows speculate; top-k/top-p rows
        decode vanilla in their own sub-batch — one truncated-sampling
        request no longer disables speculation for concurrent traffic.

        Returns (parts, fallback): `parts` rows ride a spec dispatch
        now; `fallback` rows join the vanilla sub-batch this round
        (possible only when no spec chunk is in flight — a row covered
        by an in-flight spec record has its true last token on device,
        so it can neither splice into a vanilla dispatch nor re-admit
        its draft until the chain drains back to disp == idx)."""
        if self._spec is None:
            return [], []
        rows = [i for i in active
                if self._spec_able(self._slots[i]["req"])
                and i not in van_covered]
        if not rows:
            return [], []
        chained = bool(spec_chain)
        if chained and spec_chain[-1]["doomed"]:
            return [], []  # drain the doomed chain before re-dispatching
        if chained and self._rolling:
            # Rolling cache: a doomed over-dispatch would have
            # wrap-written window rows still inside every later query's
            # attention span — unrecoverable at reconcile, so rolling
            # engines pin the spec chain to depth 1.
            return [], []
        worst = self._spec["n_spec"] * (self._spec["gamma"] + 1)
        if self._rolling and len(rows) != len(active):
            # Rolling riders would wrap-clobber live window rows the
            # same way; mixed traffic keeps the all-or-nothing gate on
            # the (flat, rolling) escape hatch.
            return [], (rows if not chained else [])
        if self._paged:
            # Per-row block tables: rows that no longer fit a worst-case
            # advance drop to the vanilla tail individually.
            fit = [i for i in rows
                   if self._slots[i]["disp"] + worst <= self.max_len]
        else:
            # Flat: one batch-wide bucket, and rider rows park their
            # writes at their own disp — the headroom gate must cover
            # every live row or a clamped write would walk backwards
            # over committed KV.
            high = max(st["disp"] for st in self._slots if st is not None)
            fit = rows if high + worst <= self.max_len else []
        tail = [i for i in rows if i not in fit]
        def usable(i: int) -> bool:
            st = self._slots[i]
            return bool(st.get("draft_ok")) or (
                st["disp"] == st["idx"] and st["pending"] is None
                and self._readmit_worthwhile(st))
        if fit and not any(usable(i) for i in fit):
            # Nobody would propose from a live (or replayable) draft
            # cache — the spec dispatch would be pure overhead over a
            # vanilla chunk.
            tail, fit = rows, []
        if not fit:
            return [], (tail if not chained else [])
        if chained:
            # Chain chunk k+1 only while some participant's budget is
            # not already covered in flight (same rule as the vanilla
            # chain) — otherwise the over-dispatch is pure waste.
            if not any(self._budget_uncovered(self._slots[i])
                       for i in fit):
                return [], []
        return fit, (tail if not chained else [])

    # tpk-hot: dispatch-rows
    def _gather_rows(self, rows: list[int]) -> tuple:
        """Snapshot the slot state one decode dispatch reads, as six
        [n_slots] host arrays (idx, temps, ks, ps, aids, last): `rows`
        are the dispatch's participants; `last` holds a row's last token
        where the host knows it (a pending first token, or a row riding
        an on-device carry, is spliced in by `_last_tokens`)."""
        last = np.zeros((self.n_slots,), np.int32)
        idx = np.zeros((self.n_slots,), np.int32)
        temps = np.zeros((self.n_slots,), np.float32)
        ks = np.zeros((self.n_slots,), np.int32)
        ps = np.ones((self.n_slots,), np.float32)
        aids = np.zeros((self.n_slots,), np.int32)
        for i in rows:
            st = self._slots[i]
            idx[i] = st["disp"]
            temps[i] = st["req"]["temperature"]
            ks[i] = st["req"].get("top_k", 0)
            ps[i] = st["req"].get("top_p", 1.0)
            aids[i] = st.get("aid", 0)
            if st["pending"] is None and st["last"] is not None:
                last[i] = st["last"]
        if not self._paged:
            partset = set(rows)
            for j, stj in enumerate(self._slots):
                if stj is None or j in partset:
                    continue
                # Rider parking: a live row excluded from this sub-batch
                # (it belongs to the other one) aims its batch-wide
                # write at its own uncommitted tail — idx 0 would
                # clobber committed prompt KV (paged riders write the
                # NULL block instead and need no parking).
                idx[j] = stj["disp"]
        return idx, temps, ks, ps, aids, last

    # tpk-hot: dispatch-last-tokens
    def _last_tokens(self, rows: list[int], last, carry: dict | None,
                     carried):
        """The dispatch's on-device last-token vector: `carried` (the
        in-flight `carry` record's last column) where there is one, else
        the host's `last`. Rows that didn't ride the carry — a slot
        admitted mid-pipe, or one re-synced after a drain — are
        overridden individually."""
        last_dev = jnp.asarray(last) if carry is None else carried
        for i in rows:
            st = self._slots[i]
            if carry is not None and carry["parts"].get(i) is st:
                continue  # row rides the on-device carry
            if st["pending"] is not None:
                # Mid-pipe admission: splice the prefill's on-device
                # first token into the carried vector (a scalar
                # update dispatch, no host round-trip).
                last_dev = last_dev.at[i].set(st["pending"][0][0])
            elif carry is not None:
                last_dev = last_dev.at[i].set(np.int32(st["last"]))
        return last_dev

    # tpk-hot: dispatch-tables
    def _block_tables(self, rows: list[int], nb: int,
                      which: str = "blocks"):
        """Per-row block tables [n_slots, nb], padded with the NULL
        block. Built from host lists — no device sync, so chained
        pipelined dispatch works exactly as flat. A state of several
        kinds of block gets one table a kind, at the state's own widths."""
        def table(nb, which):
            tables = np.zeros((self.n_slots, nb), np.int32)
            for i in rows:
                blk = self._slots[i][which]
                k = min(len(blk), nb)
                tables[i, :k] = blk[:k]
            return jnp.asarray(tables)

        if self._state.grows:
            return {kind: table(width, key)
                    for kind, width, key in self._kind_tables()}
        return table(nb, which)

    def _kind_tables(self):
        """(kind, its table's compiled width, its slot-state key) for each
        kind of block of a state that has several."""
        return zip(self._state.kinds, self._state.widths, self._keys)

    def _scatter_tables(self, owned: dict, n_shared: int = 0):
        """The table(s) an admission fragment is scattered through: the
        blocks the request owns, the `n_shared` it maps by reference
        masked to the NULL block, padded with it to the program's width."""
        def padded(width, blocks, skip=0):
            tbl = np.zeros((width,), np.int32)
            tbl[skip:len(blocks)] = blocks[skip:]
            return jnp.asarray(tbl)

        if self._state.grows:
            return {kind: padded(width, owned.get(key, ()))
                    for kind, width, key in self._kind_tables()}
        return padded(self.max_len // self._kv_bs,
                      owned.get("blocks", ()), n_shared)

    def _grow(self, active: list[int]) -> None:
        """Take the blocks the rows of the coming chunk need, kind by
        kind, up to the rows admission reserved for (`st["rows"]`: steps
        past them are dead and write through NULL-block pads, as a row
        cache's do). Admission kept the blocks free (`_kv_owed`), so the
        pool cannot run out here."""
        for i in active:
            st = self._slots[i]
            need = self._state.held(min(st["disp"] + self.chunk,
                                        st["rows"]))
            for key, n in zip(self._keys, need):
                if n > len(st[key]):
                    got = self._kv_alloc.alloc(n - len(st[key]))
                    if got is None:
                        raise RuntimeError(
                            "the paged pool ran out under a live request: "
                            "admission gave away blocks it owed")
                    st[key].extend(got)

    def _release(self, rec: dict) -> None:
        """At the fetch boundary: give back what no row of a live
        request, written or in flight, still reads: of each kind, the
        blocks past the most that any row count from the fetched one to
        the dispatched one holds (`held`)."""
        for i, st in rec["parts"].items():
            if self._slots[i] is not st:
                continue  # retired: `_free_slot_blocks` gave all back
            disp = min(st["disp"], st["rows"])
            if all(len(st[key]) <= n for key, n in zip(
                    self._keys, self._state.held(disp))):
                continue
            keep = [max(col) for col in zip(*(
                self._state.held(n) for n in range(st["idx"], disp + 1)))]
            gone = [st[key][n:] for key, n in zip(self._keys, keep)]
            if not any(gone):
                continue
            for key, n in zip(self._keys, keep):
                del st[key][n:]
            with obs.span("engine.release", round=self._round,
                          blocks=sum(map(len, gone))):
                for blocks in gone:
                    self._kv_alloc.decref(blocks)
            self._count(self._state.released(tuple(map(len, gone))))

    # tpk-hot: spec-dispatch
    def _dispatch_spec_chunk(self, parts: list[int],
                             carry: dict | None = None) -> dict:
        """Issue one speculative dispatch over the spec sub-batch
        WITHOUT fetching: draft proposes gamma tokens per step, target
        verifies (greedy rows exact-match the target argmax — token-
        identical to vanilla greedy; tempered rows rejection-sample the
        exact target marginal). `carry` chains chunk k+1 on chunk k's
        WORST-CASE carry — the last bonus token, valid iff every
        proposal was accepted; `_fetch_spec_chunk` dooms over-advanced
        records at reconcile exactly like speculatively-dead chunks,
        which is what lifts the old forced pipeline_depth=1.

        Per-slot draft re-admission rides here (gated to rows with no
        chunk in flight: the replay reads finalized token history);
        permanently-unworthy demoted rows ride with STALE draft rows —
        a pure acceptance-rate cost counted in spec_stale_rides, never
        a correctness one."""
        spec = self._spec
        worst = spec["n_spec"] * (spec["gamma"] + 1)
        worthy = []
        stale = 0
        for i in parts:
            st = self._slots[i]
            if st.get("draft_ok"):
                continue
            if (st["disp"] == st["idx"] and st["pending"] is None
                    and self._readmit_worthwhile(st)):
                worthy.append(i)
            else:
                stale += 1
        with self._scope():
            for i in worthy:
                self._readmit_draft(i, self._slots[i])
        if stale:
            with self._stats_lock:
                self.stats["spec_stale_rides"] += stale
        # Spec rows are never truncated: top_k / top_p go unused.
        idx, temps, _, _, aids, last = self._gather_rows(parts)
        assumed = {i: self._slots[i]["disp"] for i in parts}
        bucket = self._decode_bucket_for(int(max(idx)) + worst)
        self._key, sub = jax.random.split(self._key)
        t0 = time.monotonic()
        p0 = time.perf_counter()
        with self._scope():
            last_dev = self._last_tokens(
                parts, last, carry,
                None if carry is None else carry["toks"][:, -1, -1])
            tables = ()
            if self._paged:
                nb = bucket // self._kv_bs
                tables = (self._block_tables(parts, nb),
                          self._block_tables(parts, nb, "dblocks"))
            self._cache, self._dcache, toks, lps, acc = \
                self._spec_decode[bucket](
                    self._params, self._dparams, self._cache,
                    self._dcache, *tables, last_dev, jnp.asarray(idx),
                    jnp.asarray(temps), sub, aid=self._aid_batch(aids))
        for arr in (toks, lps, acc):
            getattr(arr, "copy_to_host_async", lambda: None)()
        with self._stats_lock:
            self.stats["decode_dispatches"] += 1
            self.stats["spec_dispatches"] += 1
            self.stats["decode_context_tokens"] += sum(assumed.values())
        rec_parts: dict[int, dict] = {}
        for i in parts:
            st = self._slots[i]
            st["disp"] += worst
            rec_parts[i] = st
        return {"kind": "spec", "toks": toks, "lps": lps, "acc": acc,
                "parts": rec_parts, "assumed": assumed, "worst": worst,
                "doomed": False, "t0": t0, "p0": p0}

    # tpk-hot: spec-reconcile
    def _fetch_spec_chunk(self, rec: dict, inflight,
                          overlapped: bool) -> None:
        """Fetch one spec record (the host sync point) and reconcile.
        Three row outcomes, mirroring the vanilla dead-chunk reconcile:
          * dead — the dispatch-time occupant retired; rows dropped.
          * over-advanced — an earlier record's partial acceptance
            falsified this record's all-accepted start assumption (or
            it was doomed wholesale): rows dropped, disp rolled back by
            this record's worst-case width. The garbage KV it wrote
            sits past the committed index, masked until sequential
            decode rewrites it.
          * valid — emit per the accepted counts; any acceptance short
            of worst-case dooms every LATER in-flight spec record (its
            carry token and start indices are fabrications).
        The doomed protocol is whole-record: bounded waste
        (pipeline_depth-1 records per rejection event), zero carry
        splicing."""
        # `stalled_s`: as in _fetch_chunk.
        with self._stats_lock:
            stalled = self.stats["host_stall_seconds"]
        with obs.span("engine.fetch", round=self._round, stalled_s=stalled):
            t0 = time.monotonic()
            pf0 = time.perf_counter()
            # tpk-lint: allow(host-sync) reason=the designed per-spec-chunk fetch boundary; D2H was prestaged by copy_to_host_async at dispatch
            toks = np.asarray(rec["toks"])  # [B, n_spec, gamma+1]
            # tpk-lint: allow(host-sync) reason=second half of the designed spec fetch boundary (logprobs ride the same prestaged copy)
            lps = np.asarray(rec["lps"])
            # tpk-lint: allow(host-sync) reason=accepted counts ARE the reconcile input — each row's next index is decided by them, on host, once per record
            acc = np.asarray(rec["acc"])    # [B, n_spec] accepted counts
            now = time.monotonic()
            pf1 = time.perf_counter()
        # engine.fetch is the host sync and nothing else (the interval
        # host_stall_seconds sums); engine.emit is the rest of the pass:
        # reconcile, first tokens, the callers' streams.
        with obs.span("engine.emit", round=self._round):
            tracer = obs.get_tracer()
            if tracer.enabled:
                for i, st in rec["parts"].items():
                    trace = st["req"].get("trace", "")
                    tracer.record("serve.decode_chunk", rec["p0"], pf0,
                                  trace, slot=i, spec=True,
                                  overlapped=overlapped)
                    tracer.record("serve.fetch", pf0, pf1, trace, slot=i)
            start = (rec["t0"] if self._busy_mark is None
                     else max(self._busy_mark, rec["t0"]))
            with self._stats_lock:
                self.stats["host_stall_seconds"] += now - t0
                self.stats["decode_fetch_overlapped" if overlapped
                            else "decode_fetch_blocking"] += 1
                self.stats["decode_seconds"] += now - start
            self._busy_mark = now
            worst = rec["worst"]
            spec = self._spec

            def doom_later() -> None:
                for r in inflight:
                    if r.get("kind") == "spec":
                        r["doomed"] = True

            for i, st in rec["parts"].items():
                if self._slots[i] is not st:
                    with self._stats_lock:
                        self.stats["decode_dead_slot_chunks"] += 1
                        self.stats["decode_wasted_tokens"] += worst
                    continue
                if st["pending"] is not None:
                    # First token of a mid-pipe admission: emit it before
                    # the spec tokens (the record decoded FROM it).
                    self._emit_pending(i, st)
                    if self._slots[i] is not st:  # EOS/budget at token 1
                        with self._stats_lock:
                            self.stats["decode_dead_slot_chunks"] += 1
                            self.stats["decode_wasted_tokens"] += worst
                        continue
                if rec["doomed"] or st["idx"] != rec["assumed"][i]:
                    # Over-advanced: decoded from a start index that partial
                    # acceptance upstream made fictional. Settle this
                    # record's disp contribution and drop the rows.
                    st["disp"] -= worst
                    with self._stats_lock:
                        self.stats["decode_wasted_tokens"] += worst
                    continue
                emit_t: list[int] = []
                emit_l: list[float] = []
                accepted = 0
                for s in range(spec["n_spec"]):
                    kk = int(acc[i, s])
                    emit_t += [int(t) for t in toks[i, s, :kk + 1]]
                    emit_l += [float(v) for v in lps[i, s, :kk + 1]]
                    accepted += kk
                st["idx"] += len(emit_t)
                st["disp"] -= worst - len(emit_t)
                st["last"] = emit_t[-1]
                if len(emit_t) < worst:
                    # Partial acceptance: every later in-flight spec record
                    # chained on the all-accepted assumption — doom them
                    # wholesale (they reconcile as drops above).
                    doom_later()
                with self._stats_lock:
                    self.stats["spec_proposed"] += (spec["gamma"]
                                                    * spec["n_spec"])
                    self.stats["spec_accepted"] += accepted
                    self.stats["decode_tokens"] += len(emit_t)
                self._emit(i, st, emit_t, emit_l)

    # tpk-hot: engine-dispatch
    def _dispatch_chunk(self, active: list[int],
                        carry: dict | None = None) -> dict:
        """Issue one chunked decode dispatch over the slot batch WITHOUT
        fetching its result. `carry` is the previous (still in-flight)
        dispatch record: its on-device last-token column chains straight
        into this dispatch, so back-to-back chunks execute with no host
        round-trip between them. Rows that didn't ride the carry — a
        slot admitted mid-pipe (its prefill's sampled token is spliced in
        as an on-device scalar) or one re-synced after a drain — are
        overridden individually. Truncation costs a full-vocab sort per
        step; only pay it when some active request actually asked for
        top-k/top-p. The cache-length bucket is the smallest covering
        every active sequence after this chunk — short conversations
        never pay max_len-wide attention."""
        idx, temps, ks, ps, aids, last = self._gather_rows(active)
        trunc = any(ks[i] > 0 or ps[i] < 1.0 for i in active)
        bucket = self._decode_bucket_for(int(max(idx)) + self.chunk)
        self._key, sub = jax.random.split(self._key)
        t0 = time.monotonic()
        p0 = time.perf_counter()  # span clock for the decode-chunk span
        with self._scope():
            last_dev = self._last_tokens(
                active, last, carry,
                None if carry is None else carry["toks"][:, -1])
            tables = ()
            if self._paged:
                if self._state.grows:
                    self._grow(active)
                tables = (self._block_tables(active,
                                             bucket // self._kv_bs),)
            self._cache, toks, lps, counts = self._decode[(bucket, trunc)](
                self._params, self._cache, *tables, last_dev,
                jnp.asarray(idx), jnp.asarray(temps), jnp.asarray(ks),
                jnp.asarray(ps), sub, aid=self._aid_batch(aids))
        # Start the D2H transfer now; the fetch a pipeline-depth later
        # should find the bytes already on host.
        for arr in (toks, lps, *counts.values()):
            getattr(arr, "copy_to_host_async", lambda: None)()
        with self._stats_lock:
            self.stats["decode_dispatches"] += 1
            self.stats["decode_context_tokens"] += sum(
                self._slots[i]["disp"] for i in active)
            if self._state.grows:
                for name, n in self._state.read(
                        [self._slots[i]["disp"] for i in active]).items():
                    self.stats[name] += n
        parts: dict[int, dict] = {}
        for i in active:
            st = self._slots[i]
            st["disp"] += self.chunk
            parts[i] = st
        return {"kind": "van", "toks": toks, "lps": lps, "counts": counts,
                "parts": parts, "t0": t0, "p0": p0, "chunk": self.chunk}

    # tpk-hot: engine-fetch
    def _fetch_chunk(self, rec: dict, overlapped: bool) -> None:
        """Fetch one dispatch record's tokens (the host sync point) and
        reconcile: a slot whose dispatch-time occupant already retired
        (EOS / budget / deadline at an earlier boundary) gets its rows
        dropped — the chunk was speculatively dead for it. `overlapped`
        records whether another chunk was still in flight during this
        fetch (the steady-state pipelining invariant the CPU dispatch-
        count guard test pins)."""
        # `stalled_s` is host_stall_seconds as it stands before this fetch:
        # between two engine.fetch spans of one trace the counter's change
        # and the spans' seconds cover the same passes
        # (benchmarks/xplane_host.py, `fetch_check`).
        with self._stats_lock:
            stalled = self.stats["host_stall_seconds"]
        with obs.span("engine.fetch", round=self._round, stalled_s=stalled):
            t0 = time.monotonic()
            pf0 = time.perf_counter()
            # THE one designed host sync of the decode pipeline: everything
            # below is host numpy. (The runtime fetch-count guard test pins
            # exactly one fetch pair per chunk.)
            # tpk-lint: allow(host-sync) reason=the designed per-chunk fetch boundary; D2H was prestaged by copy_to_host_async at dispatch
            toks = np.asarray(rec["toks"])  # host sync point: [B, chunk]
            # tpk-lint: allow(host-sync) reason=second half of the designed per-chunk fetch boundary (logprobs ride the same prestaged copy)
            lps = np.asarray(rec["lps"])
            # What the model counted on the device at this dispatch's first
            # step (a few scalars of the same program, prestaged with the
            # tokens: no sync of their own; none for most models).
            # tpk-lint: allow(host-sync) reason=scalars of the dispatch already fetched above, prestaged by copy_to_host_async
            self._count({name: int(np.asarray(n))
                         for name, n in rec["counts"].items()})
            now = time.monotonic()
            pf1 = time.perf_counter()
        with obs.span("engine.emit", round=self._round):
            tracer = obs.get_tracer()
            if tracer.enabled:
                # Chunk-granular spans (never per-token — the hot loop adds
                # no syncs, and the ring stays bounded): one decode-chunk
                # span per rider covering dispatch→fetch-start, one fetch
                # span per rider covering the host sync itself.
                for i, st in rec["parts"].items():
                    trace = st["req"].get("trace", "")
                    tracer.record("serve.decode_chunk", rec["p0"], pf0, trace,
                                  slot=i, chunk=rec["chunk"],
                                  overlapped=overlapped)
                    tracer.record("serve.fetch", pf0, pf1, trace, slot=i)
            # decode_seconds sums ENGINE-BUSY wall time (non-overlapping
            # intervals), so throughput() stays honest when chunks overlap.
            start = (rec["t0"] if self._busy_mark is None
                     else max(self._busy_mark, rec["t0"]))
            with self._stats_lock:
                self.stats["host_stall_seconds"] += now - t0
                self.stats["decode_fetch_overlapped" if overlapped
                            else "decode_fetch_blocking"] += 1
                self.stats["decode_seconds"] += now - start
            self._busy_mark = now
            for i, st in rec["parts"].items():
                if self._slots[i] is not st:
                    with self._stats_lock:
                        self.stats["decode_dead_slot_chunks"] += 1
                        self.stats["decode_wasted_tokens"] += rec["chunk"]
                    continue
                if st["pending"] is not None:
                    # First token of a mid-pipe admission: emit it before
                    # the chunk tokens (the chunk was decoded FROM it).
                    self._emit_pending(i, st)
                    if self._slots[i] is not st:  # EOS/budget at token 1
                        with self._stats_lock:
                            self.stats["decode_dead_slot_chunks"] += 1
                            self.stats["decode_wasted_tokens"] += rec["chunk"]
                        continue
                st["idx"] += rec["chunk"]
                st["last"] = int(toks[i, -1])
                # This vanilla chunk left the slot's DRAFT cache rows
                # unwritten — spec decoding must not trust them until
                # re-admission replays the slot's history
                # (_readmit_draft, once the batch is all-spec-able
                # again). spec_demotions / spec_readmissions count both
                # sides (perf effects, never correctness).
                with self._stats_lock:
                    if st.get("draft_ok"):
                        self.stats["spec_demotions"] += 1
                    self.stats["decode_tokens"] += rec["chunk"]
                st["draft_ok"] = False
                self._emit(i, st, [int(t) for t in toks[i]],
                           [float(v) for v in lps[i]])
            if self._state.grows:
                self._release(rec)

    # tpk-hot: engine-loop
    def _loop(self) -> None:
        """The scheduler: admit → sweep deadlines → keep up to
        `pipeline_depth` decode chunks in flight → fetch the oldest.
        At depth 1 each iteration dispatches then immediately fetches —
        the synchronous engine, bit-for-bit (same RNG splits, same sync
        points). At depth >= 2 the fetch of chunk k overlaps the device
        executing chunk k+1 (and any admission dispatches), hiding the
        host round trip that otherwise bounds 1-slot decode at one chunk
        per round trip regardless of chip speed.

        Each round splits the batch into TWO sub-batches dispatched
        independently (per-sub-batch dispatch): the SPEC sub-batch
        (greedy + plain-temperature rows, when a draft model is
        configured) and the VANILLA sub-batch (top-k/top-p rows, plus
        spec rows falling back near the context end). Each kind keeps
        its own chain of up to `pipeline_depth` records in flight;
        fetches drain oldest-first across both. Pure-vanilla traffic
        reduces bit-for-bit to the single-chain loop above; pure-spec
        traffic at depth 1 reproduces the classic synchronous spec
        engine."""
        inflight: deque = deque()
        while not self._stop:
            # Each pass's phases are `engine.*` spans on this thread, one
            # per phase per pass (`round` = the pass), so that a profiler
            # trace shows which phase the host was in while the device
            # ran or idled. Spans are host clock reads: none touches a
            # device value.
            self._round = rnd = self._round + 1
            with obs.span("engine.admit", round=rnd):
                self._admit_waiting(overlap=bool(inflight))
            with obs.span("engine.sweep", round=rnd):
                # Chunk-boundary deadline sweep: an expired request frees
                # its slot NOW instead of decoding tokens its caller
                # (already 504'd) will never read — expiry costs the
                # batch at most pipeline_depth chunks of waste.
                for i, st in enumerate(self._slots):
                    if st is not None and self._expire(st["req"]):
                        self._slots[i] = None
                        self._free_slot_blocks(st)
                self._poll_pending_first()
            active = [i for i, s in enumerate(self._slots)
                      if s is not None]
            if not active and not inflight:
                self._busy_mark = None
                with obs.span("engine.wait", round=rnd):
                    # One span (and one pass) per idle period, not one
                    # per 50 ms poll: an idle server must not turn the
                    # span ring over. Every submit sets `_wake`; the
                    # poll only bounds a missed wake-up, and a stashed
                    # request's deadline is checked by the next pass.
                    while not (self._wake.wait(0.05) or self._stop
                               or self._kv_stash
                               or not self._queue.empty()):
                        pass
                    self._wake.clear()
                continue
            while active:
                dispatched = False
                spec_chain = [r for r in inflight if r["kind"] == "spec"]
                van_chain = [r for r in inflight if r["kind"] == "van"]
                van_covered = {i for r in van_chain
                               for i, st in r["parts"].items()
                               if self._slots[i] is st}
                parts, fallback = self._spec_batch(active, van_covered,
                                                   spec_chain)
                if parts and len(spec_chain) < self.pipeline_depth:
                    with obs.span("engine.dispatch", round=rnd, spec=True):
                        inflight.append(self._dispatch_spec_chunk(
                            parts,
                            carry=spec_chain[-1] if spec_chain else None))
                    self.inflight_depth = len(inflight)
                    dispatched = True
                spec_rows = {i for i in active
                             if self._spec is not None
                             and self._spec_able(self._slots[i]["req"])}
                fb = set(fallback)
                van_batch = [i for i in active
                             if i not in spec_rows or i in fb]
                if (van_batch and len(van_chain) < self.pipeline_depth
                        and (not van_chain
                             or self._worth_speculating(van_batch))
                        and self._van_riders_fit(van_batch)):
                    with obs.span("engine.dispatch", round=rnd):
                        inflight.append(self._dispatch_chunk(
                            van_batch,
                            carry=van_chain[-1] if van_chain else None))
                    self.inflight_depth = len(inflight)
                    dispatched = True
                if not dispatched:
                    break
            if inflight:
                rec = inflight.popleft()
                self.inflight_depth = len(inflight)
                if rec["kind"] == "spec":
                    self._fetch_spec_chunk(rec, inflight,
                                           overlapped=bool(inflight))
                else:
                    self._fetch_chunk(rec, overlapped=bool(inflight))

    def stats_snapshot(self) -> dict:
        """Tear-free copy of the engine counters for metrics/metadata
        readers on other threads. Shallow by design: inner values are
        swapped whole (copy-on-write), never mutated in place."""
        with self._stats_lock:
            out = dict(self.stats)
        # The process's backend compiles, live: one inside a measured
        # window would otherwise pass for a slow step or a TTFT outlier.
        clock = devices.compile_clock()
        out["compiles"] = clock.compiles
        out["compile_seconds"] = clock.seconds
        return out

    def throughput(self) -> float:
        s = self.stats_snapshot()
        return s["decode_tokens"] / s["decode_seconds"] if s["decode_seconds"] else 0.0


class GenerativeJAXModel(Model):
    """KServe-Model-shaped wrapper: load() builds the engine (AOT compiles
    prefill buckets + decode); generate() is the request surface. Also
    answers plain predict() with a full-forward logits call for protocol
    parity (v1/v2 infer on a generative model)."""

    def __init__(self, name: str, model, params, cfg, *,
                 generation: dict | None = None,
                 donate_params: bool = False):
        super().__init__(name)
        self._model, self._params, self.cfg = model, params, cfg
        # A runtime that made `params` for this wrapper alone says so, and
        # load() hands the tree over for good (GenerationEngine's
        # `donate_params`); a tree that others still read is left alive.
        self._donate_params = bool(donate_params)
        self._gen_cfg = dict(generation or {})
        self.engine: GenerationEngine | None = None
        self.eos_id = self._gen_cfg.pop("eos_id", None)
        self.tokenizer = self._gen_cfg.pop("tokenizer", None)
        # {"tensor": N, ...} from the bundle / ISVC spec — resolved to a
        # device mesh at load() time, when the devices exist.
        self._mesh_spec = dict(self._gen_cfg.pop("mesh", None) or {})
        # Speculative decoding spec: {"checkpoint": <HF dir>, "gamma": N,
        # "model_overrides": {...}} — the draft checkpoint is resolved at
        # load() time (same import path as the target).
        self._draft_spec = dict(self._gen_cfg.pop("draft", None) or {})

    def _build_mesh(self):
        import math

        from kubeflow_tpu.parallel.mesh import (MESH_AXES, MeshConfig,
                                                build_mesh)

        unknown = set(self._mesh_spec) - set(MESH_AXES)
        if unknown:
            raise ValueError(
                f"mesh spec has unknown axes {sorted(unknown)}; "
                f"valid: {list(MESH_AXES)}")
        sizes = {k: int(v) for k, v in self._mesh_spec.items()}
        if any(v < 1 for v in sizes.values()):
            raise ValueError(f"mesh axis sizes must be >= 1: {sizes}")
        need = math.prod(sizes.values())
        devs = jax.devices()
        if len(devs) < need:
            raise ValueError(
                f"mesh {sizes} needs {need} devices, have {len(devs)}")
        sizes.setdefault("data", 1)
        return build_mesh(MeshConfig(**sizes), devs[:need])

    def load(self) -> bool:
        t0 = time.monotonic()
        kwargs = dict(self._gen_cfg)
        if self._mesh_spec:
            kwargs["mesh"] = self._build_mesh()
        if self._draft_spec:
            spec = dict(self._draft_spec)
            ckpt = spec.pop("checkpoint", None)
            overrides = spec.pop("model_overrides", None) or {}
            gamma = spec.pop("gamma", None)
            if spec:
                # Validate BEFORE the (potentially GB-scale) checkpoint
                # import — a typo'd key must fail in milliseconds.
                raise ValueError(
                    f"unknown generative.draft keys {sorted(spec)}")
            if not ckpt:
                raise ValueError(
                    "generative.draft needs a 'checkpoint' (HF dir of "
                    "the draft model)")
            from kubeflow_tpu.models.hf_import import build_from_hf

            dmodule, dcfg, dparams = build_from_hf(ckpt, **overrides)
            draft = {"model": dmodule, "params": dparams, "cfg": dcfg}
            if gamma is not None:
                draft["gamma"] = int(gamma)
            kwargs["draft"] = draft
        # `predict()` reads the engine's tree from here on: the forward
        # rounds the same leaves, so its logits are the same, and this
        # wrapper keeps no second tree alive.
        self.engine = GenerationEngine(
            self._model, self._params, self.cfg,
            donate_params=self._donate_params, **kwargs)
        self._params = self.engine._params
        self.load_time_s = time.monotonic() - t0
        self.ready = True
        return True

    def unload(self) -> None:
        self.ready = False
        if self.engine:
            self.engine.close()
            self.engine = None

    def _resolve_ids(self, payload: dict) -> list[int]:
        from kubeflow_tpu.serve.tokenizer_util import resolve_ids

        return resolve_ids(self.tokenizer, payload)

    def _decode_text(self, ids: list[int]) -> str:
        from kubeflow_tpu.serve.tokenizer_util import decode_ids

        return decode_ids(self.tokenizer, ids)

    def _submit_kwargs(self, payload: dict) -> dict:
        return dict(
            max_tokens=int(payload.get("max_tokens", 32)),
            temperature=float(payload.get("temperature", 0.0)),
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 1.0)),
            eos_id=payload.get("eos_id", self.eos_id),
            adapter=payload.get("adapter"),
            timeout=float(payload.get("timeout", 300.0)),
            # In-process deadline/trace propagation: the server stashes
            # the request's Deadline under "_deadline" and its
            # X-Request-Id under "_trace" (never wire fields).
            deadline=payload.get("_deadline"),
            trace_id=payload.get("_trace", ""))

    def generate(self, payload: dict) -> dict:
        if not self.ready or self.engine is None:
            raise RuntimeError(f"model {self.name} is not loaded")
        ids = self._resolve_ids(payload)
        out = self.engine.submit(ids, **self._submit_kwargs(payload))
        if self.tokenizer is not None:
            out["text"] = self._decode_text(out["output_ids"])
        out["decode_tokens_per_sec"] = round(self.engine.throughput(), 2)
        return out

    def generate_stream(self, payload: dict):
        """Generator of streaming events: {"tokens": [...]} (plus
        "text_delta" when a tokenizer is bundled) per emitted chunk, then
        a final {"done": true, ...summary} — the huggingfaceserver
        streaming surface, chunk-granular (the engine's scheduling
        quantum)."""
        if not self.ready or self.engine is None:
            raise RuntimeError(f"model {self.name} is not loaded")
        ids = self._resolve_ids(payload)
        kwargs = self._submit_kwargs(payload)
        events: queue.Queue = queue.Queue()

        def on_tokens(tokens, done):
            events.put(("tok", tokens, done))

        def run():
            try:
                events.put(("final", self.engine.submit(
                    ids, on_tokens=on_tokens, **kwargs), None))
            except Exception as e:  # surfaced to the consumer
                events.put(("error", e, None))

        threading.Thread(target=run, daemon=True,
                         name="tpk-generate-stream").start()
        emitted: list[int] = []
        # Windowed incremental detokenization (the vLLM recipe): decode
        # only from a trailing offset, emit the suffix beyond the
        # previously rendered window, and hold back while the tail is an
        # incomplete codepoint — O(window) per chunk instead of
        # re-decoding the whole prefix (quadratic in output length), and
        # deltas telescope to the exact full decode.
        prefix_off = read_off = 0
        sent_text = ""
        deadline = time.monotonic() + kwargs["timeout"] + 10.0
        while True:
            try:
                kind, val, done = events.get(
                    timeout=max(deadline - time.monotonic(), 1.0))
            except queue.Empty:
                raise RuntimeError(
                    f"generation stream timed out after "
                    f"{kwargs['timeout']}s") from None
            if kind == "error":
                raise val
            if kind == "final":
                out = dict(val)
                if self.tokenizer is not None:
                    out["text"] = self._decode_text(out["output_ids"])
                    # Flush anything still held back by the window.
                    out["text_delta"] = (
                        out["text"][len(sent_text):]
                        if out["text"].startswith(sent_text) else "")
                out["decode_tokens_per_sec"] = round(
                    self.engine.throughput(), 2)
                yield {"done": True, **out}
                return
            if not val:
                continue
            emitted.extend(val)
            ev: dict = {"tokens": [int(t) for t in val]}
            if self.tokenizer is not None:
                prev = self._decode_text(emitted[prefix_off:read_off])
                text = self._decode_text(emitted[prefix_off:])
                # Emit ONLY when the new rendering strictly extends the
                # previous one and its tail is not a possibly-incomplete
                # codepoint (U+FFFD). Anything else — a held partial, or
                # a rewrite where a completing codepoint replaces an
                # earlier U+FFFD — stays buffered: an emitted delta can
                # never be retracted, and the final event's residue flush
                # delivers whatever was held, so deltas always join to
                # the exact full decode.
                if (len(text) > len(prev) and text.startswith(prev)
                        and not text.endswith("�")):
                    ev["text_delta"] = text[len(prev):]
                    prefix_off, read_off = read_off, len(emitted)
                else:
                    ev["text_delta"] = ""
                sent_text += ev["text_delta"]
            yield ev

    def prefill_ship(self, payload: dict) -> dict:
        """POST :prefill backend — chunk-prefill and return the KV
        shipment (disaggregation phase 1). The caller's stream flag and
        requested surface ride the shipment's `extra` so the decode
        replica can answer in the right shape."""
        if not self.ready or self.engine is None:
            raise RuntimeError(f"model {self.name} is not loaded")
        ids = self._resolve_ids(payload)
        kwargs = self._submit_kwargs(payload)
        kwargs.pop("timeout", None)
        deadline = kwargs.pop("deadline", None)
        trace = kwargs.pop("trace_id", "")
        return self.engine.prefill_ship(
            ids, deadline=deadline, trace_id=trace,
            timeout=float(payload.get("timeout", 300.0)),
            extra={"stream": bool(payload.get("stream"))}, **kwargs)

    def decode_remote(self, shipment, *, deadline=None,
                      trace_id: str = "") -> dict:
        """POST :decode backend (non-stream): admit a shipment straight
        into decode and block for the full result."""
        if not self.ready or self.engine is None:
            raise RuntimeError(f"model {self.name} is not loaded")
        out = self.engine.submit_remote(shipment, deadline=deadline,
                                        trace_id=trace_id)
        if self.tokenizer is not None:
            out["text"] = self._decode_text(out["output_ids"])
        out["decode_tokens_per_sec"] = round(self.engine.throughput(), 2)
        return out

    def decode_remote_stream(self, shipment, *, deadline=None,
                             trace_id: str = ""):
        """Streaming :decode backend: the generate_stream event shape
        (chunk token events, final done summary) over a remote
        admission.

        RESUME CURSOR (ISSUE 14): `resume_skip` in the shipment meta is
        the number of leading tokens the original caller was already
        served before its previous decode replica died mid-stream. The
        engine replays the SAME deterministic token stream (the shipment
        carries the post-prefill RNG key and sampling params), and this
        layer suppresses the first `resume_skip` tokens from the CHUNK
        events — the resumed stream continues exactly where the dead one
        stopped. The final done event still carries the FULL output_ids/
        logprobs, identical to an uninterrupted run's."""
        if not self.ready or self.engine is None:
            raise RuntimeError(f"model {self.name} is not loaded")
        from kubeflow_tpu.serve.kv_transfer import peek_meta

        meta = peek_meta(shipment)
        skip = int(meta.get("resume_skip", 0))
        if skip < 0 or skip > int(meta.get("max_tokens", 32)):
            raise ValueError(
                f"resume_skip {skip} outside [0, max_tokens="
                f"{meta.get('max_tokens')}]")
        # Bound the event wait by the SHIPPED request budget (+ grace),
        # mirroring generate_stream's clock — never a magic constant
        # coupled to submit_remote's default.
        timeout_s = float(meta.get("timeout", 300.0))
        events: queue.Queue = queue.Queue()

        def on_tokens(tokens, done):
            events.put(("tok", tokens, done))

        def run():
            try:
                events.put(("final", self.engine.submit_remote(
                    shipment, deadline=deadline, trace_id=trace_id,
                    on_tokens=on_tokens), None))
            except Exception as e:
                events.put(("error", e, None))

        threading.Thread(target=run, daemon=True,
                         name="tpk-decode-remote-stream").start()
        stream_deadline = time.monotonic() + timeout_s + 10.0
        while True:
            try:
                kind, val, _done = events.get(
                    timeout=max(stream_deadline - time.monotonic(), 1.0))
            except queue.Empty:
                raise RuntimeError(
                    f"remote decode stream timed out after "
                    f"{timeout_s}s") from None
            if kind == "error":
                raise val
            if kind == "final":
                out = dict(val)
                if self.tokenizer is not None:
                    out["text"] = self._decode_text(out["output_ids"])
                out["decode_tokens_per_sec"] = round(
                    self.engine.throughput(), 2)
                yield {"done": True, **out}
                return
            if skip:
                # Replayed tokens the caller already holds: drop them
                # from the chunk stream (the done summary stays full).
                dropped = min(skip, len(val))
                skip -= dropped
                val = val[dropped:]
            if val:
                yield {"tokens": [int(t) for t in val]}

    def predict(self, inputs):
        """Full-forward logits (no cache) — v1/v2 infer parity."""
        toks = jnp.asarray(np.asarray(inputs[0], np.int32))
        logits = self._model.apply({"params": self._params}, toks)
        return [np.asarray(logits, np.float32)]

    def metadata(self) -> dict:
        md = super().metadata()
        md.update({
            "generative": True,
            "max_len": self._gen_cfg.get("max_len", 256),
            "vocab_size": getattr(self.cfg, "vocab_size", None),
            "stats": self.engine.stats_snapshot() if self.engine else {},
            "mesh": self._mesh_spec or None,
        })
        if self.engine:
            md["decode_buckets"] = list(self.engine.decode_buckets)
            md["pipeline_depth"] = self.engine.pipeline_depth
            md["speculative"] = self.engine._spec is not None
            md["paged_kv"] = self.engine.kv_info()
            md["role"] = self.engine.role
            if self.engine.adapter_names():
                md["adapters"] = self.engine.adapter_names()
        return md
