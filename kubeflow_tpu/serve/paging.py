"""Block-paged KV memory management for the generation engine.

The flat engine (ISSUE 3 lineage) holds one slot-contiguous cache
`[L, B_slots, max_len, KH, D]`: every request owns a `max_len`-sized row
for its whole lifetime regardless of actual length. Slots are the proven
throughput lever (1→4 slots scaled decode 78→296 tok/s, SERVEBENCH.json)
but each slot charges worst-case HBM, so mixed-length traffic caps out
long before the arithmetic does.

This module is the host half of the PagedAttention-style answer (the
vLLM design the serve module header cites): the KV tensor becomes a pool
of fixed-size blocks `[L, n_blocks, block_size, KH, D]`, each request
owns a *block table* (a host-side list of block ids), and the jitted
step gathers the table into a contiguous view, runs the flat engine's
decode scan on it and scatters it back (serve/generation.py
`gather_view` / `scatter_view` around `decode_scan`). Everything here is
plain-Python bookkeeping mutated only by the engine worker thread —
block allocation sits at admit/retire, off the decode critical path, so
pipelined dispatch (`pipeline_depth > 1`) needs no new host syncs.

Sharing model (copy-on-write prefix reuse):

  * block id 0 is the reserved NULL block — the pad target for table
    entries past a request's allocation. It is written with garbage by
    padded scatters and never read as meaningful data (absolute-position
    masking hides every row past a request's write index).
  * a block referenced by more than one table (or by the prefix cache)
    is IMMUTABLE in value: only fully-committed, block-aligned prefix
    blocks are ever shared. A prefix-cache hit maps those ids into the
    new request's table with a refcount bump — zero-copy.
  * the partially-filled tail block of a stored prefix is never shared
    into a new table: the hit forks it (fresh block, committed rows
    copied via the admission fragment) because the new request will
    append into that block — the one copy CoW pays.

Quantized pools (`kv_quant=int8|fp8`, ISSUE 19) change NOTHING here:
the pool grows parallel per-row-per-head scale planes (`ks`/`vs`,
`[L, n_blocks, block_size, KH]`) addressed by the SAME block ids, so
one table entry names a value block and its scale block together.
Allocation, refcounts, CoW forks, and the NULL block are identical —
a shared quantized prefix shares its scales by construction, and a
tail fork copies them through the same admission-fragment scatter.
Blocks stay opaque above the engine; this module never sees a dtype.

What a block holds is the model's to say (`serving_state`): a row of K and
V per token (models/llama.py `RowState`: one kind of block, a request's
whole worst case taken at admission, as everything above describes), or
two kinds in one pool, taken as the request's rows are dispatched and given
back while it still decodes (models/evabyte.py `EvaState`). The allocator
is the same for both: a table entry names a block of either kind.
"""

from __future__ import annotations


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold `tokens` cache rows (ceil division)."""
    if tokens <= 0:
        return 0
    return -(-int(tokens) // int(block_size))


def serving_state(cfg, block_size: int, max_len: int):
    """What the model keeps of a request between steps, as the engine asks
    it: the configuration's own `serving_state`, else the row-per-token
    cache of models/llama.py (whose contract GPT-2 and the expert models
    share). It answers `kinds`, `held(n)` (blocks of each kind that n
    written rows hold), `peak(n)` (the most a request holds on its way to n
    rows), names its `counters` and makes the device arrays (`pool`,
    `fragment`). A state that `grows` (blocks taken as rows are dispatched
    and given back mid-request) also gives its tables' compiled `widths`,
    scatters its own fragment (`insert`), refuses what it cannot take
    (`check`) and counts (`read` at a dispatch, `released` at a fetch)."""
    make = getattr(cfg, "serving_state", None)
    if make is not None:
        return make(block_size, max_len)
    from kubeflow_tpu.models.llama import RowState

    return RowState(cfg, block_size, max_len)


def require_rows(state, what: str) -> None:
    """Refuse, when an engine is made, to serve `state` with a part of the
    engine that is written against rows of per-head K and V
    (models/llama.py `RowState`): `what` names the part and says why."""
    from kubeflow_tpu.models.llama import RowState

    if not isinstance(state, RowState):
        raise ValueError(
            f"{type(state).__name__} keeps {' and '.join(state.kinds)} "
            f"blocks; the engine cannot serve that with {what}")


class BlockAllocator:
    """Fixed-pool block allocator with refcounted sharing.

    Ids are indices into the device pool's block axis; id 0 is reserved
    (NULL). `alloc` is all-or-nothing — a request either gets its whole
    allocation or nothing, so admission can never strand a half-admitted
    request holding blocks it cannot use. Free ids are handed out in
    LIFO order: recently freed blocks are re-written first, keeping the
    pool's cold tail untouched (and making use-after-free bugs loud in
    tests, since stale readers see fresh writes immediately)."""

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        if block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {block_size}")
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        #: usable ids are 1..n_blocks (0 is NULL); the device pool is
        #: therefore n_blocks + 1 blocks long.
        self._free: list[int] = list(range(self.n_blocks, 0, -1))
        self._ref: dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Take `n` blocks (refcount 1 each), or None if the pool can't
        cover the whole request right now (caller queues/sheds)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._ref[b] = 1
        return ids

    def incref(self, ids) -> None:
        """Add one reference to each id (prefix-cache store / zero-copy
        hit). Double-incref of the same id in one call is legal — each
        occurrence counts."""
        for b in ids:
            if b not in self._ref:
                raise ValueError(f"incref of unallocated block {b}")
            self._ref[b] += 1

    def decref(self, ids) -> int:
        """Drop one reference per id; blocks reaching zero return to the
        free list. Returns how many blocks were actually freed."""
        freed = 0
        for b in ids:
            c = self._ref.get(b)
            if c is None:
                raise ValueError(f"decref of unallocated block {b}")
            if c == 1:
                del self._ref[b]
                self._free.append(b)
                freed += 1
            else:
                self._ref[b] = c - 1
        return freed

    def refcount(self, block_id: int) -> int:
        return self._ref.get(block_id, 0)
