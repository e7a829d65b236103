"""KV blocks as a wire format + the host-RAM block tier (ISSUE 13).

The paged KV cache (serve/paging.py, ISSUE 6) made a request's decode
state a *transferable unit*: fixed-size refcounted blocks plus a block
table. This module is everything that moves those blocks OFF the device
pool and back:

  * **Wire format.** `pack_shipment`/`unpack_shipment` frame a JSON
    metadata header plus raw array payloads (per-layer K/V block
    gathers) into one byte string — versioned, magic-prefixed, with no
    pickle anywhere. The SAME bytes serve two transports:

      - **prefill→decode handoff** (DistServe-style disaggregation): a
        prefill replica chunk-prefills a prompt into pool blocks, ships
        `committed blocks + tokens + sampled first token/logprob + RNG
        key state` through the router to a decode replica, which admits
        the request straight into decode — zero prefill chunks ever run
        on a decode replica.
      - **host-RAM spill tier**: cold prefix-cache blocks evicted under
        pool pressure serialize through the same path into `HostKVTier`
        and restore on the next hit, lifting the effective pool beyond
        HBM.

  * **HostKVTier.** A bounded LRU of packed block payloads keyed the
    way the engine prefix cache is keyed — `(adapter, prefix_len,
    hash(tokens))` with the token tuple stored for hash-collision
    verification and a per-adapter length index for longest-prefix
    probes. Capacity is counted in BLOCKS (the pool's own currency).

Format versions (the `fmt` meta field — the frame itself never
changes, only what rides in it):

  * **fmt 1** — full-precision K/V blocks.
  * **fmt 2** — fmt 1 plus a versioned draft-KV section (speculative
    prefill handoff); refused by draft-less decode replicas.
  * **fmt 3** — QUANTIZED blocks (ISSUE 19): `k`/`v` arrays carry the
    raw int8/fp8 payloads, `ks`/`vs` carry the per-row-per-head f32
    scale planes, and `meta["kv_quant"]` names the mode. ≈2× smaller
    on the wire than fmt 1 for the same blocks. A decode replica whose
    `kv_quant` does not match refuses at submit_remote — never a
    silent dequant-upcast (mixed-precision fleets must not split a
    stream's numerics by which replica prefilled it). fmt 1 into a
    quantized replica is accepted: it quantizes at import with the
    same encode local admission uses. fmt 3 never combines with the
    draft section (`kv_quant × draft` is refused at engine init).

Determinism note: the shipment carries the prefill engine's RNG key
state (post-admission-splits, `jax.random.key_data`). A decode engine
that adopts it continues the exact key-split stream the unified engine
would have used, which is what makes a disaggregated stream
token+logprob-identical to the unified engine on the same seed
(test-pinned in tests/test_kv_transfer.py, per-stream — concurrent
shipments multiplex one engine key, exactly as concurrent local
admissions always have).
"""

from __future__ import annotations

import json
import struct
import threading
from collections import OrderedDict

import numpy as np

#: Wire magic + format version. Bump the digit on any layout change;
#: unpack refuses unknown versions loudly (a silently misparsed KV
#: payload would decode garbage tokens, not crash).
MAGIC = b"TPKV1\n"

_LEN = struct.Struct(">Q")


def require_row_blocks(state) -> None:
    """The wire format and the host tier carry one table of blocks of rows
    of per-head K and V: a model that keeps another state is refused when
    its engine is made, not mis-shaped on the wire."""
    from kubeflow_tpu.serve.paging import require_rows

    require_rows(state, "disaggregated shipment and the host tier: the wire "
                 "format carries one table of blocks of K and V rows")


class ShipmentError(ValueError):
    """Malformed / incompatible shipment bytes (bad magic, truncated
    frame, unknown version, dtype/shape mismatch with this engine)."""


def _dtype_of(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bfloat16 and friends live in ml_dtypes (a jax dependency).
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def pack_shipment(meta: dict, arrays: dict) -> bytes:
    """Frame `meta` (JSON-safe dict) + named host arrays into one byte
    string: MAGIC, u64 header length, JSON header, raw buffers in
    header order. Arrays round-trip byte-identically (C-order)."""
    names = sorted(arrays)
    specs = []
    bufs = []
    for name in names:
        arr = np.ascontiguousarray(arrays[name])
        specs.append({"name": name, "dtype": str(arr.dtype),
                      "shape": list(arr.shape)})
        bufs.append(arr.tobytes())
    header = json.dumps({"meta": meta, "arrays": specs},
                        sort_keys=True).encode()
    return b"".join([MAGIC, _LEN.pack(len(header)), header] + bufs)


def _parse_header(data) -> tuple[dict, memoryview, int]:
    """Shared frame parse: validate magic + length, decode the JSON
    header → (header, data_view, payload_offset). THE single home of
    the header layout — unpack_shipment, peek_meta, and rewrite_meta
    all go through it, so a format change cannot silently diverge the
    three parsers. Every malformation raises ShipmentError."""
    if not isinstance(data, (bytes, bytearray, memoryview)):
        raise ShipmentError(f"shipment must be bytes, got {type(data)}")
    data = memoryview(data)
    if bytes(data[:len(MAGIC)]) != MAGIC:
        raise ShipmentError(
            f"bad shipment magic {bytes(data[:len(MAGIC)])!r} "
            f"(want {MAGIC!r})")
    off = len(MAGIC)
    if len(data) < off + _LEN.size:
        raise ShipmentError("truncated shipment header length")
    (hlen,) = _LEN.unpack(bytes(data[off:off + _LEN.size]))
    off += _LEN.size
    if len(data) < off + hlen:
        raise ShipmentError("truncated shipment header")
    try:
        header = json.loads(bytes(data[off:off + hlen]))
    except ValueError as e:
        raise ShipmentError(f"bad shipment header: {e}") from e
    if not isinstance(header, dict):
        raise ShipmentError(
            f"bad shipment header: expected object, got "
            f"{type(header).__name__}")
    return header, data, off + hlen


def unpack_shipment(data: bytes) -> tuple[dict, dict]:
    """Inverse of `pack_shipment` → (meta, {name: np.ndarray}). Every
    malformation raises ShipmentError — truncated or alien bytes must
    never come back as a half-parsed cache."""
    header, data, off = _parse_header(data)
    try:
        meta = header["meta"]
        specs = header["arrays"]
    except KeyError as e:
        raise ShipmentError(f"bad shipment header: {e}") from e
    arrays = {}
    for spec in specs:
        try:
            dt = _dtype_of(spec["dtype"])
            shape = tuple(int(s) for s in spec["shape"])
        except (AttributeError, KeyError, TypeError, ValueError) as e:
            raise ShipmentError(f"bad array spec {spec!r}: {e}") from e
        n = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        if len(data) < off + n:
            raise ShipmentError(
                f"truncated shipment payload for {spec.get('name')!r}")
        arrays[spec["name"]] = np.frombuffer(
            data[off:off + n], dtype=dt).reshape(shape)
        off += n
    if off != len(data):
        raise ShipmentError(
            f"{len(data) - off} trailing bytes after shipment payload")
    return meta, arrays


def rewrite_meta(data, **updates) -> bytes:
    """Return a copy of a shipment with `updates` merged into its meta
    header — the array payload bytes are spliced through UNTOUCHED (no
    unpack, no array copies), so annotating a multi-MB shipment costs
    one header re-encode. The router uses this to stamp the RESUME
    CURSOR (`resume_skip`) onto a held shipment before re-submitting it
    to a surviving decode replica: the decode engine replays the same
    deterministic token stream and the cursor tells it how many leading
    tokens the caller has already been served (ISSUE 14)."""
    header, data, off = _parse_header(data)
    try:
        header["meta"].update(updates)
    except (KeyError, AttributeError) as e:
        raise ShipmentError(f"bad shipment header: {e}") from e
    new_header = json.dumps(header, sort_keys=True).encode()
    return b"".join([MAGIC, _LEN.pack(len(new_header)), new_header,
                     bytes(data[off:])])


def peek_meta(data) -> dict:
    """Parse ONLY the metadata header of a shipment (no array copies) —
    the server's :decode handler reads the stream flag and sizing here
    before handing the full payload to the engine."""
    header, _data, _off = _parse_header(data)
    try:
        return header["meta"]
    except KeyError as e:
        raise ShipmentError(f"bad shipment header: {e}") from e


class HostKVTier:
    """Host-RAM LRU tier for spilled KV block payloads.

    Keys follow the engine prefix cache's family — `(aid, n,
    hash(tokens))`, token tuple stored for verification, per-adapter
    length index for longest-prefix probes — so a spilled prefix is
    findable by exactly the probe that would have hit it in HBM.
    `take()` REMOVES the entry (restore-on-hit moves blocks back to the
    pool; the tier never holds a second copy of resident state).

    All state is mutated under one lock: the engine worker spills and
    restores, while metrics readers snapshot counters from other
    threads."""

    def __init__(self, capacity_blocks: int):
        if capacity_blocks < 1:
            raise ValueError(
                f"capacity_blocks must be >= 1, got {capacity_blocks}")
        self.capacity_blocks = int(capacity_blocks)
        # key -> (token_tuple, n_blocks, payload_bytes)
        self._lru: OrderedDict = OrderedDict()  # guarded-by: _lock
        self._lens: dict[int, dict[int, int]] = {}  # guarded-by: _lock
        self._blocks = 0  # guarded-by: _lock
        self._lock = threading.Lock()
        self.stats = {  # guarded-by: _lock
            "spilled_blocks": 0, "restored_blocks": 0,
            "evicted_blocks": 0, "rejected_blocks": 0,
        }

    @property
    def resident_blocks(self) -> int:
        with self._lock:
            return self._blocks

    def stats_snapshot(self) -> dict:
        with self._lock:
            return dict(self.stats, resident_blocks=self._blocks,
                        entries=len(self._lru))

    @staticmethod
    def _drop(lru: OrderedDict, lens: dict, stats: dict, key: tuple,
              counter: str) -> int:
        """Remove one entry from the passed-in table state (callers hold
        `_lock` and pass the guarded containers explicitly — the helper
        itself touches no `self` field, so the lock discipline stays
        lexically checkable). Returns the freed block count."""
        _, n, _ = lru.pop(key)
        stats[counter] += n
        aid, ln, _ = key
        per = lens.get(aid, {})
        if per.get(ln, 0) <= 1:
            per.pop(ln, None)
            if not per:
                lens.pop(aid, None)
        else:
            per[ln] -= 1
        return n

    def put(self, aid: int, kt: tuple, n_blocks: int,
            payload: bytes) -> bool:
        """Spill one prefix's packed blocks. Evicts LRU entries to make
        room; an entry larger than the whole tier is refused (False) —
        spilling it would just wipe the tier for nothing."""
        n_blocks = int(n_blocks)
        if n_blocks > self.capacity_blocks:
            with self._lock:
                self.stats["rejected_blocks"] += n_blocks
            return False
        key = (aid, len(kt), hash(kt))
        with self._lock:
            existing = self._lru.get(key)
            if existing is not None:
                if existing[0] == kt:
                    self._lru.move_to_end(key)
                    return True  # already resident: pure LRU touch
                self._blocks -= self._drop(  # hash collision
                    self._lru, self._lens, self.stats, key,
                    "evicted_blocks")
            while self._blocks + n_blocks > self.capacity_blocks:
                oldest = next(iter(self._lru))
                self._blocks -= self._drop(
                    self._lru, self._lens, self.stats, oldest,
                    "evicted_blocks")
            per = self._lens.setdefault(aid, {})
            per[len(kt)] = per.get(len(kt), 0) + 1
            self._lru[key] = (kt, n_blocks, payload)
            self._blocks += n_blocks
            self.stats["spilled_blocks"] += n_blocks
        return True

    def take(self, aid: int, kt: tuple) -> tuple[int, bytes] | None:
        """Remove and return (n_blocks, payload) for an exact prefix, or
        None. Restore-on-hit: the caller re-materializes the blocks in
        the pool, so the tier copy is retired here."""
        key = (aid, len(kt), hash(kt))
        with self._lock:
            entry = self._lru.get(key)
            if entry is None or entry[0] != kt:
                return None
            _, n, payload = entry
            self._blocks -= self._drop(self._lru, self._lens,
                                       self.stats, key,
                                       "restored_blocks")
        return n, payload

    def probe_longest(self, aid: int, ids) -> int | None:
        """Longest spilled prefix STRICTLY shorter than `ids` (the same
        contract as the engine's `_prefix_probe_paged`), or None. Read
        only — the caller follows up with `take()` once it has blocks
        to restore into."""
        with self._lock:
            lens = self._lens.get(aid)
            if not lens:
                return None
            for n in sorted(lens, reverse=True):
                if n >= len(ids):
                    continue
                kt = tuple(ids[:n])
                entry = self._lru.get((aid, n, hash(kt)))
                if entry is not None and entry[0] == kt:
                    return n
        return None
