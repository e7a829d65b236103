"""Front-door router: one address over N engine replicas (ISSUE 9).

One engine serves one chip (or one mesh); more traffic than that is
served horizontally. This module is the front door: it proxies the native
`:generate`, the OpenAI facade, the v1/v2 predict surfaces, and the gRPC
open-inference plane over a `Fleet` of model-server replicas
(serve/fleet.py), placing each request by:

  * **Prefix/adapter affinity.** Requests whose prompts share a prefix
    land on the same replica — the engine's prefix cache is keyed on the
    `(adapter, len, hash)` family, so cache warmth is per replica and
    scattering a hot prefix across the fleet wastes it. Placement
    consistent-hashes the request's affinity key (adapter + prompt
    prefix) onto a ring of virtual nodes per replica, so membership
    changes only remap the keys of the replicas that changed.
  * **Load-based spill-over.** Affinity yields when the cache-warm
    replica is more than `spill_margin` requests deeper than the
    least-loaded one — a hot prefix must not melt one replica while the
    rest idle. Load is router-outstanding + the replica's scraped
    `tpk_decode_inflight_depth` + admission occupancy; the scrape runs
    on the fleet's background poller, NEVER on the placement path.
  * **Least-loaded fallback.** No affinity signal (tensor inference,
    metadata GETs) → lowest load, ties broken by name (deterministic).

Composition with the existing layers (not a bypass):

  * `X-Request-Id` is honored/assigned and forwarded; the router's
    place/forward spans join the same trace the replica's admit →
    prefill → decode spans carry.
  * `X-Request-Timeout-Ms` is re-issued to the replica as the REMAINING
    budget at forward time — deadline propagation, not per-hop resets.
  * A replica's 503 overload shed is FORWARDED (Retry-After intact),
    never retried: backpressure must reach the caller or the router
    converts overload into a retry storm.
  * Connect-level failures and draining-replica rejections ARE retried,
    on a different replica, under the caller's remaining deadline —
    these are placement mistakes, not capacity signals. A POST-CONNECT
    timeout is neither: the replica accepted the request and may still
    be decoding it, so the caller gets a 504 and no replay (a replay
    would duplicate the work on a second replica).

Scale events come from serve/fleet.py: `drain()` stops placement while
in-flight requests finish; `FleetAutoscaler` turns router-observed shed
rate/occupancy into scale-out and drain-then-retire scale-in.
"""

from __future__ import annotations

import asyncio
import bisect
import dataclasses
import hashlib
import http.client
import json
import math
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlsplit

import tornado.httpserver
import tornado.ioloop
import tornado.iostream
import tornado.netutil
import tornado.web

from kubeflow_tpu.serve.fleet import Fleet
from kubeflow_tpu.serve.headers import (ATTEMPTS_HEADER, DEADLINE_HEADER,
                                        DRAINING_HEADER, REPLICA_HEADER,
                                        REQUEST_ID_HEADER)
from kubeflow_tpu.utils import obs
from kubeflow_tpu.utils.resilience import (Deadline, MetricsMergeError,
                                           merge_prometheus_texts,
                                           metrics as res_metrics)

#: Headers copied replica → caller. Everything else is router-owned
#: (the router echoes ITS X-Request-Id; hop-by-hop headers must not
#: leak through a proxy).
_FORWARD_RESP_HEADERS = ("Content-Type", "Retry-After")

#: Request paths that are inference traffic (placement + retry + body
#: parse for affinity); everything else is metadata/control and just
#: takes the least-loaded forward.
_GENERATIVE_SUFFIXES = (":generate", "/generate")
_OPENAI_PATHS = ("/openai/v1/completions", "/openai/v1/chat/completions")
_INFER_SUFFIXES = (":predict", ":explain", "/infer")

#: Bodies above this size skip the affinity parse (see _proxy).
_AFFINITY_PARSE_CAP = 512 * 1024


def _hash64(s: str) -> int:
    return int.from_bytes(
        hashlib.blake2b(s.encode(), digest_size=8).digest(), "big")


def affinity_key(path: str, body: dict | None) -> str | None:
    """The placement-affinity key of one request, or None when the
    request carries no prefix signal (→ least-loaded).

    Built to follow the engine prefix cache's key family (adapter, len,
    hash): the ADAPTER (either the payload field or the OpenAI
    "<base>:<adapter>" model id) plus a bounded PREFIX of the prompt —
    leading token ids when the caller sends `input_ids`, leading text
    otherwise. Two requests that would hit the same cached prefix
    produce the same key; max_tokens/temperature/suffix differences
    don't perturb it."""
    if not isinstance(body, dict):
        return None
    scope = path.rsplit("/", 1)[-1] if path else ""
    adapter = body.get("adapter") or ""
    model = body.get("model") or scope
    ids = body.get("input_ids")
    if isinstance(ids, (list, tuple)) and ids:
        head = ",".join(str(t) for t in ids[:32])
        return f"{model}|{adapter}|ids:{head}"
    for field in ("text", "prompt"):
        v = body.get(field)
        if isinstance(v, str) and v:
            return f"{model}|{adapter}|txt:{v[:128]}"
    msgs = body.get("messages")
    if isinstance(msgs, list) and msgs:
        try:
            head = json.dumps(msgs[0], sort_keys=True)[:128]
        except (TypeError, ValueError):
            return None
        return f"{model}|{adapter}|msg:{head}"
    return None


class Router:
    """Placement policy over a Fleet: consistent-hash affinity with
    load-based spill-over, least-loaded otherwise. Pure table math —
    every signal it reads was cached by the fleet poller."""

    def __init__(self, fleet: Fleet, *, affinity: bool = True,
                 spill_margin: float = 4.0, vnodes: int = 48):
        self.fleet = fleet
        self.affinity = bool(affinity)
        self.spill_margin = float(spill_margin)
        self.vnodes = int(vnodes)
        #: intent -> (version, ring) — the prefill/unified rings differ
        #: in a role-split fleet, so each intent caches its own.
        self._rings: dict = {}  # guarded-by: _ring_lock
        self._ring_lock = threading.Lock()
        self.stats = {  # guarded-by: _stats_lock
            "placed": 0, "affinity_hits": 0, "spills": 0,
            "least_loaded": 0, "decode_pool": 0, "retries": 0, "ok": 0,
            "handoffs": 0, "handoff_retries": 0,
            "resumes": 0, "resume_failures": 0,
            "sheds_forwarded": 0, "no_replica": 0, "errors": 0,
        }
        self._stats_lock = threading.Lock()

    def stats_snapshot(self) -> dict:
        with self._stats_lock:
            return dict(self.stats)

    def _bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self.stats[key] = self.stats.get(key, 0) + n

    def _ring_for(self, names: list[str], version: int,
                  intent: str | None = None) -> list[tuple[int, str]]:
        """The consistent-hash ring over `names`, rebuilt only when fleet
        membership/state changed (cheap version check otherwise); cached
        PER INTENT, since a role-split fleet's prefill ring covers a
        different replica set than the unified one. `version` must have
        been read BEFORE `names` was snapshotted: a membership change
        between the two then stamps the fresher set with the older
        version — over-invalidation (one spare rebuild), never a stale
        ring cached under the newest version."""
        with self._ring_lock:
            cached = self._rings.get(intent)
            if cached is not None and cached[0] == version:
                return cached[1]
        ring = sorted((_hash64(f"{name}#{i}"), name)
                      for name in names for i in range(self.vnodes))
        with self._ring_lock:
            self._rings[intent] = (version, ring)
        return ring

    def _ring_lookup(self, ring, point: int) -> str | None:
        if not ring:
            return None
        # (point,) sorts below every (point, name), so bisect_left gives
        # the first vnode at-or-after the point; wrap closes the ring.
        return ring[bisect.bisect_left(ring, (point,)) % len(ring)][1]

    # Placement is on every request's critical path: table reads and
    # hash math only — the fleet poller already cached every load
    # signal, so nothing here blocks on a scrape, a device, or I/O.
    # tpk-hot: router-placement
    def place(self, key: str | None, exclude: frozenset = frozenset(),
              intent: str | None = None) -> tuple[str | None, str]:
        """Choose a replica for a request with affinity key `key`
        (None = no prefix signal). Returns (replica_name, reason);
        (None, "no_replica") when nothing is placeable. `exclude` drops
        replicas that already failed this request (retry path).

        `intent` selects the disaggregation phase (ISSUE 13): "prefill"
        placements keep the prefix-affinity logic over prefill-capable
        replicas (cache warmth lives where prefills run); "decode"
        placements are load/pool-driven — least loaded, ties broken by
        the LARGEST free-block pool (the admission currency), then name
        — affinity would be meaningless there, the KV arrives on the
        wire. None is the unified full-request intent."""
        version = self.fleet.version()  # before loads() — see _ring_for
        if intent == "decode":
            sig = self.fleet.signals("decode")
            candidates = {n: v for n, v in sig.items()
                          if n not in exclude}
            if not candidates:
                self._bump("no_replica")
                return None, "no_replica"
            chosen = min(candidates,
                         key=lambda n: (candidates[n][0],
                                        -candidates[n][1], n))
            res_metrics.inc("tpk_router_placement_total",
                            reason="decode-pool")
            self._bump("placed")
            self._bump("decode_pool")
            return chosen, "decode-pool"
        loads = self.fleet.loads(intent=intent)
        candidates = loads if not exclude else \
            {n: v for n, v in loads.items() if n not in exclude}
        if not candidates:
            self._bump("no_replica")
            return None, "no_replica"
        floor = min(candidates.values())
        reason = "least-loaded"
        chosen = None
        if self.affinity and key is not None and len(loads) > 1:
            # The ring covers the FULL placeable set — it is cached
            # against the fleet version, so a retry's per-request
            # exclusions must apply at lookup time, never to the ring
            # itself (a poisoned cache would silently drop a healthy
            # replica from affinity until the next membership change).
            ring = self._ring_for(sorted(loads), version, intent)
            target = self._ring_lookup(ring, _hash64(key))
            if target in candidates:
                if candidates[target] - floor < self.spill_margin:
                    chosen, reason = target, "affinity-hit"
                else:
                    reason = "spill"
        elif self.affinity and key is not None:
            # Single candidate: the hash could only name it anyway.
            reason = "affinity-hit"
        if chosen is None:
            chosen = min(candidates, key=lambda n: (candidates[n], n))
        res_metrics.inc("tpk_router_placement_total", reason=reason)
        self._bump("placed")
        self._bump({"affinity-hit": "affinity_hits", "spill": "spills",
                    "least-loaded": "least_loaded"}[reason])
        return chosen, reason


class _ForwardResult:
    """One upstream attempt's outcome: a live response to stream, or a
    complete small response (sheds, errors) already read."""

    __slots__ = ("status", "headers", "conn", "resp", "body")

    def __init__(self, status, headers, conn=None, resp=None, body=None):
        self.status = status
        self.headers = headers
        self.conn = conn
        self.resp = resp
        self.body = body


class RetryableForwardError(Exception):
    """Connect-level failure or a draining replica — retry elsewhere."""


class ForwardTimeoutError(Exception):
    """The upstream ran past its time budget AFTER the connection was
    established. The replica accepted the request and may still be
    executing it, so replaying elsewhere would duplicate decode work —
    the caller gets a 504 instead."""


def _forward_once(url: str, method: str, path: str, body: bytes | None,
                  headers: dict, timeout_s: float,
                  read_body: bool = True) -> _ForwardResult:
    """One blocking proxy attempt against `url`. Raises
    RetryableForwardError on connect-level failures and drain
    rejections, ForwardTimeoutError on a post-connect timeout. With
    `read_body` (every non-streaming request) the WHOLE response is
    read here — one executor hop per request instead of one per chunk;
    streams keep the live (conn, resp) to relay chunk-by-chunk."""
    parts = urlsplit(url)
    conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                      timeout=timeout_s)
    try:
        conn.connect()
    except OSError as e:
        # Pre-request failure (refused, reset, connect timeout):
        # nothing reached the replica, replaying elsewhere is safe.
        conn.close()
        raise RetryableForwardError(f"{type(e).__name__}: {e}") from e
    try:
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        draining = (resp.status == 503
                    and resp.getheader(DRAINING_HEADER) is not None)
        whole = draining or read_body or resp.status == 503
        data = resp.read() if whole else None
    except TimeoutError as e:
        # socket.timeout past the established connection: the request
        # is in the replica's hands — slow is not retry fodder.
        conn.close()
        raise ForwardTimeoutError(
            f"no response within {timeout_s:.1f}s") from e
    except (ConnectionError, OSError, http.client.HTTPException) as e:
        # HTTPException covers a replica dying mid-response
        # (BadStatusLine / IncompleteRead on a closed socket) — same
        # retry class as a straight connect reset: nothing reached the
        # caller yet, so replaying elsewhere is safe.
        conn.close()
        raise RetryableForwardError(f"{type(e).__name__}: {e}") from e
    if draining:
        conn.close()
        raise RetryableForwardError(
            f"replica draining: {data[:120]!r}")
    if whole:
        conn.close()
        return _ForwardResult(resp.status, resp.getheaders(), body=data)
    return _ForwardResult(resp.status, resp.getheaders(), conn=conn,
                          resp=resp)


@dataclasses.dataclass
class _ForwardState:
    """Mutable retry bookkeeping threaded through `_forward_attempt`.
    Lives OUTSIDE the helper so a caller that re-enters the loop (the
    decode resume path) keeps its exclusions and its attempt budget
    across entries — a resumed stream must not get a fresh budget."""

    exclude: set = dataclasses.field(default_factory=set)
    attempts: int = 0


@dataclasses.dataclass
class _Attempt:
    """Terminal outcome of one `_forward_attempt` run.

    kind:
      * ``no_replica`` — placement found nothing live.
      * ``deadline``   — the caller's deadline expired before a forward
        (already counted + metered by the helper).
      * ``exhausted``  — a connect-class failure and no retry budget /
        deadline left; `expired`/`draining` say which terminal flavor.
      * ``timeout``    — post-connect timeout (replica may still be
        working: no replay; already counted + metered by the helper).
      * ``ok``         — `result` is live and `name` is STILL CHECKED
        OUT: the caller owns the matching `fleet.checkin`.
    """

    kind: str
    name: str | None = None
    result: _ForwardResult | None = None
    t0: float = 0.0
    error: Exception | None = None
    expired: bool = False
    draining: bool = False


class _RouterBase(tornado.web.RequestHandler):
    def initialize(self, server: "RouterServer"):
        self.server = server
        self.router = server.router
        self.fleet = server.fleet

    def write_json(self, obj, status: int = 200) -> None:
        self.set_status(status)
        self.set_header("Content-Type", "application/json")
        self.finish(json.dumps(obj))

    def write_error(self, status_code: int, **kwargs) -> None:
        reason = self._reason
        if "exc_info" in kwargs:
            exc = kwargs["exc_info"][1]
            if not isinstance(exc, tornado.web.HTTPError):
                reason = f"{type(exc).__name__}: {exc}"
        self.write_json({"error": reason}, status=status_code)


class ProxyHandler(_RouterBase):
    """The catch-all data-plane proxy: place, forward, stream back."""

    async def get(self, path):
        await self._proxy(path)

    async def post(self, path):
        await self._proxy(path)

    async def put(self, path):
        await self._proxy(path)

    async def delete(self, path):
        await self._proxy(path)

    def _count(self, replica: str | None, outcome: str) -> None:
        res_metrics.inc("tpk_router_requests_total",
                        replica=replica or "-", outcome=outcome)
        # Every terminal count doubles as SLO/flight-recorder evidence:
        # the latest outcome wins (a resumed stream's mid-loop
        # upstream_error is overwritten by the final ok) and the replica
        # joins the request's trail.
        slo = getattr(self, "_slo", None)
        if slo is not None:
            slo["outcome"] = outcome
            if replica:
                self._slo_replica(replica)

    def _slo_replica(self, name: str) -> None:
        """Append `name` to the request's replica trail (consecutive
        duplicates collapsed — retries against the same replica are an
        attempt count, not a trail hop)."""
        slo = getattr(self, "_slo", None)
        if slo is not None and name and (not slo["replicas"]
                                         or slo["replicas"][-1] != name):
            slo["replicas"].append(name)

    def _observe_flush(self) -> None:
        """SLO accounting at the byte-flush boundary: the FIRST flushed
        content frame is TTFT (what the caller experienced — placement,
        queueing, prefill, handoff all included); subsequent flushes on
        a stream are inter-token-latency gaps."""
        slo = getattr(self, "_slo", None)
        if slo is None:
            return
        now = time.perf_counter()
        if slo["ttft_s"] is None:
            slo["ttft_s"] = now - slo["t0"]
            # tpk-slo: router-ttft-observe — THE TTFT observe site
            # (tpklint's red-switch test pins this marker: deleting the
            # observation silently is a finding).
            res_metrics.observe("tpk_router_ttft_seconds",
                                slo["ttft_s"], intent=slo["intent"])
        elif slo["stream"] and slo["last_flush"] is not None:
            res_metrics.observe("tpk_router_itl_seconds",
                                now - slo["last_flush"])
        slo["last_flush"] = now

    def _finalize_slo(self) -> None:
        """Conclude one proxied request: e2e/deadline-miss observations
        plus the flight-recorder record — the one place every request
        (ok, shed, resumed, died) reports what actually happened.
        Idempotent: the relay paths can conclude through several exits."""
        slo = getattr(self, "_slo", None)
        if slo is None or slo["final"]:
            return
        slo["final"] = True
        e2e = time.perf_counter() - slo["t0"]
        outcome = slo["outcome"]
        if outcome is None:
            status = self.get_status()
            outcome = ("ok" if status < 400 else
                       "shed" if status == 503 else
                       "deadline" if status == 504 else
                       "client_error" if status < 500
                       else "upstream_error")
        missed = (slo["deadline"] is not None
                  and slo["deadline"].expired())
        res_metrics.observe("tpk_router_e2e_seconds", e2e,
                            outcome=outcome)
        if missed:
            res_metrics.inc("tpk_router_deadline_miss_total",
                            intent=slo["intent"])
        self.server.flight_recorder.record(
            trace_id=slo["trace_id"], path=slo["path"],
            intent=slo["intent"], stream=slo["stream"],
            t_start_unix=slo["t_start_unix"], ttft_s=slo["ttft_s"],
            e2e_s=e2e, outcome=outcome, reason=slo["reason"],
            replicas=list(slo["replicas"]), resumes=slo["resumes"],
            attempts=slo["attempts"], deadline_miss=missed)

    def _deadline(self) -> Deadline | None:
        raw = self.request.headers.get(DEADLINE_HEADER)
        if raw is None:
            return None
        try:
            ms = float(raw)
            # Mirrors server.py request_deadline: NaN/inf would defeat
            # every expiry comparison (and overflow the remaining-ms
            # re-issue) downstream.
            if not math.isfinite(ms) or ms <= 0:
                raise ValueError
        except ValueError:
            raise tornado.web.HTTPError(
                400, reason=f"{DEADLINE_HEADER} must be a positive "
                            f"number of milliseconds, got {raw!r}") \
                from None
        return Deadline(ms / 1e3)

    async def _proxy(self, path: str) -> None:
        trace_id = obs.sanitize_trace_id(
            self.request.headers.get(REQUEST_ID_HEADER))
        self.set_header(REQUEST_ID_HEADER, trace_id)
        route = "/" + path
        # Classify (and key affinity) on the bare ROUTE: a query string
        # must not reclassify inference traffic as metadata — that would
        # drop both the affinity key and the drain-retry contract.
        is_generative = (route.endswith(_GENERATIVE_SUFFIXES)
                         or route in _OPENAI_PATHS)
        is_inference = is_generative or route.endswith(_INFER_SUFFIXES)
        self._slo = {
            "t0": time.perf_counter(), "t_start_unix": time.time(),
            "trace_id": trace_id, "path": route,
            "intent": ("generate" if is_generative else
                       "infer" if is_inference else "meta"),
            "deadline": None, "stream": False, "ttft_s": None,
            "last_flush": None, "replicas": [], "resumes": 0,
            "attempts": 0, "outcome": None, "reason": None,
            "final": False,
        }
        try:
            await self._proxy_impl(route, trace_id, is_generative,
                                   is_inference)
        except tornado.web.HTTPError as e:
            slo = self._slo
            if slo["outcome"] is None:
                slo["outcome"] = ("shed" if e.status_code == 503 else
                                  "deadline" if e.status_code == 504 else
                                  "upstream_error" if e.status_code >= 500
                                  else "client_error")
            if not slo["reason"]:
                slo["reason"] = e.reason or ""
            raise
        finally:
            self._finalize_slo()

    async def _proxy_impl(self, route: str, trace_id: str,
                          is_generative: bool,
                          is_inference: bool) -> None:
        deadline = self._deadline()
        self._slo["deadline"] = deadline
        full_path = route
        if self.request.query:
            full_path += "?" + self.request.query
        key = None
        wants_stream = False
        if is_generative and self.request.body:
            raw = self.request.body
            if len(raw) <= _AFFINITY_PARSE_CAP:
                try:
                    parsed = json.loads(raw)
                    key = affinity_key(route, parsed)
                    wants_stream = bool(isinstance(parsed, dict)
                                        and parsed.get("stream"))
                except (ValueError, TypeError):
                    key = None  # malformed body: the replica renders the 400
            else:
                # json.loads holds the GIL for the whole parse — a
                # multi-MB longctx payload parsed on the ioloop would
                # stall every other request for placement sugar worth a
                # 32-token prefix. Forego affinity; a substring test
                # picks the relay mode (a false positive only costs
                # chunk-wise relay of a non-streamed reply).
                wants_stream = b'"stream"' in raw
        self._slo["stream"] = wants_stream
        if (is_generative and self.request.method == "POST"
                and self.fleet.role_split()):
            # Disaggregated fleet (ISSUE 13): two-phase handoff —
            # prefill replica ships KV blocks, decode replica streams
            # the tokens. Falls through to the unified path when no
            # prefill-capable replica is placeable (or the surface has
            # no :prefill mapping, e.g. the OpenAI facade).
            if await self._proxy_disagg(route, trace_id, deadline, key,
                                        wants_stream):
                return
        # A full generate needs a replica serving BOTH phases (a
        # decode-role replica would refuse the prefill); metadata and
        # tensor-infer traffic places over every role.
        intent = "generate" if is_generative else None
        state = _ForwardState()
        a = await self._forward_attempt(
            state=state, key=key, intent=intent,
            method=self.request.method, path=full_path,
            body=self.request.body or None,
            content_type=self.request.headers.get("Content-Type"),
            trace_id=trace_id, deadline=deadline,
            read_body=not wants_stream,
            retryable=(is_inference or self.request.method == "GET"),
            drain_rejects=True)
        if a.kind == "no_replica":
            self._count(None, "no_replica")
            self.router._bump("errors")
            self.set_header("Retry-After", "1")
            self.write_json({"error": "no live replica"}, status=503)
            return
        if a.kind == "deadline":
            raise tornado.web.HTTPError(
                504, reason="request deadline exceeded (router)")
        if a.kind == "exhausted":
            if a.expired:
                raise tornado.web.HTTPError(
                    504, reason="request deadline exceeded "
                                "(router retries)") from a.error
            if a.draining:
                # The replica answered cleanly — reflect its drain
                # rejection as the 503 it was, not a 502. NOT counted
                # as a shed: sheds feed the autoscaler's scale-out
                # signal, and a drain rejection is the opposite of
                # overload evidence.
                self.router._bump("draining_rejects")
                self.set_header("Retry-After", "1")
                self.set_header(DRAINING_HEADER, "1")
                self.write_json(
                    {"error": f"replica {a.name} draining"}, status=503)
                return
            raise tornado.web.HTTPError(
                502, reason=f"replica {a.name} unreachable: {a.error}") \
                from a.error
        if a.kind == "timeout":
            raise tornado.web.HTTPError(
                504, reason=f"replica {a.name} timed out: {a.error}") \
                from a.error
        self.set_header(REPLICA_HEADER, a.name)
        self.set_header(ATTEMPTS_HEADER, str(state.attempts))
        try:
            await self._relay(a.result, a.name, trace_id, a.t0)
        finally:
            self.fleet.checkin(a.name)

    def _remaining_headers(self, trace_id: str,
                           deadline: Deadline | None,
                           content_type: str | None = None) -> dict:
        headers = {REQUEST_ID_HEADER: trace_id}
        if content_type:
            headers["Content-Type"] = content_type
        if deadline is not None:
            rem = deadline.remaining()
            headers[DEADLINE_HEADER] = str(max(int((rem or 0.0) * 1e3), 1))
        return headers

    async def _forward_attempt(
            self, *, state: _ForwardState, key: str | None,
            intent: str | None, method: str, path: str,
            body: bytes | None, content_type: str | None,
            trace_id: str, deadline: Deadline | None, read_body: bool,
            retryable: bool = True, retry_reason: str | None = None,
            drain_rejects: bool = False,
            count_handoff: bool = False) -> _Attempt:
        """ONE place → checkout → forward → classify pass, shared by
        the unified proxy loop and both disaggregation phases (prefill,
        decode/resume). Owns the retry loop for connect-class failures
        and drain rejections: nothing reached the caller on those, so
        re-placing elsewhere is safe — `state` carries the exclusions
        and attempt budget so a re-entrant caller (decode resume) keeps
        both across calls. All counting the three callers share lives
        here (pre-forward deadline, retry/exhausted/timeout metrics);
        terminal outcomes come back as an `_Attempt` for the caller to
        render, because the renders legitimately differ (the unified
        path raises HTTPErrors, a started decode stream must close with
        an error frame instead). On ``ok`` the replica is STILL checked
        out — the caller owns the checkin after relaying.

        `retryable=False` (non-inference non-GET traffic) turns the
        first connect failure terminal. `retry_reason` overrides the
        draining/connect retry label (decode passes "prefill_handoff");
        `count_handoff` adds the handoff_retries bump. `drain_rejects`
        (unified path only) keeps a drain-exhausted terminal out of the
        error count — a drain rejection is the opposite of overload
        evidence — so the caller can render it as the 503 it was."""
        loop = asyncio.get_event_loop()
        max_attempts = max(len(self.fleet.names()), 1)
        while True:
            with obs.span("router.place", trace_id=trace_id,
                          path=path) as sp:
                name, reason = self.router.place(
                    key, exclude=frozenset(state.exclude), intent=intent)
                sp.set(replica=name or "-", reason=reason)
            if name is None:
                return _Attempt("no_replica")
            url = self.fleet.url_of(name)
            if url is None:
                state.exclude.add(name)
                continue
            if deadline is not None and deadline.expired():
                self._count(name, "deadline")
                res_metrics.inc("tpk_deadline_expired_total",
                                component="router")
                return _Attempt("deadline", name=name)
            headers = self._remaining_headers(trace_id, deadline,
                                              content_type)
            timeout_s = (deadline.bound(self.server.forward_timeout_s)
                         if deadline is not None
                         else self.server.forward_timeout_s)
            self.fleet.checkout(name)
            state.attempts += 1
            slo = getattr(self, "_slo", None)
            if slo is not None:
                slo["attempts"] += 1
            t0 = time.perf_counter()
            try:
                result = await loop.run_in_executor(
                    self.server.executor, _forward_once, url, method,
                    path, body, headers, timeout_s, read_body)
            except RetryableForwardError as e:
                draining = "draining" in str(e)
                self.fleet.checkin(name, failed=not draining)
                obs.record("router.forward", t0, time.perf_counter(),
                           trace_id=trace_id, replica=name,
                           error=str(e)[:120])
                expired = deadline is not None and deadline.expired()
                if (retryable and state.attempts <= max_attempts
                        and not expired):
                    state.exclude.add(name)
                    res_metrics.inc(
                        "tpk_router_retry_total",
                        reason=(retry_reason if retry_reason
                                else "draining" if draining
                                else "connect"))
                    self.router._bump("retries")
                    if count_handoff:
                        self.router._bump("handoff_retries")
                    continue
                self._count(name, "deadline" if expired
                            else "draining" if draining and drain_rejects
                            else "retry_exhausted")
                if expired or not (draining and drain_rejects):
                    self.router._bump("errors")
                if expired:
                    res_metrics.inc("tpk_deadline_expired_total",
                                    component="router")
                return _Attempt("exhausted", name=name, error=e,
                                expired=expired, draining=draining)
            except ForwardTimeoutError as e:
                # The replica may still be executing the request: no
                # replay (that would duplicate decode work) and no
                # failure mark (slow is not dead) — the caller renders
                # a 504. The gray-ejection EWMA still gets the latency
                # evidence.
                self.fleet.checkin(name)
                self.fleet.observe_forward(name, timeout_s)
                obs.record("router.forward", t0, time.perf_counter(),
                           trace_id=trace_id, replica=name,
                           error=str(e)[:120])
                self._count(name, "upstream_error")
                self.router._bump("errors")
                return _Attempt("timeout", name=name, error=e)
            except Exception:
                # Anything non-retryable still releases the outstanding
                # count, or a drain on this replica would wait forever.
                self.fleet.checkin(name)
                raise
            return _Attempt("ok", name=name, result=result, t0=t0)

    async def _proxy_disagg(self, route: str, trace_id: str,
                            deadline: Deadline | None, key: str | None,
                            wants_stream: bool) -> bool:
        """The prefill→decode handoff (ISSUE 13). Phase 1 places by
        PREFIX AFFINITY over prefill-capable replicas (cache warmth
        lives where prefills run) and receives the KV shipment; phase 2
        places by load/pool over decode-capable replicas and relays the
        token stream. THE ROUTER HOLDS THE SHIPMENT between phases:
        once phase 1 returns, the prefill replica owes this request
        nothing — its death cannot force a re-prefill, and a decode
        replica failing at connect retries on ANOTHER decode replica
        with the same bytes (`tpk_router_retry_total{reason=
        "prefill_handoff"}`), never replaying prefill work. Returns
        False to fall through to the unified single-phase path (no
        prefill replica placeable / unmapped surface). Both phases ride
        `_forward_attempt` — the same place → checkout → forward →
        classify machinery as the unified loop."""
        if route.endswith(":generate"):
            model = route.rsplit("/", 1)[-1][:-len(":generate")]
        elif route.endswith("/generate"):
            parts = route.split("/")
            model = parts[-2] if len(parts) >= 2 else ""
        else:
            return False  # no :prefill mapping for this surface
        if not model:
            return False
        prefill_path = f"/v1/models/{model}:prefill"
        decode_path = f"/v1/models/{model}:decode"
        t_handoff0 = time.perf_counter()

        # -- phase 1: chunked prefill → KV shipment ----------------------
        # A pre-ship failure computed nothing for this request yet, so
        # re-placing the prefill is the plain connect/draining retry
        # class, not a handoff.
        pstate = _ForwardState()
        a = await self._forward_attempt(
            state=pstate, key=key, intent="prefill", method="POST",
            path=prefill_path, body=self.request.body or None,
            content_type="application/json", trace_id=trace_id,
            deadline=deadline, read_body=True)
        if a.kind == "no_replica":
            if pstate.attempts == 0:
                return False  # no prefill capacity: unified path
            self._count(None, "no_replica")
            self.router._bump("errors")
            self.set_header("Retry-After", "1")
            self.write_json({"error": "no live prefill replica"},
                            status=503)
            return True
        if a.kind == "deadline":
            raise tornado.web.HTTPError(
                504, reason="request deadline exceeded (router)")
        if a.kind == "exhausted":
            if a.expired:
                raise tornado.web.HTTPError(
                    504, reason="request deadline exceeded "
                                "(router retries)") from a.error
            raise tornado.web.HTTPError(
                502, reason=f"prefill replica {a.name} unreachable: "
                            f"{a.error}") from a.error
        if a.kind == "timeout":
            raise tornado.web.HTTPError(
                504, reason=f"prefill replica {a.name} timed out: "
                            f"{a.error}") from a.error
        name, result, t0 = a.name, a.result, a.t0
        self.fleet.checkin(name)
        if result.status != 200:
            # Sheds forward as backpressure, errors relay as-is —
            # exactly the unified path's contract. (_relay observes
            # the forward latency itself — observing here too would
            # double-count the sample into the gray EWMA.)
            self.set_header(REPLICA_HEADER, name)
            self.set_header(ATTEMPTS_HEADER, str(pstate.attempts))
            await self._relay(result, name, trace_id, t0)
            return True
        self.fleet.observe_forward(name, time.perf_counter() - t0)
        obs.record("router.forward", t0, time.perf_counter(),
                   trace_id=trace_id, replica=name, status=200,
                   phase="prefill")
        self._slo_replica(name)
        # Stamp the caller's trace id into the held shipment meta
        # (header splice via rewrite_meta — array bytes untouched, fmt
        # unchanged, older replicas ignore the key): the decode
        # replica's spans join the caller's trace even though the
        # :decode POST body is opaque TPKV1, and every resume
        # re-submission restates the stamp along with its cursor.
        from kubeflow_tpu.serve.kv_transfer import rewrite_meta

        try:
            shipment = rewrite_meta(result.body, trace=trace_id)
        except Exception:
            shipment = result.body  # unparseable meta: ship verbatim
        res_metrics.observe("tpk_prefill_handoff_seconds",
                            time.perf_counter() - t_handoff0)
        self.router._bump("handoffs")

        # -- phase 2: shipment → decode replica → caller -----------------
        # The resume loop (ISSUE 14): because the router HOLDS the
        # shipment, a decode replica dying MID-STREAM is recoverable —
        # the same bytes are re-submitted to a surviving decode replica
        # with a `resume_skip` cursor stamped into the shipment meta
        # (the count of tokens already relayed to the caller), and the
        # replica's deterministic replay continues the stream exactly
        # where it stopped: zero re-prefill, zero duplicated or lost
        # tokens, no caller-visible error. Bounded by `max_resumes` and
        # the caller's riding deadline; once those run out the stream
        # ends with a terminal error frame + honest abrupt close.
        dstate = _ForwardState()
        resumes = 0
        delivered = 0           # whole-frame tokens already at the caller
        stream_started = False  # status+headers already on the wire
        served: list[str] = []
        active_shipment = shipment
        while True:
            # THE handoff-resume path: the prefill work is safe in the
            # router-held shipment, so a dead/draining decode target
            # costs one re-placement and ZERO re-prefill. One `dstate`
            # across resume iterations: a resumed stream keeps its
            # exclusions and does NOT get a fresh attempt budget.
            a = await self._forward_attempt(
                state=dstate, key=None, intent="decode", method="POST",
                path=decode_path, body=active_shipment,
                content_type="application/x-tpk-kv", trace_id=trace_id,
                deadline=deadline, read_body=not wants_stream,
                retry_reason="prefill_handoff", count_handoff=True)
            if a.kind == "no_replica":
                self._count(None, "no_replica")
                self.router._bump("errors")
                if stream_started:
                    self.router._bump("resume_failures")
                    await self._stream_error_close(
                        "no live decode replica to resume on")
                    return True
                self.set_header("Retry-After", "1")
                self.write_json({"error": "no live decode replica"},
                                status=503)
                return True
            if a.kind == "deadline":
                if stream_started:
                    self.router._bump("errors")
                    self.router._bump("resume_failures")
                    await self._stream_error_close(
                        "request deadline exceeded (router resume)")
                    return True
                raise tornado.web.HTTPError(
                    504, reason="request deadline exceeded (router)")
            if a.kind == "exhausted":
                if a.expired:
                    if stream_started:
                        self.router._bump("resume_failures")
                        await self._stream_error_close(
                            "request deadline exceeded (router resume)")
                        return True
                    raise tornado.web.HTTPError(
                        504, reason="request deadline exceeded "
                                    "(router retries)") from a.error
                if stream_started:
                    self.router._bump("resume_failures")
                    await self._stream_error_close(
                        f"decode replica {a.name} unreachable during "
                        f"resume: {a.error}")
                    return True
                raise tornado.web.HTTPError(
                    502, reason=f"decode replica {a.name} unreachable: "
                                f"{a.error}") from a.error
            if a.kind == "timeout":
                # The decode replica may still be generating: 504, no
                # replay (a replay would duplicate decode work).
                if stream_started:
                    self.router._bump("resume_failures")
                    await self._stream_error_close(
                        f"decode replica {a.name} timed out: {a.error}")
                    return True
                raise tornado.web.HTTPError(
                    504, reason=f"decode replica {a.name} timed out: "
                                f"{a.error}") from a.error
            dname, result, t0 = a.name, a.result, a.t0
            if not wants_stream:
                self.set_header(REPLICA_HEADER, dname)
                self.set_header(ATTEMPTS_HEADER,
                                str(pstate.attempts + dstate.attempts))
                try:
                    await self._relay(result, dname, trace_id, t0)
                finally:
                    self.fleet.checkin(dname)
                return True
            if stream_started and result.status != 200:
                # A resume attempt answered an error/shed AFTER the 200
                # status already went out — nothing left to forward it
                # as; terminal error frame.
                if result.conn is not None:
                    result.conn.close()
                self.fleet.checkin(dname)
                self._count(dname, "upstream_error")
                self.router._bump("errors")
                self.router._bump("resume_failures")
                await self._stream_error_close(
                    f"decode resume on {dname} answered "
                    f"{result.status}")
                return True
            if result.body is not None or result.status != 200:
                # Pre-stream shed/error from the FIRST attempt: relay it
                # verbatim (sheds forward as backpressure, errors as-is
                # — exactly the unified path's contract).
                self.set_header(REPLICA_HEADER, dname)
                self.set_header(ATTEMPTS_HEADER,
                                str(pstate.attempts + dstate.attempts))
                try:
                    await self._relay(result, dname, trace_id, t0)
                finally:
                    self.fleet.checkin(dname)
                return True
            if not stream_started:
                self.set_header(REPLICA_HEADER, dname)
                self.set_header(ATTEMPTS_HEADER,
                                str(pstate.attempts + dstate.attempts))
            prov = {"replicas": served + [dname], "resumes": resumes}
            try:
                status, delta, err, flushed = await self._relay_ndjson(
                    result, dname, trace_id, t0,
                    started=stream_started, prov=prov)
            except Exception:
                # Unexpected relay failure (executor shutdown, handler
                # teardown): the outstanding count must still release,
                # or this replica's load stays inflated and a drain on
                # it never completes.
                self.fleet.checkin(dname)
                raise
            # Committed only once bytes actually reached the caller: an
            # attempt that died pre-flush leaves the status line free,
            # so terminal failures can still answer a real 5xx.
            stream_started = stream_started or flushed
            delivered += delta
            served.append(dname)
            dt = time.perf_counter() - t0
            if status in ("done", "caller_gone"):
                self.fleet.checkin(dname)
                self.fleet.observe_forward(dname, dt)
                return True
            # Died mid-stream. A read timeout means the replica is
            # STALLED, not dead — no failure nudge (the gray-ejection
            # EWMA gets the latency evidence instead); anything else is
            # a death and counts toward the probe-failure trip.
            stalled = isinstance(err, TimeoutError)
            self.fleet.checkin(dname, failed=not stalled)
            self.fleet.observe_forward(dname, dt)
            self._count(dname, "upstream_error")
            expired = deadline is not None and deadline.expired()
            if resumes >= self.server.max_resumes or expired:
                self.router._bump("errors")
                self.router._bump("resume_failures")
                if expired:
                    res_metrics.inc("tpk_deadline_expired_total",
                                    component="router")
                msg = (f"decode replica {dname} died mid-stream and "
                       f"the resume budget is exhausted "
                       f"({resumes}/{self.server.max_resumes}): {err}")
                if stream_started:
                    await self._stream_error_close(msg)
                    return True
                # Nothing reached the caller yet: a real status beats
                # a 200 + error frame.
                raise tornado.web.HTTPError(504 if expired else 502,
                                            reason=msg)
            resumes += 1
            res_metrics.inc("tpk_router_resume_total",
                            reason="stall" if stalled else "death")
            self.router._bump("resumes")
            self._slo["resumes"] = resumes
            # The resume SEAM is a first-class trace event: a zero-
            # duration span on the router timeline marking where the
            # stream crossed replicas — the assembled distributed trace
            # shows the kill and the continuation either side of it.
            t_seam = time.perf_counter()
            obs.record("router.resume", t_seam, t_seam,
                       trace_id=trace_id, from_replica=dname,
                       delivered=delivered,
                       reason="stall" if stalled else "death")
            self.server.flight_recorder.snapshot(
                f"resume:{dname}", trace_id=trace_id,
                cause="stall" if stalled else "death",
                delivered=delivered, resumes=resumes)
            dstate.exclude.add(dname)
            # Stamp the cursor on the ORIGINAL held bytes (idempotent —
            # each resume restates the full delivered count; the trace
            # stamp above rides along, rewrite_meta splices into the
            # already-stamped shipment).
            active_shipment = rewrite_meta(shipment,
                                           resume_skip=delivered,
                                           trace=trace_id)

    async def _stream_error_close(self, msg: str) -> None:
        """Terminal error envelope for an already-started ndjson stream,
        followed by an honest ABRUPT close: the envelope names the
        failure for clients that parse frames, the missing terminator
        keeps the truncation visible to clients that don't."""
        slo = getattr(self, "_slo", None)
        if slo is not None and not slo["reason"]:
            slo["reason"] = msg
        try:
            self.write(json.dumps({"error": msg}) + "\n")
            await self.flush()
        except Exception:
            pass
        try:
            self.request.connection.stream.close()
        except Exception:
            pass

    async def _relay_ndjson(
            self, result: _ForwardResult, name: str, trace_id: str,
            t0: float, *, started: bool,
            prov: dict) -> tuple[str, int, Exception | None, bool]:
        """Relay one decode replica's x-ndjson token stream LINE
        BUFFERED: only COMPLETE frames reach the caller (a death
        mid-frame must not deliver a torn line — the resume cursor
        counts tokens from whole frames, so router-delivered and
        replica-skipped counts always agree), tokens are tallied as
        frames pass, and the terminal done frame is enriched with the
        router's provenance (`_router`: serving replicas + resume
        count) so load harnesses get per-request truth. Returns
        (status, delivered_tokens, err, flushed) with status one of
        "done" (terminal frame relayed), "caller_gone" (client
        disconnected), "died" (upstream ended without a done frame);
        `flushed` reports whether any bytes actually reached the
        caller's socket — an attempt that died before flushing leaves
        the response UNCOMMITTED, so a later terminal failure can still
        answer a proper 5xx instead of a 200 + error frame."""
        loop = asyncio.get_event_loop()
        if not started:
            self.set_status(result.status)
            hdrs = dict(result.headers or ())
            for h in _FORWARD_RESP_HEADERS:
                if h in hdrs:
                    self.set_header(h, hdrs[h])
        conn, resp = result.conn, result.resp
        delivered = 0
        done = False
        flushed = False
        err: Exception | None = None
        buf = b""
        try:
            while not done:
                try:
                    chunk = await loop.run_in_executor(
                        self.server.executor, resp.read1, 65536)
                except (OSError, http.client.HTTPException) as e:
                    err = e
                    break
                if not chunk:
                    break
                buf += chunk
                out: list[bytes] = []
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if not line.strip():
                        out.append(line + b"\n")
                        continue
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        out.append(line + b"\n")
                        continue
                    if isinstance(ev, dict):
                        delivered += len(ev.get("tokens") or ())
                        if ev.get("done"):
                            done = True
                            ev["_router"] = dict(prov)
                            out.append(json.dumps(ev).encode() + b"\n")
                            break
                    out.append(line + b"\n")
                if out:
                    self.write(b"".join(out))
                    try:
                        await self.flush()
                        flushed = True
                        self._observe_flush()
                    except tornado.iostream.StreamClosedError:
                        self._count(name, "ok")
                        self.router._bump("ok")
                        return ("caller_gone", delivered, None, flushed)
        finally:
            conn.close()
        if done:
            self._count(name, "ok")
            self.router._bump("ok")
            obs.record("router.forward", t0, time.perf_counter(),
                       trace_id=trace_id, replica=name,
                       status=result.status)
            try:
                self.finish()
            except tornado.iostream.StreamClosedError:
                pass
            return ("done", delivered, None, True)
        if err is None:
            err = RuntimeError("upstream closed before the done frame")
        obs.record("router.forward", t0, time.perf_counter(),
                   trace_id=trace_id, replica=name,
                   error=str(err)[:120])
        return ("died", delivered, err, flushed)

    async def _relay(self, result: _ForwardResult, name: str,
                     trace_id: str, t0: float) -> None:
        """Stream one upstream response back to the caller."""
        loop = asyncio.get_event_loop()
        self.set_status(result.status)
        hdrs = dict(result.headers or ())
        for h in _FORWARD_RESP_HEADERS:
            if h in hdrs:
                self.set_header(h, hdrs[h])
        if result.body is not None:  # fully-read (non-stream) response
            if result.status == 503:
                outcome, stat = "shed", "sheds_forwarded"
            elif result.status >= 500:
                outcome, stat = "upstream_error", "errors"
            else:
                outcome, stat = "ok", "ok"
            self._count(name, outcome)
            self.router._bump(stat)
            self.fleet.observe_forward(name,
                                       time.perf_counter() - t0)
            obs.record("router.forward", t0, time.perf_counter(),
                       trace_id=trace_id, replica=name,
                       status=result.status)
            self.finish(result.body)
            if result.status < 400:
                # Non-streamed content: the one body flush IS the first
                # content frame (sheds/errors are accounted by the e2e
                # outcome histogram, not TTFT).
                self._observe_flush()
            return
        conn, resp = result.conn, result.resp
        outcome = "ok" if result.status < 500 else "upstream_error"
        upstream_err = None
        try:
            while True:
                try:
                    # read1: at most ONE chunk per hop. read(amt) on a
                    # chunked response accumulates until `amt` bytes or
                    # end-of-stream — it would buffer a whole token
                    # stream and deliver it at EOF.
                    chunk = await loop.run_in_executor(
                        self.server.executor, resp.read1, 65536)
                except (OSError, http.client.HTTPException) as e:
                    # Replica died mid-stream — exactly the fleet event
                    # the counters exist to surface.
                    upstream_err = e
                    outcome = "upstream_error"
                    break
                if not chunk:
                    break
                self.write(chunk)
                try:
                    await self.flush()
                    if result.status < 400:
                        self._observe_flush()
                except tornado.iostream.StreamClosedError:
                    break  # caller went away; stop pulling
            self._count(name, outcome)
            self.router._bump("ok" if outcome == "ok" else "errors")
            self.fleet.observe_forward(name,
                                       time.perf_counter() - t0)
            obs.record("router.forward", t0, time.perf_counter(),
                       trace_id=trace_id, replica=name,
                       status=result.status,
                       **({"error": str(upstream_err)[:120]}
                          if upstream_err is not None else {}))
            if upstream_err is not None:
                # Headers (and chunks) are already on the wire: the
                # abrupt close below stays the honest truncation signal
                # — but where the surface has an in-band error envelope
                # (ndjson frames, SSE events), write one terminal error
                # frame first so parsing clients see the failure named
                # instead of a bare connection reset (ISSUE 14).
                ct = hdrs.get("Content-Type") or ""
                msg = (f"upstream replica {name} died mid-stream: "
                       f"{type(upstream_err).__name__}")
                frame = None
                # Leading newline: this relay forwards RAW chunks, so
                # the upstream may have died mid-line — appending the
                # envelope straight after a torn partial line would
                # make it unparseable to exactly the line-parsing
                # clients it exists for (blank lines are skipped by
                # both surfaces' parsers).
                if ct.startswith("application/x-ndjson"):
                    frame = "\n" + json.dumps({"error": msg}) + "\n"
                elif ct.startswith("text/event-stream"):
                    frame = "\n\ndata: " + json.dumps(
                        {"error": {"message": msg}}) + "\n\n"
                if frame is not None:
                    try:
                        self.write(frame)
                        await self.flush()
                    except Exception:
                        pass
                try:
                    self.request.connection.stream.close()
                except Exception:
                    pass
            else:
                try:
                    self.finish()
                except tornado.iostream.StreamClosedError:
                    pass
        finally:
            conn.close()


class AdminReplicasHandler(_RouterBase):
    def get(self):
        self.write_json({
            "replicas": self.fleet.snapshot(),
            "router": self.router.stats_snapshot(),
        })

    def post(self):
        try:
            body = json.loads(self.request.body or b"{}")
        except json.JSONDecodeError as e:
            raise tornado.web.HTTPError(400, reason=f"bad JSON: {e}") \
                from None
        name, url = body.get("name"), body.get("url")
        if not name or not url:
            raise tornado.web.HTTPError(
                400, reason="replica registration needs name and url")
        try:
            self.fleet.add(name, url, grpc=body.get("grpc"),
                           role=body.get("role", "any"))
        except ValueError as e:
            raise tornado.web.HTTPError(400, reason=str(e)) from None
        self.write_json({"added": name})


class AdminReplicaHandler(_RouterBase):
    def delete(self, name):
        self.fleet.remove(name)
        self.write_json({"removed": name})


class AdminDrainHandler(_RouterBase):
    def post(self, name):
        if not self.fleet.drain(name):
            raise tornado.web.HTTPError(
                404, reason=f"replica {name!r} not found")
        self.write_json({"draining": name})


class RouterMetricsHandler(_RouterBase):
    def get(self):
        self.set_header("Content-Type", "text/plain; version=0.0.4")
        self.finish(res_metrics.prometheus_text())


class FleetMetricsHandler(_RouterBase):
    """GET /fleet/metrics — ONE exposition for the whole fleet, merged
    from the poller's already-scraped per-replica documents (zero extra
    scrape traffic: aggregation rides the poll the fleet already pays
    for). Counters sum, gauges keep a `replica` label, same-bucket
    histograms sum bucket-wise; incompatible families answer 500 —
    refusal is the contract, silent merging never happens."""

    def get(self):
        texts = self.fleet.metrics_texts()
        try:
            merged = merge_prometheus_texts(texts)
        except MetricsMergeError as e:
            self.write_json(
                {"error": f"fleet metrics merge refused: {e}"},
                status=500)
            return
        self.set_header("Content-Type", "text/plain; version=0.0.4")
        self.finish(merged)


class FlightRecorderHandler(_RouterBase):
    """GET /admin/flightrecorder[?n=K] — the per-request outcome ring
    (most recent last) plus the chaos snapshots frozen at resume/eject
    events. Bounded by construction: `capacity` records, ever."""

    def get(self):
        raw = self.get_query_argument("n", default=None)
        n = None
        if raw is not None:
            try:
                n = int(raw)
            except ValueError:
                raise tornado.web.HTTPError(
                    400, reason=f"n must be an integer, got {raw!r}") \
                    from None
        fr = self.server.flight_recorder
        self.write_json({"records": fr.tail(n),
                         "snapshots": fr.snapshots(),
                         "capacity": fr.capacity})


class RouterTraceHandler(_RouterBase):
    """GET /debug/trace[?trace_id=] — without a trace id, this process's
    own span ring (the ISSUE-5 behavior, unchanged). WITH one:
    distributed assembly — fan out to the replicas on that request's
    flight-recorder trail (the whole fleet when the trail is unknown),
    pull each ring over the same per-replica /debug/trace surface,
    estimate each replica's clock offset from the fetch RTT midpoint,
    and serve ONE merged Chrome trace: router place/forward spans,
    prefill chunks, the shipment hop, decode chunks, and the resume
    seam on a single timeline, with the alignment error bars stated."""

    async def get(self):
        tid = self.get_query_argument("trace_id", default=None)
        if tid is None:
            self.write_json(obs.get_tracer().chrome_trace(None))
            return
        tid = obs.sanitize_trace_id(tid)
        rec = self.server.flight_recorder.lookup(tid)
        names = list((rec or {}).get("replicas") or self.fleet.names())
        loop = asyncio.get_event_loop()
        fetches = []
        for name in dict.fromkeys(names):
            url = self.fleet.url_of(name)
            if url is not None:
                fetches.append(loop.run_in_executor(
                    self.server.executor, self._fetch_replica_trace,
                    name, url, tid))
        results = await asyncio.gather(*fetches) if fetches else []
        parts = [{"process": "router",
                  "doc": obs.get_tracer().chrome_trace(tid),
                  "offset_us": 0.0, "err_us": 0.0}]
        unreachable = []
        for name, doc, offset_us, err_us, err in results:
            if err is not None:
                # A dead replica's ring died with it — say so instead
                # of silently serving a partial trace as complete.
                unreachable.append({"replica": name, "error": err})
                continue
            parts.append({"process": name, "doc": doc,
                          "offset_us": offset_us, "err_us": err_us})
        merged = obs.merge_chrome_traces(parts)
        merged["trace_id"] = tid
        if rec is not None:
            merged["flight_record"] = rec
        if unreachable:
            merged["unreachable"] = unreachable
        self.write_json(merged)

    def _fetch_replica_trace(self, name: str, url: str, tid: str):
        """One blocking per-replica ring fetch (executor only) + the
        RTT-midpoint clock-offset estimate: the replica stamps its own
        `now_us` while serving the fetch, which on OUR timeline happened
        ~at the fetch midpoint — so offset = our_midpoint - its_now,
        with half the RTT as the honest error bar. Returns
        (name, doc, offset_us, err_us, error)."""
        t0 = time.perf_counter()
        try:
            # tid came through sanitize_trace_id: URL-safe charset.
            with urllib.request.urlopen(
                    f"{url}/debug/trace?trace_id={tid}",
                    timeout=self.server.trace_timeout_s) as r:
                doc = json.loads(r.read().decode())
        except Exception as e:
            return name, None, 0.0, None, f"{type(e).__name__}: {e}"
        t1 = time.perf_counter()
        now_us = doc.get("now_us") if isinstance(doc, dict) else None
        if now_us is None:
            # Older replica without the export stamp: spans ride
            # un-shifted, marked unaligned in clock_alignment.
            return name, doc, 0.0, None, None
        mid_us = obs.perf_to_us((t0 + t1) / 2.0)
        return (name, doc, mid_us - float(now_us),
                (t1 - t0) / 2.0 * 1e6, None)


class RouterServer:
    """Hosts the proxy + admin plane; same lifecycle shape as
    ModelServer (daemon-thread ioloop, worker executor for blocking
    upstream I/O)."""

    def __init__(self, fleet: Fleet | None = None, *,
                 affinity: bool = True, spill_margin: float = 4.0,
                 forward_timeout_s: float = 300.0,
                 max_resumes: int = 3,
                 max_workers: int = 128,
                 trace_timeout_s: float = 5.0):
        self.fleet = fleet or Fleet()
        self.router = Router(self.fleet, affinity=affinity,
                             spill_margin=spill_margin)
        self.forward_timeout_s = float(forward_timeout_s)
        #: Per-replica budget for the distributed-trace fan-out fetch
        #: (a dead replica must not wedge assembly of everyone else's
        #: spans — it lands in the `unreachable` list instead).
        self.trace_timeout_s = float(trace_timeout_s)
        #: Per-request outcome ring (+ chaos snapshots). The fleet's
        #: eject transitions freeze a snapshot so postmortems keep the
        #: requests surrounding an ejection.
        self.flight_recorder = obs.FlightRecorder()
        self.fleet.on_transition = self._on_fleet_transition
        #: Mid-stream decode-failover cap (ISSUE 14): how many times one
        #: disaggregated stream may be resumed on a fresh decode replica
        #: before the router gives up with a terminal error frame.
        self.max_resumes = int(max_resumes)
        # One worker is HELD for the whole upstream round trip of one
        # in-flight request (blocking http.client forward), so the pool
        # must cover peak CONCURRENT requests, not CPU count — the
        # workers spend their lives in network waits. Threads are lazy;
        # an idle router allocates none of them.
        self.executor = ThreadPoolExecutor(
            max_workers=int(max_workers),
            thread_name_prefix="tpk-router-fwd")
        self._loop: tornado.ioloop.IOLoop | None = None
        self._thread: threading.Thread | None = None
        self.port: int | None = None
        self._grpc = None
        self.grpc_port: int | None = None

    def _on_fleet_transition(self, name: str, kind: str) -> None:
        if kind == "eject":
            self.flight_recorder.snapshot(f"eject:{name}", replica=name)

    def app(self) -> tornado.web.Application:
        kw = {"server": self}
        return tornado.web.Application([
            (r"/admin/replicas", AdminReplicasHandler, kw),
            (r"/admin/replicas/([^/]+)", AdminReplicaHandler, kw),
            (r"/admin/drain/([^/]+)", AdminDrainHandler, kw),
            (r"/admin/flightrecorder", FlightRecorderHandler, kw),
            (r"/metrics", RouterMetricsHandler, kw),
            (r"/fleet/metrics", FleetMetricsHandler, kw),
            (r"/debug/trace", RouterTraceHandler, kw),
            (r"/(.*)", ProxyHandler, kw),
        ])

    def start_grpc(self, port: int = 0) -> int:
        from kubeflow_tpu.serve.grpc_router import build_grpc_router

        self._grpc, self.grpc_port = build_grpc_router(self, port)
        self._grpc.start()
        return self.grpc_port

    def _serve(self, port: int, ready: threading.Event) -> None:
        asyncio.set_event_loop(asyncio.new_event_loop())
        self._loop = tornado.ioloop.IOLoop.current()
        sockets = tornado.netutil.bind_sockets(port, address="127.0.0.1")
        server = tornado.httpserver.HTTPServer(self.app())
        server.add_sockets(sockets)
        self.port = sockets[0].getsockname()[1]
        ready.set()
        self._loop.start()

    def start_background(self, port: int = 0) -> int:
        ready = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, args=(port, ready), daemon=True,
            name="tpk-router")
        self._thread.start()
        if not ready.wait(10.0):
            raise TimeoutError("router failed to bind")
        assert self.port is not None
        return self.port

    def run(self, port: int) -> None:
        self._serve(port, threading.Event())

    def stop(self) -> None:
        if self._grpc is not None:
            self._grpc.stop(grace=1.0).wait(1.5)
        if self._loop is not None:
            self._loop.add_callback(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        self.executor.shutdown(wait=False)
        self.fleet.close()


def main(argv: list[str] | None = None) -> int:
    """`tpk-router` entrypoint: front a static replica list (grow/shrink
    later through the admin endpoint or the autoscaler)."""
    import argparse

    p = argparse.ArgumentParser(prog="tpk-router")
    p.add_argument("--port", type=int, default=8090)
    p.add_argument("--grpc-port", type=int, default=None)
    p.add_argument("--replica", action="append", default=[],
                   metavar="NAME=URL[,GRPC][,role=ROLE]",
                   help="replica registration (repeatable); role is "
                        "any|prefill|decode (disaggregated fleets)")
    p.add_argument("--no-affinity", action="store_true",
                   help="disable prefix/adapter affinity (least-loaded "
                        "only; the A/B control)")
    p.add_argument("--spill-margin", type=float, default=4.0)
    args = p.parse_args(argv)

    server = RouterServer(affinity=not args.no_affinity,
                          spill_margin=args.spill_margin)
    for spec in args.replica:
        name, _, rest = spec.partition("=")
        if not rest:
            p.error(f"--replica must be NAME=URL[,GRPC][,role=ROLE], "
                    f"got {spec!r}")
        url, _, tail = rest.partition(",")
        grpc, role = None, "any"
        for part in (tail.split(",") if tail else []):
            if part.startswith("role="):
                role = part[len("role="):]
            elif part:
                grpc = part
        server.fleet.add(name, url, grpc=grpc, role=role)
    if args.grpc_port is not None:
        bound = server.start_grpc(args.grpc_port)
        print(json.dumps({"event": "router_grpc", "port": bound}),
              flush=True)
    print(json.dumps({"event": "router_serving", "port": args.port,
                      "replicas": server.fleet.names()}), flush=True)
    server.run(args.port)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
