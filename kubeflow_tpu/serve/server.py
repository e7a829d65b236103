"""ModelServer — the serving data plane (KServe model server equivalent).

Speaks both protocols the reference serves (⟨kserve: python/kserve —
ModelServer, v1/v2 endpoints⟩, SURVEY.md §2.2/§3.3):

  v1:  POST /v1/models/{name}:predict      {"instances": [...]}
       GET  /v1/models/{name}              readiness
       GET  /v1/models                     list
  v2:  GET  /v2/health/{live,ready}
       GET  /v2/models/{name}[/ready]      metadata / readiness
       POST /v2/models/{name}/infer        open-inference tensors
       POST /v2/repository/models/{name}/{load,unload}
  ops: GET  /metrics                       prometheus text format

Inference runs through the coalescing Batcher (batcher.py) so concurrent
requests share one padded AOT device call; handlers stay async and await the
batcher future, keeping the event loop free (the reference gets the same
effect from uvicorn workers + the agent sidecar batcher).
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import gc
import json
import math
import os
import signal
import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any

import numpy as np
import tornado.httpserver
import tornado.iostream
import tornado.ioloop
import tornado.netutil
import tornado.web

from kubeflow_tpu.serve.batcher import Batcher
from kubeflow_tpu.serve.generation import KVCapacityExceeded
from kubeflow_tpu.serve.model import Model, _v2_dtype, v2_to_numpy_dtype
# The wire header names live in serve/headers.py (dependency-free) so
# the router process can import them without paying THIS module's
# engine-stack import; they are re-exported here for compatibility.
# DEADLINE_HEADER: the KServe/Istio-style relative budget, deadline-
# propagated in-process — expiry anywhere on the request path
# (admission queue, batcher, generation) returns 504. REQUEST_ID_HEADER:
# the one trace identity (SURVEY.md §5.1 rebuild), threaded through
# admission, the batcher, and the engine spans (see /debug/trace).
from kubeflow_tpu.serve.headers import (DEADLINE_HEADER, DRAINING_HEADER,
                                        REQUEST_ID_HEADER)
from kubeflow_tpu.utils import obs
from kubeflow_tpu.utils.resilience import (Deadline, DeadlineExceeded,
                                           metrics as res_metrics)

#: GenerationEngine stats → /metrics series (ISSUE 3 observability): the
#: engine's own counters rendered per model on every scrape, so the
#: overlapped-scheduling claim (host-stall removal, overlapped fetches,
#: off-critical-path admissions) and the prefix-cache economy are
#: observable in Prometheus, not just in SERVEBENCH.json. The sentinel
#: "__depth__" row reads the engine attribute instead of a stats key.
_ENGINE_METRICS = (
    ("requests", "tpk_engine_requests_total", "counter"),
    ("prompt_tokens", "tpk_engine_prompt_tokens_total", "counter"),
    ("decode_tokens", "tpk_engine_decode_tokens_total", "counter"),
    ("decode_dispatches", "tpk_decode_dispatch_total", "counter"),
    ("prefix_hits", "tpk_engine_prefix_hits_total", "counter"),
    ("prefix_hit_tokens", "tpk_engine_prefix_hit_tokens_total", "counter"),
    ("prefix_misses", "tpk_engine_prefix_misses_total", "counter"),
    ("host_stall_seconds", "tpk_engine_host_stall_seconds_total",
     "counter"),
    ("admit_overlap", "tpk_admit_overlap_total", "counter"),
    ("decode_fetch_blocking", "tpk_engine_decode_fetch_blocking_total",
     "counter"),
    ("decode_fetch_overlapped",
     "tpk_engine_decode_fetch_overlapped_total", "counter"),
    ("decode_wasted_tokens", "tpk_engine_decode_wasted_tokens_total",
     "counter"),
    ("spec_dispatches", "tpk_engine_spec_dispatch_total", "counter"),
    # Speculative decoding observability (ISSUE 18): proposal/accept
    # volume and stale draft rides per model, so the sub-batch split
    # ("mixed traffic still speculates") and draft quality are
    # observable in production, not just in SERVEBENCH.json. The
    # accept-rate gauge is computed at scrape (accepted/proposed) and
    # only emitted once proposals flowed — draft-less engines and
    # idle spec engines emit no rate, never a fake 0.
    ("spec_proposed", "tpk_spec_proposed_total", "counter"),
    ("spec_accepted", "tpk_spec_accepted_total", "counter"),
    ("spec_stale_rides", "tpk_spec_stale_rides_total", "counter"),
    ("__spec_accept_rate__", "tpk_spec_accept_rate", "gauge"),
    # Paged KV cache (ISSUE 6): prefix hits served as zero-copy block
    # references, copy-on-write tail-block forks, and the live pool
    # occupancy admission decides by. Flat engines (kv_block_size=0)
    # emit the counters at 0 and skip the pool gauges.
    ("kv_cow_copies", "tpk_kv_cow_copies_total", "counter"),
    ("prefix_zero_copy_hits", "tpk_prefix_zero_copy_hits_total",
     "counter"),
    ("__kv_free__", "tpk_kv_blocks_free", "gauge"),
    ("__kv_used__", "tpk_kv_blocks_used", "gauge"),
    # Disaggregated prefill/decode + host-RAM spill tier (ISSUE 13):
    # prefill-chunk dispatches (a decode-role replica must read 0 —
    # the DISAGGBENCH mechanism pin), shipped/received wire blocks,
    # remote admissions, spill-tier traffic and residency.
    ("prefill_chunks", "tpk_engine_prefill_chunks_total", "counter"),
    ("remote_admits", "tpk_engine_remote_admits_total", "counter"),
    ("kv_blocks_shipped", "tpk_kv_blocks_shipped_total", "counter"),
    ("kv_blocks_received", "tpk_kv_blocks_received_total", "counter"),
    ("kv_spilled_blocks", "tpk_kv_spilled_blocks_total", "counter"),
    ("kv_restored_blocks", "tpk_kv_restored_blocks_total", "counter"),
    ("__kv_spill__", "tpk_kv_spill_blocks", "gauge"),
    # Quantized KV blocks (ISSUE 19): admission-side full-width dequant
    # materializations (prefix-hit fragment rebuilds — the ONE place
    # the quantized design allows one; the decode scan never pays it).
    # The mode itself renders as the tpk_kv_quant_mode info gauge
    # below, next to tpk_engine_role.
    ("kv_dequant_fallbacks", "tpk_kv_dequant_fallbacks_total",
     "counter"),
    # Live in-flight dispatch count (0 when drained; stuck at ≤1 means
    # the pipeline re-serialized) vs the configured ceiling.
    ("__inflight__", "tpk_decode_inflight_depth", "gauge"),
    ("__depth__", "tpk_engine_pipeline_depth", "gauge"),
)


class AdmissionController:
    """Bounded admission for the inference data plane — the KServe/
    Knative containerConcurrency + activator-queue behavior, in-process.

    At most `max_inflight` inference requests are admitted concurrently
    (admitted = queued in a batcher/engine OR executing). Beyond that the
    server SHEDS: 503 + `Retry-After` instead of unbounded queueing, and
    the readiness probe degrades (`/v2/health/ready` → 503) while the
    replica is actively rejecting work so the platform's LB/controller
    routes around it — fail fast and visibly, never silently queue into
    timeout. Merely being full does NOT degrade readiness (Knative's
    queue-proxy stays ready at containerConcurrency): a single long
    request on a small-capacity replica must not pull it from endpoints
    when nothing was rejected."""

    def __init__(self, max_inflight: int = 256,
                 retry_after_s: float = 1.0):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got "
                             f"{max_inflight}")
        self.max_inflight = int(max_inflight)
        self.retry_after_s = float(retry_after_s)
        self._inflight = 0  # guarded-by: _lock
        self._last_shed = -float("inf")  # guarded-by: _lock
        self._lock = threading.Lock()

    def try_acquire(self, component: str = "serve") -> bool:
        """`component` labels the shed counter with the data plane that
        hit the gate (serve vs serve_grpc), mirroring the deadline
        counter's surface labels."""
        with self._lock:
            if self._inflight >= self.max_inflight:
                self._last_shed = time.monotonic()
                res_metrics.inc("tpk_shed_total", component=component)
                return False
            self._inflight += 1
            return True

    def release(self) -> None:
        with self._lock:
            self._inflight -= 1

    def note_shed(self, component: str = "serve") -> None:
        """Record an out-of-band shed — e.g. the generation engine
        refusing a request whose worst-case paged-KV footprint can never
        fit its pool — so the shed counter and the readiness-degradation
        window see it exactly like a queue-full rejection."""
        with self._lock:
            self._last_shed = time.monotonic()
        res_metrics.inc("tpk_shed_total", component=component)

    @property
    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    @property
    def shedding(self) -> bool:
        """True while the replica is at capacity AND rejected a request
        within the last `retry_after_s` (readiness degrades): degradation
        tracks actual rejections, so a full-but-quiet replica stays in
        the endpoint set and recovers the moment load drains."""
        with self._lock:
            return (self._inflight >= self.max_inflight
                    and time.monotonic() - self._last_shed
                    <= self.retry_after_s)


class ModelRepository:
    """Name → Model with load/unload — the multi-model surface the reference
    exposes via its repository API + agent model puller."""

    #: Seconds a replaced model version stays loaded after a swap so
    #: in-flight requests against it finish (class attr: tests shrink it).
    UNLOAD_GRACE_S = 10.0

    def __init__(self):
        self._models: dict[str, Model] = {}
        self._batchers: dict[str, Batcher] = {}
        self._dirs: dict[str, str] = {}
        self._meshes: dict[str, dict] = {}
        self._load_errors: dict[str, str] = {}
        # Async-load intents: name -> wanted model_dir ("" = unload was
        # requested mid-load; the worker discards its result).
        self._want: dict[str, str] = {}
        self._inflight: set[str] = set()
        self._lock = threading.Lock()

    def register(self, model: Model, *, load: bool = True,
                 max_batch_size: int = 32, max_latency_ms: float = 5.0,
                 model_dir: str | None = None,
                 mesh: dict | None = None) -> Model:
        if load and not model.ready:
            model.load()
        with self._lock:
            old_model = self._models.get(model.name)
            self._models[model.name] = model
            if model_dir:
                self._dirs[model.name] = model_dir
            if mesh:
                # Remembered per name so every RELOAD path (load(),
                # load_async() on a model_dir update) re-applies the
                # tensor-parallel layout — a TP model silently reloaded
                # single-device would OOM on real hardware.
                self._meshes[model.name] = dict(mesh)
            else:
                # A meshless registration is an intent change (e.g. the
                # name now points at a single-device or non-generative
                # bundle): drop the remembered mesh or it would be
                # re-applied to a bundle it no longer fits.
                self._meshes.pop(model.name, None)
            old = self._batchers.pop(model.name, None)
            self._batchers[model.name] = Batcher(
                model.predict, max_batch_size=max_batch_size,
                max_latency_ms=max_latency_ms)
        if old:
            old.close()
        if old_model is not None and old_model is not model:
            # Drop the replaced version's device buffers/AOT executables —
            # without this, a TrainedModel version swap keeps BOTH
            # versions resident until GC, which can OOM HBM-constrained
            # serving. Deferred by a grace window so requests that
            # grabbed the old model just before the swap (e.g. oversized
            # calls that bypass the drained batcher) finish first; a
            # request still running after the grace sees the same cut a
            # rolling pod replacement would give it. The callback
            # re-checks the live registration: a rollback can re-register
            # the same object inside the grace window, and unloading it
            # then would kill the now-live model.
            def _deferred_unload(name=model.name, old=old_model):
                # Check-and-unload under the lock so it serializes with a
                # concurrent rollback's install; the post-install re-load
                # below covers the remaining interleaving.
                with self._lock:
                    if self._models.get(name) is old:
                        return  # rolled back — old is live again
                    old.unload()
                gc.unfreeze()  # what it leaves is the collector's again

            t = threading.Timer(self.UNLOAD_GRACE_S, _deferred_unload)
            t.daemon = True  # never delays interpreter exit
            t.start()
        if load and not model.ready:
            # A stale grace-window timer from an earlier swap can unload
            # this object between our readiness check above and the
            # install; now that we ARE the live registration any later
            # timer spares us, so one re-load makes this race-free.
            model.load()
        return model

    def get(self, name: str) -> Model:
        try:
            return self._models[name]
        except KeyError:
            raise tornado.web.HTTPError(
                404, reason=f"model {name!r} not found") from None

    def batcher(self, name: str) -> Batcher:
        self.get(name)
        return self._batchers[name]

    def names(self) -> list[str]:
        return sorted(self._models)

    def load(self, name: str) -> Model:
        """(Re)load by name — from its recorded model dir if registered that
        way, else by flipping the in-process model's lifecycle."""
        with self._lock:
            model_dir = self._dirs.get(name)
            mesh = self._meshes.get(name)
        if model_dir:
            from kubeflow_tpu.serve import runtimes
            model = runtimes.load_model(model_dir, name=name, mesh=mesh)
            return self.register(model, model_dir=model_dir, mesh=mesh)
        model = self.get(name)
        model.load()
        return model

    def load_async(self, name: str, model_dir: str) -> None:
        """Attach a new model from `model_dir` in a background thread (the
        TrainedModel path): AOT compiles take seconds, and the control
        plane's POST must return immediately — the controller polls
        /v2/models/{name}/ready until the load lands. Latest intent wins:
        a newer model_dir (or an unload) arriving mid-load supersedes the
        in-flight result instead of being dropped."""
        with self._lock:
            self._want[name] = model_dir
            self._load_errors.pop(name, None)
            if name in self._inflight:
                return  # the worker re-checks _want when it finishes
            self._inflight.add(name)

        def work():
            from kubeflow_tpu.serve import runtimes

            while True:
                with self._lock:
                    target = self._want.get(name, "")
                    if not target:  # unloaded / intent cleared mid-load
                        self._inflight.discard(name)
                        return
                with self._lock:
                    mesh = self._meshes.get(name)
                try:
                    model = runtimes.load_model(target, name=name,
                                                mesh=mesh)
                except Exception as e:
                    # Exit decisions happen under the SAME lock that
                    # releases _inflight — a concurrent load_async either
                    # sees us in flight (and we loop on the new intent)
                    # or sees us gone (and starts its own worker).
                    with self._lock:
                        if self._want.get(name, "") == target:
                            self._load_errors[name] = (
                                f"{type(e).__name__}: {e}")
                            self._inflight.discard(name)
                            return
                    continue  # intent changed while failing: retry
                with self._lock:
                    if self._want.get(name, "") != target:
                        continue  # newer dir (or unload) requested: redo
                self.register(model, model_dir=target, mesh=mesh)
                with self._lock:
                    want_now = self._want.get(name, "")
                    if want_now == target:
                        self._inflight.discard(name)
                        return
                if not want_now:  # unload arrived during register
                    self.get(name).unload()
                    with self._lock:
                        if not self._want.get(name, ""):
                            self._inflight.discard(name)
                            return
                # newer dir requested: loop to load it

        threading.Thread(target=work, daemon=True,
                         name=f"tpk-load-{name}").start()

    def loading_error(self, name: str) -> str | None:
        with self._lock:
            return self._load_errors.get(name)

    def model_dir(self, name: str) -> str | None:
        with self._lock:
            return self._dirs.get(name)

    def unload(self, name: str) -> None:
        with self._lock:
            in_flight = name in self._inflight
            self._want[name] = ""  # cancels an in-flight load
            self._load_errors.pop(name, None)
            known = name in self._models
        if not known:
            if in_flight:
                return  # cancelled before it ever registered
            raise tornado.web.HTTPError(
                404, reason=f"model {name!r} not found")
        self.get(name).unload()
        gc.unfreeze()  # what it leaves is the collector's again

    def close(self) -> None:
        for b in self._batchers.values():
            b.close()


# -- handlers ---------------------------------------------------------------


async def pump_stream(handler, it, render, render_error) -> None:
    """Drive a blocking generator into a chunked HTTP response: one
    executor hop per event, shared by the ndjson :generate stream and the
    OpenAI SSE surfaces. Pre-stream failures raise clean HTTP errors
    (ValueError/RuntimeError → 400 request faults; anything else → 500 so
    bugs hit server-side monitoring, matching the non-stream path);
    mid-stream failures become a terminal `render_error` frame (the
    status line is already on the wire). A client disconnect closes the
    generator (the engine still decodes the request to completion — no
    cancellation in v1). `render(ev, first) -> bool` writes frames and
    returns True to end the stream."""
    _END = object()

    def step():
        try:
            return ("ev", next(it, _END))
        except DeadlineExceeded as e:
            return ("expired", f"{type(e).__name__}: {e}")
        except KVCapacityExceeded as e:
            # Before ValueError/RuntimeError: pool exhaustion is an
            # overload shed, not a bad request — same 503 contract as
            # the non-stream paths.
            return ("shed", str(e))
        except (ValueError, RuntimeError) as e:
            return ("badreq", f"{type(e).__name__}: {e}")
        except Exception as e:
            return ("err", f"{type(e).__name__}: {e}")

    loop = asyncio.get_event_loop()
    kind, ev = await loop.run_in_executor(None, step)
    if kind == "expired":
        # Streams surface the expiry here (once per request — the inner
        # layers only free resources, they never count).
        res_metrics.inc("tpk_deadline_expired_total", component="serve")
        raise tornado.web.HTTPError(504, reason=ev)
    if kind == "shed":
        # Pre-stream shed (submit refused before any frame went out).
        handler.write_capacity_shed(ev)
        return
    if kind == "badreq":
        raise tornado.web.HTTPError(400, reason=ev)
    if kind == "err":
        raise tornado.web.HTTPError(500, reason=ev)
    first = True
    try:
        while ev is not _END:
            if kind != "ev":
                if kind == "expired":
                    # Mid-stream expiry: status line already went out, so
                    # the 504 becomes a terminal error frame — but it is
                    # still one expired request for the counter.
                    res_metrics.inc("tpk_deadline_expired_total",
                                    component="serve")
                handler.write(render_error(ev))
                await handler.flush()
                break
            done = render(ev, first)
            first = False
            await handler.flush()
            if done:
                break
            kind, ev = await loop.run_in_executor(None, step)
    except tornado.iostream.StreamClosedError:
        it.close()


class _Base(tornado.web.RequestHandler):
    def initialize(self, server: "ModelServer"):
        self.server = server
        self.repo = server.repo

    def prepare(self) -> None:
        # One trace id per request, caller-set or assigned, echoed back —
        # every span this request opens downstream carries it.
        self.trace_id = obs.sanitize_trace_id(
            self.request.headers.get(REQUEST_ID_HEADER))
        self.set_header(REQUEST_ID_HEADER, self.trace_id)

    def write_json(self, obj: Any, status: int = 200) -> None:
        self.set_status(status)
        self.set_header("Content-Type", "application/json")
        self.finish(json.dumps(obj))

    def body_json(self) -> dict:
        try:
            return json.loads(self.request.body or b"{}")
        except json.JSONDecodeError as e:
            raise tornado.web.HTTPError(400, reason=f"bad JSON: {e}") from None

    # -- resilience (deadline + admission) ----------------------------------

    def request_deadline(self) -> Deadline | None:
        """The request's end-to-end budget from DEADLINE_HEADER (None
        when the client set none)."""
        raw = self.request.headers.get(DEADLINE_HEADER)
        if raw is None:
            return None
        try:
            ms = float(raw)
            # NaN/inf would defeat every expiry comparison downstream.
            if not math.isfinite(ms) or ms <= 0:
                raise ValueError
        except ValueError:
            raise tornado.web.HTTPError(
                400, reason=f"{DEADLINE_HEADER} must be a positive "
                            f"number of milliseconds, got {raw!r}") \
                from None
        return Deadline(ms / 1e3)

    def admit(self) -> bool:
        """Admission-gate an inference request. False = the 503 +
        Retry-After shed response has been written; the caller must
        return without releasing. True = admitted; the caller owns one
        release()."""
        if self.server.draining:
            # Drain rejection, NOT an overload shed: marked with
            # DRAINING_HEADER so the front-door router retries the
            # request on a surviving replica instead of forwarding the
            # 503 as backpressure. In-flight requests (already past this
            # gate) keep running to completion.
            self.set_header("Retry-After", "1")
            self.set_header(DRAINING_HEADER, "1")
            self.write_json(self.capacity_body("replica draining"),
                            status=503)
            return False
        adm = self.server.admission
        with obs.span("serve.admit", trace_id=self.trace_id,
                      path=self.request.path) as sp:
            if adm is None or adm.try_acquire():
                sp.set(admitted=True)
                return True
            sp.set(admitted=False)
        self.set_header("Retry-After",
                        str(max(int(adm.retry_after_s), 1)))
        self.write_json(self.shed_body(), status=503)
        return False

    def shed_body(self) -> dict:
        """The 503 shed response body — facades with their own error
        envelope (OpenAI) override this so SDK clients can parse it."""
        return {"error": "server overloaded: admission queue full"}

    def capacity_body(self, msg: str) -> dict:
        """503 body for a paged-KV capacity shed (KVCapacityExceeded) —
        same override contract as shed_body."""
        return {"error": msg}

    def write_capacity_shed(self, msg: str) -> None:
        """THE shed path for paged-KV capacity refusals, shared by every
        HTTP surface (native :generate, streaming, OpenAI): count it
        like a queue-full rejection (tpk_shed_total + the readiness
        window), then write 503 + Retry-After with this surface's
        envelope. Written directly — send_error would clear the
        Retry-After header."""
        adm = self.server.admission
        if adm is not None:
            adm.note_shed("serve")
        else:
            res_metrics.inc("tpk_shed_total", component="serve")
        self.set_header("Retry-After", "1")
        self.write_json(self.capacity_body(msg), status=503)

    def _release(self) -> None:
        adm = self.server.admission
        if adm is None:
            return
        held_by = getattr(self, "_slot_rides_with", None)
        if held_by is not None:
            # The request 504'd but its blocking work may still be
            # running: the admission slot stays held until the work
            # really finishes (immediately, if it was cancelled in the
            # queue) — so max_inflight bounds CONCURRENT WORK, not just
            # concurrent waiting callers.
            held_by.add_done_callback(lambda _f: adm.release())
        else:
            adm.release()

    def submit_blocking(self, fn, *args) -> Future:
        """Run `fn(*args)` on the server's worker pool, returning the
        concurrent future. Gated handlers use this instead of
        run_in_executor so await_bounded can tie the admission slot to
        the work's true completion on expiry."""
        return self.server.executor.submit(fn, *args)

    async def await_bounded(self, fut, deadline: Deadline | None):
        """Await a (concurrent or asyncio) future under the request
        deadline; expiry — whether raised by the work itself (batcher
        queue pruning) or by the clock here — maps to 504. The work is
        not preempted mid-dispatch; instead an expired request's
        admission slot rides the concurrent future to completion, so the
        gate still bounds total concurrent work."""
        cfut = fut if isinstance(fut, Future) else None
        if cfut is not None:
            fut = asyncio.wrap_future(cfut)
        if deadline is None:
            # No budget: the work's own errors (including a model-raised
            # TimeoutError) must keep their 500 path, not map to 504.
            return await fut
        rem = deadline.remaining()
        if rem is None:  # Deadline.never(): unbounded, same as None
            return await fut
        try:
            return await asyncio.wait_for(fut, max(rem, 0.0))
        except (asyncio.TimeoutError, DeadlineExceeded) as e:
            if (not isinstance(e, DeadlineExceeded)
                    and not deadline.expired()):
                # On py3.11+ asyncio.TimeoutError IS builtin
                # TimeoutError, so a timeout raised by the work's own
                # internals lands here too — with budget left it is a
                # server fault (500 path), not an expired deadline.
                raise
            if cfut is not None:
                self._slot_rides_with = cfut
            # This surface raises at most once per request, and the
            # inner layers (batcher prune, engine sweep) never count —
            # so the counter is exactly one increment per expiry.
            res_metrics.inc("tpk_deadline_expired_total",
                            component="serve")
            raise tornado.web.HTTPError(
                504, reason=f"request deadline exceeded "
                            f"({type(e).__name__})") from e

    def write_error(self, status_code: int, **kwargs) -> None:
        reason = self._reason
        if "exc_info" in kwargs:
            exc = kwargs["exc_info"][1]
            if not isinstance(exc, tornado.web.HTTPError):
                reason = f"{type(exc).__name__}: {exc}"
        self.write_json({"error": reason}, status=status_code)

    def on_finish(self) -> None:
        # Inference traffic only — health/metadata probes and repository
        # control calls would pollute the data-plane log (the reference's
        # logger samples the data plane, not the control plane).
        rl = self.server.request_logger
        path = self.request.path
        if (rl is not None and self.request.method == "POST"
                and (path.endswith(":predict") or path.endswith(":generate")
                     or path.endswith(":prefill") or path.endswith(":decode")
                     or path.endswith("/infer")
                     or path.endswith("/generate"))):
            args = self.path_args or (None,)
            rl.log(self, args[0])


def admission_gated(method):
    """Wrap an async inference handler method behind the admission gate:
    shed (503 already written) or run with a guaranteed release. Every
    inference entry point uses this ONE wrapper, so a new handler can't
    silently become an unbounded side door around --max-inflight."""
    @functools.wraps(method)
    async def gated(self, *args, **kwargs):
        if not self.admit():
            return
        try:
            return await method(self, *args, **kwargs)
        finally:
            self._release()
    return gated


class V1ListHandler(_Base):
    def get(self):
        self.write_json({"models": self.repo.names()})


class V1ModelHandler(_Base):
    def get(self, name: str):
        model = self.repo.get(name)
        if not model.ready:
            raise tornado.web.HTTPError(
                503, reason=f"model {name!r} not ready")
        self.write_json({"name": name, "ready": model.ready})


class V1PredictHandler(_Base):
    @admission_gated
    async def post(self, name: str):
        model = self.repo.get(name)
        deadline = self.request_deadline()
        body = model.preprocess(self.body_json())
        instances = body.get("instances")
        if instances is None:
            raise tornado.web.HTTPError(
                400, reason='v1 request needs "instances"')
        t0 = time.monotonic()
        if getattr(model, "wants_raw_payload", False):
            # InferenceGraphs take the whole JSON body (routing fields
            # included) and bypass the batcher — per-request routing can't
            # survive cross-request coalescing.
            out = await self.await_bounded(
                self.submit_blocking(model.predict, body), deadline)
            preds = out.get("instances") if isinstance(out, dict) else out
            self.server.observe(name, len(instances),
                                time.monotonic() - t0)
            self.write_json({"predictions": np.asarray(preds).tolist()})
            return
        # v1 protocol is single-tensor: "instances" stack along batch dim 0.
        spec = getattr(model, "input_spec", None)
        inputs = [np.asarray(instances, dtype=spec[0][1] if spec else None)]
        fut = self.repo.batcher(name).submit(inputs, deadline=deadline,
                                             trace_id=self.trace_id)
        outs = await self.await_bounded(fut, deadline)
        outs = model.postprocess(outs)
        self.server.observe(name, len(instances), time.monotonic() - t0)
        preds = outs[0] if isinstance(outs, (list, tuple)) else outs
        self.write_json({"predictions": np.asarray(preds).tolist()})


class V1ExplainHandler(_Base):
    """POST /v1/models/{name}:explain — the reference's v1 explain verb
    (explainer component), served by the model's attached native explainer
    (serve/explain.py). 501 when the model has none configured."""

    @admission_gated
    async def post(self, name: str):
        model = self.repo.get(name)
        deadline = self.request_deadline()
        # Same preprocess as :predict — explanations must be computed on
        # the input the model actually serves.
        body = model.preprocess(self.body_json())
        instances = body.get("instances")
        if instances is None:
            raise tornado.web.HTTPError(
                400, reason='v1 request needs "instances"')
        spec = getattr(model, "input_spec", None)
        t0 = time.monotonic()
        try:
            arr = np.asarray(instances, dtype=spec[0][1] if spec else None)
            out = await self.await_bounded(
                self.submit_blocking(model.explain, arr), deadline)
        except NotImplementedError as e:
            raise tornado.web.HTTPError(501, reason=str(e))
        except (ValueError, TypeError) as e:
            # TypeError is the AOT executable refusing a wrong-shaped
            # instance (per-example shape is static) — a client error.
            raise tornado.web.HTTPError(400, reason=str(e))
        self.server.observe(name, len(out), time.monotonic() - t0)
        self.write_json({"explanations": out})


class GenerateHandler(_Base):
    """POST /v1/models/{name}:generate and /v2/models/{name}/generate —
    the generative data plane (KServe huggingfaceserver's generate surface).
    Body: {"input_ids": [...] | "text": "...", "max_tokens", "temperature",
    "eos_id"}. Bypasses the coalescing batcher: the generation engine does
    its own continuous batching across concurrent requests."""

    @admission_gated
    async def post(self, name: str):
        model = self.repo.get(name)
        gen = getattr(model, "generate", None)
        if gen is None:
            raise tornado.web.HTTPError(
                400, reason=f"model {name!r} is not generative")
        body = self.body_json()
        # "_deadline"/"_trace" are in-process fields only: a wire-supplied
        # value would reach the engine as a non-Deadline / spoofed trace.
        body.pop("_deadline", None)
        body.pop("_trace", None)
        body["_trace"] = self.trace_id
        deadline = self.request_deadline()
        if deadline is not None:
            # In-process deadline propagation: the engine checks the SAME
            # object at admission and every chunk boundary, so an expired
            # request frees its decode slot instead of burning the batch.
            body["_deadline"] = deadline
        t0 = time.monotonic()
        if body.get("stream"):
            await self._stream(name, model, body, t0)
            return
        try:
            out = await self.await_bounded(
                self.submit_blocking(gen, body), deadline)
        except KVCapacityExceeded as e:
            # Paged-KV exhaustion is an overload shed, not a bad request
            # (the spec is valid; THIS replica's pool is too small).
            self.write_capacity_shed(str(e))
            return
        except (ValueError, RuntimeError) as e:
            raise tornado.web.HTTPError(400, reason=str(e)) from None
        self.server.observe(name, out.get("num_output_tokens", 0),
                            time.monotonic() - t0)
        self.write_json({"model_name": name, **out})

    async def _stream(self, name: str, model, body: dict, t0: float):
        """"stream": true → newline-delimited JSON events flushed as the
        engine emits chunks (tornado chunked transfer; the KServe/vLLM
        streaming generate surface), via the shared pump_stream helper."""
        stream_fn = getattr(model, "generate_stream", None)
        if stream_fn is None:
            raise tornado.web.HTTPError(
                400, reason=f"model {name!r} does not stream")
        it = stream_fn(body)
        tokens_out = 0

        def render(ev, first):
            nonlocal tokens_out
            if first:
                self.set_header("Content-Type", "application/x-ndjson")
            tokens_out += len(ev.get("tokens", ()))
            self.write(json.dumps({"model_name": name, **ev}) + "\n")
            return bool(ev.get("done"))

        def render_error(msg):
            return json.dumps({"model_name": name, "error": msg}) + "\n"

        await pump_stream(self, it, render, render_error)
        self.server.observe(name, tokens_out, time.monotonic() - t0)


#: Content type of a KV shipment (serve/kv_transfer.py wire format) —
#: the router relays these bytes opaquely between prefill and decode
#: replicas.
KV_SHIPMENT_CONTENT_TYPE = "application/x-tpk-kv"


class PrefillHandler(_Base):
    """POST /v1/models/{name}:prefill — disaggregation phase 1 (ISSUE
    13): the :generate request body in, a binary KV shipment out. The
    router (or any caller) forwards those bytes to a decode replica's
    :decode; the prefill replica's pool holds nothing for this request
    once the response is on the wire."""

    @admission_gated
    async def post(self, name: str):
        model = self.repo.get(name)
        ship = getattr(model, "prefill_ship", None)
        if ship is None:
            raise tornado.web.HTTPError(
                400, reason=f"model {name!r} cannot prefill-ship "
                            "(not generative, or no paged KV pool)")
        body = self.body_json()
        body.pop("_deadline", None)
        body.pop("_trace", None)
        body["_trace"] = self.trace_id
        deadline = self.request_deadline()
        if deadline is not None:
            body["_deadline"] = deadline
        t0 = time.monotonic()
        try:
            out = await self.await_bounded(
                self.submit_blocking(ship, body), deadline)
        except KVCapacityExceeded as e:
            self.write_capacity_shed(str(e))
            return
        except (ValueError, RuntimeError) as e:
            raise tornado.web.HTTPError(400, reason=str(e)) from None
        self.server.observe(name, out.get("num_input_tokens", 0),
                            time.monotonic() - t0)
        self.set_header("Content-Type", KV_SHIPMENT_CONTENT_TYPE)
        self.finish(out["shipment"])


class DecodeHandler(_Base):
    """POST /v1/models/{name}:decode — disaggregation phase 2: a KV
    shipment in, the :generate response shape out (streaming when the
    original caller asked to stream — the flag rides the shipment
    metadata). The engine admits the shipped blocks straight into
    decode; this replica never runs a prefill chunk."""

    @admission_gated
    async def post(self, name: str):
        from kubeflow_tpu.serve.kv_transfer import (ShipmentError,
                                                    peek_meta)

        model = self.repo.get(name)
        dec = getattr(model, "decode_remote", None)
        if dec is None:
            raise tornado.web.HTTPError(
                400, reason=f"model {name!r} cannot decode a shipment "
                            "(not generative, or no paged KV pool)")
        shipment = self.request.body or b""
        try:
            meta = peek_meta(shipment)
        except ShipmentError as e:
            raise tornado.web.HTTPError(
                400, reason=f"bad KV shipment: {e}") from None
        if meta.get("trace") and \
                REQUEST_ID_HEADER not in self.request.headers:
            # The router stamps the caller's trace id into the shipment
            # meta: a :decode POST without an explicit X-Request-Id
            # (direct tooling, older routers' resumes) still joins the
            # caller's distributed trace. A forwarded header wins — the
            # router already threads the id on its own requests.
            self.trace_id = obs.sanitize_trace_id(str(meta["trace"]))
            self.set_header(REQUEST_ID_HEADER, self.trace_id)
        deadline = self.request_deadline()
        t0 = time.monotonic()
        if (meta.get("extra") or {}).get("stream"):
            it = model.decode_remote_stream(shipment, deadline=deadline,
                                            trace_id=self.trace_id)
            tokens_out = 0

            def render(ev, first):
                nonlocal tokens_out
                if first:
                    self.set_header("Content-Type",
                                    "application/x-ndjson")
                tokens_out += len(ev.get("tokens", ()))
                self.write(json.dumps({"model_name": name, **ev}) + "\n")
                return bool(ev.get("done"))

            def render_error(msg):
                return json.dumps({"model_name": name,
                                   "error": msg}) + "\n"

            await pump_stream(self, it, render, render_error)
            self.server.observe(name, tokens_out, time.monotonic() - t0)
            return
        try:
            out = await self.await_bounded(
                self.submit_blocking(
                    functools.partial(dec, shipment, deadline=deadline,
                                      trace_id=self.trace_id)),
                deadline)
        except KVCapacityExceeded as e:
            self.write_capacity_shed(str(e))
            return
        except (ValueError, RuntimeError) as e:
            raise tornado.web.HTTPError(400, reason=str(e)) from None
        self.server.observe(name, out.get("num_output_tokens", 0),
                            time.monotonic() - t0)
        self.write_json({"model_name": name, **out})


class V2HealthHandler(_Base):
    def get(self, kind: str):
        if kind == "ready":
            ready, why = self.server.readiness()
            if not ready:
                raise tornado.web.HTTPError(503, reason=why)
        self.write_json({"live" if kind == "live" else "ready": True})


class V2ModelHandler(_Base):
    def get(self, name: str, sub: str = ""):
        # A failed background load (load_async) answers here so the
        # controller polling readiness sees the error, not a bare 404 —
        # but never at the expense of a live model: if a previous version
        # is still registered and serving, report ITS state (the failed
        # re-load surfaces via the controller's repost cycle instead).
        if name not in self.repo.names():
            err = self.repo.loading_error(name)
            if err:
                raise tornado.web.HTTPError(
                    503, reason=f"model {name!r} failed to load: {err}")
        model = self.repo.get(name)
        if sub == "/ready":
            if not model.ready:
                raise tornado.web.HTTPError(
                    503, reason=f"model {name!r} not ready")
            # model_dir lets version-aware clients (the TrainedModel
            # controller) distinguish "old version still serving" from
            # "my re-load landed".
            self.write_json({"name": name, "ready": True,
                             "model_dir": self.repo.model_dir(name)})
        else:
            self.write_json(model.metadata())


class V2InferHandler(_Base):
    @admission_gated
    async def post(self, name: str):
        model = self.repo.get(name)
        deadline = self.request_deadline()
        body = model.preprocess(self.body_json())
        tensors = body.get("inputs")
        if not tensors:
            raise tornado.web.HTTPError(400, reason='v2 request needs "inputs"')
        inputs = []
        for t in tensors:
            dtype = v2_to_numpy_dtype(t.get("datatype", "FP32"))
            arr = np.asarray(t["data"], dtype=dtype).reshape(t["shape"])
            inputs.append(arr)
        t0 = time.monotonic()
        if getattr(model, "wants_raw_payload", False):
            # Graph path: first tensor becomes "instances"; v2 request
            # parameters ride along as routing fields.
            payload = dict(body.get("parameters") or {})
            payload["instances"] = inputs[0]
            out = await self.await_bounded(
                self.submit_blocking(model.predict, payload), deadline)
            outs = [out.get("instances") if isinstance(out, dict) else out]
        else:
            fut = self.repo.batcher(name).submit(inputs, deadline=deadline,
                                                 trace_id=self.trace_id)
            outs = await self.await_bounded(fut, deadline)
        outs = model.postprocess(outs)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        self.server.observe(name, int(inputs[0].shape[0]),
                            time.monotonic() - t0)
        self.write_json({
            "model_name": name, "id": body.get("id", ""),
            "outputs": [{
                "name": f"output_{i}", "shape": list(np.shape(o)),
                "datatype": _v2_dtype(str(np.asarray(o).dtype)),
                "data": np.asarray(o).ravel().tolist(),
            } for i, o in enumerate(outs)]})


class RepositoryHandler(_Base):
    def post(self, name: str, verb: str):
        if verb == "load":
            # A body {"model_dir": ...} attaches a NEW model to this
            # running server (the TrainedModel / agent model-puller path:
            # ⟨kserve: pkg/apis/serving/v1alpha1 — TrainedModel⟩). The
            # load runs in the background (AOT compiles take seconds) —
            # 202 now, poll /v2/models/{name}/ready. Bodyless load
            # re-loads a known model synchronously.
            model_dir = self.body_json().get("model_dir")
            if model_dir:
                self.repo.load_async(name, model_dir)
                self.write_json({"name": name, "state": "LOADING"},
                                status=202)
                return
            self.repo.load(name)
        else:
            self.repo.unload(name)
        self.write_json({"name": name, "state":
                         "READY" if verb == "load" else "UNAVAILABLE"})


class RepositoryIndexHandler(_Base):
    def post(self):
        out = []
        for name in self.repo.names():
            m = self.repo.get(name)
            out.append({"name": name,
                        "state": "READY" if m.ready else "UNAVAILABLE"})
        self.write_json(out)


class MetricsHandler(_Base):
    def get(self):
        self.set_header("Content-Type", "text/plain; version=0.0.4")
        self.finish(self.server.prometheus_text())


class DebugTraceHandler(_Base):
    """GET /debug/trace[?trace_id=...] — the process's span ring as
    Chrome trace-event JSON (load in chrome://tracing / Perfetto). One
    slow request is diagnosable by filtering its X-Request-Id: admit →
    batch-gather → prefill → per-chunk decode → fetch spans all carry
    it. Bounded ring, so this is always a small read."""

    def get(self):
        tid = self.get_query_argument("trace_id", default=None)
        self.write_json(obs.get_tracer().chrome_trace(tid))


class RequestLogger:
    """Inference request log — the KServe agent logger equivalent (⟨kserve:
    pkg/agent — request logger⟩, SURVEY.md §2.2). The reference emits
    CloudEvents to a sink URL; here each request appends one JSONL record
    to a local file (ts, path, model, status, latency, sizes; payloads too
    in mode="all"), which the platform's log plumbing ships like any other
    worker log."""

    def __init__(self, path: str, mode: str = "metadata"):
        if mode not in ("metadata", "all"):
            raise ValueError(f"request log mode {mode!r}: metadata | all")
        self.mode = mode
        self._fh = open(path, "a", buffering=1)
        self._lock = threading.Lock()

    def log(self, handler: tornado.web.RequestHandler,
            model: str | None) -> None:
        req = handler.request
        rec = {
            "ts": time.time(),
            "method": req.method,
            "path": req.path,
            "model": model,
            "status": handler.get_status(),
            "latency_ms": round(req.request_time() * 1e3, 3),
            "request_bytes": len(req.body or b""),
        }
        if self.mode == "all":
            try:
                rec["request"] = json.loads(req.body or b"{}")
            except json.JSONDecodeError:
                rec["request"] = None
        with self._lock:
            self._fh.write(json.dumps(rec) + "\n")

    def close(self) -> None:
        self._fh.close()


class ModelServer:
    """Hosts a ModelRepository over HTTP; runs inline or on a daemon thread."""

    def __init__(self, repo: ModelRepository | None = None,
                 request_logger: RequestLogger | None = None,
                 admission: AdmissionController | None = None,
                 max_inflight: int = 256,
                 executor_workers: int | None = None):
        self.repo = repo or ModelRepository()
        self.request_logger = request_logger
        # max_inflight=0 disables admission control entirely (None);
        # an explicit controller wins over the convenience knob.
        if max_inflight < 0:
            raise ValueError(f"max_inflight must be >= 0, got "
                             f"{max_inflight}")
        self.admission = admission
        if admission is None and max_inflight > 0:
            self.admission = AdmissionController(max_inflight)
        # Handler-submitted blocking work runs here (not the asyncio
        # default executor) so expired requests hand back a CONCURRENT
        # future: the admission slot can ride it to true completion
        # instead of freeing while the abandoned call still runs.
        # `executor_workers` overrides the CPU-derived default: a
        # worker is held for each admitted blocking call's full
        # duration (mostly device/engine waits, not CPU), so small-CPU
        # hosts serving concurrency-heavy traffic size it by admission
        # depth instead.
        self.executor = ThreadPoolExecutor(
            max_workers=(int(executor_workers) if executor_workers
                         else min(32, (os.cpu_count() or 1) + 4)),
            thread_name_prefix="tpk-serve-work")
        self._counters: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._loop: tornado.ioloop.IOLoop | None = None
        self._thread: threading.Thread | None = None
        self.port: int | None = None
        self._grpc = None
        self.grpc_port: int | None = None
        # Connection-draining state (scale-in, ISSUE 9): a plain bool —
        # single writer (the drain trigger), GIL-atomic reads from
        # request threads and probes.
        self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Enter draining: BOTH readiness surfaces (HTTP /v2/health/ready
        and gRPC ServerReady share readiness()) go not-ready, new
        inference requests are rejected 503 + DRAINING_HEADER (HTTP) /
        UNAVAILABLE "replica draining" (gRPC), and in-flight requests
        run to completion — the router/controller retires the process
        once the in-flight gauges reach zero."""
        self._draining = True

    def end_drain(self) -> None:
        """Abort a drain (scale-in cancelled): the replica resumes
        admitting and both readiness surfaces recover together."""
        self._draining = False

    def start_grpc(self, port: int = 0) -> int:
        """Open Inference Protocol v2 over gRPC (grpc_server.py), sharing
        this server's repository/batchers. Returns the bound port."""
        from kubeflow_tpu.serve.grpc_server import build_grpc_server

        self._grpc, self.grpc_port = build_grpc_server(self, port)
        self._grpc.start()
        return self.grpc_port

    def readiness(self) -> tuple[bool, str]:
        """THE readiness rule, shared by the HTTP probe and gRPC
        ServerReady so the two surfaces cannot drift: not ready while
        draining (scale-in in progress — pollers on EITHER surface must
        see the same degradation, or a gRPC-only client keeps sending
        to a replica the HTTP plane already retired), while any model is
        still loading, or while the replica is actively shedding
        (admission rejections within the last retry_after_s — KServe
        probe semantics: route around a saturated replica instead of
        feeding more traffic into 503s; a full-but-quiet replica stays
        ready)."""
        if self._draining:
            return False, "draining"
        for name in self.repo.names():
            try:
                model = self.repo.get(name)
            except Exception:
                continue  # unloaded between names() and get(): not loading
            if not model.ready:
                return False, "models loading"
        if self.admission is not None and self.admission.shedding:
            return False, "shedding: admission queue full"
        return True, ""

    def observe(self, model: str, examples: int, seconds: float) -> None:
        with self._lock:
            c = self._counters.setdefault(
                model, {"requests": 0, "examples": 0, "seconds": 0.0})
            c["requests"] += 1
            c["examples"] += examples
            c["seconds"] += seconds
        # Latency distribution, not just the running sum: the counter
        # pair gives average latency only — p50/p99 need buckets
        # (SURVEY.md §5.1 rebuild item).
        res_metrics.observe("tpk_serve_request_latency_seconds", seconds,
                            model=model)

    def prometheus_text(self) -> str:
        lines = [
            "# TYPE tpk_serve_requests_total counter",
            "# TYPE tpk_serve_examples_total counter",
            "# TYPE tpk_serve_request_seconds_total counter",
        ]
        with self._lock:
            for model, c in sorted(self._counters.items()):
                tag = f'{{model="{model}"}}'
                lines += [
                    f"tpk_serve_requests_total{tag} {c['requests']}",
                    f"tpk_serve_examples_total{tag} {c['examples']}",
                    f"tpk_serve_request_seconds_total{tag} {c['seconds']:.6f}",
                ]
        if self.admission is not None:
            lines += [
                "# TYPE tpk_serve_inflight gauge",
                f"tpk_serve_inflight {self.admission.inflight}",
            ]
        lines += self._engine_metric_lines()
        out = "\n".join(lines) + "\n"
        # The shared resilience counters (retries, deadline expiries,
        # sheds) render on the same scrape — one metrics surface for the
        # whole failure story.
        return out + res_metrics.prometheus_text()

    def _engine_metric_lines(self) -> list[str]:
        """Per-model generation-engine counters (see _ENGINE_METRICS)."""
        rows = []
        for name in self.repo.names():
            try:
                model = self.repo.get(name)
            except Exception:
                continue  # unloaded between names() and get()
            engine = getattr(model, "engine", None)
            snap = getattr(engine, "stats_snapshot", None)
            # Locked shallow snapshot (the engine worker mutates its
            # dict); plain-dict fallback for engines without the lock
            # (text2text's single-threaded stats).
            stats = snap() if callable(snap) else getattr(engine, "stats",
                                                          None)
            if not stats:
                continue
            rows.append((name, engine, dict(stats)))
        lines: list[str] = []
        for stat_key, metric, kind in _ENGINE_METRICS:
            typed = False
            for name, engine, stats in rows:
                if stat_key == "__depth__":
                    val = getattr(engine, "pipeline_depth", 1)
                elif stat_key == "__inflight__":
                    val = getattr(engine, "inflight_depth", 0)
                elif stat_key == "__spec_accept_rate__":
                    proposed = stats.get("spec_proposed") or 0
                    if not proposed:
                        continue
                    val = stats.get("spec_accepted", 0) / proposed
                elif stat_key in ("__kv_free__", "__kv_used__",
                                  "__kv_spill__"):
                    # None on flat engines — the pool gauges only exist
                    # where a pool does (and the spill gauge only where
                    # a host tier does).
                    attr = {"__kv_free__": "kv_blocks_free",
                            "__kv_used__": "kv_blocks_used",
                            "__kv_spill__": "kv_spill_blocks"}[stat_key]
                    val = getattr(engine, attr, None)
                    if val is None:
                        continue
                else:
                    val = stats.get(stat_key)
                    if val is None:
                        continue
                if not typed:
                    lines.append(f"# TYPE {metric} {kind}")
                    typed = True
                v = (int(val) if float(val).is_integer()
                     else round(float(val), 6))
                lines.append(f'{metric}{{model="{name}"}} {v}')
        # Engine role as a labeled presence gauge (the fleet poller and
        # operators read which phase of disaggregated serving a replica
        # runs): one series per model, value always 1.
        typed = False
        for name, engine, _stats in rows:
            role = getattr(engine, "role", None)
            if not role:
                continue
            if not typed:
                lines.append("# TYPE tpk_engine_role gauge")
                typed = True
            lines.append(
                f'tpk_engine_role{{model="{name}",role="{role}"}} 1')
        # KV quantization mode as a labeled info gauge (ISSUE 19):
        # which encode a replica's pool blocks use — operators pair
        # disagg fleets by this series (mismatched modes refuse at
        # submit_remote), and "none" is rendered too so the escape
        # hatch is as observable as the quantized modes.
        typed = False
        for name, engine, _stats in rows:
            mode = getattr(engine, "kv_quant", None)
            if not mode:
                continue
            if not typed:
                lines.append("# TYPE tpk_kv_quant_mode gauge")
                typed = True
            lines.append(
                f'tpk_kv_quant_mode{{model="{name}",mode="{mode}"}} 1')
        return lines

    def app(self) -> tornado.web.Application:
        from kubeflow_tpu.serve import openai_api

        kw = {"server": self}
        return tornado.web.Application(openai_api.routes(self) + [
            (r"/v1/models", V1ListHandler, kw),
            (r"/v1/models/([^/:]+)", V1ModelHandler, kw),
            (r"/v1/models/([^/:]+):predict", V1PredictHandler, kw),
            (r"/v1/models/([^/:]+):explain", V1ExplainHandler, kw),
            (r"/v1/models/([^/:]+):generate", GenerateHandler, kw),
            (r"/v1/models/([^/:]+):prefill", PrefillHandler, kw),
            (r"/v1/models/([^/:]+):decode", DecodeHandler, kw),
            (r"/v2/models/([^/]+)/generate", GenerateHandler, kw),
            (r"/v2/health/(live|ready)", V2HealthHandler, kw),
            (r"/v2/models/([^/]+)/infer", V2InferHandler, kw),
            (r"/v2/repository/models/([^/]+)/(load|unload)",
             RepositoryHandler, kw),
            (r"/v2/repository/index", RepositoryIndexHandler, kw),
            (r"/v2/models/([^/]+)(/ready)?", V2ModelHandler, kw),
            (r"/metrics", MetricsHandler, kw),
            (r"/debug/trace", DebugTraceHandler, kw),
        ])

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
        """Starts on a daemon thread; returns the bound port (tests, local)."""
        ready = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, args=(port, ready), daemon=True,
            name="tpk-model-server")
        self._thread.start()
        if not ready.wait(10.0):
            raise TimeoutError("model server failed to bind")
        assert self.port is not None
        return self.port

    def stop(self) -> None:
        grpc_drained = None
        if self._grpc is not None:
            grpc_drained = self._grpc.stop(grace=1.0)
        if self._loop is not None:
            self._loop.add_callback(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        # Executor last, after the gRPC grace window actually drains:
        # in-flight handlers may still submit_blocking(), and shutting
        # down first would 500 them with 'cannot schedule new futures'.
        if grpc_drained is not None:
            grpc_drained.wait(1.5)
        self.executor.shutdown(wait=False)
        self.repo.close()

    def run(self, port: int) -> None:
        """Blocking serve — the in-pod entrypoint."""
        self._serve(port, threading.Event())


def _close_profiler() -> None:
    """Write out a profiler session that whoever hosts `main()` opened
    (the spans' second sink, utils/obs.py) before the process goes. A
    trace is serialised by `stop_trace`, tens of seconds for a busy
    engine's: one that a side thread is still writing when SIGTERM
    arrives would die with that thread. `stop_trace` takes the profiler's
    lock, so this waits for a stop under way elsewhere, stops a session
    nobody stopped, and raises where there is none."""
    profiler = sys.modules.get("jax.profiler")  # not imported: no session
    try:
        if profiler is not None:
            profiler.stop_trace()
    except RuntimeError:
        pass


def settle_heap() -> None:
    """Put what loading left on the heap (parameter trees, traced programs,
    modules: hundreds of thousands of objects that live as long as the
    process) out of the cyclic collector's reach. A full collection walks
    every tracked object with every thread stopped: over the load's heap
    that is longer than a decode pipeline has work queued, so the device
    idles a few rounds each time (PERF.md section 6, PR 36); over the
    requests' objects alone it is not. A model unloaded later is thawed
    first (`ModelRepository`), so nothing it leaves is kept."""
    gc.collect()
    gc.freeze()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="tpk-model-server")
    p.add_argument("--model-dir", action="append", default=[],
                   help="model bundle dir (repeatable; see runtimes.py)")
    p.add_argument("--storage-uri", action="append", default=[],
                   help="uri to materialize then serve (file://, pvc://)")
    p.add_argument("--name", action="append", default=[],
                   help="override name for the i-th model")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--max-batch-size", type=int, default=32)
    p.add_argument("--max-latency-ms", type=float, default=5.0)
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="force N virtual CPU devices (test mode)")
    p.add_argument("--request-log", default=None,
                   help="JSONL inference request log path (agent logger)")
    p.add_argument("--request-log-mode", default="metadata",
                   choices=["metadata", "all"])
    p.add_argument("--grpc-port", type=int, default=None,
                   help="also serve the v2 open-inference gRPC protocol")
    p.add_argument("--max-inflight", type=int, default=256,
                   help="admitted-request cap before 503 shedding "
                        "(0 disables admission control)")
    p.add_argument("--mesh", default=None,
                   help="device mesh for tensor-parallel generative "
                        "serving, e.g. 'tensor=8' or 'tensor=4,data=2' "
                        "(the ISVC model.mesh field)")
    args = p.parse_args(argv)

    mesh_spec = None
    if args.mesh:
        mesh_spec = {}
        for part in args.mesh.split(","):
            axis, _, n = part.partition("=")
            try:
                mesh_spec[axis.strip()] = int(n)
            except ValueError:
                p.error(f"--mesh parts must be axis=N, got {part!r}")

    from kubeflow_tpu.utils import devices

    if args.cpu_devices:
        devices.force_cpu_device_count(args.cpu_devices)
    devices.enable_compile_cache()
    clock = devices.compile_clock()
    print(json.dumps({
        "event": "device",
        **devices.require_tpu_or_requested_cpu("tpk-model-server")}),
        flush=True)

    # SIGTERM unwinds main() like ^C does (default disposition would drop
    # the process mid-dispatch): the finally below stops the decode
    # threads, then the interpreter tears the PJRT client down in order,
    # which is what hands the chip to the next process.
    def _on_sigterm(signum, frame):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _on_sigterm)

    from kubeflow_tpu.serve import runtimes, storage

    dirs = list(args.model_dir)
    for i, uri in enumerate(args.storage_uri):
        dirs.append(storage.download(uri, f"/tmp/tpk-models/{i}"))

    logger = (RequestLogger(args.request_log, args.request_log_mode)
              if args.request_log else None)
    server = ModelServer(request_logger=logger,
                         max_inflight=args.max_inflight)
    try:
        for i, d in enumerate(dirs):
            name = args.name[i] if i < len(args.name) else None
            model = runtimes.load_model(d, name=name, mesh=mesh_spec)
            server.repo.register(model, model_dir=d, mesh=mesh_spec,
                                 max_batch_size=args.max_batch_size,
                                 max_latency_ms=args.max_latency_ms)
            # The peak so far is the load's: weights made and held, every
            # program compiled and warmed (device_end has the whole run's).
            print(json.dumps({"event": "model_loaded", "name": model.name,
                              "load_time_s": model.load_time_s,
                              "peak_bytes_in_use":
                                  devices.peak_bytes_in_use()}),
                  flush=True)
        settle_heap()
        if args.grpc_port is not None:
            bound = server.start_grpc(args.grpc_port)
            print(json.dumps({"event": "grpc_serving", "port": bound}),
                  flush=True)
        print(json.dumps({"event": "serving", "port": args.port}),
              flush=True)
        server.run(args.port)
    finally:
        for name in server.repo.names():
            server.repo.get(name).unload()
        server.stop()
        _close_profiler()
        print(json.dumps({"event": "device_end", **clock.snapshot(),
                          "peak_bytes_in_use":
                              devices.peak_bytes_in_use()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
