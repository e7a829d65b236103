"""End-to-end tracing acceptance tests (ISSUE 5).

Serve e2e: one request with a caller-set X-Request-Id must yield valid
Chrome trace-event JSON on /debug/trace whose admit → batch-gather →
prefill → per-chunk decode → fetch spans all carry that id (HTTP and
gRPC share the contract). Controlplane client: per-verb RPC latency
histograms + the trace field on the wire. Span-overhead guards: tracing
at default settings adds ZERO host syncs and no per-step allocation
growth on the train and decode hot loops.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.utils import obs


def _http(method, url, body=None, headers=None):
    req = urllib.request.Request(
        url, method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, dict(r.headers), json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"{}")


@pytest.fixture(scope="module")
def trace_server(tmp_path_factory):
    from kubeflow_tpu.serve import ModelServer, export_for_serving, load_model

    d = str(tmp_path_factory.mktemp("tracebundle"))
    export_for_serving(
        d, model="llama_tiny",
        model_kwargs={"dtype": "float32", "num_layers": 2},
        extra={"generative": {"slots": 2, "max_len": 64, "chunk": 4,
                              "prefill_buckets": [8, 16]}})
    srv = ModelServer()
    srv.repo.register(load_model(d, name="llm"), model_dir=d)
    port = srv.start_background()
    yield f"http://127.0.0.1:{port}", srv
    srv.stop()


def test_serve_trace_e2e_request_id_links_all_spans(trace_server):
    """THE serve acceptance: caller-set X-Request-Id → /debug/trace
    returns valid Chrome trace JSON with linked admit/batch-gather/
    prefill/decode/fetch spans, every one carrying that id."""
    base, _ = trace_server
    obs.get_tracer().clear()
    rid = "trace-e2e-abc123"
    code, headers, body = _http(
        "POST", f"{base}/v1/models/llm:generate",
        {"input_ids": [5, 9, 2, 44], "max_tokens": 6},
        headers={"X-Request-Id": rid})
    assert code == 200, body
    assert headers.get("X-Request-Id") == rid  # echoed
    code, _, doc = _http("GET", f"{base}/debug/trace")
    assert code == 200
    # Valid Chrome trace-event JSON: ph "X" complete events with µs
    # ts/dur and args.
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert ev["dur"] >= 0
        assert "trace_id" in ev["args"]
    mine = [ev for ev in doc["traceEvents"]
            if ev["args"]["trace_id"] == rid]
    names = {ev["name"] for ev in mine}
    assert {"serve.admit", "serve.batch_gather", "serve.prefill",
            "serve.decode_chunk", "serve.fetch"} <= names, names
    # Linked and ordered: admission precedes the prefill, the prefill
    # precedes every decode chunk of this request.
    by = {n: min(ev["ts"] for ev in mine if ev["name"] == n)
          for n in names}
    assert by["serve.admit"] <= by["serve.prefill"]
    assert by["serve.prefill"] <= by["serve.decode_chunk"]
    # Server-side filter matches client-side filtering.
    code, _, filtered = _http("GET",
                              f"{base}/debug/trace?trace_id={rid}")
    assert {ev["name"] for ev in filtered["traceEvents"]} == names


def test_wire_supplied_trace_field_cannot_spoof(trace_server):
    """A body-level "_trace" from the wire must be discarded — the
    header is the only identity source."""
    base, _ = trace_server
    obs.get_tracer().clear()
    code, headers, _ = _http(
        "POST", f"{base}/v1/models/llm:generate",
        {"input_ids": [5, 9, 2], "max_tokens": 2, "_trace": "spoofed"})
    assert code == 200
    assigned = headers.get("X-Request-Id")
    assert assigned and assigned != "spoofed"
    ids = {ev["args"]["trace_id"]
           for ev in obs.get_tracer().chrome_trace()["traceEvents"]}
    assert "spoofed" not in ids
    assert assigned in ids


def test_grpc_infer_carries_request_id_spans(trace_server):
    from kubeflow_tpu.serve.grpc_server import InferenceClient

    base, srv = trace_server
    port = srv.start_grpc(0)
    obs.get_tracer().clear()
    client = InferenceClient(f"127.0.0.1:{port}")
    try:
        outs = client.infer("llm", [np.zeros((1, 8), np.int32)],
                            request_id="grpc-req-7")
        assert outs[0].shape[0] == 1
    finally:
        client.close()
    evs = obs.get_tracer().events("grpc-req-7")
    names = {e["name"] for e in evs}
    # The infer path batches through the coalescing batcher: admission,
    # gather, and the shared predict call all wear the gRPC metadata id.
    assert {"serve.admit", "serve.batch_gather", "serve.predict"} <= names


def test_controlplane_client_histograms_and_trace_field(tmp_path):
    """The Client attaches its trace id to each request and records a
    per-verb RPC latency histogram — proven against a fake control-plane
    socket that captures the wire bytes."""
    import socket as socketlib

    from kubeflow_tpu.controlplane.client import Client
    from kubeflow_tpu.utils.resilience import metrics

    path = str(tmp_path / "fake.sock")
    seen: list[dict] = []
    srv = socketlib.socket(socketlib.AF_UNIX, socketlib.SOCK_STREAM)
    srv.bind(path)
    srv.listen(1)

    def serve_one():
        conn, _ = srv.accept()
        buf = b""
        while b"\n" not in buf:
            buf += conn.recv(65536)
        seen.append(json.loads(buf.split(b"\n", 1)[0]))
        conn.sendall(b'{"ok": true, "items": []}\n')
        conn.close()

    t = threading.Thread(target=serve_one, daemon=True)
    t.start()
    client = Client(path, timeout=5, trace_id="cp-trace-9")
    obs.get_tracer().clear()
    try:
        assert client.list("JAXJob") == []
    finally:
        client.close()
        srv.close()
    t.join(timeout=5)
    assert seen and seen[0]["op"] == "list"
    assert seen[0]["trace"] == "cp-trace-9"  # attached on the wire
    h = metrics.get_histogram("tpk_controlplane_rpc_latency_seconds",
                              verb="list")
    assert h["count"] == 1
    assert h["buckets"]["+Inf"] == 1
    (ev,) = obs.get_tracer().events("cp-trace-9")
    assert ev["name"] == "controlplane.rpc"
    assert ev["attrs"]["op"] == "list"


def test_profile_window_knobs_from_spec(monkeypatch, tmp_path, devices8):
    """The flat profile_start_step/profile_stop_step knobs wrap exactly
    [start, stop) in jax.profiler.start_trace/stop_trace, writing to the
    job workdir ($TPK_WORKDIR/profile) — the SURVEY §5.1 spec-keyed
    trace window, no hand-written profile dict needed."""
    from kubeflow_tpu.train.trainer import TrainJobSpec, Trainer

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    monkeypatch.setenv("TPK_WORKDIR", str(tmp_path))
    spec = TrainJobSpec(model="mnist_mlp", dataset="mnist_like",
                        strategy="dp", mesh={"data": 8}, steps=4,
                        batch_size=16, log_every=4,
                        profile_start_step=1, profile_stop_step=3)
    Trainer(spec).run()
    assert calls == [("start", str(tmp_path / "profile")),
                     ("stop", None)]
    # stop <= start disables the window entirely.
    calls.clear()
    spec = TrainJobSpec(model="mnist_mlp", dataset="mnist_like",
                        strategy="dp", mesh={"data": 8}, steps=4,
                        batch_size=16, log_every=4,
                        profile_start_step=2, profile_stop_step=2)
    Trainer(spec).run()
    assert calls == []
    # The dict-style knob still wins when both are set.
    calls.clear()
    spec = TrainJobSpec(model="mnist_mlp", dataset="mnist_like",
                        strategy="dp", mesh={"data": 8}, steps=4,
                        batch_size=16, log_every=4,
                        profile={"dir": str(tmp_path / "d"),
                                 "start_step": 0, "num_steps": 2},
                        profile_start_step=1, profile_stop_step=3)
    Trainer(spec).run()
    assert calls == [("start", str(tmp_path / "d")), ("stop", None)]


# -- span-overhead guards (acceptance) ---------------------------------------


@contextlib.contextmanager
def _profiler_session(tmp_path, on: bool):
    """With `on`, a jax.profiler session around the block: the spans'
    second sink (TraceAnnotation, utils/obs.py) then records, and the
    guards below must hold all the same."""
    if on:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "profile"),
                                 profiler_options=options)
    try:
        yield
    finally:
        if on:
            jax.profiler.stop_trace()


@pytest.mark.parametrize("profiling", [False, True],
                         ids=["ring", "ring+profiler"])
def test_train_span_overhead_guard(monkeypatch, devices8, tmp_path,
                                   profiling):
    """Tracing at DEFAULT settings must be free on the train hot loop:
    the host-sync budget is bit-identical to the pre-tracing guard
    (tests/test_prefetch.py) — zero extra float()s or block_until_ready
    — and span storage is a bounded ring, so per-step allocations can't
    accumulate (no growth after capacity is reached). The same with a
    profiler session open, when every span is also an annotation."""
    from jax._src.array import ArrayImpl

    from kubeflow_tpu.train.trainer import TrainJobSpec, Trainer

    events = []
    orig_float = ArrayImpl.__float__
    orig_sync = jax.block_until_ready
    monkeypatch.setattr(
        ArrayImpl, "__float__",
        lambda self: (events.append("float"), orig_float(self))[1])
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (events.append("sync"), orig_sync(x))[1])

    prev = obs.set_tracer(obs.Tracer(capacity=8, enabled=True))
    try:
        spec = TrainJobSpec(model="mnist_mlp", dataset="mnist_like",
                            strategy="dp", mesh={"data": 8}, steps=6,
                            batch_size=16, learning_rate=1e-2,
                            log_every=3, prefetch=2)
        with _profiler_session(tmp_path, profiling):
            result = Trainer(spec).run()
        tracer = obs.get_tracer()
        assert result["final_step"] == 6
        # Identical budget to the pre-tracing hot-loop guard: 2 logging
        # boundaries, each 1 sync + 3 scalar fetches. Tracing added none.
        assert events.count("sync") == 2, events
        assert events.count("float") == 3 * 2, events
        # Bounded storage: 6 step spans + fetch spans + checkpoints >
        # capacity 8, yet the ring holds exactly its cap — no per-step
        # allocation growth.
        assert len(tracer) == 8
        # Span summaries rolled into the JSONL window stream.
        assert result["span_step_ms"] >= 0.0
        assert result["span_fetch_ms"] >= 0.0
    finally:
        obs.set_tracer(prev)


@pytest.mark.parametrize("profiling", [False, True],
                         ids=["ring", "ring+profiler"])
def test_decode_span_overhead_guard(devices8, tmp_path, profiling):
    """Tracing at DEFAULT settings must be free on the decode hot loop:
    the same greedy request decoded with tracing enabled vs disabled
    performs an IDENTICAL number of device→host fetches (and identical
    tokens), spans are chunk-granular (never per token), and the ring
    stays bounded. The same with a profiler session open, when the
    engine loop's phase spans are also annotations."""
    from jax._src.array import ArrayImpl

    from kubeflow_tpu.models.llama import Llama, llama_tiny
    from kubeflow_tpu.serve.generation import GenerationEngine

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32,
                              num_layers=2)
    model = Llama(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = GenerationEngine(model, params, cfg, slots=2, max_len=64,
                              chunk=4, prefill_buckets=[8, 16])
    counts = {"fetch": 0}
    orig_array = ArrayImpl.__array__

    def counting_array(self, *a, **kw):
        counts["fetch"] += 1
        return orig_array(self, *a, **kw)

    prompt = [5, 9, 2, 44]

    def run_once(enabled):
        prev = obs.set_tracer(obs.Tracer(capacity=64, enabled=enabled))
        ArrayImpl.__array__ = counting_array
        counts["fetch"] = 0
        try:
            out = engine.submit(prompt, max_tokens=8,
                                trace_id="decode-guard")
            fetches = counts["fetch"]
            spans = obs.get_tracer().events("decode-guard")
            return out["output_ids"], fetches, spans
        finally:
            ArrayImpl.__array__ = orig_array
            obs.set_tracer(prev)

    try:
        run_once(True)  # warm the scheduler state
        with _profiler_session(tmp_path, profiling):
            toks_on, fetches_on, spans_on = run_once(True)
            toks_off, fetches_off, spans_off = run_once(False)
    finally:
        engine.close()
    assert toks_on == toks_off
    assert fetches_on == fetches_off, (
        f"tracing changed the decode fetch count: {fetches_on} vs "
        f"{fetches_off}")
    assert spans_off == []
    # Chunk-granular: ≤ a handful of spans per request (batch_gather +
    # prefill + per-chunk decode/fetch pairs), never one per token.
    decode_spans = [s for s in spans_on
                    if s["name"] == "serve.decode_chunk"]
    assert decode_spans, "decode chunks must be visible in the trace"
    assert len(spans_on) <= 4 + 3 * (8 // 4 + 2)
