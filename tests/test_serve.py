"""Serving data-plane tests — the analog of KServe's in-process server tests
(SURVEY.md §4.4: 'KServe server tests hit the ASGI app in-process with dummy
models'): dummy + real JAX models behind the real HTTP server on localhost.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from kubeflow_tpu.serve import (Batcher, JAXModel, Model, ModelServer,
                                export_for_serving, load_model)


class EchoTimes2(Model):
    def predict(self, inputs):
        return [np.asarray(inputs[0]) * 2]


def _http(method, url, body=None):
    req = urllib.request.Request(url, method=method,
                                 data=json.dumps(body).encode()
                                 if body is not None else None)
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture(scope="module")
def server():
    srv = ModelServer()
    srv.repo.register(EchoTimes2("echo"))
    port = srv.start_background()
    yield f"http://127.0.0.1:{port}", srv
    srv.stop()


def test_v1_predict_and_list(server):
    base, _ = server
    code, body = _http("GET", f"{base}/v1/models")
    assert code == 200 and body == {"models": ["echo"]}
    code, body = _http("POST", f"{base}/v1/models/echo:predict",
                       {"instances": [[1, 2], [3, 4]]})
    assert code == 200
    assert body["predictions"] == [[2, 4], [6, 8]]


def test_v1_missing_model_404(server):
    base, _ = server
    code, body = _http("POST", f"{base}/v1/models/nope:predict",
                       {"instances": [1]})
    assert code == 404 and "not found" in body["error"]


def test_v2_health_metadata_infer(server):
    base, _ = server
    assert _http("GET", f"{base}/v2/health/live")[0] == 200
    assert _http("GET", f"{base}/v2/health/ready")[0] == 200
    code, meta = _http("GET", f"{base}/v2/models/echo")
    assert code == 200 and meta["name"] == "echo"
    code, body = _http("POST", f"{base}/v2/models/echo/infer", {
        "inputs": [{"name": "input_0", "shape": [2, 2],
                    "datatype": "FP32", "data": [1, 2, 3, 4]}]})
    assert code == 200
    out = body["outputs"][0]
    assert out["shape"] == [2, 2] and out["data"] == [2.0, 4.0, 6.0, 8.0]


def test_v2_repository_load_unload(server):
    base, _ = server
    assert _http("POST", f"{base}/v2/repository/models/echo/unload")[0] == 200
    assert _http("GET", f"{base}/v2/models/echo/ready")[0] == 503
    assert _http("POST", f"{base}/v2/repository/models/echo/load")[0] == 200
    assert _http("GET", f"{base}/v2/models/echo/ready")[0] == 200


def test_metrics_endpoint(server):
    base, _ = server
    _http("POST", f"{base}/v1/models/echo:predict", {"instances": [[1.0]]})
    req = urllib.request.Request(f"{base}/metrics")
    with urllib.request.urlopen(req, timeout=10) as r:
        text = r.read().decode()
    assert 'tpk_serve_requests_total{model="echo"}' in text


# -- batcher ----------------------------------------------------------------


def test_batcher_coalesces_concurrent_requests():
    calls = []

    def predict(inputs):
        calls.append(inputs[0].shape[0])
        return [inputs[0] + 1]

    b = Batcher(predict, max_batch_size=64, max_latency_ms=30.0)
    futs, threads = [], []

    def submit(i):
        futs.append((i, b.submit([np.full((2, 3), i, np.float32)])))

    for i in range(8):
        t = threading.Thread(target=submit, args=(i,))
        threads.append(t)
        t.start()
    for t in threads:
        t.join()
    for i, f in futs:
        out = f.result(timeout=10)[0]
        assert out.shape == (2, 3) and np.all(out == i + 1)
    assert sum(calls) == 16
    assert len(calls) < 8  # at least some coalescing happened
    b.close()


def test_batcher_propagates_errors():
    def predict(inputs):
        raise ValueError("boom")

    b = Batcher(predict, max_batch_size=4, max_latency_ms=1.0)
    with pytest.raises(ValueError, match="boom"):
        b.predict([np.zeros((1, 2))])
    b.close()


# -- JAX model + runtime bundle --------------------------------------------


def test_jax_model_bucketing_and_padding():
    def apply_fn(params, x):
        return x @ params["w"]

    params = {"w": np.eye(3, dtype=np.float32)}
    m = JAXModel("lin", apply_fn, params, input_spec=[((3,), "float32")],
                 batch_buckets=(2, 4), warm_buckets=(2,))
    m.load()
    assert m.stats["compiles"] == 1
    out = m.predict([np.arange(9, dtype=np.float32).reshape(3, 3)])[0]
    assert out.shape == (3, 3)  # padded 3->4, stripped back
    np.testing.assert_allclose(out, np.arange(9).reshape(3, 3))
    # above largest bucket: chunked through the 4-bucket
    out = m.predict([np.ones((10, 3), np.float32)])[0]
    assert out.shape == (10, 3)
    assert set(m._compiled) == {2, 4}


def test_export_load_serve_roundtrip(tmp_path):
    """Train-side export -> ServingRuntime resolution -> HTTP predict: the
    config-3 path (BERT-class predictor) minus the real checkpoint."""
    d = tmp_path / "bundle"
    export_for_serving(str(d), model="mnist_mlp",
                       model_kwargs={"in_dim": 16, "hidden": [8], "num_classes": 4},
                       batch_buckets=(1, 2, 4), seed=7)
    model = load_model(str(d), name="clf")
    srv = ModelServer()
    srv.repo.register(model)
    port = srv.start_background()
    base = f"http://127.0.0.1:{port}"
    try:
        x = np.random.default_rng(0).normal(size=(3, 16)).astype(np.float32)
        code, body = _http("POST", f"{base}/v1/models/clf:predict",
                           {"instances": x.tolist()})
        assert code == 200
        preds = np.asarray(body["predictions"])
        assert preds.shape == (3, 4)
        # HTTP result must match a direct in-process forward
        direct = model.predict([x])[0]
        np.testing.assert_allclose(preds, direct, rtol=1e-5)
    finally:
        srv.stop()


def test_export_with_params_roundtrip(tmp_path):
    """Params saved via orbax are what the runtime restores."""
    import jax

    from kubeflow_tpu.utils import registry

    module, _ = registry.build_model("mnist_mlp", in_dim=8, hidden=(4,),
                                     num_classes=2)
    params = module.init(jax.random.key(3), np.zeros((1, 8), np.float32))
    params = params["params"]
    d = tmp_path / "bundle"
    export_for_serving(str(d), model="mnist_mlp", params=params,
                       model_kwargs={"in_dim": 8, "hidden": [4], "num_classes": 2},
                       batch_buckets=(2,))
    m = load_model(str(d))
    m.load()
    x = np.ones((2, 8), np.float32)
    got = m.predict([x])[0]
    want = module.apply({"params": params}, x)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5)


def test_batcher_isolates_incompatible_shapes():
    """A malformed request must not poison a coalesced batch (requests only
    batch together when per-example shape/dtype signatures match)."""
    def predict(inputs):
        if inputs[0].shape[1] != 3:
            raise ValueError("bad shape reached the model")
        return [inputs[0] * 2]

    b = Batcher(predict, max_batch_size=64, max_latency_ms=20.0)
    good1 = b.submit([np.ones((1, 3), np.float32)])
    bad = b.submit([np.ones((1, 5), np.float32)])
    good2 = b.submit([np.ones((2, 3), np.float32)])
    assert good1.result(10)[0].shape == (1, 3)
    assert good2.result(10)[0].shape == (2, 3)
    with pytest.raises(ValueError):
        bad.result(10)
    b.close()


# -- gRPC data plane (open inference protocol v2 over grpcio) ----------------


def test_grpc_live_ready_metadata_infer(server):
    from kubeflow_tpu.serve.grpc_server import InferenceClient

    base, srv = server
    port = srv.start_grpc()
    client = InferenceClient(f"127.0.0.1:{port}")
    try:
        assert client.server_live()
        assert client.model_ready("echo")
        md = client.model_metadata("echo")
        assert md.name == "echo"

        x = np.asarray([[1.0, 2.0], [3.0, 4.0]], np.float32)
        outs = client.infer("echo", [x])
        np.testing.assert_allclose(outs[0], x * 2)
        # Raw (packed little-endian) encoding — same result.
        outs = client.infer("echo", [x], raw=True)
        np.testing.assert_allclose(outs[0], x * 2)

        # gRPC and HTTP hit the SAME model/batcher: counters advance.
        import urllib.request
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert 'tpk_serve_requests_total{model="echo"}' in body
    finally:
        client.close()


def test_grpc_unknown_model_and_bad_dtype(server):
    import grpc

    from kubeflow_tpu.serve.grpc_server import InferenceClient
    from kubeflow_tpu.serve import open_inference_pb2 as pb

    base, srv = server
    port = srv.grpc_port or srv.start_grpc()
    client = InferenceClient(f"127.0.0.1:{port}")
    try:
        with pytest.raises(grpc.RpcError) as e:
            client.infer("nope", [np.zeros((1, 2), np.float32)])
        assert e.value.code() == grpc.StatusCode.NOT_FOUND

        # Mis-sized raw payload surfaces INVALID_ARGUMENT, not a crash.
        req = pb.ModelInferRequest(model_name="echo")
        t = req.inputs.add(name="x", datatype="FP32", shape=[2, 2])
        del t  # typed contents empty; raw list mismatched on purpose
        req.raw_input_contents.append(b"\x00" * 4)  # 1 float, shape says 4
        with pytest.raises(grpc.RpcError) as e:
            client._call("ModelInfer", req, pb.ModelInferResponse)
        assert e.value.code() in (grpc.StatusCode.INVALID_ARGUMENT,
                                  grpc.StatusCode.INTERNAL)
    finally:
        client.close()


def test_repository_async_load_supersede_and_cancel(tmp_path):
    """load_async lifecycle: latest intent wins (a newer model_dir
    supersedes an in-flight load) and unload-during-load cancels instead
    of orphaning the model."""
    import time

    from kubeflow_tpu.serve.runtimes import export_for_serving
    from kubeflow_tpu.serve.server import ModelRepository

    d1 = export_for_serving(str(tmp_path / "v1"), model="mnist_mlp",
                            model_kwargs={"in_dim": 8, "hidden": [4],
                                          "num_classes": 2},
                            batch_buckets=(1,), seed=1)
    d2 = export_for_serving(str(tmp_path / "v2"), model="mnist_mlp",
                            model_kwargs={"in_dim": 8, "hidden": [4],
                                          "num_classes": 3},
                            batch_buckets=(1,), seed=2)

    repo = ModelRepository()
    # Two rapid intents: only the LAST may win.
    repo.load_async("m", d1)
    repo.load_async("m", d2)
    deadline = time.time() + 60
    while time.time() < deadline:
        if "m" in repo.names() and repo.get("m").ready:
            x = np.zeros((1, 8), np.float32)
            if repo.get("m").predict([x])[-1].shape == (1, 3):
                break
        time.sleep(0.1)
    assert repo.get("m").predict([np.zeros((1, 8), np.float32)])[-1].shape \
        == (1, 3)  # v2 (3 classes) won

    # Cancel: unload while the load is in flight -> never serves.
    repo2 = ModelRepository()
    repo2.load_async("x", d1)
    repo2.unload("x")  # may land before or after registration
    deadline = time.time() + 30
    while time.time() < deadline:
        names = repo2.names()
        if "x" not in names or not repo2.get("x").ready:
            break
        time.sleep(0.1)
    assert "x" not in repo2.names() or not repo2.get("x").ready

    # Failed load surfaces an error; a live model is never 503'd by it.
    repo3 = ModelRepository()
    repo3.load_async("bad", str(tmp_path / "nope"))
    deadline = time.time() + 30
    while time.time() < deadline:
        if repo3.loading_error("bad"):
            break
        time.sleep(0.1)
    assert repo3.loading_error("bad")
    repo3.close()
    repo.close()
    repo2.close()


def test_deferred_unload_spares_rolled_back_model():
    """A version swap schedules the old model's unload after a grace
    window; a rollback that re-registers the SAME object inside the
    window must cancel the effect — the pending timer may not unload the
    now-live model. A genuinely replaced version still unloads."""
    import time

    from kubeflow_tpu.serve.server import ModelRepository

    class Tracked(Model):
        def predict(self, inputs):
            return inputs

    old_grace = ModelRepository.UNLOAD_GRACE_S
    ModelRepository.UNLOAD_GRACE_S = 0.1
    try:
        repo = ModelRepository()
        v1, v2 = Tracked("m"), Tracked("m")
        repo.register(v1)
        repo.register(v2)   # swap: v1's unload scheduled
        repo.register(v1)   # rollback inside the grace window
        time.sleep(0.5)
        assert v1.ready, "rollback victim was unloaded by stale timer"

        repo.register(v2)   # swap away again, no rollback this time
        time.sleep(0.5)
        assert not v1.ready, "replaced version never unloaded"
        assert v2.ready
        repo.close()
    finally:
        ModelRepository.UNLOAD_GRACE_S = old_grace


@pytest.mark.parametrize("how", ["unload by name", "a version swap"])
def test_settled_heap_is_thawed_when_a_model_goes(how):
    """`main()` puts the heap that loading left out of the cyclic
    collector's reach (a full collection over it stops the engine's thread
    for longer than its pipeline covers); a model that goes later must not
    leave cycles the collector can no longer see."""
    import gc
    import time
    import weakref

    from kubeflow_tpu.serve import server as srv

    class Cyclic(Model):
        def predict(self, inputs):
            return inputs

    old_grace = srv.ModelRepository.UNLOAD_GRACE_S
    srv.ModelRepository.UNLOAD_GRACE_S = 0.05
    try:
        repo = srv.ModelRepository()
        model = Cyclic("m")
        model.me = model  # a cycle: only the collector frees it
        gone = weakref.ref(model)
        repo.register(model)
        srv.settle_heap()
        assert gc.get_freeze_count() > 0
        if how == "unload by name":
            repo.unload("m")
            repo._models.pop("m")  # (the repository keeps an unloaded
            repo._batchers.pop("m").close()  # model's entry for a reload)
        else:
            repo.register(Cyclic("m"))
            time.sleep(0.5)
        assert gc.get_freeze_count() == 0
        del model
        gc.collect()
        assert gone() is None
        repo.close()
    finally:
        gc.unfreeze()
        srv.ModelRepository.UNLOAD_GRACE_S = old_grace


def test_happy_path_unchanged_with_no_faults_armed(server):
    """Zero-overhead check (ISSUE 1): with no fault harness installed and
    no deadline header, the resilience layer must be invisible — same
    responses as the seed, no admission friction, and fire() short-
    circuiting to a single global read."""
    import time as _time

    from kubeflow_tpu.utils import faults

    base, srv = server
    assert faults.active() is None
    for _ in range(3):
        code, body = _http("POST", f"{base}/v1/models/echo:predict",
                           {"instances": [[1, 2], [3, 4]]})
        assert code == 200
        assert body["predictions"] == [[2, 4], [6, 8]]
    # Admission fully drains between requests; readiness stays green.
    # (The handler thread decrements inflight AFTER flushing the body,
    # so the client can observe the gauge a beat early under load —
    # poll briefly instead of racing it.)
    assert srv.admission is not None
    deadline = _time.monotonic() + 2.0
    while srv.admission.inflight != 0 and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert srv.admission.inflight == 0
    code, _ = _http("GET", f"{base}/v2/health/ready")
    assert code == 200
    # The disarmed hot-path hook costs one global None-check.
    t0 = _time.monotonic()
    for i in range(10_000):
        faults.fire("serve.predict", batch=i)
    assert _time.monotonic() - t0 < 0.5
