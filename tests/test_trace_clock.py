"""The program's spans on the profiler's clock (ISSUE 26).

One span surface, two sinks: an `obs.span` opened while a `jax.profiler`
session is open is in the host plane of the same `*.xplane.pb` as the
operations it wraps; `obs` still imports without JAX; `TPK_TRACE=0` emits
nothing on either sink. The engine loop's phases are `engine.*` spans, one
per phase per pass; the engine's counters sum queue wait, time to first
token and decode context where the events happen; backend compiles are
counted live; and the names of the jitted executables, which the
benchmark's trace reduction matches on, are pinned.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.utils import devices, obs

ENGINE_PHASES = ("engine.admit", "engine.sweep", "engine.wait",
                 "engine.dispatch", "engine.fetch", "engine.emit")


def _host_events(trace_dir):
    """[(thread line name, event name, start_ns, end_ns, stats)] of the
    host plane of the one trace under `trace_dir`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((line.name, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, ev))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session: an enabled tracer's span around a jitted
    call, and a disabled tracer's span that must leave nothing."""

    @jax.jit
    def bracketed(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    bracketed(x).block_until_ready()  # compiled before the session
    trace_dir = tmp_path_factory.mktemp("xplane")
    on = obs.Tracer(capacity=8, enabled=True)
    off = obs.Tracer(capacity=8, enabled=False)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # what benchmarks/serve_child.py sets
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with on.span("train.step", trace_id="job-7", step=3) as sp:
            sp.set(note="late")
            bracketed(x).block_until_ready()
        with off.span("train.never", trace_id="job-7", step=4):
            bracketed(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return {"events": _host_events(trace_dir), "on": on, "off": off}


def test_span_is_in_the_profilers_host_plane_with_its_attributes(traced):
    spans = [e for e in traced["events"] if e[1] == "train.step"]
    assert len(spans) == 1
    stats = dict(spans[0][4].stats)
    assert stats["step"] == 3 and stats["trace_id"] == "job-7"
    assert stats["note"] == "late"          # Span.set reaches the sink too
    # The ring got the same span, as before.
    (ring,) = traced["on"].events("job-7")
    assert ring["name"] == "train.step"
    assert ring["attrs"] == {"step": 3, "note": "late"}


def test_span_brackets_the_jitted_call_it_wraps(traced):
    (span,) = [e for e in traced["events"] if e[1] == "train.step"]
    ops = [e for e in traced["events"]
           if dict(e[4].stats).get("hlo_module") == "jit_bracketed"]
    assert ops, "the op events name their executable (hlo_module)"
    inside = [e for e in ops if span[2] <= e[2] and e[3] <= span[3]]
    # Two runs were traced; exactly the first lies inside the span, on the
    # same clock (the second ran under the disabled tracer, after it).
    assert inside and len(inside) < len(ops)
    assert all(e[2] >= span[3] for e in ops if e not in inside)


def test_disabled_tracer_emits_neither_ring_span_nor_annotation(traced):
    assert not [e for e in traced["events"] if e[1] == "train.never"]
    assert len(traced["off"]) == 0
    assert traced["off"].span("x") is obs.NOP_SPAN


@pytest.mark.parametrize("stopper", ["nobody", "a side thread"])
def test_server_shutdown_writes_an_open_profiler_session(tmp_path, stopper):
    """benchmarks/serve_child.py's side thread stops its profiler window
    while the server goes on; SIGTERM can arrive while it still writes.
    The server's shutdown waits for that stop (the profiler's lock), or
    makes it where nobody did, and is a no-op with no session."""
    import threading

    from kubeflow_tpu.serve import server

    server._close_profiler()  # no session: nothing to do, no raise
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    jnp.ones((8, 8)).sum().block_until_ready()
    side = None
    if stopper == "a side thread":
        def stop():  # whichever of the two comes second finds none open
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass

        side = threading.Thread(target=stop, daemon=True)
        side.start()
    server._close_profiler()
    # Written by the time shutdown goes on, whoever stopped it.
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    if side is not None:
        side.join(timeout=30)
        assert not side.is_alive()
    server._close_profiler()


def test_obs_imports_and_spans_without_jax():
    code = (
        "import sys\n"
        "from kubeflow_tpu.utils import obs\n"
        "with obs.span('controlplane.rpc', trace_id='t', op='list'):\n"
        "    pass\n"
        "assert len(obs.get_tracer()) == 1\n"
        "assert 'jax' not in sys.modules, 'obs pulled JAX in'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_trace_env_switch_off_means_no_span_at_all(monkeypatch):
    monkeypatch.setenv("TPK_TRACE", "0")
    assert obs.Tracer().span("engine.admit", round=1) is obs.NOP_SPAN


# -- the engine loop's phases and counters ------------------------------------


def _tiny_engine(**kw):
    from kubeflow_tpu.models.llama import Llama, llama_tiny
    from kubeflow_tpu.serve.generation import GenerationEngine

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=2)
    model = Llama(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return GenerationEngine(model, params, cfg, slots=2, max_len=64,
                            chunk=4, prefill_buckets=[8, 16], **kw)


@pytest.fixture(scope="module")
def engine_run():
    """Four requests through a tiny engine under a roomy tracer: the
    ring's spans and the counters before and after."""
    engine = _tiny_engine()
    # The same requests once before: the warm-up covers the executables,
    # not the op-by-op programs of the first admission and dispatch.
    for n, m in ((4, 9), (3, 6), (2, 1)):
        engine.submit([5, 9, 2, 44][:n], max_tokens=m)
    # The loop parks in engine.wait under the tracer it was opened with; a
    # swap between that pass's sweep and its wait would catch the wait alone.
    time.sleep(0.2)
    prev = obs.set_tracer(obs.Tracer(capacity=100_000, enabled=True))
    try:
        before = engine.stats_snapshot()
        outs = [engine.submit([5, 9, 2, 44][:n], max_tokens=m)
                for n, m in ((4, 9), (3, 6), (2, 1))]
        time.sleep(0.2)  # the loop goes idle: one engine.wait, open
        engine.submit([7, 7, 7], max_tokens=5)  # ... and closed by this
        after = engine.stats_snapshot()
        spans = obs.get_tracer().events()
    finally:
        obs.set_tracer(prev)
        engine.close()
    return {"spans": spans, "before": before, "after": after, "outs": outs}


def test_every_engine_phase_is_a_span_one_per_pass(engine_run):
    spans = [s for s in engine_run["spans"]
             if s["name"].startswith("engine.")]
    names = {s["name"] for s in spans}
    assert names == set(ENGINE_PHASES), names
    assert all(s["tid"] == "tpk-generate" and s["trace_id"] == ""
               for s in spans)
    per_round: dict[int, list] = {}
    for s in spans:
        per_round.setdefault(s["attrs"]["round"], []).append(s["name"])
    for rnd, phases in per_round.items():
        # One admit and one sweep open every pass; then it either waits,
        # or dispatches (as often as the pipeline takes) and fetches one
        # record and emits it.
        assert phases[:2] == ["engine.admit", "engine.sweep"], (rnd, phases)
        for once in ("engine.admit", "engine.sweep", "engine.wait",
                     "engine.fetch", "engine.emit"):
            assert phases.count(once) <= 1, (rnd, phases)
        if "engine.wait" in phases:
            assert phases == ["engine.admit", "engine.sweep", "engine.wait"]
        else:
            assert phases.count("engine.fetch") == phases.count(
                "engine.emit")
    assert sorted(per_round) == list(range(min(per_round),
                                           max(per_round) + 1))
    # Phases follow one another on the one thread: none overlaps the next.
    spans.sort(key=lambda s: s["ts_us"])
    for a, b in zip(spans, spans[1:]):
        assert a["ts_us"] + a["dur_us"] <= b["ts_us"] + 1.0, (a, b)
    # The per-request prefill spans nest in engine.admit.
    admits = [(s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
              if s["name"] == "engine.admit"]
    prefills = [s for s in engine_run["spans"]
                if s["name"] == "serve.prefill"]
    assert len(prefills) == 4
    for p in prefills:
        assert any(lo <= p["ts_us"] and p["ts_us"] + p["dur_us"] <= hi + 1.0
                   for lo, hi in admits)


def test_idle_engine_leaves_one_wait_span_not_one_per_poll(engine_run):
    waits = [s for s in engine_run["spans"] if s["name"] == "engine.wait"]
    # Idle at most once before each of the four requests (they come one
    # after another), and ~0.2 s (four 50 ms polls) before the last: one
    # span per idle period, that one as long as the idleness.
    assert 1 <= len(waits) <= 4
    assert max(w["dur_us"] for w in waits) >= 150e3


def test_engine_fetch_span_is_the_host_stall_counter(engine_run):
    fetch_s = sum(s["dur_us"] for s in engine_run["spans"]
                  if s["name"] == "engine.fetch") / 1e6
    stall_s = (engine_run["after"]["host_stall_seconds"]
               - engine_run["before"]["host_stall_seconds"])
    assert stall_s > 0
    assert fetch_s == pytest.approx(stall_s, rel=0.05)
    # Each fetch span carries the counter as it stood before it, so that a
    # trace can check the two over one interval (xplane_host.fetch_check).
    fetches = sorted((s for s in engine_run["spans"]
                      if s["name"] == "engine.fetch"),
                     key=lambda s: s["ts_us"])
    marks = [s["attrs"]["stalled_s"] for s in fetches]
    assert marks[0] == engine_run["before"]["host_stall_seconds"]
    assert marks == sorted(marks)
    assert marks[-1] - marks[0] == pytest.approx(
        sum(s["dur_us"] for s in fetches[:-1]) / 1e6, rel=0.05)


def test_first_token_counters_add_up(engine_run):
    d = {k: engine_run["after"][k] - engine_run["before"][k]
         for k in ("requests", "admitted", "first_tokens",
                   "queue_wait_seconds", "ttft_seconds")}
    assert d["requests"] == d["admitted"] == d["first_tokens"] == 4
    assert d["queue_wait_seconds"] > 0
    assert d["ttft_seconds"] >= d["queue_wait_seconds"]
    assert [len(o["output_ids"]) for o in engine_run["outs"]] == [9, 6, 1]
    # The same interval the serve.batch_gather span records.
    gather_s = sum(s["dur_us"] for s in engine_run["spans"]
                   if s["name"] == "serve.batch_gather") / 1e6
    assert gather_s == pytest.approx(d["queue_wait_seconds"], rel=1e-6)


def test_decode_context_tokens_is_the_rows_context_at_dispatch():
    """Two rows of known length, one dispatch, by hand: 11 + 23."""
    engine = _tiny_engine()
    engine.close()  # the loop is gone; this thread drives the dispatch
    try:
        for slot, ctx in ((0, 11), (1, 23)):
            engine._slots[slot] = {
                "req": {"temperature": 0.0}, "idx": ctx, "disp": ctx,
                "last": 3, "pending": None, "draft_ok": False, "aid": 0}
        before = engine.stats_snapshot()
        rec = engine._dispatch_chunk([0, 1])
        after = engine.stats_snapshot()
        assert (after["decode_context_tokens"]
                - before["decode_context_tokens"]) == 11 + 23
        assert after["decode_dispatches"] - before["decode_dispatches"] == 1
        assert [st["disp"] for st in rec["parts"].values()] == [15, 27]
        # One row alone: only the rows that ride the dispatch count.
        engine._dispatch_chunk([1])
        assert (engine.stats_snapshot()["decode_context_tokens"]
                - after["decode_context_tokens"]) == 27
    finally:
        engine._slots = [None] * engine.n_slots


def test_compiles_are_counted_live(engine_run):
    clock = devices.compile_clock()
    assert devices.compile_clock() is clock          # one per process
    assert engine_run["after"]["compiles"] >= 1      # the engine's warm-up
    assert engine_run["after"]["compile_seconds"] > 0
    # An engine that has served these shapes before compiles nothing.
    assert engine_run["after"]["compiles"] == engine_run["before"]["compiles"]

    @jax.jit
    def fresh(x):
        return x * 3 + 1

    a, b = jnp.ones((7,)), jnp.ones((9,))
    jax.block_until_ready((a, b))
    n0 = clock.compiles
    fresh(a).block_until_ready()
    assert clock.compiles == n0 + 1                  # a new shape: one
    for _ in range(5):
        fresh(a).block_until_ready()
    assert clock.compiles == n0 + 1                  # a steady loop: none
    fresh(b).block_until_ready()
    assert clock.compiles == n0 + 2


def test_trainer_rows_report_compiles_per_window(devices8):
    from kubeflow_tpu.train.trainer import TrainJobSpec, Trainer

    rows = []
    spec = TrainJobSpec(model="mnist_mlp", dataset="mnist_like",
                        strategy="dp", mesh={"data": 8}, steps=9,
                        batch_size=16, log_every=3)
    trainer = Trainer(spec)
    log = trainer.logger.log
    trainer.logger.log = lambda step, m: (rows.append((step, dict(m))),
                                          log(step, m))[1]
    trainer.run()
    windows = [m["compiles"] for step, m in rows
               if "loss" in m and "event" not in m]
    assert len(windows) == 3
    assert windows[0] >= 1          # the first window holds the step's compile
    assert windows[1:] == [0, 0]    # a steady loop compiles nothing


# -- the executables' names are part of the contract --------------------------


def _jitted_names(engine) -> set:
    """`__name__` of everything `GenerationEngine._compile` jitted."""
    found = set()
    for value in vars(engine).values():
        members = value.values() if isinstance(value, dict) else [value]
        for m in members:
            if type(m).__name__ == "PjitFunction":
                found.add(m.__name__)
    return found


@pytest.mark.parametrize("kw, names", [
    ({}, {"prefill", "extend", "extend_mid", "insert", "decode_chunk"}),
    ({"kv_block_size": 8, "kv_blocks": 24},
     {"prefill", "extend", "extend_mid", "insert_paged", "frag_from_pool",
      "export_blocks", "import_blocks", "decode_chunk"}),
    ({"kv_block_size": 8, "kv_blocks": 24, "kv_quant": "int8"},
     {"prefill", "extend", "extend_mid", "insert_paged_quant",
      "frag_from_pool_quant", "export_blocks", "import_blocks",
      "decode_chunk"}),
], ids=["flat", "paged", "paged-int8"])
def test_engine_executable_names_are_pinned(monkeypatch, kw, names):
    """benchmarks/xplane_host.py tells prefill from decode device time by
    `jit_<name>` (and a rename re-keys the persistent compile cache): a
    rename must fail here, not read as a metric that found nothing."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    monkeypatch.setattr(GenerationEngine, "_warmup", lambda self: None)
    engine = _tiny_engine(**kw)
    try:
        assert _jitted_names(engine) - {"<lambda>"} == names
    finally:
        engine.close()


def test_spec_and_trainer_executable_names_are_pinned(devices8):
    from kubeflow_tpu.models.llama import Llama, llama_tiny
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
    from kubeflow_tpu.serve.generation import build_spec_decode
    from kubeflow_tpu.train.step import make_train_step

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=1)
    model = Llama(cfg)
    for paged, name in ((0, "spec_chunk"), (8, "spec_chunk_paged")):
        make = build_spec_decode(model, model, gamma=2, n_spec=1,
                                 max_len=32, kv_block_size=paged)
        assert make(32).__name__ == name
    mesh = build_mesh(MeshConfig(data=8), devices8)
    # The trainer's profiler window shows its executable as `jit_step`.
    assert make_train_step(model, mesh, DEFAULT_RULES).jitted.__name__ \
        == "step"
