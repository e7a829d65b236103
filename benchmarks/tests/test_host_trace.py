"""CPU tests of the second trace reduction (xplane_host.py) and its readers:

    python -m pytest benchmarks/tests -q

The arithmetic on events worked out by hand, then the serve cell's
rehearsal end to end (the engine's spans and counters reach the readers).
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import xplane_host  # noqa: E402

host_trace = common.load_module(os.path.join(BENCH, "readers",
                                             "host_trace.py"))


def test_nested_spans_self_time():
    # engine.admit 0-100 holds serve.prefill 10-40 and 50-90; the second
    # holds a grandchild 60-70. engine.fetch 100-130 stands alone. A span
    # 900-950 lies outside the window 0-200 and one 190-260 is cut to it.
    t = xplane_host.span_times([
        ("engine.admit", 0, 100), ("serve.prefill", 10, 30),
        ("serve.prefill", 50, 40), ("serve.inner", 60, 10),
        ("engine.fetch", 100, 30), ("engine.emit", 190, 70),
        ("engine.emit", 900, 50)], window=(0, 200))
    assert t == {
        "engine.admit": [pytest.approx(100e-9), pytest.approx(30e-9), 1],
        "serve.prefill": [pytest.approx(70e-9), pytest.approx(60e-9), 2],
        "serve.inner": [pytest.approx(10e-9), pytest.approx(10e-9), 1],
        "engine.fetch": [pytest.approx(30e-9), pytest.approx(30e-9), 1],
        "engine.emit": [pytest.approx(10e-9), pytest.approx(10e-9), 1]}
    # Self times add up to what the spans cover: 0-130 and 190-200.
    assert sum(v[1] for v in t.values()) == pytest.approx(140e-9)


def test_busy_and_gaps_match_the_first_reduction():
    import xplane

    events = [("while", 0, 100), ("a", 10, 20), ("d", 150, 50),
              ("e", 190, 30), ("f", 300, 10), ("marker", 250, 0)]
    u = xplane_host.busy_and_gaps([(s, d) for _, s, d in events])
    r = xplane.reduce_events(events)
    assert u["busy_ns"] / 1e9 == pytest.approx(r["busy_s"])
    assert (u["last"] - u["first"]) / 1e9 == pytest.approx(r["window_s"])
    assert u["gaps"] == [(100, 150), (220, 300)]
    assert u["busy_ns"] == 100 + 70 + 10


def test_a_gap_is_split_between_the_spans_that_cover_it():
    # The device idles 100-200. engine.emit covers 90-130, engine.admit
    # 130-180 with serve.prefill 140-160 nested in it, nothing 180-200.
    # Another thread's long serve.request 0-1000 covers everything, but
    # every engine span started later: it gets only what they leave.
    cover = xplane_host.Cover([
        ("serve.request", 0, 1000), ("engine.emit", 90, 40),
        ("engine.admit", 130, 50), ("serve.prefill", 140, 20)])
    assert cover.split(100, 200) == {
        "engine.emit": 30, "engine.admit": 30, "serve.prefill": 20,
        "serve.request": 20}
    got = xplane_host.attribute([(100, 200), (2000, 2100)], cover)
    assert got["by_span"] == {
        "engine.emit": 30, "engine.admit": 30, "serve.prefill": 20,
        "serve.request": 20, xplane_host.NO_SPAN: 100}
    # Each gap is named by the span over most of it (a tie: the first).
    assert got["gaps"] == [(100, 100, "engine.emit"),
                           (2000, 100, xplane_host.NO_SPAN)]
    assert cover.at(135) == "engine.admit" and cover.at(150) == \
        "serve.prefill" and cover.at(1500) == xplane_host.NO_SPAN
    assert xplane_host.Cover([]).split(0, 10) == {xplane_host.NO_SPAN: 10}


def planes_by_hand():
    # One device: decode runs 0-100 and 140-240 (ops fill them but for a
    # hole 50-60), a prefill 100-130, so the device idles 50-60, 130-140.
    ops = [(0, 50), (60, 40), (100, 30), (140, 100)]
    mods = [("jit_decode_chunk(7)", 100), ("jit_prefill(3)", 30),
            ("jit_decode_chunk(9)", 100)]
    engine = [("engine.dispatch", 40, 15), ("engine.fetch", 55, 60),
              ("engine.emit", 115, 10), ("engine.admit", 125, 10),
              ("serve.prefill", 128, 4)]
    return {"devices": [{"ops": ops, "modules": mods}],
            "threads": [engine, [("serve.admit", 20, 2)]], "host_ops": []}


def test_reduce_trace_by_hand():
    r = xplane_host.reduce_trace(planes_by_hand())
    assert r["planes"] == 1
    assert r["busy_s"] == pytest.approx(220e-9)
    assert r["window_s"] == pytest.approx(240e-9)
    assert r["idle_s"] == pytest.approx(20e-9)
    assert r["modules"] == {
        "jit_decode_chunk": [pytest.approx(200e-9), 2.0],
        "jit_prefill": [pytest.approx(30e-9), 1.0]}
    # Gap 50-60: dispatch until 55, then fetch. Gap 130-140: admit, with
    # the nested prefill 130-132; after 135 no span.
    assert r["idle_by_span"] == {
        "engine.dispatch": pytest.approx(5e-9),
        "engine.fetch": pytest.approx(5e-9),
        "serve.prefill": pytest.approx(2e-9),
        "engine.admit": pytest.approx(3e-9),
        xplane_host.NO_SPAN: pytest.approx(5e-9)}
    assert r["spans"]["engine.admit"] == [pytest.approx(10e-9),
                                          pytest.approx(6e-9), 1]
    assert r["spans"]["engine.fetch"][2] == 1
    assert [g[2] for g in r["gaps"]] == [xplane_host.NO_SPAN,
                                         "engine.dispatch"]
    engine_thread = r["threads"][0]
    assert engine_thread["window_s"] == pytest.approx(95e-9)   # 40-135
    assert engine_thread["covered_s"] == pytest.approx(95e-9)

    class Ctx:
        trace = True
        facts = {"host_trace": r}

    read = host_trace.read
    assert read(Ctx, "module_share",
                pattern=r"^jit_(prefill|extend|insert)") == pytest.approx(
                    100 * 30 / 220)
    assert read(Ctx, "span_share", span="engine.admit") == pytest.approx(
        100 * 10 / 240)
    assert read(Ctx, "idle_named_share") == pytest.approx(75.0)
    # A program without the spans (a parent commit): nothing to read.
    assert read(Ctx, "span_share", span="engine.wait") is None
    bare = planes_by_hand()
    bare["threads"] = []
    Ctx.facts = {"host_trace": xplane_host.reduce_trace(bare)}
    assert read(Ctx, "idle_named_share") is None
    assert read(Ctx, "span_share", span="engine.admit") is None
    assert read(Ctx, "module_share", pattern="^jit_prefill") == \
        pytest.approx(100 * 30 / 220)
    Ctx.facts = {"host_trace": None}       # the reduction failed
    assert read(Ctx, "module_share", pattern="x") is None


def test_fetch_check_covers_the_same_fetches_by_span_and_by_counter():
    # Three fetches of 10, 20 and 30 ns; the counter stood at 5.0 s before
    # the first and rose by each fetch's own seconds (here: 1, 2, 3 s, to
    # tell the two sums apart). First to last: two fetches, 30 ns of spans,
    # 3 s of counter, over 200 ns.
    planes = planes_by_hand()
    planes["fetch_marks"] = [(300, 30, 8.0), (100, 10, 5.0), (200, 20, 6.0)]
    assert xplane_host.reduce_trace(planes)["fetch_check"] == {
        "fetches": 2, "interval_s": pytest.approx(200e-9),
        "span_s": pytest.approx(30e-9), "counter_s": pytest.approx(3.0)}
    # A program whose spans carry no mark (a parent commit), or one fetch.
    assert "fetch_check" not in xplane_host.reduce_trace(planes_by_hand())
    planes["fetch_marks"] = planes["fetch_marks"][:1]
    assert "fetch_check" not in xplane_host.reduce_trace(planes)


def test_modules_by_hlo_module_where_there_is_no_device_plane():
    # A CPU rehearsal: the host plane's op events name their executable.
    r = xplane_host.reduce_trace({
        "devices": [], "threads": [[("engine.dispatch", 0, 50)]],
        "host_ops": [("jit_decode_chunk", 10, 30), ("jit_prefill", 60, 20),
                     ("jit_decode_chunk", 100, 10)]})
    assert r["planes"] == 0     # and the device readers then give nothing
    assert r["modules"] == {"jit_decode_chunk": [pytest.approx(40e-9), 0.0],
                            "jit_prefill": [pytest.approx(20e-9), 0.0]}
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["idle_by_span"] == {"engine.dispatch": pytest.approx(10e-9),
                                 xplane_host.NO_SPAN: pytest.approx(30e-9)}

    class Ctx:
        trace = True
        facts = {"host_trace": r}

    assert host_trace.read(Ctx, "module_share", pattern="jit_") is None
    assert host_trace.read(Ctx, "idle_named_share") is None


def test_counter_readers():
    decode_bw = common.load_module(os.path.join(BENCH, "readers",
                                                "decode_bw.py"))
    change = common.load_module(os.path.join(BENCH, "readers",
                                             "counter_change.py"))
    rows_sum = common.load_module(os.path.join(BENCH, "readers",
                                               "trainer_rows_sum.py"))
    import shapes

    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "mistral-7b-v0.3-d6.json")))

    class Ctx:
        trace = True
        config = cfg
        facts = {
            "counters": {"decode_context_tokens": 8 * 16 * 512,
                         "decode_dispatches": 8, "compiles": 0},
            "engine": {"chunk": 16},
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "rows": [{"compiles": 0}, {"compiles": 2}, {"loss": 1.0}],
            "host_trace": {"planes": 1, "modules": {
                "jit_decode_chunk": [0.8, 4.0], "jit_prefill": [0.1, 9.0]}}}

    per_step = shapes.decode_bytes_per_step(cfg, 2, 2, 16 * 512)
    assert decode_bw.read(Ctx, pattern="^jit_(decode_chunk|spec_chunk)") \
        == pytest.approx(100 * per_step * 16 * 4 / 0.8 / 819e9)
    assert change.read(Ctx, names=["compiles"]) == 0
    assert change.read(Ctx, names=["nope"]) is None
    assert rows_sum.read(Ctx, field="compiles") == 2
    assert rows_sum.read(Ctx, field="nope") is None
    # A parent commit's engine has no such counter; a CPU has no peak.
    del Ctx.facts["counters"]["decode_context_tokens"]
    assert decode_bw.read(Ctx, pattern="^jit_decode") is None
    Ctx.facts["counters"]["decode_context_tokens"] = 1
    Ctx.facts["device"] = {"platform": "cpu", "kind": "cpu"}
    assert decode_bw.read(Ctx, pattern="^jit_decode") is None


def test_rehearsal_of_the_serve_cell_reaches_the_new_readers():
    """The serve cell through the server's entry point at the rehearsal's
    sizes on the CPU, traced: the engine's counters and its spans (in the
    profiler's trace) reach their readers; the device readers, with no
    device plane, leave their metrics out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "mistral-7b-v0.3-d6.chat", "--seed", "3000000019",
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.strip().splitlines()]
    computed = set(lines[0]["metrics_computed"])
    assert {"queue_wait_ms", "queue_wait_share", "compiles_in_window.serve",
            "engine_admit_share", "engine_dispatch_share",
            "engine_emit_share", "host_stall_share"} <= computed
    assert not {"prefill_device_share", "decode_bw_share",
                "idle_named_share.serve"} & computed
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}
    ht = json.load(open(os.path.join(
        BENCH, "out", "mistral-7b-v0.3-d6.chat", "host_trace.json")))
    assert {"engine.admit", "engine.sweep", "engine.dispatch",
            "engine.fetch", "engine.emit", "serve.prefill"} <= set(
                ht["spans"])
    assert {"jit_decode_chunk", "jit_prefill"} <= set(ht["modules"])
    # The engine thread's phases cover nearly all of its traced window.
    engine_thread = ht["threads"][0]
    assert engine_thread["covered_s"] >= 0.9 * engine_thread["window_s"]
