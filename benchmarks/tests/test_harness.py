"""CPU tests of the harness itself (not part of the repo's tests/):

    python -m pytest benchmarks/tests -q

They need no chip and import nothing from tests/, bench.py, chip_smoke.py
or kubeflow_tpu/serve/loadgen.py.
"""

import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import client  # noqa: E402
import shapes  # noqa: E402
import xplane  # noqa: E402

# -- xplane.py ---------------------------------------------------------------


def test_reduce_events_by_hand():
    # A parent 0-100 with children 10-30 and 50-90 (the second with a
    # grandchild 60-70), a gap 100-150, then a lone op 150-200 that a
    # straggler 190-220 overlaps without nesting.
    r = xplane.reduce_events([
        ("while", 0, 100), ("a", 10, 20), ("b", 50, 40), ("c", 60, 10),
        ("d", 150, 50), ("e", 190, 30)])
    assert r["busy_s"] == pytest.approx(170e-9)      # 100 + 70
    assert r["window_s"] == pytest.approx(220e-9)
    assert r["ops"] == pytest.approx({
        "while": 40e-9, "a": 20e-9, "b": 30e-9, "c": 10e-9,
        "d": 40e-9, "e": 30e-9})
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["gaps"] == [("after while | before d", pytest.approx(50e-9))]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One step of the train rehearsal (llama_tiny sizes, the CPU),
    recorded by the trainer's own profiler window."""
    path = tmp_path_factory.mktemp("trace") / "rehearsal.xplane.pb"
    with gzip.open(os.path.join(BENCH, "tests", "data",
                                "rehearsal.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    return str(path)


def test_xplane_against_recorded_trace(recorded):
    # One XLA worker thread of the CPU trace stands in for a device's op
    # line: it nests (ThunkExecutor::Execute around the ops) and idles.
    # The numbers were worked out from the file by painting every
    # nanosecond of the line with its innermost event (numpy, 45M cells)
    # and counting: not with xplane.py's sweep.
    s = xplane.summarize(recorded, plane_rx=r"^/host:CPU$",
                         line_rx=r"^tf_XLAEigen/-1146494459135266455$")
    assert s["planes"] == 1
    assert s["busy_s"] == pytest.approx(11519367e-9, rel=1e-9)
    assert s["window_s"] == pytest.approx(44873929e-9, rel=1e-9)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(
        0.7432948873275615)
    ops = dict(s["ops"])
    assert ops["ThunkExecutor::Execute"] == pytest.approx(3960275e-9)
    assert ops["dot_general.5"] == pytest.approx(1158597e-9)
    assert s["ops"][0][0] == "ThunkExecutor::Execute"
    assert s["gaps"][0][1] == pytest.approx(8516584e-9)
    assert sum(ops.values()) == pytest.approx(s["busy_s"])

    class Ctx:
        facts = {"xplane": s}

    from readers import xplane_idle, xplane_name_share
    assert xplane_idle.read(Ctx) == pytest.approx(74.32948873275615)
    assert xplane_name_share.read(Ctx, pattern=r"^dot\.") == pytest.approx(
        100 * 999941 / 11519367)
    # No TPU plane in a CPU trace: the default reduction finds nothing,
    # and the readers then return nothing rather than a number.
    empty = xplane.summarize(recorded)
    assert empty["planes"] == 0 and empty["busy_s"] == 0.0
    Ctx.facts = {"xplane": empty}
    assert xplane_idle.read(Ctx) is None
    assert xplane_name_share.read(Ctx, pattern="x") is None


# -- client.py ---------------------------------------------------------------


def _rec(t_send, events, max_tokens=None, **done):
    n = sum(k for _, k in events)
    want = n if max_tokens is None else max_tokens
    return {"t_send": t_send, "status": 200, "error": None,
            "max_tokens": want, "events": events, "t_done": 0.0,
            "done": {"done": True, "num_output_tokens": n,
                     "output_logprobs": [-1.0] * n, **done}}


def test_client_arithmetic():
    a = _rec(10.0, [(10.5, 1), (11.0, 16), (12.0, 16)])
    assert client.ttft_s(a) == pytest.approx(0.5)
    assert client.tpot_s(a) == pytest.approx(1.5 / 32)
    one = _rec(10.0, [(10.25, 8)])
    assert client.ttft_s(one) == pytest.approx(0.25)
    assert client.tpot_s(one) is None       # a single event has no gap
    assert client.ttft_s(_rec(1.0, [])) is None
    # Tokens count where their event arrived, not where the request began.
    assert client.tokens_between([a, one], 10.4, 11.5) == 17
    assert client.tokens_between([a, one], 0.0, 99.0) == 41
    assert client.tokens_between([a, one], 12.0, 12.0) == 0
    assert client.percentile([3.0, 1.0, 2.0, 4.0], 50) == pytest.approx(2.5)
    assert client.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert client.percentile([1.0, 2.0], 95) == pytest.approx(1.95)
    assert client.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        client.percentile([], 95)


def test_client_ok_refuses_what_failed():
    assert client.ok(_rec(0.0, [(1.0, 4)]))
    assert not client.ok(_rec(0.0, [(1.0, 4)], max_tokens=5))
    bad = _rec(0.0, [(1.0, 2)])
    bad["done"]["output_logprobs"] = [-1.0, float("nan")]
    assert not client.ok(bad)
    shed = _rec(0.0, [])
    shed.update(status=503, done=None)
    assert not client.ok(shed)
    cut = _rec(0.0, [(1.0, 2)])
    cut["done"] = None                       # never finished
    assert not client.ok(cut)


# -- shapes.py ---------------------------------------------------------------


def test_shapes_from_a_configuration_file():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "qwen2.5-1.5b-d12.json")))
    # 1536*1536*2 + 2*1536*256 + 3*1536*8960 = 46,792,704 a layer
    assert shapes.layer_matmul_params(cfg) == 46792704
    assert shapes.matmul_params(cfg) == 12 * 46792704 + 1536 * 151936
    flops = shapes.train_flops_per_token(cfg, 512)
    assert flops == 6 * shapes.matmul_params(cfg) + \
        3 * 12 * 4 * 12 * 128 * 513 / 2
    m = json.load(open(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3-d6.json")))
    assert shapes.layer_matmul_params(m) == 218103808
    # bf16 weights, bf16 K/V, 16 rows at 512 tokens of context each
    assert shapes.decode_bytes_per_step(m, 2, 2, 16 * 512) == \
        shapes.matmul_params(m) * 2 + 2 * 6 * 8 * 128 * 2 * 16 * 512
    assert shapes.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(ValueError):
        shapes.peak("TPU v9", "bf16_flops")


# -- run.py: everything by name, nothing registered ---------------------------


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A cell, a configuration, a traffic mix, a cell kind, a per-layer
    metric and a reader that exist only as files added to a copy of the
    benchmark, plus entries in BENCHMARK.json: run.py finds them all by
    name, and no file that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (root / "kubeflow_tpu").mkdir()      # run.py asks only that it is there
    b = root / "benchmarks"
    (b / "configs" / "newcfg.json").write_text(json.dumps(
        {"source": "test", "answer": 21}))
    (b / "traffic" / "newmix.json").write_text(json.dumps(
        {"kind": "newkind", "factor": 2}))
    (b / "kinds" / "newkind.py").write_text(
        "def run(ctx):\n"
        "    ctx.facts['product'] = ctx.config['answer'] * ctx.mix['factor']\n"
        "    return {'correct': True, 'attempted': 1, 'failed': 0,\n"
        "            'e2e': {'setup_s': 1.5, 'new_rate': 7.0},\n"
        "            'device': {'platform': 'tpu', 'kind': 'fake',\n"
        "                       'count': 1, 'memory_peak_bytes': 0}}\n")
    (b / "readers" / "newreader.py").write_text(
        "def read(ctx, key, plus=0):\n    return ctx.facts.get(key, None)"
        " and ctx.facts[key] + plus\n")
    (b / "layer_metrics" / "new_metric.json").write_text(json.dumps(
        {"name": "new_metric", "reader": "newreader",
         "args": {"key": "product", "plus": 0.5}}))
    (b / "layer_metrics" / "absent_metric.json").write_text(json.dumps(
        {"name": "absent_metric", "reader": "newreader",
         "args": {"key": "nothing-to-read"}}))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "newcfg", "source": "test",
                             "file": "benchmarks/configs/newcfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "newcfg.new", "config": "newcfg",
                               "traffic": "newmix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append(
        {"name": "new_rate", "unit": "x/s", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": ["newcfg.new"]})
    for name in ("new_metric", "absent_metric"):
        bench["per_layer"].append(
            {"name": name, "unit": "x", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "new_rate", "workloads": ["newcfg.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    timed = _run(str(root), "--workload", "newcfg.new", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert timed.returncode == 0, timed.stderr
    line = json.loads(timed.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {"setup_s": {"value": 1.5, "unit": "s"},
                               "new_rate": {"value": 7.0, "unit": "x/s"}}
    traced = _run(str(root), "--workload", "newcfg.new", "--seed", "1",
                  "--seconds", "1", "--trace", "1")
    assert traced.returncode == 0, traced.stderr
    line = json.loads(traced.stdout.strip().splitlines()[-1])
    # The reader that found nothing is left out of the line.
    assert line["metrics"] == {"new_metric": {"value": 42.5, "unit": "x"}}

    # No cell of that name, and no program beside the benchmark: no result.
    assert _run(str(root), "--workload", "nope").returncode != 0
    (root / "kubeflow_tpu").rmdir()
    gone = _run(str(root), "--workload", "newcfg.new")
    assert gone.returncode != 0 and gone.stdout.strip() == ""


def test_rehearsal_of_a_train_cell_end_to_end():
    """The real train cell through the trainer's entry point at the
    rehearsal's sizes on the CPU: the last line parses, says it ran on the
    CPU, and carries no time, rate or share."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w["name"] for w in bench["workloads"]
                if w["traffic"].startswith("pretrain"))
    done = _run(ROOT, "--workload", cell, "--seed", "3000000011",
                "--seconds", "2", "--trace", "1", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert line["metrics"] == {}
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    assert "breakdown" not in line
    # The same cell without the switch must not fall back to this CPU.
    real = _run(ROOT, "--workload", cell, "--seconds", "2")
    assert real.returncode != 0 and real.stdout.strip() == ""
