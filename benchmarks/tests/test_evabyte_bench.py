"""What ISSUE 33 added to the yardstick: the cell found as files only, the
required work of EvaByte's serving path against numbers worked by hand at toy
and published sizes, `serve_ref.compare` fed each recorded control, and one
`--rehearse-cpu` of the cell end to end."""

import inspect
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import run as bench_run  # noqa: E402

CELL = "evabyte-6.5b-d6.longdoc-closed16"
required = common.load_module(os.path.join(BENCH, "required", "evabyte.py"))
serve_ref = common.load_module(os.path.join(BENCH, "kinds", "serve_ref.py"))


@pytest.fixture(scope="module")
def bench():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def cfg():
    return common.load_json(
        os.path.join(BENCH, "configs", "evabyte-6.5b-d6.json"))


def test_the_cell_is_found_as_files_only(bench):
    cell, config, mix, kind = bench_run.resolve(bench, CELL, rehearse=False)
    assert (cell["chips"], mix["kind"], mix["loop"]) == (1, "serve_ref",
                                                         "closed")
    assert mix["callers"] == config["engine"]["slots"] == 16
    assert config["registry_model"] == "evabyte_6_5b"
    assert kind.__name__ == "bench_kinds_serve_ref"
    mine = [m for m in bench["per_layer"] if bench_run.applies(m, CELL)]
    assert len(mine) >= 21
    for m in mine:
        spec = common.load_json(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".json"))
        assert os.path.isfile(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    e2e = {m["name"] for m in bench["end_to_end"]
           if bench_run.applies(m, CELL)}
    assert e2e == {"setup_s", "out_tok_s", "ttft_p95_ms"}


def test_the_configuration_keeps_the_published_widths(cfg):
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "num_pred_heads",
        "chunk_size", "window_size")] == [4096, 32, 32, 128, 11008, 320, 8,
                                          16, 2048]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == cfg["model_kwargs"]["num_layers"] == 6
    engine = cfg["engine"]
    assert engine["kv_block_size"] == cfg["chunk_size"]
    assert engine["prefill_buckets"][-1] == cfg["window_size"]
    # 16 slots x (128 exact + 48 summary blocks of 16 rows).
    assert engine["kv_blocks"] == 16 * (128 + 48) == 2816


def test_required_work_by_hand(cfg):
    # A layer: q, k, v, o 4 x 4096^2 and the gated MLP 3 x 4096 x 11008.
    assert required.layer_matmul_params(cfg) == (
        67_108_864 + 135_266_304) == 202_375_168
    assert required.head_params(cfg) == 8 * 320 * 4096 == 10_485_760
    assert required.matmul_params(cfg) == 6 * 202_375_168 + 10_485_760
    # A state row of a layer: a key and a value of 32 x 128 in bf16.
    assert required.row_bytes(cfg) == 16_384
    # Every slot at its worst: 2,048 exact + 6 x 128 summary rows x 6 layers.
    assert required.pool_bytes(cfg, 16, 14336) == (
        16 * 2816 * 16_384 * 6) == 4_429_185_024
    assert required.pool_bytes(cfg, 16, 14336) == (
        cfg["engine"]["kv_blocks"] * 16 * 16_384 * 6)
    # The query at 5,000 reads 905 exact rows and two windows' 256 summaries.
    assert required.rows_read(cfg, 5000) == (905, 256)
    assert required.rows_read(cfg, 2047) == (2048, 0)
    assert required.rows_read(cfg, 2048) == (1, 128)
    # A decode step of 16 rows at such a state: bf16 weights once + rows.
    rows = 16 * (905 + 256)
    assert required.decode_bytes_per_step(cfg, rows) == (
        2 * 1_224_736_768 + rows * 16_384 * 6)
    assert required.core_flops(cfg, rows) == 4 * 4096 * 6 * rows
    assert required.decode_flops(cfg, 16, rows) == (
        2 * 1_224_736_768 * 16 + 4 * 4096 * 6 * rows)


@pytest.mark.parametrize("n", [1, 7, 32, 33, 100])
def test_prompt_rows_against_a_count_by_position(n):
    toy = {"window_size": 32, "chunk_size": 4}
    assert required.prompt_rows(toy, n) == sum(
        sum(required.rows_read(toy, t)) for t in range(n))


def test_prompt_flops_at_toy_size():
    toy = {"hidden_size": 8, "num_attention_heads": 2, "head_dim": 4,
           "intermediate_size": 16, "num_hidden_layers": 2,
           "num_pred_heads": 2, "vocab_size": 10, "window_size": 8,
           "chunk_size": 2}
    layer = 4 * 8 * 8 + 3 * 8 * 16
    assert required.layer_matmul_params(toy) == layer == 640
    # 10 bytes: window 0 whole (8 x 9 / 2 pairs), two more rows that read
    # 1 and 2 exact rows and its 4 summaries each; the 8 keys of window 0
    # are pooled once.
    pairs = 36 + (1 + 2) + 2 * 4
    assert required.prompt_rows(toy, 10) == pairs
    assert required.prompt_flops(toy, 10) == (
        2 * 2 * layer * 10 + 2 * 2 * 10 * 8 + 4 * 8 * 2 * pairs
        + 10 * 8 * 2 * 8)


def test_compare_refuses_each_recorded_control(cfg):
    limits = cfg["reference"]["limits"]
    assert set(limits) == {"logprob_gap_max", "logprob_gap_mean",
                           "stated_gap_mean"}
    recorded = common.load_json(
        os.path.join(BENCH, "reference", "evabyte_controls.json"))
    assert len(recorded["sound"]) >= 6
    assert len(recorded["sound"]) + len(recorded["sound_probes_only"]) >= 8
    assert set(recorded["controls"]) == {"no_summary", "sliding",
                                         "no_offset", "bfloat16"}

    def compare(reading):
        """Under the limits on the numbers the reading has (the first
        session's readings lack the gap to the stated precision)."""
        return serve_ref.compare(
            reading, {k: v for k, v in limits.items() if k in reading})

    for reading in recorded["sound"]:
        assert set(limits) <= set(reading)
    for reading in recorded["sound"] + recorded["sound_probes_only"]:
        ok, compared = compare(reading)
        assert ok, compared
    for name, readings in recorded["controls"].items():
        assert len(readings) >= 2
        for reading in readings:
            ok, compared = compare(reading)
            failed = {k for k, c in compared.items() if c["err"] > c["limit"]}
            assert not ok
            if name == "bfloat16":
                # The precision below the configuration's: its gaps to
                # the fp32 reference pass (the weights' rounding, which it
                # shares with the server, makes most of them); its
                # distance from the stated precision does not.
                assert failed == {"stated_gap_mean"}, compared
            else:
                assert {"logprob_gap_max", "logprob_gap_mean"} <= failed


def test_serve_ref_repeats_the_serve_kinds_window():
    """`serve_ref.run` repeats `serve.run` until a `benchmark` PR folds the
    two (PERF.md section 7): every line of the latter that places the
    window, the traced span, the drain or an end-to-end metric stands in
    the former, letter for letter."""
    serve = common.load_module(os.path.join(BENCH, "kinds", "serve.py"))

    def lines(fn):
        return [re.sub(r"\bserve\.", "", x.strip())
                for x in inspect.getsource(fn).splitlines()]

    placing = re.compile(
        r"\b(w0|w1|span|t_loop)\b|loop\.stop|sleep_until|trace\.(start|stop)"
        r"|idle_blocks\(|tokens_between|percentile|out_tok_s")
    theirs = [x for x in lines(serve.run) if placing.search(x)]
    mine = set(lines(serve_ref.run))
    assert len(theirs) >= 18
    assert [x for x in theirs if x not in mine] == []


def test_rehearsal_of_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "4", "--trace", "0",
         "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    line, parts = lines[-1], lines[-2]["parts"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}  # a CPU number is never a result
    assert parts["idle_blocks_by_kind"] == {"exact_blocks_used": 0,
                                            "summary_blocks_used": 0}
    assert parts["window_requests_compared"] >= 1
    for name in ("logprob_gap_max", "logprob_gap_mean", "stated_gap_mean"):
        assert parts["compared"][name]["err"] <= parts["compared"][name][
            "limit"]
