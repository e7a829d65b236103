"""What ISSUE 36 added to the yardstick: the cell found as files only, the
required work of JoyAI-LLM-Flash's serving path against numbers worked by hand
from the issue's arithmetic, `serve_ref.compare` fed each recorded control,
and one `--rehearse-cpu` of the cell end to end."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import run as bench_run  # noqa: E402

CELL = "joyai-llm-flash-d5.reason-closed16"
required = common.load_module(os.path.join(BENCH, "required", "joyai.py"))
serve = common.load_module(os.path.join(BENCH, "kinds", "serve.py"))
serve_ref = common.load_module(os.path.join(BENCH, "kinds", "serve_ref.py"))

NEW = {"serve_mfu.joyai", "decode_bw_share.joyai", "mla_share.serve",
       "moe_share.serve", "mla_decode_roofline_share.serve",
       "moe_decode_roofline_share.serve", "moe_experts_touched_share"}
SHARED = {"slot_occupancy", "host_stall_share", "fetch_overlap_share",
          "decode_ms_per_dispatch", "device_idle_share.serve", "tpot_p95_ms",
          "prefill_device_share", "queue_wait_ms", "queue_wait_share",
          "engine_admit_share", "engine_dispatch_share", "engine_emit_share",
          "idle_named_share.serve", "compiles_in_window.serve",
          "weight_convert_share.serve"}


@pytest.fixture(scope="module")
def bench():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def cfg():
    return common.load_json(
        os.path.join(BENCH, "configs", "joyai-llm-flash-d5.json"))


def test_the_cell_is_found_as_files_only(bench):
    cell, config, mix, kind = bench_run.resolve(bench, CELL, rehearse=False)
    assert (cell["chips"], mix["kind"], mix["loop"]) == (1, "serve_ref",
                                                         "closed")
    assert mix["callers"] == config["engine"]["slots"] == 16
    assert config["registry_model"] == "joyai_llm_flash"
    assert kind.__name__ == "bench_kinds_serve_ref"
    assert bench["workloads"][-1]["name"] == CELL      # appended, not placed
    mine = {m["name"] for m in bench["per_layer"]
            if bench_run.applies(m, CELL)}
    assert mine == NEW | SHARED
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == [
        "serve_mfu.joyai", "decode_bw_share.joyai", "mla_share.serve",
        "moe_share.serve", "mla_decode_roofline_share.serve",
        "moe_decode_roofline_share.serve", "moe_experts_touched_share"]
    for m in bench["per_layer"]:
        if m["name"] not in mine:
            continue
        spec = common.load_json(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".json"))
        assert os.path.isfile(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
    e2e = {m["name"] for m in bench["end_to_end"]
           if bench_run.applies(m, CELL)}
    assert e2e == {"setup_s", "out_tok_s", "ttft_p95_ms"}


def test_the_traffic_is_the_issues(bench):
    _, config, mix, _ = bench_run.resolve(bench, CELL, rehearse=False)
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"median": 1024, "sigma": 0.8, "min": 128, "max": 6144},
        {"median": 512, "sigma": 0.6, "min": 128, "max": 1536})
    assert (mix["round"], mix["sizes_seed"], mix["warm_s"], mix["drain_s"],
            mix["trace_s"], mix["request_timeout_s"], mix["sharing"]) == (
        64, 20261005, 20, 45, 3, 120, "none")
    pool = serve.size_pool(mix)
    assert len(pool) == 64
    # No request outgrows a slot, and every probe fits one too.
    assert max(p + o for p, o in pool) < config["engine"]["max_len"]
    assert all(p + o < config["engine"]["max_len"]
               for p, o in config["reference"]["probes"])


def test_the_configuration_keeps_the_published_widths(cfg):
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "vocab_size",
        "first_k_dense_replace")] == [2048, 32, 1536, 512, 128, 64, 128,
                                      7168, 768, 256, 8, 1, 129280, 1]
    assert (cfg["rope_theta"], cfg["rope_interleave"], cfg["rms_norm_eps"],
            cfg["routed_scaling_factor"], cfg["scoring_func"],
            cfg["norm_topk_prob"], cfg["topk_method"]) == (
        32000000, True, 1e-6, 2.5, "sigmoid", True, "noaux_tc")
    assert list(cfg["reduced"]) == ["num_hidden_layers",
                                    "num_nextn_predict_layers"]
    assert cfg["num_hidden_layers"] == cfg["model_kwargs"]["num_layers"] == 5
    assert cfg["num_nextn_predict_layers"] == 0
    assert set(cfg["assumed"]) >= {"precision", "initialisers", "max_len",
                                   "window_size"}
    engine = cfg["engine"]
    assert cfg["window_size"] == engine["max_len"] == 8192
    assert engine["kv_blocks"] * engine["kv_block_size"] == 16 * 8192
    assert all(engine["prefill_buckets"][-1] % b == 0
               for b in engine["prefill_buckets"])


def test_required_work_by_hand(cfg):
    # Attention: 2048 x 1536 + 1536 x 32 x 192 + 2048 x 576 + 512 x 32 x 256
    # + 32 x 128 x 2048.
    assert required.mla_params(cfg) == (
        3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608
    ) == 26_345_472
    assert required.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert required.expert_bytes(cfg) == 9_437_184          # "9.44 MB"
    assert required.router_params(cfg) == 524_288
    # An expert layer 1,239.5M, layer 0 70.4M, both tables 529.5M: 5,558.1M.
    expert_layer = 26_345_472 + 257 * 4_718_592 + 524_288
    assert expert_layer == 1_239_547_904
    assert required.held_params(cfg) == (
        4 * expert_layer + 26_345_472 + 3 * 2048 * 7168
        + 2 * 129_280 * 2048) == 5_558_108_160
    assert 2 * required.held_params(cfg) == pytest.approx(11.12e9, rel=1e-3)
    # A token: attention everywhere, the dense SwiGLU once, 8 + 1 experts
    # and the router four times, the head.
    assert required.active_params(cfg) == (
        5 * 26_345_472 + 44_040_192 + 4 * (9 * 4_718_592 + 524_288)
        + 264_765_440) == 612_499_456
    # A cached row: 576 values in bf16; the pool of 16 x 8192 rows x 5.
    assert required.row_bytes(cfg) == 1_152
    assert required.pool_bytes(cfg, 16, 8192) == 131_072 * 5_760 \
        == 754_974_720
    # The absorbed core: 2 x 32 x 1,088 FLOPs a row a layer.
    assert required.core_flops(cfg, 1) == 2 * 32 * 1_088 * 5
    assert required.core_bytes_decode(cfg, 1000) == 1000 * 5_760
    # A decode step of 16 rows at 2,000 rows each touching 101 experts a
    # layer: 0.39 GB of other weights + 0.53 GB of head + 3.8 GB + 0.18 GB.
    fixed = 2 * (5 * 26_345_472 + 44_040_192 + 4 * 4_718_592
                 + 264_765_440) + 4 * 524_288 * 4
    assert required.fixed_bytes_per_step(cfg) == fixed == 927_203_328
    step = required.decode_bytes_per_step(cfg, 16 * 2000, 4 * 101)
    assert step == fixed + 404 * 9_437_184 + 32_000 * 5_760
    assert step == pytest.approx(4.92e9, rel=5e-3)
    # Prompts: unabsorbed pairs of 2 x 32 x (192 + 128) FLOPs a layer.
    assert required.prompt_rows(cfg, 1024) == 1024 * 1025 // 2
    layers_active = 612_499_456 - 264_765_440
    assert required.prompt_flops(cfg, 1024) == (
        2 * layers_active * 1024 + 2 * 264_765_440
        + 2 * 32 * 320 * 5 * (1024 * 1025 // 2))
    assert required.decode_flops(cfg, 16, 32_000) == (
        2 * 612_499_456 * 16 + 2 * 32 * 1_088 * 5 * 32_000)


def test_touched_share_of_a_fair_router():
    spec = common.load_json(os.path.join(
        BENCH, "layer_metrics", "moe_experts_touched_share.json"))
    assert spec["args"]["scale"] == 100 / (256 * 4)
    fair = 256 * (1 - (1 - 1 / 256) ** 128)
    assert 100 * fair / 256 == pytest.approx(39.4, abs=0.05)


def test_compare_refuses_each_recorded_control(cfg):
    limits = cfg["reference"]["limits"]
    assert set(limits) == {"logprob_gap_max", "logprob_gap_mean",
                           "stated_gap_mean", "stated_gap_median"}
    recorded = common.load_json(
        os.path.join(BENCH, "reference", "joyai_controls.json"))
    assert len(recorded["sound"]) >= 8
    assert set(recorded["controls"]) == {
        "rope_half", "scale_nope", "raw_gates", "no_shared", "no_bias",
        "bfloat16"}
    for reading in recorded["sound"]:
        ok, compared = serve_ref.compare(reading, limits)
        assert ok, compared
    for name, readings in recorded["controls"].items():
        # The three that read nearest the limits were taken at two seeds.
        assert len(readings) >= (2 if name in (
            "scale_nope", "no_bias", "bfloat16") else 1), name
        for reading in readings:
            ok, compared = serve_ref.compare(reading, limits)
            assert not ok, (name, compared)
    # What the limits let through stays on record as that: the fp32
    # reference with its router alone in bf16 lies 1.13-1.15 x the sound
    # readings' top, too near for a limit with room on both sides.
    assert set(recorded["unseen"]) == {"router_bf16"}
    for reading in recorded["unseen"]["router_bf16"]:
        ok, compared = serve_ref.compare(reading, limits)
        assert ok, compared
    # Each limit has room on both sides of its readings.
    top = {name: max(r[name] for r in recorded["sound"]) for name in limits}
    for name, limit in limits.items():
        assert limit >= 1.3 * top[name], name


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
    assert parts["idle_blocks_by_kind"] == {"latent_blocks_used": 0}
    assert parts["window_requests_compared"] >= 1
    for name in ("logprob_gap_max", "logprob_gap_mean", "stated_gap_mean"):
        assert parts["compared"][name]["err"] <= parts["compared"][name][
            "limit"]
