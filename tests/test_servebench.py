"""Pins the serving-benchmark harness (kubeflow_tpu/serve/bench.py): the
quick/tiny shape must produce every artifact section with sane values, so
the chip run (`bench.py --serve` → SERVEBENCH.json) can't silently rot."""

import numpy as np
import pytest

from kubeflow_tpu.serve.bench import run_servebench

pytestmark = pytest.mark.slow  # multi-process/e2e/AOT tier


def test_servebench_quick_shape():
    import dataclasses

    import jax.numpy as jnp

    from kubeflow_tpu.models.llama import llama_tiny

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32, num_layers=2)
    r = run_servebench(cfg=cfg, quick=True)
    # Pipelined-vs-sync A/B (ISSUE 3 tentpole): both engines measured,
    # and the overlap mechanism visibly engaged — the sync engine blocks
    # on every fetch, the pipelined one overlaps its steady state.
    ab = r["pipelined_vs_sync"]
    for row in ("sync_depth1", "pipelined_depth2"):
        assert ab[row]["tok_s_e2e"] > 0
        assert ab[row]["wall_s"] > 0
    assert ab["sync_depth1"]["overlapped_fetches"] == 0
    assert ab["pipelined_depth2"]["overlapped_fetches"] > 0
    assert ab["speedup_wall"] > 0
    # Paged-vs-flat A/B (ISSUE 6 tentpole): equal pool memory, paged
    # decode width doubled — the paged engine must actually RUN more
    # concurrent requests than the flat engine has slots.
    pf = r["paged_vs_flat"]
    assert pf["flat"]["tok_s_e2e"] > 0 and pf["paged"]["tok_s_e2e"] > 0
    assert pf["paged"]["pool_tokens"] == pf["flat"]["pool_tokens"]
    assert pf["paged"]["peak_inflight_requests"] > pf["flat"]["slots"]
    assert pf["concurrency_gain"] > 1
    # Spec × paged × depth-2 A/B (ISSUE 18 tentpole): both arms on the
    # same paged pool at pipeline_depth=2; the greedy probe is token+
    # logprob-identical across arms (lossless claim on the composed
    # path), and the mixed waves (one top-p row each) still speculated
    # for their greedy rows — the sub-batch split proven by counters.
    sg = r["spec_paged"]
    assert sg["vanilla_paged"]["tok_s_e2e"] > 0
    assert sg["spec_paged"]["tok_s_e2e"] > 0
    assert sg["spec_paged"]["pipeline_depth"] == 2
    assert sg["spec_paged"]["kv_block_size"] == 16
    assert sg["greedy_identical"] is True
    assert sg["mixed_traffic_speculated"] is True
    assert sg["spec_paged"]["acceptance"] > 0.9  # self-draft ceiling
    assert sg["speedup_wall"] > 0
    # Quant × paged A/B (ISSUE 19 tentpole): equal pool HBM, the int8
    # arm's block count scaled by the byte ratio (>1.5× everywhere,
    # ≈2× at bf16/D=64, 3.2× on the f32 tiny model) — and the extra
    # blocks became extra CONCURRENT requests (peak in-flight ≥1.8×
    # the full-precision arm). Quality delta is measured (greedy probe
    # token-identical on the tiny model, logprob drift reported), and
    # the fmt-3 handoff ships ≤0.55× the fmt-1 bytes for the same
    # prompt.
    qp = r["quant_paged"]
    assert qp["full_paged"]["tok_s_e2e"] > 0
    assert qp["quant_paged"]["tok_s_e2e"] > 0
    assert qp["quant_paged"]["pool_bytes"] <= qp["full_paged"]["pool_bytes"]
    assert qp["kv_blocks_ratio"] > 1.5
    assert (qp["quant_paged"]["kv_blocks"]
            > 1.5 * qp["full_paged"]["kv_blocks"])
    assert qp["concurrency_gain"] >= 1.8
    assert qp["quality"]["greedy_ids_identical"] is True
    assert qp["quality"]["max_logprob_delta"] < 0.05
    assert qp["wire"]["fmt1_fmt"] == 1 and qp["wire"]["fmt3_fmt"] == 3
    assert qp["wire"]["fmt3_vs_fmt1"] <= 0.55
    # Decode concurrency section: throughput positive at each slot count.
    assert set(r["decode"]) == {"slots_1", "slots_2"}
    for v in r["decode"].values():
        assert v["decode_tok_s"] > 0
    # Length-aware decode section: both variants measured.
    db = r["decode_buckets"]
    assert db["bucketed_tok_s"] > 0 and db["flat_tok_s"] > 0
    assert db["speedup"] > 0
    # TTFT per bucket + chunked admission (largest bucket 16 < max_len-1).
    assert set(r["ttft_s"]) == {"8", "16"}
    assert all(v > 0 for v in r["ttft_s"].values())
    assert r["chunked_prefill"]["prompt_len"] > 16
    assert r["chunked_prefill"]["admission_s"] > 0
    # Quantization deltas: all three arms decoded (bf16, the FIXED
    # output-side-scale int8 path, and the legacy dequant-per-apply
    # control — ROADMAP item 4 first half); int8 params are smaller.
    # The throughput ordering is a chip claim (the HLO-shape guard in
    # test_quant_dequant.py pins the mechanism on CPU).
    q = r["quant"]
    assert q["bf16_tok_s"] > 0 and q["int8_tok_s"] > 0
    assert q["int8_legacy_tok_s"] > 0
    assert q["fixed_vs_legacy"] > 0
    assert q["param_bytes"]["quantized"] < q["param_bytes"]["full"]
    # Long-max_len bucketed-decode row (where the win can appear).
    dbl = r["decode_buckets_long"]
    assert dbl["max_len"] > r["max_len"]
    assert dbl["bucketed_tok_s"] > 0 and dbl["flat_tok_s"] > 0
    # Speculative decoding rows: self-draft must accept nearly all
    # proposals; the random small draft nearly none.
    sp = r["spec_decode"]
    assert sp["vanilla"]["tok_s"] > 0
    assert sp["self_draft"]["acceptance"] > 0.9
    assert sp["small_draft"]["acceptance"] < 0.5
    assert sp["self_draft"]["spec_dispatches"] > 0
    # Multi-LoRA mixed-adapter batch measured against base.
    ml = r["multilora"]
    assert ml["base_tok_s"] > 0 and ml["mixed_adapter_tok_s"] > 0
    # Batcher percentiles under load.
    b = r["batcher"]
    assert b["requests"] == 64
    assert 0 < b["p50_ms"] <= b["p99_ms"]
    assert np.isfinite(b["throughput_rps"]) and b["throughput_rps"] > 0
