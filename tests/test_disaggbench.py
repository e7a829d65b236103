"""Pins the disaggregation benchmark harness
(kubeflow_tpu/serve/disaggbench.py, `bench.py --disaggbench`, ISSUE 13):
a slow-tier run of the quick shape with the mechanism assertions the
acceptance criteria name (blocks shipped > 0, ZERO decode-replica prefill
chunks, spill/restore counters consistent), so the harness can't rot.

Absolute latencies are CPU-tiny-model numbers (the result says so);
assertions here are mechanism-strong / absolute-weak. Single quick runs
on a shared host are too noisy to gate a latency claim on.
"""

import pytest


def _check_shape(r: dict) -> None:
    assert r["metric"] == "disaggbench"
    assert r["mode"] == "real-tiny-engines-cpu"
    assert "REAL GenerationEngine" in r["note"]  # honest labeling
    uni, dis = r["arms"]["unified"], r["arms"]["disagg"]
    for arm in (uni, dis):
        assert arm["requests"] > 0
        assert arm["completed_ok"] > 0
        assert arm["errors"] == 0
        assert arm["ttft_p50_ms"] and arm["ttft_p99_ms"]
        assert arm["ttft_p99_ms"] >= arm["ttft_p50_ms"]
        assert arm["decode_tail_p99_ms"] and arm["decode_tail_p99_ms"] > 0

    # -- mechanism: the role split actually happened ---------------------
    roles = {v["role"] for v in dis["replicas"].values()}
    assert roles == {"prefill", "decode"}
    shipped = received = 0
    for rep in dis["replicas"].values():
        if rep["role"] == "decode":
            # THE disaggregation invariant: zero prefill chunks ever
            # ran on a decode replica; every admission came off the
            # wire.
            assert rep["prefill_chunks"] == 0
            assert rep["remote_admits"] == dis["completed_ok"]
            received += rep["kv_blocks_received"]
        else:
            assert rep["decode_dispatches"] == 0
            assert rep["prefill_chunks"] > 0
            shipped += rep["kv_blocks_shipped"]
        # Spill counters consistent: restored never exceeds spilled.
        assert rep["kv_restored_blocks"] <= rep["kv_spilled_blocks"]
    assert shipped > 0
    assert shipped == received  # every shipped block landed
    assert dis["router"]["handoffs"] == dis["completed_ok"]
    assert dis["router"]["decode_pool"] == dis["router"]["handoffs"]
    # The unified arm never ships — it IS the escape hatch.
    for rep in uni["replicas"].values():
        assert rep["role"] == "unified"
        assert rep["kv_blocks_shipped"] == 0
        assert rep["remote_admits"] == 0
    assert uni["router"]["handoffs"] == 0


@pytest.mark.slow
def test_disaggbench_quick_shape():
    from kubeflow_tpu.serve.disaggbench import run_disaggbench

    _check_shape(run_disaggbench(quick=True))
