"""Pins the long-context harness (kubeflow_tpu/utils/longctx.py): the
tiny-model shape must produce a complete fit report off-chip, so
`bench.py --longctx` can't rot between chip runs (a harness that only ever
runs when the chip is up breaks unnoticed)."""

import jax
import pytest

from kubeflow_tpu.utils import longctx


def test_analyze_fit_tiny_shape():
    r = longctx.analyze_fit(2, 64, size="tiny")
    assert r["batch"] == 2 and r["seq_len"] == 64
    assert r["loss_impl"] == "chunked"
    assert r["total_conservative_bytes"] == (
        r["argument_bytes"] + r["temp_bytes"] + r["output_bytes"]
        - r["alias_bytes"])
    assert r["total_conservative_gib"] >= 0
    assert r["fits_v5e_hbm"] is True  # tiny model trivially fits
    assert r["hbm_budget_gib"] == 16.0
    assert r["model_params"] > 0


def test_measure_tiny_shape():
    """The measured path (what the chip run executes) works off-chip too:
    real steps on the CPU backend, a sane tok/s — and no MFU, because
    the CPU has no accelerator peak to hold it against."""
    r = longctx.measure(2, 64, timed_steps=2, size="tiny")
    assert r["tok_s"] > 0
    assert r["mfu"] is None
    assert r["avg_step_time_s"] > 0
    assert r["device_kind"] == jax.devices()[0].device_kind


@pytest.mark.slow  # live knob sweep; heaviest representative here
def test_tune_point_tiny_shape():
    """The knob sweep (bench.py --longctx-tune) runs off-chip on the
    tiny shape: every variant measured or its failure recorded inline,
    fastest-first ordering, knob fields present."""
    variants = ({}, {"remat_policy": "save_attn"}, {"loss_chunk": 32},
                {"flash_block": (64, 32)})
    rows = longctx.tune_point(2, 64, timed_steps=1, variants=variants,
                              size="tiny")
    assert len(rows) == len(variants)
    ok = [r for r in rows if "tok_s" in r]
    assert ok, rows  # at least the default variant must measure
    assert ok == sorted(ok, key=lambda r: -r["tok_s"])
    for r in ok:
        assert {"remat_policy", "loss_chunk", "flash_block"} <= set(r)
