"""Fine-tune trainer amenities: grad clipping, LR schedules, gradient
accumulation, and the in-run eval stream (reference SDK `train()` semantics,
SURVEY.md §2.1)."""

import json

import numpy as np
import pytest

from kubeflow_tpu.train.trainer import TrainJobSpec, Trainer

pytestmark = pytest.mark.slow  # multi-process/e2e/AOT tier


def _base(tmp_path, **over):
    base = dict(model="llama_tiny", dataset="learnable_lm",
                mesh={"data": 4, "fsdp": 2}, steps=20, batch_size=8,
                seq_len=16, learning_rate=3e-3,
                metrics_path=str(tmp_path / "metrics.jsonl"), log_every=10)
    base.update(over)
    return TrainJobSpec(**base)


def test_accum_steps_matches_full_batch(tmp_path, devices8):
    """accum_steps=2 is the same optimizer math as the full batch. Pinned
    at fp32 compute where the only residual is reduction order (~1e-7);
    the default bf16 compute adds microbatch-shape rounding noise that
    would force a tolerance too loose to mean anything."""
    kw = dict(model_kwargs={"dtype": "float32"})
    full = Trainer(_base(tmp_path, steps=5, **kw)).run()
    accum = Trainer(_base(tmp_path, steps=5, accum_steps=2, **kw)).run()
    np.testing.assert_allclose(accum["loss"], full["loss"], rtol=1e-5)
    # grad_accum is the canonical spelling of the same knob.
    alias = Trainer(_base(tmp_path, steps=5, grad_accum=2, **kw)).run()
    assert alias["loss"] == accum["loss"]


def test_accum_divisibility_rejected(tmp_path):
    with pytest.raises(ValueError, match="not divisible by"):
        Trainer(_base(tmp_path, batch_size=8, accum_steps=3))


def test_grad_clip_and_cosine_schedule(tmp_path, devices8):
    spec = _base(tmp_path, max_grad_norm=1.0, lr_schedule="cosine",
                 warmup_steps=5)
    result = Trainer(spec).run()
    assert np.isfinite(result["loss"])
    lines = [json.loads(l) for l in
             open(tmp_path / "metrics.jsonl").read().splitlines()]
    first = next(l for l in lines if "loss" in l)
    assert result["loss"] < first["loss"]


def test_linear_decay_schedule_constructs(tmp_path):
    t = Trainer(_base(tmp_path, lr_schedule="linear", warmup_steps=3,
                      lr_final=1e-5))
    assert t.tx is not None


def test_bad_lr_schedule_rejected(tmp_path):
    with pytest.raises(ValueError, match="lr_schedule"):
        Trainer(_base(tmp_path, lr_schedule="exponential"))


def test_eval_stream_logged(tmp_path, devices8):
    spec = _base(tmp_path, steps=20, eval_every=10, eval_batches=2)
    result = Trainer(spec).run()
    assert "eval_loss" in result and np.isfinite(result["eval_loss"])
    lines = [json.loads(l) for l in
             open(tmp_path / "metrics.jsonl").read().splitlines()]
    evals = [l for l in lines if "eval_loss" in l]
    assert {l["step"] for l in evals} >= {10, 20}
    assert all(np.isfinite(l["eval_accuracy"]) for l in evals)
    # Eval windows must not pollute the train perf stream.
    perf = [l for l in lines if "tokens_per_sec" in l]
    assert perf and all(np.isfinite(l["tokens_per_sec"]) for l in perf)


def test_spec_roundtrip_with_new_fields():
    spec = TrainJobSpec(max_grad_norm=1.0, lr_schedule="cosine",
                        accum_steps=2, eval_every=10)
    assert TrainJobSpec.from_json(spec.to_json()) == spec
