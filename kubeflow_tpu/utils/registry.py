"""Model / dataset / optimizer registries.

The config-driven analog of the reference's ConfigMap-based registries
(katib-config's algorithm→image map, KServe's ServingRuntime model-format→
container recipe; SURVEY.md §5.6): a job spec names a model and dataset by
string; controllers and runtimes resolve them here.
"""

from __future__ import annotations

from typing import Any, Callable

_MODELS: dict[str, Callable[..., Any]] = {}
_DATASETS: dict[str, Callable[..., Any]] = {}


def register_model(name: str):
    def deco(fn):
        _MODELS[name] = fn
        return fn
    return deco


def register_dataset(name: str):
    def deco(fn):
        _DATASETS[name] = fn
        return fn
    return deco


def build_model(name: str, **kwargs):
    """Returns (flax_module, info dict with num_params/batch spec hints)."""
    _ensure_builtin()
    try:
        fn = _MODELS[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; have {sorted(_MODELS)}") from None
    return fn(**kwargs)


def build_dataset(name: str, **kwargs):
    _ensure_builtin()
    try:
        fn = _DATASETS[name]
    except KeyError:
        raise ValueError(
            f"unknown dataset {name!r}; have {sorted(_DATASETS)}") from None
    return fn(**kwargs)


def list_models() -> list[str]:
    _ensure_builtin()
    return sorted(_MODELS)


_builtin_loaded = False


def _ensure_builtin() -> None:
    global _builtin_loaded
    if _builtin_loaded:
        return

    import jax.numpy as jnp  # noqa: F401

    from kubeflow_tpu.models import bert, llama, mlp

    @register_model("mnist_mlp")
    def _mnist_mlp(**kw):
        cfg = mlp.MLPConfig(**kw)
        model = mlp.MLP(cfg)
        return model, {"task": "classify", "example_shape": (1, cfg.in_dim),
                       "example_dtype": "float32", "num_params": None}

    def _llama(cfg: llama.LlamaConfig):
        return llama.Llama(cfg), {
            "task": "lm", "example_shape": (1, 16), "example_dtype": "int32",
            "num_params": cfg.num_params, "vocab_size": cfg.vocab_size,
            "config": cfg}

    @register_model("llama_tiny")
    def _llama_tiny(**kw):
        import dataclasses
        return _llama(dataclasses.replace(llama.llama_tiny(), **kw))

    @register_model("llama_1b")
    def _llama_1b(**kw):
        import dataclasses
        return _llama(dataclasses.replace(llama.llama_1b(), **kw))

    @register_model("llama3_8b")
    def _llama3_8b(**kw):
        import dataclasses
        return _llama(dataclasses.replace(llama.llama3_8b(), **kw))

    from kubeflow_tpu.models import moe

    def _moe(cfg):
        return moe.MoELlama(cfg), {
            "task": "lm", "example_shape": (1, 16), "example_dtype": "int32",
            "num_params": cfg.num_params,
            "active_params": cfg.active_params,
            "vocab_size": cfg.vocab_size, "config": cfg}

    @register_model("moe_tiny")
    def _moe_tiny(**kw):
        import dataclasses
        return _moe(dataclasses.replace(moe.moe_tiny(), **kw))

    @register_model("mixtral_8x7b")
    def _mixtral_8x7b(**kw):
        import dataclasses
        return _moe(dataclasses.replace(moe.mixtral_8x7b(), **kw))

    from kubeflow_tpu.models import kimi_linear

    def _kimi_linear(cfg):
        # num_params feeds the trainer's own `mfu` row (6 * num_params *
        # tokens/s): the weights a token is multiplied by on this chip,
        # not the experts held (most idle for any one token) and not the
        # published model's (most of them on other chips).
        return kimi_linear.KimiLinear(cfg), {
            "task": "lm", "example_shape": (1, 16), "example_dtype": "int32",
            "num_params": cfg.active_params,
            "held_params": cfg.held_params,
            "published_params": cfg.published_params,
            "vocab_size": cfg.vocab_size, "config": cfg}

    @register_model("kimi_linear_tiny")
    def _kimi_linear_tiny(**kw):
        import dataclasses
        return _kimi_linear(
            dataclasses.replace(kimi_linear.kimi_linear_tiny(), **kw))

    @register_model("kimi_linear_48b")
    def _kimi_linear_48b(**kw):
        import dataclasses
        return _kimi_linear(
            dataclasses.replace(kimi_linear.kimi_linear_48b(), **kw))

    from kubeflow_tpu.models import evabyte

    def _evabyte(cfg):
        return evabyte.EvaByte(cfg), {
            "task": "lm", "example_shape": (1, 16), "example_dtype": "int32",
            "num_params": cfg.num_params, "vocab_size": cfg.vocab_size,
            "config": cfg}

    @register_model("evabyte_tiny")
    def _evabyte_tiny(**kw):
        import dataclasses
        return _evabyte(dataclasses.replace(evabyte.evabyte_tiny(), **kw))

    @register_model("evabyte_6_5b")
    def _evabyte_6_5b(**kw):
        import dataclasses
        return _evabyte(dataclasses.replace(evabyte.evabyte_6_5b(), **kw))

    from kubeflow_tpu.models import joyai

    def _joyai(cfg):
        return joyai.JoyAI(cfg), {
            "task": "lm", "example_shape": (1, 16), "example_dtype": "int32",
            "num_params": cfg.active_params, "held_params": cfg.held_params,
            "vocab_size": cfg.vocab_size, "config": cfg}

    @register_model("joyai_tiny")
    def _joyai_tiny(**kw):
        import dataclasses
        return _joyai(dataclasses.replace(joyai.joyai_tiny(), **kw))

    @register_model("joyai_llm_flash")
    def _joyai_llm_flash(**kw):
        import dataclasses
        return _joyai(dataclasses.replace(joyai.joyai_llm_flash(), **kw))

    @register_model("bert_tiny")
    def _bert_tiny(**kw):
        import dataclasses
        cfg = dataclasses.replace(bert.bert_tiny(), **kw)
        return bert.Bert(cfg), {
            "task": "classify", "example_shape": (1, 16),
            "example_dtype": "int32", "num_params": None, "config": cfg}

    @register_model("bert_base")
    def _bert_base(**kw):
        import dataclasses
        cfg = dataclasses.replace(bert.bert_base(), **kw)
        return bert.Bert(cfg), {
            "task": "classify", "example_shape": (1, 128),
            "example_dtype": "int32", "num_params": None, "config": cfg}

    @register_model("gpt2_tiny")
    def _gpt2_tiny(**kw):
        import dataclasses

        from kubeflow_tpu.models import gpt2

        cfg = dataclasses.replace(gpt2.gpt2_tiny(), **kw)
        return gpt2.GPT2(cfg), {
            "task": "lm", "example_shape": (1, 16),
            "example_dtype": "int32", "num_params": cfg.num_params,
            "vocab_size": cfg.vocab_size, "config": cfg}

    from kubeflow_tpu.data import synthetic

    @register_dataset("synthetic_lm")
    def _synthetic_lm(batch_size=8, seq_len=128, vocab_size=512, seed=0, **kw):
        return synthetic.token_batches(batch_size, seq_len, vocab_size, seed)

    @register_dataset("learnable_lm")
    def _learnable_lm(batch_size=8, seq_len=32, vocab_size=64, seed=0, **kw):
        return synthetic.learnable_token_batches(
            batch_size, seq_len, vocab_size, seed)

    @register_dataset("mnist_like")
    def _mnist_like(batch_size=64, seed=0, **kw):
        return synthetic.mnist_like(batch_size, seed)

    @register_dataset("token_file")
    def _token_file(path, batch_size=8, seq_len=128, seed=0, shuffle=True,
                    vocab_size=None, process_index=None, process_count=None,
                    **kw):
        """Grain-backed tokenized corpus (.npy/.bin/.txt) with
        checkpointable iterator state — the production input path. The
        trainer passes the model's vocab_size so a wrong-tokenizer corpus
        fails at startup instead of training on clamped ids, and its batch
        replica group as (process_index, process_count) so ranks sharing a
        batch shard load identical rows."""
        from kubeflow_tpu.data import loader

        return loader.lm_dataset(
            path, batch_size=batch_size, seq_len=seq_len, seed=seed,
            shuffle=shuffle, vocab_size=vocab_size,
            process_index=process_index, process_count=process_count)

    @register_dataset("packed_lm")
    def _packed_lm(path, batch_size=8, seq_len=128, eos_id=0, seed=0,
                   shuffle=True, vocab_size=None, **kw):
        """Document-packed corpus: batches carry segment_ids/positions/mask
        so attention and loss respect document boundaries (the packed-
        sequence path through the fused kernels)."""
        from kubeflow_tpu.data import loader

        return loader.packed_lm_dataset(
            path, batch_size=batch_size, seq_len=seq_len, eos_id=eos_id,
            seed=seed, shuffle=shuffle, vocab_size=vocab_size,
            process_index=kw.get("process_index"),
            process_count=kw.get("process_count"))

    # Only mark loaded once every builtin registered — a failed import above
    # must re-raise on the next call, not leave the registry silently empty.
    _builtin_loaded = True
