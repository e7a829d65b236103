"""Serving runtimes — the ServingRuntime/ClusterServingRuntime equivalent.

The reference resolves `modelFormat` → container recipe through ServingRuntime
CRs (⟨kserve: pkg/apis/serving/v1alpha1 — ServingRuntime⟩, SURVEY.md §2.2,
§5.6). Here a runtime is a Python builder `fn(model_dir, spec) -> Model`,
registered by format name; an exported model directory carries a `model.json`
naming its format, so `load_model(dir)` is the whole resolution path.

Model directory layout (produced by `export_for_serving`):
    model.json   {"format": "jax-registry", "model": "...", "model_kwargs": {},
                  "batch_buckets": [...], "seed": 0}
    params/      orbax params-only checkpoint (optional; init from seed if absent)
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable

import jax
import numpy as np

from kubeflow_tpu.serve.model import JAXModel, Model

_RUNTIMES: dict[str, Callable[[str, dict], Model]] = {}


def register_runtime(fmt: str):
    def deco(fn):
        _RUNTIMES[fmt] = fn
        return fn
    return deco


def list_runtimes() -> list[str]:
    return sorted(_RUNTIMES)


def load_model(model_dir: str, name: str | None = None,
               mesh: dict | None = None) -> Model:
    """Resolve model.json's format to a runtime and build the Model.

    `mesh` ({"tensor": N, ...}) overrides the bundle's device-mesh spec —
    the ISVC `model.mesh` field lands here via the server's --mesh flag,
    turning a single-device generative bundle into tensor-parallel
    serving without touching the bundle."""
    spec_path = os.path.join(model_dir, "model.json")
    with open(spec_path) as f:
        spec = json.load(f)
    if mesh:
        gen = spec.get("generative")
        if not gen:
            raise ValueError(
                "a mesh override requires a generative bundle (fixed-"
                "forward models replicate per replica instead)")
        spec = {**spec, "generative": {**gen, "mesh": dict(mesh)}}
    fmt = spec.get("format", "jax-registry")
    try:
        builder = _RUNTIMES[fmt]
    except KeyError:
        raise ValueError(
            f"no serving runtime for format {fmt!r}; have {list_runtimes()}"
        ) from None
    model = builder(model_dir, spec)
    if name:
        model.name = name
    if spec.get("explainer"):
        from kubeflow_tpu.serve.explain import build_explainer

        attach = getattr(model, "attach_explainer", None)
        if attach is None:
            raise ValueError(
                f"runtime {fmt!r} model does not support explainers")
        attach(build_explainer(spec["explainer"]))
    return model


def export_for_serving(model_dir: str, *, model: str, params: Any = None,
                       model_kwargs: dict | None = None,
                       batch_buckets=(1, 2, 4, 8, 16, 32),
                       seed: int = 0, extra: dict | None = None) -> str:
    """Writes the serving bundle: model.json + optional orbax params.

    The training side calls this after fine-tuning (the analog of pushing a
    trained model to the KServe storage bucket)."""
    import flax.linen as nn
    import orbax.checkpoint as ocp

    os.makedirs(model_dir, exist_ok=True)
    spec = {"format": "jax-registry", "model": model,
            "model_kwargs": model_kwargs or {},
            "batch_buckets": list(batch_buckets), "seed": seed}
    spec.update(extra or {})
    with open(os.path.join(model_dir, "model.json"), "w") as f:
        json.dump(spec, f, indent=1)
    if params is not None:
        path = os.path.join(os.path.abspath(model_dir), "params")
        with ocp.StandardCheckpointer() as ckptr:
            # Strip flax logical-partitioning boxes: the bundle stores plain
            # arrays; serving re-shards (or replicates) at load time.
            ckptr.save(path, nn.meta.unbox(params))
    return model_dir


@register_runtime("jax-registry")
def _jax_registry_runtime(model_dir: str, spec: dict) -> Model:
    """Builds a JAXModel from the model zoo + optional orbax params."""
    from kubeflow_tpu.utils import registry

    module, info = registry.build_model(spec["model"],
                                        **spec.get("model_kwargs", {}))
    example_shape = tuple(info["example_shape"][1:])
    dtype = info.get("example_dtype", "float32")

    params_dir = os.path.join(os.path.abspath(model_dir), "params")
    if os.path.isdir(params_dir):
        import orbax.checkpoint as ocp
        with ocp.StandardCheckpointer() as ckptr:
            params = ckptr.restore(params_dir)
    else:  # no trained weights: init from the recorded seed (tests, smoke)
        from kubeflow_tpu.serve.weights import Seeded

        params = Seeded(module, jax.random.key(spec.get("seed", 0)),
                        np.zeros((1, *example_shape), dtype=dtype))
        if not spec.get("generative") or spec.get("quantize"):
            # A fixed forward and the int8 pass read the tree itself; the
            # generation engine makes it leaf group by leaf group, each in
            # the dtype it stores (serve/weights.py).
            params = params.whole()

    module, params = _maybe_quantize(module, params, spec)

    if spec.get("generative"):
        # LLM bundle: KV-cache decode engine instead of a fixed forward
        # (⟨kserve: python/huggingfaceserver⟩ equivalent; generation.py).
        from kubeflow_tpu.serve.generation import GenerativeJAXModel

        return GenerativeJAXModel(
            spec.get("name") or spec["model"], module, params,
            info.get("config"), generation=dict(spec["generative"]),
            donate_params=True)  # made above, held by nobody else

    def apply_fn(params, x):
        out = module.apply({"params": params}, x)
        return out[-1] if isinstance(out, tuple) else out

    return JAXModel(
        spec.get("name") or spec["model"], apply_fn, params,
        input_spec=[(example_shape, dtype)],
        batch_buckets=spec.get("batch_buckets", (1, 2, 4, 8, 16, 32)),
        warm_buckets=spec.get("warm_buckets", (1, 8)))


def _maybe_quantize(module, params, spec: dict):
    """spec.quantize == "int8" → weight-only int8 storage (serve/quant.py),
    transparent to the model via QuantizedModule."""
    mode = spec.get("quantize")
    if not mode:
        return module, params
    if mode != "int8":
        raise ValueError(f"unsupported quantize mode {mode!r} (have: int8)")
    from kubeflow_tpu.serve.quant import QuantizedModule, quantize_tree

    return QuantizedModule(module), quantize_tree(params)


@register_runtime("huggingface")
def _huggingface_runtime(model_dir: str, spec: dict) -> Model:
    """HF safetensors checkpoint → native JAX model (the huggingfaceserver
    equivalent; models/hf_import.py). The bundle is the HF directory itself
    plus a model.json {"format": "huggingface"}; `checkpoint` may point at a
    subdirectory or an absolute path, default the bundle dir.

    Llama-family checkpoints serve generatively when the spec carries a
    `generative` block (KV-cache engine), else as a full-forward logits
    model; BERT checkpoints serve as classifiers (pooled logits).
    """
    from kubeflow_tpu.models.bert import Bert
    from kubeflow_tpu.models.hf_import import build_from_hf, read_hf_config
    from kubeflow_tpu.models.t5 import T5

    ckpt = spec.get("checkpoint") or "."
    if not os.path.isabs(ckpt):
        ckpt = os.path.join(os.path.abspath(model_dir), ckpt)
    overrides = dict(spec.get("model_overrides") or {})
    module, cfg, params = build_from_hf(ckpt, **overrides)
    adapter = spec.get("peft_adapter")
    if adapter:
        # PEFT LoRA adapter dir (tuned here via spec.lora or elsewhere
        # via HF peft): overlay onto the base and FOLD FLAT — the engine
        # serves a plain base tree, zero changes downstream
        # (models/peft_import.py; exactness tested vs the peft-wrapped
        # torch model).
        if not os.path.isabs(adapter):
            adapter = os.path.join(os.path.abspath(model_dir), adapter)
        from kubeflow_tpu.models.peft_import import attach_peft_adapter
        from kubeflow_tpu.train.lora import merge

        acfg, aparams = attach_peft_adapter(adapter, cfg, params)
        params = merge(aparams, acfg)
    is_bert = isinstance(module, Bert)  # before the quantize wrapper
    is_t5 = isinstance(module, T5)
    module, params = _maybe_quantize(module, params, spec)
    name = spec.get("name") or os.path.basename(os.path.abspath(model_dir))

    if is_t5:
        # Encoder-decoder → the text2text task (whole-decode-as-one-
        # program greedy generation; serve/text2text.py).
        from kubeflow_tpu.serve.text2text import Text2TextJAXModel

        gen = dict(spec.get("generative") or {})
        if "tokenizer" not in gen:
            from kubeflow_tpu.serve.tokenizer_util import \
                load_bundled_tokenizer

            tok = load_bundled_tokenizer(ckpt, name)
            if tok is not None:
                gen["tokenizer"] = tok
        return Text2TextJAXModel(name, module, params, cfg,
                                 generation=gen)

    if is_bert:
        # Pad tokens must not enter attention: the mask is derived from the
        # checkpoint's pad_token_id (HF tokenizers right-pad with it), so a
        # single-input v1/v2 request with padded rows scores identically to
        # the reference server.
        pad_id = int(read_hf_config(ckpt).get("pad_token_id") or 0)

        def apply_fn(params, input_ids):
            _, logits = module.apply({"params": params}, input_ids,
                                     attention_mask=input_ids != pad_id)
            return logits

        seq = int(spec.get("seq_len", min(cfg.max_seq_len, 128)))
        return JAXModel(
            name, apply_fn, params, input_spec=[((seq,), "int32")],
            batch_buckets=spec.get("batch_buckets", (1, 2, 4, 8, 16, 32)),
            warm_buckets=spec.get("warm_buckets", (1, 8)))

    if spec.get("generative"):
        from kubeflow_tpu.serve.generation import GenerativeJAXModel

        gen = dict(spec["generative"])
        if gen.get("adapters"):
            # Multi-LoRA: {name: PEFT adapter dir}, relative to the
            # bundle like `checkpoint`.
            gen["adapters"] = {
                k: (v if os.path.isabs(v)
                    else os.path.join(os.path.abspath(model_dir), v))
                for k, v in dict(gen["adapters"]).items()}
        # Bundle the checkpoint's own tokenizer when present (vLLM-parity
        # text in/out + streaming text deltas): generation then accepts
        # "text" and returns decoded "text"; eos defaults to the
        # tokenizer's unless the spec pins one.
        if "tokenizer" not in gen:
            from kubeflow_tpu.serve.tokenizer_util import \
                load_bundled_tokenizer

            tok = load_bundled_tokenizer(ckpt, name)
            if tok is not None:
                gen["tokenizer"] = tok
                if tok.eos_token_id is not None:
                    gen.setdefault("eos_id", int(tok.eos_token_id))
        return GenerativeJAXModel(name, module, params, cfg,
                                  generation=gen, donate_params=True)

    def apply_fn(params, tokens):
        return module.apply({"params": params}, tokens)

    seq = int(spec.get("seq_len", 128))
    return JAXModel(
        name, apply_fn, params, input_spec=[((seq,), "int32")],
        batch_buckets=spec.get("batch_buckets", (1, 2, 4, 8)),
        warm_buckets=spec.get("warm_buckets", (1,)))
