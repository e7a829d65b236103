"""Spec drift guard: ONE schema, two consumers (SURVEY.md §5.6).

The reference shares job-spec types between Go controllers and Python SDKs
through protoc/OpenAPI codegen ⟨kfp: api/ — proto; training-operator:
pkg/apis — OpenAPI⟩; this build's JSON-convention deviation (README
"Config schema") previously had no mechanical guard — C++ admission and
`TrainJobSpec.from_json` could drift and only an e2e would notice.

This module is the single source of truth for the JAXJob `runtime`
field table. One generator emits BOTH artifacts, checked in:

  * `spec_schema.json`      — the schema document (repo root)
  * `cpp/spec_schema.gen.h` — the same table embedded as a C++ constant,
                              parsed once by `cpp/admission.h`, which
                              validates every runtime field against it
                              (unknown fields are rejected — typo'd knobs
                              fail at submit, not as a worker crash)

Drift breaks mechanically, without e2e:
  * dataclass field added/removed without regenerating → the Python
    suite fails (`tests/test_spec_schema.py` cross-checks KNOBS against
    `TrainJobSpec` and the on-disk artifacts against the generator);
  * schema field deleted → the same Python cross-check fails, AND the
    C++ suite fails (`cpp/tests/test_spec_schema.cc` loops the embedded
    table and asserts admission enforces every entry; a spec using the
    deleted field is now rejected as unknown).

Regenerate after editing KNOBS or TrainJobSpec:
    python -m kubeflow_tpu.utils.spec_schema

Tier-1 also enforces this WITHOUT importing jax: tpklint's `spec-schema`
rule regenerates both artifacts in memory from these tables and diffs
the committed files, so "edited a table, forgot to regenerate (or to
rebuild the C++ binary)" fails as a lint finding with a file:line, not
as a C++ admission e2e surprise.
"""

from __future__ import annotations

import json
import os

#: field -> constraint spec. Types:
#:   int            JSON number, integral, >= min
#:   number         JSON number, >= min
#:   string         JSON string (optionally from enum)
#:   string_or_null string or null
#:   bool_or_string bool or string (ring_attention's switch/mode union)
#:   object         JSON object (contents validated downstream)
#:   int_array      non-empty JSON array of integral numbers (each
#:                  >= min when given — an empty bucket list would
#:                  crash the engine at load, after admission)
#:   int_or_null    integral number or null
KNOBS: dict[str, dict] = {
    "model": {"type": "string"},
    "model_kwargs": {"type": "object"},
    "dataset": {"type": "string"},
    "dataset_kwargs": {"type": "object"},
    "strategy": {"type": "string"},
    "mesh": {"type": "object"},
    "steps": {"type": "int", "min": 1},
    "batch_size": {"type": "int", "min": 1},
    "seq_len": {"type": "int", "min": 1},
    "learning_rate": {"type": "number", "min": 0},
    "warmup_steps": {"type": "int", "min": 0},
    "weight_decay": {"type": "number", "min": 0},
    "lr_schedule": {"type": "string",
                    "enum": ["constant", "cosine", "linear"]},
    "lr_final": {"type": "number", "min": 0},
    "max_grad_norm": {"type": "number", "min": 0},
    "accum_steps": {"type": "int", "min": 1},
    # Canonical gradient-accumulation knob (0 defers to the legacy
    # accum_steps alias; both set and disagreeing is refused).
    "grad_accum": {"type": "int", "min": 0},
    # FSDP master-state sharding degree over the `fsdp` mesh axis
    # (parallel/fsdp.py); 0 = off, N fills mesh.fsdp = N.
    "fsdp": {"type": "int", "min": 0},
    # Compute dtype of the fsdp runtime's gathered param copies.
    "param_dtype": {"type": "string_or_null",
                    "enum": ["float32", "bfloat16"]},
    "seed": {"type": "int", "min": 0},
    "ring_attention": {"type": "bool_or_string"},
    "loss_impl": {"type": "string", "enum": ["full", "chunked"]},
    "loss_chunk": {"type": "int", "min": 1},
    "pipeline": {"type": "object"},
    "lora": {"type": "object"},
    "checkpoint": {"type": "object"},
    "restart_policy": {"type": "string",
                       "enum": ["Never", "OnFailure",
                                "ExponentialBackoff"]},
    "backoff_limit": {"type": "int", "min": 0},
    "prefetch": {"type": "int", "min": 0},
    "metrics_path": {"type": "string_or_null"},
    "profile": {"type": "object"},
    "profile_start_step": {"type": "int", "min": 0},
    "profile_stop_step": {"type": "int", "min": 0},
    "log_every": {"type": "int", "min": 1},
    "eval_dataset": {"type": "string_or_null"},
    "eval_dataset_kwargs": {"type": "object"},
    "eval_every": {"type": "int", "min": 0},
    "eval_batches": {"type": "int", "min": 1},
}

#: InferenceService `model.generative` knob table — the serving twin of
#: KNOBS (same generator, same two consumers). C++ admission validates
#: the generative object field-by-field against it, so a typo'd serving
#: knob (or a kv_block_size on a binary that predates paging) fails at
#: submit instead of as a replica crash-loop. Superset of both
#: generative runtimes: the causal-LM engine (GenerationEngine kwargs +
#: GenerativeJAXModel's eos_id/tokenizer/mesh/draft) and the T5
#: text2text engine (in_buckets/max_tokens/pad_id). Deliberate limit:
#: which runtime applies is decided by the checkpoint's architectures
#: at LOAD time, which admission cannot see — so a cross-runtime knob
#: (in_buckets on a causal-LM service) passes admission and fails at
#: model load; the table exists to catch typos and type errors early,
#: not to discriminate engines.
GENERATIVE_KNOBS: dict[str, dict] = {
    "slots": {"type": "int", "min": 1},
    "max_len": {"type": "int", "min": 2},
    "chunk": {"type": "int", "min": 1},
    "prefill_buckets": {"type": "int_array", "min": 1},
    "decode_buckets": {"type": "int_array", "min": 1},
    "prefix_cache": {"type": "int", "min": 0},
    "seed": {"type": "int", "min": 0},
    "pipeline_depth": {"type": "int", "min": 1},
    # Paged KV cache (serve/paging.py): 0 = flat escape hatch.
    "kv_block_size": {"type": "int", "min": 0},
    "kv_blocks": {"type": "int", "min": 0},
    # Disaggregated prefill/decode (ISSUE 13): "unified" (default) |
    # "prefill" | "decode"; split roles need kv_block_size > 0 (the
    # cross-field rule lives in cpp/admission.h next to the table).
    "role": {"type": "string_or_null",
             "enum": ["unified", "prefill", "decode"]},
    # Host-RAM KV spill tier capacity in blocks (0 = off).
    "kv_host_tier_blocks": {"type": "int", "min": 0},
    # Quantized KV pool blocks (ISSUE 19): "none" (default, bit-exact
    # escape hatch) | "int8" | "fp8". Cross-field rules live in
    # cpp/admission.h next to the table: kv_quant requires
    # kv_block_size > 0 (the scale pool is a paged structure) and
    # refuses draft (a speculative rejection rewind would re-quantize
    # committed rows — see PROFILE.md §17 for the measured decision).
    "kv_quant": {"type": "string_or_null",
                 "enum": ["none", "int8", "fp8"]},
    "mesh": {"type": "object"},
    # Speculative decoding draft spec: {"checkpoint": hf_dir,
    # "gamma"?: int >= 1, "model_overrides"?: {...}} — contents are
    # cross-field-validated in cpp/admission.h (ISSUE 18): a draft
    # without a checkpoint, a fractional gamma, or a typo'd key fails
    # at submit instead of crash-looping the replica at load. Since
    # ISSUE 18 the draft COMPOSES with kv_block_size, role and
    # pipeline_depth; only checkpoint-derived refusals (sliding-window
    # drafts past their window, vocab mismatch) remain load-time.
    "draft": {"type": "object"},
    "adapters": {"type": "object"},
    "eos_id": {"type": "int_or_null"},
    "tokenizer": {"type": "string_or_null"},
    "in_buckets": {"type": "int_array", "min": 1},
    "max_tokens": {"type": "int", "min": 1},
    "pad_id": {"type": "int", "min": 0},
}


def check_against_dataclass() -> None:
    """KNOBS must name exactly the TrainJobSpec fields — a field on either
    side only is drift, refused here (this runs in the test suite)."""
    import dataclasses

    from kubeflow_tpu.train.trainer import TrainJobSpec

    dc = {f.name for f in dataclasses.fields(TrainJobSpec)}
    missing = dc - set(KNOBS)
    extra = set(KNOBS) - dc
    if missing or extra:
        raise AssertionError(
            f"spec schema drift: fields on TrainJobSpec but not in KNOBS "
            f"{sorted(missing)}; in KNOBS but not on TrainJobSpec "
            f"{sorted(extra)} — edit kubeflow_tpu/utils/spec_schema.py "
            f"and regenerate (python -m kubeflow_tpu.utils.spec_schema)")


def check_generative_against_engine() -> None:
    """Every GenerationEngine kwarg must have a GENERATIVE_KNOBS entry
    (plus the wrapper-level keys GenerativeJAXModel pops) — a new engine
    knob without a schema row would be REJECTED by C++ admission on
    every spec that sets it. `rules` is deliberately schema-less: it
    takes in-process sharding-rule objects, never JSON. So is
    `donate_params`: a caller's word about who owns a Python tree, which
    no spec can give (a bundle that names it is refused)."""
    import inspect

    from kubeflow_tpu.serve.generation import GenerationEngine

    sig = inspect.signature(GenerationEngine.__init__)
    knobs = {n for n in sig.parameters
             if n not in ("self", "model", "params", "cfg", "rules",
                          "donate_params")}
    missing = knobs - set(GENERATIVE_KNOBS)
    if missing:
        raise AssertionError(
            f"generative schema drift: GenerationEngine kwargs missing "
            f"from GENERATIVE_KNOBS: {sorted(missing)} — edit "
            f"kubeflow_tpu/utils/spec_schema.py and regenerate")


def schema_document() -> dict:
    return {
        "version": 1,
        "generated_by": "kubeflow_tpu/utils/spec_schema.py",
        "JAXJob.runtime": KNOBS,
        "InferenceService.model.generative": GENERATIVE_KNOBS,
    }


def render_json() -> str:
    return json.dumps(schema_document(), indent=1, sort_keys=True) + "\n"


def render_cpp_header() -> str:
    """The schema as an embedded C++ string constant, parsed once by
    admission. Generated — do not edit by hand."""
    payload = json.dumps(schema_document(), sort_keys=True)
    escaped = payload.replace("\\", "\\\\").replace('"', '\\"')
    return (
        "// GENERATED by kubeflow_tpu/utils/spec_schema.py — DO NOT EDIT.\n"
        "// Regenerate: python -m kubeflow_tpu.utils.spec_schema\n"
        "// The JAXJob runtime field table; cpp/admission.h validates\n"
        "// every runtime field against it (unknown fields rejected).\n"
        "#pragma once\n\n"
        "namespace tpk {\n\n"
        "inline const char* kSpecSchemaJson =\n"
        f'    "{escaped}";\n\n'
        "}  // namespace tpk\n")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def main() -> int:
    root = repo_root()
    with open(os.path.join(root, "spec_schema.json"), "w") as fh:
        fh.write(render_json())
    with open(os.path.join(root, "cpp", "spec_schema.gen.h"), "w") as fh:
        fh.write(render_cpp_header())
    print("wrote spec_schema.json + cpp/spec_schema.gen.h")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
