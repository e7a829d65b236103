"""Serving feature composition: speculative decoding x TP, multi-LoRA x
TP, spec-decode x multi-LoRA — the pairs vLLM composes and the engine
used to refuse (ops/ROADMAP.md composition ledger).

Contract: every composition is TOKEN-IDENTICAL to the same request on
the single-device / single-feature engine — composition must never
change what is generated, only how fast.
"""

from __future__ import annotations

import copy
import os

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
peft = pytest.importorskip("peft")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from kubeflow_tpu.models.llama import Llama, LlamaConfig  # noqa: E402
from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh  # noqa: E402
from kubeflow_tpu.serve.generation import GenerationEngine  # noqa: E402

pytestmark = pytest.mark.slow  # torch-reference / multi-device tier

ENGINE_KW = dict(slots=2, max_len=24, chunk=4, prefill_buckets=(4,), seed=0)


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    """Tiny HF Llama base + one PEFT adapter + a TP-shardable draft."""
    tmp = tmp_path_factory.mktemp("compose")
    torch.manual_seed(31)
    hcfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        attn_implementation="eager")
    bm = transformers.LlamaForCausalLM(hcfg)
    bm.eval()
    bdir = str(tmp / "base")
    bm.save_pretrained(bdir, safe_serialization=True)
    lcfg = peft.LoraConfig(r=4, lora_alpha=8,
                           target_modules=["q_proj", "v_proj"],
                           lora_dropout=0.0, bias="none",
                           task_type="CAUSAL_LM")
    pm = peft.get_peft_model(copy.deepcopy(bm), lcfg)
    with torch.no_grad():
        for n, p in pm.named_parameters():
            if "lora_" in n:
                p.copy_(torch.randn_like(p) * 0.08)
    adir = str(tmp / "ada")
    pm.save_pretrained(adir)

    from kubeflow_tpu.models.hf_import import import_llama

    cfg, params = import_llama(bdir, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    # Draft: 2 KV heads so the cache shards over tensor=2 like the target.
    dcfg = LlamaConfig(vocab_size=256, hidden_size=32, intermediate_size=64,
                       num_layers=1, num_heads=2, num_kv_heads=2,
                       head_dim=16, max_seq_len=64, remat=False,
                       dtype=jnp.float32, param_dtype=jnp.float32)
    dmodel = Llama(dcfg)
    dparams = dmodel.init(jax.random.key(5),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    draft = {"model": dmodel, "params": dparams, "cfg": dcfg, "gamma": 3}

    rng = np.random.default_rng(1)
    prompt = [int(t) for t in rng.integers(1, 256, 6)]
    # Single-feature references: multi-LoRA engine, no mesh/draft.
    ref = GenerationEngine(Llama(cfg), params, cfg,
                           adapters={"ada": adir}, **ENGINE_KW)
    try:
        want_base = ref.submit(prompt, max_tokens=8)["output_ids"]
        want_ada = ref.submit(prompt, max_tokens=8,
                              adapter="ada")["output_ids"]
    finally:
        ref.close()
    assert want_ada != want_base, "adapter changed nothing — weak oracle"
    return dict(cfg=cfg, params=params, adir=adir, draft=draft,
                prompt=prompt, want_base=want_base, want_ada=want_ada)


def _mesh2(devices8):
    return build_mesh(MeshConfig(data=1, tensor=2), devices8[:2])


def test_multilora_x_tp(setup, devices8):
    s = setup
    eng = GenerationEngine(Llama(s["cfg"]), s["params"], s["cfg"],
                           adapters={"ada": s["adir"]},
                           mesh=_mesh2(devices8), **ENGINE_KW)
    try:
        assert eng.submit(s["prompt"],
                          max_tokens=8)["output_ids"] == s["want_base"]
        assert eng.submit(s["prompt"], max_tokens=8,
                          adapter="ada")["output_ids"] == s["want_ada"]
    finally:
        eng.close()


def test_spec_decode_x_tp(setup, devices8):
    s = setup
    eng = GenerationEngine(Llama(s["cfg"]), s["params"], s["cfg"],
                           draft=dict(s["draft"]), mesh=_mesh2(devices8),
                           **ENGINE_KW)
    try:
        got = eng.submit(s["prompt"], max_tokens=8)["output_ids"]
        assert got == s["want_base"]
        assert eng.stats["spec_dispatches"] > 0, "spec path never ran"
    finally:
        eng.close()


def test_spec_decode_x_multilora(setup):
    """The draft proposes from BASE weights while the target verifies
    under the adapter — outputs must still be token-identical to the
    non-speculative adapter decode (acceptance is the only casualty)."""
    s = setup
    eng = GenerationEngine(Llama(s["cfg"]), s["params"], s["cfg"],
                           draft=dict(s["draft"]),
                           adapters={"ada": s["adir"]}, **ENGINE_KW)
    try:
        assert eng.submit(s["prompt"], max_tokens=8,
                          adapter="ada")["output_ids"] == s["want_ada"]
        assert eng.submit(s["prompt"],
                          max_tokens=8)["output_ids"] == s["want_base"]
        assert eng.stats["spec_dispatches"] > 0
    finally:
        eng.close()


def test_spec_x_multilora_x_tp(setup, devices8):
    """All three flagship features in one engine."""
    s = setup
    eng = GenerationEngine(Llama(s["cfg"]), s["params"], s["cfg"],
                           draft=dict(s["draft"]),
                           adapters={"ada": s["adir"]},
                           mesh=_mesh2(devices8), **ENGINE_KW)
    try:
        assert eng.submit(s["prompt"], max_tokens=8,
                          adapter="ada")["output_ids"] == s["want_ada"]
        assert eng.stats["spec_dispatches"] > 0
    finally:
        eng.close()


def test_spec_x_tp_draft_heads_must_divide(setup, devices8):
    s = setup
    dcfg = LlamaConfig(vocab_size=256, hidden_size=32, intermediate_size=64,
                       num_layers=1, num_heads=2, num_kv_heads=1,
                       head_dim=16, max_seq_len=64, remat=False,
                       dtype=jnp.float32, param_dtype=jnp.float32)
    dmodel = Llama(dcfg)
    dparams = dmodel.init(jax.random.key(5),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="draft"):
        GenerationEngine(
            Llama(s["cfg"]), s["params"], s["cfg"],
            draft={"model": dmodel, "params": dparams, "cfg": dcfg},
            mesh=_mesh2(devices8), **ENGINE_KW)
