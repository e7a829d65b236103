"""Chunked fused cross-entropy (ops/ROADMAP.md item 1): identical numerics
and gradients to the full-logits path, without materializing [B·S, V]."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubeflow_tpu.models.llama import Llama, llama_tiny
from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
from kubeflow_tpu.train.step import (
    chunked_cross_entropy,
    cross_entropy_loss,
    init_train_state,
    make_train_step,
)


def _case(b=2, s=24, d=16, v=97, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    hidden = jax.random.normal(ks[0], (b, s, d), jnp.float32)
    head = jax.random.normal(ks[1], (d, v), jnp.float32) * 0.1
    targets = jax.random.randint(ks[2], (b, s), 0, v)
    return hidden, head, targets


def test_matches_full_loss_including_padding():
    hidden, head, targets = _case()
    full = cross_entropy_loss(
        jnp.einsum("bsd,dv->bsv", hidden, head), targets)
    for chunk in (7, 16, 48, 4096):  # non-divisible, divisible, > n
        out = chunked_cross_entropy(hidden, head, targets, chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=1e-6, atol=1e-6)


def test_mask_and_vocab_major_head():
    hidden, head, targets = _case()
    mask = (jnp.arange(24)[None, :] < 17).astype(jnp.float32).repeat(2, 0)
    full = cross_entropy_loss(
        jnp.einsum("bsd,dv->bsv", hidden, head), targets, mask)
    out = chunked_cross_entropy(hidden, head.T, targets, mask, chunk=10,
                                head_is_vocab_major=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_gradients_match_full():
    hidden, head, targets = _case(s=16)

    def loss_full(h, w):
        return cross_entropy_loss(jnp.einsum("bsd,dv->bsv", h, w), targets)

    def loss_chunked(h, w):
        return chunked_cross_entropy(h, w, targets, chunk=5)

    gf = jax.grad(loss_full, argnums=(0, 1))(hidden, head)
    gc = jax.grad(loss_chunked, argnums=(0, 1))(hidden, head)
    for a, b in zip(gf, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_train_step_chunked_matches_full(devices8):
    """Whole-step equivalence on the sharded mesh: starting from the same
    state, one chunked-loss step lands on the same loss/grad-norm as the
    full-logits step (fp32 params/tiny model: tight tolerance)."""
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8)
    cfg = llama_tiny()
    model = Llama(cfg)
    toks = jnp.zeros((8, 16), jnp.int32)
    rng = np.random.default_rng(0)
    batch = {
        "inputs": rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32),
    }

    results = {}
    for impl in ("full", "chunked"):
        state = init_train_state(model, optax.adamw(1e-3),
                                 jax.random.key(1), (toks,), mesh,
                                 DEFAULT_RULES)
        step = make_train_step(model, mesh, DEFAULT_RULES, loss_impl=impl,
                               loss_chunk=32)
        _, metrics = step(state, batch)
        results[impl] = (float(metrics["loss"]),
                         float(metrics["grad_norm"]))
    assert results["full"][0] == pytest.approx(results["chunked"][0],
                                               rel=2e-4)
    assert results["full"][1] == pytest.approx(results["chunked"][1],
                                               rel=2e-3)


def test_tied_embeddings_chunked(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices8)
    cfg = dataclasses.replace(llama_tiny(), tie_embeddings=True)
    model = Llama(cfg)
    toks = jnp.zeros((8, 16), jnp.int32)
    state = init_train_state(model, optax.adamw(1e-3), jax.random.key(2),
                             (toks,), mesh, DEFAULT_RULES)
    step = make_train_step(model, mesh, DEFAULT_RULES, loss_impl="chunked")
    rng = np.random.default_rng(1)
    batch = {
        "inputs": rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32),
    }
    prev = None
    for _ in range(3):
        state, metrics = step(state, batch)
        cur = float(metrics["loss"])
        assert np.isfinite(cur)
        if prev is not None:
            assert cur < prev
        prev = cur


def test_bad_loss_impl_rejected(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices8)
    with pytest.raises(ValueError, match="loss_impl"):
        make_train_step(Llama(llama_tiny()), mesh, loss_impl="nope")


def test_bad_loss_chunk_rejected(devices8):
    mesh = build_mesh(MeshConfig(data=-1), devices8)
    with pytest.raises(ValueError, match="loss_chunk"):
        make_train_step(Llama(llama_tiny()), mesh, loss_impl="chunked",
                        loss_chunk=0)


def _full_loss(h, w, targets, mask, vocab_major, softcap):
    logits = jnp.einsum("bsd,vd->bsv" if vocab_major else "bsd,dv->bsv",
                        h, w.astype(h.dtype))
    if softcap:
        logits = jnp.tanh(logits.astype(jnp.float32) / softcap) * softcap
    return cross_entropy_loss(logits, targets, mask)


@pytest.mark.parametrize("hidden_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("scale", [1.0, 0.37], ids=["ct1", "ct0.37"])
@pytest.mark.parametrize("softcap", [0.0, 30.0], ids=["nocap", "cap30"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("vocab_major", [False, True], ids=["DV", "VD"])
def test_gradients_match_full_logits(vocab_major, masked, softcap, scale,
                                     hidden_dtype):
    """The gradients the forward pass stores are the ones autodiff takes
    through the full [B,S,V] logits, for both head layouts, a mask over a
    token count that is no multiple of the chunk, Gemma-2's soft-cap, a
    cotangent other than 1, and a bf16 trunk over an fp32 head."""
    hidden, head, targets = _case(s=23)  # 46 tokens, chunk 8: 2 padded
    hidden = (hidden * (4.0 if softcap else 1.0)).astype(hidden_dtype)
    if vocab_major:
        head = head.T
    mask = ((jnp.arange(23)[None, :] < 17).astype(jnp.float32).repeat(2, 0)
            if masked else None)

    def full(h, w):
        return scale * _full_loss(h, w, targets, mask, vocab_major, softcap)

    def chunked(h, w):
        return scale * chunked_cross_entropy(
            h, w, targets, mask, chunk=8, head_is_vocab_major=vocab_major,
            final_softcap=softcap)

    lf, gf = jax.value_and_grad(full, argnums=(0, 1))(hidden, head)
    lc, gc = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, head)
    # Error as a share of the gradient's largest entry. bf16: both paths
    # round the logits and their gradient to bf16, and sum in another order.
    tol = 2e-2 if hidden_dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(float(lc), float(lf), rtol=tol)
    for a, b, like in zip(gf, gc, (hidden, head)):
        assert b.dtype == like.dtype and b.shape == like.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.abs(b - a).max() / np.abs(a).max()
        assert err < tol, err


_VOCAB = 977  # in no other dimension of the programs below


def _vocab_dots(lowered) -> int:
    """dot_generals of the lowered (StableHLO) program with the
    vocabulary in a shape: passes over the head."""
    return sum(1 for line in lowered.as_text().splitlines()
               if "stablehlo.dot_general" in line and str(_VOCAB) in line)


@pytest.mark.parametrize("argnums, dots", [((0, 1), 3), ((0,), 2)],
                         ids=["head_differentiated", "head_frozen"])
@pytest.mark.parametrize("vocab_major", [False, True], ids=["DV", "VD"])
def test_head_passes_of_grad(vocab_major, argnums, dots):
    """The head is three matmuls under differentiation — logits,
    d(hidden), d(head); the recomputing version had the logits twice — and
    two where the head is not differentiated."""
    hidden, head, targets = _case(v=_VOCAB)
    if vocab_major:
        head = head.T

    def loss(h, w):
        return chunked_cross_entropy(h, w, targets, chunk=16,
                                     head_is_vocab_major=vocab_major)

    assert _vocab_dots(jax.jit(loss).lower(hidden, head)) == 1
    # value_and_grad, as the train step takes it: under a bare grad the
    # unused loss value let JAX drop the old version's forward scan whole.
    assert _vocab_dots(jax.jit(jax.value_and_grad(loss, argnums=argnums))
                       .lower(hidden, head)) == dots


@pytest.mark.parametrize("lora_rank, dots", [(0, 3), (4, 2)],
                         ids=["full_finetune", "lora"])
def test_head_passes_of_train_step(devices8, lora_rank, dots):
    """The same count on the whole jitted step, tied head: three passes
    over the table in a full fine-tune, two under LoRA, whose frozen head
    gets no d(head) matmul and no [V,D] carry."""
    import flax.linen as nn

    mesh = build_mesh(MeshConfig(data=-1), devices8)
    cfg = dataclasses.replace(
        llama_tiny(vocab=_VOCAB), tie_embeddings=True, lora_rank=lora_rank)
    model = Llama(cfg)
    trainable = "lora" if lora_rank else None
    toks = jnp.zeros((8, 16), jnp.int32)
    state = init_train_state(model, optax.adamw(1e-3), jax.random.key(0),
                             (toks,), mesh, DEFAULT_RULES,
                             trainable=trainable)
    step = make_train_step(model, mesh, DEFAULT_RULES, loss_impl="chunked",
                           loss_chunk=32, trainable=trainable)
    batch = {"inputs": toks, "targets": toks}
    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        lowered = step.jitted.lower(state, batch)
    assert _vocab_dots(lowered) == dots


def _variant(name, devices8):
    """(mesh, cfg, rules, make_train_step kwargs, fsdp plan or None)."""
    from kubeflow_tpu.parallel.fsdp import FSDP
    from kubeflow_tpu.parallel.sharding import rules_for

    cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32)
    rules, kwargs, plan = DEFAULT_RULES, {}, None
    if name == "accum2":
        mesh = build_mesh(MeshConfig(data=-1), devices8)
        kwargs = {"accum_steps": 2}
    elif name == "tensor_vocab":  # the vocabulary sharded over `tensor`
        mesh = build_mesh(MeshConfig(data=4, tensor=2), devices8)
    elif name == "fsdp_bf16":  # the head arrives gathered and in bf16
        mesh = build_mesh(MeshConfig(data=2, fsdp=4), devices8)
        plan = FSDP(mesh, compute_dtype=jnp.bfloat16)
    elif name == "lora":  # frozen head
        mesh = build_mesh(MeshConfig(data=-1), devices8)
        cfg = dataclasses.replace(cfg, lora_rank=4, tie_embeddings=True)
        kwargs = {"trainable": "lora"}
    elif name == "pipeline":
        mesh = build_mesh(MeshConfig(data=4, pipe=2), devices8)
        cfg = dataclasses.replace(cfg, scan_layers=True,
                                  attention_impl="naive")
        rules = rules_for("pipeline")
        kwargs = {"pipeline": {"microbatches": 2}}
    return mesh, cfg, rules, kwargs, plan


@pytest.mark.parametrize(
    "name", ["accum2", "tensor_vocab", "fsdp_bf16", "lora", "pipeline"])
def test_train_step_variants_chunked_matches_full(devices8, name):
    """Every way the jitted step reaches the chunked loss lands on the
    full-logits step's loss and gradient norm from the same state."""
    mesh, cfg, rules, kwargs, plan = _variant(name, devices8)
    model = Llama(cfg)
    toks = jnp.zeros((8, 16), jnp.int32)
    rng = np.random.default_rng(0)
    batch = {
        "inputs": rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (8, 16), dtype=np.int32),
    }
    got = {}
    for impl in ("full", "chunked"):
        state = init_train_state(
            model, optax.adamw(1e-3), jax.random.key(1), (toks,), mesh,
            rules, trainable=kwargs.get("trainable"), fsdp=plan)
        step = make_train_step(model, mesh, rules, loss_impl=impl,
                               loss_chunk=48, fsdp=plan, **kwargs)
        _, metrics = step(state, batch)
        got[impl] = (float(metrics["loss"]), float(metrics["grad_norm"]))
    rel = 2e-2 if name == "fsdp_bf16" else 2e-4
    assert got["chunked"][0] == pytest.approx(got["full"][0], rel=rel)
    assert got["chunked"][1] == pytest.approx(got["full"][1], rel=10 * rel)
    assert got["full"][1] > 0
