"""Numerics goldens for attention kernels (SURVEY.md §7.3 item 2):
flash (Pallas) and ring/ulysses (shard_map) vs the naive einsum reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.llama import naive_attention
from kubeflow_tpu.ops.flash_attention import flash_attention
from kubeflow_tpu.ops.ring_attention import ring_attention, ulysses_attention
from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh


def _qkv(b=2, s=128, h=4, kh=2, d=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
    return q, k, v


def test_flash_matches_naive_causal():
    q, k, v = _qkv()
    ref = naive_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_non_causal():
    q, k, v = _qkv(s=64)
    ref = naive_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, False, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_gradients_match_naive():
    q, k, v = _qkv(s=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 32, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_ring_attention_matches_naive(devices8):
    mesh = build_mesh(MeshConfig(data=1, seq=4, tensor=2), devices8)
    q, k, v = _qkv(b=2, s=128, h=4, kh=2, d=16)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, axis_name="seq")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ring_attention_grads(devices8):
    mesh = build_mesh(MeshConfig(data=2, seq=4), devices8)
    q, k, v = _qkv(b=2, s=64, h=2, kh=2, d=8)

    with mesh:
        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v) ** 2)

        gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

    gn = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_ring_attention_under_jit(devices8):
    mesh = build_mesh(MeshConfig(data=1, seq=8), devices8)
    q, k, v = _qkv(b=2, s=128, h=4, kh=4, d=16)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_matches_naive(devices8):
    mesh = build_mesh(MeshConfig(data=2, seq=4), devices8)
    q, k, v = _qkv(b=2, s=128, h=4, kh=4, d=16)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = ulysses_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_ragged_seq_lengths():
    """Regression: seq not divisible by block must not misalign kv columns
    (dynamic-slice clamping bug found in round-1 verification)."""
    for s, causal in [(80, True), (80, False), (33, True)]:
        q, k, v = _qkv(b=1, s=s, h=2, kh=2, d=16)
        ref = naive_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal, 32, 32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("s,t,h,kh,causal,bq,bkv", [
    (80, 80, 4, 2, True, 32, 32),    # ragged (s % block != 0), GQA
    (64, 64, 4, 1, False, 32, 32),   # non-causal, group=4 (MQA)
    (64, 96, 4, 2, False, 32, 32),   # cross-attention s != t
    (33, 70, 8, 2, True, 32, 32),    # ragged both sides, group=4, causal
])
def test_flash_gradients_broad(s, t, h, kh, causal, bq, bkv):
    """Backward-kernel regression net: ragged rows (rows < seq_q mask),
    non-causal path, cross-attention, and larger GQA groups — each exercises
    a distinct branch of the dq/dkv kernels."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (2, s, h, 16))
    k = jax.random.normal(ks[1], (2, t, kh, 16))
    v = jax.random.normal(ks[2], (2, t, kh, 16))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, bq, bkv) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


# -- zigzag ring schedule (SURVEY.md §5.7 causal load balance) ---------------

from kubeflow_tpu.ops.ring_attention import (  # noqa: E402
    zigzag_indices,
    zigzag_ring_attention,
)


def test_zigzag_indices_layout():
    idx = np.asarray(zigzag_indices(16, 4))  # 8 chunks of 2, ring of 4
    # Shard i holds chunks (i, 7-i): [0,7], [1,6], [2,5], [3,4].
    assert idx.tolist() == [0, 1, 14, 15, 2, 3, 12, 13,
                            4, 5, 10, 11, 6, 7, 8, 9]
    # A permutation: inverse recovers identity.
    assert np.array_equal(np.argsort(idx)[idx], np.arange(16))


def test_zigzag_matches_naive(devices8):
    mesh = build_mesh(MeshConfig(data=1, seq=4, tensor=2), devices8)
    q, k, v = _qkv(b=2, s=128, h=4, kh=2, d=16)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = zigzag_ring_attention(q, k, v, axis_name="seq")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_zigzag_ring8_and_pre_permuted(devices8):
    mesh = build_mesh(MeshConfig(data=1, seq=8), devices8)
    q, k, v = _qkv(b=1, s=128, h=4, kh=4, d=8, seed=3)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = zigzag_ring_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # Pre-permuted path: caller lays out data in zigzag order (the input-
    # pipeline mode) and gets zigzag-ordered output back.
    idx = np.asarray(zigzag_indices(128, 8))
    qp, kp, vp = (np.asarray(x)[:, idx] for x in (q, k, v))
    with mesh:
        outp = zigzag_ring_attention(
            jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(vp),
            pre_permuted=True)
    np.testing.assert_allclose(np.asarray(outp), np.asarray(ref)[:, idx],
                               rtol=2e-3, atol=2e-3)


def test_zigzag_grads(devices8):
    mesh = build_mesh(MeshConfig(data=1, seq=4, tensor=2), devices8)
    q, k, v = _qkv(b=1, s=64, h=2, kh=2, d=8, seed=5)

    with mesh:
        def loss(q, k, v):
            return jnp.sum(zigzag_ring_attention(q, k, v) ** 2)
        gz = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def ref_loss(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gz, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_zigzag_step_time_vs_contiguous(devices8):
    """Before/after wall-clock at 8 virtual devices: the zigzag schedule
    skips fully-masked sub-blocks, so it should not be slower than the
    contiguous ring (on CPU the saved dense FLOPs are real work). Timing is
    reported; the assertion is a loose sanity bound, not a perf gate."""
    import time

    mesh = build_mesh(MeshConfig(data=1, seq=8), devices8)
    q, k, v = _qkv(b=1, s=1024, h=4, kh=4, d=32, seed=9)
    with mesh:
        ring_fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh=mesh))
        zz_fn = jax.jit(lambda a, b, c: zigzag_ring_attention(
            a, b, c, mesh=mesh, pre_permuted=True))
        ring_fn(q, k, v).block_until_ready()  # compile
        zz_fn(q, k, v).block_until_ready()

        def bench(fn, iters=5):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v)
            out.block_until_ready()
            return (time.perf_counter() - t0) / iters

        t_ring = bench(ring_fn)
        t_zz = bench(zz_fn)
    print(f"\nring(contiguous)={t_ring*1e3:.1f}ms  zigzag={t_zz*1e3:.1f}ms  "
          f"speedup={t_ring/t_zz:.2f}x")
    assert t_zz < t_ring * 1.5  # loose: zigzag must not regress badly


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_zigzag_training_matches_ring(devices8, tmp_path):
    """End-to-end training parity: the trainer's zigzag contract (permuted
    batches + matching RoPE positions) trains like the standard ring
    layout — same data, same init, per-step loss series compared."""
    import json

    from kubeflow_tpu.train.trainer import Trainer, TrainJobSpec

    series = {}
    for impl in ("ring", "zigzag", "ring_flash", "zigzag_flash"):
        metrics = tmp_path / f"{impl}.jsonl"
        spec = TrainJobSpec(
            model="llama_tiny",
            model_kwargs={"attention_impl": impl},
            dataset="learnable_lm",
            mesh={"data": 1, "seq": 4, "tensor": 2},
            ring_attention=impl,
            steps=4, batch_size=4, seq_len=32, learning_rate=1e-3,
            log_every=1, seed=3, metrics_path=str(metrics))
        Trainer(spec).run()
        series[impl] = [json.loads(l)["loss"]
                        for l in metrics.read_text().splitlines()
                        if "loss" in json.loads(l)]
    assert len(series["ring"]) >= 4
    for other in ("zigzag", "ring_flash", "zigzag_flash"):
        assert len(series[other]) == len(series["ring"]), (other, series)
        for a, b in zip(series["ring"], series[other]):
            assert b == pytest.approx(a, rel=2e-2), (other, series)


def test_zigzag_impl_refuses_unpermuted_data(devices8):
    """attention_impl='zigzag' without the data contract must fail loudly,
    not silently corrupt attention."""
    import jax.numpy as jnp

    from kubeflow_tpu.models.llama import Llama, llama_tiny
    import dataclasses

    cfg = dataclasses.replace(llama_tiny(), attention_impl="zigzag")
    model = Llama(cfg)
    toks = jnp.zeros((1, 32), jnp.int32)
    with pytest.raises(ValueError, match="zigzag"):
        model.init(jax.random.key(0), toks)


# -- fused (flash) inner block for ring schedules ----------------------------

from kubeflow_tpu.ops.flash_attention import flash_attention_lse  # noqa: E402


def test_flash_lse_matches_naive_stats():
    """(out, lse) variant: out matches naive; lse is the row logsumexp of
    the scaled scores (checked directly against the einsum scores)."""
    q, k, v = _qkv(s=64)
    out, lse = flash_attention_lse(q, k, v, True, 32, 32)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    b, s, h, d = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, s, kh, h // kh, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bskgt", qg, k) / np.sqrt(d)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None]
    scores = jnp.where(mask[None, :, None, None, :], scores, -1e30)
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    ref_lse = ref_lse.reshape(b, s, h, 1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-3, atol=1e-3)


def test_flash_lse_cotangent():
    """Gradients through BOTH outputs: a loss that mixes out and lse must
    match AD through the einsum reference."""
    q, k, v = _qkv(s=32, seed=3)

    def loss_flash(q, k, v):
        out, lse = flash_attention_lse(q, k, v, True, 16, 16)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        b, s, h, d = q.shape
        kh = k.shape[2]
        out = naive_attention(q, k, v, causal=True)
        qg = q.reshape(b, s, kh, h // kh, d).astype(jnp.float32)
        scores = jnp.einsum("bskgd,btkd->bskgt", qg, k) / np.sqrt(d)
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(mask[None, :, None, None, :], scores, -1e30)
        lse = jax.scipy.special.logsumexp(scores, axis=-1).reshape(b, s, h, 1)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)


def test_ring_flash_matches_naive(devices8):
    q, k, v = _qkv(s=128)
    mesh = build_mesh(MeshConfig(seq=8), devices8)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, axis_name="seq", inner="flash",
                             block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ring_flash_grads_match_einsum_ring(devices8):
    q, k, v = _qkv(s=64, seed=5)
    mesh = build_mesh(MeshConfig(seq=4), devices8[:4])

    with mesh:
        def loss_flash(q, k, v):
            return jnp.sum(ring_attention(q, k, v, inner="flash",
                                          block_q=16, block_kv=16) ** 2)

        def loss_einsum(q, k, v):
            return jnp.sum(ring_attention(q, k, v) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        ge = jax.grad(loss_einsum, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)


def test_ring_flash_rejects_custom_positions(devices8):
    q, k, v = _qkv(s=64)
    mesh = build_mesh(MeshConfig(seq=4), devices8[:4])
    with mesh, pytest.raises(ValueError, match="contiguous"):
        ring_attention(q, k, v, inner="flash",
                       positions=jnp.zeros((2, 64), jnp.int32))


def test_zigzag_flash_matches_naive(devices8):
    q, k, v = _qkv(s=128, seed=7)
    mesh = build_mesh(MeshConfig(seq=8), devices8)
    ref = naive_attention(q, k, v, causal=True)
    with mesh:
        out = zigzag_ring_attention(q, k, v, inner="flash",
                                    block_q=8, block_kv=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # heaviest representative; full tier covers it
def test_zigzag_flash_grads(devices8):
    q, k, v = _qkv(s=64, seed=9)
    mesh = build_mesh(MeshConfig(seq=4), devices8[:4])

    with mesh:
        def loss_flash(q, k, v):
            return jnp.sum(zigzag_ring_attention(
                q, k, v, inner="flash", block_q=8, block_kv=8) ** 2)

        def loss_einsum(q, k, v):
            return jnp.sum(zigzag_ring_attention(q, k, v) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        ge = jax.grad(loss_einsum, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)


# -- packed sequences (segment ids) in the fused kernels ---------------------

def _packed_setup(b=2, s=96, h=4, kh=2, d=16, seed=11):
    """Each row packs 3 sequences of 32 tokens; positions restart per
    segment (the RoPE-consistent packed layout)."""
    q, k, v = _qkv(b=b, s=s, h=h, kh=kh, d=d, seed=seed)
    seg = (jnp.arange(s) * 3 // s)[None, :].repeat(b, 0)  # 3 ~equal spans
    return q, k, v, seg


def test_flash_segments_match_naive():
    q, k, v, seg = _packed_setup()
    ref = naive_attention(q, k, v, causal=True, segment_ids=seg)
    out = flash_attention(q, k, v, True, 32, 32, None, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_segments_block_misaligned():
    """Segment boundaries that do NOT align with kernel blocks (32-token
    segments vs 64-token blocks) must still mask exactly."""
    q, k, v, seg = _packed_setup(s=96)
    ref = naive_attention(q, k, v, causal=True, segment_ids=seg)
    out = flash_attention(q, k, v, True, 64, 64, None, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_segments_isolation():
    """Tokens of one packed sequence must be invisible to the others:
    perturbing segment 0's k/v leaves segments 1-2 outputs bit-identical."""
    q, k, v, seg = _packed_setup(b=1)
    out1 = flash_attention(q, k, v, True, 32, 32, None, segment_ids=seg)
    k2 = k.at[:, :32].set(jax.random.normal(jax.random.key(99), k[:, :32].shape))
    v2 = v.at[:, :32].set(jax.random.normal(jax.random.key(98), v[:, :32].shape))
    out2 = flash_attention(q, k2, v2, True, 32, 32, None, segment_ids=seg)
    np.testing.assert_array_equal(np.asarray(out1[:, 32:]),
                                  np.asarray(out2[:, 32:]))
    assert np.abs(np.asarray(out1[:, :32]) - np.asarray(out2[:, :32])).max() > 1e-3


def test_flash_segments_gradients_match_naive():
    q, k, v, seg = _packed_setup(s=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 32, 32, None,
                                       segment_ids=seg) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True,
                                       segment_ids=seg) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)


def test_flash_segments_shape_validation():
    q, k, v, _ = _packed_setup()
    with pytest.raises(ValueError, match="segment_ids"):
        flash_attention(q, k, v, True, 32, 32, None,
                        segment_ids=jnp.zeros((2, 7), jnp.int32))


@pytest.mark.parametrize("impl", ["naive", "flash"])
def test_llama_packed_sequences_match_unpacked(impl):
    """Two sequences packed into one row (segment_ids + restarting
    positions) must produce exactly the logits each gets standalone —
    the packing is invisible to the model."""
    import dataclasses

    from kubeflow_tpu.models.llama import Llama, llama_tiny

    cfg = dataclasses.replace(llama_tiny(), attention_impl=impl,
                              remat=False, flash_block_q=16,
                              flash_block_kv=16)
    model = Llama(cfg)
    rng = np.random.default_rng(5)
    a = rng.integers(0, cfg.vocab_size, (1, 24), dtype=np.int32)
    b_ = rng.integers(0, cfg.vocab_size, (1, 40), dtype=np.int32)
    params = model.init(jax.random.key(0), jnp.asarray(a))["params"]

    packed = jnp.concatenate([jnp.asarray(a), jnp.asarray(b_)], axis=1)
    seg = jnp.concatenate([jnp.zeros((1, 24), jnp.int32),
                           jnp.ones((1, 40), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.arange(24)[None], jnp.arange(40)[None]],
                          axis=1)
    out_packed = model.apply({"params": params}, packed, positions=pos,
                             segment_ids=seg)
    out_a = model.apply({"params": params}, jnp.asarray(a))
    out_b = model.apply({"params": params}, jnp.asarray(b_))
    np.testing.assert_allclose(np.asarray(out_packed[:, :24]),
                               np.asarray(out_a), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out_packed[:, 24:]),
                               np.asarray(out_b), rtol=2e-4, atol=2e-4)


# -- RDMA ring: in-kernel remote-DMA K/V rotation ----------------------------

from kubeflow_tpu.ops.rdma_ring_attention import rdma_ring_attention  # noqa: E402


@pytest.mark.parametrize("nseq", [4, 8])
def test_rdma_ring_matches_naive(devices8, nseq):
    """Double-buffered remote-DMA rotation with DMA-ack backpressure:
    numerics must match the reference exactly (same math, explicit
    overlap)."""
    from jax.sharding import Mesh

    q, k, v = _qkv(b=2, s=128, h=4, kh=2, d=16, seed=21)
    ref = naive_attention(q, k, v, causal=True)
    mesh = Mesh(np.array(devices8[:nseq]), ("seq",))
    out = rdma_ring_attention(q, k, v, axis_name="seq", mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("nseq", [4, 8])
def test_rdma_ring_fused_backward_matches_naive(devices8, nseq):
    """The fused two-pass backward (K/V rotate for dq; q/dout/lse/delta
    rotate for resident dk/dv — ops/ROADMAP.md item 1) must match the
    einsum reference at both ring sizes."""
    from jax.sharding import Mesh

    q, k, v = _qkv(b=1, s=64, h=2, kh=2, d=8, seed=23)
    mesh = Mesh(np.array(devices8[:nseq]), ("seq",))

    def loss_rdma(q, k, v):
        return jnp.sum(rdma_ring_attention(q, k, v, "seq", mesh) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_rdma, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_rdma_ring_fused_backward_gqa_batched(devices8):
    """GQA (group > 1) + batch > 1 through the fused backward: the
    [bkh, group*s, d] head-block layout must round-trip gradients."""
    from jax.sharding import Mesh

    q, k, v = _qkv(b=2, s=64, h=4, kh=2, d=8, seed=29)
    mesh = Mesh(np.array(devices8[:4]), ("seq",))

    def loss_rdma(q, k, v):
        out = rdma_ring_attention(q, k, v, "seq", mesh)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = naive_attention(q, k, v, causal=True)
        return jnp.sum(out * jnp.cos(out))

    gr = jax.grad(loss_rdma, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_rdma_ring_on_framework_mesh_single_axis_limitation(devices8):
    """On the full multi-axis framework mesh the interpret path cannot
    discharge remote DMAs (compiled Mosaic can); a 1-axis view works and
    matches the multi-axis lax-level ring."""
    q, k, v = _qkv(b=2, s=64, h=4, kh=4, d=8, seed=25)
    fmesh = build_mesh(MeshConfig(seq=4), devices8[:4])
    with fmesh:
        ref = ring_attention(q, k, v, axis_name="seq", inner="flash",
                             block_q=16, block_kv=16)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices8[:4]), ("seq",))
    out = rdma_ring_attention(q, k, v, "seq", mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
