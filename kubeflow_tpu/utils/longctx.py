"""Long-context evidence for the single-chip bench model (SURVEY.md §5.7).

The long-context stack (chunked fused CE, flash attention, packed masks)
claims to ADMIT sequences the naive path cannot. This module produces the
evidence both ways:

  * `analyze_fit(batch, seq)` — AOT-compile the REAL bench train step
    (llama_1b, chunked CE, full-block remat, adamw bf16-mu) on one
    device and read `memory_analysis()`: the per-device working set vs
    the v5e 16 GiB HBM budget. Runs anywhere, chip or not — the same
    pre-flight arithmetic the 8B scale proof uses (utils/scaleproof.py).
  * `measure(batch, seq)` — the measured row (tok/s + MFU) on the live
    backend; `bench.py --longctx` runs it on the chip and fails without
    one.

Chunked CE is what makes s>=2048 admissible at all here: the full-CE
fp32 logits buffer is B*S*V*4 bytes (b2 s2048 * 32768 vocab = 0.5 GiB
for ONE residency, and XLA keeps fwd+bwd copies), while the chunked path
peaks at B*chunk*V.
"""

from __future__ import annotations

import sys

V5E_HBM_BYTES = 16 * 1024**3
GIB = 1024**3


def _build(batch: int, seq: int, loss_impl: str = "chunked",
           size: str = "1b", loss_chunk: int = 1024,
           remat_policy: str | None = None,
           flash_block: tuple[int, int] | None = None):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.models.llama import Llama, llama_1b, llama_tiny
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
    from kubeflow_tpu.train.step import abstract_train_state, make_train_step

    # Force the flash kernel: `auto` falls back to naive off-TPU, whose
    # materialized [B,H,S,S] scores would inflate the measured temp memory
    # with buffers the TPU deployment never allocates (same rationale as
    # scaleproof's 8B cases). `size="tiny"` is the harness-pinning test
    # shape (tests/test_longctx.py).
    base = llama_1b() if size == "1b" else llama_tiny()
    # max_seq_len sizes the RoPE table; llama_1b pins 2048, and positions
    # past the table would silently CLAMP under jit (same rotary phase for
    # every tail token) — the long-context evidence must model the config
    # a real s-length deployment would run.
    cfg = dataclasses.replace(base, attention_impl="flash",
                              max_seq_len=max(seq, base.max_seq_len))
    if remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if flash_block:
        cfg = dataclasses.replace(cfg, flash_block_q=flash_block[0],
                                  flash_block_kv=flash_block[1])
    model = Llama(cfg)
    mesh = build_mesh(MeshConfig(data=1), jax.devices()[:1])
    tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    _, abstract, shardings = abstract_train_state(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), mesh, DEFAULT_RULES)
    state_args = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    batch_args = {
        "inputs": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    step = make_train_step(model, mesh, DEFAULT_RULES, loss_impl=loss_impl,
                           loss_chunk=loss_chunk)
    return cfg, model, mesh, tx, step, state_args, batch_args


def analyze_fit(batch: int, seq: int, loss_impl: str = "chunked",
                size: str = "1b") -> dict:
    """AOT compile + memory_analysis for one (batch, seq) point, against
    the v5e HBM budget (scaleproof's shared fit arithmetic)."""
    from kubeflow_tpu.utils.scaleproof import _mem_report

    cfg, _, mesh, _, step, state_args, batch_args = _build(
        batch, seq, loss_impl, size)
    with mesh:
        compiled = step.jitted.lower(state_args, batch_args).compile()
    report = _mem_report(compiled, hbm_bytes=V5E_HBM_BYTES, chip="v5e")
    report.update({
        "batch": batch,
        "seq_len": seq,
        "loss_impl": loss_impl,
        "model_params": cfg.num_params,
    })
    return report


def measure(batch: int, seq: int, timed_steps: int = 6,
            loss_impl: str = "chunked", size: str = "1b",
            loss_chunk: int = 1024, remat_policy: str | None = None,
            flash_block: tuple[int, int] | None = None) -> dict:
    """Measured tok/s + MFU at (batch, seq) on the live backend.
    Pipelined timing, single fetch at the end (a host sync per step
    stalls the dispatch queue and is charged to the step). MFU is None
    on the CPU, where there is no peak to hold it against. The knob
    kwargs (loss_chunk / remat_policy / flash_block) back the tuning
    sweep (`tune_point`)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
    from kubeflow_tpu.train.metrics import peak_flops_per_chip
    from kubeflow_tpu.train.step import init_train_state

    cfg, model, mesh, tx, step, _, _ = _build(
        batch, seq, loss_impl, size, loss_chunk=loss_chunk,
        remat_policy=remat_policy, flash_block=flash_block)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    state = init_train_state(model, tx, jax.random.key(0), (tokens,), mesh,
                             DEFAULT_RULES)
    rng = np.random.default_rng(0)

    def make_batch():
        return {
            "inputs": rng.integers(0, cfg.vocab_size, (batch, seq),
                                   dtype=np.int32),
            "targets": rng.integers(0, cfg.vocab_size, (batch, seq),
                                    dtype=np.int32),
        }

    for _ in range(3):  # compile + steady-state warmup
        state, metrics = step(state, make_batch())
        float(metrics["loss"])
    batches = [make_batch() for _ in range(timed_steps)]
    t0 = time.perf_counter()
    for b in batches:
        state, metrics = step(state, b)
    float(metrics["loss"])  # force completion of the chain
    dt = (time.perf_counter() - t0) / timed_steps
    peak = peak_flops_per_chip()
    mfu = (None if peak is None
           else round(6 * cfg.num_params * batch * seq / dt / peak, 4))
    return {
        "batch": batch,
        "seq_len": seq,
        "loss_impl": loss_impl,
        "loss_chunk": loss_chunk,
        "remat_policy": remat_policy or cfg.remat_policy,
        "flash_block": list(flash_block) if flash_block else
        [cfg.flash_block_q, cfg.flash_block_kv],
        "tok_s": round(batch * seq / dt, 1),
        "mfu": mfu,
        "avg_step_time_s": round(dt, 4),
        "device_kind": jax.devices()[0].device_kind,
    }


#: The s3072 knob grid (PROFILE.md §4's levers): remat policy, CE chunk,
#: flash block shape. Small by design — each variant pays a full compile.
TUNE_VARIANTS = (
    {},  # committed defaults: remat nothing, chunk 1024, blocks 512x512
    {"remat_policy": "save_attn"},
    {"loss_chunk": 512},
    {"loss_chunk": 2048},
    {"flash_block": (1024, 512)},
    {"flash_block": (512, 1024)},
)


def tune_point(batch: int, seq: int, timed_steps: int = 4,
               variants=TUNE_VARIANTS, size: str = "1b") -> list[dict]:
    """Sweep the long-context knobs at one (batch, seq) on the live
    chip; returns rows sorted fastest-first (at one point tok/s and MFU
    order alike), failures recorded inline after them (an OOM or compile
    crash is a data point, not an abort; the caller fails the run)."""
    rows = []
    for kv in variants:
        try:
            rows.append(measure(batch, seq, timed_steps=timed_steps,
                                size=size, **kv))
        except Exception as e:  # noqa: BLE001 - recorded per variant
            import re

            msg = re.sub(r"\x1b\[[0-9;]*m", "", f"{type(e).__name__}: {e}")
            rows.append({"batch": batch, "seq_len": seq, **kv,
                         "error": " ".join(msg.split())[:200]})
        print(f"longctx tune {kv}: {rows[-1].get('tok_s', 'ERR')}",
              file=sys.stderr, flush=True)
    return sorted(rows, key=lambda r: -r.get("tok_s", -1.0))
