"""Sharded-training A/B harness (`python bench.py --train-fsdp`).

The claims the fsdp runtime makes (ISSUE 15) are mechanism claims, so —
like the serve benches — the harness runs REAL train steps through the
production factories (init_train_state / make_train_step with an
parallel/fsdp.FSDP plan) and records both the equivalence and the layout
arithmetic:

  * `equivalence`: replicated (mesh data=N) vs fsdp master layout
    (mesh fsdp=N, exact escape hatch) on the SAME seeded batch stream —
    per-step loss trajectories and the max relative delta (fp32 compute,
    so the only residual is cross-layout reduction order, ~1e-7);
    plus grad_accum=K on the same global batch vs K=1.
  * `memory`: param/opt-state bytes per chip from the actual shardings
    (the tpk_train_*_bytes_per_chip arithmetic) — the fsdp arm must
    divide the replicated arm by the shard degree.
  * `bf16` arm: param_dtype="bfloat16" gathered compute copies — same
    master bytes, loss finite (numeric delta reported, never hidden).
  * step wall-clock per arm: a time only when `bench.py --train-fsdp`
    runs it on the chip; the CPU test tier reads the mechanism rows only.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any


def _arm(model, mesh, rules, batches, *, fsdp_plan=None, accum=1,
         timed_from=2):
    """One A/B arm: init + step the shared batch stream; returns losses,
    per-chip state bytes, and ms/step over the steady-state window."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubeflow_tpu.parallel.fsdp import tree_bytes_per_device
    from kubeflow_tpu.train.step import init_train_state, make_train_step

    batch, seq = batches[0]["inputs"].shape
    tx = optax.adamw(1e-3)
    state = init_train_state(
        model, tx, jax.random.key(0),
        (jnp.zeros((batch, seq), jnp.int32),), mesh,
        rules, fsdp=fsdp_plan)
    step = make_train_step(model, mesh, rules, fsdp=fsdp_plan,
                           accum_steps=accum)
    losses = []
    t0 = None
    m = None
    for i, b in enumerate(batches):
        if i == timed_from:
            if m is not None:
                # Drain the warmup dispatches BEFORE the clock opens —
                # queued warmup compute must not be charged to the
                # timed window (PROFILE §1 fetch-sync hygiene).
                float(m["loss"])
            t0 = time.perf_counter()
        state, m = step(state, b)
        losses.append(m["loss"])
    losses = [float(x) for x in losses]  # one sync closes the clock
    wall = time.perf_counter() - (t0 if t0 is not None else time.perf_counter())
    timed = max(len(batches) - timed_from, 1)
    return {
        # Full precision: the equivalence deltas are computed FROM these
        # — display rounding would quantize ~1e-7 deltas to 0.0.
        "losses": losses,
        "final_loss": round(losses[-1], 6),
        "ms_per_step": round(wall / timed * 1e3, 2),
        "param_bytes_per_chip": tree_bytes_per_device(state.params),
        "opt_state_bytes_per_chip": tree_bytes_per_device(state.opt_state),
    }


def _rel_delta(a: list[float], b: list[float]) -> float:
    return max(abs(x - y) / max(abs(x), 1e-9) for x, y in zip(a, b))


def run_trainbench(quick: bool = False) -> dict[str, Any]:
    """The A/B rows. Shard degree adapts to the device count (1 chip
    degenerates to degree 1 — the harness still proves the mechanism
    shape; the CPU tier runs it at 4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.llama import Llama, llama_tiny
    from kubeflow_tpu.parallel.fsdp import FSDP
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES

    devices = jax.devices()
    degree = 1
    for cand in (4, 2):
        if len(devices) % cand == 0 and len(devices) >= cand:
            degree = cand
            break
    devices = devices[:degree]

    # fp32 compute: the equivalence rows measure LAYOUT-induced deltas;
    # bf16 rounding would drown them (the bf16 arm is separate).
    cfg = dataclasses.replace(llama_tiny(), num_layers=2,
                              dtype=jnp.float32)
    model = Llama(cfg)
    batch, seq = 8, 16
    steps = 4 if quick else 8
    rng = np.random.default_rng(0)
    batches = [
        {"inputs": rng.integers(0, cfg.vocab_size, (batch, seq),
                                dtype=np.int32),
         "targets": rng.integers(0, cfg.vocab_size, (batch, seq),
                                 dtype=np.int32)}
        for _ in range(steps)
    ]

    mesh_repl = build_mesh(MeshConfig(data=degree), devices)
    mesh_fsdp = build_mesh(MeshConfig(data=1, fsdp=degree), devices)

    repl = _arm(model, mesh_repl, DEFAULT_RULES, batches)
    fsdp = _arm(model, mesh_fsdp, DEFAULT_RULES, batches,
                fsdp_plan=FSDP(mesh_fsdp))
    accum = _arm(model, mesh_fsdp, DEFAULT_RULES, batches,
                 fsdp_plan=FSDP(mesh_fsdp), accum=2)
    bf16 = _arm(model, mesh_fsdp, DEFAULT_RULES, batches,
                fsdp_plan=FSDP(mesh_fsdp,
                               compute_dtype=jnp.bfloat16))

    result = {
        "method": (
            "real init_train_state/make_train_step arms over one seeded "
            "batch stream; fp32 compute so equivalence rows see only "
            "layout-induced reduction order; clock opened after 2 "
            "warmup steps, closed by the final loss fetch"),
        "model": "llama_tiny(layers=2, fp32)",
        "shard_degree": degree,
        "global_batch": batch,
        "seq_len": seq,
        "timed_steps": steps - 2,
        "replicated": repl,
        "fsdp_master": fsdp,
        "fsdp_grad_accum2": accum,
        "fsdp_bf16_compute": bf16,
        "equivalence": {
            "fsdp_vs_replicated_max_rel_delta": _rel_delta(
                repl["losses"], fsdp["losses"]),
            "grad_accum2_vs_1_max_rel_delta": _rel_delta(
                fsdp["losses"], accum["losses"]),
            "bf16_vs_fp32_max_rel_delta": _rel_delta(
                fsdp["losses"], bf16["losses"]),
        },
        "memory": {
            "opt_state_ratio_replicated_over_fsdp": round(
                repl["opt_state_bytes_per_chip"]
                / max(fsdp["opt_state_bytes_per_chip"], 1), 4),
            "param_ratio_replicated_over_fsdp": round(
                repl["param_bytes_per_chip"]
                / max(fsdp["param_bytes_per_chip"], 1), 4),
        },
    }
    return result
