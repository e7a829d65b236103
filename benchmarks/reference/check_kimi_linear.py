#!/usr/bin/env python3
"""The reference side of the "train_ref" kind for Kimi-Linear, a process of
its own (it takes the chip after the trainer has left it):

    check_kimi_linear.py --spec <trainer spec> --config <reference cfg>
                         --out <json> [--checkpoint <dir>]
                         [--control bfloat16,no_shared,...] [--seed <n>]

Weights and the first batch are rebuilt from the spec's seed through the
program's own initialiser and dataset (`registry.build_model(...).init`,
`registry.build_dataset`): data, not code under test. Loss and gradients are
`benchmarks/reference/kimi_linear.py`'s. The trainer's gradient is read out
of its step-1 checkpoint's first Adam moment, g = mu / (1 - b1); only that
third of the state is restored, while the device computes the reference.
Written out: the reference's loss, its global gradient norm, and for each
parameter tensor ||g - g_ref|| / ||g_ref|| (an all-zero reference gradient,
the fixed score-correction bias, compares absolutely: the trainer's must be
zero too).

`--control` is never the check. It makes the readings the limits in the
configuration's file were set between (`CONTROLS` below): a step that is
wrong in a known way, held against the fp32 reference exactly as a trainer's
step is held. Each goes out under `controls` as the `row` a trainer would
have printed and its per-tensor errors, so `train_ref.compare` can be fed
it whole. `--seed` replaces the spec's, for a control at a seed that no
trainer ran.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ADAM_B1 = 0.9  # optax.adamw's default, which the trainer does not change

#: The steps that must come out as not correct.
CONTROLS = {
    "bfloat16": "the reference computed in bf16 throughout, accumulations "
                "and the KDA state included: the precision below the "
                "configuration's",
    "no_shared": "every expert layer without its shared expert",
    "half_batch": "the first sequence of the two alone",
    "drop_pairs": "every twentieth token loses its routed experts: a "
                  "twentieth of the token-expert pairs dropped",
}


def main(argv: list[str]) -> int:
    t0 = time.monotonic()
    p = argparse.ArgumentParser()
    p.add_argument("--spec", required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--checkpoint")
    p.add_argument("--out", required=True)
    p.add_argument("--control", default="")
    p.add_argument("--seed", type=int)
    args = p.parse_args(argv)
    controls = [c for c in args.control.split(",") if c]
    if set(controls) - set(CONTROLS):
        raise SystemExit(f"--control takes {sorted(CONTROLS)}")
    if not controls and not args.checkpoint:
        raise SystemExit("--checkpoint is required for the check")
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    import kimi_linear as ref
    from kubeflow_tpu.utils import registry
    from kubeflow_tpu.utils.devices import enable_compile_cache

    enable_compile_cache()
    with open(args.spec) as fh:
        spec = json.load(fh)
    if args.seed is not None:
        spec["seed"] = args.seed
    with open(args.config) as fh:
        cfg = json.load(fh)
    for key in ("kda_layers", "full_attn_layers", "experts_held"):
        cfg[key] = tuple(cfg[key])

    model, _ = registry.build_model(spec["model"], **spec["model_kwargs"])
    shape = (spec["batch_size"], spec["seq_len"])
    params = nn.meta.unbox(jax.jit(
        lambda key: model.init(key, jnp.zeros(shape, jnp.int32))["params"]
    )(jax.random.key(spec["seed"])))
    batch = next(iter(registry.build_dataset(
        spec["dataset"], batch_size=shape[0], seq_len=shape[1],
        seed=spec["seed"], **spec["dataset_kwargs"])))
    inputs, targets = jnp.asarray(batch["inputs"]), jnp.asarray(
        batch["targets"])
    jax.block_until_ready(params)
    t_init = time.monotonic()

    def by_name(tree) -> dict:
        return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}

    def start(dtype=jnp.float32, x=inputs, y=targets):
        """One pass of the reference, dispatched and not waited for."""
        return jax.jit(lambda p, x, y: ref.loss_and_grads(
            p, x, y, cfg, dtype))(params, x, y)

    def fetch(out) -> tuple[float, dict, dict]:
        """(loss, counters, gradients by name as numpy on the host)."""
        (loss, counters), grads = out
        grads = {k: np.asarray(v, np.float32)
                 for k, v in by_name(grads).items()}
        return (float(loss), {k: float(v) for k, v in counters.items()},
                grads)

    def norm(grads: dict) -> float:
        return float(np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                                 for g in grads.values())))

    sound = start()
    t_restore = time.monotonic()
    held = (trainer_gradients(args.checkpoint, by_name, np)
            if args.checkpoint else None)
    restore_s = time.monotonic() - t_restore
    loss, counters, want = fetch(sound)
    t_ref = time.monotonic()
    if held is not None and sorted(held) != sorted(want):
        raise SystemExit("the two sides disagree on the parameter tree")

    def errors(got: dict) -> dict:
        errs = {}
        for name, g_ref in want.items():
            den = float(np.linalg.norm(g_ref))
            diff = float(np.linalg.norm(got[name] - g_ref))
            errs[name] = diff / den if den else diff
        return errs

    def faulty_moe(name: str):
        plain = ref.moe_ffn

        def moe_ffn(x, p, cfg):
            y, share, counts = plain(x, p, cfg)
            shared = ref.swiglu(x, p["shared_expert"])
            if name == "no_shared":
                return y - shared, share, counts
            lost = (jnp.arange(x.shape[1]) % 20 == 0)[None, :, None]
            return jnp.where(lost, shared, y), share, counts
        return moe_ffn

    def control(name: str) -> dict:
        """What a trainer with this fault would have shown. The precision
        control is the reference's own pass in bf16. A fault's reading is
        its change to the reference's loss and gradients, added to the
        trainer's own (to the reference's where no trainer ran)."""
        if name == "bfloat16":
            low_loss, _, low = fetch(start(jnp.bfloat16))
            return {"row": {"loss": low_loss, "grad_norm": norm(low)},
                    "grad_rel_err": errors(low)}
        if name == "half_batch":
            f_loss, _, f = fetch(start(x=inputs[:1], y=targets[:1]))
        else:
            plain, ref.moe_ffn = ref.moe_ffn, faulty_moe(name)
            try:
                f_loss, _, f = fetch(start())
            finally:
                ref.moe_ffn = plain
        base = held if held is not None else want
        got = {k: base[k] + (f[k] - want[k]) for k in want}
        return {"row": {"loss": f_loss, "grad_norm": norm(got)},
                "grad_rel_err": errors(got)}

    result = {"loss": loss, "grad_norm": norm(want), "counters": counters,
              "seed": spec["seed"],
              # The tensors whose gradient depends on which tokens chose
              # which expert: the routed experts' weights and the router.
              "is_expert": {name: name.rsplit("/", 1)[-1] in (
                  "w_gate", "w_up", "w_down", "router") for name in want}}
    if controls:
        result["controls"] = {name: control(name) for name in controls}
    else:
        result["grad_rel_err"] = errors(held)
    with open(args.out, "w") as fh:
        json.dump({**result, "device": jax.devices()[0].platform,
                   "init_s": t_init - t0, "restore_s": restore_s,
                   "reference_pass_s": t_ref - t_init,
                   "seconds": time.monotonic() - t0}, fh, indent=1)
    return 0


def trainer_gradients(checkpoint: str, by_name, np) -> dict:
    """The gradient the trainer's first step applied, by parameter name:
    mu / (1 - b1) of optax's scale_by_adam, the only leaves of the saved
    state that are read from disk."""
    import jax
    import orbax.checkpoint as ocp

    path = os.path.join(os.path.abspath(checkpoint), "1", "state")
    ckptr = ocp.PyTreeCheckpointer()
    tree = ckptr.metadata(path).item_metadata.tree

    def is_mu(key_path) -> bool:
        return any(getattr(k, "key", None) == "mu" for k in key_path)

    state = ckptr.restore(path, args=ocp.args.PyTreeRestore(
        item=jax.tree_util.tree_map_with_path(
            lambda kp, leaf: leaf if is_mu(kp) else ocp.PLACEHOLDER, tree),
        restore_args=jax.tree.map(
            lambda _: ocp.RestoreArgs(restore_type=np.ndarray), tree)))
    mu = find_mu(state["opt_state"])
    return {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1)
            for k, v in by_name(mu).items()}


def find_mu(opt_state):
    """The `mu` of optax's scale_by_adam inside the saved optimizer state,
    wherever the chain put it."""
    if isinstance(opt_state, dict):
        if "mu" in opt_state:
            return opt_state["mu"]
        opt_state = list(opt_state.values())
    if isinstance(opt_state, (list, tuple)):
        for item in opt_state:
            found = find_mu(item)
            if found is not None:
                return found
    return None


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
