"""AOT scale proof: compile the 8B contract on virtual v5p-shaped meshes.

The capability contract (BASELINE.json / SURVEY.md §6) is Llama-3-8B
fine-tune via JAXJob on v5p at >=45% MFU. This environment has one emulated
v5e chip, so 8B can never *run* here — but it can be **proven to compile and
fit**: XLA's AOT path (`jit(step).lower(...).compile()`) works on N virtual
CPU devices with the real shardings, and `compiled.memory_analysis()`
reports per-device buffer sizes (arguments = parameter/optimizer/batch
shards, temp = activation working set). That is the strongest signal this
environment can produce about the target topology, and it is exactly how a
production launch would pre-flight a config before burning pod-hours.

Cases (device == chip; v5p carries 95 GB HBM per chip):
  * train_8b_v5p8       — fsdp=4 x tensor=2 over 8 devices, seq 4096
  * train_8b_v5p8_long  — same mesh, seq 8192 (long-context fine-tune point)
  * train_8b_v5p32_2slice — data=2 (DCN) x fsdp=16 over 32 devices as two
    slices: the eval-config-5 topology, slice-major device order so only DP
    gradient all-reduce crosses DCN (parallel/mesh.py).
  * serve_8b_tp8        — bf16 weights sharded tensor=8; prefill bucket +
    batched decode step against an 8k KV cache (serving memory envelope).

Every training case compiles the FULL train step — fwd + bwd + adamw
(bf16 mu) — with full-block remat and chunked cross entropy, i.e. the same
knobs the trainer runs (train/step.py, train/trainer.py).

Each case runs in a fresh subprocess so the virtual device count can be set
before backend init (same re-exec pattern as __graft_entry__.dryrun).
Output: SCALEPROOF.json with per-device byte budgets + fit assertions.

Reference parity note: the reference platform cannot make this promise at
all — Kubeflow schedules pods and leaves OOM discovery to the user's first
real run (SURVEY.md §2.6: no parallelism math in the platform).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

V5P_HBM_BYTES = 95 * 1024**3  # 95 GiB per v5p chip
GIB = 1024**3

CASES = (
    "train_8b_v5p8",
    "train_8b_v5p8_long",
    "train_8b_v5p8_fsdp",
    "train_8b_v5p32_2slice",
    "serve_8b_tp8",
)

_CASE_DEVICES = {
    "train_8b_v5p8": 8,
    "train_8b_v5p8_long": 8,
    "train_8b_v5p8_fsdp": 8,
    "train_8b_v5p32_2slice": 32,
    "serve_8b_tp8": 8,
}


def _mem_report(compiled, *, hbm_bytes: int = V5P_HBM_BYTES,
                chip: str = "v5p") -> dict:
    """memory_analysis() → conservative per-device fit report. The ONE
    copy of this arithmetic — the long-context analysis
    (utils/longctx.py) consumes it with the v5e budget."""
    ma = compiled.memory_analysis()
    args = int(ma.argument_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    peak = int(ma.peak_memory_in_bytes)
    # Conservative per-device live set: arguments + temps + outputs with no
    # donation credit (alias_size already subtracts what XLA aliased; the
    # CPU backend typically reports 0, so this double-counts donated state
    # — if even that fits, the TPU number fits with margin).
    total = args + temp + out - alias
    return {
        "argument_bytes": args,
        "temp_bytes": temp,
        "output_bytes": out,
        "alias_bytes": alias,
        "peak_memory_bytes": peak,
        "total_conservative_bytes": total,
        "total_conservative_gib": round(total / GIB, 2),
        f"fits_{chip}_hbm": total <= hbm_bytes,
        "hbm_budget_gib": round(hbm_bytes / GIB, 2),
    }


def _train_case(mesh_cfg_kwargs: dict, batch: int, seq: int, *,
                fsdp_runtime: bool = False,
                param_dtype: str | None = None,
                grad_accum: int = 1) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    import optax

    from kubeflow_tpu.models.llama import Llama, llama3_8b
    from kubeflow_tpu.parallel.fsdp import FSDP, parse_compute_dtype, \
        tree_bytes_per_device
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
    from kubeflow_tpu.train.step import abstract_train_state, make_train_step

    # Force the flash kernel (interpret-lowered off-TPU): the production
    # config's attention never materializes the [S,T] score tensor, and
    # `auto` would fall back to naive on the CPU backend, inflating the
    # measured temp memory with buffers the TPU deployment doesn't have.
    cfg = dataclasses.replace(llama3_8b(), attention_impl="flash")
    model = Llama(cfg)
    mesh = build_mesh(MeshConfig(**mesh_cfg_kwargs))
    rules = DEFAULT_RULES
    tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)

    from jax.sharding import NamedSharding, PartitionSpec as P

    # The fsdp master-state runtime (parallel/fsdp.py), exactly as the
    # trainer would launch it: every fp32-param/Adam-moment leaf carries
    # the fsdp axis, gathers for compute happen inside the step.
    plan = None
    if fsdp_runtime:
        plan = FSDP(mesh, compute_dtype=parse_compute_dtype(param_dtype))

    # The SAME layout derivation the trainer uses (train/step.py) — the
    # proof must measure the production layout, not a reimplementation.
    _, abstract, shardings = abstract_train_state(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), mesh, rules, fsdp=plan)
    state_args = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)

    with mesh, nn.logical_axis_rules(rules):
        batch_sh = NamedSharding(mesh, P(("data", "fsdp"), None))
        batch_args = {
            "inputs": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                           sharding=batch_sh),
            "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                            sharding=batch_sh),
        }

        step = make_train_step(model, mesh, rules, loss_impl="chunked",
                               loss_chunk=2048, fsdp=plan,
                               accum_steps=grad_accum)
        lowered = step.jitted.lower(state_args, batch_args)
    compiled = lowered.compile()

    n_params = cfg.num_params
    report = _mem_report(compiled)
    report.update({
        "model": "llama3_8b",
        "num_params": n_params,
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "num_devices": mesh.devices.size,
        "global_batch": batch,
        "seq_len": seq,
        "remat": cfg.remat_policy,
        "loss_impl": "chunked",
        "optimizer": "adamw(mu=bf16)",
        # Analytic floor for sanity: fp32 params + bf16 mu + fp32 nu,
        # sharded over every mesh axis the param rules use.
        "analytic_state_gib": round(
            n_params * (4 + 2 + 4) / mesh.devices.size / GIB, 2),
        # State-layout accounting from the ACTUAL shardings (the same
        # arithmetic the trainer's tpk_train_*_bytes_per_chip gauges
        # report): what one chip holds of params / optimizer state.
        "param_bytes_per_chip": tree_bytes_per_device(state_args.params),
        "opt_state_bytes_per_chip": tree_bytes_per_device(
            state_args.opt_state),
    })
    if fsdp_runtime:
        report.update({
            "fsdp_runtime": True,
            "param_dtype": param_dtype or "master",
            "grad_accum": grad_accum,
            # What pure-DP replication would pin on EVERY chip (fp32
            # params + bf16 mu + fp32 nu) — the number the fsdp axis
            # divides; the measured per-chip fields above are the
            # divided reality.
            "analytic_state_replicated_gib": round(
                n_params * (4 + 2 + 4) / GIB, 2),
        })
    return report


def _case_train_8b_v5p8() -> dict:
    return _train_case(dict(data=1, fsdp=4, tensor=2), batch=8, seq=4096)


def _case_train_8b_v5p8_long() -> dict:
    return _train_case(dict(data=1, fsdp=4, tensor=2), batch=8, seq=8192)


def _case_train_8b_v5p8_fsdp() -> dict:
    """ISSUE 15 tentpole row: the same v5p-8 bench point as
    train_8b_v5p8, but through the fsdp master-state runtime — fp32
    params + Adam moments sharded over fsdp on EVERY leaf, bf16 gathered
    compute copies, grad_accum=2 decoupling global batch from per-chip
    activation memory. The delta against train_8b_v5p8 is the
    optimizer-state unlock PROFILE §4 names."""
    return _train_case(dict(data=1, fsdp=4, tensor=2), batch=8, seq=4096,
                       fsdp_runtime=True, param_dtype="bfloat16",
                       grad_accum=2)


def _case_train_8b_v5p32_2slice() -> dict:
    return _train_case(dict(data=2, fsdp=16, num_slices=2),
                       batch=32, seq=8192)


def _case_serve_8b_tp8() -> dict:
    """Serving envelope: bf16 8B weights tensor-sharded 8-way. Compiles
    the GENERATION ENGINE'S OWN functions (serve/generation.py
    build_engine_fns — the exact prefill/chunked-decode programs the
    product dispatches, not hand-written stand-ins) with the same
    shardings `GenerationEngine(mesh=...)` installs, and asserts the
    working set fits one v5p chip's HBM share. This is the proof that TP
    serving of the flagship — which an 8B bf16 model *requires*, not
    fitting one chip — compiles and fits as the product would run it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    from kubeflow_tpu.models.llama import Llama, init_cache, llama3_8b
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec
    from kubeflow_tpu.serve.generation import build_engine_fns
    from jax.sharding import NamedSharding, PartitionSpec as P

    # remat off: inference has no backward, and the remat wrapper's static
    # argnums don't admit a traced cache anyway.
    cfg = dataclasses.replace(llama3_8b(), param_dtype=jnp.bfloat16,
                              remat=False)
    model = Llama(cfg)
    mesh = build_mesh(MeshConfig(data=1, tensor=8))
    rules = DEFAULT_RULES

    slots, max_len, prefill_bucket, chunk = 8, 8192, 2048, 16

    with mesh, nn.logical_axis_rules(rules):
        abstract = jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.key(0))
        specs = nn.get_partition_spec(abstract)
        shardings = nn.logical_to_mesh_sharding(specs, mesh, rules)
        params_args = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            nn.meta.unbox(abstract), shardings)

        # KV heads shard over tensor — the same spec GenerationEngine
        # derives (generation.py _shard_params).
        cache_sh = NamedSharding(
            mesh, logical_to_spec(("layers", None, None, "heads", "kv"),
                                  rules))
        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, slots, max_len))
        cache_args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=cache_sh), cache_shape)
        repl = NamedSharding(mesh, P())

        fns = build_engine_fns(
            model, cfg, max_len=max_len, chunk=chunk,
            prefill_buckets=(prefill_bucket,),
            offset_writes=True, cache_sharding=cache_sh)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

        key_arg = jax.eval_shape(lambda: jax.random.key(0))
        # Engine prefill: slot-batch-1 fragment, on-device sampling.
        pre_lowered = jax.jit(fns["prefill"]).lower(
            params_args, sds((1, prefill_bucket), jnp.int32),
            sds((1,), jnp.int32), sds((1,), jnp.float32),
            sds((1,), jnp.int32), sds((1,), jnp.float32), key_arg)
        # Engine chunked decode: `chunk` steps over the full slot batch
        # under one dispatch (the steady-state hot program).
        dec_lowered = jax.jit(fns["make_decode"](False, max_len),
                              donate_argnums=(1,)).lower(
            params_args, cache_args, sds((slots,), jnp.int32),
            sds((slots,), jnp.int32), sds((slots,), jnp.float32),
            sds((slots,), jnp.int32), sds((slots,), jnp.float32), key_arg)
    pre = _mem_report(pre_lowered.compile())
    dec = _mem_report(dec_lowered.compile())
    return {
        "model": "llama3_8b",
        "weights": "bf16",
        "mesh": {"tensor": 8},
        "num_devices": 8,
        "slots": slots,
        "max_len": max_len,
        "prefill_bucket": prefill_bucket,
        "decode_chunk": chunk,
        "engine_fns": "serve/generation.py build_engine_fns",
        "prefill": pre,
        "decode": dec,
        "fits_v5p_hbm": pre["fits_v5p_hbm"] and dec["fits_v5p_hbm"],
    }


def run_case(name: str) -> dict:
    fn = globals()[f"_case_{name}"]
    return fn()


def run_case_subprocess(name: str, timeout_s: float = 1800.0) -> dict:
    """Re-exec with the CPU platform and the case's virtual device count
    (backends can't be reconfigured after init — same constraint as
    __graft_entry__.dryrun_multichip)."""
    from kubeflow_tpu.utils.reexec import cpu_reexec_env

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = cpu_reexec_env(_CASE_DEVICES[name], repo=repo)
    code = (
        "import json, sys\n"
        "from kubeflow_tpu.utils import scaleproof\n"
        f"r = scaleproof.run_case({name!r})\n"
        "print('SCALEPROOF_JSON:' + json.dumps(r))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(
            f"scaleproof case {name} failed rc={proc.returncode}:\n"
            f"{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("SCALEPROOF_JSON:"):
            return json.loads(line[len("SCALEPROOF_JSON:"):])
    raise RuntimeError(f"scaleproof case {name}: no result line in output")


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="SCALEPROOF.json")
    parser.add_argument("--cases", nargs="*", default=list(CASES))
    parser.add_argument(
        "--merge", action="store_true",
        help="update only --cases inside an existing --out document "
             "(other rows kept verbatim; all_fit recomputed over the "
             "union) instead of rewriting it with just this run")
    args = parser.parse_args(argv)

    results, ok = {}, True
    if args.merge and os.path.exists(args.out):
        with open(args.out) as fh:
            results = dict(json.load(fh).get("cases", {}))
    for name in args.cases:
        print(f"[scaleproof] compiling {name} "
              f"({_CASE_DEVICES[name]} virtual devices)...",
              file=sys.stderr, flush=True)
        try:
            results[name] = run_case_subprocess(name)
            fit = results[name].get("fits_v5p_hbm")
            print(f"[scaleproof] {name}: fits_v5p_hbm={fit}",
                  file=sys.stderr, flush=True)
            ok = ok and bool(fit)
        except Exception as e:  # record the failure, keep proving the rest
            results[name] = {"error": str(e)}
            ok = False
            print(f"[scaleproof] {name}: ERROR {e}", file=sys.stderr)
    # all_fit covers the whole document — including rows a --merge run
    # kept verbatim — so a merge can never launder a failing row.
    ok = ok and all("error" not in r and bool(r.get("fits_v5p_hbm"))
                    for r in results.values())
    payload = {
        "contract": "Llama-3-8B fine-tune via JAXJob on v5p (BASELINE.json)",
        "method": "AOT jit().lower().compile() + memory_analysis() on "
                  "virtual CPU device meshes with production shardings",
        "hbm_budget_gib": round(V5P_HBM_BYTES / GIB, 2),
        "all_fit": ok,
        "cases": results,
    }
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(json.dumps({"scaleproof": {"all_fit": ok, "out": args.out}}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
