"""Headline benchmark: Llama-class causal-LM training throughput on TPU.

Prints ONE JSON line:
  {"metric": "tokens_per_sec_per_chip", "value": N, "unit": "tok/s/chip",
   "vs_baseline": MFU/0.45, ...}

The reference publishes no numbers (BASELINE.md: published={}), so
vs_baseline is measured MFU against the north-star 45% MFU target for
Llama-8B-class fine-tuning. Runs on whatever TPU chips are present and
fails without one: every mode that times something on the device calls
`_device()` first, and no TPU is a message and a non-zero exit, never a
skip record or a relabelled CPU run. The host-only modes (--ctrlbench,
--routerbench, --disaggbench, --chaosbench, --trainchaos) measure host
code on the CPU and say so in their artifacts.

Steps are timed *pipelined* — chained through the donated TrainState
with one device fetch closing the clock — because a host sync per step
stalls the dispatch queue and charges the stall to the step.
"""

from __future__ import annotations

import json
import sys
import time


def _device(who: str) -> dict:
    """The one in-process device check for every mode that times the
    chip: compile cache on, then platform must be `tpu` (SystemExit
    otherwise). Returns {"platform", "kind", "count"}."""
    from kubeflow_tpu.utils import devices

    devices.enable_compile_cache()
    return devices.require_tpu(f"bench.py {who}".rstrip())


def train_input_ab(step, state, mesh, vocab_size: int, batch: int,
                   seq: int, steps: int = 8, warmup: int = 2,
                   depth: int = 2, corpus_tokens: int | None = None):
    """Sync-vs-prefetch input-pipeline A/B for the training hot path
    (ISSUE 4). One seeded packed-corpus grain stream feeds both arms:
    arm "sync" is `Prefetcher` depth 0 (pull + packed-row assembly + H2D
    inline between dispatches — the pre-prefetch trainer loop), arm
    "prefetch" is depth `depth` (the same host work + device placement
    on the worker thread, overlapping device compute). Each arm's clock
    closes on a single final `float(loss)`, so work still queued on the
    device cannot flatter either arm.
    Returns (state, section) — state rides through both arms' steps.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubeflow_tpu.data.loader import packed_lm_dataset
    from kubeflow_tpu.data.prefetch import Prefetcher

    eos = 1
    rng = np.random.default_rng(0)
    need = corpus_tokens or (warmup + steps + 2) * batch * (seq + 1) * 2
    docs = []
    total = 0
    while total < need:
        d = np.append(rng.integers(2, vocab_size, rng.integers(
            16, max(seq // 2, 17)), dtype=np.int32), eos)
        docs.append(d)
        total += len(d)
    corpus = np.concatenate(docs).astype(np.int32)

    dp = mesh.shape["data"] * mesh.shape["fsdp"]

    def place(b):
        def conv(x):
            x = np.asarray(x)
            # dp sharding when the batch divides; replicated otherwise
            # (the step reshards, same as the numpy path).
            spec = (P(("data", "fsdp"), *([None] * (x.ndim - 1)))
                    if x.ndim and x.shape[0] % dp == 0 else P())
            return jax.device_put(x, NamedSharding(mesh, spec))
        return jax.tree.map(conv, b)

    section = {
        "method": ("identical seeded packed-corpus stream; fetch-synced "
                   "(single final float(loss)); sync = "
                   "prefetch depth 0 (inline pull+pack+H2D), prefetch = "
                   f"depth {depth} (worker thread stages device-resident "
                   "batches)"),
        "batch": batch, "seq_len": seq, "timed_steps": steps,
    }
    for label, d in (("sync", 0), (f"prefetch_depth{depth}", depth)):
        ds = packed_lm_dataset(corpus, batch_size=batch, seq_len=seq,
                               eos_id=eos, seed=0, process_index=0,
                               process_count=1)
        pf = Prefetcher(iter(ds), depth=d, place=place)
        try:
            if warmup:
                for _ in range(warmup):
                    state, metrics = step(state, next(pf))
                float(metrics["loss"])  # drain before opening the clock
            wait0, h2d0 = pf.data_wait_s, pf.h2d_s
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, next(pf))
            final = float(metrics["loss"])  # closes the clock honestly
            wall = time.perf_counter() - t0
        finally:
            pf.close()
        section[label] = {
            "ms_per_step": round(wall / steps * 1e3, 2),
            "tok_s": round(batch * seq * steps / wall, 1),
            "data_wait_s": round(pf.data_wait_s - wait0, 4),
            "h2d_s": round(pf.h2d_s - h2d0, 4),
            "final_loss": round(final, 4),
        }
    sync_ms = section["sync"]["ms_per_step"]
    pre_ms = section[f"prefetch_depth{depth}"]["ms_per_step"]
    if pre_ms > 0:
        section["speedup"] = round(sync_ms / pre_ms, 4)
    return state, section


def main() -> None:
    device = _device("")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.models.llama import Llama, llama_1b
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
    from kubeflow_tpu.train.metrics import peak_flops_per_chip
    from kubeflow_tpu.train.step import init_train_state, make_train_step

    # 0.9B-param bench model: flagship topology (GQA/RoPE/SwiGLU/scan,
    # head_dim 128) at the largest size that fits one v5e with Adam
    # state. Full-block remat; bf16 Adam first moment buys batch 12
    # (PROFILE.md has the sweep).
    cfg = llama_1b()
    batch, seq = 12, 1024

    n_chips = jax.device_count()
    mesh = build_mesh(MeshConfig(), jax.devices())
    model = Llama(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    state = init_train_state(
        model, tx, jax.random.key(0), (tokens,), mesh, DEFAULT_RULES)
    step = make_train_step(model, mesh, DEFAULT_RULES)

    rng = np.random.default_rng(0)
    def make_batch():
        return {
            "inputs": rng.integers(0, cfg.vocab_size, (batch, seq),
                                   dtype=np.int32),
            "targets": rng.integers(0, cfg.vocab_size, (batch, seq),
                                    dtype=np.int32),
        }

    # Warmup: compile + 2 steady-state steps (each synced; excluded
    # from the measurement).
    for i in range(3):
        state, metrics = step(state, make_batch())
        loss = float(metrics["loss"])
        print(f"warmup {i}: loss={loss:.3f}", file=sys.stderr)

    # Timed: chained steps, one fetch at the end. Each step consumes the
    # previous step's state (donated), so the device executes them
    # back-to-back; dividing wall time by N gives true per-step time.
    timed = 10
    batches = [make_batch() for _ in range(timed)]
    t0 = time.perf_counter()
    for b in batches:
        state, metrics = step(state, b)
    final_loss = float(metrics["loss"])  # forces completion of the chain
    dt = (time.perf_counter() - t0) / timed
    print(f"timed {timed} steps: {dt*1e3:.1f} ms/step "
          f"loss={final_loss:.3f}", file=sys.stderr)

    model_flops = 6 * cfg.num_params * batch * seq
    mfu = model_flops / dt / (peak_flops_per_chip() * n_chips)
    result = {
        "metric": "tokens_per_sec_per_chip",
        "value": round(batch * seq / dt / n_chips, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "model_params": cfg.num_params,
        "chips": n_chips,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "peak_flops_per_chip": peak_flops_per_chip(),
        "batch": batch,
        "seq_len": seq,
        "avg_step_time_s": round(dt, 4),
    }
    # Input-pipeline A/B (ISSUE 4): same chip, packed-corpus stream fed
    # synchronously vs through the depth-2 device prefetcher. A failure
    # here keeps the headline line (chip time paid for it) but fails the
    # run.
    failed = False
    try:
        _, result["sync_vs_prefetch"] = train_input_ab(
            step, state, mesh, cfg.vocab_size, batch, seq)
    except Exception as e:
        result["sync_vs_prefetch"] = {"error": _clean_err(e)}
        failed = True
    print(json.dumps(result))
    if failed:
        raise SystemExit("bench.py: sync_vs_prefetch failed: "
                         + result["sync_vs_prefetch"]["error"])


def main_serve() -> None:
    """`python bench.py --serve`: serving benchmark → SERVEBENCH.json +
    one JSON line on stdout (kubeflow_tpu/serve/bench.py)."""
    device = _device("--serve")

    from kubeflow_tpu.serve.bench import run_servebench

    result = run_servebench()
    result["platform"] = device["platform"]
    result["device_count"] = device["count"]
    with open("SERVEBENCH.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({
        "metric": "serve_decode_tok_s",
        "value": result["decode"][
            f"slots_{max(int(k.split('_')[1]) for k in result['decode'])}"][
                "decode_tok_s"],
        "unit": "tok/s",
        "platform": result["platform"],
        "device_kind": device["kind"],
        "detail": "SERVEBENCH.json",
    }))


def _clean_err(e: Exception) -> str:
    """One readable line for a failed case: ANSI escapes stripped
    (compiler errors carry coloured log lines), whitespace folded,
    bounded."""
    import re
    txt = re.sub(r"\x1b\[[0-9;]*m", "", f"{type(e).__name__}: {e}")
    return " ".join(txt.split())[:300]


def main_ctrlbench() -> None:
    """`python bench.py --ctrlbench`: control-plane group-commit benchmark
    → CTRLBENCH.json + one JSON line (kubeflow_tpu/controlplane/bench.py).

    Pure host-side (real tpk-controlplane binary over its unix socket) —
    no device involved. The headline is the `--fsync always` submit-rps pair:
    group commit ON amortizes one covering fsync over every mutation of
    an event-loop pass; OFF pays one fsync per mutation (ISSUE 8)."""
    from kubeflow_tpu.controlplane.bench import run_ctrlbench

    result = run_ctrlbench(quick="--quick" in sys.argv)
    with open("CTRLBENCH.json", "w") as fh:
        json.dump(result, fh, indent=1)
    if result.get("skipped"):
        print(json.dumps({"metric": "ctrlbench_submit_rps_always",
                          "value": None, "unit": "rps",
                          "skipped": result["skipped"],
                          "detail": result.get("detail", ""),
                          "artifact": "CTRLBENCH.json"}))
        return
    always = result["group_commit"]["always"]
    repl = result.get("replicated", {})
    print(json.dumps({
        "metric": "ctrlbench_submit_rps_always",
        "value": always["on"]["submit_rps"],
        "unit": "rps",
        "group_commit_off_rps": always["off"]["submit_rps"],
        "speedup": always["speedup_submit"],
        "clients": result["clients"],
        "coalesced_events": result["watch_fanout"]["coalesced_events"],
        # The replicated arm (ISSUE 11): quorum-acked rps vs single node
        # (< 1 by design — the price of ack-after-quorum) plus the
        # horizontal read surface followers add.
        "replicated_submit_rps": repl.get("replicated",
                                          {}).get("submit_rps"),
        "replicated_vs_single": repl.get(
            "rps_ratio_replicated_vs_single"),
        "quorum_commits": repl.get("quorum_commits"),
        "follower_get_rps": repl.get("follower_get_rps"),
        "detail": "CTRLBENCH.json",
    }))


def main_routerbench() -> None:
    """`python bench.py --routerbench`: multi-replica serving-fabric
    benchmark → ROUTERBENCH.json + one JSON line
    (kubeflow_tpu/serve/loadgen.py).

    Pure host-side: an open-loop Poisson load harness over FAKE
    slot-limited replicas behind real ModelServers and the real router —
    measures the router (proxy overhead bound, 1→4 horizontal scaling,
    prefix-affinity hit-rate vs the hash-off control), not model decode.
    No device involved; runs on any box."""
    from kubeflow_tpu.serve.loadgen import run_routerbench

    result = run_routerbench(quick="--quick" in sys.argv)
    with open("ROUTERBENCH.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({
        "metric": "routerbench_scaling_x",
        "value": result["scaling_x"],
        "unit": "x_1_replica_goodput",
        "routed_overhead_p50": result.get("routed_overhead_p50"),
        "affinity_hit_rate_on": result["affinity"]["hit_rate_on"],
        "affinity_hit_rate_off": result["affinity"]["hit_rate_off"],
        "detail": "ROUTERBENCH.json",
    }))


def main_disaggbench() -> None:
    """`python bench.py --disaggbench`: disaggregated-prefill/decode
    vs unified fleet A/B → DISAGGBENCH.json + one JSON line
    (kubeflow_tpu/serve/disaggbench.py).

    REAL tiny engines on CPU behind real ModelServers and the real
    router, equal engines per arm, open-loop Poisson mixed
    long-prompt/short-decode traffic; records goodput, p50/p99 TTFT,
    decode-tail p99 and the wire-format mechanism counters. Host code
    measured on the CPU; it says nothing about the chip."""
    from kubeflow_tpu.serve.disaggbench import run_disaggbench

    result = run_disaggbench(quick="--quick" in sys.argv)
    with open("DISAGGBENCH.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({
        "metric": "disaggbench_ttft_p99_ratio",
        "value": result.get("ttft_p99_ratio"),
        "unit": "disagg_over_unified",
        "goodput_ratio": result.get("goodput_ratio"),
        "decode_tail_p99_ratio": result.get("decode_tail_p99_ratio"),
        "detail": "DISAGGBENCH.json",
    }))


def main_chaosbench() -> None:
    """`python bench.py --chaosbench`: fabric chaos harness →
    CHAOSBENCH.json + one JSON line (kubeflow_tpu/serve/chaosbench.py).

    REAL tiny-engine replicas in their own subprocesses behind the real
    router under open-loop Poisson load, while a seeded fault schedule
    SIGKILLs, SIGSTOP/CONT-stalls, and drains replicas mid-run — the
    disagg mid-stream resume, gray-failure ejection vs control, and
    replicated-control-plane leader-kill claims, computed from
    per-request provenance rows."""
    from kubeflow_tpu.serve.chaosbench import run_chaosbench

    result = run_chaosbench(quick="--quick" in sys.argv)
    with open("CHAOSBENCH.json", "w") as fh:
        json.dump(result, fh, indent=1)
    disagg = result["arms"]["disagg_decode_kill"]
    gray = result["arms"]["gray_stall"]
    print(json.dumps({
        "metric": "chaosbench_disagg_caller_visible_errors",
        "value": disagg.get("caller_visible_errors"),
        "resumes": disagg.get("resumes"),
        "goodput_recovery_ratio": disagg.get("goodput_recovery_ratio"),
        "gray_p99_ratio_on_vs_off": gray.get("p99_ratio_on_vs_off"),
        "detail": "CHAOSBENCH.json",
    }))


def main_trainchaos() -> None:
    """`python bench.py --trainchaos`: train-plane chaos harness →
    TRAINCHAOS.json + one JSON line (kubeflow_tpu/train/trainchaos.py).

    REAL trainer workers launched by the REAL tpk-controlplane binary
    under a seeded SIGKILL/SIGSTOP schedule: fault-free control vs
    unattended elastic 4 -> 2 resize vs restart-from-scratch, goodput
    (useful steps/wall-second) per arm, plus the mechanism claims —
    resize event chain observed, zero lost acked checkpoints."""
    from kubeflow_tpu.controlplane.client import find_binary
    from kubeflow_tpu.train.trainchaos import run_trainchaos

    find_binary()  # fail fast with the build hint, not mid-bench
    result = run_trainchaos(quick="--quick" in sys.argv)
    with open("TRAINCHAOS.json", "w") as fh:
        json.dump(result, fh, indent=1)
    claims = result["claims"]
    print(json.dumps({
        "metric": "trainchaos_goodput_elastic_over_restart",
        "value": claims["goodput_elastic_over_restart"],
        "unit": "x_restart_from_scratch_goodput",
        "zero_lost_acked_checkpoints":
            claims["zero_lost_acked_checkpoints"],
        "resize_event_observed": claims["resize_event_observed"],
        "detail": "TRAINCHAOS.json",
    }))


def main_trainfsdp() -> None:
    """`python bench.py --train-fsdp`: sharded-training A/B →
    TRAINBENCH.json + one JSON line (kubeflow_tpu/train/fsdpbench.py).

    Real init/step arms (ISSUE 15): replicated vs fsdp master layout
    equivalence, grad-accum equivalence, bf16-gather delta, and the
    per-chip state-bytes arithmetic, at the shard degree the host's
    chips allow."""
    device = _device("--train-fsdp")

    from kubeflow_tpu.train.fsdpbench import run_trainbench

    result = run_trainbench(quick="--quick" in sys.argv)
    result["platform"] = device["platform"]
    result["device_kind"] = device["kind"]
    result["device_count"] = device["count"]
    with open("TRAINBENCH.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({
        "metric": "trainbench_opt_state_ratio",
        "value": result["memory"]["opt_state_ratio_replicated_over_fsdp"],
        "unit": "x_replicated_bytes_per_chip",
        "fsdp_vs_replicated_max_rel_delta": result["equivalence"][
            "fsdp_vs_replicated_max_rel_delta"],
        "platform": result["platform"],
        "detail": "TRAINBENCH.json",
    }))


def main_longctx() -> None:
    """`python bench.py --longctx`: the long-context evidence row —
    measured tok/s + MFU at s>=2048 (chunked CE, the config full-CE
    cannot admit). A case that fails is recorded and fails the run."""
    device = _device("--longctx")
    from kubeflow_tpu.utils import longctx

    result: dict = {"metric": "longctx", "platform": device["platform"],
                    "device_kind": device["kind"],
                    "device_count": device["count"], "cases": []}
    for b, s in ((1, 2048), (2, 2048), (1, 3072), (1, 4096)):
        try:
            result["cases"].append(longctx.measure(b, s))
        except Exception as e:
            result["cases"].append(
                {"batch": b, "seq_len": s, "error": _clean_err(e)})
        print(f"longctx case b{b} s{s}: {result['cases'][-1]}",
              file=sys.stderr, flush=True)
    with open("LONGCTX.json", "w") as fh:
        json.dump(result, fh, indent=1)
    failed = [c for c in result["cases"] if "error" in c]
    print(json.dumps({"metric": "longctx", "platform": result["platform"],
                      "cases": len(result["cases"]),
                      "failed": len(failed), "detail": "LONGCTX.json"}))
    if failed:
        raise SystemExit(f"bench.py --longctx: {len(failed)} case(s) "
                         "failed; see LONGCTX.json")


def main_8bshape() -> None:
    """`python bench.py --8bshape`: the measured 8B-shape proxy
    (SCALEPROOF.json's 8B evidence is fit arithmetic, not measurement).
    Times the PRODUCTION train step on a 2-layer trunk at exact llama3_8b
    widths (hidden 4096, inter 14336, heads 32/8, head_dim 128, vocab
    128256) — the matmul shapes an 8B step is made of, runnable on one
    v5e. MFU counts matmul params only (the input embedding is a gather
    — at 2 layers it would inflate the number ~1.4x); writes
    PROXY8B.json."""
    device = _device("--8bshape")

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.models.llama import Llama, llama3_8b
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.parallel.sharding import DEFAULT_RULES
    from kubeflow_tpu.train.metrics import peak_flops_per_chip
    from kubeflow_tpu.train.step import init_train_state, make_train_step

    cfg = dataclasses.replace(llama3_8b(), num_layers=2)
    batch, seq = 1, 2048
    mesh = build_mesh(MeshConfig(), jax.devices())
    model = Llama(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    state = init_train_state(
        model, tx, jax.random.key(0), (tokens,), mesh, DEFAULT_RULES)
    step = make_train_step(model, mesh, DEFAULT_RULES,
                           loss_impl="chunked", loss_chunk=512)

    rng = np.random.default_rng(0)

    def make_batch():
        return {
            "inputs": rng.integers(0, cfg.vocab_size, (batch, seq),
                                   dtype=np.int32),
            "targets": rng.integers(0, cfg.vocab_size, (batch, seq),
                                    dtype=np.int32),
        }

    for i in range(3):
        state, metrics = step(state, make_batch())
        print(f"proxy8b warmup {i}: loss={float(metrics['loss']):.3f}",
              file=sys.stderr)
    timed = 8
    batches = [make_batch() for _ in range(timed)]
    t0 = time.perf_counter()
    for b in batches:
        state, metrics = step(state, b)
    final = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / timed
    n_chips = jax.device_count()
    # Matmul params only: the input embedding is a gather, no MXU FLOPs
    # — at full depth it's noise, at 2 layers it's ~35% of num_params.
    flop_params = cfg.num_params - cfg.vocab_size * cfg.hidden_size
    mfu = (6 * flop_params * batch * seq / dt
           / (peak_flops_per_chip() * n_chips))
    result = {
        "metric": "proxy8b_mfu",
        "value": round(mfu, 4),
        "unit": "mfu",
        "vs_baseline": round(mfu / 0.45, 4),
        "note": ("2-layer trunk at exact llama3_8b widths; the MFU the "
                 "8B model's own matmul shapes run at on this chip — "
                 "the measured companion to SCALEPROOF.json's "
                 "fit-arithmetic"),
        "widths": {"hidden": cfg.hidden_size,
                   "intermediate": cfg.intermediate_size,
                   "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
                   "head_dim": cfg.head_dim, "vocab": cfg.vocab_size},
        "layers": cfg.num_layers,
        "batch": batch,
        "seq_len": seq,
        "params": cfg.num_params,
        "flop_params": flop_params,
        "avg_step_time_s": round(dt, 4),
        "tokens_per_sec": round(batch * seq / dt, 1),
        "chips": n_chips,
        "final_loss": round(final, 3),
        "platform": device["platform"],
        "device_kind": device["kind"],
    }
    with open("PROXY8B.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))


def main_longctx_tune() -> None:
    """`python bench.py --longctx-tune [seq [batch]]`: sweep the
    long-context knobs (remat policy / CE chunk / flash blocks) at one
    point on the chip and write LONGCTX_TUNE.json best-first. A variant
    that fails is recorded and fails the run."""
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    seq = int(args[0]) if args else 3072
    batch = int(args[1]) if len(args) > 1 else 1
    device = _device("--longctx-tune")
    from kubeflow_tpu.utils import longctx

    rows = longctx.tune_point(batch, seq)
    out = {"metric": "longctx_tune", "platform": device["platform"],
           "device_kind": device["kind"], "batch": batch, "seq_len": seq,
           "rows": rows}
    with open("LONGCTX_TUNE.json", "w") as fh:
        json.dump(out, fh, indent=1)
    best = next((r for r in rows if "mfu" in r), None)
    print(json.dumps({"metric": "longctx_tune", "seq_len": seq,
                      "best_mfu": best and best["mfu"],
                      "best_knobs": best and {
                          k: best[k] for k in ("remat_policy", "loss_chunk",
                                               "flash_block")},
                      "detail": "LONGCTX_TUNE.json"}))
    failed = sum("error" in r for r in rows)
    if failed:
        raise SystemExit(f"bench.py --longctx-tune: {failed} variant(s) "
                         "failed; see LONGCTX_TUNE.json")


if __name__ == "__main__":
    if "--ctrlbench" in sys.argv:
        main_ctrlbench()
    elif "--routerbench" in sys.argv:
        main_routerbench()
    elif "--disaggbench" in sys.argv:
        main_disaggbench()
    elif "--chaosbench" in sys.argv:
        main_chaosbench()
    elif "--trainchaos" in sys.argv:
        main_trainchaos()
    elif "--serve" in sys.argv:
        main_serve()
    elif "--train-fsdp" in sys.argv:
        main_trainfsdp()
    elif "--longctx-tune" in sys.argv:
        main_longctx_tune()
    elif "--longctx" in sys.argv:
        main_longctx()
    elif "--8bshape" in sys.argv:
        main_8bshape()
    else:
        main()
