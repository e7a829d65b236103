"""The training runtime: what a JAXJob worker process actually runs.

The reference's equivalent is user-image code launched by torchrun with env
injected by the operator (SURVEY.md §3.1) — the platform owns nothing inside
the pod. Here the runtime is first-class: mesh + sharding rules from the job
spec, jitted SPMD step, metrics/MFU stream, orbax checkpoint/auto-resume,
and an optional `jax.profiler` trace window (§5.1).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kubeflow_tpu.comms.bootstrap import ProcessEnv, initialize, read_env
from kubeflow_tpu.data.prefetch import Prefetcher
from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
from kubeflow_tpu.parallel.sharding import rules_for
from kubeflow_tpu.train.checkpoint import CheckpointManager
from kubeflow_tpu.train.metrics import MetricsLogger, StepTimer
from kubeflow_tpu.train.step import init_train_state, make_train_step
from kubeflow_tpu.utils import devices, faults, obs, resilience

#: Fires at the top of every training step (ctx: step) — arming FailN
#: with match={"step": K} is the in-process analog of the controller's
#: TPK_FAULT step-precise process kill.
_FP_STEP = faults.register_point(
    "train.step", "top of each training step; ctx: step")


@dataclasses.dataclass
class TrainJobSpec:
    """Declarative training job — the in-process analog of a JAXJob CR's
    `spec.runtime` section. Controllers serialize this as JSON."""

    model: str = "llama_tiny"
    model_kwargs: dict = dataclasses.field(default_factory=dict)
    dataset: str = "synthetic_lm"
    dataset_kwargs: dict = dataclasses.field(default_factory=dict)
    strategy: str = "hybrid"  # preset name resolved by rules_for()
    mesh: dict = dataclasses.field(default_factory=dict)  # MeshConfig fields
    steps: int = 100
    batch_size: int = 8
    seq_len: int = 64
    learning_rate: float = 1e-3
    warmup_steps: int = 0
    weight_decay: float = 0.0
    # Peak-LR decay after warmup: "constant" | "cosine" | "linear", decaying
    # to lr_final over the remaining spec.steps (the reference SDK's HF
    # trainer exposes the same three families).
    lr_schedule: str = "constant"
    lr_final: float = 0.0
    # 0 disables clipping; > 0 wires optax.clip_by_global_norm ahead of
    # adamw (the reported grad_norm metric stays pre-clip).
    max_grad_norm: float = 0.0
    # > 1 splits each global batch into accum_steps microbatches scanned
    # inside the jitted step, averaging grads — same optimizer math at
    # 1/accum_steps the activation memory.
    accum_steps: int = 1
    # Canonical name for the same knob (the reference SDK's
    # gradient_accumulation_steps): 0 defers to accum_steps; setting both
    # to different values is refused. fp32 accumulator, ordered adds —
    # grad_accum=K on batch B reproduces K=1 on batch B (test-pinned).
    grad_accum: int = 0
    # FSDP master-state sharding (parallel/fsdp.py): 0 = off (today's
    # rules-only layout); N >= 1 shards fp32 master params + both Adam
    # moments N-way over the `fsdp` mesh axis on every state leaf,
    # filling mesh.fsdp = N when the mesh doesn't set it. Checkpoints
    # stay topology-portable: save on N-way, restore on M-way.
    fsdp: int = 0
    # Compute dtype of the gathered per-use param copies when fsdp >= 1:
    # null keeps the master dtype (bit-exact escape hatch); "bfloat16"
    # halves all-gather bytes and compute-copy memory. The master state
    # and the grad accumulator stay fp32 either way.
    param_dtype: str | None = None
    seed: int = 0
    # False | True/"ring" (contiguous ring CP) | "ring_flash" (fused Pallas
    # inner block) | "zigzag"/"zigzag_flash" (balanced causal schedule: the
    # trainer permutes batches + positions to match; _flash = fused inner).
    ring_attention: bool | str = False
    # "full" materializes [B,S,V] logits; "chunked" is the fused blockwise
    # CE (no logits buffer — the long-context/large-vocab memory saver):
    # per loss_chunk tokens the logits once, and from them the chunk's
    # loss and its gradients, so the head is three matmul passes a step.
    loss_impl: str = "full"
    loss_chunk: int = 1024
    # Pipeline parallelism: set mesh.pipe >= 2 and optionally
    # {"microbatches": M (default: pipe), "chunks": C (default 1; >1 runs
    # the interleaved circular schedule)}. The trunk runs the compiled
    # GPipe/circular schedule (models/llama_pp.py); params keep the
    # scanned layout, sharded over `pipe` via the "pipeline" rules.
    pipeline: dict = dataclasses.field(default_factory=dict)
    # LoRA fine-tuning (the reference SDK's PEFT LoraConfig):
    # {"rank": r, "alpha": a (default 16), "targets": "attn"|"attn_mlp"}.
    # Adapters are trained, the base is frozen (no base grads or optimizer
    # state); merge for serving via train/lora.py merge().
    lora: dict = dataclasses.field(default_factory=dict)
    checkpoint: dict = dataclasses.field(default_factory=dict)
    # {"dir": str, "interval": int, "keep": int}
    # In-process supervision (training-operator restartPolicy/backoffLimit
    # semantics, SURVEY.md §3.2): "Never" propagates the first failure;
    # "OnFailure" restarts immediately; "ExponentialBackoff" restarts
    # with jittered exponential delays. Each restart re-enters the run
    # loop through the checkpoint auto-resume path (latest step + saved
    # data-iterator state), so a mid-run failure costs at most one
    # checkpoint interval of recompute. backoff_limit counts RESTARTS:
    # the (backoff_limit+1)-th failure raises BackoffLimitExceeded.
    restart_policy: str = "Never"
    backoff_limit: int = 3
    # Async input pipeline depth: the trainer stages up to `prefetch`
    # device-resident batches ahead of compute on a background thread
    # (pull + zigzag permute + H2D placement all off the critical path —
    # data/prefetch.py). 0 = fully synchronous; every depth trains the
    # identical batch sequence with identical numerics, and checkpoints
    # under prefetch save the state of the batch actually trained, not
    # the read-ahead position.
    prefetch: int = 2
    metrics_path: str | None = None
    profile: dict = dataclasses.field(default_factory=dict)
    # {"dir": str, "start_step": int, "num_steps": int}
    # Flat jax.profiler window keyed off the job spec (SURVEY.md §5.1
    # rebuild item): steps [profile_start_step, profile_stop_step) run
    # under jax.profiler.start_trace/stop_trace, writing to
    # $TPK_WORKDIR/profile (the job's workdir under the control plane)
    # — or next to metrics_path, or ./tpk-profile — unless profile.dir
    # overrides. stop <= start disables (the default). The dict-style
    # `profile` knob wins when both are set.
    profile_start_step: int = 0
    profile_stop_step: int = 0
    log_every: int = 10
    # In-run validation stream: every eval_every steps (0 = off), run
    # eval_batches batches of eval_dataset (default: the train dataset with
    # a disjoint seed) through make_eval_step and log eval_loss/accuracy.
    eval_dataset: str | None = None
    eval_dataset_kwargs: dict = dataclasses.field(default_factory=dict)
    eval_every: int = 0
    eval_batches: int = 8

    @classmethod
    def from_json(cls, text: str) -> "TrainJobSpec":
        data = json.loads(text)
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(data) - fields
        if unknown:
            raise ValueError(f"unknown TrainJobSpec fields: {sorted(unknown)}")
        return cls(**data)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


class Trainer:
    def __init__(self, spec: TrainJobSpec, penv: ProcessEnv | None = None):
        self.spec = spec
        self.penv = penv or read_env()
        initialize(self.penv)

        from kubeflow_tpu.utils import registry

        valid_ring = (False, True, "ring", "ring_flash", "zigzag",
                      "zigzag_flash")
        if spec.ring_attention not in valid_ring:
            raise ValueError(
                f"ring_attention {spec.ring_attention!r}: one of "
                f"{valid_ring}")
        model_kwargs = dict(spec.model_kwargs)
        if spec.ring_attention in ("zigzag", "zigzag_flash", "ring_flash"):
            # Keep the kernel and the data contract in lockstep: the spec
            # is the single switch, the model impl follows. Derived locally
            # — the caller's spec must stay as submitted (it gets
            # re-serialized for resume/retry).
            model_kwargs["attention_impl"] = spec.ring_attention
        mesh_fields = dict(spec.mesh)
        mesh_fields.setdefault("num_slices", self.penv.num_slices)
        if spec.fsdp < 0:
            raise ValueError(f"fsdp must be >= 0, got {spec.fsdp}")
        if spec.fsdp:
            declared = mesh_fields.get("fsdp")
            if declared not in (None, spec.fsdp):
                raise ValueError(
                    f"spec.fsdp={spec.fsdp} conflicts with "
                    f"mesh.fsdp={declared} — set one (fsdp is the "
                    "shorthand that fills the mesh axis)")
            mesh_fields["fsdp"] = spec.fsdp
        from kubeflow_tpu.parallel.fsdp import parse_compute_dtype

        if spec.param_dtype is not None and not spec.fsdp:
            raise ValueError(
                "param_dtype configures the fsdp runtime's gathered "
                "compute copies — set fsdp >= 1 (fsdp=1 is the "
                "single-shard escape hatch)")
        self._fsdp_dtype = parse_compute_dtype(spec.param_dtype)
        self.mesh = build_mesh(MeshConfig(**mesh_fields))
        strategy = spec.strategy
        if self.mesh.shape["pipe"] > 1:
            # pipe in the mesh IS the pipeline switch; the rules must put
            # the scanned `layers` dim on `pipe` or init would replicate
            # the trunk over the pipeline stages.
            if strategy == "hybrid":
                strategy = "pipeline"
            elif strategy != "pipeline":
                raise ValueError(
                    f"mesh.pipe={self.mesh.shape['pipe']} needs strategy "
                    f"'pipeline' (or the default), not {strategy!r}")
            if spec.ring_attention:
                # With PP, mesh.seq IS the CP switch (CP-inside-PP rides
                # the pipeline shard_map region); the scanned-model
                # ring_attention spec knob is the wrong mechanism.
                raise ValueError(
                    "pipeline parallelism doesn't take ring_attention — "
                    "set mesh.seq > 1 for context parallelism inside the "
                    "pipeline")
            if self.mesh.shape["tensor"] > 1:
                # The pipeline shard_map would silently REPLICATE the
                # trunk over this axis (full weights + redundant compute
                # on every rank) — refuse rather than quietly burn 2x the
                # provisioned HBM/FLOPs. PP composes with data/fsdp (DP
                # rows), seq (CP inside the stage region), and expert
                # (MoE-PP; checked against the model below).
                raise ValueError(
                    "pipeline parallelism doesn't compose with mesh axes "
                    "['tensor'] (PP composes with data/fsdp/seq/expert)")
            unknown = set(spec.pipeline) - {"microbatches", "chunks"}
            if unknown:
                raise ValueError(
                    f"unknown spec.pipeline keys {sorted(unknown)}; "
                    "valid: microbatches, chunks")
        elif spec.pipeline:
            raise ValueError("spec.pipeline set but mesh.pipe <= 1")
        self.rules = rules_for(strategy)
        self._pipeline = None
        if self.mesh.shape["pipe"] > 1:
            self._pipeline = {
                "microbatches": int(spec.pipeline.get(
                    "microbatches", self.mesh.shape["pipe"])),
                "chunks": int(spec.pipeline.get("chunks", 1)),
            }
            if self.mesh.shape["seq"] > 1:
                self._pipeline["seq_axis"] = "seq"
        self._trainable = None
        if spec.lora:
            unknown = set(spec.lora) - {"rank", "alpha", "targets"}
            if unknown:
                raise ValueError(
                    f"unknown spec.lora keys {sorted(unknown)}; valid: "
                    "rank, alpha, targets")
            rank = int(spec.lora.get("rank", 0))
            if rank < 1:
                raise ValueError(f"lora.rank must be >= 1, got {rank}")
            targets = spec.lora.get("targets", "attn")
            if targets not in ("attn", "attn_mlp"):
                raise ValueError(
                    f"lora.targets {targets!r}: attn | attn_mlp")
            if self._pipeline is not None:
                raise ValueError(
                    "LoRA doesn't compose with pipeline parallelism "
                    "(the stage forward has no adapter path)")
            model_kwargs["lora_rank"] = rank
            model_kwargs["lora_alpha"] = float(spec.lora.get("alpha", 16.0))
            model_kwargs["lora_targets"] = targets
            self._trainable = "lora"
        try:
            self.model, self.info = registry.build_model(
                spec.model, **model_kwargs)
        except TypeError as e:
            # A non-Llama registry entry chokes on the injected lora_*
            # kwargs with an opaque TypeError from its config dataclass
            # (every builder takes **kw, so a signature pre-check can't
            # see it). Translate ONLY the unexpected-keyword error for
            # the exact kwargs WE injected — a TypeError that merely
            # mentions a lora_* name (e.g. the user's own lora_rnk typo
            # in model_kwargs) keeps its type, and the original traceback
            # rides along as __cause__ either way.
            msg = str(e)
            injected = ("lora_rank", "lora_alpha", "lora_targets")
            if (self._trainable == "lora"
                    and "unexpected keyword argument" in msg
                    and any(f"'{k}'" in msg for k in injected)):
                raise ValueError(
                    f"spec.lora needs a Llama-family model; "
                    f"{spec.model!r} has no adapter path") from e
            raise
        if self._trainable == "lora":
            from kubeflow_tpu.models.llama import LlamaConfig
            from kubeflow_tpu.models.moe import MoEConfig

            mcfg = getattr(self.model, "cfg", None)
            if not isinstance(mcfg, LlamaConfig):
                raise ValueError(
                    f"spec.lora needs a Llama-family model; "
                    f"{spec.model!r} has no adapter path")
            if (isinstance(mcfg, MoEConfig)
                    and mcfg.lora_targets == "attn_mlp"):
                # MoEBlock's routed experts have no adapter path — the
                # user asked for FFN adapters and would silently get
                # attention-only ones.
                raise ValueError(
                    "lora.targets='attn_mlp' is not supported on MoE "
                    "models (expert FFNs have no adapter path); use "
                    "targets='attn'")
        if (self._pipeline is not None
                and self.mesh.shape["expert"] > 1):
            from kubeflow_tpu.models.moe import MoEConfig

            if not isinstance(getattr(self.model, "cfg", None), MoEConfig):
                # A dense trunk would silently replicate over `expert`.
                raise ValueError(
                    "mesh.expert with pipeline parallelism needs a "
                    "MoE model (routed-expert FFNs)")

        if spec.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got "
                             f"{spec.accum_steps}")
        if spec.grad_accum < 0:
            raise ValueError(f"grad_accum must be >= 0, got "
                             f"{spec.grad_accum}")
        if (spec.grad_accum and spec.accum_steps > 1
                and spec.grad_accum != spec.accum_steps):
            raise ValueError(
                f"grad_accum={spec.grad_accum} and its legacy alias "
                f"accum_steps={spec.accum_steps} disagree — set one")
        # The effective microbatch count (grad_accum is canonical,
        # accum_steps the legacy alias).
        self.grad_accum = spec.grad_accum or spec.accum_steps
        if spec.batch_size % self.grad_accum:
            raise ValueError(
                f"batch_size {spec.batch_size} not divisible by "
                f"grad_accum/accum_steps {self.grad_accum}")
        if spec.fsdp:
            if self._pipeline is not None:
                raise ValueError(
                    "fsdp master sharding doesn't compose with pipeline "
                    "parallelism (stage params keep the scanned pipe "
                    "layout)")
            if self._trainable == "lora":
                raise ValueError(
                    "fsdp master sharding doesn't compose with LoRA "
                    "(the adapter-only optimizer state is the memory "
                    "win there)")
        if spec.eval_every < 0 or spec.eval_batches < 1:
            raise ValueError("eval_every must be >= 0 and eval_batches "
                             ">= 1")
        if spec.restart_policy not in ("Never", "OnFailure",
                                       "ExponentialBackoff"):
            raise ValueError(
                f"restart_policy {spec.restart_policy!r}: Never | "
                "OnFailure | ExponentialBackoff")
        if spec.backoff_limit < 0:
            raise ValueError(f"backoff_limit must be >= 0, got "
                             f"{spec.backoff_limit}")
        if spec.prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {spec.prefetch}")
        if spec.profile_start_step < 0 or spec.profile_stop_step < 0:
            raise ValueError(
                "profile_start_step/profile_stop_step must be >= 0, got "
                f"{spec.profile_start_step}/{spec.profile_stop_step}")
        # Trace identity for this worker's spans: the job name under a
        # control plane, a fixed label standalone.
        self._trace = os.environ.get("TPK_JOB_NAME", "") or "train"
        self._event_client = None
        self.tx = optax.adamw(self._lr_schedule(),
                              weight_decay=spec.weight_decay)
        if spec.max_grad_norm:
            if spec.max_grad_norm < 0:
                raise ValueError(f"max_grad_norm must be >= 0, got "
                                 f"{spec.max_grad_norm}")
            self.tx = optax.chain(
                optax.clip_by_global_norm(spec.max_grad_norm), self.tx)

        self._ckpt = None
        if spec.checkpoint.get("dir"):
            self._ckpt = CheckpointManager(
                spec.checkpoint["dir"],
                interval=spec.checkpoint.get("interval", 50),
                keep=spec.checkpoint.get("keep", 3),
                async_save=spec.checkpoint.get("async_save", True))
        self.logger = MetricsLogger(spec.metrics_path)

    def _post_event(self, reason: str, message: str = "") -> None:
        """Best-effort event into the job's control-plane event log
        (CheckpointSaved & co.): only when launched by the control plane
        (TPK_SOCKET + TPK_JOB_NAME injected), only from process 0, and
        never fatal — a missing/slow control plane must not fail
        training."""
        sock = os.environ.get("TPK_SOCKET")
        job = os.environ.get("TPK_JOB_NAME")
        if not sock or not job or jax.process_index() != 0:
            return
        try:
            if self._event_client is None:
                from kubeflow_tpu.controlplane.client import Client

                self._event_client = Client(sock, timeout=2.0,
                                            max_attempts=1, deadline_s=2.0,
                                            trace_id=job)
            self._event_client.post_event(job, reason, message)
        except Exception:
            self._event_client = None  # reconnect on the next event

    def _lr_schedule(self) -> optax.Schedule | float:
        spec = self.spec
        peak, warm = spec.learning_rate, spec.warmup_steps
        if spec.lr_schedule == "constant":
            if warm:
                return optax.linear_schedule(0.0, peak, warm)
            return peak
        # Decay horizon is the full run: warmup then decay to lr_final at
        # spec.steps (resume keeps the schedule aligned since opt step
        # count rides in the checkpointed opt_state).
        decay_steps = max(spec.steps - warm, 1)
        if spec.lr_schedule == "cosine":
            return optax.warmup_cosine_decay_schedule(
                0.0, peak, warm, warm + decay_steps,
                end_value=spec.lr_final)
        if spec.lr_schedule == "linear":
            decay = optax.linear_schedule(peak, spec.lr_final, decay_steps)
            if not warm:
                return decay
            return optax.join_schedules(
                [optax.linear_schedule(0.0, peak, warm), decay], [warm])
        raise ValueError(
            f"lr_schedule {spec.lr_schedule!r}: constant | cosine | linear")

    # -- data ---------------------------------------------------------------

    @property
    def _dp_shards(self) -> int:
        """Extent of the batch-sharding axes (data × fsdp)."""
        return self.mesh.shape["data"] * self.mesh.shape["fsdp"]

    @property
    def _batch_groups(self) -> int:
        """How many DISTINCT per-process data streams the mesh admits.

        The batch dim shards over the leading (data, fsdp) mesh axes, so a
        process's devices cover dp·n_proc-relative shard spans: with
        dp >= n_proc each process owns exclusive shards (n distinct
        streams); with dp < n_proc each shard is replicated across
        n_proc/dp processes, which must feed IDENTICAL data (dp streams);
        pure CP/TP (dp == 1) replicates the whole batch everywhere."""
        n = jax.process_count()
        dp = self._dp_shards
        if dp % n and n % dp:
            raise ValueError(
                f"batch shards ({dp} = data*fsdp) and processes ({n}) "
                "must divide one another for process-aligned data loading")
        return min(dp, n)

    @property
    def local_batch_size(self) -> int:
        """spec.batch_size is the GLOBAL batch; each process loads the
        share of its batch replica group (the reference's per-worker
        DataLoader sharding, done for the user)."""
        g = self._batch_groups
        if self.spec.batch_size % g:
            raise ValueError(
                f"global batch {self.spec.batch_size} not divisible by "
                f"{g} batch replica groups")
        return self.spec.batch_size // g

    def _make_stream(self, name: str, kwargs: dict,
                     seed_base: int) -> Iterator[dict]:
        """Shared dataset-builder: model-derived defaults plus the batch
        replica-group contract — processes sharing a batch shard (or a
        fully replicated batch) must load IDENTICAL data: same seed AND
        the same grain row shard (the loader's sharding is group-indexed,
        not process-indexed)."""
        from kubeflow_tpu.utils import registry

        kwargs = dict(kwargs)
        kwargs.setdefault("batch_size", self.local_batch_size)
        if self.info.get("task") == "lm":
            kwargs.setdefault("seq_len", self.spec.seq_len)
            kwargs.setdefault("vocab_size", self.info["vocab_size"])
        n = jax.process_count()
        group = jax.process_index() * self._batch_groups // n
        kwargs.setdefault("seed", seed_base + 7919 * group)
        kwargs.setdefault("process_index", group)
        kwargs.setdefault("process_count", self._batch_groups)
        return registry.build_dataset(name, **kwargs)

    def _data(self) -> Iterator[dict]:
        return self._make_stream(self.spec.dataset,
                                 self.spec.dataset_kwargs, self.spec.seed)

    def _eval_data(self) -> Iterator[dict]:
        """Validation stream. Defaults to the train dataset family —
        INCLUDING its kwargs (a token_file corpus path must carry over) —
        with a disjoint seed so synthetic/eval-less corpora still get a
        held-out-like stream."""
        if self.spec.eval_dataset:
            name, kwargs = self.spec.eval_dataset, self.spec.eval_dataset_kwargs
        else:
            name = self.spec.dataset
            kwargs = {**self.spec.dataset_kwargs,
                      **self.spec.eval_dataset_kwargs}
        return self._make_stream(name, kwargs, self.spec.seed + 104729)

    def _globalize(self, batch: dict) -> dict:
        """Assemble process-local numpy batches into global jax.Arrays
        sharded over the dp axes (multi-host path; no-op single-process)."""
        if jax.process_count() == 1:
            return batch
        from jax.sharding import NamedSharding, PartitionSpec as P

        def conv(x):
            spec = P(("data", "fsdp"), *([None] * (x.ndim - 1)))
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, spec), np.asarray(x))

        return jax.tree.map(conv, batch)

    def _place_on_device(self, batch: dict) -> dict:
        """Explicit H2D staging for the prefetch path: each leaf lands on
        device BEFORE the trainer thread sees it, so the transfer
        overlaps device compute instead of riding implicitly inside the
        next step's dispatch. Multi-host goes through `_globalize`
        (make_array_from_process_local_data with the dp sharding — the
        per-process shards ARE the placement). Single-process places
        with the replicated layout the jitted step resolves for
        uncommitted batch inputs anyway: the same bytes land on the same
        devices as the numpy path, just off the critical path — which
        keeps every prefetch depth bit-identical to the synchronous
        loop (a dp-sharded committed input would compile a different —
        cheaper to transfer but numerically reordered — program)."""
        if jax.process_count() > 1:
            return self._globalize(batch)
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P())

        def conv(x):
            return jax.device_put(np.asarray(x), sharding)

        return jax.tree.map(conv, batch)

    def _example_inputs(self) -> tuple:
        if self.info.get("task") == "lm":
            return (jnp.zeros((self.spec.batch_size, self.spec.seq_len),
                              jnp.int32),)
        shape = (self.spec.batch_size,) + tuple(
            self.info["example_shape"][1:])
        return (jnp.zeros(shape, self.info["example_dtype"]),)

    def _loss_fn(self):
        if self.info.get("task") == "classify":
            def loss_fn(logits, batch):
                if isinstance(logits, tuple):
                    logits = logits[-1]
                onehot = jax.nn.one_hot(batch["targets"], logits.shape[-1])
                return optax.softmax_cross_entropy(logits, onehot).mean()
            return loss_fn
        return None  # default causal-LM loss

    # -- run ----------------------------------------------------------------

    def run(self) -> dict:
        """Supervised entry point: runs the training loop under the
        spec's restart policy (training-operator restartPolicy/
        backoffLimit, in-process). Every restart flows through
        `_run_once`'s checkpoint auto-resume — latest TrainState AND the
        saved data-iterator position — so the run converges to the same
        final step a fault-free run reaches."""
        spec = self.spec
        if spec.restart_policy == "Never":
            return self._run_once()
        backoff = resilience.BackoffPolicy(initial_s=0.05, max_s=10.0)
        restarts = 0
        while True:
            try:
                return self._run_once()
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if self._ckpt is not None:
                    # An async save may be mid-flight; restarting before
                    # it lands could resume from the previous (older)
                    # step. Failures inside wait() itself mean the ckpt
                    # dir is suspect — surface the original error.
                    try:
                        self._ckpt.wait()
                    except Exception:
                        pass
                restarts += 1
                if restarts > spec.backoff_limit:
                    resilience.metrics.inc("tpk_retry_exhausted_total",
                                           component="train")
                    raise resilience.BackoffLimitExceeded(
                        f"training failed {restarts} times "
                        f"(backoff_limit={spec.backoff_limit}, "
                        f"restart_policy={spec.restart_policy}): "
                        f"{type(e).__name__}: {e}") from e
                # Counted only when a restart actually happens — the
                # terminal failure above is an exhaustion, not a restart.
                resilience.metrics.inc("tpk_restarts_total",
                                       component="train")
                delay = (backoff.delay(restarts - 1)
                         if spec.restart_policy == "ExponentialBackoff"
                         else 0.0)
                self.logger.log(0, {
                    "event": "restarting", "attempt": restarts,
                    "backoff_s": round(delay, 3),
                    "error": f"{type(e).__name__}: {e}"})
                if delay:
                    time.sleep(delay)

    def _run_once(self) -> dict:
        spec = self.spec

        model_kwargs = {}
        if spec.ring_attention:
            model_kwargs["ring_axis"] = "seq"
        # Zigzag context parallelism (SURVEY.md §5.7 causal load balance):
        # spec.ring_attention == "zigzag" is the single switch — the
        # trainer lays batches out in zigzag order and passes the matching
        # absolute positions for RoPE; the LM loss is invariant (inputs
        # and targets move together). Model-side impl is forced to match
        # in __init__ so spec and kernel can't drift.
        zigzag_idx = None
        init_kwargs = None
        if spec.ring_attention in ("zigzag", "zigzag_flash"):
            from kubeflow_tpu.ops.ring_attention import zigzag_indices

            n_seq = self.mesh.shape["seq"]
            zigzag_idx = np.asarray(zigzag_indices(spec.seq_len, n_seq))
            model_kwargs["positions"] = jnp.broadcast_to(
                jnp.asarray(zigzag_idx, jnp.int32)[None],
                (spec.batch_size, spec.seq_len))
            init_kwargs = model_kwargs  # zigzag's init needs positions too

        fsdp_plan = None
        if spec.fsdp:
            from kubeflow_tpu.parallel.fsdp import FSDP

            fsdp_plan = FSDP(self.mesh, compute_dtype=self._fsdp_dtype)

        state = init_train_state(
            self.model, self.tx, jax.random.key(spec.seed),
            self._example_inputs(), self.mesh, self.rules,
            example_kwargs=init_kwargs, trainable=self._trainable,
            fsdp=fsdp_plan)

        start_step = 0
        if self._ckpt is not None:
            # Corrupt-latest fallback: a torn orbax write (SIGKILL
            # mid-save) quarantines that step and resumes from the
            # next-newest good one instead of wedging every restart of
            # the backoff loop on the same poisoned restore.
            with obs.span("train.restore", trace_id=self._trace):
                state, latest, quarantined = \
                    self._ckpt.restore_latest_good(state)
            for bad in quarantined:
                self.logger.log(int(bad), {
                    "event": "checkpoint_quarantined", "step": int(bad)})
            if latest is not None:
                start_step = int(latest)
                self.logger.log(start_step, {"event": "restored"})

        # State-layout accounting (pure sharding metadata — no device
        # sync): how many bytes of params/optimizer state each chip
        # actually holds, the number the fsdp knob exists to divide.
        from kubeflow_tpu.parallel.fsdp import tree_bytes_per_device

        param_bytes = tree_bytes_per_device(state.params)
        opt_bytes = tree_bytes_per_device(state.opt_state)
        resilience.metrics.set_gauge("tpk_train_param_bytes_per_chip",
                                     param_bytes, component="train")
        resilience.metrics.set_gauge("tpk_train_opt_state_bytes_per_chip",
                                     opt_bytes, component="train")
        resilience.metrics.set_gauge("tpk_train_grad_accum_steps",
                                     self.grad_accum, component="train")
        # The live fsdp topology this attempt is training at — under an
        # elastic resize the controller rewrites runtime.json, so this
        # gauge is how dashboards see the post-resize mesh.
        resilience.metrics.set_gauge("tpk_train_fsdp_size",
                                     spec.fsdp, component="train")
        self.logger.log(start_step, {
            "event": "state_sharding", "fsdp": spec.fsdp,
            "param_bytes_per_chip": param_bytes,
            "opt_state_bytes_per_chip": opt_bytes,
            "grad_accum_steps": self.grad_accum})

        step_fn = make_train_step(self.model, self.mesh, self.rules,
                                  loss_fn=self._loss_fn(),
                                  model_kwargs=model_kwargs,
                                  loss_impl=spec.loss_impl,
                                  loss_chunk=spec.loss_chunk,
                                  pipeline=self._pipeline,
                                  accum_steps=self.grad_accum,
                                  trainable=self._trainable,
                                  fsdp=fsdp_plan)

        eval_step = None
        if spec.eval_every:
            from kubeflow_tpu.train.step import make_eval_step

            eval_step = make_eval_step(self.model, self.mesh, self.rules,
                                       model_kwargs=model_kwargs)

        # One persistent eval stream for the whole run: file-backed
        # corpora pay their tokenize/pack cost in the constructor, so
        # rebuilding per window would stall training every eval_every
        # steps. Rebuilt only when exhausted.
        eval_iter_box: list = [None]

        def next_eval_batch():
            for _ in range(2):
                if eval_iter_box[0] is None:
                    eval_iter_box[0] = iter(self._eval_data())
                try:
                    return next(eval_iter_box[0])
                except StopIteration:
                    eval_iter_box[0] = None  # exhausted: fresh pass
            return None

        def run_eval(params, at_step):
            # Accumulate DEVICE scalars and fetch once per eval window:
            # a float() per batch would pay one full host sync each — an
            # eval_batches-deep stall inside the training timeline.
            loss_sum = acc_sum = None
            seen = 0
            for _ in range(spec.eval_batches):
                raw = next_eval_batch()
                if raw is None:
                    break
                if zigzag_idx is not None:
                    raw = {k: np.asarray(v)[:, zigzag_idx]
                           for k, v in raw.items()}
                m = eval_step(params, self._globalize(raw))
                loss_sum = (m["loss"] if loss_sum is None
                            else loss_sum + m["loss"])
                acc_sum = (m["accuracy"] if acc_sum is None
                           else acc_sum + m["accuracy"])
                seen += 1
            if not seen:
                return {}
            totals = np.asarray(jnp.stack([loss_sum, acc_sum]))  # 1 fetch
            out = {"eval_loss": float(totals[0]) / seen,
                   "eval_accuracy": float(totals[1]) / seen,
                   "eval_batches": seen}
            self.logger.log(at_step, out)
            return out

        tokens_per_step = spec.batch_size * (
            spec.seq_len if self.info.get("task") == "lm" else 1)
        timer = StepTimer(
            num_params=self.info.get("num_params") or 0,
            tokens_per_step=tokens_per_step)

        # Profile window [prof_start, prof_stop): the dict-style knob
        # (dir + start_step + num_steps) or the flat spec knobs
        # (profile_start_step/profile_stop_step, trace dir defaulting to
        # the job workdir); clamped so the trace always closes before
        # the loop ends.
        prof = spec.profile
        prof_start = prof_stop = None
        prof_dir = prof.get("dir")
        if prof_dir and prof.get("start_step") is not None:
            prof_start = max(int(prof["start_step"]), start_step)
            prof_stop = min(prof_start + int(prof.get("num_steps", 3)),
                            spec.steps)
            if prof_start >= spec.steps:
                prof_start = prof_stop = None
        elif spec.profile_stop_step > spec.profile_start_step:
            base = (os.environ.get("TPK_WORKDIR")
                    or (os.path.dirname(spec.metrics_path)
                        if spec.metrics_path else "")
                    or ".")
            prof_dir = prof_dir or os.path.join(base, "profile")
            prof_start = max(spec.profile_start_step, start_step)
            prof_stop = min(spec.profile_stop_step, spec.steps)
            if prof_start >= prof_stop:
                prof_start = prof_stop = None
        prof_active = False

        from kubeflow_tpu.data.loader import restore_iterator

        def pack_data_state():
            # Under prefetch the iterator runs ahead of training;
            # consumed_state() is the snapshot paired with the batch the
            # checkpoint step actually trained on, so resume replays
            # exactly the right rows.
            st = prefetch.consumed_state()
            if st is None:
                return None
            # The iterator state is only valid for the same per-process
            # shard layout; tag it so an elastic resize (different world
            # size) restarts the stream instead of mis-seeking. The fsdp
            # tag records the mesh the checkpoint trained at — resize
            # detection on resume, not a seek invalidator (the stream is
            # process-sharded, so a same-process-count fsdp resize seeks
            # the exact trajectory).
            return {"process_count": jax.process_count(), "state": st,
                    "fsdp": spec.fsdp}

        dataset = self._data()
        data = iter(dataset)
        if start_step:
            saved = self._ckpt.restore_data_state()
            if saved is None:
                # Plain generators: replay consumed batches.
                for _ in range(start_step):
                    next(data)
            elif (isinstance(saved, dict) and "process_count" in saved):
                saved_fsdp = saved.get("fsdp")
                if saved_fsdp is not None and saved_fsdp != spec.fsdp:
                    # Elastic resize: the checkpoint was written by a
                    # different fsdp topology and orbax just resharded it
                    # into this one (restore_latest_good above). Record
                    # the transition — the trajectory itself must not
                    # notice (fp32 fsdp=N trains the replicated
                    # trajectory exactly; PROFILE §14/§15).
                    resilience.metrics.inc("tpk_train_reshard_restores_total",
                                           component="train")
                    self.logger.log(start_step, {
                        "event": "resharded",
                        "from_fsdp": int(saved_fsdp),
                        "to_fsdp": int(spec.fsdp), "step": start_step})
                    self._post_event(
                        "Resharded",
                        f"fsdp {int(saved_fsdp)} -> {int(spec.fsdp)} "
                        f"at step {start_step}")
                if saved["process_count"] == jax.process_count():
                    # Checkpointable iterators (grain) seek in O(1).
                    restore_iterator(data, saved.get("state"))
                else:
                    # Resized world: per-process shards changed; a fresh
                    # stream is the correct (and standard) resume behavior.
                    self.logger.log(start_step, {
                        "event": "data_stream_restarted",
                        "reason": "world size changed"})
            else:
                # Pre-tag checkpoint: raw iterator state, same-world by
                # assumption (the tag didn't exist to say otherwise).
                restore_iterator(data, saved)

        # The async input pipeline: pull + zigzag + H2D staged up to
        # `spec.prefetch` batches ahead on a worker thread (depth 0 runs
        # the same ops inline — the synchronous escape hatch). Created
        # AFTER the iterator seek above so read-ahead starts at the
        # resume position.
        transform = None
        if zigzag_idx is not None:
            def transform(raw):
                return {k: np.asarray(v)[:, zigzag_idx]
                        for k, v in raw.items()}

        # Fault injection (SURVEY.md §5.3): the controller sets
        # TPK_FAULT="step=K;signal=S" on one worker; it kills itself at the
        # top of step K — the deterministic, step-precise chaos fixture.
        fault_step = fault_signal = None
        fault = os.environ.get("TPK_FAULT", "")
        if fault:
            kv = dict(part.split("=", 1) for part in fault.split(";") if "=" in part)
            fault_step = int(kv.get("step", -1))
            fault_signal = int(kv.get("signal", 9))

        prefetch = Prefetcher(
            data, depth=spec.prefetch, transform=transform,
            place=(self._globalize if spec.prefetch == 0
                   else self._place_on_device))

        last_metrics: dict = {}
        last_eval: dict = {}
        # CheckpointSaved events are deferred one save boundary: orbax
        # saves asynchronously, and a WAL-persisted event must never
        # claim a checkpoint that a kill-9 then tore. Starting save k+1
        # blocks on save k's commit, so at the next boundary (and after
        # the final wait()) the previous save is known durable.
        ckpt_event_pending: int | None = None
        # Per-window data-starvation accounting: how much of the window's
        # wall the training thread spent waiting on input (data_wait_frac
        # ≈ 0 when the prefetcher keeps up; → 1 when the pipeline is the
        # bottleneck and depth/host work needs attention).
        win = {"t0": 0.0, "wait": 0.0, "h2d": 0.0, "compiles": 0}
        clock = devices.compile_clock()
        # Per-window span rollup (tentpole: "span summaries in the JSONL
        # stream"): host-side wall spent in step dispatch / boundary
        # fetches / checkpoint saves / eval, summed between log
        # boundaries — the coarse where-did-the-window-go view; the full
        # per-span timeline lives in the obs tracer ring.
        span_win: dict[str, list] = {}

        def acc_span(key: str, sp) -> None:
            if sp is obs.NOP_SPAN:
                # Tracing disabled (TPK_TRACE=0): omit the span_* keys
                # entirely rather than emitting constant 0.0 — "not
                # measured" must not read as "zero host time".
                return
            w = span_win.setdefault(key, [0, 0.0])
            w[0] += 1
            w[1] += sp.dur_s

        def win_reset():
            win["t0"] = time.perf_counter()
            win["wait"] = prefetch.data_wait_s
            win["h2d"] = prefetch.h2d_s
            win["compiles"] = clock.compiles
            span_win.clear()

        def win_metrics() -> dict:
            wall = time.perf_counter() - win["t0"]
            dw = prefetch.data_wait_s - win["wait"]
            out = {
                "data_wait_s": round(dw, 6),
                "data_wait_frac": round(dw / wall, 4) if wall > 0 else 0.0,
                "data_h2d_s": round(prefetch.h2d_s - win["h2d"], 6),
                # Backend compiles in this window: one after the first
                # row is a step that was not the step it looks like.
                "compiles": clock.compiles - win["compiles"],
                "tpk_data_wait_seconds_total": round(
                    resilience.metrics.get("tpk_data_wait_seconds_total",
                                           component="train"), 6),
            }
            for key, (_, total) in sorted(span_win.items()):
                out[f"span_{key}_ms"] = round(total * 1e3, 3)
            return out

        try:
            timer.start()
            win_reset()
            window = 0
            # The hot loop: between log/eval boundaries nothing below
            # may touch a device value — host data prep and device
            # compute only overlap while the dispatch queue stays full.
            # The tpk-hot region makes that reviewable-by-machine; the
            # runtime sync-budget guard test pins the same invariant
            # dynamically. Every deliberate boundary fetch below carries
            # its reason inline.
            # tpk-hot: begin trainer-step-loop
            for step in range(start_step, spec.steps):
                faults.fire(_FP_STEP, step=step)
                if fault_step is not None and step == fault_step:
                    if self._ckpt is not None:
                        self._ckpt.wait()  # die w/ a consistent checkpoint
                    self.logger.log(step, {"event": "fault_injected",
                                           "signal": fault_signal})
                    os.kill(os.getpid(), fault_signal)
                if prof_start is not None and step == prof_start:
                    jax.profiler.start_trace(prof_dir)
                    prof_active = True
                # The step span measures HOST dispatch wall (data wait +
                # enqueue) — the device executes asynchronously, and the
                # span never touches a device value, so tracing adds
                # zero host syncs to the hot loop (the span-overhead
                # guard test pins this).
                with obs.span("train.step", trace_id=self._trace,
                              step=step) as sp:
                    batch = next(prefetch)
                    state, metrics = step_fn(state, batch)
                acc_span("step", sp)
                window += 1
                if prof_active and step + 1 == prof_stop:
                    # tpk-lint: allow(host-sync) reason=profiler window close must drain the device or the trace tail is lost; runs only on the configured profile_stop_step
                    jax.block_until_ready(metrics["loss"])
                    jax.profiler.stop_trace()
                    prof_active = False
                if self._ckpt is not None:
                    # Only collect iterator state on steps that will save
                    # — consumed_state() may walk the grain pipeline
                    # (depth 0) and doesn't belong in the non-blocking
                    # hot loop.
                    if self._ckpt.should_save(step + 1):
                        with obs.span("train.checkpoint_save",
                                      trace_id=self._trace,
                                      step=step + 1) as sp:
                            self._ckpt.maybe_save(
                                step + 1, state,
                                data_state=pack_data_state())
                        acc_span("ckpt", sp)
                        if ckpt_event_pending is not None:
                            self._post_event(
                                "CheckpointSaved",
                                f"step {ckpt_event_pending}")
                        ckpt_event_pending = step + 1
                    else:
                        self._ckpt.maybe_save(step + 1, state)
                if (eval_step is not None
                        and (step + 1) % spec.eval_every == 0):
                    # Close the timing window first so eval wall time
                    # never pollutes the train tokens/sec / MFU averages.
                    sp_fetch = None
                    if window:
                        with obs.span("train.fetch",
                                      trace_id=self._trace) as sp_fetch:
                            # tpk-lint: allow(host-sync) reason=eval boundary closes the timing window so eval wall never pollutes tokens/sec (designed per-eval_every fetch)
                            jax.block_until_ready(metrics["loss"])
                        timer.stop(n_steps=window)
                        window = 0
                    with obs.span("train.eval", trace_id=self._trace,
                                  step=step + 1) as sp:
                        last_eval = run_eval(state.params, step + 1)
                    timer.start()
                    win_reset()
                    # Recorded AFTER the reset so the boundary costs
                    # show on the next window's line instead of
                    # vanishing with the window they closed.
                    if sp_fetch is not None:
                        acc_span("fetch", sp_fetch)
                    acc_span("eval", sp)
                if ((step + 1) % spec.log_every == 0
                        or step + 1 == spec.steps):
                    # Block only at logging boundaries — keeping the
                    # dispatch queue full between them lets host data prep
                    # overlap device compute (per-step numbers are window
                    # averages).
                    with obs.span("train.fetch",
                                  trace_id=self._trace) as sp:
                        # tpk-lint: allow(host-sync) reason=the designed per-log_every window boundary; the runtime guard budgets exactly one fetch here
                        jax.block_until_ready(metrics["loss"])
                    acc_span("fetch", sp)
                    if window:
                        perf = timer.stop(n_steps=window)
                        window = 0
                    else:  # an eval just flushed this window
                        perf = timer.snapshot()
                    last_metrics = {
                        # tpk-lint: allow(host-sync) reason=already on host after the boundary block_until_ready above; free fetch
                        "loss": float(metrics["loss"]),
                        # tpk-lint: allow(host-sync) reason=already on host after the boundary block_until_ready above; free fetch
                        "grad_norm": float(metrics["grad_norm"]),
                        "tokens_per_sec": perf["tokens_per_sec"],
                        "step_time_s": perf["step_time_s"],
                        **win_metrics(),
                    }
                    if perf["mfu"] is not None:  # None: no peak on a CPU
                        last_metrics["mfu"] = perf["mfu"]
                    # MoE models report the router balance penalty too.
                    # tpk-lint: allow(host-sync) reason=log-boundary only, value already on host after the window fetch above
                    if float(metrics.get("aux_loss", 0.0)) > 0:
                        # tpk-lint: allow(host-sync) reason=log-boundary only, value already on host after the window fetch
                        last_metrics["aux_loss"] = float(
                            metrics["aux_loss"])
                    # A model's own counters (the `counters` collection).
                    for name in metrics.keys() - {"loss", "grad_norm",
                                                  "aux_loss", "step"}:
                        # tpk-lint: allow(host-sync) reason=log-boundary only, value already on host after the window fetch
                        last_metrics[name] = float(metrics[name])
                    self.logger.log(step + 1, last_metrics)
                    timer.start()
                    win_reset()
            # tpk-hot: end trainer-step-loop

            if self._ckpt is not None:
                if self._ckpt.latest_step() != spec.steps:
                    with obs.span("train.checkpoint_save",
                                  trace_id=self._trace, step=spec.steps):
                        self._ckpt.maybe_save(spec.steps, state,
                                              data_state=pack_data_state(),
                                              force=True)
                self._ckpt.wait()
                # Everything is durable now: flush the deferred interior
                # event (it never met its "next boundary"), then the
                # final step's (the two merge into one aggregated row).
                if (ckpt_event_pending is not None
                        and ckpt_event_pending != self._ckpt.latest_step()):
                    self._post_event("CheckpointSaved",
                                     f"step {ckpt_event_pending}")
                self._post_event("CheckpointSaved",
                                 f"step {self._ckpt.latest_step()}")
            self.logger.log(spec.steps,
                            {"event": "done", **last_metrics, **last_eval})
            return {"final_step": spec.steps, **last_metrics, **last_eval}
        finally:
            # Every exit path of the supervised restart loop lands here:
            # normal completion, a raising step (restart policies rebuild
            # the stream), KeyboardInterrupt — the worker thread must
            # never outlive its run.
            prefetch.close()


def main(argv: list[str] | None = None) -> int:
    """`python -m kubeflow_tpu.train.trainer --spec job.json` — the worker
    entrypoint the JAXJob executor launches (with TPK_* env injected)."""
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--spec", required=True,
                        help="path to TrainJobSpec JSON")
    parser.add_argument("--cpu-devices", type=int, default=0,
                        help="force N virtual CPU devices (test mode)")
    args = parser.parse_args(argv)

    if args.cpu_devices:
        devices.force_cpu_device_count(args.cpu_devices)
    devices.enable_compile_cache()
    clock = devices.compile_clock()
    with open(args.spec) as fh:
        spec = TrainJobSpec.from_json(fh.read())
    # Distributed init must precede the first backend use; Trainer's own
    # initialize() call is then a no-op.
    initialize(read_env())
    print(json.dumps({
        "event": "device",
        **devices.require_tpu_or_requested_cpu("tpk-trainer")}), flush=True)
    result = Trainer(spec).run()
    print(json.dumps({"event": "device_end", **clock.snapshot(),
                      "peak_bytes_in_use": devices.peak_bytes_in_use()}),
          flush=True)
    print(json.dumps({"result": result}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
