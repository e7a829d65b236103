"""Train-step factory: one jitted SPMD step on a named mesh.

Replaces the reference's 🔥 in-container DDP/NCCL step loop (SURVEY.md §3.1:
`torchrun → DDP fwd/bwd/allreduce`) with a single `jit`-compiled function —
gradient collectives are emitted by XLA from sharding annotations rather than
invoked via NCCL, and the whole step (fwd+bwd+optimizer) fuses into one
executable with donated buffers.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict

from kubeflow_tpu.parallel.sharding import Rules, DEFAULT_RULES


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads):
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean token-level cross entropy in fp32. logits [..., V], targets [...]"""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_cross_entropy(hidden: jax.Array, head: jax.Array,
                          targets: jax.Array,
                          mask: jax.Array | None = None, *,
                          chunk: int = 1024,
                          head_is_vocab_major: bool = False,
                          final_softcap: float = 0.0) -> jax.Array:
    """Fused blockwise cross entropy (ops/ROADMAP.md item 1): logits are
    computed per token-chunk against the unembedding and never
    materialized as the [B·S, V] fp32 buffer that dominates peak memory at
    the bench point (PROFILE.md §3).

    The gradient is taken in the forward pass (a `jax.custom_vjp`): under
    differentiation each chunk's logits are computed once and give the
    chunk's loss, its d(hidden) and its share of d(head), so the head is
    three matmul passes a step (logits, d(hidden), d(head)) and the
    backward only scales the two stored gradients by the incoming
    cotangent. Operands are in the activation dtype, the softmax in fp32,
    and d(head) accumulates in fp32 over the chunks, in chunk order. A
    gradient nobody asks for is not computed: with a frozen head (LoRA)
    there is no d(head) matmul and no [V,D] carry, with no differentiation
    at all only the loss. Targets and mask get no cotangent.

    hidden [B,S,D]; head [D,V] (lm_head kernel) or [V,D] with
    `head_is_vocab_major` (tied embedding); targets [B,S].
    `final_softcap` applies Gemma-2's logit cap tanh(l/cap)*cap inside
    each chunk — the return_hidden path skips the model's own cap, so
    omitting it here would train against uncapped logits.
    """
    b, s, d = hidden.shape
    n = b * s
    h = hidden.reshape(n, d)
    t = targets.reshape(n)
    m = (jnp.ones((n,), jnp.float32) if mask is None
         else mask.reshape(n).astype(jnp.float32))
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
        m = jnp.pad(m, (0, pad))  # padded rows carry mask 0
    nblk = (n + pad) // chunk
    hb = h.reshape(nblk, chunk, d)
    tb = t.reshape(nblk, chunk)
    mb = m.reshape(nblk, chunk)

    logits_spec, dx_spec, dw_spec = (
        ("cd,vd->cv", "cv,vd->cd", "cv,cd->vd") if head_is_vocab_major
        else ("cd,dv->cv", "cv,dv->cd", "cv,cd->dv"))
    head_dtype = head.dtype

    def scan_chunks(hb, head, tb, mb, want_dx, want_dw):
        """(loss, d(hb) or None, fp32 d(head) or None) at cotangent 1."""
        w = head.astype(hb.dtype)
        cnt = jnp.maximum(jnp.sum(mb), 1.0)

        def block(carry, xs):
            hx, tx, mx = xs
            logits = jnp.einsum(logits_spec, hx, w).astype(jnp.float32)
            if final_softcap:
                logits = jnp.tanh(logits / final_softcap) * final_softcap
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tx[:, None], axis=-1)[:, 0]
            tot, dw = carry
            tot = tot + jnp.sum((logz - gold) * mx)
            if not (want_dx or want_dw):
                return (tot, dw), None
            onehot = tx[:, None] == jnp.arange(logits.shape[-1])[None, :]
            g = jnp.exp(logits - logz[:, None]) - onehot
            g = g * (mx / cnt)[:, None]
            if final_softcap:
                g = g * (1.0 - jnp.square(logits / final_softcap))
            g = g.astype(hx.dtype)
            dx = jnp.einsum(dx_spec, g, w) if want_dx else None
            if want_dw:
                dw = dw + jnp.einsum(dw_spec, g, hx,
                                     preferred_element_type=jnp.float32)
            return (tot, dw), dx

        dw0 = jnp.zeros(head.shape, jnp.float32) if want_dw else None
        (tot, dw), dx = jax.lax.scan(
            block, (jnp.zeros((), jnp.float32), dw0), (hb, tb, mb))
        return tot / cnt, dx, dw

    @jax.custom_vjp
    def loss(hb, head, tb, mb):
        return scan_chunks(hb, head, tb, mb, False, False)[0]

    def loss_fwd(hb, head, tb, mb):
        # symbolic_zeros: each argument arrives with `.perturbed`, whether
        # the caller differentiates with respect to it.
        out, dx, dw = scan_chunks(hb.value, head.value, tb.value, mb.value,
                                  hb.perturbed, head.perturbed)
        return out, (dx, dw)

    def loss_bwd(res, ct):
        dx, dw = res
        if dx is not None:
            dx = (dx.astype(jnp.float32) * ct).astype(dx.dtype)
        if dw is not None:
            dw = (dw * ct).astype(head_dtype)
        return dx, dw, None, None

    loss.defvjp(loss_fwd, loss_bwd, symbolic_zeros=True)
    return loss(hb, head, tb, mb)


def _unembed_head(params: Any) -> tuple[jax.Array, bool]:
    """(head weights, vocab_major) for the chunked-CE path: the lm_head
    kernel [D,V], or the tied embedding [V,D]."""
    if "lm_head" in params:
        return params["lm_head"]["kernel"], False
    if "embed" in params:
        return params["embed"], True
    raise ValueError(
        "chunked loss needs an 'lm_head' or tied 'embed' param "
        f"(have {sorted(params)})")


def abstract_train_state(
    model: nn.Module,
    tx: optax.GradientTransformation,
    example_inputs: tuple,
    mesh: jax.sharding.Mesh,
    rules: Rules = DEFAULT_RULES,
    example_kwargs: dict | None = None,
    trainable: str | None = None,
    fsdp=None,
):
    """(init_fn, abstract_state, shardings): the sharding-layout derivation
    shared by real initialization (init_train_state) and AOT scale proofs
    (utils/scaleproof.py) — eval_shape the init, map flax logical metadata
    through the rules to NamedShardings. `abstract_state` is unboxed
    ShapeDtypeStructs; `shardings` is the matching NamedSharding tree.
    Callers must be inside `with mesh, nn.logical_axis_rules(rules)` when
    tracing `init_fn`.

    `fsdp` (a parallel/fsdp.FSDP plan) rewrites the STATE shardings to the
    ZeRO-style master layout — every param/moment leaf gains the fsdp
    axis — and records the (compute, master) layout pair on the plan for
    make_train_step's gather-for-compute."""
    example_kwargs = example_kwargs or {}

    def _init(rng):
        variables = model.init(rng, *example_inputs, **example_kwargs)
        params = variables["params"]
        opt_target = params
        if trainable == "lora":
            # LoRA memory win: optimizer state covers ONLY the adapter
            # leaves (fp32 Adam moments for the frozen base would
            # dominate the budget, defeating the point).
            from kubeflow_tpu.train.lora import partition

            opt_target, _ = partition(dict(params))
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(opt_target), tx=tx)

    with mesh, nn.logical_axis_rules(rules):
        abstract = jax.eval_shape(_init, jax.random.key(0))
        logical_specs = nn.get_partition_spec(abstract)
        shardings = nn.logical_to_mesh_sharding(logical_specs, mesh, rules)
    abstract = nn.meta.unbox(abstract)
    if fsdp is not None:
        if trainable == "lora":
            raise ValueError(
                "fsdp master sharding doesn't compose with trainable="
                "'lora' (the adapter-only optimizer state is the memory "
                "win there)")
        fsdp.prepare(abstract.params, shardings.params)
        shardings = fsdp.master_state_shardings(abstract, shardings)
    return _init, abstract, shardings


def init_train_state(
    model: nn.Module,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    example_inputs: tuple,
    mesh: jax.sharding.Mesh,
    rules: Rules = DEFAULT_RULES,
    example_kwargs: dict | None = None,
    trainable: str | None = None,
    fsdp=None,
) -> TrainState:
    """Initialize params already laid out per the sharding rules: we eval_shape
    the init, derive NamedShardings from logical metadata, then run the real
    init jitted with those out_shardings — params are born sharded, never
    materialized replicated (essential at 8B scale).

    `example_kwargs` rides into model.init for impls whose trace needs the
    full call contract (e.g. zigzag attention requires explicit positions).
    `trainable="lora"` restricts the optimizer state to adapter leaves.
    `fsdp` (parallel/fsdp.FSDP) births the state in the ZeRO-style master
    layout — fp32 params + Adam moments sharded over the fsdp axis."""
    _init, _, shardings = abstract_train_state(
        model, tx, example_inputs, mesh, rules, example_kwargs, trainable,
        fsdp=fsdp)
    # Partitionable threefry for the init trace: the legacy generator's
    # bits depend on how XLA partitions the RNG op, so born-sharded
    # params would differ BY LAYOUT — fsdp=K could never equal fsdp=1,
    # and a topology change would be a silent reseed. Value-semantics
    # threefry makes init a function of (key, shape) alone; restored to
    # the ambient setting right after (serving RNG is untouched).
    old_threefry = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        with mesh, nn.logical_axis_rules(rules):
            state = jax.jit(_init, out_shardings=shardings)(rng)
            # Unbox flax logical-partitioning metadata for downstream use.
            return nn.meta.unbox(state)
    finally:
        jax.config.update("jax_threefry_partitionable", old_threefry)


def make_train_step(
    model: nn.Module,
    mesh: jax.sharding.Mesh,
    rules: Rules = DEFAULT_RULES,
    loss_fn: Callable | None = None,
    model_kwargs: dict | None = None,
    loss_impl: str = "full",
    loss_chunk: int = 1024,
    pipeline: dict | None = None,
    accum_steps: int = 1,
    trainable: str | None = None,
    fsdp=None,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Build the jitted train step for a causal-LM-style batch:
      batch = {"inputs": [B,S] int32, "targets": [B,S] int32,
               "mask": optional [B,S]}
    Returns (new_state, metrics) with donated state.

    loss_impl="chunked" computes cross entropy blockwise against the
    unembedding (model must support return_hidden) — the [B·S, V] fp32
    logits buffer never materializes; each chunk's gradient is taken with
    its logits in the forward pass, none is recomputed
    (chunked_cross_entropy).

    pipeline={"microbatches": M, "chunks": C}: run the trunk through the
    compiled pipeline schedule over the `pipe` mesh axis
    (models/llama_pp.py) instead of model.apply — params stay in the
    scanned-Llama layout (leading `layers` dim, sharded over `pipe` by the
    "pipeline" rules); GPipe when C == 1, interleaved circular otherwise.

    accum_steps > 1 scans the loss+grad over accum_steps row-slices of the
    batch, averaging grads before the (single) optimizer update — identical
    optimizer math to the full batch at 1/accum_steps the activation
    memory (the reference SDK's gradient_accumulation_steps). The
    accumulator carries the master dtype (fp32) and the scan adds in
    microbatch order — deterministic, so K x (B/K) reproduces 1 x B.

    fsdp (a prepared parallel/fsdp.FSDP plan): the state holds fp32
    master shards; each (micro)batch's forward starts from
    fsdp.gather_params — cast to the compute dtype, then all-gather into
    the rules-derived compute layout, both inside the jitted step so XLA
    overlaps the gathers with compute — and grads flow back through the
    same pair as master-layout fp32 reduce(-scatter)s."""
    model_kwargs = model_kwargs or {}
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if loss_impl not in ("full", "chunked"):
        raise ValueError(f"loss_impl {loss_impl!r}: full | chunked")
    if loss_impl == "chunked" and loss_fn is not None:
        raise ValueError("loss_impl='chunked' implies the built-in LM loss")
    if loss_chunk < 1:
        raise ValueError(f"loss_chunk must be >= 1, got {loss_chunk}")
    if pipeline is not None:
        if mesh.shape["pipe"] < 2:
            raise ValueError(
                "pipeline train step needs a mesh with pipe >= 2 "
                f"(got {mesh.shape['pipe']})")
        if not hasattr(model, "cfg") or not getattr(
                model.cfg, "scan_layers", False):
            raise ValueError(
                "pipeline parallelism needs the scanned Llama-family "
                "model (params with a leading 'layers' dim)")
        if loss_fn is not None:
            raise ValueError("pipeline implies the built-in LM loss")
        if model_kwargs.get("ring_axis") is not None:
            raise ValueError(
                "pipeline parallelism doesn't take ring_axis — pass "
                "pipeline={'seq_axis': ...} for context parallelism "
                "inside the pipeline")
        static_packed = {"segment_ids", "positions"} & set(model_kwargs)
        if any(model_kwargs.get(k) is not None for k in static_packed):
            # The pipeline path reads packed metadata from the BATCH
            # (pipeline_loss); silently ignoring static model_kwargs here
            # would train with arange positions and no document masking.
            raise ValueError(
                f"pipeline parallelism takes {static_packed} from the "
                "batch (packed_lm loader), not from model_kwargs")

    def pipeline_loss(params, batch):
        from kubeflow_tpu.models.llama_pp import pipeline_forward

        hidden = loss_impl == "chunked"
        # Packed batches (data/loader.py) carry per-document restarting
        # positions + segment ids; they travel the pipeline ring with the
        # activations so stage attention masks within documents.
        out = pipeline_forward(
            model.cfg, params, batch["inputs"], mesh=mesh,
            num_microbatches=int(pipeline["microbatches"]),
            num_chunks=int(pipeline.get("chunks", 1)),
            return_hidden=hidden,
            positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"),
            seq_axis=pipeline.get("seq_axis"))
        aux = jnp.zeros((), jnp.float32)
        if isinstance(out, tuple):
            # MoE-PP: the Switch load-balance aux rides out of the
            # pipeline (per-microbatch statistic, see pipeline_forward).
            out, raw_aux = out
            aux = model.cfg.router_aux_coef * raw_aux
        if hidden:
            head, vocab_major = _unembed_head(params)
            main = chunked_cross_entropy(
                out, head, batch["targets"], batch.get("mask"),
                chunk=loss_chunk, head_is_vocab_major=vocab_major,
                final_softcap=getattr(model.cfg, "final_softcap", 0.0))
        else:
            main = cross_entropy_loss(out, batch["targets"],
                                      batch.get("mask"))
        return main + aux, {"aux_loss": aux}

    def compute_loss(params, batch):
        # mutable=["aux_loss"]: MoE routers sow load-balance penalties there
        # (models/moe.py); dense models leave it empty. "counters": scalars
        # a model wants on the log rows (models/kimi_linear.py sows its
        # routing counters there); they ride beside aux_loss.
        kwargs = dict(model_kwargs)
        # Packed-sequence batches carry their own segment ids and
        # per-segment restarting positions (models honor both; the fused
        # kernel masks across segment boundaries).
        if "segment_ids" in batch:
            kwargs["segment_ids"] = batch["segment_ids"]
        if "positions" in batch:
            kwargs["positions"] = batch["positions"]
        if loss_impl == "chunked":
            kwargs["return_hidden"] = True
        out, mutated = model.apply(
            {"params": params}, batch["inputs"],
            mutable=["aux_loss", "counters"], **kwargs)
        if loss_impl == "chunked":
            head, vocab_major = _unembed_head(params)
            main = chunked_cross_entropy(
                out, head, batch["targets"], batch.get("mask"),
                chunk=loss_chunk, head_is_vocab_major=vocab_major,
                final_softcap=getattr(model.cfg, "final_softcap", 0.0))
        else:
            logits = out
            if isinstance(logits, tuple):  # models returning (hidden, logits)
                logits = logits[-1]
            if loss_fn is not None:
                main = loss_fn(logits, batch)
            else:
                main = cross_entropy_loss(logits, batch["targets"],
                                          batch.get("mask"))
        aux = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(mutated.get("aux_loss", {})):
            aux = aux + jnp.sum(leaf)
        counters = {name: jnp.sum(jnp.stack(sown)).astype(jnp.float32)
                    for name, sown in mutated.get("counters", {}).items()}
        return main + aux, {"aux_loss": aux, **counters}

    def constrain_batch(x):
        # dim 0 is always the batch; dim 1 is the sequence only for
        # token-like integer arrays — float features (e.g. MLP inputs
        # [B, 784]) must not be sharded over the seq axis.
        axes: tuple = ("batch",)
        if x.ndim >= 2 and jnp.issubdtype(x.dtype, jnp.integer):
            axes = ("batch", "act_seq")
        return nn.with_logical_constraint(x, axes + (None,) * (x.ndim - len(axes)))

    loss_impl_fn = pipeline_loss if pipeline is not None else compute_loss
    if trainable not in (None, "lora"):
        raise ValueError(f"trainable {trainable!r}: None | 'lora'")
    if trainable == "lora" and pipeline is not None:
        raise ValueError(
            "LoRA doesn't compose with pipeline parallelism (the stage "
            "forward has no adapter path)")
    if fsdp is not None:
        if pipeline is not None:
            raise ValueError(
                "fsdp master sharding doesn't compose with pipeline "
                "parallelism (stage params keep the scanned pipe layout)")
        if trainable == "lora":
            raise ValueError(
                "fsdp master sharding doesn't compose with trainable="
                "'lora' (the adapter-only optimizer state is the memory "
                "win there)")
        fsdp._require_prepared()
        inner_loss_fn = loss_impl_fn

        def loss_impl_fn(master, b):  # noqa: F811 — deliberate rebind
            return inner_loss_fn(fsdp.gather_params(master), b)

    def loss_and_grads(loss_fn, target, batch):
        """(loss, extras, grads) w.r.t. `target`, with the gradient-
        accumulation scan when accum_steps > 1 — ONE copy of the
        microbatching machinery shared by full fine-tune and LoRA.
        `extras` is the loss function's dict of scalars for the log row
        (`aux_loss` and a model's counters), averaged over microbatches."""
        if accum_steps > 1:
            # Scan over row-slices; the grad carry costs one extra
            # target-sized buffer.
            def split(x):
                if x.shape[0] % accum_steps:
                    raise ValueError(
                        f"batch dim {x.shape[0]} not divisible by "
                        f"accum_steps {accum_steps}")
                return x.reshape(
                    (accum_steps, x.shape[0] // accum_steps) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                mb = jax.tree.map(constrain_batch, mb)
                (mloss, maux), mgrads = jax.value_and_grad(
                    loss_fn, has_aux=True)(target, mb)
                if fsdp is not None:
                    # Keep every partial grad — and therefore the fp32
                    # accumulator carry — in the sharded master layout;
                    # a replicated grad tree would undo the state's
                    # memory win for the duration of the scan.
                    mgrads = fsdp.constrain_master_grads(mgrads)
                gsum, lsum, asum = carry
                return (jax.tree.map(jnp.add, gsum, mgrads), lsum + mloss,
                        jax.tree.map(jnp.add, asum, maux)), None

            zeros = jax.tree.map(jnp.zeros_like, target)
            extras0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, x.dtype), jax.eval_shape(
                    lambda: loss_fn(target, jax.tree.map(
                        lambda x: x[0], micro))[1]))
            (gsum, lsum, asum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32), extras0), micro)
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            return (lsum / accum_steps,
                    jax.tree.map(lambda a: a / accum_steps, asum), grads)
        batch = jax.tree.map(constrain_batch, batch)
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(target, batch)
        if fsdp is not None:
            grads = fsdp.constrain_master_grads(grads)
        return loss, aux, grads

    def lora_step(state: TrainState, batch: dict):
        """Differentiate and update ONLY the adapter leaves: grads and
        optimizer state are adapter-sized (the frozen base never gets a
        grad buffer or Adam moments — the LoRA memory win)."""
        from kubeflow_tpu.train.lora import combine, partition

        train_sub, frozen = partition(dict(state.params))

        def sub_loss(tr, b):
            return loss_impl_fn(combine(tr, frozen), b)

        loss, aux, grads = loss_and_grads(sub_loss, train_sub, batch)
        updates, new_opt = state.tx.update(grads, state.opt_state,
                                           train_sub)
        new_train = optax.apply_updates(train_sub, updates)
        new_state = state.replace(
            step=state.step + 1, params=combine(new_train, frozen),
            opt_state=new_opt)
        return new_state, {"loss": loss, **aux,
                           "grad_norm": optax.global_norm(grads),
                           "step": new_state.step}

    def step(state: TrainState, batch: dict):
        loss, aux, grads = loss_and_grads(loss_impl_fn, state.params, batch)
        new_state = state.apply_gradients(grads)
        gnorm = optax.global_norm(grads)
        return new_state, {"loss": loss, **aux,
                           "grad_norm": gnorm, "step": new_state.step}

    jitted = jax.jit(lora_step if trainable == "lora" else step,
                     donate_argnums=(0,))

    def wrapped(state, batch):
        # Tracing happens on first call, under the mesh + logical-rules
        # contexts so constraints resolve; later calls hit the jit cache.
        with mesh, nn.logical_axis_rules(rules):
            return jitted(state, batch)

    wrapped.jitted = jitted
    return wrapped


def make_eval_step(model: nn.Module, mesh: jax.sharding.Mesh,
                   rules: Rules = DEFAULT_RULES,
                   model_kwargs: dict | None = None):
    model_kwargs = model_kwargs or {}

    def step(params, batch):
        logits = model.apply({"params": params}, batch["inputs"], **model_kwargs)
        if isinstance(logits, tuple):
            logits = logits[-1]
        mask = batch.get("mask")
        loss = cross_entropy_loss(logits, batch["targets"], mask)
        hits = (jnp.argmax(logits, -1) == batch["targets"]).astype(jnp.float32)
        if mask is not None:
            m = mask.astype(jnp.float32)
            acc = jnp.sum(hits * m) / jnp.maximum(jnp.sum(m), 1.0)
        else:
            acc = jnp.mean(hits)
        return {"loss": loss, "accuracy": acc}

    jitted = jax.jit(step)

    def wrapped(params, batch):
        with mesh, nn.logical_axis_rules(rules):
            return jitted(params, batch)

    return wrapped
