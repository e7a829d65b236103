"""Pipeline-parallel Llama: the scanned trunk partitioned over `pipe`.

SURVEY.md §2.6 PP row maps the reference's DeepSpeed/Megatron pipeline
engines (p2p microbatch send/recv inside user containers) to a compiled
stage-sharded schedule. parallel/pipeline.py provides the schedules (GPipe +
interleaved circular, AD straight through); this module binds them to the
REAL flagship model:

  * **Same parameter pytree as the scanned Llama** (models/llama.py with
    `scan_layers=True`): trunk leaves carry a leading `layers` dim L. PP is
    a *rules* change — logical axis `layers` maps to mesh axis `pipe`
    (sharding.py "pipeline" preset) — plus a reshape [L, ...] ->
    [stages, L/stages, ...] inside the step. Checkpoints, HF import, and
    the single-path model stay bit-identical; no second weight format.
  * **Embed / final-norm / unembed ride GSPMD outside the shard_map**: the
    pipeline region covers exactly the homogeneous trunk (constant
    activation shape), which is what the schedule requires; the vocab-sized
    ends keep their usual tensor/fsdp sharding rules and gradients
    all-reduce over `data` automatically.
  * **Per-layer forward is pure jnp** (no flax apply): inside the manual
    shard_map region, flax's logical-constraint machinery would try to
    issue auto-sharding constraints, which don't compose with manual axes.
    The math matches DecoderLayer exactly (RMSNorm fp32, RoPE fp32, GQA
    attention, SwiGLU in cfg.dtype).

Packed pre-training composes with PP: pass `positions` + `segment_ids`
and they ride the pipeline ring alongside the activations (a pytree
microbatch — parallel/pipeline.py), so each stage masks attention within
documents exactly like the scanned model. Block-sparse MaskSpecs
(cfg.mask_kind) flow into the stage attention the same way.

CP composes INSIDE the pipeline (`seq_axis`): traveling activations shard
their sequence dim over `seq` and stage attention runs the ring schedule
(position-masked einsum ring for 'naive', fused offset-case ring for
'flash') — ops/ring_attention.py manual bodies, callable because the
`seq` axis is part of the pipeline's own shard_map region. Packed
segment masks compose too (round 5): segs travel the pipeline AND rotate
the stage ring with K/V, on the einsum ring. MaskSpec families still
need the non-CP pipeline.

MoE composes too: a scanned MoELlama tree pipelines with expert weights
sharded over `expert` (_moe_ffn — EP's combine-psum inside the stage
region); MoE-PP and CP-inside-PP are mutually exclusive (expert capacity
is a global-sequence statistic).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_tpu.models.llama import LlamaConfig, apply_rope, rope_table
from kubeflow_tpu.ops.reference import naive_attention
from kubeflow_tpu.parallel.pipeline import (
    pipeline_apply, pipeline_apply_circular)
from kubeflow_tpu.utils.devices import on_tpu


def _rms(x: jax.Array, scale: jax.Array, eps: float, dtype,
         plus_one: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if plus_one:  # Gemma stores zero-centered scales, applied as (1 + w)
        scale = 1.0 + scale
    return (y * scale).astype(dtype)


def _resolve_attn(cfg: LlamaConfig) -> str:
    impl = cfg.attention_impl
    if impl == "auto":
        return "flash" if on_tpu() else "naive"
    if impl not in ("naive", "flash"):
        raise ValueError(
            f"pipeline parallelism supports attention_impl 'naive'/'flash' "
            f"(contiguous or packed causal sequences), not {impl!r}")
    return impl


def layer_fwd(cfg: LlamaConfig, lp: dict, x: jax.Array, cos: jax.Array,
              sin: jax.Array, positions: jax.Array,
              attn_impl: str = "naive",
              segment_ids: jax.Array | None = None,
              ring: tuple[str, int] | None = None,
              expert: tuple[str, int] | None = None,
              ) -> tuple[jax.Array, jax.Array]:
    """One decoder layer, pure jnp. lp: the layer's param subtree (kernels
    exactly as flax lays them out: q/k/v [H, heads, D], o [heads, D, H],
    gate/up [H, M], down [M, H]); x [mb, S, H] in cfg.dtype.
    `segment_ids` [mb, S] confines attention within packed documents;
    cfg.mask_spec selects the block-sparse mask family — both match the
    scanned Attention module's semantics (models/llama.py).

    `ring=(axis_name, n)`: context parallelism INSIDE the pipeline stage —
    x/positions arrive seq-sharded over the `axis_name` mesh axis (the
    enclosing shard_map region includes it) and attention runs the ring
    schedule over that axis (ops/ring_attention.py manual bodies).

    Returns (x, aux): aux is the layer's Switch load-balance statistic for
    routed-expert FFNs (`expert=(axis, n)` shards them), 0 for dense."""
    dt = cfg.dtype
    h = _rms(x, lp["input_norm"]["scale"], cfg.rms_eps, dt,
             cfg.norm_plus_one)
    q = jnp.einsum("bsh,hnd->bsnd", h, lp["attn"]["q_proj"]["kernel"].astype(dt))
    k = jnp.einsum("bsh,hnd->bsnd", h, lp["attn"]["k_proj"]["kernel"].astype(dt))
    v = jnp.einsum("bsh,hnd->bsnd", h, lp["attn"]["v_proj"]["kernel"].astype(dt))
    if "bias" in lp["attn"]["q_proj"]:  # Qwen2-family QKV biases
        q = q + lp["attn"]["q_proj"]["bias"].astype(dt)
        k = k + lp["attn"]["k_proj"]["bias"].astype(dt)
        v = v + lp["attn"]["v_proj"]["bias"].astype(dt)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    mask = cfg.mask_spec
    if ring is not None:
        from kubeflow_tpu.ops.ring_attention import (
            ring_attention_flash_manual, ring_attention_manual)
        if mask is not None:
            raise ValueError(
                "ring attention inside the pipeline stage is causal-only "
                "(no MaskSpec families)")
        if attn_impl == "flash":
            # Contiguous layout: shard r owns positions [r*s_loc, ...), so
            # causality comes from ring offsets (fused Pallas inner).
            # Packed batches take the einsum ring (pipeline_forward
            # downgrades the impl) — the fused ring has no segment mask.
            if segment_ids is not None:
                raise ValueError(
                    "the fused ring has no segment mask; packed "
                    "CP-inside-PP uses the einsum ring (attn 'naive')")
            attn = ring_attention_flash_manual(
                q, k, v, ring[0], ring[1],
                block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv)
        else:
            # Position+segment-masked einsum ring: exact for packed
            # documents — segs rotate with K/V.
            attn = ring_attention_manual(q, k, v, positions, *ring,
                                         segment_ids=segment_ids)
    elif attn_impl == "flash":
        from kubeflow_tpu.ops.flash_attention import flash_attention
        attn = flash_attention(q, k, v, causal=True,
                               block_q=cfg.flash_block_q,
                               block_kv=cfg.flash_block_kv,
                               segment_ids=segment_ids, mask=mask)
    else:
        attn = naive_attention(q, k, v, causal=True, positions_q=positions,
                               positions_kv=positions,
                               segment_ids=segment_ids, mask=mask)
    attn = jnp.einsum("bsnd,ndh->bsh", attn,
                      lp["attn"]["o_proj"]["kernel"].astype(dt))
    x = x + attn
    h2 = _rms(x, lp["post_attn_norm"]["scale"], cfg.rms_eps, dt,
              cfg.norm_plus_one)
    if "router" in lp["mlp"]:
        y, aux = _moe_ffn(cfg, lp["mlp"], h2, expert)
        return x + y, aux
    gate = h2 @ lp["mlp"]["gate_proj"]["kernel"].astype(dt)
    up = h2 @ lp["mlp"]["up_proj"]["kernel"].astype(dt)
    if cfg.mlp_act == "silu":
        act = jax.nn.silu(gate)
    elif cfg.mlp_act == "gelu_tanh":  # Gemma's GeGLU gate
        act = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(f"mlp_act {cfg.mlp_act!r}: silu | gelu_tanh")
    y = (act * up) @ lp["mlp"]["down_proj"]["kernel"].astype(dt)
    return x + y, jnp.zeros((), jnp.float32)


def _moe_ffn(cfg, mp: dict, h2: jax.Array,
             expert: tuple[str, int] | None):
    """Routed-expert FFN for the pipeline stage (MoE-PP), pure jnp. mp:
    router [H, E] (replicated over `expert`), w_gate/w_up [E_loc, H, M],
    w_down [E_loc, M, H] — the LOCAL expert slice when the enclosing
    shard_map shards the expert dim. Routing math is the shared
    gshard_route (models/moe.py), so dispatch/combine/aux cannot drift
    from the scanned MoEBlock. With expert=(axis, n): every rank computes
    the full dispatch from its (replicated-over-expert) activations,
    slices its experts, and the combine psums partial outputs — the EP
    collective pattern inside the pipeline region."""
    from kubeflow_tpu.models.moe import expert_capacity, gshard_route

    dt = cfg.dtype
    s = h2.shape[1]
    C = expert_capacity(cfg, s)
    dispatch, combine, aux = gshard_route(
        h2, mp["router"], cfg.experts_per_token, C,
        renormalize=getattr(cfg, "norm_topk_prob", True))
    e_loc = mp["w_gate"].shape[0]
    if expert is not None and expert[1] > 1:
        start = jax.lax.axis_index(expert[0]) * e_loc
        dispatch = jax.lax.dynamic_slice_in_dim(dispatch, start, e_loc, 2)
        combine = jax.lax.dynamic_slice_in_dim(combine, start, e_loc, 2)
    xin = jnp.einsum("bsec,bsh->ebch", dispatch.astype(dt), h2.astype(dt))
    g = jnp.einsum("ebch,ehm->ebcm", xin, mp["w_gate"].astype(dt))
    u = jnp.einsum("ebch,ehm->ebcm", xin, mp["w_up"].astype(dt))
    hh = jax.nn.silu(g) * u
    out = jnp.einsum("ebcm,emh->ebch", hh, mp["w_down"].astype(dt))
    y = jnp.einsum("bsec,ebch->bsh", combine.astype(dt), out)
    if expert is not None and expert[1] > 1:
        y = jax.lax.psum(y, expert[0])
    if "w_shared_gate" in mp:
        # Qwen2-MoE shared expert (replicated over `expert`) — the ONE
        # definition in models/moe.py, same as MoEBlock.
        from kubeflow_tpu.models.moe import shared_expert_ffn

        y = y + shared_expert_ffn(h2, mp["w_shared_gate"],
                                  mp["w_shared_up"], mp["w_shared_down"],
                                  mp["shared_gate"], dt)
    return y.astype(dt), aux


def pipeline_forward(
    cfg: LlamaConfig,
    params: Any,
    tokens: jax.Array,
    *,
    mesh,
    num_microbatches: int,
    num_chunks: int = 1,
    data_axis: str | tuple[str, ...] | None = ("data", "fsdp"),
    return_hidden: bool = False,
    positions: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    seq_axis: str | None = None,
    expert_axis: str = "expert",
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Full causal-LM forward with the trunk pipelined over `pipe`.

    params: the SAME pytree the scanned Llama produces (trunk under
    params['layers'] with leading dim L). tokens [B, S]. Returns logits
    [B, S, V] (or post-norm hidden [B, S, H] with return_hidden for the
    chunked-CE path). Numerics match the non-pipelined model.

    MoE-PP: a scanned MoELlama param tree (models/moe.py — layer FFNs are
    routed experts) pipelines the same way; expert weights additionally
    shard over `expert_axis` when the mesh has it (>1), with the combine
    psum as the EP collective inside the pipeline region. Returns
    (out, aux) — the Switch load-balance aux averaged per (microbatch x
    data shard), the standard microbatched-routing statistic (it matches
    the scanned model's global-batch aux only at one microbatch/shard;
    logits match exactly regardless, routing is per-row).

    Packed pre-training: pass per-document restarting `positions` and
    `segment_ids` [B, S] (data/loader.py packing) — they microbatch and
    travel the pipeline ring with the activations, so every stage applies
    the same RoPE offsets and within-document attention mask the scanned
    model would.

    Context parallelism inside the pipeline (`seq_axis`): the traveling
    activations shard their SEQUENCE dim over `seq_axis` (in addition to
    microbatch rows over `data_axis`), and each stage's attention runs the
    ring schedule over that axis — PP x CP composition for long sequences
    (SURVEY §5.7 x §2.6). Contiguous layout; attn 'naive' uses the
    position-masked einsum ring (exact), 'flash' the fused offset-case
    ring. Packed batches compose: segment_ids shard with the sequence and
    rotate the stage ring alongside K/V (einsum ring — the impl
    auto-downgrades from 'flash'). MaskSpec families still refuse."""
    if cfg.num_layers % (mesh.shape["pipe"] * num_chunks):
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pipe "
            f"({mesh.shape['pipe']}) * chunks ({num_chunks})")
    if (getattr(cfg, "sliding_pattern", "all") != "all"
            or getattr(cfg, "qk_norm", False)
            or getattr(cfg, "rope_theta_local", 0)
            or getattr(cfg, "attn_softcap", 0)):
        # The stage body applies ONE attention recipe to every layer it
        # scans — per-layer kinds (Gemma-2/3 alternating windows, dual
        # rope bases) and the softcap/qk-norm score transforms would be
        # silently wrong, not slow. Train those families on the scanned
        # model.
        raise ValueError(
            "pipeline parallelism doesn't implement per-layer attention "
            "kinds or Gemma-2/3 score transforms (alternating windows / "
            "dual rope bases / qk_norm / softcap) — use the scanned "
            "model")
    attn_impl = _resolve_attn(cfg)
    ring = None
    if seq_axis is not None and mesh.shape[seq_axis] > 1:
        n_seq = mesh.shape[seq_axis]
        if segment_ids is not None:
            # Packed documents x CP-inside-PP: segment ids shard with the
            # sequence and rotate around the stage ring with K/V — exact
            # on the position+segment-masked einsum ring only (the fused
            # ring derives causality from layout, not positions).
            attn_impl = "naive"
        if cfg.mask_spec is not None:
            raise ValueError(
                f"CP-inside-PP is causal-only; mask_kind={cfg.mask_kind!r} "
                "needs the non-CP pipeline or the scanned model")
        if tokens.shape[1] % n_seq:
            raise ValueError(
                f"seq len {tokens.shape[1]} not divisible by seq axis "
                f"({n_seq})")
        if attn_impl == "flash" and positions is not None:
            raise ValueError(
                "CP-inside-PP flash ring derives causality from the "
                "contiguous layout; custom positions need 'naive'")
        ring = (seq_axis, n_seq)
    if (attn_impl == "flash" and positions is not None
            and segment_ids is None):
        # Mirror the scanned Attention's refusal: the flash kernel masks
        # causality by array index, so custom positions need the segment
        # mask to carry document structure.
        raise ValueError(
            "pipeline flash attention with custom positions needs "
            "segment_ids (packed sequences)")
    dt = cfg.dtype
    b, s = tokens.shape
    embed = params["embed"]
    x = embed.astype(dt)[tokens]
    if cfg.embed_scale:  # Gemma: sqrt(hidden) input scaling
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, dt)
    cos, sin = rope_table(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta, cfg)

    is_moe = "router" in params["layers"]["mlp"]
    expert = None
    if is_moe:
        if ring is not None:
            raise ValueError(
                "MoE-PP doesn't compose with CP-inside-PP (seq_axis) — "
                "expert capacity is a global-sequence statistic")
        n_exp = mesh.shape.get(expert_axis, 1)
        if n_exp > 1:
            if cfg.num_experts % n_exp:
                raise ValueError(
                    f"num_experts {cfg.num_experts} not divisible by "
                    f"mesh axis {expert_axis!r} ({n_exp})")
            expert = (expert_axis, n_exp)

    n_stages = mesh.shape["pipe"] * num_chunks
    per_stage = cfg.num_layers // n_stages
    stages = jax.tree.map(
        lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]),
        params["layers"])
    # MoE expert weights shard their expert dim over `expert_axis`; the
    # router (and everything else) replicates over it.
    param_specs = None
    if expert is not None:
        param_specs = jax.tree.map(lambda _: None, stages)
        # Leaves are [n_stages, per_stage, E, ...]: entry 1 (per_stage)
        # replicates, entry 2 (experts) shards over the expert axis.
        param_specs["mlp"] = {
            k: ((None, expert_axis) if k in ("w_gate", "w_up", "w_down")
                else None)
            for k in stages["mlp"]}

    # The traveling microbatch: activations plus any packed metadata the
    # stages need (pipeline_apply treats the pytree opaquely).
    travel = {"h": x}
    if positions is not None or ring is not None:
        pos_in = (positions if positions is not None
                  else jnp.arange(s, dtype=jnp.int32)[None])
        travel["pos"] = jnp.broadcast_to(pos_in, (b, s))
    if segment_ids is not None:
        travel["seg"] = jnp.broadcast_to(segment_ids, (b, s))
    if is_moe:
        # Per-row aux accumulator: every row of a microbatch carries the
        # stage-summed Switch aux (identical values within a microbatch
        # x data shard) — a [mb] leaf rides the ring like everything else.
        travel["aux"] = jnp.zeros((b,), jnp.float32)
    # CP-inside-PP: sequence dims of the traveling leaves shard over the
    # seq axis; positions ALWAYS travel so each shard carries its global
    # offsets (RoPE + ring causal masking).
    travel_specs = None
    if ring is not None:
        travel_specs = {k: ((seq_axis, None) if k == "h" else (seq_axis,))
                        for k in travel}

    def stage_fn(sp, tr):
        h = tr["h"]
        pos = tr.get("pos")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(s), (h.shape[0], s))
        seg = tr.get("seg")

        def body(carry, lp):
            hh, aux = carry
            hh, a = layer_fwd(cfg, lp, hh, cos, sin, pos, attn_impl,
                              segment_ids=seg, ring=ring, expert=expert)
            return (hh, aux + a), None

        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), sp)
        out = {**tr, "h": h}
        if "aux" in tr:
            out["aux"] = tr["aux"] + aux
        return out

    axes = ((data_axis,) if isinstance(data_axis, str)
            else tuple(data_axis or ()))
    dax = tuple(a for a in axes if mesh.shape[a] > 1) or None
    if dax is not None and len(dax) == 1:
        dax = dax[0]
    if num_chunks > 1:
        out = pipeline_apply_circular(
            stage_fn, stages, travel, mesh=mesh,
            num_microbatches=num_microbatches, num_chunks=num_chunks,
            data_axis=dax, travel_specs=travel_specs,
            param_specs=param_specs)
    else:
        out = pipeline_apply(
            stage_fn, stages, travel, mesh=mesh,
            num_microbatches=num_microbatches, data_axis=dax,
            travel_specs=travel_specs, param_specs=param_specs)
    x = out["h"]

    x = _rms(x, params["final_norm"]["scale"], cfg.rms_eps, dt,
             cfg.norm_plus_one)
    if return_hidden:
        result = x
    elif cfg.tie_embeddings:
        result = jnp.einsum("bsh,vh->bsv", x, embed.astype(dt))
    else:
        result = x @ params["lm_head"]["kernel"].astype(dt)
    if is_moe:
        # Rows within a (microbatch x data shard) carry identical values;
        # the global mean IS the mean over those sub-batches.
        return result, jnp.mean(out["aux"])
    return result
