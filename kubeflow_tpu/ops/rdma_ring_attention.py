"""RDMA ring attention — K/V rotation by remote DMA inside one kernel.

The lax-level rings (ops/ring_attention.py) rotate K/V with
`jax.lax.ppermute` between per-step compute calls and rely on XLA to
overlap the collective with compute. This kernel makes the overlap
EXPLICIT (pallas_guide.md ring-collectives pattern, ops/ROADMAP.md item):
one Pallas program per device owns a double-buffered K/V scratch, STARTS
the remote copy of the current buffer to the right neighbour, computes
attention against it while the DMA flies, then waits the incoming buffer.

Backpressure is DMA-based: after finishing compute on a slot, a device
sends a tiny "slot free" ack to its LEFT neighbour (the one that writes
into its buffers); a sender waits that ack before overwriting a slot the
receiver may still be reading. Two slots + acks give lockstep-free
pipelining with bounded VMEM — the kernel never materialises more than
2 K/V shards.

Causality is masked by global positions (shard offset + row index), so
every ring step is one masked flash-style block — no cross-step state
besides the online-softmax partials.

The backward is fused too (ops/ROADMAP.md item 1, landed round 3): a
two-pass design where every traveling payload is READ-ONLY, so the DMA
overlaps compute exactly like the forward —
  * pass 1 (dq): K/V rotate (read-only), each device accumulates its
    resident dq from saved (lse, delta) row stats;
  * pass 2 (dk/dv): q/dout/lse/delta rotate (read-only), each device
    accumulates its RESIDENT dk/dv — no traveling accumulator, so no
    post-compute copy serialization and no final homing rotation.
Forward saves lse when under AD (`save_lse`); delta = rowsum(dout·out) is
computed at the lax level inside the shard_map region.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P
from jax import shard_map

from kubeflow_tpu.parallel.mesh import current_mesh
from kubeflow_tpu.utils.devices import on_tpu

NEG_INF = -1e30


def _rdma_kernel(q_ref, k_ref, v_ref, o_ref, *rest, n: int, axis: str,
                 bkh: int, group: int, s: int, d: int, sm_scale: float,
                 save_lse: bool = False):
    """q_ref [bkh*group, s, d]; k/v_ref [bkh, s, d]; o_ref like q.
    kvbuf [2, 2, bkh, s, d] (slot, k|v, head, row, d); ackbuf [2, 1, 128].
    All VMEM. n = ring size (static); unrolled python loop. With
    `save_lse`, also writes lse [bkh, group*s, 1] f32 (AD residual)."""
    if save_lse:
        lse_ref, kvbuf, ackbuf, dsend, drecv, asend, arecv = rest
    else:
        kvbuf, ackbuf, dsend, drecv, asend, arecv = rest
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me + n - 1, n)

    # Slot 0 starts as the local shard.
    kvbuf[0, 0] = k_ref[...]
    kvbuf[0, 1] = v_ref[...]

    rows = jax.lax.broadcasted_iota(jnp.int32, (group * s, s), 0)
    rows = jax.lax.rem(rows, s) + me * s  # global q positions per head row
    cols_local = jax.lax.broadcasted_iota(jnp.int32, (group * s, s), 1)

    # Per-kv-head online-softmax partials (python lists: bkh is static).
    accs = [jnp.zeros((group * s, d), jnp.float32) for _ in range(bkh)]
    ms = [jnp.full((group * s, 1), NEG_INF, jnp.float32) for _ in range(bkh)]
    ls = [jnp.zeros((group * s, 1), jnp.float32) for _ in range(bkh)]

    for i in range(n):
        cur, nxt = i % 2, (i + 1) % 2
        data_copy = None
        if i < n - 1:
            if i >= 1:
                # Right must have freed slot `nxt` (its compute i-1 done).
                pltpu.make_async_remote_copy(
                    src_ref=ackbuf.at[nxt], dst_ref=ackbuf.at[nxt],
                    send_sem=asend.at[nxt], recv_sem=arecv.at[nxt],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL).wait_recv()
            data_copy = pltpu.make_async_remote_copy(
                src_ref=kvbuf.at[cur], dst_ref=kvbuf.at[nxt],
                send_sem=dsend.at[nxt], recv_sem=drecv.at[nxt],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            data_copy.start()

        # The resident buffer originated at shard (me - i) mod n.
        src = jax.lax.rem(me + n - i, n)
        cols = cols_local + src * s
        mask = rows >= cols
        # q is laid out [bkh, group*s, d] (_rdma_fwd), so each kv head's
        # queries are one contiguous 2-D block.
        for h in range(bkh):
            qh = q_ref[h].astype(jnp.float32) * sm_scale      # [group*s, d]
            kh = kvbuf[cur, 0, h].astype(jnp.float32)         # [s, d]
            vh = kvbuf[cur, 1, h].astype(jnp.float32)
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [group*s, s]
            sc = jnp.where(mask, sc, NEG_INF)
            m_new = jnp.maximum(ms[h], jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(ms[h] - m_new)
            ls[h] = ls[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            accs[h] = accs[h] * alpha + jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ms[h] = m_new

        if i < n - 1:
            data_copy.wait_send()  # outgoing read of `cur` complete...
        if i <= n - 3:
            # ...so LEFT may now overwrite my `cur` slot: ack it.
            ack = pltpu.make_async_remote_copy(
                src_ref=ackbuf.at[cur], dst_ref=ackbuf.at[cur],
                send_sem=asend.at[cur], recv_sem=arecv.at[cur],
                device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            ack.start()
            ack.wait_send()
        if i < n - 1:
            data_copy.wait_recv()  # incoming `nxt` from LEFT has landed

    for h in range(bkh):
        o_ref[h] = (accs[h] / jnp.maximum(ls[h], 1e-30)).astype(o_ref.dtype)
        if save_lse:
            lse_ref[h] = ms[h] + jnp.log(jnp.maximum(ls[h], 1e-30))


def _rdma_fwd(q, k, v, axis_name, mesh, n, interpret, save_lse=False):
    b, s_glob, h, d = q.shape
    kh = k.shape[2]
    group = h // kh

    # Specs adapt to the mesh's axes (shared rule with the lax-level
    # rings): the full framework mesh shards batch over (data, fsdp); a
    # dedicated single-axis ring mesh (the only shape the INTERPRET
    # path's DMA discharge supports — compiled Mosaic has no such limit)
    # leaves batch replicated.
    from kubeflow_tpu.ops.ring_attention import _batch_spec

    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)
    spec3 = P(_batch_spec(mesh, axis_name), axis_name, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=((spec, spec3) if save_lse else spec), check_vma=False)
    def _run(q, k, v):
        bl, s, _, _ = q.shape  # local shapes
        bkh = bl * kh
        # Layout: one contiguous [group*s, d] q block per kv head.
        q3 = q.transpose(0, 2, 1, 3).reshape(bl, kh, group, s, d)
        q3 = q3.reshape(bkh, group * s, d)
        k3 = k.transpose(0, 2, 1, 3).reshape(bkh, s, d)
        v3 = v.transpose(0, 2, 1, 3).reshape(bkh, s, d)
        kernel = functools.partial(
            _rdma_kernel, n=n, axis=axis_name, bkh=bkh, group=group, s=s,
            d=d, sm_scale=1.0 / (d ** 0.5), save_lse=save_lse)
        out_shape = jax.ShapeDtypeStruct((bkh, group * s, d), q.dtype)
        if save_lse:
            out_shape = (out_shape, jax.ShapeDtypeStruct(
                (bkh, group * s, 1), jnp.float32))
        res = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((2, 2, bkh, s, d), k.dtype),
                pltpu.VMEM((2, 1, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(collective_id=7),
        )(q3, k3, v3)
        o3 = res[0] if save_lse else res
        out = o3.reshape(bl, kh, group, s, d).transpose(0, 3, 1, 2, 4)
        out = out.reshape(bl, s, h, d)
        if not save_lse:
            return out
        lse = res[1].reshape(bl, kh, group, s).transpose(0, 3, 1, 2)
        return out, lse.reshape(bl, s, h)

    return _run(q, k, v)


def _rdma_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, kvbuf, ackbuf, dsend, drecv, asend, arecv,
                        *, n, axis, bkh, group, s, d, sm_scale):
    """Backward pass 1: K/V rotate exactly as in the forward (read-only
    payload ⇒ full DMA/compute overlap); resident dq accumulates from the
    saved row stats. q/do [bkh, group*s, d]; lse/delta [bkh, group*s, 1]."""
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me + n - 1, n)
    kvbuf[0, 0] = k_ref[...]
    kvbuf[0, 1] = v_ref[...]

    rows = jax.lax.broadcasted_iota(jnp.int32, (group * s, s), 0)
    rows = jax.lax.rem(rows, s) + me * s
    cols_local = jax.lax.broadcasted_iota(jnp.int32, (group * s, s), 1)
    dqs = [jnp.zeros((group * s, d), jnp.float32) for _ in range(bkh)]

    for i in range(n):
        cur, nxt = i % 2, (i + 1) % 2
        data_copy = None
        if i < n - 1:
            if i >= 1:
                pltpu.make_async_remote_copy(
                    src_ref=ackbuf.at[nxt], dst_ref=ackbuf.at[nxt],
                    send_sem=asend.at[nxt], recv_sem=arecv.at[nxt],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL).wait_recv()
            data_copy = pltpu.make_async_remote_copy(
                src_ref=kvbuf.at[cur], dst_ref=kvbuf.at[nxt],
                send_sem=dsend.at[nxt], recv_sem=drecv.at[nxt],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            data_copy.start()

        src = jax.lax.rem(me + n - i, n)
        mask = rows >= cols_local + src * s
        for h in range(bkh):
            qh = q_ref[h].astype(jnp.float32) * sm_scale
            doh = do_ref[h].astype(jnp.float32)
            kh = kvbuf[cur, 0, h].astype(jnp.float32)
            vh = kvbuf[cur, 1, h].astype(jnp.float32)
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            sc = jnp.where(mask, sc, NEG_INF)
            p = jnp.exp(sc - lse_ref[h])                       # [gs, s]
            dp = jax.lax.dot_general(
                doh, vh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[h])
            dqs[h] = dqs[h] + jax.lax.dot_general(
                ds, kh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale

        if i < n - 1:
            data_copy.wait_send()
        if i <= n - 3:
            ack = pltpu.make_async_remote_copy(
                src_ref=ackbuf.at[cur], dst_ref=ackbuf.at[cur],
                send_sem=asend.at[cur], recv_sem=arecv.at[cur],
                device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            ack.start()
            ack.wait_send()
        if i < n - 1:
            data_copy.wait_recv()

    for h in range(bkh):
        dq_ref[h] = dqs[h].astype(dq_ref.dtype)


def _rdma_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, qbuf, statbuf, ackbuf,
                         qsend, qrecv, ssend, srecv, asend, arecv,
                         *, n, axis, bkh, group, s, d, sm_scale):
    """Backward pass 2: q/dout (qbuf) and lse/delta (statbuf) rotate —
    all read-only — while RESIDENT dk/dv accumulate. No traveling
    accumulator ⇒ no post-compute copy serialization, no homing step."""
    me = jax.lax.axis_index(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me + n - 1, n)
    qbuf[0, 0] = q_ref[...]
    qbuf[0, 1] = do_ref[...]
    statbuf[0, 0] = lse_ref[...]
    statbuf[0, 1] = delta_ref[...]

    qrows_local = jax.lax.broadcasted_iota(jnp.int32, (group * s, s), 0)
    qrows_local = jax.lax.rem(qrows_local, s)
    cols = jax.lax.broadcasted_iota(jnp.int32, (group * s, s), 1) + me * s
    dks = [jnp.zeros((s, d), jnp.float32) for _ in range(bkh)]
    dvs = [jnp.zeros((s, d), jnp.float32) for _ in range(bkh)]

    for i in range(n):
        cur, nxt = i % 2, (i + 1) % 2
        q_copy = s_copy = None
        if i < n - 1:
            if i >= 1:
                pltpu.make_async_remote_copy(
                    src_ref=ackbuf.at[nxt], dst_ref=ackbuf.at[nxt],
                    send_sem=asend.at[nxt], recv_sem=arecv.at[nxt],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL).wait_recv()
            q_copy = pltpu.make_async_remote_copy(
                src_ref=qbuf.at[cur], dst_ref=qbuf.at[nxt],
                send_sem=qsend.at[nxt], recv_sem=qrecv.at[nxt],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            s_copy = pltpu.make_async_remote_copy(
                src_ref=statbuf.at[cur], dst_ref=statbuf.at[nxt],
                send_sem=ssend.at[nxt], recv_sem=srecv.at[nxt],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            q_copy.start()
            s_copy.start()

        # The resident q/do block originated at shard (me - i) mod n.
        src = jax.lax.rem(me + n - i, n)
        mask = (qrows_local + src * s) >= cols
        for h in range(bkh):
            qh = qbuf[cur, 0, h].astype(jnp.float32) * sm_scale
            doh = qbuf[cur, 1, h].astype(jnp.float32)
            lse = statbuf[cur, 0, h]
            delta = statbuf[cur, 1, h]
            kh = k_ref[h].astype(jnp.float32)
            vh = v_ref[h].astype(jnp.float32)
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            sc = jnp.where(mask, sc, NEG_INF)
            p = jnp.exp(sc - lse)                              # [gs, s]
            dvs[h] = dvs[h] + jax.lax.dot_general(
                p, doh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                doh, vh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            dks[h] = dks[h] + jax.lax.dot_general(
                ds, qh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if i < n - 1:
            q_copy.wait_send()
            s_copy.wait_send()
        if i <= n - 3:
            ack = pltpu.make_async_remote_copy(
                src_ref=ackbuf.at[cur], dst_ref=ackbuf.at[cur],
                send_sem=asend.at[cur], recv_sem=arecv.at[cur],
                device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            ack.start()
            ack.wait_send()
        if i < n - 1:
            q_copy.wait_recv()
            s_copy.wait_recv()

    for h in range(bkh):
        dk_ref[h] = dks[h].astype(dk_ref.dtype)
        dv_ref[h] = dvs[h].astype(dv_ref.dtype)


def _rdma_bwd(q, k, v, o, lse, g, axis_name, mesh, n, interpret):
    """Fused two-pass backward driver: both passes mirror the forward's
    double-buffered rotation with DMA-ack backpressure; delta is the lax-
    level rowsum(dout·out) computed inside the shard_map region."""
    b, s_glob, h, d = q.shape
    kh = k.shape[2]
    group = h // kh
    from kubeflow_tpu.ops.ring_attention import _batch_spec

    spec = P(_batch_spec(mesh, axis_name), axis_name, None, None)
    spec3 = P(_batch_spec(mesh, axis_name), axis_name, None)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec3, spec),
        out_specs=(spec, spec, spec), check_vma=False)
    def _run(q, k, v, o, lse, g):
        bl, s, _, _ = q.shape
        bkh = bl * kh

        def qlayout(x):
            x3 = x.transpose(0, 2, 1, 3).reshape(bl, kh, group, s, d)
            return x3.reshape(bkh, group * s, d)

        q3, do3, o3 = qlayout(q), qlayout(g), qlayout(o)
        k3 = k.transpose(0, 2, 1, 3).reshape(bkh, s, d)
        v3 = v.transpose(0, 2, 1, 3).reshape(bkh, s, d)
        lse3 = lse.transpose(0, 2, 1).reshape(bl, kh, group, s)
        lse3 = lse3.reshape(bkh, group * s, 1)
        delta3 = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                         axis=-1, keepdims=True)

        common = dict(n=n, axis=axis_name, bkh=bkh, group=group, s=s, d=d,
                      sm_scale=1.0 / (d ** 0.5))
        sems = [pltpu.SemaphoreType.DMA((2,))] * 6
        dq3 = pl.pallas_call(
            functools.partial(_rdma_bwd_dq_kernel, **common),
            out_shape=jax.ShapeDtypeStruct((bkh, group * s, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((2, 2, bkh, s, d), k.dtype),
                pltpu.VMEM((2, 1, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(collective_id=8),
        )(q3, k3, v3, do3, lse3, delta3)
        dk3, dv3 = pl.pallas_call(
            functools.partial(_rdma_bwd_dkv_kernel, **common),
            out_shape=(jax.ShapeDtypeStruct((bkh, s, d), k.dtype),
                       jax.ShapeDtypeStruct((bkh, s, d), v.dtype)),
            scratch_shapes=[
                pltpu.VMEM((2, 2, bkh, group * s, d), q.dtype),
                pltpu.VMEM((2, 2, bkh, group * s, 1), jnp.float32),
                pltpu.VMEM((2, 1, 128), jnp.float32),
                *sems,
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(collective_id=9),
        )(q3, k3, v3, do3, lse3, delta3)

        def unq(x3):
            x = x3.reshape(bl, kh, group, s, d).transpose(0, 3, 1, 2, 4)
            return x.reshape(bl, s, h, d)

        def unkv(x3):
            return x3.reshape(bl, kh, s, d).transpose(0, 2, 1, 3)

        return unq(dq3), unkv(dk3), unkv(dv3)

    return _run(q, k, v, o, lse, g)


def _resolve_ring(axis_name, mesh, interpret):
    """Shared (mesh, n, interpret) resolution for the primal and both VJP
    rules — one place for the backend heuristic and the mesh requirement,
    so forward and backward can't desynchronize."""
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("rdma_ring_attention needs a mesh")
    if interpret is None:
        interpret = not on_tpu()
    return mesh, mesh.shape[axis_name], interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def rdma_ring_attention(q, k, v, axis_name: str = "seq", mesh=None,
                        interpret: bool | None = None):
    """Causal ring attention with in-kernel remote-DMA K/V rotation.
    q [B,S,H,D], k/v [B,S,KH,D] over the `axis_name` ring (contiguous
    layout). Forward runs the fused RDMA kernel (saving lse under AD);
    the backward is the fused two-pass RDMA design as well — K/V rotate
    for resident dq, then q/dout/lse/delta rotate for resident dk/dv —
    so CP training steady-state stays on the in-kernel rotation path."""
    mesh, n, interpret = _resolve_ring(axis_name, mesh, interpret)
    if n == 1:
        from kubeflow_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, True)
    return _rdma_fwd(q, k, v, axis_name, mesh, n, interpret)


def _vjp_fwd(q, k, v, axis_name, mesh, interpret):
    mesh, n, interpret = _resolve_ring(axis_name, mesh, interpret)
    if n == 1:
        from kubeflow_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, True), (q, k, v, None, None)
    out, lse = _rdma_fwd(q, k, v, axis_name, mesh, n, interpret,
                         save_lse=True)
    return out, (q, k, v, out, lse)


def _vjp_bwd(axis_name, mesh, interpret, res, g):
    q, k, v, o, lse = res
    if o is None:  # single-member ring: plain flash attention
        from kubeflow_tpu.ops.flash_attention import flash_attention
        _, pullback = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True), q, k, v)
        return pullback(g)
    mesh, n, interpret = _resolve_ring(axis_name, mesh, interpret)
    return _rdma_bwd(q, k, v, o, lse, g, axis_name, mesh, n, interpret)


rdma_ring_attention.defvjp(_vjp_fwd, _vjp_bwd)
