"""Mesh + sharding-rule engine tests (parallel/)."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh, mesh_shape
from kubeflow_tpu.parallel.sharding import (
    DEFAULT_RULES, logical_to_spec, rules_for, tree_logical_to_sharding)


def test_mesh_wildcard_absorbs_devices(devices8):
    mesh = build_mesh(MeshConfig(data=-1, tensor=2), devices8)
    assert mesh_shape(mesh) == {
        "data": 4, "fsdp": 1, "pipe": 1, "tensor": 2, "seq": 1, "expert": 1}


def test_mesh_full_product(devices8):
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8)
    assert mesh.devices.shape == (2, 2, 1, 2, 1, 1)


def test_mesh_bad_product_raises(devices8):
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(data=3, tensor=2), devices8)
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(data=-1, fsdp=-1), devices8)


def test_logical_to_spec_default_rules():
    assert logical_to_spec(("batch", "act_seq", "act_embed")) == P(
        ("data", "fsdp"), "seq")
    assert logical_to_spec(("embed", "mlp")) == P("fsdp", "tensor")
    assert logical_to_spec((None, "vocab")) == P(None, "tensor")


def test_strategy_presets():
    fsdp = rules_for("fsdp")
    assert logical_to_spec(("embed", "mlp"), fsdp) == P("fsdp")
    dp = rules_for("dp")
    assert logical_to_spec(("embed", "mlp"), dp) == P()
    with pytest.raises(ValueError):
        rules_for("nope")


def test_sharded_matmul_runs_on_mesh(devices8):
    """End-to-end GSPMD sanity: sharded matmul equals the local result."""
    mesh = build_mesh(MeshConfig(data=2, tensor=4), devices8)
    x = np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(16, 32)).astype(np.float32)
    xs = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("data", None)))
    ws = jax.device_put(w, jax.sharding.NamedSharding(mesh, P(None, "tensor")))
    out = jax.jit(lambda a, b: a @ b)(xs, ws)
    np.testing.assert_allclose(np.asarray(out), x @ w, rtol=1e-4, atol=1e-5)
    assert out.sharding.spec == P("data", "tensor")


def test_tree_logical_to_sharding(devices8):
    mesh = build_mesh(MeshConfig(data=8), devices8)
    tree = {"w": ("embed", "mlp"), "b": ("mlp",)}
    sh = tree_logical_to_sharding(tree, mesh, DEFAULT_RULES)
    assert sh["w"].spec == P("fsdp", "tensor")
    assert sh["b"].spec == P("tensor")


# -- two-level ICI/DCN hybrid mesh (SURVEY.md §5.8(c), eval config 5) --------


def test_hybrid_mesh_data_axis_slice_major(devices8):
    """num_slices=2: the slice index is the slow factor of the data axis, so
    each data-axis block of fsdp devices lives entirely inside one slice."""
    mesh = build_mesh(MeshConfig(data=2, fsdp=4, num_slices=2), devices8)
    assert mesh_shape(mesh) == {
        "data": 2, "fsdp": 4, "pipe": 1, "tensor": 1, "seq": 1, "expert": 1}
    dev = mesh.devices.reshape(2, 4)
    # Single-process CPU fallback: contiguous halves of the device list.
    assert [d.id for d in dev[0]] == [d.id for d in devices8[:4]]
    assert [d.id for d in dev[1]] == [d.id for d in devices8[4:]]


def test_hybrid_mesh_dcn_factor_within_data_axis(devices8):
    """data=4 over 2 slices: within the data axis, the two ICI members of a
    slice stay adjacent; crossing the mid-point crosses the slice."""
    mesh = build_mesh(MeshConfig(data=4, fsdp=2, num_slices=2), devices8)
    dev = mesh.devices.reshape(4, 2)
    ids = [sorted(d.id for d in row) for row in dev]
    slice0 = {d.id for d in devices8[:4]}
    assert set(ids[0]) | set(ids[1]) == slice0
    assert set(ids[2]).isdisjoint(slice0) and set(ids[3]).isdisjoint(slice0)


def test_hybrid_mesh_pipe_axis_fallback(devices8):
    """When data doesn't divide num_slices, pipe carries the DCN factor."""
    cfg = MeshConfig(data=1, fsdp=2, pipe=2, tensor=2, num_slices=2)
    assert cfg.dcn_axis(8) == "pipe"
    mesh = build_mesh(cfg, devices8)
    # pipe stage 0 entirely in slice 0, stage 1 in slice 1.
    dev = mesh.devices  # [1, 2, 2, 2, 1, 1]
    s0 = {d.id for d in devices8[:4]}
    assert {d.id for d in dev[0, :, 0, :].flat} == s0
    assert {d.id for d in dev[0, :, 1, :].flat}.isdisjoint(s0)


def test_hybrid_mesh_indivisible_raises(devices8):
    with pytest.raises(ValueError, match="num_slices"):
        build_mesh(MeshConfig(data=1, fsdp=8, tensor=1, num_slices=3),
                   devices8)


def test_hybrid_mesh_collectives_run(devices8):
    """A dp gradient-style psum over the hybrid mesh executes: the data axis
    spans the slice boundary (DCN on real hw) and still reduces globally."""
    from jax.experimental.shard_map import shard_map

    mesh = build_mesh(MeshConfig(data=2, fsdp=4, num_slices=2), devices8)
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(x):
        return jax.lax.psum(jax.lax.psum(x, "fsdp"), "data")

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(("data", "fsdp")), out_specs=P(("data", "fsdp"))))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), x.sum()))


def test_mesh_factors_all_world_sizes():
    """The driver's mesh-factor split must cover every world size, not
    just the n=8 the dryrun exercises: products
    always match and odd remainders land on fsdp."""
    import importlib

    graft = importlib.import_module("__graft_entry__")
    for n in (1, 2, 3, 4, 5, 6, 8, 12, 16, 24):
        f = graft._mesh_factors(n)
        assert (f["data"] * f["fsdp"] * f["tensor"] * f["seq"] == n), (n, f)
        assert all(v >= 1 for v in f.values()), (n, f)
    assert graft._mesh_factors(6) == {
        "tensor": 2, "seq": 1, "fsdp": 3, "data": 1}
