"""What ISSUE 28 added to the yardstick: the required work of a Kimi-Linear
step against values worked out by hand from the configuration's file, and the
reduction of a trace to device time by named scope on a synthetic trace."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import common  # noqa: E402
import xplane_scopes  # noqa: E402

required = common.load_module(
    os.path.join(BENCH, "required", "kimi_linear.py"))
scope_share = common.load_module(
    os.path.join(BENCH, "readers", "xplane_scope_share.py"))
scope_roofline = common.load_module(
    os.path.join(BENCH, "readers", "scope_roofline.py"))


@pytest.fixture(scope="module")
def cfg():
    return common.load_json(os.path.join(
        BENCH, "configs", "kimi-linear-48b-a3b-d5e8.json"))


def test_required_work_by_hand(cfg):
    # One KDA mixer: q, k, v, o 4 x 2304 x 4096; decay and gate, each
    # 2304 x 128 + 128 x 4096; beta 2304 x 32; three convolutions 4 x 4096.
    assert required.kda_mixer_params(cfg) == (
        37_748_736 + 2 * (294_912 + 524_288) + 73_728 + 49_152)
    # One MLA mixer: q 2304 x 32 x 192; kv_a 2304 x 576; kv_b 512 x 32 x
    # 256; o 32 x 128 x 2304.
    assert required.mla_mixer_params(cfg) == (
        14_155_776 + 1_327_104 + 4_194_304 + 9_437_184)
    assert required.dense_ffn_params(cfg) == 3 * 2304 * 9216
    # One expert layer for one token here: the router's 256 outputs, the
    # shared expert, and 8 x 8 / 256 = a quarter of an expert routed.
    assert required.expert_ffn_params(cfg) == (
        589_824 + 7_077_888 + 0.25 * 7_077_888)
    # Layers 1-5: KDA+dense, KDA+MoE, KDA+MoE, MLA+MoE, KDA+MoE; the head
    # over the 20,480-row slice.
    assert required.matmul_params(cfg) == (
        47_185_920 + 4 * 39_510_016 + 29_114_368 + 63_700_992
        + 4 * 9_437_184) == 335_790_080
    # MLA's scores at 8,192: 3 x 2 x 32 heads x (192 + 128) x 8193 / 2.
    assert required.mla_score_flops_per_token(cfg, 8192) == 251_688_960
    # The recurrence: 3 x 7 x 128 x 128 x 32 heads.
    assert required.kda_recurrence_flops_per_token(cfg) == 11_010_048
    assert required.train_flops_per_token(cfg, 8192) == (
        6 * 335_790_080 + 251_688_960 + 4 * 11_010_048) == 2_310_469_632
    # fp32 a head: forward 513 in + 128 out, backward 513 + 128 in, 513 out.
    assert required.kda_recurrence_bytes_per_token(cfg) == 4 * 32 * 1795


JIT = "jit(step)/transpose(jvp(KimiLinear))/checkpoint/"


def trace():
    """One device's op line, (name, start ns, duration ns, path): a `while`
    of the KDA scan (10 us) holding two body ops (3 + 4 us), a grouped matmul
    of the expert layer (6 us), an op the compiler made (2 us, no path), then
    after a gap an MLA kernel (5 us)."""
    return [
        ("%while.1 = ...", 0.0, 10_000.0,
         JIT + "layer_0/kda/kda/kda_scan/while"),
        ("%fusion.1 = ...", 1_000.0, 3_000.0,
         JIT + "layer_0/kda/kda/kda_scan/while/body/dot_general"),
        ("%fusion.2 = ...", 5_000.0, 4_000.0,
         JIT + "layer_0/kda/kda/kda_scan/while/body/mul"),
        ("%gmm.1 = ...", 10_000.0, 6_000.0,
         JIT + "layer_1/moe/moe_experts/gmm"),
        ("%copy.1 = ...", 16_000.0, 2_000.0, None),
        ("%mla.1 = ...", 20_000.0, 5_000.0, JIT + "layer_3/mla/mla/pallas_call"),
    ]


def check_scopes(out):
    assert out["planes"] == 1 and out["stat"] == "tf_op"
    assert out["busy_s"] == pytest.approx(23e-6)  # 18 us, a 2 us gap, 5 us
    paths = dict(out["paths"])
    # The while's own time is what its body does not cover: 10 - 3 - 4.
    assert paths[JIT + "layer_0/kda/kda/kda_scan/while"] == \
        pytest.approx(3e-6)
    assert paths["(no path) %copy.1"] == pytest.approx(2e-6)
    assert sum(paths.values()) == pytest.approx(out["busy_s"])
    ctx = types.SimpleNamespace(facts={"scopes": out}, trace=True)
    assert scope_share.seconds_under(ctx, "/kda_scan(/|$)") == \
        pytest.approx(10e-6)
    assert scope_share.read(ctx, "/kda(/|$)") == pytest.approx(
        100 * 10 / 23)
    assert scope_share.read(ctx, "/moe(/|$)") == pytest.approx(100 * 6 / 23)
    assert scope_share.read(ctx, "/mla(/|$)") == pytest.approx(100 * 5 / 23)
    # A scope the program does not have (a parent commit): nothing to read.
    assert scope_share.read(ctx, "/no_such_scope(/|$)") is None


def test_scope_reduction_by_hand():
    check_scopes(xplane_scopes.merge(
        [xplane_scopes.reduce_plane(trace())], "tf_op"))


def test_scope_reduction_of_a_written_trace(tmp_path):
    """The same events as an `.xplane.pb` the way the TPU's profiler lays
    them out: the path is the `tf_op` stat of the event's *metadata*."""
    pb2 = xplane_scopes.xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane_pb2 to write a trace with")
    space = pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = "flops"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1_000)
    space.planes.add(name="/host:CPU").lines.add(name="XLA Ops")
    for i, (name, start, dur, path) in enumerate(trace(), 1):
        meta = plane.event_metadata[i]
        meta.id, meta.name = i, name
        meta.stats.add(metadata_id=2, int64_value=16)
        if path:
            meta.stats.add(metadata_id=1, str_value=path)
        line.events.add(metadata_id=i, offset_ps=int(start * 1000),
                        duration_ps=int(dur * 1000))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    check_scopes(xplane_scopes.summarize(str(path)))


def test_a_trace_without_paths_reads_nothing():
    raw = [(n, s, d, None) for n, s, d, _ in trace()]
    out = xplane_scopes.merge([xplane_scopes.reduce_plane(raw)], None)
    assert out["paths"] == [] and out["busy_s"] == pytest.approx(23e-6)
    ctx = types.SimpleNamespace(facts={"scopes": out}, trace=True)
    assert scope_share.read(ctx, "/kda(/|$)") is None


def test_roofline_share_of_the_recurrence(cfg):
    """16,384 tokens x 4 KDA layers x 5 traced steps: the bytes bind (44
    FLOPs a byte against the chip's 240), so the least time is bytes / 819
    GB/s; 0.5 s under the scope then reads 18.4%."""
    out = {"planes": 1, "busy_s": 1.0, "stat": "tf_op",
           "paths": [["jit(step)/layer_0/kda/kda/kda_scan/while", 0.5]]}
    ctx = types.SimpleNamespace(
        facts={"scopes": out, "device": {"platform": "tpu",
                                         "kind": "TPU v5 lite"},
               "spec": {"batch_size": 2, "seq_len": 8192}},
        config=cfg, chips=1, trace=True, mix={"profile_steps": [15, 20]})
    least = 16384 * 4 * 5 * 229_760 / 819e9
    assert least > 16384 * 4 * 5 * 11_010_048 / 197e12
    assert scope_roofline.read(
        ctx, scope="/kda_scan(/|$)", module="kimi_linear",
        flops="kda_recurrence_flops_per_token",
        bytes="kda_recurrence_bytes_per_token",
        layers="kda_layers") == pytest.approx(100 * least / 0.5)


def test_compare_holds_median_and_worst_of_each_class():
    kind = common.load_module(os.path.join(BENCH, "kinds", "train_ref.py"))
    limits = common.load_json(os.path.join(
        BENCH, "configs", "kimi-linear-48b-a3b-d5e8.json")
    )["reference"]["limits"]
    errs = {f"layer_{i}/kda/q_proj/kernel": 0.05 for i in range(5)}
    errs.update({"layer_1/moe/w_up": 0.2, "layer_1/moe/router": 0.3,
                 "layer_1/moe/w_down": 0.21})
    ref = {"loss": 10.43, "grad_norm": 2.68, "grad_rel_err": errs,
           "is_expert": {k: "moe/" in k for k in errs}}
    row = {"loss": 10.4302, "grad_norm": 2.6805}
    ok, compared = kind.compare(row, ref, limits)
    assert ok
    assert compared["grad_rel_median"]["err"] == pytest.approx(0.05)
    assert compared["grad_rel_experts_median"]["err"] == pytest.approx(0.21)
    assert compared["grad_rel_experts_max"]["worst_tensor"] == \
        "layer_1/moe/router"
    # A lower precision moves every tensor a little: the medians catch it.
    low = dict(ref, grad_rel_err={k: 1.56 * v for k, v in errs.items()})
    assert not kind.compare(row, low, limits)[0]
    # One wrong tensor (a shared expert left out): the largest catches it.
    one = dict(ref, grad_rel_err=dict(errs, **{
        "layer_2/kda/q_proj/kernel": 1.0}))
    assert not kind.compare(row, one, limits)[0]
    # A token left out of the mean: the loss.
    assert not kind.compare(dict(row, loss=10.45), ref, limits)[0]


def control_readings():
    """(seed, control, the trainer row it stands for, the reference side as
    `compare` takes it) of every reading in the committed file: what
    `check_kimi_linear.py --control` wrote on the chip (PR 28)."""
    runs = common.load_json(os.path.join(
        BENCH, "reference", "kimi_linear_controls.json"))["runs"]
    return [(run["seed"], name, c["row"],
             {"loss": run["loss"], "grad_norm": run["grad_norm"],
              "is_expert": run["is_expert"],
              "grad_rel_err": c["grad_rel_err"]})
            for run in runs for name, c in run["controls"].items()]


@pytest.mark.parametrize(
    "seed,name,row,ref", control_readings(),
    ids=[f"{name}-{seed}" for seed, name, _, _ in control_readings()])
def test_a_control_read_on_the_chip_is_not_correct(seed, name, row, ref):
    kind = common.load_module(os.path.join(BENCH, "kinds", "train_ref.py"))
    limits = common.load_json(os.path.join(
        BENCH, "configs", "kimi-linear-48b-a3b-d5e8.json")
    )["reference"]["limits"]
    ok, compared = kind.compare(row, ref, limits)
    assert not ok
    failed = {k for k, c in compared.items() if c["err"] > c["limit"]}
    assert failed >= {
        # The precision below: the median, which a seed hardly moves.
        "bfloat16": {"grad_rel_median"},
        # A twentieth of the pairs: the experts' median alone.
        "drop_pairs": {"grad_rel_experts_median"},
        # A missing part or a missing sequence: everything.
        "no_shared": set(compared), "half_batch": set(compared),
    }[name]
