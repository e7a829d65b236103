"""The process boundary (ISSUE 21): which device a process came up on, what
happens when that is not a TPU, where compiled programs are kept, and
`chip_smoke.py`'s behaviour without a chip. All CPU; the chip itself is
only reachable through the chip tool (see .claude/skills/verify)."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from kubeflow_tpu.utils import devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# -- compile cache -----------------------------------------------------------


def _cache_dir_in_child(env_value):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c",
         "from kubeflow_tpu.utils.devices import compile_cache_dir;"
         "print(compile_cache_dir())"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_compile_cache_env_set_is_left_alone(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where/else")
    assert devices.enable_compile_cache() == "/some/where/else"
    assert jax.config.jax_compilation_cache_dir == before
    assert _cache_dir_in_child("/some/where/else") == "/some/where/else"


def test_compile_cache_unset_is_one_fixed_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = devices.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert devices.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == os.path.join(REPO, ".jax_compile_cache")
    # Two more processes agree (no pid, tempdir or clock in the path), and
    # git ignores it.
    assert _cache_dir_in_child(None) == first == _cache_dir_in_child(None)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_compile_cache/" in fh.read().split()


# -- peak FLOP/s table -------------------------------------------------------


def test_peak_flops_known_unknown_and_cpu():
    from kubeflow_tpu.train.metrics import peak_flops_per_chip

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert peak_flops_per_chip(dev("tpu", "TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(dev("tpu", "TPU v5")) == 459e12
    with pytest.raises(ValueError, match="TPU v9 hyper"):
        peak_flops_per_chip(dev("tpu", "TPU v9 hyper"))
    # The CPU is recognised by platform — whatever its kind string says —
    # and has no peak: MFU on it is "not measured", not a number.
    assert peak_flops_per_chip(dev("cpu", "TPU v5 lite")) is None
    assert peak_flops_per_chip() is None


# -- TPU or an explicit CPU request ------------------------------------------


def test_cpu_was_requested_here_and_device_checks_agree():
    # conftest exports JAX_PLATFORMS=cpu: this process asked for the CPU.
    assert devices.cpu_requested()
    assert not devices.on_tpu()
    assert devices.require_tpu_or_requested_cpu("t")["platform"] == "cpu"
    with pytest.raises(SystemExit) as e:
        devices.require_tpu("bench.py")
    assert "needs a TPU" in str(e.value.code)


@pytest.fixture
def unrequested_cpu(monkeypatch):
    """A worker that lands on the CPU without having asked for it (no
    TPU found, JAX_PLATFORMS unset)."""
    monkeypatch.setattr(devices, "cpu_requested", lambda: False)
    monkeypatch.setattr(devices, "enable_compile_cache", lambda: "")


def test_trainer_main_refuses_an_unrequested_cpu(unrequested_cpu, tmp_path,
                                                 capsys):
    from kubeflow_tpu.train import trainer

    spec = tmp_path / "spec.json"
    spec.write_text("{}")
    with pytest.raises(SystemExit) as e:
        trainer.main(["--spec", str(spec)])
    assert e.value.code not in (0, None)
    assert "not a TPU" in str(e.value.code)
    assert '"event": "device"' not in capsys.readouterr().out


def test_server_main_refuses_an_unrequested_cpu(unrequested_cpu, tmp_path,
                                                capsys):
    from kubeflow_tpu.serve import server

    with pytest.raises(SystemExit) as e:
        server.main(["--model-dir", str(tmp_path), "--port", "0"])
    assert e.value.code not in (0, None)
    assert "not a TPU" in str(e.value.code)
    assert '"event": "device"' not in capsys.readouterr().out


def test_model_metadata_reports_the_real_device():
    from kubeflow_tpu.serve.model import Model

    md = Model("m").metadata()
    assert md["device"] == devices.device_summary()
    assert md["device"]["platform"] == "cpu"
    assert "tpu" not in md["platform"]


def test_bench_device_check_fails_without_a_tpu(monkeypatch):
    monkeypatch.setattr(devices, "enable_compile_cache", lambda: "")
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    for gone in ("LAST_GOOD", "acquire_backend", "_probe_backend",
                 "_emit_skip", "_probe_attempts"):
        assert not hasattr(bench, gone)
    with pytest.raises(SystemExit) as e:
        bench._device("--serve")
    assert "bench.py --serve: needs a TPU" in str(e.value.code)


# -- chip_smoke.py without a chip --------------------------------------------


def test_chip_smoke_without_a_chip_fails_and_parent_never_imports_jax():
    code = (
        "import runpy, sys\n"
        "try:\n"
        f"    runpy.run_path({SMOKE!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "print('PARENT_JAX', any(m == 'jax' or m.startswith('jax.')\n"
        "                        for m in sys.modules))\n"
        "sys.exit(rc)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode not in (0, None), out.stdout
    assert out.stdout.strip() == "PARENT_JAX False", out.stdout
    assert "needs platform 'tpu'" in out.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    with open(SMOKE) as fh:
        lone.write_text(fh.read())
    out = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode not in (0, None)
    assert out.stdout == ""
    assert not (tmp_path / "chip_smoke_out").exists()


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    """chip_smoke.py as a module (stdlib only), writing under tmp_path."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "OUT", str(tmp_path))
    return mod


def test_chip_smoke_refused_kernel_shows_the_compiler_and_run_goes_on(
        smoke, monkeypatch, capsys):
    """A kernels child that says where it runs and then dies compiling:
    the parent keeps the device, puts the log's tail on stderr, still runs
    the other phases, and ends stdout with an ok:false verdict."""
    child = ("import json; print(json.dumps({'event': 'device', 'platform':"
             " 'tpu', 'kind': 'TPU v5 lite', 'count': 1}), flush=True);"
             " raise NotImplementedError('Mosaic says no')")
    real_spawn = smoke.Run.spawn
    monkeypatch.setattr(smoke.Run, "spawn", lambda self, argv, log:
                        real_spawn(self, ["-c", child], log))
    ran = []
    for name in ("serve", "train"):
        monkeypatch.setattr(
            smoke, f"phase_{name}",
            lambda run, name=name: ran.append(name) or {"compile_s": 0.0})
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.splitlines()]
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert ran == ["serve", "train"]
    assert [(ln["phase"], ln["ok"]) for ln in lines[:-1]] == [
        ("kernels", False), ("serve", True), ("train", True)]
    assert lines[-1] == {"ok": False, "device": device,
                         "failed": ["kernels"]}
    assert "Mosaic says no" in err


def test_chip_smoke_child_that_never_names_its_device_ends_the_run(
        smoke, monkeypatch, capsys):
    real_spawn = smoke.Run.spawn
    monkeypatch.setattr(smoke.Run, "spawn", lambda self, argv, log:
                        real_spawn(self, ["-c", "raise SystemExit('boom')"],
                                   log))
    monkeypatch.setattr(smoke, "phase_serve", lambda run: 1 / 0)
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""  # no device, no result
    assert "never reported its device" in err and "boom" in err


@pytest.mark.slow  # spawns both mains; ~1 min
def test_chip_smoke_phase_code_runs_at_tiny_size_on_the_cpu():
    """The script's own phase code — same children, same checks — at
    llama_tiny with interpret-mode kernels, so it cannot rot between
    chip runs."""
    out = subprocess.run([sys.executable, SMOKE, "--cpu-tiny"], cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "kernels", "serve", "train"]
    for ln in lines[:-1]:
        assert ln["ok"] and ln["platform"] == "cpu"
        assert ln["compile_s"] > 0 and ln["wall_s"] > 0
    assert lines[1]["prefix_hits"] >= 1
    assert lines[1]["decode_fetch_overlapped"] > 0
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


@pytest.mark.slow  # one interpreter + jax import per mode
@pytest.mark.parametrize("mode", ["", "--serve", "--train-fsdp",
                                  "--longctx", "--8bshape"])
def test_bench_modes_exit_nonzero_without_a_tpu(mode):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")] + mode.split(),
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""  # no skip record, no fallback result
    assert "needs a TPU" in out.stderr


# -- compiled kernels under a multi-device mesh ------------------------------


def _mesh_flash_case(with_grad):
    """flash_attention_on_mesh on a (data=2, fsdp=2, tensor=2) mesh equals
    the plain call. (On the chip the plain call under such a mesh does not
    lower at all — Mosaic kernels cannot be partitioned by GSPMD — which
    only a chip run can show; here the shard_map route is pinned.)"""
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.flash_attention import (flash_attention,
                                                  flash_attention_on_mesh)
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    b, s, h, kh, d = 4, 64, 4, 2, 16
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k = jax.random.normal(keys[1], (b, s, kh, d))
    v = jax.random.normal(keys[2], (b, s, kh, d))
    seg = jnp.asarray(np.repeat(np.arange(4), 16)[None].repeat(b, 0))

    def on_mesh(q, k, v):
        return flash_attention_on_mesh(q, k, v, mesh, block_q=32,
                                       block_kv=32, segment_ids=seg)

    def plain(q, k, v):
        return flash_attention(q, k, v, True, 32, 32, None, seg)

    fns = (on_mesh, plain)
    if with_grad:
        fns = [jax.grad(lambda q, k, v, f=f: f(q, k, v).sum(),
                        argnums=(0, 1, 2)) for f in fns]
    with mesh:
        got = jax.jit(fns[0])(q, k, v)
    want = jax.jit(fns[1])(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    # One device (or no mesh) is the plain call: nothing to partition.
    assert flash_attention_on_mesh(q, k, v, None, block_q=32, block_kv=32,
                                   segment_ids=seg).shape == q.shape


def test_flash_on_mesh_forward_equals_plain():
    _mesh_flash_case(with_grad=False)


@pytest.mark.slow  # the backward's two extra kernels under shard_map
def test_flash_on_mesh_backward_equals_plain():
    _mesh_flash_case(with_grad=True)
