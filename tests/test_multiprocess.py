"""Localhost multi-process e2e — the rebuild's `kind` equivalent (SURVEY.md
§4): real `jax.distributed` over 127.0.0.1, 2 processes × 2 virtual CPU
devices, training through the Trainer runtime with the TPK_* env contract
(comms/bootstrap.py). Covers DP, the 2-slice hybrid mesh (eval config 5
shape), and cross-process context parallelism (the ring's ppermute rides
the process boundary — the ICI/DCN path on real hardware)."""

import json
import pytest
import os
import socket
import subprocess
import sys

pytestmark = pytest.mark.slow  # multi-process/e2e/AOT tier


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(tmp_path, spec, prefix, *, extra_env=None, n_procs=2,
                 timeout=280):
    """Launch n trainer workers over real jax.distributed; returns the
    per-rank metric streams after asserting clean exits."""
    port = _free_port()
    procs = []
    for pid in range(n_procs):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            TPK_COORDINATOR=f"127.0.0.1:{port}",
            TPK_NUM_PROCS=str(n_procs),
            TPK_PROC_ID=str(pid),
        )
        for k, v in (extra_env or {}).items():
            env[k] = v(pid) if callable(v) else v
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        metrics = tmp_path / f"{prefix}_metrics_{pid}.jsonl"
        path_i = tmp_path / f"{prefix}_spec_{pid}.json"
        path_i.write_text(json.dumps(dict(spec, metrics_path=str(metrics))))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.train.trainer",
             "--spec", str(path_i)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))

    results = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        results.append((p.returncode, out, err))
    for rc, out, err in results:
        assert rc == 0, (f"worker failed rc={rc}\nstdout:{out[-2000:]}\n"
                         f"stderr:{err[-3000:]}")

    streams = []
    for pid in range(n_procs):
        lines = (tmp_path / f"{prefix}_metrics_{pid}.jsonl").read_text()
        streams.append([json.loads(l) for l in lines.splitlines()
                        if "loss" in json.loads(l)])
    return streams


def _assert_converged_and_agreeing(streams, steps):
    assert all(streams)
    for m in streams:
        assert m[-1]["step"] == steps
    for m in streams[1:]:  # every rank, not just rank 1
        assert abs(m[-1]["loss"] - streams[0][-1]["loss"]) < 1e-5
    assert streams[0][-1]["loss"] < streams[0][0]["loss"]


def test_two_process_dp_training(tmp_path):
    spec = {
        "model": "llama_tiny",
        "dataset": "learnable_lm",
        "mesh": {"data": 4},
        "steps": 12,
        "batch_size": 8,
        "seq_len": 16,
        "learning_rate": 3e-3,
        "log_every": 4,
    }
    streams = _run_workers(tmp_path, spec, "dp")
    _assert_converged_and_agreeing(streams, 12)


def test_two_slice_hybrid_mesh_training(tmp_path):
    """Emulated multi-slice (eval config 5, SURVEY.md §5.8(c)): 2 processes,
    each one "slice" of 2 virtual CPU devices. The hybrid mesh puts `data`
    across the slice boundary (DCN on real hw) and `fsdp` within a slice, so
    gradient all-reduce crosses processes while param all-gathers stay
    slice-local. Real `jax.distributed` rendezvous; loss identical on both
    ranks and decreasing."""
    spec = {
        "model": "llama_tiny",
        "dataset": "learnable_lm",
        "mesh": {"data": 2, "fsdp": 2},
        "steps": 12,
        "batch_size": 8,
        "seq_len": 16,
        "learning_rate": 3e-3,
        "log_every": 4,
    }
    streams = _run_workers(
        tmp_path, spec, "ms",
        extra_env={"TPK_NUM_SLICES": "2", "TPK_SLICE_ID": lambda pid: str(pid)})
    _assert_converged_and_agreeing(streams, 12)


def test_cross_process_context_parallel_training(tmp_path):
    """Context parallelism ACROSS processes: the seq axis (4) spans both
    workers, so every ring-attention ppermute step crosses the process
    boundary over real jax.distributed — the SURVEY §5.7/§5.8 long-context
    path at its hardest grain (DCN hops on real multi-host). Zigzag
    schedule: the trainer's permuted batches + positions must agree across
    ranks."""
    import numpy as np

    # Grain-backed corpus (NOT a seed-driven generator): with the seq
    # axis replicated over both processes, the loader must give BOTH
    # ranks the identical row shard — a per-process shard here would
    # silently train each host on different data (regression for the
    # batch-replica-group contract).
    corpus = np.random.default_rng(3).integers(
        0, 512, 20000, dtype=np.int32)
    np.save(tmp_path / "corpus.npy", corpus)
    spec = {
        "model": "llama_tiny",
        "dataset": "token_file",
        "dataset_kwargs": {"path": str(tmp_path / "corpus.npy")},
        "mesh": {"seq": 4},
        "ring_attention": "zigzag",
        "steps": 20,
        "batch_size": 8,
        "seq_len": 16,
        "learning_rate": 5e-3,
        "log_every": 5,
    }
    streams = _run_workers(tmp_path, spec, "cp")
    _assert_converged_and_agreeing(streams, 20)


def test_four_process_two_slice_cross_slice_cp(tmp_path):
    """Scale the e2e past 2 processes: 4 processes ×
    2 virtual devices = 2 emulated slices of 2 processes each, with the
    seq axis (8) spanning EVERYTHING — every zigzag ring step crosses a
    process boundary and half of them cross the slice boundary (DCN on
    real hardware). With dp == 1 all four ranks form ONE batch replica
    group and must feed identical grain rows (the group-indexed loader
    contract at its widest replication)."""
    import numpy as np

    corpus = np.random.default_rng(7).integers(
        0, 512, 20000, dtype=np.int32)
    np.save(tmp_path / "corpus4.npy", corpus)
    spec = {
        "model": "llama_tiny",
        "dataset": "token_file",
        "dataset_kwargs": {"path": str(tmp_path / "corpus4.npy")},
        "mesh": {"seq": 8},
        "ring_attention": "ring",  # contiguous ring: every step ppermutes
        "steps": 10,
        "batch_size": 4,
        "seq_len": 32,
        "learning_rate": 5e-3,
        "log_every": 5,
    }
    streams = _run_workers(
        tmp_path, spec, "cp4", n_procs=4, timeout=420,
        extra_env={"TPK_NUM_SLICES": "2",
                   "TPK_SLICE_ID": lambda pid: str(pid // 2)})
    _assert_converged_and_agreeing(streams, 10)
