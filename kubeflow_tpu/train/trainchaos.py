"""Train-plane chaos harness (ISSUE 17) → TRAINCHAOS.json.

The serve plane got its chaos harness in ISSUE 14 (serve/chaosbench.py);
this is the train-plane arm: REAL trainer workers (each its own
subprocess, launched by the REAL tpk-controlplane binary) under a seeded
SIGKILL/SIGSTOP schedule, measuring **goodput** — useful (non-redone)
training steps per wall-second — for three arms at identical corpus,
seed, and fault schedule:

  * **control** — fault-free run at the submitted 4-way fsdp topology;
    the goodput ceiling everything else is read against.
  * **elastic** — the job carries `elastic.min_fsdp`; the worker is
    SIGKILLed at a seeded step threshold (condition-triggered off the
    live metrics JSONL, so the kill lands mid-training, not mid-compile)
    and the controller downsizes 4 -> 2 unattended: next-divisor
    topology, runtime.json rewrite, relaunch, checkpoint reshard. A
    later SIGSTOP/SIGCONT window stalls the post-resize worker
    (slow-but-alive straggler) without killing it.
  * **restart_scratch** — the no-checkpoint baseline: same kill, same
    stall, and the SAME capacity loss (the controller downsizes this
    gang 4 -> 2 too — the fault is a capacity event, identical across
    arms), but no checkpointing: the relaunch starts from step 0 and
    every pre-kill step is redone at the degraded topology. Holding the
    capacity trajectory fixed makes the goodput delta the value of
    checkpoint-resume-with-reshard alone, not of having more devices.

Pinned claims (tests/test_trainchaos.py): the resize event chain is
OBSERVED (ElasticDownsize naming old -> new topology, then the worker's
Resharded once the restored state landed), ZERO acked checkpoints are
lost (every step the trainer durably acked via CheckpointSaved is <= the
step the resumed attempt restored), and elastic goodput is STRICTLY
above restart-from-scratch (the redone-work gap is the mechanism).
Absolute rates are 1-CPU tiny-model numbers — the artifact says so, and
the claims are arm DELTAS plus mechanism facts, never absolute speed.

Harness discipline (PROFILE §11/§15): the fault schedule is seeded and
recorded; kills are condition-triggered at step thresholds read from the
worker's own metrics stream. Workers run with the trainer's persistent
compile cache like any other: under jaxlib 0.9.0 a post-resize attempt
loads entries written at the other topology cleanly (3 of 3 runs with
every program cached, ISSUE 21). Compile cost is symmetric either way: every arm compiles 4-way at launch, and the two
compared arms each pay one 2-way compile or cache load after the
identical downsize.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time

import numpy as np

#: Shared trainer shape for every arm (tiny llama, fp32 CPU mesh — the
#: trajectory math must be exact, and the harness runs on 1 CPU).
#: batch/seq are sized so one step costs ~1s of real compute: the
#: goodput A/B measures REDONE WORK, and the redone-prefix gap has to
#: dominate the (symmetric) compile + restore overheads, not drown in
#: them — all the capacity on a CPU mesh is one physical CPU, so
#: per-step cost, not device count, is what the kill puts at stake.
TRAIN_KW = dict(model="llama_tiny", model_kwargs={"dtype": "float32"},
                dataset="token_file", batch_size=32, seq_len=64,
                learning_rate=1e-3, log_every=1, prefetch=2)

#: The submitted (maximum) fsdp topology every arm starts at.
FSDP = 4


def make_schedule(seed: int, steps: int, interval: int) -> dict:
    """Seeded fault schedule, RECORDED in the artifact. The kill step is
    pinned to `ckpt_interval*k + 1` — one step past a save boundary, so
    the elastic arm's redo is minimal (the checkpoint just landed) while
    restart-from-scratch redoes everything before it: the honest shape
    of 'a checkpoint existed and only one arm could use it'."""
    rng = np.random.default_rng(seed + 6211)
    kill = int(rng.uniform(0.55, 0.70) * steps)
    kill = (kill // interval) * interval + 1
    stall = min(steps - 2, (kill + steps) // 2)
    return {
        "kill_step": kill,
        "stall_step": stall,
        "stall_s": round(float(rng.uniform(1.5, 2.5)), 2),
    }


class _StepMonitor(threading.Thread):
    """Tails a trainer's metrics JSONL and exposes its live progress to
    the fault driver — the condition-triggered kill ('SIGKILL once the
    worker has really passed step K') reads this, never wall-clock."""

    def __init__(self, path: str):
        super().__init__(daemon=True, name="tpk-trainchaos-monitor")
        self.path = path
        self._lock = threading.Lock()
        self._halt = threading.Event()
        self.max_step = 0  # guarded-by: _lock
        self.events: list[dict] = []  # guarded-by: _lock

    def run(self):
        fh = None
        buf = ""
        try:
            while not self._halt.is_set():
                if fh is None:
                    if not os.path.exists(self.path):
                        time.sleep(0.05)
                        continue
                    fh = open(self.path)
                chunk = fh.read()
                if not chunk:
                    time.sleep(0.05)
                    continue
                buf += chunk
                while "\n" in buf:
                    line, buf = buf.split("\n", 1)
                    if not line.strip():
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    with self._lock:
                        if "loss" in rec:
                            self.max_step = max(self.max_step,
                                                int(rec["step"]))
                        if "event" in rec:
                            self.events.append(rec)
        finally:
            if fh is not None:
                fh.close()

    def step(self) -> int:
        with self._lock:
            return self.max_step

    def snapshot_events(self) -> list[dict]:
        with self._lock:
            return list(self.events)

    def wait_step(self, threshold: int, timeout_s: float) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.step() >= threshold:
                return True
            time.sleep(0.05)
        return False

    def stop(self):
        self._halt.set()


class _FaultDriver(threading.Thread):
    """Runs the seeded kill/stall schedule against a live job's worker,
    gating each action on the monitor's observed step. Fired actions are
    recorded (with the step they actually landed at) for the artifact —
    the bench reports outcomes, not injector intent."""

    def __init__(self, client, job: str, monitor: _StepMonitor,
                 schedule: dict, *, timeout_s: float):
        super().__init__(daemon=True, name="tpk-trainchaos-faults")
        self.client = client
        self.job = job
        self.monitor = monitor
        self.schedule = schedule
        self.timeout_s = timeout_s
        self._lock = threading.Lock()
        self.fired: list[dict] = []  # guarded-by: _lock

    def _pid(self) -> int | None:
        try:
            pids = self.client.get("JAXJob", self.job)["status"].get(
                "pids") or []
            return int(pids[0]) if pids else None
        except Exception:
            return None

    def _record(self, what: str, **kw):
        with self._lock:
            self.fired.append(dict({"action": what}, **kw))

    def run(self):
        sched = self.schedule
        # SIGKILL once the worker has genuinely trained past kill_step.
        if self.monitor.wait_step(sched["kill_step"], self.timeout_s):
            pid = self._pid()
            if pid is not None:
                try:
                    os.kill(pid, signal.SIGKILL)
                    self._record("kill", step=self.monitor.step(),
                                 pid=pid)
                except ProcessLookupError:
                    self._record("kill_missed", pid=pid)
        # SIGSTOP/SIGCONT stall on the (relaunched) worker once it has
        # passed stall_step: slow-but-alive, not dead — the controller
        # must NOT resize again; the run just stretches by ~stall_s.
        if self.monitor.wait_step(sched["stall_step"], self.timeout_s):
            pid = self._pid()
            if pid is not None:
                try:
                    os.kill(pid, signal.SIGSTOP)
                    try:
                        time.sleep(sched["stall_s"])
                    finally:
                        os.kill(pid, signal.SIGCONT)
                    self._record("stall", step=self.monitor.step(),
                                 pid=pid, stall_s=sched["stall_s"])
                except ProcessLookupError:
                    self._record("stall_missed", pid=pid)

    def snapshot_fired(self) -> list[dict]:
        with self._lock:
            return list(self.fired)


# -- arms -------------------------------------------------------------------


def _runtime(corpus: str, steps: int, interval: int | None,
             metrics_path: str) -> dict:
    rt = dict(TRAIN_KW, dataset_kwargs={"path": corpus}, fsdp=FSDP,
              steps=steps, metrics_path=metrics_path)
    if interval is not None:
        rt["checkpoint"] = {
            "dir": os.path.join(os.path.dirname(metrics_path),
                                "ck-" + os.path.basename(metrics_path)),
            "interval": interval,
        }
    return rt


def _base_spec(runtime: dict) -> dict:
    return {
        "replicas": 1, "devices_per_proc": FSDP,
        "cpu_devices_per_proc": FSDP, "restart_policy": "OnFailure",
        "runtime": runtime,
    }


def _run_job(client, name: str, spec: dict, *, schedule: dict | None,
             timeout_s: float) -> dict:
    """Submit one job, optionally drive the fault schedule against it,
    and block to a terminal phase. Returns wall time + observability."""
    monitor = _StepMonitor(spec["runtime"]["metrics_path"])
    monitor.start()
    t0 = time.monotonic()
    client.submit_jaxjob(name, spec)
    driver = None
    if schedule is not None:
        driver = _FaultDriver(client, name, monitor, schedule,
                              timeout_s=timeout_s)
        driver.start()
    phase = client.wait_for_phase(name, timeout=timeout_s, poll=0.2)
    wall = time.monotonic() - t0
    if driver is not None:
        driver.join(timeout=schedule["stall_s"] + 10)
    monitor.stop()
    monitor.join(timeout=5)
    status = client.get("JAXJob", name)["status"]
    ctl_events = client.events(name)["events"]
    return {
        "phase": phase,
        "wall_s": round(wall, 2),
        "status": status,
        "ctl_events": ctl_events,
        "jsonl_events": monitor.snapshot_events(),
        "fired": driver.snapshot_fired() if driver else [],
    }


def _acked_steps(ctl_events: list[dict], before_unix: float) -> list[int]:
    """Steps the trainer durably acked via CheckpointSaved before
    `before_unix` (the trainer defers the ack one save boundary, so an
    acked step is known committed — never a torn write)."""
    out = []
    for e in ctl_events:
        if e["reason"] != "CheckpointSaved":
            continue
        if e["unix"] > before_unix:
            continue
        try:
            out.append(int(e["message"].split()[-1]))
        except (ValueError, IndexError):
            pass
    return sorted(out)


def _summarize(run: dict, steps: int, kill_step: int | None) -> dict:
    ev = run["jsonl_events"]
    restored = [e for e in ev if e.get("event") == "restored"]
    resharded = [e for e in ev if e.get("event") == "resharded"]
    restored_step = int(restored[-1]["step"]) if restored else 0
    # Useful steps = distinct steps of the final trajectory; redone =
    # work the schedule forced the arm to repeat.
    redone = max(0, (kill_step or 0) - restored_step) if kill_step \
        else 0
    kills = [f for f in run["fired"] if f["action"] == "kill"]
    kill_unix = None
    downs = [e for e in run["ctl_events"]
             if e["reason"] == "ElasticDownsize"]
    if downs:
        kill_unix = downs[0]["unix"]
    acked = _acked_steps(run["ctl_events"],
                         kill_unix if kill_unix is not None
                         else float("inf"))
    pre_kill_acked = [s for s in acked
                      if kill_step is None or s <= kill_step]
    return {
        "phase": run["phase"],
        "wall_s": run["wall_s"],
        "final_step": steps if run["phase"] == "Succeeded" else
        max((int(e["step"]) for e in ev), default=0),
        "goodput_steps_per_s": round(steps / run["wall_s"], 4),
        "restarts": int(run["status"].get("restarts", 0)),
        "effective_fsdp_final": run["status"].get("effectiveFsdp"),
        "kill_fired": kills[0] if kills else None,
        "stalls_fired": [f for f in run["fired"]
                         if f["action"] == "stall"],
        "restored_step": restored_step if restored else None,
        "resharded": [{"from": int(e["from_fsdp"]),
                       "to": int(e["to_fsdp"]),
                       "step": int(e["step"])} for e in resharded],
        "redone_steps": redone,
        "acked_checkpoints_before_kill": pre_kill_acked,
        # Only meaningful when a kill happened AND a restore ran: an
        # un-killed arm loses nothing, a no-checkpoint arm acks nothing.
        "lost_acked_checkpoints": ([s for s in pre_kill_acked
                                    if s > restored_step]
                                   if kill_step and restored else []),
        "resize_events": [e["message"] for e in downs],
    }


# -- entrypoint -------------------------------------------------------------


def run_trainchaos(quick: bool = False, seed: int = 0,
                   workdir: str | None = None) -> dict:
    import shutil
    import tempfile

    from kubeflow_tpu.controlplane.client import Client, start_controlplane

    # Full mode is long enough that the restart arm's redone prefix
    # (~0.55-0.70 of the run) dwarfs the symmetric per-attempt
    # overheads — on a CPU mesh the downsized topology is actually
    # FASTER per step (fewer fake devices = less sharding overhead, the
    # physical CPU is the same), so redone work is the ONLY cost the
    # kill imposes and the prefix has to be long to measure it; quick
    # mode only shakes out the mechanism chain.
    steps = 12 if quick else 48
    interval = 2 if quick else 4
    timeout_s = 600.0 if quick else 1200.0
    sched = make_schedule(seed, steps, interval)

    base = workdir or tempfile.mkdtemp(prefix="tpk-trainchaos-")
    own_dir = workdir is None
    os.makedirs(base, exist_ok=True)
    corpus = os.path.join(base, "corpus.npy")
    np.save(corpus, np.random.default_rng(seed + 11).integers(
        0, 64, 200000, dtype=np.int32))

    sock = os.path.join(base, "cp.sock")
    work = os.path.join(base, "work")
    proc = start_controlplane(sock, work)
    # Generous socket timeout: harness gets are cheap reads, but a CI
    # host under the arms' own CPU load can stall the event loop well
    # past a tight budget, and a timed-out poll aborts the whole bench.
    client = Client(sock, timeout=60)
    result: dict = {
        "metric": "trainchaos",
        "mode": "real-trainer-subprocess-controlplane",
        "note": ("workers are the REAL trainer (tiny llama, fp32, CPU "
                 "mesh) in their OWN subprocesses, launched and "
                 "relaunched by the REAL tpk-controlplane binary, so "
                 "SIGKILL/SIGSTOP and the elastic resize are the real "
                 "thing; absolute rates are 1-CPU tiny-model numbers — "
                 "the artifact is the mechanism claims (resize chain "
                 "observed, zero lost acked checkpoints) and the arm "
                 "goodput deltas, computed from per-run provenance "
                 "(controller events + the worker's own JSONL stream)"),
        "params": {"steps": steps, "ckpt_interval": interval,
                   "fsdp": FSDP, "seed": seed, "quick": bool(quick),
                   "train_kw": {k: v for k, v in TRAIN_KW.items()
                                if k != "model_kwargs"}},
        "schedule": sched,
        "arms": {},
    }
    try:
        # Arm 1: fault-free control at the submitted topology.
        ctl = _run_job(
            client, "tc-control",
            _base_spec(_runtime(corpus, steps, interval,
                                os.path.join(base, "control.jsonl"))),
            schedule=None, timeout_s=timeout_s)
        result["arms"]["control"] = _summarize(ctl, steps, None)

        # Arm 2: elastic — kill past backoff forces the 4 -> 2 resize;
        # the later stall is a straggler, not a death.
        el_spec = _base_spec(_runtime(
            corpus, steps, interval, os.path.join(base, "elastic.jsonl")))
        el_spec["backoff_limit"] = 0
        # upsize_cooldown_s >> arm runtime: the probe must not regrow
        # the gang mid-measurement.
        el_spec["elastic"] = {"min_fsdp": 1, "upsize_cooldown_s": 3600}
        el = _run_job(client, "tc-elastic", el_spec, schedule=sched,
                      timeout_s=timeout_s)
        result["arms"]["elastic"] = _summarize(el, steps,
                                               sched["kill_step"])

        # Arm 3: restart-from-scratch — same kill, same stall, same
        # elastic downsize (the capacity loss is the fault, identical
        # across arms), but NO checkpoint dir: the relaunch starts at
        # step 0 and redoes the whole pre-kill prefix at the degraded
        # topology. The elastic-vs-restart delta is therefore the
        # checkpoint-resume-with-reshard mechanism, nothing else.
        rs_spec = _base_spec(_runtime(
            corpus, steps, None, os.path.join(base, "restart.jsonl")))
        rs_spec["backoff_limit"] = 0
        rs_spec["elastic"] = {"min_fsdp": 1, "upsize_cooldown_s": 3600}
        rs = _run_job(client, "tc-restart", rs_spec, schedule=sched,
                      timeout_s=timeout_s)
        summary = _summarize(rs, steps, sched["kill_step"])
        # No checkpoint -> nothing restorable: the whole pre-kill
        # prefix is redone work.
        summary["redone_steps"] = sched["kill_step"]
        result["arms"]["restart_scratch"] = summary

        e, r = result["arms"]["elastic"], result["arms"]["restart_scratch"]
        result["claims"] = {
            "resize_event_observed": bool(e["resize_events"]),
            "resharded_observed": bool(e["resharded"]),
            "zero_lost_acked_checkpoints":
                e["lost_acked_checkpoints"] == [],
            "goodput_elastic_over_restart": round(
                e["goodput_steps_per_s"]
                / max(r["goodput_steps_per_s"], 1e-9), 3),
        }
        return result
    finally:
        client.close()
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
        if own_dir:
            shutil.rmtree(base, ignore_errors=True)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="tpk-trainchaos")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    out = run_trainchaos(quick=args.quick, seed=args.seed)
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
