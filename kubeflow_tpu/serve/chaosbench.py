"""Fabric chaos harness (ISSUE 14) → CHAOSBENCH.json.

ISSUEs 9/11/13 proved the fabric's pieces in isolation; this harness
proves them TOGETHER under injected faults: the REAL router + REAL
tiny-engine replicas (each its own subprocess, so SIGKILL / SIGSTOP are
the real thing) under open-loop Poisson load, while a seeded fault
schedule kills, stalls, and drains replicas mid-run.

Arms and their pinned claims (tests/test_chaosbench.py):

  * **disagg_decode_kill** — 1 prefill + 2 decode replicas; a decode
    replica is SIGKILLed MID-STREAM and later replaced. Claim: every
    stream completes with ZERO caller-visible errors (the router
    resumes the held shipment on the survivor — `tpk_router_resume_
    total{reason}`), token counts are exact (no duplicate, no loss),
    and the fleet ran EXACTLY ONE prefill per request (zero re-prefill
    across the failover); a decode replica is also DRAINED mid-run
    (in-flight completes). Goodput recovers to >= 90% of pre-fault.
  * **unified_kill** — 2 unified replicas, one SIGKILLed and replaced.
    Unified streams have no held shipment: mid-stream deaths are
    HONEST caller-visible failures — but every one carries the
    terminal error envelope (no silent truncation), and goodput
    recovers to >= 90% of pre-fault within the bounded window.
  * **gray_stall** — 3 unified replicas; one suffers a CYCLIC
    SIGSTOP/SIGCONT stall (slow-but-alive: probes still answer — the
    binary `down` detector never fires). Run twice: gray-failure
    ejection ON vs OFF at identical seed/schedule. Claim: the ejection
    arm ejects the stalled replica to `slow` (and REJOINS it after the
    stall lifts) and its p99 stays strictly below the no-ejection
    control's.
  * **ctrl_leader_kill** — a 3-node replicated control plane (real
    binaries) behind the serving fleet; the LEADER is SIGKILLed while
    the router serves loadgen traffic. Claim: serving does not blip
    (the data-plane hot path has no control-plane dependency — zero
    non-200s), and the autoscaler's next reconcile (spec.replicas
    patch) succeeds against the promoted follower. Records
    skipped-with-reason when the binary is not built (the
    test_ctrlbench convention).

Harness discipline (PROFILE §11/§13): open-loop arrivals FIRE AT
SCHEDULE; replicas are REAL engines behind real ModelServers and the
real router (absolute latencies are 1-CPU tiny-model numbers — the
artifact is the claims and the arm DELTAS); every claim is computed
from PER-REQUEST provenance rows (replica, resume count, fault-window
overlap), not aggregates; the fault schedule is seeded and recorded.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

#: Serving model name every worker registers under.
MODEL = "m"

#: Engine shape shared by every REAL worker (the disaggbench family:
#: tiny 2-layer llama, paged KV, pipelined decode).
GEN_KW = dict(slots=4, max_len=120, chunk=8, prefill_buckets=(16, 32),
              kv_block_size=8, kv_blocks=0, pipeline_depth=2)


# -- subprocess replica workers ---------------------------------------------


def _worker_main(args) -> int:
    """`python -m kubeflow_tpu.serve.chaosbench --worker`: one replica
    subprocess — builds the tiny REAL engine (or the fake timed model
    with --fake), serves it on a ModelServer, prints the ready line,
    and parks until killed. Being a real process is the point: SIGKILL
    and SIGSTOP from the parent are the actual faults."""
    import dataclasses

    from kubeflow_tpu.serve.server import ModelServer

    if args.fake:
        from kubeflow_tpu.serve.loadgen import FakeGenerativeModel

        model = FakeGenerativeModel(MODEL, slots=4)
    else:
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.models.llama import Llama, llama_tiny
        from kubeflow_tpu.serve.generation import GenerativeJAXModel
        from kubeflow_tpu.utils.devices import enable_compile_cache

        enable_compile_cache()  # every worker compiles the same engine
        cfg = dataclasses.replace(llama_tiny(), dtype=jnp.float32,
                                  num_layers=2)
        net = Llama(cfg)
        params = jax.jit(lambda r: net.init(
            r, jnp.zeros((1, 8), jnp.int32))["params"])(
                jax.random.key(0))
        model = GenerativeJAXModel(
            MODEL, net, params, cfg,
            generation=dict(GEN_KW, role=args.role, seed=args.seed))
    server = ModelServer(max_inflight=128, executor_workers=128)
    server.repo.register(model, load=not args.fake)
    port = server.start_background()
    print(json.dumps({"event": "chaos_replica_ready", "port": port,
                      "role": args.role, "pid": os.getpid()}),
          flush=True)
    while True:  # parked: the parent kills/stalls/terminates us
        time.sleep(3600)


class ReplicaProc:
    """One replica subprocess + its fault controls."""

    def __init__(self, role: str = "any", *, fake: bool = False,
                 seed: int = 0, startup_timeout_s: float = 300.0):
        self.role = role
        cmd = [sys.executable, "-m", "kubeflow_tpu.serve.chaosbench",
               "--worker", "--role", role, "--seed", str(seed)]
        if fake:
            cmd.append("--fake")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True)
        self.port: int | None = None
        # The ready line is read on a side thread: readline() blocks
        # indefinitely, so waiting on it directly would let a wedged
        # worker (hung engine build, no output, no exit) hold the
        # whole harness hostage past startup_timeout_s.
        ready = threading.Event()

        def read_ready():
            while True:
                line = self.proc.stdout.readline()
                if not line:
                    return
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("event") == "chaos_replica_ready":
                    self.port = int(ev["port"])
                    ready.set()
                    return

        reader = threading.Thread(target=read_ready, daemon=True,
                                  name="tpk-chaos-worker-ready")
        reader.start()
        if not ready.wait(startup_timeout_s) or self.port is None:
            self.proc.kill()
            raise RuntimeError(
                f"chaos replica worker (role={role}) never became "
                "ready")
        self.url = f"http://127.0.0.1:{self.port}"

    def kill(self) -> None:
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=10)

    def stall(self) -> None:
        self.proc.send_signal(signal.SIGSTOP)

    def unstall(self) -> None:
        self.proc.send_signal(signal.SIGCONT)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()

    def scrape(self, timeout_s: float = 5.0) -> str:
        with urllib.request.urlopen(f"{self.url}/metrics",
                                    timeout=timeout_s) as r:
            return r.read().decode()


def _metric_value(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and " " in line:
            base = line.partition(" ")[0].partition("{")[0]
            if base == name:
                try:
                    total += float(line.rpartition(" ")[2])
                except ValueError:
                    pass
    return total


# -- streaming open-loop driver ---------------------------------------------


def _stream_one(base: str, payload: dict, t_origin: float,
                timeout_s: float = 60.0) -> dict:
    """One streaming :generate through the router, reading frames
    INCREMENTALLY. Records per-request truth: token count, error
    frames, the router's provenance (replica header + the done frame's
    `_router` resume/replica trail), TTFT, and the request's wall
    window (for fault-overlap arithmetic)."""
    import urllib.parse

    parts = urllib.parse.urlsplit(base)
    rec = {"t_start_s": time.monotonic() - t_origin, "status": -1,
           "tokens": 0, "ttft_ms": None, "error_frame": False,
           "resumes": 0, "replicas": [], "done": False}
    t0 = time.monotonic()
    conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                      timeout=timeout_s)
    try:
        conn.request(
            "POST", f"/v1/models/{MODEL}:generate",
            body=json.dumps(dict(payload, stream=True)),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        rec["replica_hdr"] = resp.getheader("X-Tpk-Replica")
        buf = b""
        while True:
            try:
                chunk = resp.read1(65536)
            except (http.client.HTTPException, OSError):
                break  # truncation: any terminal envelope already read
            if not chunk:
                break
            buf += chunk
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if not line.strip():
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("tokens") and rec["ttft_ms"] is None:
                    rec["ttft_ms"] = (time.monotonic() - t0) * 1e3
                rec["tokens"] += len(ev.get("tokens") or ())
                if "error" in ev:
                    rec["error_frame"] = True
                if ev.get("done"):
                    rec["done"] = True
                    prov = ev.get("_router") or {}
                    rec["resumes"] = int(prov.get("resumes", 0))
                    rec["replicas"] = list(prov.get("replicas", ()))
            if rec["done"]:
                break
    except Exception as e:
        rec["transport_error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    rec["t_end_s"] = time.monotonic() - t_origin
    rec["total_ms"] = (time.monotonic() - t0) * 1e3
    return rec


def _open_loop_stream(base: str, prompts, *, rate_rps: float,
                      duration_s: float, max_tokens: int,
                      seed: int) -> list[dict]:
    """Seeded Poisson arrivals, fired AT SCHEDULE (open loop), all
    streaming. One provenance record per request."""
    rng = np.random.default_rng(seed)
    arrivals = []
    t = 0.0
    while t < duration_s:
        t += float(rng.exponential(1.0 / rate_rps))
        if t < duration_s:
            arrivals.append(t)
    records: list[dict] = []
    lock = threading.Lock()
    threads = []
    start = time.monotonic()

    def fire(i: int, sched: float):
        payload = {"input_ids": prompts[i % len(prompts)],
                   "max_tokens": max_tokens}
        rec = _stream_one(base, payload, start)
        rec["sched_s"] = sched
        with lock:
            records.append(rec)

    for i, sched in enumerate(arrivals):
        delay = start + sched - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(i, sched), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=120.0)
    return records


def _overlaps(rec: dict, t0: float, t1: float) -> bool:
    return rec["t_start_s"] < t1 and rec.get("t_end_s", rec["t_start_s"]) > t0


def _goodput(records: list[dict], t0: float, t1: float,
             ok=lambda r: r.get("done")) -> float:
    """Completions/second landing inside [t0, t1)."""
    n = sum(1 for r in records
            if ok(r) and t0 <= r.get("t_end_s", -1.0) < t1)
    return n / max(t1 - t0, 1e-9)


def _pct(vals, p):
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return None
    return round(vals[min(int(len(vals) * p), len(vals) - 1)], 2)


# -- fault schedule ---------------------------------------------------------


def make_schedule(seed: int, duration_s: float) -> dict:
    """The seeded fault schedule, derived from `seed` inside bounded
    windows and RECORDED in the artifact — reruns at the same seed
    replay the same chaos."""
    rng = np.random.default_rng(seed + 7919)
    kill_t = float(rng.uniform(0.30, 0.38) * duration_s)
    relaunch_t = kill_t + 0.16 * duration_s
    drain_t = float(rng.uniform(0.70, 0.78) * duration_s)
    stall_t0 = float(rng.uniform(0.25, 0.30) * duration_s)
    stall_t1 = stall_t0 + 0.35 * duration_s
    return {
        "kill_t_s": round(kill_t, 2),
        "relaunch_t_s": round(relaunch_t, 2),
        "drain_t_s": round(drain_t, 2),
        "stall_window_s": [round(stall_t0, 2), round(stall_t1, 2)],
        "stall_duty": {"stop_s": 0.45, "run_s": 0.15},
        "prefault_window_s": [round(0.08 * duration_s, 2),
                              round(kill_t, 2)],
        "recovery_window_s": [round(relaunch_t + 0.08 * duration_s, 2),
                              round(duration_s, 2)],
    }


class _FaultInjector(threading.Thread):
    """Runs (t_rel_s, fn) actions against the traffic clock."""

    def __init__(self, t_origin: float, actions):
        super().__init__(daemon=True, name="tpk-chaos-faults")
        self.t_origin = t_origin
        self.actions = sorted(actions)
        self.fired: list[float] = []

    def run(self):
        for t_rel, fn in self.actions:
            delay = self.t_origin + t_rel - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                fn()
            except Exception:
                pass  # the bench records outcomes, not injector luck
            self.fired.append(t_rel)


def _kill_when_busy(fleet, name: str, proc: ReplicaProc,
                    t_origin: float, not_before: float,
                    give_up: float) -> float:
    """SIGKILL `proc` at the first instant >= `not_before` (the seeded
    schedule time) at which the router holds an IN-FLIGHT stream on the
    replica — the warm tiny engine finishes a 64-token stream in tens
    of milliseconds, so a purely time-scheduled kill usually lands
    between streams and the mid-stream claim would be vacuous. The
    actual fire time is returned and recorded in the artifact."""
    while time.monotonic() - t_origin < not_before:
        time.sleep(0.005)
    while time.monotonic() - t_origin < give_up:
        rec = fleet.get(name)
        if rec is not None and rec["outstanding"] > 0:
            # Outstanding covers the whole forward, connect included:
            # ride past the TTFT so the kill lands inside the RELAY
            # window (a connect-phase kill would only exercise the
            # plain handoff retry, not the mid-stream resume), then
            # confirm the stream is still open.
            time.sleep(0.03)
            rec = fleet.get(name)
            if rec is not None and rec["outstanding"] > 0:
                break
        time.sleep(0.002)
    proc.kill()
    return time.monotonic() - t_origin


def _stall_cycler(proc: ReplicaProc, until_rel: float, t_origin: float,
                  stop_s: float, run_s: float):
    """Cyclic SIGSTOP/SIGCONT — a slow-but-ALIVE gray replica: probes
    answer in the CONT windows, so the binary down-detector never
    fires, yet every request it owns crawls."""
    def run():
        try:
            while time.monotonic() - t_origin < until_rel:
                proc.stall()
                time.sleep(stop_s)
                proc.unstall()
                time.sleep(run_s)
        finally:
            proc.unstall()
    th = threading.Thread(target=run, daemon=True,
                          name="tpk-chaos-stall")
    th.start()
    return th


# -- arms -------------------------------------------------------------------


def _prompts(seed: int, n: int, length: int, vocab: int = 30000):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(2, vocab, length)]
            for _ in range(n)]


def _mk_router(gray: bool = True):
    from kubeflow_tpu.serve.fleet import Fleet
    from kubeflow_tpu.serve.router import RouterServer

    fleet = Fleet(poll_interval_s=0.2, gray_ejection=gray)
    router = RouterServer(fleet, forward_timeout_s=30.0)
    base = f"http://127.0.0.1:{router.start_background()}"
    return router, base


def arm_disagg_decode_kill(duration: float, rate: float,
                           seed: int) -> dict:
    """SIGKILL a decode replica mid-stream; drain another later."""
    from kubeflow_tpu.utils.resilience import metrics as res_metrics

    sched = make_schedule(seed, duration)
    pre = ReplicaProc("prefill", seed=seed)
    decs = {"d0": ReplicaProc("decode", seed=seed + 1),
            "d1": ReplicaProc("decode", seed=seed + 2)}
    router, base = _mk_router()
    replacement: dict = {}
    resumes0 = (res_metrics.get("tpk_router_resume_total",
                                reason="death") or 0) + \
               (res_metrics.get("tpk_router_resume_total",
                                reason="stall") or 0)
    try:
        router.fleet.add("pre0", pre.url, role="prefill")
        for name, proc in decs.items():
            router.fleet.add(name, proc.url, role="decode")
        time.sleep(0.5)  # first scrape
        fired: dict = {}

        def do_kill():
            fired["kill_t_s"] = round(_kill_when_busy(
                router.fleet, "d0", decs["d0"], t_origin,
                sched["kill_t_s"], sched["relaunch_t_s"] - 0.5), 3)

        def do_relaunch():
            replacement["proc"] = ReplicaProc("decode", seed=seed + 3)
            router.fleet.add("d2", replacement["proc"].url,
                             role="decode")

        def do_drain():
            router.fleet.drain("d1")

        t_origin = time.monotonic()
        inj = _FaultInjector(t_origin, [
            (sched["kill_t_s"], do_kill),
            (sched["relaunch_t_s"], do_relaunch),
            (sched["drain_t_s"], do_drain),
        ])
        inj.start()
        # Streams must be LONG relative to the kill: ~64 tiny-model
        # tokens keeps several streams in flight on the doomed replica
        # at the kill instant, so the resume path is genuinely mid-
        # stream, not connect-phase.
        prompts = _prompts(seed, 24, 12)
        records = _open_loop_stream(base, prompts, rate_rps=rate,
                                    duration_s=duration,
                                    max_tokens=96, seed=seed)
        inj.join(timeout=10)
        completed = [r for r in records if r.get("done")]
        kill_fired = fired.get("kill_t_s", sched["kill_t_s"])
        fault_hits = [r for r in records
                      if _overlaps(r, kill_fired - 0.05,
                                   kill_fired + 0.05)]
        pre_w, rec_w = sched["prefault_window_s"], \
            sched["recovery_window_s"]
        g_pre = _goodput(records, *pre_w)
        g_rec = _goodput(records, *rec_w)
        resumes = sum(r.get("resumes", 0) for r in records)
        resumes_metric = ((res_metrics.get("tpk_router_resume_total",
                                           reason="death") or 0)
                          + (res_metrics.get("tpk_router_resume_total",
                                             reason="stall") or 0)
                          - resumes0)
        prefill_chunks = _metric_value(
            pre.scrape(), "tpk_engine_prefill_chunks_total")
        # Flight-recorder provenance (ISSUE 20): fetched over the admin
        # endpoint (not in-process) so the artifact pins what an
        # operator would actually see, and fetched BEFORE teardown —
        # the ring dies with the router.
        with urllib.request.urlopen(f"{base}/admin/flightrecorder",
                                    timeout=5.0) as r:
            fr = json.loads(r.read())
        fr_resumed_ok = [rec for rec in fr["records"]
                         if rec.get("resumes", 0) > 0
                         and rec.get("outcome") == "ok"]
        flightrecorder = {
            "records": len(fr["records"]),
            "snapshots": len(fr["snapshots"]),
            "snapshot_reasons": sorted({s.get("reason", "")
                                        for s in fr["snapshots"]}),
            "resumed_ok": len(fr_resumed_ok),
            "resumed_ok_multi_replica": sum(
                1 for rec in fr_resumed_ok
                if len(rec.get("replicas", [])) >= 2),
        }
        return {
            "schedule": sched,
            "kill_fired_t_s": fired.get("kill_t_s"),
            "requests": len(records),
            "completed": len(completed),
            "caller_visible_errors": sum(
                1 for r in records
                if r.get("error_frame") or not r.get("done")),
            "token_integrity_violations": sum(
                1 for r in completed if r["tokens"] != 96),
            "streams_overlapping_kill": len(fault_hits),
            "resumes": resumes,
            "router_resume_metric": resumes_metric,
            "resumed_requests": sum(1 for r in records
                                    if r.get("resumes", 0) > 0),
            "fleet_prefill_chunks": prefill_chunks,
            "goodput_prefault_rps": round(g_pre, 2),
            "goodput_recovery_rps": round(g_rec, 2),
            "goodput_recovery_ratio": round(g_rec / max(g_pre, 1e-9), 3),
            "ttft_p50_ms": _pct([r["ttft_ms"] for r in completed], .5),
            "ttft_p99_ms": _pct([r["ttft_ms"] for r in completed], .99),
            "flightrecorder": flightrecorder,
            "router": {k: v for k, v in
                       router.router.stats_snapshot().items()
                       if k in ("handoffs", "handoff_retries", "resumes",
                                "resume_failures", "retries", "errors",
                                "no_replica")},
        }
    finally:
        router.stop()
        pre.stop()
        for p in decs.values():
            p.stop()
        if "proc" in replacement:
            replacement["proc"].stop()


def arm_unified_kill(duration: float, rate: float, seed: int) -> dict:
    """SIGKILL a unified replica mid-stream: honest caller-visible
    failures (every one enveloped), bounded recovery."""
    sched = make_schedule(seed, duration)
    reps = {"u0": ReplicaProc("any", seed=seed),
            "u1": ReplicaProc("any", seed=seed + 1)}
    router, base = _mk_router()
    replacement: dict = {}
    try:
        for name, proc in reps.items():
            router.fleet.add(name, proc.url)
        time.sleep(0.5)
        fired: dict = {}

        def do_kill():
            fired["kill_t_s"] = round(_kill_when_busy(
                router.fleet, "u0", reps["u0"], t_origin,
                sched["kill_t_s"], sched["relaunch_t_s"] - 0.5), 3)

        def do_relaunch():
            replacement["proc"] = ReplicaProc("any", seed=seed + 2)
            router.fleet.add("u2", replacement["proc"].url)

        t_origin = time.monotonic()
        inj = _FaultInjector(t_origin, [
            (sched["kill_t_s"], do_kill),
            (sched["relaunch_t_s"], do_relaunch),
        ])
        inj.start()
        prompts = _prompts(seed + 5, 24, 12)
        records = _open_loop_stream(base, prompts, rate_rps=rate,
                                    duration_s=duration,
                                    max_tokens=96, seed=seed)
        inj.join(timeout=10)
        completed = [r for r in records if r.get("done")]
        failed = [r for r in records if not r.get("done")]
        # Honest accounting: failures that had their 200 status out
        # must carry the terminal envelope (error_frame); ones that
        # never connected surface as transport/5xx errors.
        truncated = [r for r in failed if r.get("status") == 200]
        pre_w, rec_w = sched["prefault_window_s"], \
            sched["recovery_window_s"]
        g_pre = _goodput(records, *pre_w)
        g_rec = _goodput(records, *rec_w)
        return {
            "schedule": sched,
            "kill_fired_t_s": fired.get("kill_t_s"),
            "requests": len(records),
            "completed": len(completed),
            "failed": len(failed),
            "failed_overlapping_kill": sum(
                1 for r in failed
                if _overlaps(r, 0.0, sched["relaunch_t_s"])),
            "truncated_with_envelope": sum(
                1 for r in truncated if r.get("error_frame")),
            "truncated_silently": sum(
                1 for r in truncated if not r.get("error_frame")),
            "goodput_prefault_rps": round(g_pre, 2),
            "goodput_recovery_rps": round(g_rec, 2),
            "goodput_recovery_ratio": round(g_rec / max(g_pre, 1e-9), 3),
        }
    finally:
        router.stop()
        for p in reps.values():
            p.stop()
        if "proc" in replacement:
            replacement["proc"].stop()


def arm_gray_stall(duration: float, rate: float, seed: int) -> dict:
    """Cyclic SIGSTOP/CONT on one of three replicas; ejection ON vs OFF
    at the identical seed/schedule."""
    from kubeflow_tpu.serve.loadgen import open_loop
    from kubeflow_tpu.utils.resilience import metrics as res_metrics

    sched = make_schedule(seed, duration)

    def run(gray: bool) -> dict:
        reps = [ReplicaProc("any", seed=seed + i) for i in range(3)]
        router, base = _mk_router(gray=gray)
        ej0 = sum(res_metrics.get("tpk_fleet_ejections_total",
                                  replica=f"g{i}") or 0
                  for i in range(3))
        rj0 = sum(res_metrics.get("tpk_fleet_rejoins_total",
                                  replica=f"g{i}") or 0
                  for i in range(3))
        try:
            for i, proc in enumerate(reps):
                router.fleet.add(f"g{i}", proc.url)
            time.sleep(0.6)
            t_origin = time.monotonic()
            t0, t1 = sched["stall_window_s"]
            duty = sched["stall_duty"]
            inj = _FaultInjector(t_origin, [
                (t0, lambda: _stall_cycler(
                    reps[0], t1, t_origin, duty["stop_s"],
                    duty["run_s"])),
            ])
            inj.start()
            prompts = _prompts(seed + 9, 24, 12)
            records = open_loop(base, MODEL, prompts, rate_rps=rate,
                                duration_s=duration, max_tokens=8,
                                deadline_ms=None, seed=seed)
            inj.join(timeout=10)
            # Post-stall: give the half-open probes room to rejoin.
            state = router.fleet.get("g0")["state"]
            rejoin_deadline = time.monotonic() + 12.0
            while gray and state == "slow" \
                    and time.monotonic() < rejoin_deadline:
                time.sleep(0.3)
                state = router.fleet.get("g0")["state"]
            lat = [r["latency_ms"] for r in records
                   if r["status"] == 200]
            stall_hits = [r for r in records if _overlaps(r, t0, t1)]
            # The honest tail comparison is the SECOND HALF of the
            # stall window: ejection trips within the first couple of
            # strikes, so requests arriving after the midpoint see the
            # post-ejection fleet — while the control keeps placing a
            # share of them onto the stalled replica. (Overall p99 at
            # these request counts is just the worst sample, and BOTH
            # arms own at least one pre-ejection crawl.)
            mid = (t0 + t1) / 2
            late = [r for r in records if mid <= r["t_start_s"] < t1]
            return {
                "requests": len(records),
                "ok": sum(1 for r in records if r["status"] == 200),
                "errors": sum(1 for r in records
                              if r["status"] not in (200, 503, 504)),
                "p50_ms": _pct(lat, 0.5),
                "p99_ms": _pct(lat, 0.99),
                "late_window_p99_ms": _pct(
                    [r["latency_ms"] for r in late
                     if r["status"] == 200], 0.99),
                "late_window_requests": len(late),
                "late_window_stalled_hits": sum(
                    1 for r in late if r.get("replica") == "g0"),
                "stall_overlapping_requests": len(stall_hits),
                "stalled_replica_requests_during_window": sum(
                    1 for r in stall_hits if r.get("replica") == "g0"),
                "ejections": sum(
                    res_metrics.get("tpk_fleet_ejections_total",
                                    replica=f"g{i}") or 0
                    for i in range(3)) - ej0,
                "rejoins": sum(
                    res_metrics.get("tpk_fleet_rejoins_total",
                                    replica=f"g{i}") or 0
                    for i in range(3)) - rj0,
                "final_stalled_state": state,
            }
        finally:
            router.stop()
            for p in reps:
                p.stop()

    on = run(gray=True)
    off = run(gray=False)
    return {
        "schedule": sched,
        "ejection_on": on,
        "ejection_off": off,
        "p99_ratio_on_vs_off": round(
            (on["p99_ms"] or 0) / max(off["p99_ms"] or 1e-9, 1e-9), 3),
        "late_window_p99_ratio": round(
            (on["late_window_p99_ms"] or 0)
            / max(off["late_window_p99_ms"] or 1e-9, 1e-9), 3),
    }


def arm_ctrl_leader_kill(duration: float, rate: float,
                         seed: int, workdir: str) -> dict:
    """SIGKILL the replicated control-plane LEADER while the router
    serves traffic; serving must not blip and the autoscaler's next
    reconcile must land on the promoted follower."""
    try:
        from kubeflow_tpu.controlplane.client import find_binary

        find_binary()
    except (ImportError, FileNotFoundError):
        return {"skipped": "binary_not_built"}
    from kubeflow_tpu.controlplane.replication import ReplicaSet
    from kubeflow_tpu.serve.fleet import ControlPlaneScaler
    from kubeflow_tpu.serve.loadgen import open_loop

    sched = make_schedule(seed, duration)
    rs = ReplicaSet(workdir, n=3, lease_ms=400)
    rs.start()
    reps = [ReplicaProc("any", seed=seed + i) for i in range(2)]
    router, base = _mk_router()
    killed: dict = {}
    try:
        lead = rs.wait_leader()
        client = rs.client(timeout=30.0, deadline_s=30.0)
        # replicas=0: the reconcile target EXISTS (created pre-kill, so
        # the promoted follower must have replicated it) without the
        # controller launching replica processes into the bench's CPU
        # budget (there is no real bundle behind it).
        client.create("InferenceService", "chaos-isvc",
                      {"model": {"name": MODEL,
                                 "model_dir": "/nonexistent-chaos"},
                       "replicas": 0, "cpu_devices": 1})
        for i, proc in enumerate(reps):
            router.fleet.add(f"c{i}", proc.url)
        time.sleep(0.5)

        def do_kill():
            killed["lead"] = lead
            rs.handles[lead].proc.send_signal(signal.SIGKILL)

        t_origin = time.monotonic()
        inj = _FaultInjector(t_origin, [(sched["kill_t_s"], do_kill)])
        inj.start()
        prompts = _prompts(seed + 13, 24, 12)
        records = open_loop(base, MODEL, prompts, rate_rps=rate,
                            duration_s=duration, max_tokens=8,
                            deadline_ms=None, seed=seed)
        inj.join(timeout=10)
        # The reconcile AFTER failover: the scaler's spec.replicas
        # patch rides the client's redirect-chasing to the promoted
        # follower.
        scaler = ControlPlaneScaler(client, "chaos-isvc")
        scaler.scale_up()
        after = client.get("InferenceService", "chaos-isvc")
        new_lead = rs.wait_leader(exclude=lead)
        client.delete("InferenceService", "chaos-isvc")
        client.close()
        return {
            "schedule": sched,
            "requests": len(records),
            "ok": sum(1 for r in records if r["status"] == 200),
            "non_200_during_failover": sum(
                1 for r in records if r["status"] != 200),
            "killed_leader": lead,
            "promoted_leader": new_lead,
            "reconcile_replicas_after": int(
                after["spec"]["replicas"]),
        }
    finally:
        router.stop()
        for p in reps:
            p.stop()
        rs.stop()


# -- entrypoint -------------------------------------------------------------


def run_chaosbench(quick: bool = False, seed: int = 0) -> dict:
    import shutil
    import tempfile

    duration = 12.0 if quick else 26.0
    rate = 3.0 if quick else 4.0
    result: dict = {
        "metric": "chaosbench",
        "mode": "real-tiny-engines-subprocess",
        "note": ("replicas are REAL GenerationEngines (tiny model, "
                 "CPU) in their OWN subprocesses behind real "
                 "ModelServers and the real router, so SIGKILL/SIGSTOP "
                 "are the real faults; absolute latencies are 1-CPU "
                 "tiny-model numbers — the artifact is the claims "
                 "(zero-error resume, bounded recovery, ejection vs "
                 "control) computed from per-request provenance rows"),
        "params": {"duration_s": duration, "rate_rps": rate,
                   "seed": seed, "quick": bool(quick),
                   "gen_kw": dict(GEN_KW)},
        "arms": {},
    }
    result["arms"]["disagg_decode_kill"] = arm_disagg_decode_kill(
        duration, rate, seed)
    result["arms"]["unified_kill"] = arm_unified_kill(
        duration, rate, seed)
    result["arms"]["gray_stall"] = arm_gray_stall(
        duration, max(rate * 0.75, 2.0), seed)
    base = tempfile.mkdtemp(prefix="tpk-chaos-ctrl-")
    try:
        result["arms"]["ctrl_leader_kill"] = arm_ctrl_leader_kill(
            duration, rate, seed, base)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return result


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="tpk-chaosbench")
    p.add_argument("--worker", action="store_true")
    p.add_argument("--role", default="any",
                   choices=("any", "prefill", "decode", "unified"))
    p.add_argument("--fake", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--quick", action="store_true")
    args = p.parse_args(argv)
    if args.worker:
        if args.role == "any":
            args.role = "unified"
        return _worker_main(args)
    out = run_chaosbench(quick=args.quick)
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
