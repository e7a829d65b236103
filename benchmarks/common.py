"""Plumbing shared by the cell kinds: child processes, their JSON lines,
HTTP, files found by name. Copied from chip_smoke.py (which passed on the
chip in PR 21) so that a later PR to the program cannot move the yardstick.
Plain stdlib; nothing here imports JAX, because a chip belongs to one
process at a time and that process is the child.
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: The driver allows a run 360 s, and the first run of a cell in a
#: checkout (it compiles) 1200 s. The harness cannot tell which it is, so
#: every wait is bounded by the longer one less room to stop and report.
DEADLINE_S = 1140.0


class BenchError(Exception):
    """The run has no result: wrong platform, a child that died, a file
    that is not there. run.py prints the message and exits non-zero."""


def load_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except OSError as e:
        raise BenchError(f"cannot read {path}: {e}") from None
    except ValueError as e:
        raise BenchError(f"{path} is not JSON: {e}") from None


def load_module(path: str):
    """A harness file found by its name on disk: `kinds/<kind>.py`,
    `loops/<loop>.py`, `readers/<reader>.py`. No registry, so a later PR
    adds one by adding the file."""
    if not os.path.isfile(path):
        raise BenchError(f"no such harness file: {path}")
    name = "bench_" + os.path.relpath(path, BENCH)[:-3].replace(os.sep, "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    """One run of one cell: what was asked, the children started (all
    stopped on the way out), and what the kind found (`facts`), which is
    what the per-layer readers read."""

    def __init__(self, *, cell: dict, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, rehearse: bool, t0: float):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds = seed, seconds
        self.trace, self.rehearse = trace, rehearse
        self.t0 = t0
        self.chips = int(cell["chips"])
        self.platform = "cpu" if rehearse else "tpu"
        self.out = os.path.join(BENCH, "out", cell["name"])
        self.children: list[subprocess.Popen] = []
        self.facts: dict = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [ROOT] + [p for p in self.env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        self.env.pop("BENCH_RUN", None)  # the driver's own; not ours
        if rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"

    def left(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t0)

    def spawn(self, argv: list[str], log_path: str, *,
              stdout=None) -> subprocess.Popen:
        """Start `python <argv>` from the checkout's root. stderr goes to
        `log_path`; so does stdout unless the caller wants to read it as
        it comes (`stdout=subprocess.PIPE`)."""
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=ROOT, env=self.env,
                stdout=log if stdout is None else stdout,
                stderr=log if stdout is not None else subprocess.STDOUT,
                text=True, bufsize=1, start_new_session=True)
        finally:
            log.close()  # the child holds its own descriptor
        self.children.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen, grace_s: float = 60.0) -> None:
        """SIGTERM and wait: a SIGKILLed libtpu process can leave the chip
        locked, so the kill is the last resort only."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def stop_all(self) -> None:
        for proc in self.children:
            self.stop(proc, grace_s=30.0)

    def check_device(self, row: dict | None, who: str) -> dict:
        """The child's own `device` line, its first, printed before
        anything compiles. Another platform or another number of chips than
        the cell asks for is a failed run, never a fallback."""
        if row is None:
            raise BenchError(f"{who}: never reported its device")
        dev = {"platform": row.get("platform"), "kind": row.get("kind"),
               "count": row.get("count")}
        if dev["platform"] != self.platform:
            raise BenchError(f"{who}: came up on {dev}; this run needs "
                             f"platform {self.platform!r}")
        if not self.rehearse and dev["count"] != self.chips:
            raise BenchError(f"{who}: came up on {dev['count']} chips; the "
                             f"cell asks for {self.chips}")
        self.facts["device"] = dev
        return dev


def json_lines(path: str) -> list[dict]:
    rows = []
    with open(path, errors="replace") as fh:
        for line in fh:
            row = parse_json_line(line)
            if row is not None:
                rows.append(row)
    return rows


def parse_json_line(line: str) -> dict | None:
    line = line.strip()
    if line.startswith("{"):
        try:
            row = json.loads(line)
        except ValueError:
            return None
        return row if isinstance(row, dict) else None
    return None


def event(rows: list[dict], name: str) -> dict | None:
    return next((r for r in rows if r.get("event") == name), None)


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, errors="replace") as fh:
            return fh.read()[-n:]
    except OSError as e:
        return f"<{e}>"


def get_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def peak_bytes(end_row: dict | None) -> int:
    """`memory_peak_bytes`: the fullest chip's peak from the child's
    `device_end` line; 0 where the backend keeps no memory stats (the
    CPU of a rehearsal)."""
    peaks = (end_row or {}).get("peak_bytes_in_use")
    return max(peaks) if peaks else 0


def summarize_trace(ctx: Ctx, trace_dir: str) -> dict | None:
    """Reduce the `*.xplane.pb` under `trace_dir` in a short child of its
    own (xplane.py imports jax.profiler; this parent never does), pinned to
    the CPU and started only after the chip's owner has exited. A measured
    run whose trace shows no operation on the device has no result; a
    rehearsal's CPU trace has no device plane and gives None."""
    env = dict(ctx.env)
    env["JAX_PLATFORMS"] = "cpu"
    out = os.path.join(ctx.out, "xplane_summary.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "xplane.py"), trace_dir, out],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=max(ctx.left(), 30.0))
    summary = load_json(out) if proc.returncode == 0 else None
    if summary is None or not summary["busy_s"]:
        if not ctx.rehearse:
            raise BenchError("the traced run left no trace of an operation "
                             f"on the device: {proc.stderr[-2000:]}")
        return None
    return summary
