"""The process boundary's view of the device: which backend this process
came up on, whether that was asked for, where compiled programs are kept.

Everything that used to decide something from `jax.default_backend()` by
its own list of names asks `on_tpu()` here; both worker mains, `bench.py`
and `chip_smoke.py`'s children call `enable_compile_cache()` first and
`require_tpu()` / `require_tpu_or_requested_cpu()` before any device work.
"""

from __future__ import annotations

import os

#: Git-ignored, at one fixed place in the checkout: every process of a
#: checkout (both mains, bench.py, the smoke's children, the next run)
#: finds the same entries, with no /tmp, pid or clock in the path. A
#: machine that should start warm is handed its cache from outside, through
#: JAX_COMPILATION_CACHE_DIR. This sandbox's directory holds CPU programs,
#: so .chiprunignore keeps it out of the chip tool's copy.
_CACHE_DIRNAME = ".jax_compile_cache"


def force_cpu_device_count(n: int) -> None:
    """Pin jax to the CPU backend with `n` virtual devices — the
    device-plane analog of envtest/kind: real XLA collectives over `n`
    host devices. Must run before any backend use; importing jax
    beforehand is fine."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def on_tpu() -> bool:
    """The one rule for "are we on a TPU": `auto` attention means the
    compiled Pallas kernel exactly when this is true, and the kernels'
    `interpret` default is its negation (interpret mode is a CPU-test
    facility)."""
    import jax

    return jax.default_backend() == "tpu"


def device_summary() -> dict:
    """The device as JAX reports it — the triple every result names."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes_in_use() -> list | None:
    """Per-device `peak_bytes_in_use`, or None where the backend keeps no
    memory stats (the CPU)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def cpu_requested() -> bool:
    """True when this process was explicitly pointed at the CPU:
    `JAX_PLATFORMS=cpu` in its environment (jax.config reads it at
    import) or `--cpu-devices` (force_cpu_device_count)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def require_tpu(who: str) -> dict:
    """For code that times something on the device: no TPU is an error,
    never a relabelled CPU run. Returns device_summary()."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"{who}: needs a TPU; JAX came up on {dev['platform']} "
            f"({dev['kind']} x{dev['count']}). Nothing was measured.")
    return dev


def require_tpu_or_requested_cpu(who: str) -> dict:
    """For the worker mains: a worker that did not ask for the CPU and
    comes up on anything but a TPU exits non-zero instead of carrying on
    at CPU speed under a TPU's name. Returns device_summary()."""
    dev = device_summary()
    if dev["platform"] != "tpu" and not cpu_requested():
        raise SystemExit(
            f"{who}: JAX came up on {dev['platform']} ({dev['kind']} "
            f"x{dev['count']}), not a TPU, and the CPU was not asked for. "
            "Pass --cpu-devices N or set JAX_PLATFORMS=cpu to run on the "
            "CPU on purpose.")
    return dev


def compile_cache_dir() -> str:
    """Where compiled programs are kept: `JAX_COMPILATION_CACHE_DIR` when
    the environment sets it, else one fixed directory in the checkout
    (the path is part of the cache key's context, so it never moves)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), _CACHE_DIRNAME)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on. With
    `JAX_COMPILATION_CACHE_DIR` set, jax.config already points there and
    nothing is touched; unset, jax is pointed at the in-checkout
    directory. Returns the directory in use."""
    path = compile_cache_dir()
    if path != os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileClock:
    """Seconds this process spent in backend compiles (persistent-cache
    retrieval included, so a warm start shows as a small number, not as
    zero compiles) and how many of them the persistent cache answered.
    Fed by jax.monitoring; listeners are process-global and cannot be
    removed one by one, so there is one per process: `compile_clock()`."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": round(self.seconds, 3),
                "compiles": self.compiles,
                "compile_cache_hits": self.cache_hits}


_CLOCK: CompileClock | None = None


def compile_clock() -> CompileClock:
    """The process's one CompileClock, made on first use: the worker
    mains take it at start-up (for `device_end`), the engine's `stats`
    and the trainer's log rows read the same one live."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK
