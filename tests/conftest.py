"""Test harness: virtual 8-device CPU mesh.

The reference tests controllers without a cluster via envtest and e2e via
kind (SURVEY.md §4); our analog for the *device* plane is
`--xla_force_host_platform_device_count=8` on the CPU backend — real XLA
collectives over 8 virtual devices on one host. Must run before jax import.
"""

import os

# The whole suite runs on the CPU and says so in the environment, so every
# worker subprocess a test launches inherits the explicit CPU request the
# worker mains insist on (utils/devices.require_tpu_or_requested_cpu).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

from kubeflow_tpu.utils.devices import force_cpu_device_count  # noqa: E402

force_cpu_device_count(8)

import jax  # noqa: E402

jax.config.update("jax_debug_nans", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process / e2e / AOT-compile tests. The default "
        "iteration tier is `pytest -m 'not slow'`; CI and round-end runs "
        "use the full suite (see README Testing).")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / resilience tests (utils/faults.py "
        "harness). Unmarked slow-wise, so `-m 'not slow'` still "
        "collects them; `-m faults` runs the failure story alone.")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture(autouse=True)
def _fresh_metrics_registry():
    """The resilience `metrics` registry and the obs tracer ring are
    process-global: without a reset, counts/spans bleed across tests and
    any assertion on exact values becomes order-dependent (passes alone,
    fails in the suite — or worse, the reverse). Every test starts from
    a clean registry; accumulation within one test is untouched."""
    from kubeflow_tpu.utils import obs
    from kubeflow_tpu.utils.resilience import metrics

    metrics.reset()
    obs.get_tracer().clear()
    yield


@pytest.fixture(autouse=True)
def _no_leaked_prefetch_threads():
    """Every trainer exit path (normal, raising step, restart/backoff
    loop, injected fault) must close its input prefetcher — a worker
    thread that outlives its test is a shutdown-path regression
    (kubeflow_tpu/data/prefetch.py). Checked after EVERY test."""
    yield
    import threading
    import time

    def leaked():
        return [t.name for t in threading.enumerate()
                if t.name.startswith("tpk-prefetch")]

    deadline = time.monotonic() + 2.0  # grace for a close() in flight
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not leaked(), (
        f"prefetch worker threads leaked: {leaked()} — a trainer exit "
        "path failed to close() its Prefetcher")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Cap cumulative compiled-executable growth across the full tier:
    438 tests build hundreds of engines/train steps in ONE process, and
    the global jit cache holds every executable forever — by ~80% of the
    suite the process dies (SIGSEGV under allocation pressure, seen
    twice at the same index in round 5). Modules don't share traces, so
    per-module cache drops only cost intra-module recompiles: none."""
    yield
    jax.clear_caches()
