"""Background input prefetcher: host data prep + H2D off the hot loop.

The trainer's step loop used to pay `next(data)` (the grain pipeline plus
packed-row assembly), the zigzag permute, and the implicit host->device
transfer synchronously between dispatches — and every host-driven stall
in the dispatch path is time the device's queue runs dry. `Prefetcher`
moves all of that onto one worker thread
that stages up to `depth` device-resident batches ahead of compute — the
`prefetch_to_device` discipline MaxText-class JAX trainers use, and the
tf.data argument (Murray et al. 2021) that input pipelines belong off the
accelerator's critical path.

Resume correctness is the subtle part. The worker snapshots the
iterator's checkpoint state *alongside each batch as it pulls it*, and
`consumed_state()` returns the snapshot paired with the batch most
recently handed to the caller — NOT the iterator's read-ahead position.
A checkpoint taken after training batch N therefore resumes at batch
N+1 even though the worker had already pulled batches N+1..N+depth; a
kill-9 under prefetch replays exactly the right rows.

`depth=0` is the synchronous escape hatch: no thread, every call does
pull -> transform -> place inline, bit-for-bit the pre-prefetch loop
(the `data.next` fault point fires on the calling thread instead).

Failure semantics: any exception raised while pulling or preparing a
batch on the worker (including faults injected at `data.next`) is
queued in order and re-raised from `next()` on the *training* thread —
the step that would have consumed the batch is the step that fails, so
restart policies see data faults exactly like step faults. The worker
exits after queuing an error; `close()` is idempotent, drains the
queue, and joins the thread on every trainer exit path.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Iterator, Mapping

from kubeflow_tpu.utils import faults, resilience

_LOG = logging.getLogger(__name__)

#: Fires before every raw-batch pull (ctx: n = 0-based pull index). With
#: depth >= 1 it fires on the worker thread; the injected error is still
#: delivered to the training thread at the matching `next()`.
_FP_NEXT = faults.register_point(
    "data.next", "before each raw-batch pull from the input iterator; "
    "ctx: n (0-based pull index)")

#: Thread-name prefix for every prefetch worker — the test suite's
#: thread-leak guard (tests/conftest.py) keys on it.
THREAD_NAME = "tpk-prefetch"

_STOP = object()  # sentinel: the wrapped iterator is exhausted


class _Failure:
    """An exception captured on the worker, queued in stream order."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class Prefetcher:
    """Depth-K queue of prepared (transformed + device-placed) batches.

    Args:
      it: the raw batch iterator (checkpointable grain iterator or plain
        generator). The prefetcher takes ownership: nothing else may
        pull from it while the prefetcher lives.
      depth: queue capacity. 0 = synchronous passthrough (no thread);
        K >= 1 lets the worker run up to K+1 batches ahead (K queued
        plus one in hand waiting for a slot).
      transform: optional host-side per-batch transform (e.g. the zigzag
        permute) applied before placement.
      place: optional device placement (jax.device_put with the dp
        sharding / make_array_from_process_local_data). Its wall time is
        accounted as `h2d_s`.
      state_fn: returns the iterator's resume state (defaults to
        `loader.iterator_state(it)`; None for plain generators).
      component: label for the shared tpk_* metrics.
    """

    def __init__(self, it: Iterator[Any], *, depth: int,
                 transform: Callable[[Any], Any] | None = None,
                 place: Callable[[Any], Any] | None = None,
                 state_fn: Callable[[], Mapping[str, Any] | None] | None
                 = None,
                 component: str = "train"):
        if depth < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {depth}")
        from kubeflow_tpu.data.loader import iterator_state

        self._it = iter(it)
        self._depth = int(depth)
        self._transform = transform
        self._place = place
        self._state_fn = state_fn or (lambda: iterator_state(self._it))
        self._component = component
        # Worker-thread writes race the training thread's stats/window
        # reads (and depth-0 counters live on the consumer thread): one
        # lock keeps the counter quartet tear-free.
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._pulled = 0     # raw batches pulled from the iterator
        # guarded-by: _lock
        self._consumed = 0   # batches handed to the caller
        self._exc: BaseException | None = None
        self._exhausted = False
        self._closed = False
        # guarded-by: _lock
        self.data_wait_s = 0.0  # training-thread time spent inside next()
        # guarded-by: _lock
        self.h2d_s = 0.0        # wall time spent in place() (H2D staging)
        resilience.metrics.set_gauge("tpk_data_prefetch_depth",
                                     self._depth, component=component)
        self._thread: threading.Thread | None = None
        if self._depth:
            # Captured BEFORE the worker starts reading ahead: the
            # floor consumed_state() returns until a batch is consumed.
            self._consumed_state = self._state_fn()
            self._q: queue.Queue = queue.Queue(maxsize=self._depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._worker, name=THREAD_NAME, daemon=True)
            self._thread.start()

    # -- worker --------------------------------------------------------------

    # tpk-hot: prefetch-worker
    def _prep(self, raw: Any) -> Any:
        if self._transform is not None:
            raw = self._transform(raw)
        if self._place is not None:
            t0 = time.perf_counter()
            raw = self._place(raw)
            dt = time.perf_counter() - t0
            with self._lock:
                self.h2d_s += dt
            resilience.metrics.inc("tpk_data_h2d_seconds_total", dt,
                                   component=self._component)
        return raw

    def _offer(self, item: Any) -> bool:
        """Blocking put that stays responsive to close(): a worker stuck
        on a full queue must observe the stop flag, not deadlock."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # tpk-hot: prefetch-worker
    def _worker(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                n = self._pulled
            try:
                faults.fire(_FP_NEXT, n=n)
                raw = next(self._it)
            except StopIteration:
                self._offer(_STOP)
                return
            except BaseException as e:
                self._offer(_Failure(e))
                return
            with self._lock:
                self._pulled += 1
            try:
                # Snapshot BEFORE reading ahead any further: this state
                # resumes at the batch after `raw` — what a checkpoint
                # taken after training `raw` must record.
                state = self._state_fn()
                item = (self._prep(raw), state)
            except BaseException as e:
                self._offer(_Failure(e))
                return
            if not self._offer(item):
                return

    # -- consumer ------------------------------------------------------------

    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self) -> Any:
        t0 = time.perf_counter()
        try:
            if self._depth == 0:
                if self._closed:
                    raise RuntimeError("Prefetcher is closed")
                with self._lock:
                    n = self._pulled
                faults.fire(_FP_NEXT, n=n)
                raw = next(self._it)  # StopIteration propagates as-is
                with self._lock:
                    self._pulled += 1
                batch = self._prep(raw)
                with self._lock:
                    self._consumed += 1
                return batch
            if self._exc is not None:
                raise self._exc
            if self._exhausted:
                raise StopIteration
            if self._closed:
                # The queue was drained and the worker stopped — a
                # bare q.get() here would block forever.
                raise RuntimeError("Prefetcher is closed")
            item = self._q.get()
            if item is _STOP:
                self._exhausted = True
                raise StopIteration
            if isinstance(item, _Failure):
                self._exc = item.exc
                raise item.exc
            batch, state = item
            self._consumed_state = state
            with self._lock:
                self._consumed += 1
            return batch
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.data_wait_s += dt
            resilience.metrics.inc("tpk_data_wait_seconds_total", dt,
                                   component=self._component)

    next = __next__

    def consumed_state(self) -> Mapping[str, Any] | None:
        """Iterator resume state matching the batches handed out so far
        (None for plain generators). Safe to call after close()."""
        if self._depth == 0:
            return self._state_fn()
        return self._consumed_state

    @property
    def stats(self) -> dict:
        with self._lock:
            return {
                "depth": self._depth,
                "pulled": self._pulled,
                "consumed": self._consumed,
                "data_wait_s": self.data_wait_s,
                "h2d_s": self.h2d_s,
            }

    def close(self, timeout: float = 5.0) -> None:
        """Stop and join the worker (idempotent; every trainer exit path
        must land here so restarts never leak threads)."""
        self._closed = True
        if self._thread is None:
            return
        self._stop.set()
        try:  # unblock a worker waiting on a full queue
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout)
        if self._thread.is_alive():
            # Worker is wedged inside next(self._it) (e.g. a stalled
            # storage pull). Keep the handle so a later close() can
            # retry the join, and make the leak visible — the daemon
            # thread still holds the old iterator's resources.
            resilience.metrics.inc("tpk_data_prefetch_close_timeout_total",
                                   component=self._component)
            _LOG.warning(
                "prefetch worker did not exit within %.1fs (stuck in the "
                "input iterator?); thread left running, close() may be "
                "retried", timeout)
            return
        self._thread = None

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
