"""Traffic loop "closed": a fixed number of callers, each sending its next
request only when the previous one has come back. A slow server therefore
gets less load; that is what an evaluation or batch pipeline with a fixed
number of workers does. The open loop (arrivals on a schedule, timed from
the due time) arrives as loops/open.py, a new file.
"""

from __future__ import annotations

import itertools
import threading
import time


class Loop:
    def __init__(self, mix: dict, send, request_at):
        """`send(request) -> record` does one request; `request_at(i)` is
        the i-th request of the run (pure, so any caller may ask)."""
        self._send, self._request_at = send, request_at
        self._next = itertools.count()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.records: list[dict] = []
        self._threads = [threading.Thread(target=self._caller, daemon=True,
                                          name=f"caller-{i}")
                         for i in range(int(mix["callers"]))]

    def _caller(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                i = next(self._next)
            rec = self._send(self._request_at(i))
            with self._lock:
                self.records.append(rec)

    def start(self) -> None:
        for th in self._threads:
            th.start()

    def stop(self, grace_s: float) -> int:
        """No new requests; wait up to `grace_s` for those in flight.
        Returns how many callers were still waiting for a reply."""
        self._stop.set()
        end = time.monotonic() + grace_s
        for th in self._threads:
            th.join(timeout=max(end - time.monotonic(), 0.0))
        return sum(th.is_alive() for th in self._threads)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self.records)
