"""One streamed `:generate` request and the arithmetic on what came back.

The program's own load generator (`kubeflow_tpu/serve/loadgen.py`) times
from the send of a non-streamed request to its whole reply, so its "TTFT"
is the full latency. This client streams, and stamps the send, every event
that carries tokens, and the final event on the harness's clock.
"""

from __future__ import annotations

import http.client
import json
import math
import time


def generate(host: str, port: int, model: str, ids: list[int],
             max_tokens: int, timeout: float = 120.0) -> dict:
    """Returns a record: t_send, status, events [(t, n_tokens)], t_done,
    done (the final event) or error. Never raises: a request that fails is
    a record that `ok()` refuses."""
    rec = {"prompt_tokens": len(ids), "max_tokens": max_tokens,
           "t_send": None, "status": None, "events": [], "t_done": None,
           "done": None, "error": None}
    body = json.dumps({"input_ids": ids, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": True,
                       "timeout": timeout})
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        rec["t_send"] = time.monotonic()
        conn.request("POST", f"/v1/models/{model}:generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(500).decode(errors="replace")
            return rec
        for line in resp:
            now = time.monotonic()
            if not line.strip():
                continue
            ev = json.loads(line)
            if "error" in ev:
                rec["error"] = str(ev["error"])
                break
            if ev.get("done"):
                rec["done"], rec["t_done"] = ev, now
                break
            if ev.get("tokens"):
                rec["events"].append((now, len(ev["tokens"])))
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def ok(rec: dict) -> bool:
    """200, finished, exactly the tokens asked for (streamed and summed),
    every logprob finite and <= 0."""
    done = rec.get("done")
    if rec.get("status") != 200 or rec.get("error") or not done:
        return False
    want = rec["max_tokens"]
    lps = done.get("output_logprobs") or []
    return (done.get("num_output_tokens") == want
            and sum(n for _, n in rec["events"]) == want
            and len(lps) == want
            and all(isinstance(x, (int, float)) and math.isfinite(x)
                    and x <= 1e-6 for x in lps))


def ttft_s(rec: dict) -> float | None:
    """Send to the first streamed event that carries a token."""
    if not rec["events"]:
        return None
    return rec["events"][0][0] - rec["t_send"]


def tpot_s(rec: dict) -> float | None:
    """The user's mean gap between tokens after the first event: (last
    event - first event) over the tokens that came after the first event.
    The engine delivers tokens a chunk at a time, so this is a mean over
    chunks, not a gap between two tokens. None for a reply that came in one
    event."""
    ev = rec["events"]
    if len(ev) < 2:
        return None
    later = sum(n for _, n in ev[1:])
    return (ev[-1][0] - ev[0][0]) / later


def tokens_between(records: list[dict], t0: float, t1: float) -> int:
    """Output tokens whose streamed event arrived in [t0, t1), whenever
    their request was sent."""
    return sum(n for rec in records for t, n in rec["events"]
               if t0 <= t < t1)


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
