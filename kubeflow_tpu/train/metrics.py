"""Step metrics and MFU accounting.

The reference delegates training metrics to user containers and scrapes them
back via Katib's stdout-regex sidecar (SURVEY.md §5.5); here the runtime owns
a metrics channel directly: per-step wall time, tokens/sec, and MFU computed
with the BASELINE.md convention MFU = 6·N·tok/s ÷ (chips · peak BF16 FLOP/s).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax

#: Peak dense bf16 FLOP/s per chip, keyed by `device_kind` exactly as
#: JAX spells it. Figures: Google Cloud TPU documentation, the "System
#: architecture" page of each version (v4 275, v5e 197, v5p 459, v6e 918
#: TFLOP/s); kind spellings: jaxlib 0.9.0's own table,
#: jax/_src/pallas/mosaic/tpu_info.py. A kind that is not here is an
#: error — an assumed peak turns every MFU after it into fiction.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> float | None:
    """Peak bf16 FLOP/s of `device` (default: the first device). None on
    the CPU platform — there is no accelerator peak to hold a CPU run
    against, so its MFU is "not measured", never a number. Raises on an
    accelerator whose kind is not in the table."""
    dev = device if device is not None else jax.devices()[0]
    if dev.platform == "cpu":
        return None
    try:
        return PEAK_BF16_FLOPS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s recorded for device_kind "
            f"{dev.device_kind!r} (platform {dev.platform!r}); add it to "
            f"PEAK_BF16_FLOPS with its source — have "
            f"{sorted(PEAK_BF16_FLOPS)}") from None


@dataclasses.dataclass
class StepTimer:
    """Tracks smoothed step time / tokens/s / MFU across the training loop."""

    num_params: int
    tokens_per_step: int
    num_chips: int = 0
    warmup_steps: int = 2  # exclude compile steps from averages
    _count: int = 0
    _total_time: float = 0.0
    _last: float | None = None

    def __post_init__(self):
        self.num_chips = self.num_chips or jax.device_count()
        self.peak = peak_flops_per_chip()

    def start(self):
        self._last = time.perf_counter()

    def stop(self, n_steps: int = 1) -> dict:
        """Close a timing window covering `n_steps` device steps (the trainer
        only blocks on logging steps, so a window spans several steps)."""
        now = time.perf_counter()
        dt = now - (self._last if self._last is not None else now)
        prev = self._count
        self._count += n_steps
        # Steps beyond the warmup threshold count toward the average.
        counted = self._count - max(prev, self.warmup_steps)
        if counted > 0:
            self._total_time += dt * (counted / n_steps)
        return self.snapshot(step_time=dt / max(n_steps, 1))

    def snapshot(self, step_time: float | None = None) -> dict:
        counted = max(self._count - self.warmup_steps, 0)
        avg = self._total_time / counted if counted else (step_time or 0.0)
        tps = self.tokens_per_step / avg if avg else 0.0
        model_flops = 6.0 * self.num_params * tps  # fwd+bwd matmul FLOPs
        mfu = None
        if self.peak is not None:
            mfu = model_flops / (self.num_chips * self.peak) if avg else 0.0
        return {
            "step_time_s": step_time if step_time is not None else avg,
            "avg_step_time_s": avg,
            "tokens_per_sec": tps,
            "tokens_per_sec_per_chip": tps / self.num_chips,
            "mfu": mfu,
        }


class MetricsLogger:
    """JSONL metrics stream — consumed by the CLI (`tpukit logs -f`), the HPO
    metrics collector (tune/), and humans. One JSON object per line, always
    with "step"."""

    def __init__(self, path: str | None = None, stream=None):
        self._fh = open(path, "a", buffering=1) if path else None
        self._stream = stream if stream is not None else sys.stdout

    def log(self, step: int, payload: dict):
        rec = {"step": int(step)}
        for k, v in payload.items():
            try:
                rec[k] = float(v)
            except (TypeError, ValueError):
                tolist = getattr(v, "tolist", None)
                rec[k] = tolist() if tolist is not None else str(v)
        line = json.dumps(rec)
        if self._fh:
            self._fh.write(line + "\n")
        if self._stream:
            print(line, file=self._stream, flush=True)

    def close(self):
        if self._fh:
            self._fh.close()
