"""Text2text serving — the huggingfaceserver text2text_generation task.

Encoder-decoder checkpoints (T5 family) serve through `greedy_generate`
(models/t5.py): the WHOLE generate call — encoder, cross-KV precompute,
and the scanned decoder loop — is one AOT-compiled XLA executable per
input-length bucket. One host dispatch and one fetch per request: a
per-token decode loop would sync the host once per token, and every host
sync stalls the device's dispatch queue.

Trade-off vs the decoder-only GenerationEngine (serve/generation.py):
no continuous batching or streaming — text2text outputs are short
(translation/summarization), so whole-program latency is the right
shape; the engine's slot machinery would buy little and cost the
per-token host loop.
"""

from __future__ import annotations

import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.serve.model import Model


class Text2TextJAXModel(Model):
    """KServe-Model-shaped wrapper over T5-class greedy generation.

    `generation` spec: {"in_buckets": [...], "max_tokens": N,
    "tokenizer": ... ("bytes" | HF tokenizer), "pad_id": 0}.
    """

    def __init__(self, name: str, model, params, cfg, *,
                 generation: dict | None = None):
        super().__init__(name)
        gen = dict(generation or {})
        self._model, self._params, self.cfg = model, params, cfg
        self.in_buckets = sorted({int(b) for b in
                                  gen.get("in_buckets", (16, 64))})
        self.max_tokens = int(gen.get("max_tokens", 64))
        self.pad_id = int(gen.get("pad_id", 0))
        self.tokenizer = gen.get("tokenizer")
        self._compiled: dict[int, Any] = {}
        # Requests run on arbitrary tornado executor threads (unlike the
        # engine's single worker) — the compile cache and stats need the
        # lock or two first requests double-compile a bucket.
        self._lock = threading.Lock()
        self.stats = {"requests": 0, "generated_tokens": 0,
                      "generate_s": 0.0, "compiles": 0}

    def _fn(self):
        from kubeflow_tpu.models.t5 import greedy_generate

        def run(params, input_ids, enc_mask):
            return greedy_generate(self._model, params, input_ids,
                                   enc_mask, max_tokens=self.max_tokens)

        return run

    def _executable(self, bucket: int):
        exe = self._compiled.get(bucket)
        if exe is not None:
            return exe
        with self._lock:
            exe = self._compiled.get(bucket)
            if exe is None:
                args = (jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                        jax.ShapeDtypeStruct((1, bucket), jnp.bool_))
                exe = (jax.jit(self._fn())
                       .lower(self._params, *args).compile())
                self._compiled[bucket] = exe
                self.stats["compiles"] += 1
        return exe

    def load(self) -> bool:
        t0 = time.monotonic()
        self._params = jax.device_put(self._params)
        self._executable(self.in_buckets[0])
        self.load_time_s = time.monotonic() - t0
        self.ready = True
        return True

    def unload(self) -> None:
        self.ready = False
        self._compiled.clear()

    def _resolve_ids(self, payload: dict) -> list[int]:
        from kubeflow_tpu.serve.tokenizer_util import resolve_ids

        ids = resolve_ids(self.tokenizer, payload)
        if len(ids) > self.in_buckets[-1]:
            raise ValueError(
                f"input of {len(ids)} tokens exceeds the largest bucket "
                f"{self.in_buckets[-1]}")
        return ids

    def generate(self, payload: dict) -> dict:
        if not self.ready:
            raise RuntimeError(f"model {self.name} is not loaded")
        ids = self._resolve_ids(payload)
        max_tokens = int(payload.get("max_tokens", self.max_tokens))
        if max_tokens > self.max_tokens:
            raise ValueError(
                f"max_tokens {max_tokens} exceeds the compiled budget "
                f"{self.max_tokens}")
        bucket = next(b for b in self.in_buckets if len(ids) <= b)
        toks = np.full((1, bucket), self.pad_id, np.int32)
        toks[0, :len(ids)] = ids
        mask = np.zeros((1, bucket), bool)
        mask[0, :len(ids)] = True
        t0 = time.monotonic()
        out_toks, n_valid = self._executable(bucket)(
            self._params, toks, mask)
        n = min(int(n_valid[0]), max_tokens)
        out_ids = [int(t) for t in np.asarray(out_toks)[0, :n]]
        dt = time.monotonic() - t0
        with self._lock:
            self.stats["requests"] += 1
            self.stats["generated_tokens"] += n
            self.stats["generate_s"] += dt
        result = {
            "output_ids": out_ids,
            "num_input_tokens": len(ids),
            "num_output_tokens": n,
            "latency_s": round(dt, 4),
        }
        if self.tokenizer is not None:
            from kubeflow_tpu.serve.tokenizer_util import decode_ids

            result["text"] = decode_ids(self.tokenizer, out_ids)
        return result

    def metadata(self) -> dict:
        return {**super().metadata(),
                "task": "text2text_generation",
                "in_buckets": self.in_buckets,
                "max_tokens": self.max_tokens,
                "stats": dict(self.stats)}
