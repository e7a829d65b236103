"""Serving model abstraction — KServe's `kserve.Model` contract, TPU-first.

The reference model server (⟨kserve: python/kserve — Model, ModelServer⟩,
SURVEY.md §2.2/§3.3) defines load/preprocess/predict/postprocess with the
GPU framework hidden behind `predict`. Here the TPU path is explicit:
`JAXModel` AOT-compiles the forward for a fixed set of batch buckets at
load time (`jit(...).lower(...).compile()`), so the serving hot path never
hits a trace/compile and every request lands on a static-shaped MXU-friendly
executable. Requests are padded up to the nearest bucket.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Model:
    """Lifecycle + request hooks. Subclasses override load()/predict().

    Mirrors the reference's kserve.Model surface: `ready` gates the
    readiness probes, preprocess/postprocess wrap the hot predict call.
    """

    def __init__(self, name: str):
        self.name = name
        self.ready = False
        self.load_time_s: float | None = None

    def load(self) -> bool:
        self.ready = True
        return self.ready

    def unload(self) -> None:
        self.ready = False

    def preprocess(self, payload: Any) -> Any:
        return payload

    def predict(self, inputs: Any) -> Any:
        raise NotImplementedError

    def postprocess(self, outputs: Any) -> Any:
        return outputs

    def explain(self, instances: Any) -> Any:
        """v1 `:explain` hook (serve/explain.py attaches implementations)."""
        raise NotImplementedError(
            f"model {self.name!r} has no explainer configured")

    def __call__(self, payload: Any) -> Any:
        return self.postprocess(self.predict(self.preprocess(payload)))

    # Metadata for the v2 protocol's GET /v2/models/{name}. `platform` is
    # the protocol's framework string; `device` is what this replica is
    # actually running on, as JAX reports it.
    def metadata(self) -> dict:
        from kubeflow_tpu.utils.devices import device_summary

        return {"name": self.name, "platform": "jax",
                "device": device_summary(),
                "inputs": [], "outputs": []}


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class JAXModel(Model):
    """A jitted forward over fixed params, AOT-compiled per batch bucket.

    `apply_fn(params, *inputs)` must be shape-polymorphic over the leading
    batch dim only; everything else is static. `input_spec` gives the
    per-example shape/dtype of each positional input.
    """

    def __init__(self, name: str, apply_fn, params: Any,
                 input_spec: Sequence[tuple[tuple[int, ...], str]],
                 batch_buckets: Sequence[int] = (1, 2, 4, 8, 16, 32),
                 warm_buckets: Sequence[int] = (1, 8)):
        super().__init__(name)
        self._apply = apply_fn
        self._params = params
        self.input_spec = [(tuple(s), str(d)) for s, d in input_spec]
        self.batch_buckets = sorted(set(int(b) for b in batch_buckets))
        self.warm_buckets = [b for b in warm_buckets
                             if b in self.batch_buckets]
        self._compiled: dict[int, Any] = {}
        self._lock = threading.Lock()
        self.explainer = None  # serve/explain.py; set via attach_explainer
        self.stats = {"requests": 0, "examples": 0, "padded_examples": 0,
                      "compiles": 0, "predict_s": 0.0}

    def attach_explainer(self, explainer) -> None:
        self.explainer = explainer

    def apply_and_params(self):
        """(apply_fn, params) for explainers that differentiate through
        the model (integrated gradients) rather than calling predict."""
        return self._apply, self._params

    def explain(self, instances) -> Any:
        if self.explainer is None:
            raise NotImplementedError(
                f"model {self.name!r} has no explainer configured")
        if not self.ready:
            raise RuntimeError(f"model {self.name} is not loaded")
        return self.explainer.explain(self, instances)

    # -- compilation --------------------------------------------------------

    def _abstract_inputs(self, batch: int):
        return [jax.ShapeDtypeStruct((batch, *shape), jnp.dtype(dtype))
                for shape, dtype in self.input_spec]

    def _executable(self, batch: int):
        """AOT executable for one bucket; compiled once, cached forever."""
        exe = self._compiled.get(batch)
        if exe is not None:
            return exe
        with self._lock:
            exe = self._compiled.get(batch)
            if exe is None:
                args = self._abstract_inputs(batch)
                exe = (jax.jit(self._apply)
                       .lower(self._params, *args).compile())
                self._compiled[batch] = exe
                self.stats["compiles"] += 1
        return exe

    def load(self) -> bool:
        t0 = time.monotonic()
        self._params = jax.device_put(self._params)
        for b in self.warm_buckets:
            self._executable(b)
        self.load_time_s = time.monotonic() - t0
        self.ready = True
        return True

    def unload(self) -> None:
        # Keep params: unload/load through the repository API must be able
        # to round-trip for models registered without a model_dir. Only the
        # compiled executables (the large device allocations) are dropped.
        self.ready = False
        self._compiled.clear()

    # -- hot path -----------------------------------------------------------

    def predict(self, inputs: Sequence[np.ndarray]) -> list[np.ndarray]:
        """Pads the batch up to the nearest bucket, runs the AOT executable,
        and strips the padding. Returns a list of output arrays."""
        if not self.ready:
            raise RuntimeError(f"model {self.name} is not loaded")
        arrays = [np.asarray(x, dtype=np.dtype(d))
                  for x, (_, d) in zip(inputs, self.input_spec)]
        n = arrays[0].shape[0]
        if any(a.shape[0] != n for a in arrays):
            raise ValueError("inputs disagree on batch size")
        bucket = _next_bucket(n, self.batch_buckets)
        t0 = time.monotonic()
        if n > bucket:  # above the largest bucket: split into max-size chunks
            outs = [self.predict([a[i:i + bucket] for a in arrays])
                    for i in range(0, n, bucket)]
            return [np.concatenate(parts) for parts in zip(*outs)]
        if n < bucket:
            arrays = [np.concatenate(
                [a, np.repeat(a[-1:], bucket - n, axis=0)]) for a in arrays]
        exe = self._executable(bucket)
        out = exe(self._params, *arrays)
        leaves = [np.asarray(x)[:n] for x in jax.tree.leaves(out)]
        self.stats["requests"] += 1
        self.stats["examples"] += n
        self.stats["padded_examples"] += bucket - n
        self.stats["predict_s"] += time.monotonic() - t0
        return leaves

    def metadata(self) -> dict:
        return {
            **super().metadata(),
            "inputs": [{"name": f"input_{i}", "shape": [-1, *shape],
                        "datatype": _v2_dtype(dtype)}
                       for i, (shape, dtype) in enumerate(self.input_spec)],
            "outputs": [{"name": "output_0", "shape": [-1],
                         "datatype": "FP32"}],
            "batch_buckets": self.batch_buckets,
        }


_V2_DTYPES = {
    "float32": "FP32", "float16": "FP16", "bfloat16": "BF16",
    "float64": "FP64", "int32": "INT32", "int64": "INT64",
    "int8": "INT8", "int16": "INT16", "uint8": "UINT8",
    "uint16": "UINT16", "uint32": "UINT32", "uint64": "UINT64",
    "bool": "BOOL",
}
_NP_DTYPES = {v: k for k, v in _V2_DTYPES.items()}


def _v2_dtype(np_dtype: str) -> str:
    return _V2_DTYPES.get(str(np_dtype), "FP32")


def v2_to_numpy_dtype(v2: str) -> str:
    try:
        return _NP_DTYPES[v2.upper()]
    except KeyError:
        raise ValueError(f"unsupported v2 datatype {v2!r}") from None
