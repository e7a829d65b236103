"""The yardstick's arithmetic: the chip's published peaks, and the
operations and bytes a configuration requires, computed from its file.

Kept with the benchmark (not read from `kubeflow_tpu.train.metrics`) so
that the program cannot move it. A configuration file carries its sizes
under the keys of the source's `config.json`.
"""

from __future__ import annotations

#: One chip's published peaks, keyed by `device_kind` exactly as JAX spells
#: it. Source: Google Cloud TPU documentation, "TPU v5e" (system
#: architecture): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. jaxlib 0.9.0
#: spells the v5e's kind "TPU v5 lite". A kind that is not here is an
#: error: an assumed peak turns every share after it into fiction.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no {what!r} peak recorded for device_kind {device_kind!r}; "
            f"add it to shapes.PEAKS with its source") from None


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer that a token is multiplied by: q, k, v
    and o projections and the gated MLP's three matrices. Biases and norms
    are not matmuls."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = h * nq * d + 2 * h * nkv * d + nq * d * h
    return attn + 3 * h * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """All weights a token is multiplied by: the layers and the output
    head (tied or not, it is a [hidden, vocab] matmul). The input embedding
    is a gather and counts nothing."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes *require* for one token of a
    causal-LM step at sequence length `seq_len`:

      matmuls     2 FLOPs a weight forward, twice that backward:
                  6 * matmul_params
      attention   a token at position p attends to p + 1 keys; over a
                  sequence that averages (seq_len + 1) / 2. QK^T and PV are
                  each 2 * heads * head_dim FLOPs a key forward, twice that
                  backward: 3 * layers * 4 * heads * head_dim * (seq_len+1)/2

    Recomputation (remat, the chunked loss's second pass over the head) is
    work the program chooses to do, not work the step requires, and does
    not count."""
    attn = (3 * cfg["num_hidden_layers"] * 4 * cfg["num_attention_heads"]
            * cfg["head_dim"] * (seq_len + 1) / 2)
    return 6.0 * matmul_params(cfg) + attn


def decode_bytes_per_step(cfg: dict, weight_bytes: int, kv_bytes: int,
                          context_tokens: int) -> float:
    """Bytes one decode step has to read: every matmul weight once (the
    whole batch shares the read) and the K and V of every token in the
    batch's contexts. `context_tokens` is the sum of the context lengths
    over the batch."""
    kv = (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
          * cfg["head_dim"] * kv_bytes)
    return matmul_params(cfg) * weight_bytes + kv * context_tokens
