"""What EvaByte's serving path *requires*, from its configuration file (keys
as in the source's config.json): weights a byte is multiplied by, FLOPs a
prompt byte and an output byte need at a given count of exact and summary
rows, bytes a decode step has to read, and the same for the two attention
cores alone. Kept with the benchmark so that the program cannot move it.

A layer's matmul weights: q, k, v, o at hidden x heads x head_dim and the
gated MLP's three, 4 * 4096^2 + 3 * 4096 * 11008 = 202.4M. The embedding is a
gather and counts nothing; the `num_pred_heads` heads are one [hidden, 8 x
320] matmul, needed at a prompt's last position and at every output byte. A
state row (an exact row or a summary row) is one key and one value of heads x
head_dim in bf16: 32 * 128 * 2 * 2 B = 16 KiB a layer. A query scores a row
and adds its value: 4 * heads * head_dim FLOPs a row a layer. Pooling a chunk
costs each of its keys two dot products with the learned vectors, its own
squared norm and two weighted sums: 10 * heads * head_dim FLOPs a key a
layer, once, when its window closes.

Required, not done: the program's bf16 copy of the fp32 weights, the rows a
masked table entry reads and the flash kernel's masked half count nothing.
"""

from __future__ import annotations

STATE_BYTES = 2          # bf16 keys and values
WEIGHT_BYTES = 2         # bf16 matmul operands


def _hd(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def layer_matmul_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    return 4 * h * _hd(cfg) + 3 * h * cfg["intermediate_size"]


def head_params(cfg: dict) -> int:
    return cfg["num_pred_heads"] * cfg["vocab_size"] * cfg["hidden_size"]


def matmul_params(cfg: dict) -> int:
    """Weights an output byte is multiplied by: the layers and the heads."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + head_params(cfg))


def row_bytes(cfg: dict) -> int:
    """One state row of one layer: a key and a value."""
    return 2 * _hd(cfg) * STATE_BYTES


def pool_bytes(cfg: dict, slots: int, max_len: int) -> int:
    """The paged pool if every slot stood at its worst at once: a full open
    window and the summaries of every window before the last."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    rows = window + ((max_len - 1) // window) * (window // chunk)
    return slots * rows * row_bytes(cfg) * cfg["num_hidden_layers"]


def rows_read(cfg: dict, t: int) -> tuple[int, int]:
    """(exact rows, summary rows) the query at position t reads."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    return t % window + 1, (t // window) * (window // chunk)


def core_flops(cfg: dict, rows: float) -> float:
    """Scores and values of `rows` query-row pairs, all layers."""
    return 4.0 * _hd(cfg) * cfg["num_hidden_layers"] * rows


def pooling_flops(cfg: dict, keys: float) -> float:
    return 10.0 * _hd(cfg) * cfg["num_hidden_layers"] * keys


def prompt_rows(cfg: dict, n: int) -> int:
    """Query-row pairs of a prompt of n bytes, and the keys it pools."""
    window, per = cfg["window_size"], cfg["window_size"] // cfg["chunk_size"]
    full, rest = divmod(n, window)
    pairs = full * window * (window + 1) // 2 + rest * (rest + 1) // 2
    pairs += per * window * full * (full - 1) // 2 + per * full * rest
    return pairs


def prompt_flops(cfg: dict, n: int) -> float:
    """A prompt of n bytes: every layer's matmuls a byte, the heads once
    (the last position's logits are all that is sampled from), the attention
    of every position, the pooling of every window that closes."""
    layers = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    closed = max(n - 1, 0) // cfg["window_size"] * cfg["window_size"]
    return (2.0 * layers * n + 2.0 * head_params(cfg)
            + core_flops(cfg, prompt_rows(cfg, n))
            + pooling_flops(cfg, closed))


def decode_flops(cfg: dict, tokens: float, rows: float) -> float:
    """`tokens` output bytes whose steps read `rows` state rows in all."""
    return 2.0 * matmul_params(cfg) * tokens + core_flops(cfg, rows)


def decode_bytes_per_step(cfg: dict, rows: float) -> float:
    """One decode step of a batch whose rows read `rows` state rows in all:
    every matmul weight once (the batch shares the read) and every state
    row of every layer."""
    return (matmul_params(cfg) * WEIGHT_BYTES
            + core_bytes_decode(cfg, rows))


def core_bytes_decode(cfg: dict, rows: float) -> float:
    return row_bytes(cfg) * cfg["num_hidden_layers"] * rows


def core_bytes_prefill(cfg: dict, n: float) -> float:
    """A prompt's q, k, v read and o written once a layer (bf16); the
    summaries are read once a piece and are few beside them."""
    return 4.0 * _hd(cfg) * STATE_BYTES * cfg["num_hidden_layers"] * n
