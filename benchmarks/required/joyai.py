"""What JoyAI-LLM-Flash's serving path *requires*, from its configuration
file (keys as in the source's config.json): weights a token is multiplied by,
FLOPs a prompt token and an output token need, bytes a decode step has to
read, and the same for the attention core and the routed experts alone. Kept
with the benchmark so that the program cannot move it.

Matmul weights (ISSUE 36's arithmetic, published widths): a latent-attention
layer 2048 x 1536 + 1536 x 32 x 192 + 2048 x 576 + 512 x 32 x 256 + 32 x 128 x
2048 = 26.35M; an expert 3 x 2048 x 768 = 4.72M (9.44 MB in bf16), of which a
token is multiplied by 8 routed and 1 shared, and the router's 2048 x 256 (fp32);
the dense layer's SwiGLU 3 x 2048 x 7168; the head 129,280 x 2048, needed at a
prompt's last position and at every output token. The embedding is a gather
and counts nothing. W_kvb counts once a token whichever form runs: the
unabsorbed form expands keys and values with it, the absorbed one applies its
key half to the query and its value half to the output.

A cached row of a layer is 512 + 64 values in bf16, 1,152 B, shared by all
heads. A decode query scores a row and adds its latent for every head: 2 x 32 x
(576 + 512) = 2 x 32 x 1,088 FLOPs a row a layer. A prompt's query-key pair,
unabsorbed: 2 x 32 x (192 + 128) FLOPs a layer.

Required, not done: the 64 zeros that pad a row to whole lane tiles in the
pool, an expert's weights read for a tile that holds none of its rows, the
rows before a later piece expanded again, the logits of a prompt's other
positions, and the flash kernel's masked half count nothing.
"""

from __future__ import annotations

STATE_BYTES = 2          # bf16 latent rows
WEIGHT_BYTES = 2         # bf16 matmul operands
ROUTER_BYTES = 4         # the router stays fp32


def _heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def _row(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_params(cfg: dict) -> int:
    h, n = cfg["hidden_size"], _heads(cfg)
    return (h * cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * n * (cfg["qk_nope_head_dim"]
                                        + cfg["qk_rope_head_dim"])
            + h * _row(cfg)
            + cfg["kv_lora_rank"] * n * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * h)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: dict) -> int:
    """Every matmul weight the chip stores: both tables, every expert."""
    dense = cfg["first_k_dense_replace"]
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + dense * 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            + expert_layers(cfg) * (
                (cfg["n_routed_experts"] + cfg["n_shared_experts"])
                * expert_params(cfg) + router_params(cfg))
            + 2 * head_params(cfg))


def layer_active_params(cfg: dict) -> int:
    """Weights of all layers that one token is multiplied by."""
    dense = cfg["first_k_dense_replace"]
    return (cfg["num_hidden_layers"] * mla_params(cfg)
            + dense * 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            + expert_layers(cfg) * (
                (cfg["num_experts_per_tok"] + cfg["n_shared_experts"])
                * expert_params(cfg) + router_params(cfg)))


def active_params(cfg: dict) -> int:
    """Weights an output token is multiplied by: the layers and the head."""
    return layer_active_params(cfg) + head_params(cfg)


def row_bytes(cfg: dict) -> int:
    """One cached row of one layer."""
    return _row(cfg) * STATE_BYTES


def pool_bytes(cfg: dict, slots: int, max_len: int) -> int:
    """The paged pool if every slot stood at `max_len` at once."""
    return slots * max_len * row_bytes(cfg) * cfg["num_hidden_layers"]


def expert_bytes(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return expert_params(cfg) * WEIGHT_BYTES


def core_flops(cfg: dict, rows: float) -> float:
    """The absorbed core over `rows` query-row pairs (`rows` counted for one
    layer, as the engine's `latent_rows` is), all layers."""
    return (2.0 * _heads(cfg) * (_row(cfg) + cfg["kv_lora_rank"])
            * cfg["num_hidden_layers"] * rows)


def core_bytes_decode(cfg: dict, rows: float) -> float:
    return row_bytes(cfg) * cfg["num_hidden_layers"] * rows


def prompt_rows(cfg: dict, n: int) -> int:
    """Query-key pairs of a causal prompt of n tokens, a layer."""
    return n * (n + 1) // 2


def prompt_flops(cfg: dict, n: int) -> float:
    """A prompt of n tokens: every layer's active matmuls a token, the head
    once, the unabsorbed attention of every position."""
    pair = 2.0 * _heads(cfg) * (cfg["qk_nope_head_dim"]
                                + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return (2.0 * layer_active_params(cfg) * n + 2.0 * head_params(cfg)
            + pair * cfg["num_hidden_layers"] * prompt_rows(cfg, n))


def decode_flops(cfg: dict, tokens: float, rows: float) -> float:
    """`tokens` output tokens whose steps read `rows` cached rows in all."""
    return 2.0 * active_params(cfg) * tokens + core_flops(cfg, rows)


def fixed_bytes_per_step(cfg: dict) -> float:
    """What every decode step reads whatever it routes to: attention, the
    dense layer, the shared experts and the head in bf16, the routers in
    fp32 (the batch shares each read)."""
    dense = cfg["first_k_dense_replace"]
    bf16 = (cfg["num_hidden_layers"] * mla_params(cfg)
            + dense * 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            + expert_layers(cfg) * cfg["n_shared_experts"]
            * expert_params(cfg) + head_params(cfg))
    return (bf16 * WEIGHT_BYTES
            + expert_layers(cfg) * router_params(cfg) * ROUTER_BYTES)


def decode_bytes_per_step(cfg: dict, rows: float, touched: float) -> float:
    """One decode step of a batch whose rows read `rows` cached rows in all
    and are routed to `touched` distinct experts, summed over the layers."""
    return (fixed_bytes_per_step(cfg) + touched * expert_bytes(cfg)
            + core_bytes_decode(cfg, rows))
