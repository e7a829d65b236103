"""Operations and bytes a Kimi-Linear training step *requires*, from the
configuration's file (the source's keys). Kept with the benchmark so that
the program cannot move it. Recomputation (remat, the chunked form's
second pass) is work the program chooses and counts nothing; neither do
zero columns a kernel pads with.

A token is multiplied by (2 FLOPs a weight forward, twice that backward,
so 6 a weight):

  KDA mixer   q, k, v, o: 4 h w; decay and output gate, each h r + r w;
              beta h H; the short convolutions 3 K w          (w = H d)
  MLA mixer   q: h n (dn + dr); kv_a: h (rank + dr); kv_b: rank n (dn + dv);
              o: n dv h
  dense FFN   3 h I                     (layers <= first_k_dense_replace)
  expert FFN  the router h E; the shared experts 3 h m each; of the token's
              top-k routed experts the share held here in expectation,
              k * held / E experts of 3 h m
  head        h V (the vocabulary slice held); the embedding is a gather

Beside the weights: MLA's scores, a token at position p attends p + 1 keys,
QK^T 2 n (dn + dr) and PV 2 n dv a key forward, twice that backward; and the
KDA recurrence, 7 d_k d_v a head a step forward (decay of S, k^T S, the
rank-one update, S^T q), twice that backward.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return dict(
        h=cfg["hidden_size"], H=lin["num_heads"], d=lin["head_dim"],
        K=lin["short_conv_kernel_size"],
        r=cfg["sizes_assumed"]["kda_lowrank"],
        n=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        rank=cfg["kv_lora_rank"], I=cfg["intermediate_size"],
        m=cfg["moe_intermediate_size"], E=cfg["published"]["num_experts"],
        held=cfg["num_experts"], k=cfg["num_experts_per_token"],
        shared=cfg["num_shared_experts"], V=cfg["vocab_size"])


def kda_mixer_params(cfg: dict) -> int:
    s = _sizes(cfg)
    w = s["H"] * s["d"]
    return (4 * s["h"] * w + 2 * (s["h"] * s["r"] + s["r"] * w)
            + s["h"] * s["H"] + 3 * s["K"] * w)


def mla_mixer_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return (s["h"] * s["n"] * (s["dn"] + s["dr"])
            + s["h"] * (s["rank"] + s["dr"])
            + s["rank"] * s["n"] * (s["dn"] + s["dv"])
            + s["n"] * s["dv"] * s["h"])


def expert_ffn_params(cfg: dict) -> float:
    """Weights of one expert layer's FFN a token is multiplied by here."""
    s = _sizes(cfg)
    one = 3 * s["h"] * s["m"]
    return (s["h"] * s["E"] + s["shared"] * one
            + s["k"] * s["held"] / s["E"] * one)


def dense_ffn_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return 3 * s["h"] * s["I"]


def matmul_params(cfg: dict) -> float:
    lin = cfg["linear_attn_config"]
    total = float(cfg["hidden_size"] * cfg["vocab_size"])
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        total += (kda_mixer_params(cfg) if layer in lin["kda_layers"]
                  else mla_mixer_params(cfg))
        total += (dense_ffn_params(cfg)
                  if layer <= cfg["first_k_dense_replace"]
                  else expert_ffn_params(cfg))
    return total


def mla_score_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, one MLA layer."""
    s = _sizes(cfg)
    return (3 * 2 * s["n"] * (s["dn"] + s["dr"] + s["dv"])
            * (seq_len + 1) / 2)


def kda_recurrence_flops_per_token(cfg: dict) -> float:
    """Forward and backward, one KDA layer."""
    s = _sizes(cfg)
    return 3 * 7 * s["d"] * s["d"] * s["H"]


def kda_recurrence_bytes_per_token(cfg: dict, itemsize: int = 4) -> float:
    """What one KDA layer's recurrence has to move a token, forward and
    backward, at the fp32 the configuration states for it: forward reads q,
    k, v, g (d a head each) and beta (1) and writes o (d); backward reads
    them and dO again and writes dq, dk, dv, dg, dbeta."""
    s = _sizes(cfg)
    inputs = 4 * s["d"] + 1
    return itemsize * s["H"] * ((inputs + s["d"])          # forward
                                + (inputs + s["d"])        # backward reads
                                + inputs)                  # backward writes


def layers_of(cfg: dict, kind: str) -> int:
    return len(cfg["linear_attn_config"][kind])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return (6.0 * matmul_params(cfg)
            + layers_of(cfg, "full_attn_layers")
            * mla_score_flops_per_token(cfg, seq_len)
            + layers_of(cfg, "kda_layers")
            * kda_recurrence_flops_per_token(cfg))
