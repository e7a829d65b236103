"""EvaByte's forward pass, plainly: fp32 `jax.numpy` at `highest`, no kernel,
no cache, no batching. One sequence from position 0 in, the logits of all
`num_pred_heads` heads at every position out. Independent of
`kubeflow_tpu/models/evabyte.py` and `kubeflow_tpu/ops/eva.py`: it shares the
parameter tree's names with the model and nothing else.

The layer (ISSUE 33; sizes from EvaByte/EvaByte `config.json`, the estimator
from Zheng et al., arXiv:2302.04542, the two learned pooling vectors as the
release has them). H heads of d, s = d^-1/2, window W, chunk C, w(t) =
floor(t / W):

  u = RMSNorm(x) with weight (1 + g), eps; q, k, v = W_q u, W_k u, W_v u;
  rotary over the whole head (theta) on q and k at absolute positions.
  Chunk c with members P_c (C consecutive positions):
      k~_c = sum_j softmax_{j in P_c}(s mu_h.k_j) k_j
      v~_c = sum_j softmax_{j in P_c}(s phi_h.k_j - (s/2)|k_j|^2) v_j
  Query t: exact set E_t = {j : w(j) = w(t), j <= t} (block-local, it does
  not slide), remote set R_t = {c : c < w(t) W / C}; one softmax over both:
      o_t = (sum_E e^{s q.k_j} v_j + sum_R e^{s q.k~_c} v~_c)
            / (sum_E e^{s q.k_j} + sum_R e^{s q.k~_c})
  h = x + W_o o; x' = h + W_down(silu(W_gate n) * W_up n), n = RMSNorm(h).
  After the last layer a final RMSNorm and logits = W_head n in R^{P x V}:
  head p scores byte t + 1 + p.

Departures from the source, each because `config.json` does not fix it (the
configuration file lists them under `assumed`): where s enters the two
pooling weights (from the paper's scaling of keys by d^-1/4); that a
window's chunks become visible when the window closes; rotary in the
half-split ("rotate_half") layout.

The sequence is padded to whole windows (the pad follows every real
position, so no real query reads it) and attention is computed a block of
queries at a time, so that 14k positions fit one chip.

`precision="stated"` is the same forward in the precision the configuration
states for the program (its `assumed.precision`), still plain `jax.numpy`:
every matmul takes bf16 operands and gives a bf16 result accumulated in
fp32; keys, values and summaries are bf16; norms, rotary, the scores and the
softmax over them, the pooling weights, the residual stream and the logits
are fp32. A program of that precision differs from it by the order of its
sums and by where it rounds between two matmuls, not by the rounding of
every operand, so the gap to it is the number that a precision below the
stated one moves.

`control` makes the readings the cell's limits are set between: a reference
that is wrong in one named way, which the comparison must refuse.
`bfloat16` is the precision below the stated one: the residual stream, the
scores and the logits rounded to bf16 too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = ("no_summary", "sliding", "no_offset", "bfloat16")
PRECISIONS = ("fp32", "stated", "bfloat16")


def _norm(x, g, eps, offset: bool):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * ((1.0 + g) if offset else g)).astype(x.dtype)


def _rotary(x, theta: float):
    """x [T, H, d] at positions 0..T-1, half-split layout."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _summaries(k, v, mu, phi, chunk: int):
    """k, v [T, H, d] -> one summary key and value a chunk [T / C, H, d]."""
    t, h, d = k.shape
    s = d ** -0.5
    kc = k.reshape(t // chunk, chunk, h, d).astype(jnp.float32)
    vc = v.reshape(t // chunk, chunk, h, d).astype(jnp.float32)
    wk = s * jnp.sum(kc * mu, axis=-1)
    wv = s * jnp.sum(kc * phi, axis=-1) - 0.5 * s * jnp.sum(kc * kc, axis=-1)
    pk = jax.nn.softmax(wk, axis=1)[..., None]
    pv = jax.nn.softmax(wv, axis=1)[..., None]
    return (jnp.sum(pk * kc, axis=1).astype(k.dtype),
            jnp.sum(pv * vc, axis=1).astype(v.dtype))


def _attention(q, k, v, mu, phi, *, window: int, chunk: int, block: int,
               control: str | None):
    """q, k, v [T, H, d] rotated, T a multiple of the window. Scores are
    fp32 whatever the operands are, but under the control `bfloat16`."""
    scores = None if control == "bfloat16" else jnp.float32
    t, h, d = q.shape
    s = d ** -0.5
    sk, sv = _summaries(k, v, mu, phi, chunk)
    per = window // chunk
    outs = []
    for t0 in range(0, t, block):
        t1 = min(t0 + block, t)
        w = t0 // window
        qs = jnp.arange(t0, t1)
        if control == "sliding":
            lo = max(t0 - window + 1, 0)
            js = jnp.arange(lo, t1)
            seen = ((js[None] <= qs[:, None])
                    & (js[None] > qs[:, None] - window))
        else:
            lo = w * window
            js = jnp.arange(lo, t1)
            seen = js[None] <= qs[:, None]
        le = s * jnp.einsum("qhd,jhd->hqj", q[t0:t1], k[lo:t1],
                            preferred_element_type=scores)
        le = jnp.where(seen[None], le.astype(jnp.float32), -jnp.inf)
        n_sum = 0 if control == "no_summary" else w * per
        lr = s * jnp.einsum("qhd,chd->hqc", q[t0:t1], sk[:n_sum],
                            preferred_element_type=scores)
        p = jax.nn.softmax(
            jnp.concatenate([le, lr.astype(jnp.float32)], axis=-1), axis=-1)
        p = p.astype(q.dtype)
        vals = jnp.concatenate([v[lo:t1], sv[:n_sum]], axis=0)
        outs.append(jnp.einsum("hqr,rhd->qhd", p, vals))
    return jnp.concatenate(outs, axis=0)


def _layer(p, x, *, eps: float, theta: float, window: int, chunk: int,
           block: int, control: str | None):
    offset = control != "no_offset"
    a = p["attn"]
    operand = a["q_proj"]["kernel"].dtype     # of every matmul
    u = _norm(x, p["input_norm"]["scale"], eps, offset).astype(operand)
    q = _rotary(jnp.einsum("th,hnd->tnd", u, a["q_proj"]["kernel"]),
                theta)
    k = _rotary(jnp.einsum("th,hnd->tnd", u, a["k_proj"]["kernel"]), theta)
    v = jnp.einsum("th,hnd->tnd", u, a["v_proj"]["kernel"])
    o = _attention(q, k, v, a["adaptive_mu_k"], a["adaptive_phi"],
                   window=window, chunk=chunk, block=block, control=control)
    h = x + jnp.einsum("tnd,ndh->th", o, a["o_proj"]["kernel"])
    n = _norm(h, p["post_attn_norm"]["scale"], eps, offset).astype(operand)
    m = p["mlp"]
    rows = []
    for t0 in range(0, n.shape[0], block):
        nb = n[t0:t0 + block]
        rows.append((jax.nn.silu(nb @ m["gate_proj"]["kernel"])
                     * (nb @ m["up_proj"]["kernel"]))
                    @ m["down_proj"]["kernel"])
    return h + jnp.concatenate(rows, axis=0)


@functools.lru_cache(maxsize=None)
def _jitted_layer(**static):
    """One compiled layer a set of sizes: every layer of every sequence of a
    check runs the same program."""
    return jax.jit(functools.partial(_layer, **static))


def _cast(params: dict, precision: str) -> dict:
    """The parameter tree as a precision holds it: whole in fp32 or bf16, or
    (`stated`) the matmuls' weights in bf16 and the rest in fp32."""
    if precision != "stated":
        dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
        return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)

    def leaf(path, a):
        names = {getattr(k, "key", None) for k in path}
        matmul = names & {"kernel", "lm_head"}
        return jnp.asarray(a, jnp.bfloat16 if matmul else jnp.float32)

    return jax.tree_util.tree_map_with_path(leaf, params)


def forward(params: dict, tokens, cfg: dict, *, block: int = 512,
            control: str | None = None,
            precision: str = "fp32") -> jax.Array:
    """params: the model's parameter tree (layers stacked on axis 0);
    tokens [T] ints; cfg: the configuration file's keys. Returns the fp32
    logits [T, num_pred_heads, vocab_size]."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: one of {PRECISIONS}")
    if control == "bfloat16":
        precision = "bfloat16"
    params = _cast(params, precision)
    window = cfg["window_size"]
    t = len(tokens)
    padded = -(-t // window) * window
    ids = jnp.zeros((padded,), jnp.int32).at[:t].set(jnp.asarray(tokens))
    layer = _jitted_layer(
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        window=window, chunk=cfg["chunk_size"], block=min(block, window),
        control=control)
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else "highest"):
        x = params["embed"][ids]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(jax.tree.map(lambda a: a[i], params["layers"]), x)
        n = _norm(x[:t], params["final_norm"]["scale"], cfg["rms_norm_eps"],
                  control != "no_offset").astype(params["lm_head"].dtype)
        logits = jnp.einsum(
            "th,hpv->tpv", n, params["lm_head"],
            preferred_element_type=(None if precision == "bfloat16"
                                    else jnp.float32))
    return logits.astype(jnp.float32)
