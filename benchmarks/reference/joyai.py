"""JoyAI-LLM-Flash's forward pass, plainly: `jax.numpy` at `highest`, no
kernel, no cache, no batching, the *unabsorbed* attention, every expert
computed for every token and weighed by its gate (zero where it was not
chosen). One sequence from position 0 in, the logits at every position out.
Independent of `kubeflow_tpu/models/joyai.py`, `models/moe.py` and
`ops/mla.py`: it shares the parameter tree's names with the model and nothing
else.

The layer (ISSUE 36; every key from jdopensource/JoyAI-LLM-Flash
`config.json`, the equations DeepSeek-V3's, arXiv:2412.19437 sections 2.1 and
2.2). n heads, nope / rope / v head sizes, latent rank r:

  h = RMSNorm(x); c_q = RMSNorm(h W_qa); q = c_q W_qb -> n x (nope ‖ rope)
  [c ‖ k_r] = h W_kva; c <- RMSNorm(c); [k_nope ‖ v] = c W_kvb -> n x (nope ‖ v)
  rotary over interleaved pairs (2i, 2i + 1) at absolute positions (theta,
  no scaling) on q's rope part and on k_r, one vector a token for all heads
  score_h(t, s) = (q_nope,h(t).k_nope,h(s) + q_rope,h(t).k_r(s)) (nope + rope)^-1/2
  causal softmax; x += concat_h(sum p v_h) W_o
  layer < first_k_dense_replace: x += W_down(silu(W_gate m) * W_up m), m = RMSNorm(x)
  else: s = sigmoid(m W_r) in fp32; the k experts with the largest s + b;
        g_i = scale * s_i / sum of the chosen s;
        x += sum_i g_i SwiGLU_i(m) + SwiGLU_shared(m)
  After the last layer a final RMSNorm and logits = n W_head.

The tree is taken a top-level group at a time through `get(name)` ("embed",
"layer_<i>", "final_norm", "lm_head"), so that a caller can make one layer's
fp32 weights at a time: `embed`, `layer` and `head` are the three stages, and
`forward` strings them together (`dict.__getitem__` of a whole tree is a `get`).

`precision="stated"` is the same forward in the precision the configuration
states for the program (its `assumed.precision`), still plain `jax.numpy`:
every matmul takes bf16 operands and gives a bf16 result accumulated in fp32,
the residual stream and what a cache would hold are bf16; norms, rotary, the
scores and the softmax over them, the router's matmul, sigmoid, top-k and
gates, and the logits are fp32 (the gates are rounded to bf16 where they
multiply the experts' outputs, as the program's combine does). A program of
that precision differs from it by the order of its sums and by where it
rounds between two matmuls (the server's decode step folds W_kvb's key half
into the query), not by the rounding of every operand.

`control` makes the readings the cell's limits are set between: a reference
that is wrong in one named way, which the comparison must refuse. `bfloat16`
is the precision below the stated one: norms, rotary, scores, softmax, the
router and the logits in bf16 too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = ("rope_half", "scale_nope", "raw_gates", "no_shared", "no_bias",
            "router_bf16", "bfloat16")
PRECISIONS = ("fp32", "stated", "bfloat16")


def _dtypes(precision: str) -> tuple:
    """(matmul operands and what lies between matmuls, the fine parts)."""
    return {"fp32": (jnp.float32, jnp.float32),
            "stated": (jnp.bfloat16, jnp.float32),
            "bfloat16": (jnp.bfloat16, jnp.bfloat16)}[precision]


def _mm(spec: str, a, w, op):
    """A matmul as the precision has it: operands in `op`, the result
    accumulated in fp32 and rounded to `op`."""
    return jnp.einsum(spec, a.astype(op), w.astype(op),
                      preferred_element_type=jnp.float32).astype(op)


def _norm(x, g, eps: float, fine, out):
    xf = x.astype(fine)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + jnp.asarray(eps, fine))
    return (y * g.astype(fine)).astype(out)


def _rotary(x, theta: float, fine, half: bool):
    """x [T, ..., d] at positions 0..T-1: interleaved pairs (2i, 2i + 1),
    or (the control) the half-split pairs (i, i + d/2)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang).astype(fine), jnp.sin(ang).astype(fine)
    xf = x.astype(fine)
    if half:
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    else:
        a, b = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                        axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _attention(q, k, v, scale: float, block: int, fine):
    """q, k [T, n, dk], v [T, n, dv]; causal; a block of queries at a time
    so that 8k positions fit one chip."""
    t = q.shape[0]
    outs = []
    for t0 in range(0, t, block):
        t1 = min(t0 + block, t)
        st = scale * jnp.einsum("qhd,jhd->hqj", q[t0:t1], k[:t1],
                                preferred_element_type=jnp.float32
                                ).astype(fine)
        seen = jnp.arange(t1)[None] <= jnp.arange(t0, t1)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], st, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqj,jhd->qhd", p.astype(v.dtype), v[:t1],
                               preferred_element_type=jnp.float32
                               ).astype(v.dtype))
    return jnp.concatenate(outs, axis=0)


def _swiglu(x, p, op):
    return _mm("tm,mh->th",
               jax.nn.silu(_mm("th,hm->tm", x, p["gate_proj"]["kernel"], op))
               * _mm("th,hm->tm", x, p["up_proj"]["kernel"], op),
               p["down_proj"]["kernel"], op)


def moe_ffn(x, p, cfg: dict, *, op=jnp.float32, fine=jnp.float32,
            control: str | None = None):
    """The expert layer of x [T, H]: (y [T, H], expert ids [T, k])."""
    k, scale = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    route = jnp.bfloat16 if control == "router_bf16" else fine
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(route), p["router"].astype(route),
            preferred_element_type=jnp.float32).astype(fine))
    bias = 0.0 if control == "no_bias" else p["e_score_correction_bias"]
    _, idx = jax.lax.top_k(s + jnp.asarray(bias, fine), k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    g = scale * chosen
    if control != "raw_gates":
        g = g / jnp.sum(chosen, axis=-1, keepdims=True)
    first = cfg.get("experts_held", (0, s.shape[-1]))[0]
    held = p["w_gate"].shape[0]
    # [held, T]: expert e's gate for token t, zero where e was not chosen.
    gates = jnp.sum(
        jax.nn.one_hot(idx - first, held, dtype=jnp.float32)
        * g.astype(jnp.float32)[..., None], axis=1).T.astype(op)
    xo = x.astype(op)

    def one(y, e):
        w_gate, w_up, w_down, gate = e
        out = _mm("tm,mh->th",
                  jax.nn.silu(_mm("th,hm->tm", xo, w_gate, op))
                  * _mm("th,hm->tm", xo, w_up, op), w_down, op)
        return y + gate.astype(jnp.float32)[:, None] * out.astype(
            jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (p["w_gate"], p["w_up"], p["w_down"], gates))
    y = y.astype(op)
    if control != "no_shared" and "shared_expert" in p:
        y = y + _swiglu(xo, p["shared_expert"], op)
    return y, idx


def _layer(p, x, *, cfg: tuple, precision: str, block: int,
           control: str | None):
    cfg = dict(cfg)
    op, fine = _dtypes(precision)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    dn, dr, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["kv_lora_rank"])
    half = control == "rope_half"
    a = p["mla"]
    h = _norm(x, p["input_norm"]["scale"], eps, fine, op)
    c_q = _norm(_mm("th,hr->tr", h, a["q_a_proj"]["kernel"], op),
                a["q_a_norm"]["scale"], eps, fine, op)
    q = _mm("tr,rnd->tnd", c_q, a["q_b_proj"]["kernel"], op)
    kva = _mm("th,hr->tr", h, a["kv_a_proj"]["kernel"], op)
    c = _norm(kva[:, :rank], a["kv_a_norm"]["scale"], eps, fine, op)
    k_r = _rotary(kva[:, rank:], theta, fine, half)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta, fine,
                                              half)], axis=-1)
    kvb = _mm("tr,rnd->tnd", c, a["kv_b_proj"], op)
    k = jnp.concatenate(
        [kvb[..., :dn],
         jnp.broadcast_to(k_r[:, None, :], (*kvb.shape[:2], dr))], axis=-1)
    scale = (dn if control == "scale_nope" else dn + dr) ** -0.5
    o = _attention(q, k, kvb[..., dn:], scale, block, fine)
    x = x + _mm("tnd,ndh->th", o, a["o_proj"]["kernel"], op)
    m = _norm(x, p["post_attn_norm"]["scale"], eps, fine, op)
    if "moe" in p:
        y, _ = moe_ffn(m, p["moe"], cfg, op=op, fine=fine, control=control)
    else:
        y = _swiglu(m, p["mlp"], op)
    return x + y


@functools.lru_cache(maxsize=None)
def _jitted_layer(**static):
    """One compiled layer a set of sizes and a kind of layer."""
    return jax.jit(functools.partial(_layer, **static))


def _resolve(precision: str, control: str | None) -> str:
    if control not in (None,) + CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: one of {PRECISIONS}")
    return "bfloat16" if control == "bfloat16" else precision


def padded(n: int, block: int = 512) -> int:
    """The length a sequence of n is computed at: the next power of two of
    blocks, so that a check compiles a handful of shapes. The pad follows
    every real position, so no real query reads it."""
    size = block
    while size < n:
        size *= 2
    return size


def embed(table, tokens, *, precision: str = "fp32",
          control: str | None = None):
    """tokens [T] ints -> the residual stream [padded(T), H]."""
    op, _ = _dtypes(_resolve(precision, control))
    ids = jnp.zeros((padded(len(tokens)),), jnp.int32).at[:len(tokens)].set(
        jnp.asarray(tokens, jnp.int32))
    return table.astype(op)[ids]


def layer(group, x, cfg: dict, *, precision: str = "fp32",
          control: str | None = None, block: int = 512):
    """One layer's group of the tree over the residual stream x."""
    precision = _resolve(precision, control)
    keys = ("rms_norm_eps", "rope_theta", "qk_nope_head_dim",
            "qk_rope_head_dim", "kv_lora_rank", "num_experts_per_tok",
            "routed_scaling_factor", "experts_held")
    static = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                   for k in keys if k in cfg)
    fn = _jitted_layer(cfg=static, precision=precision,
                       block=min(block, x.shape[0]), control=control)
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else "highest"):
        return fn(group, x)


def head(norm, w_head, x, cfg: dict, *, precision: str = "fp32",
         control: str | None = None):
    """The fp32 logits [T, V] of the residual stream's rows x [T, H] (a
    caller that needs a few positions hands those rows only)."""
    precision = _resolve(precision, control)
    op, fine = _dtypes(precision)
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else "highest"):
        y = _norm(x, norm["scale"], cfg["rms_norm_eps"], fine, op)
        logits = jnp.einsum("th,hv->tv", y, w_head.astype(op),
                            preferred_element_type=jnp.float32)
    return logits.astype(fine).astype(jnp.float32)


def forward(get, tokens, cfg: dict, *, precision: str = "fp32",
            control: str | None = None, block: int = 512) -> jax.Array:
    """get(name) -> the top-level group `name` of the model's parameter
    tree; tokens [T] ints; cfg: the configuration file's keys. Returns the
    fp32 logits [T, vocab_size]."""
    how = {"precision": precision, "control": control}
    x = embed(get("embed"), tokens, **how)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(get(f"layer_{i}"), x, cfg, block=block, **how)
    return head(get("final_norm"), get("lm_head"), x[:len(tokens)], cfg,
                **how)
