"""Plain reference of the Kimi-Linear decoder (moonshotai/Kimi-Linear-48B-A3B;
Kimi Linear report, arXiv 2510.26692, as recalled): forward pass, loss and
gradients in straightforward `jax.numpy`, fp32, matmuls at
`jax.default_matmul_precision("highest")`. No kernels, no chunked forms, no
sorting: the KDA layer is its recurrence one step at a time, latent attention
a masked softmax over blocks of queries, the expert layer a loop over the
experts held. It shares no code with `kubeflow_tpu`; it reads the program's
parameter tree as data (the names below are that tree's).

Layer equations, pre-norm residual: x += Mix(RMSNorm(x)); x += FFN(RMSNorm(x));
final RMSNorm; untied head. RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale.

KDA (H heads, d_k = d_v = d): q~, k~, v~ = W_q x, W_k x, W_v x; q, k, v =
SiLU(conv_K(.)) with a causal depthwise convolution over time; q <-
q / sqrt(sum q^2 + eps_l2) * d^-1/2 and k <- k / sqrt(sum k^2 + eps_l2), per
head. g_t = -exp(A_log_h) * softplus(W_fb W_fa x_t + dt_bias) per head and
channel; beta_t = sigmoid(W_b x_t) per head. S_0 = 0,
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t,
out = W_o [RMSNorm_head(o_t) * sigmoid(W_gb W_ga x_t)].

MLA without positions: q = W_q x -> heads x (d_n + d_r); [c; k_r] = W_kva x,
c <- RMSNorm(c); [k_n; v] = W_kvb c per head; k_h = [k_n,h; k_r] with the one
k_r shared by all heads, no rotary on either part; causal
softmax(q k^T / sqrt(d_n + d_r)) v; W_o.

MoE: s = sigmoid(W_r x) over all experts; the K experts with the largest
s + b (b: score-correction bias, no gradient); w_i = scale * s_i / sum of
the chosen s; y = sum over the chosen experts *that are held here* of
w_i E_i(x), plus E_shared(x); E a SwiGLU.

Departures from the report, all of which the program shares: (1) the sum
runs over the held experts only — this is one chip's share of an
expert-parallel job, and what the absent experts would add is left out;
(2) the vocabulary is the chip's slice; (3) b stays fixed (the config gives
no update rate); (4) A_log, dt_bias, the low-rank width and eps_l2 are the
gated-delta-rule family's conventions, the config does not give them; (5) the
L2 norm adds its epsilon under the root, the output norm's scale is one
vector of d shared by the heads.

The recurrence's 8,192 steps and the attention's 8,192 x 8,192 scores are
rematerialised in blocks (`jax.checkpoint` around a block of time steps, a
block of queries, a layer): that changes memory, not the mathematics.

`dtype=jnp.bfloat16` computes everything, accumulations and the state
included, in bf16: the "precision below" reading that the cell's limits are
set against. It is not the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: What the published config.json fixes, under the program's names.
DEFAULTS = dict(
    hidden_size=2304, first_k_dense_replace=1, kda_heads=32,
    kda_head_dim=128, kda_norm_eps=1e-6, num_heads=32, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    num_experts=256, experts_per_token=8, routed_scaling_factor=2.446,
    rms_eps=1e-5)


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32) if x.dtype == jnp.float32 else x
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * scale.astype(x.dtype)


def swiglu(x, p):
    w = lambda n: p[n]["kernel"].astype(x.dtype)
    return (jax.nn.silu(x @ w("gate_proj")) * (x @ w("up_proj"))) \
        @ w("down_proj")


def causal_conv(x, w):
    """y_t = sum_i w_i x_{t-K+1+i}; x [B,T,C], w [K,C]."""
    kernel, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * w[i].astype(x.dtype) for i in range(kernel))


def kda_recurrence(q, k, v, g, beta, block: int = 64):
    """o_t of the delta rule above, one step at a time. q, k, g [B,T,H,dk],
    v [B,T,H,dv], beta [B,T,H]. Time is walked in blocks whose inner steps
    are recomputed in the backward, so that only one state a block is kept."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % block
    nb = (t + pad) // block

    def by_time(x):  # [B,T,...] -> [nb, block, B, ...]; padded steps are
        # g = 0, beta = 0, k = 0: they leave the state as it is.
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((nb, block) + x.shape[1:])

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    @jax.checkpoint
    def steps(S, xs):
        return jax.lax.scan(step, S, xs)

    S0 = jnp.zeros((b, h, dk, dv), q.dtype)
    _, o = jax.lax.scan(steps, S0, tuple(map(by_time, (q, k, v, g, beta))))
    o = o.reshape((nb * block,) + o.shape[2:])[:t]
    return jnp.moveaxis(o, 0, 1)


def kda_mixer(x, p, cfg):
    b, t, _ = x.shape
    h, d = cfg["kda_heads"], cfg["kda_head_dim"]
    w = lambda n: p[n]["kernel"].astype(x.dtype)

    def head(n):
        y = jax.nn.silu(causal_conv(x @ w(n + "_proj"), p[n + "_conv"]))
        return y.reshape(b, t, h, d)

    q, k, v = head("q"), head("k"), head("v")
    l2 = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + cfg["kda_norm_eps"])
    q, k = l2(q) * d ** -0.5, l2(k)
    f = (x @ w("f_a")) @ w("f_b") + p["dt_bias"].astype(x.dtype)
    g = (-jnp.exp(p["A_log"].astype(x.dtype))[:, None]
         * jax.nn.softplus(f).reshape(b, t, h, d))
    beta = jax.nn.sigmoid(x @ w("b_proj"))
    o = kda_recurrence(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_eps"])
    o = o * jax.nn.sigmoid((x @ w("g_a")) @ w("g_b")).reshape(b, t, h, d)
    return o.reshape(b, t, h * d) @ w("o_proj")


def mla_mixer(x, p, cfg, q_block: int = 512):
    b, t, _ = x.shape
    n, dn, dr, dv = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    w = lambda name: p[name]["kernel"].astype(x.dtype)
    q = jnp.einsum("bth,hnd->btnd", x, w("q_proj"))
    kva = x @ w("kv_a_proj")
    c = rms_norm(kva[..., :rank], p["kv_a_norm"]["scale"], cfg["rms_eps"])
    kvb = jnp.einsum("btr,rnd->btnd", c, w("kv_b_proj"))
    k = jnp.concatenate(
        [kvb[..., :dn],
         jnp.broadcast_to(kva[:, :, None, rank:], (b, t, n, dr))], axis=-1)
    v = kvb[..., dn:]
    scale = (dn + dr) ** -0.5
    pad = -t % q_block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = (t + pad) // q_block
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qb, first = args                      # [B, q_block, n, d], scalar
        s = jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale
        rows = first + jnp.arange(q_block)
        s = jnp.where(rows[:, None] >= cols[None, :], s,
                      jnp.asarray(-1e30, s.dtype))
        return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)

    qs = jnp.moveaxis(qp.reshape(b, nblk, q_block, n, dn + dr), 1, 0)
    out = jax.lax.map(block, (qs, jnp.arange(nblk) * q_block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nblk * q_block, n, dv)[:, :t]
    return jnp.einsum("btnd,ndh->bth", out, w("o_proj"))


def moe_ffn(x, p, cfg):
    """Returns (y, pairs routed to held experts / all pairs, tokens of each
    held expert)."""
    start, held = cfg["experts_held"]
    top = cfg["experts_per_token"]
    # The router is fp32 whatever `dtype` says: which experts a token goes
    # to is part of the input to both readings, not of the precision.
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(p["e_score_correction_bias"]), top)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = (cfg["routed_scaling_factor"] * chosen
               / jnp.sum(chosen, axis=-1, keepdims=True))
    y = swiglu(x, p["shared_expert"])
    counts = []
    for e in range(held):
        # This expert's weight for every token: w_i where it was chosen, 0
        # where it was not (a token chooses an expert at most once).
        mine = idx == start + e
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        counts.append(jnp.sum(mine))
        h = (jax.nn.silu(x @ p["w_gate"][e].astype(x.dtype))
             * (x @ p["w_up"][e].astype(x.dtype)))
        y = y + w_e[..., None].astype(x.dtype) * (
            h @ p["w_down"][e].astype(x.dtype))
    counts = jnp.stack(counts)
    return y, jnp.sum(counts) / (idx.size), counts


def layer(x, p, cfg, index: int):
    """One decoder layer; `index` is 0-based. Returns (x, routing share,
    counts of the held experts) — the last two None on a dense layer."""
    h = rms_norm(x, p["input_norm"]["scale"], cfg["rms_eps"])
    if index + 1 in cfg["kda_layers"]:
        x = x + kda_mixer(h, p["kda"], cfg)
    else:
        x = x + mla_mixer(h, p["mla"], cfg)
    h = rms_norm(x, p["post_attn_norm"]["scale"], cfg["rms_eps"])
    if index < cfg["first_k_dense_replace"]:
        return x + swiglu(h, p["mlp"]), None, None
    y, share, counts = moe_ffn(h, p["moe"], cfg)
    return x + y, share, counts


def hidden_states(params, tokens, cfg, dtype=jnp.float32):
    """Post-final-norm hidden states [B,T,hidden] and the routing counters
    the program reports."""
    cfg = {**DEFAULTS, **cfg}
    x = params["embed"].astype(dtype)[tokens]
    shares, loads = [], []
    for i in range(cfg["num_layers"]):
        fn = jax.checkpoint(functools.partial(layer, cfg=cfg, index=i))
        x, share, counts = fn(x, params[f"layer_{i}"])
        if share is not None:
            counts = counts.astype(jnp.float32)
            shares.append(share)
            loads.append(jnp.max(counts) / jnp.maximum(jnp.mean(counts),
                                                        1e-9))
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_eps"])
    counters = {}
    if shares:
        counters = {"moe_local_pair_share": jnp.mean(jnp.stack(shares)),
                    "moe_load_max_over_mean": jnp.max(jnp.stack(loads))}
    return x, counters


def logits(params, tokens, cfg, dtype=jnp.float32):
    x, _ = hidden_states(params, tokens, cfg, dtype)
    return x @ params["lm_head"]["kernel"].astype(dtype)


def loss(params, tokens, targets, cfg, dtype=jnp.float32, chunk: int = 2048):
    """Mean token cross entropy over the (sliced) vocabulary, and the
    counters. The head and the softmax run over `chunk` tokens at a time."""
    x, counters = hidden_states(params, tokens, cfg, dtype)
    head = params["lm_head"]["kernel"].astype(dtype)
    n = targets.size
    xf, tf = x.reshape(n, -1), targets.reshape(n)
    pad = -n % chunk
    xf = jnp.pad(xf, ((0, pad), (0, 0)))
    tf = jnp.pad(tf, (0, pad))
    live = (jnp.arange(n + pad) < n).astype(jnp.float32)

    @jax.checkpoint
    def block(args):
        xb, tb, mb = args
        lg = (xb @ head).astype(jnp.float32) if dtype == jnp.float32 \
            else (xb @ head)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((logz - gold).astype(jnp.float32) * mb)

    parts = jax.lax.map(block, tuple(
        a.reshape((-1, chunk) + a.shape[1:]) for a in (xf, tf, live)))
    return jnp.sum(parts) / n, counters


def loss_and_grads(params, tokens, targets, cfg, dtype=jnp.float32):
    """((loss, counters), gradients): the gradients with respect to the
    fp32 parameters, whatever `dtype` the pass computes in."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            params, tokens, targets, cfg, dtype)
