"""EvaByte: a byte-level decoder whose attention keeps exact keys and values
for one open window only and one summary row per closed chunk (EVA,
ops/eva.py). After the published `config.json` of EvaByte/EvaByte
(`model_type: evabyte`, `attention_class: eva`) and Zheng et al.,
arXiv:2302.04542.

Per layer: `u = RMSNorm(x)` with weight `(1 + g)` (`norm_add_unit_offset`);
q, k, v without bias, rotary over the whole head at absolute positions;
EVA; `h = x + W_o o`; `x' = h + W_down(silu(W_gate n) * W_up n)`, `n =
RMSNorm(h)`. After the last layer a final RMSNorm and `num_pred_heads`
untied heads: head p scores byte t + 1 + p. Matmul operands are `cfg.dtype`
(bf16) with fp32 accumulation; norms, the softmax's statistics, the pooling
weights, the residual stream (`fp32_skip_add`) and the logits
(`fp32_logits`) are fp32.

What a request of n tokens holds (serving): the exact rows of its open
window, n - W * floor((n - 1) / W) of them, and one summary row for each
chunk of the floor((n - 1) / W) windows before it. A window is pooled by
the first step or piece of the next one ("closing" is lazy: a summary uses
only its own chunk's rows, so any time before its first read will do), so
the state after n rows does not depend on how they were written: prefill
piece by piece, or decode step by step. `EvaState` is what the serving
engine asks: the shapes of that state as a paged pool of two kinds of block
and as a one-request fragment, and how many blocks of each kind n rows hold.

With `cache` the forward is one of three: a piece of a prompt (S > 1: the
cache is a fragment, `cache_index` the piece's first position, a multiple of
the window; `attend_full_cache` says a window came before it and is pooled
first), or one decode step a row (S == 1: the cache is the pool with the
rows' block tables beside it, read and written in place). Without, the
whole sequence from position 0 (`ops.eva.attend_sequence`) and all heads'
logits [B, S, P, V]; with a cache, head 0's [B, S, V], which serving samples
from.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.models.llama import MLPBlock, RMSNorm, apply_rope, rope_table
from kubeflow_tpu.ops import eva


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320                # 64 special ids + 256 bytes
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    head_dim: int = 128
    num_pred_heads: int = 8
    chunk_size: int = 16
    window_size: int = 2048
    max_seq_len: int = 32768
    rope_theta: float = 100000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    flash_block: int = 512
    # What models/llama.py's MLPBlock reads of its configuration.
    mlp_act: str = "silu"
    lora_rank: int = 0
    lora_targets: str = "attn"
    quantized_dense: bool = False

    def __post_init__(self):
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"chunk_size {self.chunk_size} must divide window_size "
                f"{self.window_size}: a window is pooled chunk by chunk")

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads            # no grouping

    @property
    def layer_params(self) -> int:
        """Matmul weights of one layer (+ the two pooling vectors)."""
        h, d = self.hidden_size, self.num_heads * self.head_dim
        return 4 * h * d + 3 * h * self.intermediate_size + 2 * d

    @property
    def num_params(self) -> int:
        h = self.hidden_size
        return (self.num_layers * (self.layer_params + 2 * h) + h
                + (1 + self.num_pred_heads) * self.vocab_size * h)

    def serving_state(self, block_size: int, max_len: int) -> "EvaState":
        return EvaState(self, block_size, max_len)


def evabyte_6_5b() -> EvaByteConfig:
    return EvaByteConfig()


def evabyte_tiny() -> EvaByteConfig:
    """Test size: the same topology, toy widths."""
    return EvaByteConfig(hidden_size=64, intermediate_size=128, num_layers=2,
                         num_heads=4, head_dim=16, chunk_size=4,
                         window_size=32, max_seq_len=256, flash_block=32)


def open_rows(n: int, window: int) -> int:
    """Exact rows that n written rows leave: the window not yet pooled."""
    return n - closed_windows(n, window) * window


def closed_windows(n: int, window: int) -> int:
    """Windows pooled once n rows are written: floor((n - 1) / W), the
    windows before the one that holds the last row."""
    return max(n - 1, 0) // window


class EvaState:
    """What the serving engine asks of this model's decode state: two kinds
    of block in one pool. An exact block is `block_size` rows of K and V,
    one EVA chunk; a summary block is `block_size` summary rows. Both are
    `[block_size, H, D]` of K and of V, so one pool `{"k", "v"}` of
    `[L, blocks, block_size, H, D]` holds both and one table entry names a
    block of either kind."""

    kinds = ("exact", "summary")
    #: A request's blocks come and go while it decodes: taken as its rows
    #: are dispatched, an open window's given back once it is pooled.
    grows = True
    #: Counters the engine keeps for this state, live in its `stats`: at
    #: each decode dispatch the rows of each kind its first step reads,
    #: summed over its rows as `decode_context_tokens` is; windows pooled
    #: and the exact blocks given back for them while the request decoded.
    counters = ("eva_exact_rows", "eva_summary_rows", "eva_windows_closed",
                "eva_exact_blocks_released")

    def __init__(self, cfg: EvaByteConfig, block_size: int, max_len: int):
        if block_size != cfg.chunk_size:
            raise ValueError(
                f"kv_block_size {block_size}: this model's exact blocks are "
                f"its chunks, set kv_block_size = chunk_size "
                f"({cfg.chunk_size})")
        self.cfg, self.bs = cfg, int(block_size)
        self.window = cfg.window_size
        self.per_window = cfg.window_size // cfg.chunk_size
        if self.per_window % self.bs:
            raise ValueError(
                f"a window's {self.per_window} summary rows must fill whole "
                f"blocks of {self.bs}")
        self.summary_rows = (closed_windows(max_len, self.window)
                             * self.per_window)
        #: Table widths of the compiled programs, by kind.
        self.widths = (self.window // self.bs,
                       max(self.summary_rows // self.bs, 1))

    def check(self, prefill_buckets: list) -> None:
        """Refuse an engine whose prompt pieces this state cannot take."""
        if prefill_buckets[-1] != self.window:
            raise ValueError(
                f"prefill_buckets {prefill_buckets}: a prompt is prefilled "
                "one window at a time, so the largest bucket must be the "
                f"window ({self.window})")

    def read(self, written: list) -> dict:
        """Counted at a decode dispatch over rows that have `written` rows
        each: what the chunk's first step reads (its own row among them)."""
        exact, summary = zip(*(self.rows(n + 1) for n in written))
        return {"eva_exact_rows": sum(exact),
                "eva_summary_rows": sum(summary)}

    def released(self, gone: tuple) -> dict:
        """Counted when a live request gives `gone` blocks of each kind
        back: a pooled window's exact blocks (all but the one the new
        window took over in place)."""
        return {"eva_windows_closed": int(gone[0] > 0),
                "eva_exact_blocks_released": gone[0]}

    def rows(self, n: int) -> tuple[int, int]:
        """(exact rows, summary rows) that n written rows leave."""
        return (open_rows(n, self.window),
                closed_windows(n, self.window) * self.per_window)

    def held(self, n: int) -> tuple[int, int]:
        """Blocks of each kind that n written rows hold."""
        ex, sm = self.rows(n)
        return -(-ex // self.bs), sm // self.bs

    def peak(self, n: int) -> int:
        """The most blocks a request holds at once on its way to n rows.
        While the step that pools a window is in flight the request holds
        that window's exact blocks whole *and* already the blocks its
        summaries go into; the last window it closes is the worst."""
        full = closed_windows(n, self.window)
        if not full:
            return self.held(n)[0]
        return self.widths[0] + full * self.per_window // self.bs

    def pool(self, n_blocks: int, kv_quant: str = "none") -> dict:
        """The paged pool, block 0 the reserved NULL block."""
        cfg = self.cfg
        shape = (cfg.num_layers, n_blocks + 1, self.bs, cfg.num_heads,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    def fragment(self, length: int = 0) -> dict:
        """One request's state as prefill builds it: the open window's rows
        and every summary row `max_len` can need, contiguous (whatever
        `length` the engine's row-shaped fragments would have)."""
        cfg = self.cfg
        ex = (cfg.num_layers, 1, self.window, cfg.num_heads, cfg.head_dim)
        sm = (cfg.num_layers, 1, self.widths[1] * self.bs, cfg.num_heads,
              cfg.head_dim)
        return {"k": jnp.zeros(ex, cfg.dtype), "v": jnp.zeros(ex, cfg.dtype),
                "sk": jnp.zeros(sm, cfg.dtype),
                "sv": jnp.zeros(sm, cfg.dtype)}

    def insert(self, pool: dict, frag: dict, tables: dict) -> dict:
        """Scatter a fragment into a request's blocks: `tables["exact"]`
        [widths[0]] and `tables["summary"]` [widths[1]], entries the request
        does not hold (yet) at the NULL block."""
        def blocked(rows):
            return rows.reshape(rows.shape[0], -1, self.bs, *rows.shape[3:])

        out = {}
        for name, sname in (("k", "sk"), ("v", "sv")):
            p = pool[name].at[:, tables["exact"]].set(blocked(frag[name]))
            out[name] = p.at[:, tables["summary"]].set(blocked(frag[sname]))
        return out


def _project(cfg: EvaByteConfig, name: str):
    return nn.DenseGeneral(
        features=(cfg.num_heads, cfg.head_dim), use_bias=False,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "heads", "kv")),
        name=name)


class EvaAttention(nn.Module):
    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, u, cos, sin, positions, cache, cache_index, layer,
                 after_window: bool):
        cfg = self.cfg
        b, s, _ = u.shape
        q = apply_rope(_project(cfg, "q_proj")(u), cos, sin, positions)
        k = apply_rope(_project(cfg, "k_proj")(u), cos, sin, positions)
        v = _project(cfg, "v_proj")(u)
        # The two learned pooling vectors a head (the release's
        # adaptive_mu_k, adaptive_phi): normal(1), so that s mu.k has a
        # spread of order 1 at random weights and no check is blind to them.
        pooling = nn.with_logical_partitioning(
            nn.initializers.normal(1.0), ("heads", "kv"))
        mu = self.param("adaptive_mu_k", pooling,
                        (cfg.num_heads, cfg.head_dim), jnp.float32)
        phi = self.param("adaptive_phi", pooling,
                         (cfg.num_heads, cfg.head_dim), jnp.float32)
        if cache is None:
            o = eva.attend_sequence(q, k, v, mu, phi, window=cfg.window_size,
                                    chunk=cfg.chunk_size)
        elif s > 1:
            o, cache = self._piece(q, k, v, mu, phi, cache, cache_index,
                                   layer, after_window)
        else:
            o, cache = self._step(q[:, 0], k[:, 0], v[:, 0], mu, phi, cache,
                                  cache_index, layer)
            o = o[:, None]
        y = nn.DenseGeneral(
            features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("heads", "kv", "embed")),
            name="o_proj")(o)
        return y, cache

    def _piece(self, q, k, v, mu, phi, frag, index, layer, after_window):
        """A piece of one request's prompt that starts window
        `index // W`; the fragment holds the window before it, whole."""
        cfg = self.cfg
        if q.shape[0] != 1:
            raise ValueError("a fragment is one request's state")
        per = cfg.window_size // cfg.chunk_size
        done = (index[0] // cfg.window_size) * per   # summary rows so far
        fk, fv, sk, sv = (jax.lax.dynamic_index_in_dim(frag[n], layer, 0,
                                                       keepdims=False)
                          for n in ("k", "v", "sk", "sv"))
        if after_window:
            ks, vs = eva.pool_chunks(
                fk.reshape(1, per, cfg.chunk_size, *fk.shape[2:]),
                fv.reshape(1, per, cfg.chunk_size, *fv.shape[2:]), mu, phi)
            sk = jax.lax.dynamic_update_slice(sk, ks, (0, done - per, 0, 0))
            sv = jax.lax.dynamic_update_slice(sv, vs, (0, done - per, 0, 0))
        fk = jax.lax.dynamic_update_slice(fk, k, (0, 0, 0, 0))
        fv = jax.lax.dynamic_update_slice(fv, v, (0, 0, 0, 0))
        o = eva.attend_piece(q, k, v, sk, sv, done[None],
                             block=cfg.flash_block)
        frag = {n: jax.lax.dynamic_update_index_in_dim(frag[n], x, layer, 0)
                for n, x in (("k", fk), ("v", fv), ("sk", sk), ("sv", sv))}
        return o, frag

    def _step(self, q, k, v, mu, phi, cache, index, layer):
        """One decode step a row at absolute position `index` [B], through
        the pool `cache["k"|"v"]` [L, N, bs, H, D] and the rows' tables
        `cache["exact"]`, `cache["summary"]`. A row whose step opens a
        window first pools the one before it: the pool is only *read* under
        a `cond` a row (so no branch returns it, and nothing copies it), and
        the summaries of the rows that did not cross (all zeros) land in the
        NULL block."""
        cfg = self.cfg
        window, bs = cfg.window_size, cache["k"].shape[2]
        per = window // cfg.chunk_size
        n_layer = cache["k"].shape[1]
        flat = {n: cache[n].reshape(-1, *cache[n].shape[2:])
                for n in ("k", "v")}
        base = layer * n_layer
        exact, summary = cache["exact"], cache["summary"]
        off, win = index % window, index // window
        cross = (off == 0) & (index > 0)

        shape = (exact.shape[1], *flat["k"].shape[2:])

        def nothing(*lead):
            return (jnp.zeros(lead + shape, flat["k"].dtype),
                    jnp.zeros(lead + shape, flat["v"].dtype))

        def pooled(row):
            def do():
                blocks = base + exact[row]
                return eva.pool_chunks(flat["k"][blocks], flat["v"][blocks],
                                       mu, phi)
            return jax.lax.cond(cross[row], do, nothing)

        # Most steps open no window for any row: the walk over the rows is
        # itself under a `cond`.
        ks, vs = jax.lax.cond(
            jnp.any(cross),
            lambda: jax.lax.map(pooled, jnp.arange(index.shape[0])),
            lambda: nothing(index.shape[0]))
        first = jnp.maximum(win - 1, 0) * (per // bs)
        dst = jax.vmap(lambda t, f: jax.lax.dynamic_slice(
            t, (f,), (per // bs,)))(summary, first)
        dst = jnp.where(cross[:, None], base + dst, base).reshape(-1)
        block = base + jnp.take_along_axis(exact, (off // bs)[:, None],
                                           axis=1)[:, 0]
        for n, rows, new in (("k", ks, k), ("v", vs, v)):
            f = flat[n].at[dst].set(rows.reshape(-1, bs, *rows.shape[2:]))
            flat[n] = f.at[block, off % bs].set(new.astype(f.dtype))
        o = eva.attend_step(
            q, flat["k"], flat["v"],
            base + jnp.concatenate([exact, summary], axis=1),
            off + 1, win * per, exact.shape[1])
        cache = dict(cache, **{n: flat[n].reshape(cache[n].shape)
                               for n in ("k", "v")})
        return o, cache


class EvaLayer(nn.Module):
    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, carry, layer, cos, sin, positions, cache_index,
                 after_window):
        cfg = self.cfg
        x, cache = carry                      # x: the fp32 residual stream
        u = RMSNorm(cfg.rms_eps, cfg.dtype, True, name="input_norm")(x)
        with jax.named_scope("eva"):
            y, cache = EvaAttention(cfg, name="attn")(
                u, cos, sin, positions, cache, cache_index, layer,
                after_window)
        h = x + y.astype(jnp.float32)
        n = RMSNorm(cfg.rms_eps, cfg.dtype, True, name="post_attn_norm")(h)
        x = h + MLPBlock(cfg, name="mlp")(n).astype(jnp.float32)
        return (x, cache), None


class EvaByte(nn.Module):
    """Causal byte LM. See the module's text for the three cached forms."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, positions: jax.Array | None = None,
                 cache: dict | None = None,
                 cache_index: jax.Array | None = None,
                 return_hidden: bool = False,
                 attend_full_cache: bool = False):
        cfg = self.cfg
        b, s = tokens.shape
        if cache is not None and cache_index is None:
            cache_index = jnp.zeros((b,), jnp.int32)
        if positions is None:
            start = 0 if cache is None else cache_index[:, None]
            positions = start + jnp.broadcast_to(jnp.arange(s), (b, s))
        embed = self.param(
            "embed", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = embed[tokens].astype(jnp.float32)
        cos, sin = rope_table(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        (x, cache), _ = nn.scan(
            lambda mdl, carry, layer: mdl(carry, layer, cos, sin, positions,
                                          cache_index, attend_full_cache),
            variable_axes={"params": 0}, split_rngs={"params": True},
            length=cfg.num_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(EvaLayer(cfg, name="layers"), (x, cache),
          jnp.arange(cfg.num_layers))
        x = RMSNorm(cfg.rms_eps, cfg.dtype, True, name="final_norm")(x)
        if return_hidden:
            return (x, cache) if cache is not None else x
        head = self.param(
            "lm_head", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
                ("embed", None, "vocab")),
            (cfg.hidden_size, cfg.num_pred_heads, cfg.vocab_size),
            cfg.param_dtype)
        logits = jnp.einsum("bsh,hpv->bspv", x, head.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        if cache is not None:
            return logits[:, :, 0], cache     # serving samples from head 0
        return logits
