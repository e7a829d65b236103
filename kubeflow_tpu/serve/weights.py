"""How the serving engine holds a model's weights.

A checkpoint arrives in its parameter dtype (fp32 from the model zoo's
seed, from orbax, from an imported HF checkpoint) and the model's forward
rounds most of it to `cfg.dtype` before its first use: every program run
would read 4 B and write 2 B a parameter again to round the same constant
the same way. The engine rounds such a leaf once, when it takes the tree,
and its programs read the stored leaf in place.

Which leaves is read off the program, not off names or ranks: the
model's forward is traced the four ways it is called on the engine's tree
(a prompt's first piece, a later piece, one decode step, the plain
forward behind `predict()`), and a leaf is stored in `cfg.dtype` exactly
when every read of it, in every trace, is a `convert_element_type` to
`cfg.dtype`. A norm scale multiplied in fp32, an embedding table gathered
into an fp32 residual stream, a router's fp32 matmul, a pooling vector:
each has a read that is not that convert, and stays as it came. An
`Int8Leaf` (serve/quant.py) is not a float leaf and passes through
untouched.

A bundle without trained weights is served from its seed, and the tree
`module.init` makes from it is fp32: whole, it can be larger than the device
though what the engine stores of it is not. `Seeded` is that tree not made
yet: `init` traced once into a jaxpr. The rule above needs its shapes only;
`hold` then makes it a leaf at a time (the stacked leaves of a scanned trunk
together: one `scan` makes them), each by running the equations that leaf
depends on (the others, the example's forward pass among them, are dead to
it) one by one as an eager `init` would, so a leaf has the bits the whole
`init` gives it, a check script that rebuilds the weights either way sees the
served ones, and the most fp32 on the device at any moment is one equation's
leaves: one leaf of an unrolled model.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.extend import core as jex
from jax.interpreters import partial_eval as pe

from kubeflow_tpu.serve.quant import _is_quant_leaf as _is_int8


class Seeded:
    """The parameters `module.init(rng, example)` gives, not made yet."""

    def __init__(self, module, rng, example):
        def init(rng):
            return nn.meta.unbox(module.init(rng, example)["params"])

        self._whole, self._rng = init, rng
        closed, self.abstract = jax.make_jaxpr(init, return_shape=True)(rng)
        self._jaxpr, self._consts = closed.jaxpr, closed.consts

    def whole(self):
        """The tree itself, every leaf at once, as `init` makes it."""
        return self._whole(self._rng)

    def together(self) -> list[list[int]]:
        """The leaves' indices (flatten order), those that one equation of
        `init` makes in one list: a scanned trunk's stacked leaves come out
        of one `scan` and are made by running it once, as `init` does; an
        unrolled model's leaves are each alone."""
        made_by = {v: n for n, eqn in enumerate(self._jaxpr.eqns)
                   for v in eqn.outvars}
        groups: dict = {}
        for i, var in enumerate(self._jaxpr.outvars):
            groups.setdefault(made_by.get(var, ("in", i)), []).append(i)
        return list(groups.values())

    def make(self, indices: list[int]) -> list[jax.Array]:
        """The leaves at `indices`, as `init` makes them: the equations they
        depend on, each dispatched by itself."""
        wanted = set(indices)
        jaxpr, _ = pe.dce_jaxpr(
            self._jaxpr, [j in wanted for j in range(len(self._jaxpr.outvars))],
            instantiate=True)
        out = jax.core.eval_jaxpr(jaxpr, self._consts, self._rng)
        return [out[sorted(wanted).index(i)] for i in indices]

    def group(self, name: str):
        """The subtree under the top-level `name`, made the same way: what
        a reference that looks at one layer's fp32 weights at a time asks
        for."""
        first = sum(len(jax.tree.leaves(self.abstract[k]))
                    for k in sorted(self.abstract) if k < name)
        sub = jax.tree.structure(self.abstract[name])
        return sub.unflatten(self.make(
            list(range(first, first + sub.num_leaves))))


def _bodies(eqn) -> list | None:
    """The jaxprs an equation runs, each with the operand position that
    feeds each of its inputs; [] for a plain primitive, None for a call
    whose operands cannot be followed here (its operands then count as
    read some other way)."""
    p, n = eqn.params, len(eqn.invars)
    name = eqn.primitive.name
    if name == "cond":           # operand 0 picks the branch
        return [(b.jaxpr, range(1, n)) for b in p["branches"]]
    if name == "while":          # cond consts, body consts, carry
        c, b = p["cond_nconsts"], p["body_nconsts"]
        return [(p["cond_jaxpr"].jaxpr, [*range(c), *range(c + b, n)]),
                (p["body_jaxpr"].jaxpr, range(c, n))]
    subs = [s for v in p.values()
            for s in (v if isinstance(v, (tuple, list)) else (v,))
            if isinstance(s, (jex.Jaxpr, jex.ClosedJaxpr))]
    if not subs:
        return []
    # jit, scan, remat and the custom-derivative calls hand their operands
    # to one jaxpr in order.
    inner = [getattr(s, "jaxpr", s) for s in subs]
    if len(inner) == 1 and len(inner[0].invars) == n:
        return [(inner[0], range(n))]
    return None


def only_converted(jaxpr, dtype) -> list:
    """For each input of `jaxpr`: None where nothing reads it, True where
    every read of it is a convert to `dtype`, else False."""
    verdict = {v: None for v in jaxpr.invars}

    def read(var, ok) -> None:
        if isinstance(var, jex.Var) and var in verdict and ok is not None:
            verdict[var] = ok if verdict[var] is None else verdict[var] and ok

    for eqn in jaxpr.eqns:
        bodies = _bodies(eqn)
        if bodies is None:
            for var in eqn.invars:
                read(var, False)
        elif bodies:
            for body, feeds in bodies:
                for pos, ok in zip(feeds, only_converted(body, dtype)):
                    read(eqn.invars[pos], ok)
        else:
            ok = (eqn.primitive.name == "convert_element_type"
                  and eqn.params["new_dtype"] == dtype)
            for var in eqn.invars:
                read(var, ok)
    for var in jaxpr.outvars:    # handed on as it is: not a convert
        read(var, False)
    return [verdict[v] for v in jaxpr.invars]


def _forwards(model, state, max_len: int, piece: int) -> list[Callable]:
    """The model's forward as it is run on the engine's tree, each a
    function of the parameters alone (the caches are made inside, so a
    trace allocates nothing)."""
    tokens = jnp.zeros((1, piece), jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)

    def first_piece(p):
        return model.apply({"params": p}, tokens, cache_index=zero,
                           cache=state.fragment(max_len + piece))

    def later_piece(p):
        return model.apply({"params": p}, tokens, cache_index=zero,
                           cache=state.fragment(max_len + piece),
                           positions=jnp.arange(piece)[None],
                           attend_full_cache=True)

    def step(p):
        if state.grows:  # the pool itself, the row's tables beside it
            view = {**state.pool(1),
                    **{kind: jnp.zeros((1, width), jnp.int32)
                       for kind, width in zip(state.kinds, state.widths)}}
        else:
            view = state.slots(1, max_len)
        return model.apply({"params": p}, tokens[:, :1], cache=view,
                           cache_index=zero)

    def plain(p):
        return model.apply({"params": p}, tokens)

    return [first_piece, later_piece, step, plain]


def stored_narrow(model, params, state, dtype, *, max_len: int,
                  piece: int) -> list[bool]:
    """One bool a leaf of `params` (an `Int8Leaf` one leaf), in flatten
    order: True where the engine stores the leaf in `dtype`, the model's
    compute dtype (None: a configuration that names none)."""
    if isinstance(params, Seeded):
        params = params.abstract
    leaves = jax.tree.leaves(params, is_leaf=_is_int8)

    def wider(leaf) -> bool:
        return (not _is_int8(leaf)
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.dtype.itemsize > jnp.dtype(dtype).itemsize)

    if dtype is None or not any(wider(leaf) for leaf in leaves):
        return [False] * len(leaves)   # nothing to round: nothing to trace
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    reads = [only_converted(jax.make_jaxpr(fwd)(abstract).jaxpr, dtype)
             for fwd in _forwards(model, state, max_len, piece)]
    narrow, at = [], 0
    for leaf in leaves:
        seen = [r[at] for r in reads]
        narrow.append(wider(leaf) and True in seen and False not in seen)
        at += len(jax.tree.leaves(leaf))
    return narrow


def hold(params, narrow: list[bool], dtype, put: Callable,
         places: list | None = None, *, donate: bool = False):
    """The tree as the engine keeps it: each leaf that `narrow` marks is
    rounded to `dtype` where it lies, then every leaf goes through
    `put(leaf, place)` (onto the device, or its shard of the mesh: half
    the bytes move). One leaf at a time, and with `donate` the caller
    gives its tree up: a device leaf is deleted as soon as its rounded
    twin exists, so loading never holds the whole tree twice. A `Seeded`
    tree is made here, the leaves of one equation at a time (one leaf, for
    an unrolled model), and given up as it is made."""
    seeded = params if isinstance(params, Seeded) else None
    if seeded is not None:
        params, donate = seeded.abstract, True
    leaves, treedef = jax.tree.flatten(params, is_leaf=_is_int8)
    places = places or [None] * len(leaves)
    cast = jax.jit(lambda x: x.astype(dtype))
    out = [None] * len(leaves)
    for group in (seeded.together() if seeded is not None
                  else [[i] for i in range(len(leaves))]):
        made = seeded.make(group) if seeded is not None else [
            leaves[i] for i in group]
        for i, leaf in zip(group, made):
            if narrow[i]:
                twin = jax.block_until_ready(cast(leaf))
                if donate and isinstance(leaf, jax.Array):
                    leaf.delete()
                leaf = twin
            out[i] = put(leaf, places[i])
        del made
    return treedef.unflatten(out)


def weight_bytes(*trees) -> dict:
    """The two gauges of the engine's `stats`: bytes of weights held, and
    those of them still fp32 (norm scales, what the model reads as fp32,
    an int8 leaf's scales)."""
    leaves = jax.tree.leaves(trees)
    return {"weight_bytes": sum(int(x.nbytes) for x in leaves),
            "weight_bytes_fp32": sum(int(x.nbytes) for x in leaves
                                     if x.dtype == jnp.float32)}
