"""Rule `host-sync`: no host synchronization inside registered hot paths.

The static complement to the runtime sync-budget guards (tests that
monkeypatch-count `ArrayImpl.__float__` / `block_until_ready`): the
runtime guards catch dynamic paths that actually execute; this rule
catches new code at review time, before it runs once.

Registration is in-source, so annotations travel with refactors:

    # tpk-hot: <label>
    def worker(self):             # whole function is a hot region
        ...

    # tpk-hot: begin <label>
    ...region statements...       # any statement in the line range
    # tpk-hot: end <label>

Inside a hot region the rule flags the device-fetch shapes:

  * `.item()` calls, `.block_until_ready()` / `jax.block_until_ready`,
    `jax.device_get` — unconditional host syncs;
  * `print(...)` — a hidden sync when handed device values, and hot
    loops log via the structured logger anyway;
  * `np.asarray(x)` / `np.array(x)` — D2H fetch, unless every name in
    `x` is provably host-resident (assigned from a numpy constructor /
    `np.asarray` earlier in the same function — the "fetch once, then
    host math is free" idiom);
  * `int(x)` / `float(x)` where `x` subscripts a non-host array — the
    per-element fetch idiom (`int(tok[0])`).

This is a shape heuristic, not a type checker: scalar `int(n)` casts
and `jnp.asarray` (H2D) pass untouched, and the deliberate fetch at a
designed pipeline boundary carries an allow-pragma whose reason
documents the design. REQUIRED_HOT_PATHS pins the seed annotations:
deleting one (e.g. while refactoring the engine loop) is itself a
finding, so the guard cannot silently rot.
"""

from __future__ import annotations

import ast
import re

from .core import Context, Finding, rule

RULE = "host-sync"

#: Labels that must exist whenever their home file exists — the seed
#: hot paths (engine dispatch/fetch loop, trainer step loop, prefetcher
#: worker, batcher worker). Fixture trees without these files skip the
#: requirement.
REQUIRED_HOT_PATHS = {
    "engine-loop": "kubeflow_tpu/serve/generation.py",
    "engine-dispatch": "kubeflow_tpu/serve/generation.py",
    "engine-fetch": "kubeflow_tpu/serve/generation.py",
    "trainer-step-loop": "kubeflow_tpu/train/trainer.py",
    "prefetch-worker": "kubeflow_tpu/data/prefetch.py",
    "batcher-worker": "kubeflow_tpu/serve/batcher.py",
    # Router placement runs on every proxied request: table math over
    # poller-cached load signals only — a blocking scrape or host sync
    # here would serialize the whole front door (ISSUE 9).
    "router-placement": "kubeflow_tpu/serve/router.py",
    # Decode-side remote admission (ISSUE 13): import + bookkeeping
    # only — a host fetch here would stall every in-flight decode
    # chunk behind the handoff, undoing the isolation the role split
    # exists to buy (the shipped first token/logprob are already host
    # scalars; nothing may sync).
    "remote-admit": "kubeflow_tpu/serve/generation.py",
    # Speculative sub-batch dispatch + reconcile (ISSUE 18): the spec
    # twin of engine-dispatch/engine-fetch. The reconcile owns the
    # disp-invariant bookkeeping (over-dispatch carry vs emitted
    # width) — an unmarked host fetch here would re-serialize BOTH
    # sub-batch chains, not just the spec one.
    "spec-dispatch": "kubeflow_tpu/serve/generation.py",
    "spec-reconcile": "kubeflow_tpu/serve/generation.py",
    # What both dispatchers call (ISSUE 30): the slot-state snapshot,
    # the on-device last-token splice, the block tables. They run once
    # per dispatch, so the rule reads them like the dispatchers.
    "dispatch-rows": "kubeflow_tpu/serve/generation.py",
    "dispatch-last-tokens": "kubeflow_tpu/serve/generation.py",
    "dispatch-tables": "kubeflow_tpu/serve/generation.py",
}

_MARK = re.compile(r"#\s*tpk-hot:\s*(.+?)\s*$")

#: numpy constructors whose results are host arrays by construction.
_HOST_CTORS = {"zeros", "ones", "empty", "full", "arange", "asarray",
               "array", "concatenate", "stack", "frombuffer"}
_HOST_BUILTINS = {"int", "float", "len", "list", "tuple", "sorted",
                  "min", "max", "range", "sum"}

#: Method names whose result commonly IS a device scalar when the
#: receiver is a device array / metrics dict (`x.sum()`, `d.get(k)`):
#: `int()/float()` over one of these on a non-host receiver is the
#: reduce-then-fetch idiom.
_FETCHY_METHODS = {"get", "sum", "mean", "min", "max", "prod", "any",
                   "all", "item"}


def _func_at(tree: ast.Module, line: int):
    """The FunctionDef whose `def` sits at `line` (marker above) or that
    spans it (marker on the def line)."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.lineno in (line, line + 1):
                return node
    return None


def _enclosing_func(tree: ast.Module, lo: int, hi: int):
    """Innermost function containing the [lo, hi] line range."""
    best = None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= lo and end >= hi:
                if best is None or node.lineno > best.lineno:
                    best = node
    return best


def _is_host_value(value) -> bool:
    if not isinstance(value, ast.Call):
        return False
    fn = value.func
    if (isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name)
            and fn.value.id in ("np", "numpy")
            and fn.attr in _HOST_CTORS):
        return True
    return isinstance(fn, ast.Name) and fn.id in _HOST_BUILTINS


def _host_names(func) -> set[str]:
    """Names whose EVERY binding in `func` comes from a host-array
    constructor or scalar builtin — 'provably host' for this rule. A
    single rebinding from anything else (a device value, a loop target,
    a with-alias, a walrus) poisons the name: host status requires all
    paths to agree, or `np.asarray(x)` after `x = np.zeros(...)` on one
    branch would hide a real D2H fetch on the other."""
    host: set[str] = set()
    poisoned: set[str] = set()
    if func is None:
        return host

    def poison(target) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                poisoned.add(n.id)

    for node in ast.walk(func):
        if isinstance(node, ast.Assign):
            if (len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and _is_host_value(node.value)):
                host.add(node.targets[0].id)
            else:
                for t in node.targets:
                    poison(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            poison(node.target)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            poison(node.target)
        elif isinstance(node, ast.NamedExpr):
            poison(node.target)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            poison(node.optional_vars)
    return host - poisoned


def _names_in(node) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _sub_base(node):
    """The base Name of a (possibly nested) subscript chain, or None."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _check_call(node: ast.Call, label: str, host: set[str],
                rel: str) -> Finding | None:
    fn = node.func
    msg = None
    if isinstance(fn, ast.Attribute):
        if fn.attr == "item" and not node.args and not node.keywords:
            msg = "`.item()` fetches a device scalar"
        elif fn.attr == "block_until_ready":
            msg = "`block_until_ready` stalls the host on the device"
        elif (fn.attr == "device_get" and isinstance(fn.value, ast.Name)
                and fn.value.id == "jax"):
            msg = "`jax.device_get` copies device memory to host"
        elif (fn.attr in ("asarray", "array")
                and isinstance(fn.value, ast.Name)
                and fn.value.id in ("np", "numpy") and node.args):
            names = _names_in(node.args[0])
            if not names or not names <= host:
                msg = (f"`np.{fn.attr}(...)` on a possibly-device value "
                       "is a D2H fetch")
    elif isinstance(fn, ast.Name):
        if fn.id == "print":
            msg = ("`print` in a hot path (host I/O, and a sync when "
                   "handed device values) — use the structured logger")
        elif fn.id in ("int", "float") and len(node.args) == 1:
            arg = node.args[0]
            if isinstance(arg, ast.Subscript):
                base = _sub_base(arg)
                if base is not None and base not in host:
                    msg = (f"`{fn.id}(...)` on an element of `{base}` "
                           "fetches a device scalar")
            elif (isinstance(arg, ast.Call)
                    and isinstance(arg.func, ast.Attribute)
                    and arg.func.attr in _FETCHY_METHODS):
                base = _sub_base(arg.func.value)
                if base is None or base not in host:
                    msg = (f"`{fn.id}(....{arg.func.attr}(...))` on a "
                           "possibly-device value fetches a device "
                           "scalar")
    if msg is None:
        return None
    return Finding(RULE, rel, node.lineno,
                   f"{msg} inside hot path '{label}' — move it off the "
                   "hot path, fetch at a designed boundary, or pragma "
                   "with the design reason")


@rule(RULE, "no host syncs (.item/float/np.asarray/block_until_ready/"
            "print) inside registered hot paths")
def check(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    seen_in: dict[str, set[str]] = {}  # label -> files carrying it
    for rel in ctx.py_files():
        marks: list[tuple[int, list[str]]] = []
        for line, comment in ctx.comments(rel):
            m = _MARK.search(comment)
            if m:
                marks.append((line, m.group(1).split()))
        if not marks:
            continue
        text = ctx.read(rel) or ""
        try:
            tree = ast.parse(text)
        except SyntaxError as e:
            findings.append(Finding(RULE, rel, e.lineno or 1,
                                    f"file does not parse: {e.msg}"))
            continue
        regions: list[tuple[str, object, int, int]] = []
        open_begins: dict[str, int] = {}
        for line, words in marks:
            if words[0] == "begin" and len(words) == 2:
                open_begins[words[1]] = line
            elif words[0] == "end" and len(words) == 2:
                start = open_begins.pop(words[1], None)
                if start is None:
                    findings.append(Finding(
                        RULE, rel, line,
                        f"tpk-hot: end '{words[1]}' without a begin"))
                else:
                    regions.append((words[1], None, start + 1, line - 1))
            elif len(words) == 1:
                func = _func_at(tree, line)
                if func is None:
                    findings.append(Finding(
                        RULE, rel, line,
                        f"tpk-hot: '{words[0]}' is not attached to a "
                        "def (place it on or directly above one, or "
                        "use begin/end)"))
                else:
                    regions.append((words[0], func, func.lineno,
                                    getattr(func, "end_lineno",
                                            func.lineno)))
            else:
                findings.append(Finding(
                    RULE, rel, line,
                    f"malformed tpk-hot marker: {' '.join(words)!r}"))
        for label, start in open_begins.items():
            findings.append(Finding(
                RULE, rel, start,
                f"tpk-hot: begin '{label}' is never closed"))
        for label, func, lo, hi in regions:
            seen_in.setdefault(label, set()).add(rel)
            scope = func or _enclosing_func(tree, lo, hi)
            host = _host_names(scope)
            walk_root = func if func is not None else tree
            for node in ast.walk(walk_root):
                if not isinstance(node, ast.Call):
                    continue
                if func is None and not lo <= node.lineno <= hi:
                    continue
                f = _check_call(node, label, host, rel)
                if f is not None:
                    findings.append(f)
    for label, home in sorted(REQUIRED_HOT_PATHS.items()):
        # The label must live in its HOME file — a same-named marker in
        # some other module must not satisfy the seed requirement.
        if ctx.exists(home) and home not in seen_in.get(label, ()):
            findings.append(Finding(
                RULE, home, 1,
                f"required hot-path annotation '{label}' not found — "
                "the region was deleted or its marker dropped; "
                "re-annotate the loop (see README 'Static analysis')"))
    return findings
