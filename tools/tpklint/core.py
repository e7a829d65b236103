"""tpklint core: rule registry, findings, suppression pragmas, runner.

The platform's correctness rests on invariants that used to live in
review comments — "zero added host syncs on the hot paths", "this field
is only touched under its lock", "regenerate the spec schema after
editing KNOBS". tpklint
turns each into a machine-checked tier-1 gate (the generalization of
tools/check_metrics.py, which is rule `metrics` here).

Contract:

  * A rule is a function `check(ctx) -> list[Finding]` registered via
    `@rule("name", doc)`. Rules are pure readers of the tree under
    `ctx.root` — no imports of heavy runtime deps (jax stays cold), so
    `python -m tools.tpklint` runs in seconds anywhere.
  * Findings render as `path:line: rule: message` (clickable; the
    format is pinned by tests/test_tpklint.py).
  * Suppression: `# tpk-lint: allow(<rule>) reason=<non-empty>` (C++:
    `// tpk-lint: ...`) on the finding's line or the line directly
    above. A pragma with no reason suppresses NOTHING and is itself a
    finding — every silence in the tree explains itself.
"""

from __future__ import annotations

import dataclasses
import io
import os
import re
import tokenize
from typing import Callable

#: Directories never scanned (build trees, VCS, caches, and the git-ignored
#: run-time output directories — a builder unpacks a second copy of the
#: tree under one of them to prove chip_smoke.py from committed files).
SKIP_DIRS = {".git", "__pycache__", "build", "build-asan", "build-tsan",
             ".claude", "node_modules", ".pytest_cache",
             ".jax_compile_cache", "chip_smoke_out", "chiprun_out"}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative, '/'-separated
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"


class Context:
    """Read-only view of one source tree, with cached file/comment
    access shared by every rule (tests point it at fixture trees)."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self._text: dict[str, str | None] = {}
        self._comments: dict[str, list[tuple[int, str]]] = {}

    def exists(self, rel: str) -> bool:
        return os.path.isfile(os.path.join(self.root, rel))

    def read(self, rel: str) -> str | None:
        if rel not in self._text:
            path = os.path.join(self.root, rel)
            try:
                with open(path, encoding="utf-8", errors="replace") as fh:
                    self._text[rel] = fh.read()
            except OSError:
                self._text[rel] = None
        return self._text[rel]

    def files(self, *suffixes: str, under: str = "") -> list[str]:
        """Repo-relative paths with one of `suffixes`, sorted, skipping
        build/VCS directories. `under` restricts to a subtree."""
        base = os.path.join(self.root, under) if under else self.root
        out = []
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames if d not in SKIP_DIRS)
            for fn in sorted(filenames):
                if fn.endswith(tuple(suffixes)):
                    rel = os.path.relpath(os.path.join(dirpath, fn),
                                          self.root)
                    out.append(rel.replace(os.sep, "/"))
        return out

    def py_files(self, under: str = "") -> list[str]:
        return self.files(".py", under=under)

    def comments(self, rel: str) -> list[tuple[int, str]]:
        """Real COMMENT tokens of a Python file as (line, text) — via
        tokenize, so marker-looking strings inside string literals (e.g.
        lint self-test fixtures) never register as markers."""
        if rel not in self._comments:
            text = self.read(rel)
            out: list[tuple[int, str]] = []
            if text is not None:
                try:
                    for tok in tokenize.generate_tokens(
                            io.StringIO(text).readline):
                        if tok.type == tokenize.COMMENT:
                            out.append((tok.start[0], tok.string))
                except (tokenize.TokenError, SyntaxError,
                        IndentationError):
                    pass  # unparseable file: other rules will say why
            self._comments[rel] = out
        return self._comments[rel]


RULES: dict[str, Callable[[Context], list[Finding]]] = {}
RULE_DOCS: dict[str, str] = {}

#: Meta-rule id for malformed suppression pragmas.
PRAGMA_RULE = "pragma"


def rule(name: str, doc: str = ""):
    def deco(fn):
        RULES[name] = fn
        RULE_DOCS[name] = doc or (fn.__doc__ or "").strip().splitlines()[0]
        return fn
    return deco


_PRAGMA_RE = re.compile(
    r"(?:#|//)\s*tpk-lint:\s*allow\(([A-Za-z0-9_-]+)\)\s*(.*)$")
_REASON_RE = re.compile(r"reason=(.*\S)")


def collect_pragmas(ctx: Context) -> tuple[set[tuple[str, str, int]],
                                           list[Finding]]:
    """All well-formed suppressions as (rule, path, line), plus findings
    for malformed ones (missing/empty reason, unknown rule id)."""
    allowed: set[tuple[str, str, int]] = set()
    problems: list[Finding] = []
    py = set(ctx.py_files())
    scan = sorted(py | set(ctx.files(".cc", ".h", ".cpp")))
    for rel in scan:
        if rel in py:
            sites = ctx.comments(rel)
        else:
            text = ctx.read(rel) or ""
            sites = [(i + 1, ln) for i, ln in enumerate(text.splitlines())
                     if "tpk-lint:" in ln]
        for line, comment in sites:
            m = _PRAGMA_RE.search(comment)
            if not m:
                continue
            name, rest = m.group(1), m.group(2)
            reason = _REASON_RE.search(rest)
            if name not in RULES:
                problems.append(Finding(
                    PRAGMA_RULE, rel, line,
                    f"allow({name}) names an unknown rule — known: "
                    f"{', '.join(sorted(RULES))}"))
                continue
            if reason is None:
                problems.append(Finding(
                    PRAGMA_RULE, rel, line,
                    f"allow({name}) has no reason= — a suppression "
                    "without a written reason suppresses nothing"))
                continue
            allowed.add((name, rel, line))
    return allowed, problems


def run(root: str, rules: list[str] | None = None) -> list[Finding]:
    """Run the selected rules (default: all) over the tree at `root`,
    apply suppression pragmas, and return surviving findings sorted by
    location."""
    ctx = Context(root)
    allowed, problems = collect_pragmas(ctx)
    findings: list[Finding] = list(problems)
    for name in rules or sorted(RULES):
        if name not in RULES:
            raise KeyError(f"unknown rule {name!r}; known: "
                           f"{', '.join(sorted(RULES))}")
        for f in RULES[name](ctx):
            # A pragma covers its own line and the line directly below
            # (pragma-above style for multi-line statements).
            if ((f.rule, f.path, f.line) in allowed
                    or (f.rule, f.path, f.line - 1) in allowed):
                continue
            findings.append(f)
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))
