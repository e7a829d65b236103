"""tpklint self-tests: every rule fires on a seeded violation, stays
silent on the fixed form, honors pragmas only with a reason, and the
real tree is clean (the tier-1 gate). Fixture snippets run against tmp
trees via tpklint.run(root, rules), exactly the production entrypoint.
"""

from __future__ import annotations

import os
import re
import shutil
import subprocess
import sys
import textwrap
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import tpklint  # noqa: E402
from tools.tpklint import Finding  # noqa: E402


def lint(root, files: dict[str, str] | None = None,
         rules: list[str] | None = None):
    """Write fixture files under `root` and run the selected rules."""
    for rel, content in (files or {}).items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(content))
    return tpklint.run(str(root), rules)


def fmts(findings):
    return [f.format() for f in findings]


# -- findings format (clickable file:line pin) ------------------------------


def test_finding_format_is_clickable():
    f = Finding("host-sync", "kubeflow_tpu/serve/generation.py", 42, "msg")
    assert f.format() == "kubeflow_tpu/serve/generation.py:42: host-sync: msg"
    # Pin the shape: path:line: rule-id: message (tools and editors parse it).
    assert re.fullmatch(r"[^:]+:\d+: [a-z0-9-]+: .+", f.format())


def test_runner_output_matches_format(tmp_path):
    fs = lint(tmp_path, {"a.py": """\
        # tpk-hot: worker
        def worker(x):
            print(x)
        """}, ["host-sync"])
    assert len(fs) == 1
    assert fs[0].path == "a.py" and fs[0].line == 3
    assert re.fullmatch(r"a\.py:3: host-sync: .+", fs[0].format())


# -- rule: host-sync --------------------------------------------------------


HOT_VIOLATIONS = """\
    import numpy as np
    import jax

    # tpk-hot: worker
    def worker(self, dev, rec):
        v = dev.item()                  # flagged
        jax.block_until_ready(dev)      # flagged
        jax.device_get(dev)             # flagged
        print("tick")                   # flagged
        host = np.zeros((4,))
        toks = np.asarray(rec)          # flagged (rec unknown)
        a = int(toks[0])                # ok: toks now host-known
        b = float(host[1])              # ok: np.zeros is host
        c = int(dev[0])                 # flagged (device subscript)
        d = int(len(rec))               # ok: scalar cast
        return a, b, c, d
    """


def test_host_sync_flags_the_fetch_shapes(tmp_path):
    fs = lint(tmp_path, {"mod.py": HOT_VIOLATIONS}, ["host-sync"])
    lines = sorted(f.line for f in fs)
    assert lines == [6, 7, 8, 9, 11, 14]
    assert all(f.rule == "host-sync" for f in fs)


def test_host_sync_rebinding_poisons_host_status(tmp_path):
    """A name bound host on one path and device on another must NOT
    count as host — every binding has to be a host constructor."""
    fs = lint(tmp_path, {"mod.py": """\
        import numpy as np

        # tpk-hot: worker
        def worker(self, rec, cold):
            if cold:
                toks = np.zeros((4,))
            else:
                toks = rec["toks"]        # device value rebinds the name
            fetched = np.asarray(toks)    # flagged: toks is poisoned
            return int(fetched[0])        # ok: fetched is host-known
        """}, ["host-sync"])
    assert [f.line for f in fs] == [9]


def test_host_sync_silent_outside_hot_regions(tmp_path):
    # The same body without the marker: not a hot path, no findings.
    body = HOT_VIOLATIONS.replace("# tpk-hot: worker\n    ", "")
    assert lint(tmp_path, {"mod.py": body}, ["host-sync"]) == []


def test_host_sync_region_markers(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        def run(dev):
            x = dev.item()      # outside the region: fine
            # tpk-hot: begin loop
            for _ in range(3):
                y = dev.item()
            # tpk-hot: end loop
            return x, y
        """}, ["host-sync"])
    assert [f.line for f in fs] == [5]


def test_host_sync_unclosed_region_is_a_finding(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-hot: begin loop
        def run():
            pass
        """}, ["host-sync"])
    assert len(fs) == 1 and "never closed" in fs[0].message


def test_host_sync_marker_must_attach_to_a_def(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-hot: floating
        X = 1
        """}, ["host-sync"])
    assert len(fs) == 1 and "not attached" in fs[0].message


def test_required_hot_paths_enforced_when_home_file_exists(tmp_path):
    # A tree that HAS the trainer file but no trainer-step-loop marker:
    # deleting the annotation must itself be a finding.
    fs = lint(tmp_path, {"kubeflow_tpu/train/trainer.py": "x = 1\n"},
              ["host-sync"])
    assert len(fs) == 1
    assert "trainer-step-loop" in fs[0].message


def test_required_hot_path_not_satisfied_from_another_file(tmp_path):
    # A same-named marker in some OTHER module must not satisfy the
    # seed requirement — the label has to live in its home file.
    fs = lint(tmp_path, {
        "kubeflow_tpu/train/trainer.py": "x = 1\n",
        "scratch.py": """\
            # tpk-hot: trainer-step-loop
            def elsewhere():
                pass
            """,
    }, ["host-sync"])
    assert len(fs) == 1 and fs[0].path == "kubeflow_tpu/train/trainer.py"


def test_host_sync_flags_fetchy_method_calls(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-hot: worker
        def worker(self, metrics, x):
            a = float(metrics.get("aux_loss", 0.0))   # flagged
            b = int(x.sum())                          # flagged
            n = len(x)
            c = float(int(n))                         # ok: plain casts
            return a, b, c
        """}, ["host-sync"])
    assert sorted(f.line for f in fs) == [3, 4]


# -- suppression pragmas ----------------------------------------------------


def test_pragma_with_reason_suppresses(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-hot: worker
        def worker(dev):
            # tpk-lint: allow(host-sync) reason=designed fetch boundary
            return dev.item()
        """}, ["host-sync"])
    assert fs == []


def test_pragma_same_line_suppresses(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-hot: worker
        def worker(dev):
            return dev.item()  # tpk-lint: allow(host-sync) reason=designed boundary
        """}, ["host-sync"])
    assert fs == []


def test_pragma_without_reason_suppresses_nothing(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-hot: worker
        def worker(dev):
            # tpk-lint: allow(host-sync)
            return dev.item()
        """}, ["host-sync"])
    rules = sorted(f.rule for f in fs)
    assert rules == ["host-sync", "pragma"]  # finding survives + bad pragma
    assert any("no reason=" in f.message for f in fs)


def test_pragma_unknown_rule_is_a_finding(tmp_path):
    fs = lint(tmp_path, {"mod.py": """\
        # tpk-lint: allow(no-such-rule) reason=whatever
        x = 1
        """}, ["host-sync"])
    assert len(fs) == 1 and fs[0].rule == "pragma"
    assert "unknown rule" in fs[0].message


# -- rule: spec-schema ------------------------------------------------------


@pytest.fixture
def schema_tree(tmp_path):
    """Real generator + freshly rendered artifacts in a tmp tree."""
    gen_rel = "kubeflow_tpu/utils/spec_schema.py"
    dst = tmp_path / gen_rel
    dst.parent.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, gen_rel), dst)
    sys.path.insert(0, str(tmp_path))
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location("_fx_schema", dst)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(str(tmp_path))
    (tmp_path / "spec_schema.json").write_text(mod.render_json())
    cpp = tmp_path / "cpp"
    cpp.mkdir()
    (cpp / "spec_schema.gen.h").write_text(mod.render_cpp_header())
    return tmp_path


def test_spec_schema_clean_when_artifacts_fresh(schema_tree):
    assert lint(schema_tree, rules=["spec-schema"]) == []


def test_spec_schema_stale_json_fires(schema_tree):
    p = schema_tree / "spec_schema.json"
    p.write_text(p.read_text().replace('"steps"', '"stepz"'))
    fs = lint(schema_tree, rules=["spec-schema"])
    assert len(fs) == 1 and fs[0].path == "spec_schema.json"
    assert "stale" in fs[0].message and fs[0].line > 1


def test_spec_schema_missing_header_fires(schema_tree):
    (schema_tree / "cpp" / "spec_schema.gen.h").unlink()
    fs = lint(schema_tree, rules=["spec-schema"])
    assert len(fs) == 1 and fs[0].path == "cpp/spec_schema.gen.h"
    assert "missing" in fs[0].message


# -- rule: lock-discipline --------------------------------------------------


def test_lock_discipline_fires_outside_the_lock(tmp_path):
    fs = lint(tmp_path, {"m.py": """\
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock
                self.stats = {}

            def good(self):
                with self._lock:
                    self.stats["x"] = 1

            def bad(self):
                self.stats["x"] += 1
        """}, ["lock-discipline"])
    assert len(fs) == 1 and fs[0].line == 14
    assert "outside `with self._lock:`" in fs[0].message


def test_lock_discipline_declaring_method_and_nesting_exempt(tmp_path):
    fs = lint(tmp_path, {"m.py": """\
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock
                self.stats = {}
                self.stats["init"] = 0   # pre-thread construction: fine

            def nested_ok(self):
                with self._lock:
                    for k in ("a", "b"):
                        if k:
                            self.stats[k] = 1
        """}, ["lock-discipline"])
    assert fs == []


def test_lock_discipline_trailing_comment_stays_on_its_statement(tmp_path):
    """A trailing `# guarded-by:` must annotate the statement on ITS
    line only — not also the next line, which would absurdly register
    `self._lock = threading.Lock()` as guarded by itself."""
    fs = lint(tmp_path, {"m.py": """\
        import threading

        class Bucket:
            def __init__(self):
                self._tokens = 0.0  # guarded-by: _lock
                self._lock = threading.Lock()

            def probe(self):
                return self._lock.locked()   # lock use: never a finding

            def peek(self):
                return self._tokens          # real finding
        """}, ["lock-discipline"])
    assert len(fs) == 1 and fs[0].line == 12
    assert "_tokens" in fs[0].message


def test_lock_discipline_closure_does_not_inherit_the_lock(tmp_path):
    """A function/lambda DEFINED inside `with self._lock:` runs later,
    possibly on another thread with the lock released — its guarded
    accesses must still be findings."""
    fs = lint(tmp_path, {"m.py": """\
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock
                self.stats = {}

            def register(self):
                with self._lock:
                    def cb():
                        self.stats["x"] = 1   # deferred: not locked
                    self._cb = cb
                    self._lam = lambda: self.stats["y"]
        """}, ["lock-discipline"])
    assert sorted(f.line for f in fs) == [12, 14]


def test_lock_discipline_pragma_with_reason(tmp_path):
    fs = lint(tmp_path, {"m.py": """\
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock
                self.stats = {}

            def reader(self):
                # tpk-lint: allow(lock-discipline) reason=single-writer int read, GIL-atomic
                return self.stats
        """}, ["lock-discipline"])
    assert fs == []


# -- rule: cpp-checked-io ---------------------------------------------------


CPP_FIXTURE = """\
    #include <cstdio>
    void f(FILE* fp, const char* b, unsigned n) {
      fwrite(b, 1, n, fp);                       // flagged: bare statement
      if (fwrite(b, 1, n, fp) != n) return;      // checked
      size_t w = fwrite(b, 1, n, fp);            // assigned
      (void)w;
      bool ok = fflush(fp) == 0 &&
                fsync(1) == 0;                   // wrapped but checked
      (void)ok;
      (void)fsync(1);                            // explicit discard passes
      // a comment saying fsync(fd); never counts
      const char* s = "fsync(fd); in a string";
      (void)s;
      ftruncate(1, 0);                           // flagged
    }
    """


def test_cpp_checked_io_flags_bare_calls_only(tmp_path):
    fs = lint(tmp_path, {"cpp/io.cc": CPP_FIXTURE}, ["cpp-checked-io"])
    assert sorted(f.line for f in fs) == [3, 14]
    assert all("unchecked" in f.message for f in fs)


def test_cpp_checked_io_braceless_control_bodies(tmp_path):
    fs = lint(tmp_path, {"cpp/b.cc": """\
        void f(FILE* fp, const char* b, unsigned n, bool have) {
          if (have) fwrite(b, 1, n, fp);             // flagged
          if (have) { } else fsync(1);               // flagged
          for (int i = 0; i < 2; ++i) ftruncate(1, 0);  // flagged
          if (fwrite(b, 1, n, fp) != n) return;      // checked
          bool ok = have && rename("a", "b") == 0;   // checked
          (void)ok;
        }
        """}, ["cpp-checked-io"])
    assert sorted(f.line for f in fs) == [2, 3, 4]


def test_cpp_checked_io_pragma(tmp_path):
    fixed = CPP_FIXTURE.replace(
        "  fwrite(b, 1, n, fp);",
        "  // tpk-lint: allow(cpp-checked-io) reason=best-effort side file\n"
        "  fwrite(b, 1, n, fp);").replace(
        "  ftruncate(1, 0);",
        "  ftruncate(1, 0);  // tpk-lint: allow(cpp-checked-io) reason=advisory truncate")
    assert lint(tmp_path, {"cpp/io.cc": fixed}, ["cpp-checked-io"]) == []


# -- rule: ack-after-durable ------------------------------------------------


def _copy_server(tmp_path):
    rel = "cpp/server.cc"
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, rel), dst)
    return dst


def test_ack_after_durable_real_server_is_clean(tmp_path):
    _copy_server(tmp_path)
    assert lint(tmp_path, rules=["ack-after-durable"]) == []


def test_ack_after_durable_silent_without_server(tmp_path):
    # Fixture trees without cpp/server.cc must not fire (other rule
    # tests build such trees constantly).
    assert lint(tmp_path, {"cpp/other.cc": "int x;\n"},
                ["ack-after-durable"]) == []


def test_release_before_commit_turns_red(tmp_path):
    """THE red switch: a copy of the real server.cc that flushes staged
    replies BEFORE the covering fsync (the whole CommitAndRelease body
    reordered, markers riding along) must be flagged."""
    dst = _copy_server(tmp_path)
    src = dst.read_text()
    commit_mark = "// ack-after-durable: commit"
    release_mark = "// ack-after-durable: release"
    assert commit_mark in src and release_mark in src
    # Swap the two marker labels — textually equivalent to moving the
    # release block above the commit call.
    mutated = (src.replace(commit_mark, "@@TMP@@")
                  .replace(release_mark, commit_mark)
                  .replace("@@TMP@@", release_mark))
    dst.write_text(mutated)
    fs = lint(tmp_path, rules=["ack-after-durable"])
    assert len(fs) == 1
    assert "BEFORE the covering fsync" in fs[0].message


def test_deleting_ack_marker_turns_red(tmp_path):
    dst = _copy_server(tmp_path)
    src = dst.read_text()
    dst.write_text(src.replace("// ack-after-durable: release", "// gone"))
    fs = lint(tmp_path, rules=["ack-after-durable"])
    assert len(fs) == 1
    assert "ack-after-durable: release" in fs[0].message


# -- rule: ack-after-quorum (ISSUE 11) --------------------------------------


def _copy_replica(tmp_path):
    rel = "cpp/replica.cc"
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, rel), dst)
    return dst


def test_ack_after_quorum_real_files_are_clean(tmp_path):
    _copy_server(tmp_path)
    _copy_replica(tmp_path)
    assert lint(tmp_path, rules=["ack-after-quorum"]) == []


def test_ack_after_quorum_silent_in_fixture_trees(tmp_path):
    assert lint(tmp_path, {"cpp/other.cc": "int x;\n"},
                ["ack-after-quorum"]) == []


def test_release_before_quorum_wait_turns_red(tmp_path):
    """THE red switch: a copy of the real server.cc where staged
    replies flush BEFORE the quorum wait (marker order swapped — the
    textual equivalent of releasing acks while a minority holds the
    batch) must be flagged."""
    dst = _copy_server(tmp_path)
    _copy_replica(tmp_path)
    src = dst.read_text()
    qmark = "// ack-after-quorum: quorum-wait"
    rmark = "// ack-after-durable: release"
    assert qmark in src and rmark in src
    mutated = (src.replace(qmark, "@@TMP@@")
                  .replace(rmark, qmark)
                  .replace("@@TMP@@", rmark))
    dst.write_text(mutated)
    fs = lint(tmp_path, rules=["ack-after-quorum"])
    assert len(fs) == 1
    assert "minority holds the batch" in fs[0].message


def test_deleting_quorum_wait_marker_turns_red(tmp_path):
    dst = _copy_server(tmp_path)
    _copy_replica(tmp_path)
    src = dst.read_text()
    dst.write_text(src.replace("// ack-after-quorum: quorum-wait",
                               "// gone"))
    fs = lint(tmp_path, rules=["ack-after-quorum"])
    assert len(fs) == 1
    assert "ack-after-quorum: quorum-wait" in fs[0].message


def test_apply_before_term_check_turns_red(tmp_path):
    """Follower-path red switch: a copy of the real replica.cc whose
    apply marker precedes the term check (fencing bypassed) must be
    flagged."""
    _copy_server(tmp_path)
    dst = _copy_replica(tmp_path)
    src = dst.read_text()
    tmark = "// ack-after-quorum: term-check"
    amark = "// ack-after-quorum: apply"
    assert tmark in src and amark in src
    mutated = (src.replace(tmark, "@@TMP@@")
                  .replace(amark, tmark)
                  .replace("@@TMP@@", amark))
    dst.write_text(mutated)
    fs = lint(tmp_path, rules=["ack-after-quorum"])
    assert len(fs) == 1
    assert "fencing bypassed" in fs[0].message


def test_deleting_term_check_marker_turns_red(tmp_path):
    _copy_server(tmp_path)
    dst = _copy_replica(tmp_path)
    src = dst.read_text()
    dst.write_text(src.replace("// ack-after-quorum: term-check",
                               "// gone"))
    fs = lint(tmp_path, rules=["ack-after-quorum"])
    assert len(fs) == 1
    assert "ack-after-quorum: term-check" in fs[0].message


def test_bare_fwrite_in_group_commit_turns_red(tmp_path):
    """cpp-checked-io coverage of the new commit path: a copy of the
    real store.cc whose covering batch fwrite stops checking its return
    must be flagged (the ISSUE 2 bug class resurfacing inside ISSUE 8's
    hot path)."""
    rel = "cpp/store.cc"
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, rel), dst)
    src = dst.read_text()
    checked = ("size_t wrote = fwrite(batch_buf_.data(), 1, "
               "batch_buf_.size(), wal_);")
    assert checked in src  # the real commit write, currently checked
    assert lint(tmp_path, rules=["cpp-checked-io"]) == []
    dst.write_text(src.replace(
        checked, "fwrite(batch_buf_.data(), 1, batch_buf_.size(), wal_);"))
    fs = lint(tmp_path, rules=["cpp-checked-io"])
    assert len(fs) == 1
    assert "unchecked `fwrite`" in fs[0].message


# -- rule: metrics (the migrated check_metrics) -----------------------------


def test_metrics_rule_fires_in_fixture_tree(tmp_path):
    fs = lint(tmp_path, {
        "kubeflow_tpu/m.py": """\
            from kubeflow_tpu.utils.resilience import metrics
            metrics.inc("bad_name_total")
            metrics.inc("tpk_good_things")
            """,
        "README.md": "| `tpk_documented_total` | counter | stale row |\n",
    }, ["metrics"])
    msgs = " ".join(f.message for f in fs)
    assert "must carry the tpk_ prefix" in msgs
    assert "tpk_good_things must end in _total" in msgs
    assert "missing from the README" in msgs
    assert "no code emits it" in msgs
    # Locations are real file:line anchors, not placeholders.
    assert all(f.line >= 1 and f.path for f in fs)


def test_metrics_shim_keeps_cli_and_api():
    """tools/check_metrics.py must keep its historical module API (the
    test_obs gate loads it by path) and its CLI output."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_metrics_shim", os.path.join(REPO, "tools",
                                           "check_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check() == []
    series, problems = mod.scan_code()
    assert problems == []
    assert len(series) >= 36  # the 36-series check, not weakened
    out = subprocess.run([sys.executable, "tools/check_metrics.py"],
                         cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0
    assert "README in sync" in out.stdout


# -- acceptance: the real tree, and red-switch mutations on copies ----------


def _copy_engine_tree(tmp_path):
    rel = "kubeflow_tpu/serve/generation.py"
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, rel), dst)
    return dst


def test_real_engine_copy_is_clean(tmp_path):
    _copy_engine_tree(tmp_path)
    assert lint(tmp_path, rules=["host-sync"]) == []


def test_bare_item_in_hot_path_turns_red(tmp_path):
    dst = _copy_engine_tree(tmp_path)
    marker = "        inflight: deque = deque()"
    dst.write_text(dst.read_text().replace(
        marker, marker + "\n        _ = self._cache.item()"))
    fs = lint(tmp_path, rules=["host-sync"])
    assert len(fs) == 1 and "engine-loop" in fs[0].message


def test_deleting_hot_markers_turns_red(tmp_path):
    dst = _copy_engine_tree(tmp_path)
    dst.write_text(dst.read_text().replace("# tpk-hot: engine-fetch\n", ""))
    fs = lint(tmp_path, rules=["host-sync"])
    assert any("engine-fetch" in f.message for f in fs)


def test_deleting_remote_admit_marker_turns_red(tmp_path):
    dst = _copy_engine_tree(tmp_path)
    dst.write_text(dst.read_text().replace(
        "    # tpk-hot: remote-admit\n", ""))
    fs = lint(tmp_path, rules=["host-sync"])
    assert any("remote-admit" in f.message for f in fs)


def test_host_fetch_in_remote_admit_turns_red(tmp_path):
    """A host sync inside the decode-side remote-admit loop would stall
    every in-flight decode chunk behind the handoff — the isolation the
    role split exists to buy."""
    dst = _copy_engine_tree(tmp_path)
    marker = '        kd = req.get("rng_key")'
    src = dst.read_text()
    assert marker in src
    dst.write_text(src.replace(
        marker, "        _ = self._cache.item()\n" + marker))
    fs = lint(tmp_path, rules=["host-sync"])
    assert len(fs) == 1 and "remote-admit" in fs[0].message


def test_deleting_spec_hot_markers_turns_red(tmp_path):
    for label in ("spec-dispatch", "spec-reconcile"):
        dst = _copy_engine_tree(tmp_path / label)
        dst.write_text(dst.read_text().replace(
            f"    # tpk-hot: {label}\n", ""))
        fs = lint(tmp_path / label, rules=["host-sync"])
        assert any(label in f.message for f in fs)


def test_deleting_dispatch_helper_markers_turns_red(tmp_path):
    """What both dispatchers call runs once per dispatch: each helper's
    marker is required, so the rule keeps reading the code that moved
    out of the dispatchers."""
    for label in ("dispatch-rows", "dispatch-last-tokens",
                  "dispatch-tables"):
        dst = _copy_engine_tree(tmp_path / label)
        dst.write_text(dst.read_text().replace(
            f"    # tpk-hot: {label}\n", ""))
        fs = lint(tmp_path / label, rules=["host-sync"])
        assert any(label in f.message for f in fs)


def test_host_fetch_in_gather_rows_turns_red(tmp_path):
    """Reading a row's pending first token on the host, in the snapshot
    both dispatchers take, would sync every dispatch behind the prefill
    it was meant to overlap."""
    dst = _copy_engine_tree(tmp_path)
    needle = '                last[i] = st["last"]'
    src = dst.read_text()
    assert src.count(needle) == 1
    dst.write_text(src.replace(
        needle, needle + '\n            else:\n'
        '                last[i] = int(st["pending"][0][0])'))
    fs = lint(tmp_path, rules=["host-sync"])
    assert len(fs) == 1 and "dispatch-rows" in fs[0].message


def test_host_fetch_in_spec_reconcile_turns_red(tmp_path):
    """The spec reconcile owns the disp-invariant bookkeeping for BOTH
    sub-batch chains — an unmarked host sync here re-serializes the
    whole pipelined loop, exactly what the hot-path guard exists to
    catch."""
    dst = _copy_engine_tree(tmp_path)
    marker = "            def doom_later() -> None:"  # in `engine.emit`
    src = dst.read_text()
    assert src.count(marker) == 1
    dst.write_text(src.replace(
        marker, "            _ = self._cache.item()\n" + marker))
    fs = lint(tmp_path, rules=["host-sync"])
    assert len(fs) == 1 and "spec-reconcile" in fs[0].message


def test_tier_state_outside_lock_turns_red(tmp_path):
    """HostKVTier's transfer/spill state is guarded-by-declared; an
    access escaping `with self._lock:` is a finding on a copy of the
    REAL file."""
    rel = "kubeflow_tpu/serve/kv_transfer.py"
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, rel), dst)
    assert lint(tmp_path, rules=["lock-discipline"]) == []
    src = dst.read_text()
    marker = "    def probe_longest(self, aid: int, ids) -> int | None:"
    dst.write_text(src.replace(
        marker,
        "    def sneaky(self):\n        return len(self._lru)\n\n"
        + marker))
    fs = lint(tmp_path, rules=["lock-discipline"])
    assert len(fs) == 1 and "_lru" in fs[0].message


def test_staling_real_schema_turns_red(tmp_path):
    for rel in ("kubeflow_tpu/utils/spec_schema.py", "spec_schema.json",
                "cpp/spec_schema.gen.h"):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    assert lint(tmp_path, rules=["spec-schema"]) == []
    # Simulate "edited KNOBS, forgot to regenerate": add a knob to the
    # generator only.
    gen = tmp_path / "kubeflow_tpu/utils/spec_schema.py"
    gen.write_text(gen.read_text().replace(
        '    "steps": {"type": "int", "min": 1},',
        '    "steps": {"type": "int", "min": 1},\n'
        '    "brand_new_knob": {"type": "int", "min": 0},'))
    fs = lint(tmp_path, rules=["spec-schema"])
    assert sorted(f.path for f in fs) == ["cpp/spec_schema.gen.h",
                                          "spec_schema.json"]


def test_tree_is_clean_tier1_gate():
    """THE gate: `python -m tools.tpklint` on the real tree exits 0.
    Any rule regression, stale artifact, bare hot-path sync,
    or reasonless pragma in the repo turns this (and tier-1) red."""
    out = subprocess.run([sys.executable, "-m", "tools.tpklint"],
                         cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, f"tpklint findings:\n{out.stdout}{out.stderr}"
    assert "OK" in out.stdout


# -- drive-by regression: the engine-stats snapshot race --------------------


def test_engine_stats_snapshot_survives_key_insertion():
    """ISSUE 3's engine mutated `stats` from the worker thread while
    metrics/metadata threads took unlocked `dict(stats)` snapshots; the
    first adapter request INSERTS a key ('adapter_requests'), and a dict
    copy concurrent with a size change can raise RuntimeError. The lock
    (guarded-by: _stats_lock) closes it; this pins stats_snapshot() as
    tear-free under key-churning writes without building an engine."""
    from kubeflow_tpu.serve.generation import GenerationEngine

    eng = GenerationEngine.__new__(GenerationEngine)
    eng._stats_lock = threading.Lock()
    eng.stats = {"requests": 0}
    stop = threading.Event()
    errors: list[BaseException] = []

    def writer():
        i = 0
        while not stop.is_set():
            i += 1
            with eng._stats_lock:
                # Churn the dict's SIZE, the raced path: new key, drop.
                eng.stats[f"k{i % 61}"] = i
                if i % 7 == 0:
                    eng.stats.pop(f"k{(i - 3) % 61}", None)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(3000):
            try:
                snap = eng.stats_snapshot()
            except BaseException as e:  # noqa: BLE001 — the regression
                errors.append(e)
                break
            assert snap.get("requests") == 0
    finally:
        stop.set()
        t.join(timeout=5)
    assert not errors, f"snapshot raced the writer: {errors[0]!r}"


def test_prefetcher_counters_are_locked():
    """The prefetcher's counter quartet is guarded-by _lock; stats must
    read a coherent snapshot while the worker-side increments run."""
    from kubeflow_tpu.data.prefetch import Prefetcher

    p = Prefetcher(iter([{"x": 1}, {"x": 2}]), depth=0,
                   state_fn=lambda: None)
    next(p)
    s = p.stats
    assert s["pulled"] == 1 and s["consumed"] == 1
    assert s["data_wait_s"] >= 0.0
    p.close()
