"""sparklint self-tests: planted-violation fixtures per rule family
(trace purity, knob registry, concurrency discipline, deprecation
hygiene), the suppression-comment and baseline round trips, and the
self-run gate — the committed tree must lint clean against the
committed baseline, which is exactly what tools/run_tier1.sh enforces.

Everything here is pure-AST and JAX-free by construction (the analyzer
never imports jax), so the whole module runs in well under a second.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from sparknet_tpu.analysis import engine  # noqa: E402
from sparknet_tpu.analysis.core import Baseline, SourceFile  # noqa: E402

pytestmark = pytest.mark.lint


def plant(tmp_path, files):
    """Materialize {rel: source} as a scannable project and lint it."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return engine.load_project(tmp_path)


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# trace purity
# ---------------------------------------------------------------------------

IMPURE_JIT = """\
    import os
    import random
    import time

    import jax
    import numpy as np


    @jax.jit
    def step(x):
        if os.environ.get("HOME"):          # TP001
            pass
        t = time.time()                      # TP002
        r = random.random()                  # TP003
        open("/tmp/x").read()                # TP004
        print("tracing", t, r)               # TP005
        return np.asarray(x)                 # TP006
"""


def test_purity_flags_every_sin_class_under_jit(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": IMPURE_JIT})
    found = rules_of(engine.run_rules(project, {"purity"}))
    assert {"TP001", "TP002", "TP003", "TP004", "TP005",
            "TP006"} <= found


def test_purity_ignores_untraced_functions(tmp_path):
    # the same sins in a plain helper are host-side code, not findings
    project = plant(tmp_path, {"sparknet_tpu/mod.py": textwrap.dedent(
        IMPURE_JIT).replace("@jax.jit\n", "")})
    assert engine.run_rules(project, {"purity"}) == []


def test_purity_follows_the_call_graph(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import os

        import jax


        def helper():
            return os.environ.get("HOME")    # reached from a jit root


        @jax.jit
        def step(x):
            helper()
            return x
    """})
    findings = engine.run_rules(project, {"purity"})
    assert [f.rule for f in findings] == ["TP001"]
    assert findings[0].symbol == "helper"


# ---------------------------------------------------------------------------
# knob registry
# ---------------------------------------------------------------------------

def test_unregistered_knob_read_is_kr001_and_kr002(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import os

        x = os.environ.get("SPARKNET_NOT_A_REAL_KNOB")
    """})
    found = rules_of(engine.run_rules(project, {"knobs"}))
    assert "KR001" in found and "KR002" in found


def test_registered_read_outside_registry_is_kr002_only(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import os

        x = os.environ.get("SPARKNET_FUSE")
    """})
    found = rules_of(engine.run_rules(project, {"knobs"}))
    assert "KR002" in found and "KR001" not in found


def test_env_writes_and_scrub_pops_are_allowed(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import os

        os.environ["SPARKNET_FUSE"] = "off"
        os.environ.pop("SPARKNET_FUSE", None)
    """})
    assert not any(f.rule == "KR002"
                   for f in engine.run_rules(project, {"knobs"}))


def test_unregistered_literal_helper_arg_is_kr001(tmp_path):
    # helper delegation must not launder an unregistered name
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        def _env_float(name, default):
            return default

        x = _env_float("SPARKNET_NOT_A_REAL_KNOB", 1.0)
    """})
    assert "KR001" in rules_of(engine.run_rules(project, {"knobs"}))


def test_committed_registry_has_no_dead_or_undocumented_knobs():
    project = engine.load_project(REPO)
    findings = engine.run_rules(project, {"knobs"})
    assert [f for f in findings if f.rule in ("KR003", "KR004")] == []


# ---------------------------------------------------------------------------
# concurrency discipline
# ---------------------------------------------------------------------------

WORKER = """\
    import threading


    class Worker:
        def __init__(self):
            self.count = 0
            self._t = threading.Thread(target=self._run)

        def _run(self):
            while True:
                try:
                    self.count = self.count + 1
                except Exception:
                    pass

        def reset(self):
            self.count = 0
"""


def test_unguarded_cross_thread_write_is_cd001(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": WORKER})
    assert "CD001" in rules_of(engine.run_rules(project, {"concurrency"}))


def test_unguarded_ok_declaration_silences_cd001(tmp_path):
    src = textwrap.dedent(WORKER).replace(
        "    def __init__",
        '    _unguarded_ok = frozenset({"count"})\n\n    def __init__')
    project = plant(tmp_path, {"sparknet_tpu/mod.py": src})
    assert "CD001" not in rules_of(
        engine.run_rules(project, {"concurrency"}))


def test_lock_guarded_writes_are_not_cd001(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import threading


        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._t = threading.Thread(target=self._run)

            def _run(self):
                with self._lock:
                    self.count = 1

            def reset(self):
                with self._lock:
                    self.count = 0
    """})
    assert "CD001" not in rules_of(
        engine.run_rules(project, {"concurrency"}))


def test_swallowing_worker_handler_is_cd002(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": WORKER})
    assert "CD002" in rules_of(engine.run_rules(project, {"concurrency"}))


def test_parking_the_error_on_self_satisfies_cd002(tmp_path):
    src = textwrap.dedent(WORKER).replace(
        "            except Exception:\n"
        "                pass",
        "            except Exception as e:\n"
        "                self.err = e")
    project = plant(tmp_path, {"sparknet_tpu/mod.py": src})
    found = rules_of(engine.run_rules(project, {"concurrency"}))
    assert "CD002" not in found
    # still broad — CD003 stays, to be narrowed or baselined with a
    # reason; parking only clears the swallow-in-worker charge
    assert "CD003" in found


def test_plain_overbroad_handler_is_cd003(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        def f():
            try:
                g()
            except Exception:
                pass
    """})
    assert "CD003" in rules_of(engine.run_rules(project, {"concurrency"}))


# ---------------------------------------------------------------------------
# deprecation hygiene
# ---------------------------------------------------------------------------

def test_removed_knob_mention_is_dp002(tmp_path):
    # SPARKNET_LRN_CUMSUM is a real tombstone in the committed registry
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import os

        os.environ["SPARKNET" + "_LRN_CUMSUM"] = "1"  # dodge is fine
        PIN = "SPARKNET_LRN_CUMSUM"
    """})
    findings = engine.run_rules(project, {"deprecation"})
    assert [f.rule for f in findings] == ["DP002"]
    assert findings[0].line == 4


def test_dead_symbol_reference_is_dp003(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        from sparknet_tpu.ops import vision

        vision._shim_pin("lrn")
    """})
    assert "DP003" in rules_of(engine.run_rules(project, {"deprecation"}))


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_same_line_and_next_line_suppressions(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        def f():
            try:
                g()
            except Exception:  # sparklint: disable=CD003
                pass


        def h():
            try:
                g()
            # sparklint: disable-next-line=CD003
            except Exception:
                pass


        def unsuppressed():
            try:
                g()
            except Exception:
                pass
    """})
    findings = engine.run_rules(project, {"concurrency"})
    assert [f.symbol for f in findings] == ["unsuppressed"]


def test_disable_all_suppresses_every_rule(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        import os

        x = os.environ.get("SPARKNET_NOT_A_REAL_KNOB")  # sparklint: disable=all
    """})
    assert engine.run_rules(project, {"knobs"}) == []


def test_suppression_comment_grammar():
    sf = SourceFile(Path("/x"), "m.py",
                    "a = 1  # sparklint: disable=TP001, CD003\n"
                    "# sparklint: disable-next-line=KR002\n"
                    "b = 2\n")
    assert sf.suppressed(1, "TP001") and sf.suppressed(1, "CD003")
    assert not sf.suppressed(1, "KR002")
    assert sf.suppressed(3, "KR002") and not sf.suppressed(2, "KR002")


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def test_baseline_round_trip_covers_by_symbol_not_line(tmp_path):
    project = plant(tmp_path, {"sparknet_tpu/mod.py": """\
        def f():
            try:
                g()
            except Exception:
                pass
    """})
    [finding] = engine.run_rules(project, {"concurrency"})
    entries = [{"rule": finding.rule, "path": finding.path,
                "symbol": finding.symbol, "reason": "planted"}]
    path = tmp_path / "baseline.json"
    path.write_text(Baseline.render(entries))
    baseline = Baseline.load(path)
    kept, covered = engine.apply_baseline([finding], baseline)
    assert kept == [] and covered == [finding]
    assert baseline.unused() == []


def test_unused_baseline_entries_are_reported(tmp_path):
    baseline = Baseline([{"rule": "CD003", "path": "gone.py",
                          "symbol": "f", "reason": "stale"}])
    kept, covered = engine.apply_baseline([], baseline)
    assert kept == [] and covered == []
    assert [e["path"] for e in baseline.unused()] == ["gone.py"]


def test_baseline_rejects_empty_reasons():
    with pytest.raises(ValueError, match="reason"):
        Baseline([{"rule": "CD003", "path": "x.py", "symbol": "f",
                   "reason": "  "}])


def test_committed_baseline_has_no_todo_reasons():
    doc = json.loads((REPO / engine.BASELINE_REL).read_text())
    assert doc["kind"] == "sparklint_baseline"
    todo = [e for e in doc["entries"] if e["reason"].startswith("TODO")]
    assert todo == []


# ---------------------------------------------------------------------------
# the CI gate: committed tree is clean
# ---------------------------------------------------------------------------

def _lint_cli(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "lint.py"), *args],
        capture_output=True, text=True, cwd=REPO)


def test_self_run_committed_tree_is_clean():
    res = _lint_cli("run")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 error(s)" in res.stdout
    # every grandfathered entry still matches a real finding
    assert "unused baseline entry" not in res.stdout


def test_knobs_md_is_in_sync():
    res = _lint_cli("knobs", "--check")
    assert res.returncode == 0, res.stdout + res.stderr
