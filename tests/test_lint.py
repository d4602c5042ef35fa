"""eksml-lint (eksml_tpu/analysis/): the framework-invariant gate.

Fixture snippets drive each checker positive + negative, suppression
and baseline semantics get their own pins, and the self-check runs the
real CLI over the real repo — which makes every invariant (jit purity,
post-override config drift, signal-handler safety, atomic artifact
writes, scope coverage, chart/values sync) a tier-1 gate.  The
acceptance pair from ISSUE 8 is pinned in both directions: the final
tree exits 0, and a synthetic ``args.precision`` read injected after
override application exits 1 naming the rule, file and line.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from eksml_tpu.analysis import ALL_RULES, run_lint
from eksml_tpu.analysis.engine import (Finding, format_human,
                                       load_baseline, write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "eksml_lint.py")


def lint_src(tmp_path, src, rules, name="mod.py"):
    """Write one fixture module and lint it with the given rules."""
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    return run_lint(targets=[str(path)], repo_root=str(tmp_path),
                    rules=rules)


# ---------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------

def test_jit_purity_flags_impurity_through_call_graph(tmp_path):
    r = lint_src(tmp_path, """
        import time, os
        import numpy as np
        import jax

        def helper():
            return time.time()

        def train_step(params, batch):
            helper()
            np.random.seed(0)
            os.environ["X"] = "1"
            return params

        step = jax.jit(train_step, donate_argnums=(0,))
        """, rules=["jit-purity"])
    msgs = [f.message for f in r.findings]
    assert len(r.findings) == 3
    assert any("time.time" in m for m in msgs)
    assert any("np.random" in m for m in msgs)
    assert any("os.environ" in m for m in msgs)
    # every message names the jit root
    assert all("'train_step'" in m for m in msgs)


def test_jit_purity_decorator_and_partial_forms(tmp_path):
    r = lint_src(tmp_path, """
        from functools import partial
        import jax

        @jax.jit
        def a(x):
            print(x)
            return x

        @partial(jax.jit, static_argnums=(1,))
        def b(x, n):
            open("/tmp/f", "w")
            return x
        """, rules=["jit-purity"])
    assert len(r.findings) == 2
    assert any("print()" in f.message for f in r.findings)
    assert any("open()" in f.message for f in r.findings)


def test_jit_purity_plan_jit_and_method_target(tmp_path):
    # the repo idiom: self.plan.jit(self._train_step, ...)
    r = lint_src(tmp_path, """
        import time

        class Trainer:
            def _train_step(self, state, batch):
                t = time.perf_counter()
                return state

            def compiled_step(self):
                return self.plan.jit(self._train_step,
                                     donate_argnums=(0,))
        """, rules=["jit-purity"])
    assert len(r.findings) == 1
    assert "time.perf_counter" in r.findings[0].message


def test_jit_purity_shared_helper_reports_once(tmp_path):
    # two jit roots reaching one impure helper: one finding, not two
    r = lint_src(tmp_path, """
        import time
        import jax

        def helper():
            return time.time()

        @jax.jit
        def step_a(x):
            return helper()

        @jax.jit
        def step_b(x):
            return helper()
        """, rules=["jit-purity"])
    assert len(r.findings) == 1


def test_jit_purity_negative_host_code_and_env_reads(tmp_path):
    r = lint_src(tmp_path, """
        import os, time
        import jax

        def host_loop():
            t = time.time()          # host side: fine
            os.environ["A"] = "1"    # host side: fine

        def train_step(params):
            precision = os.environ.get("EKSML_DEFAULT_PRECISION")  # ok
            key = jax.random.PRNGKey(0)                    # jax rng: ok
            return params

        step = jax.jit(train_step)
        """, rules=["jit-purity"])
    assert r.findings == []


# ---------------------------------------------------------------------
# config-drift
# ---------------------------------------------------------------------

DRIFT_SRC = """
    def run(args, cfg):
        cfg.TRAIN.PRECISION = args.precision
        cfg.TRAIN.REMAT = bool(args.remat)
        cfg.update_args(args.config)
        return args.precision
    """


def test_config_drift_flags_shadowed_read_after_override(tmp_path):
    r = lint_src(tmp_path, DRIFT_SRC, rules=["config-drift"])
    assert len(r.findings) == 1
    f = r.findings[0]
    assert "args.precision" in f.message
    assert "cfg.TRAIN.PRECISION" in f.message  # tells the fix


def test_config_drift_getattr_form_and_wrapped_copy(tmp_path):
    r = lint_src(tmp_path, """
        def run(args, cfg):
            cfg.TRAIN.PARAM_DTYPE = getattr(args, "param_dtype", "f32")
            cfg.update_args(args.config)
            return getattr(args, "param_dtype", "f32")
        """, rules=["config-drift"])
    assert len(r.findings) == 1
    assert "args.param_dtype" in r.findings[0].message


def test_config_drift_negatives(tmp_path):
    r = lint_src(tmp_path, """
        def before(args, cfg):
            cfg.TRAIN.PRECISION = args.precision
            p = args.precision            # read BEFORE override: ok
            cfg.update_args(args.config)
            return cfg.TRAIN.PRECISION

        def unshadowed(args, cfg):
            cfg.TRAIN.PRECISION = args.precision
            cfg.update_args(args.config)
            return args.steps             # never copied into cfg: ok

        def no_override(args, cfg):
            cfg.TRAIN.PRECISION = args.precision
            return args.precision         # no update_args here: ok
        """, rules=["config-drift"])
    assert r.findings == []


# ---------------------------------------------------------------------
# signal-safety
# ---------------------------------------------------------------------

def test_signal_safety_flags_logging_locks_and_telemetry(tmp_path):
    r = lint_src(tmp_path, """
        import signal, logging

        log = logging.getLogger(__name__)

        class H:
            def _on_signal(self, signum, frame):
                self._flag.set()
                log.warning("got %d", signum)
                with self._lock:
                    pass
                registry.counter("sigterm").inc()

            def install(self):
                signal.signal(signal.SIGTERM, self._on_signal)
        """, rules=["signal-safety"])
    msgs = [f.message for f in r.findings]
    assert any("logging call" in m for m in msgs)
    assert any("lock acquisition" in m for m in msgs)
    assert any("telemetry call" in m for m in msgs)
    assert all("'_on_signal'" in m for m in msgs)


def test_signal_safety_walks_handler_call_graph(tmp_path):
    r = lint_src(tmp_path, """
        import signal

        def publish():
            recorder.event("sigterm")

        def on_signal(signum, frame):
            publish()

        signal.signal(signal.SIGTERM, on_signal)
        """, rules=["signal-safety"])
    assert len(r.findings) == 1
    assert "recorder.event" in r.findings[0].message


def test_signal_safety_negative_flag_only_and_unresolved(tmp_path):
    r = lint_src(tmp_path, """
        import signal, time

        class H:
            def _on_signal(self, signum, frame):
                first = not self._flag.is_set()
                self._flag.set()          # Event.set is THE idiom
                if first:
                    self.signal_time = time.time()

            def install(self):
                signal.signal(signal.SIGTERM, self._on_signal)

            def uninstall(self, prev):
                signal.signal(signal.SIGTERM, prev)   # unresolvable: ok
        """, rules=["signal-safety"])
    assert r.findings == []


# ---------------------------------------------------------------------
# atomic-write
# ---------------------------------------------------------------------

def test_atomic_write_flags_plain_write(tmp_path):
    r = lint_src(tmp_path, """
        import json, os

        def bank(path, payload):
            with open(path, "w") as f:
                json.dump(payload, f)
        """, rules=["atomic-write"])
    assert len(r.findings) == 1
    assert "os.replace" in r.findings[0].message


def test_atomic_write_negative_idiom_append_and_read(tmp_path):
    r = lint_src(tmp_path, """
        import json, os

        def bank(path, payload):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)

        def mirror(path, line):
            with open(path, "a") as f:     # jsonl append stream: ok
                f.write(line)

        def load(path):
            with open(path) as f:          # read: ok
                return json.load(f)
        """, rules=["atomic-write"])
    assert r.findings == []


def test_atomic_write_scope_is_per_function(tmp_path):
    # the replace must live with ITS open: a replace of a different
    # expression in the same function does not excuse the write
    r = lint_src(tmp_path, """
        import os

        def two_writes(a, b):
            tmp = a + ".tmp"
            with open(tmp, "w") as f:
                f.write("x")
            os.replace(tmp, a)
            with open(b, "w") as f:        # no replace for b
                f.write("y")
        """, rules=["atomic-write"])
    assert len(r.findings) == 1
    assert r.findings[0].context.startswith('with open(b, "w")')


# ---------------------------------------------------------------------
# scope-coverage
# ---------------------------------------------------------------------

def test_scope_coverage_flags_unresolvable_scope(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.named_scope("totally_unknown_scope")
        def f(x):
            return x
        """, rules=["scope-coverage"],
        name="eksml_tpu/models/fixture.py")
    assert len(r.findings) == 1
    assert "totally_unknown_scope" in r.findings[0].message
    assert "'other' bucket" in r.findings[0].message


def test_scope_coverage_negative_known_scope(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.named_scope("roi_align")
        def f(x):
            with jax.named_scope("rpn_nms"):
                return x
        """, rules=["scope-coverage"],
        name="eksml_tpu/ops/fixture.py")
    assert r.findings == []


def test_scope_coverage_rule_anchor_direction(tmp_path):
    # a tree that still carries SCOPE_RULES but lost its scopes: every
    # component must be reported as un-anchored
    dst = tmp_path / "eksml_tpu" / "profiling"
    dst.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "eksml_tpu", "profiling",
                             "attribution.py"),
                dst / "attribution.py")
    (tmp_path / "eksml_tpu" / "models").mkdir()
    (tmp_path / "eksml_tpu" / "models" / "empty.py").write_text("")
    r = run_lint(targets=["eksml_tpu"], repo_root=str(tmp_path),
                 rules=["scope-coverage"])
    comps = {m.split("'")[1] for m in
             (f.message for f in r.findings) if "'" in m}
    assert "optimizer" in comps and "backbone" in comps
    # findings anchor at the rule's line in attribution.py
    assert all(f.path.endswith("attribution.py") and f.line > 0
               for f in r.findings)


def test_scope_coverage_real_tree_is_covered():
    r = run_lint(targets=["eksml_tpu"], repo_root=REPO,
                 rules=["scope-coverage"])
    assert r.findings == []


# ---------------------------------------------------------------------
# values-config-sync
# ---------------------------------------------------------------------

@pytest.fixture()
def chart_repo(tmp_path):
    """A minimal repo clone: real charts + the real resolver."""
    shutil.copytree(os.path.join(REPO, "charts"), tmp_path / "charts")
    (tmp_path / "tools").mkdir()
    shutil.copy(os.path.join(REPO, "tools", "render_charts.py"),
                tmp_path / "tools" / "render_charts.py")
    return tmp_path


def test_values_sync_clean_on_real_charts(chart_repo):
    # target must contain .py files (the empty-target guard is its own
    # test); the values-sync project checker keys off repo_root/charts
    r = run_lint(targets=["tools"], repo_root=str(chart_repo),
                 rules=["values-config-sync"])
    assert r.findings == []


def test_values_sync_flags_unknown_key_and_dead_value(chart_repo):
    tpl = (chart_repo / "charts" / "maskrcnn" / "templates"
           / "maskrcnn.yaml")
    tpl.write_text(tpl.read_text().replace(
        "- TRAIN.PRECISION={{ .Values.maskrcnn.precision }}",
        "- TRAIN.TYPO_PRECISION={{ .Values.maskrcnn.precision }}"))
    vals = chart_repo / "charts" / "maskrcnn" / "values.yaml"
    vals.write_text(vals.read_text().replace(
        "  data_val: val2017",
        "  data_val: val2017\n  dead_knob_xyz: 1"))
    r = run_lint(targets=["tools"], repo_root=str(chart_repo),
                 rules=["values-config-sync"])
    typo = [f for f in r.findings
            if "TRAIN.TYPO_PRECISION" in f.message]
    dead = [f for f in r.findings if "dead_knob_xyz" in f.message]
    assert typo and dead
    # the unknown-key finding anchors at its SOURCE: the template
    # line that renders it, with real line + context
    assert typo[0].path == "charts/maskrcnn/templates/maskrcnn.yaml"
    assert typo[0].line > 0
    assert "TRAIN.TYPO_PRECISION=" in typo[0].context
    assert dead[0].path == "charts/maskrcnn/values.yaml"
    assert dead[0].line > 0 and "dead_knob_xyz" in dead[0].context
    # distinct defects carry distinct baseline keys (one baselined
    # entry must not grandfather every future finding of the rule)
    keys = [f.key() for f in r.findings]
    assert len(keys) == len(set(keys))


def test_values_sync_resolves_serve_chart(chart_repo):
    """PR 8 round 3 made the checker *degrade gracefully* for a
    non-maskrcnn chart; now that charts/serve exists the checker must
    actually RESOLVE its layout (render_charts.CHART_SPECS) — a clean
    tree yields neither a layout finding nor an unknown-key finding
    for the serve chart."""
    r = run_lint(targets=["tools"], repo_root=str(chart_repo),
                 rules=["values-config-sync"])
    serve = [f for f in r.findings if "charts/serve" in f.path]
    assert serve == [], serve


def test_values_sync_flags_serve_typo_and_dead_key(chart_repo):
    """Both drift directions pinned on the SERVE chart: a rendered
    --config key config.py doesn't know (the pod dies at start), and
    a values.yaml key the template never references (dead knob)."""
    tpl = (chart_repo / "charts" / "serve" / "templates"
           / "serve.yaml")
    tpl.write_text(tpl.read_text().replace(
        "- SERVE.MAX_QUEUE={{ int .Values.serve.max_queue }}",
        "- SERVE.MAX_QUEUE_TYPO={{ int .Values.serve.max_queue }}"))
    vals = chart_repo / "charts" / "serve" / "values.yaml"
    vals.write_text(vals.read_text().replace(
        "  port: 8081",
        "  port: 8081\n  dead_serve_knob: 1"))
    r = run_lint(targets=["tools"], repo_root=str(chart_repo),
                 rules=["values-config-sync"])
    typo = [f for f in r.findings
            if "SERVE.MAX_QUEUE_TYPO" in f.message]
    dead = [f for f in r.findings
            if "serve.dead_serve_knob" in f.message]
    assert typo and dead, r.findings
    assert typo[0].path == "charts/serve/templates/serve.yaml"
    assert typo[0].line > 0
    assert "SERVE.MAX_QUEUE_TYPO=" in typo[0].context
    assert dead[0].path == "charts/serve/values.yaml"
    assert dead[0].line > 0 and "dead_serve_knob" in dead[0].context


# ---------------------------------------------------------------------
# suppression + baseline semantics
# ---------------------------------------------------------------------

def test_inline_suppression_same_line_and_line_above(tmp_path):
    r = lint_src(tmp_path, """
        import json, os

        def bank(path, payload):
            with open(path, "w") as f:  # eksml-lint: disable=atomic-write
                json.dump(payload, f)

        def bank2(path, payload):
            # eksml-lint: disable=atomic-write
            with open(path, "w") as f:
                json.dump(payload, f)

        def bank3(path, payload):
            # eksml-lint: disable=config-drift   (wrong rule: no effect)
            with open(path, "w") as f:
                json.dump(payload, f)
        """, rules=["atomic-write"])
    assert len(r.findings) == 1
    assert len(r.suppressed) == 2


def test_baseline_grandfathers_by_context_not_line(tmp_path):
    src = """
        import json

        def bank(path, payload):
            with open(path, "w") as f:
                json.dump(payload, f)
        """
    r = lint_src(tmp_path, src, rules=["atomic-write"])
    assert len(r.findings) == 1
    baseline = [f.key() for f in r.findings]
    # same code shifted down two lines: the context key still matches
    shifted = "\n\n" + textwrap.dedent(src)
    (tmp_path / "mod.py").write_text(shifted)
    r2 = run_lint(targets=[str(tmp_path / "mod.py")],
                  repo_root=str(tmp_path), rules=["atomic-write"],
                  baseline=baseline)
    assert r2.findings == [] and len(r2.baselined) == 1
    # the offending line changed → the baseline entry no longer covers
    (tmp_path / "mod.py").write_text(textwrap.dedent(src).replace(
        'open(path, "w")', 'open(other, "w")'))
    r3 = run_lint(targets=[str(tmp_path / "mod.py")],
                  repo_root=str(tmp_path), rules=["atomic-write"],
                  baseline=baseline)
    assert len(r3.findings) == 1


def test_baseline_file_round_trip(tmp_path):
    f = Finding("atomic-write", "tools/x.py", 12, "msg",
                context='with open(p, "w") as fh:')
    path = str(tmp_path / "baseline.json")
    write_baseline(path, [f])
    assert load_baseline(path) == [f.key()]
    entries = json.load(open(path))
    assert entries[0]["reason"]          # every entry carries a reason
    assert load_baseline(str(tmp_path / "missing.json")) == []


def test_baseline_update_merges_reasons_and_out_of_scope(tmp_path):
    """--update-baseline must not destroy hand-written reasons or
    silently drop grandfathered debt outside a scoped run."""
    path = str(tmp_path / "baseline.json")
    f_atomic = Finding("atomic-write", "tools/x.py", 5, "m",
                       context='with open(p, "w") as fh:')
    f_drift = Finding("config-drift", "tools/y.py", 9, "m",
                      context="return args.precision")
    write_baseline(path, [f_atomic, f_drift])
    entries = json.load(open(path))
    for e in entries:
        e["reason"] = f"justified: {e['rule']}"
    json.dump(entries, open(path, "w"))
    # scoped re-run: only atomic-write over tools/x.py, finding persists
    write_baseline(path, [f_atomic],
                   active_rules=["atomic-write"],
                   checked_paths=["tools/x.py"])
    by_rule = {e["rule"]: e for e in json.load(open(path))}
    assert by_rule["atomic-write"]["reason"] == "justified: atomic-write"
    assert by_rule["config-drift"]["reason"] == "justified: config-drift"
    # full-scope re-run where the atomic finding vanished: entry dies
    write_baseline(path, [f_drift],
                   active_rules=list(ALL_RULES),
                   checked_paths=["tools/x.py", "tools/y.py"])
    rules = [e["rule"] for e in json.load(open(path))]
    assert rules == ["config-drift"]


def test_empty_target_fails_the_gate(tmp_path):
    r = run_lint(targets=["no/such/dir"], repo_root=str(tmp_path),
                 rules=["atomic-write"])
    assert len(r.findings) == 1
    assert r.findings[0].rule == "parse-error"
    assert "matches no .py files" in r.findings[0].message


def test_dead_values_key_prefix_of_live_key_is_flagged(tmp_path):
    import yaml

    from eksml_tpu.analysis.checkers import ValuesConfigSyncChecker

    chart = tmp_path / "charts" / "mini"
    (chart / "templates").mkdir(parents=True)
    (chart / "values.yaml").write_text(
        "maskrcnn:\n  chips: 1\n  chips_per_host: 2\n")
    (chart / "templates" / "t.yaml").write_text(
        "tpu: {{ .Values.maskrcnn.chips_per_host }}\n")
    out = ValuesConfigSyncChecker()._dead_values_keys(
        yaml, str(tmp_path), "charts/mini")
    assert [f.message.split()[2] for f in out] == ["maskrcnn.chips"]


def test_unknown_rule_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="unknown rule"):
        lint_src(tmp_path, "x = 1\n", rules=["no-such-rule"])


def test_format_human_names_rule_file_line(tmp_path):
    r = lint_src(tmp_path, DRIFT_SRC, rules=["config-drift"])
    text = format_human(r)
    f = r.findings[0]
    assert f"{f.path}:{f.line}: config-drift:" in text


# ---------------------------------------------------------------------
# the CLI gate, both directions (ISSUE 8 acceptance)
# ---------------------------------------------------------------------

def _run_cli(*argv, cwd=REPO):
    return subprocess.run([sys.executable, LINT, *argv],
                          capture_output=True, text=True, cwd=cwd)


def test_self_check_real_repo_zero_findings():
    """THE gate: the committed tree lints clean — every non-baselined
    finding in a future PR fails tier-1 right here."""
    proc = _run_cli("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    # the one reviewed exception (preemption's single log line) is an
    # inline suppression, not silent debt
    assert any(s["path"] == "eksml_tpu/resilience/preemption.py"
               and s["rule"] == "signal-safety"
               for s in payload["suppressed"])
    assert payload["checked_files"] > 50


def test_injected_violation_fails_naming_rule_file_line(tmp_path):
    """Reverse direction: a synthetic post-override args.precision
    read in a small entry-point module exits 1 and names rule, file,
    line."""
    target = tmp_path / "entry_injected.py"
    target.write_text(textwrap.dedent("""
        def run(args, cfg):
            cfg.TRAIN.PRECISION = args.precision
            cfg.update_args(args.config)
            print(f"image={cfg.PREPROC.MAX_SIZE}, {args.precision}")
        """))
    proc = _run_cli("--rules", "config-drift", str(target))
    assert proc.returncode == 1
    line = [ln for ln in proc.stdout.splitlines()
            if "config-drift" in ln][0]
    assert "args.precision" in line
    assert "entry_injected.py" in line
    import re
    assert re.search(r"entry_injected\.py:5: config-drift", line)


def test_cli_update_baseline_then_clean(tmp_path):
    fixture = tmp_path / "mod.py"
    fixture.write_text(textwrap.dedent("""
        import json

        def bank(path, payload):
            with open(path, "w") as f:
                json.dump(payload, f)
        """))
    baseline = str(tmp_path / "baseline.json")
    proc = _run_cli("--rules", "atomic-write", "--baseline", baseline,
                    "--update-baseline", str(fixture))
    assert proc.returncode == 0, proc.stderr
    proc = _run_cli("--rules", "atomic-write", "--baseline", baseline,
                    str(fixture))
    assert proc.returncode == 0, proc.stdout
    # and without the baseline the debt is visible again
    proc = _run_cli("--rules", "atomic-write", "--baseline", baseline,
                    "--no-baseline", str(fixture))
    assert proc.returncode == 1


def test_shipped_baseline_is_empty():
    """ISSUE 8: fix the violations, don't grandfather them.  Anyone
    adding a baseline entry later must justify it in review."""
    entries = json.load(open(os.path.join(REPO, "tools",
                                          "lint_baseline.json")))
    assert entries == []
