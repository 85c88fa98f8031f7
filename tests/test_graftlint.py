"""graftlint: per-rule fixture tests (one true positive + one true negative
each), baseline mechanics, and the whole-repo gate run.

The repo run IS the suite-time lint the round-5 verdict asked for: it fails
this test file — and therefore tier-1 — on any finding not grandfathered in
lint_baseline.json.
"""

import json
import os
import subprocess
import sys
import textwrap

from deeplearning4j_tpu.lint import (
    AST_RULES, Finding, diff_baseline, lint_paths, lint_source,
    load_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "lint_baseline.json")


def _lint(src, rules=None):
    return lint_source(textwrap.dedent(src), path="fixture.py", rules=rules)


def _rules_hit(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# GL001 — host sync under jit
# ---------------------------------------------------------------------------


class TestGL001HostSync:
    def test_true_positive_decorated(self):
        fs = _lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                y = np.asarray(x)
                return y.item()
        """, rules={"GL001"})
        assert len(fs) == 2
        assert all(f.rule == "GL001" for f in fs)
        assert "np.asarray" in fs[0].message

    def test_true_positive_jit_wrapped(self):
        fs = _lint("""
            import jax
            import numpy as np

            def g(x):
                return np.array(x) + 1

            h = jax.jit(g)
        """, rules={"GL001"})
        assert len(fs) == 1 and fs[0].severity == "error"

    def test_true_positive_float_cast(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                return float(x) * 2
        """, rules={"GL001"})
        assert len(fs) == 1 and fs[0].severity == "warning"

    def test_true_negative(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp
            import numpy as np

            @jax.jit
            def f(x):
                return jnp.asarray(x) + 1

            def host_side(x):     # not jitted: np here is fine
                return np.asarray(x).item()
        """, rules={"GL001"})
        assert fs == []


# ---------------------------------------------------------------------------
# GL002 — unguarded backend probes
# ---------------------------------------------------------------------------


class TestGL002BackendProbe:
    def test_true_positive_import_time(self):
        fs = _lint("""
            import jax

            DEVICES = jax.devices()
        """, rules={"GL002"})
        assert len(fs) == 1 and fs[0].severity == "error"
        assert "import time" in fs[0].message

    def test_true_positive_class_body_is_import_time(self):
        fs = _lint("""
            import jax

            class Trainer:
                N_DEV = jax.local_device_count()
        """, rules={"GL002"})
        assert len(fs) == 1 and fs[0].severity == "error"

    def test_true_positive_module_level_branch(self):
        fs = _lint("""
            import jax

            if jax.device_count() > 1:
                MULTI = True
        """, rules={"GL002"})
        assert len(fs) == 1 and fs[0].severity == "error"

    def test_true_negative_in_process_probe_in_function(self):
        """How a program finds its device: in-process, when called. (A
        child process started to probe would hold the chip its parent
        then needs — the rule no longer asks for one.)"""
        fs = _lint("""
            import jax

            def require_tpu():
                dev = jax.devices()[0]
                if dev.platform != "tpu":
                    raise RuntimeError("no TPU")
                return dev
        """, rules={"GL002"})
        assert fs == []


# ---------------------------------------------------------------------------
# GL003 — side effects under jit
# ---------------------------------------------------------------------------


class TestGL003SideEffects:
    def test_true_positive(self):
        fs = _lint("""
            import jax

            _CALLS = 0

            @jax.jit
            def f(x):
                global _CALLS
                _CALLS += 1
                print("tracing", x)
                return x * 2
        """, rules={"GL003"})
        assert len(fs) == 2
        sev = {f.severity for f in fs}
        assert sev == {"error", "warning"}   # global=error, print=warning

    def test_true_negative_debug_print(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                jax.debug.print("x = {}", x)
                return x * 2

            def host():
                print("not traced")
        """, rules={"GL003"})
        assert fs == []


# ---------------------------------------------------------------------------
# GL004 — PRNG key reuse
# ---------------------------------------------------------------------------


class TestGL004KeyReuse:
    def test_true_positive(self):
        fs = _lint("""
            import jax

            def f(key):
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
        """, rules={"GL004"})
        assert len(fs) == 1 and "consumed again" in fs[0].message

    def test_true_negative_split(self):
        fs = _lint("""
            import jax

            def f(key):
                k1, k2 = jax.random.split(key)
                a = jax.random.normal(k1, (2,))
                b = jax.random.uniform(k2, (2,))
                return a + b
        """, rules={"GL004"})
        assert fs == []

    def test_true_negative_exclusive_branches(self):
        # the weight-init dispatch pattern: one consumption per CALL
        fs = _lint("""
            import jax

            def init(key, scheme):
                if scheme == "normal":
                    return jax.random.normal(key, (2,))
                if scheme == "uniform":
                    return jax.random.uniform(key, (2,))
                return jax.random.bernoulli(key, 0.5, (2,))
        """, rules={"GL004"})
        assert fs == []

    def test_true_negative_stdlib_random(self):
        fs = _lint("""
            import random

            def f(xs):
                a = random.choice(xs)
                b = random.choice(xs)
                return a, b
        """, rules={"GL004"})
        assert fs == []

    def test_true_positive_fold_in_then_double_use(self):
        fs = _lint("""
            import jax

            def f(key, i):
                k = jax.random.fold_in(key, i)
                a = jax.random.normal(k, (2,))
                b = jax.random.normal(k, (2,))
                return a + b
        """, rules={"GL004"})
        assert len(fs) == 1


# ---------------------------------------------------------------------------
# GL005 — mutable defaults
# ---------------------------------------------------------------------------


class TestGL005MutableDefaults:
    def test_true_positive(self):
        fs = _lint("""
            def fit(x, callbacks=[], options={}):
                return x
        """, rules={"GL005"})
        assert len(fs) == 2

    def test_true_negative(self):
        fs = _lint("""
            def fit(x, callbacks=None, option=()):
                callbacks = callbacks or []
                return x

            def _internal(x, scratch=[]):   # private: not the public surface
                return x
        """, rules={"GL005"})
        assert fs == []


# ---------------------------------------------------------------------------
# GL007 — bare/swallowed except
# ---------------------------------------------------------------------------


class TestGL007BareExcept:
    def test_true_positive(self):
        fs = _lint("""
            def f():
                try:
                    risky()
                except:
                    return None

            def g():
                try:
                    risky()
                except Exception:
                    pass
        """, rules={"GL007"})
        assert len(fs) == 2
        assert {f.severity for f in fs} == {"error", "warning"}

    def test_true_negative(self):
        fs = _lint("""
            def f():
                try:
                    risky()
                except ValueError:
                    pass
                except Exception as e:
                    log(e)
        """, rules={"GL007"})
        assert fs == []


# ---------------------------------------------------------------------------
# GL006 — registry shadowing (consistency rule, live registries)
# ---------------------------------------------------------------------------


class TestGL009NumpyInOpImpl:
    def test_true_positive_dict_literal(self):
        findings = _lint("""
            import numpy as np
            GRAPH_OPS = {
                "my_op": lambda a: np.asarray(a).sum(),
            }
        """)
        assert "GL009" in _rules_hit(findings)

    def test_true_positive_annotated_dict_literal(self):
        # the REAL table is `GRAPH_OPS: Dict[...] = {...}` (AnnAssign) —
        # the rule must scan it too (review regression)
        findings = _lint("""
            import numpy as np
            GRAPH_OPS: Dict[str, Callable] = {
                "bad_op": lambda a: np.asarray(a).sum(),
            }
        """)
        assert "GL009" in _rules_hit(findings)

    def test_true_positive_subscript_assign(self):
        findings = _lint("""
            import numpy as np
            def _impl(a):
                return np.stack([a, a])
            _sdmod.GRAPH_OPS["patched_op"] = _impl
        """)
        assert "GL009" in _rules_hit(findings)

    def test_true_positive_registry_decorator(self):
        findings = _lint("""
            import numpy as np
            @_op("my_reduce")
            def my_reduce(x):
                return np.sum(x)
        """)
        assert "GL009" in _rules_hit(findings)

    def test_true_positive_register_call(self):
        findings = _lint("""
            import numpy as np
            def fancy(x):
                return np.asarray(x)
            _REG.register("fancy", fancy)
        """)
        assert "GL009" in _rules_hit(findings)

    def test_true_negative_whitelisted_numpy_static(self):
        # shape_of/stack/unstack are DOCUMENTED numpy-static (their host
        # behavior is the contract) — never flagged
        findings = _lint("""
            import numpy as np
            @_op("stack")
            def stack(*xs, axis=0):
                return np.stack([np.asarray(x) for x in xs], axis=axis)

            @_op("shape_of")
            def shape_of(x):
                return np.asarray(x.shape, np.int32)
        """)
        assert "GL009" not in _rules_hit(findings)

    def test_true_negative_jnp_and_helpers(self):
        # jnp inside an impl and np inside a NON-op helper are both fine
        findings = _lint("""
            import numpy as np
            import jax.numpy as jnp

            GRAPH_OPS = {"ok_op": lambda a: jnp.asarray(a).sum()}

            def plain_helper(x):
                return np.asarray(x)   # not a graph-op impl
        """)
        assert "GL009" not in _rules_hit(findings)

    def test_repo_op_impl_numpy_is_whitelisted_or_justified(self):
        """The live ops/ tree carries no un-justified np in op impls —
        every hit is either whitelisted (shape_of/stack/unstack) or has an
        inline disable with a written justification."""
        findings = lint_paths(["deeplearning4j_tpu/ops"], REPO,
                              rules=["GL009"])
        assert findings == [], "\n".join(f.render() for f in findings)


class TestGL010WalltimeDuration:
    def test_true_positive_direct_subtraction(self):
        fs = _lint("""
            import time

            def run(job):
                t0 = time.time()
                job()
                return time.time() - t0
        """, rules={"GL010"})
        assert len(fs) == 1
        assert fs[0].rule == "GL010" and fs[0].severity == "error"
        assert "perf_counter" in fs[0].message

    def test_true_positive_attribute_anchor_across_methods(self):
        # the repo's own listener pattern: anchor stashed in __init__,
        # subtracted in a later callback
        fs = _lint("""
            import time

            class L:
                def __init__(self):
                    self._t0 = time.time()

                def done(self):
                    return (time.time() - self._t0) * 1000.0
        """, rules={"GL010"})
        assert len(fs) == 1 and fs[0].rule == "GL010"

    def test_true_positive_from_import_alias(self):
        fs = _lint("""
            from time import time as now

            def f():
                a = now()
                return now() - a
        """, rules={"GL010"})
        assert len(fs) == 1 and fs[0].rule == "GL010"

    def test_true_negative_timestamps_and_epoch_arithmetic(self):
        # timestamps (stored, compared, shifted by a constant) are
        # whitelisted: only a two-wall-operand subtraction is a duration
        fs = _lint("""
            import time

            def record(store, timeout):
                store["timestamp"] = time.time()
                yesterday = time.time() - 86400
                deadline = time.time() + timeout
                return time.time() > deadline, yesterday
        """, rules={"GL010"})
        assert fs == []

    def test_true_negative_perf_counter(self):
        fs = _lint("""
            import time

            def run(job):
                t0 = time.perf_counter()
                job()
                return time.perf_counter() - t0
        """, rules={"GL010"})
        assert fs == []

    def test_repo_durations_are_monotonic(self):
        """The package itself carries no wall-clock durations (the
        observability PR swept listeners/arbiter/earlystopping)."""
        findings = lint_paths(["deeplearning4j_tpu"], REPO, rules=["GL010"])
        assert findings == [], "\n".join(f.render() for f in findings)


class TestGL006RegistryShadowing:
    def test_repo_whitelist_is_exact(self):
        from deeplearning4j_tpu.lint.rules_consistency import (
            rule_registry_shadowing)
        assert rule_registry_shadowing(REPO) == []

    def test_unlisted_shadow_is_flagged(self, monkeypatch):
        from deeplearning4j_tpu.autodiff import samediff
        from deeplearning4j_tpu.lint.rules_consistency import (
            rule_registry_shadowing)
        from deeplearning4j_tpu.ops.registry import registry
        victim = next(n for n in registry().names()
                      if n not in samediff.GRAPH_OPS)
        monkeypatch.setitem(samediff.GRAPH_OPS, victim, lambda *a: a)
        fs = rule_registry_shadowing(REPO)
        assert len(fs) == 1 and victim in fs[0].message
        assert fs[0].rule == "GL006" and fs[0].severity == "error"

    def test_stale_whitelist_entry_is_flagged(self, monkeypatch):
        from deeplearning4j_tpu.autodiff import samediff
        from deeplearning4j_tpu.lint.rules_consistency import (
            rule_registry_shadowing)
        monkeypatch.setattr(
            samediff, "REGISTRY_SHADOW_WHITELIST",
            samediff.REGISTRY_SHADOW_WHITELIST | {"not_a_real_op_name"})
        fs = rule_registry_shadowing(REPO)
        assert len(fs) == 1 and "stale" in fs[0].message


# ---------------------------------------------------------------------------
# GL008 — README surface counts (consistency rule)
# ---------------------------------------------------------------------------


class TestGL008ReadmeCounts:
    def test_repo_readme_matches_live_registries(self):
        from deeplearning4j_tpu.lint.rules_consistency import (
            rule_readme_counts)
        assert rule_readme_counts(REPO) == []

    def test_drifted_claim_is_flagged(self, tmp_path):
        from deeplearning4j_tpu.lint.rules_consistency import (
            rule_readme_counts)
        (tmp_path / "README.md").write_text(
            "a 99999-entry named declarable-op registry of things\n")
        fs = rule_readme_counts(str(tmp_path))
        assert len(fs) == 1 and fs[0].rule == "GL008"
        assert "99999" in fs[0].message


# ---------------------------------------------------------------------------
# suppression + baseline mechanics
# ---------------------------------------------------------------------------


class TestSuppressionAndBaseline:
    def test_inline_disable_comment(self):
        fs = _lint("""
            def fit(x, callbacks=[]):  # graftlint: disable=GL005
                return x
        """, rules={"GL005"})
        assert fs == []

    def test_disable_is_rule_scoped(self):
        fs = _lint("""
            def fit(x, callbacks=[]):  # graftlint: disable=GL001
                return x
        """, rules={"GL005"})
        assert len(fs) == 1   # disabling GL001 does not silence GL005

    def test_skip_file_marker(self):
        fs = _lint("""\
            # graftlint: skip-file
            def fit(x, callbacks=[]):
                return x
        """)
        assert fs == []

    def test_diff_baseline_new_and_fixed(self):
        f1 = Finding(path="a.py", line=3, rule="GL005", severity="warning",
                     message="m1")
        f2 = Finding(path="a.py", line=9, rule="GL005", severity="warning",
                     message="m1")   # same key, second occurrence
        new, fixed = diff_baseline([f1, f2], {f1.key: 1})
        assert new == [f2]           # one grandfathered, one new
        new, fixed = diff_baseline([], {f1.key: 1})
        assert new == [] and fixed == [f1.key]   # fixed: baseline can shrink
        new, fixed = diff_baseline([f1], {f1.key: 1})
        assert new == [] and fixed == []

    def test_write_baseline_refuses_growth(self, tmp_path):
        """Regenerating the baseline can never silently grandfather a
        regression: new keys are refused unless allow_growth is explicit."""
        from deeplearning4j_tpu.lint import write_baseline
        path = str(tmp_path / "baseline.json")
        old = Finding(path="a.py", line=1, rule="GL007", severity="warning",
                      message="old debt")
        assert write_baseline(path, [old]) == {}         # fresh file: all in
        new = Finding(path="b.py", line=2, rule="GL002", severity="warning",
                      message="new regression")
        refused = write_baseline(path, [old, new])
        assert refused == {new.key: 1}
        assert load_baseline(path) == {old.key: 1}       # regression NOT blessed
        assert write_baseline(path, [old, new], allow_growth=True) == {}
        assert load_baseline(path) == {old.key: 1, new.key: 1}

    def test_write_baseline_subset_paths_refused_by_cli(self, capsys):
        """A subset scan must not clobber the repo-wide baseline."""
        from deeplearning4j_tpu.lint.cli import run
        try:
            run(["deeplearning4j_tpu/nn", "--write-baseline",
                 "--no-consistency"])
        except SystemExit as e:
            assert e.code == 2
        else:
            raise AssertionError("subset --write-baseline must be refused")
        assert "subset" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the gate run: whole repo vs the committed baseline
# ---------------------------------------------------------------------------


class TestRepoIsClean:
    def test_rule_catalog_documented(self):
        """Every registered rule has an entry in docs/LINT.md."""
        from deeplearning4j_tpu.lint.rules_consistency import (
            CONSISTENCY_RULES)
        doc = open(os.path.join(REPO, "docs", "LINT.md")).read()
        for rule_id in set(AST_RULES) | set(CONSISTENCY_RULES):
            assert rule_id in doc, f"{rule_id} missing from docs/LINT.md"

    def test_repo_has_no_new_findings(self):
        """THE suite-time lint: deeplearning4j_tpu/, tools/, examples/
        against lint_baseline.json. A new footgun fails tier-1 here."""
        from deeplearning4j_tpu.lint.rules_consistency import run_consistency
        findings = lint_paths(["deeplearning4j_tpu", "tools", "examples"],
                              REPO)
        findings.extend(run_consistency(REPO))
        baseline = load_baseline(BASELINE)
        new, _fixed = diff_baseline(sorted(findings), baseline)
        assert new == [], "new lint findings:\n" + "\n".join(
            f.render() for f in new)

    def test_baseline_entries_all_still_real(self):
        """The baseline is debt, not decoration: every grandfathered entry
        must still correspond to a live finding (no stale padding)."""
        from deeplearning4j_tpu.lint.rules_consistency import run_consistency
        findings = lint_paths(["deeplearning4j_tpu", "tools", "examples"],
                              REPO)
        findings.extend(run_consistency(REPO))
        baseline = load_baseline(BASELINE)
        _new, fixed = diff_baseline(sorted(findings), baseline)
        assert fixed == [], (
            "baseline entries now fixed — shrink lint_baseline.json via "
            "`make lint-baseline`: " + ", ".join(fixed))

    def test_seeded_violation_fails_the_gate(self, tmp_path):
        """Acceptance criterion: a seeded footgun in a scratch fixture is
        caught as a NEW finding against the committed baseline."""
        bad = tmp_path / "scratch_violation.py"
        bad.write_text(textwrap.dedent("""
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x).sum()
        """))
        findings = lint_source(bad.read_text(),
                               path=str(bad.relative_to(tmp_path)))
        baseline = load_baseline(BASELINE)
        new, _ = diff_baseline(findings, baseline)
        assert any(f.rule == "GL001" for f in new), \
            "seeded GL001 violation must surface as a new finding"

    def test_cli_json_contract(self):
        """tools/graftlint.py --json emits exactly one parsable JSON line
        and exits 0 on the clean repo — the gate/driver artifact contract."""
        proc = subprocess.run(
            [sys.executable, "tools/graftlint.py", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        lines = [l for l in proc.stdout.splitlines() if l.strip()]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["tool"] == "graftlint" and rec["new"] == 0
