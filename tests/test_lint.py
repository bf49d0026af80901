"""repro-lint: per-rule true-positive + suppression fixtures, plus the
whole-repo gate (zero unsuppressed findings on the committed tree).

Fixtures are in-memory SourceFiles whose *module names* are chosen to
land inside each rule's scope (e.g. R1 fixtures claim to be
``repro.control.detector`` so they seed the jit closure).  The linter
itself must import and run without jax — that property is asserted here
too (it replaces the old by-convention "repro.obs is jax-free" check).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import layers
from repro.analysis.engine import (SourceFile, discover_files,
                                   find_repo_root, lint_files,
                                   parse_suppressions, run_lint)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_TARGETS = [os.path.join(REPO, p)
                for p in ("src", "benchmarks", "examples")]


def sf(module: str, text: str, rel: str | None = None) -> SourceFile:
    rel = rel or f"fixtures/{module.replace('.', '_')}.py"
    return SourceFile(rel, rel, module, textwrap.dedent(text))


def rules_hit(files, rule=None):
    report = lint_files(files if isinstance(files, list) else [files])
    found = report.findings if rule is None else [
        f for f in report.findings if f.rule == rule]
    return report, found


# -------------------------------------------------------------- suppressions


def test_parse_suppressions_lines_and_strings():
    text = ('x = 1  # repro-lint: disable=R3\n'
            '# repro-lint: disable=R1,R5 -- why\n'
            'y = 2\n'
            's = "repro-lint: disable=R2"\n'
            '# repro-lint: disable\n')
    sup = parse_suppressions(text)
    assert sup[1] == frozenset({"R3"})
    assert sup[2] == frozenset({"R1", "R5"})
    assert 4 not in sup                      # string literal never counts
    assert sup[5] == "ALL"


# ----------------------------------------------------------------------- R1


R1_BAD = """\
    import time
    import jax

    @jax.jit
    def traced(x):
        t = time.time()
        print(x)
        v = x.item()
        f = float(x)
        x.field = 1
        return x
"""


def test_r1_fires_on_host_calls_in_jit():
    _, found = rules_hit(sf("repro.control.detector", R1_BAD), "R1")
    messages = " | ".join(f.message for f in found)
    assert "host-side call `time.time`" in messages
    assert "`print`" in messages
    assert "`.item()`" in messages
    assert "`float()` cast" in messages
    assert "attribute assignment" in messages
    assert len(found) == 5


def test_r1_covers_scan_bodies_and_call_closure():
    fixture = sf("repro.cluster.state", """\
        import jax
        from jax import lax

        def helper(c):
            print(c)
            return c

        def body(carry, x):
            return helper(carry), x

        def outer(xs):
            return lax.scan(body, 0, xs)
    """)
    _, found = rules_hit(fixture, "R1")
    assert len(found) == 1 and "helper" in found[0].message


def test_r1_silent_on_pure_code_and_out_of_scope_modules():
    pure = sf("repro.control.detector", """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def traced(x):
            return jnp.maximum(x, 0.0)
    """)
    _, found = rules_hit(pure, "R1")
    assert found == []
    # same host calls, but not a jit-root module: out of R1's scope
    _, found = rules_hit(sf("repro.cluster.simulator", R1_BAD), "R1")
    assert found == []


def test_r1_covers_fleet_prefilter_roots():
    """repro.cluster.fleet is a jit-root module: a host call reachable
    from its jit'd top-k prefilter must fire R1."""
    assert "repro.cluster.fleet" in layers.JIT_ROOT_MODULES
    fixture = sf("repro.cluster.fleet", """\
        import time
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("k",))
        def topk(scores, k):
            t = time.time()
            return jax.lax.top_k(scores, k)
    """)
    _, found = rules_hit(fixture, "R1")
    assert len(found) == 1 and "time.time" in found[0].message


def test_r1_covers_replay_stats_roots():
    """repro.cluster.experiment is a jit-root module: a host pull reachable
    from the replay's jit'd statistics program must fire R1."""
    assert "repro.cluster.experiment" in layers.JIT_ROOT_MODULES
    fixture = sf("repro.cluster.experiment", """\
        import jax
        import jax.numpy as jnp

        def _pooled(rt):
            return float(rt.sum())

        @jax.jit
        def _replay_stats(rt):
            return jnp.asarray(_pooled(rt))
    """)
    _, found = rules_hit(fixture, "R1")
    assert len(found) == 1 and "float()" in found[0].message


def test_r1_covers_rollout_tick_roots():
    """repro.kernels.rollout_tick is a jit-root module: a host call inside
    the jitted fused-tick wrapper must fire R1."""
    assert "repro.kernels.rollout_tick" in layers.JIT_ROOT_MODULES
    fixture = sf("repro.kernels.rollout_tick", """\
        import time
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("block",))
        def fused_tick(x, block):
            t = time.time()
            return x * 2.0
    """)
    _, found = rules_hit(fixture, "R1")
    assert len(found) == 1 and "time.time" in found[0].message


def test_r1_suppression():
    text = R1_BAD.replace("t = time.time()",
                          "t = time.time()  # repro-lint: disable=R1")
    report, found = rules_hit(sf("repro.control.detector", text), "R1")
    assert all("time.time" not in f.message for f in found)
    assert any(f.rule == "R1" for f in report.suppressed)


# ----------------------------------------------------------------------- R2


_R2_HEADER = """\
    import dataclasses
    import jax

    @dataclasses.dataclass(frozen=True)
    class Good:
        a: int
        b: int
"""


def _r2_fixture(register: str) -> SourceFile:
    text = textwrap.dedent(_R2_HEADER) + "\n" + textwrap.dedent(register)
    return sf("repro.cluster.state", text)


def test_r2_fires_on_unfrozen_and_mutable_default():
    fixture = sf("repro.cluster.state", """\
        import dataclasses
        import jax

        @dataclasses.dataclass
        class Bad:
            xs: list = dataclasses.field(default_factory=list)
            ys: list = []

        jax.tree_util.register_dataclass(
            Bad, data_fields=["xs", "ys"], meta_fields=[])
    """)
    _, found = rules_hit(fixture, "R2")
    messages = " | ".join(f.message for f in found)
    assert "not `@dataclass(frozen=True)`" in messages
    assert "mutable default" in messages


def test_r2_fires_on_computed_and_incomplete_split():
    computed = _r2_fixture("""\
        jax.tree_util.register_dataclass(
            Good,
            data_fields=[f.name for f in dataclasses.fields(Good)],
            meta_fields=[])
    """)
    _, found = rules_hit(computed, "R2")
    assert len(found) == 1 and "not literal" in found[0].message

    incomplete = _r2_fixture("""\
        jax.tree_util.register_dataclass(
            Good, data_fields=["a"], meta_fields=[])
    """)
    _, found = rules_hit(incomplete, "R2")
    assert len(found) == 1 and "does not cover" in found[0].message
    assert "'b'" in found[0].message

    overlap = _r2_fixture("""\
        jax.tree_util.register_dataclass(
            Good, data_fields=["a", "b"], meta_fields=["b"])
    """)
    _, found = rules_hit(overlap, "R2")
    assert any("both data and meta" in f.message for f in found)


def test_r2_clean_and_suppressed():
    good = _r2_fixture("""\
        jax.tree_util.register_dataclass(
            Good, data_fields=["a", "b"], meta_fields=[])
    """)
    _, found = rules_hit(good, "R2")
    assert found == []

    suppressed = _r2_fixture("""\
        # repro-lint: disable=R2 -- migration shim, split audited by hand
        jax.tree_util.register_dataclass(
            Good, data_fields=["a"], meta_fields=[])
    """)
    report, found = rules_hit(suppressed, "R2")
    assert found == []
    assert any(f.rule == "R2" for f in report.suppressed)


# ----------------------------------------------------------------------- R3


def _r3(body: str) -> SourceFile:
    text = ("from repro.obs import HotspotFlag\n\n"
            + textwrap.dedent(body))
    return sf("repro.control.fixture", text)


def test_r3_fires_without_guard():
    _, found = rules_hit(_r3("""\
        def emit(rec, node):
            rec.emit(HotspotFlag(node=node))
    """), "R3")
    assert len(found) == 1 and "HotspotFlag" in found[0].message


def test_r3_accepts_guard_shapes():
    guarded = _r3("""\
        def a(rec, node):
            if rec:
                rec.emit(HotspotFlag(node=node))

        def b(recorder, node):
            if recorder is not None:
                recorder.emit(HotspotFlag(node=node))

        def c(self, node):
            if not self.recorder:
                return
            self.recorder.emit(HotspotFlag(node=node))

        def d(rec, node, hot):
            if rec and hot:
                rec.emit(HotspotFlag(node=node))
    """)
    _, found = rules_hit(guarded, "R3")
    assert found == []


def test_r3_else_branch_is_not_guarded():
    _, found = rules_hit(_r3("""\
        def emit(rec, node):
            if rec:
                pass
            else:
                rec.emit(HotspotFlag(node=node))
    """), "R3")
    assert len(found) == 1


def test_r3_ignores_obs_package_and_suppression():
    inside_obs = sf("repro.obs.recorder", """\
        from repro.obs.events import HotspotFlag

        def make(node):
            return HotspotFlag(node=node)
    """)
    _, found = rules_hit(inside_obs, "R3")
    assert found == []

    report, found = rules_hit(_r3("""\
        def emit(rec, node):
            rec.emit(HotspotFlag(node=node))  # repro-lint: disable=R3
    """), "R3")
    assert found == []
    assert any(f.rule == "R3" for f in report.suppressed)


def test_r3_event_table_matches_events_module():
    """OBS_EVENT_TYPES must not drift from the classes in events.py."""
    from repro.analysis.rules import Context, discovered_event_types
    files = discover_files([os.path.join(REPO, "src")], REPO)
    discovered = discovered_event_types(Context(files))
    assert discovered, "repro.obs.events not found in src"
    assert set(discovered) == set(layers.OBS_EVENT_TYPES)


# ----------------------------------------------------------------------- R4


def test_r4_direct_and_transitive():
    direct = sf("repro.core.bad", "from repro.control import loop\n")
    _, found = rules_hit(direct, "R4")
    assert len(found) == 1 and "repro.control" in found[0].message

    mid = sf("repro.obs.mid", "from repro.obs import deep\n")
    deep = sf("repro.obs.deep", "import jax\n")
    _, found = rules_hit([mid, deep], "R4")
    # deep is a direct violation; mid violates transitively through deep
    paths = {f.path for f in found}
    assert paths == {mid.rel, deep.rel}
    chain = next(f for f in found if f.path == mid.rel)
    assert "repro.obs.mid -> repro.obs.deep -> jax" in chain.message


def test_r4_allows_carveouts_and_function_level_imports():
    ok = sf("repro.obs.fine", """\
        import numpy as np
        from repro.obs import events

        def lazy():
            import jax  # function-level: the sanctioned idiom
            return jax
    """)
    _, found = rules_hit(ok, "R4")
    assert found == []


def test_r4_fleet_stays_below_control():
    """The fleet-specific row: repro.cluster.fleet must not reach
    repro.control even transitively (the broader repro.cluster row only
    checks direct imports)."""
    direct = sf("repro.cluster.fleet",
                "from repro.control import policy\n")
    _, found = rules_hit(direct, "R4")
    assert found and all("repro.control" in f.message for f in found)

    mid = sf("repro.cluster.fleet", "from repro.cluster import helper\n")
    helper = sf("repro.cluster.helper",
                "from repro.control import actions\n")
    _, found = rules_hit([mid, helper], "R4")
    chain = [f for f in found if f.path == mid.rel]
    assert chain, "transitive fleet -> helper -> control edge must fire"
    assert "repro.cluster.helper" in chain[0].message


def test_r4_kernels_stay_below_control():
    """The kernels row: leaf accelerator code must not reach repro.control,
    even transitively."""
    direct = sf("repro.kernels.rollout_tick",
                "from repro.control import policy\n")
    _, found = rules_hit(direct, "R4")
    assert found and all("repro.control" in f.message for f in found)

    mid = sf("repro.kernels.rollout_tick",
             "from repro.kernels import helper\n")
    helper = sf("repro.kernels.helper",
                "from repro.control import actions\n")
    _, found = rules_hit([mid, helper], "R4")
    chain = [f for f in found if f.path == mid.rel]
    assert chain, "transitive kernels -> helper -> control edge must fire"
    assert "repro.kernels.helper" in chain[0].message


def test_r4_suppression():
    text = "import jax  # repro-lint: disable=R4 -- fixture carve-out\n"
    report, found = rules_hit(sf("repro.obs.bad", text), "R4")
    assert found == []
    assert any(f.rule == "R4" for f in report.suppressed)


# ----------------------------------------------------------------------- R5


def test_r5_fires_on_key_reuse():
    fixture = sf("repro.core.fixture", """\
        import jax

        def draws(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b

        def derive_after_draw(key):
            a = jax.random.normal(key, (3,))
            k2 = jax.random.fold_in(key, 1)
            return a, k2
    """)
    _, found = rules_hit(fixture, "R5")
    assert len(found) == 2
    assert "drawn again" in found[0].message
    assert "passed to `fold_in`" in found[1].message


def test_r5_accepts_split_idiom_and_exclusive_branches():
    fixture = sf("repro.core.fixture", """\
        import jax

        def good(key):
            key, k1 = jax.random.split(key)
            a = jax.random.normal(k1, (3,))
            key, k2 = jax.random.split(key)
            return a + jax.random.uniform(k2, (3,))

        def branches(key, flag):
            if flag:
                u = jax.random.uniform(key, (3,))
            else:
                u = jax.random.normal(key, (3,))
            return u

        def loop(key, n):
            out = 0.0
            for _ in range(n):
                key, k = jax.random.split(key)
                out = out + jax.random.normal(k, ())
            return out
    """)
    _, found = rules_hit(fixture, "R5")
    assert found == []


def test_r5_consumption_survives_a_branch():
    fixture = sf("repro.core.fixture", """\
        import jax

        def bad(key, flag):
            if flag:
                u = jax.random.uniform(key, (3,))
            v = jax.random.normal(key, (3,))
            return v
    """)
    _, found = rules_hit(fixture, "R5")
    assert len(found) == 1 and "drawn again" in found[0].message


def test_r5_suppression():
    fixture = sf("repro.core.fixture", """\
        import jax

        def draws(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))  # repro-lint: disable=R5
            return a + b
    """)
    report, found = rules_hit(fixture, "R5")
    assert found == []
    assert any(f.rule == "R5" for f in report.suppressed)


# ------------------------------------------------------------------- engine


def test_parse_errors_are_reported_and_unsuppressable():
    broken = sf("repro.core.broken",
                "def f(:\n    pass  # repro-lint: disable\n")
    report = lint_files([broken])
    assert len(report.findings) == 1
    assert report.findings[0].rule == "PARSE"


def test_unknown_rule_id_rejected():
    with pytest.raises(ValueError):
        lint_files([sf("repro.core.x", "x = 1\n")], rule_ids=["R9"])


# -------------------------------------------------------------- whole repo


def test_repo_is_lint_clean():
    """The committed tree has zero unsuppressed findings (CI gate)."""
    report = run_lint(LINT_TARGETS, root=REPO)
    assert report.findings == [], "\n".join(
        f.render() for f in report.findings)
    # the suppression census stays visible: the tree documents at least
    # one justified exemption (scheduler._admission_event)
    assert report.suppressed, "expected at least one suppressed finding"


def test_cli_json_and_exit_codes(tmp_path):
    out = tmp_path / "report.json"
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *LINT_TARGETS,
         "--json", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out.read_text())
    assert payload["ok"] is True
    assert payload["num_findings"] == 0
    assert payload["num_suppressed"] >= 1
    assert payload["num_files"] > 50


def test_linter_runs_without_jax():
    """repro.analysis (and repro.obs) import cleanly with jax absent —
    the runtime teeth behind the R4 layering rows."""
    code = ("import sys; sys.modules['jax'] = None\n"
            "import repro.analysis, repro.analysis.rules, repro.obs\n"
            "assert not isinstance(sys.modules.get('numpy'), type(None))\n"
            "import repro.obs.events, repro.obs.recorder\n"
            "print('ok')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


def test_obs_import_does_not_pull_jax():
    """Importing repro.obs must not import jax as a side effect."""
    code = ("import repro.obs, sys\n"
            "assert 'jax' not in sys.modules, 'repro.obs pulled in jax'\n"
            "print('ok')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
