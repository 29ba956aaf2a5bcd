"""Static-analysis suite tests (paddle_tpu/analysis/).

Each pass is exercised both ways: seeded-violation fixtures it MUST
flag, and known-good idioms it must NOT flag (the false-positive
exemptions — `is None`, membership tests, `.shape` metadata,
`jax.process_count()` — are contracts too).  The self-lint test runs
the whole suite over the real tree and must come back clean modulo the
committed baseline — that's the machine-checked version of PR 2's
one-sync-per-step comment.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis import registered_surfaces
from paddle_tpu.analysis.runner import (run_passes, make_context,
                                        load_baseline, write_baseline,
                                        split_new, REPO_ROOT,
                                        DEFAULT_BASELINE)

pytestmark = pytest.mark.lint

AST_PASSES = ["tracer-safety", "host-sync", "collective-order"]


def _lint(tmp_path, code, passes=AST_PASSES, name="fixture.py"):
    (tmp_path / name).write_text(textwrap.dedent(code))
    return run_passes(paths=[str(tmp_path)], passes=passes)


def _codes(findings):
    return [f.code for f in findings]


class TestTracerSafety:
    def test_flags_every_seeded_violation(self, tmp_path):
        found = _lint(tmp_path, """
            import numpy as np
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def bad_step(grads, lr):
                total = jnp.sum(grads)
                if total > 0:
                    lr = lr * 0.5
                while total > 1:
                    total = total - 1
                f = float(total)
                h = np.asarray(total)
                i = total.item()
                return f, h, i, len(grads)
            """, passes=["tracer-safety"])
        codes = _codes(found)
        assert codes.count("control-flow-on-traced") == 2  # if + while
        assert "cast-on-traced" in codes
        assert "numpy-on-traced" in codes
        assert "host-readback" in codes
        assert "len-on-traced" in codes

    def test_reaches_helpers_and_nested_defs(self, tmp_path):
        found = _lint(tmp_path, """
            import jax
            from paddle_tpu.analysis import jit_surface

            def helper(x):
                return x.item()            # reached via surface call

            @jit_surface
            def build():
                def step(xs):              # nested def = traced body
                    if xs:
                        return helper(xs)
                    return xs
                return jax.jit(step)

            def unreachable(x):
                return x.item()            # never flagged: not reachable
            """, passes=["tracer-safety"])
        quals = {(f.qualname, f.code) for f in found}
        assert ("helper", "host-readback") in quals
        assert ("build.step", "control-flow-on-traced") in quals
        assert not any(q.startswith("unreachable") for q, _ in quals)

    def test_known_good_idioms_stay_quiet(self, tmp_path):
        # amp/cache are closure config of the builder (the real stepper
        # shape); xs are the traced values
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def build(amp, cache):
                def good_step(xs):
                    out = []
                    for i, x in enumerate(xs):
                        if x is None:                    # identity: static
                            continue
                        if i in cache:                   # membership: keys
                            continue
                        if amp in ("O1", "O2") and \\
                                jnp.issubdtype(x.dtype, jnp.floating):
                            x = x.astype(jnp.bfloat16)   # metadata: static
                        n = x.shape[0]                   # shape: static
                        out.append(jnp.where(x > 0, x, n))
                    k = float(3.5)                       # host literal
                    return out, k
                return good_step
            """, passes=["tracer-safety"])
        assert found == []

    def test_ifexp_and_assert_on_traced_flagged(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def step(x, eos):
                a = 1 if x > 0 else 0            # traced: flag
                assert x > 0                     # traced: flag
                b = jnp.zeros(3) if eos is None else x   # static: quiet
                return a, b
            """, passes=["tracer-safety"])
        kinds = sorted(f.detail.split(":")[0] for f in found)
        assert kinds == ["assert", "if-expression"], found

    def test_membership_traced_array_vs_container_keys(self, tmp_path):
        # `k in dict_of_traced` probes static keys (quiet); `k in xs`
        # on a traced array calls the tracer's __contains__ (flagged)
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def step(xs, idx):
                table = dict(zip(idx, xs))
                hit = 0
                if 3 in table:
                    hit = 1
                if 3 in xs:
                    hit = 2
                return hit
            """, passes=["tracer-safety"])
        assert _codes(found) == ["control-flow-on-traced"]
        assert "3 in xs" in found[0].message

    def test_pragma_suppresses(self, tmp_path):
        found = _lint(tmp_path, """
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def step(xs):
                return len(xs)  # lint: allow(len-on-traced)
            """, passes=["tracer-safety"])
        assert found == []


class TestHostSync:
    def test_sync_inside_jit_surface_always_flagged(self, tmp_path):
        found = _lint(tmp_path, """
            import numpy as np
            from paddle_tpu.analysis import jit_surface
            from paddle_tpu.framework.guardian import _host_bool

            @jit_surface
            def step(flag):
                return _host_bool(flag), np.asarray(flag), flag.item()
            """, passes=["host-sync"])
        assert _codes(found) == ["sync-in-jit-surface"] * 3

    def test_monitored_module_budget(self, tmp_path):
        # a file at the monitored relpath is held to the allowlist:
        # grads_ok's budget is 1 `_host_bool`; a second one must fail,
        # and an un-allowlisted function gets no budget at all
        mod = tmp_path / "paddle_tpu" / "framework"
        mod.mkdir(parents=True)
        (mod / "guardian.py").write_text(textwrap.dedent("""
            def _host_bool(x):
                return bool(x)

            class NumericSentinel:
                def grads_ok(self, named, step):
                    ok = _host_bool(named)        # within budget
                    ok2 = _host_bool(named)       # budget exceeded
                    return ok and ok2

            def sneaky_new_path(flag):
                return _host_bool(flag)           # unbudgeted
            """))
        found = run_passes(paths=[str(tmp_path)], passes=["host-sync"])
        by_qual = {f.qualname: f.code for f in found}
        assert by_qual == {
            "NumericSentinel.grads_ok": "unbudgeted-host-sync",
            "sneaky_new_path": "unbudgeted-host-sync"}

    def test_pragma_does_not_consume_budget_slot(self, tmp_path):
        # a pragma'd new site must be exempt BEFORE budgeting, so the
        # pre-existing allowlisted site keeps its slot and the run
        # stays green (the remediation the error message suggests)
        mod = tmp_path / "paddle_tpu" / "framework"
        mod.mkdir(parents=True)
        (mod / "guardian.py").write_text(textwrap.dedent("""
            def _host_bool(x):
                return bool(x)

            class NumericSentinel:
                def grads_ok(self, named, step):
                    dbg = _host_bool(named)  # lint: allow(host-sync)
                    return _host_bool(named)     # the budgeted site
            """))
        found = run_passes(paths=[str(tmp_path)], passes=["host-sync"])
        assert found == [], [repr(f) for f in found]

    def test_extra_nested_surfaces_are_monitored(self, tmp_path):
        # EXTRA_JIT_SURFACES (decorator-unreachable nested defs) must be
        # held to the same no-sync rule — a suffix-matching fixture
        # stands in for paddle_tpu/models/generation.py
        mod = tmp_path / "paddle_tpu" / "models"
        mod.mkdir(parents=True)
        (mod / "generation.py").write_text(textwrap.dedent("""
            def generate(model, ids):
                def run(pv, prompt, key):
                    prompt.block_until_ready()     # sync in jit surface
                    return pv, prompt.item()
                return run
            """))
        found = run_passes(paths=[str(tmp_path)],
                           passes=["host-sync", "tracer-safety"])
        got = {(f.pass_name, f.code) for f in found}
        assert ("host-sync", "sync-in-jit-surface") in got
        assert ("tracer-safety", "host-readback") in got

    def test_renamed_extra_surface_is_a_finding(self, tmp_path):
        # a renamed nested def must not silently drop lint coverage:
        # a file matching an EXTRA_JIT_SURFACES relpath without the
        # registered qualname is itself flagged
        mod = tmp_path / "paddle_tpu" / "models"
        mod.mkdir(parents=True)
        (mod / "generation.py").write_text(textwrap.dedent("""
            def generate(model, ids):
                def sample_run(pv):        # renamed from `run`
                    return pv
                return sample_run
            """))
        found = run_passes(paths=[str(tmp_path)], passes=["tracer-safety"])
        assert {f.code for f in found} == {"unresolved-surface"}
        assert "generate.run" in {f.detail for f in found}

    def test_explicit_repo_paths_keep_policy_relpaths(self):
        # running over a subdirectory of the repo must not re-root
        # relpaths (which would silently disable monitored-module
        # matching, EXTRA surfaces, and baseline keys)
        ctx = make_context(paths=[os.path.join(REPO_ROOT, "paddle_tpu")])
        assert ctx.root == REPO_ROOT
        assert "paddle_tpu/framework/guardian.py" in ctx.index.by_relpath

    def test_real_hot_paths_fit_their_budgets(self):
        found = run_passes(passes=["host-sync"])
        baseline = load_baseline(os.path.join(REPO_ROOT, DEFAULT_BASELINE))
        new, _ = split_new(found, baseline)
        assert new == [], [repr(f) for f in new]


class TestCollectiveOrder:
    def test_rank_conditional_collective_flagged(self, tmp_path):
        found = _lint(tmp_path, """
            from paddle_tpu.distributed.collective import barrier

            def save(rank):
                if rank == 0:
                    barrier()

            def save2():
                from paddle_tpu.distributed import get_rank
                if get_rank() == 0:
                    barrier()
            """, passes=["collective-order"])
        assert _codes(found) == ["rank-conditional-collective"] * 2

    def test_divergent_order_flagged_same_order_not(self, tmp_path):
        found = _lint(tmp_path, """
            from paddle_tpu.distributed.collective import (barrier,
                                                           all_reduce)

            def bad(flag, x):
                if flag:
                    all_reduce(x)
                    barrier()
                else:
                    barrier()
                    all_reduce(x)

            def fine(flag, x):
                if flag:
                    all_reduce(x)
                    barrier()
                else:
                    all_reduce(x)
                    barrier()
            """, passes=["collective-order"])
        assert len(found) == 1
        assert found[0].code == "divergent-collective-order"
        assert found[0].qualname == "bad"

    def test_divergent_neutral_elif_chain_flagged_once(self, tmp_path):
        found = _lint(tmp_path, """
            from paddle_tpu.distributed.collective import (barrier,
                                                           all_reduce)

            def bad(mode, x):
                if mode == "a":
                    all_reduce(x)
                    barrier()
                elif mode == "b":
                    barrier()
                    all_reduce(x)
            """, passes=["collective-order"])
        assert _codes(found) == ["divergent-collective-order"]

    def test_nested_rank_branches_report_call_once(self, tmp_path):
        found = _lint(tmp_path, """
            from paddle_tpu.distributed.collective import barrier

            def bad(rank, local_rank):
                if rank == 0:
                    if local_rank == 0:
                        barrier()
            """, passes=["collective-order"])
        assert _codes(found) == ["rank-conditional-collective"]

    def test_elif_arms_report_once_with_their_own_condition(self,
                                                            tmp_path):
        found = _lint(tmp_path, """
            from paddle_tpu.distributed.collective import (barrier,
                                                           all_reduce)

            def chain(rank, x):
                if rank == 0:
                    barrier()
                elif rank == 1:
                    all_reduce(x)
            """, passes=["collective-order"])
        # one finding per call site, each under ITS arm's condition
        assert len(found) == 2
        by_detail = {f.detail: f for f in found}
        assert "barrier:rank == 0" in by_detail
        assert "all_reduce:rank == 1" in by_detail

    def test_uniform_conditions_stay_quiet(self, tmp_path):
        found = _lint(tmp_path, """
            import jax
            from paddle_tpu.distributed.collective import barrier

            def sync():
                if jax.process_count() > 1:      # uniform across ranks
                    barrier()
            """, passes=["collective-order"])
        assert found == []

    def test_data_dependent_collective_flagged(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.distributed.collective import all_reduce

            def maybe(x):
                if jnp.any(x > 0):
                    all_reduce(x)
            """, passes=["collective-order"])
        assert _codes(found) == ["data-conditional-collective"]


class TestRegistryLints:
    # the orphan names are assembled at runtime: this test FILE is
    # itself scanned by the registry lints, and a literal orphan here
    # would (correctly!) fail the self-lint
    ORPHAN_FP = "store." + "no_such_site"
    ORPHAN_EVENT = "bogus" + "_event"

    def test_orphan_failpoint_flagged_registered_not(self, tmp_path):
        # built by concatenation so THIS file contains neither a
        # spec-shaped orphan literal nor a scannable set_failpoint call
        fixture = (
            'set_' + f'failpoint("guardian.poison_batch", "skip")\n'
            'set_' + f'failpoint("{self.ORPHAN_FP}", "raise")\n')
        (tmp_path / "t.py").write_text(fixture)
        found = run_passes(paths=[str(tmp_path)],
                           passes=["failpoint-refs"])
        assert [(f.code, f.detail) for f in found] == \
            [("orphan-failpoint", self.ORPHAN_FP)]

    def test_unknown_guardian_event_flagged(self, tmp_path):
        (tmp_path / "t.py").write_text(textwrap.dedent("""
            events("rollback")          # real event
            events("ORPHAN")            # drifted
            """).replace("ORPHAN", self.ORPHAN_EVENT))
        found = run_passes(paths=[str(tmp_path)], passes=["guardian-log"])
        assert [(f.code, f.detail) for f in found] == \
            [("unknown-guardian-event", self.ORPHAN_EVENT)]


    def test_doc_table_checked_on_explicit_docs_run(self, monkeypatch):
        # an explicit `docs/` run must still check the schema table —
        # simulate drift by adding an (undocumented) event to the
        # emitter schema
        from paddle_tpu.framework.guardian import EVENT_SCHEMA
        monkeypatch.setitem(EVENT_SCHEMA, "zz_drifted", {"step"})
        found = run_passes(paths=[os.path.join(REPO_ROOT, "docs")],
                           passes=["guardian-log"])
        assert [(f.code, f.detail) for f in found] == \
            [("schema-drift", "zz_drifted")]


class TestRunnerAndBaseline:
    def test_self_lint_clean_modulo_baseline(self):
        """THE gate: all passes over the real tree, no new findings."""
        findings = run_passes()
        baseline = load_baseline(os.path.join(REPO_ROOT, DEFAULT_BASELINE))
        new, _ = split_new(findings, baseline)
        assert new == [], "new lint findings:\n" + \
            "\n".join(repr(f) for f in new)

    def test_deterministic_ordering(self, tmp_path):
        code = """
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def s(a, b):
                x = a.item()
                y = b.item()
                return x, y, float(a)
            """
        keys1 = [f.key() for f in _lint(tmp_path, code)]
        keys2 = [f.key() for f in _lint(tmp_path, code)]
        # 2x host-readback + 1x cast (tracer) + 2x sync-in-jit-surface
        assert keys1 == keys2 and len(keys1) == 5

    def test_baseline_roundtrip_suppresses_old_not_new(self, tmp_path):
        (tmp_path / "f.py").write_text(textwrap.dedent("""
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def s(a):
                return a.item()
            """))
        found = run_passes(paths=[str(tmp_path)], passes=AST_PASSES)
        assert len(found) == 2      # tracer host-readback + host-sync
        bl_path = tmp_path / "baseline.json"
        write_baseline(str(bl_path), found)
        baseline = load_baseline(str(bl_path))
        new, old = split_new(
            run_passes(paths=[str(tmp_path)], passes=AST_PASSES), baseline)
        assert new == [] and len(old) == 2
        # a NEW violation in the same file is not absorbed by the key
        # of the old one
        (tmp_path / "f.py").write_text(textwrap.dedent("""
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def s(a):
                return a.item(), float(a)
            """))
        new, old = split_new(
            run_passes(paths=[str(tmp_path)], passes=AST_PASSES), baseline)
        assert [f.code for f in new] == ["cast-on-traced"]
        assert len(old) == 2

    def test_cli_full_tree_exits_zero(self):
        """Acceptance: `python -m paddle_tpu.analysis` runs all passes
        over the tree against the committed baseline and exits 0."""
        env = dict(os.environ, PYTHONPATH=REPO_ROOT,
                   JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis"],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK: no new findings" in r.stdout

    def test_cli_seeded_violation_exits_one_and_json(self, tmp_path):
        (tmp_path / "bad.py").write_text(textwrap.dedent("""
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def s(a):
                if a > 0:
                    return a.item()
            """))
        env = dict(os.environ, PYTHONPATH=REPO_ROOT,
                   JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", str(tmp_path),
             "--no-baseline", "--json"],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=120)
        assert r.returncode == 1, r.stdout + r.stderr
        out = json.loads(r.stdout)
        assert out["new"] == out["total"] >= 3
        codes = {f["code"] for f in out["findings"]}
        assert {"control-flow-on-traced", "host-readback",
                "sync-in-jit-surface"} <= codes


class TestPathValidation:
    def test_nonexistent_path_is_an_error_not_a_green_run(self):
        from paddle_tpu.analysis import main as cli_main
        assert cli_main(["definitely/not/a/path.py"]) == 2

    def test_empty_match_is_an_error(self, tmp_path):
        d = tmp_path / "empty"
        d.mkdir()
        with pytest.raises(ValueError, match="no .py"):
            make_context(paths=[str(d)])

    def test_update_baseline_rejects_partial_scopes(self):
        # neither a path subset nor a pass subset may overwrite the
        # shared baseline — it would erase findings outside its scope
        from paddle_tpu.analysis import main as cli_main
        assert cli_main(["paddle_tpu/framework", "--update-baseline"]) == 2
        assert cli_main(["--passes", "host-sync",
                         "--update-baseline"]) == 2


class TestSurfaceRegistry:
    def test_runtime_registry_matches_annotations(self):
        import paddle_tpu.hapi.model          # noqa: F401
        import paddle_tpu.optimizer.optimizer  # noqa: F401
        import paddle_tpu.framework.guardian   # noqa: F401
        import paddle_tpu.models.generation    # noqa: F401
        regs = set(registered_surfaces())
        expect = {
            ("paddle_tpu.hapi.model", "_CompiledStepper._build_train"),
            ("paddle_tpu.hapi.model", "_CompiledStepper._build_grad"),
            ("paddle_tpu.hapi.model", "_CompiledStepper._build_apply"),
            ("paddle_tpu.hapi.model", "_CompiledStepper._build_eval"),
            ("paddle_tpu.optimizer.optimizer",
             "apply_functional_with_clip"),
            ("paddle_tpu.optimizer.optimizer",
             "Optimizer.apply_functional"),
            ("paddle_tpu.framework.guardian", "tree_all_finite"),
            ("paddle_tpu.models.generation", "generate.run"),
            ("paddle_tpu.models.generation", "generate.beam_run"),
        }
        assert expect <= regs, expect - regs

    def test_runtime_registry_mirrored_in_ast_sources(self):
        """Drift guard: every runtime-registered surface must be visible
        to the AST passes — either decorated in source, or (nested defs)
        mirrored in EXTRA_JIT_SURFACES.  A register_jit_surface() call
        without its mirror would silently drop the surface from
        analysis."""
        import paddle_tpu.hapi.model          # noqa: F401
        import paddle_tpu.optimizer.optimizer  # noqa: F401
        import paddle_tpu.framework.guardian   # noqa: F401
        import paddle_tpu.models.generation    # noqa: F401
        from paddle_tpu.analysis.allowlist import EXTRA_JIT_SURFACES
        extra = set(EXTRA_JIT_SURFACES)
        ctx = make_context()
        for module, qual in registered_surfaces():
            rel = module.replace(".", "/")
            mod = ctx.index.by_relpath.get(rel + ".py") or \
                ctx.index.by_relpath.get(rel + "/__init__.py")
            assert mod is not None, module
            fi = mod.funcs.get(qual)
            assert fi is not None, (module, qual)
            if not fi.is_surface:
                assert (mod.relpath, qual) in extra, (
                    f"{module}:{qual} is register_jit_surface()'d but "
                    "not mirrored in EXTRA_JIT_SURFACES — the AST "
                    "passes will never analyze it")

    def test_extra_surfaces_resolve_in_ast(self):
        """EXTRA_JIT_SURFACES entries must name functions that actually
        exist — a renamed nested def must not silently un-register."""
        from paddle_tpu.analysis.allowlist import EXTRA_JIT_SURFACES
        ctx = make_context()
        for rel, qual in EXTRA_JIT_SURFACES:
            mod = ctx.index.by_relpath.get(rel)
            assert mod is not None, rel
            assert qual in mod.funcs, (rel, qual)

    def test_allowlist_entries_point_at_real_functions(self):
        from paddle_tpu.analysis.allowlist import HOST_SYNC_ALLOWLIST
        ctx = make_context()
        for rel, qual, _callee in HOST_SYNC_ALLOWLIST:
            mod = ctx.index.by_relpath.get(rel)
            assert mod is not None, rel
            assert qual in mod.funcs, (rel, qual)
