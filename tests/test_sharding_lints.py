"""Sharding & numerics lint suite tests (ISSUE 18): mesh-axes,
dtype-flow and spec-drift, each exercised both ways — seeded-violation
fixtures the pass MUST flag, and known-good idioms (including the
contract allowlists) it must NOT flag.  The self-lint test runs the
three passes over the real tree and must come back empty against the
EMPTY committed baseline: the tree itself is the permanent TN fixture.
"""
import textwrap

import pytest

from paddle_tpu.analysis import base as _base
from paddle_tpu.analysis.allowlist import COMPILE_SURFACES, MESH_AXES
from paddle_tpu.analysis.runner import make_context, run_passes

pytestmark = pytest.mark.lint

SHARDING_PASSES = ["mesh-axes", "dtype-flow", "spec-drift"]


def _lint(tmp_path, code, passes, name="fixture.py"):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return run_passes(paths=[str(tmp_path)], passes=passes)


def _codes(findings):
    return [f.code for f in findings]


class TestMeshAxes:
    def test_flags_undeclared_and_duplicate_axis(self, tmp_path):
        found = _lint(tmp_path, """
            from jax.sharding import PartitionSpec as P

            SPEC_TYPO = P("dta", None)          # undeclared (typo)
            SPEC_DUP = P("data", "data")        # duplicate
            """, passes=["mesh-axes"])
        codes = _codes(found)
        assert "undeclared-axis" in codes
        assert "duplicate-axis" in codes
        assert any(f.detail == "P:dta" for f in found)

    def test_conditional_spec_is_not_a_duplicate(self, tmp_path):
        # the gpt_hybrid idiom: the IfExp *test* also contains the
        # axis literal — value positions alone decide duplication
        found = _lint(tmp_path, """
            from jax.sharding import PartitionSpec as P

            def spec(has):
                return P("data" if "data" in has else None, None)
            """, passes=["mesh-axes"])
        assert found == []

    def test_flags_shard_map_arity_mismatch(self, tmp_path):
        found = _lint(tmp_path, """
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def kernel(x):
                return x

            def build(mesh):
                return shard_map(kernel, mesh,
                                 in_specs=(P("data"), P(None)),
                                 out_specs=P("data"))
            """, passes=["mesh-axes"])
        assert "spec-arity-mismatch" in _codes(found)

    def test_matching_arity_is_clean(self, tmp_path):
        found = _lint(tmp_path, """
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def kernel(x, y):
                return x + y

            def build(mesh):
                return shard_map(kernel, mesh,
                                 in_specs=(P("data"), P(None)),
                                 out_specs=P("data"))
            """, passes=["mesh-axes"])
        assert found == []

    def test_flags_unbound_collective_axis_name(self, tmp_path):
        found = _lint(tmp_path, """
            from jax import lax

            def reduce(x):
                return lax.psum(x, "data")   # nothing binds 'data'
            """, passes=["mesh-axes"])
        assert "unbound-axis-name" in _codes(found)

    def test_shard_map_binding_clears_collective(self, tmp_path):
        found = _lint(tmp_path, """
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from jax import shard_map

            def reduce(x):
                return lax.psum(x, "data")

            def build(mesh):
                return shard_map(reduce, mesh, in_specs=(P("data"),),
                                 out_specs=P(None))
            """, passes=["mesh-axes"])
        assert found == []

    def test_pragma_suppresses(self, tmp_path):
        found = _lint(tmp_path, """
            from jax.sharding import PartitionSpec as P

            SPEC = P("dta", None)  # lint: allow(undeclared-axis)
            """, passes=["mesh-axes"])
        assert found == []


class TestDtypeFlow:
    def test_flags_fp32_upcast_on_jit_surface(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def step(x):
                return x.astype(jnp.float32)
            """, passes=["dtype-flow"])
        assert "fp32-upcast" in _codes(found)

    def test_contract_cast_is_exempt(self, tmp_path):
        # quantize_kv in a module matching the monitored relpath is in
        # FP32_CONTRACT_CASTS: the declared-accumulator exemption
        found = _lint(tmp_path, """
            import jax.numpy as jnp

            def quantize_kv(x):
                xf = x.astype(jnp.float32)
                amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
                scale = jnp.maximum(amax, 1e-30) / 127.0
                q = jnp.clip(jnp.round(xf / scale[..., None, None]),
                             -127.0, 127.0).astype(jnp.int8)
                return q, scale

            def dequantize_kv(q, scale, dtype):
                return (q.astype(jnp.float32)
                        * scale[..., None, None]).astype(dtype)
            """, passes=["dtype-flow"],
            name="paddle_tpu/inference/kvcache.py")
        assert found == []

    def test_flags_untyped_alloc(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def step(n):
                return jnp.zeros((n, 4))
            """, passes=["dtype-flow"])
        assert "untyped-alloc" in _codes(found)

    def test_explicit_dtype_alloc_is_clean(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp
            from paddle_tpu.analysis import jit_surface

            @jit_surface
            def step(n):
                return jnp.zeros((n, 4), jnp.bfloat16)
            """, passes=["dtype-flow"])
        assert found == []

    def test_flags_unpaired_kv_quantize(self, tmp_path):
        found = _lint(tmp_path, """
            def write_cache(cache, x):
                q, scale = quantize_kv(x)   # dequantize_kv: nowhere
                return cache.store(q, scale)
            """, passes=["dtype-flow"])
        assert any(f.code == "unpaired-quantize" and
                   f.detail == "quantize_kv-without-dequantize_kv"
                   for f in found)

    def test_balanced_kv_pair_is_clean(self, tmp_path):
        found = _lint(tmp_path, """
            def roundtrip(cache, x, dtype):
                q, scale = quantize_kv(x)
                return dequantize_kv(q, scale, dtype)
            """, passes=["dtype-flow"])
        assert found == []

    def test_flags_unscaled_narrow_cast(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp

            def narrow(x):
                return jnp.round(x).astype(jnp.int8)
            """, passes=["dtype-flow"])
        assert "unscaled-narrow-cast" in _codes(found)

    def test_scaled_narrow_cast_is_clean(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp

            def narrow(x):
                amax = jnp.max(jnp.abs(x))
                scale = jnp.maximum(amax, 1e-30) / 127.0
                return jnp.round(x / scale).astype(jnp.int8), scale
            """, passes=["dtype-flow"])
        assert found == []

    def test_flags_equarx_narrow_without_dequant(self, tmp_path):
        found = _lint(tmp_path, """
            def reduce(x, scale):
                q = _to_narrow(x / scale, "int8")
                return all_to_all_wire(q)   # never widened back
            """, passes=["dtype-flow"])
        assert any(f.code == "unpaired-quantize" and
                   f.detail == "narrow-without-dequant" for f in found)

    def test_equarx_with_fp32_dequant_is_clean(self, tmp_path):
        found = _lint(tmp_path, """
            import jax.numpy as jnp

            def reduce(x, scale):
                q = _to_narrow(x / scale, "int8")
                return q.astype(jnp.float32) * scale
            """, passes=["dtype-flow"])
        assert found == []


class TestSpecDrift:
    def test_flags_undeclared_mesh_construction_axis(self, tmp_path):
        found = _lint(tmp_path, """
            from jax.sharding import Mesh

            def build(devs):
                return Mesh(devs, ("data", "oops"))
            """, passes=["spec-drift"])
        assert any(f.code == "mesh-axis-undeclared" and
                   f.detail == "oops" for f in found)

    def test_declared_mesh_construction_is_clean(self, tmp_path):
        found = _lint(tmp_path, """
            from jax.sharding import Mesh

            def build(devs):
                return Mesh(devs, ("data", "model"))
            """, passes=["spec-drift"])
        assert found == []

    def test_flags_stale_doc_ref(self, tmp_path):
        (tmp_path / "DISTRIBUTED.md").write_text(
            "see `paddle_tpu/no_such_module.py` for details\n")
        found = run_passes(paths=[str(tmp_path)], passes=["spec-drift"])
        assert any(f.code == "stale-doc-ref" and
                   f.detail == "paddle_tpu/no_such_module.py"
                   for f in found)

    def test_live_doc_ref_is_clean(self, tmp_path):
        (tmp_path / "paddle_tpu").mkdir()
        (tmp_path / "paddle_tpu" / "real.py").write_text("X = 1\n")
        (tmp_path / "DISTRIBUTED.md").write_text(
            "see `paddle_tpu/real.py` for details\n")
        found = run_passes(paths=[str(tmp_path)], passes=["spec-drift"])
        assert found == []

    def test_flags_drifted_grad_comm_doc_row(self, tmp_path):
        # the ISSUE-named fixture: a documented config key the real
        # GradCommConfig does not take, plus an undocumented parameter
        (tmp_path / "grad_comm.py").write_text(textwrap.dedent("""
            _QUANT_MODES = (None, "bf16", "int8")

            class GradCommConfig:
                def __init__(self, enabled, bucket_mb, quantize):
                    self.enabled = enabled
        """))
        (tmp_path / "DISTRIBUTED.md").write_text(textwrap.dedent("""
            ## Communication-efficient gradient reduction

            ```python
            grad_comm_configs = {
                "bucket_bm": 25,
                "quantize": "int8",
            }
            ```

            Wire modes: `"bf16"`, `"int8"`, `"fp8"`.
        """))
        found = run_passes(paths=[str(tmp_path)], passes=["spec-drift"])
        details = {(f.code, f.detail) for f in found}
        assert ("grad-comm-drift", "bucket_bm") in details   # typo'd key
        assert ("grad-comm-drift", "bucket_mb") in details   # missing row
        assert ("wire-mode-drift", "fp8") in details         # not accepted

    def test_matching_grad_comm_doc_is_clean(self, tmp_path):
        (tmp_path / "grad_comm.py").write_text(textwrap.dedent("""
            _QUANT_MODES = (None, "bf16", "int8")

            class GradCommConfig:
                def __init__(self, enabled, bucket_mb, quantize):
                    self.enabled = enabled
        """))
        (tmp_path / "DISTRIBUTED.md").write_text(textwrap.dedent("""
            ## Communication-efficient gradient reduction

            ```python
            grad_comm_configs = {
                "bucket_mb": 25,
                "quantize": "int8",
            }
            ```

            Wire modes: `"bf16"`, `"int8"`.
        """))
        found = run_passes(paths=[str(tmp_path)], passes=["spec-drift"])
        assert found == []

    def test_default_tree_flags_unused_axes_and_surface_drift(
            self, tmp_path):
        # fabricate a minimal default tree: only 'data' is used and no
        # wrap literal carries the declared surfaces — the aggregate
        # directions that only make sense on a full sweep
        pkg = tmp_path / "paddle_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text(textwrap.dedent("""
            from jax.sharding import PartitionSpec as P
            from paddle_tpu.observability import compilestats

            SPEC = P("data")
            STEP_SURFACE = "fixture.step"

            def step(x):
                return compilestats.wrap("fixture.other", lambda: x)()
        """))
        (tmp_path / "tests").mkdir()
        (tmp_path / "docs").mkdir()
        ctx = make_context(root=str(tmp_path))
        assert ctx.default_tree
        found = run_passes(ctx=ctx, passes=["spec-drift"])
        details = {(f.code, f.detail) for f in found}
        for ax in MESH_AXES:
            if ax != "data":
                assert ("mesh-axis-unused", ax) in details
        assert ("mesh-axis-unused", "data") not in details
        # wrapped-but-undeclared and declared-but-unwrapped directions
        assert ("surface-drift", "fixture.other") in details
        assert ("surface-drift", "fixture.step") in details
        for label in COMPILE_SURFACES:
            assert ("surface-drift", label) in details

    def test_scoped_run_skips_aggregate_directions(self, tmp_path):
        # a partial run must not report absence-of-usage: vocabulary
        # completeness is only meaningful over the whole tree
        found = _lint(tmp_path, """
            from jax.sharding import PartitionSpec as P

            SPEC = P("data")
            """, passes=["spec-drift"])
        assert found == []


class TestSweepInfrastructure:
    def test_timings_and_module_cache(self, tmp_path):
        (tmp_path / "fixture.py").write_text("X = 1\n")
        timings = {}
        run_passes(paths=[str(tmp_path)], passes=SHARDING_PASSES,
                   timings=timings)
        assert set(timings) == set(SHARDING_PASSES) | {"total"}
        assert all(t >= 0 for t in timings.values())
        # second run over the unchanged tree reuses the parsed module
        key = (str(tmp_path / "fixture.py"), "fixture.py")
        cached = _base._MODULE_CACHE.get(key)
        assert cached is not None
        _, info = cached
        run_passes(paths=[str(tmp_path)], passes=["mesh-axes"])
        assert _base._MODULE_CACHE[key][1] is info

    def test_self_lint_sharding_passes_clean(self):
        # the committed baseline is EMPTY: the real tree must satisfy
        # the three new passes outright (declared contracts included)
        found = run_passes(passes=SHARDING_PASSES)
        assert _codes(found) == []
