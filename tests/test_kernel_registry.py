"""Kernel registry + fused-kernel dispatch (ISSUE 15).

Covers the `ops/registry.py` policy layer (platform selection, env /
``sdp_kernel`` overrides, interpret mode, the block-size rule and the
environment variables the kernels may read), the attention dispatch
ladder (padding so
S need not be a multiple of 512, the key-bias mask path, constraint
fallbacks), compilestats tracking of standalone kernel dispatches, and
the dense-vs-flash TRAIN-STEP gradient parity suite (GPT causal /
LLaMA rope+GQA / BERT additive-mask) in interpret mode.

Tolerance contract (docs/kernels.md "Numerics"): fp32 interpret-mode
flash vs the XLA dense path — forward within atol/rtol 2e-3, gradients
within 5e-3 relative-max; the XLA fallback paths are the dense math
itself and therefore bitwise.
"""
import ast
import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import registry as kreg


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    for var in ("PADDLE_TPU_KERNEL_INTERPRET",
                "PADDLE_TPU_KERNEL_ATTENTION", "PADDLE_TPU_KERNEL_XENT"):
        monkeypatch.delenv(var, raising=False)
    kreg._reset_for_tests()
    yield
    kreg._reset_for_tests()


def _dense_sdpa(q, k, v, mask=None, causal=False):
    from paddle_tpu.nn.functional.attention import _xla_attention
    return _xla_attention(q, k, v, mask=mask, causal=causal)


# ---------------------------------------------------------------------------
# selection policy
# ---------------------------------------------------------------------------

class TestChoose:
    def test_cpu_defaults_to_xla(self):
        sel = kreg.choose("attention")
        assert sel.impl == "xla" and not sel.forced and not sel.interpret

    def test_interpret_mode_selects_pallas(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        sel = kreg.choose("attention")
        assert sel.impl == "pallas" and sel.interpret and not sel.forced

    def test_generic_kernel_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "xla")
        sel = kreg.choose("attention")
        assert sel.impl == "xla" and sel.forced
        monkeypatch.setenv("PADDLE_TPU_KERNEL_XENT", "xla")
        assert kreg.choose("xent").impl == "xla"

    def test_force_context_nests_and_restores(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        with kreg.force("attention", "xla"):
            assert kreg.choose("attention").impl == "xla"
            with kreg.force("attention", "pallas"):
                assert kreg.choose("attention").impl == "pallas"
            assert kreg.choose("attention").impl == "xla"
        assert kreg.choose("attention").impl == "pallas"  # interpret dflt

    def test_typo_forced_impl_raises(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "no_such_impl")
        with pytest.raises(ValueError, match="no impl 'no_such_impl'"):
            kreg.choose("attention")

    def test_unknown_kernel_raises(self):
        with pytest.raises(KeyError):
            kreg.choose("no_such_kernel")

    def test_tpu_platform_selects_pallas_compiled(self):
        sel = kreg.choose("attention", platform="tpu")
        assert sel.impl == "pallas" and not sel.interpret

    def test_sdp_kernel_context_forces(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        with F.sdp_kernel(enable_flash=False):
            assert kreg.choose("attention").impl == "xla"
        with F.sdp_kernel(enable_math=False):
            assert kreg.choose("attention").impl == "pallas"
        assert not kreg.choose("attention").forced

    def test_selects_counter_books(self):
        reg = paddle.observability.get_registry()
        before = reg.get("pt_kernel_selects_total")
        base = before.value(kernel="attention", impl="xla") if before else 0
        kreg.choose("attention")
        m = reg.get("pt_kernel_selects_total")
        assert m.value(kernel="attention", impl="xla") == base + 1


# ---------------------------------------------------------------------------
# flash block sizes, and the knobs a kernel may read
# ---------------------------------------------------------------------------

_KERNEL_ENV_PREFIX = "PADDLE_TPU_KERNEL_"
_OPS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "ops")


def _module_strings(tree):
    """Module-level ``NAME = "literal"`` assignments."""
    return {t.id: node.value.value for node in tree.body
            if isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            for t in node.targets if isinstance(t, ast.Name)}


def _env_reads(tree):
    """(lineno, key expression or None) of every use of ``os.environ`` /
    ``os.getenv`` in ``tree``; None = a use that is no keyed read."""
    parent = {c: n for n in ast.walk(tree) for c in ast.iter_child_nodes(n)}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "os" and any(
                a.name in ("environ", "getenv") for a in node.names):
            yield node.lineno, None
        if not (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "os"
                and node.attr in ("environ", "getenv")):
            continue
        up = parent[node]
        key = None
        if node.attr == "getenv":
            if isinstance(up, ast.Call) and up.func is node and up.args:
                key = up.args[0]
        elif isinstance(up, ast.Subscript) and up.value is node:
            key = up.slice
        elif isinstance(up, ast.Attribute) and up.attr == "get":
            call = parent[up]
            if isinstance(call, ast.Call) and call.func is up and call.args:
                key = call.args[0]
        yield node.lineno, key


def _allowed_env_key(key, strings):
    name = strings.get(key.id) if isinstance(key, ast.Name) \
        else getattr(key, "value", None)
    if name == _KERNEL_ENV_PREFIX + "INTERPRET":
        return True
    # the per-kernel override: f"PADDLE_TPU_KERNEL_{kernel.upper()}"
    return (isinstance(key, ast.JoinedStr) and len(key.values) == 2
            and isinstance(key.values[0], ast.Constant)
            and key.values[0].value == _KERNEL_ENV_PREFIX
            and isinstance(key.values[1], ast.FormattedValue))


class TestFlashBlocks:
    @pytest.mark.parametrize("S, D, expected", [
        (4096, 64, (512, 512)),      # S >= 4096 and S % 512 == 0
        (2048, 64, (256, 256)),      # the fit cell
        (1024, 64, (256, 256)),
        (2560, 96, (256, 256)),
        (2048, 128, (256, 256)),     # the chat cell's prefill head size
        # the must-divide-S contract covers the last resort too: direct
        # callers (incubate flash_attention gates on S % 128 == 0 only)
        # can present S = 640, and a non-dividing answer makes the
        # kernel silently skip the key tail
        (640, 64, (128, 128)),
        (384, 64, (128, 128)),
        (1152, 64, (128, 128)),
        (100, 64, (100, 100)),       # unaligned: one whole-sequence block
    ])
    def test_rule_pairs_divide_s(self, S, D, expected):
        for heads in (None, 16):
            assert kreg.flash_blocks(S, D, heads) == expected
        assert S % expected[0] == 0 and S % expected[1] == 0

    def test_kernels_read_no_environment_but_the_registry_knobs(self):
        """A kernel's implementation and block sizes follow from platform
        and shape.  ``paddle_tpu/ops`` reads PADDLE_TPU_KERNEL_INTERPRET
        and the PADDLE_TPU_KERNEL_<K> override, nothing else: the next
        knob is a diff to this test."""
        files = glob.glob(os.path.join(_OPS_DIR, "**", "*.py"),
                          recursive=True)
        assert files
        bad, seen = [], 0
        for path in sorted(files):
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            strings = _module_strings(tree)
            for lineno, key in _env_reads(tree):
                seen += 1
                if key is None or not _allowed_env_key(key, strings):
                    bad.append(f"{os.path.relpath(path, _OPS_DIR)}:{lineno}")
        assert not bad, bad
        assert seen >= 2          # the walk still finds the two it allows

    def test_s640_kernel_matches_dense(self):
        # the S=640 shape that used to get (512,512): rows 512+ were
        # never written.  interpret mode, vs the dense reference
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_fwd)
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(1, 640, 2, 64).astype("f4"))
                   for _ in range(3))
        o = flash_attention_fwd(q, k, v, causal=True, interpret=True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
        mask = jnp.tril(jnp.ones((640, 640), bool))
        s = jnp.where(mask, s, -jnp.inf)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# compilestats tracking
# ---------------------------------------------------------------------------

class TestTrackedKernel:
    def test_standalone_dispatch_registers_surface(self):
        from paddle_tpu.observability import compilestats
        from paddle_tpu.nn.functional.attention import _flash_fwd_lse
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 128, 2, 32).astype("f4"))
        o, lse = _flash_fwd_lse(q, q, q, None, causal=True,
                                interpret=True)
        assert o.shape == (1, 128, 2, 32)
        assert kreg.FLASH_FWD_LSE_SURFACE in compilestats.surfaces()
        st = compilestats.snapshot()[kreg.FLASH_FWD_LSE_SURFACE]
        assert st["compiles"] >= 1

    def test_traced_dispatch_inlines_into_caller(self):
        from paddle_tpu.observability import compilestats
        from paddle_tpu.nn.functional.attention import _flash_fwd_lse
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 128, 2, 32).astype("f4"))
        _flash_fwd_lse(q, q, q, None, causal=True, interpret=True)
        st0 = compilestats.snapshot()[kreg.FLASH_FWD_LSE_SURFACE]

        @jax.jit
        def outer(qv):
            o, _ = _flash_fwd_lse(qv, qv, qv, None, causal=True,
                                  interpret=True)
            return o
        outer(q)   # tracer operands: must NOT add kernel-surface rows
        st1 = compilestats.snapshot()[kreg.FLASH_FWD_LSE_SURFACE]
        assert st1["compiles"] == st0["compiles"]


# ---------------------------------------------------------------------------
# dispatch ladder: padding, masks, fallbacks
# ---------------------------------------------------------------------------

class TestDispatch:
    def _qkv(self, B=2, S=300, H=2, D=64, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: paddle.to_tensor(rng.randn(B, S, H, D).astype("f4"))
        return mk(), mk(), mk()

    def test_padded_causal_parity(self, monkeypatch):
        q, k, v = self._qkv(S=300)   # not a multiple of 256 or 512
        ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(out._value),
                                   np.asarray(ref._value),
                                   atol=2e-3, rtol=2e-3)

    def test_padded_noncausal_parity(self, monkeypatch):
        q, k, v = self._qkv(S=300)
        ref = F.scaled_dot_product_attention(q, k, v, is_causal=False)
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "pallas")
        out = F.scaled_dot_product_attention(q, k, v, is_causal=False)
        np.testing.assert_allclose(np.asarray(out._value),
                                   np.asarray(ref._value),
                                   atol=2e-3, rtol=2e-3)

    def test_key_bias_mask_parity(self, monkeypatch):
        B, S = 2, 300
        q, k, v = self._qkv(B=B, S=S)
        mnp = np.zeros((B, 1, 1, S), "f4")
        mnp[:, :, :, 280:] = -1e30          # key-padding tail
        m = paddle.to_tensor(mnp)
        ref = F.scaled_dot_product_attention(q, k, v, attn_mask=m)
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "pallas")
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=m)
        np.testing.assert_allclose(np.asarray(out._value),
                                   np.asarray(ref._value),
                                   atol=2e-3, rtol=2e-3)

    def test_per_query_mask_falls_back(self, monkeypatch):
        from paddle_tpu.nn.functional.attention import _select_flash
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "pallas")
        reg = paddle.observability.get_registry()
        m0 = reg.get("pt_kernel_fallbacks_total")
        base = m0.value(kernel="attention", reason="mask") if m0 else 0
        sel = _select_flash(512, 512, 64, causal=False, has_mask=True,
                            mask_is_keybias=False, scale=None,
                            heads=(8, 8))
        assert not sel.use
        m = reg.get("pt_kernel_fallbacks_total")
        assert m.value(kernel="attention", reason="mask") == base + 1

    def test_constraint_ladder_reasons(self, monkeypatch):
        from paddle_tpu.nn.functional.attention import _select_flash
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        reg = paddle.observability.get_registry()

        def reason_of(**kw):
            args = dict(S=2048, Sk=2048, D=64, causal=True,
                        has_mask=False, mask_is_keybias=False,
                        scale=None, heads=(16, 16))
            args.update(kw)
            return _select_flash(**args)

        assert reason_of().use                        # baseline accepts
        assert not reason_of(dropout_p=0.1).use       # dropout
        assert not reason_of(scale=0.5).use           # non-default scale
        assert not reason_of(Sk=1024).use             # cross-seq
        # masked shape past the head-folded VMEM cap
        assert not reason_of(has_mask=True, mask_is_keybias=True).use
        m = reg.get("pt_kernel_fallbacks_total")
        for r in ("dropout", "scale", "cross-seq", "mask-large"):
            assert m.value(kernel="attention", reason=r) >= 1, r

    def test_short_seq_floor_auto_vs_forced(self, monkeypatch):
        from paddle_tpu.nn.functional.attention import _select_flash
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        auto = _select_flash(256, 256, 64, causal=True, has_mask=False,
                             mask_is_keybias=False, scale=None,
                             heads=(8, 8))
        assert not auto.use                       # S < 1024, not forced
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "pallas")
        forced = _select_flash(256, 256, 64, causal=True, has_mask=False,
                               mask_is_keybias=False, scale=None,
                               heads=(8, 8))
        assert forced.use and forced.interpret


# ---------------------------------------------------------------------------
# fused xent: row padding + registry
# ---------------------------------------------------------------------------

class TestXentDispatch:
    def test_unaligned_rows_pad_through_kernel(self):
        from paddle_tpu.ops.pallas import fused_xent as fx
        rng = np.random.RandomState(0)
        T, V = 200, 384                       # T % 256 != 0: pads rows
        lg = jnp.asarray(rng.randn(T, V).astype("f4"))
        lb_np = rng.randint(-1, V, (T,)).astype("i4")
        lb = jnp.asarray(lb_np)
        fx._FORCE_INTERPRET = True
        try:
            out = fx.fused_softmax_xent(lg, lb)
            g = jax.grad(lambda x: jnp.sum(fx.fused_softmax_xent(x, lb))
                         )(lg)
        finally:
            fx._FORCE_INTERPRET = False
        ref = fx._ref_rowloss(lg, lb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        gr = jax.grad(lambda x: jnp.sum(fx._ref_rowloss(x, lb)))(lg)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-6)
        assert out.shape == (T,) and g.shape == (T, V)

    def test_unaligned_vocab_books_fallback(self, monkeypatch):
        from paddle_tpu.ops.pallas import fused_xent as fx
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        reg = paddle.observability.get_registry()
        m0 = reg.get("pt_kernel_fallbacks_total")
        base = m0.value(kernel="xent", reason="unaligned-vocab") \
            if m0 else 0
        rng = np.random.RandomState(0)
        lg = jnp.asarray(rng.randn(64, 100).astype("f4"))   # V % 128 != 0
        lb = jnp.asarray(rng.randint(0, 100, (64,)).astype("i4"))
        out = fx.fused_softmax_xent(lg, lb)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(fx._ref_rowloss(lg, lb)),
                                   rtol=1e-5, atol=1e-5)
        m = reg.get("pt_kernel_fallbacks_total")
        assert m.value(kernel="xent", reason="unaligned-vocab") == base + 1


# ---------------------------------------------------------------------------
# train-step gradient parity (the ISSUE 15 acceptance contract)
# ---------------------------------------------------------------------------

_FLASH_ENV = {"PADDLE_TPU_KERNEL_INTERPRET": "1",
              "PADDLE_TPU_KERNEL_ATTENTION": "pallas"}


def _grad_rel_max(ga, gb):
    worst = 0.0
    for a, b in zip(ga, gb):
        denom = float(jnp.abs(b).max()) + 1e-9
        worst = max(worst, float(jnp.abs(a - b).max()) / denom)
    return worst


def _model_grads(build, loss_of):
    """(loss, grads, params) of one train-step-equivalent fwd+bwd: the
    same value_and_grad-over-the-network shape the hapi stepper jits."""
    from paddle_tpu.framework import autograd as _ag
    from paddle_tpu.framework.random import rng_scope
    paddle.seed(0)
    net = build()
    params = [p for _, p in net.named_parameters()]
    pvals = [p._value for p in params]

    def loss_fn(pv):
        olds = [p._value for p in params]
        for p, v in zip(params, pv):
            p._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                return loss_of(net)
        finally:
            for p, v in zip(params, olds):
                p._value = v

    loss, grads = jax.value_and_grad(loss_fn)(pvals)
    return float(loss), grads


class TestPartitionedDispatch:
    """PR 21: XLA cannot partition a Mosaic kernel ("wrap the call in a
    shard_map" — first seen on four v5e chips), so under a multi-device
    trace the dispatch sites run the kernels per shard.  Interpret mode
    on the CPU mesh checks the wrapping (specs, the (B, H, S) lse
    residual, row sharding) against the single-device result."""

    def _mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))

    def test_context_is_trace_scoped_and_skips_one_device_meshes(self):
        from jax.sharding import Mesh
        assert kreg.current_partition() is None
        with kreg.partitioned(self._mesh(), ("data", "sharding"), "model") \
                as part:
            assert kreg.current_partition() is part
            assert part.batch_axes == ("data",)      # absent axis dropped
            assert part.batch(8) == "data" and part.batch(3) is None
            assert part.heads(4, 2) == "model" and part.heads(4, 1) is None
        assert kreg.current_partition() is None
        with kreg.partitioned(Mesh(np.asarray(jax.devices()[:1]),
                                   ("data",)), ("data",), None):
            assert kreg.current_partition() is None

    @pytest.mark.parametrize("masked", [False, True])
    def test_flash_per_shard_matches_single_device(self, masked):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.nn.functional import attention as A
        mesh = self._mesh()
        rng = np.random.RandomState(0)
        B, S, H, D = 4, 256, 4, 64
        q, k, v, g = (jnp.asarray(rng.randn(B, S, H, D).astype("f4"))
                      for _ in range(4))
        bias = jnp.asarray(np.where(rng.rand(B, S) < 0.2, -1e30, 0.0)
                           .astype("f4")) if masked else None
        flash = A._Flash(True, True)

        def loss(q, k, v):
            o = A._attention_core(q, k, v, True, None, flash) \
                if bias is None else \
                A._attention_core_bias(q, k, v, bias, False, flash)
            return jnp.sum(o * g)

        def sharded(q, k, v):
            with kreg.partitioned(mesh, ("data",), "model"):
                return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

        ref = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
        sh = NamedSharding(mesh, P("data", None, "model", None))
        got = jax.jit(sharded, in_shardings=(sh, sh, sh))(q, k, v)
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-5)
        for a, b in zip(got[1], ref[1]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        assert "shard_map" in str(jax.make_jaxpr(sharded)(q, k, v))

    def test_xent_per_row_shard_matches_single_device(self, monkeypatch):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.ops.pallas import fused_xent as fx
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        mesh = self._mesh()
        rng = np.random.RandomState(1)
        T, V = 1000, 512                  # 500 rows per shard: row padding
        lg = jnp.asarray(rng.randn(T, V).astype("f4"))
        lb = jnp.asarray(rng.randint(0, V, (T,)).astype("i4")) \
            .at[::5].set(-100)

        def loss(x):
            return jnp.sum(fx.fused_softmax_xent(x, lb))

        def sharded(x):
            with kreg.partitioned(mesh, ("data",), "model"):
                return jax.value_and_grad(loss)(x)

        ref = jax.jit(jax.value_and_grad(loss))(lg)
        got = jax.jit(sharded, in_shardings=(
            NamedSharding(mesh, P("data", "model")),))(lg)   # vocab-parallel in
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref[1]),
                                   atol=1e-6)


class TestTrainStepParity:
    """Dense-vs-flash gradient parity in interpret mode.  Contract:
    loss within 1e-4 absolute, per-tensor gradients within 5e-3
    relative-max (fp32; docs/kernels.md "Numerics")."""

    def test_gpt_causal_hapi_train_step(self, monkeypatch):
        """Full hapi stepper fidelity: one SGD train_batch, dense vs
        flash+fused-xent — the applied update IS -lr * grad."""
        from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                           GPTPretrainingCriterion)
        import paddle_tpu.nn as nn

        cfg = GPTConfig(vocab_size=512, hidden_size=64,
                        num_hidden_layers=2, num_attention_heads=2,
                        max_position_embeddings=256)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 512, (2, 256)).astype("int32")

        def one_step():
            paddle.seed(0)
            net = GPTForPretraining(cfg)
            before = [np.asarray(p._value)
                      for _, p in net.named_parameters()]
            opt = paddle.optimizer.SGD(
                learning_rate=0.1, parameters=net.parameters())
            model = paddle.Model(net)
            model.prepare(opt, GPTPretrainingCriterion())
            loss = model.train_batch([ids], [ids])
            after = [np.asarray(p._value)
                     for _, p in net.named_parameters()]
            deltas = [a - b for a, b in zip(after, before)]
            val = loss[0] if isinstance(loss, (list, tuple)) else loss
            return float(np.asarray(val).reshape(-1)[0]), deltas

        loss_d, delta_d = one_step()
        for k, v in _FLASH_ENV.items():
            monkeypatch.setenv(k, v)
        loss_f, delta_f = one_step()
        assert abs(loss_d - loss_f) < 1e-4, (loss_d, loss_f)
        rel = _grad_rel_max([jnp.asarray(d) for d in delta_f],
                            [jnp.asarray(d) for d in delta_d])
        assert rel < 5e-3, rel

    def test_llama_rope_gqa_grads(self, monkeypatch):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=256,
                          max_position_embeddings=256)
        rng = np.random.RandomState(1)
        ids = paddle.to_tensor(
            rng.randint(0, 512, (2, 256)).astype("int32"))
        lb = rng.randint(0, 512, (2, 256)).astype("int32")

        def loss_of(net):
            logits = net(ids)
            V = logits.shape[-1]
            from paddle_tpu.tensor.manipulation import reshape
            return F.cross_entropy(reshape(logits, [-1, V]),
                                   paddle.to_tensor(lb.reshape(-1)))._value

        build = lambda: LlamaForCausalLM(cfg)
        loss_d, gd = _model_grads(build, loss_of)
        for k, v in _FLASH_ENV.items():
            monkeypatch.setenv(k, v)
        loss_f, gf = _model_grads(build, loss_of)
        assert abs(loss_d - loss_f) < 1e-4
        assert _grad_rel_max(gf, gd) < 5e-3

    def test_bert_additive_mask_grads(self, monkeypatch):
        from paddle_tpu.models.bert import bert_tiny, BertForPretraining
        cfg = bert_tiny(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        rng = np.random.RandomState(2)
        B, S = 2, 128
        ids = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (B, S)).astype("int32"))
        # ragged key-padding: the (B, S) 1/0 mask the model folds into
        # an additive (B, 1, 1, S) bias — the flash key-bias path
        mask_np = np.ones((B, S), "f4")
        mask_np[0, 100:] = 0.0
        mask_np[1, 64:] = 0.0
        mask = paddle.to_tensor(mask_np)
        lb = rng.randint(0, cfg.vocab_size, (B, S)).astype("int32")

        def loss_of(net):
            logits, _nsp = net(ids, attention_mask=mask)
            V = logits.shape[-1]
            from paddle_tpu.tensor.manipulation import reshape
            return F.cross_entropy(reshape(logits, [-1, V]),
                                   paddle.to_tensor(lb.reshape(-1)))._value

        build = lambda: BertForPretraining(cfg)
        loss_d, gd = _model_grads(build, loss_of)
        for k, v in _FLASH_ENV.items():
            monkeypatch.setenv(k, v)
        loss_f, gf = _model_grads(build, loss_of)
        assert abs(loss_d - loss_f) < 1e-4
        assert _grad_rel_max(gf, gd) < 5e-3
