"""Paged decode attention (ops/pallas/paged_attention.py) against the
plain form of attention over a paged cache (gather the table, mask,
attend), and the rule that picks between them in
``models/gpt.py`` ``_cached_attention``.

Everything here runs the kernel INTERPRETED on the CPU
(``PADDLE_TPU_KERNEL_INTERPRET=1``) at sizes of a few pages.  Tolerances:
float32 pools agree with the plain form within 2e-5 (both accumulate in
float32, in another order); bfloat16 pools within 1e-2, the rounding of
the output to bfloat16 (the kernel keeps the probabilities in float32,
the plain form rounds them to the values' dtype before the weighted
sum).  That the kernel compiles for a v5e at the cells' real shapes is
``tests/test_flash_tpu_compile.py``'s; its speed is PERF.md's.
"""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import kvcache as kvc
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.ops import registry as kreg
from paddle_tpu.ops.pallas import paged_attention as pa

pytestmark = pytest.mark.serving

P, NPG, POOL, D = 16, 16, 48, 128
MAX = P * NPG
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 1e-2}


def _plain(q, kp, vp, table, lengths):
    """Gather every table entry, mask past ``lengths``, attend: the
    ``"xla"`` form's math in float32 (``gather_pages`` is its own)."""
    B, nH, _ = q.shape
    k, v = kvc.gather_pages(kp, vp, table)
    G = nH // k.shape[2]
    k = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    v = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k,
                   precision="highest") / math.sqrt(D)
    live = jnp.arange(k.shape[1])[None, None] < lengths[:, None, None]
    p = jnp.where(live, jax.nn.softmax(jnp.where(live, s, -1e30), -1), 0)
    v = jnp.where(live[:, 0, :, None, None], v, 0)
    return jnp.einsum("bhk,bkhd->bhd", p, v, precision="highest")


def _case(lengths, nH, nKV, dtype, seed=0, poison=None):
    """Pools, a permuted non-contiguous table for ``lengths`` and a
    query.  ``poison`` fills every page no slot maps, the trash page
    among them, and the dead tail of each slot's last page."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    kp = rng.randn(POOL, P, nKV, D).astype("float32")
    vp = rng.randn(POOL, P, nKV, D).astype("float32")
    perm = rng.permutation(np.arange(1, POOL))
    table = np.zeros((B, NPG), np.int32)
    o = 0
    for b, n in enumerate(lengths):
        pages = -(-int(n) // P)
        table[b, :pages] = perm[o:o + pages]
        o += pages
    if poison is not None:
        mapped = set(table.ravel().tolist()) - {0}
        for page in range(POOL):
            if page not in mapped:
                kp[page] = vp[page] = poison
        for b, n in enumerate(lengths):
            if n % P:
                kp[table[b, n // P], n % P:] = 1e4
                vp[table[b, n // P], n % P:] = 1e4
    q = rng.randn(B, nH, D).astype("float32")
    return (jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), jnp.asarray(table),
            jnp.asarray(np.asarray(lengths, np.int32)))


def _kernel(*args):
    return np.asarray(pa.paged_attention(*args, interpret=True)
                      .astype(jnp.float32))


class TestKernelAgainstPlainForm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_lengths_mixed_in_one_batch(self, dtype):
        """1, 15, 16, 17, 220 and MAX live keys side by side, each slot's
        pages scattered over the pool in a permuted order."""
        args = _case([1, 15, 16, 17, 220, MAX], 2, 2, dtype)
        want = np.asarray(_plain(*args))
        np.testing.assert_allclose(_kernel(*args), want, atol=TOL[dtype],
                                   rtol=TOL[dtype])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_grouped_heads_read_their_kv_head(self, dtype):
        """nH = 4 nKV: query head h reads kv head h // 4, as repeating
        the kv heads would give."""
        args = _case([33, 5, 100], 8, 2, dtype, seed=1)
        want = np.asarray(_plain(*args))
        np.testing.assert_allclose(_kernel(*args), want, atol=TOL[dtype],
                                   rtol=TOL[dtype])

    def test_dead_pages_are_never_read(self):
        """NaN in every page no slot maps (the trash page too) and 1e4 in
        the tail of each last page change nothing: a page past
        ceil(length / page_size) is not copied, the tail is masked."""
        lengths = [1, 17, 40, 220]
        clean = _case(lengths, 2, 2, jnp.float32, seed=2)
        dirty = _case(lengths, 2, 2, jnp.float32, seed=2, poison=np.nan)
        got = _kernel(*dirty)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, _kernel(*clean))

    def test_inactive_slot_on_the_trash_page(self):
        """A slot whose table row is all trash page has no live key
        (``live_lengths``), reads nothing, returns zeros, and leaves the
        live slots' rows as they are without it."""
        q, kp, vp, table, lengths = _case([40, 77, 9], 2, 2, jnp.float32,
                                          seed=3, poison=np.nan)
        alone = _kernel(q, kp, vp, table, lengths)
        table4 = jnp.concatenate([table[:1], jnp.zeros((1, NPG), jnp.int32),
                                  table[1:]])
        q4 = jnp.concatenate([q[:1], q[:1], q[1:]])
        # the inactive slot's stale position counts nothing
        pos = jnp.asarray([39, 123, 76, 8], jnp.int32)
        live = kvc.live_lengths(table4, pos, P)
        np.testing.assert_array_equal(np.asarray(live), [40, 0, 77, 9])
        got = _kernel(q4, kp, vp, table4, live)
        assert np.isfinite(got).all() and not got[1].any()
        np.testing.assert_array_equal(got[[0, 2, 3]], alone)

    def test_every_slot_empty(self):
        q, kp, vp, table, lengths = _case([0, 0], 2, 2, jnp.float32)
        assert not _kernel(q, kp, vp, table, lengths).any()


# ---------------------------------------------------------------------------
# the dispatch rule
# ---------------------------------------------------------------------------

def _view(nKV=8, dtype=jnp.float32, quant=False, d=D):
    pool = jnp.zeros((4, P, nKV, d), jnp.int8 if quant else dtype)
    scales = jnp.zeros((4, P), jnp.float32) if quant else None
    return kvc.PagedCacheView(pool, pool, scales, scales,
                              jnp.zeros((2, NPG), jnp.int32))


def _count(name, **labels):
    m = paddle.observability.get_registry().get(name)
    return m.value(kernel="paged_attention", **labels) if m else 0


class TestDispatchRule:
    CASES = [
        # S, mask, view, runs, fallback reason
        (1, False, {}, "pallas", None),
        (8, False, {}, "xla", "multi-token"),
        (1, False, {"quant": True}, "xla", "int8-kv"),
        (1, True, {}, "xla", "mask"),
        (1, False, {"d": 64}, "xla", "head-dim"),
        (1, False, {"nKV": 4}, "xla", "kv-heads"),
        (1, False, {"nKV": 16, "dtype": jnp.bfloat16}, "pallas", None),
    ]

    @pytest.mark.parametrize("S,mask,view,runs,reason", CASES, ids=[
        "decode", "prefill", "int8_kv", "mask", "head_dim", "kv_heads",
        "chat_cell"])
    def test_labels(self, monkeypatch, S, mask, view, runs, reason):
        """What the rule books: the form that runs in
        ``pt_kernel_selects_total``, and why a call the platform gave the
        kernel fell to the gather path in ``pt_kernel_fallbacks_total``."""
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        cache = _view(**view)
        nKV, d = cache.k_pages.shape[2:]
        sel0 = _count("pt_kernel_selects_total", impl=runs)
        fb0 = _count("pt_kernel_fallbacks_total", reason=reason) \
            if reason else None
        got = pa.select((2, S, 2 * nKV, d), cache, mask)
        assert got.use == (runs == "pallas") and got.interpret == got.use
        assert _count("pt_kernel_selects_total", impl=runs) == sel0 + 1
        if reason:
            assert _count("pt_kernel_fallbacks_total",
                          reason=reason) == fb0 + 1

    def test_off_the_chip_the_plain_form_runs_and_books_no_fallback(
            self, monkeypatch):
        """No interpret mode: the platform's pick is ``xla``, whatever is
        forced, and nothing is a fallback (the bitwise tests of
        tests/test_kvcache.py run this form)."""
        monkeypatch.delenv("PADDLE_TPU_KERNEL_INTERPRET", raising=False)
        before = {r: _count("pt_kernel_fallbacks_total", reason=r)
                  for r in ("multi-token", "int8-kv", "mask")}
        with kreg.force("paged_attention", "pallas"):
            assert not pa.select((2, 1, 8, D), _view(), False).use
        assert not pa.select((2, 8, 8, D), _view(quant=True), True).use
        assert before == {r: _count("pt_kernel_fallbacks_total", reason=r)
                          for r in before}

    def test_forcing_xla_under_interpret_mode(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        with kreg.force("paged_attention", "xla"):
            assert not pa.select((2, 1, 8, D), _view(), False).use
        assert pa.select((2, 1, 8, D), _view(), False).use


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide_head_gpt():
    """A float32 GPT small enough for the interpreter whose heads are
    128 wide, the width the kernel tiles: 2 layers, 8 heads."""
    paddle.seed(0)
    return GPTForPretraining(GPTConfig(
        vocab_size=512, hidden_size=8 * D, num_hidden_layers=2,
        num_attention_heads=8, max_position_embeddings=128))


def _serve(net, prompts, budgets, impl, **kw):
    with kreg.force("paged_attention", impl):
        eng = ServingEngine(net, num_slots=4, chunk=4, kv_mode="paged",
                            page_size=8, prefill_buckets=(8, 16, 32), **kw)
        reqs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.run()
    assert eng._kv.check()
    return [list(map(int, r.tokens)) for r in reqs]


def test_engine_tokens_equal_under_both_forms(monkeypatch, wide_head_gpt):
    """Greedy tokens of a float32 paged engine over 3 chunks and more,
    ragged prompts, slots freed and reused: the kernel's tokens are the
    gather path's (float32 leaves the argmax no room to flip), and the
    decode program's trace booked the kernel once a layer."""
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 512, (n,)).astype("int32")
               for n in (5, 17, 9, 30, 12, 3)]
    budgets = [12, 9, 14, 5, 13, 10]
    plain = _serve(wide_head_gpt, prompts, budgets, "xla")
    n0 = _count("pt_kernel_selects_total", impl="pallas")
    got = _serve(wide_head_gpt, prompts, budgets, "pallas")
    assert _count("pt_kernel_selects_total", impl="pallas") == n0 + 2
    assert [len(t) for t in got] == budgets
    assert got == plain
