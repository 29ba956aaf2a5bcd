"""Continuous-batching serving engine (inference/serving.py +
scheduler.py): greedy parity vs generate(), slot lifecycle, in-flight
admission, eos eviction, ragged-prompt bucket prefill, and the
attention_mask satellite on generate() itself.

The parity tests are the real check of the per-slot vector-pos KV math:
the engine's bucket prefill + chunked scan must reproduce, token for
token, the single-scan generate() path."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import guardian
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.inference.scheduler import FCFSScheduler
from paddle_tpu.models import GPTForPretraining, gpt3_tiny

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    return GPTForPretraining(gpt3_tiny())


def _gen(gpt, prompt_np, n, **kw):
    """generate() reference for a single prompt / uniform batch."""
    if prompt_np.ndim == 1:
        prompt_np = prompt_np[None, :]
    ids, _ = gpt.generate(paddle.to_tensor(prompt_np), max_new_tokens=n,
                          **kw)
    return np.asarray(ids._value)


class TestGreedyParity:
    def test_uniform_batch_bitwise_matches_generate(self, gpt):
        """Acceptance: uniform-length, uniform-budget batch — engine
        output bitwise-identical to generate()."""
        rng = np.random.RandomState(1)
        ids = rng.randint(0, 1024, (3, 8)).astype("int32")
        ref = _gen(gpt, ids, 6)
        eng = ServingEngine(gpt, num_slots=3, chunk=4,
                            prefill_buckets=(8, 16))
        reqs = [eng.submit(ids[r], 6) for r in range(3)]
        done = eng.run()
        assert [r.req_id for r in done] == [r.req_id for r in reqs]
        got = np.stack([np.asarray(r.tokens, np.int32) for r in done])
        np.testing.assert_array_equal(got, ref)

    def test_ragged_prompts_bucket_prefill_matches_single(self, gpt):
        """Ragged prompts pad to power-of-two buckets; the pad KV sits
        after the real tokens and must never leak into the output —
        every request matches its own B=1 generate() run bitwise."""
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, 1024, (n,)).astype("int32")
                   for n in (5, 11, 8, 3)]
        eng = ServingEngine(gpt, num_slots=2, chunk=4,
                            prefill_buckets=(8, 16))
        reqs = [eng.submit(p, 5) for p in prompts]
        eng.run()
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), _gen(gpt, p, 5)[0])


class TestSlotLifecycle:
    def test_staggered_budgets_reuse_slots(self, gpt):
        """4 requests through 2 slots with staggered max_new_tokens:
        early finishers must free their slot for the queue (the
        continuous-batching win) and every request still matches its
        solo generate() run."""
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 1024, (6,)).astype("int32")
                   for _ in range(4)]
        budgets = [3, 9, 5, 7]
        eng = ServingEngine(gpt, num_slots=2, chunk=4,
                            prefill_buckets=(8,))
        reqs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        done = eng.run()
        assert len(done) == 4 and eng.stats["prefills"] == 4
        assert eng.stats["max_concurrent"] == 2
        assert not eng.scheduler.has_work
        for p, b, r in zip(prompts, budgets, reqs):
            assert len(r.tokens) == b and r.finish_reason == "budget"
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), _gen(gpt, p, b)[0])

    def test_admission_mid_flight(self, gpt):
        """A request submitted while another is decoding must be
        admitted at the next chunk boundary — not after the first
        request drains (the static-batch failure mode)."""
        rng = np.random.RandomState(4)
        p1 = rng.randint(0, 1024, (6,)).astype("int32")
        p2 = rng.randint(0, 1024, (4,)).astype("int32")
        eng = ServingEngine(gpt, num_slots=2, chunk=2,
                            prefill_buckets=(8,))
        a = eng.submit(p1, 8)
        eng.step()                       # a is mid-flight (8 > chunk=2)
        assert not a.done
        b = eng.submit(p2, 4)
        eng.step()                       # b admitted beside a
        assert eng.stats["max_concurrent"] == 2
        while eng.scheduler.has_work:
            eng.step()
        np.testing.assert_array_equal(np.asarray(a.tokens, np.int32),
                                      _gen(gpt, p1, 8)[0])
        np.testing.assert_array_equal(np.asarray(b.tokens, np.int32),
                                      _gen(gpt, p2, 4)[0])

    def test_eos_evicts_and_frees_slot(self, gpt):
        """A slot hitting eos stops early (finish_reason "eos", token
        stream ends at the eos) instead of burning its budget."""
        rng = np.random.RandomState(5)
        p = rng.randint(0, 1024, (7,)).astype("int32")
        ref = _gen(gpt, p, 9)[0]
        eos = int(ref[2])                # a token greedy decode emits
        first = int(np.argmax(ref == eos))
        eng = ServingEngine(gpt, num_slots=1, chunk=8,
                            prefill_buckets=(8,), eos_token_id=eos)
        r = eng.submit(p, 9)
        eng.run()
        assert r.finish_reason == "eos"
        assert r.tokens[-1] == eos and len(r.tokens) == first + 1
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      ref[:first + 1])

    def test_streaming_callback_order_and_is_last(self, gpt):
        rng = np.random.RandomState(6)
        p = rng.randint(0, 1024, (5,)).astype("int32")
        seen = []
        eng = ServingEngine(gpt, num_slots=1, chunk=3,
                            prefill_buckets=(8,))
        r = eng.submit(p, 5, callback=lambda rq, t, last:
                       seen.append((rq.req_id, t, last)))
        eng.run()
        assert [t for _, t, _ in seen] == r.tokens
        assert [last for _, _, last in seen] == \
            [False] * 4 + [True]
        assert r.ttft_ms is not None and r.ttft_ms >= 0

    def test_submit_validation(self, gpt):
        eng = ServingEngine(gpt, num_slots=1, chunk=2,
                            prefill_buckets=(8, 16))
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="largest"):
            eng.submit(np.zeros((17,), np.int32), 4)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.zeros((8,), np.int32), 1000)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros((4,), np.int32), 0)
        with pytest.raises(ValueError, match="bucket"):
            # bucket == max_seq_len leaves no room to generate
            ServingEngine(gpt, num_slots=1, max_seq_len=16,
                          prefill_buckets=(16,))

    def test_reset_reuses_compiled_programs(self, gpt):
        rng = np.random.RandomState(7)
        p = rng.randint(0, 1024, (6,)).astype("int32")
        eng = ServingEngine(gpt, num_slots=1, chunk=4,
                            prefill_buckets=(8,))
        r1 = eng.submit(p, 4)
        eng.run()
        jits = (eng._decode_jit, eng._prefill_jit)
        eng.reset()
        assert eng.stats["requests"] == 0
        assert (eng._decode_jit, eng._prefill_jit) == jits
        r2 = eng.submit(p, 4)
        eng.run()
        assert r2.tokens == r1.tokens

    def test_refresh_weights_keeps_dtype_override(self):
        """A dtype override must survive refresh_weights() even when the
        model's own params are mixed-dtype with the override dtype
        already dominant (an uncast fp32 norm would silently retrace the
        decode program with mixed dtypes)."""
        paddle.seed(3)
        net = GPTForPretraining(gpt3_tiny())
        params = [p for _, p in net.named_parameters()]
        floats = [p for p in params
                  if jnp.issubdtype(p._value.dtype, jnp.floating)]
        keep_fp32 = min(floats, key=lambda p: p._value.size)
        for p in floats:                 # mostly-bf16 model, one fp32 norm
            if p is not keep_fp32:
                p._value = p._value.astype(jnp.bfloat16)
        eng = ServingEngine(net, num_slots=1, chunk=2, dtype="bfloat16",
                            prefill_buckets=(8,))

        def float_dtypes(pvals):
            return {str(v.dtype) for v in pvals
                    if jnp.issubdtype(v.dtype, jnp.floating)}
        assert float_dtypes(eng._pvals) == {"bfloat16"}
        params[0]._value = params[0]._value + 0   # "train step": new array
        eng.refresh_weights()
        assert float_dtypes(eng._pvals) == {"bfloat16"}


class TestGuardianEvents:
    def test_admit_finish_stats_emitted(self, gpt):
        guardian.clear_events()
        rng = np.random.RandomState(8)
        eng = ServingEngine(gpt, num_slots=2, chunk=4,
                            prefill_buckets=(8,))
        for _ in range(3):
            eng.submit(rng.randint(0, 1024, (6,)).astype("int32"), 4)
        eng.run()
        admits = guardian.events("serving_admit")
        fins = guardian.events("serving_finish")
        stats = guardian.events("serving_stats")
        assert len(admits) == 3 and len(fins) == 3 and len(stats) == 1
        assert {a["slot"] for a in admits} <= {0, 1}
        assert all(f["reason"] == "budget" and f["tokens"] == 4
                   for f in fins)
        s = stats[-1]
        assert s["requests"] == 3 and s["decoded_tokens"] == 12
        assert s["tokens_per_sec"] > 0 and s["mean_ttft_ms"] > 0


class TestScheduler:
    def test_fcfs_order_and_interleave_knob(self):
        s = FCFSScheduler(4, max_prefills_per_gap=2)
        reqs = [s.submit(np.zeros(2, np.int32), 4) for _ in range(5)]
        first = s.admissions()
        assert [r.req_id for r, _ in first] == [reqs[0].req_id,
                                                reqs[1].req_id]
        second = s.admissions()          # knob caps at 2 per gap
        assert len(second) == 2 and s.queue_depth == 1
        assert s.admissions() == []      # no free slots left
        s.release(first[0][1])
        third = s.admissions()
        assert [r.req_id for r, _ in third] == [reqs[4].req_id]
        assert third[0][1] == first[0][1]     # freed slot reused

    def test_validation(self):
        with pytest.raises(ValueError):
            FCFSScheduler(0)
        with pytest.raises(ValueError):
            FCFSScheduler(2, max_prefills_per_gap=0)


class TestAttentionMask:
    """Satellite: generate() folds an attention_mask into the additive
    prefill/decode mask so left-padded ragged prompts stop silently
    attending pad tokens."""

    def test_pad_content_is_irrelevant_under_mask(self, gpt):
        """Two left-padded batches that differ ONLY in the pad cells
        must decode identically when the mask excludes those cells —
        the defining property of not attending pads."""
        rng = np.random.RandomState(9)
        real = rng.randint(1, 1024, (2, 5)).astype("int32")
        mask = np.ones((2, 9), np.int32)
        mask[:, :4] = 0
        a = np.concatenate([np.zeros((2, 4), np.int32), real], axis=1)
        b = np.concatenate(
            [rng.randint(1, 1024, (2, 4)).astype("int32"), real], axis=1)
        out_a = _gen(gpt, a, 6, attention_mask=mask)
        out_b = _gen(gpt, b, 6, attention_mask=mask)
        np.testing.assert_array_equal(out_a, out_b)
        # and the mask actually changes the computation vs attending
        # pads (token-level greedy picks can coincide on a tiny random
        # model; the selected-token log-probs cannot)
        _, sc_masked = gpt.generate(paddle.to_tensor(b),
                                    max_new_tokens=6,
                                    attention_mask=mask)
        _, sc_plain = gpt.generate(paddle.to_tensor(b),
                                   max_new_tokens=6)
        assert not np.array_equal(np.asarray(sc_masked._value),
                                  np.asarray(sc_plain._value))

    def test_mask_matches_tensor_and_array_inputs(self, gpt):
        rng = np.random.RandomState(10)
        ids = rng.randint(1, 1024, (2, 6)).astype("int32")
        mask = np.ones((2, 6), np.int32)
        mask[0, :2] = 0
        out_np = _gen(gpt, ids, 4, attention_mask=mask)
        out_t = _gen(gpt, ids, 4,
                     attention_mask=paddle.to_tensor(mask))
        np.testing.assert_array_equal(out_np, out_t)

    def test_all_ones_mask_is_bitwise_noop(self, gpt):
        rng = np.random.RandomState(11)
        ids = rng.randint(0, 1024, (2, 6)).astype("int32")
        np.testing.assert_array_equal(
            _gen(gpt, ids, 5),
            _gen(gpt, ids, 5, attention_mask=np.ones((2, 6), np.int32)))

    def test_beam_search_accepts_mask(self, gpt):
        rng = np.random.RandomState(12)
        real = rng.randint(1, 1024, (1, 4)).astype("int32")
        a = np.concatenate([np.zeros((1, 3), np.int32), real], axis=1)
        b = np.concatenate(
            [rng.randint(1, 1024, (1, 3)).astype("int32"), real], axis=1)
        mask = np.ones((1, 7), np.int32)
        mask[:, :3] = 0
        kw = dict(decode_strategy="beam_search", num_beams=2,
                  attention_mask=mask)
        np.testing.assert_array_equal(_gen(gpt, a, 4, **kw),
                                      _gen(gpt, b, 4, **kw))

    def test_bad_mask_shape_raises(self, gpt):
        ids = np.zeros((2, 6), np.int32)
        with pytest.raises(ValueError, match="attention_mask"):
            _gen(gpt, ids, 4, attention_mask=np.ones((2, 5), np.int32))


class TestOverdueBurst:
    """70 requests handed in at once to an 8-slot paged engine: what a
    driver's loop does after a stall (PERF.md, PR 27: 66 requests came
    due behind a 47 s trace write, and the record could not tell whether
    the stall alone left them unanswered).  Under the gather path and
    under the paged-attention kernel, interpreted."""

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_seventy_at_once_all_finish(self, monkeypatch, impl):
        from paddle_tpu.models import GPTConfig
        from paddle_tpu.ops import registry as kreg
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        paddle.seed(0)
        # heads of 128, the width the kernel tiles; 8 of them in float32
        net = GPTForPretraining(GPTConfig(
            vocab_size=256, hidden_size=1024, num_hidden_layers=1,
            num_attention_heads=8, max_position_embeddings=64))
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 256, (int(n),)).astype("int32")
                   for n in rng.randint(3, 25, 70)]
        budgets = [int(b) for b in rng.randint(2, 12, 70)]
        reg = paddle.observability.get_registry()

        def engaged():
            m = reg.get("pt_kernel_selects_total")
            return m.value(kernel="paged_attention", impl="pallas") \
                if m else 0
        before = engaged()
        with kreg.force("paged_attention", impl):
            eng = ServingEngine(net, num_slots=8, chunk=4,
                                kv_mode="paged", page_size=8,
                                prefill_buckets=(8, 16, 32))
            reqs = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
            done = eng.run()
        assert engaged() - before == (1 if impl == "pallas" else 0)
        assert len(done) == 70
        unanswered = [r.req_id for r in reqs if not r.tokens]
        assert unanswered == []
        assert [len(r.tokens) for r in reqs] == budgets
        assert all(r.finish_reason == "budget" for r in reqs)
        assert eng._kv.check()
