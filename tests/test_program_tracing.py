"""The program names its device work and stamps its own loops (ISSUE 26):

- ``jax.named_scope`` from ``tracing.SCOPES`` in the lowered HLO of the
  hapi train step and of the paged decode chunk;
- one span mechanism, ``tracing.region``: per fit step and per engine
  cycle the children tile the parent and name it; the two host-gap
  histograms book what the spans give;
- ``report --device``: the reduction's arithmetic on hand-made event
  lists, the HLO ``op_name`` join, and the CLI on a CPU trace (no device
  plane) and on no trace at all.
"""
import json
import os
import weakref

import numpy as np
import pytest
import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability import compilestats, report, timeline, tracing
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                               GPTPretrainingCriterion, gpt3_tiny)

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.enable(True)
    obs.get_registry().reset()
    tracing.reset()
    yield
    obs.get_registry().reset()
    tracing.reset()


def _tiny_fit_model(jit=True):
    paddle.seed(0)
    net = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, max_position_embeddings=32))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(1e-3, parameters=net.parameters()),
                  GPTPretrainingCriterion(),
                  amp_configs={"level": "O2", "dtype": "bfloat16"},
                  **({} if jit else {"jit": False}))
    return model


def _token_batches(n=4):
    ids = np.random.RandomState(0).randint(0, 256, (2 * n, 16)).astype("int32")
    return [(ids[i:i + 2], ids[i:i + 2]) for i in range(0, 2 * n, 2)]


@pytest.fixture(scope="module")
def fitted():
    """One compiled tiny GPT ``Model`` that has trained four steps."""
    model = _tiny_fit_model()
    model.fit(_token_batches(), epochs=1, verbose=0)
    return model


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    net = GPTForPretraining(gpt3_tiny())
    net.eval()
    return net


def _paged_engine(gpt):
    return ServingEngine(gpt, num_slots=2, chunk=4, kv_mode="paged",
                         page_size=8, prefill_buckets=(8, 16),
                         max_seq_len=128)


def _run_paged(gpt, budgets=(9, 6, 5)):
    rng = np.random.RandomState(5)
    eng = _paged_engine(gpt)
    reqs = [eng.submit(rng.randint(0, 1024, (n,)).astype("int32"), b)
            for n, b in zip((5, 12, 7), budgets)]
    eng.run()
    return eng, reqs


def _hlo_of(surface):
    """HLO text (with ``metadata={op_name=...}``) of the executables a
    ``compilestats`` wrapper holds."""
    return "\n".join(e.as_text() for e in surface._cache.values())


def _by_phase(spans, trace):
    out = {}
    for s in spans:
        if s["trace"] == trace:
            out.setdefault(s["phase"], {}).setdefault(s["req_id"], []).append(s)
    return out


# -- scopes in the device programs -------------------------------------------

class TestScopes:
    def test_vocabulary_is_fixed_and_checked(self):
        assert tracing.SCOPES == (
            "embed", "norm", "attention.qkv", "attention.core",
            "attention.out", "kv.gather", "kv.scatter", "mlp", "lm_head",
            "xent", "sample", "amp_cast", "optimizer", "guard",
            "attention.latent_kv", "attention.absorb", "moe.router",
            "moe.dispatch", "moe.experts", "moe.shared", "moe.combine",
            "attention.kda", "attention.indexer", "attention.sparse",
            "residual.hc")
        with pytest.raises(ValueError):
            tracing.scope("attention")

    def test_train_step_hlo_names_forward_and_backward(self, fitted):
        (step,) = fitted._stepper._train_cache.values()
        hlo = _hlo_of(step)
        assert "HloModule jit_step" in hlo          # the program's name stays
        for name in ("embed", "norm", "attention.qkv", "attention.core",
                     "attention.out", "mlp", "lm_head", "xent", "amp_cast"):
            assert f"jvp({name})" in hlo, name
        for name in ("attention.qkv", "attention.core", "attention.out",
                     "mlp", "norm", "lm_head", "xent", "embed"):
            assert f"transpose(jvp({name}))" in hlo, name
        assert "/optimizer/" in hlo
        # the op_names, not the whole text: its file-name table can hold
        # any test file this process ran before (test_training_guardian)
        ops = "\n".join(compilestats._HLO_OP_NAME.findall(hlo))
        for name in ("kv.gather", "kv.scatter", "sample", "guard"):
            assert name not in ops                  # not on this path

    def test_guarded_train_step_names_the_select(self):
        model = _tiny_fit_model()
        model.fit(_token_batches(1), epochs=1, verbose=0,
                  guardian={"check_grads": True})
        # fit clears the guarded executables on exit: build one again
        model._stepper.guard_numerics = True
        x, y = _token_batches(1)[0]
        model._stepper.train_step([x], [y])
        (step,) = model._stepper._train_cache.values()
        assert "/guard/" in _hlo_of(step)

    def test_paged_decode_chunk_and_prefill_hlo(self, gpt):
        eng, _ = _run_paged(gpt)
        chunk = _hlo_of(eng._decode_jit)
        assert "HloModule jit_paged_decode_chunk" in chunk
        for name in ("embed", "norm", "attention.qkv", "attention.core",
                     "attention.out", "kv.gather", "kv.scatter", "mlp",
                     "lm_head", "sample"):
            assert f"/{name}/" in chunk, name
        assert "transpose(jvp(" not in chunk and "xent" not in chunk
        prefill = "\n".join(_hlo_of(p) for p in eng._prefill_jit.values())
        assert "HloModule jit_paged_prefill" in prefill
        assert "/kv.scatter/" in prefill and "/sample/" in prefill

    def test_scopes_change_no_result(self, gpt):
        eng, reqs = _run_paged(gpt)
        for r in reqs:
            ids, _ = gpt.generate(paddle.to_tensor(r.prompt[None, :]),
                                  max_new_tokens=r.max_new_tokens)
            assert list(map(int, r.tokens)) == \
                list(map(int, np.asarray(ids._value)[0]))


# -- the span mechanism ------------------------------------------------------

class TestRegion:
    def test_books_id_parent_args_and_mirrors_into_the_profiler(self):
        prof = profiler.Profiler(timer_only=True)
        prof.start()
        with tracing.region("t", 7, "serving.step") as outer:
            with tracing.region("t", 7, "serving.admit", parent=outer.id,
                                start_ns=outer.start_ns, bucket=8) as inner:
                inner.args["more"] = 1
            outer.end_ns = inner.end_ns
        prof.stop()
        admit, step = tracing.spans()
        assert (admit["phase"], step["phase"]) == \
            ("serving.admit", "serving.step")
        assert admit["parent"] == step["id"] == outer.id
        assert step["parent"] is None and admit["req_id"] == 7
        assert admit["args"] == {"bucket": 8, "more": 1}
        assert admit["start_ns"] == step["start_ns"]      # start_ns chained
        assert admit["end_ns"] == step["end_ns"]          # end pinned
        names = {e.name for e in profiler._collect_events()}
        assert {"serving.step", "serving.admit"} <= names

    def test_books_on_exception_and_nothing_with_the_gate_off(self):
        with pytest.raises(RuntimeError):
            with tracing.region("t", 0, "fit.step"):
                raise RuntimeError("boom")
        assert [s["phase"] for s in tracing.spans()] == ["fit.step"]
        tracing.reset()
        with obs.disabled():
            with tracing.region("t", 0, "fit.step") as r:
                pass
        assert tracing.spans() == []
        assert r.id is None and r.start_ns is None and r.end_ns is None

    def test_request_summaries_and_lanes_leave_program_spans_out(self, gpt,
                                                                 tmp_path):
        _, reqs = _run_paged(gpt)
        assert {r["trace"] for r in tracing.request_summaries()} == \
            {r.trace_id for r in reqs}
        path = str(tmp_path / "t.trace.json")
        timeline.export_chrome_trace(path, include_profiler=False,
                                     include_guardian=False,
                                     include_samples=False)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        program = [e for e in events if e.get("cat") == "program"]
        assert {e["tid"] for e in program} == {timeline.TID_PROGRAM}
        assert {"serving.step", "serving.sync"} <= {e["name"] for e in program}
        assert all(not tracing.is_program_span(e["name"])
                   for e in events if e.get("cat") == "request")


class TestFitSpans:
    def test_children_tile_the_step_and_name_it(self, fitted):
        tracing.reset()
        fitted.fit(_token_batches(), epochs=1, verbose=0)
        spans = tracing.spans()
        (root,) = [s for s in spans if s["phase"] == "fit"]
        by = _by_phase(spans, root["trace"])
        assert sorted(by["fit.step"]) == [0, 1, 2, 3]
        for n, (step,) in by["fit.step"].items():
            assert step["parent"] == root["id"]
            (disp,), (rb,), (post,) = (by[p][n] for p in (
                "fit.dispatch", "fit.readback", "fit.post"))
            assert {disp["parent"], rb["parent"], post["parent"]} == \
                {step["id"]}
            # the step's own time is on_batch_begin; from _split_batch on
            # the three children tile it to its end
            assert step["start_ns"] <= disp["start_ns"]
            assert disp["end_ns"] == rb["start_ns"]
            assert rb["end_ns"] == post["start_ns"]
            assert post["end_ns"] == step["end_ns"]
            (data,) = by["fit.data"][n]
            assert data["parent"] == root["id"]
            assert data["end_ns"] <= step["start_ns"]
        assert by["fit.data"][4][0]["args"] == {"exhausted": True}
        assert root["start_ns"] <= by["fit.data"][0][0]["start_ns"]
        assert root["end_ns"] >= by["fit.step"][3][0]["end_ns"]

    def test_host_gap_histogram_books_what_the_spans_give(self, fitted):
        tracing.reset()
        obs.get_registry().reset()
        fitted.fit(_token_batches(), epochs=1, verbose=0)
        spans = tracing.spans()
        trace = next(s["trace"] for s in spans if s["phase"] == "fit")
        by = _by_phase(spans, trace)
        want = [(by["fit.dispatch"][n + 1][0]["end_ns"]
                 - by["fit.readback"][n][0]["end_ns"]) / 1e6
                for n in range(3)]
        hist = obs.get_registry().get("pt_train_host_gap_ms")
        assert hist.count() == 3
        assert hist.sum() == pytest.approx(sum(want))
        assert all(g > 0 for g in want)

    def test_a_steps_outputs_are_freed_before_the_next_dispatch(
            self, fitted, monkeypatch):
        """Held across the boundary, the fp32 logits of step N cost the
        chip 1.6 GB and 22 ms of every dispatch (PERF.md section 6)."""
        stepper = type(fitted._stepper)
        sound, refs, alive = stepper.train_step, [], []

        def spying(self, inputs, labels, update=True):
            alive.append([r() is not None for r in refs])
            loss, outs = sound(self, inputs, labels, update)
            refs.append(weakref.ref(outs[0]))
            return loss, outs
        monkeypatch.setattr(stepper, "train_step", spying)
        fitted.fit(_token_batches(), epochs=1, verbose=0)
        assert alive == [[], [False], [False] * 2, [False] * 3]

    def test_eager_fit_books_data_and_step_only(self):
        model = _tiny_fit_model(jit=False)
        tracing.reset()
        model.fit(_token_batches(2), epochs=1, verbose=0)
        phases = {s["phase"] for s in tracing.spans()}
        assert phases == {"fit", "fit.data", "fit.step"}
        assert obs.get_registry().get("pt_train_host_gap_ms").count() == 0


class TestServingSpans:
    def test_children_tile_the_cycle_and_requests_name_it(self, gpt):
        eng, reqs = _run_paged(gpt)
        spans = tracing.spans()
        by = _by_phase(spans, eng._trace)
        cycles = sorted(by["serving.step"])
        assert cycles == list(range(eng._cycle)) and len(cycles) >= 3
        step_ids = {}
        for c in cycles:
            (step,) = by["serving.step"][c]
            step_ids[step["id"]] = c
            assert step["parent"] is None
            kids = [by[p][c][0] for p in (
                "serving.admit", "serving.decode_chunk", "serving.sync",
                "serving.deliver") if c in by.get(p, {})]
            assert [k["phase"] for k in kids][0] == "serving.admit"
            assert [k["phase"] for k in kids][-2:] == \
                ["serving.sync", "serving.deliver"]
            assert all(k["parent"] == step["id"] for k in kids)
            assert kids[0]["start_ns"] == step["start_ns"]
            for a, b in zip(kids, kids[1:]):
                assert a["end_ns"] == b["start_ns"]
            assert kids[-1]["end_ns"] == step["end_ns"]
            admit_id = kids[0]["id"]
            for p in by.get("serving.prefill", {}).get(c, []):
                assert p["parent"] == admit_id
                assert kids[0]["start_ns"] <= p["start_ns"] \
                    and p["end_ns"] <= kids[0]["end_ns"]
        assert sum(len(v) for v in by["serving.prefill"].values()) == \
            eng.stats["prefills"] == len(reqs)
        # every request span names the serving.step of the cycle whose
        # sync booked it, and ends inside that cycle's delivery
        request_spans = [s for s in spans
                         if not tracing.is_program_span(s["phase"])]
        assert {s["phase"] for s in request_spans} == \
            {"queue_wait", "prefill", "decode"}
        for s in request_spans:
            c = step_ids[s["parent"]]
            (deliver,) = by["serving.deliver"][c]
            assert deliver["start_ns"] <= s["end_ns"] <= deliver["end_ns"] \
                or s["phase"] == "queue_wait"

    def test_host_gap_histogram_books_what_the_spans_give(self, gpt):
        eng, _ = _run_paged(gpt)
        by = _by_phase(tracing.spans(), eng._trace)
        want = []
        for c in sorted(by["serving.decode_chunk"]):
            before = by["serving.step"].get(c - 1)
            # only while requests stayed in flight across the boundary
            if before and before[0]["args"]["in_flight"]:
                want.append((by["serving.decode_chunk"][c][0]["end_ns"]
                             - by["serving.sync"][c - 1][0]["end_ns"]) / 1e6)
        hist = obs.get_registry().get("pt_serving_host_gap_ms")
        assert want and hist.count() == len(want)
        assert hist.sum() == pytest.approx(sum(want))


# -- report --device ---------------------------------------------------------

def _ev(name, start, end, op=None):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", start, end, op)


class TestDeviceView:
    def test_scope_of_an_op_name(self):
        assert report.scope_of(
            "jit(step)/jvp(attention.core)/dot_general") == \
            ("attention.core", "fwd")
        assert report.scope_of(
            "jit(step)/transpose(jvp(mlp))/transpose(jvp(norm))/mul") == \
            ("norm", "bwd")
        assert report.scope_of(
            "jit(f)/jit(main)/while/body/kv.gather/gather") == \
            ("kv.gather", "fwd")
        assert report.scope_of(
            "jit(step)/jvp(mlp)/jvp(jit(_flash_bhsd_fwd_mh))/x") == \
            ("mlp", "fwd")
        assert report.scope_of("jit(step)/optimizer/sub") == \
            ("optimizer", "fwd")
        # a scope nested in another stays bare under the outer's transpose
        assert report.scope_of(
            "jit(step)/transpose(jvp(mlp))/mlp/dot_general") == \
            ("mlp", "bwd")
        assert report.scope_of("jit(step)/jvp(mlp)/mlp/tanh") == \
            ("mlp", "fwd")
        assert report.scope_of("jit(step)/jvp(jit(gelu))/tanh") is None
        assert report.scope_of("") is None and report.scope_of(None) is None

    LOADED = {
        "devices": {0: {
            "modules": [("jit_step(123)", 1_000, 9_000),
                        ("jit_other(9)", 20_000, 21_000)],
            "ops": [
                _ev("fusion.1", 1_000, 3_000),       # mlp forward
                _ev("fusion.2", 3_000, 6_000),       # mlp backward
                _ev("while.3", 6_000, 9_000),        # holds the next two
                _ev("fusion.4", 6_500, 7_500),       # optimizer
                _ev("copy.5", 7_500, 8_000),         # no op_name: outside
                _ev("fusion.1", 20_000, 21_000),     # another program's
            ]}},
        "spans": [("fit", 0, 50_000), ("fit.step", 500, 19_000),
                  ("fit.readback", 9_100, 18_000),
                  ("fit.data", 19_000, 19_900)],
    }
    NAMES = {"jit_step": {
        "fusion.1": "jit(step)/jvp(mlp)/dot_general",
        "fusion.2": "jit(step)/transpose(jvp(mlp))/dot_general",
        "while.3": "jit(step)/while",
        "fusion.4": "jit(step)/optimizer/sub"}}

    def test_by_scope_sums_to_busy_time(self):
        view = report.device_view(self.LOADED, self.NAMES)
        rows = {(r["scope"], r["direction"]): r["seconds"]
                for r in view["by_scope"]}
        assert rows == {
            ("mlp", "fwd"): pytest.approx(2e-6),
            ("mlp", "bwd"): pytest.approx(3e-6),
            ("optimizer", "fwd"): pytest.approx(1e-6),
            ("while", None): pytest.approx(1.5e-6),      # its SELF time
            ("copy", None): pytest.approx(0.5e-6),
            # the same instruction name in a program with no map
            ("fusion", None): pytest.approx(1e-6)}
        assert sum(rows.values()) == pytest.approx(view["busy_s"])
        assert view["busy_s"] == pytest.approx(9e-6)
        assert view["window_s"] == pytest.approx(20e-6)
        assert view["idle_s"] == pytest.approx(11e-6)
        assert view["outside_scope_s"] == pytest.approx(3e-6)
        assert {(c["scope"], c["hlo"], c["program"])
                for c in view["cross"]} >= {
            ("mlp", "fusion", "jit_step"), ("copy", "copy", "jit_step"),
            ("optimizer", "fusion", "jit_step"),
            ("fusion", "fusion", "jit_other")}

    def test_programs_and_idle_gaps_by_innermost_span(self):
        view = report.device_view(self.LOADED, self.NAMES)
        assert view["by_program"] == [
            {"program": "jit_step", "seconds": pytest.approx(8e-6),
             "runs": 1},
            {"program": "jit_other", "seconds": pytest.approx(1e-6),
             "runs": 1}]
        # one gap, 9_000 -> 20_000: its middle lies in fit.readback,
        # inside fit.step, inside fit
        assert view["idle_gaps"] == [
            {"span": "fit.readback", "seconds": pytest.approx(11e-6)}]
        loaded = dict(self.LOADED, spans=[("fit", 0, 50_000)])
        assert report.device_view(loaded)["idle_gaps"][0]["span"] == "fit"
        loaded = dict(self.LOADED, spans=[])
        assert report.device_view(loaded)["idle_gaps"][0]["span"] == \
            report.NO_SPAN

    def test_op_name_on_the_event_wins_over_the_join(self):
        ops = [_ev("fusion.1", 0, 1_000, "jit(f)/xent/reduce_max")]
        view = report.device_view(
            {"devices": {0: {"modules": [], "ops": ops}}, "spans": []},
            {"jit_step": {"fusion.1": "jit(step)/jvp(mlp)/dot_general"}})
        assert [(r["scope"], r["direction"]) for r in view["by_scope"]] == \
            [("xent", "fwd")]

    def test_render_and_empty_traces(self):
        text = report.render_device(
            report.device_view(self.LOADED, self.NAMES))
        assert "mlp (bwd)" in text and "hlo:copy" in text
        assert "jit_step" in text and "fit.readback" in text
        with pytest.raises(ValueError, match="no device plane"):
            report.device_view({"devices": {}, "spans": []})

    def test_hlo_op_names_and_the_merged_table(self, gpt):
        text = "\n".join([
            "HloModule jit_step, is_scheduled=true",
            "%fused_computation (p: f32[8]) -> f32[8] {",
            '  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name='
            '"jit(step)/jvp(mlp)/add"}',
            "}",
            "ENTRY %main (a: f32[8]) -> f32[8] {",
            "  %a = f32[8]{0} parameter(0)",
            '  ROOT %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls='
            '%fused_computation, metadata={op_name="jit(step)/jvp(mlp)/add"'
            " stack_frame_id=3}",
            "}"])
        assert compilestats.hlo_op_names(text) == ("jit_step", {
            "add.1": "jit(step)/jvp(mlp)/add",
            "fusion.7": "jit(step)/jvp(mlp)/add"})
        eng, _ = _run_paged(gpt)
        table = compilestats.op_names()
        chunk = table["jit_paged_decode_chunk"]
        assert {report.scope_of(op)[0] for op in chunk.values()
                if report.scope_of(op)} >= {"kv.gather", "mlp", "sample"}
        # the two prefill buckets share one program name: what they
        # disagree on is None, never one bucket's answer for the other
        assert "jit_paged_prefill" in table
        del eng


class TestDeviceCli:
    def test_cpu_trace_loads_spans_and_says_no_device_plane(
            self, gpt, tmp_path, capsys):
        eng = _paged_engine(gpt)
        eng.submit(np.arange(5, dtype=np.int32), 3)
        log_dir = str(tmp_path / "trace")
        with profiler.Profiler(log_dir=log_dir):
            eng.run()
        # the profiler leaves the op_name maps beside the trace
        with open(os.path.join(log_dir, compilestats.OP_NAMES_FILE)) as f:
            assert "jit_paged_decode_chunk" in json.load(f)
        loaded = report.load_device_trace(report.find_xplane(log_dir))
        assert loaded["devices"] == {}
        assert {"serving.step", "serving.admit", "serving.decode_chunk",
                "serving.sync", "serving.deliver"} <= \
            {name for name, _, _ in loaded["spans"]}
        assert report.main(["report", "--device", log_dir]) == 1
        err = capsys.readouterr().err
        assert "no device plane" in err and "program spans loaded" in err
        assert report.main(["report", "--device", log_dir, "--json"]) == 1

    def test_no_trace_at_all(self, tmp_path, capsys):
        assert report.main(["report", "--device", str(tmp_path)]) == 1
        assert "no .xplane.pb" in capsys.readouterr().err

    def test_json_of_a_device_trace(self, tmp_path, capsys, monkeypatch):
        monkeypatch.setattr(report, "find_xplane", lambda d: "x.xplane.pb")
        monkeypatch.setattr(report, "load_device_trace",
                            lambda path: TestDeviceView.LOADED)
        with open(tmp_path / compilestats.OP_NAMES_FILE, "w") as f:
            json.dump(TestDeviceView.NAMES, f)
        assert report.main(["report", "--device", str(tmp_path),
                            "--json"]) == 0
        view = json.loads(capsys.readouterr().out)
        assert view["trace"] == "x.xplane.pb"
        assert view["by_scope"][0] == {"scope": "mlp", "direction": "bwd",
                                       "seconds": pytest.approx(3e-6)}
        assert report.main(["report", "--device", str(tmp_path)]) == 0
        assert "== device time by scope ==" in capsys.readouterr().out

    def test_the_roofline_view_is_gone(self, capsys):
        with pytest.raises(SystemExit):
            report.main(["report", "--roofline", "--prom", "x.prom"])
        assert "--roofline" in capsys.readouterr().err
        assert not hasattr(report, "roofline_view")
        assert not hasattr(report, "render_roofline")
