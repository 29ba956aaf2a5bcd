"""The benchmark's own tests (``BENCHMARK.json`` ``paths``): the manifest,
the yardstick's arithmetic, the traffic generator, the trace reducer, and
rehearsals of the whole command at a tiny size on the CPU, where the
harness's look for a chip is stepped over here, in the test, and not by
an option of the program.  Nothing in this file loads libtpu.
"""
import filecmp
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import arrivals, compare, flops, harness, trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "head_dim": 16, "max_position_embeddings": 256, "vocab_size": 1024,
        "intermediate_size": 256, "layer_norm_epsilon": 1e-5,
        "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0}


CHAT = harness.load_json(ROOT, "benchmark", "traffic", "chat-open-0p8.json")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(ROOT)


# -- the manifest -----------------------------------------------------------

def check_configuration(root, entry):
    """The rule for one entry of ``configs``: its file gives the family,
    the reference, the sizes and the deployment they are a share of, and
    states every cut it makes (the ``model-configs`` guide's section 4)."""
    config = harness.load_json(root, entry["file"])
    assert os.path.isfile(os.path.join(
        root, "benchmark", "families", config["family"] + ".py"))
    assert os.path.isfile(os.path.join(root, config["reference"]))
    deployment = config.get("deployment")     # one shape, cut or not
    assert isinstance(deployment, dict), deployment
    assert deployment["chips_sharing_a_layer"] >= 1
    assert deployment["how"].strip()
    reduced = config["reduced"]
    assert isinstance(reduced, list) and reduced == entry["reduced"]
    assert all(isinstance(key, str) for key in reduced)
    for key in reduced:                        # what was cut from what
        assert key in config["model"], key
        assert key in config.get("published", {}), key
        assert config["published"][key] != config["model"][key], key


def check_manifest(root, manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for entry in (manifest["configs"] + manifest["workloads"]
                  + manifest["end_to_end"] + manifest["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    under = tuple(p + "/" for p in manifest["paths"])
    for c in manifest["configs"]:
        assert c["file"].startswith(under)
        check_configuration(root, c)
    configs = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        mix = harness.load_json(root, "benchmark", "traffic",
                                w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            root, "benchmark", "drivers", mix["kind"] + ".py"))
        assert os.path.isfile(os.path.join(
            root, "benchmark", "limits", w["name"] + ".json"))


def check_the_benchmarks_manifest(root, manifest):
    """All that ``test_manifest_names_units_and_files`` holds the real
    manifest to; whatever a later PR adds to it has to pass this too."""
    check_manifest(root, manifest)
    reduced = {c["name"]: c["reduced"] for c in manifest["configs"]}
    assert reduced["gpt3-medium"] == reduced["gpt3-xl"] == []   # both whole


def test_manifest_names_units_and_files(manifest):
    check_the_benchmarks_manifest(ROOT, manifest)


def test_every_cell_reports_what_the_contract_asks(manifest):
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e_names
    for w in manifest["workloads"]:
        cell = w["name"]
        e2e = {m["name"] for m in
               harness.cell_metrics(manifest, cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(manifest, cell, "per_layer")
        assert layer
        for m in layer:
            # every `moves` names an end-to-end metric the cell reports
            assert m["moves"] in e2e, (cell, m["name"])
            spec = harness.load_json(ROOT, "benchmark", "metrics",
                                     m["name"] + ".json")
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "readers", spec["reader"] + ".py"))
        kernels = [m for m in layer if m["name"].endswith("_roofline")]
        for k in kernels:
            assert k["unit"] == "%"
            assert any("mfu" in m["name"] and m["moves"] == k["moves"]
                       for m in layer), k["name"]


KNOBS = ("num_slots", "chunk", "page_size", "num_pages", "prefill_buckets")


def pinned_knobs(mix):
    """The engine knobs among a mix's keys, at any depth.  What a ``why``
    says is prose and pins nothing."""
    found = set()
    if isinstance(mix, dict):
        found = {k for k in mix if k in KNOBS}
        mix = list(mix.values())
    if isinstance(mix, list):
        for inner in mix:
            found.update(pinned_knobs(inner))
    return sorted(found)


def test_no_engine_knob_is_pinned():
    for name in os.listdir(os.path.join(ROOT, "benchmark", "traffic")):
        mix = harness.load_json(ROOT, "benchmark", "traffic", name)
        assert pinned_knobs(mix) == [], name


@pytest.mark.parametrize("change, pinned", [
    ({"why": "chunked prefill over a 16-token page_size"}, []),
    ({"engine": {"kv_mode": "paged", "chunk": 16}}, ["chunk"]),
    ({"phases": [{"engine": {"num_pages": 512}}], "num_slots": 4},
     ["num_pages", "num_slots"])])
def test_a_knob_is_a_key_and_not_a_word(change, pinned):
    assert pinned_knobs(dict(CHAT, **change)) == pinned


# -- flops.py against hand-worked numbers ------------------------------------

def test_flops_hand_worked():
    medium = harness.load_json(ROOT, "benchmark", "configs",
                               "gpt3-medium.json")["model"]
    xl = harness.load_json(ROOT, "benchmark", "configs",
                           "gpt3-xl.json")["model"]
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) and 50304 x 1024
    assert flops.matmul_params(medium) == (301_989_888, 51_511_296)
    assert flops.matmul_params(xl) == (1_207_959_552, 103_022_592)
    # 6 x 353,501,184 + 12 x 1024.5 x 1024 x 24
    per_token = flops.train_flops_per_token(medium, 2048)
    assert per_token == pytest.approx(2_121_007_104 + 302_137_344)
    peak = flops.peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert 100 * per_token * 39_013 / peak == pytest.approx(47.99, abs=0.02)
    assert flops.attention_train_flops(medium, 4, 2048) == \
        pytest.approx(4 * 2048 * 302_137_344)
    # one decode step: every matmul weight in bf16 + 196,608 B per live token
    assert flops.decode_step_min_bytes(xl, 1000) == \
        2 * 1_310_982_144 + 1000 * 196_608
    # one prompt of 3 tokens, then one decoded token that sees 4 keys
    blocks, head = flops.matmul_params(xl)
    attn = 4 * 2048 * 24
    assert flops.serve_flops(xl, [3], [4]) == pytest.approx(
        2 * blocks * 3 + attn * 6 + 2 * head
        + 2 * (blocks + head) + attn * 4)
    with pytest.raises(LookupError):
        flops.peaks("TPU v9 imaginary")


# -- the arrival generator ----------------------------------------------------

def test_arrivals_same_seed_same_schedule_and_clips():
    a = arrivals.schedule(CHAT, 2_500_000_123, 40.0, 50304)
    b = arrivals.schedule(CHAT, 2_500_000_123, 40.0, 50304)
    c = arrivals.schedule(CHAT, 77, 40.0, 50304)
    assert len(a) == len(b) == len(c) == int(CHAT["rate_rps"] * 40.0)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    p, o = CHAT["prompt_len"], CHAT["output_len"]
    for r in a:
        assert 0.0 <= r.due_s < 40.0
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 50304
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    # another seed: the same requests at the same times, other tokens
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in c]
    assert not np.array_equal(a[0].prompt, c[0].prompt)


def test_arrivals_other_processes_and_shared_prefixes():
    mix = dict(CHAT, arrivals={"process": "gamma", "cv": 3},
               prompt_len={"dist": "fixed", "value": 300},
               output_len={"dist": "uniform", "min": 8, "max": 32},
               shared_prefix={"count": 2, "length": 256, "share": 1.0})
    s = arrivals.schedule(mix, 5, 30.0, 1000)
    heads = {tuple(r.prompt[:256]) for r in s}
    assert len(heads) == 2 and all(len(r.prompt) == 300 for r in s)
    assert all(8 <= r.max_new_tokens <= 32 for r in s)
    assert arrivals.percentile(list(range(1, 101)), 90) == 90
    assert arrivals.percentile([5.0], 90) == 5.0


# -- the comparison's arithmetic ---------------------------------------------

def test_worst_leaf_gap_and_flat_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, at = compare.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 2e-9}, ref)
    assert at == "a" and gap == pytest.approx(0.1)     # c: against the median
    gap, at = compare.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5}, ref)
    assert at == "c" and gap == pytest.approx(0.5)
    assert compare.worst_leaf_gap({"a": 0.0, "b": 2.0, "c": 0.0}, ref,
                                  skip={"c"})[0] == pytest.approx(1.0)
    assert compare.flat_gradient_leaves(ref) == {"c"}
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, ref)


# -- the trace reducer --------------------------------------------------------

def test_reduce_busy_union_self_time_and_gap_attribution():
    us = 1000
    loaded = {
        "spans": [("bench.window", 0, 1000 * us),
                  ("bench.fit_step", 0, 600 * us),
                  ("bench.loader_next", 600 * us, 700 * us),
                  ("bench.fit_step", 700 * us, 1000 * us)],
        "devices": {0: {
            "ops": [("%while.3 = s32[] while(x)", 100 * us, 500 * us),
                    ("%fusion.7 = f32[2] fusion(y)", 100 * us, 300 * us),
                    ("%fusion.9 = f32[2] fusion(z)", 300 * us, 400 * us),
                    ("%copy.1 = f32[2] copy(y)", 650 * us, 900 * us),
                    ("%copy.2 = f32[2] copy(y)", 905 * us, 950 * us),
                    ("%late.1 = f32[2] copy(y)", 990 * us, 1200 * us)],
            "modules": [("jit_step(123)", 100 * us, 500 * us),
                        ("jit_step(123)", 650 * us, 950 * us)]}}}
    r = trace_reduce.reduce(loaded)
    assert r["window_s"] == pytest.approx(1e-3)
    # busy: [100,500] + [650,900] + [905,950] + [990,1000 clipped]
    assert r["busy_s"] == pytest.approx(705e-6)
    assert r["idle0_share"] == pytest.approx(1 - 0.705)
    ops = r["op_seconds"]
    assert ops["fusion"] == pytest.approx(300e-6)       # numbers dropped
    assert ops["while"] == pytest.approx(100e-6)        # self time only
    assert ops["copy"] == pytest.approx(295e-6)
    assert r["module_seconds"]["jit_step"] == (pytest.approx(700e-6), 2)
    gaps = r["gap_seconds"]
    # [0,100] and [500,650 -> middle 575] and [950,990] lie in fit_step;
    # [900,905] is a short gap between operations
    assert gaps["bench.fit_step"] == pytest.approx(290e-6)
    assert gaps[trace_reduce.SHORT_GAPS] == pytest.approx(5e-6)
    assert "bench.loader_next" not in gaps
    assert r["breakdown"]["device_ops"][0][0] == "fusion"
    with pytest.raises(ValueError):
        trace_reduce.reduce({"spans": [], "devices": loaded["devices"]})


def _cpu_planes(monkeypatch):
    """On the CPU the operations run on host threads: read those lines as
    the device's, so that the whole traced path can be rehearsed."""
    monkeypatch.setattr(trace_reduce, "device_index",
                        lambda name: 0 if name == "/host:CPU" else None)
    monkeypatch.setattr(trace_reduce, "line_kind",
                        lambda name: "ops" if name.startswith("tf_XLA")
                        else None)


def test_load_reads_a_recorded_trace(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    _cpu_planes(monkeypatch)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracer = harness.Tracer(True, str(tmp_path / "trace"), [0.0, 0.0])
    assert tracer.tick(0.0) == "started"
    for _ in range(3):
        with harness.span("bench.fit_step"):
            f(x).block_until_ready()
    tracer.stop()
    loaded = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path / "trace")))
    names = [n for n, _, _ in loaded["spans"]]
    assert names.count("bench.fit_step") == 3 and "bench.window" in names
    r = trace_reduce.reduce(loaded)
    assert 0 < r["busy_s"] < r["window_s"]


# -- the command, end to end --------------------------------------------------

def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "fit-gpt3-medium", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == harness.NO_CHIP_EXIT
    assert '"metrics"' not in p.stdout


MARK, MARKED_FLOPS = 0.125, 4.2e9
MARKED_FAMILY = f"""\
# a second family: leans on the GPT one and counts serving its own way
from benchmark.families.gpt import *  # noqa: F401,F403


def serve_flops(model, obs):
    return {MARKED_FLOPS}
"""
MARKED_REFERENCE = f"""\
# the GPT reference with every gap it reads raised by {MARK}
from benchmark.reference import gpt
from benchmark.reference.gpt import *  # noqa: F401,F403


def next_token_gaps(model, w, ids, targets):
    return gpt.next_token_gaps(model, w, ids, targets) + {MARK}
"""
CUT = {"name": "tiny-cut", "source": "test", "family": "marked",
       "reference": "benchmark/reference/marked.py", "model": TINY,
       "reduced": ["num_hidden_layers", "vocab_size"],
       "published": {"num_hidden_layers": 24, "vocab_size": 4096},
       "deployment": {"chips_sharing_a_layer": 4,
                      "how": "a quarter of the vocabulary's rows here"}}


def _write(root, rel, obj):
    """A JSON file (or, of a string, a text file) of the copy."""
    with open(os.path.join(root, "benchmark", rel), "w") as f:
        f.write(obj) if isinstance(obj, str) else json.dump(obj, f)


@pytest.fixture()
def tiny_root(tmp_path, manifest, monkeypatch):
    """A copy of the benchmark with two configurations (one of them cut,
    of a family and with a reference of its own), two mixes, two metrics
    and a reader ADDED as new files plus one entry each: no file that is
    there is edited, and the fixture checks that."""
    import jax
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(root, "benchmark")
    m = json.loads(json.dumps(manifest))

    _write(root, "configs/tiny.json", {
        "name": "tiny", "source": "test", "family": "gpt",
        "reference": "benchmark/reference/gpt.py", "model": TINY,
        "reduced": [],
        "deployment": {"chips_sharing_a_layer": 1, "how": "whole"}})
    m["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny.json", "why": "t"})
    _write(root, "families/marked.py", MARKED_FAMILY)
    _write(root, "reference/marked.py", MARKED_REFERENCE)
    _write(root, "configs/tiny-cut.json", CUT)
    m["configs"].append({"name": "tiny-cut", "source": "test",
                         "reduced": CUT["reduced"], "why": "t",
                         "file": "benchmark/configs/tiny-cut.json"})
    fit = harness.load_json(bench, "traffic", "fit-s2048-b4.json")
    _write(root, "traffic/fit-tiny.json",
           dict(fit, seq_len=128, rows_per_second=400,
                trace_window_s=[0.2, 0.8]))
    _write(root, "traffic/chat-tiny.json", dict(
        CHAT, rate_rps=6, trace_window_s=[0.3, 2.0],
        engine=dict(CHAT["engine"], max_seq_len=256),
        prompt_len=dict(CHAT["prompt_len"], median=40, min=4, max=120),
        output_len=dict(CHAT["output_len"], median=16, min=2, max=64)))
    for cell, config, traffic, like in (
            ("fit-tiny", "tiny", "fit-tiny", "fit-gpt3-medium"),
            ("chat-tiny", "tiny", "chat-tiny", "chat-gpt3-xl"),
            ("chat-cut", "tiny-cut", "chat-tiny", "chat-gpt3-xl")):
        m["workloads"].append({"name": cell, "config": config, "chips": 1,
                               "traffic": traffic, "why": "t"})
        shutil.copy(os.path.join(bench, "limits", like + ".json"),
                    os.path.join(bench, "limits", cell + ".json"))
        for e in m["end_to_end"] + m["per_layer"]:
            if like in e.get("workloads", []):
                e["workloads"].append(cell)
    chat_limit = harness.load_json(bench, "limits", "chat-gpt3-xl.json")
    _write(root, "limits/chat-cut.json",
           {"token_gap_mean": MARK + chat_limit["token_gap_mean"]})
    _write(root, "readers/steps_counted.py",
           "def read(run, params):\n"
           "    return run.obs.get(params['key'])\n")
    _write(root, "metrics/fit.steps.json",
           {"reader": "steps_counted", "params": {"key": "steps"}})
    # a counter of the engine's, read by a data file alone
    _write(root, "metrics/serve.chunks.json",
           {"reader": "ratio", "params": {"num": "obs.engine.chunks"}})
    for name, unit, layer, moves, cells in (
            ("fit.steps", "steps", "user loop", "train_tokens_per_s",
             ["fit-tiny"]),
            ("serve.chunks", "chunks", "serving engine",
             "serve_tokens_per_s", ["chat-tiny", "chat-cut"])):
        m["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": layer, "moves": moves,
            "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    same = filecmp.dircmp(os.path.join(ROOT, "benchmark"), bench,
                          ignore=["__pycache__"])
    _assert_nothing_edited(same)
    # the look for a chip is stepped over here, in the test
    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(flops, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    _cpu_planes(monkeypatch)
    return root


def _assert_nothing_edited(same):
    """Every file of the benchmark is in the copy, byte for byte."""
    assert not same.left_only and not same.diff_files and not same.funny_files
    for sub in same.subdirs.values():
        _assert_nothing_edited(sub)


def _marked_mfu(result):
    """``serve.mfu`` as the marked family's count gives it (the fixture's
    peak is 1e12 FLOP/s)."""
    return 100 * MARKED_FLOPS / (result["device"]["window_s"] * 1e12)


def _cut(**change):
    """The cut configuration's file with ``change`` (None: key left out)."""
    return {k: v for k, v in dict(CUT, **change).items() if v is not None}


def _drive(root, capsys, cell, trace, seconds="1.5", seed="3000000019"):
    harness.main(["--workload", cell, "--seed", seed, "--seconds", seconds,
                  "--trace", str(trace)], root=root)
    captured = capsys.readouterr()
    lines = [json.loads(x) for x in captured.out.splitlines()
             if x.startswith("{")]
    return lines[-1], lines[:-1], captured.err


@pytest.mark.parametrize("trace", [0, 1])
def test_fit_cell_rehearsal(tiny_root, capsys, trace):
    result, earlier, err = _drive(tiny_root, capsys, "fit-tiny", trace)
    assert result["correct"] is True, err
    assert list(result)[-1] == "compared" and "correct: True" in err
    assert result["device"]["platform"] == "cpu"        # named, never hidden
    assert result["attempted"] > 3 and result["failed"] == 0
    if trace:
        assert {"fit.mfu", "fit.step_device_ms", "device.fit_idle_share",
                "fit.input_wait_share", "fit.steps"} <= set(result["metrics"])
        # a reader that finds nothing to read leaves its metric out
        assert "kernel.fit_attention_roofline" not in result["metrics"]
        assert result["device"]["busy_s"] > 0
        assert result["breakdown"]["device_ops"]
        assert [e["event"] for e in earlier].count("trace_written") == 1
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert "trace_written" not in [e["event"] for e in earlier]
    setup = next(e for e in earlier if e["event"] == "setup")
    assert {"weights_s", "first_steps_s", "compiles"} <= set(setup)
    assert result["compared"]["compiles_in_window"] == [0, 0]


@pytest.mark.parametrize("trace", [0, 1])
def test_chat_cell_rehearsal(tiny_root, capsys, trace):
    result, earlier, err = _drive(tiny_root, capsys, "chat-tiny", trace, "3")
    assert result["correct"] is True, err
    assert result["attempted"] == 18 and result["failed"] == 0
    generator = next(e for e in earlier if e["event"] == "generator")
    assert generator["late_ms_max"] >= generator["late_ms_mean"] >= 0
    if trace:
        assert {"serve.mfu", "device.serve_idle_share", "serve.chunks"} <= \
            set(result["metrics"])
        window = next(e for e in earlier if e["event"] == "window")
        assert result["metrics"]["serve.chunks"]["value"] == \
            window["engine.chunks"] > 0
        assert "engine_stats" not in window            # one form, the flat
        # the GPT family's count and reference, not the marked ones beside it
        assert result["compared"]["token_gap_mean"][0] < MARK
        assert result["metrics"]["serve.mfu"]["value"] != pytest.approx(
            _marked_mfu(result))
    else:
        assert set(result["metrics"]) == {
            "serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
        assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


# -- a cut configuration of another family, by new files alone ----------------

def test_second_family_serves_and_counts_its_own_way(tiny_root, capsys):
    """``chat-cut`` is ``chat-tiny`` under a configuration whose family
    and reference are files of the copy: the rehearsal is correct against
    THAT reference (every gap it reads is raised by MARK, and so is the
    limit), and ``serve.mfu`` is THAT family's count over the window."""
    result, _, err = _drive(tiny_root, capsys, "chat-cut", 1, "3")
    assert result["correct"] is True, err
    gap, limit = result["compared"]["token_gap_mean"]
    assert MARK <= gap <= limit < MARK + 2e-3
    assert result["compared"]["requests_unanswered"] == [0, 0]
    assert result["metrics"]["serve.mfu"]["value"] == pytest.approx(
        _marked_mfu(result))


def test_added_configurations_pass_the_manifest_test(tiny_root, manifest):
    """The real manifest with three configurations more, one of them cut,
    passes all that its own test holds it to: that test counts no
    entries and asks of no added one that it be whole."""
    grown = harness.load_manifest(tiny_root)
    assert [c["name"] for c in grown["configs"]] == \
        [c["name"] for c in manifest["configs"]] + ["tiny", "tiny-cut"]
    assert grown["configs"][-1]["reduced"]
    check_the_benchmarks_manifest(tiny_root, grown)


@pytest.mark.parametrize("fault, listed", [
    ({"published": None}, None),                        # cut from what?
    ({"published": {"num_hidden_layers": 24}}, None),   # ... and the other?
    ({"published": {"num_hidden_layers": 24,            # 1024 is what is run
                    "vocab_size": TINY["vocab_size"]}}, None),
    ({"deployment": None}, None),
    ({"deployment": "a quarter of a layer"}, None),     # over how many chips?
    ({"reduced": ["num_hidden_layers"]}, None),         # the manifest says two
    ({"reduced": ["num_hidden_layers", "depth"],        # no key of ``model``
      "published": {"num_hidden_layers": 24, "depth": 24}},
     ["num_hidden_layers", "depth"]),
    ({"family": "nowhere"}, None),
    ({"reference": "benchmark/reference/nowhere.py"}, None)])
def test_a_cut_that_is_not_stated_fails_the_manifest_test(tiny_root, fault,
                                                          listed):
    _write(tiny_root, "configs/tiny-cut.json", _cut(**fault))
    manifest = harness.load_manifest(tiny_root)
    if listed:
        manifest["configs"][-1]["reduced"] = listed
    with pytest.raises(AssertionError):
        check_manifest(tiny_root, manifest)


@pytest.mark.parametrize("change, error, named", [
    ({"family": "nowhere"}, FileNotFoundError,
     "benchmark/families/nowhere.py"),
    ({"reference": "benchmark/reference/nowhere.py"}, FileNotFoundError,
     "benchmark/reference/nowhere.py"),
    ({"family": None}, KeyError, "benchmark/configs/tiny-cut.json"),
    ({"reference": None}, KeyError, "benchmark/configs/tiny-cut.json")])
def test_a_run_loads_what_the_configuration_names(tiny_root, change, error,
                                                  named):
    run = _tiny_run(tiny_root, "chat-cut", 1)
    assert run.family.__file__ == os.path.join(
        tiny_root, "benchmark", "families", "marked.py")
    assert run.reference.__file__ == os.path.join(
        tiny_root, "benchmark", "reference", "marked.py")
    _write(tiny_root, "configs/tiny-cut.json", _cut(**change))
    with pytest.raises(error, match=re.escape(named)):
        _tiny_run(tiny_root, "chat-cut", 1)


def names_a_family(text, harness_itself=False):
    """What a file of the harness, a driver or a reader may not do:
    import a family, a reference, the weights or the program's models by
    name, or call ``flops.py`` for more than the chip's peaks (the
    harness alone imports it, for those).  Prose names what it likes."""
    imported = set(re.findall(
        r"^\s*(?:from|import) benchmark\.(families|reference|weights|flops)\b",
        text, re.M)) | set(re.findall(
            r"^\s*from benchmark import .*\b(families|reference|weights|flops)\b",
            text, re.M)) | set(re.findall(
                r"^\s*(?:from|import) (paddle_tpu\.models)\b", text, re.M))
    called = set(re.findall(r"\bflops\.(\w+)", text)) - {"peaks"}
    return sorted((imported - {"flops"} if harness_itself else imported)
                  | {"flops." + name for name in called})


def test_harness_drivers_and_readers_name_no_family():
    """What belongs to a family is reached as ``run.family`` and
    ``run.reference``; ``flops.py`` is called for the chip's peaks alone."""
    bench = os.path.join(ROOT, "benchmark")
    files = [os.path.join(bench, "harness.py")] + [
        os.path.join(bench, kind, name) for kind in ("drivers", "readers")
        for name in sorted(os.listdir(os.path.join(bench, kind)))
        if name.endswith(".py")]
    assert len(files) >= 10
    for path in files:
        assert names_a_family(
            open(path).read(), path.endswith("harness.py")) == [], path


@pytest.mark.parametrize("text, found", [
    ('"""Unlike GPT\'s cache of 2H a token, the latent one (gpt.py has the\n'
     'other) is 576 wide."""\ndef read(run, params):\n'
     '    return run.family.decode_step_min_bytes(run.model, run.obs)\n', []),
    ("from benchmark import flops\npeak = flops.peaks(kind)\n", ["flops"]),
    ("    from benchmark.families import gpt\n", ["families"]),
    ("from benchmark.reference import gpt as reference\n", ["reference"]),
    ("from benchmark import arrivals, weights\n", ["weights"]),
    ("from paddle_tpu.models import gpt\n", ["paddle_tpu.models"]),
    ("need = flops.serve_flops(run.model, [], [])\n", ["flops.serve_flops"])])
def test_naming_a_family_is_structure_and_not_prose(text, found):
    """A later PR's reader may SAY GPT; it may not import or count it."""
    assert names_a_family(text) == found


def test_the_family_counts_what_flops_counts():
    from benchmark.families import gpt
    medium = harness.load_json(ROOT, "benchmark", "configs",
                               "gpt3-medium.json")["model"]
    xl = harness.load_json(ROOT, "benchmark", "configs",
                           "gpt3-xl.json")["model"]
    obs = {"batch": 4, "seq_len": 2048, "traced_prompt_lens": [3],
           "traced_decode_positions": [4],
           "traced_live_kv_tokens_mean": 1000, "engine.chunks": 7}
    assert gpt.train_flops_per_token(medium, obs) == \
        flops.train_flops_per_token(medium, 2048)
    assert gpt.attention_train_flops(medium, obs) == \
        flops.attention_train_flops(medium, 4, 2048)
    assert gpt.serve_flops(xl, obs) == flops.serve_flops(xl, [3], [4])
    assert gpt.decode_step_min_bytes(xl, obs) == \
        2 * 1_310_982_144 + 1000 * 196_608
    assert gpt.leaf_name("gpt.layers.3.attn.qkv_proj.weight") == \
        "h.3.attn.qkv.weight"
    with pytest.raises(KeyError):
        gpt.leaf_name("bert.pooler.weight")


# -- the profiler's stop holds the loop: no request pays for it ---------------

def test_a_slow_trace_write_costs_no_request(tiny_root, capsys, monkeypatch):
    """The profiler's stop, from inside the serving loop, outlasts the
    drain: the window's clock stands still across it, every request
    still arrives at its own time and is answered."""
    mix = harness.load_json(tiny_root, "benchmark", "traffic",
                            "chat-tiny.json")
    _write(tiny_root, "traffic/chat-tiny.json", dict(mix, drain_s=2))
    import jax
    sound = jax.profiler.stop_trace

    def slow():
        time.sleep(4)
        sound()

    monkeypatch.setattr(jax.profiler, "stop_trace", slow)
    result, earlier, err = _drive(tiny_root, capsys, "chat-tiny", 1, "3")
    written = [e for e in earlier if e["event"] == "trace_written"]
    assert len(written) == 1 and written[0]["seconds"] >= 4
    assert result["compared"]["requests_unanswered"] == [0, 0]
    assert result["correct"] is True, err
    assert result["attempted"] == 18 and result["failed"] == 0
    late = next(e for e in earlier if e["event"] == "generator")
    assert late["late_ms_max"] < 2000       # nobody waited out the write


def test_the_tracers_clock_stands_still_across_its_stop(tmp_path, capsys,
                                                        monkeypatch):
    """``Tracer.stop`` owns the hold: it books it, prints it, and
    ``clock()``, the window's time in every driver, leaves it out."""
    import jax
    sound = jax.profiler.stop_trace
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: (time.sleep(0.5), sound()))
    tracer = harness.Tracer(True, str(tmp_path / "trace"), [0.0, 0.0])
    assert tracer.tick(0.0) == "started" and tracer.held_s == 0.0
    before, real = tracer.clock(), time.perf_counter()
    assert tracer.tick(0.1) == "stopped"
    assert time.perf_counter() - real >= 0.5 > 0.1 > tracer.clock() - before
    tracer.stop()                           # stopped already: nothing more
    said = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert said == [{"event": "trace_written",
                     "seconds": round(tracer.held_s, 3)}]
    assert tracer.held_s >= 0.5


# -- a broken timed path has to come out as not correct -----------------------

def _break_train_step(monkeypatch, fault):
    from paddle_tpu.hapi import model as hapi_model
    sound = hapi_model._CompiledStepper.train_step

    def broken(self, inputs, labels, update=True):
        import jax.numpy as jnp
        if fault == "half_batch":       # the mean taken over the rest
            inputs = [x[:len(x) // 2] for x in inputs]
            labels = [x[:len(x) // 2] for x in labels]
            return sound(self, inputs, labels, update)
        before = [jnp.copy(p._value) for p in self.params]
        out = sound(self, inputs, labels, update)
        for p, v in zip(self.params, before):   # the state comes back unchanged
            p._value = v
        return out

    monkeypatch.setattr(hapi_model._CompiledStepper, "train_step", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fit_cell_refuses_a_broken_step(tiny_root, capsys, monkeypatch, fault):
    _break_train_step(monkeypatch, fault)
    result, _, err = _drive(tiny_root, capsys, "fit-tiny", 0, "0.5")
    assert result["correct"] is False
    assert "FAILED" in err and "correct: False" in err
    failed = {k for k, (v, lim) in result["compared"].items() if not v <= lim}
    expected = {"state_unchanged": "delta_norm_gap",
                "half_batch": "grad_norm_gap"}[fault]
    assert expected in failed, result["compared"]


def test_chat_cell_refuses_an_altered_token(tiny_root, capsys, monkeypatch):
    from paddle_tpu.inference import serving
    sound = serving.build_pick

    def altered(*args):
        pick = sound(*args)

        def pick_next(logits, key):
            token, score = pick(logits, key)
            return (token + 1) % logits.shape[-1], score
        return pick_next

    monkeypatch.setattr(serving, "build_pick", altered)
    result, _, err = _drive(tiny_root, capsys, "chat-tiny", 0, "2")
    assert result["correct"] is False
    value, limit = result["compared"]["token_gap_mean"]
    assert value > limit and "correct: False" in err


# -- the control: the reference at int8 in the program's place ---------------

def _tiny_run(root, cell, seed):
    import argparse
    args = argparse.Namespace(workload=cell, seed=seed, seconds=2.0, trace=0)
    return harness.Run(args, harness.load_manifest(root), root, 0.0)


@pytest.mark.parametrize("seed", [11, 2_200_000_022, 33])
def test_fit_control_at_int8_is_refused(tiny_root, seed):
    run = _tiny_run(tiny_root, "fit-tiny", seed)
    driver = harness.load_module(tiny_root, "drivers", "fit")
    ids = driver.synthetic_tokens(12, 128, TINY["vocab_size"], seed)
    reference = driver.reference_steps(run, ids)
    control = driver.reference_steps(run, ids, precision="int8")
    gaps, _ = driver.gaps_between(control, reference)
    over = [n for n in driver.COMPARED
            if gaps[n] > run.limits[n.split(".")[0]]]
    assert over, gaps


@pytest.mark.parametrize("seed", [11, 2_200_000_022, 33])
def test_served_control_at_int8_is_refused(seed):
    """At a size a test run can hold (4 layers x 512, heads of 128) the
    reference at bfloat16 in the program's place reads a mean token gap
    of 1.1e-4 to 1.3e-4 and the int8 control 5.8e-4 to 8.7e-4 (CPU
    readings on three seeds): a limit between them, placed as the cell's
    own is, refuses the control."""
    from benchmark import weights
    from benchmark.reference import gpt as reference
    model = dict(TINY, num_hidden_layers=4, hidden_size=512, head_dim=128,
                 vocab_size=8192, intermediate_size=2048)
    w = weights.make_stacked(model, seed)
    rng = np.random.RandomState(seed % (2 ** 32))
    rows = [rng.randint(0, 8192, 256).astype(np.int32) for _ in range(4)]

    def mean_gap(precision):
        gaps = [np.asarray(reference.next_token_gaps(
            model, w, row, reference.best_next_tokens(model, w, row,
                                                      precision)))
                for row in rows]
        return float(np.concatenate(gaps).mean())

    limit = 3e-4
    assert mean_gap("bf16") < limit < mean_gap("int8")
    assert mean_gap("highest") == 0.0
