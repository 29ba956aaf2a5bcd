"""The ``sarvam-105b`` configuration and its cell ``docqa-sarvam-105b``:
the configuration's file against the catalog row's keys, and a rehearsal
of the cell at a tiny size on the CPU through ``drivers/serve_open.py``
(the same family, reference, mix shape, readers and metrics; the look
for a chip is stepped over here, as in ``test_benchmark_harness.py``)."""
import importlib.util
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness  # noqa: E402

CELL, CONFIG = "docqa-sarvam-105b", "sarvam-105b"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# source https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json)
CATALOG = {
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144}
TINY_MODEL = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "use_qk_norm": True,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "num_experts": 4, "router_num_experts": 8,
    "first_expert_held": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"type": "deepseek_yarn", "factor": 4, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64}}
# CPU readings at this size, seed 3000000019, 12 requests.  At hidden 64 a
# bfloat16 program's mean gap swings with the draw (6e-6 .. 2.5e-4: one
# flipped pick among 150 tokens), so the tiny twin serves in float32 and
# reads 0 to rounding; the bf16 witness reads 3.2e-5, the int8 control
# 4.5e-4 and the reference with m^2 left out more.  The cell's own limit
# comes from chip readings at the real size (PERF.md section 2).
TINY_LIMIT = 1e-4


def _harness_tests():
    """``test_benchmark_harness.py`` as a module (not collected here)."""
    path = os.path.join(ROOT, "tests", "benchmark",
                        "test_benchmark_harness.py")
    spec = importlib.util.spec_from_file_location("_harness_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the configuration's file ---------------------------------------------------

def test_configuration_carries_the_catalog_rows_keys():
    manifest = harness.load_manifest(ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    config = harness.load_json(ROOT, entry["file"])
    reduced = config["reduced"]
    assert reduced == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] == config["model"][key] != value
        else:
            assert config[key] == value, key            # key for key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 32, 65536)
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json"
    model = config["model"]
    for key in ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "moe_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor",
                "rope_scaling", "rope_theta", "rms_norm_eps"):
        assert model[key] == CATALOG[key], key          # no width is cut
    assert model["router_num_experts"] == 128
    assert config["router"]["experts_held"] == model["num_experts"] == 32
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert {"use_qk_norm", "norm_topk_prob", "n_group", "scoring_func",
            "biases"} <= set(config["assumed"])
    _harness_tests().check_the_benchmarks_manifest(ROOT, manifest)


def test_the_cell_its_mix_and_its_metrics():
    manifest = harness.load_manifest(ROOT)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    mix = harness.load_json(ROOT, "benchmark", "traffic",
                            cell["traffic"] + ".json")
    assert mix["engine"] == {"kv_mode": "paged", "dtype": "bfloat16",
                             "max_seq_len": 8192}
    assert not _harness_tests().pinned_knobs(mix)
    assert "shared_prefix" not in mix and mix["check_requests"] == 11
    e2e = {m["name"] for m in
           harness.cell_metrics(manifest, CELL, "end_to_end")}
    # the tails are printed (the ``window`` event) and not held: six chip
    # runs spread tpot_p90_ms 3.4% and ttft_p90_ms 4.4%, over half their
    # bounds, which the contract refuses in a new cell (PERF.md section 4)
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in
             harness.cell_metrics(manifest, CELL, "per_layer")}
    # eight: the three span metrics of the serving engine stay the chat
    # cell's alone (test_program_spans_reader.py holds their ``workloads``
    # to ``[cell]``; that file is the benchmark's, not this PR's to edit),
    # and serve.decode_step_ms moves tpot_p90_ms, which this cell does not
    # hold (kernel.serve_decode_roofline reads the same step time)
    assert len(layer) == 8 and {
        "moe.pairs_here_share", "moe.experts_touched_per_step",
        "kernel.serve_expert_matmul_roofline",
        "kernel.serve_prefill_attention_roofline", "serve.mfu",
        "kernel.serve_decode_roofline"} <= layer


def test_the_cut_as_built_holds_what_the_arithmetic_says():
    """Parameters of the configuration as run, from the family's shapes:
    4,535M, of which 32 routed experts a layer are 805M."""
    from benchmark import weights_mla_moe as weights
    from benchmark import flops_mla_moe as counts
    model = harness.load_json(ROOT, "benchmark", "configs",
                              CONFIG + ".json")["model"]
    shapes = weights.shapes(model)
    total = sum(int(np.prod(shapes[leaf])) for leaf in weights.GLOBAL_LEAVES)
    for i in range(model["num_hidden_layers"]):
        for leaf in weights.layer_leaves(model, i):
            n = int(np.prod(shapes[leaf]))
            total += n * model["num_experts"] \
                if leaf in weights.EXPERT_LEAVES else n
    assert round(total / 1e6) == 4535
    assert counts.attention_params(model) == 94633984          # 94.6M
    assert counts.expert_params(model) * 32 == 805306368       # 805.3M
    latent_page = 16 * 576 * 2 * model["num_hidden_layers"]
    assert 4097 * latent_page == 377_579_520                   # 0.38 GB


# -- the cell at a tiny size ------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE traced run of the cell's tiny twin; keeps the run and the
    samples the driver compared, for the tests that judge them again."""
    import jax
    helpers = _harness_tests()
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(ROOT)
    config = harness.load_json(ROOT, "benchmark", "configs", CONFIG + ".json")
    mix = harness.load_json(ROOT, "benchmark", "traffic",
                            "docqa-open-0p8.json")
    helpers._write(root, "configs/sarvam-tiny.json", dict(
        config, name="sarvam-tiny", model=TINY_MODEL,
        published={"num_hidden_layers": 32, "num_experts": 8,
                   "vocab_size": 2048}))
    helpers._write(root, "traffic/docqa-tiny.json", dict(
        mix, rate_rps=4, trace_window_s=[0.3, 2.5],
        engine=dict(mix["engine"], max_seq_len=256, dtype="float32"),
        prompt_len=dict(mix["prompt_len"], median=60, min=20, max=120),
        output_len=dict(mix["output_len"], median=12, min=4, max=40)))
    helpers._write(root, "limits/docqa-tiny.json",
                   {"token_gap_mean": TINY_LIMIT})
    manifest["configs"].append({
        "name": "sarvam-tiny", "source": "test", "why": "t",
        "reduced": config["reduced"],
        "file": "benchmark/configs/sarvam-tiny.json"})
    manifest["workloads"].append({
        "name": "docqa-tiny", "config": "sarvam-tiny", "chips": 1,
        "traffic": "docqa-tiny", "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("docqa-tiny")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    helpers.check_manifest(root, manifest)
    kept = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "require_chip",
                      lambda chips: jax.devices()[:chips])
        patch.setattr(flops, "peaks", lambda kind: {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        patch.setattr(harness, "enable_compile_cache", lambda: None)
        helpers._cpu_planes(patch)
        driver = harness.load_module(root, "drivers", "serve_open")
        compare = driver.compare_with_reference

        def keeping(run, samples, control=None):
            kept.update(run=run, samples=samples, compare=compare)
            return compare(run, samples, control)
        patch.setattr(driver, "compare_with_reference", keeping)
        patch.setattr(harness, "load_module", lambda r, kind, name: driver
                      if (kind, name) == ("drivers", "serve_open")
                      else harness.load_module_file(
                          r, f"benchmark/{kind}/{name}.py"))
        import io
        import contextlib
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            harness.main(["--workload", "docqa-tiny", "--seed", "3000000019",
                          "--seconds", "3", "--trace", "1"], root=root)
    lines = [json.loads(x) for x in out.getvalue().splitlines()
             if x.startswith("{")]
    kept.update(result=lines[-1], earlier=lines[:-1], err=err.getvalue())
    return kept


def test_cell_rehearsal_is_correct_and_reports_its_layers(rehearsal):
    result = rehearsal["result"]
    assert result["correct"] is True, rehearsal["err"]
    assert result["attempted"] == 12 and result["failed"] == 0
    assert result["compared"]["requests_unanswered"] == [0, 0]
    assert result["compared"]["compiles_in_window"] == [0, 0]
    metrics = result["metrics"]
    assert {"serve.mfu", "device.serve_idle_share", "moe.pairs_here_share",
            "moe.experts_touched_per_step"} <= set(metrics)
    # 4 of the router's 8 experts are held: about half of the pairs
    assert 30 < metrics["moe.pairs_here_share"]["value"] < 70
    assert 0 < metrics["moe.experts_touched_per_step"]["value"] <= 4
    # no flash kernel and no Pallas grouped matmul runs on the CPU: their
    # readers find nothing to read and leave the metrics out
    assert "kernel.serve_prefill_attention_roofline" not in metrics
    window = next(e for e in rehearsal["earlier"] if e["event"] == "window")
    assert window["engine.moe_pairs_routed"] > 0
    assert window["engine.moe_pairs_here"] < window["engine.moe_pairs_routed"]


def test_cell_refuses_a_wrong_scale_and_the_int8_control(rehearsal,
                                                         monkeypatch):
    run, samples = rehearsal["run"], rehearsal["samples"]
    compare = rehearsal["compare"]
    sound = compare(run, samples)["token_gap_mean"]
    assert sound < TINY_LIMIT
    witness = compare(run, samples, control="bf16")["token_gap_mean"]
    control = compare(run, samples, control="int8")["token_gap_mean"]
    assert witness < TINY_LIMIT < control
    reference = run.reference
    whole = reference.row_logits
    monkeypatch.setattr(
        reference, "row_logits",
        lambda model, w, ids, precision="highest", fault=None:
        whole(model, w, ids, precision, fault="scale"))
    wrong = compare(run, samples)["token_gap_mean"]     # m^2 left out
    assert wrong > TINY_LIMIT


def test_readers_return_nothing_where_the_program_counts_nothing():
    """On the parent's program (no counters, no grouped matmul) the new
    metrics are left out, not raised: the readers see empty ``obs``."""
    import types
    run = types.SimpleNamespace(
        obs={"traced_prompt_lens": [600]}, trace={"op_seconds": {"fusion": 1.0}},
        model={}, peaks={}, chips=1, family=types.SimpleNamespace())
    reader = harness.load_module(ROOT, "readers", "serve_kernel_roofline")
    for name in ("kernel.serve_expert_matmul_roofline",
                 "kernel.serve_prefill_attention_roofline",
                 "moe.pairs_here_share", "moe.experts_touched_per_step"):
        assert harness.read_metric(
            types.SimpleNamespace(root=ROOT, **vars(run)), name) is None
    run.trace = None
    assert reader.read(run, {"patterns": ["x"], "needed_seconds": "f"}) is None
