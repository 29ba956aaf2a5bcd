"""The ``glm-5.3-flash`` configuration and its cell ``longdoc-glm-5.3-flash``:
the configuration's file against the catalog row's keys, the cut
re-reckoned from the family's shapes, and a rehearsal of the cell at a
tiny size on the CPU through ``drivers/serve_open.py`` (the same family,
reference, mix shape, readers and metrics; the look for a chip is
stepped over here, as in ``test_benchmark_harness.py``)."""
import contextlib
import importlib.util
import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness  # noqa: E402

CELL, CONFIG = "longdoc-glm-5.3-flash", "glm-5.3-flash"
SOURCE = "https://huggingface.co/zai-org/GLM-5.3-Flash/blob/main/config.json"
L, S = "linear_attention", "deepseek_sparse_attention"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl),
# its numbers and flags; the per-layer lists are checked below
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hc_eps": 1e-06,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "head_dim": 0,
    "hidden_act": "silu", "hidden_size": 4096, "index_head_dim": 128,
    "index_kpool": 4, "index_kpool_always_select_tail": True,
    "index_kpool_compress": True, "index_n_heads": 32, "index_topk": 2048,
    "index_share_for_mtp_iteration": True, "indexer_rope_interleave": True,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576, "mhc": True, "mla_use_nope": True,
    "model_type": "glm5_next_text", "moe_intermediate_size": 2048,
    "n_group": 1, "n_routed_experts": 288, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 45,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 256, "qk_nope_head_dim": 256,
    "qk_rope_head_dim": 0, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "swiglu_limit": 10, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}
TINY_MODEL = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": [L, L, S, L],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "linear_num_heads": 4, "linear_head_dim": 16, "short_conv_kernel_size": 4,
    "gate_lower_bound": -5, "linear_gate_rank": 8,
    "num_attention_heads": 4, "q_lora_rank": 32, "qk_head_dim": 16,
    "v_head_dim": 16, "kv_lora_rank": 24, "index_n_heads": 2,
    "index_head_dim": 16, "index_topk": 16, "index_kpool": 4,
    "index_rope_dim": 8, "index_rope_theta": 10000, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "router_num_experts": 16, "first_expert_held": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "swiglu_limit": 10,
    "max_position_embeddings": 1024, "rms_norm_eps": 1e-05,
    "prefill_piece": 32}
# CPU readings at this size, seed 3000000019, 6 requests: the twin serves
# in float32 (a tiny bfloat16 model's mean gap swings with the draw,
# PERF.md section 6) and reads 0; the bf16 witness 6.0e-6, the
# int8 control 3.9e-4, the reference with every key attended (the pick
# bypassed) 8.9e-4.  The cell's own limit comes from chip readings at the
# real size (PERF.md section 2).
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
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] == config["model"][key] != value
        else:
            assert config[key] == value, key            # key for key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 36, 19360)
    assert entry["source"] == config["source"] == SOURCE
    # the per-layer lists copied whole; the layers run are published 0, 4-7
    assert config["layer_types"] == [S if i % 4 == 3 else L
                                     for i in range(45)]
    assert config["mlp_layer_types"] == ["dense"] * 3 + ["sparse"] * 42
    assert config["indexer_types"] == ["full"] * 45
    assert config["linear_attn_config"]["num_heads"] == 64
    kept = config["layers_kept"]["published_indices"]
    assert kept == [0, 4, 5, 6, 7]
    model = config["model"]
    assert model["layer_types"] == [config["layer_types"][i] for i in kept]
    assert model["mlp_layer_types"] == [config["mlp_layer_types"][i]
                                        for i in kept]
    lin = config["linear_attn_config"]
    assert (model["linear_num_heads"], model["linear_head_dim"],
            model["short_conv_kernel_size"], model["gate_lower_bound"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
        lin["gate_lower_bound"])
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "qk_head_dim", "v_head_dim", "kv_lora_rank", "index_n_heads",
                "index_head_dim", "index_topk", "index_kpool", "hc_mult",
                "hc_sinkhorn_iters", "hc_eps", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "swiglu_limit",
                "rms_norm_eps"):
        assert model[key] == CATALOG[key], key          # no width is cut
    assert model["router_num_experts"] == 288
    assert config["router"]["experts_held"] == model["n_routed_experts"] == 36
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert {"kda", "kda_gates", "index_kpool", "index_kpool_always_select_tail",
            "indexer_rope_interleave", "mhc", "mhc_streams", "swiglu_limit",
            "router"} <= set(config["assumed"])
    _harness_tests().check_the_benchmarks_manifest(ROOT, manifest)


def test_the_cell_its_mix_and_its_metrics():
    manifest = harness.load_manifest(ROOT)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    mix = harness.load_json(ROOT, "benchmark", "traffic",
                            cell["traffic"] + ".json")
    assert mix["engine"] == {"kv_mode": "paged", "dtype": "bfloat16",
                             "max_seq_len": 34816}
    assert not _harness_tests().pinned_knobs(mix)
    assert "shared_prefix" not in mix
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (
        4096, 32768)
    e2e = {m["name"] for m in
           harness.cell_metrics(manifest, CELL, "end_to_end")}
    # the tails are printed and not held, as in docqa-sarvam-105b: a
    # prefill of 0.5-1 s lands inside requests' lives
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in
             harness.cell_metrics(manifest, CELL, "per_layer")}
    assert layer == {
        "serve.mfu", "kernel.serve_decode_roofline",
        "device.serve_idle_share", "device.serve_peak_hbm_gb",
        "moe.pairs_here_share", "moe.experts_touched_per_step",
        "kernel.serve_expert_matmul_roofline", "dsa.keys_attended_share",
        "serve.prefill_mfu"}


def test_no_benchmark_file_that_was_there_is_edited():
    """What this configuration brought is new files and appended entries:
    every file the benchmark had at the commit that added it is as it
    was (where the checkout has its history)."""
    added = "benchmark/configs/glm-5.3-flash.json"
    try:
        first = subprocess.run(
            ["git", "log", "--diff-filter=A", "--format=%H", "--", added],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.SubprocessError):
        pytest.skip("no git here")
    commits = first.stdout.split()
    if first.returncode or not commits:
        pytest.skip("the configuration is not committed yet")
    parent = commits[-1] + "^"
    changed = subprocess.run(
        ["git", "diff", "--name-status", parent, commits[-1], "--",
         "benchmark", "tests/benchmark"],
        cwd=ROOT, capture_output=True, text=True, timeout=60).stdout
    edited = [line for line in changed.splitlines()
              if not line.startswith("A\t")]
    assert edited == []


def test_the_cut_as_built_holds_what_the_arithmetic_says():
    """Parameters of the configuration as run, from the family's shapes:
    4,718M, of which the 36 held routed experts a layer are 906M."""
    from benchmark import weights_glm5_next as weights
    from benchmark import flops_glm5_next as counts
    model = harness.load_json(ROOT, "benchmark", "configs",
                              CONFIG + ".json")["model"]
    held = model["n_routed_experts"]

    def params(i):
        return sum(int(np.prod(shape)) * (held if leaf in weights.EXPERT_LEAVES
                                          else 1)
                   for leaf, shape in weights.shapes(model, i).items())
    layers = [params(i) for i in range(model["num_hidden_layers"])]
    assert round(params(-1) / 1e6, 1) == 158.6       # embedding + head
    assert [round(n / 1e6, 1) for n in layers] == [
        289.5, 1070.8, 1070.8, 1070.8, 1057.5]
    assert round((params(-1) + sum(layers)) / 1e6) == 4718
    assert round(counts.kda_params(model) / 1e6, 1) == 137.6
    assert round(counts.dsa_params(model) / 1e6, 1) == 124.4
    assert counts.expert_params(model) * held == 905_969_664
    # the sparse layer's plane: 8 slots x 34,816 positions x 640 x 2 B;
    # the linear layers' float32 states: 8 slots x 4 x 64 x 128 x 128 x 4 B
    assert 8 * 34816 * 640 * 2 == 356_515_840
    assert 8 * 4 * 64 * 128 * 128 * 4 == 134_217_728


def test_counts_of_a_decode_step_and_a_prompt():
    from benchmark import flops_glm5_next as counts
    model = harness.load_json(ROOT, "benchmark", "configs",
                              CONFIG + ".json")["model"]
    obs = {"traced_decode_steps": 1, "traced_decode_positions": [16383] * 8,
           "engine.moe_decode_layer_steps": 4,
           "engine.moe_experts_touched": 4 * 7.2}
    least = counts.decode_step_min_bytes(model, obs)
    # weights 3.48 GB (2.03 outside the experts, 7.2 touched a layer
    # 1.45), states 0.27 GB, index keys and picked latent rows 0.05 GB
    assert 3.7e9 < least < 3.9e9
    # a 16k prompt at one pair in eight held: 2.24 GFLOP a token (1.87 in
    # the weights every token passes, 0.20 in the held experts, 0.14 in
    # the indexer and the kept keys)
    share = {"engine.moe_pairs_routed": 8, "engine.moe_pairs_here": 1}
    per_token = counts.prompt_flops(model, share, 16384) / 16384
    assert 2.2e9 < per_token < 2.3e9
    assert counts.prefill_programs(model, 16384) == 4
    assert counts.prefill_programs(model, 100) == 1


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
                            "longdoc-open-0p8.json")
    helpers._write(root, "configs/glm-tiny.json", dict(
        config, name="glm-tiny", model=TINY_MODEL,
        published={"num_hidden_layers": 45, "n_routed_experts": 16,
                   "vocab_size": 4096}))
    helpers._write(root, "traffic/longdoc-tiny.json", dict(
        mix, rate_rps=4, trace_window_s=[0.3, 2.5], check_requests=5,
        engine=dict(mix["engine"], max_seq_len=256, dtype="float32"),
        prompt_len=dict(mix["prompt_len"], median=60, min=20, max=120),
        output_len=dict(mix["output_len"], median=12, min=4, max=40)))
    helpers._write(root, "limits/longdoc-tiny.json",
                   {"token_gap_mean": TINY_LIMIT})
    manifest["configs"].append({
        "name": "glm-tiny", "source": "test", "why": "t",
        "reduced": config["reduced"],
        "file": "benchmark/configs/glm-tiny.json"})
    manifest["workloads"].append({
        "name": "longdoc-tiny", "config": "glm-tiny", "chips": 1,
        "traffic": "longdoc-tiny", "why": "t"})
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append("longdoc-tiny")
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
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            harness.main(["--workload", "longdoc-tiny", "--seed",
                          "3000000019", "--seconds", "3", "--trace", "1"],
                         root=root)
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
    # the CPU's trace has no module line: the readers of programs' device
    # time (serve.prefill_mfu, kernel.serve_decode_roofline) find nothing
    # here and are tried on a recorded reduction below
    assert {"serve.mfu", "device.serve_idle_share", "moe.pairs_here_share",
            "moe.experts_touched_per_step", "dsa.keys_attended_share"} <= \
        set(metrics)
    # 8 of the router's 16 experts are held: about half of the pairs
    assert 30 < metrics["moe.pairs_here_share"]["value"] < 70
    # prompts of 20-120 against an indexer that keeps 16 keys
    assert 5 < metrics["dsa.keys_attended_share"]["value"] < 60
    window = next(e for e in rehearsal["earlier"] if e["event"] == "window")
    # prompts past the piece of 32 are prefilled in pieces
    assert window["engine.prefill_pieces"] > window["engine.prefills"]
    assert window["engine.dsa_keys_attended"] < window["engine.dsa_keys_live"]


def test_cell_refuses_a_bypassed_pick_and_the_int8_control(rehearsal,
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
        whole(model, w, ids, precision, fault="dense"))
    wrong = compare(run, samples)["token_gap_mean"]     # every key attended
    assert wrong > TINY_LIMIT


def test_prefill_mfu_reads_the_prefill_programs_of_the_trace():
    """Two prompts of 16,384 and 4,096 tokens take 5 programs; the trace
    holds 4 of them (one fell before the window) in 1.0 s of device time:
    the share is 4 x the mean program's FLOPs over the peak's second."""
    import types
    family = harness.load_module(ROOT, "families", "glm5_next")
    model = harness.load_json(ROOT, "benchmark", "configs",
                              CONFIG + ".json")["model"]
    obs = {"traced_prompt_lens": [16384, 4096]}
    run = types.SimpleNamespace(
        root=ROOT, obs=obs, model=model, chips=1, family=family,
        peaks={"bf16_flops_per_s": 197e12},
        trace={"module_seconds": {"jit_paged_prefill": (1.0, 4),
                                  "jit_paged_decode_chunk": (2.0, 9)}})
    per_program = (family.prefill_flops_per_program(model, obs))
    whole = sum(__import__("benchmark.flops_glm5_next", fromlist=["x"])
                .prompt_flops(model, obs, n) for n in (16384, 4096))
    assert per_program == pytest.approx(whole / 5)
    value = harness.read_metric(run, "serve.prefill_mfu")
    assert value == pytest.approx(100 * 4 * per_program / 197e12)
    assert 5 < value < 30


def test_readers_return_nothing_where_the_program_counts_nothing():
    """On the parent's program (no counters) the new metrics are left
    out, not raised: the readers see empty ``obs``."""
    import types
    family = harness.load_module(ROOT, "families", "glm5_next")
    run = types.SimpleNamespace(
        root=ROOT, obs={"traced_prompt_lens": [600]},
        trace={"op_seconds": {"fusion": 1.0}, "module_seconds": {}},
        model={}, peaks={}, chips=1, family=family)
    for name in ("dsa.keys_attended_share", "serve.prefill_mfu"):
        assert harness.read_metric(run, name) is None
    run.trace = None
    assert harness.read_metric(run, "serve.prefill_mfu") is None
