"""The configuration ``nemotron-3-nano-30b-ep2-pp4-13l-bf16`` and its cell, as
far as the CPU can say: the file against the catalog's row key by key, the
counts of parameters, the export, the floors, the metric files over the
accepted readers, and the cell's own pieces (weights script, check child,
floors module, ``judge``) through ``run.py`` against a real server at the
tests' small size.

``BENCHMARK.json`` names the cell by appended entries, and two assertions of
accepted tests fail for it by design: ``test_bm_manifest.py`` lines 69-72 (a
cell's model a network of ``reference/nets.py``) as a new case, and
``test_bm_longcat.py`` lines 102-103, 138-145 (PR 32's entries the last of
their lists, two configurations and two cells). A ``model_config`` PR may edit
no file the benchmark has; PERF.md section 7 has the lines for the
``benchmark`` PR that may, and this file holds the new entries to the same
contract less those lines."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import cost, manifest as M, run as R
from benchmark.manifest import Cell
from benchmark.reference import leaves, nemotron_h, nemotron_h_floors, nemotron_h_weights

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL = "nemotron-3-nano-30b-ep2-pp4-13l-bf16", "nem3-pages-saturate"
CONFIG = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
# the catalog's row (model-configs guide, NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): every key of its ``config``
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
REDUCED = {"num_hidden_layers": (13, 52), "hybrid_override_pattern": ("EMEMEM*EMEMEM", CATALOG["hybrid_override_pattern"]),
           "n_routed_experts": (64, 128), "vocab_size": (65536, 131072)}
SEED = 2**31 + 77

# The tests' small size: hidden 64, the five layers EM*ME, 4 Mamba heads of 16 with state 32 in 2 groups, chunk
# 16, 4 query over 2 key/value heads of 16, 8 of 16 experts of width 32 held, 64 ids; the gains are the
# published widths' own.
SMALL = {"hidden_size": 64, "hybrid_override_pattern": "EM*ME", "mamba_num_heads": 4, "mamba_head_dim": 16,
         "ssm_state_size": 32, "n_groups": 2, "conv_kernel": 4, "chunk_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 64, "n_routed_experts": 16, "num_experts_per_tok": 4,
         "routed_scaling_factor": 2.5, "experts_held": 8, "experts_held_first": 0, "vocab_size": 64,
         "layer_norm_epsilon": 1e-5, "patch": 8, "answer_steps": 16, "max_token_slots": 1024,
         "norm_topk_prob": True, "topk": 5, "dtype": "bfloat16", "leaf_gain": CONFIG["model"]["leaf_gain"]}
SMALL_DECODER = {k: v for k, v in SMALL.items() if k not in ("topk", "dtype", "leaf_gain", "norm_topk_prob")}
SMALL_CONFIG = {
    "model": SMALL,
    "server_model": {"name": "nemotron_h", "source": "native", "task": "generate", "decoder": SMALL_DECODER,
                     "dtype": "bfloat16", "topk": 5},
    "weights": CONFIG["weights"], "check": {**CONFIG["check"], "sample_images": 8, "limit_s": 200},
    "floors": CONFIG["floors"], "http_workers": 4,
    "server_flags": ["--http-workers", "4", "--canvas-buckets", "64,128", "--max-batch", "8"],
    # bfloat16 against the float32 reference at this size on the CPU reads 0.012 / 0.06 an answer (five layers of
    # hidden 64 under the published widths' gains); answers of other images read above 0.3. The chip's readings at
    # the published widths are in PERF.md. The int8 share is not judged at this size (8 images leave the
    # least-squares share of a small direction to chance).
    "limits": {"logit_rms": 0.05, "logit_max": 0.5, "int8_weight_share": 1e9},
}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_the_file_holds_the_catalogs_row_key_by_key(key):
    """Every key of the row under the same name, but the four ``reduced``
    lists, which stand with the published value beside them; no width moved,
    in the model block and in what the server is told alike."""
    assert CONFIG["reduced"] == list(REDUCED)
    if key in REDUCED:
        held, published = REDUCED[key]
        assert CONFIG[key] == held and CATALOG[key] == CONFIG["model"]["published"][key] == published
        return
    assert CONFIG[key] == CATALOG[key] and type(CONFIG[key]) is type(CATALOG[key])
    for block in (CONFIG["model"], CONFIG["server_model"]["decoder"]):
        if key in block:
            assert block[key] == CATALOG[key], key


def test_the_model_block_states_the_cut_and_the_deployment():
    m, served = CONFIG["model"], CONFIG["server_model"]
    assert CONFIG["source"] == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
    assert set(CONFIG["source_keys_used"]) <= set(CATALOG)
    assert (m["patch"], m["answer_steps"], m["topk"], m["dtype"], m["max_token_slots"]) == (32, 16, 5, "bfloat16", 16384)
    assert (m["deployment_chips_per_layer"], m["deployment_pipeline_stages"], m["deployment_chips"]) == (2, 4, 8)
    # the pattern held is layers 13-25 of the published one; the router keeps its 128 outputs, 64 experts live here
    assert CATALOG["hybrid_override_pattern"][13:26] == m["hybrid_override_pattern"] == "EMEMEM*EMEMEM"
    assert (m["n_routed_experts"], m["experts_held"], m["experts_held_first"], m["vocab_size"]) == (128, 64, 0, 65536)
    assert "2 chips share each layer, 4 stages, 8 chips" in CONFIG["cut"] and "4.57 G, 9.14 GB" in CONFIG["cut"]
    for silent in ("positions", "e_score_correction_bias", "state_dtype", "initial_draws", "vision_tower",
                   "answer_steps", "leaf_gain"):
        assert CONFIG["assumed"][silent]
    assert "no positional encoding" in CONFIG["assumed"]["positions"]
    assert served["decoder"] == {k: m[k] for k in served["decoder"]} and served["task"] == "generate"
    # every width a layer is made of is in what the server is told, as published
    widths = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
              "chunk_size", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok", "routed_scaling_factor", "n_routed_experts")
    assert all(served["decoder"][k] == CATALOG[k] for k in widths)
    longcat = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-omni-ep32-4l-bf16.json").read_text())
    assert CONFIG["server_flags"] == longcat["server_flags"] and CONFIG["http_workers"] == longcat["http_workers"]


def test_the_published_count_of_parameters_and_the_cuts():
    """The issue's arithmetic: 38.74 M a Mamba layer, 23.40 M an attention
    layer, 1,297.5 M an expert layer, 704.6 M embedding and head: 31.58 G
    published; 4.57 G held here, 9.14 GB in bfloat16."""
    m, f = CONFIG["model"], nemotron_h_floors
    layer = lambda kind, model=m: sum(int(np.prod(s)) for s in nemotron_h.layer_leaves(model, kind).values())
    pub = f.published(m)
    assert (pub["hybrid_override_pattern"], pub["experts_held"], pub["vocab_size"]) == \
        (CATALOG["hybrid_override_pattern"], 128, 131072)
    assert layer("M") == 38_744_896 and layer("*") == 23_399_040 and layer("E", pub) == 1_297_468_160
    assert f.layers(pub) == {"M": 23, "*": 6, "E": 23} and len(pub["hybrid_override_pattern"]) == 52
    whole = f.param_count(pub, patch_embedding=False)
    assert whole == 23 * 38_744_896 + 6 * 23_399_040 + 23 * 1_297_468_160 + 2 * 131072 * 2688 + 2688
    assert round(whole / 1e9, 2) == 31.58
    held = sum(int(np.prod(s)) for s in nemotron_h.all_leaves(m).values())
    assert held == f.param_count(m) == 4_569_762_432 and round(held / 1e9, 2) == 4.57 and round(2 * held / 1e9, 2) == 9.14
    assert layer("E") == 658_885_376                                     # 64 of 128 experts, the router and the shared expert whole


NEW_METRICS = ("ssd_prefill_roofline", "gqa_prefill_roofline", "ssd_chunk_skip_share", "cached_step_share")
SILENT_HERE = {"top_program_row_share", "zero_pick_share", "mla_prefill_roofline"}


def test_the_manifest_names_the_cell_by_appended_entries_alone():
    """What ``test_bm_manifest.py`` and ``test_bm_longcat.py`` assert of a
    cell and of the entries, less their lines that know one classifier and
    two configurations."""
    man = M.load_manifest()
    cell = M.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (NAME, "pages-saturate", 1)
    assert cell.traffic_path == ROOT / "benchmark" / "traffic" / "pages-saturate.json" and cell.traffic_path.is_file()
    assert [c["name"] for c in man["configs"]] == ["iv3-299-bf16-4k", "longcat-flash-omni-ep32-4l-bf16", NAME]
    assert [w["name"] for w in man["workloads"]] == ["iv3-bigalbums-saturate", "lcfo-pages-saturate", CELL]
    assert man["configs"][-1]["reduced"] == CONFIG["reduced"] and man["configs"][-1]["source"] == CONFIG["source"]
    assert man["configs"][-1]["file"] == f"benchmark/configs/{NAME}.json"
    assert {"model", "server_model", "server_flags", "http_workers", "limits", "deployment"} <= set(cell.config)
    assert cell.config["server_flags"][:2] == ["--http-workers", str(cell.config["http_workers"])]
    served, model = cell.config["server_model"], cell.config["model"]
    assert (served["dtype"], served["topk"], served["decoder"]["answer_steps"]) == \
        (model["dtype"], model["topk"], model["answer_steps"])
    e2e = {e["name"] for e in cell.end_to_end}
    assert e2e == {"images_per_s", "setup_s"}
    reported = {p["name"] for p in cell.per_layer}
    assert {p["name"] for p in man["per_layer"]} - reported == SILENT_HERE and len(reported) == 39
    assert {"expert_gmm_roofline", "held_pick_share", "held_expert_load_max_over_mean", "tokens_per_image",
            "token_pad_share", "step_mfu", "serve_roofline", *NEW_METRICS} <= reported
    assert [p["name"] for p in man["per_layer"][-4:]] == list(NEW_METRICS)
    for p in man["per_layer"][-4:]:
        assert p["workloads"] == [CELL] and p["unit"] == "%" and p["moves"] == "images_per_s"
        assert p["better"] == "higher" and p["layer"] == "model forward"
    for e in man["end_to_end"] + man["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["workloads"][-1] == CELL and e["workloads"].count(CELL) == 1, e["name"]
    # the accepted cells report what they reported: 31 and 37
    assert [len(M.load_cell(w).per_layer) for w in ("iv3-bigalbums-saturate", "lcfo-pages-saturate")] == [31, 37]
    named = M.named(cell.config)
    assert (named.sample_images, named.answer_steps, named.limit_s) == (16, 16, 300.0)
    assert [named.weights.name, named.check.name, named.floors.name] == \
        ["nemotron_h_weights.py", "nemotron_h_check.py", "nemotron_h_floors.py"]
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] == 1 and len(w["why"]) <= 200
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16 and all(M.NAME.match(k) for k in c["reduced"])
    for p in man["per_layer"]:
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert M.NAME.match(p["name"]) and M.UNIT.match(p["unit"])
    names = [x["name"] for x in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    for p in cell.per_layer:
        assert p["moves"] in e2e and callable(M.load_reader(p["name"])[0])
    assert {p["layer"] for p in man["per_layer"][-4:]} <= {p["layer"] for p in man["per_layer"][:-4]}


@pytest.mark.parametrize("metric,reader,args", [
    ("ssd_prefill_roofline", "kernel_roofline", {"match": "ssd_prefill", "program": "jit_serve"}),
    ("gqa_prefill_roofline", "kernel_roofline", {"match": "gqa_prefill", "program": "jit_serve"}),
    ("ssd_chunk_skip_share", "stats_ratio", {"num": "batcher.lifecycle.ssd_chunks_skipped_total",
                                             "den": "batcher.lifecycle.ssd_chunks_total", "scale": 100.0}),
    ("cached_step_share", "stats_ratio", {"num": "batcher.lifecycle.answer_steps_cached_total",
                                          "den": "batcher.lifecycle.answer_steps_total", "scale": 100.0}),
    ("expert_gmm_roofline", "kernel_roofline", {"match": "expert_gmm", "program": "jit_serve"})])
def test_a_metric_of_the_cell_is_a_file_over_an_accepted_reader(metric, reader, args):
    """A kernel traced at its floor reads 100; a program without the kernel
    or the counter (the parent's) reads nothing and does not raise."""
    spec = json.loads((ROOT / "benchmark" / "metrics" / f"{metric}.json").read_text())
    assert spec == {"reader": reader, "args": args}
    read, _ = M.load_reader(metric)
    row = {"canvas": 2048, "batch_bucket": 4, "batches": 3, "rows_real": 12, "rows_dispatched": 12,
           "px_real": 12 * 3072 * 1024}
    ctx = SimpleNamespace(
        before={"batcher": {"lifecycle": {"ssd_chunks_total": 10.0, "ssd_chunks_skipped_total": 4.0,
                                          "answer_steps_total": 0.0, "answer_steps_cached_total": 0.0},
                            "builders": {"padding": {"2048x4": dict.fromkeys(row, 0)}}}},
        after={"batcher": {"lifecycle": {"ssd_chunks_total": 110.0, "ssd_chunks_skipped_total": 29.0,
                                         "answer_steps_total": 640.0, "answer_steps_cached_total": 600.0},
                           "builders": {"padding": {"2048x4": row}}}},
        config=CONFIG, device={"kind": "TPU v5 lite"}, trace={"programs": [["jit_serve", 1.0, 2]], "ops": []})
    if reader == "stats_ratio":
        assert read(ctx, **args) == {"ssd_chunk_skip_share": 25.0, "cached_step_share": 93.75}[metric]
        ctx.after = ctx.before = {"batcher": {"lifecycle": {}}}          # the parent's program: no such counter
        assert read(ctx, **args) is None
        return
    flops, moved = nemotron_h_floors.kernel_floor(CONFIG["model"], row, args["match"])
    floor_s = max(flops / 197e12, moved / 819e9)
    ctx.trace["ops"] = [[f"{args['match']}.3 bf16[4,4096,4096]", 2 * floor_s, 12], ["fusion.1", 0.5, 2]]
    assert read(ctx, **args) == pytest.approx(100.0)                    # traced at its floor
    ctx.trace["ops"] = [["fusion.1", 0.5, 2]]                          # the parent's program: no such kernel
    assert read(ctx, **args) is None
    if metric != "expert_gmm_roofline":
        ctx.config = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-omni-ep32-4l-bf16.json").read_text())
        ctx.trace["ops"] = [[f"{args['match']}.3", 1.0, 1]]
        assert read(ctx, **args) is None                                # floors that know no such kernel


def test_the_floors_count_what_no_implementation_can_avoid():
    m, f = CONFIG["model"], nemotron_h_floors
    row = lambda t, rows=4, batches=1: {"canvas": 2048, "batch_bucket": 4, "batches": batches, "rows_real": rows,
                                        "rows_dispatched": rows, "px_real": rows * t * 1024}
    assert cost.load_floors(CONFIG).__name__.endswith("nemotron_h_floors")
    assert f.tokens(m, row(768)) == 768.0
    # per real token the chip's layers touch about 557 M parameters: 232 M the Mamba mixers', 301 M the expert
    # layers' (router, shared expert, 3 held picks), 23 M the attention's
    assert f.matrix_macs_per_token(m) == 6 * 38_707_200 + 23_396_352 + 6 * (344_064 + 19_955_712 + 3 * 9_977_856)
    assert round(f.matrix_macs_per_token(m) / 1e6) == 557
    one = f.image_flops(m, row(768))
    assert f.image_flops(m, row(768, rows=3, batches=2)) == one          # a real image's, whatever the batch
    # the mix's mean image: 2.17 TFLOP (the issue's 2.1 of matrices; the scan, the core and the steps on top)
    mix_mean = sum(n * f.image_flops(m, row(t)) for t, n in ((768, 3), (1728, 4), (3072, 3))) / 10
    assert mix_mean == pytest.approx(2.17e12, rel=0.01)
    # bytes: every parameter outside the experts once a step, sixteen steps a call
    few = f.serve_bytes(m, {"canvas": 1024, "batches": 1, "rows_real": 1, "px_real": 1024})
    assert few > 16 * 2 * (f.dense_params(m) - 3072 * 2688) and few / 819e9 == pytest.approx(0.0292, rel=0.01)
    t, which = cost.serve_floor_s(f, m, row(3072), 197e12, 819e9)
    assert which == "compute" and t == pytest.approx(4 * f.image_flops(m, row(3072)) / 197e12)
    assert f.kernel_floor(m, row(3072), "mla_prefill") is None
    scan, moved = f.kernel_floor(m, row(3072), "ssd_prefill")
    assert scan == 2 * 6 * 4 * 3072 * 1_376_256 and moved > 6 * 4 * 3072 * 2 * (2 * 4096 + 2048)
    core, moved = f.kernel_floor(m, row(3072), "gqa_prefill")
    assert core == pytest.approx(2 * 1 * 4 * 32 * 3072 ** 2 / 2 * 256) and moved == 4 * 3072 * 2 * 128 * 68
    gmm, moved = f.kernel_floor(m, row(3072), "expert_gmm")
    assert gmm == 2 * 6 * 3 * 4 * (3072 + 15) * 9_977_856
    # real tokens only: the same row in a larger canvas has the same floor, for every kernel
    for kernel in ("ssd_prefill", "gqa_prefill", "expert_gmm"):
        assert f.kernel_floor(m, dict(row(768), canvas=1024), kernel) == f.kernel_floor(m, row(768), kernel)


def test_the_export_is_made_block_by_block_and_read_back_leaf_by_leaf(tmp_path, monkeypatch):
    """A leaf over the block size is drawn in row blocks of their own
    streams, whichever thread makes them; the export holds every leaf as
    ``make_leaf`` gives it, and is written in place over one of the same shapes."""
    monkeypatch.setattr(nemotron_h, "BLOCK_VALUES", 2048)                # a Mamba w_in 64 x 260 in ten blocks, the head in two
    m = dict(SMALL, dtype="float32")
    shapes = nemotron_h.all_leaves(m)
    assert len(nemotron_h.blocks(shapes["layer1/mixer/w_in"])) == 10 and len(nemotron_h.blocks(shapes["final_norm"])) == 1
    nemotron_h_weights.write_export(m, SEED, tmp_path / "export", threads=4)
    manifest = json.loads((tmp_path / "export" / "manifest.json").read_text())
    assert manifest["dtype"] == "float32" and set(manifest["leaves"]) == set(shapes)
    assert len(shapes) == 4 + 2 * 9 + 5 + 2 * (5 + 2 * 8)
    for name in shapes:
        back = nemotron_h_weights.read_leaf(m, tmp_path / "export", name)
        assert np.array_equal(back, nemotron_h.make_leaf(SEED, name, shapes[name], m)), name
    block = nemotron_h.make_block(SEED, "layer1/mixer/w_in", shapes["layer1/mixer/w_in"], m, 3)
    r0, r1 = nemotron_h.blocks(shapes["layer1/mixer/w_in"])[3]
    want = leaves.normal(SEED, "layer1/mixer/w_in#3", (r1 - r0, 260), nemotron_h.std("layer1/mixer/w_in", (64, 260), m))
    assert np.array_equal(block, want)
    inode = (tmp_path / "export" / "head").stat().st_ino
    nemotron_h_weights.write_export(m, SEED + 1, tmp_path / "export", threads=4)
    assert (tmp_path / "export" / "head").stat().st_ino == inode
    assert np.array_equal(nemotron_h_weights.read_leaf(m, tmp_path / "export", "head"),
                          nemotron_h.make_leaf(SEED + 1, "head", shapes["head"], m))
    # the steps, the decays and the gains are where the configuration's ``assumed`` says
    big = dict(m, mamba_num_heads=4096)
    dt = np.log1p(np.exp(nemotron_h.make_leaf(SEED, "layer1/mixer/dt_bias", (4096,), big)))
    assert 0.001 * 0.999 <= dt.min() < 0.0012 and 0.09 < dt.max() <= 0.1 * 1.001
    a = np.exp(nemotron_h.make_leaf(SEED, "layer1/mixer/a_log", (4096,), big))
    assert 1.0 <= a.min() < 1.1 and 15.9 < a.max() <= 16.0
    assert not nemotron_h.make_leaf(SEED, "layer0/router_bias", (16,), m).any()
    assert nemotron_h.std("layer0/expert3/w_down", (32, 64), m) == pytest.approx(m["leaf_gain"]["expert_w_down"] / np.sqrt(32))
    assert nemotron_h.std("layer0/shared/w_down", (64, 64), m) == pytest.approx(m["leaf_gain"]["shared_w_down"] / 8)
    assert nemotron_h.std("layer1/mixer/w_out", (64, 64), m) == pytest.approx(1 / 8)
    assert nemotron_h.std("embed/token", (64, 64), m) == 1.0


def test_the_cell_through_run_py_on_the_cpu_at_the_small_size(tmp_path, monkeypatch):
    """The named weights script, a real server on the CPU (the decoder at the
    tests' size through ``--ckpt``), ``judge`` on its sixteen ``steps``, the
    named check child on the window's own answers; then the same outcomes
    with the answers moved to other images say not correct."""
    monkeypatch.setattr(R, "work_dir", lambda cell: tmp_path)
    e2e = ({"name": "images_per_s", "unit": "images/s"}, {"name": "setup_s", "unit": "s"})
    cell = Cell("tiny-pages-nem3", 1, "small", SMALL_CONFIG, "tiny-photos",
                ROOT / "tests" / "benchmark" / "data" / "tiny-photos.json", e2e, ())
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    ctx = R.drive(cell, SEED, 3.0, False, require_platform=None,
                  extra_flags=("--aot-cache-dir", str(tmp_path / "aot_cache")), env=env)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    line = R.report(ctx)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] == len(ctx.outcomes) > 10
    ok = [o for o in ctx.outcomes if o.answers is not None]
    assert all(len(a) == 16 and all(len(step) == 5 for step in a) for o in ok for a in o.answers)
    life = lambda s: s["batcher"]["lifecycle"]
    moved = {k: life(ctx.after)[k] - life(ctx.before)[k] for k in life(ctx.after) if k.endswith("_total")
             and isinstance(life(ctx.after)[k], float)}
    images = sum(o.images for o in ok)
    assert moved["images_total"] == images and moved["answer_steps_total"] == 16 * images
    assert moved["answer_steps_cached_total"] == 15 * images             # cached_step_share 93.75
    assert moved["token_slots_total"] - moved["tokens_real_total"] == moved["token_slots_pad_total"] > 0
    assert 0 < moved["ssd_chunks_skipped_total"] < moved["ssd_chunks_total"]
    assert moved["picks_total"] == 2 * 4 * (moved["tokens_real_total"] + 15 * images)
    assert 0.35 < moved["held_picks_total"] / moved["picks_total"] < 0.65
    assert "zero_picks_total" not in life(ctx.after)                     # no zero experts: the counter is left out
    rotated = [o.answers for o in ok[1:]] + [ok[0].answers]
    for o, a in zip(ok, rotated):
        o.answers = a
    tampered = R.report(ctx)
    assert tampered["correct"] is False
    assert tampered["compared"]["logit_rms"]["value"] > 3 * line["compared"]["logit_rms"]["value"]


@pytest.mark.parametrize("control", ["no_state_carry", "no_shared_expert"])
def test_the_check_child_calls_a_control_not_correct_at_the_small_size(control, monkeypatch):
    """The child itself with ``control`` set: the reference computed that
    way, greedily and padded to the full fifteen ids, stands in for the
    served answers (a part left out, or the steps from a zero state, reads
    far over the small size's limits)."""
    import base64
    import io
    from PIL import Image

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rs = np.random.default_rng(3)
    items = []
    for hw in ((64, 48), (40, 56)):
        buf = io.BytesIO()
        Image.fromarray(rs.integers(0, 256, (*hw, 3), dtype=np.uint8)).save(buf, "JPEG", quality=88)
        items.append({"jpeg": base64.b64encode(buf.getvalue()).decode(), "served": []})
    out = R.check_child(SMALL_CONFIG, SEED, items, control, 200.0)
    assert out["correct"] is False and out["images"] == 2
    assert out["compared"]["logit_rms"]["value"] > SMALL_CONFIG["limits"]["logit_rms"]
