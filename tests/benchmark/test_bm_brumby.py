"""The configuration ``brumby-14b-pp8-5l-bf16`` and its cell, as far as the
CPU can say: the file against the catalog's row key by key, the counts of
parameters, the export, the floors against the cost model's walker, the
metric files over the accepted readers, the reference's named pieces, and
the cell's own pieces (weights script, check child, floors module,
``judge``) through ``run.py`` against a real server at the tests' small size.

``BENCHMARK.json`` names the cell by appended entries. Two accepted tests
fail for it by design, and a ``model_config`` PR edits neither:
``test_bm_manifest.py::test_every_cell_finds_its_files_and_reports_enough``
gains the case ``[brumby-pages-saturate]`` (lines 69-72: a cell's model a
network of ``reference/nets.py``), and
``test_bm_nemotron_h.py::test_the_manifest_names_the_cell_by_appended_entries_alone``
pins three configurations and cells and ``nem3-pages-saturate`` last in every
list. PERF.md section 7 has the lines for the ``benchmark`` PR; this file
holds the new entries to the same contract less those lines."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import cost, manifest as M, run as R
from benchmark.manifest import Cell
from benchmark.reference import brumby, brumby_floors, brumby_weights, leaves
from tensorflow_web_deploy_tpu.serving import costmodel

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL = "brumby-14b-pp8-5l-bf16", "brumby-pages-saturate"
CONFIG = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
# the catalog's row (model-configs guide, Brumby-14B-Base): every key of its ``config``
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40, "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
SEED = 2**31 + 91

# The tests' small size: hidden 64, two layers, 4 query over 2 key/value heads of 16, SwiGLU 96, 64 ids, chunk
# 16, sixteen answer steps; the gains are the published widths' own.
SMALL = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 96, "vocab_size": 64, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "chunk_size": 16,
         "patch": 8, "answer_steps": 16, "max_token_slots": 1024, "topk": 5, "dtype": "bfloat16",
         "gate_memory": [64, 4096], "leaf_gain": CONFIG["model"]["leaf_gain"]}
SMALL_DECODER = {k: v for k, v in SMALL.items() if k not in ("topk", "dtype", "leaf_gain", "gate_memory")}
SMALL_CONFIG = {
    "model": SMALL,
    "server_model": {"name": "brumby", "source": "native", "task": "generate", "decoder": SMALL_DECODER,
                     "dtype": "bfloat16", "topk": 5},
    "weights": CONFIG["weights"], "check": {**CONFIG["check"], "sample_images": 8, "limit_s": 200},
    "floors": CONFIG["floors"], "http_workers": 4,
    "server_flags": ["--http-workers", "4", "--canvas-buckets", "64,128", "--max-batch", "8"],
    # bfloat16 against the float32 reference at this size on the CPU reads about 0.008 / 0.03 an answer; answers
    # of other images read above 1. The chip's readings at the published widths are in PERF.md. The int8 share is
    # not judged at this size (8 images leave the least-squares share of a small direction to chance).
    "limits": {"logit_rms": 0.05, "logit_max": 0.5, "int8_weight_share": 1e9},
}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_the_file_holds_the_catalogs_row_key_by_key(key):
    """Every key of the row under the same name, but the one ``reduced``,
    which stands with the published value beside it; no width moved, in the
    model block and in what the server is told alike."""
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    if key == "num_hidden_layers":
        assert CONFIG[key] == CONFIG["model"][key] == 5 and CONFIG["model"]["published"][key] == CATALOG[key] == 40
        return
    assert CONFIG[key] == CATALOG[key] and type(CONFIG[key]) is type(CATALOG[key])
    for block in (CONFIG["model"], CONFIG["server_model"]["decoder"]):
        if key in block:
            assert block[key] == CATALOG[key], key


def test_the_model_block_states_the_cut_and_the_deployment():
    m, served = CONFIG["model"], CONFIG["server_model"]
    assert CONFIG["source"] == "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json"
    assert set(CONFIG["source_keys_used"]) <= set(CATALOG)
    assert (m["patch"], m["answer_steps"], m["topk"], m["dtype"], m["max_token_slots"], m["chunk_size"]) == \
        (32, 64, 5, "bfloat16", 16384, 128)
    assert (m["deployment_chips_per_layer"], m["deployment_pipeline_stages"], m["deployment_chips"]) == (1, 8, 8)
    assert "1 chip a layer, 8 stages, 8 chips" in CONFIG["cut"] and "3.22 G, 6.45 GB" in CONFIG["cut"]
    for silent in ("retention", "gate", "normalisation", "positions", "qk_norm", "state_dtype", "feature_map",
                   "gate_bias", "initial_draws", "vision_tower", "answer_steps", "leaf_gain"):
        assert CONFIG["assumed"][silent]
    assert "arXiv:2507.04239" in CONFIG["assumed"]["retention"] and "2025-10" in CONFIG["assumed"]["retention"]
    assert served["decoder"] == {k: m[k] for k in served["decoder"]} and served["task"] == "generate"
    widths = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "rms_norm_eps", "rope_theta")
    assert all(served["decoder"][k] == CATALOG[k] for k in widths)
    nem3 = json.loads((ROOT / "benchmark" / "configs" / "nemotron-3-nano-30b-ep2-pp4-13l-bf16.json").read_text())
    assert CONFIG["server_flags"] == nem3["server_flags"] and CONFIG["http_workers"] == nem3["http_workers"]


def test_the_published_count_of_parameters_and_the_cut():
    """The issue's arithmetic: 330,352,904 a layer, 1,555,824,640 embedding
    and head, 14.77 G published; held here 3,223,322,920, 6.45 GB in
    bfloat16 (ISSUE 39 says 3,223,317,800: it leaves out the final norm's 5,120)."""
    m, f = CONFIG["model"], brumby_floors
    layer = sum(int(np.prod(s)) for s in brumby.layer_leaves(m).values())
    assert layer == 330_352_904 and f.attn_params(m) == 62_914_560 and f.ffn_params(m) == 267_386_880
    whole = f.param_count(f.published(m), patch_embedding=False)
    assert whole == 40 * 330_352_904 + 1_555_824_640 + 5120 and round(whole / 1e9, 2) == 14.77
    held = sum(int(np.prod(s)) for s in brumby.all_leaves(m).values())
    assert held == f.param_count(m) == 3_223_322_920 == 3_223_317_800 + 5120
    assert round(2 * held / 1e9, 2) == 6.45


NEW_METRICS = ("retention_prefill_roofline", "retention_chunk_skip_share")
SILENT_HERE = {"top_program_row_share", "zero_pick_share", "mla_prefill_roofline", "held_pick_share",
               "held_expert_load_max_over_mean", "expert_gmm_roofline", "ssd_prefill_roofline", "gqa_prefill_roofline",
               "ssd_chunk_skip_share"}


def test_the_manifest_names_the_cell_by_appended_entries_alone():
    man = M.load_manifest()
    cell = M.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (NAME, "pages-saturate", 1)
    assert [c["name"] for c in man["configs"]][-1] == NAME and [w["name"] for w in man["workloads"]][-1] == CELL
    assert man["configs"][-1]["reduced"] == CONFIG["reduced"] and man["configs"][-1]["source"] == CONFIG["source"]
    assert man["configs"][-1]["file"] == f"benchmark/configs/{NAME}.json"
    assert {p["name"] for p in cell.end_to_end} == {"images_per_s", "setup_s"}
    reported = {p["name"] for p in cell.per_layer}
    assert {p["name"] for p in man["per_layer"]} - reported == SILENT_HERE and len(reported) == 35
    assert {"tokens_per_image", "token_pad_share", "cached_step_share", "step_mfu", *NEW_METRICS} <= reported
    assert [p["name"] for p in man["per_layer"][-2:]] == list(NEW_METRICS)
    for p in man["per_layer"][-2:]:
        assert p["workloads"] == [CELL] and p["unit"] == "%" and p["moves"] == "images_per_s"
        assert p["better"] == "higher" and p["layer"] == "model forward"
    for e in man["end_to_end"] + man["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["workloads"][-1] == CELL and e["workloads"].count(CELL) == 1, e["name"]
    # the accepted cells report what they reported: 31, 37 and 39
    assert [len(M.load_cell(w).per_layer) for w in ("iv3-bigalbums-saturate", "lcfo-pages-saturate",
                                                   "nem3-pages-saturate")] == [31, 37, 39]
    named = M.named(cell.config)
    assert (named.sample_images, named.answer_steps, named.limit_s) == (16, 64, 300.0)
    assert [named.weights.name, named.check.name, named.floors.name] == \
        ["brumby_weights.py", "brumby_check.py", "brumby_floors.py"]
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 0
    for entry in man["configs"] + man["workloads"]:
        assert len(entry["why"]) <= 200 and M.NAME.match(entry["name"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 << 10


@pytest.mark.parametrize("metric,reader,args", [
    ("retention_prefill_roofline", "kernel_roofline", {"match": "retention_prefill", "program": "jit_serve"}),
    ("retention_chunk_skip_share", "stats_ratio", {"num": "batcher.lifecycle.retention_chunks_skipped_total",
                                                   "den": "batcher.lifecycle.retention_chunks_total", "scale": 100.0})])
def test_a_metric_of_the_cell_is_a_file_over_an_accepted_reader(metric, reader, args):
    """A kernel traced at its floor reads 100; a program without the kernel
    or the counter (the parent's) reads nothing and does not raise."""
    spec = json.loads((ROOT / "benchmark" / "metrics" / f"{metric}.json").read_text())
    assert spec == {"reader": reader, "args": args}
    read, _ = M.load_reader(metric)
    row = {"canvas": 1024, "batch_bucket": 16, "batches": 3, "rows_real": 40, "rows_dispatched": 48,
           "px_real": 40 * 768 * 1024}
    ctx = SimpleNamespace(
        before={"batcher": {"lifecycle": {"retention_chunks_total": 10.0, "retention_chunks_skipped_total": 4.0},
                            "builders": {"padding": {"1024x16": dict.fromkeys(row, 0)}}}},
        after={"batcher": {"lifecycle": {"retention_chunks_total": 110.0, "retention_chunks_skipped_total": 29.0},
                           "builders": {"padding": {"1024x16": row}}}},
        config=CONFIG, device={"kind": "TPU v5 lite"}, trace={"programs": [["jit_serve", 1.0, 3]], "ops": []})
    if reader == "stats_ratio":
        assert read(ctx, **args) == 25.0
        ctx.after = ctx.before = {"batcher": {"lifecycle": {}}}          # the parent's program: no such counter
        assert read(ctx, **args) is None
        return
    flops, moved = brumby_floors.kernel_floor(CONFIG["model"], row, args["match"])
    floor_s = max(flops / 197e12, moved / 819e9)
    ctx.trace["ops"] = [[f"{args['match']}.3 f32[16,8,128,8704]", 3 * floor_s, 15], ["fusion.1", 0.5, 2]]
    assert read(ctx, **args) == pytest.approx(100.0)                    # traced at its floor
    ctx.trace["ops"] = [["fusion.1", 0.5, 2]]                          # the parent's program: no such kernel
    assert read(ctx, **args) is None
    ctx.config = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-omni-ep32-4l-bf16.json").read_text())
    ctx.trace["ops"] = [[f"{args['match']}.3", 1.0, 1]]
    assert read(ctx, **args) is None                                    # floors that know no such kernel


def test_the_floors_count_what_no_implementation_can_avoid():
    m, f = CONFIG["model"], brumby_floors
    row = lambda t, rows=4, batches=1: {"canvas": 2048, "batch_bucket": 4, "batches": batches, "rows_real": rows,
                                        "rows_dispatched": rows, "px_real": rows * t * 1024}
    assert cost.load_floors(CONFIG).__name__.endswith("brumby_floors")
    assert f.features(m) == 8256 and f.state_values(m) == 8 * 8256 * 129
    # the issue's 51.8 M multiply-adds a token in the chunked form, 330.3 M in the matrices
    assert f.chunked_macs_per_token(m) == 40 * (8256 * 128 + 64 * 256) + 8 * 8256 * 128
    assert round(f.chunked_macs_per_token(m) / 1e6, 1) == 51.4 and round(f.matrix_macs_per_token(m) / 1e6, 1) == 330.3
    # at the pages' lengths the attention form is the lesser: 24.2 M at 3,072 tokens, 12.4 M at 768
    assert f.retention_macs_per_token(m, 3072) == f.attention_macs_per_token(m, 3072) == 40 * 1536 * 256 + 8 * 8256 * 128
    assert f.retention_macs_per_token(m, 1e5) == f.chunked_macs_per_token(m)
    one = f.image_flops(m, row(768))
    assert f.image_flops(m, row(768, rows=3, batches=2)) == one           # a real image's, whatever the batch
    core, moved = f.kernel_floor(m, row(3072), "retention_prefill")
    assert core == 2 * 5 * 4 * 3072 * f.attention_macs_per_token(m, 3072)
    assert moved == 5 * 4 * (3072 * 2 * 96 * 128 + 4 * 8 * 8256 * 129)
    step, moved = f.kernel_floor(m, row(3072), "retention_step")
    assert step == 2 * 63 * 5 * 4 * 8256 * 128 * 48 and moved == 63 * 5 * 4 * (8 * 8 * 8256 * 129 + 2 * 96 * 128)
    assert f.kernel_floor(m, row(3072), "mla_prefill") is None
    # a step's bytes at 16 rows: the states' 5.45 GB read and written against 4.86 GB of weights and head
    states = 16 * 5 * 2 * 4 * f.state_values(m)
    weights = 2 * (f.dense_params(m) - 3072 * 5120)
    assert round(states / 1e9, 2) == 5.45 and round(weights / 1e9, 2) == 4.86
    # real tokens only: the same row in a larger canvas has the same floor, for every kernel
    for kernel in ("retention_prefill", "retention_step"):
        assert f.kernel_floor(m, dict(row(768), canvas=1024), kernel) == f.kernel_floor(m, row(768), kernel)


def test_the_cost_models_walker_equals_the_benchmarks_floors():
    m, decoder, f = CONFIG["model"], CONFIG["server_model"]["decoder"], brumby_floors
    c = costmodel.decoder_cost(decoder, "brumby")
    assert costmodel.decoder_cost(decoder) == c                          # found by the sizes alone, too
    assert c["layer_params"] == f.matrix_macs_per_token(m) and c["features"] == f.features(m)
    assert c["param_count"] == f.param_count(m) == sum(int(np.prod(s)) for s in brumby.all_leaves(m).values())
    assert c["dense_params"] == f.dense_params(m) and c["chunked_macs_per_token"] == f.chunked_macs_per_token(m)
    assert c["step_macs_per_token"] == f.step_macs(m)
    for t in (768.0, 1728.0, 3072.0, 40000.0):
        assert c["attention_macs_per_token_sq"] * t + c["state_macs_per_token"] == f.attention_macs_per_token(m, t)
        row = {"batches": 1, "rows_real": 2, "px_real": 2 * t * 1024}
        assert costmodel.decoder_image_flops(decoder, t, "brumby") == f.image_flops(m, row)


def test_the_reference_names_its_pieces():
    assert brumby.CONTROLS == ("fp8", "int8", "no_state_carry", "no_norm", "no_gate", "state_bf16")
    for piece in ("layer_leaves", "outer_leaves", "all_leaves", "make_leaf", "make_block", "projections",
                  "attention_form", "state_form", "prefill_layer", "step_layer", "recurrent", "layer",
                  "embed", "head_probs", "forward"):
        assert callable(getattr(brumby, piece)), piece
    assert "attention form" in brumby.__doc__ and "arXiv:2507.04239" in brumby.__doc__


def test_the_export_is_made_block_by_block_and_read_back_leaf_by_leaf(tmp_path, monkeypatch):
    from benchmark.reference import nemotron_h

    monkeypatch.setattr(nemotron_h, "BLOCK_VALUES", 2048)                # an FFN matrix 64 x 96 in four blocks of 21 rows
    m = dict(SMALL, dtype="float32")
    shapes = brumby.all_leaves(m)
    assert len(nemotron_h.blocks(shapes["layer1/ffn/w_up"])) == 4 and len(nemotron_h.blocks(shapes["final_norm"])) == 1
    brumby_weights.write_export(m, SEED, tmp_path / "export", threads=4)
    manifest = json.loads((tmp_path / "export" / "manifest.json").read_text())
    assert manifest["dtype"] == "float32" and set(manifest["leaves"]) == set(shapes) and len(shapes) == 4 + 2 * 13
    for name in shapes:
        back = brumby_weights.read_leaf(m, tmp_path / "export", name)
        assert np.array_equal(back, brumby.make_leaf(SEED, name, shapes[name], m)), name
    block = brumby.make_block(SEED, "layer1/ffn/w_up", shapes["layer1/ffn/w_up"], m, 1)
    r0, r1 = nemotron_h.blocks(shapes["layer1/ffn/w_up"])[1]
    assert np.array_equal(block, leaves.normal(SEED, "layer1/ffn/w_up#1", (r1 - r0, 96), 1 / 8))
    inode = (tmp_path / "export" / "head").stat().st_ino
    brumby_weights.write_export(m, SEED + 1, tmp_path / "export", threads=4)
    assert (tmp_path / "export" / "head").stat().st_ino == inode
    assert not np.array_equal(brumby_weights.read_leaf(m, tmp_path / "export", "head"),
                              brumby.make_leaf(SEED, "head", shapes["head"], m))
    # the gate's memories are where the configuration's ``assumed`` says, the gains where leaf_gain says
    memory = 1 + np.exp(brumby.make_leaf(SEED, "layer0/attn/b_g", (4096,), m))
    assert 64 <= memory.min() < 66 and 4000 < memory.max() <= 4096
    assert brumby.std("layer0/attn/w_o", (64, 64), m) == pytest.approx(2.0 / 8)
    assert brumby.std("layer0/attn/w_g", (64, 2), m) == pytest.approx(0.5 / 8)
    assert brumby.std("embed/token", (64, 64), m) == 1.0


def test_the_cell_through_run_py_on_the_cpu_at_the_small_size(tmp_path, monkeypatch):
    """The named weights script, a real server on the CPU (the decoder at the
    tests' size through ``--ckpt``), ``judge`` on its sixteen ``steps``, the
    named check child on the window's own answers; then the same outcomes
    with the answers moved to other images say not correct."""
    monkeypatch.setattr(R, "work_dir", lambda cell: tmp_path)
    e2e = ({"name": "images_per_s", "unit": "images/s"}, {"name": "setup_s", "unit": "s"})
    cell = Cell("tiny-pages-brumby", 1, "small", SMALL_CONFIG, "tiny-photos",
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
    assert moved["answer_steps_cached_total"] == 15 * images
    assert moved["token_slots_total"] - moved["tokens_real_total"] == moved["token_slots_pad_total"] > 0
    assert 0 < moved["retention_chunks_skipped_total"] < moved["retention_chunks_total"]
    assert "picks_total" not in life(ctx.after)                          # no experts: the counter is left out
    rotated = [o.answers for o in ok[1:]] + [ok[0].answers]
    for o, a in zip(ok, rotated):
        o.answers = a
    tampered = R.report(ctx)
    assert tampered["correct"] is False
    assert tampered["compared"]["logit_rms"]["value"] > 3 * line["compared"]["logit_rms"]["value"]


@pytest.mark.parametrize("control", ["no_state_carry", "no_norm"])
def test_the_check_child_calls_a_control_not_correct_at_the_small_size(control, monkeypatch):
    """The child itself with ``control`` set: the reference computed that
    way, greedily, stands in for the served answers."""
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

