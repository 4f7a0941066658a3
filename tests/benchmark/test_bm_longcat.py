"""The configuration ``longcat-flash-omni-ep32-4l-bf16`` and its cell, as far
as the CPU can say: the file against the catalog's row, the reference's
parts against each other, the export, the floors, the new reader, and the
cell's own pieces (weights script, check child, floors module, ``judge``)
through ``run.py`` against a real server at the tests' small size.

``BENCHMARK.json`` names the cell, and three assertions of the accepted
tests here, which hold what was true of one convolutional classifier, fail
for it by design (``reduced == []``, a cell's model a network of
``reference/nets.py``, every mix phone photos of 3-12 MP), and a fourth for
the accepted cell (a lifecycle metric's ``workloads`` the one cell): a
``model_config`` PR may edit no file the benchmark has, and PERF.md section 7
has the four lines for the ``benchmark`` PR that may."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cost, manifest as M, run as R
from benchmark.manifest import Cell
from benchmark.reference import leaves, longcat, longcat_floors, longcat_weights

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL = "longcat-flash-omni-ep32-4l-bf16", "lcfo-pages-saturate"
CONFIG = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
# the catalog's row (model-configs guide, LongCat-Flash-Omni): every number of its ``config``
CATALOG = {"attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
           "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
           "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
           "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 512,
           "max_position_embeddings": 131072, "rms_norm_eps": 1e-05, "rope_theta": 10000000,
           "attention_method": "MLA", "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
SEED = 2**31 + 77

# The tests' small size: hidden 64, 4 heads, 2 layers, 24 routed + 12 zero experts, top-4, 6 held, 64 ids.
SMALL = {"hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
         "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
         "qk_nope_head_dim": 16, "v_head_dim": 16, "n_routed_experts": 24, "zero_expert_num": 12, "moe_topk": 4,
         "routed_scaling_factor": 6, "experts_held": 6, "vocab_size": 64, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
         "patch": 8, "answer_steps": 4, "topk": 5, "dtype": "bfloat16", "max_token_slots": 1024,
         "leaf_gain": CONFIG["model"]["leaf_gain"]}
SMALL_DECODER = {k: v for k, v in SMALL.items() if k not in ("topk", "dtype", "leaf_gain")}
SMALL_CONFIG = {
    "model": SMALL,
    "server_model": {"name": "longcat_flash", "source": "native", "task": "generate", "decoder": SMALL_DECODER,
                     "dtype": "bfloat16", "topk": 5},
    "weights": CONFIG["weights"], "check": {**CONFIG["check"], "sample_images": 8, "limit_s": 200},
    "floors": CONFIG["floors"], "http_workers": 4,
    "server_flags": ["--http-workers", "4", "--canvas-buckets", "64,128", "--max-batch", "8"],
    # bfloat16 against the float32 reference at this size on the CPU reads 0.005-0.015 an answer, and up to
    # 0.07 / 0.7 where rounding moved one of a token's four picks to another expert (at 36 experts a pick
    # weighs up to 0.9; at the published 768 it weighs 0.04-0.12); answers of other images read above 1 / 3.
    # The chip's readings at the published widths are in PERF.md
    # The int8 share is not judged at this size: 8 images and an error made of a few moved picks leave the
    # least-squares share of a small direction to chance (it is judged at the published widths: PERF.md).
    "limits": {"logit_rms": 0.2, "logit_max": 1.5, "int8_weight_share": 1e9},
}


def small_weights(m=SMALL, seed=SEED):
    return {n: jnp.asarray(longcat.make_leaf(seed, n, s, m)) for n, s in longcat.all_leaves(m).items()}


def test_the_file_holds_the_catalogs_row_but_for_what_reduced_lists():
    assert CONFIG["source"] == "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json"
    assert CONFIG["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] < value
        else:
            assert CONFIG[key] == value, key
    m = CONFIG["model"]
    assert (CONFIG["num_layers"], CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == \
        (m["num_layers"], m["experts_held"], m["vocab_size"]) == (4, 16, 16384)
    assert m["published"] == {k: CATALOG[k] for k in CONFIG["reduced"]}
    # no width moved, in the model block and in what the server is told alike
    for key in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_attention_heads", "kv_lora_rank",
                "q_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "moe_topk", "zero_expert_num",
                "n_routed_experts"):
        assert m[key] == CATALOG[key] == CONFIG["server_model"]["decoder"][key], key
    assert (m["patch"], m["answer_steps"], m["topk"], m["dtype"], m["deployment_chips_per_layer"]) == \
        (32, 4, 5, "bfloat16", 32)


NEW_METRICS = {"tokens_per_image", "token_pad_share", "zero_pick_share", "held_pick_share",
               "held_expert_load_max_over_mean", "mla_prefill_roofline", "expert_gmm_roofline"}


def test_the_manifest_names_the_cell_by_appended_entries_alone():
    """What ``test_bm_manifest.py`` asserts of a cell and of the entries,
    less its lines that know one convolutional classifier; and that naming
    the cell only appended: with this PR's entries and the cell's name taken
    off again, one configuration, one cell and metrics that list that cell."""
    man = M.load_manifest()
    cell = M.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (NAME, "pages-saturate", 1)
    assert man["workloads"][-1]["name"] == CELL and man["configs"][-1]["name"] == NAME
    assert man["configs"][-1]["reduced"] == CONFIG["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert man["configs"][-1]["source"] == CONFIG["source"]
    assert cell.traffic_path == ROOT / "benchmark" / "traffic" / "pages-saturate.json" and cell.traffic_path.is_file()
    assert {"model", "server_model", "server_flags", "http_workers", "limits"} <= set(cell.config)
    assert cell.config["server_flags"][:2] == ["--http-workers", str(cell.config["http_workers"])]
    assert cell.config["deployment"]
    served, model = cell.config["server_model"], cell.config["model"]
    assert (served["dtype"], served["topk"], served["decoder"]["answer_steps"]) == \
        (model["dtype"], model["topk"], model["answer_steps"])
    e2e = {e["name"] for e in cell.end_to_end}
    assert e2e == {"images_per_s", "setup_s"}
    reported = {p["name"] for p in cell.per_layer}
    # every per-layer metric the benchmark has reads something here (the response cache is on: every page is
    # digested and looked up) but one: top_program_row_share takes the top canvas at the top batch bucket for
    # the largest program, which a decoder never builds (its rows go with the canvas), and read 0.0 on the chip
    assert {p["name"] for p in man["per_layer"]} - reported == {"top_program_row_share"}
    assert NEW_METRICS == {p["name"] for p in man["per_layer"] if p["workloads"] == [CELL]}
    for p in cell.per_layer:
        assert p["moves"] in e2e and callable(M.load_reader(p["name"])[0])
    named = M.named(cell.config)
    assert (named.sample_images, named.answer_steps) == (24, 4)
    assert [named.weights.name, named.check.name, named.floors.name] == \
        ["longcat_weights.py", "longcat_check.py", "longcat_floors.py"]
    # the entries keep the contract's keys, names and lengths
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16 and all(M.NAME.match(k) for k in c["reduced"])
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] == 1 and len(w["why"]) <= 200
    for p in man["per_layer"]:
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert M.NAME.match(p["name"]) and M.UNIT.match(p["unit"])
    names = [x["name"] for x in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    # only appended: this PR's entries are the last of their lists, and the cell's name the last of a metric's
    assert [c["name"] for c in man["configs"]] == ["iv3-299-bf16-4k", NAME]
    assert [w["name"] for w in man["workloads"]] == ["iv3-bigalbums-saturate", CELL]
    per_layer = [p["name"] for p in man["per_layer"]]
    assert set(per_layer[-len(NEW_METRICS):]) == NEW_METRICS
    for e in man["end_to_end"] + man["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["workloads"] in (["iv3-bigalbums-saturate", CELL], [CELL]), e["name"]
    assert layers_of(man["per_layer"][-len(NEW_METRICS):]) <= layers_of(man["per_layer"][:-len(NEW_METRICS)])


def layers_of(metrics):
    return {p["layer"] for p in metrics}


def test_the_mix_is_the_issues():
    from benchmark import traffic
    path = ROOT / "benchmark" / "traffic" / "pages-saturate.json"
    mix = traffic.Mix.load(path)
    table = json.loads(path.read_text())["images"]["long_side_px"]
    assert (mix.loop, mix.clients, mix.files_per_request, mix.timeout_s, mix.quality) == ("closed", 16, 4, 120.0, 88)
    tokens = sorted((h // 32) * (w // 32) for h, w in mix.shapes)
    assert sorted(set(tokens)) == [768, 1728, 3072] and len(tokens) == 100
    assert [tokens.count(t) for t in (768, 1728, 3072)] == [30, 40, 30]
    assert [[s, n] for s, n in table] == [[1024, 3], [1536, 4], [2048, 3]]
    assert sum(h > w for h, w in mix.shapes) == 70                 # portrait pages, 70 of 100
    assert np.mean(tokens) == pytest.approx(1843.2)
    # none over the largest canvas the configuration serves: a larger one would take the decoder's downscale path
    assert max(max(hw) for hw in mix.shapes) <= 2048 == max(
        int(s) for s in CONFIG["server_flags"][CONFIG["server_flags"].index("--canvas-buckets") + 1].split(","))


def test_the_published_count_of_parameters():
    """The issue's arithmetic: 90.6 M an MLA block, 226.5 M a dense FFN, 4.7 M
    the router, 37.75 M an expert, 5.19 G held here."""
    m = CONFIG["model"]
    f = longcat_floors
    assert f.mla_params(m) == 90_570_752 and f.ffn_params(m) == 226_492_416
    assert f.router_params(m) == 4_718_592 and f.expert_params(m) == 37_748_736
    held = sum(int(np.prod(s)) for s in longcat.all_leaves(m).values())
    assert 5.18e9 < held < 5.20e9
    whole = (m["published"]["num_layers"] * (2 * f.mla_params(m) + 2 * f.ffn_params(m) + f.router_params(m)
                                             + m["published"]["n_routed_experts"] * f.expert_params(m))
             + 2 * m["published"]["vocab_size"] * m["hidden_size"])
    assert 560e9 < whole < 561e9


def test_all_the_shares_held_parts_and_the_identity_part_once_are_the_uncut_layer():
    """The share test: 24 routed experts over 4 holders of 6. Each holder's
    expert layer output, less the identity part it computes like every
    other, summed over the holders, plus the identity part once, is what a
    holder of all 24 gives."""
    m = dict(SMALL, experts_held=24)
    rs = np.random.default_rng(3)
    u = jnp.asarray(rs.standard_normal((50, m["hidden_size"])).astype(np.float32))
    w = {n: jnp.asarray(longcat.make_leaf(SEED, n, s, m)) for n, s in longcat.layer_leaves(m).items()}
    whole = longcat._moe(m, w, u, None)
    identity = longcat._moe(m, w, u, "no_held_experts")
    parts = []
    for share in range(4):
        ws = {"router": w["router"]}
        # a share holds experts 6 * share onward; told as ids 0..5 of a router whose columns are rolled to it
        ids = list(range(6 * share, 6 * share + 6))
        order = ids + [e for e in range(24) if e not in ids] + list(range(24, 36))
        ws["router"] = w["router"][:, np.asarray(order)]
        for local, e in enumerate(ids):
            ws |= {f"expert{local}/{k}": w[f"expert{e}/{k}"] for k in ("w_gate", "w_up", "w_down")}
        m6 = dict(m, experts_held=6)
        parts.append(longcat._moe(m6, ws, u, None) - longcat._moe(m6, ws, u, "no_held_experts"))
    np.testing.assert_allclose(np.asarray(sum(parts) + identity), np.asarray(whole), rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(identity).max()) > 0.05 and float(jnp.abs(whole - identity).max()) > 0.05


def test_one_forward_over_the_served_ids_reads_every_step():
    """Causal: position T - 1 + s of a forward over the image's tokens and
    three ids is what a forward over the first T + s tokens ends in."""
    w = small_weights()
    rs = np.random.default_rng(5)
    tokens = longcat.patches(rs.integers(0, 256, (40, 56, 3), dtype=np.uint8), 8)
    ids = [7, 63, 0]
    at_once = longcat.forward(SMALL, w, tokens, ids, 4)
    for s in range(4):
        alone = longcat.forward(SMALL, w, tokens, ids[:s], 1)[0]
        np.testing.assert_allclose(at_once[s], alone, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("control", longcat.CONTROLS)
def test_a_control_moves_the_reference(control):
    """Each control changes the reference's own answer by far more than
    float32 arithmetic does: at the published widths the check's limits lie
    between (PERF.md)."""
    from benchmark import check
    w = small_weights()
    rs = np.random.default_rng(9)
    tokens = longcat.patches(rs.integers(0, 256, (64, 48, 3), dtype=np.uint8), 8)
    sound = longcat.forward(SMALL, w, tokens, [], 1)
    moved = longcat.forward(SMALL, w, tokens, [], 1, control)
    top = np.argsort(-moved[0])[:5]
    values = check.compare(sound, [[(int(c), float(moved[0][c])) for c in top]])
    assert values["logit_rms"] > 0.003, values          # float32 against itself reads 1e-6


def test_the_export_read_back_leaf_by_leaf_is_leaves_normal(tmp_path):
    m = dict(SMALL, dtype="float32")
    longcat_weights.write_export(m, SEED, tmp_path / "export", threads=4)
    manifest = json.loads((tmp_path / "export" / "manifest.json").read_text())
    shapes = longcat.all_leaves(m)
    assert manifest["dtype"] == "float32" and set(manifest["leaves"]) == set(shapes) and len(shapes) == 4 + 2 * 43
    for name in sorted(shapes, reverse=True):
        back = longcat_weights.read_leaf(m, tmp_path / "export", name)
        draw = leaves.normal(SEED, name, shapes[name], 0.1 if (name.endswith("norm") or "/norm/" in name)
                             else longcat.std(name, shapes[name], m))
        want = 1.0 + draw if (name.endswith("norm") or "/norm/" in name) else draw
        assert np.array_equal(back, want), name
    # another seed over the same export is written in place: the same files, the new seed's values
    inode = (tmp_path / "export" / "head").stat().st_ino
    longcat_weights.write_export(m, SEED + 1, tmp_path / "export", threads=4)
    assert (tmp_path / "export" / "head").stat().st_ino == inode
    assert np.array_equal(longcat_weights.read_leaf(m, tmp_path / "export", "head"),
                          longcat.make_leaf(SEED + 1, "head", shapes["head"], m))
    # in bfloat16 the export holds each value rounded once
    longcat_weights.write_export(SMALL, SEED, tmp_path / "bf16", threads=2)
    back = longcat_weights.read_leaf(SMALL, tmp_path / "bf16", "layer1/expert5/w_down")
    want = longcat.make_leaf(SEED, "layer1/expert5/w_down", shapes["layer1/expert5/w_down"], SMALL)
    assert np.array_equal(back, want.astype(jnp.bfloat16).astype(np.float32))


def test_the_floors_go_with_a_rows_tokens():
    m, f = CONFIG["model"], cost.load_floors(CONFIG)
    assert f.__file__.endswith("longcat_floors.py")
    row = lambda t, rows=4, batches=1: {"canvas": 2048, "batch_bucket": 4, "batches": batches, "rows_real": rows,
                                        "rows_dispatched": 4 * batches, "px_real": rows * t * 1024}
    one, two, four = (f.image_flops(m, row(t)) for t in (768, 1536, 3072))
    assert one < two < four and (four - two) > 2 * (two - one)              # a part goes with the square
    assert f.image_flops(m, row(768, rows=3, batches=2)) == one             # a real image's, whatever the batch
    # the mix's mean image is the issue's 10.3 TFLOP
    mix_mean = sum(n * f.image_flops(m, row(t)) for t, n in ((768, 3), (1728, 4), (3072, 3))) / 10
    assert mix_mean == pytest.approx(10.3e12, rel=0.03)
    # bytes: the dense parameters once a step, and the experts a call can reach
    few = f.serve_bytes(m, {"canvas": 1024, "batches": 1, "rows_real": 1, "px_real": 1024})
    assert few > 4 * 2 * f.dense_params(m)
    assert f.serve_bytes(m, row(3072)) - few > 4 * 15 * 2 * f.expert_params(m)
    t, which = cost.serve_floor_s(f, m, row(3072), 197e12, 819e9)
    assert which == "compute" and t == pytest.approx(4 * f.image_flops(m, row(3072)) / 197e12)
    assert f.kernel_floor(m, row(3072), "no_such_kernel") is None
    core, moved = f.kernel_floor(m, row(3072), "mla_prefill")
    assert core == pytest.approx(2 * 8 * 4 * 64 * 3072 ** 2 / 2 * 320) and moved > 0


def test_the_expert_kernels_floor_is_what_any_routing_has_to_do():
    """The kernel skips an expert that no token of the call picked, so of the
    weights one expert's a layer and a step is the floor, whatever the row;
    a kernel that streamed all sixteen at the chip's full bandwidth reads
    under 100%."""
    m, f = CONFIG["model"], longcat_floors
    for rows, t in ((1, 768), (4, 3072), (16, 768)):
        row = {"canvas": 2048, "batches": 2, "rows_real": 2 * rows, "px_real": 2 * rows * t * 1024}
        flops, moved = f.kernel_floor(m, row, "expert_gmm")
        picks = 0.25 * rows * (t + 3)
        assert flops == pytest.approx(2 * 4 * picks * f.expert_params(m))
        weights = moved - 2 * 4 * picks * (2 * 6144 + 3 * 2048)
        assert 2 * 4 * f.expert_params(m) < weights <= 2 * 4 * 4 * f.expert_params(m)     # prefill and three steps
        all_sixteen_s = 2 * 4 * 16 * f.expert_params(m) / 819e9
        assert max(flops / 197e12, moved / 819e9) < all_sixteen_s


def test_a_kernel_traced_at_its_floor_reads_100_and_a_program_without_it_reads_nothing():
    read, args = M.load_reader("mla_prefill_roofline")
    m, f = CONFIG["model"], longcat_floors
    row = {"canvas": 2048, "batch_bucket": 4, "batches": 3, "rows_real": 12, "rows_dispatched": 12,
           "px_real": 12 * 3072 * 1024}
    flops, moved = f.kernel_floor(m, row, "mla_prefill")
    floor_s = max(flops / 197e12, moved / 819e9)
    pad = {"2048x4": row}
    ctx = SimpleNamespace(before={"batcher": {"builders": {"padding": {"2048x4": dict.fromkeys(row, 0)}}}},
                          after={"batcher": {"builders": {"padding": pad}}}, config=CONFIG,
                          device={"kind": "TPU v5 lite"},
                          trace={"programs": [["jit_serve", 1.0, 2]],
                                 "ops": [["mla_prefill.3 bf16[4,64,4096,128]", 2 * floor_s, 16], ["fusion.1", 0.5, 2]]})
    assert read(ctx, **args) == pytest.approx(100.0)
    ctx.trace["ops"] = [["fusion.1", 0.5, 2]]                  # the parent's program: no such kernel
    assert read(ctx, **args) is None
    del ctx.trace["ops"]                                         # a trace reduced before PR 29
    assert read(ctx, **args) is None
    ctx.config = json.loads((ROOT / "benchmark" / "configs" / "iv3-299-bf16-4k.json").read_text())
    ctx.trace["ops"] = [["mla_prefill.3", 1.0, 1]]
    assert read(ctx, **args) is None                             # floors that know no kernels


def test_the_cell_through_run_py_on_the_cpu_at_the_small_size(tmp_path, monkeypatch):
    """The named weights script, a real server on the CPU (the decoder at the
    tests' size through ``--ckpt``), ``judge`` on its ``steps`` answers, the
    named check child on the window's own answers; then the same outcomes
    with the answers moved to other images say not correct."""
    monkeypatch.setattr(R, "work_dir", lambda cell: tmp_path)
    e2e = ({"name": "images_per_s", "unit": "images/s"}, {"name": "setup_s", "unit": "s"})
    cell = Cell("tiny-pages", 1, "small", SMALL_CONFIG, "tiny-photos",
                ROOT / "tests" / "benchmark" / "data" / "tiny-photos.json", e2e, ())
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    ctx = R.drive(cell, SEED, 3.0, False, require_platform=None,
                  extra_flags=("--aot-cache-dir", str(tmp_path / "aot_cache")), env=env)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    line = R.report(ctx)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] == len(ctx.outcomes) > 10
    ok = [o for o in ctx.outcomes if o.answers is not None]
    assert all(len(a) == 4 and all(len(step) == 5 for step in a) for o in ok for a in o.answers)
    life = lambda s: s["batcher"]["lifecycle"]
    moved = {k: life(ctx.after)[k] - life(ctx.before)[k] for k in life(ctx.after) if k.endswith("_total")
             and isinstance(life(ctx.after)[k], float)}
    images = sum(o.images for o in ok)
    assert moved["images_total"] == images and moved["decode_steps_total"] == 3 * images
    assert moved["picks_total"] == 4 * 2 * (moved["tokens_real_total"] + moved["decode_steps_total"])
    assert 0 < moved["held_picks_total"] < moved["zero_picks_total"] < moved["picks_total"]
    assert moved["token_slots_total"] - moved["tokens_real_total"] == moved["token_slots_pad_total"] > 0
    rotated = [o.answers for o in ok[1:]] + [ok[0].answers]
    for o, a in zip(ok, rotated):
        o.answers = a
    tampered = R.report(ctx)
    assert tampered["correct"] is False
    assert tampered["compared"]["logit_rms"]["value"] > 3 * line["compared"]["logit_rms"]["value"]
