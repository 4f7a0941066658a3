"""The token decoder (models/longcat_flash.py) against the plain reference
of benchmark/reference/longcat.py, at a small size on the CPU: hidden 64, 4
heads, 2 layers, 24 routed + 12 zero experts, top-4, 6 held, 64 ids."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.reference import longcat, longcat_floors, longcat_weights
from tensorflow_web_deploy_tpu.models import longcat_flash as lf
from tensorflow_web_deploy_tpu.models.adapter import read_leaf_export
from tensorflow_web_deploy_tpu.ops import experts, mla
from tensorflow_web_deploy_tpu.ops.image import patch_tokens
from tensorflow_web_deploy_tpu.serving import costmodel

ROOT = Path(__file__).resolve().parents[1]
FULL = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-omni-ep32-4l-bf16.json").read_text())
SMALL = {"hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
         "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
         "qk_nope_head_dim": 16, "v_head_dim": 16, "n_routed_experts": 24, "zero_expert_num": 12, "moe_topk": 4,
         "routed_scaling_factor": 6, "experts_held": 6, "vocab_size": 64, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
         "patch": 8, "answer_steps": 4, "topk": 5, "dtype": "float32", "max_token_slots": 1024,
         "leaf_gain": FULL["model"]["leaf_gain"]}
CFG = lf.Config.from_dict(SMALL)
SEED = 2**31 + 5
SIZES = ((64, 48), (40, 56), (33, 64), (64, 64))


@pytest.fixture(scope="module")
def leaves():
    return {n: longcat.make_leaf(SEED, n, s, SMALL) for n, s in longcat.all_leaves(SMALL).items()}


def program_params(leaves, dtype):
    out = {k: np.empty(s, np.float32) for k, s in lf.param_shapes(CFG).items()}
    for leaf, shape, key, index in lf.leaf_table(CFG):
        assert leaves[leaf].shape == tuple(shape), leaf
        out[key][index] = leaves[leaf]
    return {k: jnp.asarray(v).astype(dtype) for k, v in out.items()}


def images(canvas: int, sizes=SIZES, seed=0):
    rs = np.random.default_rng(seed)
    imgs = [rs.integers(0, 256, (h, w, 3), dtype=np.uint8) for h, w in sizes]
    canvases = np.zeros((len(imgs), canvas, canvas, 3), np.uint8)
    for i, im in enumerate(imgs):
        canvases[i, :im.shape[0], :im.shape[1]] = im
    return imgs, jnp.asarray(canvases), jnp.asarray([im.shape[:2] for im in imgs], jnp.int32)


def served_and_reference(leaves, dtype, canvas=64):
    """The program's answers for SIZES (prefill, then three cached steps)
    and, per image and step, the reference's full forward after the ids the
    program put first."""
    imgs, canvases, hws = images(canvas)
    tokens, lengths = patch_tokens(canvases, hws, SMALL["patch"])
    params = program_params(leaves, dtype)
    with jax.default_matmul_precision("highest"):
        scores, ids, counters = jax.jit(
            lambda p, t, l: lf.answer(CFG, p, t.astype(dtype), l, SMALL["topk"]))(params, tokens, lengths)
    w = {k: jnp.asarray(v) for k, v in leaves.items()}
    ref, pairs = [], []
    for i, im in enumerate(imgs):
        steps = [[(int(c), float(s)) for c, s in zip(ids[i, k], scores[i, k])] for k in range(SMALL["answer_steps"])]
        ref.append(longcat.forward(SMALL, w, longcat.patches(im, SMALL["patch"]), [s[0][0] for s in steps[:-1]],
                                   SMALL["answer_steps"]))
        pairs += steps
    return np.concatenate(ref), pairs, dict(zip(lf.COUNTERS, np.asarray(counters)))


def test_the_export_and_the_program_name_the_same_leaves():
    table = lf.leaf_table(CFG)
    assert {t[0]: tuple(t[1]) for t in table} == longcat.all_leaves(SMALL)
    full = lf.Config.from_dict(FULL["server_model"]["decoder"])
    assert {t[0]: tuple(t[1]) for t in lf.leaf_table(full)} == longcat.all_leaves(FULL["model"])
    assert (full.max_token_slots, full.experts_held, full.n_routed_experts) == (16384, 16, 512)


def test_patch_tokens_are_the_real_pixels_in_raster_order_packed_to_the_front():
    imgs, canvases, hws = images(128, sizes=((64, 48), (120, 90), (7, 128), (128, 128)))
    tokens, lengths = patch_tokens(canvases, hws, 8)
    assert tokens.shape == (4, 256, 192) and list(np.asarray(lengths)) == [48, 165, 0, 256]
    for i, im in enumerate(imgs):
        want = longcat.patches(im, 8) if min(im.shape[:2]) >= 8 else np.zeros((0, 192), np.float32)
        np.testing.assert_allclose(np.asarray(tokens[i, :len(want)]), want, atol=1e-6)
        assert not np.asarray(tokens[i, len(want):]).any()


def test_prefill_and_cached_steps_equal_the_references_full_forward_in_float32(leaves):
    """Tight: both compute in float32 at ``highest``; what differs is the
    order of sums (the cache, the absorbed products, the blocked softmax)."""
    ref, pairs, counters = served_and_reference(leaves, jnp.float32)
    values = check.compare(ref, pairs)
    assert values["logit_max"] < 2e-5, values
    tokens = sum((h // 8) * (w // 8) for h, w in SIZES)
    assert counters["images"] == 4 and counters["tokens_real"] == tokens and counters["token_slots"] == 4 * 64
    assert counters["token_slots_pad"] == 4 * 64 - tokens and counters["decode_steps"] == 12
    assert counters["picks"] == 4 * 2 * (tokens + 12)          # top-4, two layers, every real token and step
    assert 0 < counters["held_picks"] < counters["zero_picks"] < counters["picks"]
    assert counters["held_expert_load_max"] >= counters["held_expert_load_mean"] > 0


def test_in_bfloat16_it_stays_within_the_stated_tolerance(leaves):
    """bfloat16 weights and matrix products (float32 accumulation, norms,
    softmax, router and residual stream) against the float32 reference: an
    answer's logits move by about a hundredth of their spread; 0.15 / 1.0
    leaves room for a token whose rounding moved one of its four picks to
    another expert (a pick weighs up to 0.9 at 36 experts, 0.04-0.12 at the
    published 768). Another image's answer reads above 1 / 3."""
    ref, pairs, _ = served_and_reference(leaves, jnp.bfloat16)
    values = check.compare(ref, pairs)
    assert values["logit_rms"] < 0.15 and values["logit_max"] < 1.0, values
    rotated = pairs[4:] + pairs[:4]
    assert check.compare(ref, rotated)["logit_rms"] > 1.0


def test_the_same_image_in_a_larger_canvas_answers_the_same(leaves):
    """The padding mask: token slots past an image's own are neither keys,
    nor routed, nor counted."""
    small = served_and_reference(leaves, jnp.float32, canvas=64)
    large = served_and_reference(leaves, jnp.float32, canvas=128)
    for (_, a, ca), (_, b, cb) in [(small, large)]:
        for step_a, step_b in zip(a, b):
            assert [c for c, _ in step_a] == [c for c, _ in step_b]
            np.testing.assert_allclose([s for _, s in step_a], [s for _, s in step_b], rtol=2e-5)
        assert ca["tokens_real"] == cb["tokens_real"] and ca["picks"] == cb["picks"]
        assert cb["token_slots"] == 4 * 256


def plain_core(q_n, q_r, k_n, k_r, v, lengths, scale: float, precision=None):
    """The causal core's definition: ``[B, H, T, T]`` scores at once.
    q_n, k_n [B, H, T, dn]; q_r [B, H, T, dr]; k_r [B, T, dr]; v [B, H, T, dv]."""
    t = q_n.shape[2]
    s = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n, precision=precision, preferred_element_type=jnp.float32)
         + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r, precision=precision, preferred_element_type=jnp.float32))
    s = jnp.where(mla._mask(t, t, 0, 0, lengths), s * scale, mla.NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v, precision=precision,
                     preferred_element_type=jnp.float32)
    valid = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, :, None]
    return jnp.where(valid, out, 0.0).astype(v.dtype)


@pytest.mark.parametrize("t,lengths", [(40, (40, 23)), (100, (100, 1))])
def test_blocked_attention_equals_the_plain_one_at_a_length_that_is_no_multiple_of_the_block(t, lengths):
    rs = np.random.default_rng(t)
    mk = lambda *s: jnp.asarray(rs.standard_normal(s).astype(np.float32))
    args = (mk(2, 3, t, 16), mk(2, 3, t, 8), mk(2, 3, t, 16), mk(2, t, 8), mk(2, 3, t, 16),
            jnp.asarray(lengths, jnp.int32), 0.2)
    with jax.default_matmul_precision("highest"):
        plain = plain_core(*args)
        blocked = mla.blocked_core(*args, block=16)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(plain), atol=2e-6)
    assert not np.asarray(plain[1, :, lengths[1]:]).any()          # padding queries answer zero


def test_the_prefill_kernel_equals_the_plain_core_through_the_interpreter(monkeypatch):
    rs = np.random.default_rng(1)
    mk = lambda *s: jnp.asarray(rs.standard_normal(s).astype(np.float32))
    args = (mk(2, 2, 256, 16), mk(2, 2, 256, 8), mk(2, 2, 256, 16), mk(2, 256, 8), mk(2, 2, 256, 16),
            jnp.asarray([256, 130], jnp.int32), 0.2)
    monkeypatch.setattr(mla, "pick_block", lambda t: 128)             # two blocks a row
    with jax.default_matmul_precision("highest"):
        plain = plain_core(*args)
        kernel = mla.pallas_core(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain), atol=2e-6)


@pytest.mark.parametrize("chunk,held_first", [(experts.CHUNK, 0), (256, 0), (256, 12)])
def test_the_grouped_expert_sum_equals_the_dense_one(monkeypatch, chunk, held_first):
    """Sorted by expert, padded to row tiles, ``expert_gmm`` through the
    interpreter, a window at a time (several windows where the window is
    small): every pick arrives, none twice."""
    monkeypatch.setattr(experts, "CHUNK", chunk)
    rs = np.random.default_rng(2)
    mk = lambda *s: jnp.asarray(rs.standard_normal(s).astype(np.float32))
    u, w_router = mk(300, 64), mk(64, 36)
    w = (mk(6, 64, 32) / 8, mk(6, 64, 32) / 8, mk(6, 32, 64) / 5)
    valid = jnp.arange(300) < 280
    with jax.default_matmul_precision("highest"):
        weights, ids = experts.route(u, w_router, 4, 6.0)
        weights = jnp.where(valid[:, None], weights, 0.0)
        # a share that holds ids 12-17 is told so
        dense = experts._dense_sum(u, experts.held_weights(weights, ids, held_first, 6), *w)
        grouped = experts._grouped_sum(u, weights, ids, held_first, *w, interpret=True)
        layer, counted = experts.expert_layer(u, valid, w_router, *w, topk=4, scale=6.0, n_routed=24,
                                              held_first=held_first)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), atol=1e-5)
    assert not np.asarray(dense[280:]).any() and np.asarray(dense[:280]).any()
    # the layer, which takes the dense form off the chip, adds the identity part and counts
    zero_w = jnp.sum(jnp.where(ids >= 24, weights, 0.0), axis=1)
    np.testing.assert_allclose(np.asarray(layer), np.asarray(dense + zero_w[:, None] * u), atol=1e-5)
    assert counted["picks"] == 280 * 4


def test_an_export_is_read_leaf_by_leaf_into_the_programs_parameters(tmp_path, leaves):
    m = dict(SMALL, dtype="bfloat16")
    longcat_weights.write_export(m, SEED, tmp_path, threads=2)
    params = read_leaf_export(str(tmp_path), lf.leaf_table(CFG), lf.param_shapes(CFG))
    assert set(params) == set(lf.param_shapes(CFG)) and params["head"].dtype == jnp.bfloat16
    want = leaves["layer1/expert4/w_up"].astype(jnp.bfloat16)
    assert np.array_equal(params["layer1/experts/w_up"][4], want)
    (tmp_path / "manifest.json").write_text(json.dumps({"dtype": "bfloat16", "leaves": {}}))
    with pytest.raises(ValueError, match="the model states"):
        read_leaf_export(str(tmp_path), lf.leaf_table(CFG), lf.param_shapes(CFG))


def test_the_cost_models_walkers_equal_the_benchmarks_floors():
    m, decoder = FULL["model"], FULL["server_model"]["decoder"]
    c, f = costmodel.decoder_cost(decoder), longcat_floors
    assert (c["mla_params"], c["ffn_params"], c["router_params"], c["expert_params"]) == \
        (f.mla_params(m), f.ffn_params(m), f.router_params(m), f.expert_params(m))
    assert c["held_picks_per_token"] == f.held_picks_per_token(m) == 0.25
    assert c["layer_macs_per_token"] == f.layer_macs_per_token(m) and c["dense_params"] == f.dense_params(m)
    assert c["param_count"] == sum(int(np.prod(s)) for s in longcat.all_leaves(m).values())
    for t in (768.0, 1728.0, 3072.0):
        assert c["core_macs_per_token_sq"] * t * t == f.core_macs(m, t)
        assert c["absorbed_macs_per_cached_token"] * t == f.absorbed_macs(m, t)
        row = {"batches": 1, "rows_real": 2, "px_real": 2 * t * 1024}
        assert costmodel.decoder_image_flops(decoder, t) == f.image_flops(m, row)


def test_the_engine_takes_rows_a_call_from_the_model_and_calls_in_flight_from_the_compiled_programs():
    """No key states either: the rows a call may hold at a canvas come from
    the model's token slots fitted to the batch buckets, and the calls in
    flight from what is free on the device over the largest temporary of a
    warmed executable (the published widths' numbers: 15.75 GB less 10.5 in
    use, 3.33 GB for canvas 2048 x 4 rows by the compiler's account)."""
    from types import SimpleNamespace
    from tensorflow_web_deploy_tpu.models.adapter import decoder_converted
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine

    eng = InferenceEngine.__new__(InferenceEngine)
    eng.batch_buckets, eng.max_batch = (1, 2, 4, 8, 16), 16
    assert eng.max_rows(2048) == 16                                   # no model's own ceiling: the top bucket
    eng.model = decoder_converted(dict(FULL["server_model"]["decoder"], num_layers=0, vocab_size=8,
                                       hidden_size=8, ffn_hidden_size=8), topk=5)
    assert eng.model.from_canvases and eng.model.counter_names == lf.COUNTERS
    assert [eng.max_rows(s) for s in (1024, 1536, 2048, 4096)] == [16, 4, 4, 1]
    eng.max_batch = 2
    assert eng.max_rows(1024) == 2

    exe = lambda temp: SimpleNamespace(memory_analysis=lambda: SimpleNamespace(temp_size_in_bytes=temp))
    eng._replicas = [SimpleNamespace(exe={("serve", 2048, 4): exe(3_326_409_216), ("serve", 1536, 4): exe(1_892_762_624)})]
    memory = [{"id": 0, "bytes_in_use": 10_500_000_000, "bytes_limit": 15_750_000_000}]
    eng.device_memory = lambda: memory
    assert eng._calls_that_fit() == 1
    memory[0]["bytes_in_use"] = 2_000_000_000
    assert eng._calls_that_fit() == 4
    eng.device_memory = lambda: [{"id": 0}]                           # the CPU reports no memory: no ceiling
    assert eng._calls_that_fit() is None
    assert InferenceEngine.max_calls_in_flight is None


def test_the_decoders_program_is_jit_serve_and_its_scopes_name_the_phases():
    """What the benchmark's readers find the model's work by: the serve
    program keeps the name ``jit_serve`` for a model that answers from the
    canvases itself, and each phase is a scope in its operations' names.
    The engine itself: a real one at the small size, two canvas buckets."""
    import re
    from tensorflow_web_deploy_tpu.parallel.mesh import build_mesh
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

    one_device = build_mesh([jax.devices("cpu")[0]])
    decoder = {k: v for k, v in SMALL.items() if k not in ("topk", "dtype", "leaf_gain")}
    cfg = ServerConfig(
        model=ModelConfig(name="longcat_flash", source="native", task="generate", decoder=decoder,
                          dtype="float32", topk=5),
        canvas_buckets=(64, 128), batch_buckets=(1, 2, 4, 8), max_batch=8, ragged=True, wire_format="rgb")
    engine = InferenceEngine(cfg, mesh=one_device)
    try:
        assert engine.counter_names == lf.COUNTERS and engine.max_calls_in_flight is None
        assert [engine.max_rows(s) for s in (64, 128)] == [8, 4]          # 1,024 slots: 16 x 64, 4 x 256
        exe, _ = engine._get_serve_exe(engine._replicas[0], 64, 2)
        text = exe.as_text()
        assert re.match(r"HloModule jit_serve\b", text)
        for scope in ("patches", "mla_prefill", "dense_ffn", "router", "experts", "mla_decode", "head"):
            assert re.search(rf'op_name="jit\(serve\)/[^"]*\b{scope}/', text), scope
        imgs, canvases, hws = images(64)
        scores, ids = engine.run_batch(np.asarray(canvases), np.asarray(hws))
        assert scores.shape == ids.shape == (4, SMALL["answer_steps"], 5)
    finally:
        engine.close()
    with pytest.raises(ValueError, match="rgb canvases"):
        InferenceEngine(ServerConfig(model=cfg.model, canvas_buckets=(64,), wire_format="yuv420"), mesh=one_device)
