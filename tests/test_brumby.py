"""The third token decoder (models/brumby.py, ops/retention.py) against the
plain reference of benchmark/reference/brumby.py, at a small size on the CPU:
hidden 64, two layers, 4 query over 2 key/value heads of 16, SwiGLU 96, 64
ids, chunks of 16, 64 answer steps (63 through the carried states)."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.reference import brumby as ref, brumby_weights
from tensorflow_web_deploy_tpu.models import brumby as br, decoder as shared
from tensorflow_web_deploy_tpu.models.adapter import decoder_converted, read_leaf_export
from tensorflow_web_deploy_tpu.ops import retention as rt, ssd
from tensorflow_web_deploy_tpu.ops.image import patch_tokens

ROOT = Path(__file__).resolve().parents[1]
FULL = json.loads((ROOT / "benchmark" / "configs" / "brumby-14b-pp8-5l-bf16.json").read_text())
SMALL = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 96, "vocab_size": 64, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "chunk_size": 16,
         "patch": 8, "answer_steps": 64, "max_token_slots": 1024, "topk": 5, "dtype": "float32",
         "gate_memory": [64, 4096], "leaf_gain": FULL["model"]["leaf_gain"]}
DECODER = {k: v for k, v in SMALL.items() if k not in ("topk", "dtype", "leaf_gain", "gate_memory")}
CFG = br.Config.from_dict(DECODER)
SEED = 2**31 + 7
# lengths that end inside a chunk (35, 30), on a chunk's edge (48, 64, 16), and a single token
SIZES = ((64, 48), (40, 56), (56, 40), (64, 64), (8, 16), (8, 8))
STEPS = SMALL["answer_steps"]


@pytest.fixture(scope="module")
def leaves():
    return {n: ref.make_leaf(SEED, n, s, SMALL) for n, s in ref.all_leaves(SMALL).items()}


def images(canvas: int, sizes=SIZES, seed=0):
    rs = np.random.default_rng(seed)
    imgs = [rs.integers(0, 256, (h, w, 3), dtype=np.uint8) for h, w in sizes]
    canvases = np.zeros((len(imgs), canvas, canvas, 3), np.uint8)
    for i, im in enumerate(imgs):
        canvases[i, :im.shape[0], :im.shape[1]] = im
    return imgs, jnp.asarray(canvases), jnp.asarray([im.shape[:2] for im in imgs], jnp.int32)


def served(leaves, dtype, canvas=64):
    """The program's answers for SIZES: prefill, then 63 steps through the states."""
    imgs, canvases, hws = images(canvas)
    tokens, lengths = patch_tokens(canvases, hws, SMALL["patch"])
    params = {k: jnp.asarray(v).astype(dtype) for k, v in leaves.items()}
    with jax.default_matmul_precision("highest"):
        scores, ids, counters = jax.jit(lambda p, t, l: br.answer(CFG, p, t, l, SMALL["topk"]))(params, tokens, lengths)
    return imgs, np.asarray(scores), np.asarray(ids), dict(zip(br.COUNTERS, np.asarray(counters)))


def against_reference(leaves, imgs, scores, ids):
    """Per image and step, the reference's one forward over the image's
    tokens and the ids the program put first."""
    w = {k: jnp.asarray(v) for k, v in leaves.items()}
    out, pairs = [], []
    for i, im in enumerate(imgs):
        steps = [[(int(c), float(s)) for c, s in zip(ids[i, k], scores[i, k])] for k in range(STEPS)]
        out.append(ref.forward(SMALL, w, ref.patches(im, SMALL["patch"]), [s[0][0] for s in steps[:-1]], STEPS))
        pairs += steps
    return np.concatenate(out), pairs


# ------------------------------------------------------------------ the feature map

@pytest.mark.parametrize("d", [16, 128])
def test_the_feature_maps_dot_product_is_the_square_of_the_keys(d):
    """The program's staircase (``D`` 8,704 at 128) and the reference's exact
    symmetric map (8,256) both give ``(q . k)**2``, and the staircase is the
    exact map with zeros between: the same products, no other."""
    rs = np.random.default_rng(d)
    q, k = rs.standard_normal((5, d)).astype(np.float32), rs.standard_normal((7, d)).astype(np.float32)
    want = (q.astype(np.float64) @ k.T.astype(np.float64)) ** 2
    with jax.default_matmul_precision("highest"):
        for phi in (rt.features, ref.features):
            got = np.asarray(phi(jnp.asarray(q)) @ phi(jnp.asarray(k)).T)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4 * d)
    assert rt.feature_count(d) == rt.features(jnp.asarray(q)).shape[-1] and ref.features(jnp.asarray(q)).shape[-1] == d * (d + 1) // 2
    assert rt.feature_count(128) == 8704
    stair, exact = np.asarray(rt.features(jnp.asarray(k))), np.asarray(ref.features(jnp.asarray(k)))
    assert (stair != 0).sum(1).max() == d * (d + 1) // 2
    np.testing.assert_allclose(np.sort(stair[:, (stair != 0).any(0)]), np.sort(exact), rtol=1e-6)


# ------------------------------------------------------------------ the three forms

def retention_inputs(lengths, b=3, t=48, g=2, r=3, d=16, seed=0):
    """q, k (zero at padding), v, log g (zero at padding) of unit-size heads, as the layer makes them."""
    rs = np.random.default_rng(seed)
    valid = np.arange(t)[None] < np.asarray(lengths)[:, None]
    unit = lambda *s: rs.standard_normal(s).astype(np.float32) / np.sqrt(d) * 4
    q, k, v = unit(b, t, g, r, d), unit(b, t, g, d) * valid[..., None, None], rs.standard_normal((b, t, g, d))
    log_g = np.log(rs.uniform(0.8, 1.0, (b, t, g))) * valid[..., None]
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, log_g)) + (jnp.asarray(lengths, jnp.int32),)


@pytest.mark.parametrize("lengths", [(48, 13, 2), (16, 32, 1), (40, 0, 47)],
                         ids=["inside-a-chunk-and-short", "on-a-chunks-edge", "an-empty-row"])
def test_the_chunked_the_recurrent_and_the_attention_form_agree(lengths):
    """Gates and normalisation in all three, ragged rows, padding behind:
    the chunked walk, the token-by-token recurrence (the steps' own form) and
    the reference's attention form, which forms neither a feature map nor a
    state; the state after the padded row is the state after its last real
    token, and an empty row's is zero."""
    q, k, v, log_g, n_real = retention_inputs(lengths)
    with jax.default_matmul_precision("highest"):
        y, s, z = rt.chunked(q, k, v, log_g, chunk=8)
        for row, n in enumerate(lengths):
            if not n:
                assert not np.asarray(s[row]).any() and not np.asarray(z[row]).any()
                continue
            g_, r_ = q.shape[2], q.shape[3]
            flat = lambda a: a.reshape(n, -1, a.shape[-1])
            want = ref.attention_form(flat(q[row, :n].reshape(n, g_ * r_, -1)), k[row, :n], v[row, :n], log_g[row, :n])
            np.testing.assert_allclose(np.asarray(y[row, :n]).reshape(n, g_ * r_, -1), np.asarray(want), atol=2e-5)
            s1 = jnp.zeros_like(s[row:row + 1])
            z1 = jnp.zeros_like(z[row:row + 1])
            for t in range(n):
                y1, s1, z1 = rt.step(q[row:row + 1, t], k[row:row + 1, t], v[row:row + 1, t], log_g[row:row + 1, t], s1, z1)
                np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y[row, t]), rtol=1e-3, atol=1e-3)
            np.testing.assert_allclose(np.asarray(s1[0]), np.asarray(s[row]), rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(z1[0]), np.asarray(z[row]), rtol=1e-5, atol=1e-5)
    chunks, skipped = ssd.chunk_counts(n_real, 48, 8)
    assert chunks == 18 and skipped == sum(6 - -(-n // 8) for n in lengths)


def test_a_padding_slot_leaves_the_state_untouched():
    """A slot past the row's length has log g = 0 and k = 0: the state after
    64 slots of which 21 are real is the state after those 21, whatever
    the padding's queries and values hold."""
    q, k, v, log_g, _ = retention_inputs((21,), b=1, t=64)
    noisy_v = v.at[:, 21:].set(99.0)
    with jax.default_matmul_precision("highest"):
        _, s, z = rt.chunked(q, k, noisy_v, log_g, chunk=16)
        _, s21, z21 = rt.chunked(q[:, :32], k[:, :32], v[:, :32], log_g[:, :32], chunk=16)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s21), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z21), rtol=1e-6)


@pytest.mark.parametrize("lengths", [(48, 13, 2), (16, 0, 47)])
def test_the_prefill_kernel_equals_the_walk_through_the_interpreter(lengths):
    q, k, v, log_g, n_real = retention_inputs(lengths, seed=1)
    with jax.default_matmul_precision("highest"):
        y, s, z = rt.chunked(q, k, v, log_g, chunk=8)
        y_k, s_k, z_k = rt.pallas_prefill(q, k, v, log_g, n_real, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s), atol=2e-6)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z), atol=2e-6)
    for row, n in enumerate(lengths):
        live = -(-n // 8) * 8
        np.testing.assert_allclose(np.asarray(y_k[row, :live]), np.asarray(y[row, :live]), atol=2e-6)
        assert not np.asarray(y_k[row, live:]).any()                     # a skipped chunk is written as zeros


def test_the_step_kernel_equals_the_step_and_updates_the_state_it_was_given():
    q, k, v, log_g, n_real = retention_inputs((48, 13, 2), seed=2)
    with jax.default_matmul_precision("highest"):
        _, s, z = rt.chunked(q, k, v, log_g, chunk=8)
        one = lambda a: a[:, 5] + 0.1
        want = rt.step(one(q), one(k), one(v), log_g[:, 5] - 0.01, s, z)
        got = rt.pallas_step(one(q), one(k), one(v), log_g[:, 5] - 0.01, s, z, interpret=True)
    for w_, g_ in zip(want, got):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ the program against the reference

def test_prefill_and_63_steps_equal_the_references_one_forward_in_float32(leaves):
    """Tight: both compute in float32 at ``highest``; what differs is the
    form (chunks and a state against the attention form) and the order of
    sums. Logits, not ids."""
    imgs, scores, ids, counters = served(leaves, jnp.float32)
    values = check.compare(*against_reference(leaves, imgs, scores, ids))
    assert values["logit_max"] < 5e-5, values
    tokens = sum((h // 8) * (w // 8) for h, w in SIZES)
    n = len(SIZES)
    assert counters["images"] == n and counters["tokens_real"] == tokens and counters["token_slots"] == n * 64
    assert counters["token_slots_pad"] == n * 64 - tokens
    live = sum(-(-(h // 8) * (w // 8) // 16) for h, w in SIZES)         # two layers, chunks of 16 slots
    assert counters["retention_chunks"] == 2 * n * 4 and counters["retention_chunks_skipped"] == 2 * (n * 4 - live) > 0
    assert counters["answer_steps"] == n * STEPS and counters["answer_steps_cached"] == n * (STEPS - 1)
    assert 100.0 * counters["answer_steps_cached"] / counters["answer_steps"] == 98.4375      # cached_step_share


def test_in_bfloat16_it_stays_within_the_stated_tolerance(leaves):
    """bfloat16 weights and products (float32 accumulation, norms, decays,
    state and residual stream) against the float32 reference: an answer's
    logits move by under a hundredth of their spread here (0.008 / 0.03 on
    this seed); another image's answers read above 1."""
    imgs, scores, ids, _ = served(leaves, jnp.bfloat16)
    ref_probs, pairs = against_reference(leaves, imgs, scores, ids)
    values = check.compare(ref_probs, pairs)
    assert values["logit_rms"] < 0.03 and values["logit_max"] < 0.2, values
    assert check.compare(ref_probs, pairs[STEPS:] + pairs[:STEPS])["logit_rms"] > 1.0


def test_a_row_padded_to_a_longer_canvas_answers_every_step_as_the_same_row_unpadded(leaves):
    _, s_small, i_small, c_small = served(leaves, jnp.float32, canvas=64)
    _, s_large, i_large, c_large = served(leaves, jnp.float32, canvas=128)
    assert np.array_equal(i_small, i_large) and i_small.shape == (len(SIZES), STEPS, 5)
    np.testing.assert_allclose(s_small, s_large, rtol=2e-5)
    assert c_small["tokens_real"] == c_large["tokens_real"] and c_large["retention_chunks"] == 4 * c_small["retention_chunks"]


# ------------------------------------------------------------------ the reference's own parts

def test_one_forward_over_the_served_ids_reads_every_step(leaves):
    """Causal: position T - 1 + s of a forward over the image's tokens and
    the ids is what a forward over the first T + s tokens ends in; ids that
    come later move nothing."""
    w = {k: jnp.asarray(v) for k, v in leaves.items()}
    rs = np.random.default_rng(5)
    tokens = ref.patches(rs.integers(0, 256, (40, 56, 3), dtype=np.uint8), 8)
    ids = [7, 63, 0, 5, 5, 9, 1, 44]
    at_once = ref.forward(SMALL, w, tokens, ids, 9)
    for s in (0, 1, 8):
        np.testing.assert_allclose(at_once[s], ref.forward(SMALL, w, tokens, ids[:s], 1)[0], rtol=2e-4, atol=1e-7)
    filled = ref.forward(SMALL, w, tokens, ids[:3] + [0] * 5, 9)
    np.testing.assert_allclose(filled[:4], at_once[:4], rtol=2e-4, atol=1e-7)


def recurrent_forward(m, w, tokens, ids, control=None):
    """The reference's recurrent form on one image, its answer's ids given:
    the distributions after the image and after each id, [len(ids) + 1, vocab]."""
    lay = lambda l: {k[len(f"layer{l}/"):]: v for k, v in w.items() if k.startswith(f"layer{l}/")}
    out, feed = [], iter(ids)

    def rows_after(probs):
        out.append(np.asarray(probs[0]))
        i = next(feed, None)
        return None if i is None else w["embed/token"][jnp.asarray([i])]

    ref.recurrent(m, lay, {"final_norm": w["final_norm"], "head": w["head"]},
                  [ref.embed(w["embed/patch"], tokens, ())], len(ids) + 1, rows_after, control)
    return np.stack(out)


def control_inputs(leaves):
    """The leaves with short gate memories (so that g = 1 is seen here) and a
    64 x 64 image: 64 tokens, half a chunk of 128."""
    w = {k: jnp.asarray(v) for k, v in leaves.items()}
    m = dict(SMALL, gate_memory=[4, 16])
    w |= {k: jnp.asarray(ref.make_leaf(SEED, k, (2,), m)) for k in w if k.endswith("b_g")}
    rs = np.random.default_rng(9)
    return m, w, ref.patches(rs.integers(0, 256, (64, 64, 3), dtype=np.uint8), 8)


def test_the_controls_recurrent_form_is_the_reference_where_no_control_is_set(leaves):
    """What a control answers through (the image in chunks, then a token a
    row through the states it hands on) is the reference itself where no
    control is set, to float32 arithmetic."""
    m, w, tokens = control_inputs(leaves)
    ids = [3, 9, 27, 5, 11]
    with jax.default_matmul_precision("highest"):
        want = ref.forward(m, w, tokens, ids, len(ids) + 1)
        got = recurrent_forward(m, w, tokens, ids)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_moves_the_reference(control, leaves):
    """Each control changes the reference's own answers by more than float32
    arithmetic does (which reads 1e-6): at the published widths the check's
    limits lie between. ``no_state_carry`` acts on the hand-over from the
    prefill to the steps: it leaves step 1 as it is and moves the rest."""
    m, w, tokens = control_inputs(leaves)
    with jax.default_matmul_precision("highest"):
        sound = ref.forward(m, w, tokens, [3, 9, 27], 4)
        moved = recurrent_forward(m, w, tokens, [3, 9, 27], control)
    diff = np.abs(np.log(moved) - np.log(sound)).max(axis=1)
    if control == "no_state_carry":
        assert diff[0] < 1e-5 and diff[1:].min() > 1e-4, diff
    else:
        assert diff.min() > 1e-4, diff


@pytest.mark.parametrize("control", [None, "state_bf16"])
def test_the_state_form_is_the_attention_form_but_for_its_rounding(control):
    """Over 48 tokens in chunks of 16 from a zero state: the attention form
    to float32 arithmetic, or within bfloat16's rounding of the state where
    ``state_bf16`` rounds it."""
    q, k, v, log_g, _ = retention_inputs((48,), b=1, t=48, d=16)
    q, k, v, log_g = (a[0] for a in (q, k, v, log_g))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention_form(q.reshape(48, 6, 16), k, v, log_g))
        got = np.asarray(ref.state_form(q.reshape(48, 6, 16), k, v, log_g, *ref.zero_state(k), 16, control)[0])
    err = np.abs(got - want).max()
    assert (err < 2e-5) if control is None else (1e-5 < err < 0.05), err


def test_an_export_is_read_leaf_by_leaf_into_the_programs_parameters(tmp_path, leaves):
    m = dict(SMALL, dtype="bfloat16")
    brumby_weights.write_export(m, SEED, tmp_path, threads=2)
    params = read_leaf_export(str(tmp_path), br.leaf_table(CFG), br.param_shapes(CFG))
    assert set(params) == set(br.param_shapes(CFG)) and params["head"].dtype == jnp.bfloat16
    assert np.array_equal(params["layer1/attn/w_g"], leaves["layer1/attn/w_g"].astype(jnp.bfloat16))
    assert np.array_equal(params["layer0/attn/b_g"], leaves["layer0/attn/b_g"].astype(jnp.bfloat16))
    full = br.Config.from_dict(FULL["server_model"]["decoder"])
    assert {t[0]: tuple(t[1]) for t in br.leaf_table(full)} == ref.all_leaves(FULL["model"])
    assert (full.max_token_slots, full.answer_steps, full.num_hidden_layers, full.vocab_size, full.per) == \
        (16384, 64, 5, 151936, 5)


def test_the_family_is_found_by_its_zoo_name_and_by_its_sizes():
    assert shared.family("brumby") is br and "brumby" in shared.families()
    assert shared.family(None, DECODER) is br
    model = decoder_converted(dict(DECODER, num_hidden_layers=0), topk=5, name="brumby")
    assert model.from_canvases and model.counter_names == br.COUNTERS
    assert [model.max_rows(s) for s in (64, 128)] == [16, 4]
    for what in ("Config", "param_shapes", "leaf_table", "init_params", "answer", "COUNTERS"):
        assert hasattr(br, what), what
    p = br.init_params(CFG, seed=1)
    memory = 1 + np.exp(p["layer0/attn/b_g"])
    assert ((64 <= memory) & (memory <= 4096)).all()


def test_the_decoders_program_is_jit_serve_and_its_scopes_name_the_phases():
    """What the benchmark's readers find the model's work by: the serve
    program is ``jit_serve`` and each phase a scope in its operations'
    names. The engine itself: a real one at the small size, two canvas buckets."""
    import re
    from tensorflow_web_deploy_tpu.parallel.mesh import build_mesh
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

    one_device = build_mesh([jax.devices("cpu")[0]])
    cfg = ServerConfig(
        model=ModelConfig(name="brumby", source="native", task="generate", decoder=dict(DECODER, answer_steps=4),
                          dtype="float32", topk=5),
        canvas_buckets=(64, 128), batch_buckets=(1, 2, 4, 8), max_batch=8, ragged=True, wire_format="rgb")
    engine = InferenceEngine(cfg, mesh=one_device)
    try:
        assert engine.counter_names == br.COUNTERS
        assert [engine.max_rows(s) for s in (64, 128)] == [8, 4]
        exe, _ = engine._get_serve_exe(engine._replicas[0], 64, 2)
        text = exe.as_text()
        assert re.match(r"HloModule jit_serve\b", text)
        for scope in ("patches", "retention", "mlp", "head", "cached_steps"):
            assert re.search(rf'op_name="jit\(serve\)/[^"]*\b{scope}/', text), scope
        for inside in ("retention", "mlp", "head"):                      # the steps' own, under the one scope
            assert re.search(rf'op_name="jit\(serve\)/[^"]*\bcached_steps/[^"]*\b{inside}/', text), inside
        _, canvases, hws = images(64, sizes=SIZES[:4])
        scores, ids = engine.run_batch(np.asarray(canvases), np.asarray(hws))
        assert scores.shape == ids.shape == (4, 4, 5)
    finally:
        engine.close()
