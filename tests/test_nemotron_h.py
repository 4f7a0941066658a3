"""The second token decoder (models/nemotron_h.py, ops/ssd.py, ops/gqa.py,
the second router and expert shape of ops/experts.py) against the plain
reference of benchmark/reference/nemotron_h.py, at a small size on the CPU:
hidden 64, the five layers ``EM*ME``, 4 Mamba heads of 16 with state 32 in 2
groups, chunk 16, 4 query over 2 key/value heads of 16, 8 of 16 experts of
width 32 held with 4 a token, 64 ids, 16 answer steps."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.reference import nemotron_h as ref, nemotron_h_floors, nemotron_h_weights
from tensorflow_web_deploy_tpu.models import decoder as shared, longcat_flash as lf, nemotron_h as nh
from tensorflow_web_deploy_tpu.models.adapter import decoder_converted, read_leaf_export
from tensorflow_web_deploy_tpu.ops import experts, gqa, mla, ssd
from tensorflow_web_deploy_tpu.ops.image import patch_tokens
from tensorflow_web_deploy_tpu.serving import costmodel

ROOT = Path(__file__).resolve().parents[1]
FULL = json.loads((ROOT / "benchmark" / "configs" / "nemotron-3-nano-30b-ep2-pp4-13l-bf16.json").read_text())
SMALL = {"hidden_size": 64, "hybrid_override_pattern": "EM*ME", "mamba_num_heads": 4, "mamba_head_dim": 16,
         "ssm_state_size": 32, "n_groups": 2, "conv_kernel": 4, "chunk_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 64, "n_routed_experts": 16, "num_experts_per_tok": 4,
         "routed_scaling_factor": 2.5, "experts_held": 8, "experts_held_first": 0, "vocab_size": 64,
         "layer_norm_epsilon": 1e-5, "patch": 8, "answer_steps": 16, "max_token_slots": 1024,
         "norm_topk_prob": True, "topk": 5, "dtype": "float32", "leaf_gain": FULL["model"]["leaf_gain"]}
DECODER = {k: v for k, v in SMALL.items() if k not in ("topk", "dtype", "leaf_gain", "norm_topk_prob")}
CFG = nh.Config.from_dict(DECODER)
SEED = 2**31 + 5
# lengths that end inside a chunk (35, 32 + 3), on a chunk's edge (48, 32, 64), and under 3 tokens (2, 1)
SIZES = ((64, 48), (40, 56), (33, 64), (64, 64), (8, 16), (8, 8))
STEPS = SMALL["answer_steps"]


@pytest.fixture(scope="module")
def leaves():
    return {n: ref.make_leaf(SEED, n, s, SMALL) for n, s in ref.all_leaves(SMALL).items()}


def program_params(leaves, cfg=CFG, dtype=jnp.float32):
    """The export's leaves where the program keeps them (an expert's matrix in its layer's stack)."""
    params = {k: np.zeros(s, np.float32) for k, s in nh.param_shapes(cfg).items()}
    for leaf, _, name, index in nh.leaf_table(cfg):
        params[name][index] = leaves[leaf]
    return {k: jnp.asarray(v).astype(dtype) for k, v in params.items()}


def images(canvas: int, sizes=SIZES, seed=0):
    rs = np.random.default_rng(seed)
    imgs = [rs.integers(0, 256, (h, w, 3), dtype=np.uint8) for h, w in sizes]
    canvases = np.zeros((len(imgs), canvas, canvas, 3), np.uint8)
    for i, im in enumerate(imgs):
        canvases[i, :im.shape[0], :im.shape[1]] = im
    return imgs, jnp.asarray(canvases), jnp.asarray([im.shape[:2] for im in imgs], jnp.int32)


def served(leaves, dtype, canvas=64):
    """The program's answers for SIZES: prefill, then fifteen steps through both kinds of state."""
    imgs, canvases, hws = images(canvas)
    tokens, lengths = patch_tokens(canvases, hws, SMALL["patch"])
    params = program_params(leaves, dtype=dtype)
    with jax.default_matmul_precision("highest"):
        scores, ids, counters = jax.jit(lambda p, t, l: nh.answer(CFG, p, t, l, SMALL["topk"]))(params, tokens, lengths)
    return imgs, np.asarray(scores), np.asarray(ids), dict(zip(nh.COUNTERS, np.asarray(counters)))


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


def test_the_export_and_the_program_name_the_same_leaves():
    assert {t[0]: tuple(t[1]) for t in nh.leaf_table(CFG)} == ref.all_leaves(SMALL)
    full = nh.Config.from_dict(FULL["server_model"]["decoder"])
    assert {t[0]: tuple(t[1]) for t in nh.leaf_table(full)} == ref.all_leaves(FULL["model"])
    assert (full.max_token_slots, full.answer_steps, full.hybrid_override_pattern, full.vocab_size, full.experts_held) \
        == (16384, 16, "EMEMEM*EMEMEM", 65536, 64)
    # the stacks hold an expert's hidden width in whole blocks of 128: 1,856 -> 1,920, the rest zero
    assert nh.param_shapes(full)["layer0/experts/w_up"] == (64, 2688, 1920) and full.expert_rows == 1920
    assert sum(int(np.prod(t[1])) for t in nh.leaf_table(full)) == nemotron_h_floors.param_count(FULL["model"])
    p = nh.init_params(CFG, seed=1)
    assert not p["layer0/experts/w_up"][..., 32:].any() and not p["layer0/experts/w_down"][:, 32:].any()
    assert p["layer0/experts/w_up"][..., :32].all() and not p["layer0/router_bias"].any()


def test_prefill_and_steps_through_both_states_equal_the_references_one_forward_in_float32(leaves):
    """Tight: both compute in float32 at ``highest``; what differs is the
    order of sums (chunks against the token-by-token recurrence, the blocked
    softmax, the cache, the experts' order). Logits, not ids."""
    imgs, scores, ids, counters = served(leaves, jnp.float32)
    values = check.compare(*against_reference(leaves, imgs, scores, ids))
    assert values["logit_max"] < 5e-5, values
    tokens = sum((h // 8) * (w // 8) for h, w in SIZES)
    n = len(SIZES)
    assert counters["images"] == n and counters["tokens_real"] == tokens and counters["token_slots"] == n * 64
    assert counters["token_slots_pad"] == n * 64 - tokens
    # two expert layers, four picks a real token and a step; about half of them held (8 of 16)
    assert counters["picks"] == 2 * 4 * (tokens + n * (STEPS - 1))
    assert 0.35 < counters["held_picks"] / counters["picks"] < 0.65
    assert counters["held_expert_load_max"] >= counters["held_expert_load_mean"] > 0
    # two Mamba layers, chunks of 16 slots: a chunk with no real token is skipped, and counted
    live = sum(-(-(h // 8) * (w // 8) // 16) for h, w in SIZES)
    assert counters["ssd_chunks"] == 2 * n * 4 and counters["ssd_chunks_skipped"] == 2 * (n * 4 - live) > 0
    assert counters["answer_steps"] == n * STEPS and counters["answer_steps_cached"] == n * (STEPS - 1)
    assert 100.0 * counters["answer_steps_cached"] / counters["answer_steps"] == 93.75       # cached_step_share


def test_in_bfloat16_it_stays_within_the_stated_tolerance(leaves):
    """bfloat16 weights and products (float32 accumulation, norms, softmax,
    decays, state and residual stream) against the float32 reference: an
    answer's logits move by a few hundredths of their spread here (five
    layers of hidden 64 under the published widths' gains; a pick that
    rounding moves to another expert is in it); another image's answers
    read above 0.3."""
    imgs, scores, ids, _ = served(leaves, jnp.bfloat16)
    ref_probs, pairs = against_reference(leaves, imgs, scores, ids)
    values = check.compare(ref_probs, pairs)
    assert values["logit_rms"] < 0.05 and values["logit_max"] < 0.4, values
    rotated = pairs[STEPS:] + pairs[:STEPS]
    assert check.compare(ref_probs, rotated)["logit_rms"] > 0.3


def test_a_row_padded_to_a_longer_canvas_answers_all_sixteen_steps_as_the_same_row_unpadded(leaves):
    """The state and the conv tail are taken at ``lengths``, the keys past
    it are masked, padding is routed nowhere: 64 slots or 256, every step's
    ids and scores are the same."""
    _, s_small, i_small, c_small = served(leaves, jnp.float32, canvas=64)
    _, s_large, i_large, c_large = served(leaves, jnp.float32, canvas=128)
    assert np.array_equal(i_small, i_large) and i_small.shape == (len(SIZES), STEPS, 5)
    np.testing.assert_allclose(s_small, s_large, rtol=2e-5)
    assert c_small["tokens_real"] == c_large["tokens_real"] and c_large["token_slots"] == len(SIZES) * 256
    assert c_small["picks"] == c_large["picks"] and c_large["ssd_chunks"] == 4 * c_small["ssd_chunks"]


def scan_inputs(b, t, h, p, g, n, lengths, seed=0):
    rs = np.random.default_rng(seed)
    xbc = jnp.asarray(rs.standard_normal((b, t, h * p + 2 * g * n)).astype(np.float32))
    valid = jnp.arange(t)[None] < jnp.asarray(lengths)[:, None]
    dt = jnp.where(valid[..., None], jnp.asarray(rs.uniform(0.001, 0.1, (b, t, h)).astype(np.float32)), 0.0)
    return xbc, dt, -jnp.asarray(rs.uniform(1, 16, h).astype(np.float32)), jnp.asarray(lengths, jnp.int32)


def recurrence(xbc, dt, a, h, p, g, n):
    """The definition, token by token: (y [B, T, H*P], state after the last slot)."""
    b, t, _ = xbc.shape
    x = xbc[..., :h * p].reshape(b, t, h, p)
    bm = jnp.repeat(xbc[..., h * p:h * p + g * n].reshape(b, t, g, n), h // g, axis=2)
    cm = jnp.repeat(xbc[..., h * p + g * n:].reshape(b, t, g, n), h // g, axis=2)

    def token(state, inputs):
        x_t, b_t, c_t, d_t = inputs
        state = jnp.exp(d_t * a)[..., None, None] * state + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")

    first = lambda z: jnp.moveaxis(z, 1, 0)
    state, y = jax.lax.scan(token, jnp.zeros((b, h, p, n)), (first(x), first(bm), first(cm), first(dt)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, h * p), state


@pytest.mark.parametrize("lengths", [(32, 13, 2), (8, 16, 1), (24, 0, 31)],
                         ids=["inside-a-chunk-and-under-3", "on-a-chunks-edge", "an-empty-row"])
def test_the_chunked_scan_equals_the_token_by_token_recurrence(lengths):
    """Row lengths that are and are not multiples of the chunk, padding
    slots behind: the real slots' outputs, and the state after the row ==
    the state after its last real token (delta 0 at padding)."""
    xbc, dt, a, n_real = scan_inputs(3, 32, 4, 16, 2, 32, lengths)
    sizes = dict(heads=4, head_dim=16, groups=2)
    with jax.default_matmul_precision("highest"):
        y, state = ssd.chunked_scan(xbc, dt, a, chunk=8, **sizes)
        want_y, _ = recurrence(xbc, dt, a, 4, 16, 2, 32)
        for row, n in enumerate(lengths):
            _, want_state = recurrence(xbc[row:row + 1, :n], dt[row:row + 1, :n], a, 4, 16, 2, 32)
            np.testing.assert_allclose(np.asarray(state[row]), np.asarray(want_state[0]), atol=2e-6)
            np.testing.assert_allclose(np.asarray(y[row, :n]), np.asarray(want_y[row, :n]), atol=2e-5)
    chunks, skipped = ssd.chunk_counts(n_real, 32, 8)
    assert chunks == 12 and skipped == sum(4 - -(-n // 8) for n in lengths)


# heads side by side in one block of 128 lanes: eight of 16 (a group's four, all at once), two of 64 (the
# published shape), one of 128 (nothing to share)
@pytest.mark.parametrize("heads,head_dim,groups,lengths", [
    (8, 16, 2, (32, 13, 2)), (4, 64, 2, (16, 0, 32)), (4, 64, 1, (32, 13, 2)), (2, 128, 1, (16, 0, 32))])
def test_the_scan_kernel_equals_the_walk_through_the_interpreter(heads, head_dim, groups, lengths):
    xbc, dt, a, n_real = scan_inputs(3, 32, heads, head_dim, groups, 32, lengths, seed=1)
    sizes = dict(heads=heads, head_dim=head_dim, groups=groups, chunk=8)
    with jax.default_matmul_precision("highest"):
        y, state = ssd.chunked_scan(xbc, dt, a, **sizes)
        y_k, state_k = ssd.pallas_scan(xbc, dt, a, n_real, interpret=True, **sizes)
    np.testing.assert_allclose(np.asarray(state_k), np.asarray(state), atol=2e-6)
    for row, n in enumerate(lengths):
        live = -(-n // 8) * 8
        np.testing.assert_allclose(np.asarray(y_k[row, :live]), np.asarray(y[row, :live]), atol=5e-6)
        assert not np.asarray(y_k[row, live:]).any()                     # a skipped chunk is written as zeros


def test_the_conv_hands_on_the_three_inputs_before_a_rows_length_and_a_step_goes_on_from_them():
    rs = np.random.default_rng(4)
    x = jnp.asarray(rs.standard_normal((3, 12, 6)).astype(np.float32))
    w, bias = jnp.asarray(rs.standard_normal((4, 6)).astype(np.float32)), jnp.asarray(rs.standard_normal(6).astype(np.float32))
    lengths = jnp.asarray([12, 5, 2], jnp.int32)
    y, tail = ssd.causal_conv(x, w, bias, lengths)
    for row, n in enumerate((12, 5, 2)):
        want = np.concatenate([np.zeros((3, 6), np.float32), np.asarray(x[row, :n])])[-3:]
        np.testing.assert_allclose(np.asarray(tail[row]), want)       # taken at the last real token, not at the row's end
        t = n - 1
        seen = np.concatenate([np.zeros((3, 6), np.float32), np.asarray(x[row])])[t:t + 4]
        np.testing.assert_allclose(np.asarray(y[row, t]), np.asarray(bias) + (np.asarray(w) * seen).sum(0), rtol=1e-5)


# ------------------------------------------------------------------ the two causal cores, one discipline
# ``mla_prefill`` takes latent-attention operands (two-part queries, one shared rotary key); ``gqa_prefill`` is its
# sibling for grouped plain heads. Each case below runs over both: the blocked walk and the kernel against a full
# masked softmax, at lengths that end inside a block, with a row that stops early.

def _plain(scores, v_of, lengths, t):
    """softmax(scores) v with the cores' mask, padding queries zero; ``scores`` [B, H, T, T], ``v_of`` [B, H, T, d]."""
    s = jnp.where(mla._mask(t, t, 0, 0, lengths), scores, mla.NEG)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v_of, precision="highest")
    return jnp.where((jnp.arange(t)[None, :] < lengths[:, None])[:, None, :, None], out, 0.0)


def core_case(kind: str, t: int, lengths, seed: int):
    """(plain, blocked(block), kernel()) of one core on seeded inputs, outputs as [B, heads, T, d]."""
    rs = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rs.standard_normal(s).astype(np.float32))
    n, scale = jnp.asarray(lengths, jnp.int32), 0.2
    if kind == "mla":
        q_n, q_r, k_n, k_r, v = mk(2, 3, t, 16), mk(2, 3, t, 8), mk(2, 3, t, 16), mk(2, t, 8), mk(2, 3, t, 16)
        scores = (jnp.einsum("bhqd,bhkd->bhqk", q_n, k_n, precision="highest")
                  + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r, precision="highest")) * scale
        args = (q_n, q_r, k_n, k_r, v, n, scale)
        return (_plain(scores, v, n, t), lambda block: mla.blocked_core(*args, block=block),
                lambda: mla.pallas_core(*args, interpret=True))
    q, k, v = mk(2, 2, 3, t, 16), mk(2, 2, t, 16), mk(2, 2, t, 16)
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k, precision="highest").reshape(2, 6, t, t) * scale
    flat = lambda o: o.reshape(2, 6, t, 16)
    return (_plain(scores, jnp.repeat(v, 3, axis=1), n, t), lambda block: flat(gqa.blocked_core(q, k, v, n, scale, block=block)),
            lambda: flat(gqa.pallas_core(q, k, v, n, scale, interpret=True)))


@pytest.mark.parametrize("kind", ["mla", "gqa"])
@pytest.mark.parametrize("t,lengths", [(40, (40, 23)), (100, (100, 1))])
def test_a_blocked_core_equals_the_full_masked_softmax_at_a_length_that_is_no_multiple_of_the_block(kind, t, lengths):
    with jax.default_matmul_precision("highest"):
        plain, blocked, _ = core_case(kind, t, lengths, t)
        np.testing.assert_allclose(np.asarray(blocked(16)), np.asarray(plain), atol=2e-6)
    assert not np.asarray(plain[1, :, lengths[1]:]).any()               # padding queries answer zero


@pytest.mark.parametrize("kind", ["mla", "gqa"])
def test_a_prefill_kernel_equals_the_full_masked_softmax_through_the_interpreter(kind, monkeypatch):
    monkeypatch.setattr(mla, "pick_block", lambda t: 128)                # two blocks a row: one above the diagonal
    monkeypatch.setattr(gqa, "pick_block", lambda t, per: 128)           # is skipped, one stops at the row's length
    with jax.default_matmul_precision("highest"):
        plain, _, kernel = core_case(kind, 256, (256, 130), 1)
        np.testing.assert_allclose(np.asarray(kernel()), np.asarray(plain), atol=2e-6)


def test_the_block_a_grouped_core_walks_goes_with_the_query_heads_a_group_holds():
    assert [gqa.pick_block(t, 16) for t in (1024, 2304, 4096)] == [256, 256, 256]     # 4,096 rows of scores
    assert gqa.pick_block(4096, 5) == 512 and gqa.pick_block(384, 64) == 128 and gqa.pick_block(64, 16) == 64
    with pytest.raises(ValueError):
        gqa.pick_block(2000, 16)


# ------------------------------------------------------------------ the second router, the second expert shape

def test_the_selection_bias_moves_the_choice_and_not_the_weight_and_weights_sum_to_the_scale():
    rs = np.random.default_rng(6)
    u, w_r = jnp.asarray(rs.standard_normal((50, 64)).astype(np.float32)), jnp.asarray(rs.standard_normal((64, 16)).astype(np.float32) / 8)
    weights, ids = experts.route_sigmoid(u, w_r, None, 4, 2.5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(u, w_r, precision="highest")))
    picked = np.take_along_axis(s, np.asarray(ids), axis=1)
    assert np.array_equal(np.sort(np.asarray(ids), axis=1), np.sort(np.argsort(-s, axis=1)[:, :4], axis=1))
    np.testing.assert_allclose(np.asarray(weights), 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # a bias that lifts expert 11 over every score: it is chosen by every token, and weighs by its own score
    bias = jnp.zeros(16).at[11].set(1.0)
    w_b, ids_b = experts.route_sigmoid(u, w_r, bias, 4, 2.5)
    assert (np.asarray(ids_b) == 11).any(axis=1).all() and not (np.asarray(ids) == 11).any(axis=1).all()
    picked_b = np.take_along_axis(s, np.asarray(ids_b), axis=1)          # the scores, without the bias
    np.testing.assert_allclose(np.asarray(w_b), 2.5 * picked_b / picked_b.sum(-1, keepdims=True), rtol=1e-6)
    # the reference's router says the same
    m = dict(SMALL, num_experts_per_tok=4)
    w_ref, ids_ref = ref.route(m, {"router": w_r, "router_bias": bias}, u)
    assert np.array_equal(np.asarray(ids_ref), np.asarray(ids_b))
    np.testing.assert_allclose(np.asarray(w_ref), np.asarray(w_b), rtol=1e-6)


def test_the_two_halves_parts_with_the_shared_expert_once_add_up_to_the_uncut_layer(leaves):
    """The share test: a chip that holds experts 0-7 and one that holds 8-15
    each route over all 16 and compute their own experts' part; the shared
    expert is computed by both alike. Their parts, the shared expert counted
    once, are what the uncut reference gives for the whole layer."""
    uncut = dict(SMALL, experts_held=16)
    w = {n[len("layer0/"):]: ref.make_leaf(SEED, n, s, uncut) for n, s in ref.all_leaves(uncut).items()
         if n.startswith("layer0/")}
    rs = np.random.default_rng(8)
    u = jnp.asarray(rs.standard_normal((70, 64)).astype(np.float32))
    valid = jnp.ones(70, bool)
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(uncut, ref.stack_experts(uncut, w), u)
        shared_part = ref.experts(uncut, ref.stack_experts(uncut, w), u, "no_held_experts")
        parts, held_picks = [], 0
        for first in (0, 8):
            cfg = nh.Config.from_dict(dict(DECODER, experts_held_first=first))
            p = {k: np.zeros(s, np.float32) for k, s in nh.layer_shapes(cfg, "E").items()}
            for leaf, _, name, index in nh.leaf_table(cfg):
                if leaf.startswith("layer0/"):
                    p[name[len("layer0/"):]][index] = w[leaf[len("layer0/"):]]
            m, counters = nh._moe(cfg, {k: jnp.asarray(v) for k, v in p.items()}, u, valid)
            parts.append(m)
            held_picks += counters["held_picks"]
            assert counters["picks"] == 70 * 4
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared_part), np.asarray(whole), atol=2e-5)
    assert held_picks == 70 * 4                                          # every pick is held by exactly one half
    assert np.abs(np.asarray(parts[0] - parts[1])).max() > 0.01          # and the halves are not each other


@pytest.mark.parametrize("chunk,held_first", [(experts.CHUNK, 0), (256, 0), (256, 8)])
def test_the_grouped_sum_of_two_matrix_experts_equals_the_dense_one(monkeypatch, chunk, held_first):
    """Grouping, windows and ``expert_gmm`` are the three-matrix experts'
    own; the squared ReLU between two matrices goes through them alike."""
    monkeypatch.setattr(experts, "CHUNK", chunk)
    rs = np.random.default_rng(2)
    mk = lambda *s: jnp.asarray(rs.standard_normal(s).astype(np.float32))
    u, w_router = mk(300, 64), mk(64, 16) / 8
    w = (mk(6, 64, 32) / 8, mk(6, 32, 64) / 5)
    valid = jnp.arange(300) < 280
    with jax.default_matmul_precision("highest"):
        weights, ids = experts.route_sigmoid(u, w_router, None, 4, 2.5)
        weights = jnp.where(valid[:, None], weights, 0.0)
        dense = experts._dense_sum(u, experts.held_weights(weights, ids, held_first, 6), *w)
        grouped = experts._grouped_sum(u, weights, ids, held_first, *w, interpret=True)
        layer, counted = experts.expert_layer(u, valid, w_router, *w, topk=4, scale=2.5, n_routed=16,
                                              held_first=held_first, router="sigmoid")
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), atol=1e-5)
    assert not np.asarray(dense[280:]).any() and np.asarray(dense[:280]).any()
    np.testing.assert_allclose(np.asarray(layer), np.asarray(dense), atol=1e-5)       # no zero experts: nothing added
    assert counted["picks"] == 280 * 4 and counted["zero_picks"] == 0
    one = experts.relu2(u[:5], w[0][2], w[1][2])
    np.testing.assert_allclose(np.asarray(one), np.square(np.maximum(np.asarray(u[:5] @ w[0][2]), 0)) @ np.asarray(w[1][2]),
                               rtol=2e-5, atol=1e-6)


def test_the_column_tile_of_the_grouped_product_divides_the_columns():
    assert [experts._col_tile(n, want) for n, want in ((2048, 256), (6144, 512), (2688, 512), (1920, 512), (32, 256))] \
        == [256, 512, 384, 384, 32]


# ------------------------------------------------------------------ the reference's own parts

def test_one_forward_over_the_served_ids_reads_every_step(leaves):
    """Causal, the recurrence and the conv too: position T - 1 + s of a
    forward over the image's tokens and fifteen ids is what a forward over
    the first T + s tokens ends in; ids that come later move nothing."""
    w = {k: jnp.asarray(v) for k, v in leaves.items()}
    rs = np.random.default_rng(5)
    tokens = ref.patches(rs.integers(0, 256, (40, 56, 3), dtype=np.uint8), 8)
    ids = [7, 63, 0, 5, 5, 9, 1, 44, 2, 3, 17, 8, 30, 31, 60]
    at_once = ref.forward(SMALL, w, tokens, ids, 16)
    for s in (0, 1, 7, 15):
        alone = ref.forward(SMALL, w, tokens, ids[:s], 1)[0]
        np.testing.assert_allclose(at_once[s], alone, rtol=2e-4, atol=1e-7)
    filled = ref.forward(SMALL, w, tokens, ids[:4] + [0] * 11, 16)
    np.testing.assert_allclose(filled[:5], at_once[:5], rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_control_moves_the_reference(control, leaves):
    """Each control changes the reference's own answers by far more than
    float32 arithmetic does (which reads 1e-6): at the published widths the
    check's limits lie between (PERF.md). The two that break the hand-over
    from the prefill to the steps leave step 1 as it is and move the rest."""
    w = {k: jnp.asarray(v) for k, v in dict(leaves).items()}
    m = dict(SMALL, leaf_gain={})
    rs = np.random.default_rng(9)
    tokens = ref.patches(rs.integers(0, 256, (64, 48, 3), dtype=np.uint8), 8)
    sound = ref.forward(m, w, tokens, [3, 9, 27], 4)
    moved = ref.forward(m, w, tokens, [3, 9, 27], 4, control)
    diff = np.abs(np.log(moved) - np.log(sound)).max(axis=1)
    if control in ("no_state_carry", "no_conv_tail"):
        assert diff[0] < 1e-5 and diff[1:].min() > 1e-3, diff
    else:
        assert diff.min() > 1e-3, diff


def test_an_export_is_read_leaf_by_leaf_into_the_programs_parameters(tmp_path, leaves):
    m = dict(SMALL, dtype="bfloat16")
    nemotron_h_weights.write_export(m, SEED, tmp_path, threads=2)
    params = read_leaf_export(str(tmp_path), nh.leaf_table(CFG), nh.param_shapes(CFG))
    assert set(params) == set(nh.param_shapes(CFG)) and params["head"].dtype == jnp.bfloat16
    assert np.array_equal(params["layer1/mixer/w_in"], leaves["layer1/mixer/w_in"].astype(jnp.bfloat16))
    assert np.array_equal(params["layer1/mixer/dt_bias"], leaves["layer1/mixer/dt_bias"].astype(jnp.bfloat16))
    stack = params["layer4/experts/w_down"]
    assert stack.shape == (8, 128, 64) and not np.asarray(stack[:, 32:], np.float32).any()
    assert np.array_equal(stack[3, :32], leaves["layer4/expert3/w_down"].astype(jnp.bfloat16))
    assert np.array_equal(nemotron_h_weights.read_leaf(m, tmp_path, "layer0/expert7/w_up"),
                          np.asarray(leaves["layer0/expert7/w_up"].astype(jnp.bfloat16), np.float32))
    # a second export over the first writes in place, and another seed is other weights
    nemotron_h_weights.write_export(m, SEED + 1, tmp_path, threads=2)
    assert not np.array_equal(nemotron_h_weights.read_leaf(m, tmp_path, "head"),
                              np.asarray(leaves["head"].astype(jnp.bfloat16), np.float32))


def test_a_large_leaf_is_drawn_in_row_blocks_that_the_check_makes_alone(monkeypatch):
    monkeypatch.setattr(ref, "BLOCK_VALUES", 1024)
    shape = (64, 64)
    assert ref.blocks(shape) == [(0, 16), (16, 32), (32, 48), (48, 64)] and ref.blocks((4096,)) == [(0, 4096)]
    whole = ref.make_leaf(SEED, "embed/token", shape, SMALL)
    np.testing.assert_array_equal(whole[16:32], ref.make_block(SEED, "embed/token", shape, SMALL, 1))
    assert abs(float(whole.std()) - 1.0) < 0.05


def test_a_family_is_found_by_its_zoo_name_and_longcat_flash_still_through_the_widened_adapter():
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig

    assert shared.family("nemotron_h") is nh and shared.family("longcat_flash") is lf
    assert shared.families() == ["longcat_flash", "nemotron_h", "brumby"]
    with pytest.raises(ValueError, match="no token decoder"):
        shared.family("resnet50")
    # a caller that holds only the sizes: the family whose Config states every one of them
    assert shared.family(None, DECODER) is nh and shared.family(None, {"kv_lora_rank": 16, "hidden_size": 8}) is lf
    with pytest.raises(ValueError, match="name the zoo entry"):
        shared.family(None, {"hidden_size": 8})
    model = decoder_converted(dict(DECODER, hybrid_override_pattern=""), topk=5, name="nemotron_h")
    assert model.from_canvases and model.counter_names == nh.COUNTERS
    assert [model.max_rows(s) for s in (64, 128)] == [16, 4]
    longcat = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-omni-ep32-4l-bf16.json").read_text())
    small = dict(longcat["server_model"]["decoder"], num_layers=0, vocab_size=8, hidden_size=8, ffn_hidden_size=8)
    assert decoder_converted(small, topk=5).counter_names == lf.COUNTERS
    assert decoder_converted(small, topk=5, name="longcat_flash").counter_names == lf.COUNTERS
    for c in (nh, lf):                                                   # the contract, by name
        for what in ("Config", "param_shapes", "leaf_table", "init_params", "answer", "COUNTERS"):
            assert hasattr(c, what), (c.__name__, what)
        assert callable(c.Config.from_dict) and callable(c.Config.token_slots)
    with pytest.raises(ValueError, match="a layer is one of"):
        nh.Config.from_dict(dict(DECODER, hybrid_override_pattern="EMX"))
    with pytest.raises(ValueError, match="the family's module"):
        ModelConfig(name="nemotron_h", source="native", task="generate")


def test_the_cost_models_walkers_equal_the_benchmarks_floors():
    m, decoder = FULL["model"], FULL["server_model"]["decoder"]
    c, f = costmodel.decoder_cost(decoder, "nemotron_h"), nemotron_h_floors
    assert costmodel.decoder_cost(decoder) == c                          # found by the sizes alone, too
    assert (c["mamba_params"], c["attn_params"], c["router_params"], c["shared_params"], c["expert_params"]) == \
        (f.mamba_params(m), f.attn_params(m), f.router_params(m), f.shared_params(m), f.expert_params(m)) == \
        (38_707_200, 23_396_352, 344_064, 19_955_712, 9_977_856)
    assert c["held_picks_per_token"] == f.held_picks_per_token(m) == 3.0
    assert c["layers"] == f.layers(m) == {"M": 6, "*": 1, "E": 6}
    assert c["param_count"] == f.param_count(m) == sum(int(np.prod(s)) for s in ref.all_leaves(m).values())
    assert c["dense_params"] == f.dense_params(m) and c["matrix_macs_per_token"] == f.matrix_macs_per_token(m)
    assert c["scan_macs_per_token"] == f.scan_macs_per_token(m) == 1_376_256
    assert c["step_macs_per_token"] == f.step_macs_per_token(m) == 2 * 64 * 64 * 128
    for t in (768.0, 1728.0, 3072.0):
        assert c["core_macs_per_token_sq"] * t * t == f.core_macs(m, t)
        assert c["decode_macs_per_cached_token"] * t == f.decode_macs(m, t)
        row = {"batches": 1, "rows_real": 2, "px_real": 2 * t * 1024}
        assert costmodel.decoder_image_flops(decoder, t, "nemotron_h") == f.image_flops(m, row)


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
        model=ModelConfig(name="nemotron_h", source="native", task="generate", decoder=DECODER, dtype="float32", topk=5),
        canvas_buckets=(64, 128), batch_buckets=(1, 2, 4, 8), max_batch=8, ragged=True, wire_format="rgb")
    engine = InferenceEngine(cfg, mesh=one_device)
    try:
        assert engine.counter_names == nh.COUNTERS
        assert [engine.max_rows(s) for s in (64, 128)] == [8, 4]          # 1,024 slots: 16 x 64, 4 x 256
        exe, _ = engine._get_serve_exe(engine._replicas[0], 64, 2)
        text = exe.as_text()
        assert re.match(r"HloModule jit_serve\b", text)
        for scope in ("patches", "mamba", "attention", "router", "experts", "shared_expert", "head", "cached_steps"):
            assert re.search(rf'op_name="jit\(serve\)/[^"]*\b{scope}/', text), scope
        for inside in ("mamba", "attention", "router", "experts", "head"):   # the steps' own, under the one scope
            assert re.search(rf'op_name="jit\(serve\)/[^"]*\bcached_steps/[^"]*\b{inside}/', text), inside
        imgs, canvases, hws = images(64, sizes=SIZES[:4])
        scores, ids = engine.run_batch(np.asarray(canvases), np.asarray(hws))
        assert scores.shape == ids.shape == (4, STEPS, 5)
    finally:
        engine.close()
