"""Device-economics cost model: analytic FLOPs and HBM-byte costs per
(model config, canvas bucket, batch bucket), plus backend peak detection —
the arithmetic the live ``/stats`` "economics" block and the bench/
profile_serve roofline tables are computed from.

Three layers:

1. **Analytic layer walk** (:func:`model_cost`): each zoo architecture's
   conv/depthwise/dense layers are re-walked from the SAME data tables the
   flax modules are built from (``mobilenet_v2._BLOCKS``,
   ``resnet50._STAGES``, the inception/ssd block structure), accumulating
   MACs, parameter scalars, and activation elements. FLOPs = 2 × MACs
   (conv/dense multiplies only — the standard convention the paper-quoted
   "300 M mult-adds" MobileNetV2 number uses; BN folds at inference and
   elementwise epilogues are noise next to the convs). The walk is pinned
   against hand-derived totals for mobilenet_v2 and resnet50 and against a
   real flax init's parameter count in tests/test_costmodel.py, so a model
   edit that forgets this file fails loudly.

2. **Traffic model**: per-image HBM bytes = activations written + read
   once each (2 × elements × dtype bytes), plus the params read once per
   BATCH (``param_bytes / batch`` per image), plus the uint8 input canvas
   and the (tiny) output. Arithmetic intensity = FLOPs / bytes; the
   roofline ridge point is ``peak_flops / peak_bw`` — a config whose AI
   sits above the ridge is compute-bound, below it bandwidth-bound, and
   the attainable ceiling is ``min(peak_flops, AI × peak_bw)``.

3. **Backend peaks** (:func:`backend_peak`): on TPU the per-chip dense
   bf16 peak and HBM bandwidth come from the spec-sheet table keyed by
   PJRT ``device_kind`` (:data:`DEVICE_PEAKS`; an unlisted kind raises).
   On the CPU dev mesh there is no spec sheet, so the peak is CALIBRATED
   ONCE per process: a jitted f32 matmul measures achievable FLOP/s and a
   jitted streaming add measures achievable bytes/s, cached under
   ``econ.lock``. CPU "MFU" is therefore fraction-of-calibrated-peak —
   honest for trend lines on the dev mesh, not comparable to TPU MFU.

Costs for models without an analytic walker (converter graphs outside the
zoo's four architectures) degrade gracefully: ``model_cost`` returns None
and the economics block reports measured device time without FLOP-derived
gauges.
"""

from __future__ import annotations

import math
import time

from ..utils.locks import named_lock

# Peak dense bf16 TFLOP/s and HBM GB/s of one chip, keyed by the exact
# PJRT ``device_kind`` (Google Cloud documentation, system architecture
# page of each generation; the v5e row is "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s). The one table every MFU and roofline denominator comes from —
# bench.py imports it. A TPU that is not listed is an error, not a default.
DEVICE_PEAKS = {
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),  # v5e
    "TPU v5p": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),  # v6e / Trillium
}


def device_peak(device_kind: str) -> tuple[float, float]:
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one ``device_kind`` chip."""
    try:
        tf, gb = DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s and bytes/s known for device_kind "
            f"{device_kind!r}: add its row to costmodel.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})") from None
    return tf * 1e12, gb * 1e9


def compute_dtype(dtype: str) -> str:
    """Serving dtype → the dtype the matrix units actually compute in.

    int8 serves dequant-on-the-fly: weights live in HBM as one byte per
    scalar but multiply at bfloat16 — its win is BYTES (param traffic,
    bandwidth ceiling), not FLOPs. So int8 and bf16 share a compute peak;
    only float32 computes at full width."""
    return "float32" if dtype == "float32" else "bfloat16"


# ------------------------------------------------------------ layer tape


class _Tape:
    """Shape-flow accumulator for one forward pass at batch 1.

    Tracks the live activation shape (h, w, c) and accumulates MACs,
    parameter scalars (kernels + BN scale/bias + dense bias — the flax
    ``params`` collection, NOT batch_stats), and activation elements
    written (every layer output, the HBM traffic model's input).
    """

    __slots__ = ("h", "w", "c", "macs", "params", "act_elems")

    def __init__(self, h: int, w: int, c: int = 3):
        self.h, self.w, self.c = h, w, c
        self.macs = 0
        self.params = 0
        self.act_elems = 0

    # Spatial arithmetic matches XLA's SAME/VALID conventions exactly.
    @staticmethod
    def _dim(d: int, k: int, s: int, padding: str) -> int:
        if padding == "SAME":
            return -(-d // s)  # ceil
        return (d - k) // s + 1

    def _out_hw(self, kernel, strides, padding):
        return (
            self._dim(self.h, kernel[0], strides[0], padding),
            self._dim(self.w, kernel[1], strides[1], padding),
        )

    def conv(self, features: int, kernel=(1, 1), strides=(1, 1),
             padding: str = "SAME", bn: bool = True, bias: bool = False):
        oh, ow = self._out_hw(kernel, strides, padding)
        self.macs += oh * ow * features * kernel[0] * kernel[1] * self.c
        self.params += kernel[0] * kernel[1] * self.c * features
        if bn:
            self.params += 2 * features  # scale + bias (batch_stats apart)
        if bias:
            self.params += features
        self.h, self.w, self.c = oh, ow, features
        self.act_elems += oh * ow * features

    def dwconv(self, kernel=(3, 3), strides=(1, 1), padding: str = "SAME",
               bn: bool = True):
        oh, ow = self._out_hw(kernel, strides, padding)
        self.macs += oh * ow * self.c * kernel[0] * kernel[1]
        self.params += kernel[0] * kernel[1] * self.c
        if bn:
            self.params += 2 * self.c
        self.h, self.w = oh, ow
        self.act_elems += oh * ow * self.c

    def pool(self, kernel=(3, 3), strides=(2, 2), padding: str = "VALID"):
        self.h, self.w = self._out_hw(kernel, strides, padding)
        self.act_elems += self.h * self.w * self.c

    def gap(self):
        self.h = self.w = 1
        self.act_elems += self.c

    def dense(self, features: int):
        self.macs += self.c * features
        self.params += self.c * features + features  # kernel + bias
        self.c = features
        self.act_elems += features

    # Branch/join for inception concats and residual shortcuts: a branch
    # clones the live shape, computes independently, and merges its
    # accumulators back (concat on channels / add in place).
    def branch(self) -> "_Tape":
        t = _Tape(self.h, self.w, self.c)
        return t

    def _absorb(self, other: "_Tape"):
        self.macs += other.macs
        self.params += other.params
        self.act_elems += other.act_elems

    def concat(self, *branches: "_Tape"):
        assert all((b.h, b.w) == (branches[0].h, branches[0].w)
                   for b in branches), "concat branches must agree spatially"
        for b in branches:
            self._absorb(b)
        self.h, self.w = branches[0].h, branches[0].w
        self.c = sum(b.c for b in branches)

    def add(self, other: "_Tape"):
        """Residual merge: shapes must match; FLOPs of the add are noise."""
        assert (self.h, self.w, self.c) == (other.h, other.w, other.c)
        self._absorb(other)


# ---------------------------------------------------------- arch walkers


def _inverted_residual(t: _Tape, w, features: int, stride: int,
                       expansion: int = 6):
    cin = t.c
    if expansion != 1:
        t.conv(cin * expansion, (1, 1))
    t.dwconv((3, 3), (stride, stride))
    t.conv(features, (1, 1))


def _walk_mobilenet_v2(t: _Tape, width: float, num_classes: int):
    from ..models.common import scale_ch
    from ..models.mobilenet_v2 import _BLOCKS

    w = lambda c: scale_ch(c, width)
    t.conv(w(32), (3, 3), (2, 2))
    for exp, c, n, s in _BLOCKS:
        for j in range(n):
            _inverted_residual(t, w, w(c), s if j == 0 else 1, exp)
    last = max(1280, scale_ch(1280, width)) if width > 1.0 else 1280
    t.conv(last, (1, 1))
    t.gap()
    t.dense(num_classes)


def _walk_resnet50(t: _Tape, width: float, num_classes: int):
    from ..models.common import scale_ch
    from ..models.resnet50 import _STAGES

    w = lambda c: scale_ch(c, width)
    t.conv(w(64), (7, 7), (2, 2))
    t.pool((3, 3), (2, 2), "SAME")
    for c, n, s in _STAGES:
        for j in range(n):
            feats, stride = w(c), (s if j == 0 else 1)
            out_ch = feats * 4
            shortcut = t.branch()
            if t.c != out_ch or stride != 1:
                shortcut.conv(out_ch, (1, 1), (stride, stride))
            t.conv(feats, (1, 1))
            t.conv(feats, (3, 3), (stride, stride))
            t.conv(out_ch, (1, 1))
            t.add(shortcut)
    t.gap()
    t.dense(num_classes)


def _walk_inception_v3(t: _Tape, width: float, num_classes: int):
    from ..models.common import scale_ch

    w = lambda c: scale_ch(c, width)
    # Stem: 299 → 35 spatial (all VALID except stem3).
    t.conv(w(32), (3, 3), (2, 2), "VALID")
    t.conv(w(32), (3, 3), padding="VALID")
    t.conv(w(64), (3, 3))
    t.pool((3, 3), (2, 2), "VALID")
    t.conv(w(80), (1, 1), padding="VALID")
    t.conv(w(192), (3, 3), padding="VALID")
    t.pool((3, 3), (2, 2), "VALID")

    def inception_a(pool_features):
        b1, b5, b3, bp = t.branch(), t.branch(), t.branch(), t.branch()
        b1.conv(w(64), (1, 1))
        b5.conv(w(48), (1, 1)); b5.conv(w(64), (5, 5))
        b3.conv(w(64), (1, 1)); b3.conv(w(96), (3, 3)); b3.conv(w(96), (3, 3))
        bp.pool((3, 3), (1, 1), "SAME"); bp.conv(w(pool_features), (1, 1))
        t.concat(b1, b5, b3, bp)

    def reduction_a():
        b3, bd, bp = t.branch(), t.branch(), t.branch()
        b3.conv(w(384), (3, 3), (2, 2), "VALID")
        bd.conv(w(64), (1, 1)); bd.conv(w(96), (3, 3))
        bd.conv(w(96), (3, 3), (2, 2), "VALID")
        bp.pool((3, 3), (2, 2), "VALID")
        t.concat(b3, bd, bp)

    def inception_b(c7_base):
        c7 = w(c7_base)
        b1, b7, bd, bp = t.branch(), t.branch(), t.branch(), t.branch()
        b1.conv(w(192), (1, 1))
        b7.conv(c7, (1, 1)); b7.conv(c7, (1, 7)); b7.conv(w(192), (7, 1))
        bd.conv(c7, (1, 1)); bd.conv(c7, (7, 1)); bd.conv(c7, (1, 7))
        bd.conv(c7, (7, 1)); bd.conv(w(192), (1, 7))
        bp.pool((3, 3), (1, 1), "SAME"); bp.conv(w(192), (1, 1))
        t.concat(b1, b7, bd, bp)

    def reduction_b():
        b3, b7, bp = t.branch(), t.branch(), t.branch()
        b3.conv(w(192), (1, 1)); b3.conv(w(320), (3, 3), (2, 2), "VALID")
        b7.conv(w(192), (1, 1)); b7.conv(w(192), (1, 7))
        b7.conv(w(192), (7, 1)); b7.conv(w(192), (3, 3), (2, 2), "VALID")
        bp.pool((3, 3), (2, 2), "VALID")
        t.concat(b3, b7, bp)

    def inception_c():
        b1, b3, bd, bp = t.branch(), t.branch(), t.branch(), t.branch()
        b1.conv(w(320), (1, 1))
        b3.conv(w(384), (1, 1))
        b3a, b3b = b3.branch(), b3.branch()
        b3a.conv(w(384), (1, 3)); b3b.conv(w(384), (3, 1))
        b3.concat(b3a, b3b)
        bd.conv(w(448), (1, 1)); bd.conv(w(384), (3, 3))
        bda, bdb = bd.branch(), bd.branch()
        bda.conv(w(384), (1, 3)); bdb.conv(w(384), (3, 1))
        bd.concat(bda, bdb)
        bp.pool((3, 3), (1, 1), "SAME"); bp.conv(w(192), (1, 1))
        t.concat(b1, b3, bd, bp)

    inception_a(32); inception_a(64); inception_a(64)
    reduction_a()
    inception_b(128); inception_b(160); inception_b(160); inception_b(192)
    reduction_b()
    inception_c(); inception_c()
    t.gap()
    t.dense(num_classes)


def _walk_ssd_mobilenet(t: _Tape, width: float, num_classes: int):
    from ..models.common import scale_ch
    from ..models.ssd_mobilenet import ASPECT_RATIOS

    w = lambda c: scale_ch(c, width)
    n_anchor = len(ASPECT_RATIOS)
    t.conv(w(16), (3, 3), (2, 2))
    for c, s in [(24, 2), (32, 2), (64, 2), (64, 1)]:
        _inverted_residual(t, w, w(c), s)
    _inverted_residual(t, w, w(128), 2)  # feat1, stride 32
    f1 = t.branch()
    _inverted_residual(t, w, w(256), 2)  # feat2, stride 64
    # Heads (plain nn.Conv: bias, no BN) on both feature maps.
    for feat in (f1, t):
        loc, cls = feat.branch(), feat.branch()
        loc.conv(n_anchor * 4, (3, 3), bn=False, bias=True)
        cls.conv(n_anchor * (num_classes + 1), (3, 3), bn=False, bias=True)
        t._absorb(loc)
        t._absorb(cls)


_WALKERS = {
    "mobilenet_v2": _walk_mobilenet_v2,
    "resnet50": _walk_resnet50,
    "inception_v3": _walk_inception_v3,
    "ssd_mobilenet": _walk_ssd_mobilenet,
}


# -------------------------------------------------------------- model cost

_cost_cache: dict[tuple, dict | None] = {}
_cost_lock = named_lock("econ.lock")


def model_cost(model_cfg) -> dict | None:
    """Analytic per-image cost of one model config, or None when the
    architecture has no walker (non-zoo converter graphs).

    Returns ``{"flops_per_image", "macs_per_image", "param_count",
    "param_bytes", "act_bytes_per_image", "dtype", "dtype_bytes"}`` —
    batch- and canvas-independent (the model always runs at its
    input_size; the canvas-dependent preprocess cost is
    :func:`preprocess_flops`). Byte terms are per-dtype so MFU and
    roofline_bound_fraction stay honest across the serving tiers:
    activations move at the COMPUTE width (f32 = 4 B, bf16 AND int8 =
    2 B — int8 dequantizes to bf16 on the fly), params at the STORAGE
    width (int8 = 1 B; the per-channel scales and unquantized BN/bias
    leaves are a sub-percent rounding error next to the kernels).
    """
    name = model_cfg.name
    walker = _WALKERS.get(name)
    if walker is None:
        return None
    width = float(getattr(model_cfg, "zoo_width", 1.0) or 1.0)
    from .. import models as zoo

    try:
        default_classes = zoo.get(name).num_classes
    except KeyError:
        default_classes = 1000
    classes = int(getattr(model_cfg, "zoo_classes", None) or default_classes)
    h, w = model_cfg.input_size
    dtype = getattr(model_cfg, "dtype", "bfloat16") or "bfloat16"
    dtype_bytes = 4 if dtype == "float32" else 2  # compute/activation width
    param_dtype_bytes = 1 if dtype == "int8" else dtype_bytes
    key = (name, width, classes, h, w, dtype)
    with _cost_lock:
        if key in _cost_cache:
            return _cost_cache[key]
    t = _Tape(int(h), int(w), 3)
    walker(t, width, classes)
    cost = {
        "macs_per_image": t.macs,
        "flops_per_image": 2 * t.macs,
        "param_count": t.params,
        "param_bytes": t.params * param_dtype_bytes,
        # Each activation written once and read once by its consumer.
        "act_bytes_per_image": 2 * t.act_elems * dtype_bytes,
        "dtype": dtype,
        "dtype_bytes": dtype_bytes,
    }
    with _cost_lock:
        _cost_cache[key] = cost
    return cost


# ------------------------------------------------------- token decoders

def _longcat_flash_cost(decoder: dict) -> dict:
    """Walkers for the ``longcat_flash`` family, from the sizes its config
    states: multiply-adds per
    token of each kind of block, per token squared of the attention core,
    and the parameters a call reads. Held equal to the benchmark's floors
    module (benchmark/reference/longcat_floors.py) by a test, as the conv
    walkers are to theirs.

    - ``mla_params``: one latent attention's matrices (query down and up,
      key/value down and up, output): a multiply-add each a token;
    - ``ffn_params``: one dense SwiGLU (three matrices);
    - ``router_params``: the router over routed and zero experts;
    - ``expert_params``: one routed expert (three matrices), and
      ``held_picks_per_token``: how many of a token's picks a uniform router
      sends to the experts held here;
    - ``core_macs_per_token_sq``: the causal core of one attention, per
      token squared (half the pairs, a score and a value each);
    - ``absorbed_macs_per_cached_token``: one new token's attention against
      one cached latent (score against the latent and the rotary key, the
      weighted sum of latents), all heads.
    """
    g = decoder.__getitem__
    d, h = g("hidden_size"), g("num_attention_heads")
    dn, dr, dv = g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim")
    rq, rkv = g("q_lora_rank"), g("kv_lora_rank")
    experts_all = g("n_routed_experts") + g("zero_expert_num")
    mla = d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d
    ffn = 3 * d * g("ffn_hidden_size")
    router = d * experts_all
    expert = 3 * d * g("expert_ffn_hidden_size")
    held = g("moe_topk") * g("experts_held") / experts_all
    layers = g("num_layers")
    return {
        "mla_params": mla, "ffn_params": ffn, "router_params": router, "expert_params": expert,
        "held_picks_per_token": held,
        "layer_macs_per_token": 2 * mla + 2 * ffn + router + held * expert,
        "core_macs_per_token_sq": h * (dn + dr + dv) / 2,
        "absorbed_macs_per_cached_token": h * (2 * rkv + dr),
        "dense_params": g("patch") ** 2 * 3 * d + d * g("vocab_size") + layers * (2 * mla + 2 * ffn + router),
        "param_count": (g("patch") ** 2 * 3 * d + 2 * d * g("vocab_size") + d
                        + layers * (2 * mla + 2 * ffn + router + g("experts_held") * expert
                                    + 4 * d + 2 * (rq + rkv))),
    }


def _longcat_flash_image_flops(decoder: dict, tokens: float) -> float:
    """Prefill (matrices per token, the core per token squared), the further
    answer steps against the cache, the head at every step."""
    c, layers = _longcat_flash_cost(decoder), decoder["num_layers"]
    more = decoder["answer_steps"] - 1
    prefill = (tokens * (decoder["patch"] ** 2 * 3 * decoder["hidden_size"] + layers * c["layer_macs_per_token"])
               + layers * 2 * c["core_macs_per_token_sq"] * tokens * tokens)
    steps = more * layers * (c["layer_macs_per_token"] + 2 * c["absorbed_macs_per_cached_token"] * tokens)
    head = decoder["answer_steps"] * decoder["hidden_size"] * decoder["vocab_size"]
    return 2.0 * (prefill + steps + head)


def _nemotron_h_cost(decoder: dict) -> dict:
    """Walkers for the ``nemotron_h`` family (a layer is one mixer, its kind a
    character of ``hybrid_override_pattern``), held equal to
    benchmark/reference/nemotron_h_floors.py by a test.

    - ``mamba_params``: a Mamba-2 mixer's two projections, a multiply-add
      each a token; ``scan_macs_per_token``: its chunked scan (per head two
      products against the state and half a chunk's masked product, per
      group half a chunk's ``C B'``); ``step_macs_per_token``: the
      recurrence of one token (state update and read-out);
    - ``attn_params``: query, key, value and output matrices;
      ``core_macs_per_token_sq``: the causal core per token squared (half
      the pairs, a score and a value each); ``decode_macs_per_cached_token``:
      one new token against one cached key and value, all query heads;
    - ``router_params``, ``shared_params`` (the shared expert's two
      matrices), ``expert_params`` (one routed expert's two) and
      ``held_picks_per_token``: how many of a token's picks a uniform router
      sends to the experts held here.
    """
    g = decoder.__getitem__
    d, pattern = g("hidden_size"), g("hybrid_override_pattern")
    h, p, n, groups, q = g("mamba_num_heads"), g("mamba_head_dim"), g("ssm_state_size"), g("n_groups"), g("chunk_size")
    d_inner, conv = h * p, h * p + 2 * groups * n
    hq, hk, dh = g("num_attention_heads"), g("num_key_value_heads"), g("head_dim")
    mamba = d * (d_inner + conv + h) + d_inner * d
    attn = d * (hq + 2 * hk) * dh + hq * dh * d
    router = d * g("n_routed_experts")
    shared = 2 * d * g("moe_shared_expert_intermediate_size")
    expert = 2 * d * g("moe_intermediate_size")
    held = g("num_experts_per_tok") * g("experts_held") / g("n_routed_experts")
    n_m, n_a, n_e = (pattern.count(k) for k in "M*E")
    small = {"M": g("conv_kernel") * conv + conv + 3 * h + d_inner + d, "*": d, "E": g("n_routed_experts") + d}
    outer = g("patch") ** 2 * 3 * d + 2 * d * g("vocab_size") + d
    return {
        "mamba_params": mamba, "attn_params": attn, "router_params": router, "shared_params": shared,
        "expert_params": expert, "held_picks_per_token": held,
        "scan_macs_per_token": h * (2 * p * n + q * p // 2) + groups * (q * n // 2),
        "step_macs_per_token": 2 * h * p * n,
        "core_macs_per_token_sq": hq * dh,
        "decode_macs_per_cached_token": hq * 2 * dh,
        "layers": {"M": n_m, "*": n_a, "E": n_e},
        "matrix_macs_per_token": n_m * mamba + n_a * attn + n_e * (router + shared + held * expert),
        "dense_params": (g("patch") ** 2 * 3 * d + d * g("vocab_size")
                         + n_m * mamba + n_a * attn + n_e * (router + shared)),
        "param_count": (outer + n_m * (mamba + small["M"]) + n_a * (attn + small["*"])
                        + n_e * (router + shared + g("experts_held") * expert + small["E"])),
    }


def _nemotron_h_image_flops(decoder: dict, tokens: float) -> float:
    """Prefill (matrices and the scan per token, the core per token squared),
    the further answer steps through both kinds of state, the head at every step."""
    c = _nemotron_h_cost(decoder)
    n = c["layers"]
    more = decoder["answer_steps"] - 1
    prefill = (tokens * (decoder["patch"] ** 2 * 3 * decoder["hidden_size"] + c["matrix_macs_per_token"]
                         + n["M"] * c["scan_macs_per_token"])
               + n["*"] * c["core_macs_per_token_sq"] * tokens * tokens)
    steps = more * (c["matrix_macs_per_token"] + n["M"] * c["step_macs_per_token"]
                    + n["*"] * c["decode_macs_per_cached_token"] * tokens)
    head = decoder["answer_steps"] * decoder["hidden_size"] * decoder["vocab_size"]
    return 2.0 * (prefill + steps + head)


def _brumby_cost(decoder: dict) -> dict:
    """Walkers for the ``brumby`` family (every layer gated power retention
    of degree 2 and a SwiGLU), held equal to
    benchmark/reference/brumby_floors.py by a test; ``features`` is the
    minimal symmetric map, ``d (d + 1) / 2`` products a head.

    - ``layer_params``: a layer's query, key, value, gate, output and FFN
      matrices, a multiply-add each a token;
    - ``chunked_macs_per_token``: the retention core in its chunked form
      (per query head the read-out against the state and half a chunk's
      masked products, per key/value head the state's update);
      ``attention_macs_per_token_sq`` and ``state_macs_per_token``: its
      attention form (half the row's scores and weighted values a query
      head, per token squared; the final state once, a token); a prefill
      costs the lesser;
    - ``step_macs_per_token``: one token through a layer's state (every
      query head's read-out, every key/value head's update).
    """
    g = decoder.__getitem__
    d, hq, hk, dh, layers = g("hidden_size"), g("num_attention_heads"), g("num_key_value_heads"), g("head_dim"), \
        g("num_hidden_layers")
    big = dh * (dh + 1) // 2
    layer = d * (hq + 2 * hk) * dh + hq * dh * d + d * hk + 3 * d * g("intermediate_size")
    return {
        "features": big, "layer_params": layer,
        "chunked_macs_per_token": hq * (big * dh + g("chunk_size") / 2 * 2 * dh) + hk * big * dh,
        "attention_macs_per_token_sq": hq * dh,
        "state_macs_per_token": hk * big * dh,
        "step_macs_per_token": big * dh * (hq + hk),
        "dense_params": g("patch") ** 2 * 3 * d + d * g("vocab_size") + layers * layer,
        "param_count": (g("patch") ** 2 * 3 * d + 2 * d * g("vocab_size") + d
                        + layers * (layer + 2 * d + 2 * dh + hk)),
    }


def _brumby_image_flops(decoder: dict, tokens: float) -> float:
    """Prefill (matrices per token, the retention core in the cheaper of its
    two forms), the further answer steps through the states, the head at every step."""
    c, layers = _brumby_cost(decoder), decoder["num_hidden_layers"]
    more = decoder["answer_steps"] - 1
    core = min(c["chunked_macs_per_token"], c["attention_macs_per_token_sq"] * tokens + c["state_macs_per_token"])
    prefill = tokens * (decoder["patch"] ** 2 * 3 * decoder["hidden_size"] + layers * (c["layer_params"] + core))
    steps = more * layers * (c["layer_params"] + c["step_macs_per_token"])
    head = decoder["answer_steps"] * decoder["hidden_size"] * decoder["vocab_size"]
    return 2.0 * (prefill + steps + head)


# A family's walkers, by its zoo name (models/decoder.py has what else a family keeps).
_DECODER_WALKERS = {"longcat_flash": (_longcat_flash_cost, _longcat_flash_image_flops),
                    "nemotron_h": (_nemotron_h_cost, _nemotron_h_image_flops),
                    "brumby": (_brumby_cost, _brumby_image_flops)}


def _walkers(decoder: dict, name: str | None):
    if name is None:    # a caller that holds only the sizes: the family whose Config states them
        from ..models.decoder import family
        name = family(None, decoder).__name__.rsplit(".", 1)[-1]
    return _DECODER_WALKERS[name]


def decoder_cost(decoder: dict, name: str | None = None) -> dict:
    """A token decoder's walkers, from the sizes its config states
    (``ModelConfig.decoder``) and its family's zoo name: multiply-adds per
    token of each kind of block, per token squared of the attention core,
    and the parameters a call reads. Each family's are held equal to its
    floors module under ``benchmark/reference/`` by a test."""
    return _walkers(decoder, name)[0](decoder)


def decoder_image_flops(decoder: dict, tokens: float, name: str | None = None) -> float:
    """Floor operations of one image of ``tokens`` patch tokens through the decoder."""
    return _walkers(decoder, name)[1](decoder, tokens)


def preprocess_flops(canvas_s: int, input_hw, wire: str = "rgb") -> int:
    """FLOPs of the on-device separable matmul resize from one canvas
    bucket to the model input: resize H (h×s matmul over s×s×C canvas)
    then W (w×s over h×s×C). yuv420 canvases carry 1.5 B/px but convert
    to 3 RGB channels before/while resizing — the matmul operand count is
    the same, so one formula serves both wires (gather/pallas resize do
    strictly less multiply work; this is the matmul-path upper bound)."""
    h, w = int(input_hw[0]), int(input_hw[1])
    s = int(canvas_s)
    c = 3
    # The ragged wire changes WHERE canvases come from (an on-device
    # gather-unpack from the packed byte arena) but not the resize that
    # follows: unpack is pure data movement (zero MACs), then the same
    # canvas→input separable matmul runs. Same formula for all wires.
    macs = h * s * s * c + h * w * s * c
    return 2 * macs


def bytes_per_image(cost: dict, canvas_s: int, batch: int,
                    wire: str = "rgb") -> int:
    """HBM traffic model for one image served at ``batch``: activations
    (2× touched), params amortized over the batch, the uint8 input canvas,
    and the resized input tensor the preprocess writes."""
    canvas_px = canvas_s * canvas_s
    if wire == "yuv420":
        in_bytes = (canvas_px * 3) // 2
    elif wire == "ragged":
        # Packed arena in (bounded above by one canvas of tight bytes,
        # read by the gather) + the unpacked canvas written on device and
        # read back by the resize. 2× canvas is the honest upper bound —
        # the analytic model has no per-image tight size at this level.
        in_bytes = 2 * canvas_px * 3
    else:
        in_bytes = canvas_px * 3
    return int(
        cost["act_bytes_per_image"]
        + cost["param_bytes"] / max(1, batch)
        + in_bytes
    )


# ------------------------------------------------------------ backend peak

_peak_cache: dict[str, dict] = {}


def _calibrate_cpu(dtype: str = "bfloat16") -> dict:
    """One-shot achievable-peak calibration for the CPU dev backend: a
    jitted matmul at the COMPUTE dtype (FLOP/s) and a jitted streaming
    add (bytes/s). Keyed per dtype because the host's f32 and bf16
    matmul rates genuinely differ (bf16 often runs through an upcast on
    CPUs without native support). Both run OUTSIDE econ.lock — a
    concurrent duplicate costs a few hundred ms once, a blocking call
    under a declared lock is a twdlint finding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # Calibration wall-clock rides along in the peak dict: it is the
    # one-time boot cost the engine's warmup logs as its own step, and
    # /stats economics echoes it so a slow boot is attributable.
    t_cal = time.perf_counter()
    n = 768
    mm_dtype = jnp.float32 if dtype == "float32" else jnp.bfloat16
    a = jnp.asarray(
        np.random.RandomState(0).rand(n, n).astype(np.float32)
    ).astype(mm_dtype)
    mm = jax.jit(lambda x: x @ x)
    mm(a).block_until_ready()
    reps = 4
    t0 = time.perf_counter()
    for _ in range(reps):
        mm(a).block_until_ready()
    flops = 2 * n**3 * reps / max(1e-9, time.perf_counter() - t0)

    m = 1 << 24  # 16 M f32 = 64 MB per stream
    v = jnp.zeros((m,), jnp.float32)
    st = jax.jit(lambda x: x + 1.0)
    st(v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        st(v).block_until_ready()
    bw = 2 * 4 * m * reps / max(1e-9, time.perf_counter() - t0)  # read+write
    return {"flops_per_chip": flops, "bytes_per_s_per_chip": bw,
            "source": "cpu-calibrated",
            "calibration_s": round(time.perf_counter() - t_cal, 3)}


def backend_peak(dtype: str = "bfloat16") -> dict:
    """Per-chip peak FLOP/s + HBM bytes/s for the current backend at one
    SERVING dtype, with provenance: ``{"flops_per_chip",
    "bytes_per_s_per_chip", "source"}``. int8 maps to the bf16 compute
    peak (dequant-on-the-fly multiplies at bf16; see :func:`compute_dtype`)
    and f32 to half of it on TPU (the MXU runs f32 through bf16 passes).
    TPU bandwidth is dtype-independent (HBM moves bytes). The CPU dev
    mesh calibrates once per process PER compute dtype (cached keyed
    (backend, compute dtype)). On a CPU mesh every virtual device shares
    the host's cores, so the per-chip number is the HOST's achievable peak
    divided by the device count — MFU summed across replicas then stays
    ≤ 1 by construction."""
    import jax

    backend = jax.default_backend()
    cdtype = compute_dtype(dtype)
    cache_key = (backend, cdtype)
    with _cost_lock:
        cached = _peak_cache.get(cache_key)
    if cached is not None:
        return cached
    if backend == "tpu":
        kind = jax.devices()[0].device_kind
        flops, bw = device_peak(kind)
        if cdtype == "float32":
            flops /= 2.0
        peak = {
            "flops_per_chip": flops,
            "bytes_per_s_per_chip": bw,
            "source": f"tpu-table:{kind}:{cdtype}",
        }
    else:
        host = _calibrate_cpu(cdtype)
        n_dev = len(jax.devices())
        peak = {
            "flops_per_chip": host["flops_per_chip"] / max(1, n_dev),
            "bytes_per_s_per_chip": host["bytes_per_s_per_chip"]
            / max(1, n_dev),
            "source": f"{host['source']}:{cdtype}:/{n_dev}dev",
            "calibration_s": host["calibration_s"],
        }
    with _cost_lock:
        _peak_cache[cache_key] = peak
    return peak


# ------------------------------------------------------------- economics


def bucket_economics(cost: dict | None, canvas_s: int, batch_bucket: int,
                     rows: int, rows_dispatched: int, device_s: float,
                     peak: dict, devices: int, input_hw,
                     wire: str = "rgb", rows_tight: float = 0.0) -> dict:
    """Roofline attribution for one (canvas bucket, batch bucket) cell of
    one replica: achieved FLOP/s over measured dispatch→fetch device time,
    MFU against the replica's peak (``devices`` chips), arithmetic
    intensity, the binding roofline ceiling, and the padded-rows fraction
    (rows dispatched at the compiled bucket vs rows that carried
    requests). On the ragged wire the engine counts ``rows_dispatched``
    as arena rows actually SHIPPED (quantized bump-cursor bytes → rows),
    not the compiled bucket; ``rows`` still counts images, which occupy
    FEWER arena rows than they number, so the fraction is computed from
    ``rows_tight`` (exact used arena rows before quantization) instead —
    it then measures wire padding, the quantity ragged packing exists to
    kill, and ``mfu_dispatched`` becomes a wire-rate rather than a
    hardware-rate gauge."""
    if wire == "ragged" and rows_dispatched:
        pad_rows = 1.0 - min(rows_tight, rows_dispatched) / rows_dispatched
    elif rows_dispatched:
        pad_rows = 1.0 - rows / rows_dispatched
    else:
        pad_rows = 0.0
    out = {
        "canvas": int(canvas_s),
        "batch_bucket": int(batch_bucket),
        "rows": int(rows),
        "rows_dispatched": int(rows_dispatched),
        "device_s": round(device_s, 4),
        "padded_rows_fraction": round(pad_rows, 4),
    }
    if wire == "ragged":
        out["rows_tight"] = round(rows_tight, 3)
    if cost is None or device_s <= 0 or rows <= 0:
        return out
    flops_img = cost["flops_per_image"] + preprocess_flops(
        canvas_s, input_hw, wire
    )
    bpi = bytes_per_image(cost, canvas_s, batch_bucket, wire)
    ai = flops_img / max(1, bpi)
    peak_flops = peak["flops_per_chip"] * max(1, devices)
    peak_bw = peak["bytes_per_s_per_chip"] * max(1, devices)
    achieved = rows * flops_img / device_s
    dispatched_rate = rows_dispatched * flops_img / device_s
    attainable = min(peak_flops, ai * peak_bw) if peak_bw else peak_flops
    ridge = (peak_flops / peak_bw) if peak_bw else math.inf
    out.update(
        flops_per_image=int(flops_img),
        hbm_bytes_per_image=int(bpi),
        achieved_flops=int(achieved),
        # Useful-work MFU (padding excluded) next to the hardware-work
        # rate including padded rows — the gap IS the padding waste.
        mfu=round(achieved / peak_flops, 5) if peak_flops else None,
        mfu_dispatched=round(dispatched_rate / peak_flops, 5)
        if peak_flops else None,
        arithmetic_intensity=round(ai, 2),
        ridge_intensity=round(ridge, 2) if ridge != math.inf else None,
        bound="compute" if ai >= ridge else "bandwidth",
        # Fraction of the BINDING ceiling achieved: "compute-bound at
        # 0.058 of peak" as a number, not a BASELINE sentence.
        roofline_bound_fraction=round(achieved / attainable, 5)
        if attainable else None,
    )
    return out


def economics_snapshot(engine, model_cfg) -> dict | None:
    """The /stats "economics" block for one model version: per-replica,
    per-(canvas, batch-bucket) roofline attribution from the engine's
    measured dispatch→fetch device-time counters, plus the model's
    analytic cost card and the backend peak. None when the engine exposes
    no econ counters (mocks, embedders)."""
    econ_stats = getattr(engine, "econ_stats", None)
    if econ_stats is None:
        return None
    cost = model_cost(model_cfg)
    peak = backend_peak(getattr(model_cfg, "dtype", "bfloat16") or "bfloat16")
    wire = getattr(engine.cfg, "wire_format", "rgb")
    if getattr(engine, "ragged", False):
        wire = "ragged"  # effective wire: packed arenas, not full canvases
    input_hw = model_cfg.input_size
    replicas = []
    agg_rows = agg_disp = 0
    agg_tight = 0.0
    agg_device_s = 0.0
    agg_useful_flops = 0.0
    for rep in econ_stats():
        cells = [
            bucket_economics(
                cost, c["canvas"], c["batch_bucket"], c["rows"],
                c["rows_dispatched"], c["device_s"], peak,
                rep["devices"], input_hw, wire,
                rows_tight=c.get("rows_tight", 0.0),
            )
            for c in rep["buckets"]
        ]
        for cell in cells:
            agg_rows += cell["rows"]
            agg_disp += cell["rows_dispatched"]
            agg_tight += cell.get("rows_tight", 0.0)
            agg_device_s += cell["device_s"]
            if cell.get("achieved_flops"):
                agg_useful_flops += cell["achieved_flops"] * cell["device_s"]
        replicas.append({
            "replica": rep["replica"],
            "devices": rep["devices"],
            "buckets": cells,
        })
    out = {
        "peak": {
            "flops_per_chip": int(peak["flops_per_chip"]),
            "hbm_bytes_per_s_per_chip": int(peak["bytes_per_s_per_chip"]),
            "source": peak["source"],
        },
        "model_cost": (
            {
                "flops_per_image": cost["flops_per_image"],
                "macs_per_image": cost["macs_per_image"],
                "param_count": cost["param_count"],
                "param_bytes": cost["param_bytes"],
                "act_bytes_per_image": cost["act_bytes_per_image"],
                "dtype": cost["dtype"],
            }
            if cost
            else None
        ),
        "dtype": getattr(model_cfg, "dtype", "bfloat16") or "bfloat16",
        "wire": wire,
        "replicas": replicas,
        "rows_total": agg_rows,
        "rows_dispatched_total": agg_disp,
        "device_s_total": round(agg_device_s, 4),
        # Same-unit fraction on either wire: classic = batch padding up
        # to compiled buckets; ragged = wire padding (quantization
        # residual of the shipped arena prefix, from the tight-rows term).
        "padded_rows_fraction": round(
            (1.0 - min(agg_tight, agg_disp) / agg_disp) if wire == "ragged"
            else (1.0 - agg_rows / agg_disp), 4) if agg_disp else 0.0,
    }
    if wire == "ragged":
        out["rows_tight_total"] = round(agg_tight, 3)
    # Whole-model aggregate MFU over every replica's busy time, against
    # the FULL placement's peak — the single number bench quotes.
    n_chips = sum(r["devices"] for r in replicas) or 1
    if cost and agg_device_s > 0 and peak["flops_per_chip"]:
        mean_rate = agg_useful_flops / agg_device_s
        out["mfu"] = round(mean_rate / (peak["flops_per_chip"] * n_chips), 5)
    return out


def pipeline_attribution(pipeline_stats: dict, registry) -> dict:
    """Per-stage economic attribution for one pipeline: which stage owns
    the composition's wall time, device cost and D2H traffic.

    ``pipeline_stats`` is one entry of PipelineCatalog.stats()
    ["pipelines"]; stage wall seconds come from its measured counters,
    analytic per-image cost from :func:`model_cost` of the stage's LIVE
    serving version (resolved through the registry so a hot-swap to a
    cheaper dtype reprices the stage on the next read). Fractions are of
    the pipeline's own totals — an operator deciding which stage to
    quantize or re-place reads this, not absolute dollars.
    """
    stages = pipeline_stats.get("stages", {})
    total_s = sum(c["seconds"] for c in stages.values()) or 0.0
    total_d2h = sum(c["d2h_bytes"] for c in stages.values()) or 0
    out = {}
    for model, cell in stages.items():
        entry = {
            "seconds_total": round(cell["seconds"], 4),
            "seconds_fraction": round(cell["seconds"] / total_s, 4)
            if total_s else None,
            "images_total": cell["images"],
            "cache_hits_total": cell["cache_hits"],
            "d2h_bytes_total": cell["d2h_bytes"],
            "d2h_fraction": round(cell["d2h_bytes"] / total_d2h, 4)
            if total_d2h else None,
        }
        try:
            mv = registry.acquire(model)
        except Exception:
            # Stage between versions: report the measured half only.
            out[model] = entry
            continue
        try:
            cost = model_cost(mv.model_cfg)
            if cost:
                entry["flops_per_image"] = cost["flops_per_image"]
                entry["dtype"] = cost["dtype"]
                # Analytic device work this stage contributed per
                # PIPELINE request: stage images × per-image FLOPs
                # (stage 1 runs one image, stage 2 runs the crops).
                reqs = pipeline_stats.get("requests_total", 0)
                if reqs:
                    entry["flops_per_request"] = int(
                        cost["flops_per_image"] * cell["images"] / reqs)
        finally:
            registry.release(mv)
        out[model] = entry
    return out
