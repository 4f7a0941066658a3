"""The plain reference: decoded pixels -> class probabilities, in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision: bilinear resize of
the decoded image to the model's input size (half-pixel centres, no
antialiasing, as ``ops/image.py`` documents its own), ``x / 127.5 - 1``,
the network of ``nets.py``, softmax. No kernel, no batching trick, no
bfloat16, nothing imported from the program.

``precision="int8"``, ``"fp8"`` (e4m3) and ``"fp8_e5m2"`` are controls, not
references: the same walk with every conv and dense kernel rounded per
output channel and every layer's input rounded per tensor (int8: symmetric,
amax / 127; fp8: scaled to the format's range and cast), the products summed
exactly and the rest in
bfloat16: what serving on 8-bit units would compute, the nearest precision
below the bfloat16 that the configurations state. ``"int8_weights"`` is the
nearest of all: only the kernels are held in 8 bits (:func:`stored_as`), the
arithmetic stays bfloat16. A control's answers stand in for the program's in
``check.py``, and the comparison has to call them wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import nets

HI = lax.Precision.HIGHEST
# The controls' 8-bit grids: int8 (None: integers), and the two fp8 formats,
# three mantissa bits (e4m3) and two (e5m2).
EIGHT_BIT = {"int8": None, "int8_weights": None, "fp8": jnp.float8_e4m3fn, "fp8_e5m2": jnp.float8_e5m2}
DN = ("NHWC", "HWIO", "NHWC")
BN_EPS = 1e-3


def _kernel_as(k, tier: str, xp=jnp):
    """One kernel as a serving tier stores it, back in float32 (``xp``:
    ``jnp`` inside a jitted walk, ``np`` on the host)."""
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(xp.float32)
    k = xp.asarray(k, xp.float32)
    if tier == "float32":
        return k
    if tier == "bfloat16":
        return bf16(k)
    if tier == "int8":  # an int8 value per weight, a scale per output channel, multiplied in bfloat16
        amax = xp.max(xp.abs(k), axis=tuple(range(k.ndim - 1)))
        scale = xp.where(amax > 0, amax / 127.0, 1.0).astype(xp.float32)
        return bf16(bf16(xp.clip(xp.rint(k / scale), -127, 127)) * bf16(scale))
    raise ValueError(f"tier {tier!r}: float32, bfloat16 or int8")


def stored_as(params: dict, tier: str) -> dict:
    """``params`` with every conv and dense kernel as a serving tier holds
    it: ``bfloat16`` rounds each weight; ``int8`` is weight-only
    quantisation, symmetric per output channel (amax / 127), dequantised in
    bfloat16. Walked in float32, the two differ by what 8-bit kernels alone
    do to an answer: the direction ``check.py`` looks along. On the host:
    a hundred small kernels are a hundred small programs on a device."""
    return {name: _kernel_as(np.asarray(v), tier, np) if name.endswith("/kernel") else v
            for name, v in params.items()}


def _act(x, act):
    if act == "relu":
        return jnp.maximum(x, 0)
    if act == "relu6":
        return jnp.clip(x, 0, 6)
    return x


class JnpOps:
    """Walks a network of ``nets.py`` with arrays [B, H, W, C]."""

    def __init__(self, params: dict, precision: str = "float32"):
        if precision not in ("float32", *EIGHT_BIT):
            raise ValueError(f"precision {precision!r}: float32 (the reference) or a control of {sorted(EIGHT_BIT)}")
        self.p = params
        self.low = precision != "float32"
        self.weights_only = precision == "int8_weights"
        self.fp8 = EIGHT_BIT.get(precision)
        self.dtype = jnp.bfloat16 if self.low else jnp.float32

    def channels(self, x):
        return x.shape[-1]

    def _kernel(self, name):
        k = jnp.asarray(self.p[name], jnp.float32)
        if not self.low:
            return k
        if self.weights_only:
            return _kernel_as(k, "int8")
        return self._round8(k, jnp.max(jnp.abs(k), axis=tuple(range(k.ndim - 1))))

    def _round8(self, x, amax):
        """``x`` on the 8-bit grid that ``amax`` spans, back in float32."""
        top = float(jnp.finfo(self.fp8).max) if self.fp8 else 127.0
        scale = jnp.where(amax > 0, amax / top, 1.0)
        if self.fp8:
            return (x / scale).astype(self.fp8).astype(jnp.float32) * scale
        return jnp.clip(jnp.rint(x / scale), -127, 127) * scale

    def _vec(self, name):
        return jnp.asarray(self.p[name], jnp.float32).astype(self.dtype)

    def _bn(self, name, x, act):
        inv = self._vec(f"params/{name}/bn/scale") * lax.rsqrt(
            self._vec(f"batch_stats/{name}/bn/var") + jnp.asarray(BN_EPS, self.dtype))
        x = (x - self._vec(f"batch_stats/{name}/bn/mean")) * inv + self._vec(f"params/{name}/bn/bias")
        return _act(x, act)

    def _act8(self, x):
        """The control's activations: 8 bits per tensor, as a layer that
        feeds the 8-bit units would round its input."""
        if not self.low:
            return x
        x = x.astype(jnp.float32)
        return x if self.weights_only else self._round8(x, jnp.max(jnp.abs(x)))

    def _conv(self, x, k, stride, padding, groups=1):
        # int8 products accumulate exactly, so the control multiplies its
        # rounded values in float32 and rounds the sum to bfloat16.
        y = lax.conv_general_dilated(
            self._act8(x), k, (stride, stride), padding,
            dimension_numbers=DN, feature_group_count=groups, precision=HI)
        return y.astype(self.dtype)

    def conv_bn(self, name, x, features, kernel, stride=1, padding="SAME", act="relu"):
        k = self._kernel(f"params/{name}/conv/kernel")
        return self._bn(name, self._conv(x, k, stride, padding), act)

    def dw_bn(self, name, x, stride, act):
        k = self._kernel(f"params/{name}/dwconv/kernel")
        return self._bn(name, self._conv(x, k, stride, "SAME", groups=x.shape[-1]), act)

    def avg_pool3(self, x):
        s = lax.reduce_window(x, jnp.zeros((), x.dtype), lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME")
        return s / jnp.asarray(9, x.dtype)

    def max_pool3s2(self, x):
        return lax.reduce_window(x, jnp.asarray(-jnp.inf, x.dtype), lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "VALID")

    def concat(self, xs):
        return jnp.concatenate(xs, axis=-1)

    def add(self, a, b):
        return a + b

    def head(self, name, x, num_classes):
        pooled = jnp.mean(x, axis=(1, 2))
        k = self._kernel(f"params/{name}/kernel")
        y = jnp.dot(self._act8(pooled), k, precision=HI)
        return y.astype(self.dtype) + self._vec(f"params/{name}/bias")


def resize_bilinear(img, out: int):
    """[H, W, 3] float32 -> [out, out, 3]: two taps per axis at half-pixel
    centres, coordinates clamped to the image."""
    def axis(n):
        c = (jnp.arange(out, dtype=jnp.float32) + 0.5) * (n / out) - 0.5
        c = jnp.clip(c, 0.0, n - 1.0)
        lo = jnp.floor(c)
        hi = jnp.minimum(lo + 1.0, n - 1.0)
        return lo.astype(jnp.int32), hi.astype(jnp.int32), c - lo

    h_lo, h_hi, h_f = axis(img.shape[0])
    w_lo, w_hi, w_f = axis(img.shape[1])
    rows = img[h_lo] * (1 - h_f)[:, None, None] + img[h_hi] * h_f[:, None, None]
    return rows[:, w_lo] * (1 - w_f)[None, :, None] + rows[:, w_hi] * w_f[None, :, None]


def make_probs(network: str, input_size: int, num_classes: int, width: float, precision: str = "float32"):
    """A jitted ``(params, x [B, S, S, 3] in [-1, 1]) -> probabilities [B, classes]``
    of ``network`` (``nets.load``)."""
    net = nets.load(network)

    @jax.jit
    def probs(params, x):
        ops = JnpOps(params, precision)
        logits = net(ops, x.astype(ops.dtype), num_classes, width)
        return jax.nn.softmax(logits.astype(ops.dtype), axis=-1).astype(jnp.float32)

    return probs


_resize = jax.jit(resize_bilinear, static_argnums=1)


def preprocess(pixels: np.ndarray, input_size: int):
    """Decoded RGB uint8 [H, W, 3] -> [S, S, 3] float32 in [-1, 1]."""
    return _resize(jnp.asarray(pixels, jnp.float32), input_size) / 127.5 - 1.0
