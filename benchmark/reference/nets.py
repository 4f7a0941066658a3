"""The classifiers as plain layer lists, written once and walked three ways.

Each network is a function of an ``ops`` object. ``ShapeOps`` walks it with
shapes alone and records every parameter's name and shape (what
``weights.py`` fills from the seed, no JAX needed) and every layer's
multiply-adds (what ``cost.py`` sums);
``forward.JnpOps`` walks it with arrays and computes the forward pass in
float32 at ``highest`` precision. Nothing here imports the program.

Inception-v3 follows Szegedy et al., arXiv:1512.00567, as TF-Slim and
keras.applications ship it (299 px, BN eps 1e-3, no conv bias);
MobileNetV2 follows Sandler et al., arXiv:1801.04381, table 2. Departures
from the papers, shared with the program: the 3x3 average pools divide by 9
everywhere (padding counted), and no auxiliary head or dropout exists at
inference. Parameter names are the checkpoint's (flax ``/``-joined paths).
"""

from __future__ import annotations

import importlib.util
from functools import lru_cache
from pathlib import Path

# (expansion t, output channels c, repeats n, first stride s): MobileNetV2 table 2.
MV2_BLOCKS = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
              (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def scale_ch(c: int, width: float, divisor: int = 8) -> int:
    """The MobileNet width rule: ``c * width`` rounded to a multiple of 8,
    never more than 10% below."""
    v = max(divisor, int(c * width + divisor / 2) // divisor * divisor)
    if v < 0.9 * c * width:
        v += divisor
    return v


def _inception_a(o, x, n, w, pool):
    b1 = o.conv_bn(f"{n}/b1x1", x, w(64), (1, 1))
    b5 = o.conv_bn(f"{n}/b5x5_1", x, w(48), (1, 1))
    b5 = o.conv_bn(f"{n}/b5x5_2", b5, w(64), (5, 5))
    b3 = o.conv_bn(f"{n}/b3x3dbl_1", x, w(64), (1, 1))
    b3 = o.conv_bn(f"{n}/b3x3dbl_2", b3, w(96), (3, 3))
    b3 = o.conv_bn(f"{n}/b3x3dbl_3", b3, w(96), (3, 3))
    bp = o.conv_bn(f"{n}/bpool", o.avg_pool3(x), w(pool), (1, 1))
    return o.concat([b1, b5, b3, bp])


def _reduction_a(o, x, n, w):
    b3 = o.conv_bn(f"{n}/b3x3", x, w(384), (3, 3), 2, "VALID")
    bd = o.conv_bn(f"{n}/b3x3dbl_1", x, w(64), (1, 1))
    bd = o.conv_bn(f"{n}/b3x3dbl_2", bd, w(96), (3, 3))
    bd = o.conv_bn(f"{n}/b3x3dbl_3", bd, w(96), (3, 3), 2, "VALID")
    return o.concat([b3, bd, o.max_pool3s2(x)])


def _inception_b(o, x, n, w, c7):
    c7 = w(c7)
    b1 = o.conv_bn(f"{n}/b1x1", x, w(192), (1, 1))
    b7 = o.conv_bn(f"{n}/b7x7_1", x, c7, (1, 1))
    b7 = o.conv_bn(f"{n}/b7x7_2", b7, c7, (1, 7))
    b7 = o.conv_bn(f"{n}/b7x7_3", b7, w(192), (7, 1))
    bd = o.conv_bn(f"{n}/b7x7dbl_1", x, c7, (1, 1))
    bd = o.conv_bn(f"{n}/b7x7dbl_2", bd, c7, (7, 1))
    bd = o.conv_bn(f"{n}/b7x7dbl_3", bd, c7, (1, 7))
    bd = o.conv_bn(f"{n}/b7x7dbl_4", bd, c7, (7, 1))
    bd = o.conv_bn(f"{n}/b7x7dbl_5", bd, w(192), (1, 7))
    bp = o.conv_bn(f"{n}/bpool", o.avg_pool3(x), w(192), (1, 1))
    return o.concat([b1, b7, bd, bp])


def _reduction_b(o, x, n, w):
    b3 = o.conv_bn(f"{n}/b3x3_1", x, w(192), (1, 1))
    b3 = o.conv_bn(f"{n}/b3x3_2", b3, w(320), (3, 3), 2, "VALID")
    b7 = o.conv_bn(f"{n}/b7x7x3_1", x, w(192), (1, 1))
    b7 = o.conv_bn(f"{n}/b7x7x3_2", b7, w(192), (1, 7))
    b7 = o.conv_bn(f"{n}/b7x7x3_3", b7, w(192), (7, 1))
    b7 = o.conv_bn(f"{n}/b7x7x3_4", b7, w(192), (3, 3), 2, "VALID")
    return o.concat([b3, b7, o.max_pool3s2(x)])


def _inception_c(o, x, n, w):
    b1 = o.conv_bn(f"{n}/b1x1", x, w(320), (1, 1))
    b3 = o.conv_bn(f"{n}/b3x3_1", x, w(384), (1, 1))
    b3a = o.conv_bn(f"{n}/b3x3_2a", b3, w(384), (1, 3))
    b3b = o.conv_bn(f"{n}/b3x3_2b", b3, w(384), (3, 1))
    bd = o.conv_bn(f"{n}/b3x3dbl_1", x, w(448), (1, 1))
    bd = o.conv_bn(f"{n}/b3x3dbl_2", bd, w(384), (3, 3))
    bda = o.conv_bn(f"{n}/b3x3dbl_3a", bd, w(384), (1, 3))
    bdb = o.conv_bn(f"{n}/b3x3dbl_3b", bd, w(384), (3, 1))
    bp = o.conv_bn(f"{n}/bpool", o.avg_pool3(x), w(192), (1, 1))
    return o.concat([b1, b3a, b3b, bda, bdb, bp])


def inception_v3(o, x, num_classes: int = 1000, width: float = 1.0):
    w = lambda c: scale_ch(c, width)
    x = o.conv_bn("stem1", x, w(32), (3, 3), 2, "VALID")
    x = o.conv_bn("stem2", x, w(32), (3, 3), 1, "VALID")
    x = o.conv_bn("stem3", x, w(64), (3, 3))
    x = o.max_pool3s2(x)
    x = o.conv_bn("stem4", x, w(80), (1, 1), 1, "VALID")
    x = o.conv_bn("stem5", x, w(192), (3, 3), 1, "VALID")
    x = o.max_pool3s2(x)
    x = _inception_a(o, x, "mixed5b", w, 32)
    x = _inception_a(o, x, "mixed5c", w, 64)
    x = _inception_a(o, x, "mixed5d", w, 64)
    x = _reduction_a(o, x, "mixed6a", w)
    x = _inception_b(o, x, "mixed6b", w, 128)
    x = _inception_b(o, x, "mixed6c", w, 160)
    x = _inception_b(o, x, "mixed6d", w, 160)
    x = _inception_b(o, x, "mixed6e", w, 192)
    x = _reduction_b(o, x, "mixed7a", w)
    x = _inception_c(o, x, "mixed7b", w)
    x = _inception_c(o, x, "mixed7c", w)
    return o.head("logits", x, num_classes)


def mobilenet_v2(o, x, num_classes: int = 1000, width: float = 1.0):
    w = lambda c: scale_ch(c, width)
    x = o.conv_bn("stem", x, w(32), (3, 3), 2, "SAME", "relu6")
    for i, (t, c, n, s) in enumerate(MV2_BLOCKS):
        for j in range(n):
            name, cin, feat = f"block{i}_{j}", o.channels(x), w(c)
            stride = s if j == 0 else 1
            h = x
            if t != 1:
                h = o.conv_bn(f"{name}/expand", h, cin * t, (1, 1), 1, "SAME", "relu6")
            h = o.dw_bn(f"{name}/dw", h, stride, "relu6")
            h = o.conv_bn(f"{name}/project", h, feat, (1, 1), 1, "SAME", None)
            x = o.add(h, x) if stride == 1 and cin == feat else h
    last = max(1280, scale_ch(1280, width)) if width > 1.0 else 1280
    x = o.conv_bn("head", x, last, (1, 1), 1, "SAME", "relu6")
    return o.head("logits", x, num_classes)


@lru_cache(maxsize=None)
def load(network: str):
    """The network that a configuration names, ``<file>::<function>`` with
    the file relative to the checkout (a bare name is a function of this
    file): a function of ``(ops, x, num_classes, width)`` written like the
    two above. A new architecture is a new file, and nothing here is edited."""
    path, sep, name = network.rpartition("::")
    file = Path(path) if Path(path).is_absolute() else Path(__file__).resolve().parents[2] / path
    if not sep or file.resolve() == Path(__file__).resolve():
        fn = globals().get(name)
    else:
        spec = importlib.util.spec_from_file_location(f"benchmark_network_{file.stem}", file)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        fn = getattr(mod, name, None)
    if not callable(fn):
        raise ValueError(f"no network {network!r}")
    return fn


def _out(size: int, k: int, stride: int, padding: str) -> int:
    return -(-size // stride) if padding == "SAME" else (size - k) // stride + 1


class ShapeOps:
    """Walks a network with (h, w, c, m) tuples, m being the tensor's expected
    mean square under ``weights.make``'s scaling. Records each parameter's
    checkpoint name and shape in ``params``, per layer the multiply-adds in
    ``macs`` (a conv's output positions x kernel taps x cin x cout; a
    depthwise conv's x kernel taps x c; the dense layer's cin x cout), and
    per kernel the mean square of the layer's input in ``in_moment``, from
    which ``weights.make`` scales the kernel so that every layer's output
    has about unit variance: a net whose activations neither vanish nor pile
    up against ReLU6, as a trained one's do not."""

    IMAGE_MOMENT = 1 / 3  # pixels spread over [-1, 1]

    def __init__(self):
        self.params: dict[str, tuple[int, ...]] = {}
        self.macs: dict[str, int] = {}
        self.in_moment: dict[str, float] = {}

    def channels(self, x):
        return x[2]

    def _bn(self, name, c):
        for leaf in ("params/%s/bn/scale", "params/%s/bn/bias",
                     "batch_stats/%s/bn/mean", "batch_stats/%s/bn/var"):
            self.params[leaf % name] = (c,)

    @staticmethod
    def _after(act):
        return 1.0 if act is None else 0.5  # a rectifier keeps half of a centred unit variance

    def conv_bn(self, name, x, features, kernel, stride=1, padding="SAME", act="relu"):
        h, w, cin, m = x
        kh, kw = kernel
        self.params[f"params/{name}/conv/kernel"] = (kh, kw, cin, features)
        self.in_moment[f"params/{name}/conv/kernel"] = m
        self._bn(name, features)
        oh, ow = _out(h, kh, stride, padding), _out(w, kw, stride, padding)
        self.macs[name] = oh * ow * kh * kw * cin * features
        return (oh, ow, features, self._after(act))

    def dw_bn(self, name, x, stride, act):
        h, w, c, m = x
        self.params[f"params/{name}/dwconv/kernel"] = (3, 3, 1, c)
        self.in_moment[f"params/{name}/dwconv/kernel"] = m
        self._bn(name, c)
        oh, ow = _out(h, 3, stride, "SAME"), _out(w, 3, stride, "SAME")
        self.macs[name] = oh * ow * 9 * c
        return (oh, ow, c, self._after(act))

    def avg_pool3(self, x):
        return x

    def max_pool3s2(self, x):
        return (_out(x[0], 3, 2, "VALID"), _out(x[1], 3, 2, "VALID"), x[2], x[3])

    def concat(self, xs):
        c = sum(x[2] for x in xs)
        return (xs[0][0], xs[0][1], c, sum(x[2] * x[3] for x in xs) / c)

    def add(self, a, b):
        return (a[0], a[1], a[2], a[3] + b[3])

    def head(self, name, x, num_classes):
        self.params[f"params/{name}/kernel"] = (x[2], num_classes)
        self.params[f"params/{name}/bias"] = (num_classes,)
        self.in_moment[f"params/{name}/kernel"] = x[3]
        self.macs[name] = x[2] * num_classes
        return (num_classes,)


def walk(network: str, input_size: int, num_classes: int = 1000, width: float = 1.0) -> ShapeOps:
    """Parameter shapes and multiply-adds of ``network`` (see :func:`load`) at ``input_size`` px."""
    ops = ShapeOps()
    load(network)(ops, (input_size, input_size, 3, ShapeOps.IMAGE_MOMENT), num_classes, width)
    return ops
