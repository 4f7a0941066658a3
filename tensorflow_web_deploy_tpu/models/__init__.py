"""Native JAX model zoo: the reference's model families re-expressed in flax.

The serving engine has two interchangeable model sources:
- ``graphdef.convert_pb`` — frozen ``.pb`` → JAX (the reference's operator
  asset path, SURVEY.md §2 C6);
- this zoo — the same architectures hand-written in flax (SURVEY.md §7 M1
  fallback track), used for TF-free serving, training (``train/``), and the
  driver's graft entry.

``get(name)`` returns a :class:`ModelSpec`; ``spec.build(...)`` a flax
module; ``models.adapter.native_converted(...)`` wraps a zoo model in the
engine's ``ConvertedModel`` interface.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable

from .inception_v3 import InceptionV3
from .mobilenet_v2 import MobileNetV2
from .resnet50 import ResNet50
from .ssd_mobilenet import SSDMobileNet


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    build: Callable | None  # (num_classes=..., width=...) -> nn.Module; None: task "generate"
    input_size: int
    preprocess: str
    task: str = "classify"
    num_classes: int = 1000
    # Stem conv padding — decides when the serving preprocess may hand the
    # model pack_s2d cells (input_format="s2d"): the even-extent cell
    # convention is exact for VALID stems at any size, and for SAME stems
    # only at even sizes (odd+SAME would shift the implicit padding).
    stem_padding: str = "SAME"

    def s2d_ok(self, h: int, w: int) -> bool:
        return self.stem_padding == "VALID" or (h % 2 == 0 and w % 2 == 0)


_ZOO: dict[str, ModelSpec] = {
    s.name: s
    for s in [
        ModelSpec("inception_v3", InceptionV3, 299, "inception", stem_padding="VALID"),
        ModelSpec("mobilenet_v2", MobileNetV2, 224, "inception"),
        ModelSpec("resnet50", ResNet50, 224, "caffe"),
        ModelSpec("ssd_mobilenet", SSDMobileNet, 300, "inception", task="detect", num_classes=90),
        # No flax module and no resize: a decoder's module (its zoo name; decoder.py has the contract) is
        # functional, its sizes come from the model's JSON (``decoder``), and adapter.decoder_converted wraps it.
        ModelSpec("longcat_flash", None, 0, "patches", task="generate", num_classes=131072),
        ModelSpec("nemotron_h", None, 0, "patches", task="generate", num_classes=131072),
        ModelSpec("brumby", None, 0, "patches", task="generate", num_classes=151936),
    ]
}


def get(name: str) -> ModelSpec:
    if name not in _ZOO:
        raise KeyError(f"unknown zoo model '{name}' — have {sorted(_ZOO)}")
    return _ZOO[name]


def names() -> list[str]:
    return sorted(_ZOO)
