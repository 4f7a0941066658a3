"""Zoo model → :class:`~..graphdef.converter.ConvertedModel` adapter.

The serving engine consumes one interface — ``fn(params, *inputs)`` plus a
flat params dict (SURVEY.md §3.1's ``load_graph()`` contract). This wraps a
flax zoo model in that same interface so ``--model native:inception_v3``
serves without TensorFlow anywhere in the process: flax variables are
flattened to ``"params/stem1/conv/kernel"``-style keys (the engine casts the
float leaves to bfloat16 and shards them over the mesh exactly as it does
converter weights), and the forward unflattens them per trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict

from ..graphdef.converter import ConvertedModel, InputSpec
from . import get

# Init-time forward runs at a reduced spatial size: param shapes are
# independent of H/W (conv kernels + post-globalpool dense), and a small
# canvas keeps the one-off init trace cheap on the host.
_INIT_SIZE = 96


def init_variables(
    spec,
    num_classes: int | None = None,
    width: float = 1.0,
    seed: int = 0,
    materialize: bool = True,
):
    """Build + initialize a zoo model; returns (module, variables pytree).

    ``materialize=False`` returns abstract leaves (ShapeDtypeStruct) — for
    callers that immediately overwrite every leaf (checkpoint restore), the
    host-side random init would be pure wasted work and a second full copy
    of the model in RAM.
    """
    num_classes = num_classes or spec.num_classes
    model = spec.build(num_classes=num_classes, width=width)
    size = max(_INIT_SIZE, 75 if spec.name == "inception_v3" else 32)
    dummy = jnp.zeros((1, size, size, 3), jnp.float32)
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(seed), dummy))
    if not materialize:
        return model, variables
    # eval_shape gives structure without compute; materialize leaves with a
    # cheap seeded host-side init (He for 4-D/2-D kernels, BN identity).
    rs = np.random.RandomState(seed)

    def materialize(path, leaf):
        shape, dtype = leaf.shape, leaf.dtype
        name = path[-1]
        if name == "kernel":
            fan_in = int(np.prod(shape[:-1])) or 1
            return (rs.randn(*shape) * np.sqrt(2.0 / fan_in)).astype(dtype)
        if name in ("scale", "var"):
            return np.ones(shape, dtype)
        return np.zeros(shape, dtype)

    flat = flatten_dict(variables)
    flat = {k: materialize(k, v) for k, v in flat.items()}
    return model, unflatten_dict(flat)


def restore_serving_export(variables, export_dir: str):
    """Replace ``variables``' params/batch_stats with a serving export
    written by ``tools/train.py`` (an orbax checkpoint holding exactly
    ``{"params", "batch_stats"}`` — deliberately NOT the full train state,
    so serving never needs to know the trainer's optimizer structure).
    ``variables`` may hold abstract leaves (ShapeDtypeStruct): only
    structure and shapes/dtypes are read."""
    from ..train.checkpoint import Checkpointer

    ck = Checkpointer(export_dir, create=False)
    try:
        like = {
            "params": variables["params"],
            "batch_stats": variables.get("batch_stats", {}),
        }
        restored = ck.restore(like)
        if restored is None:
            raise FileNotFoundError(f"no serving export found in {export_dir}")
        return {**variables, **restored}
    finally:
        ck.close()


def native_converted(
    name: str,
    num_classes: int | None = None,
    width: float = 1.0,
    seed: int = 0,
    input_size: int | None = None,
    ckpt_path: str | None = None,
    input_format: str = "nhwc",
    fused_dw: bool = False,
    decoder: dict | None = None,
    topk: int = 5,
) -> ConvertedModel:
    """Zoo model as a ``ConvertedModel`` (drop-in for ``convert_pb``).

    Classify models output ``(probs,)``; the detector outputs
    ``(raw_boxes, raw_scores, anchors)`` matching the frozen-graph contract
    (anchors ride as a closed-over f32 constant, not a bf16-cast param, so
    box coordinates keep full precision through the engine's dtype policy).
    ``input_size`` overrides the spec's default resolution — the detector's
    anchor grid is derived from it, so it must match what the serving layer
    resizes to. ``ckpt_path`` serves fine-tuned weights: a serving export
    from ``tools/train.py`` replaces the seeded init (the train→serve loop,
    TF-free end to end).

    ``input_format="s2d"``: the returned ``fn`` consumes the preprocess's
    ``pack_s2d`` cell layout ([B, ⌈H/2⌉, ⌈W/2⌉, 12]) instead of NHWC — the
    stem↔preprocess handshake. Params are IDENTICAL in both formats (the
    s2d stem declares the same logical kernel), so init/checkpoints flow
    through the standard layout unchanged; only valid when
    ``spec.s2d_ok(input_size, input_size)``.

    ``fused_dw=True`` serves the depthwise cells fused (conv+folded-BN+
    relu6 one op — the raw-speed tier). Param tree is again identical, so
    it composes with checkpoints and s2d; silently ignored for archs with
    no depthwise chain (inception/resnet).

    A ``task="generate"`` entry of the registry is no flax module: its
    sizes are ``decoder`` (the model's JSON), it answers ``topk`` ids a step
    itself, and :func:`decoder_converted` wraps the family's module.
    """
    spec = get(name)
    if spec.task == "generate":
        return decoder_converted(decoder or {}, topk, seed=seed, ckpt_path=ckpt_path, name=name)
    input_size = input_size or spec.input_size
    if input_format not in ("nhwc", "s2d"):
        raise ValueError(f"input_format must be 'nhwc' or 's2d', got {input_format!r}")
    if input_format == "s2d" and not spec.s2d_ok(input_size, input_size):
        raise ValueError(
            f"{name}: s2d input_format needs an even input size with a SAME "
            f"stem (got {input_size})"
        )
    # With a checkpoint, the init would be discarded wholesale — build the
    # structure abstractly and let the restore materialize every leaf (the
    # zoo's only collections are params + batch_stats, both restored).
    model, variables = init_variables(
        spec, num_classes=num_classes, width=width, seed=seed,
        materialize=not ckpt_path,
    )
    if ckpt_path:
        variables = restore_serving_export(variables, ckpt_path)
    fused_dw = fused_dw and hasattr(spec.build, "fused_dw")
    if input_format == "s2d" or fused_dw:
        # Same params, different compute: rebuild the module only.
        kwargs = {"num_classes": num_classes or spec.num_classes, "width": width}
        if input_format == "s2d":
            kwargs["input_format"] = "s2d"
        if fused_dw:
            kwargs["fused_dw"] = True
        model = spec.build(**kwargs)
    params_flat = {"/".join(k): np.asarray(v) for k, v in flatten_dict(variables).items()}

    if spec.task == "detect":
        anchors = model.anchors_for(input_size)

        def fn(params_arg, x, float_dtype=None):
            variables = unflatten_dict({tuple(k.split("/")): v for k, v in params_arg.items()})
            rb, rs = model.apply(variables, x, train=False)
            return rb, rs, jnp.asarray(anchors)

        output_names = ["raw_boxes", "raw_scores", "anchors"]
    else:

        def fn(params_arg, x, float_dtype=None):
            variables = unflatten_dict({tuple(k.split("/")): v for k, v in params_arg.items()})
            logits = model.apply(variables, x, train=False)
            return (jax.nn.softmax(logits, axis=-1),)

        output_names = ["probs"]

    size = input_size
    if input_format == "s2d":
        cells = (size + 1) // 2
        in_shape = [None, cells, cells, 12]  # pack_s2d cell layout
    else:
        in_shape = [None, size, size, 3]
    return ConvertedModel(
        fn=fn,
        params=params_flat,
        input_specs=[InputSpec(name="input", shape=in_shape, dtype=np.dtype(np.float32))],
        output_names=output_names,
    )


# ------------------------------------------------------------------ decoders

# The dtypes a decoder's export may hold, by the name its manifest gives.
_EXPORT_DTYPES = {"float32": np.float32, "bfloat16": jnp.bfloat16}


def read_leaf_export(export_dir: str, table, stacks: dict[str, tuple[int, ...]]) -> dict[str, np.ndarray]:
    """A decoder's ``--ckpt`` export: ``manifest.json`` (``dtype`` and each
    leaf's shape) beside one raw file a leaf (its name with ``/`` as ``.``),
    as the weights scripts of ``benchmark/reference/`` write one. Read leaf by
    leaf into the model's parameters (``table``: the family's
    ``leaf_table``; an expert's matrix lands in its layer's stack, and what
    of a stack no leaf fills stays zero), so the
    host never holds more than the parameters themselves; a leaf that is
    missing, or of another shape than the model states, is an error here."""
    import json
    from pathlib import Path

    root = Path(export_dir)
    manifest = json.loads((root / "manifest.json").read_text())
    dtype = np.dtype(_EXPORT_DTYPES[manifest["dtype"]])
    out = {name: np.zeros(shape, dtype) for name, shape in stacks.items()}
    for leaf, shape, stack, index in table:
        if tuple(manifest["leaves"].get(leaf, ())) != tuple(shape):
            raise ValueError(f"{export_dir}: leaf {leaf!r} is {manifest['leaves'].get(leaf)}, the model states {shape}")
        out[stack][index] = np.fromfile(root / leaf.replace("/", "."), dtype).reshape(shape)
    return out


def decoder_converted(decoder: dict, topk: int, seed: int = 0, ckpt_path: str | None = None,
                      name: str | None = None) -> ConvertedModel:
    """The token decoder of the zoo entry ``name`` (its family's module,
    found by that name: models/decoder.py has what is asked of one; without
    a name, the family whose sizes ``decoder`` states) behind the engine's one
    interface, as a model that serves ``from_canvases``:
    ``fn(params, canvases, hws)`` takes the patches of the canvases' real
    pixels (no resize) and returns (scores [B, steps, k], ids [B, steps,
    k], counters). Its ceiling a call is ``max_token_slots``, so the rows
    it allows go with the canvas."""
    from ..ops.image import patch_tokens
    from .decoder import family

    fam = family(name, decoder)
    cfg = fam.Config.from_dict(decoder)
    if ckpt_path:
        params = read_leaf_export(ckpt_path, fam.leaf_table(cfg), fam.param_shapes(cfg))
    else:
        params = fam.init_params(cfg, seed)

    def fn(params_arg, canvases, hws):
        # One program a call: patches, prefill of every layer, step 1's
        # top-k, then the cached steps. The model's own scopes name the rest.
        with jax.named_scope("patches"):
            tokens, lengths = patch_tokens(canvases, hws, cfg.patch)
        return fam.answer(cfg, params_arg, tokens, lengths, topk)

    return ConvertedModel(
        fn=fn, params=params,
        input_specs=[InputSpec(name="canvases", shape=[None, None, None, 3], dtype=np.dtype(np.uint8))],
        output_names=["scores", "ids", "counters"],
        from_canvases=True,
        max_rows=lambda canvas_s: cfg.max_token_slots // cfg.token_slots(canvas_s),
        counter_names=fam.COUNTERS,
    )
