"""Frozen ``GraphDef`` → jittable JAX function.

The reference's ``load_graph()`` deserializes a frozen ``.pb`` and defers all
execution to the TF1 runtime (SURVEY.md §3.1/§3.3). Here conversion *is* the
compile pipeline: the graph is pruned to the requested outputs, topologically
ordered, and re-emitted as a Python function over ``jax``/``lax`` ops that
``jax.jit`` traces into a single XLA program for the TPU.

Two design decisions that matter for TPU performance:

1. **Weights become a params pytree**, not baked constants. Every float
   ``Const`` above a size threshold is lifted into ``params[name]`` and passed
   as an argument to the converted function. That keeps the jaxpr small, lets
   the serving layer cast the whole tree to bfloat16 in one place, donate it,
   and shard it over a ``Mesh`` (replicated for data-parallel serving, or
   split for a tensor-parallel seam) without re-tracing.

2. **Shape arithmetic stays static.** Integer/bool consts remain numpy;
   ``Shape`` emits numpy (trace shapes are static); handlers flagged
   ``static_ok`` evaluate in numpy whenever all their inputs are static. A
   frozen graph's ``Shape → StridedSlice → Pack → Reshape`` chains therefore
   collapse at trace time and every array op XLA sees has a static shape —
   there is no dynamic-shape fallback path to fall off the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

from ..ops import tf_ops
from .proto import DT_FLOAT, GraphDef, NodeDef, load_pb, np_dtype

# Float consts at least this many elements become runtime params; smaller
# consts (eps scalars, norm means) stay static so XLA folds them.
_PARAM_MIN_SIZE = 64

_INPUT_OPS = ("Placeholder", "PlaceholderWithDefault")


def _ref_name(ref: str) -> tuple[str, int]:
    """Split an input ref ``"node:2"`` → ``("node", 2)``."""
    if ":" in ref:
        name, idx = ref.rsplit(":", 1)
        return name, int(idx)
    return ref, 0


def _is_static(v) -> bool:
    return isinstance(v, (np.ndarray, np.generic, int, float, bool, bytes))


@dataclasses.dataclass
class InputSpec:
    name: str
    shape: list[int] | None
    dtype: np.dtype


@dataclasses.dataclass
class S2DStem:
    """Input-format rewrite handle: the graph's image input feeds (through
    at most one static zero ``Pad``) a stride-2 few-channel ``Conv2D`` — the
    MXU-hostile stem shape. ``build(h, w)`` returns a variant ``fn`` that
    consumes the preprocess's ``pack_s2d`` cell layout instead of NHWC, so
    the serving resize hands the graph cells directly and the fold
    transpose never materializes (same rewrite the native zoo gets via
    ``input_format="s2d"``; profiled ~0.5 ms/batch on the frozen
    Inception-v3 path).

    ``base_pads`` come from the absorbed ``Pad`` node; the conv's own
    SAME/VALID padding is resolved against the serving (h, w) at build
    time, and the combined pads go to ``ops.stem.conv2d_s2d_input`` as
    explicit amounts (odd offsets handled there by kernel shift).
    """

    conv_name: str
    skip_names: frozenset[str]
    base_pads: tuple[tuple[int, int], tuple[int, int]]
    conv_padding: str  # "SAME" / "VALID"
    kernel_hw: tuple[int, int]
    _builder: Any  # (explicit_pads) -> fn

    def resolve_pads(self, h: int, w: int):
        (bt, bb), (bl, br) = self.base_pads
        if self.conv_padding == "VALID":
            ct = cb = cl = cr = 0
        else:  # TF SAME on the padded extent — same rule lax implements
            from jax import lax

            (ct, cb), (cl, cr) = lax.padtype_to_pads(
                (h + bt + bb, w + bl + br), self.kernel_hw, (2, 2), "SAME"
            )
        return ((bt + ct, bb + cb), (bl + cl, br + cr))

    def supports(self, h: int, w: int) -> bool:
        """Is the even-extent cell convention exact at serving size (h, w)?
        Per axis: even extent always; odd extent needs an even total pad
        (then the implied extra zero row changes no output — the window
        count and every tap match the true-extent conv)."""
        (pt, pb), (pl, pr) = self.resolve_pads(h, w)
        ok_h = h % 2 == 0 or (pt + pb) % 2 == 0
        ok_w = w % 2 == 0 or (pl + pr) % 2 == 0
        return ok_h and ok_w

    def build(self, h: int, w: int):
        assert self.supports(h, w), f"s2d stem not exact at {(h, w)}"
        return self._builder(self.resolve_pads(h, w))


@dataclasses.dataclass
class ConvertedModel:
    """A converted graph: call ``model.fn(params, *inputs)`` (jit-compatible).

    Attributes:
        fn: pure function ``(params, *inputs) -> tuple(outputs)``.
        params: numpy weight pytree (dict keyed by const node name).
        input_specs: placeholder name/shape/dtype, in call order.
        output_names: tensor refs produced, e.g. ``["logits", "boxes:0"]``.
        s2d_stem: input-format rewrite handle when the graph's stem matches
            the space-to-depth pattern (else None) — see :class:`S2DStem`.
        from_canvases: ``fn(params, canvases, hws)`` takes a batch's uint8
            canvases and real sizes itself and answers whole (a token
            decoder: patches of the real pixels in, top-k lists out), so
            the engine puts no resize before it and no top-k after.
        max_rows: canvas side -> the most rows one call may hold there,
            where the model's ceiling is not a row count (token slots);
            None for the engine's ``max_batch`` at every canvas.
        counter_names: what the model counts a call; its last output is
            then a vector of these, not a row a request, and the batcher
            sums it into ``/stats``.
    """

    fn: Any
    params: dict[str, np.ndarray]
    input_specs: list[InputSpec]
    output_names: list[str]
    s2d_stem: S2DStem | None = None
    from_canvases: bool = False
    max_rows: Any = None
    counter_names: tuple[str, ...] = ()

    @property
    def input_names(self) -> list[str]:
        return [s.name for s in self.input_specs]


def _topo_order(graph: GraphDef, output_nodes: Sequence[str]) -> list[NodeDef]:
    """Iterative DFS topological sort of the ancestors of ``output_nodes``.

    Iterative because Inception-scale graphs are hundreds of nodes deep —
    recursion would hit Python's stack limit.
    """
    node_map = graph.node_map
    order: list[NodeDef] = []
    state: dict[str, int] = {}  # 0 = visiting, 1 = done
    for root in output_nodes:
        if root in state and state[root] == 1:
            continue
        stack: list[tuple[str, bool]] = [(root, False)]
        while stack:
            name, expanded = stack.pop()
            if expanded:
                state[name] = 1
                order.append(node_map[name])
                continue
            if state.get(name) == 1:
                continue
            if state.get(name) == 0:
                raise ValueError(f"cycle in graph at node '{name}'")
            if name not in node_map:
                raise KeyError(f"graph references unknown node '{name}'")
            state[name] = 0
            stack.append((name, True))
            for ref in node_map[name].inputs:
                if ref.startswith("^"):
                    continue  # control dependency — no data flow
                dep, _ = _ref_name(ref)
                if state.get(dep) != 1:
                    stack.append((dep, False))
    return order


def _infer_outputs(graph: GraphDef) -> list[str]:
    """Default outputs: non-trivial nodes nothing else consumes."""
    consumed: set[str] = set()
    for n in graph.nodes:
        for ref in n.inputs:
            consumed.add(_ref_name(ref.lstrip("^"))[0])
    # Identity is a legitimate sink — the standard freeze pattern names the
    # model output via a trailing Identity node.
    skip = {"Const", "NoOp", "Assert"} | set(_INPUT_OPS)
    return [n.name for n in graph.nodes if n.name not in consumed and n.op not in skip]


def _detect_s2d_stem(compute_nodes, input_names, params, statics, make_fn):
    """Match [Placeholder] → (optional static zero Pad) → stride-2 small-C
    Conv2D (NHWC, undilated, odd kernel) with each link single-consumer —
    the keras/TF-Slim frozen-graph stem pattern (Inception: direct VALID
    conv; MobileNet: ZeroPadding2D → VALID conv). Returns an
    :class:`S2DStem` or None."""
    if len(input_names) != 1:
        return None
    ph = input_names[0]

    def consumers_of(name):
        return [
            n
            for n in compute_nodes
            if n.op != "NoOp"
            and any(
                _ref_name(r) == (name, 0) for r in n.inputs if not r.startswith("^")
            )
        ]

    cons = consumers_of(ph)
    if len(cons) != 1:
        return None
    node = cons[0]
    base_pads = ((0, 0), (0, 0))
    skip: frozenset[str] = frozenset()
    if node.op == "Pad":
        pads_v = statics.get(_ref_name(node.inputs[1])[0])
        if not isinstance(pads_v, np.ndarray) or pads_v.shape != (4, 2):
            return None
        p = pads_v.astype(np.int64)
        if (p < 0).any() or p[0].any() or p[3].any():
            return None  # batch/channel padding: not a spatial stem pad
        base_pads = ((int(p[1, 0]), int(p[1, 1])), (int(p[2, 0]), int(p[2, 1])))
        nxt = consumers_of(node.name)
        if len(nxt) != 1:
            return None
        skip = frozenset({node.name})
        node = nxt[0]
    if node.op != "Conv2D":
        return None

    from ..ops import stem as stem_ops
    from ..ops.tf_ops import _decode, _hw

    df = _decode(node.attr("data_format"), "NHWC")
    if df != "NHWC":
        return None
    strides = _hw(node.attr("strides"), df)
    dil = _hw(node.attr("dilations", [1, 1, 1, 1]), df)
    padding = _decode(node.attr("padding"), "VALID")
    if padding not in ("SAME", "VALID") or (padding == "SAME" and skip):
        return None  # Pad-then-SAME never occurs in the genre; keep it simple
    # Kernel may sit behind passthrough nodes (frozen keras graphs wire
    # consts through ReadVariableOp/Identity); follow them to the weight.
    node_by_name = {n.name: n for n in compute_nodes}
    kname = _ref_name(node.inputs[1])[0]
    for _ in range(8):
        if kname in params or kname in statics:
            break
        nd = node_by_name.get(kname)
        if nd is None or nd.op not in ("Identity", "ReadVariableOp"):
            break
        kname = _ref_name(nd.inputs[0])[0]
    kernel = params.get(kname)
    if kernel is None:
        kernel = statics.get(kname)
    if not isinstance(kernel, np.ndarray) or kernel.ndim != 4:
        return None
    if not stem_ops.worthwhile(kernel.shape[2], strides, kernel.shape[:2], dil):
        return None

    conv_name = node.name
    return S2DStem(
        conv_name=conv_name,
        skip_names=skip,
        base_pads=base_pads,
        conv_padding=padding,
        kernel_hw=(int(kernel.shape[0]), int(kernel.shape[1])),
        _builder=lambda pads: make_fn((conv_name, skip, pads)),
    )


def convert_graphdef(
    graph: GraphDef,
    outputs: Sequence[str] | None = None,
    inputs: Sequence[str] | None = None,
) -> ConvertedModel:
    """Convert a parsed ``GraphDef`` into a :class:`ConvertedModel`.

    Args:
        graph: parsed graph (see :func:`..graphdef.proto.parse_graphdef`).
        outputs: tensor refs to produce (``"name"`` or ``"name:idx"``); if
            omitted, inferred as the graph's sink nodes.
        inputs: placeholder order override; defaults to graph order.
    """
    output_refs = [r for r in (outputs or _infer_outputs(graph))]
    output_nodes = [_ref_name(r)[0] for r in output_refs]
    order = _topo_order(graph, output_nodes)

    params: dict[str, np.ndarray] = {}
    statics: dict[str, Any] = {}
    placeholders: list[NodeDef] = []

    for node in order:
        if node.op == "Const":
            value = node.attr("value")
            if (
                isinstance(value, np.ndarray)
                and value.dtype.kind == "f"
                and value.size >= _PARAM_MIN_SIZE
            ):
                params[node.name] = value
            else:
                statics[node.name] = value
        elif node.op in _INPUT_OPS:
            placeholders.append(node)

    if inputs is not None:
        by_name = {p.name: p for p in placeholders}
        placeholders = [by_name[n] for n in inputs]

    input_specs = [
        InputSpec(
            name=p.name,
            shape=p.attr("shape"),
            dtype=np_dtype(p.attr("dtype", DT_FLOAT)),
        )
        for p in placeholders
    ]
    input_names = [p.name for p in placeholders]
    compute_nodes = [
        n for n in order if n.op != "Const" and n.name not in {p.name for p in placeholders}
    ]
    # Resolve handlers eagerly so unsupported ops fail at convert time, not
    # on the first request (SURVEY.md §5.3 failure-detection stance).
    handlers = {n.name: tf_ops.get_handler(n.op) for n in compute_nodes if n.op != "NoOp"}

    def make_fn(s2d: tuple | None = None):
        """Graph evaluator factory. ``s2d`` = (conv_name, skip_names,
        explicit_pads): the first positional arg is then pack_s2d CELLS,
        the skipped nodes (the absorbed Pad) never run, and the stem conv
        evaluates via ``ops.stem.conv2d_s2d_input``."""
        s2d_conv, s2d_skip, s2d_pads = s2d if s2d else (None, frozenset(), None)
        from ..ops import stem as stem_ops

        def fn(params_arg: dict[str, Any], *args, float_dtype=None):
            """Evaluate the graph. ``float_dtype`` is the compute-dtype
            policy: float *statics* (small consts that stayed numpy) are
            cast to it at trace time so e.g. ``bf16_activation * f32_const``
            doesn't silently promote the whole network back to float32 on
            the MXU."""
            if len(args) != len(input_names):
                raise TypeError(
                    f"expected {len(input_names)} inputs {input_names}, got {len(args)}"
                )
            values: dict[tuple[str, int], Any] = {}
            for name, arr in zip(input_names, args):
                values[(name, 0)] = arr
            for name, v in statics.items():
                if (
                    float_dtype is not None
                    and isinstance(v, np.ndarray)
                    and v.dtype.kind == "f"
                ):
                    v = v.astype(float_dtype)
                values[(name, 0)] = v
            for name in params:
                values[(name, 0)] = params_arg[name]

            for node in compute_nodes:
                if node.op == "NoOp" or node.name in s2d_skip:
                    continue
                if node.name == s2d_conv:
                    cells = values[(input_names[0], 0)]
                    wv = values[_ref_name(node.inputs[1])]
                    values[(node.name, 0)] = stem_ops.conv2d_s2d_input(
                        cells, wv, s2d_pads
                    )
                    continue
                ins = [
                    values[_ref_name(ref)]
                    for ref in node.inputs
                    if not ref.startswith("^")
                ]
                handler = handlers[node.name]
                use_np = handler.static_ok and all(_is_static(v) for v in ins)
                out = handler.fn(node, ins, np if use_np else tf_ops.jnp)
                if isinstance(out, tuple):
                    for i, o in enumerate(out):
                        values[(node.name, i)] = o
                else:
                    values[(node.name, 0)] = out
            return tuple(values[_ref_name(r)] for r in output_refs)

        return fn

    s2d_stem = _detect_s2d_stem(
        compute_nodes, input_names, params, statics, make_fn
    )
    return ConvertedModel(
        fn=make_fn(),
        params=params,
        input_specs=input_specs,
        output_names=list(output_refs),
        s2d_stem=s2d_stem,
    )


def convert_pb(path: str, outputs: Sequence[str] | None = None, inputs: Sequence[str] | None = None) -> ConvertedModel:
    """``load_graph()`` equivalent: frozen ``.pb`` file → jittable JAX model."""
    return convert_graphdef(load_pb(path), outputs=outputs, inputs=inputs)
