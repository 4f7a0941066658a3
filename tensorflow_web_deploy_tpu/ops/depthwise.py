"""GSPMD-safe depthwise convolution (the trainer's dw-conv primitive).

XLA's SPMD partitioner mis-partitions the KERNEL gradient of a
``feature_group_count=C`` convolution: autodiff lowers that gradient as a
``batch_group_count`` convolution, and when the batch is sharded over one
mesh axis while the mesh has any OTHER axis of size m — even a completely
unused one — the kernel-grad psum runs over the full replica set instead of
the data-parallel groups, returning the gradient multiplied by m.
Reproduced deterministically on jax 0.9.0 (CPU backend, 8 fake devices,
meshes 4×2 → ×2 and 2×4 → ×4; dx and the forward pass are exact);
tests/test_depthwise.py pins both the repro and the fix.

The fix is a ``jax.custom_vjp``:

- forward and the input gradient use the stock lax convolution (both
  partition correctly — only the kernel-grad transpose is broken);
- the kernel gradient is computed as an explicit shift-multiply-reduce over
  the kernel window: kh·kw elementwise multiplies and batch+spatial sums,
  which GSPMD partitions as plain elementwise + reduction ops (psum over
  the batch axis only, by construction). For a 3×3 depthwise window that is
  9 fused multiply-adds — noise next to the surrounding 1×1 convs, and
  depthwise layers are bandwidth-bound anyway (no MXU work either way).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, kernel, strides, padding):
    return lax.conv_general_dilated(
        x,
        kernel,
        strides,
        padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1],
    )


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def depthwise_conv2d(x, kernel, strides=(1, 1), padding="SAME"):
    """Depthwise conv: x [B,H,W,C] ⊛ kernel [kh,kw,1,C] → [B,H',W',C].

    Numerically identical to ``lax.conv_general_dilated(...,
    feature_group_count=C)`` in both forward and gradient — but safe to
    differentiate under a multi-axis GSPMD mesh (see module docstring).
    ``padding`` is "SAME"/"VALID" or explicit ((lo,hi),(lo,hi)); dilation is
    out of scope (nothing in the zoo uses it).
    """
    return _conv(x, kernel, strides, padding)


def _fwd(x, kernel, strides, padding):
    return _conv(x, kernel, strides, padding), (x, kernel)


def _bwd(strides, padding, res, g):
    x, kernel = res
    # dx: the stock transpose rule partitions correctly — reuse it.
    _, vjp = jax.vjp(lambda x_: _conv(x_, kernel, strides, padding), x)
    (dx,) = vjp(g)

    # dk[dh,dw,0,c] = Σ_{b,i,j} x_pad[b, i·sh+dh, j·sw+dw, c] · g[b,i,j,c]
    kh, kw = kernel.shape[:2]
    sh, sw = strides
    if isinstance(padding, str):
        pads = lax.padtype_to_pads(x.shape[1:3], (kh, kw), strides, padding)
    else:
        pads = padding
    xp = jnp.pad(x, ((0, 0), tuple(pads[0]), tuple(pads[1]), (0, 0)))
    oh, ow = g.shape[1:3]
    # Accumulate in at least f32: the window sums run over B·oh·ow terms, too
    # many for bf16 accumulation when the policy casts activations down —
    # without downcasting f64 callers (the x64 equivalence tests).
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xp32 = xp.astype(acc)
    g32 = g.astype(acc)
    rows = []
    for dh in range(kh):
        cols = []
        for dw in range(kw):
            xs = lax.slice(
                xp32,
                (0, dh, dw, 0),
                (xp.shape[0], dh + (oh - 1) * sh + 1, dw + (ow - 1) * sw + 1, xp.shape[3]),
                (1, sh, sw, 1),
            )
            cols.append(jnp.sum(xs * g32, axis=(0, 1, 2)))
        rows.append(jnp.stack(cols))
    dk = jnp.stack(rows)[:, :, None, :].astype(kernel.dtype)
    return dx, dk


depthwise_conv2d.defvjp(_fwd, _bwd)


# ------------------------------------------------- fused inference forward
#
# The raw-speed tier's depthwise primitive: dwconv + folded-BN affine +
# relu6 in ONE op, so the dw stack's activations never round-trip through
# HBM between the three logical layers. The BN fold is exact algebra — a
# per-channel affine commutes with a depthwise conv:
#
#   bn(dwconv(x, k)) = dwconv(x, k·s) + b,  s = γ/√(var+ε),  b = β − μ·s
#
# Two implementations behind one dispatcher:
#   * "xla": kh·kw shift-multiply-accumulate over strided slices (the same
#     reformulation _bwd uses for the kernel gradient). On XLA:CPU this is
#     30-70× faster than the feature_group_count=C convolution, whose CPU
#     lowering is pathologically slow — measured 113.5 ms vs 1.6 ms per
#     batch-8 28×28×192 layer — and depthwise layers dominate MobileNetV2
#     CPU serve time.
#   * "pallas": the Mosaic kernel in ops/pallas_depthwise.py (stride-1
#     only) — one VMEM-resident pass per row tile on TPU.
# "auto" is decided by platform and shape alone: the pallas kernel for a
# stride-1 layer on a TPU, the XLA path everywhere else. There is no trial
# compile and no fallback — if Mosaic refuses the kernel at a shape the
# engine serves, the engine's warmup raises with the compiler's message.


def _shift_mac(x, kernel_c, strides, padding):
    """Depthwise conv as kh·kw strided-slice multiply-accumulates.

    ``kernel_c`` is [kh,kw,C] (the squeezed — possibly BN-folded — kernel).
    Matches ``lax.conv_general_dilated(feature_group_count=C)`` numerics up
    to float-add reordering. Accumulates in the promoted input dtype.
    """
    kh, kw = kernel_c.shape[:2]
    sh, sw = strides
    if isinstance(padding, str):
        pads = lax.padtype_to_pads(x.shape[1:3], (kh, kw), strides, padding)
    else:
        pads = padding
    xp = jnp.pad(x, ((0, 0), tuple(pads[0]), tuple(pads[1]), (0, 0)))
    oh = (xp.shape[1] - kh) // sh + 1
    ow = (xp.shape[2] - kw) // sw + 1
    acc = None
    for dh in range(kh):
        for dw in range(kw):
            xs = lax.slice(
                xp,
                (0, dh, dw, 0),
                (xp.shape[0], dh + (oh - 1) * sh + 1, dw + (ow - 1) * sw + 1, xp.shape[3]),
                (1, sh, sw, 1),
            )
            term = xs * kernel_c[dh, dw]
            acc = term if acc is None else acc + term
    return acc


def fused_depthwise_bn(x, kernel, scale, bias, strides=(1, 1), padding="SAME",
                       relu6=True, impl="auto"):
    """Fused dwconv(+BN+relu6): x [B,H,W,C] ⊛ kernel [kh,kw,1,C], then the
    folded per-channel affine (``scale``/``bias``, shape [C]) and an
    optional relu6 clamp — one op, no intermediate activations.

    ``impl``: "auto" (pallas for a stride-1 layer on a TPU, else XLA),
    "xla", "pallas", or "pallas_interpret" (tests: Mosaic semantics on CPU).
    """
    kh, kw = kernel.shape[:2]
    acc = jnp.promote_types(x.dtype, jnp.float32)
    kf = (kernel[:, :, 0, :] * scale).astype(acc)  # BN scale folds into k
    use_pallas = strides == (1, 1) and (
        impl in ("pallas", "pallas_interpret")
        or (impl == "auto" and jax.default_backend() == "tpu")
    )
    if use_pallas:
        from .pallas_depthwise import fused_dw_call

        if isinstance(padding, str):
            pads = lax.padtype_to_pads(x.shape[1:3], (kh, kw), strides, padding)
        else:
            pads = padding
        xp = jnp.pad(x, ((0, 0), tuple(pads[0]), tuple(pads[1]), (0, 0)))
        y = fused_dw_call(
            xp.astype(acc), kf.reshape(kh * kw, -1),
            bias.astype(acc).reshape(1, -1), kh=kh, kw=kw, relu6=relu6,
            interpret=(impl == "pallas_interpret"),
        )
        return y.astype(x.dtype)
    y = _shift_mac(x.astype(acc), kf, strides, padding) + bias.astype(acc)
    if relu6:
        y = jnp.clip(y, 0.0, 6.0)
    return y.astype(x.dtype)
