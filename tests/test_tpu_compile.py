"""The Pallas kernels of the serving path, compiled by Mosaic for a v5e
that is described and not attached — no chip, about two seconds each.

Interpret mode (tests/test_quant.py, tests/test_pallas_preprocess.py,
tests/test_ragged.py) pins what the kernels compute; it cannot see what the
TPU compiler refuses. The depthwise and the preprocess
kernels had passed every interpret-mode test and were refused here at
serving shapes for more VMEM than a kernel may use (25.6 MB for one padded
114×114×32 image, 18 MB for one 2048 canvas, against 16 MB), which is why
they are tiled over rows. A compile that passes is not a chip run: it says
nothing about results or times (chip_smoke.py does the running).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from tensorflow_web_deploy_tpu.ops import experts, gqa, mla, retention, ssd
from tensorflow_web_deploy_tpu.ops.depthwise import fused_depthwise_bn
from tensorflow_web_deploy_tpu.ops.image import unpack_ragged
from tensorflow_web_deploy_tpu.ops.pallas_preprocess import preprocess_i420

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip's sharding; skipped only where this
    installation cannot describe the topology at all."""
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except RuntimeError as e:
        # Exactly what jax raises where libtpu is not installed; any other
        # failure to describe the chip is a failure of the test.
        if str(e).startswith("JAX TPU support not installed; cannot generate TPU topology."):
            pytest.skip(str(e))
        raise
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again): keep the cache out of these tests."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# The stride-1 depthwise layers of MobileNetV2 at width 1.0, 224 px,
# batch 32: the largest map, the one that overflowed next, and the two
# widest channel counts (none a multiple of 128).
@pytest.mark.parametrize("shape", [(32, 112, 112, 32), (32, 56, 56, 144),
                                   (32, 14, 14, 576), (32, 7, 7, 960)])
def test_fused_depthwise_compiles_for_v5e(v5e, shape):
    """The engine's own path for a bf16/int8 model: bf16 activations in,
    XLA pads and casts to f32, the Mosaic kernel, bf16 out."""
    c = shape[-1]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)
    k = jax.ShapeDtypeStruct((3, 3, 1, c), jnp.float32, sharding=v5e)
    v = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=v5e)
    compiled = jax.jit(
        lambda x, k, s, b: fused_depthwise_bn(x, k, s, b, impl="pallas")
    ).lower(x, k, v, v).compile()
    assert compiled.as_text().count(KERNEL) == 1


# The smallest default canvas bucket and the largest the server accepts
# with --resize pallas (2048 streams through VMEM in 512-row tiles).
@pytest.mark.parametrize("canvas", [256, 2048])
def test_preprocess_compiles_for_v5e(v5e, canvas):
    packed = jax.ShapeDtypeStruct((32, canvas * 3 // 2, canvas), jnp.uint8, sharding=v5e)
    hws = jax.ShapeDtypeStruct((32, 2), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda p, hw: preprocess_i420(p, hw, 299, 299, "inception")
    ).lower(packed, hws).compile()
    assert compiled.as_text().count(KERNEL) == 1
    # One program's HBM, against the 16 GB of a v5e.
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes < 1 << 30


# The ragged unpack as the engine builds it (word arena in, canvases out):
# the smallest canvas the kernel takes, the benchmark cell's two at a full
# bucket, and an arena shipped short of its bucket.
@pytest.mark.parametrize("canvas,bucket,rows", [(512, 8, 8), (2048, 32, 32),
                                                (4096, 32, 32), (4096, 32, 20)])
def test_ragged_unpack_compiles_for_v5e(v5e, canvas, bucket, rows):
    arena = jax.ShapeDtypeStruct((rows * canvas * canvas * 3 // 4,), jnp.uint32,
                                 sharding=v5e)
    meta = jax.ShapeDtypeStruct((bucket, 4), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda a, m: unpack_ragged(a, m, canvas), out_shardings=(v5e, v5e)
    ).lower(arena, meta).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    # No loop over canvas rows is left, and nothing beside the kernel
    # touches a canvas: the transpose back to [K, s, s, 3] is a re-view of
    # the planes the kernel wrote, in the layout jit_serve takes.
    assert "while(" not in text
    assert not re.search(r"= u(?:8|32)\[[\d,]+\]\S* (?:copy|transpose|fusion)\(", text)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 1 << 20  # arena in, canvases out, no copy
    assert m.output_size_in_bytes < bucket * canvas * canvas * 3 + (1 << 20)


# The same unpack where the arena arrives as its pages (a one-device replica
# ships them as its rows commit): the pages are joined on the device, one
# copy of the prefix in the temporaries, and the kernel is unchanged.
@pytest.mark.parametrize("canvas,bucket,rows", [(1536, 4, 3), (4096, 32, 32), (4096, 32, 20)])
def test_paged_ragged_unpack_compiles_for_v5e(v5e, canvas, bucket, rows):
    from tensorflow_web_deploy_tpu.serving.engine import _join_pages, page_sizes

    nbytes = rows * canvas * canvas * 3
    pages = tuple(jax.ShapeDtypeStruct((n // 4,), jnp.uint32, sharding=v5e)
                  for n in page_sizes(nbytes))
    meta = jax.ShapeDtypeStruct((bucket, 4), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda p, m: unpack_ragged(_join_pages(p), m, canvas), out_shardings=(v5e, v5e)
    ).lower(pages, meta).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and "while(" not in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < nbytes + (4 << 20)  # the joined prefix, once
    assert m.argument_size_in_bytes < nbytes + (1 << 20)


# The token decoder's kernels at the published widths (64 heads, keys of 128 +
# 64 rotary, values of 128; experts 6144 x 2048) and at the benchmark's three
# length buckets with the most rows a call holds of each.
@pytest.mark.parametrize("rows,slots", [(16, 1024), (4, 2304), (4, 4096)])
def test_mla_prefill_compiles_for_v5e(v5e, rows, slots):
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    compiled = jax.jit(lambda qn, qr, kn, kr, v, n: mla.pallas_core(qn, qr, kn, kr, v, n, 192 ** -0.5)).lower(
        s(rows, 64, slots, 128), s(rows, 64, slots, 64), s(rows, 64, slots, 128), s(rows, slots, 64),
        s(rows, 64, slots, 128), s(rows, dt=jnp.int32)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "mla_prefill" in compiled.as_text()


@pytest.mark.parametrize("k,n,col_tile", [(6144, 2048, 256), (2048, 6144, 512)])
def test_expert_gmm_compiles_for_v5e(v5e, k, n, col_tile):
    """One window of the grouped form: 4,096 rows in 32 tiles, 16 held experts."""
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    compiled = jax.jit(lambda x, w, te, nt: experts.expert_gmm(x, w, te, nt, col_tile=col_tile)).lower(
        s(experts.CHUNK, k), s(16, k, n), s(experts.CHUNK // experts.ROW_TILE, dt=jnp.int32),
        s(1, dt=jnp.int32)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "expert_gmm" in compiled.as_text()


# The second decoder's kernels at the published widths (64 Mamba heads of 64 with state 128 in 8 groups, chunk
# 128: two heads side by side in a block of 128 lanes; 32 query over 2 key/value heads of 128; 64 held experts
# 2688 x 1856 in stacks of whole 128-blocks, 1,920) and at the benchmark's three length buckets.
@pytest.mark.parametrize("rows,slots", [(16, 1024), (4, 2304), (4, 4096)])
def test_ssd_prefill_compiles_for_v5e(v5e, rows, slots):
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    scan = lambda xbc, dt, a, n: ssd.pallas_scan(xbc, dt, a, n, heads=64, head_dim=64, groups=8, chunk=128)
    compiled = jax.jit(scan).lower(s(rows, slots, 6144), s(rows, slots, 64, dt=jnp.float32), s(64, dt=jnp.float32),
                                   s(rows, dt=jnp.int32)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "ssd_prefill" in compiled.as_text()


@pytest.mark.parametrize("rows,slots", [(16, 1024), (4, 2304), (4, 4096)])
def test_gqa_prefill_compiles_for_v5e(v5e, rows, slots):
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    compiled = jax.jit(lambda q, k, v, n: gqa.pallas_core(q, k, v, n, 128 ** -0.5)).lower(
        s(rows, 2, 16, slots, 128), s(rows, 2, slots, 128), s(rows, 2, slots, 128), s(rows, dt=jnp.int32)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "gqa_prefill" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(2688, 1920), (1920, 2688)])
def test_expert_gmm_compiles_for_v5e_at_the_second_decoders_widths(v5e, k, n):
    """One window of the grouped form over 64 held experts; 512 columns are asked, 384 divide both widths."""
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    assert experts._col_tile(n, 512) == 384
    compiled = jax.jit(lambda x, w, te, nt: experts.expert_gmm(x, w, te, nt, col_tile=512)).lower(
        s(experts.CHUNK, k), s(64, k, n), s(experts.CHUNK // experts.ROW_TILE, dt=jnp.int32),
        s(1, dt=jnp.int32)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "expert_gmm" in compiled.as_text()


# The third decoder's kernels at the published widths (40 query over 8 key/value heads of 128: 8,704 features a
# head, a state of 35.9 MB a row and layer) and at the benchmark's three length buckets.
@pytest.mark.parametrize("rows,slots", [(16, 1024), (4, 2304), (4, 4096)])
def test_retention_prefill_compiles_for_v5e(v5e, rows, slots):
    """Under the 16 MiB of scoped VMEM: the state and normaliser in float32
    (4.7 MB), their bfloat16 copy, and one chunk's features of keys and of
    one query head (2.2 MB each)."""
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    compiled = jax.jit(lambda q, k, v, g, n: retention.pallas_prefill(q, k, v, g, n, chunk=128)).lower(
        s(rows, slots, 8, 5, 128), s(rows, slots, 8, 128), s(rows, slots, 8, 128), s(rows, slots, 8, dt=jnp.float32),
        s(rows, dt=jnp.int32)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "retention_prefill" in compiled.as_text()


def test_retention_step_compiles_for_v5e_and_updates_the_states_in_place(v5e):
    s = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    n_feat = retention.feature_count(128)
    compiled = jax.jit(lambda q, k, v, g, st, z: retention.pallas_step(q, k, v, g, st, z), donate_argnums=(4, 5)).lower(
        s(16, 8, 5, 128), s(16, 8, 128), s(16, 8, 128), s(16, 8), s(16, 8, 128, n_feat), s(16, 8, 1, n_feat)).compile()
    assert compiled.as_text().count(KERNEL) == 1 and "retention_step" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 16 * 8 * 128 * n_feat * 4 and m.temp_size_in_bytes < 64 << 20


def test_the_brumby_call_holds_each_state_once(v5e, monkeypatch):
    """``answer`` at the published widths, 16 rows of 256 slots, with the
    kernels' path: a layer more adds one layer's states (16 rows x 35.9 MB)
    to the call's temporaries, not two. The 63 steps carry them through a
    ``lax.scan`` and ``retention_step`` writes them where they were read."""
    from tensorflow_web_deploy_tpu.models import brumby

    monkeypatch.setattr(retention, "_on_tpu", lambda: True)
    s = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    temps = []
    for layers in (1, 2):
        c = brumby.Config(num_hidden_layers=layers)
        params = {k: s(*v) for k, v in brumby.param_shapes(c).items()}
        compiled = jax.jit(lambda p, x, n: brumby.answer(c, p, x, n, 5)).lower(
            params, s(16, 256, 3072), s(16, dt=jnp.int32)).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    states = 16 * 8 * (128 + 1) * retention.feature_count(128) * 4
    assert states <= temps[1] - temps[0] < 1.5 * states, (temps, states)
