"""Test environment: CPU backend with 8 fake devices.

SURVEY.md §4: the TPU-world analog of a fake NCCL backend is
``--xla_force_host_platform_device_count=8`` — sharding/collective tests run
against an 8-device CPU mesh, no hardware needed. Must be set before jax
initializes a backend, hence this conftest (pytest imports it first).
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
# Keep TF single-threaded-ish and quiet; it is only used to generate goldens.
os.environ.setdefault("TF_ENABLE_ONEDNN_OPTS", "0")

import jax

jax.config.update("jax_platforms", "cpu")
# 8 fake devices even if XLA_FLAGS was consumed before this point
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: full-scale / multi-minute tests")
    config.addinivalue_line(
        "markers",
        "perf: host-path performance regression smoke tests (CPU-cheap, "
        "tolerance-padded; run with -m perf to isolate)",
    )


@pytest.fixture(autouse=True)
def _lock_order_witness(request):
    """Runtime lock-order witness wiring (twdlint's dynamic half): with
    TWD_DEBUG_LOCKS=1 every named lock in the serving stack records its
    acquisitions, so ordinary test runs double as lock-order regression
    runs. Violations raise at the acquisition site; this fixture
    additionally asserts none were swallowed by a serving thread's
    failure-isolation ``except`` during the test. Perf-marked tests are
    exempt (witness bookkeeping would skew their timings); without the
    env switch this is a no-op and locks are plain threading primitives.
    """
    from tensorflow_web_deploy_tpu.utils import locks

    witness = locks.witness_active()
    if witness is None or request.node.get_closest_marker("perf"):
        yield
        return
    before = len(witness.violations)
    yield
    new = witness.violations[before:]
    assert not new, (
        "lock-order witness violations recorded during this test "
        f"(possibly swallowed by a serving thread): {new}"
    )


@pytest.fixture()
def rng():
    # Function-scoped on purpose: a shared session RandomState makes every
    # test's data depend on which tests drew from the stream first, so a
    # data-sensitive test (e.g. sharded-vs-single-device agreement) can pass
    # alone and fail in the full suite. Each test gets its own fresh,
    # identical stream — order-independent by construction. Broad-scoped
    # fixtures must not request this one (ScopeMismatch); they construct
    # their own RandomState inline.
    return np.random.RandomState(20260729)


@pytest.fixture(scope="session")
def small_cls_pb(tmp_path_factory):
    """Small real classifier (MobileNetV2 α=0.35 @96px), dynamic batch."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )

    path = tmp_path_factory.mktemp("artifacts") / "small_cls.pb"
    tf.keras.utils.set_random_seed(7)
    m = tf.keras.applications.MobileNetV2(input_shape=(96, 96, 3), alpha=0.35, weights=None)
    cf = tf.function(lambda x: m(x)).get_concrete_function(
        tf.TensorSpec([None, 96, 96, 3], tf.float32)
    )
    gd = convert_variables_to_constants_v2(cf).graph.as_graph_def()
    path.write_bytes(gd.SerializeToString())
    return str(path)


@pytest.fixture(scope="session")
def small_ssd_pb(tmp_path_factory):
    """Small SSD-style multi-output detector @96px (tools/make_artifacts)."""
    from tools.make_artifacts import make_ssd_mobilenet

    out = tmp_path_factory.mktemp("artifacts_ssd")
    make_ssd_mobilenet(out, num_classes=10, input_size=96)
    return str(out / "ssd_mobilenet.pb")
