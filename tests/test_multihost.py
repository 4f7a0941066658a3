"""Multi-host runtime: two REAL processes join via the §5.8 bootstrap seam.

SURVEY.md §5.8's claim is that the framework's "distributed backend" is
mesh construction + shardings and that hosts join via
``jax.distributed.initialize()`` behind ``parallel.distributed``. This
test makes that claim executable without TPU hardware: two OS processes,
4 fake CPU devices each, bootstrap through ``TPU_SERVE_COORDINATOR`` (the
exact env contract ``maybe_initialize`` documents), build the global
('data', 'model') mesh spanning 8 devices, and run

  1. a cross-process collective (global sum over a data-sharded array);
  2. a sharded train step whose gradient psum crosses the process
     boundary (the DCN stand-in) — loss must be finite and identical on
     both hosts, which only happens if the collectives actually ran.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_WORKER = """
import sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np, optax
from jax.sharding import NamedSharding, PartitionSpec as P
from tensorflow_web_deploy_tpu import models
from tensorflow_web_deploy_tpu.models.adapter import init_variables
from tensorflow_web_deploy_tpu.parallel import mesh as mesh_lib
from tensorflow_web_deploy_tpu.train import create_train_state, make_train_step

mesh = mesh_lib.build_mesh()  # bootstraps jax.distributed from the env
pid, n = jax.process_index(), jax.process_count()
assert n == 2, f"expected 2 processes, got {{n}}"
assert mesh.devices.size == 8, f"mesh should span both hosts, got {{mesh.devices.size}}"

# 1. cross-process collective: each host contributes its own value.
sh = mesh_lib.data_sharding(mesh)  # the canonical batch sharding
local = np.full((4,), float(pid + 1), np.float32)
g = jax.make_array_from_process_local_data(sh, local)
total = float(jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(g))
assert total == 12.0, f"global sum wrong: {{total}}"

# 2. sharded train step: batch split across hosts, grad psum crosses them.
spec = models.get("mobilenet_v2")
model, variables = init_variables(spec, num_classes=4, width=0.25, seed=0)
state = create_train_state(model, variables, optax.sgd(1e-2))
step = make_train_step(model, optax.sgd(1e-2), mesh=mesh)
rs = np.random.RandomState(7)  # same data on both hosts; each feeds its half
x_all = rs.rand(8, 32, 32, 3).astype(np.float32)
y_all = rs.randint(0, 4, 8).astype(np.int32)
lo, hi = (0, 4) if pid == 0 else (4, 8)
x = jax.make_array_from_process_local_data(sh, x_all[lo:hi])
y = jax.make_array_from_process_local_data(sh, y_all[lo:hi])
state, metrics = step(state, x, y)
loss = float(metrics["loss"])
assert np.isfinite(loss), loss
print(f"MULTIHOST_OK pid={{pid}} total={{total}} loss={{loss:.6f}}", flush=True)
"""


def test_two_process_mesh_and_train_step(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=str(REPO)))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    procs = []
    for i in range(2):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            TPU_SERVE_COORDINATOR=f"127.0.0.1:{port}",
            TPU_SERVE_PROCESS_ID=str(i),
            TPU_SERVE_NUM_PROCESSES="2",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(worker)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )

    outs = []
    try:
        for i, p in enumerate(procs):
            out, err = p.communicate(timeout=420)
            if p.returncode != 0 and (
                "Multiprocess computations aren't implemented" in err
            ):
                # Environment guard, not a product failure: some jax builds'
                # CPU backend (e.g. 0.4.x without the CPU collectives
                # transport) cannot run cross-process computations at all,
                # so the bootstrap seam is untestable here. Any OTHER
                # failure still fails the test — this matches exactly the
                # known capability gap.
                pytest.skip(
                    "jax CPU backend in this environment does not implement "
                    "multiprocess computations"
                )
            assert p.returncode == 0, f"worker {i} failed:\n{err[-3000:]}"
            outs.append(out)
            assert "MULTIHOST_OK" in out, out[-500:]
    finally:
        # One worker failing (or timing out) must not leave the other
        # blocked in the coordinator barrier holding the port.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)

    # Same loss on both hosts: the gradient psum really crossed processes.
    losses = {o.split("loss=")[1].split()[0] for o in outs if "loss=" in o}
    assert len(losses) == 1, f"hosts disagree on the loss: {losses}"
