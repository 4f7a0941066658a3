"""AOT-serialized executable cache — the cold-start killer (ISSUE 18).

Every boot, hot-swap and rewarm used to pay the full XLA compile walk:
``engine.warmup()`` compiles one executable per (canvas bucket, batch
bucket, ragged-rows variant, replica), seconds apiece, which makes
scale-from-zero (ROADMAP item 2) a compile storm. This module makes the
rewarm a file read instead: executables compiled once are serialized via
``jax.experimental.serialize_executable`` into a content-addressed
on-disk cache, and the next warmup with the same key deserializes in
milliseconds.

Correctness model — the cache may only ever be a *speedup*:

- **Keys cover everything that invalidates an executable**: jax/jaxlib
  versions, backend + device kind, the replica's exact device ids and
  submesh shape, the model identity (name/source/dtype/fused_dw/
  input_size/topk/task/preprocess/zoo knobs/output names), placement,
  wire format + packed_io/resize/s2d, and the (canvas, batch[, rows])
  shape triple. A stale or foreign entry can never be *found* — its
  digest differs.
- **Entries self-verify**: each file carries a magic, a SHA-256 of the
  body, and the full key dict it was stored under. A truncated file, a
  flipped bit, or a digest collision (body key != expected key) counts
  as ``corrupt`` and loads as None — the caller recompiles. Failures are
  counted, never fatal, and can never serve wrong results (the payload
  either deserializes into the exact program or is discarded).
- **Writes are atomic**: serialize → unique tmp file in the same
  directory → ``os.replace``. Readers either see a complete entry or no
  entry; two engines warming against one directory race benignly (last
  writer wins with identical bytes).

Composition with JAX's persistent compilation cache (always on —
utils/env.py): on the TPU the two compose. Observed on a v5e (PR 21): AOT
entries written from executables that JAX had rebuilt out of its own
persistent cache loaded in a fresh process, 19 of 19, none corrupt,
answers bit-identical; chip_smoke.py's restart phase repeats the check on
every tree. On XLA:CPU they do NOT: such an executable re-serializes
without its jitted object code, and the entry deserializes but fails at
its first execution in another process ("Function ... not found"), which
ends warmup. On the CPU backend, clear ``.aot_cache`` and ``.jax_cache``
together; the CPU tests that boot twice on this cache keep JAX's cache off
in the test (tests/test_aotcache.py).

Counters (hits/misses/writes/corrupt/bytes written, plus cumulative
compile/deserialize seconds) are process-wide module state under
``aotcache.lock`` — a declared leaf rank in lockorder.toml. Only counter
arithmetic runs under the lock; serialization, file IO and compilation
all happen outside it (twdlint's blocking rule is the enforcement).
"""

from __future__ import annotations

import hashlib
import io
import json
import logging
import os
import pickle
import tempfile
import time

import jax
import numpy as np
from jax._src import compiler
from jax.experimental import serialize_executable as se

from ..utils.locks import named_lock

log = logging.getLogger("tpu_serve.aotcache")

# Bump to invalidate every existing cache entry (serialization layout or
# loader semantics change). Part of every key.
FORMAT_VERSION = 1

_MAGIC = b"TWDAOTX1"
_SUFFIX = ".aotx"

# Process-wide counters: monotonic across engine rebuilds and hot-swaps,
# so /metrics exports never see a counter reset when a model version
# flips. Guarded by the declared leaf lock below; pure arithmetic only.
_lock = named_lock("aotcache.lock")
_counters = {
    "hits_total": 0,
    "misses_total": 0,
    "writes_total": 0,
    "corrupt_total": 0,
    "bytes_written_total": 0,
    "compile_seconds_total": 0.0,
    "deserialize_seconds_total": 0.0,
}


def _bump(name: str, n=1):
    with _lock:
        _counters[name] += n


def record_compile_seconds(s: float):
    """Account one executable compile's wall seconds (counted whether or
    not a cache is configured — the telemetry compile.seconds series is
    the boot-cost signal even on cache-off deployments)."""
    _bump("compile_seconds_total", float(s))


def record_deserialize_seconds(s: float):
    _bump("deserialize_seconds_total", float(s))


def stats(cache: "AotCache | None" = None) -> dict:
    """Process-wide counter snapshot, plus the given cache's identity
    (the /stats "aot_cache" block; pass the default engine's cache)."""
    with _lock:
        out = dict(_counters)
    out["compile_seconds_total"] = round(out["compile_seconds_total"], 3)
    out["deserialize_seconds_total"] = round(
        out["deserialize_seconds_total"], 3)
    out["enabled"] = cache is not None
    out["dir"] = cache.dir if cache is not None else None
    return out


# Every backend compile of the process, through this cache or not: JAX
# records one duration event per executable it asks the backend for (a
# jitted call's first use, a ``.lower().compile()``, a rebuild out of JAX's
# persistent cache, which is then the retrieval's time). The AOT counters
# above see only what the engine compiles through this module; a stray
# ``jax.jit`` that compiles while serving shows only here.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_backend_compiles = {"backend_compiles_total": 0,
                     "backend_compile_s_total": 0.0}


def _on_event_duration(event: str, duration_s: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        with _lock:
            _backend_compiles["backend_compiles_total"] += 1
            _backend_compiles["backend_compile_s_total"] += float(duration_s)


# Registered once, at import: the engine imports this module before it
# builds anything, so the count covers the process's whole life.
jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def backend_compile_stats() -> dict:
    """The /stats "compile" block (cumulative, monotonic)."""
    with _lock:
        return dict(_backend_compiles)


def key_digest(key: dict) -> str:
    """Stable content address of a key dict: SHA-256 over its canonical
    JSON (sorted keys, no whitespace). Keys must be JSON-plain —
    str/int/float/bool/None and lists/dicts thereof — so the digest is
    identical across processes and restarts."""
    blob = json.dumps(key, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def loadable_on(devices) -> bool:
    """Can an executable for exactly ``devices`` be loaded back onto them?
    One device: yes. Every device of the backend, in order: yes. A proper
    sub-mesh of several devices: not on a TPU (see
    :func:`_deserialize_onto`), so the engine keeps such replicas out of
    the cache and lets JAX's persistent cache spare them the compile."""
    devices = list(devices)
    return len(devices) == 1 or devices == list(devices[0].client.devices())


def _deserialize_onto(stored_exe, devices):
    """``jax.experimental.serialize_executable.deserialize_and_load`` for
    an executable that belongs on ``devices``. Under jax 0.9 a serialized
    executable does not carry its device assignment, and what happens on
    load was found bringing ``replicas=N`` up on four v5e chips (PR 21):

    * without ``execution_devices`` the loader assumes every device of
      the backend, and a one-device executable comes back expecting a
      shard per device (any backend);
    * with them, a TPU still assigns the program to devices 0..n-1:
      replica 1's first dispatch fails ("Buffer ... is on device TPU_1,
      but replica is assigned to device TPU_0"). For ONE device the
      remedy is JAX's own persistent cache's: pass compile options that
      carry the assignment. For a sub-mesh of several devices that remedy
      halted the core on the chip, so :func:`loadable_on` keeps those out.
    """
    payload, in_tree, out_tree = stored_exe
    if len(devices) > 1:
        return se.deserialize_and_load(payload, in_tree, out_tree,
                                       execution_devices=devices)
    unloaded, args_info_flat, no_kwargs = _OneDeviceUnpickler(
        io.BytesIO(payload), devices[0]).load()
    return jax.stages.Compiled(
        unloaded.load(), [], in_tree.unflatten(args_info_flat), out_tree,
        no_kwargs=no_kwargs)


class _OneDeviceUnpickler(se._JaxPjrtUnpickler):
    """serialize_executable's unpickler, loading the executable with
    compile options that assign it to ``device``."""

    def __init__(self, file, device):
        super().__init__(file, device.client, [device])
        self.options = compiler.get_compile_options(
            num_replicas=1, num_partitions=1,
            device_assignment=np.array([[device.id]]), backend=device.client)

    def persistent_load(self, pid):
        if pid[0] == "exec":
            return self.backend.deserialize_executable(
                pid[1], executable_devices=self.execution_devices,
                compile_options=self.options)
        return super().persistent_load(pid)


class AotCache:
    """One directory of content-addressed serialized executables.

    ``load``/``store`` take the full key dict; the filename is its
    digest, and the stored body repeats the key so a digest collision or
    a tampered file degrades to ``corrupt`` + recompile instead of
    loading a foreign program.
    """

    def __init__(self, directory: str):
        self.dir = str(directory)
        os.makedirs(self.dir, exist_ok=True)

    @staticmethod
    def from_config(cfg) -> "AotCache | None":
        """The engine's constructor hook: None (disabled) unless
        ``cfg.aot_cache_dir`` names a directory ("0"/empty disable)."""
        d = getattr(cfg, "aot_cache_dir", None)
        if not d or str(d) == "0":
            return None
        try:
            return AotCache(d)
        except OSError as e:
            log.warning("aot cache disabled: cannot create %r (%s)", d, e)
            return None

    # ----------------------------------------------------------------- paths

    def _path(self, key: dict) -> str:
        return os.path.join(self.dir, key_digest(key) + _SUFFIX)

    # ------------------------------------------------------------------ load

    def load(self, key: dict, devices):
        """Deserialize the executable stored under ``key`` onto
        ``devices`` — the exact devices it was compiled for, in mesh
        order (the key's ``device_ids``) — or None.

        None means "compile it yourself": absent file is a miss; any
        integrity failure (bad magic, checksum, key mismatch, unpickle or
        PJRT deserialize error) is counted corrupt. Never raises."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            _bump("misses_total")
            return None
        except OSError as e:
            log.warning("aot cache read failed for %s (%s); recompiling",
                        path, e)
            _bump("corrupt_total")
            return None
        t0 = time.perf_counter()
        try:
            if raw[: len(_MAGIC)] != _MAGIC:
                raise ValueError("bad magic")
            digest = raw[len(_MAGIC): len(_MAGIC) + 32]
            body = raw[len(_MAGIC) + 32:]
            if hashlib.sha256(body).digest() != digest:
                raise ValueError("checksum mismatch")
            stored = pickle.loads(body)
            if stored["key"] != key:
                # Digest collision or a forged/renamed file: the body's
                # own key is authoritative, and it is not ours.
                raise ValueError("key mismatch")
            exe = _deserialize_onto(stored["exe"], list(devices))
        except Exception as e:
            # Degrade, never fail: a poisoned entry costs one recompile.
            log.warning("aot cache entry %s unusable (%s); recompiling",
                        os.path.basename(path), e)
            _bump("corrupt_total")
            return None
        record_deserialize_seconds(time.perf_counter() - t0)
        _bump("hits_total")
        return exe

    # ----------------------------------------------------------------- store

    def store(self, key: dict, compiled) -> bool:
        """Serialize ``compiled`` under ``key`` via atomic rename.

        Returns False (logged, counted nothing) on any failure — a cache
        that cannot write is a cache that simply never hits."""
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            body = pickle.dumps(
                {"key": key, "exe": (payload, in_tree, out_tree)},
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            raw = _MAGIC + hashlib.sha256(body).digest() + body
            fd, tmp = tempfile.mkstemp(
                dir=self.dir, prefix=".tmp-", suffix=_SUFFIX)
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(raw)
                os.replace(tmp, self._path(key))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:
            log.warning("aot cache store failed for %s (%s)",
                        key.get("kind"), e)
            return False
        _bump("writes_total")
        _bump("bytes_written_total", len(raw))
        return True

    # ------------------------------------------------------------ inspection

    def entry_count(self) -> int:
        """Entries currently on disk (tests/bench only — /stats reports
        the process counters, not a directory scan)."""
        try:
            return sum(1 for n in os.listdir(self.dir)
                       if n.endswith(_SUFFIX) and not n.startswith(".tmp-"))
        except OSError:
            return 0
