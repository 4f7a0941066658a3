#!/usr/bin/env python
"""Does the serving path still start and answer correctly on the chip?

    python chip_smoke.py              # one TPU chip: main, restart, kernels+parity
    python chip_smoke.py --chips 4    # four chips: ONLY the cross-chip phase
    python chip_smoke.py --rehearse   # the same control flow on the CPU, tiny
                                      # models; always ends "ok": false

Every phase starts ``server.py`` as a child through its normal CLI, waits
for ``listening on``, talks HTTP to it, sends SIGTERM and waits for the
clean drain before the next phase starts: a chip belongs to one process at
a time, so this process never imports JAX, and the platform, device kind
and count on the last line are the ones the server's ``/healthz`` reports
from its own mesh. Images are JPEGs generated from ``--seed``; nothing is
read from ``artifacts/`` and nothing from the network.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
``ok`` is true only if every check of every phase passed AND the platform
is ``tpu``, and the exit code is 0 only then. Earlier lines are smoke
observations (boot seconds, executables compiled vs loaded, a burst p50) —
what one run showed, not metrics. Server logs go to
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
LOG_DIR = ROOT / "chiprun_out" / "chip_smoke"
# The driver allows 1200 s; stop ourselves first, with every child reaped.
DEADLINE_S = 1150.0
# serving/engine.py::_PARITY_TOL["bfloat16"] — probability delta and the
# minimum margin-aware top-k agreement (tests/test_chip_smoke.py pins the
# copy to the original; importing the engine here would import JAX).
PARITY_TOL = {"prob": 0.08, "topk": 0.90}

TINY = {  # --rehearse: width-0.25 zoo configs at the smallest inputs that trace
    "inception_v3": {"name": "inception_v3", "source": "native",
                     "zoo_width": 0.25, "zoo_classes": 16,
                     "input_size": [96, 96], "preprocess": "inception"},
    "mobilenet_v2": {"name": "mobilenet_v2", "source": "native",
                     "zoo_width": 0.25, "zoo_classes": 16,
                     "input_size": [64, 64], "preprocess": "inception"},
}


# The token decoder (models/longcat_flash.py) at the tests' small size: on the
# chip it still runs the Mosaic kernels (mla_prefill at 256 and 1,024 token
# slots, expert_gmm), so a bring-up failure shows here before a cell does.
DECODER = {"name": "longcat_flash", "source": "native", "task": "generate", "dtype": "bfloat16", "topk": 5,
           "decoder": {"hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
                       "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
                       "qk_nope_head_dim": 16, "v_head_dim": 16, "n_routed_experts": 24, "zero_expert_num": 12,
                       "moe_topk": 4, "routed_scaling_factor": 6, "experts_held": 6, "vocab_size": 64,
                       "patch": 8, "answer_steps": 4, "max_token_slots": 2048}}


def seeded_jpeg(seed: int, h: int, w: int) -> bytes:
    """A deterministic h×w JPEG: smooth seeded gradients plus a little
    noise, so that the file is small and no two seeds look alike."""
    from PIL import Image

    rs = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    img = np.empty((h, w, 3), np.float32)
    for c in range(3):
        fy, fx, ph = rs.uniform(0.5, 3.0), rs.uniform(0.5, 3.0), rs.uniform(0, 6.28)
        img[..., c] = 127.5 + 110.0 * np.sin(fy * yy / h * 6.28 + fx * xx / w * 6.28 + ph)
    img += rs.normal(0.0, 6.0, img.shape)
    buf = io.BytesIO()
    Image.fromarray(img.clip(0, 255).astype(np.uint8)).save(buf, "JPEG", quality=88)
    return buf.getvalue()


def last_line(failures: list[str], device: dict | None) -> tuple[str, int]:
    """The result line and the exit code. A run on anything but a TPU is
    not a pass, however many phases passed."""
    device = device or {"platform": None, "kind": None, "count": 0}
    ok = not failures and device.get("platform") == "tpu"
    return json.dumps({"ok": ok, "device": device}), 0 if ok else 1


def compare_topk(ref: list[dict], got: list[dict], tol: float) -> tuple[float, float]:
    """(largest provable score delta, margin-aware agreement of ``got``'s
    picks) between two top-k prediction lists — engine.parity_check's two
    gates, as far as top-k lists (not whole probability vectors) can show
    them. A pick of ``got`` agrees when the reference scores it within
    ``tol`` of its own k-th best (ops/quant.py::topk_agreement); for a pick
    outside the reference's list all that is known is that the reference
    scores it between 0 and its k-th best."""
    r = {p["index"]: p["score"] for p in ref}
    g = {p["index"]: p["score"] for p in got}
    kth_r, kth_g = min(r.values()), min(g.values())
    delta = max(
        [abs(r[i] - g[i]) for i in r if i in g]
        + [r[i] - kth_g for i in r if i not in g]
        + [g[i] - kth_r for i in g if i not in r]
        + [0.0]
    )
    agree = sum(1 for i in g if i in r or kth_r - tol <= 0.0) / len(g)
    return delta, agree


class Smoke:
    """One run: the failures so far, the deadline, and the child to reap."""

    def __init__(self, args):
        self.args = args
        self.failures: list[str] = []
        self.device: dict | None = None
        self.t0 = time.monotonic()
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self._tmp = tempfile.TemporaryDirectory(prefix="chip_smoke_")
        self.tmp = Path(self._tmp.name)

    # ------------------------------------------------------------- reporting

    def note(self, **kv):
        print(json.dumps(kv), flush=True)

    def check(self, ok: bool, what: str, **kv):
        """Record one assertion. A false one flips the last line's ``ok``."""
        if not ok:
            self.failures.append(what)
        self.note(check=what, ok=bool(ok), **kv)
        return ok

    def left(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise TimeoutError(f"chip_smoke.py ran past its own {DEADLINE_S:.0f} s deadline")
        return left

    # ------------------------------------------------------------ the server

    def model_specs(self, *specs: str) -> list[str]:
        """``--model`` arguments; --rehearse swaps each zoo name for a tiny
        JSON config and keeps the option suffixes."""
        out = []
        for spec in specs:
            base, _, opts = spec.partition(",")
            if self.args.rehearse:
                name = base.split(":", 1)[1]
                path = self.tmp / f"{name}.json"
                path.write_text(json.dumps(TINY[name]))
                base = str(path)
            out += ["--model", base + ("," + opts if opts else "")]
        return out

    def start(self, phase: str, flags: list[str]) -> float:
        """Start server.py, wait for ``listening on``; seconds it took."""
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        LOG_DIR.mkdir(parents=True, exist_ok=True)
        self.log_path = LOG_DIR / f"{phase}.log"
        env = dict(os.environ)
        if self.args.rehearse:
            # Both caches start empty and die with the rehearsal: XLA:CPU
            # cannot re-serialize an executable it rebuilt from a warm JAX
            # cache, so a cold AOT directory beside a warm .jax_cache would
            # fail the restart phase for a reason the chip does not share.
            env["JAX_COMPILATION_CACHE_DIR"] = str(self.tmp / "jax_cache")
            flags = [*flags, "--aot-cache-dir", str(self.tmp / "aot_cache")]
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                                f"device_count={self.args.chips}").strip()
        cmd = [sys.executable, str(ROOT / "server.py"), "--port", str(self.port), *flags]
        self.note(phase=phase, start=" ".join(cmd[1:]))
        t0 = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                         stderr=subprocess.STDOUT)
        while "listening on" not in self.log_text():
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{phase}: server exited with {self.proc.returncode} before "
                    f"listening; log tail:\n{self.log_text()[-3000:]}")
            self.left()
            time.sleep(0.5)
        return time.monotonic() - t0

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def stop(self, phase: str):
        """SIGTERM and the clean drain: exit code 0, or it is a failure."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=min(120.0, self.left()))
        except subprocess.TimeoutExpired:
            self.reap()
            rc = "killed after 120 s"
        self.check(rc == 0, f"{phase}: SIGTERM drains and exits 0", exit=rc)
        self.proc = None

    def reap(self):
        """Leave nothing running, whatever happened."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    # ------------------------------------------------------------------ HTTP

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(self.url(path), timeout=min(120.0, self.left())) as r:
            return json.loads(r.read())

    def post(self, path: str, body: bytes, ctype: str = "image/jpeg"):
        """(status, headers, parsed body); an HTTP error status is returned,
        not raised — the caller checks it."""
        req = urllib.request.Request(self.url(path), data=body,
                                     headers={"Content-Type": ctype})
        try:
            with urllib.request.urlopen(req, timeout=min(300.0, self.left())) as r:
                return r.status, r.headers, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, e.headers, {"error": e.read().decode(errors="replace")}

    def predict(self, what: str, jpeg: bytes, model: str | None = None) -> list[dict]:
        """POST one image; check the 200, the trace id and k finite scores."""
        status, headers, body = self.post(
            "/predict" + (f"?model={model}" if model else ""), jpeg)
        preds = body.get("predictions") or []
        ok = (status == 200 and bool(headers.get("X-Trace-Id")) and len(preds) == 5
              and all(np.isfinite(p["score"]) for p in preds))
        self.check(ok, f"{what}: 200, X-Trace-Id, five finite scores",
                   status=status, error=body.get("error"))
        return preds

    def device_from_healthz(self, phase: str):
        hz = self.get("/healthz")
        self.device = {"platform": hz.get("platform"), "kind": hz.get("device_kind"),
                       "count": hz.get("devices")}
        self.note(phase=phase, healthz=hz)
        self.check(hz.get("ok") is True, f"{phase}: /healthz ok")
        self.check(self.device["count"] == self.args.chips,
                   f"{phase}: the server sees {self.args.chips} device(s)",
                   count=self.device["count"])
        if self.args.rehearse and self.device["platform"] == "tpu":
            raise RuntimeError("--rehearse is for the CPU; the server reports a TPU")

    # ---------------------------------------------------------------- phases

    def images(self) -> dict[str, tuple[int, int]]:
        """Name → (h, w). The rehearsal shrinks every side by 8."""
        sizes = {"small": (180, 240), "large": (1200, 1600), "mid": (600, 420),
                 "wide": (300, 900)}
        if self.args.rehearse:
            sizes = {k: (h // 8, w // 8) for k, (h, w) in sizes.items()}
        return sizes

    def jpeg(self, name: str, salt: int = 0) -> bytes:
        h, w = self.images()[name]
        return seeded_jpeg(self.args.seed + 1000 * salt + sorted(self.images()).index(name), h, w)

    def boot_report(self, phase: str, boot_s: float) -> dict:
        aot = self.get("/stats")["aot_cache"]
        # "Compiled" is an AOT miss: XLA built it, or JAX's persistent cache
        # had it (the seconds say which — a cache hit costs few).
        self.note(phase=phase, smoke_observation="boot to listening, seconds",
                  boot_s=round(boot_s, 1), executables_compiled=aot["misses_total"],
                  executables_loaded=aot["hits_total"],
                  compile_seconds_total=aot["compile_seconds_total"],
                  aot_cache_enabled=aot["enabled"], aot_corrupt=aot["corrupt_total"])
        return aot

    def main_requests(self, phase: str) -> dict:
        """The request mix of the main phase; returns the answers, which
        the restart phase must reproduce bit for bit."""
        answers = {}
        answers["small"] = self.predict(f"{phase}: small JPEG", self.jpeg("small"))
        big = self.jpeg("large")
        answers["large"] = self.predict(
            f"{phase}: JPEG wider than 1024 px" if not self.args.rehearse
            else f"{phase}: JPEG above the smallest canvas", big)

        names = ["small", "mid", "wide", "large"] * 2
        parts = [self.jpeg(n, salt=1 + i) for i, n in enumerate(names)]
        boundary = "chipsmoke"
        body = b"".join(
            (f"--{boundary}\r\nContent-Disposition: form-data; name=\"f{i}\"; "
             f"filename=\"f{i}.jpg\"\r\nContent-Type: image/jpeg\r\n\r\n").encode()
            + p + b"\r\n" for i, p in enumerate(parts)
        ) + f"--{boundary}--\r\n".encode()
        status, headers, resp = self.post(
            "/predict", body, f"multipart/form-data; boundary={boundary}")
        results = resp.get("results") or []
        self.check(
            status == 200 and len(results) == 8 and all(
                len(r["predictions"]) == 5
                and all(np.isfinite(p["score"]) for p in r["predictions"])
                for r in results),
            f"{phase}: 8-file multipart request, five finite scores per file",
            status=status, error=resp.get("error"))
        answers["multipart"] = [r["predictions"] for r in results]
        return answers

    def phase_main(self) -> tuple[list[str], dict]:
        phase = "main"
        # The server's default flags. Cut buckets here only if a cold
        # default boot cannot fit the time limit, and print which.
        flags = self.model_specs("native:inception_v3")
        if self.args.rehearse:
            flags += ["--canvas-buckets", "32,64,128,256"]
        boot_s = self.start(phase, flags)
        self.device_from_healthz(phase)
        aot = self.boot_report(phase, boot_s)
        self.check(aot["corrupt_total"] == 0, f"{phase}: aot_cache.corrupt_total is 0")

        # The response cache: the same bytes twice, miss then hit.
        fresh = self.jpeg("mid", salt=99)
        s1, h1, b1 = self.post("/predict", fresh)
        s2, h2, b2 = self.post("/predict", fresh)
        self.check(
            (s1, s2) == (200, 200) and h1.get("X-Cache") == "miss"
            and h2.get("X-Cache") == "hit" and h1.get("ETag") == h2.get("ETag")
            and b1.get("predictions") == b2.get("predictions"),
            f"{phase}: repeated image is X-Cache miss then hit, same ETag and predictions",
            x_cache=[h1.get("X-Cache"), h2.get("X-Cache")])

        answers = self.main_requests(phase)

        # 64 concurrent single-image requests: batch buckets above 1.
        burst = [seeded_jpeg(self.args.seed + 5000 + i, *self.images()["small"])
                 for i in range(64)]
        before = self.get("/stats")
        lat: list[float] = [0.0] * 64
        codes: list[int] = [0] * 64

        def one(i):
            t = time.monotonic()
            codes[i] = self.post("/predict", burst[i])[0]
            lat[i] = time.monotonic() - t

        threads = [threading.Thread(target=one, args=(i,)) for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=self.left())
        after = self.get("/stats")
        self.check(codes == [200] * 64, f"{phase}: 64 concurrent requests all 200",
                   codes=sorted(set(codes)))
        self.note(phase=phase, smoke_observation="burst of 64 concurrent requests",
                  p50_ms=round(1e3 * float(np.median(lat)), 1),
                  batch_size_histogram=after.get("batch_size_histogram"))
        a0, a1 = before["aot_cache"], after["aot_cache"]
        self.check(
            (a0["misses_total"], a0["compile_seconds_total"])
            == (a1["misses_total"], a1["compile_seconds_total"]),
            f"{phase}: compile counters do not move during the burst",
            before=[a0["misses_total"], a0["compile_seconds_total"]],
            after=[a1["misses_total"], a1["compile_seconds_total"]])
        self.check(any(int(b) > 1 for b in after.get("batch_size_histogram", {})),
                   f"{phase}: the burst formed batches above 1")

        self.check_stats(phase, after, wire="ragged")
        # The mid and large images fill canvases 1024 and 2048, whose unpack
        # is the Mosaic kernel on a TPU (ops/image.py::unpack_kernel_applies);
        # the small ones' canvas 256 and every canvas on the CPU take the
        # XLA gather. The answers above came through whichever ran.
        life = after["batcher"]["lifecycle"]
        kernel = life["unpack_kernel_batches_total"]
        self.check(0 < kernel < life["batches_total"] if self.device["platform"] == "tpu"
                   else kernel == 0,
                   f"{phase}: the ragged unpack ran the kernel where it applies, and only there",
                   unpack_kernel_batches=kernel, batches=life["batches_total"])
        self.stop(phase)
        return flags, answers

    def check_stats(self, phase: str, stats: dict, wire: str):
        by_status = stats["tracing"]["requests_by_status"]
        self.check(stats.get("errors_total") == 0 and set(by_status) == {"2xx"},
                   f"{phase}: /stats shows no errors", by_status=by_status)
        dec = stats["decode"]
        self.check(dec["native"] and dec["pil_jpeg_decodes_total"] == 0
                   and dec["native_decodes_total"] > 0,
                   f"{phase}: JPEGs decoded by the native extension, none by PIL",
                   decode=dec)
        want = ("cpu-calibrated" if self.device["platform"] == "cpu"
                else f"tpu-table:{self.device['kind']}")
        for name, econ in stats["economics"].items():
            self.check(econ.get("wire") == wire,
                       f"{phase}: economics.{name}.wire is {wire}", wire=econ.get("wire"))
            src = econ["peak"]["source"]
            self.check(src.startswith(want),
                       f"{phase}: economics.{name}.peak.source names this device's row",
                       source=src)

    def phase_restart(self, phase: str, flags: list[str], answers: dict, requests):
        """Identical flags again: nothing the first boot compiled is
        compiled again, and the answers are bit-identical."""
        boot_s = self.start(phase, flags)
        aot = self.boot_report(phase, boot_s)
        self.check(aot["enabled"] and aot["misses_total"] == 0
                   and aot["compile_seconds_total"] == 0 and aot["hits_total"] > 0,
                   f"{phase}: second boot compiles nothing (every executable loaded)",
                   misses=aot["misses_total"], hits=aot["hits_total"])
        self.check(aot["corrupt_total"] == 0, f"{phase}: aot_cache.corrupt_total is 0")
        again = requests(phase)
        # A request that ran alone ran the same executable on the same
        # bytes: bit-identical. The eight files of the multipart request
        # are batched by arrival, and a bf16 forward at another batch
        # shape may differ in its last bits.
        alone = [k for k in answers if k != "multipart"]
        pairs = zip(answers.get("multipart", []), again.get("multipart", []))
        delta = max([compare_topk(a, b, 0.0)[0] for a, b in pairs] + [0.0])
        self.check(all(again[k] == answers[k] for k in alone) and delta <= 1e-3,
                   f"{phase}: single-image answers bit-identical to the first boot, "
                   "multipart answers within 1e-3",
                   multipart_bit_identical=again.get("multipart") == answers.get("multipart"),
                   multipart_largest_score_delta=delta)
        self.stop(phase)

    KERNEL_ENTRIES = {
        "inception_v3": "native:inception_v3",
        "iv3_f32": "native:inception_v3,dtype=f32,as=iv3_f32",
        "mobilenet_v2": "native:mobilenet_v2",
        "mv2_q": "native:mobilenet_v2,dtype=int8,as=mv2_q",
    }

    def kernel_boot(self, phase: str, resize: str) -> dict[str, list]:
        """One boot of the four entries; every entry answers the same images."""
        flags = self.model_specs(*self.KERNEL_ENTRIES.values()) + [
            "--canvas-buckets", "64" if self.args.rehearse else "512",
            "--max-batch", "8", "--wire-format", "yuv420", "--resize", resize]
        boot_s = self.start(phase, flags)
        self.boot_report(phase, boot_s)
        probes = [self.jpeg(n, salt=7) for n in ("small", "mid", "wide")]
        answers = {
            name: [self.predict(f"{phase}: {name} image {i}", p, model=name)
                   for i, p in enumerate(probes)]
            for name in self.KERNEL_ENTRIES
        }
        return answers

    def parity(self, what: str, ref: list, got: list):
        pairs = [compare_topk(r, g, PARITY_TOL["prob"]) for r, g in zip(ref, got)]
        delta = max(d for d, _ in pairs)
        agree = sum(a for _, a in pairs) / len(pairs)
        self.check(delta <= PARITY_TOL["prob"] and agree >= PARITY_TOL["topk"], what,
                   largest_score_delta=round(delta, 6), topk_agreement=round(agree, 4),
                   tol=PARITY_TOL)

    def phase_kernels(self):
        phase = "kernels"
        pallas = self.kernel_boot(phase, "pallas")
        # (a) bf16 against f32 on the same images, on this device.
        self.parity(f"{phase}: bf16 Inception-v3 within tolerance of f32",
                    pallas["iv3_f32"], pallas["inception_v3"])
        # (b) the int8 entry's load-time gate ran the fused depthwise kernel.
        models = self.get("/models")
        mv = models["models"]["mv2_q"]["versions"][-1]
        par = mv.get("parity") or {}
        self.check(mv["state"] == "SERVING" and par.get("pass") is True
                   and par.get("fused_dw") is True,
                   f"{phase}: mv2_q is SERVING with parity.pass and parity.fused_dw",
                   state=mv["state"], parity=par)
        # (c) the serve executables hold the kernels they are meant to.
        calls = {m.group(1): int(m.group(2)) for m in re.finditer(
            r"warmup (\S+): serve executable .* holds (\d+) tpu_custom_call", self.log_text())}
        self.note(phase=phase, tpu_custom_calls=calls)
        if self.device["platform"] == "cpu":
            self.note(phase=phase, skipped="tpu_custom_call counts: the CPU backend "
                      "runs Pallas interpreted, so there are none to count")
            self.check(set(calls) == set(self.KERNEL_ENTRIES),
                       f"{phase}: every entry logged its custom-call count")
        else:
            self.check(set(calls) == set(self.KERNEL_ENTRIES)
                       and all(n >= 1 for n in calls.values())
                       and calls["mv2_q"] > calls["mobilenet_v2"],
                       f"{phase}: preprocess kernel in all four executables, "
                       "depthwise kernels in mv2_q's", calls=calls)
        self.check_stats(phase, self.get("/stats"), wire="yuv420")
        self.stop(phase)

        # (d) control: the XLA matmul resize answers the same images alike.
        phase = "kernels-control"
        matmul = self.kernel_boot(phase, "matmul")
        for name in self.KERNEL_ENTRIES:
            self.parity(f"{phase}: {name} pallas resize within tolerance of matmul",
                        matmul[name], pallas[name])
        self.stop(phase)

    def phase_decoder(self):
        """The token decoder, small: boot, one request an image size, an
        answer of ``answer_steps`` top-k lists, its counters in /stats."""
        phase = "decoder"
        path = self.tmp / "longcat_flash.json"
        path.write_text(json.dumps(DECODER))
        boot_s = self.start(phase, ["--model", str(path), "--canvas-buckets", "128,256", "--max-batch", "4"])
        self.note(phase=phase, boot_s=round(boot_s, 1))
        steps_stated = DECODER["decoder"]["answer_steps"]
        for i, (h, w) in enumerate(((128, 96), (200, 256))):
            status, _, body = self.post("/predict", seeded_jpeg(self.args.seed + 40 + i, h, w))
            steps = body.get("steps") or []
            ok = (status == 200 and len(steps) == steps_stated
                  and all(len(step) == 5 and all(np.isfinite(p["score"]) and p["score"] > 0 for p in step)
                          for step in steps))
            self.check(ok, f"{phase}: {h}x{w} answers {steps_stated} steps of five finite scores",
                       status=status, error=body.get("error"), first=[s[0]["index"] for s in steps if s])
        life = self.get("/stats")["batcher"]["lifecycle"]
        tokens = (128 // 8) * (96 // 8) + (200 // 8) * (256 // 8)
        self.check(life.get("images_total") == 2 and life.get("tokens_real_total") == tokens
                   and life.get("decode_steps_total") == 2 * (steps_stated - 1),
                   f"{phase}: /stats counts 2 images, {tokens} tokens, {2 * (steps_stated - 1)} decode steps",
                   counted={k: v for k, v in life.items() if k.endswith("_total") and isinstance(v, float)})
        calls = [int(n) for n in re.findall(r"warmup longcat_flash: serve executable .* holds (\d+) tpu_custom_call",
                                            self.log_text())]
        if self.device and self.device["platform"] == "tpu":
            self.check(bool(calls) and calls[0] >= 2, f"{phase}: the serve executable holds its Mosaic kernels",
                       calls=calls)
        self.stop(phase)

    def phase_chips(self):
        """Only what exists across chips: four one-chip replicas against
        one batch-sharded engine, then the restart."""
        phase = f"chips{self.args.chips}"
        n = self.args.chips
        flags = self.model_specs(
            f"native:inception_v3,replicas={n},as=iv3_r{n}",
            "native:inception_v3,shard=batch,as=iv3_sb",
        ) + ["--canvas-buckets", "64" if self.args.rehearse else "512",
             "--max-batch", "32",
             # Off, so that a repeated POST reaches a replica and not the
             # response cache: replica choice must never change an answer.
             "--cache-bytes", "0"]
        probes = [self.jpeg(k, salt=3) for k in ("small", "mid", "wide")]

        def requests(ph: str) -> dict:
            out = {}
            for name in (f"iv3_r{n}", "iv3_sb"):
                out[name] = [self.predict(f"{ph}: {name} image {i}", p, model=name)
                             for i, p in enumerate(probes)]
            return out

        boot_s = self.start(phase, flags)
        self.device_from_healthz(phase)
        aot = self.boot_report(phase, boot_s)
        self.check(aot["corrupt_total"] == 0, f"{phase}: aot_cache.corrupt_total is 0")
        reps0 = self.get("/stats")["staging"]["replicas"]
        answers = requests(phase)
        # Repeats walk the replicas (round-robin): same answer from each.
        repeats = [self.predict(f"{phase}: iv3_r{n} repeat {i}", probes[0],
                                model=f"iv3_r{n}") for i in range(2 * n)]
        self.check(all(r == answers[f"iv3_r{n}"][0] for r in repeats),
                   f"{phase}: {2 * n} repeated POSTs give identical predictions")
        # One image alone on a chip against a batch bucket split over n
        # chips: the same program at another batch shape, so equal up to
        # the last bits of a bf16 forward, which may reorder near-ties.
        delta = max(compare_topk(a, b, 0.0)[0]
                    for a, b in zip(answers[f"iv3_r{n}"], answers["iv3_sb"]))
        self.check(delta <= 1e-3,
                   f"{phase}: replicas={n} and shard=batch give the same predictions",
                   largest_score_delta=delta,
                   bit_identical=answers[f"iv3_r{n}"] == answers["iv3_sb"])
        stats = self.get("/stats")
        reps = stats["staging"]["replicas"]
        self.note(phase=phase, replicas=reps, device_memory=stats.get("device_memory"))
        self.check(len(reps) == n and all(
            r["dispatches_total"] > r0["dispatches_total"] for r, r0 in zip(reps, reps0)),
            f"{phase}: requests were dispatched on all {n} replicas")
        homes = [tuple(r["param_device_ids"]) for r in reps]
        self.check(len(set(homes)) == n and all(len(h) == 1 for h in homes),
                   f"{phase}: each replica's parameters live on its own device",
                   param_device_ids=homes)
        self.check_stats(phase, stats, wire="ragged")
        self.stop(phase)
        self.phase_restart(f"{phase}-restart", flags, answers, requests)

    # ------------------------------------------------------------------- run

    def run(self):
        if self.args.chips > 1:
            self.phase_chips()
            return
        flags, answers = self.phase_main()
        self.phase_restart("restart", flags, answers, self.main_requests)
        self.phase_kernels()
        self.phase_decoder()


def accelerator_or_exit():
    """Without an accelerator, fail before a full-size boot on the CPU takes
    the whole time limit: ask a child that exits before any server starts
    (this process stays off JAX). Prints no result line."""
    probe = ("import jax, json; d = jax.devices(); "
             "print(json.dumps([d[0].platform, d[0].device_kind, len(d)]))")
    p = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, timeout=300)
    if p.returncode != 0:
        sys.exit(f"chip_smoke.py: JAX found no device:\n{p.stderr[-2000:]}")
    platform, kind, count = json.loads(p.stdout.strip().splitlines()[-1])
    if platform != "tpu":
        sys.exit(f"chip_smoke.py: needs a TPU; JAX reports {count} x {kind} "
                 f"({platform}). --rehearse runs the control flow on the CPU.")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run ONLY the cross-chip phase (replicas=4 against "
                         "shard=batch, then the restart)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny model configs, small buckets; "
                         "refused when the server reports a TPU; ends ok: false")
    ap.add_argument("--seed", type=int, default=0, help="seed of every generated JPEG")
    args = ap.parse_args(argv)
    if not (ROOT / "server.py").exists():
        sys.exit("chip_smoke.py: server.py is not beside this script")
    if not args.rehearse:
        accelerator_or_exit()

    smoke = Smoke(args)
    try:
        smoke.run()
    except Exception as e:
        # A phase that could not finish is a failed phase; the traceback and
        # the server's log tail go to stderr, and ok flips.
        import traceback

        traceback.print_exc()
        if smoke.proc is not None:
            print(smoke.log_text()[-4000:], file=sys.stderr)
        smoke.failures.append(f"{type(e).__name__}: {e}")
    finally:
        smoke.reap()
        smoke._tmp.cleanup()
    for f in smoke.failures:
        smoke.note(failed=f)
    smoke.note(smoke_observation="whole run, seconds",
               wall_s=round(time.monotonic() - smoke.t0, 1))
    line, code = last_line(smoke.failures, smoke.device)
    print(line, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
