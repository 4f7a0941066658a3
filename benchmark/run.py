#!/usr/bin/env python3
"""One run of one cell: ``POST /predict`` on a server child, measured from outside.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX. It makes the weights from the seed (a child on
the CPU, the script the configuration names, writes them as a ``--ckpt``
export), builds the traffic from the seed
meanwhile, boots ``python server.py`` with the configuration's flags as a
child on a free port, waits for ``listening on`` (a child that had to compile
is stopped and booted again, so that the one measured only loads), sends each
distinct image shape once (untimed), runs the window, waits for every outstanding answer,
reads ``/stats``, and only then sends SIGTERM. Once the server is gone and
the chip is free, the configuration's check child (``check.py`` unless it
names another; on the chip) runs the plain reference over a sample of the
window's own answers and decides
``correct``; with ``--trace 1`` ``xplane.py`` (a child, on the CPU) reduces
the profiler trace that the server wrote during the window.

The last line of standard output is the result; the numbers compared, each
beside its limit, are the last lines of standard error. Exit code 0 only if
the server ran on a TPU with the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import base64
import json
import math
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

T_PROCESS_START = time.monotonic()

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from benchmark import loadgen, traffic  # noqa: E402
from benchmark.manifest import BENCH, ROOT, Cell, load_cell, load_reader, named  # noqa: E402
from benchmark.serverchild import ServerChild  # noqa: E402

TRACE_MS = 2500          # the profiler's window inside the measured window: one whole wave of batches
RUN_LIMIT_S = 360.0      # what the driver allows a run that does not compile, set-up, window and check together


def work_dir(cell: Cell) -> Path:
    """Scratch inside the checkout (``.scratch/`` is gitignored): the
    weights' export, the server's log, the trace."""
    d = ROOT / ".scratch" / "benchmark" / cell.name
    d.mkdir(parents=True, exist_ok=True)
    return d


def child_env(platform: str | None = None) -> dict:
    """A child's environment: ``BENCH_RUN`` is the driver's own and is not
    passed on; ``platform`` pins JAX (the weights' writer and the trace's
    reader stay off the chip)."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    if platform:
        env["JAX_PLATFORMS"] = platform
    return env


def server_flags(config: dict, work: Path, serve_dtype: str | None = None) -> list[str]:
    """``--model <file>``: the configuration's ``server_model``, the server's
    own JSON model config, with its weights from the export; then the
    configuration's flags. ``serve_dtype`` puts another of the program's
    precision tiers in the stated one's place: a control (``probe.py``),
    never a run of the benchmark."""
    model_json = work / "model.json"
    served = {**config["server_model"], "ckpt_path": str(work / "export")}
    if serve_dtype:
        served["dtype"] = serve_dtype
    model_json.write_text(json.dumps(served))
    return ["--model", str(model_json), *config["server_flags"]]


def write_weights(config: dict, seed: int, export_dir: Path) -> subprocess.Popen:
    """The configuration's weights script, a child on the CPU: the model
    block, the seed and where the export goes are all it is told."""
    return subprocess.Popen(
        [sys.executable, str(named(config).weights), json.dumps(config["model"]), str(seed), str(export_dir)],
        cwd=ROOT, env=child_env("cpu"), stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def judge(outcome: loadgen.Outcome, model: dict) -> bool:
    """Is this a correct 200? Per image either ``predictions``, one list of
    ``model.topk`` finite scores (a model that answers one step), or
    ``steps``, ``model.answer_steps`` such lists. Sets ``outcome.answers``
    per image as it came: [(index, score)...], or one such list a step."""
    if outcome.status != 200:
        return False
    topk, steps = int(model["topk"]), int(model.get("answer_steps", 1))

    def pairs(predictions) -> list[tuple[int, float]]:
        out = [(int(p["index"]), float(p["score"])) for p in predictions]
        if len(out) != topk or not all(math.isfinite(s) for _, s in out):
            raise ValueError("not topk finite scores")
        return out

    def answer(result: dict):
        if "steps" in result:
            if len(result["steps"]) != steps:
                raise ValueError("another number of steps than the configuration states")
            return [pairs(step) for step in result["steps"]]
        if steps != 1:
            raise ValueError("one step where the configuration states several")
        return pairs(result["predictions"])

    try:
        doc = json.loads(outcome.body)
        answers = [answer(r) for r in (doc["results"] if "results" in doc else [doc])]
    except (ValueError, KeyError, TypeError):
        return False
    if len(answers) != outcome.images:
        return False
    outcome.answers = answers
    return True


def warm_up(server: ServerChild, corpus: traffic.Corpus, source: traffic.Source, model: dict) -> int:
    """Every distinct image shape once, through the entry the window drives,
    so that the window meets no first-use cost. Returns requests sent."""
    opened: list = []
    conn = loadgen.Connection("127.0.0.1", server.port, 120.0, opened)
    sent = 0
    try:
        seen: set = set()
        while seen != set(corpus.mix.shapes):
            req = source.take()
            seen |= {base.hw for base, _ in req.images}
            body, ctype = req.body()
            out = loadgen.Outcome(req.index, len(req.images))
            conn.post("/predict", body, ctype, out)
            if not judge(out, model):
                raise RuntimeError(f"warm-up request {sent}: status {out.status}, body {out.body[:300]!r}")
            sent += 1
    finally:
        conn.close()
    return sent


def draw_sample(outcomes: list[loadgen.Outcome], requests: dict, seed: int,
                images: int) -> list[tuple[loadgen.Outcome, int]]:
    """``images`` (request, image ordinal) pairs for the reference: the
    request with the most pixels (the longest), then a draw from the seed."""
    ok = [o for o in outcomes if o.answers is not None]
    if not ok:
        return []
    rs = np.random.Generator(np.random.PCG64([seed, 4]))
    longest = max(ok, key=lambda o: sum(b.hw[0] * b.hw[1] for b, _ in requests[o.index].images))
    picks = [(longest, i) for i in range(longest.images)]
    order = [ok[i] for i in rs.permutation(len(ok))]
    for o in order:
        if len(picks) >= images:
            break
        if o is not longest:
            picks += [(o, i) for i in range(o.images)]
    return picks[:images]


def check_child(config: dict, seed: int, items: list[dict], control: str | None, limit_s: float) -> dict:
    """One answer of the configuration's check child: the document on its
    standard input (the model block, the seed, the limits, per item the JPEG
    and what was served for it, the control's name or none), its last line
    of standard output back (``correct``, ``compared``, ``images``,
    ``platform``). It may take the chip: no server is up."""
    child = named(config).check
    doc = {"model": config["model"], "seed": seed, "limits": config["limits"], "items": items,
           "control": control}
    proc = subprocess.run([sys.executable, str(child)], input=json.dumps(doc).encode(), cwd=ROOT,
                          env=child_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=limit_s)
    if proc.returncode != 0:
        raise RuntimeError(f"{child.name} exited {proc.returncode}:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


def run_check(cell: Cell, seed: int, sample, requests: dict, control: str | None = None,
              window_s: float = 0.0) -> dict:
    """The reference over ``sample``: each image's JPEG as it was sent and
    its answer as it came. The child has the configuration's
    ``check.limit_s``, and never more than a run has left beside its window."""
    items = []
    for o, i in sample:
        base, k = requests[o.index].images[i]
        items.append({"jpeg": base64.b64encode(traffic.variant(base, k)).decode(),
                      "served": o.answers[i]})
    limit_s = min(named(cell.config).limit_s, RUN_LIMIT_S - window_s)
    return check_child(cell.config, seed, items, control, limit_s)


def reduce_trace(trace_dir: Path) -> dict | None:
    proc = subprocess.run([sys.executable, str(BENCH / "xplane.py"), str(trace_dir)], cwd=ROOT,
                          env=child_env("cpu"), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=200.0)
    if proc.returncode != 0:
        raise RuntimeError(f"xplane.py exited {proc.returncode}:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


class RecordingSource:
    """A ``traffic.Source`` that remembers what it dealt, by index."""

    def __init__(self, source: traffic.Source):
        self.source = source
        self.requests: dict[int, traffic.Request] = {}

    def take(self) -> traffic.Request:
        req = self.source.take()
        self.requests[req.index] = req
        return req


def measure(server: ServerChild, mix: traffic.Mix, source, seed: int, seconds: float,
            senders: int, model: dict, trace_dir: Path | None = None) -> SimpleNamespace:
    """The window: /stats, the load, every answer awaited, /stats again."""
    due = None
    if mix.loop == "open":
        due = [float(t) for t in traffic.schedule(mix.rate_per_s, seconds, seed)]
    before = server.get("/stats")
    trace = None
    if trace_dir is not None:
        trace = (min(seconds * 0.4, max(0.0, seconds - TRACE_MS / 1e3 - 1.0)),
                 f"/debug/trace?ms={TRACE_MS}&dir={trace_dir}")
    result = loadgen.run("127.0.0.1", server.port, "/predict", source,
                         senders=mix.clients if mix.loop == "closed" else senders,
                         seconds=seconds, timeout_s=mix.timeout_s, due=due, trace=trace)
    after = server.get("/stats")
    if trace is not None and result.trace_status != 200:
        raise RuntimeError(f"POST {trace[1]} ended with {result.trace_status!r}, not 200: no trace to read")
    for o in result.outcomes:
        judge(o, model)
    return SimpleNamespace(before=before, after=after, result=result, outcomes=result.outcomes)


def end_to_end(ctx) -> dict:
    """The cell's end-to-end numbers from the generator's own clock."""
    out = {"setup_s": ctx.setup_s}
    ok = [o for o in ctx.outcomes if o.answers is not None]
    if ctx.mix.loop == "closed":
        # All the work and all the time: once the window's time is up nothing
        # more is sent, every request in flight is waited for, and the clock
        # is read after the last answer. A count of what happened to end
        # inside the window swings by a whole wave of requests where the
        # clients move together (16 x 8 photos every 2.7 s: PERF.md section 2).
        closed_at = max([ctx.seconds, *(o.done for o in ctx.outcomes)])
        out["images_per_s"] = sum(o.images for o in ok) / closed_at
    else:
        out["p50_ms"] = loadgen.percentile(loadgen.latencies_ms(ctx.outcomes, ctx.mix.timeout_s), 50)
    return out


def boot(cell: Cell, seed: int, *, extra_flags: tuple[str, ...] = (), env: dict | None = None,
         serve_dtype: str | None = None):
    """Weights and traffic from the seed, the server child up and warm.
    The caller stops ``.server``."""
    config = cell.config
    mix = traffic.Mix.load(cell.traffic_path)
    work = work_dir(cell)
    weights = write_weights(config, seed, work / "export")
    corpus_box: dict = {}
    builder = threading.Thread(
        target=lambda: corpus_box.update(corpus=traffic.Corpus(mix, seed)), name="corpus")
    builder.start()
    _, err = weights.communicate(timeout=300)
    if weights.returncode != 0:
        raise RuntimeError(f"{named(config).weights.name} exited {weights.returncode}:\n{err.decode(errors='replace')[-3000:]}")
    flags = [*server_flags(config, work, serve_dtype), *extra_flags]
    server = ServerChild(flags, work / "server.log", env={**child_env(), **(env or {})})
    compile_boot_s, stats_compile_boot = 0.0, None
    try:
        boot_s = server.wait_listening()
        stats_boot = server.get("/stats")
        if (stats_boot.get("aot_cache") or {}).get("misses_total", 0) > 0:
            # This boot compiled, and a process that has compiled keeps the
            # compiler's memory (25 GB of the host's 40 here, PERF.md section
            # 7): beside it the window's slabs and the profiler do not fit.
            # The executables are in the AOT cache now, so the process that
            # is measured is a second one, which only loads them. Once: if
            # that one misses too, the cache is at fault and the run goes on.
            compile_boot_s, stats_compile_boot = boot_s, stats_boot
            server.stop()
            shutil.copy(work / "server.log", work / "server.compile.log")
            server = ServerChild(flags, work / "server.log", env={**child_env(), **(env or {})})
            boot_s = server.wait_listening()
            stats_boot = server.get("/stats")
        builder.join()
        health = server.get("/healthz")
        device = {"platform": health.get("platform"), "kind": health.get("device_kind"),
                  "count": health.get("devices")}
        source = RecordingSource(traffic.Source(corpus_box["corpus"], seed))
        warm_up(server, corpus_box["corpus"], source, config["model"])
    except BaseException:
        server.kill()
        raise
    return SimpleNamespace(server=server, mix=mix, work=work, corpus=corpus_box["corpus"],
                           source=source, device=device, boot_s=boot_s, compile_boot_s=compile_boot_s,
                           stats_boot=stats_boot, stats_compile_boot=stats_compile_boot, model=config["model"])


class WrongDevice(RuntimeError):
    """The server runs on another platform or chip count than the cell asks."""


def check_device(device: dict, cell: Cell, platform: str | None) -> None:
    if platform and (device["platform"] != platform or device["count"] != cell.chips):
        raise WrongDevice(f"the server reports {device}; cell {cell.name} needs {cell.chips} "
                          f"{platform} chip(s)")


def drive(cell: Cell, seed: int, seconds: float, trace: bool, *, require_platform: str | None = "tpu",
          extra_flags: tuple[str, ...] = (), env: dict | None = None) -> SimpleNamespace:
    """Set-up, the window, the drain: everything up to SIGTERM.
    ``require_platform=None`` is for the tests, which rehearse on the CPU."""
    b = boot(cell, seed, extra_flags=extra_flags, env=env)
    try:
        check_device(b.device, cell, require_platform)
        trace_dir = None
        if trace:
            trace_dir = b.work / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
        setup_s = time.monotonic() - T_PROCESS_START
        w = measure(b.server, b.mix, b.source, seed, seconds, int(cell.config["http_workers"]),
                    b.model, trace_dir)
        peak = max((d.get("peak_bytes_in_use", 0) for d in w.after.get("device_memory", [])), default=0)
    finally:
        rc = b.server.stop()
    if rc != 0:
        print(f"run.py: the server's drain ended with {rc}", file=sys.stderr)
    return SimpleNamespace(cell=cell, config=cell.config, mix=b.mix, seconds=seconds, seed=seed,
                           setup_s=setup_s, boot_s=b.boot_s, compile_boot_s=b.compile_boot_s,
                           stats_boot=b.stats_boot, stats_compile_boot=b.stats_compile_boot,
                           before=w.before, after=w.after, outcomes=w.outcomes, result=w.result,
                           device={**b.device, "memory_peak_bytes": int(peak)},
                           requests=b.source.requests, trace_dir=trace_dir, trace=None)


def report(ctx, control: str | None = None) -> dict:
    """The result line: the check over a sample of ``ctx.outcomes``, then
    the cell's metrics (end-to-end, or per-layer where the run was traced)."""
    cell = ctx.cell
    sample = draw_sample(ctx.outcomes, ctx.requests, ctx.seed, named(cell.config).sample_images)
    t_check = time.monotonic()
    check = (run_check(cell, ctx.seed, sample, ctx.requests, control, ctx.seconds) if sample
             else {"correct": False, "compared": {}})
    check_s = time.monotonic() - t_check
    line = {"correct": bool(check["correct"]), "attempted": len(ctx.outcomes),
            "failed": sum(1 for o in ctx.outcomes if o.answers is None),
            "metrics": {}, "device": dict(ctx.device)}
    if ctx.trace_dir is not None:
        ctx.trace = reduce_trace(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        line["device"]["busy_s"] = ctx.trace["busy_s"]
        line["device"]["window_s"] = ctx.trace["window_s"]
        for spec in cell.per_layer:
            read, args = load_reader(spec["name"])
            value = read(ctx, **args)
            if value is not None:
                line["metrics"][spec["name"]] = {"value": value, "unit": spec["unit"]}
        line["breakdown"] = {"device_ops": ctx.trace["device_ops"], "idle_gaps": ctx.trace["idle_gaps"]}
    else:
        values = end_to_end(ctx)
        for spec in cell.end_to_end:
            line["metrics"][spec["name"]] = {"value": values[spec["name"]], "unit": spec["unit"]}
    line["check_s"] = check_s       # the reference's own time: after the window, in no metric
    line["non_200"] = failure_log(ctx.outcomes)[:5]
    line["compared"] = check["compared"]
    return line


def failure_log(outcomes) -> list[dict]:
    """Every operation that did not end in a correct 200, for the record."""
    return [{"index": o.index, "due_s": o.due, "sent_s": round(o.sent, 4), "done_s": round(o.done, 4),
             "status": o.status, "reason": o.shed_reason(), "error": o.error,
             "conn_age_s": round(o.conn_age_s, 3), "conn_requests": o.conn_requests,
             "trace_id": o.trace_id, "body": o.body[:200].decode(errors="replace")}
            for o in outcomes if o.answers is None]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not (ROOT / "server.py").is_file():
        print("run.py: no server.py beside the benchmark: nothing to measure", file=sys.stderr)
        return 4
    cell = load_cell(args.workload)
    try:
        line = report(drive(cell, args.seed, args.seconds, bool(args.trace)))
    except WrongDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    for name, c in line["compared"].items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
