#!/usr/bin/env python3
"""Run one cell once with its window traced, and read what the program's own
spans, program names, scopes and per-request token times show.

  python benchmarks/chip/trace_programs.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> [--out FILE]

The cell runs as ``run.py --trace 1`` runs it; the trace is then reduced
twice: by ``tracing.py`` (the harness's reduction) and by ``programs.py``
(programs, scopes, program spans, idle time by the innermost span). The
record the metric readers take gains ``trace.programs``, ``trace.scopes``,
``trace.program_spans`` and, when serving, ``serve.token_gaps`` (the gaps
between successive tokens of the requests finished in the window, both
tokens inside it). The last line of standard output (and a line of
``--out``) is one JSON object: ``e2e`` (with the profiler on), ``metrics``
(the cell's per-layer metrics and those of ``PROGRAM_METRICS`` that read
something), ``device``, ``breakdown`` (tracing.py's), ``programs``
(programs.py's reduction), ``token_gaps`` (their count and median) and
``checks``. ``run.py`` does not read the program's spans or token times;
this script does, until the harness does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import numpy as np  # noqa: E402

import core  # noqa: E402

PROGRAM_METRICS = ("decode_device_ms.serve", "chunk_device_ms.serve",
                   "sched_ms.serve", "itl_p95_ms.serve",
                   "grad_channel_ms.train")


def keep_finished(eng, sink: list):
    """The engine, with every step's finished requests also put in
    ``sink``."""
    step = eng.step

    def kept():
        out = step()
        sink.extend(out)
        return out

    eng.step = kept
    return eng


def token_gaps(finished, t0: float, t1: float) -> list[float]:
    """Gaps between successive tokens of the requests that finished in
    [t0, t1], both tokens committed inside it."""
    gaps = []
    for f in finished:
        t = f.token_times
        if t.size and t0 <= t[-1] <= t1:
            gaps += [float(b - a) for a, b in zip(t[:-1], t[1:]) if a >= t0]
    return gaps


def program_record(record: dict, red: dict, pred: dict,
                   gaps: list[float] | None) -> dict:
    """The run's record with what the program metrics read added."""
    rec = dict(record)
    rec["trace"] = {**red, **{k: pred[k] for k in
                              ("programs", "scopes", "program_spans")}}
    if gaps is not None:
        rec["serve"] = dict(rec["serve"], token_gaps=gaps)
    return rec


def read_metrics(names, rec: dict) -> dict:
    out = {}
    for name in names:
        v = core.load_module(core.HERE / "metrics" / f"{name}.py").read(rec)
        if v is not None:
            out[name] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    spec = core.cell_spec(args.workload)
    devices = core.require_chips(spec["cell"]["chips"])
    core.enable_compile_cache()
    core.import_program()
    import programs
    import run as harness
    import tracing

    driver = core.load_module(
        core.HERE / "drivers" / f"{spec['workload']['driver']}.py")
    ctx = harness.Context(argparse.Namespace(
        seed=args.seed, seconds=args.seconds, trace=1), spec, devices)
    ctx.t_start = T_START
    serving = spec["mix"]["kind"] == "requests"
    finished: list = []
    if serving:
        ctx.fault = lambda eng: keep_finished(eng, finished)
    out = driver.run(ctx)
    names = {n for n, _, _ in ctx.spans.items}
    red = tracing.reduce_dir(harness.TRACE_DIR, names)
    pred = programs.reduce_dir(harness.TRACE_DIR, names)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    gaps = token_gaps(finished, ctx.win.t0, ctx.win.t1) if serving else None
    rec = program_record(out["record"], red, pred, gaps)
    rec.update(peaks=core.peaks_for(devices[0].device_kind),
               config=spec["config"], workload=spec["workload"],
               mix=spec["mix"], spans=ctx.spans.items,
               window=(ctx.win.t0, ctx.win.t1))
    metrics = read_metrics([m["name"] for m in spec["per_layer"]]
                           + list(PROGRAM_METRICS), rec)
    result = {
        "workload": args.workload, "seed": args.seed,
        "setup_s": out["setup_s"], "e2e": out["e2e"], "metrics": metrics,
        "device": {"busy_s": red["busy_s"], "window_s": red["window_s"],
                   **core.device_info(devices, out["memory_peak_bytes"])},
        "breakdown": red["breakdown"], "programs": pred,
        "token_gaps": None if not gaps else {
            "n": len(gaps), "p50_ms": 1e3 * float(np.median(gaps))},
        "checks": {c["name"]: [c["value"], c["limit"]]
                   for c in out["checks"]},
    }
    line = json.dumps(result)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
