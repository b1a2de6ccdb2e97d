#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

The cell is an entry of BENCHMARK.json's ``workloads``; everything else is
found by name from it (see core.py). ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` traces the window on the device and prints
its per-layer metrics, ``device.busy_s``/``window_s`` and a ``breakdown``.
The last line of standard output is the JSON result. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import core  # noqa: E402

TRACE_DIR = core.HERE / ".traces"


class Window:
    def __init__(self):
        self.t0 = self.t1 = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Context:
    """What a driver gets: the cell's files, the seed, the devices, the
    benchmark's host spans, and the measured window."""

    def __init__(self, args, spec, devices):
        self.seed = args.seed
        self.trace = bool(args.trace)
        self.spec = spec
        self.devices = devices
        self.t_start = T_START
        self.spans = core.Spans()
        self.window_seconds = float(args.seconds)
        self.win = Window()

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced on the device with ``--trace 1``."""
        import jax

        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with jax.profiler.TraceAnnotation("window"):
                self.win.t0 = time.perf_counter()
                yield self.win
                self.win.t1 = time.perf_counter()
        finally:
            if self.trace:
                jax.profiler.stop_trace()


def per_layer(ctx, out: dict) -> tuple[dict, dict, dict]:
    import tracing

    red = tracing.reduce_dir(TRACE_DIR, span_names=set(
        n for n, _, _ in ctx.spans.items))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    record = dict(out["record"])
    record.update(trace=red,
                  peaks=core.peaks_for(ctx.devices[0].device_kind),
                  config=ctx.spec["config"],
                  workload=ctx.spec["workload"], mix=ctx.spec["mix"],
                  spans=ctx.spans.items, window=(ctx.win.t0, ctx.win.t1))
    metrics = {}
    for m in ctx.spec["per_layer"]:
        reader = core.load_module(core.HERE / "metrics" / f"{m['name']}.py")
        v = reader.read(record)
        if v is None:
            # every per-layer metric lists the cells it reads something in
            raise core.BenchError(f"{m['name']} found nothing to read in "
                                  "this cell's traced run")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
    return metrics, device, red["breakdown"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = core.cell_spec(args.workload)
        devices = core.require_chips(spec["cell"]["chips"])
        core.enable_compile_cache()
        core.import_program()
        driver = core.load_module(
            core.HERE / "drivers" / f"{spec['workload']['driver']}.py")
        ctx = Context(args, spec, devices)
        out = driver.run(ctx)
        if args.trace:
            metrics, extra, breakdown = per_layer(ctx, out)
        else:
            values = dict(out["e2e"], setup_s=out["setup_s"])
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"]}
            extra, breakdown = {}, None
    except core.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    checks = out["checks"]
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks),
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics,
        "device": {**core.device_info(devices, out["memory_peak_bytes"]),
                   **extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    core.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
