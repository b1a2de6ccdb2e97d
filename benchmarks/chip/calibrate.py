#!/usr/bin/env python3
"""Readings for a cell's correctness limits, on the chip, in one process:
the numbers the comparison reads for the program on many seeds, and for the
control or a planted fault on some.

  python benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \\
      --seconds 30 [--control] [--fault NAME] [--set key=value ...] \\
      [--out FILE]

``--control`` reads the control beside the program: for serving, the gap of
the token that the reference computed with 4-bit weights and KV puts first,
at the same prompts and tokens; for training, the program run at the
workload's ``control`` bits (its own lower rung) against the reference at
the stated bits. ``--fault`` plants one of ``faults.py``'s faults. One JSON
line per seed goes to standard output and, with ``--out``, to that file.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import core  # noqa: E402
import faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--out")
    args = ap.parse_args()
    spec = core.cell_spec(args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        where = "mix" if k in spec["mix"] else "workload"
        spec[where][k] = json.loads(v)
    devices = core.require_chips(spec["cell"]["chips"])
    core.enable_compile_cache()
    core.import_program()
    import run as harness

    driver = core.load_module(
        core.HERE / "drivers" / f"{spec['workload']['driver']}.py")
    table = faults.SERVE if spec["mix"]["kind"] == "requests" else faults.TRAIN
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = harness.Context(ns, spec, devices)
        ctx.t_start = time.perf_counter()
        ctx.control = args.control
        ctx.fault = table[args.fault] if args.fault else None
        r = driver.run(ctx)
        line = {"workload": args.workload, "seed": seed,
                "set": args.set, "fault": args.fault,
                "checks": {c["name"]: c["value"] for c in r["checks"]},
                "readings": r.get("readings"), "e2e": r["e2e"],
                "setup_s": r["setup_s"], "window_s": r["window_s"],
                "attempted": r["attempted"], "failed": r["failed"],
                "memory_peak_bytes": r["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
