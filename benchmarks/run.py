"""Benchmark orchestrator — one module per paper table/figure.

Prints ``benchmark,key=value,...`` lines plus a final CHECKS summary
validating the paper's claims. Roofline extraction (which needs the
512-device placeholder env) lives in benchmarks/bench_roofline.py as its own
entry point.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick | --smoke]

``--smoke`` is the CI lane: tiny shapes, only the fast hardware-claim benches
(bandwidth model + fused double sampling), and a ``BENCH_<name>.json`` file
per bench (uploaded as a workflow artifact so the perf trajectory accumulates
across PRs). ``--json-dir`` writes the same JSON files for any run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

# allow `python benchmarks/run.py` (script mode) as well as `-m benchmarks.run`:
# the bench modules are imported as `benchmarks.*`, so the repo root must be
# importable regardless of how this file was invoked
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

BENCHES = [
    ("fig4_linear_convergence", "benchmarks.bench_linear_convergence"),
    ("fig5_bandwidth_model", "benchmarks.bench_bandwidth_model"),
    ("fig6_minibatch", "benchmarks.bench_minibatch"),
    ("fig7a_fig8_optimal_quant", "benchmarks.bench_optimal_quant"),
    ("fig7b_dl_quant", "benchmarks.bench_dl_quant"),
    ("fig9_chebyshev_negative", "benchmarks.bench_chebyshev"),
    ("fig12_refetch", "benchmarks.bench_refetch"),
    ("ds_fused", "benchmarks.bench_ds_fused"),
    ("qmm", "benchmarks.bench_qmm"),
    ("bitplane", "benchmarks.bench_bitplane"),
    ("serve_engine", "benchmarks.bench_serve_engine"),
    ("spec_decode", "benchmarks.bench_spec_decode"),
    ("train_step", "benchmarks.bench_train_step"),
    ("chaos", "benchmarks.bench_chaos"),
]

# fast, shape-independent claims only — what CI runs on every PR
SMOKE_BENCHES = {"fig5_bandwidth_model", "ds_fused", "qmm", "bitplane",
                 "serve_engine", "spec_decode", "train_step", "chaos"}

# committed per-bench baselines the --smoke regression gate compares against
BASELINE_DIR = os.path.join(_REPO_ROOT, "benchmarks", "baselines")


def regression_gate(payloads: dict) -> list[str]:
    """Compare this run against the committed baselines: every HBM-byte /
    parity CHECK that held in the baseline must still hold, the trainer step
    wall-clock (normalized by the in-run fp32-matmul calibration, so machine
    speed cancels) must not regress more than ``ZIPML_BENCH_WALLCLOCK_TOL``
    (default 10%), and every row's fraction-of-roofline must not collapse
    below ``baseline × (1 - ZIPML_BENCH_ROOFLINE_TOL)`` (default 0.75 — the
    fraction is machine-portable but interpret-mode-noisy, so this catches
    collapses, not drift). A wall-clock skip on a noisy machine is recorded
    as ``payload["gate_skipped"]`` (audited from the uploaded JSON artifact)
    and emitted as a ``::warning::`` GitHub annotation. Returns failure
    strings; mutates ``payloads`` only to add ``gate_skipped``.
    """
    fails = []
    wall_tol = float(os.environ.get("ZIPML_BENCH_WALLCLOCK_TOL", "0.10"))
    roof_tol = float(os.environ.get("ZIPML_BENCH_ROOFLINE_TOL", "0.75"))
    for name, payload in payloads.items():
        path = os.path.join(BASELINE_DIR, f"BENCH_{name}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            base = json.load(f)

        def checks(rows):
            out = {}
            for i, row in enumerate(rows):
                tag = row.get("case", str(i))
                for k, v in row.items():
                    if isinstance(v, bool):
                        out[f"{tag}/{k}"] = v
            return out

        now_checks = checks(payload["rows"])
        for key, held in checks(base["rows"]).items():
            if not held:
                continue
            if key not in now_checks:
                # a renamed/dropped CHECK must be an explicit baseline
                # update, never a silent gate bypass
                fails.append(
                    f"{name}: baseline CHECK {key} missing from this run — "
                    "regenerate benchmarks/baselines/ if intentional")
            elif now_checks[key] is False:
                fails.append(f"{name}: CHECK {key} regressed (was PASS)")
        # normalized wall-clock: rows carrying both step_ms and calib_ms
        # (min step time over the run — the stable steady-state estimator)
        base_rows = {r.get("case"): r for r in base["rows"]}
        now_cases = {r.get("case") for r in payload["rows"]}
        for case, b in base_rows.items():
            if ("calib_ms" in b or "roofline_fraction" in b) \
                    and case not in now_cases:
                fails.append(
                    f"{name}: baseline gated case {case!r} missing "
                    "from this run — regenerate benchmarks/baselines/ if "
                    "intentional")
        # fraction-of-roofline: machine-portable (achieved GB/s over THIS
        # machine's measured peak), so it gates even where wall-clock can't
        for row in payload["rows"]:
            b = base_rows.get(row.get("case"))
            if not b or "roofline_fraction" not in b:
                continue
            if "roofline_fraction" not in row:
                fails.append(
                    f"{name}/{row['case']}: baseline carries "
                    "roofline_fraction but this run doesn't — regenerate "
                    "benchmarks/baselines/ if intentional")
                continue
            floor = b["roofline_fraction"] * (1 - roof_tol)
            if row["roofline_fraction"] < floor:
                fails.append(
                    f"{name}/{row['case']}: roofline_fraction "
                    f"{row['roofline_fraction']:.4f} < baseline "
                    f"{b['roofline_fraction']:.4f} × (1 - {roof_tol:.0%})")
        for row in payload["rows"]:
            b = base_rows.get(row.get("case"))
            if not b or "step_ms" not in row or "calib_ms" not in row:
                continue
            if not b.get("calib_ms") or not row["calib_ms"]:
                continue
            end = row.get("calib_ms_end", row["calib_ms"])
            jitter = abs(end / row["calib_ms"] - 1)
            if jitter > 0.15:
                reason = (f"calibration jitter {jitter:.0%} > 15% — "
                          "wall-clock gate skipped; byte CHECKs and "
                          "roofline fractions still gate")
                payload.setdefault("gate_skipped", {})[row["case"]] = reason
                print(f"{name}/{row['case']}: {reason}")
                print("::warning title=bench wall-clock gate skipped::"
                      f"{name}/{row['case']}: {reason}")
                continue
            calib = min(row["calib_ms"], end)
            now_norm = row.get("step_ms_min", row["step_ms"]) / calib
            b_end = b.get("calib_ms_end", b["calib_ms"])
            base_norm = b.get("step_ms_min", b["step_ms"]) / \
                min(b["calib_ms"], b_end)
            if now_norm > base_norm * (1 + wall_tol):
                fails.append(
                    f"{name}/{row['case']}: normalized step wall-clock "
                    f"{now_norm:.1f} > baseline {base_norm:.1f} "
                    f"(+{wall_tol:.0%} allowed)")
    return fails


def calibration_jitter(payloads: dict) -> float | None:
    """Worst calibration jitter across wall-clock rows (None: no such row).
    The same |calib_ms_end / calib_ms - 1| the gate's noisy-machine skip
    keys off — --update-baselines refuses above 15%, because a baseline
    minted under transient load would make every future healthy run look
    like a regression (or mask a real one)."""
    worst = None
    for payload in payloads.values():
        for row in payload["rows"]:
            if "calib_ms" not in row or not row["calib_ms"]:
                continue
            end = row.get("calib_ms_end", row["calib_ms"])
            j = abs(end / row["calib_ms"] - 1)
            worst = j if worst is None else max(worst, j)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced datasets/epochs (CI mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, fast benches only, write BENCH_*.json")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<name>.json per bench here "
                         "(default: cwd when --smoke)")
    ap.add_argument("--update-baselines", action="store_true",
                    help="re-run the smoke benches and regenerate "
                         "benchmarks/baselines/BENCH_*.json in one command; "
                         "refuses on a machine the jitter guard flags noisy")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache

    print(f"[bench] compile cache: {compile_cache.enable()}")
    smoke = args.smoke or args.update_baselines
    quick = args.quick or smoke
    json_dir = args.json_dir or ("." if args.smoke else None)

    all_checks = []
    payloads = {}
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        if smoke and not args.only and name not in SMOKE_BENCHES:
            continue
        t0 = time.time()
        mod = importlib.import_module(module)
        rows = mod.run(quick=quick)
        dt = time.time() - t0
        for row in rows:
            line = ",".join(f"{k}={v}" for k, v in row.items())
            print(f"{name},{line}")
            for k, v in row.items():
                if isinstance(v, (bool, np.bool_)):
                    all_checks.append((f"{name}/{k}", bool(v)))
        print(f"{name},_timing,seconds={dt:.1f}")
        payloads[name] = {
            "bench": name, "seconds": round(dt, 2), "quick": quick,
            "rows": [{k: (bool(v) if isinstance(v, np.bool_) else v)
                      for k, v in row.items()} for row in rows]}
    print()
    n_pass = sum(1 for _, v in all_checks if v)

    if args.update_baselines:
        for label, v in all_checks:
            print(f"CHECK {'PASS' if v else 'FAIL'}: {label}")
        if n_pass != len(all_checks):
            print("\nrefusing to update baselines: "
                  f"{len(all_checks) - n_pass} CHECK(s) failing — a baseline "
                  "must only ever encode passing claims")
            return 1
        jitter = calibration_jitter(payloads)
        if jitter is not None and jitter > 0.15:
            print(f"\nrefusing to update baselines: calibration jitter "
                  f"{jitter:.0%} > 15% — this machine is too noisy for a "
                  "trustworthy wall-clock baseline; re-run when idle")
            return 2
        os.makedirs(BASELINE_DIR, exist_ok=True)
        for name, payload in payloads.items():
            path = os.path.join(BASELINE_DIR, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2, default=str)
            print(f"baseline updated: {path}")
        print(f"\n{n_pass}/{len(all_checks)} checks passed; "
              f"{len(payloads)} baselines written (jitter "
              f"{0.0 if jitter is None else jitter:.1%})")
        return 0

    # gate BEFORE writing the JSON artifacts so gate_skipped annotations
    # (noisy-machine wall-clock skips) are auditable from the uploaded files
    gate_fails = []
    if args.smoke:
        gate_fails = regression_gate(payloads)
        for msg in gate_fails:
            print(f"REGRESSION FAIL: {msg}")
        if not gate_fails and os.path.isdir(BASELINE_DIR):
            print("regression gate: no regressions vs committed baselines")
    if json_dir:
        os.makedirs(json_dir, exist_ok=True)
        for name, payload in payloads.items():
            path = os.path.join(json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2, default=str)
            print(f"{name},_json,path={path}")
    for label, v in all_checks:
        print(f"CHECK {'PASS' if v else 'FAIL'}: {label}")
    print(f"\n{n_pass}/{len(all_checks)} paper-claim checks passed")
    return 0 if n_pass == len(all_checks) and not gate_fails else 1


if __name__ == "__main__":
    sys.exit(main())
