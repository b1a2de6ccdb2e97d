"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh, prove it fits (memory_analysis), and extract the roofline
terms (cost_analysis + HLO collective parsing).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out dryrun.json

The XLA_FLAGS lines below MUST run before any other import touches jax — jax
locks the device count on first backend init. Smoke tests and benches never
import this module, so they see the single real CPU device.
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # noqa: E402
# ^ before ANY jax-touching import — jax locks device count on first init.

import argparse
import dataclasses
import json
import re
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.launch import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import input_specs, make_prefill_step, make_serve_step
from repro.models import transformer as T
from repro.optim import adamw
from repro.quant import PrecisionPlan

# --- TPU v5e hardware model (roofline constants) ---------------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (≈ per-direction)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 0.5, "u4": 0.5,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> float:
    """'bf16[16,512,4096]{...}' → bytes. Tuples handled by the caller."""
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0.0
    dt, dims = m.groups()
    nb = _DTYPE_BYTES.get(dt)
    if nb is None:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


def parse_collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by each collective family.

    Accounting (ring algorithms, bytes on the wire per participating device):
      all-reduce: 2× payload (reduce-scatter + all-gather phases)
      all-gather: output bytes (each device receives the full gathered tensor)
      reduce-scatter: input bytes
      all-to-all / collective-permute: 1× payload
    '-start' variants counted, '-done' skipped (same transfer).
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^ ]*)\s+"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start|-done)?\(", line)
        if not m:
            continue
        shape_part, op, variant = m.groups()
        if variant == "-done":
            continue
        if shape_part.startswith("("):
            shapes = re.findall(r"[a-z0-9]+\[[\d,]*\]", shape_part)
            nbytes = sum(_shape_bytes(s) for s in shapes)
        else:
            nbytes = _shape_bytes(shape_part)
        if op == "all-reduce":
            nbytes *= 2.0
        out[op] += nbytes
    return out


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    error: str = ""
    compile_s: float = 0.0
    per_device_bytes: float = 0.0       # peak HBM (args+outs+temps, aliased)
    arg_bytes: float = 0.0
    temp_bytes: float = 0.0
    out_bytes: float = 0.0
    flops_per_device: float = 0.0
    hbm_bytes_accessed: float = 0.0     # per device
    collective_bytes: float = 0.0       # per device, weighted
    collective_breakdown: dict = dataclasses.field(default_factory=dict)
    compute_term_s: float = 0.0
    memory_term_s: float = 0.0
    collective_term_s: float = 0.0
    model_flops: float = 0.0            # 6·N·D (train) or 2·N·B (decode), global
    useful_ratio: float = 0.0
    n_devices: int = 0

    def dominant(self) -> str:
        terms = {"compute": self.compute_term_s, "memory": self.memory_term_s,
                 "collective": self.collective_term_s}
        return max(terms, key=terms.get)


def model_flops_for(cfg: T.ModelConfig, shape: configs.ShapeSpec) -> float:
    """Analytic useful FLOPs per step: 6·N_active·D for training, 2·N_active·B
    tokens for decode, 2·N_active·D for prefill (forward only)."""
    n_act = cfg.n_active_params()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        return 2.0 * n_act * tokens
    return 2.0 * n_act * shape.global_batch  # decode: one token per sequence


# per-arch gradient-accumulation for train_4k: microbatching halves activation
# + MoE dispatch memory where one pass would exceed the 16 GB v5e budget
TRAIN_ACCUM = {"mixtral-8x7b": 8, "granite-moe-3b-a800m": 2, "gemma-2b": 2,
               "llama-3.2-vision-11b": 2, "gemma-7b": 2, "qwen2.5-14b": 2,
               "zamba2-2.7b": 2}


def build_step(cfg: T.ModelConfig, shape: configs.ShapeSpec, mesh,
               opt_cfg: "adamw.AdamWConfig | None" = None):
    """Returns (jitted_fn, ordered_args list of spec-trees).

    Train cells compile the channel-composed TrainState step, so the
    memory_analysis prices the *whole* run state: quantized optimizer
    moments at their stored width (int8 when ``opt_cfg.moment_bits=8``, not
    the fp32 the old opt_state spec assumed) and the grad channel's fp32
    error-feedback residual when the plan sets ``grad_bits``.
    """
    from repro.train.step import make_step

    opt_cfg = opt_cfg if opt_cfg is not None else adamw.AdamWConfig()
    specs = input_specs(cfg, shape, opt_cfg=opt_cfg)
    p_sh = sh.make_param_shardings(mesh, specs["params"])
    if shape.kind == "train":
        fn = make_step(cfg, opt_cfg,
                       accum_steps=TRAIN_ACCUM.get(cfg.name, 1))
        st_sh = sh.make_state_shardings(mesh, specs["state"])
        b_sh = sh.train_batch_shardings(mesh, specs["batch"])
        jfn = jax.jit(fn, in_shardings=(st_sh, b_sh), donate_argnums=(0,))
        args = (specs["state"], specs["batch"])
    elif shape.kind == "prefill":
        fn = make_prefill_step(cfg)
        b_sh = sh.train_batch_shardings(mesh, specs["batch"])
        jfn = jax.jit(fn, in_shardings=(p_sh, b_sh))
        args = (specs["params"], specs["batch"])
    else:
        fn = make_serve_step(cfg)
        c_sh = sh.cache_shardings(mesh, specs["decode_state"], shape.global_batch)
        t_sh = sh.train_batch_shardings(mesh, {"t": specs["tokens"]})["t"]
        jfn = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh), donate_argnums=(1,))
        args = (specs["params"], specs["decode_state"], specs["tokens"])
    return jfn, args


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             precision: "PrecisionPlan | None" = None,
             opt_cfg: "adamw.AdamWConfig | None" = None,
             verbose: bool = True) -> CellResult:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    shape = configs.SHAPES[shape_name]
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    # Full-program compile runs in scan mode (fast, buffer-reusing — its
    # memory_analysis is the true peak). Exact FLOP/byte/collective totals come
    # from the compositional per-piece pass in benchmarks/bench_roofline.py,
    # because XLA's cost analysis counts while-loop bodies once.
    overrides = {"dp_axes": dp_axes}
    if precision is not None:
        overrides["precision"] = precision
    cfg = configs.get_config(arch, **overrides)
    res = CellResult(arch=arch, shape=shape_name, mesh=mesh_name, ok=False,
                     n_devices=int(np.prod(mesh.devices.shape)))
    t0 = time.time()
    try:
        jfn, args = build_step(cfg, shape, mesh, opt_cfg=opt_cfg)
        with jax.sharding.set_mesh(mesh):
            lowered = jfn.lower(*args)
            compiled = lowered.compile()
        res.compile_s = time.time() - t0
        ma = compiled.memory_analysis()
        if ma is not None:
            res.arg_bytes = float(getattr(ma, "argument_size_in_bytes", 0))
            res.out_bytes = float(getattr(ma, "output_size_in_bytes", 0))
            res.temp_bytes = float(getattr(ma, "temp_size_in_bytes", 0))
            alias = float(getattr(ma, "alias_size_in_bytes", 0))
            res.per_device_bytes = res.arg_bytes + res.out_bytes + res.temp_bytes - alias
        ca = compiled.cost_analysis()
        if ca:
            res.flops_per_device = float(ca.get("flops", 0.0))
            res.hbm_bytes_accessed = float(ca.get("bytes accessed", 0.0))
        coll = parse_collective_bytes(compiled.as_text())
        res.collective_breakdown = coll
        res.collective_bytes = float(sum(coll.values()))
        res.compute_term_s = res.flops_per_device / PEAK_FLOPS
        res.memory_term_s = res.hbm_bytes_accessed / HBM_BW
        res.collective_term_s = res.collective_bytes / ICI_BW
        res.model_flops = model_flops_for(cfg, shape)
        total_flops = res.flops_per_device * res.n_devices
        res.useful_ratio = res.model_flops / total_flops if total_flops else 0.0
        res.ok = True
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] compile {res.compile_s:.1f}s")
            print(f"  memory/device: args {res.arg_bytes/2**30:.2f} GiB, "
                  f"temps {res.temp_bytes/2**30:.2f} GiB, outs {res.out_bytes/2**30:.2f} GiB")
            print(f"  flops/device {res.flops_per_device:.3e}, hbm bytes {res.hbm_bytes_accessed:.3e}, "
                  f"coll bytes {res.collective_bytes:.3e}")
            print(f"  terms: compute {res.compute_term_s*1e3:.2f} ms | "
                  f"memory {res.memory_term_s*1e3:.2f} ms | "
                  f"collective {res.collective_term_s*1e3:.2f} ms → {res.dominant()}-bound")
            print(f"  MODEL_FLOPS/HLO_FLOPS = {res.useful_ratio:.3f}")
    except Exception as e:  # noqa: BLE001 — report, don't crash the matrix
        res.error = f"{type(e).__name__}: {e}"
        res.compile_s = time.time() - t0
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] FAILED after "
                  f"{res.compile_s:.1f}s: {res.error[:500]}")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--kv-bits", type=int, default=0)
    ap.add_argument("--weight-bits", type=int, default=0)
    ap.add_argument("--grad-bits", type=int, default=0)
    ap.add_argument("--moment-bits", type=int, default=0,
                    help="optimizer moment storage width (train cells price "
                         "int8 moments instead of fp32)")
    ap.add_argument("--weight-storage", default="int",
                    choices=("int", "ship", "fake"))
    args = ap.parse_args(argv)

    precision = None
    if args.kv_bits or args.weight_bits or args.grad_bits:
        precision = PrecisionPlan(model_bits=args.weight_bits,
                                  model_storage=args.weight_storage,
                                  kv_bits=args.kv_bits, grad_bits=args.grad_bits)
    opt_cfg = adamw.AdamWConfig(moment_bits=args.moment_bits) \
        if args.moment_bits else None

    if args.all:
        cells = configs.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch, shape in cells:
        for mp in meshes:
            results.append(dataclasses.asdict(run_cell(
                arch, shape, mp, precision=precision, opt_cfg=opt_cfg)))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells compiled OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
