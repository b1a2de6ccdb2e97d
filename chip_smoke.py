#!/usr/bin/env python3
"""Bring-up smoke test on a TPU: the system's main paths, end to end,
through the entry points a user calls, at published model widths.

  python chip_smoke.py             # one chip: serve, train, double sampling
  python chip_smoke.py --chips 4   # four chips: the multi-replica phase only

Phases (one chip):

* serve  — ``launch.serve.serve_engine`` on gemma-2b at its published
  ``CONFIG`` (random weights from a seed), 8 requests of 16–128 prompt
  tokens and up to 32 new tokens, at each precision rung: bf16, int8 and
  packed-int4 weights, KV at the same width. Every request must finish
  (none quarantined as ``nan``), and on a live batch the first decode steps'
  logits under the Pallas kernels must match the ``ref`` backend on the same
  chip within ``SERVE_LOGITS_RTOL``.
* train  — ``repro.train.Trainer`` on musicgen-medium at its published
  widths, depth cut 48 → 8 layers so the fp32 master and optimizer state fit
  one chip: 5 steps at bf16 and at grad_bits=8 + moment_bits=8. The loss is
  finite at every step; the first-step loss matches the ``ref`` backend
  within ``TRAIN_LOSS_RTOL``.
* ds     — ``core.linear.train_linear`` with double sampling (paper §2.2,
  through the ``ds_quant`` and ``qmv`` kernels); the final loss must lie
  within ``DS_LOSS_BAND`` of the ``ref`` backend's.

Phase (``--chips 4``): gemma-2b at int8 weights and KV served by a
``ReplicaSet`` of four replicas, one per chip, against the same trace on one
replica: each replica's weights and KV pool on its own chip, every replica
serving, token-identical outputs, no replica deaths or step failures.

Every phase prints one line naming the Pallas kernels its compiled programs
contain (read from the programs JAX hands the compiler); a phase that runs on
the ``pallas`` backend and contains none fails. The script exits non-zero if
any phase fails, or at once if JAX finds no TPU or Pallas is asked to
interpret; its last line is then no result. On success the last line is
``{"ok": true, "device": {...}}`` with the device as JAX reports it.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import json
import os
import pathlib
import re
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

SERVE_ARCH = "gemma-2b"
SERVE_RUNGS = ((0, 0), (8, 8), (4, 4))          # (weight bits, kv bits)
SERVE_REQUESTS, SERVE_MAX_NEW = 8, 32
SERVE_PROMPT = (16, 128)
SERVE_PAGE, SERVE_CHUNK_PAGES = 16, 8            # one 128-token prefill chunk
SERVE_PROBE_STEPS = 3
# pallas vs ref decode logits on one state, as RMS(Δ) / RMS(ref). The kernels
# keep f32 where ref rounds to bf16 (decoded weights, attention
# probabilities), and the bf16 residual stream of 18 random-weight layers
# amplifies that: on a v5e the bf16 rung, where only the attention kernel
# differs, sits at 0.07, int8 at 0.09 and int4 at 0.11. A broken kernel is
# off by ~1
SERVE_LOGITS_RTOL = 0.25

TRAIN_ARCH, TRAIN_LAYERS = "musicgen-medium", 8  # published widths, 48 → 8
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 8, 512
# the first step's loss precedes any update, so the backends differ only by
# how XLA fuses the same forward
TRAIN_LOSS_RTOL = 1e-3

DS_DATASET, DS_TRAIN, DS_TEST = "synthetic1000", 16384, 1024
DS_EPOCHS, DS_BATCH, DS_BITS, DS_LR = 4, 256, 5, 0.05
# pallas draws both samples from one uint32 plane, ref from two keys: the
# final losses agree in distribution, not bit for bit
DS_LOSS_BAND = 0.1                               # |Δ| / ref loss

REPLICAS = 4
REPLICA_REQUESTS, REPLICA_SLOTS = 16, 4


class PhaseError(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseError(msg)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


# --------------------------------------------------------------- helpers --
@contextlib.contextmanager
def kernels_compiled(found: collections.Counter):
    """Count the Pallas kernels in every program compiled inside the block:
    JAX dumps each module it hands the compiler (also on a persistent-cache
    hit), and a Pallas TPU kernel is a ``tpu_custom_call`` there, named by
    its ``kernel_name``."""
    import jax

    d = ROOT / ".smoke_ir"
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir()
    jax.config.update("jax_dump_ir_to", str(d))
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", "")
        for f in d.glob("*.mlir"):
            text = f.read_text()
            if "tpu_custom_call" in text:
                found.update(re.findall(r'kernel_name = "(\w+)"', text))
        shutil.rmtree(d, ignore_errors=True)


def kernel_summary(found: collections.Counter, required: bool) -> str:
    check(found or not required,
          "no Pallas kernel in the compiled programs of a pallas phase")
    return ", ".join(f"{k}×{n}" for k, n in sorted(found.items())) or "none"


def free():
    import jax

    gc.collect()
    jax.clear_caches()


# ----------------------------------------------------------------- serve --
def serve_rung(weight_bits: int, kv_bits: int) -> str:
    import numpy as np

    from repro.kernels import registry
    from repro.launch.serve import make_trace, serve_engine
    from repro.serve import ServeEngine

    kernels = collections.Counter()
    t0 = time.perf_counter()
    with registry.using("pallas"), kernels_compiled(kernels):
        eng, results = serve_engine(
            SERVE_ARCH, reduced=False, n_requests=SERVE_REQUESTS,
            max_new=SERVE_MAX_NEW, min_prompt=SERVE_PROMPT[0],
            max_prompt=SERVE_PROMPT[1], kv_bits=kv_bits,
            weight_bits=weight_bits, max_slots=SERVE_REQUESTS,
            page_size=SERVE_PAGE, chunk_pages=SERVE_CHUNK_PAGES,
            backend="pallas")
    dt = time.perf_counter() - t0
    check(sorted(results) == list(range(SERVE_REQUESTS)),
          f"finished {sorted(results)} of {SERVE_REQUESTS} requests")
    reasons = collections.Counter(f.reason for f in results.values())
    check(not (reasons.keys() & {"nan", "retries"}), f"reasons {dict(reasons)}")
    tokens = sum(f.n_generated for f in results.values())
    vocab = eng.cfg.vocab_size
    check(all(((f.tokens >= 0) & (f.tokens < vocab)).all()
              for f in results.values()), "token id out of the vocabulary")

    # the numerics probe: a fresh engine on the same weights, the same
    # trace; once every request decodes, compare both backends' logits on
    # each of the first steps' states
    probe = ServeEngine(eng.params, eng.cfg, plan=eng.plan,
                        max_slots=SERVE_REQUESTS, page_size=SERVE_PAGE,
                        max_seq_len=eng.max_seq_len, backend="pallas",
                        chunk_pages=SERVE_CHUNK_PAGES)
    del eng
    trace = make_trace(SERVE_REQUESTS, vocab, max_new=SERVE_MAX_NEW,
                       min_prompt=SERVE_PROMPT[0],
                       max_prompt=SERVE_PROMPT[1])
    with registry.using("pallas"):
        for r in trace:     # long enough that all are decoding at once
            probe.submit(dataclasses.replace(r, max_new_tokens=SERVE_MAX_NEW))
        while probe.n_active < SERVE_REQUESTS:
            check(probe.busy, "probe drained before every request decoded")
            probe.step()
        worst, agree = 0.0, []
        for _ in range(SERVE_PROBE_STEPS):      # every slot holds a request
            lp = np.asarray(probe.decode_logits("pallas"), np.float32)
            lr = np.asarray(probe.decode_logits("ref"), np.float32)
            check(np.isfinite(lp).all() and np.isfinite(lr).all(),
                  "non-finite decode logits")
            rms = np.sqrt(np.mean((lp - lr) ** 2) / np.mean(lr ** 2))
            worst = max(worst, float(rms))
            agree.append(float((lp.argmax(-1) == lr.argmax(-1)).mean()))
            probe.step()
    del probe
    free()
    check(worst <= SERVE_LOGITS_RTOL,
          f"decode logits pallas vs ref: rel RMS diff {worst!r} > "
          f"{SERVE_LOGITS_RTOL}")
    return (f"{len(results)}/{SERVE_REQUESTS} requests finished "
            f"({dict(reasons)}), {tokens} tokens in {dt:.1f}s incl. compile; "
            f"decode logits vs ref over {SERVE_PROBE_STEPS} steps: rel RMS "
            f"diff {worst!r} <= {SERVE_LOGITS_RTOL}, argmax agreement "
            f"{agree}; kernels: "
            f"{kernel_summary(kernels, required=True)}")


# ----------------------------------------------------------------- train --
def _trainer(plan, moment_bits: int):
    from repro import configs
    from repro.data.pipeline import TokenStreamConfig
    from repro.optim import adamw
    from repro.train import Trainer

    cfg = configs.get_config(TRAIN_ARCH, n_layers=TRAIN_LAYERS,
                             precision=plan)
    opt = adamw.AdamWConfig(lr=1e-4, warmup_steps=1, decay_steps=TRAIN_STEPS,
                            moment_bits=moment_bits)
    stream = TokenStreamConfig(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                               global_batch=TRAIN_BATCH)
    # max_restarts=0: a failing step raises here instead of being retried
    return Trainer(cfg, opt, stream_cfg=stream, max_restarts=0,
                   log_every=10 ** 9)


def train_run(grad_bits: int, moment_bits: int) -> str:
    import math

    from repro.kernels import registry
    from repro.quant import PrecisionPlan

    plan = PrecisionPlan(grad_bits=grad_bits)
    kernels = collections.Counter()
    t0 = time.perf_counter()
    with registry.using("pallas"), kernels_compiled(kernels):
        _, losses = _trainer(plan, moment_bits).run(TRAIN_STEPS)
    dt = time.perf_counter() - t0
    free()
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} of {TRAIN_STEPS} steps")
    check(all(math.isfinite(v) for v in losses), f"losses {losses}")
    with registry.using("ref"):
        _, ref = _trainer(plan, moment_bits).run(1)
    free()
    rel = abs(losses[0] - ref[0]) / abs(ref[0])
    check(rel <= TRAIN_LOSS_RTOL,
          f"first loss {losses[0]!r} vs ref {ref[0]!r}: rel {rel!r}")
    quantized = bool(grad_bits or moment_bits)
    return (f"{TRAIN_ARCH} at published widths, depth cut 48 -> "
            f"{TRAIN_LAYERS} layers (fp32 master + AdamW state on one chip); "
            f"{TRAIN_STEPS} steps in {dt:.1f}s incl. compile, losses "
            f"{[round(v, 4) for v in losses]}; first loss vs ref "
            f"{ref[0]!r}: rel {rel!r} <= {TRAIN_LOSS_RTOL}; kernels: "
            f"{kernel_summary(kernels, required=quantized)}")


# ------------------------------------------------------- double sampling --
def ds_run() -> str:
    import numpy as np

    from repro.core.linear import make_dataset, train_linear
    from repro.quant import PrecisionPlan

    ds = make_dataset(DS_DATASET, n_train=DS_TRAIN, n_test=DS_TEST)
    final = {}
    kernels = collections.Counter()
    for backend in ("pallas", "ref"):
        plan = PrecisionPlan(mode="double", sample_bits=DS_BITS,
                             backend=backend)
        with contextlib.ExitStack() as stack:
            if backend == "pallas":
                stack.enter_context(kernels_compiled(kernels))
            res = train_linear(ds, plan, epochs=DS_EPOCHS, batch=DS_BATCH,
                               lr=DS_LR)
        check(np.isfinite(res.losses).all(), f"{backend} losses "
              f"{res.losses}")
        final[backend] = float(res.losses[-1])
    free()
    rel = abs(final["pallas"] - final["ref"]) / final["ref"]
    check(rel <= DS_LOSS_BAND,
          f"final loss {final['pallas']!r} vs ref {final['ref']!r}: rel "
          f"{rel!r} > {DS_LOSS_BAND}")
    return (f"{DS_DATASET} ({DS_TRAIN}×{ds.n_features}), {DS_EPOCHS} epochs "
            f"at {DS_BITS} bits: final loss {final['pallas']!r} vs ref "
            f"{final['ref']!r} (rel {rel!r} <= {DS_LOSS_BAND}); kernels: "
            f"{kernel_summary(kernels, required=True)}")


# ------------------------------------------------------------ 4 replicas --
def replicas_run(devices) -> str:
    import jax
    import numpy as np

    from repro.kernels import registry
    from repro.launch.serve import serve_engine

    kw = dict(reduced=False, n_requests=REPLICA_REQUESTS,
              max_new=SERVE_MAX_NEW, min_prompt=SERVE_PROMPT[0],
              max_prompt=SERVE_PROMPT[1], kv_bits=8, weight_bits=8,
              max_slots=REPLICA_SLOTS, page_size=SERVE_PAGE,
              chunk_pages=SERVE_CHUNK_PAGES, backend="pallas")
    kernels = collections.Counter()
    with registry.using("pallas"), kernels_compiled(kernels):
        rs, many = serve_engine(SERVE_ARCH, replicas=len(devices),
                                devices=devices, **kw)
    placed = []
    for i, eng in enumerate(rs.engines):
        want = {rs.device_of(i)}
        on = {d for leaf in jax.tree.leaves((eng.params, eng.pool))
              for d in leaf.devices()}
        check(on == want, f"replica {i} arrays on {on}, want {want}")
        placed.append(rs.device_of(i).id)
    check(len(set(placed)) == len(devices), f"replica devices {placed}")
    check(min(rs.dispatched) >= 1, f"dispatched {rs.dispatched}")
    check(rs.stats["deaths"] == 0 and rs.stats["step_failures"] == 0,
          f"replica faults {rs.stats}")
    dispatched, stats = list(rs.dispatched), dict(rs.stats)
    del rs
    free()
    with registry.using("pallas"):
        _, one = serve_engine(SERVE_ARCH, **kw)
    free()
    check(sorted(many) == sorted(one) == list(range(REPLICA_REQUESTS)),
          f"finished {sorted(many)} vs {sorted(one)}")
    same = [bool(np.array_equal(many[r].tokens, one[r].tokens)) for r in one]
    check(all(same), f"{same.count(False)} requests differ from 1 replica")
    return (f"{len(devices)} replicas on devices {placed}, dispatched "
            f"{dispatched}; {REPLICA_REQUESTS} requests token-identical to "
            f"1 replica; deaths={stats['deaths']} step_failures="
            f"{stats['step_failures']}; kernels: "
            f"{kernel_summary(kernels, required=True)}")


# ------------------------------------------------------------------ main --
def device_phase(chips: int):
    """Fail at once unless JAX drives a TPU with compiled Pallas kernels."""
    env = os.environ.get("ZIPML_PALLAS_INTERPRET")
    if env is not None and env.lower() not in ("0", "false", ""):
        fail(f"ZIPML_PALLAS_INTERPRET={env} asks for interpret-mode Pallas")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devs[0].platform!r} "
             f"({devs[0].device_kind})")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    from importlib import metadata

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return "not installed"

    print(f"[device] {devs[0].platform} {devs[0].device_kind} ×{len(devs)}; "
          f"jax {version('jax')}, jaxlib {version('jaxlib')}, "
          f"libtpu {version('libtpu')}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-replica phase")
    args = ap.parse_args(argv)

    devs = device_phase(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        fail(f"the repository's package is not here ({e})")
    print(f"[cache] compile cache: {compile_cache.enable()}")

    if args.chips == 4:
        phases = [("replicas", lambda: replicas_run(devs[:REPLICAS]))]
    else:
        phases = [(f"serve w{w or 16}/kv{k or 16}",
                   lambda w=w, k=k: serve_rung(w, k))
                  for w, k in SERVE_RUNGS]
        phases += [("train bf16", lambda: train_run(0, 0)),
                   ("train g8m8", lambda: train_run(8, 8)),
                   ("ds", ds_run)]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            line = run()
        except Exception as e:                       # report, then fail below
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAIL ({time.perf_counter() - t0:.1f}s): "
                  f"{type(e).__name__}: {str(e)[:500]}", flush=True)
            free()
            continue
        print(f"[{name}] PASS ({time.perf_counter() - t0:.1f}s): {line}",
              flush=True)
    if failed:
        fail(f"{len(failed)} phase(s) failed: {', '.join(failed)}")
    import jax

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
