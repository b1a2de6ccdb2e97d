"""Training CLI — a thin shell over :class:`repro.train.Trainer`.

The supervisor loop, checkpoint/restart, straggler monitoring and the
stateful precision channels all live in :mod:`repro.train`; this module
parses flags, builds the Trainer, and keeps the legacy ``train(arch, ...)``
entry point as a compatibility wrapper (losses are bit-exact with driving
the Trainer directly — it *is* the Trainer).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import warnings

from repro import configs
from repro.kernels import registry
from repro.launch import compile_cache
from repro.data.pipeline import TokenStreamConfig
from repro.quant import PrecisionPlan
from repro.optim import adamw
from repro.train import StragglerMonitor, Trainer  # noqa: F401  (re-export)


def make_trainer(arch: str, *, reduced: bool = True, batch: int = 8,
                 seq: int = 64, steps: int = 50, lr: float = 1e-3,
                 moment_bits: int = 0, ckpt_dir: str | None = None,
                 ckpt_every: int = 20, log_every: int = 10,
                 precision: PrecisionPlan | None = None,
                 error_feedback: bool = True,
                 max_restarts: int = 8,
                 restart_backoff_s: float = 0.0) -> Trainer:
    """Build the standard Trainer for an (arch, shape) training run."""
    if precision is None:
        precision = PrecisionPlan()
    get = configs.get_reduced if reduced else configs.get_config
    cfg = get(arch, precision=precision)
    opt_cfg = adamw.AdamWConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                                decay_steps=steps, moment_bits=moment_bits)
    stream_cfg = TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch)
    return Trainer(cfg, opt_cfg, stream_cfg=stream_cfg, ckpt_dir=ckpt_dir,
                   ckpt_every=ckpt_every, log_every=log_every,
                   error_feedback=error_feedback, max_restarts=max_restarts,
                   restart_backoff_s=restart_backoff_s)


def train(arch: str, *, kernel_backend: str | None = None, **kwargs):
    """Returns (final_params, losses). Compatibility wrapper over Trainer.

    ``kernel_backend`` pins the quantization kernel backend for this run only
    ('ref'/'pallas'); None keeps the registry default (env var / hardware).
    The previous registry selection is restored when the run finishes.

    The per-channel ``grad_bits=``/``weight_bits=`` kwargs are deprecated —
    pass a full four-channel ``precision=PrecisionPlan(...)`` instead
    (``moment_bits`` stays: it is optimizer config, not a plan channel).
    """
    with registry.using(kernel_backend) as backend:
        print(f"[train] kernel backend: {backend.name} "
              f"(available: {', '.join(registry.available())})")
        return _train(arch, **kwargs)


def _train(arch: str, *, reduced: bool = True, steps: int = 50, batch: int = 8,
           seq: int = 64, ckpt_dir: str | None = None, ckpt_every: int = 20,
           lr: float = 1e-3, grad_bits: int = 0, weight_bits: int = 0,
           moment_bits: int = 0, fail_at: int | None = None,
           log_every: int = 10, precision: PrecisionPlan | None = None):
    """Supervisor body; ``fail_at`` injects a fault (testing)."""
    if precision is None:
        if grad_bits or weight_bits:
            warnings.warn(
                "train(grad_bits=/weight_bits=) is deprecated; pass a full "
                "precision=PrecisionPlan(...) (see the README deprecation "
                "table)", DeprecationWarning, stacklevel=3)
        precision = PrecisionPlan(model_bits=weight_bits, grad_bits=grad_bits)
    trainer = make_trainer(
        arch, reduced=reduced, batch=batch, seq=seq, steps=steps, lr=lr,
        moment_bits=moment_bits, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        log_every=log_every, precision=precision)
    state, losses = trainer.run(steps, fail_at=fail_at)
    return state.params, losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--grad-bits", type=int, default=0)
    ap.add_argument("--weight-bits", type=int, default=0)
    ap.add_argument("--weight-storage", default="fake",
                    choices=("fake", "ship", "int"))
    ap.add_argument("--moment-bits", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject one fault at this step (supervisor test)")
    ap.add_argument("--fail-count", type=int, default=1,
                    help="how many times --fail-at fires (0 = every time — "
                         "a deterministic crash the restart loop hits the "
                         "--max-restarts cap on)")
    ap.add_argument("--max-restarts", type=int, default=8,
                    help="supervisor restarts without forward progress "
                         "before the underlying error propagates")
    ap.add_argument("--kernel-backend", default=None,
                    choices=registry.available(),
                    help="quantization kernel backend (default: "
                         "$ZIPML_KERNEL_BACKEND or per jax.default_backend())")
    args = ap.parse_args(argv)
    print(f"[train] compile cache: {compile_cache.enable()}")
    precision = PrecisionPlan(model_bits=args.weight_bits,
                              model_storage=args.weight_storage,
                              grad_bits=args.grad_bits)
    with registry.using(args.kernel_backend) as backend:
        print(f"[train] kernel backend: {backend.name} "
              f"(available: {', '.join(registry.available())})")
        trainer = make_trainer(
            args.arch, reduced=args.reduced, batch=args.batch, seq=args.seq,
            steps=args.steps, lr=args.lr, moment_bits=args.moment_bits,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            precision=precision, max_restarts=args.max_restarts)
        _, losses = trainer.run(
            args.steps, fail_at=args.fail_at,
            fail_count=None if args.fail_count == 0 else args.fail_count)
    print(f"[train] done: first loss {losses[0]:.4f} → last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
