"""Trainer — the one object that owns a training run.

Composes the pieces that used to be scattered across ``launch/steps.py``
(step building), ``launch/train.py`` (supervisor/restart loop),
``launch/elastic.py`` (fleet resize) and ``ckpt/checkpoint.py``
(persistence) behind one API::

    cfg = configs.get_reduced("gemma-2b", precision=PrecisionPlan(grad_bits=8))
    tr = Trainer(cfg, AdamWConfig(moment_bits=8),
                 stream_cfg=TokenStreamConfig(cfg.vocab_size, 64, 8),
                 ckpt_dir="/ckpt")
    state, losses = tr.run(steps=1000)

The checkpoint is the *full* :class:`~repro.train.state.TrainState` —
error-feedback residuals and quantized optimizer moments included — so
restart is bit-exact (pinned by tests/test_trainer.py). Checkpoints written
by the pre-Trainer driver ((params, opt_state) pairs, MomentQ moment
splices) restore through a load-time shim with a DeprecationWarning.

Elastic composition: feed an :class:`~repro.launch.elastic.ElasticController`
decision to :meth:`Trainer.apply_fleet_decision` — the data stream reshards
to the surviving hosts, and on shrink/grow the state rolls back to the last
committed checkpoint with the cursor rewound alongside it (nothing skipped,
nothing repeated beyond the rollback window).
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import Cursor, TokenStream, TokenStreamConfig
from repro.launch.elastic import FleetDecision
from repro.models import transformer as T
from repro.optim import adamw
from repro.train.channels import Channel, default_channels
from repro.train.state import TrainState, init_state
from repro.train.step import make_step

CKPT_FORMAT = "trainstate-v1"


class StragglerMonitor:
    """Per-step timing ring buffer; flags hosts >3σ behind the fleet.

    On a synchronous pjit pod, one slow host gates every collective — the
    monitor's job is detection + data-shard rebalance advice, not recovery
    (recovery = evict + elastic restore, exercised in tests/test_trainer).
    """

    def __init__(self, window: int = 50):
        self.times = collections.deque(maxlen=window)
        self.flagged = 0

    def record(self, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) < 10:
            return False
        mu = float(np.mean(self.times))
        sd = float(np.std(self.times)) + 1e-9
        if dt > mu + 3 * sd:
            self.flagged += 1
            return True
        return False


class Trainer:
    """One training run: state init, jitted channel-composed step,
    supervisor loop with checkpoint/restart, elastic data resharding."""

    def __init__(self, cfg: T.ModelConfig,
                 opt_cfg: adamw.AdamWConfig | None = None, *,
                 stream_cfg: TokenStreamConfig | None = None,
                 channels: dict[str, Channel] | None = None,
                 error_feedback: bool = True, accum_steps: int = 1,
                 ckpt_dir: str | None = None, ckpt_every: int = 20,
                 log_every: int = 10, seed: int = 0,
                 max_restarts: int = 8, restart_backoff_s: float = 0.0):
        if max_restarts < 0:
            raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
        self.cfg = cfg
        self.plan = cfg.precision
        self.opt_cfg = opt_cfg if opt_cfg is not None else adamw.AdamWConfig()
        self.channels = channels if channels is not None else \
            default_channels(self.plan, error_feedback=error_feedback)
        self.accum_steps = accum_steps
        self.ckpt_every = ckpt_every
        self.log_every = log_every
        # supervisor restart cap: a *deterministic* crash (bad batch, code
        # bug) restores to the same step and crashes again forever without
        # one — after max_restarts consecutive failures with no forward
        # progress the underlying error propagates to the caller
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)
        self.key = jax.random.PRNGKey(seed)
        self.stream_cfg = stream_cfg
        self.stream = TokenStream(stream_cfg) if stream_cfg else None
        self.mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.monitor = StragglerMonitor()
        self._step_fn = jax.jit(
            make_step(cfg, self.opt_cfg, self.channels, accum_steps))

    # ------------------------------------------------------------ lifecycle --
    def init_state(self, key: jax.Array | None = None) -> TrainState:
        key = self.key if key is None else key
        params = T.init_params(key, self.cfg)
        opt = adamw.init(params, self.opt_cfg)
        ch = {name: c.init(params) for name, c in self.channels.items()}
        return init_state(params, opt, ch, key)

    def state_template(self) -> TrainState:
        """ShapeDtypeStruct skeleton of the run state (no allocation)."""
        return jax.eval_shape(lambda: self.init_state())

    # ----------------------------------------------------------------- step --
    def step(self, state: TrainState, batch: dict):
        """One training step. ``batch`` must be the one at ``state.step``
        (numpy or jnp leaves; vlm runs get zero vision stand-ins). The
        upload and the dispatch run in the host span ``train.step``."""
        with jax.profiler.TraceAnnotation("train.step"):
            batch_j = {k: jnp.asarray(v) for k, v in batch.items()}
            if self.cfg.family == "vlm" and "vision" not in batch_j:
                b = batch_j["tokens"].shape[0]
                batch_j["vision"] = jnp.zeros(
                    (b, self.cfg.n_vis_tokens, self.cfg.d_model),
                    jnp.float32)
            return self._step_fn(state, batch_j)

    # ---------------------------------------------------------- checkpoints --
    def _manifest_format(self, step: int | None) -> str | None:
        """The ``format`` field of a checkpoint's manifest (None = legacy)."""
        import json
        import os

        self.mgr.wait()
        if step is None:
            step = self.mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.mgr.directory}")
        path = os.path.join(self.mgr.directory, f"step_{step:09d}",
                            "manifest.json")
        with open(path) as f:
            return json.load(f).get("extra", {}).get("format")

    def save(self, state: TrainState, blocking: bool = False):
        if self.mgr is None:
            raise RuntimeError("Trainer built without ckpt_dir")
        self.mgr.save(int(state.step), state,
                      extra={"cursor": state.cursor.to_dict(),
                             "precision": self.plan.to_dict(),
                             "format": CKPT_FORMAT},
                      blocking=blocking)

    def restore(self, step: int | None = None) -> tuple[TrainState, dict]:
        """Restore a TrainState checkpoint; legacy (params, opt_state) pairs
        (including MomentQ moment splices) go through the load-time shim.
        Format dispatch reads the manifest's ``format`` field — a mismatched
        *new*-format checkpoint (e.g. plan drift) raises its real error
        instead of being retried as a legacy pair."""
        if self.mgr is None:
            raise RuntimeError("Trainer built without ckpt_dir")
        template = self.state_template()
        if self._manifest_format(step) == CKPT_FORMAT:
            return self.mgr.restore(template, step=step)
        legacy_t = (template.params,
                    adamw.legacy_moment_template(template.opt))
        (params, opt), manifest = self.mgr.restore(legacy_t, step=step)
        if self.opt_cfg.moment_bits:
            opt = adamw.migrate_legacy_moments(opt, self.opt_cfg.moment_bits)
        ch = {name: c.init(params) for name, c in self.channels.items()}
        cursor = Cursor.from_dict(manifest["extra"]["cursor"])
        state = init_state(params, opt, ch, self.key,
                           step=manifest["step"], epoch=cursor.epoch)
        return state, manifest

    # -------------------------------------------------------------- elastic --
    def apply_fleet_decision(self, decision: FleetDecision,
                             state: TrainState,
                             host_id: int = 0) -> TrainState:
        """Apply an ElasticController decision: reshard this host's slice of
        the data stream to the surviving fleet, and — when pods were evicted —
        roll back to the decision's restore step. The stream cursor rewinds
        with the restored state, so the resumed run consumes exactly the
        deterministic batch sequence from the rollback point."""
        from repro.launch.elastic import stream_sharding

        if self.stream_cfg is None:
            raise RuntimeError("Trainer built without stream_cfg")
        if decision.n_pods == 0:
            raise RuntimeError(f"fleet halt: {decision.reason}")
        n_hosts, shard = stream_sharding(decision, host_id)
        self.stream_cfg = dataclasses.replace(
            self.stream_cfg, n_hosts=n_hosts, host_id=shard)
        if decision.restore_step is not None and self.mgr is not None \
                and self.mgr.latest_step() is not None:
            state, _ = self.restore(step=decision.restore_step)
        self.stream = TokenStream(self.stream_cfg)
        self.stream.skip_to(state.cursor)
        return state

    # ----------------------------------------------------------- supervisor --
    def run(self, steps: int, *, state: TrainState | None = None,
            fail_at: int | None = None, fail_count: int | None = 1):
        """The supervisor loop: resume-from-checkpoint, NaN-skip (inside the
        optimizer), straggler flagging, restore-and-replay on step failure.
        Returns (final TrainState, losses) — replayed steps re-append, so
        ``len(losses) ≥ steps`` when faults occurred.

        ``fail_at``/``fail_count`` inject a crash at that step the first
        ``fail_count`` times it is reached (``None`` = every time — a
        deterministic crash the restart loop can never outrun). Restarts
        without forward progress are capped at ``max_restarts``; past the
        cap the underlying error propagates instead of looping forever."""
        if self.stream is None:
            raise RuntimeError("Trainer built without stream_cfg")
        if state is None:
            state = self.init_state()
            if self.mgr and self.mgr.latest_step() is not None:
                state, _ = self.restore()
                print(f"[train] resumed from step {int(state.step)}")
        self.stream.skip_to(state.cursor)

        losses = []
        fired = 0
        # restart accounting: crashes only count against the cap while the
        # run is stuck at the same high-water step — any forward progress
        # (checkpoint replay reaching a new step) resets the count
        high_step, crash_count = -1, 0
        while int(state.step) < steps:
            try:
                step_i = int(state.step)
                batch_np = self.stream.next_batch()
                if (fail_at is not None and step_i == fail_at
                        and (fail_count is None or fired < fail_count)):
                    fired += 1
                    raise RuntimeError("injected fault (test)")
                t0 = time.perf_counter()
                state, metrics = self.step(state, batch_np)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                if self.monitor.record(dt):
                    print(f"[train] step {step_i}: straggler flagged ({dt:.3f}s)")
                losses.append(loss)
                done = step_i + 1
                if done % self.log_every == 0:
                    print(f"[train] step {done}: loss={loss:.4f} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"skipped={float(metrics['skipped']):.0f} ({dt:.2f}s)")
                if self.mgr and done % self.ckpt_every == 0:
                    self.save(state)
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                step_now = int(state.step)
                if step_now > high_step:
                    high_step, crash_count = step_now, 1
                else:
                    crash_count += 1
                if crash_count > self.max_restarts:
                    print(f"[train] step {step_now} crashed {crash_count} "
                          f"times with no forward progress "
                          f"(max_restarts={self.max_restarts}) — giving up")
                    raise
                if self.restart_backoff_s:
                    time.sleep(min(30.0, self.restart_backoff_s
                                   * 2.0 ** (crash_count - 1)))
                print(f"[train] step {step_now} FAILED ({e}); "
                      "restoring last checkpoint")
                if self.mgr is None or self.mgr.latest_step() is None:
                    print("[train] no checkpoint — restarting from scratch")
                    state = self.init_state()
                    self.stream.skip_to(Cursor(0, 0))
                    continue
                state, _ = self.restore()
                self.stream.skip_to(state.cursor)
        if self.mgr:
            self.save(state, blocking=True)
        return state, losses
