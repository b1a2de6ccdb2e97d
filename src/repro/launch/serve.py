"""Serving driver — a thin CLI over the continuous-batching engine
(repro/serve/engine.py) with the ZipML serving channels: int8 weights at
rest, bf16/int8/packed-int4 paged KV cache, prefix sharing + chunked
prefill, self-speculative decoding, and a multi-replica data-parallel
front-end.

Engine mode (default) serves a mixed-length synthetic trace:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --requests 16 --max-new 24 --kv-bits 4 --page-size 8 \
      --prefix-cache --chunk-pages 2

Self-speculative decoding drafts k tokens per slot through a low-bit
``slice_planes`` view of the served bitplane weights and verifies them in
one batched full-precision step (output token-identical to vanilla):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --weight-bits 8 --weight-layout bitplane --spec-decode 3 --draft-bits 4

Multi-replica mode (``--replicas N``) runs N engines — one paged pool,
prefix cache and copy of the weights each, replica i on local device
i mod ``--devices`` (default: every local device) — behind one shared submit
queue (``--dispatch`` picks least-loaded, round-robin, or prefix-aware
routing):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --requests 64 --replicas 4 --prefix-cache

``--full`` serves the published configuration instead of the reduced one
(gemma-2b: 2.5 B parameters, one TPU v5e per replica):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --full \
      --weight-bits 8 --kv-bits 8 --page-size 16 --chunk-pages 8 \
      --replicas 4

Legacy single-shot mode (the pre-engine fixed-batch greedy loop, kept as a
compatibility wrapper around the ring-buffer cache):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --legacy --batch 4 --prompt-len 32 --gen 16 --kv-bits 8
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import compile_cache
from repro.launch.steps import make_serve_step
from repro.models import transformer as T
from repro.precision.qat import quantize_param_tree
from repro.quant import PrecisionPlan
from repro.serve.faults import ReplicaDeviceLost


def _resolve_plan(plan, kv_bits, weight_bits, optimal_levels) -> PrecisionPlan:
    if plan is None:
        plan = PrecisionPlan(kv_bits=kv_bits, model_bits=weight_bits,
                             model_storage="int" if weight_bits else "fake",
                             optimal_levels=optimal_levels)
    if plan.model_bits and plan.model_storage != "int":
        # 'fake'/'ship' are train-time storages; at serve time model_bits>0
        # always means real int codes at rest — normalize so a plan built for
        # training can't silently serve bf16 weights labeled as quantized
        plan = dataclasses.replace(plan, model_storage="int")
    return plan


def _build(arch: str, *, reduced: bool, plan: PrecisionPlan, seed: int,
           weight_layout: str = "dense"):
    get = configs.get_reduced if reduced else configs.get_config
    cfg = get(arch, precision=plan)
    key = jax.random.PRNGKey(seed)
    params = T.init_params(key, cfg)
    if plan.model_bits:
        params = quantize_param_tree(
            params, bits=plan.model_bits,
            optimal=plan.optimal_levels and weight_layout == "dense",
            layout=weight_layout)
    return cfg, params, key


def serve(arch: str, *, reduced: bool = True, batch: int = 4,
          prompt_len: int = 32, gen: int = 16, kv_bits: int = 0,
          weight_bits: int = 0, optimal_levels: bool = False, seed: int = 0,
          plan: PrecisionPlan | None = None):
    """Legacy single-shot serve: greedy-decode ``gen`` tokens for one random
    fixed-length prompt batch against the ring-buffer cache.

    ``plan``: a full :class:`repro.quant.PrecisionPlan`; when given it
    overrides the individual ``kv_bits``/``weight_bits``/``optimal_levels``
    knobs. Returns (tokens (B, prompt+gen), steady-state tokens/s).

    The reported tokens/s measures **steady-state decode only**: a warmup
    step runs (and is discarded — state is functional) before the clock
    starts, so jit compilation of the decode step is never billed. The old
    implementation took t0 before the first prefill, which billed the entire
    XLA compile to throughput.
    """
    plan = _resolve_plan(plan, kv_bits, weight_bits, optimal_levels)
    cfg, params, key = _build(arch, reduced=reduced, plan=plan, seed=seed)
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    vis = None
    if cfg.family == "vlm":
        vis = jnp.zeros((batch, cfg.n_vis_tokens, cfg.d_model), jnp.float32)

    smax = prompt_len + gen
    logits, state = T.prefill(params, prompts, cfg, vision_tokens=vis,
                              pad_to=smax)
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    step_fn = jax.jit(make_serve_step(cfg))
    # warmup: trigger compile + first-dispatch costs on a throwaway call
    # (state is immutable — discarding the result leaves the decode unchanged)
    _, warm_tok, _ = step_fn(params, state, next_tok)
    warm_tok.block_until_ready()

    out = [prompts, next_tok]
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        _, nxt, state = step_fn(params, state, out[-1])
        out.append(nxt[:, None])
    tokens = jnp.concatenate(out, axis=1)
    tokens.block_until_ready()
    dt = time.perf_counter() - t0
    # gen=1 times zero decode steps — report NaN rather than batch/ε nonsense
    tps = batch * (gen - 1) / dt if gen > 1 else float("nan")
    return np.asarray(tokens), tps


def make_trace(n_requests: int, vocab_size: int, *, max_new: int = 16,
               min_prompt: int = 4, max_prompt: int = 32, seed: int = 0,
               temperature: float = 0.0, top_k: int = 0):
    """A mixed-length synthetic request trace (varied prompt/gen lengths)."""
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n_requests):
        s = int(rng.integers(min_prompt, max_prompt + 1))
        g = int(rng.integers(max(1, max_new // 4), max_new + 1))
        reqs.append(Request(
            rid=rid, prompt=rng.integers(0, vocab_size, s),
            max_new_tokens=g, temperature=temperature, top_k=top_k, seed=seed))
    return reqs


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Knobs for the per-replica health state machine.

    ``step_deadline_s`` — a scheduler step slower than this counts as a
    failure (stalled device); measured on the injected clock.
    ``dead_after`` — consecutive step failures before the replica is
    declared dead (healthy → suspect on the first, dead on the Nth).
    ``restart_backoff_s`` / ``backoff_cap_s`` — capped exponential backoff
    between death and the restart attempt (``base × 2^restarts``).
    ``max_restarts`` — restart attempts before the replica is FAILED for
    good (its work migrates; it takes no more).
    """

    step_deadline_s: float = 30.0
    dead_after: int = 2
    restart_backoff_s: float = 0.5
    backoff_cap_s: float = 30.0
    max_restarts: int = 5

    def __post_init__(self):
        if self.step_deadline_s <= 0:
            raise ValueError(
                f"step_deadline_s must be > 0, got {self.step_deadline_s}")
        if self.dead_after < 1:
            raise ValueError(f"dead_after must be >= 1, got {self.dead_after}")
        if self.restart_backoff_s < 0 or self.backoff_cap_s < 0:
            raise ValueError("backoff times must be >= 0")
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0, got {self.max_restarts}")


class ReplicaHealth:
    """One replica's health record: state machine position, failure
    counters, restart bookkeeping, and an audit trail of transitions
    ``(t, from, to, why)`` on the injected clock."""

    STATES = ("healthy", "suspect", "dead", "recovering", "failed")

    def __init__(self):
        self.state = "healthy"
        self.consecutive_failures = 0
        self.restarts = 0
        self.restart_at = 0.0
        self.last_error: str | None = None
        self.transitions: list[tuple] = []

    def to(self, state: str, now: float, why: str = "") -> None:
        if state not in self.STATES:
            raise ValueError(f"unknown health state {state!r}")
        if state != self.state:
            self.transitions.append(
                (round(float(now), 6), self.state, state, why))
            self.state = state

    def __repr__(self):
        return (f"ReplicaHealth({self.state!r}, "
                f"failures={self.consecutive_failures}, "
                f"restarts={self.restarts})")


class ReplicaSet:
    """N serving engines behind one shared submit queue (data parallelism at
    the request level — the multi-replica rung below tensor sharding).

    Each replica is a full :class:`~repro.serve.ServeEngine` — its own paged
    pool, prefix cache (sharing is per-replica; the dispatcher's job is to
    keep a prefix family's requests landing on the same replica via the
    shared queue's FIFO order + least-loaded choice), and jit caches.
    ``devices`` pins replica i to ``devices[i % len(devices)]``: its engine
    is built and stepped under ``jax.default_device`` of that device, so its
    pool and step inputs live there; the factory places the weights
    (:func:`serve_engine` gives each replica its own copy on its device).

    Dispatch policies (all with bounded backlog — a replica whose
    in-flight-plus-pending count reaches ``2 × max_slots`` takes no more
    work, so one slow replica can't hoard the tail of the trace):

    * ``least_loaded`` (default): fewest in-flight-plus-pending requests.
    * ``round_robin``: strict rotation — the affinity-blind baseline the
      prefix bench row compares against.
    * ``prefix``: **prefix-aware** — the head-of-queue request's prompt is
      matched against every replica's prefix-cache trie (a pure read, no
      refcount side effects) and routed to the replica holding the deepest
      page match; on a miss (or when every matching replica is backlogged)
      it falls back to least-loaded. Keeping a prefix family on the replica
      that owns its trie pages is what turns per-replica caches into
      fleet-wide warm hits.

    **Fault tolerance.** Each replica carries a :class:`ReplicaHealth`
    state machine (healthy → suspect → dead → recovering, or failed for
    good) driven by step deadlines and consecutive-failure counts on the
    injected ``clock``. A dead replica's in-flight and queued requests are
    harvested back into the shared queue (front, original order) and
    re-dispatched to survivors, where they **replay from prompt + committed
    tokens** through the engine's recompute-preemption machinery — bit-exact,
    so migration is output-invisible. Restarts rebuild the engine through
    the original ``factory`` under capped exponential backoff; after
    ``max_restarts`` failed attempts the replica is FAILED and only the
    survivors serve. Dispatch only targets HEALTHY replicas.
    """

    def __init__(self, factory, n_replicas: int, *, devices=None,
                 dispatch: str = "least_loaded", clock=None,
                 fault_injector=None, health: HealthConfig | None = None,
                 ship_dir: str | None = None):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if dispatch not in ("least_loaded", "round_robin", "prefix"):
            raise ValueError(
                "dispatch must be 'least_loaded', 'round_robin' or "
                f"'prefix', got {dispatch!r}")
        self.devices = list(devices) if devices else None
        self.dispatch = dispatch
        self._factory = factory
        self._clock = clock if clock is not None else time.perf_counter
        self._faults = fault_injector
        self.health_cfg = health or HealthConfig()
        self.ship_dir = ship_dir
        self.engines = []
        for i in range(n_replicas):
            with self._device_ctx(i):
                self.engines.append(factory(i))
        if dispatch == "prefix" and any(e.prefix is None for e in self.engines):
            raise ValueError("dispatch='prefix' needs prefix_cache=True "
                             "engines (nothing to match against otherwise)")
        self._queue: collections.deque = collections.deque()
        self.dispatched = [0] * n_replicas
        self._rr = 0
        self._step_no = 0
        self.health = [ReplicaHealth() for _ in range(n_replicas)]
        self.stats = {"rejected": 0, "migrated": 0, "deaths": 0,
                      "restarts": 0, "step_failures": 0}

    def device_of(self, i: int):
        """The device replica ``i`` is pinned to (None: the default one)."""
        return None if self.devices is None \
            else self.devices[i % len(self.devices)]

    def _device_ctx(self, i: int):
        if self.devices is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device_of(i))

    def submit(self, req) -> None:
        """Queue a request — or reject it up front (``ValueError`` +
        ``rejected`` stat) when **no** replica could ever admit its shape,
        so an unservable request fails fast instead of circulating
        forever."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        reasons = {e.admit_impossible(prompt.size, req.max_new_tokens)
                   for e in self.engines}
        if None not in reasons:
            self.stats["rejected"] += 1
            raise ValueError(
                f"request {req.rid} rejected: no replica can ever admit it "
                f"({'; '.join(sorted(reasons))})")
        self._queue.append({"req": req, "prompt": prompt,
                            "replay": np.zeros((0,), np.int32),
                            "t_submit": self._clock(), "retries": 0})

    @property
    def n_pending(self) -> int:
        return len(self._queue) + sum(e.n_pending for e in self.engines)

    def _dispatch(self) -> None:
        n = len(self.engines)
        while self._queue:
            loads = [e.n_active + e.n_prefilling + e.n_pending
                     for e in self.engines]
            ok = [self.health[j].state == "healthy"
                  and loads[j] < 2 * self.engines[j].max_slots
                  for j in range(n)]
            if not any(self.health[j].state == "healthy" for j in range(n)):
                return
            i = None
            entry = self._queue[0]
            if self.dispatch == "prefix":
                prompt = np.asarray(entry["prompt"], np.int32).reshape(-1)
                best = 0
                for j, e in enumerate(self.engines):
                    if not ok[j] or e.prefix is None:
                        continue
                    depth = len(e.prefix.match(prompt))
                    if depth > best:
                        best, i = depth, j
            elif self.dispatch == "round_robin":
                for off in range(n):
                    j = (self._rr + off) % n
                    if self.health[j].state != "healthy":
                        continue          # rotation skips dead replicas
                    if not ok[j]:
                        return            # healthy target backlogged → wait
                    i = j
                    self._rr = j + 1
                    break
                if i is None:
                    return
            if i is None:                      # miss → least-loaded
                cand = [j for j in range(n) if ok[j]]
                if not cand:
                    return
                i = min(cand, key=lambda j: loads[j])
            with self._device_ctx(i):
                self.engines[i].submit_entry(self._queue.popleft())
            self.dispatched[i] += 1

    def step(self) -> dict:
        """One restart/dispatch pass + one scheduler step on every busy
        live replica, with deadline + failure accounting per replica."""
        self._step_no += 1
        self._maybe_skip_idle_wait()
        self._maybe_restart(self._clock())
        self._dispatch()
        finished = {}
        hc = self.health_cfg
        for i, eng in enumerate(self.engines):
            h = self.health[i]
            if h.state in ("dead", "recovering", "failed") or not eng.busy:
                continue
            t0 = self._clock()
            try:
                if self._faults is not None:
                    for sp in self._faults.poll("replica_stall",
                                                step=self._step_no, replica=i):
                        self._advance_or_sleep(sp.stall_s)
                    for sp in self._faults.poll("replica_raise",
                                                step=self._step_no, replica=i):
                        raise ReplicaDeviceLost(
                            f"replica {i}: injected device loss at "
                            f"set step {self._step_no}")
                with self._device_ctx(i):
                    for f in eng.step():
                        finished[f.rid] = f
            except Exception as e:           # device loss shows up as raises
                self._record_failure(i, e)
                continue
            dt = self._clock() - t0
            if dt > hc.step_deadline_s:
                self._record_failure(i, TimeoutError(
                    f"replica {i}: step took {dt:.3f}s > deadline "
                    f"{hc.step_deadline_s}s"))
            else:
                h.consecutive_failures = 0
                if h.state == "suspect":
                    h.to("healthy", self._clock(), "step within deadline")
        return finished

    def _advance_or_sleep(self, dt: float) -> None:
        adv = getattr(self._clock, "advance", None)
        if adv is not None:
            adv(float(dt))                   # virtual stall: no wall time
        else:
            time.sleep(float(dt))

    def _record_failure(self, i: int, err: BaseException) -> None:
        h = self.health[i]
        now = self._clock()
        h.consecutive_failures += 1
        h.last_error = f"{type(err).__name__}: {err}"
        self.stats["step_failures"] += 1
        if h.consecutive_failures >= self.health_cfg.dead_after:
            self._kill(i, now)
        elif h.state == "healthy":
            h.to("suspect", now, h.last_error)

    def _kill(self, i: int, now: float) -> None:
        """Declare replica ``i`` dead: harvest its in-flight + queued
        requests back onto the front of the shared queue (original order)
        for bit-exact replay on survivors, and schedule the restart."""
        h = self.health[i]
        h.to("dead", now, h.last_error or "killed")
        self.stats["deaths"] += 1
        entries = self.engines[i].harvest()
        for e in reversed(entries):
            self._queue.appendleft(e)
        self.stats["migrated"] += len(entries)
        h.restart_at = now + min(
            self.health_cfg.backoff_cap_s,
            self.health_cfg.restart_backoff_s * (2.0 ** h.restarts))

    def _maybe_restart(self, now: float) -> None:
        for i, h in enumerate(self.health):
            if h.state != "dead" or now < h.restart_at:
                continue
            if h.restarts >= self.health_cfg.max_restarts:
                h.to("failed", now,
                     f"max_restarts={self.health_cfg.max_restarts} "
                     f"exhausted; last error: {h.last_error}")
                continue
            h.to("recovering", now, "backoff elapsed")
            h.restarts += 1
            self.stats["restarts"] += 1
            if self._faults is not None and self.ship_dir is not None:
                from repro.serve.faults import truncate_ship_artifact
                for sp in self._faults.poll("ship_truncate",
                                            step=self._step_no, replica=i):
                    truncate_ship_artifact(self.ship_dir)
            try:
                with self._device_ctx(i):
                    self.engines[i] = self._factory(i)
            except Exception as e:           # bad artifact, OOM, ... → retry
                h.last_error = f"{type(e).__name__}: {e}"
                h.to("dead", self._clock(),
                     f"restart failed: {h.last_error}")
                h.restart_at = self._clock() + min(
                    self.health_cfg.backoff_cap_s,
                    self.health_cfg.restart_backoff_s * (2.0 ** h.restarts))
            else:
                h.consecutive_failures = 0
                h.to("healthy", self._clock(), "engine rebuilt")

    def _maybe_skip_idle_wait(self) -> None:
        """On a virtual clock with every replica down, jump straight to the
        earliest restart time instead of spinning through empty steps."""
        adv = getattr(self._clock, "advance", None)
        if adv is None:
            return
        if any(h.state in ("healthy", "suspect") for h in self.health):
            return
        due = [h.restart_at for h in self.health if h.state == "dead"]
        if not due:
            return
        dt = min(due) - self._clock()
        if dt > 0:
            adv(dt)

    def run(self, requests=None, max_steps: int = 100_000) -> dict:
        for r in requests or ():
            self.submit(r)
        out: dict = {}
        for _ in range(max_steps):
            if not self._queue and not any(e.busy for e in self.engines):
                return out
            if all(h.state == "failed" for h in self.health):
                errs = "; ".join(
                    f"replica {i}: {h.last_error}"
                    for i, h in enumerate(self.health))
                raise RuntimeError(
                    f"all {len(self.health)} replicas failed permanently "
                    f"with work outstanding — {errs}")
            before = self._progress()
            out.update(self.step())
            # a dead/recovering replica makes no engine progress while its
            # backoff runs down — that is a wait, not a stall
            if (self._progress() == before
                    and not any(h.state in ("dead", "recovering")
                                for h in self.health)):
                raise RuntimeError("replica set stalled — no engine "
                                   "admitted, prefilled, decoded, or finished")
        raise RuntimeError(f"ReplicaSet.run exceeded {max_steps} steps")

    def _progress(self) -> tuple:
        return (len(self._queue), self.stats["step_failures"],
                tuple((h.state, h.consecutive_failures, h.restarts)
                      for h in self.health),
                tuple((e.n_pending, e.n_active, e.n_prefilling,
                       e.stats["decode_steps"], e.stats["prefill_tokens"])
                      for e in self.engines))

    def stats_sum(self, key: str):
        return sum(e.stats[key] for e in self.engines)

    def throughput(self) -> float:
        """Aggregate steady-state decode tokens/s across replicas."""
        tps = [e.throughput() for e in self.engines]
        good = [t for t in tps if t == t]          # drop NaN (idle replica)
        return sum(good) if good else float("nan")


def serve_engine(arch: str, *, reduced: bool = True, n_requests: int = 16,
                 max_new: int = 16, min_prompt: int = 4, max_prompt: int = 32,
                 kv_bits: int = 0, weight_bits: int = 0,
                 optimal_levels: bool = False, seed: int = 0,
                 plan: PrecisionPlan | None = None, max_slots: int = 4,
                 page_size: int = 8, temperature: float = 0.0,
                 top_k: int = 0, backend: str | None = None,
                 weight_layout: str = "dense", autoscale: bool = False,
                 slo_admit_ms: float | None = None,
                 prefix_cache: bool = False, chunk_pages: int | None = None,
                 replicas: int = 1, devices=None, spec_decode: int = 0,
                 draft_bits: int | None = None,
                 dispatch: str = "least_loaded", clock=None,
                 fault_injector=None, health: HealthConfig | None = None,
                 ship_dir: str | None = None, retry_budget: int = 32):
    """Serve a mixed-length trace through the continuous-batching engine.

    ``weight_layout='bitplane'`` stores the weights bit-serially (one
    artifact, any precision); ``autoscale=True`` then attaches the
    :class:`repro.serve.PrecisionAutoscaler` so load drops/restores weight
    bits against the admission SLO (``slo_admit_ms``, default from
    ``$ZIPML_SLO_ADMIT_MS``). ``prefix_cache``/``chunk_pages`` enable prefix
    sharing and chunked prefill; ``spec_decode=k, draft_bits=b`` turns on
    self-speculative decoding (k-token draft through the b-bit
    ``slice_planes`` view of the same bitplane artifact, one batched
    full-precision verify — token-identical output, needs
    ``weight_layout='bitplane'``); ``replicas > 1`` serves the trace through
    a :class:`ReplicaSet` (one engine per replica, shared queue; ``devices``
    pins replicas round-robin, each with its own copy of the weights on its
    device; ``dispatch`` picks the routing policy —
    ``'prefix'`` routes prompt families to the replica owning their trie
    pages). Returns (engine-or-replicaset, results dict rid → Finished).
    Throughput/byte stats via ``engine.throughput()`` /
    ``engine.kv_pool_nbytes()`` / ``engine.stats``.

    Fault tolerance: ``clock`` injects the time source (a
    :class:`repro.serve.VirtualClock` makes chaos runs deterministic and
    wall-time-free), ``fault_injector`` arms a
    :class:`repro.serve.FaultInjector` on every engine and the replica
    set, ``health`` tunes the replica state machine, and ``ship_dir``
    saves the bitplane weights as a ship artifact once and rebuilds every
    replica from it — including restarts after a replica death (needs
    ``weight_layout='bitplane'`` with ``weight_bits > 0``).
    """
    from repro.serve import AutoscalerConfig, PrecisionAutoscaler, ServeEngine

    plan = _resolve_plan(plan, kv_bits, weight_bits, optimal_levels)
    if spec_decode and (weight_layout != "bitplane" or not plan.model_bits):
        raise ValueError(
            "spec_decode needs --weight-layout bitplane with weight_bits > 0 "
            "(the draft is a slice_planes view of the served artifact)")
    cfg, params, _ = _build(arch, reduced=reduced, plan=plan, seed=seed,
                            weight_layout=weight_layout)
    if ship_dir is not None:
        if weight_layout != "bitplane" or not plan.model_bits:
            raise ValueError(
                "ship_dir needs --weight-layout bitplane with "
                "weight_bits > 0 (the restart path reloads the artifact)")
        from repro.ckpt import save_ship_weights
        save_ship_weights(ship_dir, params)

    def mk_autoscaler():
        if not autoscale:
            return None
        if weight_layout != "bitplane" or not plan.model_bits:
            raise ValueError(
                "autoscale needs --weight-layout bitplane with weight_bits > 0")
        over = {} if slo_admit_ms is None else {"slo_admit_ms": slo_admit_ms}
        ladder = tuple(b for b in (8, 4, 2, 1) if b <= plan.model_bits)
        return PrecisionAutoscaler(
            AutoscalerConfig.from_env(bits_ladder=ladder, **over))

    max_seq_len = max_prompt + max_new + page_size

    def factory(i):
        p = params
        if ship_dir is not None:
            from repro.ckpt import load_ship_weights
            p = load_ship_weights(ship_dir, bits=plan.model_bits or None)
        if devices:
            # the replica's own copy: a replica computing on weights that
            # sit on another device would run there instead
            p = jax.device_put(p, devices[i % len(devices)])
        return ServeEngine(p, cfg, plan=plan, max_slots=max_slots,
                           page_size=page_size, max_seq_len=max_seq_len,
                           backend=backend, autoscaler=mk_autoscaler(),
                           prefix_cache=prefix_cache, chunk_pages=chunk_pages,
                           spec_decode=spec_decode, draft_bits=draft_bits,
                           clock=clock, fault_injector=fault_injector,
                           replica_id=i, retry_budget=retry_budget)

    trace = make_trace(n_requests, cfg.vocab_size, max_new=max_new,
                       min_prompt=min_prompt, max_prompt=max_prompt,
                       seed=seed, temperature=temperature, top_k=top_k)
    if replicas > 1:
        rs = ReplicaSet(factory, replicas, devices=devices,
                        dispatch=dispatch, clock=clock,
                        fault_injector=fault_injector, health=health,
                        ship_dir=ship_dir)
        return rs, rs.run(trace)
    engine = factory(0)
    results = engine.run(trace)
    return engine, results


def latency_line(results: dict) -> str:
    """Time to first token (first commit − submit) and the gaps between a
    request's successive tokens, p50/p95 with their counts, from the
    per-request times in ``Finished``."""
    ttft, gaps = [], []
    for f in results.values():
        t = f.token_times
        if t.size:
            ttft.append(t[0] - f.t_submit)
            gaps.extend(np.diff(t))

    def pct(xs):
        xs = np.asarray(xs, np.float64)
        xs = xs[np.isfinite(xs)]
        if not xs.size:
            return "n=0"
        p50, p95 = np.percentile(xs, [50, 95]) * 1e3
        return f"p50 {p50:.1f} ms, p95 {p95:.1f} ms (n={xs.size})"

    return (f"[serve-engine] time to first token {pct(ttft)}; "
            f"token gaps {pct(gaps)}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the published configuration, not the reduced one")
    ap.add_argument("--kv-bits", type=int, default=0, choices=(0, 4, 8))
    ap.add_argument("--weight-bits", type=int, default=0)
    ap.add_argument("--optimal-levels", action="store_true")
    ap.add_argument("--weight-layout", default="dense",
                    choices=("dense", "bitplane"),
                    help="bitplane = bit-serial any-precision weight storage")
    ap.add_argument("--autoscale", action="store_true",
                    help="adapt weight bits to load (needs bitplane layout)")
    ap.add_argument("--slo-admit-ms", type=float, default=None,
                    help="admission-latency SLO for --autoscale "
                         "(default $ZIPML_SLO_ADMIT_MS or 50)")
    ap.add_argument("--kernel-backend", default=None, choices=(None, "ref", "pallas"))
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share page-aligned prompt prefixes across requests")
    ap.add_argument("--chunk-pages", type=int, default=None,
                    help="chunked prefill: pages per prefill chunk "
                         "(implies interleaved prefill/decode)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind a shared submit queue")
    ap.add_argument("--devices", type=int, default=None,
                    help="spread replicas over the first N local devices, "
                         "replica i on device i mod N (default: all)")
    ap.add_argument("--dispatch", default="least_loaded",
                    choices=("least_loaded", "round_robin", "prefix"),
                    help="replica routing: prefix = route prompt families "
                         "to the replica owning their trie pages")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per slot "
                         "through the low-bit weight view, verify in one "
                         "full-precision step (needs bitplane layout)")
    ap.add_argument("--draft-bits", type=int, default=None,
                    help="weight bits of the speculative draft view "
                         "(e.g. 4 or 2; must be below the serving bits)")
    # engine mode (default)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    # legacy single-shot mode
    ap.add_argument("--legacy", action="store_true",
                    help="old fixed-batch greedy loop (ring-buffer cache)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)
    print(f"[serve] compile cache: {compile_cache.enable()}")

    if args.legacy:
        tokens, tps = serve(args.arch, reduced=args.reduced, batch=args.batch,
                            prompt_len=args.prompt_len, gen=args.gen,
                            kv_bits=args.kv_bits, weight_bits=args.weight_bits,
                            optimal_levels=args.optimal_levels)
        print(f"[serve] generated {tokens.shape} tokens at {tps:.1f} tok/s "
              f"steady-state (kv_bits={args.kv_bits}, "
              f"weight_bits={args.weight_bits})")
        return

    engine, results = serve_engine(
        args.arch, reduced=args.reduced, n_requests=args.requests,
        max_new=args.max_new, min_prompt=args.min_prompt,
        max_prompt=args.max_prompt, kv_bits=args.kv_bits,
        weight_bits=args.weight_bits, optimal_levels=args.optimal_levels,
        max_slots=args.max_slots, page_size=args.page_size,
        temperature=args.temperature, top_k=args.top_k,
        backend=args.kernel_backend, weight_layout=args.weight_layout,
        autoscale=args.autoscale, slo_admit_ms=args.slo_admit_ms,
        prefix_cache=args.prefix_cache, chunk_pages=args.chunk_pages,
        replicas=args.replicas, dispatch=args.dispatch,
        devices=jax.local_devices()[:args.devices] if args.replicas > 1
        else None,
        spec_decode=args.spec_decode, draft_bits=args.draft_bits)
    gen_total = sum(f.n_generated for f in results.values())
    if isinstance(engine, ReplicaSet):
        rs = engine
        print(f"[serve-engine] {len(results)} requests across "
              f"{len(rs.engines)} replicas, {gen_total} tokens generated "
              f"(dispatch={rs.dispatched})")
        print(latency_line(results))
        print(f"[serve-engine] aggregate steady-state decode: "
              f"{rs.throughput():.1f} tok/s; "
              f"preemptions={rs.stats_sum('preemptions')}")
        if args.spec_decode:
            drafted = rs.stats_sum("spec_draft_tokens")
            accepted = rs.stats_sum("spec_accepted_tokens")
            rate = accepted / drafted if drafted else float("nan")
            print(f"[serve-engine] speculative decode: "
                  f"{rs.stats_sum('spec_steps')} windows, "
                  f"{accepted}/{drafted} draft tokens accepted "
                  f"({rate:.2f}, k={args.spec_decode}, "
                  f"draft_bits={args.draft_bits})")
        for i, eng in enumerate(rs.engines):
            st = eng.stats
            line = (f"[serve-engine]   replica {i}: "
                    f"{st['decode_steps']} decode steps, "
                    f"{st['prefill_tokens']} prefill tokens "
                    f"[{rs.health[i].state}]")
            if args.prefix_cache:
                line += (f", prefix hits={st['prefix_hits']} "
                         f"({st['prefix_hit_tokens']} tokens skipped)")
            print(line)
        if any(rs.stats.values()):
            print(f"[serve-engine] fault tolerance: "
                  f"{rs.stats['deaths']} deaths, "
                  f"{rs.stats['migrated']} migrations, "
                  f"{rs.stats['restarts']} restarts, "
                  f"{rs.stats['step_failures']} step failures, "
                  f"{rs.stats['rejected']} rejected")
        return
    st = engine.stats
    print(f"[serve-engine] {len(results)} requests, {gen_total} tokens "
          f"generated in {st['decode_steps']} decode steps "
          f"(+{st['prefill_tokens']} prefill tokens)")
    print(latency_line(results))
    print(f"[serve-engine] steady-state decode: {engine.throughput():.1f} "
          f"tok/s; preemptions={st['preemptions']}")
    if args.spec_decode:
        print(f"[serve-engine] speculative decode: {st['spec_steps']} windows, "
              f"{st['spec_accepted_tokens']}/{st['spec_draft_tokens']} draft "
              f"tokens accepted ({engine.acceptance_rate():.2f}, "
              f"k={args.spec_decode}, draft_bits={args.draft_bits})")
    if args.prefix_cache:
        print(f"[serve-engine] prefix cache: {st['prefix_hits']} hits / "
              f"{st['prefix_misses']} misses, "
              f"{st['prefix_hit_tokens']} prefill tokens skipped "
              f"({engine.prefix.n_pages} pages cached)")
    if args.chunk_pages or args.prefix_cache:
        print(f"[serve-engine] chunked prefill: {st['prefill_chunks']} chunks, "
              f"max {st['max_prefill_tokens_per_step']} prefill tokens/step")
    print(f"[serve-engine] KV pool: {engine.kv_pool_nbytes():,} bytes "
          f"(kv_bits={args.kv_bits or 'bf16'}, "
          f"page_size={args.page_size}) via QTensor.nbytes")
    if engine.autoscaler is not None:
        asc = engine.autoscaler
        print(f"[serve-engine] autoscaler: bits={asc.bits} after "
              f"{asc.n_observations} observations, "
              f"{len(asc.decisions)} rung moves "
              f"(slo_admit_ms={asc.config.slo_admit_ms})")


if __name__ == "__main__":
    main()
