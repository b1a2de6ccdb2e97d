"""Per-request times, host spans and stable program names.

The engine stamps each request's submit and admission times and the first
commit time of every generated token on its injectable clock; a replayed
token (after preemption or migration) keeps its first time. Every scheduler
phase and the train step run in host spans whose names are the trace
reduction's list (``benchmarks/chip/programs.py``), and every jitted program
lowers under a stable module name.
"""
from __future__ import annotations

import collections
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.data.pipeline import TokenStream, TokenStreamConfig
from repro.launch.serve import latency_line
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.quant import PrecisionPlan
from repro.serve import Request, ServeEngine, VirtualClock
from repro.serve.engine import Finished
from repro.train import Trainer

ROOT = pathlib.Path(__file__).resolve().parents[1]
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = configs.get_reduced("qwen2.5-14b")
    return cfg, T.init_params(KEY, cfg)


def _reqs(n, cfg, *, prompt_len=6, gen=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, prompt_len),
                    max_new_tokens=gen) for i in range(n)]


def _observe(seen: dict, now: float, engines, queues=()) -> None:
    """The first time each token index of each request was seen committed:
    in a slot's generated tokens or in a queued entry's replay list."""
    for eng in engines:
        for st in eng._slots:
            if st is not None:
                _extend(seen[st["req"].rid], len(st["gen"]), now)
        for e in eng._queue:
            _extend(seen[e["req"].rid], len(e["replay"]), now)
    for q in queues:
        for e in q:
            _extend(seen[e["req"].rid], len(e["replay"]), now)


def _extend(times: list, n: int, now: float) -> None:
    times += [now] * max(0, n - len(times))


class TestRequestTimes:
    def test_token_times_on_virtual_clock(self, tiny_model):
        cfg, params = tiny_model
        clock = VirtualClock()
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=2, page_size=4, max_seq_len=32,
                          clock=clock)
        for r in _reqs(4, cfg):
            eng.submit(r)
        out = {}
        while eng.busy:
            clock.advance(1.0)
            out.update((f.rid, f) for f in eng.step())
        assert len(out) == 4
        for f in out.values():
            t = f.token_times
            assert t.shape == (f.n_generated,) and np.isfinite(t).all()
            assert f.t_submit == 0.0
            assert f.t_admit >= f.t_submit and t[0] >= f.t_admit
            # the first token and the first decode share a step; each
            # later token comes one step after the one before it
            assert (np.diff(t) >= 0).all() and (np.diff(t)[1:] > 0).all()

    def test_preempted_request_keeps_first_commit_times(self, tiny_model):
        cfg, params = tiny_model
        clock = VirtualClock()
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=4),
                          max_slots=3, page_size=4, max_seq_len=32,
                          n_pages=9, reserve="none", clock=clock)
        for r in _reqs(4, cfg, gen=16, seed=7):
            eng.submit(r)
        seen, out = collections.defaultdict(list), {}
        while eng.busy:
            clock.advance(1.0)
            done = eng.step()
            _observe(seen, clock(), [eng])
            for f in done:
                _extend(seen[f.rid], f.n_generated, clock())
                out[f.rid] = f
        assert eng.stats["preemptions"] >= 1
        for rid, f in out.items():
            np.testing.assert_array_equal(f.token_times, seen[rid])

    def test_migration_carries_times(self, tiny_model):
        from repro.launch.serve import HealthConfig, ReplicaSet
        from repro.serve import FaultInjector, FaultSpec

        cfg, params = tiny_model
        clock = VirtualClock()
        faults = FaultInjector([FaultSpec("replica_raise", at_step=s,
                                          replica=0) for s in (4, 5)],
                               clock=clock)

        def factory(i):
            return ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                               max_slots=2, page_size=4, max_seq_len=32,
                               clock=clock, fault_injector=faults,
                               replica_id=i)

        rs = ReplicaSet(factory, 2, clock=clock, fault_injector=faults,
                        health=HealthConfig(
                            step_deadline_s=1.0, dead_after=2,
                            restart_backoff_s=0.1, backoff_cap_s=0.5,
                            max_restarts=2))
        reqs = _reqs(8, cfg, gen=6)
        for r in reqs:
            rs.submit(r)
        seen, out = collections.defaultdict(list), {}
        for _ in range(500):
            if not rs._queue and not any(e.busy for e in rs.engines):
                break
            done = rs.step()
            _observe(seen, clock(), rs.engines, [rs._queue])
            for rid, f in done.items():
                _extend(seen[rid], f.n_generated, clock())
                out[rid] = f
            clock.advance(0.01)
        assert rs.stats["migrated"] >= 1 and len(out) == len(reqs)
        for rid, f in out.items():
            np.testing.assert_array_equal(f.token_times, seen[rid])
            assert f.t_admit >= f.t_submit

    def test_finished_defaults(self):
        f = Finished(rid=0, tokens=np.zeros(3, np.int32), prompt_len=3,
                     n_generated=0, reason="length")
        assert np.isnan(f.t_submit) and np.isnan(f.t_admit)
        assert f.token_times.shape == (0,)

    def test_latency_line(self):
        def fin(rid, times):
            return Finished(rid=rid, tokens=np.zeros(4, np.int32),
                            prompt_len=1, n_generated=len(times),
                            reason="length", t_submit=0.0, t_admit=0.0,
                            token_times=np.asarray(times))

        line = latency_line({0: fin(0, [0.1, 0.3, 0.4]),
                             1: fin(1, [0.2, 0.5])})
        assert "time to first token p50 150.0 ms" in line
        assert "(n=2)" in line
        # gaps 0.2, 0.1, 0.3
        assert "token gaps p50 200.0 ms" in line and "(n=3)" in line


# ------------------------------------------------------------ spans, names --
def _reduction():
    sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
    import programs

    return programs


@pytest.fixture(scope="module")
def traced_run(tiny_model, tmp_path_factory):
    """A short chunked engine run with the prefix cache, a speculative run,
    a monolithic-prefill run and one Trainer.step, all under the profiler,
    with every call of a jitted program kept with its arguments."""
    from repro.precision.qat import quantize_param_tree

    cfg, params = tiny_model
    calls = {}

    def spy(fn):
        def call(*args):
            calls.setdefault(id(fn), (fn, args))
            return fn(*args)
        return call

    def spied(eng):
        for name in ("_decode_jit", "_chunk_jit", "_prefill_jit",
                     "_draft_jit", "_verify_jit"):
            get = getattr(eng, name)
            setattr(eng, name, lambda *a, get=get: spy(get(*a)))
        eng._sample1 = spy(eng._sample1)
        return eng

    kw = dict(plan=PrecisionPlan(kv_bits=8), max_slots=2, page_size=4,
              max_seq_len=32)
    bp = quantize_param_tree(params, bits=8, layout="bitplane")
    engines = [
        spied(ServeEngine(params, cfg, chunk_pages=2, prefix_cache=True,
                          **kw)),
        spied(ServeEngine(bp, cfg, spec_decode=2, draft_bits=4, **kw)),
        spied(ServeEngine(params, cfg, **kw)),
    ]
    tr = Trainer(configs.get_reduced(
        "qwen2.5-14b", precision=PrecisionPlan(grad_bits=8)),
        AdamWConfig(moment_bits=8),
        stream_cfg=TokenStreamConfig(cfg.vocab_size, 16, 2))
    state = tr.init_state()
    stream = TokenStream(tr.stream_cfg)
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        for eng in engines:
            eng.run(_reqs(3, cfg, prompt_len=9, gen=4))
        batch = stream.next_batch()
        jax.block_until_ready(tr.step(state, batch))
    finally:
        jax.profiler.stop_trace()
    logits_args = (engines[0].params, engines[0].pool,
                   jax.numpy.asarray(engines[0]._last_tok)[:, None],
                   engines[0]._lens, engines[0]._bt, engines[0]._active)
    engines[0].decode_logits()
    calls["logits"] = (engines[0]._logits_jits[None], logits_args)
    calls["train"] = (tr._step_fn, (state, {k: jax.numpy.asarray(v)
                                            for k, v in batch.items()}))
    return next(trace_dir.rglob("*.xplane.pb")), calls


def test_spans_are_the_reductions_list(traced_run):
    from jax.profiler import ProfileData

    path, _ = traced_run
    pd = ProfileData.from_file(str(path))
    names = {ev.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.split(".")[0] in ("serve", "train", "data")}
    assert names == set(_reduction().SPANS)


def test_programs_lower_with_stable_names(traced_run):
    _, calls = traced_run
    names = set()
    for fn, args in calls.values():
        text = fn.lower(*args).as_text()
        names.add(re.search(r"module @(\S+)", text).group(1))
    assert names == {"jit_serve_decode", "jit_serve_chunk",
                     "jit_serve_prefill", "jit_serve_draft",
                     "jit_serve_verify", "jit_serve_sample_first",
                     "jit_serve_logits", "jit_train_step"}


def test_train_step_scopes(traced_run):
    _, calls = traced_run
    fn, args = calls["train"]
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in ("forward_backward", "grad_channel", "optimizer"):
        assert f"jit(train_step)/{scope}/" in text, scope
