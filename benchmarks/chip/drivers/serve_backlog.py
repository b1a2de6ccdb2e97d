"""Serving under a standing backlog: ``ServeEngine.step()`` driven for the
window with more requests queued than there are slots.

Set-up makes the int8 weights on the device from the seed, builds the
engine, queues every request of the mix, and steps the engine until the
first ``max_slots`` requests have been admitted and prefilled (compiling,
or loading from the cache, the chunk and decode programs and the
first-token sampler). The window then steps the engine
until ``--seconds`` have passed, at a step boundary. ``serve_tok_s`` is the
change in ``stats["decode_tokens"]`` over the window's seconds.

Correctness: after the window (where it finished fewer requests than the
sample takes, the engine steps on, uncounted, until it has), the program's
state is freed and the plain reference (``references/decoder.py``) runs once
over a seeded sample of the finished requests, the longest among them: prompt
and served tokens, teacher-forced. The number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at
that position. Greedy decoding makes that gap the effect of the program's
arithmetic alone.
"""
from __future__ import annotations

import gc

import numpy as np

import adapter
import core
import traffic
import weights as wgen

REF = core.HERE / "references" / "decoder.py"


def make_weights(a, seed: int, bits: int):
    import jax

    fn = jax.jit(wgen.make, static_argnums=0,
                 static_argnames=("dtype", "bits"))
    w = fn(a, core.seed_key(seed, 0), bits=bits)
    jax.block_until_ready(w)
    return w


def engine_args(wl: dict, mix: dict) -> dict:
    """The engine's sizes from the workload and the mix: the KV pool holds
    what ``max_slots`` requests of the mix reserve (``traffic.pool_pages``),
    not ``max_slots`` of the longest."""
    return dict(max_slots=wl["max_slots"], page_size=wl["page_size"],
                chunk_pages=wl["chunk_pages"], reserve=wl["reserve"],
                max_seq_len=traffic.max_seq_len(mix),
                n_pages=traffic.pool_pages(mix, wl["max_slots"],
                                           wl["page_size"], wl["pool_z"]),
                backend=wl["backend"])


def build(ctx):
    """Weights, engine and queued requests (no step yet)."""
    from repro.quant import PrecisionPlan
    from repro.serve import Request, ServeEngine

    ref = core.load_module(REF)
    cfg, wl, mix = ctx.spec["config"], ctx.spec["workload"], ctx.spec["mix"]
    a = ref.arch(cfg)
    bits = wl["weight_bits"]
    plan = PrecisionPlan(model_bits=bits, kv_bits=wl["kv_bits"],
                         model_storage="int" if bits else "fake")
    pcfg = adapter.model_config(a, cfg, plan)
    w = make_weights(a, ctx.seed, bits)
    eng = ServeEngine(adapter.params(w, bits), pcfg, **engine_args(wl, mix))
    reqs = traffic.requests(mix, ctx.seed, a.vocab)
    with ctx.spans("submit"):
        for i, r in enumerate(reqs):
            eng.submit(Request(rid=i, prompt=r["prompt"],
                               max_new_tokens=r["max_new"]))
    return ref, a, w, eng


def live_rows(eng, before_active, before_lens) -> int:
    """KV rows the step's paged attention needed: every slot that decoded,
    at its length after the step's append."""
    after = eng._active & ~before_active
    return int((before_lens[before_active] + 1).sum()
               + eng._lens[after].sum())


def run(ctx) -> dict:
    import jax

    from repro.kernels import registry

    wl = ctx.spec["workload"]
    with registry.using(wl["backend"]):
        ref, a, w, eng = build(ctx)
        if getattr(ctx, "fault", None):
            eng = ctx.fault(eng)
        # ramp: the first max_slots requests admitted and prefilled (a head
        # request that waits for pages is admitted once one finishes)
        for _ in range(wl["ramp_max_steps"]):
            with ctx.spans("ServeEngine.step"):
                eng.step()
            if (eng.stats["admitted"] >= eng.max_slots
                    and not eng.n_prefilling):
                break
        else:
            raise core.BenchError("the slots were never all filled in set-up")
        s0 = dict(eng.stats)
        n_times = len(eng.decode_times)
        rows = 0
        in_window = []
        with core.no_compiles(), ctx.window() as win:
            while True:
                act, lens = eng._active.copy(), eng._lens.copy()
                with ctx.spans("ServeEngine.step"):
                    out = eng.step()
                rows += live_rows(eng, act, lens)
                in_window += out
                if win.elapsed() >= ctx.window_seconds:
                    break
        if not eng.n_pending:
            raise core.BenchError("the backlog drained inside the window")
        s1 = dict(eng.stats)
        times = eng.decode_times[n_times:]
        peak = core.memory_peak_bytes(ctx.devices)
        # the comparison wants a sample of finished requests: where the
        # window finished fewer, the requests it served go on to their end
        # past its close, uncounted
        after = []
        for _ in range(wl["drain_max_steps"]):
            if len(in_window) + len(after) >= wl["check"]["sample"]:
                break
            after += eng.step()
        del eng
    gc.collect()

    delta = {k: s1[k] - s0[k] for k in ("decode_tokens", "decode_steps",
                                        "prefill_tokens", "prefill_chunks",
                                        "finished", "admitted")}
    bad = [f for f in in_window + after
           if f.reason not in ("length", "eos")]
    readings = served_gap(ctx, ref, a, w,
                          [f for f in in_window + after if f not in bad])
    del w
    gc.collect()
    jax.clear_caches()
    return {
        "setup_s": win.t0 - ctx.t_start,
        "window_s": win.seconds,
        "e2e": {"serve_tok_s": delta["decode_tokens"] / win.seconds},
        "record": {"serve": {**delta, "max_slots": wl["max_slots"],
                             "decode_times": times, "attn_rows": rows,
                             "chunk_tokens": wl["chunk_pages"]
                             * wl["page_size"],
                             "window_s": win.seconds}},
        "checks": [{"name": "served_token_gap",
                    "value": readings["served_token_gap"],
                    "limit": wl["check"]["served_token_gap"]},
                   {"name": "failed_requests", "value": len(bad),
                    "limit": 0}],
        "readings": readings,
        "attempted": len(in_window),
        "failed": len([f for f in bad if f in in_window]),
        "memory_peak_bytes": peak,
    }


def sample(ctx, done: list, n: int) -> list:
    """A seeded sample of finished requests, the longest always in it."""
    if not done:
        raise core.BenchError("no request finished inside the window")
    done = sorted(done, key=lambda f: f.rid)
    longest = max(done, key=lambda f: (f.n_generated, -f.rid))
    rest = [f for f in done if f is not longest]
    rng = core.seed_rng(ctx.seed, 7)
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def round_rows(bits: int):
    """KV rows as the configuration stores them: each (token, head) row
    rounded to the nearest step of a ``bits`` grid (scale = absmax / qmax);
    no rounding at 0 bits."""
    import jax.numpy as jnp

    if not bits:
        return None
    qmax = 2 ** (bits - 1) - 1

    def f(x):
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / qmax, 1.0)
        return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale

    return f


def int4_columns(w):
    """The control's weights: each output column rounded to 4 bits."""
    codes, scale = wgen.quantize_columns(w, 4)
    return codes.astype(scale.dtype) * scale


def gap_fn(ref, a, kv_bits: int, control: bool):
    """Jitted: tokens (1, S), next (S,), mask (S,) → the widest gap of the
    reference's logit of ``next`` below its best over ``mask``; with
    ``control`` also the widest gap of the token the reference computed
    with 4-bit weights and KV puts first."""
    import jax
    import jax.numpy as jnp

    def widest(lg, best, tok, mask):
        gap = best - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]
        return jnp.max(jnp.where(mask, gap, -jnp.inf))

    def f(w, tokens, nxt, mask):
        lg = ref.logits(a, w, ref.hidden(a, w, tokens,
                                         kv_quant=round_rows(kv_bits)))[0]
        best = jnp.max(lg, -1)
        out = [widest(lg, best, nxt, mask)]
        if control:
            lc = ref.logits(a, w, ref.hidden(a, w, tokens,
                                             kv_quant=round_rows(4),
                wq=lambda m: int4_columns(ref.decode_weight(m))))[0]
            pick = jnp.argmax(lc, -1).astype(jnp.int32)
            out.append(widest(lg, best, pick, mask))
        return out

    return jax.jit(f)


def teacher_forced(f, smax: int):
    """(tokens (1, smax), next (smax,), mask (smax,)) for one request: the
    positions that predicted its served tokens."""
    toks = np.zeros((1, smax), np.int32)
    n = len(f.tokens)
    toks[0, :n] = f.tokens
    nxt = np.zeros((smax,), np.int32)
    nxt[: n - 1] = f.tokens[1:]
    mask = np.zeros((smax,), bool)
    mask[f.prompt_len - 1: n - 1] = True
    return toks, nxt, mask


def served_gap(ctx, ref, a, w, done: list) -> dict:
    """The program's widest served-token gap over the sample (and the
    control's, when the context asks for it)."""
    smax = traffic.max_seq_len(ctx.spec["mix"])
    control = getattr(ctx, "control", False)
    fn = gap_fn(ref, a, ctx.spec["workload"]["kv_bits"], control)
    worst = {"served_token_gap": 0.0, "control_gap": 0.0}
    picked = sample(ctx, done, ctx.spec["workload"]["check"]["sample"])
    for f in picked:
        out = fn(w, *teacher_forced(f, smax))
        for k, v in zip(worst, out):
            worst[k] = max(worst[k], float(v))
    worst["served_tokens"] = int(sum(f.n_generated for f in picked))
    if not control:
        del worst["control_gap"]
    return worst
