"""Serving-engine tests: paged-attention parity (ref + Pallas, bf16/int8/int4),
paged-vs-ring bit-exactness, engine-vs-legacy equivalence, the scheduler
invariants (no page leaks, every admitted request finishes, outputs
independent of batch composition, preemption recovers), prefix-sharing
copy-on-write invariants, chunked prefill, and the scheduler-clock /
autoscaler-deferral regressions."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.kernels import registry
from repro.kernels import paged_attn as pa_mod
from repro.kernels import ref as kref
from repro.models import attention as attn
from repro.models import transformer as T
from repro.quant import PrecisionPlan, encode
from repro.serve import PageAllocator, PrefixCache, Request, ServeEngine
from repro.serve import pages as pg

KEY = jax.random.PRNGKey(0)


def _mk_qkv(b, s, h, g, d, key=KEY):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, g, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, g, d), jnp.float32)
    return q, k, v


def _pool_from_rows(k, v, page, n_pages, kv_bits):
    """Pack per-sequence rows (B, S, G, D) into a single-layer pool with an
    in-order block table (page i of seq b = rows [i·page, (i+1)·page))."""
    b, s, g, d = k.shape
    maxp = -(-s // page)
    pool = pg.init_pool(1, n_pages, page, g, d, kv_bits=kv_bits)
    bt = np.zeros((b, maxp), np.int32)
    nxt = 1                                    # page 0 is the null page
    for i in range(b):
        ids = list(range(nxt, nxt + maxp))
        nxt += maxp
        pool = pg.write_prompt(pool, k[i][None], v[i][None],
                               jnp.asarray(ids, jnp.int32))
        bt[i] = ids
    return pool, jnp.asarray(bt)


def _paged_planes(rng, n_pages, page, g, d, kv_bits):
    """Random K/V code planes and scales of a one-layer pool: (k, v, k_scale,
    v_scale), the scales None for bf16."""
    kv = rng.normal(0, 1, (2, n_pages, page, g, d)).astype(np.float32)
    if not kv_bits:
        return (jnp.asarray(kv[0], jnp.bfloat16),
                jnp.asarray(kv[1], jnp.bfloat16), None, None)
    sch = pg.kv_scheme(kv_bits)
    qk = encode(jnp.asarray(kv[0]), sch)
    qv = encode(jnp.asarray(kv[1]), sch)
    return qk.codes, qv.codes, qk.scale, qv.scale


# kernel parity at lengths on the edges of a page and of a kernel block
# (page 16: a block is _PPB pages); MAXP 20 is no multiple of the block
_PAGE = 16
_PPB = pa_mod.pages_per_block(_PAGE, 20, 2, 16, 8)
_LENS = {
    "short": ([0, 1, _PAGE - 1], 20),
    "page": ([_PAGE, _PPB * _PAGE, _PPB * _PAGE + 1], 20),
    "full": ([20 * _PAGE, 3, 20 * _PAGE - 1], 20),
}
_PALLAS_CASES = [pytest.param(kv, [17, 3, 29], 4, 8, id=str(kv))
                 for kv in (0, 8, 4)] + [
    pytest.param(kv, lens, maxp, _PAGE, id=f"{kv}-{name}")
    for kv in (0, 8, 4) for name, (lens, maxp) in _LENS.items()]


class TestPagedAttentionParity:
    @pytest.mark.parametrize("kv_bits", [8, 4])
    def test_paged_vs_chunked_fp32_reference(self, kv_bits):
        """Quantized paged decode ≈ full-precision chunked_attention on the
        last query position, within the quantization tolerance."""
        b, s, h, g, d = 2, 24, 4, 2, 16
        q, k, v = _mk_qkv(b, s, h, g, d)
        spec = attn.AttnSpec(n_heads=h, n_kv_heads=g, head_dim=d, q_chunk=8)
        ref_full = attn.chunked_attention(q, k, v, spec)[:, -1]     # (B, H, D)

        pool, bt = _pool_from_rows(k, v, page=8, n_pages=16, kv_bits=kv_bits)
        lens = jnp.full((b,), s, jnp.int32)
        out = kref.paged_attention_ref(
            q[:, -1], pool.k_pages[0], pool.v_pages[0],
            None if pool.k_scale is None else pool.k_scale[0],
            None if pool.v_scale is None else pool.v_scale[0],
            bt, lens, softmax_scale=spec.scale)
        rel = float(jnp.linalg.norm(out.astype(jnp.float32) - ref_full)
                    / jnp.linalg.norm(ref_full))
        assert rel < (0.05 if kv_bits == 8 else 0.2), rel

    def test_paged_bf16_bitexact_vs_ring(self):
        """bf16 paged decode == ring-buffer decode bit-for-bit: with pages
        laid out in ring order the gathered tensor IS the ring tensor, and
        the ref backend runs the identical decode_attention on it."""
        b, s, h, g, d, page = 2, 16, 4, 2, 16, 8
        q, k, v = _mk_qkv(b, s, h, g, d)
        kb = k.astype(jnp.bfloat16)
        vb = v.astype(jnp.bfloat16)
        spec = attn.AttnSpec(n_heads=h, n_kv_heads=g, head_dim=d)
        lens = jnp.asarray([s, s - 5], jnp.int32)

        ring = attn.decode_attention(q[:, -1:].astype(jnp.bfloat16), kb, vb,
                                     spec, kv_len=lens)[:, 0]
        pool, bt = _pool_from_rows(kb, vb, page=page, n_pages=8, kv_bits=0)
        paged = kref.paged_attention_ref(
            q[:, -1].astype(jnp.bfloat16), pool.k_pages[0], pool.v_pages[0],
            None, None, bt, lens, softmax_scale=spec.scale)
        np.testing.assert_array_equal(np.asarray(paged, np.float32),
                                      np.asarray(ring, np.float32))

    @pytest.mark.parametrize("kv_bits", [8, 4])
    def test_paged_quantized_bitexact_vs_ring(self, kv_bits):
        """int8/int4 pages hold the same codes as the ring cache (same
        row-nearest scheme) and dequantize to the same bf16 rows."""
        b, s, g, d = 2, 16, 2, 16
        _, k, v = _mk_qkv(b, s, 4, g, d)
        ring = attn.prefill_cache_from_kv(k, v, kv_bits=kv_bits)
        ring_k, ring_v = ring.materialize()
        pool, bt = _pool_from_rows(k, v, page=8, n_pages=8, kv_bits=kv_bits)
        paged_k = kref.dequant_pages_ref(
            kref.gather_pages_ref(pool.k_pages[0], bt),
            kref.gather_pages_ref(pool.k_scale[0], bt))
        paged_v = kref.dequant_pages_ref(
            kref.gather_pages_ref(pool.v_pages[0], bt),
            kref.gather_pages_ref(pool.v_scale[0], bt))
        np.testing.assert_array_equal(np.asarray(paged_k, np.float32),
                                      np.asarray(ring_k, np.float32))
        np.testing.assert_array_equal(np.asarray(paged_v, np.float32),
                                      np.asarray(ring_v, np.float32))

    @pytest.mark.parametrize("kv_bits,lens,maxp,page", _PALLAS_CASES)
    def test_pallas_matches_ref(self, kv_bits, lens, maxp, page):
        """The Pallas flash kernel ≈ the gather-ref backend on active rows
        (f32 streaming softmax vs one-shot bf16 softmax associativity), at
        lengths on every edge of a page and of a kernel block."""
        rng = np.random.default_rng(0)
        b, h, g, d = len(lens), 4, 2, 16
        n_pages = b * maxp + 1
        q = jnp.asarray(rng.normal(0, 1, (b, h, d)), jnp.float32)
        bt = jnp.asarray(rng.integers(1, n_pages, (b, maxp)), jnp.int32)
        args = _paged_planes(rng, n_pages, page, g, d, kv_bits)
        r = registry.get("ref").paged_attention(
            q, *args, bt, jnp.asarray(lens, jnp.int32),
            softmax_scale=d ** -0.5)
        p = registry.get("pallas").paged_attention(
            q, *args, bt, jnp.asarray(lens, jnp.int32),
            softmax_scale=d ** -0.5)
        live = np.asarray(lens) > 0           # an empty slot's output is 0
        r, p = np.asarray(r, np.float32), np.asarray(p, np.float32)
        err = float(np.max(np.abs(r[live] - p[live])))
        assert err < 2e-2, err
        assert not p[~live].any()

    @pytest.mark.parametrize("kv_bits", [0, 8, 4])
    def test_pallas_never_reads_dead_pages(self, kv_bits):
        """Pages the block table lists past a slot's last live page, and the
        rows past its length inside that page, may hold anything: NaN scales,
        inf bf16 values. The kernel's output stays finite and equals the ref
        run on a clean pool at the same live rows."""
        rng = np.random.default_rng(1)
        h, g, d, page, maxp = 4, 2, 16, _PAGE, 20
        lens = np.array([3, _PPB * page + 5, 0, 2 * page], np.int32)
        b = len(lens)
        n_live = b * maxp + 1
        n_pages = n_live + 8                 # 8 poisoned pages at the end
        q = jnp.asarray(rng.normal(0, 1, (b, h, d)), jnp.float32)
        kp, vp, ks, vs = _paged_planes(rng, n_pages, page, g, d, kv_bits)
        live = -(-lens // page)
        clean = np.zeros((b, maxp), np.int32)
        poison = np.zeros((b, maxp), np.int32)
        for i in range(b):
            ids = 1 + i * maxp + np.arange(maxp)
            clean[i, :live[i]] = poison[i, :live[i]] = ids[:live[i]]
            poison[i, live[i]:] = rng.integers(n_live, n_pages,
                                               maxp - live[i])
        # poison the dead pages and every row past the length in live ones
        dead_rows = np.zeros((n_pages, page), bool)
        dead_rows[n_live:] = True
        for i in range(b):
            if lens[i] % page:
                dead_rows[clean[i, live[i] - 1], lens[i] % page:] = True
        m = jnp.asarray(dead_rows)[:, :, None, None]
        if kv_bits:
            pk, pv = kp, vp
            sk, sv = (jnp.where(m, jnp.nan, x) for x in (ks, vs))
        else:
            pk, pv = (jnp.where(m, jnp.inf, x) for x in (kp, vp))
            sk = sv = None
        want = registry.get("ref").paged_attention(
            q, kp, vp, ks, vs, jnp.asarray(clean), jnp.asarray(lens),
            softmax_scale=d ** -0.5)
        got = registry.get("pallas").paged_attention(
            q, pk, pv, sk, sv, jnp.asarray(poison), jnp.asarray(lens),
            softmax_scale=d ** -0.5)
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[lens > 0],
                                   np.asarray(want, np.float32)[lens > 0],
                                   atol=2e-2)


def _cfg():
    return configs.get_reduced("qwen2.5-14b")


def _params(cfg):
    return T.init_params(KEY, cfg)


def _legacy_greedy(params, cfg, prompts, gen):
    """The ring-buffer greedy loop (what launch/serve.serve runs)."""
    from repro.launch.steps import make_serve_step

    s = prompts.shape[1]
    logits, state = T.prefill(params, prompts, cfg, pad_to=s + gen)
    toks = [jnp.argmax(logits, -1).astype(jnp.int32)[:, None]]
    step = jax.jit(make_serve_step(cfg))
    for _ in range(gen - 1):
        _, nxt, state = step(params, state, toks[-1])
        toks.append(nxt[:, None])
    return np.asarray(jnp.concatenate(toks, 1))


class TestEngineParity:
    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_engine_matches_legacy_greedy(self, kv_bits):
        """Paged engine greedy tokens == ring-buffer loop tokens (the codes
        are identical; only the cache layout changed)."""
        cfg = _cfg()
        params = _params(cfg)
        b, s, gen = 3, 12, 6
        prompts = jax.random.randint(jax.random.fold_in(KEY, 1), (b, s), 0,
                                     cfg.vocab_size)
        cfgp = dataclasses.replace(cfg,
                                   precision=PrecisionPlan(kv_bits=kv_bits))
        legacy = _legacy_greedy(params, cfgp, prompts, gen)
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=kv_bits),
                          max_slots=b, page_size=8, max_seq_len=s + gen + 8)
        out = eng.run([Request(rid=i, prompt=np.asarray(prompts[i]),
                               max_new_tokens=gen) for i in range(b)])
        got = np.stack([out[i].tokens[s:] for i in range(b)])
        np.testing.assert_array_equal(got, legacy)

    @pytest.mark.slow
    def test_engine_int4_pallas_first_steps_match_ref(self):
        """int4 KV through the Pallas kernel: the prefill token and the
        first decode-step token match the ref backend exactly (one kernel
        call's numerics), and the run completes leak-free. Full-trajectory
        token equality is NOT asserted — flash (f32 streaming) vs one-shot
        (bf16) softmax differ at float granularity, which random-weight
        tiny-vocab models amplify into argmax flips after a few steps."""
        cfg = _cfg()
        params = _params(cfg)
        b, s, gen = 2, 8, 5
        prompts = jax.random.randint(jax.random.fold_in(KEY, 2), (b, s), 0,
                                     cfg.vocab_size)
        runs = {}
        for backend in ("ref", "pallas"):
            eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=4),
                              max_slots=b, page_size=8,
                              max_seq_len=s + gen + 8, backend=backend)
            out = eng.run([Request(rid=i, prompt=np.asarray(prompts[i]),
                                   max_new_tokens=gen) for i in range(b)])
            eng.allocator.check_leaks(0)
            runs[backend] = np.stack([out[i].tokens[s:] for i in range(b)])
        np.testing.assert_array_equal(runs["pallas"][:, :2],
                                      runs["ref"][:, :2])

    def test_decode_logits_probe_leaves_state_and_predicts_next_token(self):
        """decode_logits reads the logits the next decode step samples from
        without advancing the engine: its argmax is the token the next
        greedy step emits, and a second backend probes the same state."""
        cfg = _cfg()
        eng = ServeEngine(_params(cfg), cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=2, page_size=8, max_seq_len=32)
        for i in range(2):
            eng.submit(Request(rid=i, prompt=np.arange(5 + i) % cfg.vocab_size,
                               max_new_tokens=6))
        while eng.n_active < 2:
            eng.step()
        lens = eng._lens.copy()
        logits = np.asarray(eng.decode_logits())
        other = np.asarray(eng.decode_logits("pallas"))
        np.testing.assert_array_equal(eng._lens, lens)
        # bf16 rounding apart (ref rounds attention probabilities to bf16)
        rms = np.sqrt(np.mean((other - logits) ** 2) / np.mean(logits ** 2))
        assert rms < 0.1, rms
        want = logits.argmax(-1)
        eng.step()
        got = [eng._slots[i]["gen"][-1] for i in range(2)]
        np.testing.assert_array_equal(got, want)


class TestSchedulerInvariants:
    def _mixed_requests(self, cfg, n, seed=0, **kw):
        rng = np.random.default_rng(seed)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(3, 20))),
                        max_new_tokens=int(rng.integers(2, 10)), **kw)
                for i in range(n)]

    @pytest.mark.parametrize("kv_bits", [0, 4])
    def test_all_finish_no_leaks(self, kv_bits):
        cfg = _cfg()
        eng = ServeEngine(_params(cfg), cfg, plan=PrecisionPlan(kv_bits=kv_bits),
                          max_slots=4, page_size=8, max_seq_len=48)
        reqs = self._mixed_requests(cfg, 8)
        out = eng.run(reqs)
        assert sorted(out) == list(range(8))
        for f in out.values():
            assert 1 <= f.n_generated <= 10
            assert f.reason in ("eos", "length")
            assert f.tokens.shape == (f.prompt_len + f.n_generated,)
        eng.allocator.check_leaks(0)          # raises on leaked pages

    @pytest.mark.slow
    def test_outputs_independent_of_batch_composition(self):
        """Every request (greedy and sampled) produces the same tokens
        served solo as in a churning mixed batch."""
        cfg = _cfg()
        params = _params(cfg)
        rng = np.random.default_rng(3)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 4 + 3 * i),
                        max_new_tokens=5,
                        temperature=0.8 if i % 2 else 0.0,
                        top_k=5 if i % 2 else 0, seed=7)
                for i in range(4)]
        mixed = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                            max_slots=4, page_size=8,
                            max_seq_len=64).run(reqs)
        for r in reqs:
            solo = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                               max_slots=1, page_size=8,
                               max_seq_len=64).run([r])
            np.testing.assert_array_equal(solo[r.rid].tokens,
                                          mixed[r.rid].tokens)

    def test_preemption_recovers_and_frees(self):
        """reserve='none' + a pool too small for everyone: the engine must
        preempt, replay, and still finish every request leak-free."""
        cfg = _cfg()
        eng = ServeEngine(_params(cfg), cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=3, page_size=4, max_seq_len=32,
                          n_pages=8, reserve="none")
        rng = np.random.default_rng(4)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=8) for i in range(4)]
        out = eng.run(reqs)
        assert sorted(out) == list(range(4))
        assert eng.stats["preemptions"] >= 1
        for f in out.values():
            assert f.n_generated == 8
        eng.allocator.check_leaks(0)

    @pytest.mark.slow
    def test_preemption_replay_exact_at_quantized_kv(self):
        """Recompute preemption must not change a request's greedy output
        even at int4 KV: replay rebuilds the quantized pages through the
        same decode path that produced them (re-prefilling generated tokens
        as prompt would read full-precision K/V and diverge)."""
        cfg = _cfg()
        params = _params(cfg)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, 6) for _ in range(4)]
        reqs = [Request(rid=i, prompt=p, max_new_tokens=16)
                for i, p in enumerate(prompts)]
        tight = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=4),
                            max_slots=3, page_size=4, max_seq_len=32,
                            n_pages=9, reserve="none")
        out = tight.run(reqs)
        assert tight.stats["preemptions"] >= 1
        tight.allocator.check_leaks(0)
        for r in reqs:
            solo = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=4),
                               max_slots=1, page_size=4,
                               max_seq_len=32).run([r])
            np.testing.assert_array_equal(solo[r.rid].tokens,
                                          out[r.rid].tokens)

    def test_eos_stops_early(self):
        cfg = _cfg()
        params = _params(cfg)
        probe = ServeEngine(params, cfg, max_slots=1, page_size=8,
                            max_seq_len=32)
        prompt = np.arange(5) % cfg.vocab_size
        first = probe.run([Request(rid=0, prompt=prompt, max_new_tokens=1)])
        eos = int(first[0].tokens[-1])        # greedy ⇒ reproduced below
        eng = ServeEngine(params, cfg, max_slots=1, page_size=8,
                          max_seq_len=32)
        out = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=10,
                               eos_id=eos)])
        assert out[0].reason == "eos"
        assert out[0].n_generated == 1
        eng.allocator.check_leaks(0)

    def test_unsupported_family_raises(self):
        cfg = configs.get_reduced("mamba2-780m")
        with pytest.raises(ValueError, match="SSM"):
            ServeEngine({}, cfg)

    def test_oversized_request_rejected(self):
        cfg = _cfg()
        eng = ServeEngine(_params(cfg), cfg, max_slots=1, page_size=4,
                          max_seq_len=16)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(Request(rid=0, prompt=np.zeros(20, np.int32)))


class TestPageAllocator:
    def test_null_page_reserved(self):
        a = PageAllocator(4)
        got = a.alloc(3)
        assert got is not None and 0 not in got
        assert a.alloc(1) is None             # exhausted, no partial alloc
        a.free(got)
        assert a.n_free == 3
        with pytest.raises(ValueError, match="null page"):
            a.free([0])

    def test_double_free_rejected(self):
        a = PageAllocator(4)
        (pid,) = a.alloc(1)
        a.free([pid])
        with pytest.raises(ValueError, match="double free"):
            a.free([pid])

    def test_leak_check(self):
        a = PageAllocator(4)
        a.alloc(2)
        with pytest.raises(AssertionError, match="leak"):
            a.check_leaks(0)


class TestPageAllocatorRefcount:
    def test_alloc_order_deterministic(self):
        a = PageAllocator(5)
        assert a.alloc(3) == [1, 2, 3]
        assert a.n_used == 3 and a.n_free == 1

    def test_incref_decref_and_n_used(self):
        a = PageAllocator(6)
        ids = a.alloc(2)
        a.incref(ids)                         # rc 2: one page, two sharers
        assert a.refcount(ids[0]) == 2
        assert a.n_used == 2                  # unique pages, not references
        a.free(ids)                           # first sharer drops out
        assert a.refcount(ids[0]) == 1
        assert a.n_used == 2                  # still mapped by the other
        a.free(ids)                           # last reference → really freed
        assert a.n_used == 0 and a.refcount(ids[0]) == 0
        a.check_leaks(0)

    def test_incref_of_free_page_rejected(self):
        a = PageAllocator(4)
        with pytest.raises(ValueError, match="free page"):
            a.incref([2])
        (pid,) = a.alloc(1)
        a.free([pid])
        with pytest.raises(ValueError, match="free page"):
            a.incref([pid])
        with pytest.raises(ValueError, match="null page"):
            a.incref([0])

    def test_double_free_after_full_decref_rejected(self):
        a = PageAllocator(4)
        (pid,) = a.alloc(1)
        a.incref([pid])
        a.free([pid])
        a.free([pid])                         # rc 2 → 1 → 0: both legal
        with pytest.raises(ValueError, match="double free"):
            a.free([pid])


class TestPrefixCache:
    """Trie unit tests against a bare allocator (no engine)."""

    def _cached(self, alloc, cache, prompt):
        """Register ``prompt``'s full pages as an engine drain would: alloc,
        insert (trie takes its ref), then drop the sequence's own refs."""
        ids = alloc.alloc(len(prompt) // cache.page_size)
        cache.insert(prompt, ids)
        alloc.free(ids)
        return ids

    def test_match_capped_below_full_prompt(self):
        """A hit must leave >= 1 suffix token to prefill (the last position's
        logits seed decode), so an exactly-page-aligned prompt matches one
        page short of itself."""
        a = PageAllocator(8)
        cache = PrefixCache(4, a)
        prompt = np.arange(8, dtype=np.int32)
        ids = self._cached(a, cache, prompt)
        assert cache.match(prompt) == ids[:1]          # cap (8-1)//4 = 1
        assert cache.match(np.arange(9, dtype=np.int32)) == ids
        assert cache.match(np.arange(4, 12, dtype=np.int32)) == []

    def test_use_takes_refs_match_does_not(self):
        a = PageAllocator(8)
        cache = PrefixCache(4, a)
        prompt = np.arange(9, dtype=np.int32)
        ids = self._cached(a, cache, prompt)
        assert all(a.refcount(i) == 1 for i in ids)    # trie-only
        cache.match(prompt)
        assert all(a.refcount(i) == 1 for i in ids)    # match is pure
        got = cache.use(prompt)
        assert got == ids
        assert all(a.refcount(i) == 2 for i in ids)    # caller owns one now
        a.free(got)

    def test_evict_skips_referenced_pages_lru_order(self):
        a = PageAllocator(16)
        cache = PrefixCache(4, a)
        p1 = np.arange(0, 5, dtype=np.int32)
        p2 = np.arange(100, 105, dtype=np.int32)
        (id1,) = self._cached(a, cache, p1)
        (id2,) = self._cached(a, cache, p2)
        held = cache.use(p1)                  # sharer pins p1's page
        assert cache.evict(2) == 1            # only p2's page evictable
        assert a.refcount(id2) == 0 and a.refcount(id1) == 2
        assert cache.evict(1) == 0            # p1 still pinned
        a.free(held)
        assert cache.evict(1) == 1            # now trie-only → evictable
        a.check_leaks(0)

    def test_lru_prefers_stalest_leaf(self):
        a = PageAllocator(16)
        cache = PrefixCache(4, a)
        p1 = np.arange(0, 5, dtype=np.int32)
        p2 = np.arange(100, 105, dtype=np.int32)
        (id1,) = self._cached(a, cache, p1)
        (id2,) = self._cached(a, cache, p2)
        cache.use(p1) and a.free(cache.match(p1))      # touch p1 → p2 stalest
        assert cache.evict(1) == 1
        assert a.refcount(id2) == 0 and a.refcount(id1) >= 1

    def test_release_all_drops_every_trie_ref(self):
        a = PageAllocator(16)
        cache = PrefixCache(4, a)
        self._cached(a, cache, np.arange(9, dtype=np.int32))
        self._cached(a, cache, np.arange(50, 63, dtype=np.int32))
        assert cache.n_pages == 5
        assert cache.release_all() == 5
        assert cache.n_pages == 0 and a.n_used == 0
        a.check_leaks(0)


def _family_trace(cfg, n, *, sys_len, n_families=2, max_new=4, seed=5):
    """Requests sharing ``n_families`` fixed system prompts + unique tails."""
    rng = np.random.default_rng(seed)
    fams = [rng.integers(0, cfg.vocab_size, sys_len) for _ in range(n_families)]
    return [Request(rid=i,
                    prompt=np.concatenate([
                        fams[i % n_families],
                        rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(2, 6)))]),
                    max_new_tokens=max_new)
            for i in range(n)]


class TestPrefixSharing:
    @pytest.mark.parametrize("kv_bits", [0, 8, 4])
    def test_prefix_hit_token_identical_to_cold(self, kv_bits):
        """Warm (prefix hits) greedy tokens == cold chunked engine (empty
        cache) tokens at every KV precision — hits skip chunks whose values
        the fixed-width chunk fn would recompute bit-identically."""
        cfg = _cfg()
        params = _params(cfg)
        kw = dict(plan=PrecisionPlan(kv_bits=kv_bits), max_slots=3,
                  page_size=4, max_seq_len=32, chunk_pages=2)
        reqs = _family_trace(cfg, 6, sys_len=8)
        cold = ServeEngine(params, cfg, **kw).run(reqs)
        warm_eng = ServeEngine(params, cfg, prefix_cache=True, **kw)
        warm = warm_eng.run(reqs)
        assert warm_eng.stats["prefix_hits"] >= 1
        for rid in cold:
            np.testing.assert_array_equal(cold[rid].tokens, warm[rid].tokens)
        warm_eng.release_prefix_cache()
        warm_eng.allocator.check_leaks(0)

    def test_shared_pages_never_rewritten(self):
        """COW invariant: a shared prefix page's quantized codes are
        bit-identical before and after other sharers prefill + decode over
        it, and refcounts drop back to trie-only at drain."""
        cfg = _cfg()
        eng = ServeEngine(_params(cfg), cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=2, page_size=4, max_seq_len=32,
                          prefix_cache=True, chunk_pages=1)
        reqs = _family_trace(cfg, 5, sys_len=8, n_families=1)
        eng.run([reqs[0]])
        # the family's shared pages: the 8-token system prompt = pages 0-1
        # (reqs[0]'s private suffix pages are cached too — not shared)
        shared = eng.prefix.match(reqs[0].prompt[:9])
        assert len(shared) == 2
        before = np.asarray(eng.pool.k_pages[:, shared])
        eng.run(reqs[1:])                     # two cohabiting sharers at once
        assert eng.stats["prefix_hits"] == 4
        after = np.asarray(eng.pool.k_pages[:, shared])
        np.testing.assert_array_equal(before, after)
        assert all(eng.allocator.refcount(i) == 1 for i in shared)
        assert eng.release_prefix_cache() >= 2
        eng.allocator.check_leaks(0)

    def test_sharer_preemption_never_frees_mapped_pages(self):
        """A pool too small for everyone: preemption decrefs the victim's
        shared pages but the trie + surviving sharers still hold them, so
        every request replays to its solo output and the pool drains clean."""
        cfg = _cfg()
        params = _params(cfg)
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=3, page_size=4, max_seq_len=32,
                          n_pages=10, reserve="none",
                          prefix_cache=True, chunk_pages=1)
        reqs = _family_trace(cfg, 5, sys_len=8, n_families=1, max_new=6)
        out = eng.run(reqs)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["prefix_hits"] >= 1
        for r in reqs:
            solo = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                               max_slots=1, page_size=4,
                               max_seq_len=32).run([r])
            np.testing.assert_array_equal(solo[r.rid].tokens,
                                          out[r.rid].tokens)
        eng.release_prefix_cache()
        eng.allocator.check_leaks(0)


class TestChunkedPrefill:
    def test_chunked_matches_monolithic_bf16(self):
        """chunk_pages=1 at bf16 KV reproduces the monolithic engine's greedy
        tokens exactly (no quantization → write-then-attend is lossless)."""
        cfg = _cfg()
        params = _params(cfg)
        reqs = _family_trace(cfg, 4, sys_len=9)
        mono = ServeEngine(params, cfg, max_slots=2, page_size=4,
                           max_seq_len=32).run(reqs)
        eng = ServeEngine(params, cfg, max_slots=2, page_size=4,
                          max_seq_len=32, chunk_pages=1)
        chunked = eng.run(reqs)
        for rid in mono:
            np.testing.assert_array_equal(mono[rid].tokens,
                                          chunked[rid].tokens)
        assert eng.stats["prefill_chunks"] >= 4
        assert eng.stats["max_prefill_tokens_per_step"] <= 4
        eng.allocator.check_leaks(0)

    def test_decode_interleaves_with_prefill(self):
        """While a long prompt trickles in chunk by chunk, already-admitted
        sequences keep decoding: some step must advance both."""
        cfg = _cfg()
        rng = np.random.default_rng(9)
        eng = ServeEngine(_params(cfg), cfg, max_slots=2, page_size=4,
                          max_seq_len=64, chunk_pages=1)
        eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 5),
                           max_new_tokens=12))
        eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, 40),
                           max_new_tokens=4))
        out, both = {}, 0
        while eng.busy:
            pf0 = eng.stats["prefill_tokens"]
            ds0 = eng.stats["decode_steps"]
            for f in eng.step():
                out[f.rid] = f
            if (eng.stats["prefill_tokens"] > pf0
                    and eng.stats["decode_steps"] > ds0):
                both += 1
        assert both >= 3                      # 40-token prompt = 10 chunks
        assert sorted(out) == [0, 1]
        eng.allocator.check_leaks(0)


class TestSchedulerBugfixes:
    """Regression pins for the serving-scheduler bug sweep."""

    def test_preemption_preserves_t_submit(self):
        """The requeued victim keeps its original submit timestamp — a
        restarted clock would zero the admission-wait signal the autoscaler
        governs on. Virtual clock: submits at t=0,1,2,3; preemption later."""
        cfg = _cfg()
        clk = [0.0]
        eng = ServeEngine(_params(cfg), cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=3, page_size=4, max_seq_len=32,
                          n_pages=8, reserve="none", clock=lambda: clk[0])
        rng = np.random.default_rng(4)
        submit_t = {}
        for i in range(4):
            clk[0] = float(i)
            eng.submit(Request(rid=i,
                               prompt=rng.integers(0, cfg.vocab_size, 6),
                               max_new_tokens=8))
            submit_t[i] = clk[0]
        seen_requeue = False
        out = {}
        while eng.busy:
            clk[0] += 1.0
            for f in eng.step():
                out[f.rid] = f
            if eng.stats["preemptions"] and eng._queue:
                head = eng._queue[0]
                if head["replay"].size:       # a preempted request, mid-gen
                    seen_requeue = True
                    assert head["t_submit"] == submit_t[head["req"].rid]
        assert eng.stats["preemptions"] >= 1 and seen_requeue
        assert sorted(out) == list(range(4))
        eng.allocator.check_leaks(0)

    def test_decode_timing_reads_injected_clock(self):
        """decode_times must come from the injected clock, not a hardwired
        perf_counter: under a frozen virtual clock every steady-state decode
        step measures exactly 0.0 s."""
        cfg = _cfg()
        eng = ServeEngine(_params(cfg), cfg, max_slots=2, page_size=8,
                          max_seq_len=32, clock=lambda: 0.0)
        rng = np.random.default_rng(2)
        eng.run([Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6),
                         max_new_tokens=6) for i in range(3)])
        assert len(eng.decode_times) >= 1
        assert all(dt == 0.0 for dt in eng.decode_times)
        assert eng.stats["decode_seconds"] == 0.0

    def test_autoscaler_actuation_deferred_during_replay(self):
        """A rung move requested while a preemption replay is in flight must
        not land until the replay drains — replayed KV has to be rebuilt
        under the weights that produced it. The stub governor demands 4 bits
        exactly when a replay is pending, so any actuation would change
        weights mid-replay; deferral keeps the run token-identical to a
        fixed-8-bit engine."""
        from repro.precision.qat import quantize_param_tree

        cfg = _cfg()
        params = quantize_param_tree(_params(cfg), bits=8, layout="bitplane")

        class ReplayDipGovernor:
            def __init__(self, engine_ref):
                self.engine_ref = engine_ref
                self.calls = []

            def observe(self, *, admit_wait_ms, queue_depth, now=None):
                replaying = self.engine_ref[0]._replaying()
                self.calls.append(replaying)
                return 4 if replaying else 8

        ref = [None]
        gov = ReplayDipGovernor(ref)
        kw = dict(plan=PrecisionPlan(kv_bits=8), max_slots=3, page_size=4,
                  max_seq_len=32, n_pages=8, reserve="none")
        eng = ServeEngine(params, cfg, autoscaler=gov, **kw)
        ref[0] = eng
        rng = np.random.default_rng(4)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=8) for i in range(4)]
        out = eng.run(reqs)
        assert eng.stats["preemptions"] >= 1
        assert any(gov.calls), "governor never saw a replay in flight"
        assert eng.weight_bits == 8           # every drop request deferred
        fixed = ServeEngine(params, cfg, **kw).run(
            [Request(rid=r.rid, prompt=r.prompt, max_new_tokens=8)
             for r in reqs])
        for rid in out:
            np.testing.assert_array_equal(out[rid].tokens, fixed[rid].tokens)
        eng.allocator.check_leaks(0)


class TestAttnBlocksCounter:
    def test_attn_blocks_counts_live_blocks_per_decode_step(self):
        """``stats['attn_blocks']`` = Σ over decode steps and active slots of
        cdiv(cdiv(len, page), pages_per_block), ``len`` counting the row the
        step appends — read here from the arguments each step dispatches."""
        cfg = _cfg()
        page = 16
        eng = ServeEngine(_params(cfg), cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=3, page_size=page, max_seq_len=320)
        ppb = pa_mod.pages_per_block(page, eng.max_pages_per_seq,
                                     cfg.n_kv_heads, cfg.head_dim, 8)
        assert ppb * page < 250 + 8          # one slot crosses a block
        seen = []
        wrapped = eng._decode_jit

        def spy(sampled):
            fn = wrapped(sampled)

            def call(params, pool, tokens, positions, bt, active, *rest):
                seen.append((np.array(positions), np.array(active)))
                return fn(params, pool, tokens, positions, bt, active, *rest)
            return call

        eng._decode_jit = spy
        rng = np.random.default_rng(3)
        eng.run([Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n),
                         max_new_tokens=8)
                 for i, n in enumerate([5, 250, 60])])
        want = 0
        for pos, act in seen:
            pages = -(-(pos[act] + 1) // page)
            want += int((-(-pages // ppb)).sum())
        assert seen and eng.stats["attn_blocks"] == want
        assert want > sum(int(a.sum()) for _, a in seen)   # some take two


class TestKVBytesAccounting:
    def test_pool_nbytes_ratios(self):
        """QTensor.nbytes accounting: int8 ≈ 2× and packed int4 ≥ 3× fewer
        KV bytes than bf16 at head_dim 64 (scales included)."""
        kw = dict(n_layers=2, n_pages=8, page_size=8, n_kv=2, head_dim=64)
        nb = {bits: pg.pool_nbytes(pg.init_pool(**kw, kv_bits=bits))
              for bits in (0, 8, 4)}
        assert nb[0] / nb[8] >= 1.8
        assert nb[0] / nb[4] >= 3.0


def _bitplane_params(cfg, bits=8):
    from repro.precision.qat import quantize_param_tree

    return quantize_param_tree(_params(cfg), bits=bits, layout="bitplane")


class TestSpeculativeDecoding:
    """Self-speculative decoding: low-bit draft + full-precision verify.

    The guarantee under test: speculation is an *execution strategy*, not a
    model change — outputs are token-identical to vanilla decode at every
    (kv_bits × draft_bits) combination, greedy and sampled, through
    rejections, page-boundary crossings, and preemptions."""

    def _reqs(self, cfg, n=4, max_new=8, seed=11, **kw):
        rng = np.random.default_rng(seed)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(4, 12))),
                        max_new_tokens=max_new, **kw)
                for i in range(n)]

    @pytest.mark.parametrize("kv_bits", [0, 8, 4])
    def test_greedy_token_identical_every_draft_bits(self, kv_bits):
        """Spec output == vanilla output exactly, at bf16/int8/int4 KV ×
        {4,2}-bit drafts. Accepted rows are minted by the verify pass's own
        full-precision write-then-attend, so this holds regardless of how
        wrong the low-bit draft is."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        kw = dict(plan=PrecisionPlan(kv_bits=kv_bits), max_slots=2,
                  page_size=4, max_seq_len=32)
        reqs = self._reqs(cfg)
        vanilla = ServeEngine(params, cfg, **kw).run(reqs)
        for draft_bits in (4, 2):
            eng = ServeEngine(params, cfg, spec_decode=3,
                              draft_bits=draft_bits, **kw)
            out = eng.run(reqs)
            assert eng.stats["spec_steps"] >= 1
            assert eng.stats["spec_draft_tokens"] > 0
            for rid in vanilla:
                np.testing.assert_array_equal(out[rid].tokens,
                                              vanilla[rid].tokens)
            eng.allocator.check_leaks(0)

    def test_spec_zero_degenerates_to_vanilla(self):
        """spec_decode=0 is the vanilla engine bit-for-bit: same tokens,
        no speculative counters, NaN acceptance."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        kw = dict(plan=PrecisionPlan(kv_bits=8), max_slots=2, page_size=4,
                  max_seq_len=32)
        reqs = self._reqs(cfg)
        vanilla = ServeEngine(params, cfg, **kw).run(reqs)
        eng = ServeEngine(params, cfg, spec_decode=0, **kw)
        out = eng.run(reqs)
        for rid in vanilla:
            np.testing.assert_array_equal(out[rid].tokens,
                                          vanilla[rid].tokens)
        assert eng.stats["spec_steps"] == 0
        assert eng.stats["spec_draft_tokens"] == 0
        assert np.isnan(eng.acceptance_rate())

    def test_first_draft_token_rejection_recovers(self):
        """A window whose *first* draft token is wrong commits exactly one
        token (the verify chain's), and the run still matches vanilla. The
        2-bit draft on random weights is wrong often enough that such a
        window provably occurs in this trace."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        kw = dict(plan=PrecisionPlan(kv_bits=8), max_slots=2, page_size=4,
                  max_seq_len=48)
        reqs = self._reqs(cfg, n=3, max_new=12, seed=13)
        vanilla = ServeEngine(params, cfg, **kw).run(reqs)
        eng = ServeEngine(params, cfg, spec_decode=3, draft_bits=2, **kw)
        for r in reqs:
            eng.submit(r)
        out, first_rejected = {}, False
        while eng.busy:
            before = {s: len(eng._slots[s]["gen"])
                      for s in range(eng.max_slots) if eng._active[s]}
            sp0 = eng.stats["spec_steps"]
            for f in eng.step():
                out[f.rid] = f
            if eng.stats["spec_steps"] > sp0:
                for s, n0 in before.items():
                    st = eng._slots[s]
                    # +1 token and still running ⇒ the window accepted no
                    # draft token, only verify's correction
                    if st is not None and len(st["gen"]) == n0 + 1:
                        first_rejected = True
        assert first_rejected, "no window rejected its first draft token"
        assert eng.acceptance_rate() < 1.0
        for rid in vanilla:
            np.testing.assert_array_equal(out[rid].tokens,
                                          vanilla[rid].tokens)
        eng.allocator.check_leaks(0)

    def test_window_crosses_page_boundary(self):
        """k+1 == page_size: every unaligned window spans two pages, so the
        scratch-tail page allocation and the cross-page verify scatter are
        exercised on nearly every step."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        rng = np.random.default_rng(17)
        # prompt of 7 → first window rows 7..10 straddle pages 1|2 (page 4)
        reqs = [Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 7),
                        max_new_tokens=10)]
        kw = dict(plan=PrecisionPlan(kv_bits=4), max_slots=1, page_size=4,
                  max_seq_len=32)
        vanilla = ServeEngine(params, cfg, **kw).run(reqs)
        eng = ServeEngine(params, cfg, spec_decode=3, draft_bits=4, **kw)
        out = eng.run(reqs)
        assert eng.stats["spec_steps"] >= 2
        np.testing.assert_array_equal(out[0].tokens, vanilla[0].tokens)
        eng.allocator.check_leaks(0)

    def test_preemption_with_uncommitted_draft_tail_leak_free(self):
        """Pool pressure preempting a slot that ran speculative windows:
        the window's scratch pages joined the slot's page list at
        allocation, so preemption frees them — no leak, and every request
        still replays to its solo (vanilla) output."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=3, page_size=4, max_seq_len=32,
                          n_pages=10, reserve="none",
                          spec_decode=3, draft_bits=4)
        rng = np.random.default_rng(4)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6),
                        max_new_tokens=8) for i in range(4)]
        out = eng.run(reqs)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["spec_steps"] >= 1
        for r in reqs:
            solo = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                               max_slots=1, page_size=4,
                               max_seq_len=32).run([r])
            np.testing.assert_array_equal(solo[r.rid].tokens,
                                          out[r.rid].tokens)
        eng.allocator.check_leaks(0)

    def test_sampled_verify_token_identical(self):
        """temperature > 0: the verify pass samples every window position
        with the same fold_in(base, position) key sequential decode would
        use, so a mixed greedy/sampled batch stays token-identical to
        vanilla."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        rng = np.random.default_rng(3)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 4 + 3 * i),
                        max_new_tokens=6,
                        temperature=0.8 if i % 2 else 0.0,
                        top_k=5 if i % 2 else 0, seed=7)
                for i in range(4)]
        kw = dict(plan=PrecisionPlan(kv_bits=8), max_slots=2, page_size=4,
                  max_seq_len=32)
        vanilla = ServeEngine(params, cfg, **kw).run(reqs)
        eng = ServeEngine(params, cfg, spec_decode=3, draft_bits=4, **kw)
        out = eng.run(reqs)
        assert eng.stats["spec_steps"] >= 1
        for rid in vanilla:
            np.testing.assert_array_equal(out[rid].tokens,
                                          vanilla[rid].tokens)
        eng.allocator.check_leaks(0)

    def test_decode_tokens_counted_exactly_once(self):
        """Exactly-once token accounting under speculation: a slot hitting
        eos or budget mid-window discards the rest of the accepted prefix,
        and only committed tokens count — ``decode_tokens`` must equal
        Σ (n_generated − 1) (the first token of each request comes from
        prefill). Frozen injected clock also pins that window timing reads
        the engine clock (every steady window measures 0.0 s)."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        probe = ServeEngine(params, cfg, max_slots=1, page_size=4,
                            max_seq_len=48)
        reqs = self._reqs(cfg, n=4, max_new=10, seed=19)
        ref = probe.run([reqs[0]])
        eos = int(ref[0].tokens[-4])          # forces an early mid-window eos
        clk = [0.0]
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=2, page_size=4, max_seq_len=48,
                          spec_decode=3, draft_bits=4,
                          clock=lambda: clk[0])
        out = eng.run([dataclasses.replace(r, eos_id=eos) for r in reqs])
        assert eng.stats["spec_steps"] >= 1
        assert any(f.reason == "eos" for f in out.values())
        total = sum(f.n_generated - 1 for f in out.values())
        assert eng.stats["decode_tokens"] == total
        assert eng.stats["decode_seconds"] == 0.0
        assert all(dt == 0.0 for dt in eng.decode_times)
        eng.allocator.check_leaks(0)

    def test_autoscaler_drop_to_draft_bits_disables_spec(self):
        """Serving bits at (or below) draft_bits make the draft pure
        overhead: speculation must pause after a rung drop and resume on
        restore, with actuation only ever landing between windows."""
        cfg = _cfg()
        params = _bitplane_params(cfg)
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=2, page_size=4, max_seq_len=64,
                          spec_decode=3, draft_bits=4)
        rng = np.random.default_rng(23)
        eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 6),
                           max_new_tokens=40))
        while eng.busy and not eng.stats["spec_steps"]:
            eng.step()
        assert eng.stats["spec_steps"] >= 1
        eng.set_weight_bits(4)                # == draft_bits → spec off
        frozen = eng.stats["spec_steps"]
        for _ in range(4):
            if eng.busy:
                eng.step()
        assert eng.stats["spec_steps"] == frozen
        eng.set_weight_bits(8)                # restored → spec resumes
        while eng.busy:
            eng.step()
        assert eng.stats["spec_steps"] > frozen
        eng.allocator.check_leaks(0)

    def test_constructor_validation(self):
        cfg = _cfg()
        params = _bitplane_params(cfg)
        dense = _params(cfg)
        with pytest.raises(ValueError, match="spec_decode"):
            ServeEngine(params, cfg, spec_decode=-1, draft_bits=4)
        with pytest.raises(ValueError, match="draft_bits"):
            ServeEngine(params, cfg, spec_decode=2)
        with pytest.raises(ValueError, match="draft_bits"):
            ServeEngine(params, cfg, draft_bits=4)
        with pytest.raises(ValueError, match="bitplane"):
            ServeEngine(dense, cfg, spec_decode=2, draft_bits=4)
