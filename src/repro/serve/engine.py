"""Continuous-batching serving engine over the paged quantized KV pool.

The ZipML/MLWeaving serving thesis is that inference is data-movement-bound,
so int8/int4 KV storage buys near-linear decode speedups — but a fixed-batch
fixed-length loop (the old launch/serve.py) can't exploit it under real
traffic. This engine serves a **mixed stream**: requests with arbitrary
prompt/generation lengths are admitted into decode slots as they fit,
decode runs one batched step over every live sequence, and finished
sequences free their pages immediately for the next admission.

Scheduling model (Orca-style iteration-level batching):

* ``submit()`` queues requests FIFO; ``step()`` = admit → prefill-chunk →
  ensure-pages → one batched decode.
* **admission**: a request is admitted when a decode slot is free and the
  allocator can hand it its pages — ``reserve='full'`` takes the worst-case
  page count up front (no mid-flight eviction, ever); ``reserve='none'``
  takes only the prompt pages and grows on demand. Monolithic-reserve-only
  admission (the ``reserve`` knob with neither ``prefix_cache`` nor
  ``chunk_pages``) is the legacy compatibility mode — chunked admission
  bounds the per-step prefill stall and is the intended serving default.
* **prefill** has two paths:

  - *monolithic* (default, bit-exact with the pre-chunking engine): per
    request at its page-bucketed prompt length through the unmodified
    ``transformer.prefill``; the raw post-RoPE K/V rows are then quantized
    per (token, head) and scattered into pages — bit-identical codes to the
    legacy ring buffer.
  - *chunked* (``chunk_pages=N`` or ``prefix_cache=True``, Orca/Sarathi
    style): the prompt runs through fixed-width chunks of ``N`` pages at
    absolute page-aligned boundaries, one chunk per scheduler step,
    interleaved with decode — a 2k-token admission no longer stalls the
    live batch. Each chunk **writes its quantized K/V pages first, then
    attends the dequantized context** (exactly the decode step's
    append-then-attend order), so a token's KV codes are the same whether
    it arrived via chunked prefill, decode, or replay — which is what makes
    prefix reuse exact (below). One jit compile total: every chunk call has
    the same static shapes.

* **prefix reuse** (``prefix_cache=True``): a radix/trie index
  (:class:`repro.serve.prefix.PrefixCache`) maps page-aligned token runs of
  completed prompts to their pool pages. Admission walks the trie and
  points the new block-table row at the shared pages (copy-on-write by
  refcount: full pages are immutable — decode only appends past them — and
  are freed only when the last sharer *and* the trie drop them; the partial
  tail page is always private). The chunks fully covered by the hit are
  skipped outright; the first partially-covered chunk is recomputed at
  identical shapes but writes to the null page instead of the shared pages.
  A warm admission therefore executes byte-identical chunk calls to a cold
  one — prefix-hit outputs are **bit-identical to cold-start at every
  kv-bits setting by construction**, not by tolerance.
* **decode** is one jitted step over all ``max_slots`` slots: append each
  slot's token KV into its current page (inactive slots write to the null
  page), run the paged-attention op through the kernel registry (ref or
  Pallas), sample with per-request keys (greedy / temperature / top-k).
* **eviction/preemption** (``reserve='none'``): when a sequence needs a page
  and none is free, the engine first evicts unreferenced prefix-cache
  leaves, then preempts the youngest sequence — its pages return to the
  pool and it is re-queued (front, original ``t_submit`` preserved so the
  admission-latency signal keeps accruing) carrying its generated tokens as
  a **replay list**. Re-admission recomputes: prefill the original prompt
  (same calls as the first admission — chunked prefill is deterministic and
  prefix hits are exact, so the rebuilt pages carry identical codes), then
  force-feed the replayed tokens through ordinary decode steps (batched
  with everyone else) instead of sampling. That rebuilds the quantized KV
  pages through the *same* computation path that produced them, so the
  post-replay continuation is bit-identical to the never-preempted run —
  re-prefilling generated tokens as prompt would instead read
  full-precision K/V where the original decode read quantized pages, and
  diverge.

* **self-speculative decoding** (``spec_decode=k, draft_bits=b``): the
  low-bit model sliced from the served bit-plane artifact
  (``slice_planes(b)`` — zero extra weight memory) drafts ``k`` greedy
  tokens per slot through the ordinary paged decode step, writing
  **scratch** KV rows past each slot's committed length; one batched
  full-precision forward then scores all ``k+1`` window positions
  (embed→rope→quantize-K/V→scatter→attend, the chunked-prefill shape), and
  the longest prefix of draft tokens matching the verify chain is committed
  — their KV rows were minted *by the verify pass itself* (write-then-
  attend), so accepted rows are byte-identical to what sequential decode
  would have written. Rollback is free: rejected rows sit past ``_lens``
  (masked garbage, overwritten by the next window). Greedy output is
  **token-identical to vanilla greedy decode by construction**; with
  temperature > 0 the window samples with the same per-(request, position)
  keys sequential decode uses, so sampled chains are identical too —
  acceptance just compares the greedy draft against the sampled target.
  The engine falls back to a vanilla step while a replay is in flight,
  when any active slot lacks ``k+1`` rows of page runway, or when the
  autoscaler has dropped serving bits to (or below) the draft's — and
  autoscaler actuation happens at the top of ``step()``, so a bits change
  can never land mid-window (the same deferral discipline as replay).

* **precision autoscaling** (optional): bit-plane weights
  (``quantize_param_tree(..., layout='bitplane')``) make serving precision a
  per-step dial — ``set_weight_bits(k)`` swaps in a cached
  ``slice_planes(k)`` view of every weight (zero repack, no reload; decode
  streams (k+1)/(B+1) of the code bytes). Attach a
  :class:`repro.serve.autoscaler.PrecisionAutoscaler` and ``step()`` feeds
  it the head-of-line admission wait + queue depth (queued requests plus
  slots still chunk-prefilling — admitted-but-not-decoding work is load the
  governor must see) each iteration and actuates the bits it returns.
  Actuation is **deferred while any replay is in flight** (a slot holds
  ``replay_left`` or a requeued entry carries replay tokens): switching
  weight bits between eviction and replay would rebuild the replayed KV
  under different weights than the original decode and break the bit-exact
  replay invariant above. The governor still observes every step; the rung
  move lands on the first replay-free step. An actuated bits change also
  flushes the prefix cache and marks in-flight prefills non-cacheable —
  pages computed under other weights must never serve a prefix hit.

Invariants the tests pin: every admitted request finishes; no page leaks
(shared pages freed exactly at refcount 0); per-request outputs are
independent of batch composition; paged decode matches the legacy ring
path; shared prefix pages are never written after sharing.

Throughput accounting deliberately excludes the first decode call (jit
compile) — ``stats['decode_seconds']`` is steady-state only, the fix the
old serve loop needed (its t0 sat before compilation). All scheduler timing
goes through the injectable ``clock`` (``admit_waits``, ``decode_times`` and
each request's submit, admission and per-token commit times in
:class:`Finished`), so virtual-clock replays never mix real and virtual
time.

Tracing: each scheduler phase runs in a host span (``serve.step`` holding
``serve.admit``, ``serve.prefill_chunk``, ``serve.first_token``,
``serve.decode.dispatch``, ``serve.decode.wait``, ``serve.commit`` and
``serve.spec``; ``serve.page_checksum`` around the prefix cache's page read),
and each jitted program has a stable name (``serve_decode``, ``serve_chunk``,
``serve_prefill``, ``serve_draft``, ``serve_verify``, ``serve_sample_first``,
``serve_logits``), so a profiler trace attributes host and device time to
them. A span costs under a microsecond while no trace is being taken.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
import zlib
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation as span

from repro.kernels import paged_attn as pa_mod
from repro.kernels import registry
from repro.kernels.ops import kv_bits_of
from repro.kernels.ref import dequant_pages_ref, gather_pages_ref
from repro.models import attention as attn
from repro.models import transformer as T
from repro.models.layers import apply_rope, dense, embed
from repro.quant import PrecisionPlan, QTensor
from repro.serve import pages as pg
from repro.serve import sampling
from repro.serve.prefix import PrefixCache

SUPPORTED_FAMILIES = ("dense", "moe", "audio")


@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request. ``temperature<=0`` → greedy; ``top_k<=0`` → no
    top-k filter; ``eos_id=None`` → length-only stopping."""

    rid: int
    prompt: Any                      # 1-D int array-like of token ids
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    eos_id: int | None = None
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class Finished:
    """A request's outcome. Times are on the engine's clock: ``t_submit``
    when it was queued, ``t_admit`` when it first took a slot, and
    ``token_times[i]`` when generated token ``i`` was first committed (a
    token replayed after preemption or migration keeps the time the user
    first saw it; NaN where no time was recorded)."""

    rid: int
    tokens: np.ndarray               # prompt + generated, 1-D int32
    prompt_len: int
    n_generated: int
    reason: str                      # 'eos' | 'length' | 'nan' | 'retries'
    t_submit: float = math.nan
    t_admit: float = math.nan
    token_times: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.float64))


def _finished(src: dict, tokens: np.ndarray, n_generated: int,
              reason: str) -> Finished:
    """A Finished from a slot's state or a queue entry (both carry the
    request, its prompt, ``t_submit`` and the commit ``times``)."""
    times = np.full((n_generated,), np.nan)
    seen = src.get("times", ())[:n_generated]
    times[:len(seen)] = seen
    t_admit = src.get("t_admit")
    return Finished(rid=src["req"].rid, tokens=tokens,
                    prompt_len=len(src["prompt"]), n_generated=n_generated,
                    reason=reason, t_submit=src["t_submit"],
                    t_admit=math.nan if t_admit is None else t_admit,
                    token_times=times)


def _commit(state: dict, tok: int, now: float) -> None:
    """Append a generated token; a replayed token keeps its first commit
    time, a new one is stamped ``now``."""
    state["gen"].append(tok)
    if len(state["times"]) < len(state["gen"]):
        state["times"].append(now)


class ServeEngine:
    def __init__(self, params, cfg, *, plan: PrecisionPlan | None = None,
                 max_slots: int = 4, page_size: int = 8,
                 max_seq_len: int = 128, n_pages: int | None = None,
                 reserve: str = "full", backend: str | None = None,
                 autoscaler=None, clock=None, prefix_cache: bool = False,
                 chunk_pages: int | None = None, spec_decode: int = 0,
                 draft_bits: int | None = None, fault_injector=None,
                 replica_id: int = 0, retry_budget: int = 32):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise ValueError(
                f"ServeEngine supports {SUPPORTED_FAMILIES} families, "
                f"got {cfg.family!r} (SSM/hybrid/VLM caches are not paged yet)")
        if cfg.window:
            raise ValueError("sliding-window models are not paged yet")
        if reserve not in ("full", "none"):
            raise ValueError(f"reserve must be 'full' or 'none', got {reserve!r}")
        plan = plan if plan is not None else cfg.precision
        self.cfg = dataclasses.replace(cfg, precision=plan)
        # prefill runs with kv_bits=0: the ring cache it fills is then the
        # raw post-RoPE K/V, which the pool quantizes page-wise itself
        self._cfg_fp = dataclasses.replace(
            cfg, precision=dataclasses.replace(plan, kv_bits=0))
        self.plan = plan
        self.params = params
        self.backend = backend
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.max_pages_per_seq = pg.pages_needed(max_seq_len, page_size)
        self.reserve = reserve
        if n_pages is None:
            n_pages = self.max_slots * self.max_pages_per_seq + 1
        self.allocator = pg.PageAllocator(n_pages)
        self.pool = pg.init_pool(
            cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim,
            kv_bits=plan.kv_bits, dtype=cfg.dtype)

        # prefix sharing requires the chunked path: a prefix hit attends
        # quantized shared pages, so the cold computation that *minted* them
        # must have attended its own quantized pages the same way
        if chunk_pages is not None and int(chunk_pages) < 1:
            raise ValueError(f"chunk_pages must be >= 1, got {chunk_pages}")
        self.chunk_pages = (min(int(chunk_pages), self.max_pages_per_seq)
                            if chunk_pages is not None
                            else (self.max_pages_per_seq if prefix_cache
                                  else None))
        self._chunked = self.chunk_pages is not None
        self.prefix = (PrefixCache(self.page_size, self.allocator)
                       if prefix_cache else None)

        B, MP = self.max_slots, self.max_pages_per_seq
        self._bt = np.zeros((B, MP), np.int32)
        self._lens = np.zeros((B,), np.int32)
        self._active = np.zeros((B,), bool)
        self._temps = np.zeros((B,), np.float32)
        self._topks = np.zeros((B,), np.int32)
        self._base_keys = np.zeros((B, 2), np.uint32)
        self._last_tok = np.zeros((B,), np.int32)
        self._slots: list[dict | None] = [None] * B
        self._queue: collections.deque = collections.deque()
        self._admit_seq = 0
        self._compiled_variants: set[tuple] = set()
        self.stats = {"admitted": 0, "finished": 0, "preemptions": 0,
                      "decode_steps": 0, "decode_tokens": 0,
                      "decode_seconds": 0.0, "steady_decode_tokens": 0,
                      "prefill_tokens": 0, "prefill_chunks": 0,
                      "max_prefill_tokens_per_step": 0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_hit_tokens": 0, "spec_steps": 0,
                      "spec_draft_tokens": 0, "spec_accepted_tokens": 0,
                      "quarantined": 0, "retries_exhausted": 0,
                      "kv_flips": 0, "attn_blocks": 0}
        # page blocks of kernels/paged_attn.py: ``attn_blocks`` sums, over
        # decode steps, the blocks one layer's call reads for the live slots
        self._attn_ppb = pa_mod.pages_per_block(
            self.page_size, self.max_pages_per_seq, cfg.n_kv_heads,
            cfg.head_dim, plan.kv_bits)
        self.admit_waits: list[float] = []      # per-admission queue wait, s
        self.decode_times: list[float] = []     # steady per-step decode, s
        self._clock = clock if clock is not None else time.perf_counter
        self.autoscaler = autoscaler
        # fault tolerance: an optional deterministic injector polled once
        # per scheduler step (nan_logits / kv_flip fire at this seam), a
        # poison set marking requests whose next logits must be treated as
        # non-finite, and a per-request retry budget — a request that keeps
        # getting preempted or migrated off dying replicas eventually fails
        # with reason='retries' instead of circulating forever
        self._faults = fault_injector
        self.replica_id = int(replica_id)
        if retry_budget < 1:
            raise ValueError(f"retry_budget must be >= 1, got {retry_budget}")
        self.retry_budget = int(retry_budget)
        self._poison_rids: set[int] = set()
        self._step_no = 0
        if self.prefix is not None:
            # integrity guard: stamp trie pages with a content checksum at
            # insert, re-verified at use — see PrefixCache / _page_checksum
            self.prefix.checksum_fn = self._page_checksum
        self._params_full = params
        self._params_by_bits: dict[int, Any] = {}
        self.weight_bits: int | None = None     # None until set_weight_bits

        # self-speculative decoding: the b-bit draft is a zero-copy
        # slice_planes view of the served bit-plane artifact — built (and
        # validated) eagerly so a spec engine without bitplane weights
        # fails at construction, not mid-trace
        if int(spec_decode) < 0:
            raise ValueError(f"spec_decode must be >= 0, got {spec_decode}")
        if spec_decode and draft_bits is None:
            raise ValueError(
                "spec_decode needs draft_bits (the low-bit draft view, "
                "e.g. draft_bits=4)")
        if draft_bits is not None and not spec_decode:
            raise ValueError("draft_bits without spec_decode has no effect")
        self.spec_decode = int(spec_decode)
        self.draft_bits = int(draft_bits) if draft_bits is not None else None
        self._params_draft = (self._sliced_tree(self.draft_bits)
                              if self.spec_decode else None)

        # two decode variants: the greedy-only one skips the sort +
        # categorical machinery entirely (the common case); lazily compiled
        self._decode_jits: dict[bool, Any] = {}
        self._logits_jits: dict[str | None, Any] = {}
        self._prefill_jits: dict[int, Any] = {}
        self._chunk_jit_fn = None
        self._draft_jit_fn = None
        self._verify_jits: dict[bool, Any] = {}

        def serve_sample_first(lg, t, k, key):
            return sampling.sample_tokens(lg[None], t[None], k[None],
                                          key[None])[0]

        self._sample1 = jax.jit(serve_sample_first)

    # ------------------------------------------------------------ device fns
    def _make_decode_forward(self, kb):
        """One paged decode step's forward under kernel backend ``kb``:
        append each slot's token KV, attend, and return the (B, V) logits
        with the pool as it would stand after the step."""
        cfg, spec = self.cfg, self.cfg.attn_spec
        page = self.page_size

        def forward(params, pool, tokens, positions, block_table, active):
            b = tokens.shape[0]
            pos = positions.astype(jnp.int32)
            x = embed(params["embed"], tokens).astype(cfg.dtype)      # (B,1,d)
            page_ids = jnp.take_along_axis(
                block_table, (pos // page)[:, None], axis=1)[:, 0]
            page_ids = jnp.where(active, page_ids, 0)                 # null page
            offs = pos % page
            new_lens = pos + active.astype(jnp.int32)

            def body(h, inp):
                layer, kp, vp, ks, vs = inp
                box = {}

                def attend(z):
                    q, k, v = attn.decode_qkv(layer["attn"], z, spec,
                                              pos[:, None])
                    kp2, vp2, ks2, vs2 = pg.append_rows(
                        kp, vp, ks, vs, k[:, 0], v[:, 0], page_ids, offs)
                    box["planes"] = (kp2, vp2, ks2, vs2)
                    out = kb.paged_attention(
                        q[:, 0], kp2, vp2, ks2, vs2, block_table, new_lens,
                        softmax_scale=spec.scale)
                    return dense(layer["attn"]["o"], out.reshape(
                        b, 1, spec.n_heads * spec.head_dim))

                h = T.decode_layer_block(cfg, layer, h, attend)
                return h, box["planes"]

            xs = (params["layers"], pool.k_pages, pool.v_pages,
                  pool.k_scale, pool.v_scale)
            x, planes = jax.lax.scan(body, x, xs)
            return T.final_logits(params, cfg, x)[:, 0], pg.PagedKVPool(*planes)

        return forward

    def _make_decode_fn(self, sampled: bool):
        """``sampled=False`` compiles the greedy-only fast path (no vocab
        sort, no categorical) — picked per step from host state."""
        forward = self._make_decode_forward(registry.get(self.backend))

        def serve_decode(params, pool, tokens, positions, block_table,
                         active, base_keys, temps, topks):
            logits, new_pool = forward(params, pool, tokens, positions,
                                       block_table, active)
            if sampled:
                keys = jax.vmap(sampling.slot_key)(
                    base_keys, positions.astype(jnp.int32) + 1)
                tok = sampling.sample_tokens(logits, temps, topks, keys)
            else:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # per-slot integrity flag: a NaN/inf anywhere in a slot's
            # logits means its context is poisoned — the scheduler
            # quarantines that one request instead of failing the batch
            ok = jnp.isfinite(logits).all(axis=-1)
            return jnp.where(active, tok, 0), ok, new_pool

        return serve_decode

    def decode_logits(self, backend: str | None = None) -> jax.Array:
        """(max_slots, V) logits the next decode step would sample from,
        computed on the current state without advancing it — a numerics
        probe that lets two kernel backends be compared on one live state.
        ``backend`` (default: the engine's) runs every op of the step, the
        weight matmuls included."""
        backend = backend or self.backend
        fn = self._logits_jits.get(backend)
        if fn is None:
            forward = self._make_decode_forward(registry.get(backend))

            def serve_logits(*args):
                return forward(*args)

            fn = self._logits_jits[backend] = jax.jit(serve_logits)
        with registry.using(backend):                 # traced on first call
            logits, _ = fn(self.params, self.pool,
                           jnp.asarray(self._last_tok)[:, None],
                           jnp.asarray(self._lens), jnp.asarray(self._bt),
                           jnp.asarray(self._active))
        return logits

    def _decode_jit(self, sampled: bool):
        fn = self._decode_jits.get(sampled)
        if fn is None:
            fn = self._decode_jits[sampled] = jax.jit(
                self._make_decode_fn(sampled))
        return fn

    def _prefill_jit(self, bucket: int):
        """One compile per page-multiple *bucket*, not per exact prompt
        length: prompts are right-padded to the bucket, logits read at the
        true last position (causality shields it from the pad garbage), and
        the pad rows land in the last page masked by seq_len — decode
        appends overwrite them one by one as generation proceeds."""
        fn = self._prefill_jits.get(bucket)
        if fn is None:
            cfg_fp = self._cfg_fp

            def serve_prefill(params, toks, last_pos, page_ids, pool):
                logits, state = T.prefill(params, toks, cfg_fp,
                                          last_pos=last_pos)
                k_all = state.layers.k[:, 0]       # (L, bucket, Hkv, D)
                v_all = state.layers.v[:, 0]
                return logits[0], pg.write_prompt(pool, k_all, v_all, page_ids)

            fn = self._prefill_jits[bucket] = jax.jit(serve_prefill)
        return fn

    def _make_chunk_fn(self):
        """One prefill chunk: ``C = chunk_pages × page_size`` tokens at an
        absolute page-aligned offset, **write-then-attend-quantized**.

        Every call has the same static shapes — chunk width, block-table
        row, gathered context — so the engine compiles this exactly once,
        and a warm (prefix-hit) admission replays byte-identical calls to
        the cold run that minted the shared pages: same tokens, same
        positions, same gathered values (shared pages hold the codes the
        cold run wrote); only the write targets differ (``page_ids`` entry 0
        parks a shared page's recomputed rows on the null page). Bit-exact
        hit-vs-cold outputs follow structurally.

        Per layer the chunk's K/V rows are quantized and scattered into the
        pool *first*, then every query attends the dequantized gathered
        context (earlier pages + this chunk, causally masked) — the decode
        step's append-then-attend order, so chunked prefill and decode
        produce identical codes for the same token stream.
        """
        cfg, spec = self.cfg, self.cfg.attn_spec
        page, cp = self.page_size, self.chunk_pages
        C = cp * page
        n_ctx = self.max_pages_per_seq * page
        g, d = spec.n_kv_heads, spec.head_dim

        def serve_chunk(params, pool, toks, pos0, page_ids, true_len, bt_row,
                        last_rel):
            positions = pos0 + jnp.arange(C, dtype=jnp.int32)         # (C,)
            h = embed(params["embed"], toks[None]).astype(cfg.dtype)  # (1,C,d)
            key_pos = jnp.arange(n_ctx, dtype=jnp.int32)
            # causal within the valid context; pad queries (positions ≥
            # true_len) still see ≥1 key, so no all-masked softmax rows
            mask = ((key_pos[None, :] <= positions[:, None])
                    & (key_pos[None, :] < true_len))                  # (C,S)
            bt = bt_row[None]                                         # (1,MP)

            def body(h, inp):
                layer, kp, vp, ks, vs = inp
                kv_bits = kv_bits_of(kp)
                box = {}

                def attend(z):
                    pa = layer["attn"]
                    q = dense(pa["q"], z).reshape(1, C, spec.n_heads, d)
                    k = dense(pa["k"], z).reshape(1, C, g, d)
                    v = dense(pa["v"], z).reshape(1, C, g, d)
                    q = apply_rope(q, positions[None], spec.rope_theta)
                    k = apply_rope(k, positions[None], spec.rope_theta)
                    kc, ksc = pg.quant_rows(
                        k[0].reshape(cp, page, g, d), kv_bits, kp.dtype)
                    vc, vsc = pg.quant_rows(
                        v[0].reshape(cp, page, g, d), kv_bits, vp.dtype)
                    kp2 = kp.at[page_ids].set(kc)
                    vp2 = vp.at[page_ids].set(vc)
                    ks2 = ks.at[page_ids].set(ksc) if kv_bits else ks
                    vs2 = vs.at[page_ids].set(vsc) if kv_bits else vs
                    box["planes"] = (kp2, vp2, ks2, vs2)
                    kk = dequant_pages_ref(
                        gather_pages_ref(kp2, bt),
                        gather_pages_ref(ks2, bt) if kv_bits else None)
                    vv = dequant_pages_ref(
                        gather_pages_ref(vp2, bt),
                        gather_pages_ref(vs2, bt) if kv_bits else None)
                    out = attn._attend_block(q, kk, vv, spec.scale, mask)
                    return dense(pa["o"], out.reshape(
                        1, C, spec.n_heads * d))

                h = T.decode_layer_block(cfg, layer, h, attend)
                return h, box["planes"]

            xs = (params["layers"], pool.k_pages, pool.v_pages,
                  pool.k_scale, pool.v_scale)
            h, planes = jax.lax.scan(body, h, xs)
            new_pool = pg.PagedKVPool(*planes)
            logits = T.final_logits(params, cfg, h)[0]                # (C, V)
            return logits[last_rel], new_pool

        return serve_chunk

    def _chunk_jit(self):
        if self._chunk_jit_fn is None:
            self._chunk_jit_fn = jax.jit(self._make_chunk_fn())
        return self._chunk_jit_fn

    def _make_draft_fn(self):
        """``spec_decode`` greedy decode steps through the low-bit draft
        tree against each slot's **scratch KV tail**: every scan iteration
        is the vanilla greedy decode fn (same append-then-attend paged
        step), just under ``slice_planes(draft_bits)`` weights, writing rows
        past the committed length. The draft attends its own draft-minted
        scratch rows — it is only a guesser; the verify pass overwrites
        every window row with full-precision-minted codes *before* it
        attends, so no draft bit ever reaches committed state."""
        decode_fn = self._make_decode_fn(sampled=False)
        k = self.spec_decode

        def serve_draft(params, pool, last_tok, lens, block_table, active,
                        base_keys, temps, topks):
            def body(carry, _):
                pool, tok, lens = carry
                nxt, _, pool = decode_fn(params, pool, tok[:, None], lens,
                                         block_table, active, base_keys,
                                         temps, topks)
                return (pool, nxt, lens + active.astype(jnp.int32)), nxt

            (pool, _, _), toks = jax.lax.scan(
                body, (pool, last_tok, lens), None, length=k)
            return jnp.moveaxis(toks, 0, 1), pool              # (B, k)

        return serve_draft

    def _draft_jit(self):
        if self._draft_jit_fn is None:
            self._draft_jit_fn = jax.jit(self._make_draft_fn())
        return self._draft_jit_fn

    def _make_verify_fn(self, sampled: bool):
        """Score all ``W = spec_decode + 1`` window positions — the slot's
        pending token plus its k draft tokens — in ONE full-precision
        forward, batched over slots (the chunked-prefill shape, batched:
        per-slot positions, per-slot scatter targets, per-slot causal mask).

        Write-then-attend: each layer quantizes and scatters the window's
        K/V rows into the pool first (:func:`repro.serve.pages.write_rows`,
        overwriting the draft's scratch rows), then attends the dequantized
        gathered context — so window position i reads codes identical to
        what sequential decode would have read at that position, and the
        rows of *accepted* tokens are already exactly the rows a sequential
        decode would have written. That structural identity, not a
        tolerance, is the token-identity guarantee.

        ``sampled`` draws every window position with the same
        fold_in(base, position) key sequential decode uses
        (:func:`repro.serve.sampling.window_keys`) — temperature > 0 falls
        back to verify-step sampling with unchanged output."""
        cfg, spec = self.cfg, self.cfg.attn_spec
        page = self.page_size
        W = self.spec_decode + 1
        n_ctx = self.max_pages_per_seq * page
        g, d = spec.n_kv_heads, spec.head_dim

        def serve_verify(params, pool, draft, last_tok, lens, block_table,
                         active, base_keys, temps, topks):
            b = last_tok.shape[0]
            toks = jnp.concatenate([last_tok[:, None], draft], axis=1)
            positions = lens[:, None] + jnp.arange(W, dtype=jnp.int32)
            page_ids = jnp.take_along_axis(
                block_table, positions // page, axis=1)
            page_ids = jnp.where(active[:, None], page_ids, 0)    # null page
            offs = positions % page
            h = embed(params["embed"], toks).astype(cfg.dtype)    # (B, W, d)
            key_pos = jnp.arange(n_ctx, dtype=jnp.int32)
            # per-slot causal mask: rows ≤ the query's absolute position are
            # either committed history or freshly written this window; pages
            # past the runway are unreachable (key_pos > position)
            mask = key_pos[None, None, :] <= positions[:, :, None]  # (B,W,S)

            def body(h, inp):
                layer, kp, vp, ks, vs = inp
                kv_bits = kv_bits_of(kp)
                box = {}

                def attend(z):
                    pa = layer["attn"]
                    q = dense(pa["q"], z).reshape(b, W, spec.n_heads, d)
                    kx = dense(pa["k"], z).reshape(b, W, g, d)
                    vx = dense(pa["v"], z).reshape(b, W, g, d)
                    q = apply_rope(q, positions, spec.rope_theta)
                    kx = apply_rope(kx, positions, spec.rope_theta)
                    kp2, vp2, ks2, vs2 = pg.write_rows(
                        kp, vp, ks, vs, kx, vx, page_ids, offs)
                    box["planes"] = (kp2, vp2, ks2, vs2)
                    kk = dequant_pages_ref(
                        gather_pages_ref(kp2, block_table),
                        gather_pages_ref(ks2, block_table) if kv_bits
                        else None)
                    vv = dequant_pages_ref(
                        gather_pages_ref(vp2, block_table),
                        gather_pages_ref(vs2, block_table) if kv_bits
                        else None)
                    out = attn._attend_block(q, kk, vv, spec.scale, mask)
                    return dense(pa["o"], out.reshape(
                        b, W, spec.n_heads * d))

                h = T.decode_layer_block(cfg, layer, h, attend)
                return h, box["planes"]

            xs = (params["layers"], pool.k_pages, pool.v_pages,
                  pool.k_scale, pool.v_scale)
            h, planes = jax.lax.scan(body, h, xs)
            new_pool = pg.PagedKVPool(*planes)
            logits = T.final_logits(params, cfg, h)               # (B, W, V)
            if sampled:
                keys = sampling.window_keys(base_keys, positions + 1)
                tgt = sampling.sample_tokens(
                    logits.reshape(b * W, -1), jnp.repeat(temps, W),
                    jnp.repeat(topks, W),
                    keys.reshape(b * W, 2)).reshape(b, W)
            else:
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = jnp.isfinite(logits).all(axis=(1, 2))
            return jnp.where(active[:, None], tgt, 0), ok, new_pool

        return serve_verify

    def _verify_jit(self, sampled: bool):
        fn = self._verify_jits.get(sampled)
        if fn is None:
            fn = self._verify_jits[sampled] = jax.jit(
                self._make_verify_fn(sampled))
        return fn

    # -------------------------------------------------------------- host API
    def admit_impossible(self, prompt_len: int, max_new_tokens: int) -> str | None:
        """Why a request of this shape can NEVER be admitted here (None =
        admissible once capacity frees up). The ReplicaSet asks every
        replica at submit time so an unservable request is rejected up
        front instead of circulating in the shared queue forever."""
        prompt_len = int(prompt_len)
        if prompt_len == 0:
            return "empty prompt"
        if prompt_len >= self.max_seq_len:
            return (f"prompt of {prompt_len} tokens needs max_seq_len > "
                    f"that (engine has {self.max_seq_len})")
        worst = pg.pages_needed(
            min(prompt_len + max_new_tokens, self.max_seq_len),
            self.page_size)
        if worst > self.allocator.n_pages - 1:
            return (f"needs {worst} pages, pool has "
                    f"{self.allocator.n_pages - 1}")
        return None

    def submit(self, req: Request) -> None:
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        why = self.admit_impossible(prompt.size, req.max_new_tokens)
        if why is not None:
            raise ValueError(f"request {req.rid} can never fit: {why}")
        self._queue.append({"req": req, "prompt": prompt,
                            "replay": np.zeros((0,), np.int32),
                            "t_submit": self._clock(), "retries": 0,
                            "times": []})

    def submit_entry(self, entry: dict) -> None:
        """Queue a prepared entry — the migration path: a request harvested
        off a dead replica re-enters here with its original ``t_submit``
        (the admission-latency signal keeps accruing across the failure),
        its committed tokens as the replay list (bit-exact recompute) with
        their first commit times and its first admission time, and its
        retry count (the budget is per-request, not per-replica)."""
        self._queue.append({
            "req": entry["req"],
            "prompt": np.asarray(entry["prompt"], np.int32).reshape(-1),
            "replay": np.asarray(entry.get("replay",
                                           np.zeros((0,), np.int32)),
                                 np.int32).reshape(-1),
            "t_submit": entry["t_submit"],
            "t_admit": entry.get("t_admit"),
            "times": list(entry.get("times", ())),
            "retries": int(entry.get("retries", 0))})

    def harvest(self) -> list[dict]:
        """Strip every in-flight and queued request out of the engine for
        re-dispatch elsewhere (replica death). In-flight requests come back
        as queue entries (admission order first, then the queue) whose
        replay lists carry their committed tokens — replaying prompt +
        committed tokens through the recompute-preemption machinery on a
        survivor is bit-exact, so the migration is output-invisible. Each
        entry's retry count increments (the budget bounds how many deaths
        one request may survive). **No pages are freed**: the pool died
        with the replica; host scheduler state is simply cleared."""
        entries = []
        for _, slot in sorted((s["admit_seq"], i)
                              for i, s in enumerate(self._slots) if s):
            st = self._slots[slot]
            replay = np.concatenate([
                np.asarray(st["gen"], np.int32),
                np.asarray(st["replay_left"], np.int32)])
            entries.append({"req": st["req"], "prompt": st["prompt"],
                            "replay": replay, "t_submit": st["t_submit"],
                            "t_admit": st["t_admit"], "times": st["times"],
                            "retries": int(st.get("retries", 0)) + 1})
        for e in self._queue:
            entries.append({**e, "retries": int(e.get("retries", 0)) + 1})
        self._queue.clear()
        self._slots = [None] * self.max_slots
        self._active[:] = False
        self._bt[:] = 0
        self._lens[:] = 0
        return entries

    @property
    def n_pending(self) -> int:
        return len(self._queue)

    @property
    def n_active(self) -> int:
        return int(self._active.sum())

    @property
    def n_prefilling(self) -> int:
        """Slots admitted but still chunk-prefilling (not yet decoding)."""
        return sum(1 for s in self._slots
                   if s is not None and "prefill_pos" in s)

    @property
    def busy(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def set_weight_bits(self, k: int) -> None:
        """Serve the next decode batches at ``k`` weight bits.

        Swaps ``self.params`` for the tree whose bitplane QTensor weights are
        ``slice_planes(k)`` views of the full artifact — a zero-copy plane
        slice, so no weight reload and no repacking; decode simply streams
        fewer code planes. Trees are cached per k (each k is one extra jit
        trace of the decode step — the shapes differ — amortized after the
        first switch). Requires ``layout='bitplane'`` weights
        (``quantize_param_tree(..., layout='bitplane')``).

        An effective change flushes the prefix cache and marks in-flight
        chunked prefills non-cacheable: pages minted under other weight bits
        must never serve a prefix hit (hit-vs-cold bit-identity is per
        weight precision)."""
        tree = self._sliced_tree(k)
        if tree is not self.params:
            if self.prefix is not None:
                self.prefix.release_all()
            for st in self._slots:
                if st is not None and "prefill_pos" in st:
                    st["no_insert"] = True
        self.params = tree
        self.weight_bits = int(k)

    def _sliced_tree(self, k: int):
        """The cached ``slice_planes(k)`` view of the full artifact — one
        tree per k, shared by :meth:`set_weight_bits` (serving precision)
        and the speculative draft (``draft_bits``). Zero-copy plane slices;
        each distinct k costs one extra jit trace of its decode variant."""
        tree = self._params_by_bits.get(k)
        if tree is None:
            n_hit = [0]

            def slice_leaf(leaf):
                if (isinstance(leaf, QTensor)
                        and leaf.scheme.layout == "bitplane"):
                    n_hit[0] += 1
                    return leaf.slice_planes(min(int(k), leaf.scheme.bits))
                return leaf

            tree = jax.tree.map(slice_leaf, self._params_full,
                                is_leaf=lambda x: isinstance(x, QTensor))
            if not n_hit[0]:
                raise ValueError(
                    "k-bit weight views need layout='bitplane' QTensor "
                    "weights — quantize with quantize_param_tree(..., "
                    "layout='bitplane')")
            self._params_by_bits[k] = tree
        return tree

    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the full-precision verify accepted
        (NaN before the first speculative window)."""
        drafted = self.stats["spec_draft_tokens"]
        if not drafted:
            return float("nan")
        return self.stats["spec_accepted_tokens"] / drafted

    def kv_pool_nbytes(self, used_only: bool = False) -> int:
        """Logical KV HBM bytes (QTensor.nbytes accounting; §2.2).
        ``used_only`` counts **unique** referenced pages via the allocator —
        a prefix page shared by five block-table rows is five rows of
        logical context but one page of HBM, which is the point."""
        if used_only:
            return pg.pool_nbytes(self.pool, n_pages=self.allocator.n_used)
        return pg.pool_nbytes(self.pool)

    def release_prefix_cache(self) -> int:
        """Drop every trie-held page reference (drain / shutdown); returns
        pages released. In-flight sharers keep theirs."""
        return self.prefix.release_all() if self.prefix is not None else 0

    # ------------------------------------------------------------- scheduler
    def _free_slot(self) -> int | None:
        # occupied ≠ active: a chunk-prefilling slot is occupied but not yet
        # decoding, so scanning ~self._active would double-book it
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _budget(self, entry) -> int:
        """Generation budget: the request's ask, capped by the context."""
        return min(entry["req"].max_new_tokens,
                   self.max_seq_len - len(entry["prompt"]))

    def _bucket(self, s: int) -> int:
        return pg.pages_needed(max(s, 1), self.page_size) * self.page_size

    def _alloc_pages(self, n: int) -> list[int] | None:
        """Allocate ``n`` pages, evicting unreferenced prefix-cache leaves
        (LRU) under pressure before giving up."""
        ids = self.allocator.alloc(n)
        while ids is None and self.prefix is not None \
                and self.prefix.evict(1):
            ids = self.allocator.alloc(n)
        return ids

    def _replaying(self) -> bool:
        """True while any recompute-replay is in flight (in a slot or still
        queued) — the window where weight-bits actuation must be deferred."""
        return (any(s is not None and s["replay_left"] for s in self._slots)
                or any(e["replay"].size for e in self._queue))

    def _admit(self, finished: list) -> None:
        while self._queue:
            entry = self._queue[0]
            if int(entry.get("retries", 0)) > self.retry_budget:
                # preempted/migrated past the budget: fail it with a status
                # (its committed tokens ride along) rather than letting a
                # pathological evict-replay or die-migrate cycle spin forever
                self._queue.popleft()
                self.stats["retries_exhausted"] += 1
                finished.append(self._finish_entry(entry, reason="retries"))
                continue
            slot = self._free_slot()
            if slot is None:
                return
            prompt = entry["prompt"]
            replay = entry["replay"]
            s = int(prompt.size)
            budget = self._budget(entry)
            if budget <= 0:                        # context already full
                self._queue.popleft()
                finished.append(self._finish_entry(entry, reason="length"))
                continue
            n_now = pg.pages_needed(s + 1, self.page_size)
            n_res = (pg.pages_needed(min(s + budget, self.max_seq_len),
                                     self.page_size)
                     if self.reserve == "full" else n_now)
            # prefix hit: the first m pages come shared from the trie (one
            # reference taken per page — ours now); only the rest is
            # allocated. Referencing before _alloc_pages keeps the eviction
            # scan from freeing the very pages we just matched.
            shared = self.prefix.use(prompt) if self.prefix is not None else []
            m = len(shared)
            ids = self._alloc_pages(max(n_res, n_now) - m)
            if ids is None:
                if shared:
                    self.allocator.free(shared)    # hand the refs back
                return                             # FIFO head-of-line wait
            self._queue.popleft()
            now = self._clock()
            self.admit_waits.append(max(0.0, now - entry["t_submit"]))
            req = entry["req"]
            all_ids = shared + ids
            row = np.zeros((self.max_pages_per_seq,), np.int32)
            row[:len(all_ids)] = all_ids
            self._bt[slot] = row
            self._temps[slot] = req.temperature
            self._topks[slot] = req.top_k
            base = np.asarray(jax.random.fold_in(
                jax.random.PRNGKey(req.seed), req.rid), np.uint32)
            self._base_keys[slot] = base
            if self.prefix is not None:
                if m:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += m * self.page_size
                else:
                    self.stats["prefix_misses"] += 1
            t_admit = entry.get("t_admit")
            state = {"req": req, "prompt": prompt, "gen": [],
                     "replay_left": list(replay), "pages": all_ids,
                     "admit_seq": self._admit_seq,
                     "t_submit": entry["t_submit"],
                     "t_admit": now if t_admit is None else t_admit,
                     "times": list(entry.get("times", ())),
                     "retries": int(entry.get("retries", 0))}
            self._admit_seq += 1
            self.stats["admitted"] += 1

            if self._chunked:
                # chunks are skipped only when the hit covers them entirely;
                # a partially-hit chunk recomputes at identical shapes (its
                # shared pages write to the null page) — see _make_chunk_fn
                C = self.chunk_pages * self.page_size
                state["prefill_pos"] = (m // self.chunk_pages) * C
                state["shared_pages"] = m
                self._lens[slot] = 0
                self._slots[slot] = state
                continue

            bucket = self._bucket(s)
            padded = np.zeros((bucket,), np.int32)
            padded[:s] = prompt
            fn = self._prefill_jit(bucket)
            logits, self.pool = fn(
                self.params, jnp.asarray(padded)[None], jnp.int32(s - 1),
                jnp.asarray(all_ids[:bucket // self.page_size], jnp.int32),
                self.pool)
            self._lens[slot] = s
            self._slots[slot] = state
            self.stats["prefill_tokens"] += s
            self._start_decode(slot, logits, finished)

    def _start_decode(self, slot: int, last_logits, finished: list) -> None:
        """Prompt fully prefilled: take the first token (sampled, or the
        replay head after a preemption) and activate the slot for decode."""
        state = self._slots[slot]
        req = state["req"]
        if state["replay_left"]:
            # recompute preemption: the first generated token is known;
            # the rest replays through forced decode steps
            tok = int(state["replay_left"].pop(0))
        else:
            # the reads below wait for the prefill's device work
            with span("serve.first_token"):
                if req.rid in self._poison_rids or not bool(
                        np.isfinite(np.asarray(last_logits)).all()):
                    # non-finite prefill logits: quarantine before the slot
                    # commits a garbage first token (a 1-token request
                    # would otherwise *finish* with it)
                    self._poison_rids.discard(req.rid)
                    self._quarantine_slot(slot, finished)
                    return
                s = len(state["prompt"])
                tok = int(self._sample1(
                    last_logits, jnp.float32(req.temperature),
                    jnp.int32(req.top_k),
                    sampling.slot_key(jnp.asarray(self._base_keys[slot]),
                                      jnp.int32(s))))
        _commit(state, tok, self._clock())
        self._active[slot] = True
        self._last_tok[slot] = tok
        self._maybe_finish(slot, finished)

    def _advance_prefills(self, finished: list) -> None:
        """Run ONE chunk for the oldest prefilling slot — per-step prefill
        work is bounded by ``chunk_pages × page_size`` tokens, so long
        admissions interleave with live decode instead of stalling it."""
        cands = [(st["admit_seq"], i) for i, st in enumerate(self._slots)
                 if st is not None and "prefill_pos" in st]
        if not cands:
            return
        _, slot = min(cands)
        st = self._slots[slot]
        prompt = st["prompt"]
        s = int(prompt.size)
        page, cp = self.page_size, self.chunk_pages
        C = cp * page
        start = st["prefill_pos"]
        end = min(s, start + C)
        with span("serve.prefill_chunk"):
            toks = np.zeros((C,), np.int32)
            toks[:end - start] = prompt[start:end]
            m = st["shared_pages"]
            pids = np.zeros((cp,), np.int32)
            p0 = start // page
            for j in range(cp):
                gp = p0 + j
                if m <= gp < self.max_pages_per_seq:
                    pids[j] = self._bt[slot, gp]  # 0 (null) when shared
            logits, self.pool = self._chunk_jit()(
                self.params, self.pool, jnp.asarray(toks), jnp.int32(start),
                jnp.asarray(pids), jnp.int32(end),
                jnp.asarray(self._bt[slot]),
                jnp.int32(min(s - 1 - start, C - 1)))
        self.stats["prefill_tokens"] += end - start
        self.stats["prefill_chunks"] += 1
        st["prefill_pos"] = start + C
        if end < s:
            return
        del st["prefill_pos"]
        self._lens[slot] = s
        if self.prefix is not None and not st.get("no_insert"):
            self.prefix.insert(
                prompt, [int(p) for p in self._bt[slot, :s // page]])
        self._start_decode(slot, logits, finished)

    def _full_tokens(self, state) -> np.ndarray:
        return np.concatenate([state["prompt"],
                               np.asarray(state["gen"], np.int32)])

    def _finish_entry(self, entry, *, reason: str) -> Finished:
        """A queue entry finished without (re-)admission (context full)."""
        replay = entry["replay"]
        tokens = np.concatenate([entry["prompt"], replay])
        self.stats["finished"] += 1
        return _finished(entry, tokens, int(replay.size), reason)

    def _maybe_finish(self, slot: int, finished: list) -> bool:
        state = self._slots[slot]
        req = state["req"]
        n_gen = len(state["gen"])
        full_len = len(state["prompt"]) + n_gen
        reason = None
        if req.eos_id is not None and state["gen"][-1] == req.eos_id:
            reason = "eos"
        elif n_gen >= self._budget(state) or full_len >= self.max_seq_len:
            reason = "length"
        if reason is None:
            return False
        self.allocator.free(state["pages"])        # decref: shared survive
        self._active[slot] = False
        self._bt[slot] = 0
        self._lens[slot] = 0
        self._slots[slot] = None
        self.stats["finished"] += 1
        finished.append(_finished(state, self._full_tokens(state), n_gen,
                                  reason))
        return True

    def _quarantine_slot(self, slot: int, finished: list) -> None:
        """Fail ONE request whose logits went non-finite — with a status,
        not an engine crash — and keep the rest of the batch untouched.

        Page hygiene is the subtle part: the poisoned decode steps appended
        NaN K/V rows into this slot's **private** pages, and masked
        attention does not protect a recycled page's next owner (a masked
        score's softmax weight is 0, but ``0 × NaN = NaN`` through the
        value matmul). Private (refcount-1) pages are therefore scrubbed
        back to zeros before the free; shared pages (prefix hits, trie
        refs) were minted before the poison and stay as they are."""
        state = self._slots[slot]
        private = [p for p in state["pages"]
                   if self.allocator.refcount(p) == 1]
        if private:
            self.pool = pg.scrub_pages(self.pool, private)
        self.allocator.free(state["pages"])
        self._active[slot] = False
        self._bt[slot] = 0
        self._lens[slot] = 0
        self._slots[slot] = None
        self.stats["quarantined"] += 1
        self.stats["finished"] += 1
        finished.append(_finished(state, self._full_tokens(state),
                                  len(state["gen"]), "nan"))

    def _page_checksum(self, pid: int) -> int:
        """CRC32 over one pool page's raw code (and scale) bytes across all
        layers — the cheap content fingerprint the prefix trie stamps at
        insert and re-verifies at use, so a corrupted shared page is caught
        before a new sharer ever attends it."""
        pid = int(pid)
        with span("serve.page_checksum"):
            parts = [np.asarray(self.pool.k_pages[:, pid]),
                     np.asarray(self.pool.v_pages[:, pid])]
            if self.plan.kv_bits:
                parts += [np.asarray(self.pool.k_scale[:, pid]),
                          np.asarray(self.pool.v_scale[:, pid])]
            crc = 0
            for a in parts:
                crc = zlib.crc32(np.ascontiguousarray(a).tobytes(), crc)
        return crc

    def _inject_kv_flip(self, spec) -> None:
        """Apply one armed ``kv_flip`` fault: seeded bit flips in a pool
        page's K codes (an explicit ``spec.page``, or a seeded pick among
        currently-allocated pages)."""
        from repro.serve.faults import corrupt_kv_page

        page = spec.page
        if page is None:
            used = self.allocator.used_pages()
            if not used:
                return
            rng = np.random.default_rng(spec.seed)
            page = int(used[int(rng.integers(len(used)))])
        self.pool = corrupt_kv_page(self.pool, page, n_flips=spec.n_flips,
                                    seed=spec.seed)
        self.stats["kv_flips"] += 1

    def _preempt_one(self) -> int | None:
        """Evict the youngest occupied slot (decoding or still prefilling);
        requeue it (front) with its generated tokens as the replay list and
        its **original** ``t_submit`` — restarting the clock here would
        zero the very admission-wait signal the autoscaler governs on.
        Returns the freed slot."""
        cands = [(s["admit_seq"], i) for i, s in enumerate(self._slots) if s]
        if not cands:
            return None
        _, slot = max(cands)
        state = self._slots[slot]
        self.allocator.free(state["pages"])
        self._active[slot] = False
        self._bt[slot] = 0
        self._lens[slot] = 0
        self._slots[slot] = None
        replay = np.concatenate([
            np.asarray(state["gen"], np.int32),
            np.asarray(state["replay_left"], np.int32)])
        self._queue.appendleft({"req": state["req"],
                                "prompt": state["prompt"], "replay": replay,
                                "t_submit": state["t_submit"],
                                "t_admit": state["t_admit"],
                                "times": state["times"],
                                "retries": int(state.get("retries", 0)) + 1})
        self.stats["preemptions"] += 1
        return slot

    def _ensure_pages(self) -> None:
        """Before decode: every active slot must own the page its next KV row
        lands in; grow on demand — evicting idle prefix-cache pages first,
        then preempting (youngest-first) when the pool is exhausted."""
        for slot in range(self.max_slots):
            while True:
                if not self._active[slot] or self._slots[slot] is None:
                    break
                pidx = int(self._lens[slot]) // self.page_size
                if self._bt[slot, pidx] != 0:
                    break
                ids = self._alloc_pages(1)
                if ids is not None:
                    self._bt[slot, pidx] = ids[0]
                    self._slots[slot]["pages"].append(ids[0])
                    break
                victim = self._preempt_one()
                if victim is None or victim == slot:
                    break                      # this slot itself got evicted

    def _spec_ready(self) -> bool:
        """Can this step run a speculative window? Checked *after*
        ``_ensure_pages`` so a preemption it caused (replay in flight)
        forces the vanilla fallback; a draft at ≥ the serving precision
        would be pure overhead, so an autoscaler drop to (or below)
        ``draft_bits`` disables speculation until bits are restored."""
        if not self.spec_decode:
            return False
        if self._replaying():
            return False
        if (self.weight_bits is not None
                and self.weight_bits <= self.draft_bits):
            return False
        return True

    def _ensure_spec_pages(self) -> bool:
        """Extend every active slot's block table to cover the window rows
        (positions ``lens .. lens+k``). Speculation never preempts anyone:
        when a slot lacks runway (sequence near ``max_seq_len``) or the
        pool can't supply the scratch pages, the step falls back to vanilla
        decode. Pages allocated here join the slot's ``pages`` list — they
        are committed rows' pages on acceptance, ordinary growth pages
        later otherwise, and are freed with the slot either way (the
        preemption-with-draft-tail leak test pins this)."""
        k = self.spec_decode
        for slot in range(self.max_slots):
            if not self._active[slot] or self._slots[slot] is None:
                continue
            n = int(self._lens[slot])
            if n + k + 1 > self.max_seq_len:
                return False
            for pidx in range(n // self.page_size,
                              (n + k) // self.page_size + 1):
                if self._bt[slot, pidx] != 0:
                    continue
                ids = self._alloc_pages(1)
                if ids is None:
                    return False
                self._bt[slot, pidx] = ids[0]
                self._slots[slot]["pages"].append(ids[0])
        return True

    def _spec_step(self, finished: list) -> None:
        """One speculative window: k greedy draft steps at ``draft_bits``
        + one batched full-precision verify, then commit the longest
        accepted prefix per slot. Token accounting is exactly-once: a token
        is counted when (and only when) it is committed to ``gen``, and
        committing stops the moment the slot finishes (eos / budget), so a
        slot finishing mid-window never counts its discarded tail — the
        invariant ``decode_tokens == Σ (n_generated - 1)`` holds with or
        without speculation. One wall-clock entry (draft + verify) lands in
        ``decode_times`` per window."""
        k = self.spec_decode
        sampled = bool((self._temps[self._active] > 0).any())
        args = (jnp.asarray(self._last_tok), jnp.asarray(self._lens),
                jnp.asarray(self._bt), jnp.asarray(self._active),
                jnp.asarray(self._base_keys), jnp.asarray(self._temps),
                jnp.asarray(self._topks))
        t0 = self._clock()
        draft, pool = self._draft_jit()(self._params_draft, self.pool, *args)
        tgt, ok, self.pool = self._verify_jit(sampled)(
            self.params, pool, draft, *args)
        draft_np = np.asarray(draft)
        tgt_np = np.asarray(tgt)               # blocks until ready
        ok_np = np.asarray(ok)
        t1 = self._clock()
        dt = t1 - t0

        committed = 0
        for slot in range(self.max_slots):
            if not self._active[slot]:
                continue
            state = self._slots[slot]
            rid = state["req"].rid
            if rid in self._poison_rids or not bool(ok_np[slot]):
                self._poison_rids.discard(rid)
                self._quarantine_slot(slot, finished)
                continue
            m = 0
            while m < k and draft_np[slot, m] == tgt_np[slot, m]:
                m += 1
            self.stats["spec_draft_tokens"] += k
            self.stats["spec_accepted_tokens"] += m
            # commit the verify chain: the m accepted draft tokens plus the
            # verify step's own token at the first divergence — exactly the
            # tokens sequential decode would have produced
            for tok in tgt_np[slot, :m + 1]:
                tok = int(tok)
                self._lens[slot] += 1
                _commit(state, tok, t1)
                self._last_tok[slot] = tok
                committed += 1
                if self._maybe_finish(slot, finished):
                    break

        self.stats["decode_steps"] += 1
        self.stats["spec_steps"] += 1
        self.stats["decode_tokens"] += committed
        variant = ("spec", sampled, self.weight_bits)
        if variant in self._compiled_variants:  # steady state: skip compiles
            self.stats["decode_seconds"] += dt
            self.stats["steady_decode_tokens"] += committed
            self.decode_times.append(dt)
        self._compiled_variants.add(variant)

    def step(self) -> list[Finished]:
        """One scheduler iteration: admit what fits, advance one prefill
        chunk, decode one token for every live sequence — or, with
        ``spec_decode``, run one speculative window (up to k+1 tokens per
        slot). Returns the requests that finished."""
        with span("serve.step"):
            return self._step()

    def _step(self) -> list[Finished]:
        finished: list[Finished] = []
        self._step_no += 1
        if self._faults is not None:
            for sp in self._faults.poll("nan_logits", step=self._step_no,
                                        replica=self.replica_id):
                self._poison_rids.add(sp.rid)
            for sp in self._faults.poll("kv_flip", step=self._step_no,
                                        replica=self.replica_id):
                self._inject_kv_flip(sp)
        if self.autoscaler is not None:
            now = self._clock()
            wait = (max(0.0, now - self._queue[0]["t_submit"])
                    if self._queue else 0.0)
            depth = len(self._queue) + self.n_prefilling
            bits = self.autoscaler.observe(
                admit_wait_ms=wait * 1e3, queue_depth=depth, now=now)
            # defer actuation while a replay is in flight: switching weight
            # bits between eviction and replay would rebuild the replayed KV
            # under different weights than the original decode
            if bits != self.weight_bits and not self._replaying():
                self.set_weight_bits(bits)
        pt0 = self.stats["prefill_tokens"]
        with span("serve.admit"):
            self._admit(finished)
        if self._chunked:
            self._advance_prefills(finished)
        self._ensure_pages()
        step_prefill = self.stats["prefill_tokens"] - pt0
        if step_prefill > self.stats["max_prefill_tokens_per_step"]:
            self.stats["max_prefill_tokens_per_step"] = step_prefill
        if not self._active.any():
            return finished

        # speculative window: k cheap draft steps + one full-precision
        # verify. Falls back to a vanilla step while a replay is in flight,
        # when the autoscaler sits at/below the draft's bits, or when any
        # active slot lacks k+1 rows of page runway.
        if self._spec_ready() and self._ensure_spec_pages():
            with span("serve.spec"):
                self._spec_step(finished)
            return finished

        sampled = bool((self._temps[self._active] > 0).any())
        pages = -(-(self._lens[self._active] + 1) // self.page_size)
        self.stats["attn_blocks"] += int((-(-pages // self._attn_ppb)).sum())
        t0 = self._clock()
        with span("serve.decode.dispatch"):
            tok, ok, self.pool = self._decode_jit(sampled)(
                self.params, self.pool,
                jnp.asarray(self._last_tok)[:, None],
                jnp.asarray(self._lens), jnp.asarray(self._bt),
                jnp.asarray(self._active), jnp.asarray(self._base_keys),
                jnp.asarray(self._temps), jnp.asarray(self._topks))
        with span("serve.decode.wait"):
            tok_np = np.asarray(tok)           # blocks until ready
            ok_np = np.asarray(ok)
        t1 = self._clock()                     # every token's commit time
        dt = t1 - t0
        n_live = int(self._active.sum())
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += n_live
        variant = (sampled, self.weight_bits)
        if variant in self._compiled_variants:  # steady state: skip compiles
            self.stats["decode_seconds"] += dt
            self.stats["steady_decode_tokens"] += n_live
            self.decode_times.append(dt)
        self._compiled_variants.add(variant)
        with span("serve.commit"):
            self._commit_decoded(tok_np, ok_np, t1, finished)
        return finished

    def _commit_decoded(self, tok_np, ok_np, now: float,
                        finished: list) -> None:
        """Commit one decode step's token to every live slot; finish and
        free the slots that are done."""
        for slot in range(self.max_slots):
            if not self._active[slot]:
                continue
            state = self._slots[slot]
            rid = state["req"].rid
            if rid in self._poison_rids or not bool(ok_np[slot]):
                # non-finite logits (or an injected poison): fail THIS
                # request with a status instead of crashing the engine —
                # scrub + free its pages so the NaN rows can't leak into
                # the next owner, and leave every other slot untouched
                self._poison_rids.discard(rid)
                self._quarantine_slot(slot, finished)
                continue
            if state["replay_left"]:
                # forced replay (recompute preemption): the decode step
                # rebuilt this position's KV exactly; the token is known
                tok = state["replay_left"].pop(0)
            else:
                tok = int(tok_np[slot])
            self._lens[slot] += 1
            _commit(state, tok, now)
            self._last_tok[slot] = tok
            self._maybe_finish(slot, finished)

    def run(self, requests=None, max_steps: int = 100_000) -> dict[int, Finished]:
        """Serve until the queue drains and every sequence finishes."""
        for r in requests or ():
            self.submit(r)
        out: dict[int, Finished] = {}
        for _ in range(max_steps):
            if not self.busy:
                break
            before = (len(self._queue), self.n_active, self.n_prefilling,
                      self.stats["decode_steps"], self.stats["prefill_tokens"])
            for f in self.step():
                out[f.rid] = f
            after = (len(self._queue), self.n_active, self.n_prefilling,
                     self.stats["decode_steps"], self.stats["prefill_tokens"])
            if before == after:
                raise RuntimeError(
                    "scheduler stalled (pool too small for any queued "
                    "request?) — nothing admitted, prefilled, decoded, or "
                    "finished")
        else:
            raise RuntimeError(f"run() exceeded {max_steps} steps")
        return out

    def throughput(self) -> float:
        """Steady-state decode tokens/s (compile step excluded)."""
        if self.stats["decode_seconds"] == 0:
            return float("nan")
        return self.stats["steady_decode_tokens"] / self.stats["decode_seconds"]


__all__ = ["Request", "Finished", "ServeEngine", "SUPPORTED_FAMILIES"]
