"""Pallas TPU kernel: paged flash-decode attention over a quantized KV pool.

The serving engine (repro/serve) stores the KV cache as fixed-size pages of
QTensor code planes — bf16, int8, or nibble-packed int4 — indexed per
sequence by a block table. This kernel is the decode hot loop of that
layout: one query token per sequence attends to its pages, and the int8/int4
codes are **dequantized in VMEM**, so HBM traffic is the code bytes (2×/4×
fewer than bf16 — the ZipML Fig. 2 data-movement claim applied to serving;
MLWeaving's any-precision layout is the same idea in silicon). int4 pages
dequantize by **split nibbles fused into the flash inner loop**: scores and
the value accumulator work on the even/odd D-halves directly (both are
half-sum-decomposable), so no per-page stride interleave is paid. q enters
and the output leaves as [even | odd] D-halves, permuted by XLA outside the
kernel (Mosaic has no in-kernel lane interleave).

Mechanics:
* grid = (B,): one grid step per slot. Inside it a ``fori_loop`` walks only
  the slot's live blocks, ``cdiv(cdiv(seq_lens[b], page), ppb)`` of them, of
  ``ppb = pages_per_block(...)`` pages each. A slot with ``seq_lens[b] == 0``
  runs no block: no DMA, no compute, output 0. The f32 flash-softmax state
  (running max, denominator, weighted values) is the loop's carry.
* the code planes and scales stay in HBM (``memory_space=pl.ANY``). For each
  block the kernel starts one async copy per **live** page (and its scale
  row), with the page id read from the block table in SMEM (scalar
  prefetch), into one of two VMEM buffers. While block i is computed, block
  i+1 — or the first block of the next live slot, the grid being sequential
  — is already in flight into the other buffer (double buffering across
  slot boundaries; the buffer parity carries across grid steps in SMEM).
* pages of a block past the slot's last live page are never copied. Their
  VMEM rows hold stale bytes, so every row past ``seq_lens[b]`` is masked
  out of the scores with the finite NEG_INF of models/attention.py **and**
  out of the value side (probabilities × V scale, or the bf16 V rows): a
  stale scale or value may be NaN or inf, and 0 × NaN is NaN.
* block size from shapes (``pages_per_block``): about 256 KV rows per
  block, fewer when the two buffers would overrun a VMEM budget, never more
  than MAXP pages; a MAXP that is no multiple of it only shortens the last
  block. No knob.
* MXU-shaped inner product: a page's rows are taken as one (page·Hkv, D)
  matrix and all H query heads are scored against it at once, ``q (H, D) @
  Kᵀ``; a static (H, page·Hkv) table keeps each head to its own KV group's
  columns, then ``P (H, page·Hkv) @ V``. The per-row scales factor out of
  the D-sums: they scale the score columns (K) and the probabilities (V),
  so the codes enter the MXU as they are. Operands are in q's dtype,
  accumulation f32. The scales reach the kernel lane-dense, one
  ``(1, page·Hkv)`` row per page (a reshape of the pool's
  ``(P, page, Hkv, 1)`` plane outside the kernel).

Validated against kernels/ref.paged_attention_ref in interpret mode on CPU
(tolerance: f32 flash vs one-shot softmax associativity); real TPU lowering
wants page a multiple of 8 and D a multiple of 128, which the serving pool's
defaults satisfy at production head dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry

NEG_INF = -2.0 ** 30  # matches models/attention.py: finite, exp() == 0.0 in f32
BLOCK_ROWS = 256       # KV rows a block aims at
VMEM_BUDGET = 8 << 20  # bytes for the two K/V (+ scale) block buffers


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def pages_per_block(page: int, maxp: int, hkv: int, d: int,
                    kv_bits: int) -> int:
    """Pages the kernel reads per block: about ``BLOCK_ROWS`` KV rows, as
    many as the double-buffered K/V (and scale) buffers fit in
    ``VMEM_BUDGET`` with their VMEM tile padding, at most ``maxp``. ``d`` is
    the head dim; an int4 plane holds d/2 bytes per row."""
    dk = d // 2 if kv_bits == 4 else d
    item = 1 if kv_bits else 2
    # a page's (page·Hkv, Dk) codes in (8, 128) tiles of 32-bit words, which
    # hold 16 rows of bf16 or 32 rows of 1-byte codes; a scale row pads to 8
    code = _pad(page * hkv, 32 // item) * _pad(dk, 128) * item
    scale = 8 * _pad(page * hkv, 128) * 4 if kv_bits else 0
    per_page = 2 * 2 * (code + scale)             # two buffers × K and V
    ppb = min(BLOCK_ROWS // page, VMEM_BUDGET // per_page)
    return max(1, min(ppb, maxp))


def _nibble_halves(codes):
    """(rows, D/2) packed uint8 → (lo, hi) f32 code halves, each (rows,
    D/2): lo = even D-elements, hi = odd (pack_int4's layout), offset
    binary removed. The shift+mask runs on the packed words in place."""
    c = codes.astype(jnp.int32)     # Mosaic casts no unsigned int to float
    return ((c & 0xF) - 8).astype(jnp.float32), \
        ((c >> 4) - 8).astype(jnp.float32)


def _paged_attn_kernel(bt_ref, len_ref, q_ref, grp_ref, *refs,
                       softmax_scale: float, kv_bits: int, page: int,
                       ppb: int):
    quant = kv_bits != 0
    if quant:
        (kp_hbm, vp_hbm, ks_hbm, vs_hbm, o_ref,
         kbuf, vbuf, ksbuf, vsbuf, sems, state) = refs
    else:
        kp_hbm, vp_hbm, o_ref, kbuf, vbuf, sems, state = refs
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    cols = kbuf.shape[2]
    hkv = cols // page

    @pl.when(b == 0)
    def _reset():
        state[0] = 0        # buffer the next block lands in
        state[1] = 0        # 1: the next live slot's first block is in flight

    def n_live_pages(slot):
        return (len_ref[slot] + page - 1) // page

    def copies(slot, blk, buf, j):
        pid = bt_ref[slot, blk * ppb + j]
        out = [pltpu.make_async_copy(kp_hbm.at[pid], kbuf.at[buf, j],
                                     sems.at[buf]),
               pltpu.make_async_copy(vp_hbm.at[pid], vbuf.at[buf, j],
                                     sems.at[buf])]
        if quant:
            out += [pltpu.make_async_copy(ks_hbm.at[pid], ksbuf.at[buf, j],
                                          sems.at[buf]),
                    pltpu.make_async_copy(vs_hbm.at[pid], vsbuf.at[buf, j],
                                          sems.at[buf])]
        return out

    def for_live_pages(slot, blk, buf, method):
        n = n_live_pages(slot) - blk * ppb
        for j in range(ppb):
            @pl.when(j < n)
            def _():
                for c in copies(slot, blk, buf, j):
                    getattr(c, method)()

    length = len_ref[b]
    n_blocks = (n_live_pages(b) + ppb - 1) // ppb

    # the next live slot, whose first block the last block here prefetches
    def _skip(s):
        return s + 1

    def _dead(s):
        return jnp.logical_and(s < nslots, len_ref[jnp.minimum(s, nslots - 1)]
                               == 0)

    nxt = jax.lax.while_loop(_dead, _skip, b + 1)

    @pl.when(jnp.logical_and(n_blocks > 0, state[1] == 0))
    def _first():
        for_live_pages(b, 0, state[0], "start")

    q = q_ref[0]                                        # (H, D) q.dtype
    h, d = q.shape
    mm = q.dtype
    grp = grp_ref[...] != 0                             # (H, cols)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
    nt = (((1,), (1,)), ((), ()))                       # contract D, B-trans

    def block(i, carry):
        m_prev, l_prev, acc = carry
        buf = (state[0] + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            for_live_pages(b, i + 1, 1 - buf, "start")

        @pl.when(jnp.logical_and(i + 1 == n_blocks, nxt < nslots))
        def _next_slot():
            for_live_pages(nxt, 0, 1 - buf, "start")

        for_live_pages(b, i, buf, "wait")

        # live columns of each page of the block (≤ 0 past the last one)
        left = [(length - (i * ppb + j) * page) * hkv for j in range(ppb)]
        scores, keep = [], []
        for j in range(ppb):
            kc = kbuf[buf, j]
            if kv_bits == 4:
                lo, hi = _nibble_halves(kc)
                s = (jax.lax.dot_general(q[:, :d // 2], lo.astype(mm), nt,
                                         preferred_element_type=jnp.float32)
                     + jax.lax.dot_general(q[:, d // 2:], hi.astype(mm), nt,
                                           preferred_element_type=jnp.float32))
            else:
                s = jax.lax.dot_general(q, kc.astype(jnp.float32).astype(mm),
                                        nt, preferred_element_type=jnp.float32)
            if quant:
                s = s * ksbuf[buf, j][:, :cols]
            ok = jnp.logical_and(grp, col < left[j])
            scores.append(jnp.where(ok, s * softmax_scale, NEG_INF))
            keep.append(ok)
        m_blk = functools.reduce(jnp.maximum, [jnp.max(s, axis=1,
                                                       keepdims=True)
                                               for s in scores])
        m_new = jnp.maximum(m_prev, m_blk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha
        acc = acc * alpha
        for j in range(ppb):
            # explicit re-mask: exp(NEG_INF − m) is 0, but the where() also
            # keeps a stale NaN scale of a dead row out of the value side
            p = jnp.where(keep[j], jnp.exp(scores[j] - m_new), 0.0)
            l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
            vc = vbuf[buf, j]
            if quant:
                p = jnp.where(keep[j], p * vsbuf[buf, j][:, :cols], 0.0)
            pm = p.astype(mm)
            if kv_bits == 4:
                lo, hi = _nibble_halves(vc)
                acc = acc + jnp.concatenate(
                    [jnp.dot(pm, lo.astype(mm),
                             preferred_element_type=jnp.float32),
                     jnp.dot(pm, hi.astype(mm),
                             preferred_element_type=jnp.float32)], axis=-1)
            else:
                v = vc.astype(jnp.float32)
                if not quant:   # a stale bf16 row may be inf: 0 × inf = NaN
                    v = jnp.where(row < left[j], v, 0.0)
                acc = acc + jnp.dot(pm, v.astype(mm),
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)

    @pl.when(n_blocks > 0)
    def _advance():
        state[0] = (state[0] + n_blocks) % 2
        state[1] = (nxt < nslots).astype(jnp.int32)

    # empty sequences (seq_len 0: inactive slots) divide by the 1e-30
    # floor → output 0; those rows are never read by the engine
    o_ref[0] = acc / jnp.maximum(l, 1e-30)


@functools.partial(jax.jit, static_argnames=("softmax_scale", "kv_bits",
                                             "interpret"))
def paged_decode_attn(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      k_scale: jax.Array, v_scale: jax.Array,
                      block_table: jax.Array, seq_lens: jax.Array, *,
                      softmax_scale: float, kv_bits: int = 0,
                      interpret: bool | None = None) -> jax.Array:
    """q (B, H, D) × paged KV pool → (B, H, D) f32.

    k/v_pages: (P, page, Hkv, D) bf16/int8 or (P, page, Hkv, D/2) uint8
    (packed int4); k/v_scale: (P, page, Hkv, 1) f32, or (1, 1, Hkv, 1) dummy
    for bf16 (unread); block_table (B, MAXP) int32; seq_lens (B,) int32.
    """
    b, h, d = q.shape
    n_pages, page, hkv, dk = k_pages.shape
    maxp = block_table.shape[1]
    ppb = pages_per_block(page, maxp, hkv, d, kv_bits)
    cols = page * hkv
    bt = block_table.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    # head h scores only its KV group's columns (column c = row·Hkv + group)
    grp = jnp.asarray((np.arange(cols) % hkv)[None, :]
                      == (np.arange(h) // (h // hkv))[:, None], jnp.int32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # a page's rows as one (page·Hkv, Dk) matrix, row t·Hkv + group
    operands = [k_pages.reshape(n_pages, cols, dk),
                v_pages.reshape(n_pages, cols, dk)]
    scratch = [pltpu.VMEM((2, ppb, cols, dk), k_pages.dtype),
               pltpu.VMEM((2, ppb, cols, dk), v_pages.dtype)]
    if kv_bits:
        # lane-dense scales: one (1, page·Hkv) row per page, in the column
        # order of the page's (page·Hkv, D) rows, padded to whole lanes
        lanes = ((0, 0), (0, 0), (0, _pad(cols, 128) - cols))
        operands += [jnp.pad(k_scale.reshape(n_pages, 1, cols), lanes),
                     jnp.pad(v_scale.reshape(n_pages, 1, cols), lanes)]
        scratch += [pltpu.VMEM((2, ppb, 1, _pad(cols, 128)),
                               jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((2,), jnp.int32)]
    qo = pl.BlockSpec((1, h, d), lambda bb, bt_ref, len_ref: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[qo, pl.BlockSpec((h, cols),
                                   lambda bb, bt_ref, len_ref: (0, 0))]
        + [hbm] * len(operands),
        out_specs=qo,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_paged_attn_kernel, softmax_scale=softmax_scale,
                               kv_bits=kv_bits, page=page, ppb=ppb)
    if kv_bits == 4:
        # Mosaic cannot (de)interleave lanes in a kernel: q goes in, and the
        # output comes out, as [even | odd] D-halves; XLA permutes both
        q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=registry.resolve_interpret(interpret),
    )(bt, lens, q, grp, *operands)
    if kv_bits == 4:
        out = out.reshape(b, h, 2, d // 2).swapaxes(-1, -2).reshape(b, h, d)
    return out
