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
half-sum-decomposable), so the per-page stride interleave the old
unpack-then-attend path paid — the int4-slower-than-int8 regression — is
gone. q enters and the output leaves as [even | odd] D-halves, permuted by
XLA outside the kernel (Mosaic has no in-kernel lane interleave).

Mechanics:
* grid = (B, MAXP); the page axis is the sequential minor axis, so the f32
  flash-softmax accumulators (running max / sum / weighted value) live in
  VMEM scratch across the per-sequence page loop — the same
  revisit-accumulate pattern as kernels/qmm.py's K axis.
* the block table and sequence lengths ride in as **scalar prefetch**
  operands (`pltpu.PrefetchScalarGridSpec`): the index_map of the page
  operands reads `block_table[b, p]`, so each grid step DMAs exactly the one
  page it needs — the pool itself never streams densely.
* rows past `seq_lens[b]` (allocation slack, the shared null page 0) are
  masked with the finite NEG_INF of models/attention.py and contribute
  exactly 0 probability mass.

Validated bit-for-bit against kernels/ref.paged_attention_ref in interpret
mode on CPU (tolerance: f32 flash vs one-shot softmax associativity); real
TPU lowering wants page a multiple of 8 and D a multiple of 128, which the
serving pool's defaults satisfy at production head dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry

NEG_INF = -2.0 ** 30  # matches models/attention.py: finite, exp() == 0.0 in f32


def _dequant(codes, scale, kv_bits: int):
    """(page, Hkv, D) codes + (page|1, Hkv, 1) scale → (page, Hkv, D) f32.

    bf16/int8 only: the int4 path never materializes interleaved codes —
    see the split-nibble branch in the kernel body."""
    x = codes.astype(jnp.float32)
    if kv_bits:
        x = x * scale.astype(jnp.float32)
    return x


def _nibble_halves(codes, scale):
    """(page, Hkv, D/2) packed uint8 → (lo, hi) f32 halves, each
    (page, Hkv, D/2): lo = even D-elements, hi = odd (pack_int4's layout).

    The shift+mask runs on the packed words in place — no interleave, no
    (page, Hkv, D) stride scatter. The caller keeps the two halves apart
    through the whole page loop; scores and the value accumulator are
    half-sum-decomposable, so only the final output interleaves (once, in
    XLA after the kernel)."""
    s = scale.astype(jnp.float32)
    c = codes.astype(jnp.int32)     # Mosaic casts no unsigned int to float
    lo = ((c & 0xF) - 8).astype(jnp.float32) * s
    hi = ((c >> 4) - 8).astype(jnp.float32) * s
    return lo, hi


def _paged_attn_kernel(bt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                       o_ref, m_ref, l_ref, acc_ref, *,
                       softmax_scale: float, kv_bits: int, page: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (H, D)
    h, d = q.shape
    if kv_bits == 4:
        # fused in-register nibble dequant: scores split over the even/odd
        # D-halves (the D-sum is permutation-invariant), V accumulated
        # de-interleaved — the wrapper interleaves the output once
        k_lo, k_hi = _nibble_halves(kp_ref[0], ks_ref[0])   # (page, G, D/2)
        v_lo, v_hi = _nibble_halves(vp_ref[0], vs_ref[0])
        g = k_lo.shape[1]
        r = h // g
        d2 = d // 2
        qg = q.reshape(g, r, d)                 # q arrives as [even | odd]
        s = (jnp.einsum("grd,tgd->grt", qg[..., :d2], k_lo,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("grd,tgd->grt", qg[..., d2:], k_hi,
                          preferred_element_type=jnp.float32)) * softmax_scale
    else:
        k = _dequant(kp_ref[0], ks_ref[0], kv_bits)     # (page, G, D)
        v = _dequant(vp_ref[0], vs_ref[0], kv_bits)
        g = k.shape[1]
        r = h // g
        qg = q.reshape(g, r, d)
        s = jnp.einsum("grd,tgd->grt", qg, k,
                       preferred_element_type=jnp.float32) * softmax_scale
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = pos < len_ref[b]                            # (1, 1, page)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                 # (G, R)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    # explicit re-mask: on a fully-masked page m_new stays NEG_INF and
    # exp(s − m_new) would be exp(0)=1 — the where() keeps dead rows at 0
    pexp = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1)
    acc = acc_ref[...].reshape(g, r, d) * alpha[..., None]
    if kv_bits == 4:
        # acc scratch layout for int4: [even-half | odd-half] along D
        d2 = d // 2
        acc = jnp.concatenate(
            [acc[..., :d2] + jnp.einsum("grt,tgd->grd", pexp, v_lo,
                                        preferred_element_type=jnp.float32),
             acc[..., d2:] + jnp.einsum("grt,tgd->grd", pexp, v_hi,
                                        preferred_element_type=jnp.float32)],
            axis=-1)
    else:
        acc = acc + jnp.einsum("grt,tgd->grd", pexp, v,
                               preferred_element_type=jnp.float32)
    acc_ref[...] = acc.reshape(h, d)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        # empty sequences (seq_len 0: inactive slots) divide by the 1e-30
        # floor → output 0; those rows are never read by the engine
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]   # (G, R, 1)
        out = acc_ref[...].reshape(g, r, d) / l
        o_ref[0] = out.reshape(h, d)


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
    for bf16; block_table (B, MAXP) int32; seq_lens (B,) int32.
    """
    b, h, d = q.shape
    n_pages, page, hkv, _ = k_pages.shape
    maxp = block_table.shape[1]
    bt = block_table.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    scale_blk = (1, 1, hkv, 1) if k_scale.shape[0] == 1 else (1, page, hkv, 1)

    def page_idx(bb, pp, bt_ref, len_ref):
        return (bt_ref[bb, pp], 0, 0, 0)

    def scale_idx(bb, pp, bt_ref, len_ref):
        if k_scale.shape[0] == 1:
            return (0, 0, 0, 0)
        return (bt_ref[bb, pp], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bb, pp, bt_ref, len_ref: (bb, 0, 0)),
            pl.BlockSpec((1, page, hkv, k_pages.shape[-1]), page_idx),
            pl.BlockSpec((1, page, hkv, v_pages.shape[-1]), page_idx),
            pl.BlockSpec(scale_blk, scale_idx),
            pl.BlockSpec(scale_blk, scale_idx),
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda bb, pp, bt_ref, len_ref: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, h // hkv), jnp.float32),   # running max
            pltpu.VMEM((hkv, h // hkv), jnp.float32),   # running denom
            pltpu.VMEM((h, d), jnp.float32),            # weighted values
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, softmax_scale=softmax_scale,
                               kv_bits=kv_bits, page=page)
    if kv_bits == 4:
        # Mosaic cannot (de)interleave lanes in a kernel: q goes in, and the
        # output comes out, as [even | odd] D-halves; XLA permutes both
        q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        interpret=registry.resolve_interpret(interpret),
    )(bt, lens, q, k_pages, v_pages, k_scale, v_scale)
    if kv_bits == 4:
        out = out.reshape(b, h, 2, d // 2).swapaxes(-1, -2).reshape(b, h, d)
    return out
