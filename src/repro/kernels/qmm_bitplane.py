"""Pallas TPU kernel: bit-plane (MLWeaving) dequant-GEMM.

``y[M, N] = x[M, K] · decode(planes[P, K, W] ⊙ scale[1, N])``

The weight is stored bit-serially (``repro.quant.pack_bitplanes``): plane 0
is the sign, planes 1..k the magnitude MSB-first, each plane packing 32
consecutive N-elements per uint32 word (W = N/32). The kernel streams ONLY
the planes present in the operand HBM→VMEM — the block carries the full
plane axis, which is tiny (≤ 9) — so serving a ``slice_planes(k)`` view
moves (k+1)/(B+1) of the full artifact's code bytes with zero repacking.

Codes are reconstructed in-register, one bit position j of the words at a
time: shift+mask per plane, a plane-weighted sum for the magnitude, then
``sign · mag · 2^-k · scale`` — the weight columns 32·w + j, multiplied
into output plane j. Mosaic cannot spread a word's bits across lanes, so
the output (and the scale) go by bit position, ``(32, M, W)``, and XLA
permutes the output back to column order after the kernel.
The reconstruction is value-identical to ``QTensor.decode()`` of the same
planes (integers < 2^8 are exact in f32 and the plane weights are powers of
two; pinned by tests/test_bitplane.py), so parity vs the ref backend is
bounded by the usual bf16-decode epsilon.

Blocking mirrors ``qmm``: (bm, bk)×(bk, bn) with the contraction as the
sequential minor grid axis and an fp32 accumulator tile; a block spans
``bn/32`` words, a multiple of 128 (the lanes) or the whole word axis.
``bm/bk/bn=None`` resolve through ``registry.resolve_block``;
ops.quant_dense_bitplane is the padded entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry


def _qmm_bitplane_kernel(x_ref, w_ref, scale_ref, o_ref, *, k_bits: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    # uint32 words as int32 (Mosaic casts no unsigned int to float); the
    # arithmetic shift's sign fill never reaches the masked bit
    words = w_ref[...].astype(jnp.int32)          # (P, bk, bw)
    for j in range(32):                           # static: bit j of each word
        bits = [(words[i] >> j) & 1 for i in range(k_bits + 1)]
        mag = bits[1] << (k_bits - 1)
        for i in range(1, k_bits):                # static: k_bits ≤ 8 planes
            mag = mag | (bits[1 + i] << (k_bits - 1 - i))
        sign = 1.0 - 2.0 * bits[0].astype(jnp.float32)
        w = (sign * mag.astype(jnp.float32) * (2.0 ** -k_bits)
             * scale_ref[j].astype(jnp.float32))  # columns 32·w + j
        o_ref[j] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def _word_block(bn: int, w: int) -> int:
    """The block's word width: lane-dense (a multiple of 128 words) or the
    whole word axis."""
    bw = bn // 32
    return bw if bw % 128 == 0 and w % bw == 0 else w


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def qmm_bitplane(x: jax.Array, planes: jax.Array, scale: jax.Array, *,
                 bm: int | None = None, bk: int | None = None,
                 bn: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """x: (M, K) bf16/f32 · bitplane codes (P, K, N/32) uint32 with scale
    (1, N) → (M, N) f32. P = k_bits + 1 (sign plane first).

    Bytes streamed for the weight are K·N·P/8 — linear in the requested
    planes. ``bm/bk/bn=None`` resolve through registry.resolve_block; use
    ops.quant_dense_bitplane for the padded general entry point.
    """
    interpret = registry.resolve_interpret(interpret)
    m, k = x.shape
    p, k2, w = planes.shape
    n = w * 32
    assert k == k2, (x.shape, planes.shape)
    assert scale.shape == (1, n), (scale.shape, n)
    bm, bk, bn = registry.resolve_block(
        "qmm_bitplane", {"bm": m, "bk": k, "bn": n}, dtype="uint32",
        explicit={"bm": bm, "bk": bk, "bn": bn})
    bw = _word_block(bn, w)
    grid = (pl.cdiv(m, bm), w // bw, pl.cdiv(k, bk))
    # bit j of word v is column 32·v + j: scale and output go by bit plane
    # (32, ·, W), never interleaved in the kernel, and XLA permutes them
    scale_j = scale.reshape(w, 32).T
    y = pl.pallas_call(
        functools.partial(_qmm_bitplane_kernel, k_bits=p - 1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((p, bk, bw), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((32, bw), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((32, bm, bw), lambda i, j, kk: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((32, m, w), jnp.float32),
        interpret=interpret,
    )(x, planes, scale_j)
    return y.transpose(1, 2, 0).reshape(m, n)
