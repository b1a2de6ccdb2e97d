"""Pallas TPU kernels: fused dequantize-matmul over quantized code planes.

The ZipML weight channel stores W as integer codes + fp32 scales
(int8, or nibble-packed int4 — two offset-binary codes per byte). These
kernels stream the code blocks HBM→VMEM (2×/4× fewer bytes than bf16 — the
memory-roofline win), dequantize in VMEM, and feed the MXU with fp32
accumulation. Three shapes cover the whole model path:

* ``qmm``   — forward  ``y[M, N] = x[M, K] · (codes[K, N] ⊙ scale[1, N])``
* ``qmm_t`` — transpose ``dx[M, K] = dy[M, N] · (codes[K, N] ⊙ scale)ᵀ`` —
  the code-domain backward (HALP's point: the bwd must stay low-precision
  too, or the bandwidth win evaporates). Also the tied-readout forward
  ``logits = h · tableᵀ``.
* ``qmm_qout`` — forward with a fused **quantize epilogue**: when the
  consumer is a quantized activation channel, the §2.2 double-sampling pair
  (row scales + both int8 code planes) is emitted straight from the fp32
  accumulator tile in VMEM — the full-width activation never touches HBM
  (mirrors kernels/stoch_quant.ds_quant, but fused at the matmul output).

Blocking: (bm, bk)×(bk, bn); ``bm/bk/bn=None`` resolve through
``registry.resolve_block`` — autotune-cache winner per (op, dtype,
shape-bucket) when repro.perf.autotune has tuned this hardware, else the
hand-picked defaults (bm=bn=256, bk=512 → VMEM working set bm·bk·2 +
bk·bn·1 + bm·bn·4 ≈ 0.6 MiB). The contraction axis is the sequential minor
grid axis so the fp32 accumulator tile lives across its loop. All dims
padded to multiples of 128 by the caller (ops.py) — MXU-aligned; resolved
blocks are fitted so every grid axis tiles its dim exactly. ``qmm_qout``
holds a (bm, N) accumulator (N unblocked), so its VMEM bound is
bm·N·(4+4+2·1) bytes — callers cap bm accordingly.

Packed int4 never interleaves lanes in a kernel (Mosaic cannot): a packed
block unpacks to its even and odd columns, which the kernels keep as two
planes. Scales, dy and rand enter split that way and outputs leave that way;
the wrappers permute them in XLA, and a packed ``bn`` is at least 256 so a
block's 128 code bytes fill the lanes.

``interpret=None`` resolves through :func:`repro.kernels.registry.
interpret_default` — the ONE place deciding real-compile vs interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry


def _dequant_block(w, scale):
    """int8 codes block (bk, bn) + scale (1, bn) → dequantized (bk, bn) f32.

    Dequantizes at full f32 precision in VMEM — the same values as
    ``QTensor.decode()`` (f32 default), i.e. strictly *more* accurate than
    the ref backend's bf16 decode-then-einsum, whose decoded weight carries
    one bf16 rounding. Parity vs ref is therefore bounded by bf16 epsilon;
    the code-domain gradient matches the f32 decode path to f32-accumulation
    associativity (≤ 1e-5 rel — the bench CHECK)."""
    return w.astype(jnp.float32) * scale.astype(jnp.float32)


def _nibble_halves(w, scale2):
    """Packed-int4 block (bk, bn/2) uint8 + split scale (2, bn/2) → the
    (even, odd) column halves, each (bk, bn/2) f32 — pack_int4's offset
    nibbles, unpacked in place. Mosaic has no in-kernel lane interleave, so
    the halves stay apart: packed kernels take and give [even, odd] planes
    that the wrappers (:func:`_split_cols` / :func:`_join_cols`) permute."""
    c = w.astype(jnp.int32)          # Mosaic casts no unsigned int to float
    s = scale2.astype(jnp.float32)
    return (((c & 0xF) - 8).astype(jnp.float32) * s[0:1],
            ((c >> 4) - 8).astype(jnp.float32) * s[1:2])


def _split_cols(a):
    """(…, N) → (2, …, N/2): the even and the odd columns."""
    a = a.reshape(*a.shape[:-1], a.shape[-1] // 2, 2)
    return jnp.moveaxis(a, -1, 0)


def _join_cols(a2):
    """Inverse of :func:`_split_cols`."""
    a = jnp.moveaxis(a2, 0, -1)
    return a.reshape(*a.shape[:-2], a.shape[-2] * 2)


def _packed_bn(bn: int, n: int) -> int:
    """A packed block's byte width bn/2 must fill the 128 lanes (or be the
    whole array): lift narrower resolved blocks to 256 columns."""
    return bn if bn % 256 == 0 or bn == n else registry.fit_block(256, n)


def _qmm_kernel(x_ref, w_ref, scale_ref, o_ref, *, packed: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    if packed:
        for half, w in enumerate(_nibble_halves(w_ref[...], scale_ref[...])):
            o_ref[half] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    else:
        w = _dequant_block(w_ref[...], scale_ref[...])
        o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


def _qmm_t_kernel(g_ref, w_ref, scale_ref, o_ref, *, packed: bool):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # dx[bm, bk] += dy[bm, bn] · w[bk, bn]ᵀ — contraction over the N axis
    dims = (((1,), (1,)), ((), ()))
    if packed:
        for half, w in enumerate(_nibble_halves(w_ref[...], scale_ref[...])):
            o_ref[...] += jax.lax.dot_general(
                g_ref[half].astype(jnp.float32), w, dims,
                preferred_element_type=jnp.float32)
    else:
        w = _dequant_block(w_ref[...], scale_ref[...])
        o_ref[...] += jax.lax.dot_general(
            g_ref[...].astype(jnp.float32), w, dims,
            preferred_element_type=jnp.float32)


def _qmv_kernel(c_ref, v_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    c = c_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(c, v_ref[...], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("br", "bc", "interpret"))
def qmv(codes: jax.Array, v: jax.Array, *, br: int | None = None,
        bc: int | None = None, interpret: bool | None = None) -> jax.Array:
    """int8 codes (R, C) · f32 v (C, 1) → (R, 1) f32, fp32 accumulation.

    The double-sampling gradient q₁ᵀ(q₂x − b) reduces to two of these matvecs
    on raw code planes (scales factor out), so the samples stream HBM→VMEM as
    int8 — 4× fewer bytes than the dequantized-f32 two-pass path.
    ``br/bc=None`` resolve through registry.resolve_block (autotune cache →
    hand-picked default, fitted to the dims); ops.int8_matvec is the padded
    entry point.
    """
    interpret = registry.resolve_interpret(interpret)
    r, c = codes.shape
    br, bc = registry.resolve_block("qmv", {"br": r, "bc": c}, dtype="int8",
                                   explicit={"br": br, "bc": bc})
    grid = (pl.cdiv(r, br), pl.cdiv(c, bc))
    return pl.pallas_call(
        _qmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, k: (i, k)),
            pl.BlockSpec((bc, 1), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=interpret,
    )(codes, v)


@functools.partial(jax.jit,
                   static_argnames=("packed", "bm", "bk", "bn", "interpret"))
def qmm(x: jax.Array, codes: jax.Array, scale: jax.Array, *,
        packed: bool = False, bm: int | None = None, bk: int | None = None,
        bn: int | None = None, interpret: bool | None = None) -> jax.Array:
    """x: (M, K) bf16/f32 · codes (K, N) int8 [or (K, N/2) packed-int4 uint8]
    with scale (1, N) → (M, N) f32.

    ``bm/bk/bn=None`` resolve through registry.resolve_block (autotune cache
    → hand-picked default), fitted so every grid axis tiles its dim exactly —
    use ops.quant_dense_apply for the padded general entry point.
    """
    interpret = registry.resolve_interpret(interpret)
    m, k = x.shape
    k2, n = codes.shape
    if packed:
        n *= 2
    assert k == k2, (x.shape, codes.shape)
    assert scale.shape == (1, n), (scale.shape, n)
    bm, bk, bn = registry.resolve_block(
        "qmm", {"bm": m, "bk": k, "bn": n},
        dtype="int4" if packed else "int8",
        explicit={"bm": bm, "bk": bk, "bn": bn})
    planes = 2 if packed else 1     # packed: [even, odd] column planes
    if packed:
        bn = _packed_bn(bn, n)
        scale = _split_cols(scale)[:, 0]
    w = bn // planes
    lead = (planes,) if packed else ()
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    y = pl.pallas_call(
        functools.partial(_qmm_kernel, packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, w), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((planes, w), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((*lead, bm, w),
                               lambda i, j, kk: (*(0,) * len(lead), i, j)),
        out_shape=jax.ShapeDtypeStruct((*lead, m, n // planes), jnp.float32),
        interpret=interpret,
    )(x, codes, scale)
    return _join_cols(y) if packed else y


@functools.partial(jax.jit,
                   static_argnames=("packed", "bm", "bk", "bn", "interpret"))
def qmm_t(g: jax.Array, codes: jax.Array, scale: jax.Array, *,
          packed: bool = False, bm: int | None = None, bk: int | None = None,
          bn: int | None = None, interpret: bool | None = None) -> jax.Array:
    """g: (M, N) · codes (K, N) [or (K, N/2) packed] with scale (1, N)
    → (M, K) f32: the transpose product ``g · (codes ⊙ scale)ᵀ``.

    This is the code-domain backward of ``qmm`` (dx streams int8 HBM→VMEM
    instead of re-decoding a bf16 weight) and the tied-unembed forward
    (logits = h · tableᵀ). Contraction runs over N as the sequential minor
    grid axis; ``bm/bk/bn=None`` resolve through registry.resolve_block —
    see ops.quant_dense_apply for the padded entry point.
    """
    interpret = registry.resolve_interpret(interpret)
    m, n = g.shape
    k, n2 = codes.shape
    if packed:
        n2 *= 2
    assert n == n2, (g.shape, codes.shape)
    assert scale.shape == (1, n), (scale.shape, n)
    bm, bk, bn = registry.resolve_block(
        "qmm_t", {"bm": m, "bk": k, "bn": n},
        dtype="int4" if packed else "int8",
        explicit={"bm": bm, "bk": bk, "bn": bn})
    planes = 2 if packed else 1     # packed: [even, odd] column planes
    if packed:
        bn = _packed_bn(bn, n)
        g, scale = _split_cols(g), _split_cols(scale)[:, 0]
    w = bn // planes
    lead = (planes,) if packed else ()
    grid = (pl.cdiv(m, bm), pl.cdiv(k, bk), pl.cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_qmm_t_kernel, packed=packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((*lead, bm, w),
                         lambda i, j, nn: (*(0,) * len(lead), i, nn)),
            pl.BlockSpec((bk, w), lambda i, j, nn: (j, nn)),
            pl.BlockSpec((planes, w), lambda i, j, nn: (0, nn)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, nn: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(g, codes, scale)


_EPILOGUE_ROWS = 32


def _qmm_qout_kernel(x_ref, w_ref, scale_ref, rand_ref, c1_ref, c2_ref,
                     os_ref, acc_ref, *, packed: bool, qmax: int,
                     out_dtype):
    """GEMM with fused double-sampling quantize epilogue.

    The (bm, N) fp32 accumulator lives in VMEM scratch across the K loop; at
    the last K step the row absmax → scale, and both §2.2 stochastic planes
    are emitted from the high/low 16 bits of one uint32 rand plane — the
    exact rounding convention of kernels/stoch_quant._ds_quant_kernel, so
    fused and unfused (qmm → ds row quantize) paths are bit-identical given
    the same rand bits. The full-width activation never reaches HBM.
    """
    kk = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    if packed:
        for half, w in enumerate(_nibble_halves(w_ref[...], scale_ref[...])):
            acc_ref[half] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    else:
        w = _dequant_block(w_ref[...], scale_ref[...])
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _epilogue():
        bm = os_ref.shape[0]
        rows = _EPILOGUE_ROWS if bm % _EPILOGUE_ROWS == 0 else bm

        def chunk(c, carry):
            # row chunks keep the epilogue's full-width temporaries in VMEM
            r = pl.ds(pl.multiple_of(c * rows, rows), rows)
            # quantize the dtype-rounded activation (bf16 in the model) so
            # the fused path matches einsum→astype(x.dtype)→quantize exactly
            y = acc_ref[..., r, :].astype(out_dtype).astype(jnp.float32)
            absmax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
            if packed:                          # over both column planes
                absmax = jnp.maximum(absmax[0], absmax[1])
            scale = jnp.where(absmax == 0, 1.0, absmax / qmax)    # (rows, 1)
            t = y / scale
            base = jnp.floor(t)
            frac = t - base
            u = rand_ref[..., r, :]
            u1 = (u >> 16).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 16))
            u2 = (u & 0xFFFF).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 16))
            c1 = jnp.clip(base + (u1 < frac).astype(jnp.float32), -qmax, qmax)
            c2 = jnp.clip(base + (u2 < frac).astype(jnp.float32), -qmax, qmax)
            c1_ref[..., r, :] = c1.astype(jnp.int8)
            c2_ref[..., r, :] = c2.astype(jnp.int8)
            os_ref[r, :] = scale.astype(jnp.float32)
            return carry

        jax.lax.fori_loop(0, bm // rows, chunk, 0)


@functools.partial(jax.jit, static_argnames=(
    "packed", "qmax", "out_dtype", "bm", "bk", "interpret"))
def qmm_qout(x: jax.Array, codes: jax.Array, scale: jax.Array,
             rand: jax.Array, *, qmax: int, packed: bool = False,
             out_dtype=jnp.bfloat16, bm: int | None = None,
             bk: int | None = None, interpret: bool | None = None):
    """Fused ``y = x·dequant(codes)`` + double-sampled row quantization of y.

    x: (M, K); codes (K, N[/2]); scale (1, N); rand (M, N) uint32. Returns
    (codes1, codes2) int8 (M, N) and row scales (M, 1) f32 — the symmetric
    int-grid DS pair of y.astype(out_dtype), with y never written to HBM.
    N is unblocked (full-width accumulator row in VMEM); ``bm/bk=None``
    resolve through registry.resolve_block — ops.quant_dense_out_q is the
    padded entry point.
    """
    interpret = registry.resolve_interpret(interpret)
    m, k = x.shape
    k2, n = codes.shape
    if packed:
        n *= 2
    assert k == k2, (x.shape, codes.shape)
    assert scale.shape == (1, n) and rand.shape == (m, n)
    bm, bk = registry.resolve_block(
        "qmm_qout", {"bm": m, "bk": k},
        dtype="int4" if packed else "int8", explicit={"bm": bm, "bk": bk})
    planes = 2 if packed else 1     # packed: [even, odd] column planes
    if packed:
        scale, rand = _split_cols(scale)[:, 0], _split_cols(rand)
    w = n // planes
    lead = (planes,) if packed else ()
    z = (0,) * len(lead)
    grid = (pl.cdiv(m, bm), pl.cdiv(k, bk))
    out_block = pl.BlockSpec((*lead, bm, w), lambda i, kk: (*z, i, 0))
    c1, c2, oscale = pl.pallas_call(
        functools.partial(_qmm_qout_kernel, packed=packed, qmax=qmax,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk: (i, kk)),
            pl.BlockSpec((bk, w), lambda i, kk: (kk, 0)),
            pl.BlockSpec((planes, w), lambda i, kk: (0, 0)),
            out_block,
        ],
        out_specs=[out_block, out_block,
                   pl.BlockSpec((bm, 1), lambda i, kk: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((*lead, m, w), jnp.int8),
                   jax.ShapeDtypeStruct((*lead, m, w), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((*lead, bm, w), jnp.float32)],
        interpret=interpret,
    )(x, codes, scale, rand)
    if packed:
        c1, c2 = _join_cols(c1), _join_cols(c2)
    return c1, c2, oscale
