"""jit'd public wrappers around the Pallas kernels (padding, reshaping).

Interpret-mode selection lives in ONE place — :func:`repro.kernels.registry.
interpret_default` (real compile on TPU, interpret on CPU/CI or under
``ZIPML_PALLAS_INTERPRET=1``); every kernel entry point defaults
``interpret=None`` and resolves there, so no caller can silently pin
interpret-mode Pallas into a hot loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import paged_attn as pa_mod
from . import qmm as qmm_mod
from . import qmm_bitplane as qbp_mod
from . import quant_adamw as qa_mod
from . import ssd as ssd_mod
from . import stoch_quant as sq_mod



def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def quantize_rows(x: jax.Array, s: int, key: jax.Array):
    """Row-scaled stochastic quantization via the Pallas pipeline.

    x: (R, C) → (codes int8 in [-s, s], scale (R, 1) f32). Unbiased:
    E[codes/s·scale] = x.
    """
    assert x.ndim == 2
    scale = sq_mod.row_absmax(x)
    rand = jax.random.bits(key, x.shape, jnp.uint32)
    codes = sq_mod.stoch_quant(x, rand, scale, s=s)
    return codes, scale


def dequantize_rows(codes: jax.Array, scale: jax.Array, s: int) -> jax.Array:
    return codes.astype(jnp.float32) / s * scale


def ds_quantize(x: jax.Array, s: int, key: jax.Array,
                scale: jax.Array | None = None):
    """Fused double-sampling quantization: both Q₁/Q₂ int8 code planes from a
    single streaming pass over x (paper §2.2 — shared base + 1 extra bit).

    ``scale=None`` → per-row absmax scales (R, 1); a (C,)/(1, C) array selects
    column scaling (the data-pipeline convention); a scalar broadcasts.
    Returns (codes1, codes2, scale) with E[codesᵢ/s·scale] = x.
    """
    assert x.ndim == 2
    r, c = x.shape
    if scale is None:
        scale = sq_mod.row_absmax(x)
        scale_axis = "row"
    elif jnp.shape(scale) == (r, 1):
        scale = jnp.asarray(scale, jnp.float32)
        scale_axis = "row"
    else:
        scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(1, -1),
                                 (1, c))
        scale_axis = "col"
    rand = jax.random.bits(key, x.shape, jnp.uint32)
    c1, c2 = sq_mod.ds_quant(x, rand, scale, s=s, scale_axis=scale_axis)
    return c1, c2, scale


def int8_matvec(codes: jax.Array, v: jax.Array) -> jax.Array:
    """General r = codes · v for int8 (R, C) codes and f32 (C,) v; pads both
    dims to 128 multiples (zero padding is exact for the dot) and slices.
    Block shapes resolve inside the kernel (registry.resolve_block: autotune
    cache → hand-picked default, fitted so every grid axis tiles exactly —
    partial blocks on a contraction axis would fold garbage into outputs).
    """
    r0, c0 = codes.shape
    codes, _ = _pad_to(codes, 128, 0)
    codes, _ = _pad_to(codes, 128, 1)
    v2, _ = _pad_to(v.reshape(-1, 1).astype(jnp.float32), 128, 0)
    out = qmm_mod.qmv(codes, v2)
    return out[:r0, 0]


def ds_gradient_from_codes(codes1: jax.Array, codes2: jax.Array,
                           x: jax.Array, b: jax.Array, scale: jax.Array,
                           s: int) -> jax.Array:
    """Symmetrized double-sampling LSQ gradient ½[q₁ᵀr₂ + q₂ᵀr₁]/B straight
    from int8 code planes + scales — no dequantized f32 sample tensor exists.

    With column scale m (broadcast over rows), qᵢ = cᵢ ⊙ m / s, so
    qᵢᵀ(qⱼx − b) = m ⊙ (cᵢᵀ rⱼ)/s with rⱼ = cⱼ(m ⊙ x)/s − b: four int8
    matvecs total, all streaming codes at 1 byte/elem.
    """
    B = codes1.shape[0]
    m = jnp.asarray(scale, jnp.float32).reshape(-1)
    xs = x.astype(jnp.float32) * m
    r1 = int8_matvec(codes1, xs) / s - b
    r2 = int8_matvec(codes2, xs) / s - b
    g = int8_matvec(codes1.T, r2) + int8_matvec(codes2.T, r1)
    return g * m / (2.0 * B * s)


def quantized_matmul(x: jax.Array, codes: jax.Array, scale: jax.Array) -> jax.Array:
    """General y = x · dequant(codes, scale); pads all dims to 128 multiples
    for MXU alignment, slices the result back."""
    m0, k0 = x.shape
    _, n0 = codes.shape
    x, _ = _pad_to(x, 128, 0)
    x, _ = _pad_to(x, 128, 1)
    codes, _ = _pad_to(codes, 128, 0)
    codes, _ = _pad_to(codes, 128, 1)
    scale, _ = _pad_to(scale, 128, 1)
    y = qmm_mod.qmm(x, codes, scale)
    return y[:m0, :n0]


def quant_dense_apply(x: jax.Array, codes: jax.Array, scale: jax.Array, *,
                      packed: bool = False,
                      transpose: bool = False) -> jax.Array:
    """General y = x · dequant(codes, scale)[ᵀ] for 2-D code planes.

    x: (*lead, K) [or (*lead, N) transposed]; codes (K, N) int8 or
    (K, N/2) packed-int4 uint8; scale (1, N) f32 (zipml grids pre-divide by
    s). Leading x dims fold into the GEMM M axis; every dim pads to 128
    multiples (zero padding is exact: padded x/g entries are 0, padded
    output rows/cols are sliced off) and packed planes pad to 128 bytes.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m0 = x2.shape[0]
    k0 = codes.shape[0]
    n0 = codes.shape[1] * (2 if packed else 1)
    pdiv = 2 if packed else 1
    x2, _ = _pad_to(x2, 128, 0)
    x2, _ = _pad_to(x2, 128 * pdiv if transpose else 128, 1)
    codes, _ = _pad_to(codes, 128, 0)
    codes, _ = _pad_to(codes, 128, 1)
    scale, _ = _pad_to(scale, 128 * pdiv, 1)
    if transpose:
        y = qmm_mod.qmm_t(x2, codes, scale, packed=packed)
        return y[:m0, :k0].reshape(*lead, k0)
    y = qmm_mod.qmm(x2, codes, scale, packed=packed)
    return y[:m0, :n0].reshape(*lead, n0)


def quant_dense_bitplane(x: jax.Array, codes: jax.Array, scale: jax.Array,
                         n_out: int) -> jax.Array:
    """y = x · decode(bitplane codes) for a 2-D logical weight.

    x: (*lead, K); codes (P, K, W) uint32 with W = ⌈n_out/32⌉ (plane 0 =
    sign, then magnitude MSB-first); scale (1, n_out) f32. Leading x dims
    fold into the GEMM M axis; M/K pad to 128 multiples and the word axis
    to 4-word (128-column) multiples, or past 128 words to 128-word ones
    (the kernel's lane-dense word block) — zero words decode to +0·scale,
    and the padded output columns are sliced off.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m0 = x2.shape[0]
    x2, _ = _pad_to(x2, 128, 0)
    x2, _ = _pad_to(x2, 128, 1)
    words = 128 if codes.shape[2] > 128 else 4
    codes, _ = _pad_to(codes, 128, 1)
    codes, _ = _pad_to(codes, words, 2)
    scale, _ = _pad_to(scale, 32 * words, 1)
    y = qbp_mod.qmm_bitplane(x2, codes, scale)
    return y[:m0, :n_out].reshape(*lead, n_out)


def quant_dense_out_q(x: jax.Array, codes: jax.Array, scale: jax.Array,
                      rand: jax.Array, *, qmax: int, packed: bool = False,
                      out_dtype=jnp.bfloat16):
    """Fused GEMM + double-sampled row quantization of the output
    (kernels/qmm.qmm_qout). x: (M, K); codes (K, N[/2]); scale (1, N);
    rand (M, N) uint32. Returns (codes1, codes2 (M, N) int8, row scales
    (M, 1) f32); the full-width activation never reaches HBM. Only M/K pad
    (zero rows are exact); N stays the true output width — the row absmax
    must not see padding garbage.
    """
    m0, _ = x.shape
    x, _ = _pad_to(x, 128, 0)
    x, _ = _pad_to(x, 128, 1)
    codes, _ = _pad_to(codes, 128, 0)
    rand, _ = _pad_to(rand, 128, 0)
    c1, c2, oscale = qmm_mod.qmm_qout(
        x, codes, scale, rand, qmax=qmax, packed=packed, out_dtype=out_dtype)
    return c1[:m0], c2[:m0], oscale[:m0]


def quant_adamw_update(master, g, m_codes, m_scale, v_codes, v_scale, rand, *,
                       qmax: int, b1: float, b2: float, eps: float, wd: float,
                       lr, b1c, b2c, clip, finite, uclip: float = 0.0):
    """Fused quantized-moment AdamW leaf update via the two-pass Pallas
    pipeline (kernels/quant_adamw.py): pass 1 reduces the new-moment column
    absmaxes (→ new scales), pass 2 decodes/updates/re-encodes per VMEM tile.
    The fp32 moment tensors never hit HBM.

    master/g: (R, C) f32; codes (R, C) int8; scales (C,) f32; rand (R, C)
    uint32 (hi/lo 16 bits drive the m and √v draws). lr/b1c/b2c/clip/finite
    are traced per-step scalars. Returns
    (new_master, m_codes, m_scale_new, v_codes, v_scale_new), scales (C,).
    """
    r0, c0 = master.shape

    def pad2(t):
        t, _ = _pad_to(t, 128, 0)
        t, _ = _pad_to(t, 128, 1)
        return t

    master, g, rand = pad2(master.astype(jnp.float32)), \
        pad2(g.astype(jnp.float32)), pad2(rand)
    m_codes, v_codes = pad2(m_codes), pad2(v_codes)
    ms, _ = _pad_to(jnp.asarray(m_scale, jnp.float32).reshape(1, -1), 128, 1)
    vs, _ = _pad_to(jnp.asarray(v_scale, jnp.float32).reshape(1, -1), 128, 1)
    params = jnp.stack([
        jnp.asarray(clip, jnp.float32),
        jnp.asarray(finite, jnp.float32),
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(b1c, jnp.float32),
        jnp.asarray(b2c, jnp.float32),
        jnp.float32(0), jnp.float32(0), jnp.float32(0)])
    mx, vx = qa_mod.qadamw_absmax(g, m_codes, ms, v_codes, vs, params,
                                  b1=b1, b2=b2)
    mx = jnp.max(mx, axis=0)
    vx = jnp.max(vx, axis=0)
    msn = jnp.where(mx == 0, 1.0, mx / qmax).astype(jnp.float32)
    vsn = jnp.where(vx == 0, 1.0, vx / qmax).astype(jnp.float32)
    nm, mc, vc = qa_mod.qadamw_update(
        master, g, m_codes, ms, v_codes, vs,
        msn.reshape(1, -1), vsn.reshape(1, -1), rand, params,
        b1=b1, b2=b2, eps=eps, wd=wd, qmax=qmax, uclip=uclip)
    return (nm[:r0, :c0], mc[:r0, :c0], msn[:c0], vc[:r0, :c0], vsn[:c0])


def kv_bits_of(pages: jax.Array) -> int:
    """Infer the KV quantization width from a page plane's dtype (the pool's
    storage convention): uint8 = packed int4, int8 = int8, else bf16 (0)."""
    if pages.dtype == jnp.uint8:
        return 4
    if pages.dtype == jnp.int8:
        return 8
    return 0


def paged_attention(q, k_pages, v_pages, k_scale, v_scale, block_table,
                    seq_lens, *, softmax_scale: float):
    """Paged flash-decode attention via the Pallas kernel (in-kernel int8/int4
    dequant). q: (B, H, D); pages (P, page, Hkv, D[/2]); scales may be None
    (bf16 pool). Returns (B, H, D) in q.dtype.
    """
    hkv = k_pages.shape[2]
    if k_scale is None:
        k_scale = jnp.ones((1, 1, hkv, 1), jnp.float32)
        v_scale = jnp.ones((1, 1, hkv, 1), jnp.float32)
    out = pa_mod.paged_decode_attn(
        q, k_pages, v_pages, k_scale, v_scale, block_table, seq_lens,
        softmax_scale=float(softmax_scale), kv_bits=kv_bits_of(k_pages))
    return out.astype(q.dtype)


def ssd_chunked_kernel(xh, dt, a_log, b_mat, c_mat, chunk: int = 256):
    """Drop-in for models/ssm.ssd_chunked using the Pallas intra-chunk kernel.

    xh: (B, S, H, P); dt: (B, S, H); b/c: (B, S, G·N) with G=1.
    Returns (y (B, S, H, P), state (B, H, P, N)).
    """
    b, s, h, p = xh.shape
    L = min(chunk, s)
    if s % L:
        L = s
    nc = s // L
    a = -jnp.exp(a_log)
    logdec = (dt * a[None, None, :]).astype(jnp.float32)

    def chunked(t):
        return t.reshape(b, nc, L, *t.shape[2:])

    y, state = ssd_mod.ssd_chunk_scan(
        chunked(xh), chunked(dt), chunked(logdec),
        chunked(b_mat), chunked(c_mat))
    return y.reshape(b, s, h, p), state
