"""Pallas TPU kernel: fused quantized-moment AdamW update.

The optimizer sweep is the last per-step full-tree HBM pass not behind a
fused kernel: the jnp path decodes the int8 m/v codes to f32 (round-trip 1),
updates them (round-trip 2), then re-quantizes — absmax reduction plus a
stochastic-rounding pass (round-trip 3), materializing two full fp32 moment
tensors in HBM along the way. The fused pipeline never materializes them:

* pass 1 (``qadamw_absmax``) recomputes the new m / √v per tile in VMEM from
  (codes, scales, g) and emits only per-tile column absmaxes — the host
  reduces those to the new quantization scales (the row-block axis is the
  major grid axis, so a revisited accumulator block would not stay resident
  across it).
* pass 2 (``qadamw_update``) recomputes m / v again, writes the new fp32
  master and both int8 code planes in one VMEM pass.

g and the code planes are read twice (int8 + f32 streams); the fp32 moments
exist only as VMEM tiles. Rounding consumes the high/low 16 bits of one
explicit uint32 plane (m and v draws are independent), exactly like
``stoch_quant.ds_quant``. Unlike ds_quant the tile math contains adds of
products (the EMA), which XLA may or may not contract to FMAs depending on
the surrounding program — so the pinned contract against the jnp mirror
(``ref.quant_adamw_ref``) is one-ULP parity on masters/scales plus exact
agreement of (almost all) code planes, not bitwise equality
(tests/test_quant_adamw.py).

Scalar step inputs (clip, finite, lr, bias corrections) arrive as one (8,)
f32 SMEM operand — they are traced values (lr depends on the step counter),
so they cannot be baked in statically like b1/b2/eps/wd.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry


DEFAULT_BLOCK = (256, 512)

# params layout in the (8,) f32 SMEM operand
P_CLIP, P_FINITE, P_LR, P_B1C, P_B2C = 0, 1, 2, 3, 4


def _resolve(block, r: int, c: int):
    """``block=None`` → registry.resolve_block("quant_adamw", …): autotune
    winner per shape-bucket when tuned, else DEFAULT_BLOCK, fitted so both
    grid axes tile exactly. Both passes resolve independently — pass 1's
    per-row-block absmax output is reduced on the host, so the passes don't
    need matching blocks."""
    explicit = {"br": block[0], "bc": block[1]} if block is not None else {}
    return registry.resolve_block("quant_adamw", {"br": r, "bc": c},
                                  dtype="f32", explicit=explicit)


def _moments(g, m_codes, m_scale, v_codes, v_scale, clip, finite,
             *, b1: float, b2: float):
    """Shared tile math: decode old moments, apply the EMA update, select
    prev on non-finite steps. Returns (m_prev, v_prev, m_store, v_store)."""
    g32 = g.astype(jnp.float32) * clip
    m_prev = m_codes.astype(jnp.float32) * m_scale
    v_sqrt = v_codes.astype(jnp.float32) * v_scale
    v_prev = v_sqrt * v_sqrt
    m = b1 * m_prev + (1 - b1) * g32
    v = b2 * v_prev + (1 - b2) * g32 * g32
    ok = finite > 0
    return m_prev, v_prev, jnp.where(ok, m, m_prev), jnp.where(ok, v, v_prev)


def _absmax_kernel(g_ref, mc_ref, ms_ref, vc_ref, vs_ref, par_ref,
                   mx_ref, vx_ref, *, b1: float, b2: float):
    """Per-(row-block, col-block) column absmax of the *stored* new m and √v,
    written to all 8 sublanes of an (8, bc) block (the TPU's tile height)."""
    _, _, m_store, v_store = _moments(
        g_ref[...], mc_ref[...], ms_ref[...].astype(jnp.float32),
        vc_ref[...], vs_ref[...].astype(jnp.float32),
        par_ref[P_CLIP], par_ref[P_FINITE], b1=b1, b2=b2)
    mx_ref[...] = jnp.broadcast_to(
        jnp.max(jnp.abs(m_store), axis=0, keepdims=True), mx_ref.shape)
    vx_ref[...] = jnp.broadcast_to(
        jnp.max(jnp.sqrt(v_store), axis=0, keepdims=True), vx_ref.shape)


def _update_kernel(mst_ref, g_ref, mc_ref, ms_ref, vc_ref, vs_ref,
                   msn_ref, vsn_ref, rand_ref, par_ref,
                   out_mst, out_mc, out_vc,
                   *, b1: float, b2: float, eps: float, wd: float, qmax: int,
                   uclip: float):
    """Decode → AdamW update → stochastic re-encode, one VMEM tile at a time."""
    finite = par_ref[P_FINITE]
    m_prev, v_prev, m_store, v_store = _moments(
        g_ref[...], mc_ref[...], ms_ref[...].astype(jnp.float32),
        vc_ref[...], vs_ref[...].astype(jnp.float32),
        par_ref[P_CLIP], finite, b1=b1, b2=b2)
    # master update uses the un-requantized moments (decode error enters once)
    update = (m_store / par_ref[P_B1C]) / (
        jnp.sqrt(v_store / par_ref[P_B2C]) + eps)
    if uclip:
        # √v-underflow guard: see AdamWConfig.update_clip
        update = jnp.clip(update, -uclip, uclip)
    mst = mst_ref[...].astype(jnp.float32)
    new_mst = mst - par_ref[P_LR] * (update + wd * mst)
    out_mst[...] = jnp.where(finite > 0, new_mst, mst)
    # stochastic re-encode: independent 16-bit up/down draws for m and √v
    u = rand_ref[...]
    u1 = (u >> 16).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 16))
    u2 = (u & 0xFFFF).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 16))
    m_t = m_store / msn_ref[...].astype(jnp.float32)
    lo = jnp.floor(m_t)
    mc = lo + (u1 < (m_t - lo)).astype(jnp.float32)
    out_mc[...] = jnp.clip(mc, -qmax, qmax).astype(jnp.int8)
    v_t = jnp.sqrt(v_store) / vsn_ref[...].astype(jnp.float32)
    lo2 = jnp.floor(v_t)
    vc = lo2 + (u2 < (v_t - lo2)).astype(jnp.float32)
    out_vc[...] = jnp.clip(vc, -qmax, qmax).astype(jnp.int8)


def _specs(br, bc):
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    colrow = pl.BlockSpec((1, bc), lambda i, j: (0, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return tile, colrow, smem


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "block", "interpret"))
def qadamw_absmax(g, m_codes, m_scale, v_codes, v_scale, params, *,
                  b1: float, b2: float, block=None,
                  interpret: bool | None = None):
    """g (R, C) f32; codes (R, C) int8; scales (1, C) f32; params (8,) f32.
    Returns per-row-block column absmaxes: (8·R/br, C) for new-m and new-√v
    (each row block's maxima repeated on 8 rows)."""
    r, c = g.shape
    br, bc = _resolve(block, r, c)
    grid = (pl.cdiv(r, br), pl.cdiv(c, bc))
    tile, colrow, smem = _specs(br, bc)
    out_spec = pl.BlockSpec((8, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_absmax_kernel, b1=b1, b2=b2),
        grid=grid,
        in_specs=[tile, tile, colrow, tile, colrow, smem],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((8 * grid[0], c), jnp.float32),
                   jax.ShapeDtypeStruct((8 * grid[0], c), jnp.float32)],
        interpret=registry.resolve_interpret(interpret),
    )(g, m_codes, m_scale, v_codes, v_scale, params)


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "wd", "qmax", "uclip",
                                    "block", "interpret"))
def qadamw_update(master, g, m_codes, m_scale, v_codes, v_scale,
                  m_scale_new, v_scale_new, rand, params, *,
                  b1: float, b2: float, eps: float, wd: float, qmax: int,
                  uclip: float = 0.0, block=None,
                  interpret: bool | None = None):
    """The pass-2 fused update. master/g (R, C) f32; codes (R, C) int8;
    old/new scales (1, C) f32; rand (R, C) uint32; params (8,) f32.
    Returns (new_master f32, new_m_codes int8, new_v_codes int8)."""
    r, c = master.shape
    br, bc = _resolve(block, r, c)
    grid = (pl.cdiv(r, br), pl.cdiv(c, bc))
    tile, colrow, smem = _specs(br, bc)
    return pl.pallas_call(
        functools.partial(_update_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                          qmax=qmax, uclip=uclip),
        grid=grid,
        in_specs=[tile, tile, tile, colrow, tile, colrow, colrow, colrow,
                  tile, smem],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.float32),
                   jax.ShapeDtypeStruct((r, c), jnp.int8),
                   jax.ShapeDtypeStruct((r, c), jnp.int8)],
        interpret=registry.resolve_interpret(interpret),
    )(master, g, m_codes, m_scale, v_codes, v_scale,
      m_scale_new, v_scale_new, rand, params)
