"""Pallas TPU kernel: stochastic quantization (C1) — the ZipML hot spot.

The FPGA pipeline quantizes samples in-line with the data stream; the TPU
analogue streams bf16/f32 blocks HBM→VMEM, rounds stochastically against
uniform random bits, and writes int8 codes (+ per-row scales computed in a
first reduction kernel). Rounding consumes explicit uint32 random bits passed
as an operand — `pltpu.prng_random_bits` exists on real TPUs, but an explicit
operand keeps the kernel bit-exact under `interpret=True` on CPU (how we
validate against ref.py).

Tiling: rows × 128-lane blocks; both MXU/VPU-aligned and big enough to keep
the VPU busy while the next block streams in. For a (R, C) input with block
(br, 128·k): VMEM footprint = br·128k·(4+4+1) bytes ≤ ~2 MiB per default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry


DEFAULT_BLOCK = (256, 512)


def _resolve(block, r: int, c: int, dtype: str):
    """``block=None`` → registry.resolve_block("ds_quant", …): the autotune
    cache winner for this (dtype, shape-bucket) when one exists, else
    DEFAULT_BLOCK — always fitted so both grid axes tile exactly."""
    explicit = {"br": block[0], "bc": block[1]} if block is not None else {}
    return registry.resolve_block("ds_quant", {"br": r, "bc": c},
                                  dtype=registry.dtype_key(dtype),
                                  explicit=explicit)


def _sq_kernel(x_ref, rand_ref, scale_ref, codes_ref, *, s: int):
    """One (br, bc) block: codes = sign ⊙ stochastic_round(|x|/scale · s)."""
    x = x_ref[...].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)          # (br, 1) row scales
    u = rand_ref[...]                                   # uint32
    # uniform in [0,1): top 24 bits / 2^24 (exact in f32); Mosaic casts no
    # unsigned int to float, so the shifted bits go through int32
    uf = (u >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    mag = jnp.abs(x) / jnp.maximum(scale, 1e-30)
    t = jnp.clip(mag, 0.0, 1.0) * s
    lo = jnp.clip(jnp.floor(t), 0, s - 1)
    codes = lo + (uf < (t - lo)).astype(jnp.float32)
    codes = codes * jnp.sign(x)
    codes_ref[...] = codes.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("s", "block", "interpret"))
def stoch_quant(x: jax.Array, rand: jax.Array, scale: jax.Array, *, s: int,
                block=None, interpret: bool | None = None):
    """x: (R, C) f32/bf16; rand: (R, C) uint32; scale: (R, 1) f32 row scales.
    Returns int8 codes in [-s, s]. ``block=None`` resolves through the
    autotune cache → DEFAULT_BLOCK. (interpret=True on CPU; False on TPU.)
    """
    r, c = x.shape
    br, bc = _resolve(block, r, c, x.dtype)
    grid = (pl.cdiv(r, br), pl.cdiv(c, bc))
    return pl.pallas_call(
        functools.partial(_sq_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int8),
        interpret=registry.resolve_interpret(interpret),
    )(x, rand, scale)


def _ds_quant_kernel(x_ref, rand_ref, scale_ref, c1_ref, c2_ref, *, s: int):
    """Fused double-sampling quantizer: one HBM read of x, two int8 code planes.

    Q₁/Q₂ share the base level ⌊|x|/scale·s⌋ (paper §2.2 'Overhead of Storing
    Samples': shipping both costs 1 extra up/down bit, not 2×) and differ only
    in independent Bernoulli(frac) up-bits, drawn from the high/low 16 bits of
    a single uint32 plane. E[Qᵢ] = x exactly up to 2⁻¹⁶ probability granularity.
    """
    x = x_ref[...].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)
    u = rand_ref[...]                                   # uint32
    u1 = (u >> 16).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 16))
    u2 = (u & 0xFFFF).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 16))
    mag = jnp.abs(x) / jnp.maximum(scale, 1e-30)
    t = jnp.clip(mag, 0.0, 1.0) * s
    base = jnp.clip(jnp.floor(t), 0, s - 1)             # shared base level
    frac = t - base                                     # P(round up)
    sign = jnp.sign(x)
    c1_ref[...] = ((base + (u1 < frac).astype(jnp.float32)) * sign).astype(jnp.int8)
    c2_ref[...] = ((base + (u2 < frac).astype(jnp.float32)) * sign).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("s", "scale_axis", "block", "interpret"))
def ds_quant(x: jax.Array, rand: jax.Array, scale: jax.Array, *, s: int,
             scale_axis: str = "row", block=None, interpret: bool | None = None):
    """Fused double-sampling quantization (the ZipML §2.2 hot path).

    x: (R, C) f32/bf16; rand: (R, C) uint32 (one plane feeds both draws);
    scale: (R, 1) row scales or (1, C) column scales per ``scale_axis``.
    Returns (codes1, codes2) int8 in [-s, s] — both emitted from a single
    streaming pass over x, vs two full passes for the naive two-call path.
    ``block=None`` resolves through the autotune cache → DEFAULT_BLOCK;
    block choice never changes the emitted codes (elementwise kernel).
    """
    if s > 127:
        raise ValueError(f"int8 code planes need s <= 127, got {s}")
    r, c = x.shape
    br, bc = _resolve(block, r, c, x.dtype)
    grid = (pl.cdiv(r, br), pl.cdiv(c, bc))
    if scale_axis == "row":
        scale_spec = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    elif scale_axis == "col":
        scale_spec = pl.BlockSpec((1, bc), lambda i, j: (0, j))
    else:
        raise ValueError(f"unknown scale_axis {scale_axis!r}")
    out_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_ds_quant_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            scale_spec,
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.int8),
                   jax.ShapeDtypeStruct((r, c), jnp.int8)],
        interpret=registry.resolve_interpret(interpret),
    )(x, rand, scale)


def _absmax_kernel(x_ref, out_ref):
    """Row absmax accumulated across the column grid axis (the minor,
    sequential one) into one revisited (br, 128) lane-dense block; every lane
    of a row holds the same max."""
    m = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), axis=1, keepdims=True)
    m = jnp.broadcast_to(m, out_ref.shape)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = m

    @pl.when(pl.program_id(1) > 0)
    def _acc():
        out_ref[...] = jnp.maximum(out_ref[...], m)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def row_absmax(x: jax.Array, *, block=None, interpret: bool | None = None):
    """(R, C) → (R, 1) f32 row scales M(v) = max|v| (the paper's linf row
    scaling; grid dim 1 iterates sequentially so the max accumulates)."""
    r, c = x.shape
    br, bc = _resolve(block, r, c, x.dtype)
    # pad columns: out-of-bounds reads are undefined (on TPU and in interpret
    # mode) and would fold garbage into the max
    if c % bc:
        x = jnp.pad(x, ((0, 0), (0, bc - c % bc)))
        c = x.shape[1]
    lanes = pl.pallas_call(
        _absmax_kernel,
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 128), jnp.float32),
        interpret=registry.resolve_interpret(interpret),
    )(x)
    return lanes[:, :1]
