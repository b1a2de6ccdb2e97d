"""Shared transformer building blocks (pure-function style, dict pytree params).

Conventions:
* params are nested dicts of jnp arrays; init_* functions build them, apply
  functions are pure. No framework dependency — pjit/shard_map see plain pytrees.
* compute dtype is bf16 (MXU-native), accumulations & normalizations in fp32,
  params stored in ``cfg.param_dtype`` (bf16 by default; the ZipML weight path
  stores int8 codes + scales instead — see repro/precision/qat.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant import QTensor, ShipWeight, quant_dense

Params = dict

_SPLICE_ERROR = (
    "the spliced weight formats (w_q+w_scale / w_lvl_codes+w_levels) were "
    "removed after their one-release compatibility window; run the param "
    "tree through repro.precision.qat.migrate_spliced_weights(params) once "
    "(decode-identical QTensor at the 'w' key), or re-quantize the bf16 "
    "masters with precision.qat.quantize_param_tree")


def _as_dtype(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x


# ---------------------------------------------------------------------------
# Dense / embedding
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.bfloat16, scale: float | None = None) -> Params:
    scale = scale if scale is not None else d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jax.Array) -> jax.Array:
    """Matmul supporting three weight storages:

    * ``w``: bf16/fp32 dense weight — the plain einsum path, untouched.
    * ``w``: a :class:`repro.quant.QTensor` (ZipML C1/C5 storage: int8 or
      packed-int4 codes + fp32 scales, or C4 level-table codes) — routed
      through the ``quant_dense`` registry op: the ref backend is the exact
      decode-then-einsum numerics, the Pallas backend streams the code bytes
      HBM→VMEM (``QTensor.nbytes`` of traffic) and keeps the backward in the
      code domain.
    * ``w``: a :class:`repro.quant.ShipWeight` (quantize-on-gather training
      form) — same streaming matmul, straight-through gradient to the master.
    """
    if "w_q" in p or "w_lvl_codes" in p:
        raise ValueError(_SPLICE_ERROR)
    w = p["w"]
    if isinstance(w, (QTensor, ShipWeight)):
        y = quant_dense(x, w).astype(x.dtype)
    else:
        y = jnp.einsum("...i,io->...o", x, w,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def init_embedding(key, vocab: int, d_model: int, dtype=jnp.bfloat16) -> Params:
    # d^-0.5 keeps tied-readout logits O(1) at init (loss ≈ log V)
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                      .astype(dtype)) * d_model**-0.5}


def embed(p: Params, ids: jax.Array) -> jax.Array:
    table = p["table"]
    if isinstance(table, QTensor):
        # gather the CODE rows first, decode only the gathered handful —
        # decoding the whole (V, d) table per step would materialize a full
        # bf16 vocab table just to read a few rows. Falls back to a full
        # decode only when the scale/level tables themselves carry the
        # vocab dim (no such scheme is produced by quantize_param_tree).
        vdim = table.shape[0]
        scale_rowed = jnp.ndim(table.scale) > 0 and \
            table.scale.shape[0] == vdim
        levels_rowed = table.levels is not None and table.levels.ndim > 1
        if scale_rowed or levels_rowed:
            return jnp.take(table.decode(jnp.bfloat16), ids, axis=0)
        rows = QTensor(jnp.take(table.codes, ids, axis=0), table.scale,
                       table.scheme, levels=table.levels)
        return rows.decode(jnp.bfloat16)
    return jnp.take(table, ids, axis=0)


def unembed(p: Params, x: jax.Array) -> jax.Array:
    """Tied readout: logits = x @ tableᵀ (vocab-parallel under TP). A
    QTensor table streams its codes through the transpose kernel of the
    ``quant_dense`` op family."""
    table = p["table"]
    if isinstance(table, (QTensor, ShipWeight)):
        return quant_dense(x, table, transpose=True)
    return jnp.einsum("...d,vd->...v", x, table,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.bfloat16) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["g"].astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10_000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                      # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, *, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "up": init_dense(k1, d_model, d_ff, dtype=dtype),
        "gate": init_dense(k2, d_model, d_ff, dtype=dtype),
        "down": init_dense(k3, d_ff, d_model, dtype=dtype, scale=d_ff**-0.5),
    }


def mlp(p: Params, x: jax.Array, act: str = "silu") -> jax.Array:
    h_gate = dense(p["gate"], x)
    h_up = dense(p["up"], x)
    a = jax.nn.silu(h_gate) if act == "silu" else jax.nn.gelu(h_gate, approximate=True)
    return dense(p["down"], a * h_up)


# ---------------------------------------------------------------------------
# Sharding helper — a soft constraint that is a no-op outside a mesh context.
# ---------------------------------------------------------------------------

def shard_hint(x: jax.Array, spec) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity.

    Model code calls this at activation boundaries; the launcher's mesh context
    makes it bind. ``spec`` is a PartitionSpec.
    """
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh is None or not env_mesh.shape:
        return x
    # only apply when every named axis in the spec exists in the mesh
    names = set()
    for part in spec:
        if part is None:
            continue
        parts = part if isinstance(part, (tuple, list)) else (part,)
        names.update(parts)
    if not names <= set(env_mesh.shape.keys()):
        return x
    # an axis whose size does not divide the dim raises a clear error, which
    # the dry-run surfaces as a config bug
    return jax.lax.with_sharding_constraint(x, spec)
