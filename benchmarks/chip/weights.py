"""Random weights from the seed, made on the device in one jitted call.

The layout is the benchmark's own, read by ``references/decoder.py``:

  {"embed": (Vp, d), "final_norm": (d,),
   "layers": {"ln1", "ln2": (L, d), "q", "k", "v", "o", "gate", "up",
              "down": (L, d_in, d_out) or int8 (codes, scale (L, 1, d_out))}}

Matmul weights are N(0, 1) / sqrt(d_in); the embedding N(0, 1) / sqrt(d);
norm gains 0.1 N(0, 1). With ``bits=8`` each matmul weight is rounded
per output column (scale = column absmax / 127) one layer at a time inside
the call, so the float tree never exists whole on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mat_shapes(a) -> dict:
    qd, kd = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return {"q": (a.d, qd), "k": (a.d, kd), "v": (a.d, kd), "o": (qd, a.d),
            "gate": (a.d, a.ff), "up": (a.d, a.ff), "down": (a.ff, a.d)}


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def quantize_columns(w: jax.Array, bits: int):
    """Symmetric per-output-column rounding of w (d_in, d_out)."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8)
    return codes, scale


def make(a, key, *, dtype=jnp.bfloat16, bits: int = 0) -> dict:
    """The whole tree (call under ``jax.jit`` with ``a``/``dtype``/``bits``
    static)."""
    ke, kn, kl = jax.random.split(key, 3)
    layers = {}
    for i, name in enumerate(("ln1", "ln2")):
        layers[name] = _normal(jax.random.fold_in(kn, i),
                               (a.layers, a.d), 0.1, dtype)
    for i, (name, (din, dout)) in enumerate(mat_shapes(a).items()):
        kw = jax.random.fold_in(kl, i)
        if bits:
            def one(l, kw=kw, din=din, dout=dout):
                w = _normal(jax.random.fold_in(kw, l), (din, dout),
                            din ** -0.5, jnp.float32)
                return quantize_columns(w, bits)
            layers[name] = jax.lax.map(one, jnp.arange(a.layers))
        else:
            layers[name] = _normal(kw, (a.layers, din, dout), din ** -0.5,
                                   dtype)
    return {"embed": _normal(ke, (a.vocab_padded, a.d), a.d ** -0.5, dtype),
            "final_norm": _normal(jax.random.fold_in(kn, 2), (a.d,), 0.1,
                                  dtype),
            "layers": layers}
