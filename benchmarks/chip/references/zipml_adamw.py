"""Plain reference of one step of ZipML low-precision training, as a
workload states it (``grad_bits``, ``moment_bits``, ``optimizer``).

* Gradient channel: each leaf plus its error-feedback residual is rounded
  stochastically to ``grad_bits`` on one symmetric grid per leaf
  (scale = absmax / qmax); the residual keeps what the rounding dropped.
* AdamW: global-norm clipping, linear warm-up and cosine decay, bias
  corrections, decoupled weight decay on leaves of two or more axes. The
  master update uses the fresh float moments; with ``moment_bits`` the
  moments are then stored rounded stochastically on a grid per last-axis
  column (one scale for a vector), the second moment as its square root,
  and each coordinate's update is clipped to ``update_clip``.

Leaves are a flat dict of float32 arrays. Imports nothing of the system
under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def stochastic(x, scale, qmax, key):
    t = x / scale
    lo = jnp.floor(t)
    up = jax.random.uniform(key, t.shape) < (t - lo)
    return jnp.clip(lo + up, -qmax, qmax) * scale


def grid_scale(x, qmax, axes):
    m = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    return jnp.where(m == 0, 1.0, m / qmax)


def grad_channel(g: dict, ef: dict, bits: int, key):
    """(rounded gradient, new residual) per leaf."""
    if not bits:
        return g, ef
    qmax = 2 ** (bits - 1) - 1
    out, res = {}, {}
    for i, k in enumerate(sorted(g)):
        x = g[k] + ef[k]
        q = stochastic(x, grid_scale(x, qmax, None), qmax,
                       jax.random.fold_in(key, i))
        out[k], res[k] = q, x - q
    return out, res


def store_moment(x, bits: int, key):
    if not bits:
        return x
    qmax = 2 ** (bits - 1) - 1
    axes = tuple(range(x.ndim - 1)) if x.ndim > 1 else None
    return stochastic(x, grid_scale(x, qmax, axes), qmax, key)


def lr_at(o: dict, step):
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((s - o["warmup_steps"])
                    / max(o["decay_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def adamw(w: dict, g: dict, m: dict, r: dict, step, o: dict, bits: int,
          key):
    """One update. ``m`` holds first moments, ``r`` square roots of second
    moments, both as stored. Returns (w, m, r, clip)."""
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    clip = jnp.where(gn > o["grad_clip"], o["grad_clip"] / (gn + 1e-9), 1.0)
    lr = lr_at(o, step)
    s = step.astype(jnp.float32)
    b1c, b2c = 1 - o["b1"] ** s, 1 - o["b2"] ** s
    nw, nm, nr = {}, {}, {}
    for i, k in enumerate(sorted(w)):
        gk = g[k] * clip
        mk = o["b1"] * m[k] + (1 - o["b1"]) * gk
        vk = o["b2"] * r[k] ** 2 + (1 - o["b2"]) * gk * gk
        u = (mk / b1c) / (jnp.sqrt(vk / b2c) + o["eps"])
        if bits and o["update_clip"]:
            u = jnp.clip(u, -o["update_clip"], o["update_clip"])
        wd = o["weight_decay"] * w[k] if w[k].ndim >= 2 else 0.0
        nw[k] = w[k] - lr * (u + wd)
        km, kv = jax.random.split(jax.random.fold_in(key, i))
        nm[k] = store_moment(mk, bits, km)
        nr[k] = store_moment(jnp.sqrt(vk), bits, kv)
    return nw, nm, nr, clip
