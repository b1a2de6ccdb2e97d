"""Plain float32 reference of the decoder both configurations describe.

Pre-norm decoder: token embedding (times ``embedding_multiplier``); per layer
RMSNorm with a (1 + g) gain, grouped-query causal attention with rotary
positions (half-split rotation) and softmax scale ``attention_multiplier``,
a residual scaled by ``residual_multiplier``, RMSNorm, a gated MLP
``down(act(gate(x)) * up(x))``, a second scaled residual; a final RMSNorm and
the tied read-out ``h @ tableᵀ / logits_scaling`` over the real vocabulary.

It imports nothing of the system under test. Weights come in the
benchmark's own layout (``weights.py``): a matmul weight is a float array
(L, d_in, d_out) or an int8 pair (codes, per-output-column scale), which is
decoded here to float32. Every matmul runs at ``precision="highest"``.

Sizes and constants come from the configuration file as run (``arch()``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MATS = ("q", "k", "v", "o", "gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    vocab_padded: int
    act: str
    rope_theta: float
    eps: float
    emb_mult: float
    attn_mult: float
    res_mult: float
    logit_scale: float


def arch(c: dict) -> Arch:
    """Read the configuration file's keys (Hugging Face names)."""
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    v = c["vocab_size"]
    return Arch(
        layers=c["num_hidden_layers"], d=d, heads=h,
        kv_heads=c.get("num_key_value_heads", h), head_dim=hd,
        ff=c.get("intermediate_size") or c["ffn_dim"], vocab=v,
        vocab_padded=-(-v // 256) * 256,
        act=c.get("hidden_act") or c["activation_function"],
        rope_theta=float(c.get("rope_theta", 10000.0)),
        eps=float(c["rms_norm_eps"]),
        emb_mult=float(c.get("embedding_multiplier", 1.0)),
        attn_mult=float(c.get("attention_multiplier") or hd ** -0.5),
        res_mult=float(c.get("residual_multiplier", 1.0)),
        logit_scale=float(c.get("logits_scaling", 1.0)))


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu_pytorch_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=False)
    raise ValueError(f"unknown activation {name!r}")


def decode_weight(w) -> jax.Array:
    """One layer's matmul weight as float32: a float array, or an int8
    (codes, scale) pair decoded as codes * scale."""
    if isinstance(w, tuple):
        codes, scale = w
        return codes.astype(jnp.float32) * scale.astype(jnp.float32)
    return w.astype(jnp.float32)


def mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def rope(x, pos, theta):
    """x (B, S, H, D), pos (S,): rotate the two halves of D."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(a: Arch, q, k, v, kv_quant=None):
    """Causal grouped-query attention; q (B,S,H,D), k/v (B,S,G,D).
    ``kv_quant(rows)`` optionally rounds K and V rows (the control)."""
    if kv_quant is not None:
        k, v = kv_quant(k), kv_quant(v)
    b, s, h, dh = q.shape
    g = a.kv_heads
    qg = q.reshape(b, s, g, h // g, dh)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        precision=HIGHEST) * a.attn_mult
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v, precision=HIGHEST)
    return out.reshape(b, s, h * dh)


def layer(a: Arch, lw: dict, x, pos, kv_quant=None, wq=decode_weight):
    """One decoder layer on x (B, S, d) float32."""
    b, s, _ = x.shape
    h = rmsnorm(x, lw["ln1"], a.eps)
    q = mm(h, wq(lw["q"])).reshape(b, s, a.heads, a.head_dim)
    k = mm(h, wq(lw["k"])).reshape(b, s, a.kv_heads, a.head_dim)
    v = mm(h, wq(lw["v"])).reshape(b, s, a.kv_heads, a.head_dim)
    q, k = rope(q, pos, a.rope_theta), rope(k, pos, a.rope_theta)
    x = x + a.res_mult * mm(attention(a, q, k, v, kv_quant), wq(lw["o"]))
    h = rmsnorm(x, lw["ln2"], a.eps)
    y = act_fn(a.act)(mm(h, wq(lw["gate"]))) * mm(h, wq(lw["up"]))
    return x + a.res_mult * mm(y, wq(lw["down"]))


def layer_slice(w: dict, i):
    """Layer i of the stacked per-layer weights."""
    return jax.tree.map(lambda t: t[i], w["layers"])


def hidden(a: Arch, w: dict, tokens, kv_quant=None, wq=decode_weight,
           remat: bool = False):
    """Final hidden states (B, S, d) of token ids (B, S)."""
    x = jnp.take(w["embed"].astype(jnp.float32), tokens, axis=0) * a.emb_mult
    pos = jnp.arange(tokens.shape[1])

    def body(x, lw):
        return layer(a, lw, x, pos, kv_quant, wq), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["layers"])
    return rmsnorm(x, w["final_norm"], a.eps)


def logits(a: Arch, w: dict, h):
    """Read-out over the real vocabulary: (…, d) → (…, vocab) float32."""
    table = w["embed"].astype(jnp.float32)[: a.vocab]
    return jnp.einsum("...d,vd->...v", h, table,
                      precision=HIGHEST) / a.logit_scale


def loss(a: Arch, w: dict, tokens, targets):
    """Mean next-token cross-entropy over every position."""
    lg = logits(a, w, hidden(a, w, tokens, remat=True))
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
