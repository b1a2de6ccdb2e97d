"""Mixture-of-Experts block: top-k routing with capacity-bounded local dispatch.

Two execution paths, selected statically by token count:

* ``dispatch`` (training / prefill): tokens are dispatched to per-expert
  capacity buffers via scatter-add, experts run as one batched einsum, results
  gathered back. Dispatch is *local per data shard* — the block is wrapped in a
  partial-manual `jax.shard_map` over the DP axes (the `model` axis stays in
  GSPMD-auto mode, so expert-internal d_ff tensor parallelism and the FSDP
  all-gather of expert tables over `data` are still inserted automatically).
  This mirrors production MoE: local routing + capacity drop, no global cumsum.

* ``dense`` (decode / tiny token counts): compute every expert on every token
  and combine with the (renormalized) top-k gate weights. For a decode batch
  of 128 tokens with top-2-of-8, every expert is touched w.h.p. anyway, so the
  dense path reads the same weight bytes the dispatch path would — it is the
  memory-roofline-faithful decode implementation, and it sidesteps the
  batch-divisibility constraint for global_batch=1 long-context decode.

Expert MLPs are gated (SwiGLU/GeGLU) like the host architectures' dense MLPs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.registry import einsum_f32
from repro.quant import QTensor, ShipWeight, quant_dense

from .layers import _SPLICE_ERROR, Params, init_dense, shard_hint


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gexpert_einsum(eq: str, x, w):
    return einsum_f32(eq, x, w)


def _ge_fwd(eq, x, w):
    return _gexpert_einsum(eq, x, w), (x, w)


def _ge_bwd(eq, res, g):
    """bf16 weight-gradient emission (ZipML C3 gradient channel).

    The f32 partial dW of each expert matrix is the dominant cross-device
    all-reduce payload in MoE training; emitting it in bf16 halves the wire
    bytes. dx keeps f32 accumulation → x.dtype. The optimizer's f32
    accumulator absorbs the rounding (and grad-clip runs after the reduce).
    """
    x, w = res
    in1, in2_arrow = eq.split(",")
    in2, out = in2_arrow.split("->")
    g = g.astype(x.dtype)
    dx = einsum_f32(f"{out},{in2}->{in1}", g, w).astype(x.dtype)
    # bf16 straight out of the einsum: the cross-device psum of the sharded
    # contraction happens on the einsum OUTPUT — a later astype would ride
    # after the all-reduce and save nothing
    dw = jnp.einsum(f"{in1},{out}->{in2}", x, g,
                    preferred_element_type=jnp.bfloat16)
    return dx, dw.astype(w.dtype) if w.dtype != jnp.bfloat16 else dw


_gexpert_einsum.defvjp(_ge_fwd, _ge_bwd)


def _qeinsum(eq: str, x: jax.Array, sub: Params) -> jax.Array:
    """Expert/router matmul dispatching on the weight storage: QTensor /
    ShipWeight route through the ``quant_dense`` registry op (ref backend =
    exact decode-then-einsum numerics; Pallas streams int8 / packed-int4
    codes with the code-domain backward), dense weights keep the plain
    einsum. All MoE contractions are of quant_dense's canonical form
    (x (*lead, *stack, M, K) · w (*stack, K, N)), so ``eq`` only drives the
    dense path."""
    if "w_q" in sub or "w_lvl_codes" in sub:
        raise ValueError(_SPLICE_ERROR)
    w = sub["w"]
    if isinstance(w, (QTensor, ShipWeight)):
        return quant_dense(x, w)
    return einsum_f32(eq, x, w)


def _gq_einsum(eq: str, x: jax.Array, sub: Params) -> jax.Array:
    """The grouped-dispatch variant of :func:`_qeinsum`: the dense-weight
    path keeps ``_gexpert_einsum``'s custom VJP (bf16 dW emission on the
    cross-device all-reduce); quantized storages go through quant_dense."""
    if "w_q" in sub or "w_lvl_codes" in sub:
        raise ValueError(_SPLICE_ERROR)
    w = sub["w"]
    if isinstance(w, (QTensor, ShipWeight)):
        return quant_dense(x, w)
    return _gexpert_einsum(eq, x, w)


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int
    act: str = "silu"
    capacity_factor: float = 1.25
    dense_path_max_tokens: int = 512   # ≤ this many tokens per step → dense path
    dp_axes: tuple = ("data",)         # manual axes for the dispatch shard_map
    router_jitter: float = 0.0


def init_moe(key, spec: MoESpec, dtype=jnp.bfloat16) -> Params:
    kr, kg, ku, kd = jax.random.split(key, 4)
    e, d, f = spec.n_experts, spec.d_model, spec.d_ff
    def expert_mat(k, din, dout, scale):
        return (jax.random.normal(k, (e, din, dout), jnp.float32) * scale).astype(dtype)
    return {
        "router": init_dense(kr, d, e, dtype=jnp.float32, scale=d**-0.5),
        "gate": {"w": expert_mat(kg, d, f, d**-0.5)},
        "up": {"w": expert_mat(ku, d, f, d**-0.5)},
        "down": {"w": expert_mat(kd, f, d, f**-0.5)},
    }


def _router_probs(p: Params, x: jax.Array, spec: MoESpec):
    # bf16 operands + f32 accumulation: an x.astype(f32) here would materialize
    # a full-token fp32 copy (and its cotangent) per MoE layer
    if isinstance(p["router"].get("w"), (QTensor, ShipWeight)) \
            or "w_q" in p["router"] or "w_lvl_codes" in p["router"]:
        logits = _qeinsum("...d,de->...e", x, p["router"])
    else:
        logits = jnp.einsum("...d,de->...e", x,
                            p["router"]["w"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, spec.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i, probs


def _expert_ffn(p: Params, h: jax.Array, spec: MoESpec) -> jax.Array:
    """h: (E, C, d) → (E, C, d). Batched gated MLP over the expert dim."""
    g = _qeinsum("ecd,edf->ecf", h, p["gate"]).astype(h.dtype)
    u = _qeinsum("ecd,edf->ecf", h, p["up"]).astype(h.dtype)
    a = jax.nn.silu(g) if spec.act == "silu" else jax.nn.gelu(g, approximate=True)
    return _qeinsum("ecf,efd->ecd", a * u, p["down"]).astype(h.dtype)


def moe_dense(p: Params, x: jax.Array, spec: MoESpec) -> jax.Array:
    """All-experts path: y = Σ_e gate_e(x)·FFN_e(x); exact for kept tokens."""
    b, s, d = x.shape
    top_p, top_i, _ = _router_probs(p, x, spec)                     # (B,S,k)
    onehot = jax.nn.one_hot(top_i, spec.n_experts, dtype=jnp.float32)  # (B,S,k,E)
    weights = (onehot * top_p[..., None]).sum(-2)                    # (B,S,E)
    # every expert on every token: (E, B*S, d)
    flat = x.reshape(1, b * s, d)
    h = jnp.broadcast_to(flat, (spec.n_experts, b * s, d))
    y = _expert_ffn(p, h, spec)                                      # (E, N, d)
    y = jnp.einsum("end,ne->nd", y.astype(jnp.float32),
                   weights.reshape(b * s, spec.n_experts))
    return y.reshape(b, s, d).astype(x.dtype)


def moe_dispatch_local(p: Params, x: jax.Array, spec: MoESpec) -> jax.Array:
    """Single-group dispatch (smoke tests / unsharded runs)."""
    b, s, d = x.shape
    return moe_dispatch_grouped(p, x.reshape(1, b * s, d), spec).reshape(b, s, d)


def moe_dispatch_grouped(p: Params, xg: jax.Array, spec: MoESpec) -> jax.Array:
    """Capacity-bounded dispatch with an explicit group dim.

    xg: (G, N, d) — G routing groups (one per data shard in production; the
    group dim is sharded over the DP axes so routing, capacity and the expert
    buffers are all shard-local). Earlier tokens win capacity ties;
    over-capacity choices are dropped (standard Switch/GShard semantics).
    """
    g, n, d = xg.shape
    e, k = spec.n_experts, spec.top_k
    cap = max(int(n * k / e * spec.capacity_factor), 1)
    dp_spec = spec.dp_axes if len(spec.dp_axes) > 1 else spec.dp_axes[0]

    def hint(t, *rest):
        return shard_hint(t, P(dp_spec, *rest))

    top_p, top_i, _ = _router_probs(p, xg, spec)                # (G, N, k)
    flat_e = top_i.reshape(g, n * k)                            # choice → expert
    flat_p = top_p.reshape(g, n * k).astype(jnp.float32)
    token_of = jnp.broadcast_to(
        jnp.repeat(jnp.arange(n), k)[None], (g, n * k))
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)         # (G, N·k, E)
    onehot = hint(onehot, None, None)
    pos = jnp.cumsum(onehot, axis=1) - onehot                   # per-group prefix
    my_pos = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = my_pos < cap
    slot = jnp.where(keep, flat_e * cap + my_pos, e * cap)      # (G, N·k)
    # scatter into per-group expert buffers (overflow row e*cap absorbs drops)
    rows = e * cap + 1
    src = jnp.take_along_axis(xg, token_of[..., None], axis=1)  # (G, N·k, d)
    src = hint(src, None, None)
    buf = jnp.zeros((g, rows, d), xg.dtype)
    buf = hint(buf, None, None)
    gi = jnp.broadcast_to(jnp.arange(g)[:, None], (g, n * k))
    # scatter-add with by-construction-unique (expert, position) slots; the
    # overflow row absorbs capacity drops. (XLA CPU promotes bf16 scatter-add
    # buffers to f32 — a CPU-backend artifact absent on TPU; scatter-set makes
    # GSPMD fall back to full replication, which is far worse.)
    buf = buf.at[gi, slot].add(src)
    # FFN runs with the capacity dim TP-sharded over 'model' — the f32 bwd
    # cotangents of these (G, E, cap, ·) tensors are the MoE's peak residents
    expert_in = hint(buf[:, : e * cap].reshape(g, e, cap, d), None, "model", None)
    # batched gated MLP over (G, E): d_ff stays TP-sharded over 'model'
    up = _gq_einsum("gecd,edf->gecf", expert_in, p["up"]).astype(xg.dtype)
    gate = _gq_einsum("gecd,edf->gecf", expert_in, p["gate"]).astype(xg.dtype)
    act = jax.nn.silu(gate) if spec.act == "silu" else jax.nn.gelu(gate, approximate=True)
    out = _gq_einsum("gecf,efd->gecd", act * up, p["down"]).astype(xg.dtype)
    out = hint(out, None, "model", None)
    out_flat = jnp.concatenate(
        [out.reshape(g, e * cap, d), jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    gathered = jnp.take_along_axis(out_flat, slot[..., None], axis=1)
    gathered = gathered * (flat_p * keep)[..., None].astype(xg.dtype)
    gathered = hint(gathered, None, None)
    # choices are (token-major, k-minor) ordered → combine is a plain k-sum,
    # no scatter-add needed
    y = gathered.reshape(g, n, k, d).sum(axis=2).astype(xg.dtype)
    return hint(y, None, None)


def _mesh_axis_sizes(axes: tuple) -> int | None:
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.shape:
        return None
    sizes = dict(am.shape)
    if not set(axes) <= set(sizes):
        return None
    total = 1
    for a in axes:
        total *= sizes[a]
    return total


def moe_block(p: Params, x: jax.Array, spec: MoESpec) -> jax.Array:
    """Entry point: dense path for tiny token counts, else *group-local*
    dispatch.

    Group-local = production MoE semantics: each data shard routes its own
    tokens with its own capacity, no global cumsum across shards. Expressed as
    a vmap over an explicit group dim sharded on the DP axes (a partial-manual
    shard_map would be equivalent, but its transpose currently trips an XLA
    CPU AllReducePromotion bug — vmap grouping lowers to clean per-shard HLO).
    """
    b, s, _ = x.shape
    d = x.shape[-1]
    tokens = b * s
    if tokens <= spec.dense_path_max_tokens:
        return moe_dense(p, x, spec)
    dp = _mesh_axis_sizes(spec.dp_axes)
    if dp is None or dp == 1 or b % dp != 0:
        return moe_dispatch_local(p, x, spec)
    dp_spec = spec.dp_axes if len(spec.dp_axes) > 1 else spec.dp_axes[0]
    xg = x.reshape(dp, (b // dp) * s, d)
    xg = shard_hint(xg, P(dp_spec, None, None))
    yg = moe_dispatch_grouped(p, xg, spec)
    return yg.reshape(b, s, d)


def load_balance_loss(p: Params, x: jax.Array, spec: MoESpec) -> jax.Array:
    """Auxiliary load-balancing loss (Switch-style): E·Σ_e f_e·P_e."""
    _, top_i, probs = _router_probs(p, x, spec)
    e = spec.n_experts
    frac = jax.nn.one_hot(top_i[..., 0], e, dtype=jnp.float32).reshape(-1, e).mean(0)
    imp = probs.reshape(-1, e).mean(0)
    return e * jnp.sum(frac * imp)
