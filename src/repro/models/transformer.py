"""Model assembly: dense / MoE / SSM / hybrid / VLM / audio backbones.

One ``ModelConfig`` describes any of the 10 assigned architectures. Depth is
executed as `lax.scan` over stacked layer params (HLO size O(1) in layers) with
`jax.checkpoint` remat per layer. Heterogeneous stacks (hybrid shared-attention,
VLM cross-attention cadence) scan over *blocks* whose structure is homogeneous.

Entry points:
  init_params(key, cfg)                  — real weights (smoke tests / examples)
  param_specs(cfg)                       — ShapeDtypeStructs only (dry-run)
  forward(params, tokens, cfg, extras)   — logits-producing full forward
  loss_fn(params, batch, cfg)            — chunked softmax-xent (+ MoE aux)
  init_decode_state(cfg, batch, smax)    — KV/SSM caches
  decode_step(params, state, tokens,cfg) — one-token serve step
"""
from __future__ import annotations

import dataclasses
import typing
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.quant import PrecisionPlan as _PrecisionPlan

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (Params, dense, embed, init_dense, init_embedding,
                     init_mlp, init_rmsnorm, mlp, rmsnorm, shard_hint, unembed)


# The ZipML channel plan for LM-scale training/serving is the one canonical
# repro.quant.PrecisionPlan (model_bits/model_storage/kv_bits/grad_bits/
# act_bits/optimal_levels). `transformer.PrecisionPlan` is its deprecated
# alias, served via module __getattr__ so access warns.
def __getattr__(name):
    if name == "PrecisionPlan":
        import warnings

        warnings.warn(
            "models.transformer.PrecisionPlan is deprecated; use "
            "repro.quant.PrecisionPlan (same class, canonical field names)",
            DeprecationWarning, stacklevel=2)
        return _PrecisionPlan
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # moe
    n_experts: int = 0
    top_k: int = 0
    # attention details
    window: int = 0
    qkv_bias: bool = False
    mlp_act: str = "silu"
    rope_theta: float = 10_000.0
    attn_shard: str = "heads"   # 'heads' | 'seq' | 'none'
    q_chunk: int = 1024
    # ssm / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssd_chunk: int = 256
    shared_attn_every: int = 0  # hybrid: apply shared attn block every k layers
    # vlm / audio stubs
    cross_attn_every: int = 0
    n_vis_tokens: int = 0
    # numerics & loss
    dtype: Any = jnp.bfloat16
    logit_chunk: int = 512
    tie_embeddings: bool = True
    precision: _PrecisionPlan = _PrecisionPlan()
    remat: bool = True
    scan_layers: bool = True    # False: unroll (dry-run — exact cost analysis,
                                # per-layer collectives; XLA counts scan bodies once)
    dp_axes: tuple = ("data",)

    @property
    def vocab_padded(self) -> int:
        """Embedding-table vocab padded to 256 so the vocab-parallel sharding
        divides the 16-way model axis (padded logits are masked in _readout).
        Standard practice (MaxText et al.); cfg.vocab_size stays the exact
        published value and is what the loss/targets see."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def attn_spec(self) -> attn.AttnSpec:
        return attn.AttnSpec(self.n_heads, self.n_kv_heads, self.head_dim,
                             window=self.window, rope_theta=self.rope_theta,
                             q_chunk=self.q_chunk, shard=self.attn_shard,
                             unroll=not self.scan_layers, dp=tuple(self.dp_axes))

    @property
    def moe_spec(self) -> moe_mod.MoESpec:
        return moe_mod.MoESpec(self.n_experts, self.top_k, self.d_model,
                               self.d_ff, act=self.mlp_act, dp_axes=self.dp_axes)

    @property
    def ssm_spec(self) -> ssm_mod.SSMSpec:
        return ssm_mod.SSMSpec(self.d_model, d_state=self.ssm_state,
                               head_dim=self.ssm_head_dim, chunk=self.ssd_chunk,
                               unroll=not self.scan_layers)

    def n_params(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS roofline accounting)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        qkv = d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
            + self.n_heads * self.head_dim * d
        dense_mlp = 3 * d * f
        per_layer = 0
        if self.family in ("dense", "vlm", "audio"):
            per_layer = qkv + dense_mlp
        elif self.family == "moe":
            per_layer = qkv + 3 * d * f * self.n_experts + d * self.n_experts
        elif self.family in ("ssm", "hybrid"):
            spec = self.ssm_spec
            din = 2 * spec.d_inner + 2 * spec.n_groups * spec.d_state + spec.n_heads
            per_layer = d * din + spec.d_inner * d
        total = self.n_layers * per_layer + v * d
        if self.family == "hybrid" and self.shared_attn_every:
            total += qkv + dense_mlp  # one shared block
        if self.family == "vlm" and self.cross_attn_every:
            n_cross = self.n_layers // self.cross_attn_every
            total += n_cross * qkv
        return total

    def n_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.n_params()
        d, f = self.d_model, self.d_ff
        qkv = d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
            + self.n_heads * self.head_dim * d
        per_layer = qkv + 3 * d * f * self.top_k + d * self.n_experts
        return self.n_layers * per_layer + self.vocab_size * d


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key) -> Params:
    """One homogeneous decoder layer for the family."""
    ka, km, k1, k2 = jax.random.split(key, 4)
    if cfg.family in ("ssm",):
        return {"norm": init_rmsnorm(cfg.d_model, cfg.dtype),
                "mamba": ssm_mod.init_mamba2(km, cfg.ssm_spec, cfg.dtype)}
    if cfg.family == "hybrid":
        return {"norm": init_rmsnorm(cfg.d_model, cfg.dtype),
                "mamba": ssm_mod.init_mamba2(km, cfg.ssm_spec, cfg.dtype)}
    layer = {
        "ln1": init_rmsnorm(cfg.d_model, cfg.dtype),
        "attn": attn.init_attention(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, qkv_bias=cfg.qkv_bias,
                                    dtype=cfg.dtype),
        "ln2": init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    if cfg.family == "moe":
        layer["moe"] = moe_mod.init_moe(km, cfg.moe_spec, cfg.dtype)
    else:
        layer["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, dtype=cfg.dtype)
    return layer


def _init_attn_block(cfg: ModelConfig, key, cross: bool = False) -> Params:
    ka, km = jax.random.split(key)
    blk = {
        "ln1": init_rmsnorm(cfg.d_model, cfg.dtype),
        "attn": attn.init_attention(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, qkv_bias=cfg.qkv_bias,
                                    dtype=cfg.dtype),
    }
    if not cross:
        blk["ln2"] = init_rmsnorm(cfg.d_model, cfg.dtype)
        blk["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, dtype=cfg.dtype)
    return blk


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 4)
    params: Params = {
        "embed": init_embedding(keys[0], cfg.vocab_padded, cfg.d_model, cfg.dtype),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every
        lkeys = jax.random.split(keys[1], n_cross * (per + 1)).reshape(n_cross, per + 1, 2)
        params["blocks"] = jax.vmap(
            lambda ks: {
                "self": jax.vmap(lambda k: _init_layer(cfg, k))(ks[:per]),
                "cross": _init_attn_block(cfg, ks[per], cross=True),
            })(lkeys)
    elif cfg.family == "hybrid":
        lkeys = jax.random.split(keys[1], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: _init_layer(cfg, k))(lkeys)
        params["shared_attn"] = _init_attn_block(cfg, keys[2])
    else:
        lkeys = jax.random.split(keys[1], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: _init_layer(cfg, k))(lkeys)
    if not cfg.tie_embeddings:
        params["unembed"] = init_dense(keys[3], cfg.d_model, cfg.vocab_padded,
                                       dtype=cfg.dtype)
    return params


def param_specs(cfg: ModelConfig) -> Params:
    """Shape/dtype skeleton without allocation — the dry-run's param stand-in."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _act_spec(cfg: ModelConfig):
    """Residual-stream sharding. Sequence-sharded over 'model' (Megatron-SP):
    the remat-saved per-layer carry (L, B, S, d) is the dominant training
    resident — leaving it replicated over the model axis costs 16× the HBM.
    SSM/hybrid archs shard d instead (their chunk scan iterates S)."""
    dp = tuple(cfg.dp_axes)
    dp = dp if len(dp) > 1 else dp[0]
    if cfg.family in ("ssm", "hybrid"):
        return P(dp, None, "model")
    return P(dp, "model", None)


def _layer_fwd(cfg: ModelConfig, layer: Params, x: jax.Array) -> jax.Array:
    if cfg.family in ("ssm", "hybrid"):
        return x + ssm_mod.mamba2_forward(layer["mamba"], rmsnorm(layer["norm"], x),
                                          cfg.ssm_spec)
    h = x + attn.attention_block(layer["attn"], rmsnorm(layer["ln1"], x),
                                 cfg.attn_spec)
    h = shard_hint(h, _act_spec(cfg))
    z = rmsnorm(layer["ln2"], h)
    if cfg.family == "moe":
        y = moe_mod.moe_block(layer["moe"], z, cfg.moe_spec)
    else:
        y = mlp(layer["mlp"], z, cfg.mlp_act)
    return shard_hint(h + y, _act_spec(cfg))


def _attn_block_fwd(cfg: ModelConfig, blk: Params, x: jax.Array,
                    kv_tokens=None) -> jax.Array:
    h = x + attn.attention_block(blk["attn"], rmsnorm(blk["ln1"], x),
                                 cfg.attn_spec, kv_tokens=kv_tokens)
    if "mlp" in blk:
        h = h + mlp(blk["mlp"], rmsnorm(blk["ln2"], h), cfg.mlp_act)
    return shard_hint(h, _act_spec(cfg))


def _unstack(tree, n: int):
    return [jax.tree.map(lambda a: a[i], tree) for i in range(n)]


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _scan_layers(cfg: ModelConfig, stacked: Params, x: jax.Array) -> jax.Array:
    body = lambda carry, layer: (_layer_fwd(cfg, layer, carry), None)
    if cfg.remat:
        inner = jax.checkpoint(body)

        def body(carry, layer):  # noqa: F811
            out, _ = inner(carry, layer)
            # barrier outside the checkpoint: stops XLA hoisting the bwd's
            # bf16→f32 convert into the fwd save (doubles stacked-carry memory)
            return jax.lax.optimization_barrier(out), None
    if not cfg.scan_layers:
        n = jax.tree.leaves(stacked)[0].shape[0]
        for layer in _unstack(stacked, n):
            x, _ = body(x, layer)
        return x
    x, _ = jax.lax.scan(body, x, stacked)
    return x


def _maybe_scan(cfg: ModelConfig, body, carry, xs):
    """lax.scan, or an unrolled python loop when cfg.scan_layers=False."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for item in _unstack(xs, n):
        carry, y = body(carry, item)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        return carry, _stack_trees(ys)
    return carry, None


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            vision_tokens: jax.Array | None = None) -> jax.Array:
    """tokens: (B, S) int32 → final hidden states (B, S, d). Call
    ``logits_chunked``/``loss_fn`` for the readout (full logits may be huge)."""
    x = embed(params["embed"], tokens).astype(cfg.dtype)
    x = shard_hint(x, _act_spec(cfg))
    if cfg.family == "vlm":
        vis = vision_tokens.astype(cfg.dtype)

        def block_fwd(carry, blk):
            h = _scan_layers(cfg, blk["self"], carry)
            h = _attn_block_fwd(cfg, blk["cross"], h, kv_tokens=vis)
            return h, None
        if cfg.remat:
            block_fwd = jax.checkpoint(block_fwd)
        if not cfg.scan_layers:
            n = jax.tree.leaves(params["blocks"])[0].shape[0]
            for blk in _unstack(params["blocks"], n):
                x, _ = block_fwd(x, blk)
        else:
            x, _ = jax.lax.scan(block_fwd, x, params["blocks"])
    elif cfg.family == "hybrid":
        k = cfg.shared_attn_every
        n_seg = cfg.n_layers // k
        seg_params = jax.tree.map(
            lambda a: a.reshape(n_seg, k, *a.shape[1:]), params["layers"])

        def seg_fwd(carry, seg):
            h = _scan_layers(cfg, seg, carry)
            h = _attn_block_fwd(cfg, params["shared_attn"], h)
            return h, None
        if not cfg.scan_layers:
            for seg in _unstack(seg_params, n_seg):
                x, _ = seg_fwd(x, seg)
        else:
            x, _ = jax.lax.scan(seg_fwd, x, seg_params)
    else:
        x = _scan_layers(cfg, params["layers"], x)
    return rmsnorm(params["final_norm"], x)


def _readout(params: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], h)
    else:
        logits = dense(params["unembed"], h).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        # mask the padding tail so softmax/argmax never select a pad id
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.float32(-1e30), logits)
    return logits


def final_logits(params: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """Final-norm + unembed readout over a (B, S, d) hidden stream →
    (B, S, V) f32 logits at **every** position — the multi-position variant
    every decode-shaped caller shares: single-token decode (S=1), chunked
    prefill (one chunk), and the speculative-decode verify step, which scores
    all k+1 window positions from one forward. One definition keeps the
    per-position math bit-identical across those paths."""
    return _readout(params, cfg, rmsnorm(params["final_norm"], h))


def loss_fn(params: Params, tokens: jax.Array, targets: jax.Array,
            cfg: ModelConfig, vision_tokens=None) -> jax.Array:
    """Mean next-token cross-entropy, computed in sequence chunks so the
    (B, S, V) logits tensor never fully materializes (vocab up to 256k)."""
    h = forward(params, tokens, cfg, vision_tokens)
    b, s, d = h.shape
    cs = min(cfg.logit_chunk, s)
    if s % cs:
        cs = s
    n_chunks = s // cs

    dp = tuple(cfg.dp_axes)
    logits_spec = P(dp if len(dp) > 1 else dp[0], None, "model")

    def chunk_loss(carry, i):
        hc = jax.lax.dynamic_slice_in_dim(h, i * cs, cs, axis=1)
        tc = jax.lax.dynamic_slice_in_dim(targets, i * cs, cs, axis=1)
        logits = _readout(params, cfg, hc)                      # (B, cs, V) f32
        logits = shard_hint(logits, logits_spec)
        logz = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via masked reduction — take_along_axis over the
        # vocab-sharded dim would force an all-gather of the logits
        vpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        gold = jnp.sum(jnp.where(vpos == tc[..., None], logits, 0.0), axis=-1)
        return carry + jnp.sum(logz - gold), None

    if cfg.scan_layers:
        total, _ = jax.lax.scan(chunk_loss, jnp.float32(0.0), jnp.arange(n_chunks))
    else:
        total = jnp.float32(0.0)
        for i in range(n_chunks):
            total, _ = chunk_loss(total, i)
    return total / (b * s)


# ---------------------------------------------------------------------------
# Prefill: forward + cache collection (the inference-prefill shape)
# ---------------------------------------------------------------------------

def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            vision_tokens: jax.Array | None = None, pad_to: int = 0,
            last_pos: jax.Array | None = None):
    """Process a full prompt; return (last-token logits (B, V), DecodeState).

    Caches are sized to the prompt length (the decode_* shapes measure one
    step against a cache of exactly seq_len).

    ``last_pos``: optional (traced) index of the position to read logits
    from, instead of the final one — lets callers right-pad prompts to a
    bucketed length (one jit compile per bucket, not per exact length)
    while still reading the true last-token logits; causality keeps
    positions ≤ last_pos unaffected by the padding garbage behind them.
    """
    kvb = cfg.precision.kv_bits
    x = embed(params["embed"], tokens).astype(cfg.dtype)
    x = shard_hint(x, _act_spec(cfg))

    def attn_layer_collect(layer, h):
        a_out, (k, v) = attn.attention_block(
            layer["attn"], rmsnorm(layer["ln1"], h), cfg.attn_spec, return_kv=True)
        h = h + a_out
        z = rmsnorm(layer["ln2"], h)
        if cfg.family == "moe":
            y = moe_mod.moe_block(layer["moe"], z, cfg.moe_spec)
        else:
            y = mlp(layer["mlp"], z, cfg.mlp_act)
        cache = attn.prefill_cache_from_kv(k, v, window=cfg.window, kv_bits=kvb,
                                           pad_to=pad_to)
        return shard_hint(h + y, _act_spec(cfg)), cache

    if cfg.family in ("ssm", "hybrid"):
        def body(h, layer):
            out, mc = ssm_mod.mamba2_forward(
                layer["mamba"], rmsnorm(layer["norm"], h), cfg.ssm_spec,
                return_state=True)
            return h + out, mc
        if cfg.family == "ssm":
            x, caches = _maybe_scan(cfg, body, x, params["layers"])
            state = DecodeState(caches, step=tokens.shape[1])
        else:
            k = cfg.shared_attn_every
            n_seg = cfg.n_layers // k
            seg_params = jax.tree.map(
                lambda a: a.reshape(n_seg, k, *a.shape[1:]), params["layers"])

            def seg_fwd(h, seg):
                h, mcs = _maybe_scan(cfg, body, h, seg)
                blk = params["shared_attn"]
                a_out, (kk, vv) = attn.attention_block(
                    blk["attn"], rmsnorm(blk["ln1"], h), cfg.attn_spec,
                    return_kv=True)
                h = h + a_out
                h = h + mlp(blk["mlp"], rmsnorm(blk["ln2"], h), cfg.mlp_act)
                kvc = attn.prefill_cache_from_kv(kk, vv, kv_bits=kvb, pad_to=pad_to)
                return h, (mcs, kvc)
            x, (seg_caches, shared_caches) = _maybe_scan(cfg, seg_fwd, x, seg_params)
            layer_caches = jax.tree.map(
                lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), seg_caches)
            state = DecodeState(layer_caches, shared=shared_caches,
                                step=tokens.shape[1])
    elif cfg.family == "vlm":
        vis = vision_tokens.astype(cfg.dtype)

        def blk_fwd(h, blk):
            h, caches = _maybe_scan(
                cfg, lambda hh, layer: attn_layer_collect(layer, hh), h, blk["self"])
            cross = blk["cross"]
            zc = rmsnorm(cross["ln1"], h)
            h = h + attn.attention_block(cross["attn"], zc, cfg.attn_spec,
                                         kv_tokens=vis)
            b = h.shape[0]
            nv = vis.shape[1]
            ck = dense(cross["attn"]["k"], vis).reshape(
                b, nv, cfg.n_kv_heads, cfg.head_dim)
            cv = dense(cross["attn"]["v"], vis).reshape(
                b, nv, cfg.n_kv_heads, cfg.head_dim)
            return h, (caches, {"k": ck, "v": cv})
        x, (blk_caches, cross_kv) = _maybe_scan(cfg, blk_fwd, x, params["blocks"])
        layer_caches = jax.tree.map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), blk_caches)
        state = DecodeState(layer_caches, cross=cross_kv, step=tokens.shape[1])
    else:
        x, caches = _maybe_scan(
            cfg, lambda h, layer: attn_layer_collect(layer, h), x, params["layers"])
        state = DecodeState(caches, step=tokens.shape[1])

    if last_pos is None:
        h_sel = x[:, -1:, :]
    else:
        idx = jnp.reshape(jnp.asarray(last_pos, jnp.int32), (1,))
        h_sel = jnp.take(x, idx, axis=1)
    h_last = rmsnorm(params["final_norm"], h_sel)
    logits = _readout(params, cfg, h_last)[:, 0]
    return logits, state


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

class DecodeState(typing.NamedTuple):
    """Per-layer caches + step counter (NamedTuple → automatic pytree)."""

    layers: Any
    shared: Any = None
    cross: Any = None
    step: Any = None


def init_decode_state(cfg: ModelConfig, batch: int, smax: int,
                      params: Params | None = None,
                      vision_tokens: jax.Array | None = None) -> DecodeState:
    kvb = cfg.precision.kv_bits
    cache_len = min(cfg.window, smax) if cfg.window else smax

    def stack(n, fn):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[fn() for _ in range(n)])

    if cfg.family in ("ssm",):
        layers = stack(cfg.n_layers, lambda: ssm_mod.init_mamba_cache(batch, cfg.ssm_spec))
        return DecodeState(layers, step=jnp.zeros((), jnp.int32))
    if cfg.family == "hybrid":
        layers = stack(cfg.n_layers, lambda: ssm_mod.init_mamba_cache(batch, cfg.ssm_spec))
        n_seg = cfg.n_layers // cfg.shared_attn_every
        shared = stack(n_seg, lambda: attn.init_kv_cache(
            batch, smax, cfg.n_kv_heads, cfg.head_dim, kv_bits=kvb, dtype=cfg.dtype))
        return DecodeState(layers, shared=shared, step=jnp.zeros((), jnp.int32))
    n_main = cfg.n_layers
    layers = stack(n_main, lambda: attn.init_kv_cache(
        batch, cache_len, cfg.n_kv_heads, cfg.head_dim, kv_bits=kvb, dtype=cfg.dtype))
    cross = None
    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        if params is not None and vision_tokens is not None:
            def one(i):
                blk = jax.tree.map(lambda a: a[i], params["blocks"])["cross"]
                kv = dense(blk["attn"]["k"], vision_tokens.astype(cfg.dtype))
                vv = dense(blk["attn"]["v"], vision_tokens.astype(cfg.dtype))
                nv = vision_tokens.shape[1]
                return {"k": kv.reshape(batch, nv, cfg.n_kv_heads, cfg.head_dim),
                        "v": vv.reshape(batch, nv, cfg.n_kv_heads, cfg.head_dim)}
            cross = jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[one(i) for i in range(n_cross)])
        else:
            cross = {
                "k": jnp.zeros((n_cross, batch, cfg.n_vis_tokens, cfg.n_kv_heads,
                                cfg.head_dim), cfg.dtype),
                "v": jnp.zeros((n_cross, batch, cfg.n_vis_tokens, cfg.n_kv_heads,
                                cfg.head_dim), cfg.dtype),
            }
    return DecodeState(layers, cross=cross, step=jnp.zeros((), jnp.int32))


def decode_layer_block(cfg: ModelConfig, layer: Params, h: jax.Array,
                       attend) -> jax.Array:
    """One decoder-layer body for single-token decode: pre-norm attention
    residual, then pre-norm MLP/MoE residual. ``attend(z)`` runs attention
    of the normed stream (including its cache update) — the ring-buffer
    ``decode_step`` and the paged serving engine plug their cache layouts in
    here, so the block structure exists exactly once."""
    z = rmsnorm(layer["ln1"], h)
    h = h + attend(z)
    z2 = rmsnorm(layer["ln2"], h)
    if cfg.family == "moe":
        y = moe_mod.moe_block(layer["moe"], z2, cfg.moe_spec)
    else:
        y = mlp(layer["mlp"], z2, cfg.mlp_act)
    return h + y


def _cross_decode(cfg: ModelConfig, blk: Params, x, ck, cv):
    b = x.shape[0]
    spec = cfg.attn_spec
    q = dense(blk["attn"]["q"], rmsnorm(blk["ln1"], x)).reshape(
        b, 1, cfg.n_heads, cfg.head_dim)
    out = attn.decode_attention(q, ck, cv, spec, kv_len=ck.shape[1])
    return x + dense(blk["attn"]["o"], out.reshape(b, 1, cfg.n_heads * cfg.head_dim))


def decode_step(params: Params, state: DecodeState, tokens: jax.Array,
                cfg: ModelConfig):
    """One serve step: tokens (B, 1) → (logits (B, 1, V), new state)."""
    x = embed(params["embed"], tokens).astype(cfg.dtype)
    kvb = cfg.precision.kv_bits

    if cfg.family in ("ssm", "hybrid"):
        def body(carry, inp):
            h, = carry
            layer, cache = inp
            z = rmsnorm(layer["norm"], h)
            y, new_cache = ssm_mod.mamba2_decode_step(layer["mamba"], z, cache,
                                                      cfg.ssm_spec)
            return (h + y,), new_cache
        if cfg.family == "ssm":
            (x,), new_layers = _maybe_scan(cfg, body, (x,), (params["layers"], state.layers))
            new_state = DecodeState(new_layers, None, None, state.step + 1)
        else:
            k = cfg.shared_attn_every
            n_seg = cfg.n_layers // k
            seg_p = jax.tree.map(lambda a: a.reshape(n_seg, k, *a.shape[1:]),
                                 params["layers"])
            seg_c = jax.tree.map(lambda a: a.reshape(n_seg, k, *a.shape[1:]),
                                 state.layers)
            def seg_body(carry, inp):
                (h,) = carry
                sp, sc, shared_cache = inp
                (h,), nc = _maybe_scan(cfg, body, (h,), (sp, sc))
                z = rmsnorm(params["shared_attn"]["ln1"], h)
                a_out, new_kv = attn.attention_decode_step(
                    params["shared_attn"]["attn"], z, shared_cache, cfg.attn_spec,
                    kv_bits=kvb)
                h = h + a_out
                h = h + mlp(params["shared_attn"]["mlp"],
                            rmsnorm(params["shared_attn"]["ln2"], h), cfg.mlp_act)
                return (h,), (nc, new_kv)
            (x,), (new_seg_c, new_shared) = _maybe_scan(
                cfg, seg_body, (x,), (seg_p, seg_c, state.shared))
            new_layers = jax.tree.map(
                lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), new_seg_c)
            new_state = DecodeState(new_layers, shared=new_shared, step=state.step + 1)
    elif cfg.family == "vlm":
        per = cfg.cross_attn_every
        def blk_body(carry, inp):
            (h,) = carry
            blk, caches, ck, cv = inp
            def inner(c2, inp2):
                (hh,) = c2
                layer, cache = inp2
                box = {}

                def attend(z):
                    a_out, box["cache"] = attn.attention_decode_step(
                        layer["attn"], z, cache, cfg.attn_spec, kv_bits=kvb)
                    return a_out

                hh = decode_layer_block(cfg, layer, hh, attend)
                return (hh,), box["cache"]
            (h,), new_caches = _maybe_scan(cfg, inner, (h,), (blk["self"], caches))
            h = _cross_decode(cfg, blk["cross"], h, ck, cv)
            return (h,), new_caches
        caches = jax.tree.map(lambda a: a.reshape(cfg.n_layers // per, per,
                                                  *a.shape[1:]), state.layers)
        (x,), new_c = _maybe_scan(
            cfg, blk_body, (x,), (params["blocks"], caches,
                                  state.cross["k"], state.cross["v"]))
        new_layers = jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), new_c)
        new_state = DecodeState(new_layers, cross=state.cross, step=state.step + 1)
    else:
        def body(carry, inp):
            (h,) = carry
            layer, cache = inp
            box = {}

            def attend(z):
                a_out, box["cache"] = attn.attention_decode_step(
                    layer["attn"], z, cache, cfg.attn_spec, kv_bits=kvb)
                return a_out

            h = decode_layer_block(cfg, layer, h, attend)
            return (h,), box["cache"]
        (x,), new_layers = _maybe_scan(cfg, body, (x,), (params["layers"], state.layers))
        new_state = DecodeState(new_layers, None, None, state.step + 1)

    x = rmsnorm(params["final_norm"], x)
    logits = _readout(params, cfg, x)
    return logits, new_state
