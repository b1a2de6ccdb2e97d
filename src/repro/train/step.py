"""The train step, built from the four channel objects over a TrainState.

``make_step(cfg, opt_cfg, channels)`` returns a pure jit-able
``step(state, batch) → (state, metrics)``. Channel state — notably the grad
channel's error-feedback residual — enters and leaves through
``TrainState.channels``, so it actually updates across steps under ``jit``
(the old ``grad_transform=fn(grads, key)`` closure hook could not thread
state: jit's trace-once semantics froze whatever the closure captured).

Per-step RNG discipline (bit-compatible with the seed driver): the step key
is ``fold_in(state.rng, state.step)``; it splits into the same three lanes
the legacy step used — kq (model channel / QAT), kg (grad channel), km
(quantized moments) — plus a fourth derived lane for the sample channel
(inactive outside the 'e2e' plan mode, so legacy numerics are unchanged).

The step jits as ``train_step``, with its phases under the named scopes
``sample_channel``, ``forward_backward``, ``grad_channel`` and ``optimizer``
(the op metadata a profiler trace attributes device time by).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.launch import sharding as shd
from repro.models import transformer as T
from repro.models.layers import shard_hint
from repro.optim import adamw
from repro.train.channels import Channel, default_channels
from repro.train.state import TrainState


def make_grads_fn(cfg: T.ModelConfig, model_channel: Channel,
                  accum_steps: int = 1):
    """Returns grads_of(params, batch, kq) → (loss, grads) with the model
    channel applied inside the loss (QAT fake-quant / ship-quant) and
    optional microbatch gradient accumulation."""

    def grads_of_one(params, tokens, targets, vision, kq):
        def loss(p):
            p, _ = model_channel.apply(p, {}, kq)
            return T.loss_fn(p, tokens, targets, cfg, vision_tokens=vision)
        return jax.value_and_grad(loss)(params)

    def grads_of(params, batch, kq):
        if accum_steps == 1:
            return grads_of_one(params, batch["tokens"], batch["targets"],
                                batch.get("vision"), kq)

        def resh(t):
            return t.reshape(accum_steps, t.shape[0] // accum_steps,
                             *t.shape[1:])
        mb = jax.tree.map(resh, dict(batch))

        def constrain(tree):
            # grad accumulators must live on the param sharding — without
            # the constraint GSPMD replicates the f32 accumulator tree
            return jax.tree_util.tree_map_with_path(
                lambda path, g: shard_hint(g, shd.param_spec(path, g)), tree)

        def micro(carry, mb_i):
            g_acc, l_acc = carry
            lv, g = grads_of_one(params, mb_i["tokens"], mb_i["targets"],
                                 mb_i.get("vision"), kq)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (constrain(g_acc), l_acc + lv), None

        zeros = constrain(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (g_sum, l_sum), _ = jax.lax.scan(micro, (zeros, jnp.float32(0.0)), mb)
        grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
        return l_sum / accum_steps, grads

    return grads_of


def make_step(cfg: T.ModelConfig, opt_cfg: adamw.AdamWConfig,
              channels: dict[str, Channel] | None = None,
              accum_steps: int = 1):
    """Returns step(state: TrainState, batch) → (TrainState, metrics).

    ``batch``: {"tokens": (B,S), "targets": (B,S)[, "vision": (B,nv,d)]}.
    The batch must be the one at the state's cursor (``state.step``); the
    returned state has ``step`` advanced, channel state updated, and the same
    ``rng`` lane (per-step keys derive from it).
    """
    channels = channels if channels is not None else \
        default_channels(cfg.precision)
    grads_of = make_grads_fn(cfg, channels["model"], accum_steps)

    def train_step(state: TrainState, batch):
        key = jax.random.fold_in(state.rng, state.step)
        kq, kg, km = jax.random.split(key, 3)
        ks = jax.random.fold_in(key, 3)

        ch = dict(state.channels)
        with jax.named_scope("sample_channel"):
            batch, ch["sample"] = channels["sample"].apply(
                batch, ch.get("sample", {}), ks)
        with jax.named_scope("forward_backward"):
            loss_val, grads = grads_of(state.params, batch, kq)
        with jax.named_scope("grad_channel"):
            grads, ch["grad"] = channels["grad"].apply(
                grads, ch.get("grad", {}), kg)
        mkey = km if opt_cfg.moment_bits else None
        with jax.named_scope("optimizer"):
            params, opt, metrics = adamw.apply_updates(
                state.params, grads, state.opt, opt_cfg, key=mkey)
        metrics["loss"] = loss_val
        new_state = TrainState(params, opt, ch, state.step + 1, state.rng,
                               state.epoch)
        return new_state, metrics

    return train_step
