"""Training: ``Trainer.step`` driven for the window, as ``Trainer.run`` drives
it (next batch from the program's seeded token stream, the jitted step, the
loss read back).

Set-up makes the bf16 weights on the device from the seed and builds the
program's TrainState from them (fp32 masters, quantized moments, the grad
channel's error-feedback residual) in one jitted call, then runs the first
``check.steps`` steps of that same object through the window's own calls,
recording what the comparison needs: each step's loss, the per-leaf norm of
the first gradient as the optimizer holds it (first moment / (1 - b1) after
step 1), and the per-leaf norm of the masters' change after the last of
them. The window then continues the same state. ``train_tok_s`` counts the
tokens of every step inside the window over its seconds; the window closes
at a step boundary.

Correctness: after the window the program's state is freed and the plain
float32 reference (``references/decoder.py`` with the gradient channel and
AdamW of ``references/zipml_adamw.py``, at the bits the workload states)
follows the same first steps from the same weights and the batches the
program was given. With ``ctx.control`` the program runs the workload's
``control`` bits (its own lower rung) while the reference keeps the stated
ones.
"""
from __future__ import annotations

import functools
import gc

import numpy as np

import adapter
import core

REF = core.HERE / "references" / "decoder.py"


def flat(w: dict) -> dict:
    """The benchmark's nested weight layout → flat leaf names."""
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            **w["layers"]}


def leaf_norms(tree: dict) -> dict:
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def to_host(d: dict) -> dict:
    return {k: float(v) for k, v in d.items()}


def build(ctx, a, wl: dict, mix: dict):
    """Trainer, token stream and the initial TrainState (one jitted call);
    ``wl`` holds the bits the program runs."""
    import jax

    from repro.data.pipeline import TokenStream, TokenStreamConfig
    from repro.optim import adamw
    from repro.quant import PrecisionPlan
    from repro.train import Trainer
    from repro.train.state import init_state
    import weights as wgen

    cfg = adapter.model_config(a, ctx.spec["config"],
                               PrecisionPlan(grad_bits=wl["grad_bits"]))
    opt = adamw.AdamWConfig(**wl["optimizer"],
                            moment_bits=wl["moment_bits"])
    w0, w1 = core.seed_words(ctx.seed)
    scfg = TokenStreamConfig(vocab_size=a.vocab, seq_len=mix["seq_len"],
                             global_batch=mix["global_batch"], seed=w0)
    tr = Trainer(cfg, opt, stream_cfg=scfg, max_restarts=0,
                 log_every=1 << 30, seed=w1)

    def make_state(key, rng):
        params = adapter.params(wgen.make(a, key))
        ch = {name: c.init(params) for name, c in tr.channels.items()}
        return init_state(params, adamw.init(params, opt), ch, rng)

    state = jax.jit(make_state)(core.seed_key(ctx.seed, 0), tr.key)
    return tr, TokenStream(scfg), state, opt


def first_moment_norms(opt_state, b1: float) -> dict:
    """Per-leaf norm of the gradient the optimizer took at step 1, read
    from its first moment: m1 = (1 - b1) g."""
    import jax

    from repro.quant import QTensor

    def dec(m):
        return m.decode() if isinstance(m, QTensor) else m

    m = jax.tree.map(dec, opt_state.m,
                     is_leaf=lambda x: isinstance(x, QTensor))
    return {k: v / (1 - b1) for k, v in leaf_norms(adapter.leaves(m)).items()}


def run(ctx) -> dict:
    import jax

    from repro.kernels import registry

    ref = core.load_module(REF)
    wl, mix = ctx.spec["workload"], ctx.spec["mix"]
    a = ref.arch(ctx.spec["config"])
    n_check = wl["check"]["steps"]
    step_wrap = getattr(ctx, "fault", None)
    prog_wl = {**wl, **wl["control"]} if getattr(ctx, "control", False) \
        else wl
    with registry.using(wl["backend"]):
        tr, stream, state, opt = build(ctx, a, prog_wl, mix)
        step = tr.step if step_wrap is None else step_wrap(tr.step)
        norms1 = jax.jit(lambda o: first_moment_norms(o, opt.b1))
        prog = {"loss": []}
        fed = []
        for i in range(n_check):
            with ctx.spans("next_batch"):
                batch = stream.next_batch()
            fed.append({k: np.array(v) for k, v in batch.items()})
            with ctx.spans("Trainer.step"):
                state, met = step(state, batch)
            prog["loss"].append(float(met["loss"]))
            if i == 0:
                prog["grad"] = to_host(norms1(state.opt))
        prog["change"] = to_host(change_norms(ctx, a, state.opt.master))
        tokens_per_step = mix["global_batch"] * mix["seq_len"]
        steps = 0
        with core.no_compiles(), ctx.window() as win:
            while True:
                with ctx.spans("next_batch"):
                    batch = stream.next_batch()
                with ctx.spans("Trainer.step"):
                    state, met = step(state, batch)
                with ctx.spans("loss_readback"):
                    loss = float(met["loss"])
                steps += 1
                if win.elapsed() >= ctx.window_seconds:
                    break
        peak = core.memory_peak_bytes(ctx.devices)
        del state, met, tr
    gc.collect()
    jax.clear_caches()
    refv = reference(ctx, ref, a, wl, fed)
    nums = compare(prog, refv)
    waits = ctx.spans.total("next_batch", win.t0, win.t1)
    return {
        "setup_s": win.t0 - ctx.t_start,
        "window_s": win.seconds,
        "e2e": {"train_tok_s": steps * tokens_per_step / win.seconds},
        "record": {"train": {"steps": steps, "tokens": steps * tokens_per_step,
                             "window_s": win.seconds, "next_batch_s": waits,
                             "last_loss": loss,
                             "moment_leaves": moment_leaves(a)}},
        # a number without a limit in the workload is read, not compared
        "checks": [{"name": n, "value": v, "limit": wl["check"][n]}
                   for n, v in nums.items() if n in wl["check"]],
        "readings": {**nums, "program": prog, "reference": refv},
        "attempted": steps,
        "failed": int(not np.isfinite(loss)),
        "memory_peak_bytes": peak,
    }


def change_norms(ctx, a, master) -> dict:
    """Per-leaf norm of the masters' change from the seeded weights."""
    import jax

    import weights as wgen

    def f(key, master):
        w0 = flat(wgen.make(a, key))
        m = adapter.leaves(master)
        return leaf_norms({k: m[k] - w0[k].astype(m[k].dtype) for k in m})

    return jax.jit(f)(core.seed_key(ctx.seed, 0), master)


def moment_leaves(a) -> list:
    """(rows, cols) of each leaf the fused quantized-AdamW kernel updates
    (leaves of two or more axes, flattened to (-1, last))."""
    import weights as wgen

    out = [(a.vocab_padded, a.d), (a.layers, a.d), (a.layers, a.d)]
    for din, dout in wgen.mat_shapes(a).values():
        out.append((a.layers * din, dout))
    return out


# ----------------------------------------------------------- reference --
def unflat(f: dict) -> dict:
    return {"embed": f["embed"], "final_norm": f["final_norm"],
            "layers": {k: v for k, v in f.items()
                       if k not in ("embed", "final_norm")}}


def reference(ctx, ref, a, wl: dict, batches: list) -> dict:
    """The float32 reference's losses, first-gradient and change norms: the
    decoder of references/decoder.py with the gradient channel and AdamW of
    references/zipml_adamw.py at the workload's bits, from the same weights
    and the batches the program's first steps were given."""
    import jax
    import jax.numpy as jnp

    import weights as wgen

    zo = core.load_module(core.HERE / "references" / "zipml_adamw.py")
    o = wl["optimizer"]
    key = core.seed_key(ctx.seed, 0)
    init = jax.jit(lambda k: flat(jax.tree.map(
        lambda t: t.astype(jnp.float32), wgen.make(a, k))))
    w = init(key)
    zeros = jax.jit(lambda w: {k: jnp.zeros_like(v) for k, v in w.items()})
    m, r, ef = zeros(w), zeros(w), zeros(w)

    @functools.partial(jax.jit, donate_argnums=1)
    def grad_row(w, g, t, y, scale):
        lv, gr = jax.value_and_grad(
            lambda w: ref.loss(a, unflat(w), t[None], y[None]))(w)
        return lv, {k: g[k] + gr[k] * scale for k in g}

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def update(w, m, r, ef, g, step, k):
        g, ef = zo.grad_channel(g, ef, wl["grad_bits"], k)
        w, m, r, clip = zo.adamw(w, g, m, r, step, o, wl["moment_bits"],
                                 jax.random.fold_in(k, 1))
        return w, m, r, ef

    out = {"loss": []}
    rkey = core.seed_key(ctx.seed, 11)
    for i, batch in enumerate(batches):
        rows = batch["tokens"].shape[0]
        g, total = zeros(w), 0.0
        for j in range(rows):
            lv, g = grad_row(w, g, batch["tokens"][j], batch["targets"][j],
                             jnp.float32(1.0 / rows))
            total += float(lv) / rows
        out["loss"].append(total)
        w, m, r, ef = update(w, m, r, ef, g, jnp.int32(i + 1),
                             jax.random.fold_in(rkey, i))
        del g
        if i == 0:
            out["grad"] = to_host({k: v / (1 - o["b1"])
                                   for k, v in leaf_norms(m).items()})
    w0 = init(key)
    out["change"] = to_host(leaf_norms({k: w[k] - w0[k] for k in w}))
    return out


def leaf_gaps(p: dict, r: dict, keep=None) -> list:
    """Per leaf, |‖p‖ − ‖r‖| / max(‖r‖, median leaf ‖r‖)."""
    keys = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in keys]))
    return [abs(p[k] - r[k]) / max(r[k], med) for k in keys]


def compare(prog: dict, refv: dict) -> dict:
    """The numbers compared: the widest loss gap of the first steps, the
    worst and the median leaf's gap of the first gradient's norm, and the
    worst leaf's gap of the change's norm. Leaves whose reference gradient
    is under a thousandth of the median leaf's move by round-off alone and
    are left out of the change."""
    med = float(np.median(list(refv["grad"].values())))
    keep = {k for k, g in refv["grad"].items() if g >= 1e-3 * med}
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"],
                                                    refv["loss"]))
    grad = leaf_gaps(prog["grad"], refv["grad"])
    return {"loss_rel": loss,
            "grad_norm_rel": max(grad),
            "grad_norm_median_rel": float(np.median(grad)),
            "change_norm_rel": max(leaf_gaps(prog["change"],
                                             refv["change"], keep))}
