#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e chip, without the chip,
and print what each needs of the chip's memory.

  JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py \\
      --workload <cell> [--set key=value ...]

Programs: the weight (or train-state) initialisation and, for serving, the
prefill-chunk and decode steps; for training, the train step. Shapes only:
nothing is allocated. ``--set`` overrides a key of the workload file (for
example ``--set max_slots=20``) to try sizes before any chip time.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["ZIPML_PALLAS_INTERPRET"] = "0"
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import adapter  # noqa: E402
import core  # noqa: E402
import weights as wgen  # noqa: E402

GiB = 2 ** 30


def report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: getattr(m, f"{k}_size_in_bytes") for k in
           ("argument", "output", "temp", "alias", "generated_code")}
    total = out["argument"] + out["output"] + out["temp"] - out["alias"]
    print(f"{name}: arguments {out['argument'] / GiB:.2f} GiB, outputs "
          f"{out['output'] / GiB:.2f}, temporaries {out['temp'] / GiB:.2f}, "
          f"aliased {out['alias'] / GiB:.2f}; peak ~{total / GiB:.2f} GiB",
          flush=True)
    return {**out, "peak": total}


def custom_calls(compiled) -> dict:
    """Pallas kernels in a compiled program, by the op names they carry."""
    import collections
    import re

    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'metadata=\{op_name="([^"]+)"', compiled.as_text())
    return dict(collections.Counter(n.split("/")[-1] for n in names))


def on(dev, tree):
    import jax
    from jax.sharding import SingleDeviceSharding

    s = SingleDeviceSharding(dev)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree)


def serve(spec, dev) -> dict:
    import jax
    import jax.numpy as jnp
    from unittest import mock

    from repro.quant import PrecisionPlan
    from repro.serve import ServeEngine
    from repro.serve import engine as engine_mod

    wl, mix = spec["workload"], spec["mix"]
    a = core.reference(spec["config"]).arch(spec["config"])
    driver = core.load_module(core.HERE / "drivers" / f"{wl['driver']}.py")
    bits = wl["weight_bits"]
    key = on(dev, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    init = jax.jit(lambda k: wgen.make(a, k, bits=bits))
    out = {"init": report("weights", init.lower(key).compile())}
    w = on(dev, jax.eval_shape(init, key))
    plan = PrecisionPlan(model_bits=bits, kv_bits=wl["kv_bits"],
                         model_storage="int" if bits else "fake")
    pool_fn = engine_mod.pg.init_pool
    with mock.patch.object(engine_mod.pg, "init_pool",
                           lambda *a, **k: jax.eval_shape(
                               lambda: pool_fn(*a, **k))):
        eng = ServeEngine(adapter.params(w, bits),
                          adapter.model_config(a, spec["config"], plan),
                          **driver.engine_args(wl, mix))
    pool = on(dev, eng.pool)
    params = adapter.params(w, bits)
    B, MP = eng.max_slots, eng.max_pages_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    C = eng.chunk_pages * eng.page_size
    chunk = jax.jit(eng._make_chunk_fn()).lower(
        params, pool, *on(dev, (i32(C), i32(), i32(eng.chunk_pages), i32(),
                                i32(MP), i32())))
    out["chunk"] = report("prefill chunk", chunk.compile())
    dec = jax.jit(eng._make_decode_fn(False)).lower(
        params, pool, *on(dev, (i32(B, 1), i32(B), i32(B, MP),
                                jax.ShapeDtypeStruct((B,), jnp.bool_),
                                jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                                jax.ShapeDtypeStruct((B,), jnp.float32),
                                i32(B))))
    compiled = dec.compile()
    out["decode"] = report("decode", compiled)
    out["decode_custom_calls"] = custom_calls(compiled)
    return out


def train(spec, dev) -> dict:
    import jax

    from repro.optim import adamw
    from repro.quant import PrecisionPlan
    from repro.train.channels import default_channels
    from repro.train.state import init_state
    from repro.train.step import make_step

    wl, mix = spec["workload"], spec["mix"]
    a = core.reference(spec["config"]).arch(spec["config"])
    cfg = adapter.model_config(a, spec["config"],
                               PrecisionPlan(grad_bits=wl["grad_bits"]))
    opt = adamw.AdamWConfig(**wl["optimizer"], moment_bits=wl["moment_bits"])
    chans = default_channels(cfg.precision)

    def make_state(key):
        params = adapter.params(wgen.make(a, key))
        ch = {n: c.init(params) for n, c in chans.items()}
        return init_state(params, adamw.init(params, opt), ch, key)

    key = on(dev, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    init = jax.jit(make_state)
    out = {"init": report("train state", init.lower(key).compile())}
    state = on(dev, jax.eval_shape(init, key))
    b, s = mix["global_batch"], mix["seq_len"]
    batch = on(dev, {"tokens": jax.ShapeDtypeStruct((b, s), "int32"),
                     "targets": jax.ShapeDtypeStruct((b, s), "int32")})
    step = jax.jit(make_step(cfg, opt, chans)).lower(state, batch)
    compiled = step.compile()
    out["step"] = report("train step", compiled)
    out["step_custom_calls"] = custom_calls(compiled)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    core.import_program()
    spec = core.cell_spec(args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        where = "mix" if k in spec["mix"] else "workload"
        spec[where][k] = json.loads(v)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    from repro.kernels import registry

    with registry.using(spec["workload"]["backend"]):
        kind = serve if spec["mix"]["kind"] == "requests" else train
        out = kind(spec, dev)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
