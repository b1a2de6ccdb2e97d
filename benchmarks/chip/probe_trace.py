"""Record a small device trace of the program's Pallas kernels and describe
it: planes, lines, event names and their stats. Run on a chip:

  python benchmarks/chip/probe_trace.py OUT_DIR

Writes OUT_DIR/trace_layout.json and OUT_DIR/small.xplane.pb: what
tracing.py assumes of a TPU trace (the device plane's "XLA Ops" line, each
event named by its op's HLO text, a Pallas kernel as a "tpu_custom_call"
named after its jitted wrapper) checked against a real one; the trace is
kept as tests/data/probe.xplane.pb.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import core  # noqa: E402


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    core.require_chips(1)
    core.import_program()
    from repro.kernels import ops

    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (16, 1024), jnp.bfloat16)
    codes = jax.random.randint(k, (1024, 2048), -127, 128, jnp.int8)
    scale = jnp.full((1, 2048), 0.01, jnp.float32)
    qmm = jax.jit(lambda x, c, s: ops.quant_dense_apply(x, c, s))
    pool = (16, 16, 8, 128)
    q = jax.random.normal(k, (4, 32, 128), jnp.bfloat16)
    kp = jax.random.randint(k, pool, -127, 128, jnp.int8)
    ks = jnp.full(pool[:3] + (1,), 0.01, jnp.float32)
    bt = jnp.arange(16, dtype=jnp.int32).reshape(4, 4)
    lens = jnp.array([10, 20, 40, 64], jnp.int32)
    pa = jax.jit(lambda *a: ops.paged_attention(*a, softmax_scale=0.088))
    r, c = 512, 1024
    m = jax.random.normal(k, (r, c), jnp.float32)
    mc = jnp.zeros((r, c), jnp.int8)
    sc = jnp.ones((c,), jnp.float32)
    rand = jax.random.bits(k, (r, c), jnp.uint32)
    qa = jax.jit(lambda m, g, mc, sc, rand: ops.quant_adamw_update(
        m, g, mc, sc, mc, sc, rand, qmax=127, b1=0.9, b2=0.95, eps=1e-8,
        wd=0.1, lr=1e-4, b1c=0.1, b2c=0.05, clip=1.0, finite=1.0))

    def work():
        qmm(x, codes, scale).block_until_ready()
        pa(q, kp, kp, ks, ks, bt, lens).block_until_ready()
        jax.block_until_ready(qa(m, m, mc, sc, rand))

    work()
    tdir = HERE / ".traces" / "probe"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("work"):
                work()
    jax.profiler.stop_trace()
    path = next(tdir.rglob("*.xplane.pb"))
    shutil.copy(path, out_dir / "small.xplane.pb")
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    layout = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "n": len(evs), "events": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": {str(kk): str(v)[:300] for kk, v in e.stats}}
                for e in evs[:40]]})
        layout.append({"plane": plane.name, "lines": lines})
    (out_dir / "trace_layout.json").write_text(json.dumps(layout, indent=1))
    print(f"trace {path.stat().st_size} bytes; planes "
          f"{[p['plane'] for p in layout]}")
    shutil.rmtree(HERE / ".traces", ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
