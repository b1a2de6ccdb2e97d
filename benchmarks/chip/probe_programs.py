"""Record a small device trace of the program's named programs, scopes and
host spans, and describe it. Run on a chip:

  python benchmarks/chip/probe_programs.py OUT_DIR

Inside one ``window`` span, a small chunked engine with the prefix cache
serves three requests and a small ``Trainer`` with 8-bit gradients and
moments takes two steps, each warmed before the window, traced as the
benchmark traces a window. Writes OUT_DIR/programs.xplane.pb (kept as
tests/data/programs.xplane.pb) without what no reduction reads (``slim``)
and prints programs.py's reduction of it: what programs.py assumes of a
TPU trace (module events named ``jit_<program>(<fingerprint>)``, a
``tf_op`` path on each op, the program's host spans) checked against a
real one.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import core  # noqa: E402


def slim(space) -> None:
    """Drop, in place, what no reduction reads: the "/host:metadata" plane
    (the programs' HLO), the ops' source locations (the recording
    machine's file paths) and the Python tracer's call events (named
    ``$<function>``)."""
    keep = [pl for pl in space.planes if pl.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    for pl in space.planes:
        where = {k for k, sm in pl.stat_metadata.items()
                 if sm.name in ("source", "source_stack")}
        for md in pl.event_metadata.values():
            st = [x for x in md.stats if x.metadata_id not in where]
            del md.stats[:]
            md.stats.extend(st)
        calls = {k for k, md in pl.event_metadata.items()
                 if md.name.startswith("$")}
        if not calls:
            continue
        for ln in pl.lines:
            evs = [ev for ev in ln.events if ev.metadata_id not in calls]
            del ln.events[:]
            ln.events.extend(evs)
        for k in calls:
            del pl.event_metadata[k]


def main(out: str) -> int:
    import jax
    import numpy as np

    core.require_chips(1)
    core.import_program()
    import programs
    from repro import configs
    from repro.data.pipeline import TokenStreamConfig
    from repro.models import transformer as T
    from repro.optim.adamw import AdamWConfig
    from repro.quant import PrecisionPlan
    from repro.serve import Request, ServeEngine
    from repro.train import Trainer

    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    cfg = configs.get_reduced("qwen2.5-14b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                      max_slots=2, page_size=4, max_seq_len=32,
                      chunk_pages=2, prefix_cache=True)

    def serve():
        eng.run([Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 9),
                         max_new_tokens=4) for i in range(3)])

    tcfg = configs.get_reduced("musicgen-medium",
                               precision=PrecisionPlan(grad_bits=8))
    tr = Trainer(tcfg, AdamWConfig(moment_bits=8),
                 stream_cfg=TokenStreamConfig(tcfg.vocab_size, 16, 2))

    def train(state):
        for _ in range(2):
            state, met = tr.step(state, tr.stream.next_batch())
            float(met["loss"])
        return state

    serve()
    state = train(tr.init_state())
    tdir = HERE / ".traces" / "programs"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("window"):
        serve()
        train(state)
    jax.profiler.stop_trace()
    space = programs.load_file(next(tdir.rglob("*.xplane.pb")))
    slim(space)
    path = out_dir / "programs.xplane.pb"
    path.write_bytes(space.SerializeToString())
    red = programs.reduce_file(path, set())
    print(json.dumps(red, indent=1))
    print(f"trace {path.stat().st_size} bytes")
    shutil.rmtree(HERE / ".traces", ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
