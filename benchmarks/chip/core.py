"""The harness's shared pieces: where the files are, the device check, seeds,
host spans, the compile cache and the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` at the checkout
root names the cell, ``workloads/<cell>.json`` names its configuration and its
driver, ``configs/<config>.json`` holds the sizes, ``drivers/<driver>.py`` runs
it, and ``metrics/<metric>.py`` reads each per-layer metric from the run's
record. No list in code names any of them.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = HERE / ".jax_cache"


class BenchError(RuntimeError):
    """A run that cannot produce a result: no result line, exit non-zero."""


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str) -> dict:
    """The cell's entries: BENCHMARK.json's workload, the workload, traffic
    and config files, and the metrics that apply to it."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[cell["config"]]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "workload": load_json(HERE / "workloads" / f"{workload}.json"),
        "mix": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        "config": load_json(ROOT / conf["file"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def require_chips(n: int):
    """The devices a cell runs on; fails unless JAX drives n TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX finds {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:n]


def enable_compile_cache():
    """JAX's persistent compilation cache at one fixed path in the checkout
    (or ``$JAX_COMPILATION_CACHE_DIR``); every program is cached, however
    short its compile."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def import_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError("the program (src/repro) is not in this checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words from any non-negative seed (one key per seed)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return int(w[0]), int(w[1])


def seed_key(seed: int, stream: int = 0):
    import jax

    a, b = seed_words(seed)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(a), b),
                              stream)


def seed_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class Spans:
    """Host spans of the benchmark's own calls into the program. Each span is
    a ``TraceAnnotation`` (so it lands in the profiler's host trace) and is
    also kept in memory as (name, start_s, end_s) on the perf_counter clock."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float = -1.0, t1: float = float("inf")):
        return [e - s for n, s, e in self.items
                if n == name and s >= t0 and e <= t1]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def device_info(devices, peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict, checks: list[dict]):
    """The run's last lines: each compared number beside its limit on
    standard error, then the one JSON result line on standard output."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), flush=True)


_LOWERINGS = [0]


def _count_lowering(event: str, _secs: float, **_kw):
    if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        _LOWERINGS[0] += 1


@contextlib.contextmanager
def no_compiles():
    """Fail the run if a program is traced and lowered inside the block:
    every shape the window uses has to be warmed in set-up."""
    import jax

    if not getattr(no_compiles, "registered", False):
        jax.monitoring.register_event_duration_secs_listener(_count_lowering)
        no_compiles.registered = True
    n = _LOWERINGS[0]
    yield
    if _LOWERINGS[0] != n:
        raise BenchError(f"{_LOWERINGS[0] - n} program(s) compiled inside "
                         "the measured window")


def reference(config: dict):
    """The plain reference module a configuration names."""
    return load_module(HERE / "references" / f"{config['reference']}.py")


def least_seconds(c: dict, peaks: dict) -> float:
    """The least time a chip could take for a cost: the larger of
    operations over peak bf16 rate and bytes over peak bandwidth."""
    return max(c["flops"] / peaks["bf16_flops"],
               c["bytes"] / peaks["hbm_bytes_per_s"])


def bound(c: dict, peaks: dict) -> str:
    """Which of the chip's peaks sets a cost's least time."""
    return ("compute" if c["flops"] / peaks["bf16_flops"]
            > c["bytes"] / peaks["hbm_bytes_per_s"] else "bandwidth")
