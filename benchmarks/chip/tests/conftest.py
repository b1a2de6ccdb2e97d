"""Shared set-up of the benchmark's own tests: they run on the CPU at small
sizes (Pallas in interpret mode) and skip the harness's look for a chip.

  JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import core  # noqa: E402

core.import_program()

SERVE_CELL = "granite-3-8b.w8kv8.backlog"
TRAIN_CELL = "musicgen-medium.g8m8.train"


def small_spec(cell: str) -> dict:
    """The cell's spec with its configuration, batch and lengths cut to a
    size a CPU test holds; limits and everything else as committed."""
    spec = core.cell_spec(cell)
    c, wl = dict(spec["config"]), dict(spec["workload"])
    mix = dict(spec["mix"])
    c.update(hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
             vocab_size=512)
    if "attention_multiplier" in c:
        c["attention_multiplier"] = 64 ** -0.5
    if "intermediate_size" in c:
        c.update(intermediate_size=512, num_key_value_heads=2)
    else:
        c.update(ffn_dim=512)
    if "max_slots" in wl:
        wl.update(max_slots=4, chunk_pages=2, ramp_max_steps=50,
                  check=dict(wl["check"], sample=3))
        mix.update(requests=400,
                   prompt={"median": 24, "sigma": 0.5, "min": 8, "max": 48},
                   output={"median": 12, "sigma": 0.5, "min": 4, "max": 24})
    else:
        mix.update(seq_len=64, global_batch=4)
    return dict(spec, config=c, workload=wl, mix=mix)


def context(spec: dict, seed: int, seconds: float, **extra):
    import jax

    import run as harness

    ctx = harness.Context(argparse.Namespace(seed=seed, seconds=seconds,
                                             trace=0),
                          spec, jax.devices()[:1])
    ctx.t_start = time.perf_counter()
    for k, v in extra.items():
        setattr(ctx, k, v)
    return ctx


def drive(spec: dict, seed: int = 3, seconds: float = 1.0, **extra) -> dict:
    driver = core.load_module(
        CHIP / "drivers" / f"{spec['workload']['driver']}.py")
    return driver.run(context(spec, seed, seconds, **extra))


@pytest.fixture(scope="session")
def serve_spec():
    return small_spec(SERVE_CELL)


@pytest.fixture(scope="session")
def train_spec():
    return small_spec(TRAIN_CELL)
