"""Faults planted under a cell's timed path, to show that ``correct`` comes
out false when the path is broken (tests/test_correct.py, and ``calibrate.py
--fault`` for the readings on the chip). Each takes the object a run
drives and returns it broken.

Serving (the engine): ``token_altered`` — every decoded token is replaced
where the decode step produces it; ``state_unchanged`` — the decode step
returns the KV pool it was given, so no decoded token's K/V is kept.
Training (``Trainer.step``): ``state_unchanged`` — the step returns the
state it was given; ``half_batch`` — the second half of every batch is
replaced by the first, so the mean is taken over half the rows.
"""
from __future__ import annotations

import numpy as np


def _decode_patch(eng, change):
    orig = eng._decode_jit

    def patched(sampled):
        fn = orig(sampled)

        def step(params, pool, *rest):
            tok, ok, new_pool = fn(params, pool, *rest)
            return change(tok, ok, pool, new_pool)

        return step

    eng._decode_jit = patched
    return eng


def token_altered(eng):
    vocab = eng.cfg.vocab_size
    return _decode_patch(
        eng, lambda tok, ok, pool, new: ((tok + 1) % vocab, ok, new))


def serve_state_unchanged(eng):
    return _decode_patch(eng, lambda tok, ok, pool, new: (tok, ok, pool))


def train_state_unchanged(step):
    def broken(state, batch):
        _, met = step(state, batch)
        return state, met

    return broken


def half_batch(step):
    def broken(state, batch):
        half = {}
        for k, v in batch.items():
            v = np.array(v)
            n = v.shape[0] // 2
            v[n:2 * n] = v[:n]
            half[k] = v
        return step(state, half)

    return broken


SERVE = {"token_altered": token_altered,
         "state_unchanged": serve_state_unchanged}
TRAIN = {"state_unchanged": train_state_unchanged, "half_batch": half_batch}
