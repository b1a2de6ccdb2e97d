"""Data pipeline: deterministic sharded token streams with resumable cursors.

The paper's SampleStore (Fig. 2) with its "quantize during epoch 0, stream
int4/int8 afterwards" design maps to: an int8/int4-quantized sample store whose
column scales are computed on the first pass, and a loader that emits
pre-quantized batches. For LM training the stream is synthetic (offline
container) but the machinery — per-host sharding, skip-ahead cursors,
checkpointable state — is the production part.

Determinism contract: batch i of host h is a pure function of (seed, i, h), so
restore-from-checkpoint = set cursor; elastic re-sharding = recompute host
assignment. No state lives outside ``Cursor``.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class Cursor:
    """Checkpointable pipeline position."""
    step: int = 0
    epoch: int = 0

    def to_dict(self):
        return {"step": self.step, "epoch": self.epoch}

    @staticmethod
    def from_dict(d):
        return Cursor(int(d["step"]), int(d["epoch"]))


@dataclasses.dataclass(frozen=True)
class TokenStreamConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_hosts: int = 1
    host_id: int = 0
    # synthetic stream statistics: zipf-ish unigram + short-range repetition,
    # so the loss actually has learnable structure in examples/tests
    zipf_a: float = 1.2
    repeat_p: float = 0.3


class TokenStream:
    """Deterministic, host-sharded synthetic LM token stream."""

    def __init__(self, cfg: TokenStreamConfig, cursor: Cursor = Cursor()):
        self.cfg = cfg
        self.cursor = cursor
        ranks = np.arange(1, cfg.vocab_size + 1, dtype=np.float64)
        probs = ranks ** -cfg.zipf_a
        self._probs = probs / probs.sum()
        if cfg.global_batch % cfg.n_hosts:
            raise ValueError("global_batch must divide across hosts")
        self._host_batch = cfg.global_batch // cfg.n_hosts

    def _batch_at(self, step: int) -> dict:
        cfg = self.cfg
        rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, step, cfg.host_id]))
        b, s = self._host_batch, cfg.seq_len
        toks = rng.choice(cfg.vocab_size, size=(b, s + 1), p=self._probs)
        # short-range repetition: with prob p, copy the token 2 back
        rep = rng.random((b, s + 1)) < cfg.repeat_p
        toks[:, 2:] = np.where(rep[:, 2:], toks[:, :-2], toks[:, 2:])
        return {"tokens": toks[:, :-1].astype(np.int32),
                "targets": toks[:, 1:].astype(np.int32)}

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def next_batch(self) -> dict:
        with TraceAnnotation("data.next_batch"):
            batch = self._batch_at(self.cursor.step)
        self.cursor = Cursor(self.cursor.step + 1, self.cursor.epoch)
        return batch

    def skip_to(self, cursor: Cursor):
        self.cursor = cursor


@dataclasses.dataclass
class QuantizedSampleStore:
    """The paper's pre-quantized sample store (linear models).

    First pass computes per-feature column scales (App. A.3); samples are then
    held as int codes. ``draw(step, batch, n_samples)`` reproduces the FPGA
    pipeline's read path: ship codes (+1 bit per extra double-sampling draw,
    §2.2) and dequantize at the consumer.
    """

    codes: np.ndarray          # (K, n) int8 level indices of |a| (sign folded in)
    scale: np.ndarray          # (n,) column scales
    labels: np.ndarray         # (K,)
    s: int                     # levels

    @staticmethod
    def build(a: np.ndarray, b: np.ndarray, bits: int, seed: int = 0):
        s = 2**bits - 1
        scale = np.maximum(np.abs(a).max(axis=0), 1e-12)
        t = a / scale * s                     # in [-s, s]
        rng = np.random.default_rng(seed)
        lo = np.floor(t)
        codes = lo + (rng.random(a.shape) < (t - lo))
        return QuantizedSampleStore(codes.astype(np.int8), scale.astype(np.float32),
                                    b.astype(np.float32), s)

    def bytes_per_sample(self) -> float:
        bits = np.ceil(np.log2(2 * self.s + 1))
        return bits * self.codes.shape[1] / 8.0

    def draw(self, step: int, batch: int):
        """Deterministic minibatch of dequantized samples + labels."""
        rng = np.random.default_rng(np.random.SeedSequence([17, step]))
        idx = rng.integers(0, self.codes.shape[0], batch)
        a = self.codes[idx].astype(np.float32) / self.s * self.scale
        return jnp.asarray(a), jnp.asarray(self.labels[idx])
