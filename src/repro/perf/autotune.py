"""Block-shape autotuner for the Pallas kernels + the persisted winner cache.

Every hot kernel (``qmm``, ``qmm_t``, ``qmm_qout``, ``qmm_bitplane``,
``ds_quant``, ``paged_attn``, ``quant_adamw``) ships hand-picked block
sizes. This module
sweeps a small candidate space per (op, dtype, shape-bucket), times each
candidate on representative shapes, and persists the winners to a JSON cache
keyed by :func:`~repro.perf.fingerprint.fingerprint_key`. The kernel entry
points then resolve ``block=None`` through
:func:`repro.kernels.registry.resolve_block` → :func:`lookup` here → the
hand-picked default on a miss.

Guarantees the CI gate leans on:

* the hand-picked default is ALWAYS a candidate, and the winner is the
  argmin over candidates measured in the same sweep — so the recorded
  ``ms ≤ default_ms`` holds exactly (the ``autotune_no_worse`` CHECK).
* a cache from different hardware, a corrupt file, or a disabled env
  (``ZIPML_AUTOTUNE=0``) is a clean miss — kernels fall back to defaults
  and stay bit-exact with an explicit-default call.

Shape bucketing: each logical dim rounds down to a power of two
(``m=300 → m256``), so one tuned entry serves the whole bucket — block
shapes are a coarse function of problem size, not of every last dim.

``paged_attn`` rides along with a singleton candidate space: its grid is
fully determined by (batch, pages-per-sequence) and the pool's page size,
so there is no free block axis yet — the tuner still measures it so the
roofline report covers all seven kernels.
"""
from __future__ import annotations

import json
import os
import time
import warnings

import numpy as np

from repro.perf import fingerprint as fpr

CACHE_ENV = "ZIPML_AUTOTUNE_CACHE"       # explicit cache-file override
DISABLE_ENV = "ZIPML_AUTOTUNE"           # "0" → lookups always miss
CACHE_VERSION = 1

OPS = ("qmm", "qmm_t", "qmm_qout", "qmm_bitplane", "ds_quant", "paged_attn",
       "quant_adamw")

# candidate block spaces — the hand-picked default is element 0 of each
SPACES = {
    "qmm": [
        {"bm": 256, "bk": 512, "bn": 256},
        {"bm": 128, "bk": 512, "bn": 256},
        {"bm": 256, "bk": 256, "bn": 256},
        {"bm": 128, "bk": 256, "bn": 128},
        {"bm": 256, "bk": 512, "bn": 128},
    ],
    "qmm_t": [
        {"bm": 256, "bk": 256, "bn": 512},
        {"bm": 128, "bk": 256, "bn": 512},
        {"bm": 256, "bk": 128, "bn": 512},
        {"bm": 256, "bk": 256, "bn": 256},
    ],
    "qmm_qout": [
        {"bm": 256, "bk": 512},
        {"bm": 128, "bk": 512},
        {"bm": 128, "bk": 256},
    ],
    "qmm_bitplane": [                    # bn/32 words fill the 128 lanes
        {"bm": 128, "bk": 256, "bn": 4096},
        {"bm": 256, "bk": 256, "bn": 4096},
        {"bm": 128, "bk": 512, "bn": 4096},
        {"bm": 256, "bk": 128, "bn": 4096},
    ],
    "ds_quant": [
        {"br": 256, "bc": 512},
        {"br": 128, "bc": 512},
        {"br": 256, "bc": 256},
        {"br": 128, "bc": 256},
    ],
    "quant_adamw": [
        {"br": 256, "bc": 512},
        {"br": 128, "bc": 512},
        {"br": 256, "bc": 256},
    ],
    "paged_attn": [{}],                  # grid fixed by (batch, pages)
}

# smoke keeps the default + the two nearest alternates per op
SMOKE_CANDIDATES = 3


def bucket_dim(v: int) -> int:
    """Power-of-two floor: one tuned entry serves the whole bucket."""
    return 1 << max(0, int(np.floor(np.log2(max(1, v)))))


def bucket_key(dims: dict[str, int]) -> str:
    return "_".join(f"{k}{bucket_dim(v)}" for k, v in sorted(dims.items()))


def entry_key(op: str, dtype: str, dims: dict[str, int]) -> str:
    return f"{op}/{dtype}/{bucket_key(dims)}"


# --------------------------------------------------------------- the cache --
_STATE: dict = {"path": None, "entries": None}


def cache_path() -> str:
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(fpr.cache_dir(), f"autotune_{fpr.fingerprint_key()}.json")


def reload() -> None:
    """Drop the in-process cache view (tests; after an external tune run).
    NB: kernels already traced with ``block=None`` keep their resolved
    blocks — ``jax.clear_caches()`` forces re-resolution."""
    _STATE["path"] = None
    _STATE["entries"] = None


def _load() -> dict:
    path = cache_path()
    if _STATE["entries"] is not None and _STATE["path"] == path:
        return _STATE["entries"]
    entries: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict) or "entries" not in data:
                raise ValueError("not an autotune cache")
            if data.get("key") != fpr.fingerprint_key():
                warnings.warn(
                    f"autotune cache {path} was tuned on different hardware "
                    f"(key {data.get('key')!r} != {fpr.fingerprint_key()!r}); "
                    "ignoring it — kernels use hand-picked defaults",
                    stacklevel=2)
            elif data.get("version") != CACHE_VERSION:
                warnings.warn(
                    f"autotune cache {path} has version "
                    f"{data.get('version')!r} != {CACHE_VERSION}; ignoring",
                    stacklevel=2)
            else:
                entries = data["entries"]
        except (json.JSONDecodeError, OSError, ValueError, TypeError) as e:
            warnings.warn(
                f"autotune cache {path} is unreadable ({e}); ignoring it — "
                "kernels use hand-picked defaults", stacklevel=2)
    _STATE["path"] = path
    _STATE["entries"] = entries
    return entries


def lookup(op: str, dtype: str, dims: dict[str, int]) -> dict | None:
    """Tuned block dict for (op, dtype, bucket-of-dims), or None (→ default).

    Called at kernel trace time through registry.resolve_block, so the file
    is read once per process and the hit is a dict lookup.
    """
    if os.environ.get(DISABLE_ENV, "1") in ("0", "false", ""):
        return None
    ent = _load().get(entry_key(op, dtype, dims))
    return dict(ent["block"]) if ent and ent.get("block") else None


def save(entries: dict, path: str | None = None) -> str:
    """Merge ``entries`` into the cache file (atomic replace) and reload."""
    path = path or cache_path()
    merged: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            if old.get("key") == fpr.fingerprint_key() \
                    and old.get("version") == CACHE_VERSION:
                merged = old.get("entries", {})
        except (json.JSONDecodeError, OSError, TypeError):
            pass                          # overwrite a corrupt file
    merged.update(entries)
    payload = {"version": CACHE_VERSION, "key": fpr.fingerprint_key(),
               "fingerprint": fpr.hardware_fingerprint(), "entries": merged}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)
    reload()
    return path


# ------------------------------------------------------------- the sweeps --
def _best_ms(fn, reps: int) -> float:
    fn()                                  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts)) * 1e3


def _cases(smoke: bool):
    """(op, dtype, dims, bytes_moved, bench(block) -> timed-call) tuples.

    Shapes are 128-multiples (the kernels' alignment contract). bytes_moved
    is the per-call HBM traffic of the op's I/O signature — what the
    roofline fraction divides by the measured peak.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import paged_attn as pa_mod
    from repro.kernels import qmm as qmm_mod
    from repro.kernels import qmm_bitplane as qbp_mod
    from repro.kernels import quant_adamw as qa_mod
    from repro.kernels import stoch_quant as sq_mod

    key = jax.random.PRNGKey(0)
    cases = []

    m, k, n = (256, 512, 256) if smoke else (512, 2048, 1024)
    x = jax.random.normal(key, (m, k)).astype(jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(key, 1), (m, n)).astype(jnp.bfloat16)
    scale = jnp.full((1, n), 0.01, jnp.float32)
    codes8 = jax.random.randint(jax.random.fold_in(key, 2), (k, n), -127, 128,
                                jnp.int8)
    codes4 = jax.random.randint(jax.random.fold_in(key, 3), (k, n // 2), 0, 256,
                                jnp.uint32).astype(jnp.uint8)
    rand = jax.random.bits(jax.random.fold_in(key, 4), (m, n), jnp.uint32)

    for dtype, codes, packed in (("int8", codes8, False), ("int4", codes4, True)):
        cbytes = codes.size
        cases.append((
            "qmm", dtype, {"m": m, "k": k, "n": n},
            2 * m * k + cbytes + 4 * n + 4 * m * n,
            lambda b, codes=codes, packed=packed: jax.block_until_ready(
                qmm_mod.qmm(x, codes, scale, packed=packed, **b)),
        ))
        cases.append((
            "qmm_t", dtype, {"m": m, "k": k, "n": n},
            2 * m * n + cbytes + 4 * n + 4 * m * k,
            lambda b, codes=codes, packed=packed: jax.block_until_ready(
                qmm_mod.qmm_t(g, codes, scale, packed=packed, **b)),
        ))
    cases.append((
        "qmm_qout", "int8", {"m": m, "k": k, "n": n},
        2 * m * k + codes8.size + 4 * n + 4 * m * n + 2 * m * n + 4 * m,
        lambda b: jax.block_until_ready(
            qmm_mod.qmm_qout(x, codes8, scale, rand, qmax=127, **b)),
    ))

    planes = jax.random.bits(jax.random.fold_in(key, 9), (9, k, n // 32),
                             jnp.uint32)          # sign + 8 magnitude planes
    cases.append((
        "qmm_bitplane", "uint32", {"m": m, "k": k, "n": n},
        2 * m * k + planes.size * 4 + 4 * n + 4 * m * n,
        lambda b: jax.block_until_ready(
            qbp_mod.qmm_bitplane(x, planes, scale, **b)),
    ))

    r, c = (256, 512) if smoke else (1024, 2048)
    xq = jax.random.normal(key, (r, c), jnp.float32)
    randq = jax.random.bits(jax.random.fold_in(key, 5), (r, c), jnp.uint32)
    rscale = jnp.max(jnp.abs(xq), axis=1, keepdims=True)
    cases.append((
        "ds_quant", "f32", {"r": r, "c": c},
        4 * r * c + 4 * r * c + 4 * r + 2 * r * c,
        lambda b: jax.block_until_ready(
            sq_mod.ds_quant(xq, randq, rscale, s=127,
                            block=(b["br"], b["bc"]))[0]),
    ))

    mst = jax.random.normal(key, (r, c), jnp.float32)
    gq = jax.random.normal(jax.random.fold_in(key, 6), (r, c), jnp.float32) * .1
    mcodes = jax.random.randint(jax.random.fold_in(key, 7), (r, c), -127, 128,
                                jnp.int8)
    cscale = jnp.ones((1, c), jnp.float32)
    params = jnp.array([1.0, 1.0, 1e-3, 0.1, 0.05, 0, 0, 0], jnp.float32)
    cases.append((
        "quant_adamw", "f32", {"r": r, "c": c},
        # pass2 I/O: master r/w + g + rand + both code planes r/w + scales
        4 * r * c * 3 + 4 * r * c + 2 * r * c * 2 + 4 * c * 4,
        lambda b: jax.block_until_ready(
            qa_mod.qadamw_update(mst, gq, mcodes, cscale, mcodes, cscale,
                                 cscale, cscale, randq, params, b1=0.9,
                                 b2=0.95, eps=1e-8, wd=0.1, qmax=127,
                                 block=(b["br"], b["bc"]))[0]),
    ))

    b_sz, page, hkv, h, d, maxp = 4, 8, 2, 4, 64, 4
    q = jax.random.normal(key, (b_sz, h, d)).astype(jnp.bfloat16)
    kp = jax.random.randint(jax.random.fold_in(key, 8),
                            (b_sz * maxp + 1, page, hkv, d), -127, 128, jnp.int8)
    ks = jnp.full((b_sz * maxp + 1, page, hkv, 1), 0.02, jnp.float32)
    bt = jnp.arange(1, b_sz * maxp + 1, dtype=jnp.int32).reshape(b_sz, maxp)
    lens = jnp.full((b_sz,), page * maxp, jnp.int32)
    cases.append((
        "paged_attn", "int8", {"b": b_sz, "p": maxp, "d": d},
        2 * q.size + 2 * (b_sz * maxp * page * hkv * d) + 4 * q.size,
        lambda b: jax.block_until_ready(
            pa_mod.paged_decode_attn(q, kp, kp, ks, ks, bt, lens,
                                     softmax_scale=0.125, kv_bits=8)),
    ))
    return cases


def tune(ops=None, *, smoke: bool = True, peaks: dict | None = None,
         path: str | None = None, persist: bool = True):
    """Sweep candidates, persist winners, return per-bucket report rows.

    Every row carries bytes_moved / achieved GB/s / roofline_fraction (from
    ``peaks``, defaulting to the cached probe) and the ``autotune_no_worse``
    bool the CI lane gates on — exact by construction, since the default is
    candidate 0 of the same measured sweep.
    """
    from repro.perf import probe, report

    peaks = peaks or probe.get_peaks(smoke=smoke)
    reps = 2 if smoke else 5
    rows, entries = [], {}
    for op, dtype, dims, bytes_moved, bench in _cases(smoke):
        if ops and op not in ops:
            continue
        space = SPACES[op][:SMOKE_CANDIDATES] if smoke else SPACES[op]
        timed = [(blk, _best_ms(lambda b=blk: bench(b), reps)) for blk in space]
        default_ms = timed[0][1]
        best_blk, best_ms = min(timed, key=lambda t: t[1])
        ek = entry_key(op, dtype, dims)
        entries[ek] = {"op": op, "dtype": dtype, "bucket": bucket_key(dims),
                       "block": best_blk, "ms": round(best_ms, 4),
                       "default_ms": round(default_ms, 4),
                       "bytes_moved": bytes_moved,
                       "candidates": len(space)}
        row = {"case": f"autotune_{op}_{dtype}", "op": op, "dtype": dtype,
               "bucket": bucket_key(dims),
               "block": json.dumps(best_blk, sort_keys=True),
               "default_ms": round(default_ms, 3), "best_ms": round(best_ms, 3),
               "candidates": len(space),
               "autotune_no_worse": bool(best_ms <= default_ms)}
        report.annotate_row(row, bytes_moved=bytes_moved, ms=best_ms,
                            peaks=peaks)
        rows.append(row)
    if persist and entries:
        save(entries, path)
    return rows
