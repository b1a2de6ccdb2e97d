"""Kernel-backend registry — one switch for every hot quantization path.

The ZipML hot loop (double-sample quantization + the LSQ gradient built from
it) has two implementations:

* ``ref``    — the pure-jnp path of core/quantize.py: two independent
  full-precision quantization passes. Bit-exact with the original seed
  numerics; the ground truth every other backend is tested against.
* ``pallas`` — the fused pipeline: kernels/stoch_quant.ds_quant emits both
  Q₁/Q₂ int8 code planes in a single HBM read (shared base level + two
  up/down bits, the paper's "1 extra bit, not 2×" storage claim), and
  kernels/qmm.qmv computes q₁ᵀ(q₂x − b) straight from codes+scales without
  ever materializing a dequantized f32 sample tensor.

Selection precedence: explicit ``backend=`` argument > ``select()`` >
``ZIPML_KERNEL_BACKEND`` env var > default per ``jax.default_backend()``
(pallas on TPU, ref elsewhere — interpret-mode Pallas is correctness-only on
CPU). Resolution happens at Python trace time, so the choice is static under
``jax.jit``/``lax.scan``.
"""
from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp

_BACKENDS: dict[str, "KernelBackend"] = {}
_ACTIVE: str | None = None

ENV_VAR = "ZIPML_KERNEL_BACKEND"
INTERPRET_ENV = "ZIPML_PALLAS_INTERPRET"


def interpret_default() -> bool:
    """THE one place deciding Pallas interpret mode: real compile on TPU,
    interpret elsewhere (CPU CI) or when ``ZIPML_PALLAS_INTERPRET=1`` forces
    it. Kernel entry points default ``interpret=None`` and resolve here —
    a caller can no longer silently run interpret-mode Pallas in a hot loop
    because a default said ``True``."""
    env = os.environ.get(INTERPRET_ENV)
    if env is not None:
        return env.lower() not in ("0", "false", "")
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → :func:`interpret_default`; kernels call this at entry."""
    return interpret_default() if interpret is None else interpret


# hand-picked block defaults per kernel op — the end of the
# ``block=None → autotune-cache lookup → default`` resolution chain
BLOCK_DEFAULTS = {
    "qmm": {"bm": 256, "bk": 512, "bn": 256},
    "qmm_bitplane": {"bm": 128, "bk": 256, "bn": 4096},
    "qmm_t": {"bm": 256, "bk": 256, "bn": 512},
    "qmm_qout": {"bm": 256, "bk": 512},
    "qmv": {"br": 256, "bc": 512},
    "ds_quant": {"br": 256, "bc": 512},
    "quant_adamw": {"br": 256, "bc": 512},
}


def dtype_key(dt) -> str:
    """Canonical short dtype tag for autotune-cache keys (f32/bf16/int8/…)."""
    name = jnp.dtype(dt).name
    return {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}.get(
        name, name)


def fit_block(want: int, dim: int) -> int:
    """Clamp a wanted block size to one that tiles ``dim`` exactly.

    Partial blocks on a *contraction* grid axis read out of bounds and fold
    garbage into valid outputs, so every resolved block must divide its dim:
    min(want, dim) when that divides; else 128 (every ops.py entry point
    pads to 128 multiples); else the dim itself (one exact block).
    """
    b = min(want, dim)
    if dim % b == 0:
        return b
    return 128 if dim % 128 == 0 else dim


def resolve_block(op: str, dims: dict[str, int], *, dtype: str = "f32",
                  explicit: dict | None = None) -> tuple[int, ...]:
    """THE block-shape resolution path every Pallas kernel entry runs.

    ``dims`` maps block-arg names to the actual tensor dims (``{"bm": m,
    "bk": k, "bn": n}``). Per axis: an explicitly-passed value wins; else
    the autotune cache (repro.perf.autotune — keyed by hardware fingerprint
    and the power-of-two shape bucket); else the hand-picked default from
    :data:`BLOCK_DEFAULTS`. Everything is then fitted via :func:`fit_block`.
    Resolution happens at trace time, so the choice is static under jit —
    re-tuning requires ``jax.clear_caches()`` to take effect on shapes
    already traced with ``block=None``.
    """
    want = dict(BLOCK_DEFAULTS[op])
    explicit = {k: v for k, v in (explicit or {}).items() if v is not None}
    if len(explicit) < len(want):        # any axis left to resolve?
        from repro.perf import autotune

        hit = autotune.lookup(op, dtype,
                              {k.lstrip("b"): v for k, v in dims.items()})
        if hit:
            want.update({k: int(v) for k, v in hit.items() if k in want})
    want.update(explicit)
    return tuple(fit_block(want[k], dims[k]) for k in dims)


def matmul_eq(x_ndim: int, w_ndim: int, transpose: bool = False) -> str:
    """The einsum equation of the ``quant_dense`` op family.

    w: (*stack, K, N); x: (*lead, *stack, M, K) — the stack dims (e.g. the
    MoE expert axis) ride on both operands, extra leading x dims broadcast.
    ``transpose`` contracts against Wᵀ: x (*lead, *stack, M, N) → (..., K)
    (the code-domain backward, and the tied-unembed forward).
    """
    s = w_ndim - 2
    stack = "abcdefg"[:s]
    if s and x_ndim < s + 2:
        raise ValueError(f"x needs ≥ {s + 2} dims for {s} stack dims")
    if s == 0:
        return "...k,nk->...n" if transpose else "...k,kn->...n"
    x_lbl = f"...{stack}mn" if transpose else f"...{stack}mk"
    out = f"...{stack}mk" if transpose else f"...{stack}mn"
    return f"{x_lbl},{stack}kn->{out}"


def einsum_f32(eq: str, x, w):
    """``jnp.einsum(eq, x, w)`` accumulating in f32: the form of every
    ``quant_dense`` and MoE expert matmul.

    XLA's CPU runtime has no bf16×bf16→f32 dot whose batch axes follow
    another axis of x (the MoE ``"gecd,edf->gecf"`` form). On the CPU the
    batch axes of x move to the front for the dot and back after it: the same
    products and sums. Other backends run the einsum as written.
    """
    if jax.default_backend() != "cpu":
        return jnp.einsum(eq, x, w, preferred_element_type=jnp.float32)
    ins, out = eq.split("->")
    a, b = ins.split(",")
    if "..." in a:                       # name the leading axes explicitly
        lead = "ABCDEFGH"[:jnp.ndim(x) - len(a) + 3]
        a, out = a.replace("...", lead), out.replace("...", lead)
    batch = "".join(c for c in b if c in a and c in out)
    a2 = batch + "".join(c for c in a if c not in batch)
    o2 = batch + "".join(c for c in out if c not in batch)
    y = jnp.einsum(f"{a2},{b}->{o2}",
                   jnp.transpose(x, [a.index(c) for c in a2]), w,
                   preferred_element_type=jnp.float32)
    return jnp.transpose(y, [o2.index(c) for c in out])


class KernelBackend:
    """Interface of a quantization kernel backend.

    The QTensor entry points — ``encode``/``decode``/``ds_pair``/``qt_dot`` —
    are what :mod:`repro.quant` dispatches through; the base class provides
    the pure-jnp reference implementations, so a backend only overrides the
    paths it fuses.

    The lower-level tuple API remains for the hot LSQ loop:
    ``ds_quant_values`` returns the two dequantized draws (the numerical form
    the gradient math is written in); ``ds_quant_codes`` the storage form
    (codes1, codes2, scale); ``lsq_ds_gradient`` the symmetrized §2.2
    estimator ½[Q₁ᵀ(Q₂x−b) + Q₂ᵀ(Q₁x−b)]/B.
    """

    name: str = "abstract"

    # ---------------------------------------------------- QTensor surface --
    def encode(self, x, scheme, key=None, scale=None, levels=None):
        """Quantize ``x`` under ``scheme`` → QTensor (single plane)."""
        from repro.quant.qtensor import encode_jnp

        return encode_jnp(x, scheme, key, scale=scale, levels=levels)

    def decode(self, qt, dtype=None):
        return qt.decode(dtype)

    def ds_pair(self, x, scheme, key, scale=None):
        """Draw the §2.2 double-sampling pair → QTensor with ``codes2``."""
        from repro.quant.qtensor import ds_pair_jnp

        return ds_pair_jnp(x, scheme, key, scale=scale)

    def qt_dot(self, qt, v):
        """decode(qt) @ v; backends may stream codes instead."""
        return qt.decode() @ v

    def quant_dense(self, x, qt, *, transpose: bool = False):
        """The quantized-matmul op family: y = x · decode(qt) (or · ᵀ) with
        fp32 accumulation, f32 result (callers cast). The base implementation
        is decode-then-einsum at bf16 — bit-exact with the pre-op model
        numerics of ``layers.dense`` / ``moe`` — and handles every grid
        (int / zipml / levels / packed int4) and stacked (*S, K, N) weights.
        Backends may stream the codes instead of materializing the weight."""
        w = qt.decode(jnp.bfloat16)
        return einsum_f32(matmul_eq(jnp.ndim(x), w.ndim, transpose), x, w)

    def quant_dense_out_q(self, x, qt, key, *, bits: int = 8,
                          out_dtype=None):
        """``quant_dense`` with a fused quantize epilogue: returns the §2.2
        double-sampled row-scaled int-grid pair of the output activation as a
        QTensor (codes + codes2 + (…, 1) row scales) instead of the dense y —
        what a quantized activation consumer (precision/act_quant) stores.

        Base implementation: einsum → cast to the activation dtype → the
        reference ds_pair draw. The Pallas backend emits both code planes
        straight from the fp32 accumulator tile in VMEM, so the full-width
        activation never reaches HBM (kernels/qmm.qmm_qout)."""
        from repro.quant.qtensor import ds_pair_jnp
        from repro.quant.scheme import QScheme

        dtype = out_dtype or (x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                              else jnp.float32)
        y = self.quant_dense(x, qt).astype(dtype)
        scheme = QScheme.int_symmetric(bits, scaling="row", rounding="ds")
        return ds_pair_jnp(y, scheme, key)

    def paged_attention(self, q, k_pages, v_pages, k_scale, v_scale,
                        block_table, seq_lens, *, softmax_scale):
        """Decode attention of q (B, H, D) against a paged, possibly
        quantized KV pool (repro/serve/pages.py layout). The base
        implementation gathers pages through the block table and reuses the
        legacy masked-softmax decode — bit-exact with the ring-buffer cache;
        backends may stream codes page-by-page instead."""
        from repro.kernels import ref

        return ref.paged_attention_ref(
            q, k_pages, v_pages, k_scale, v_scale, block_table, seq_lens,
            softmax_scale=softmax_scale)

    def quant_adamw_update(self, p_master, g, m_old, v_old, km, kv, *,
                           bits: int, b1: float, b2: float, eps: float,
                           b1c, b2c, lr, clip, finite, wd: float,
                           uclip: float = 0.0):
        """One quantized-moment AdamW leaf update: decode int8 m/v QTensors,
        EMA-update, write the fp32 master, stochastically re-encode.
        ``uclip`` bounds the per-coordinate |update| (the √v-underflow guard
        — see AdamWConfig.update_clip).

        The base implementation is the pure-jnp seed numerics (three
        full-tensor passes); the Pallas backend fuses them into the two-pass
        VMEM pipeline of kernels/quant_adamw.py. Returns
        (new_master, new_m: QTensor, new_v: QTensor).
        """
        from repro.optim.adamw import decode_moment, encode_moment

        g32 = g.astype(jnp.float32) * clip
        m_prev = decode_moment(m_old)
        v_prev = decode_moment(v_old, positive=True)
        m = b1 * m_prev + (1 - b1) * g32
        v = b2 * v_prev + (1 - b2) * g32 * g32
        update = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
        if uclip:
            update = jnp.clip(update, -uclip, uclip)
        new_master = p_master - lr * (update + wd * p_master)
        new_master = jnp.where(finite, new_master, p_master)
        m_q = encode_moment(jnp.where(finite, m, m_prev), bits, km)
        v_q = encode_moment(jnp.where(finite, v, v_prev), bits, kv,
                            positive=True)
        return new_master, m_q, v_q

    # ------------------------------------------------- tuple-form hot loop --
    def ds_quant_values(self, a, s, key, scale=None):
        raise NotImplementedError

    def ds_quant_codes(self, a, s, key, scale=None):
        raise NotImplementedError

    def lsq_ds_gradient(self, x, a, b, s, key, scale=None):
        raise NotImplementedError


def register(backend: KernelBackend) -> KernelBackend:
    _BACKENDS[backend.name] = backend
    return backend


def available() -> list[str]:
    return sorted(_BACKENDS)


def default_name() -> str:
    """pallas where it compiles (TPU); ref everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def select(name: str | None) -> None:
    """Set the process-wide backend (None resets to env/hardware default)."""
    global _ACTIVE
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; have {available()}")
    _ACTIVE = name


@contextlib.contextmanager
def using(name: str | None):
    """Temporarily select a backend; the previous selection is restored on
    exit (``None`` selects nothing and just yields the resolved backend)."""
    global _ACTIVE
    prev = _ACTIVE
    if name is not None:
        select(name)
    try:
        yield get()
    finally:
        _ACTIVE = prev


def get(name: str | None = None) -> KernelBackend:
    name = name or _ACTIVE or os.environ.get(ENV_VAR) or default_name()
    if name not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; have {available()}")
    return _BACKENDS[name]


class _RefBackend(KernelBackend):
    """Two independent core/quantize.py passes — the seed's exact numerics."""

    name = "ref"

    def _zipml_pair(self, a, s, key, scale=None):
        from repro.quant.qtensor import ds_pair_jnp
        from repro.quant.scheme import QScheme

        return ds_pair_jnp(a, QScheme.zipml(s), key, scale=scale)

    def ds_quant_values(self, a, s, key, scale=None):
        qt = self._zipml_pair(a, s, key, scale=scale)
        return qt.decode(), qt.decode2()

    def ds_quant_codes(self, a, s, key, scale=None):
        qt = self._zipml_pair(a, s, key, scale=scale)
        return qt.codes, qt.codes2, qt.scale

    def lsq_ds_gradient(self, x, a, b, s, key, scale=None):
        q1, q2 = self.ds_quant_values(a, s, key, scale=scale)
        B = a.shape[0]
        r2 = q2 @ x - b
        r1 = q1 @ x - b
        return (q1.T @ r2 + q2.T @ r1) / (2.0 * B)


class _PallasBackend(KernelBackend):
    """Fused ds_quant + int8-codes matvecs (kernels/stoch_quant, kernels/qmm).

    ``scale=None`` resolves to the same global-scalar absmax the ref backend
    uses (core/quantize.row_scale), so the two backends quantize against
    identical grids; column scales — the data-pipeline convention — pass
    through. Per-row scales are not used here: they don't factor through
    q₁ᵀ(q₂x − b), which ds_gradient_from_codes relies on.
    """

    name = "pallas"

    def _resolve_scale(self, a, scale):
        if scale is None:
            from repro.core.quantize import row_scale

            return row_scale(a)  # scalar global absmax, as in ref
        return scale

    def ds_quant_values(self, a, s, key, scale=None):
        c1, c2, sc = self.ds_quant_codes(a, s, key, scale=scale)
        return (c1.astype(jnp.float32) / s * sc,
                c2.astype(jnp.float32) / s * sc)

    def ds_quant_codes(self, a, s, key, scale=None):
        from repro.kernels import ops

        return ops.ds_quantize(a, s, key, scale=self._resolve_scale(a, scale))

    def lsq_ds_gradient(self, x, a, b, s, key, scale=None):
        from repro.kernels import ops

        c1, c2, sc = self.ds_quant_codes(a, s, key, scale=scale)
        return ops.ds_gradient_from_codes(c1, c2, x, b, sc, s)

    # ---------------------------------------------------- QTensor surface --
    def ds_pair(self, x, scheme, key, scale=None):
        """Fused single-read pair draw for the 2-D zipml grid; everything
        else falls back to the reference implementation."""
        from repro.quant.qtensor import QTensor, compute_scale

        if scheme.grid != "zipml" or x.ndim != 2 or not scheme.signed \
                or scheme.s > 127:
            return KernelBackend.ds_pair(self, x, scheme, key, scale=scale)
        from repro.kernels import ops

        if scale is None:
            scale = compute_scale(x, scheme)
        scale = jnp.asarray(scale, jnp.float32)
        c1, c2, _ = ops.ds_quantize(x, scheme.s, key, scale=scale)
        # store the caller's scale, not the kernel's broadcast copy — ref and
        # pallas QTensors stay structurally identical (same nbytes, stackable,
        # checkpoint-compatible)
        return QTensor(c1, scale, scheme.with_rounding("ds"), codes2=c2)

    def paged_attention(self, q, k_pages, v_pages, k_scale, v_scale,
                        block_table, seq_lens, *, softmax_scale):
        """Fused paged flash-decode: block-table-indexed page DMA (scalar
        prefetch) + in-VMEM int8/int4 dequant (kernels/paged_attn.py)."""
        from repro.kernels import ops

        return ops.paged_attention(
            q, k_pages, v_pages, k_scale, v_scale, block_table, seq_lens,
            softmax_scale=softmax_scale)

    def quant_adamw_update(self, p_master, g, m_old, v_old, km, kv, *,
                           bits: int, b1: float, b2: float, eps: float,
                           b1c, b2c, lr, clip, finite, wd: float,
                           uclip: float = 0.0):
        """Fused decode→update→re-encode (kernels/quant_adamw.py): the fp32
        moments never round-trip HBM. 2-D+ leaves only (vectors/scalars fall
        back to the jnp path — sub-tile shapes aren't worth a kernel launch);
        rounding bits come from the high/low 16 bits of one uint32 plane
        drawn from ``km`` (distribution-identical to the ref backend's two
        key-based draws, pinned by tests/test_quant_adamw.py)."""
        if p_master.ndim < 2 or bits > 8 or km is None:
            return KernelBackend.quant_adamw_update(
                self, p_master, g, m_old, v_old, km, kv, bits=bits, b1=b1,
                b2=b2, eps=eps, b1c=b1c, b2c=b2c, lr=lr, clip=clip,
                finite=finite, wd=wd, uclip=uclip)
        from repro.kernels import ops
        from repro.optim.adamw import moment_scheme
        from repro.quant.qtensor import QTensor

        shape = p_master.shape
        c = shape[-1]
        rand = jax.random.bits(km, shape, jnp.uint32).reshape(-1, c)
        nm, mc, msn, vc, vsn = ops.quant_adamw_update(
            p_master.astype(jnp.float32).reshape(-1, c),
            g.astype(jnp.float32).reshape(-1, c),
            m_old.codes.reshape(-1, c), m_old.scale,
            v_old.codes.reshape(-1, c), v_old.scale, rand,
            qmax=2 ** (bits - 1) - 1, b1=b1, b2=b2, eps=eps, wd=wd,
            uclip=uclip, lr=lr, b1c=b1c, b2c=b2c, clip=clip,
            finite=finite.astype(jnp.float32))
        scheme = moment_scheme(bits, len(shape))
        return (nm.reshape(shape),
                QTensor(mc.reshape(shape), msn, scheme),
                QTensor(vc.reshape(shape), vsn, scheme))

    # ------------------------------------------------- quant_dense family --
    def _qd_plan(self, qt):
        """Kernel-ready (codes, scale (*S, 1, N), packed) for the fused GEMM,
        or None when the storage needs the decode fallback (level tables,
        wide codes, per-row weight scales)."""
        sch = qt.scheme
        if sch.grid == "levels":
            return None
        packed = bool(sch.packed)
        codes = qt.codes
        if codes.dtype != (jnp.uint8 if packed else jnp.int8):
            return None
        stack = codes.shape[:-2]
        n = codes.shape[-1] * (2 if packed else 1)
        scale = jnp.asarray(qt.scale, jnp.float32)
        shp = scale.shape
        if shp in ((), (1,), (1, 1)):
            scale = jnp.broadcast_to(scale.reshape((1,) * (len(stack) + 2)),
                                     (*stack, 1, n))
        elif shp == (n,):
            scale = jnp.broadcast_to(scale.reshape(1, n), (*stack, 1, n))
        elif shp != (*stack, 1, n):
            return None
        if sch.grid == "zipml":
            scale = scale / sch.s
        return codes, scale, packed

    def _bitplane_scale(self, qt):
        """Kernel-ready (1, N) scale for a 2-D bitplane weight, or None when
        the scaling family needs the decode fallback (per-row scales don't
        broadcast over the GEMM's N axis)."""
        n = qt.scheme.vec_dim
        scale = jnp.asarray(qt.scale, jnp.float32)
        shp = scale.shape
        if shp in ((), (1,), (1, 1)):
            return jnp.broadcast_to(scale.reshape(1, 1), (1, n))
        if shp == (n,):
            return scale.reshape(1, n)
        if shp == (1, n):
            return scale
        return None

    def quant_dense(self, x, qt, *, transpose: bool = False):
        """Stream the code plane through the fused dequant-GEMM kernels
        (kernels/qmm.qmm / qmm_t): int8 moves ~2× fewer HBM bytes than the
        bf16 decode path, packed int4 ~4×, bitplane (k+1)/16ths
        (kernels/qmm_bitplane — only the sliced planes move). Stacked
        (S, K, N) weights (the MoE expert axis) run one kernel launch per
        slice — S is small and static."""
        if qt.scheme.layout == "bitplane":
            scale = None if transpose or qt.codes.ndim != 3 \
                else self._bitplane_scale(qt)
            if scale is None:
                # transpose / stacked / per-row scales → bf16 decode fallback
                return KernelBackend.quant_dense(self, x, qt,
                                                 transpose=transpose)
            from repro.kernels import ops

            return ops.quant_dense_bitplane(x, qt.codes, scale,
                                            qt.scheme.vec_dim)
        plan = self._qd_plan(qt)
        if plan is None or qt.ndim > 3:
            return KernelBackend.quant_dense(self, x, qt, transpose=transpose)
        codes, scale, packed = plan
        from repro.kernels import ops

        if qt.ndim == 2:
            return ops.quant_dense_apply(x, codes, scale, packed=packed,
                                         transpose=transpose)
        xs = jnp.moveaxis(x, x.ndim - 3, 0)       # stack dim sits at -3
        outs = [ops.quant_dense_apply(xs[i], codes[i], scale[i],
                                      packed=packed, transpose=transpose)
                for i in range(codes.shape[0])]
        return jnp.moveaxis(jnp.stack(outs), 0, x.ndim - 3)

    def quant_dense_out_q(self, x, qt, key, *, bits: int = 8,
                          out_dtype=None):
        """Fused quantize epilogue (kernels/qmm.qmm_qout): the §2.2 DS pair
        of the output is emitted from the fp32 accumulator tile in VMEM —
        rounding bits from the hi/lo 16 bits of one uint32 plane, exactly
        the kernels/stoch_quant.ds_quant convention (distribution-identical
        to the ref backend's split-key draws, pinned by tests)."""
        plan = self._qd_plan(qt)
        if plan is None or qt.ndim != 2 or bits > 8:
            return KernelBackend.quant_dense_out_q(self, x, qt, key,
                                                   bits=bits,
                                                   out_dtype=out_dtype)
        codes, scale, packed = plan
        from repro.kernels import ops
        from repro.quant.qtensor import QTensor
        from repro.quant.scheme import QScheme

        dtype = out_dtype or (x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                              else jnp.float32)
        lead = x.shape[:-1]
        n = codes.shape[-1] * (2 if packed else 1)
        x2 = x.reshape(-1, x.shape[-1])
        rand = jax.random.bits(key, (x2.shape[0], n), jnp.uint32)
        c1, c2, oscale = ops.quant_dense_out_q(
            x2, codes, scale, rand, qmax=2 ** (bits - 1) - 1, packed=packed,
            out_dtype=dtype)
        scheme = QScheme.int_symmetric(bits, scaling="row", rounding="ds")
        return QTensor(c1.reshape(*lead, n), oscale.reshape(*lead, 1),
                       scheme, codes2=c2.reshape(*lead, n))

    def qt_dot(self, qt, v):
        """Stream int8 codes through the qmv kernel when the scale factors
        out of the product (scalar / per-row / per-column families)."""
        codes, scale = qt.codes, qt.scale
        if (codes.ndim != 2 or jnp.ndim(v) != 1 or codes.dtype != jnp.int8
                or qt.scheme.grid == "levels"):
            return qt.decode() @ v
        from repro.kernels import ops

        denom = float(qt.scheme.s) if qt.scheme.grid == "zipml" else 1.0
        r, c = codes.shape
        shp = jnp.shape(scale)
        v32 = jnp.asarray(v, jnp.float32)
        if shp in ((), (1,), (1, 1)):
            return ops.int8_matvec(codes, v32) * (jnp.reshape(scale, ()) / denom)
        if shp == (r, 1):
            return scale.ravel() * ops.int8_matvec(codes, v32) / denom
        if shp in ((c,), (1, c)):
            return ops.int8_matvec(codes, jnp.ravel(scale) * v32) / denom
        return qt.decode() @ v


register(_RefBackend())
register(_PallasBackend())


def resolve(backend: "str | KernelBackend | None") -> KernelBackend:
    """Accept a name, an instance, or None (→ active/env/hardware default)."""
    if isinstance(backend, KernelBackend):
        return backend
    return get(backend)
