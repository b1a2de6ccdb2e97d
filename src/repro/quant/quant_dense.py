"""quant_dense — the one registry op every QTensor-weighted matmul routes
through (train, prefill, and decode alike).

ZipML's order-of-magnitude claim is that *code bytes*, not floats, move
through the memory hierarchy on every linear operation. ``layers.dense``
used to call ``QTensor.decode(bf16)`` and hope XLA fused the dequant into
the operand read; this op makes the data movement explicit and owns its
backward:

* **forward** — dispatches through :mod:`repro.kernels.registry`:
  the ``ref`` backend is decode-then-einsum at bf16 (bit-exact with the
  pre-op model numerics); the ``pallas`` backend streams int8 / packed-int4
  code blocks HBM→VMEM and dequantizes in VMEM (kernels/qmm.py).
* **backward** — a ``jax.custom_vjp`` in the *code domain*:
  dx = dy · (codes ⊙ scale)ᵀ via the transpose kernel, so the backward also
  streams codes instead of re-decoding a full-width weight (HALP's point:
  lose the backward and the bandwidth win evaporates). Integer code planes
  receive symbolic-zero (float0) cotangents.
* **quantize epilogue** — ``quant_dense_q(x, w, key)`` returns the §2.2
  double-sampled row-quantized QTensor of the *output* instead of the dense
  activation; the Pallas backend emits both code planes straight from the
  fp32 accumulator tile in VMEM (see ``precision.act_quant.ds_project``).

:class:`ShipWeight` carries the quantize-on-gather training form — the int
codes that moved through the FSDP all-gather *plus* the fp32/bf16 master the
straight-through gradient flows to — so the ship model channel trains while
its matmuls stream codes.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .qtensor import QTensor


def _registry():
    from repro.kernels import registry

    return registry


@jax.tree_util.register_pytree_node_class
class ShipWeight:
    """A shipped (quantize-on-gather) weight: ``qt`` int codes for the
    matmul + the dense ``master`` the STE gradient flows back to."""

    __slots__ = ("master", "qt")

    def __init__(self, master: jax.Array, qt: QTensor):
        self.master = master
        self.qt = qt

    def tree_flatten(self):
        return (self.master, self.qt), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    @property
    def shape(self):
        return self.qt.shape

    @property
    def ndim(self):
        return self.qt.ndim

    def __repr__(self):
        return f"ShipWeight({self.qt!r})"


def _qt_zero_cot(qt: QTensor) -> QTensor:
    """Cotangent for a QTensor input: float0 for the integer code planes,
    real zeros for the float children (scale / levels)."""

    def z(leaf):
        if leaf is None:
            return None
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return jnp.zeros_like(leaf)
        return np.zeros(leaf.shape, jax.dtypes.float0)

    return QTensor(z(qt.codes), z(qt.scale), qt.scheme,
                   codes2=z(qt.codes2), levels=z(qt.levels))


def _dw_eq(x_ndim: int, w_ndim: int, transpose: bool) -> str:
    """einsum equation of the weight cotangent Σ_batch x ⊗ dy (STE: the
    gradient wrt the decoded weight passes straight to the master)."""
    s = w_ndim - 2
    stack = "abcdefg"[:s]
    if transpose:
        return f"...{stack}mk,...{stack}mn->{stack}nk" if s else \
            "...k,...n->nk"
    return f"...{stack}mk,...{stack}mn->{stack}kn" if s else "...k,...n->kn"


def _qd_impl(x, qt, backend, transpose):
    return _registry().resolve(backend).quant_dense(x, qt,
                                                    transpose=transpose)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _qd(x, qt: QTensor, backend, transpose):
    return _qd_impl(x, qt, backend, transpose)


def _qd_fwd(x, qt, backend, transpose):
    return _qd_impl(x, qt, backend, transpose), (x, qt)


def _qd_bwd(backend, transpose, res, g):
    x, qt = res
    b = _registry().resolve(backend)
    dx = b.quant_dense(g, qt, transpose=not transpose).astype(x.dtype)
    return dx, _qt_zero_cot(qt)


_qd.defvjp(_qd_fwd, _qd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _qd_ste(x, master, qt: QTensor, backend, transpose):
    return _qd_impl(x, qt, backend, transpose)


def _qd_ste_fwd(x, master, qt, backend, transpose):
    return _qd_impl(x, qt, backend, transpose), (x, master, qt)


def _qd_ste_bwd(backend, transpose, res, g):
    x, master, qt = res
    b = _registry().resolve(backend)
    dx = b.quant_dense(g, qt, transpose=not transpose).astype(x.dtype)
    # bf16 masters get the cotangent emitted in bf16 straight out of the
    # einsum (moe._ge_bwd's trick): the cross-device psum of a sharded
    # contraction rides on the einsum OUTPUT, so a later astype would run
    # after the all-reduce and halve nothing
    pref = jnp.bfloat16 if master.dtype == jnp.bfloat16 else jnp.float32
    dw = jnp.einsum(_dw_eq(x.ndim, qt.ndim, transpose), x, g,
                    preferred_element_type=pref).astype(master.dtype)
    return dx, dw, _qt_zero_cot(qt)


_qd_ste.defvjp(_qd_ste_fwd, _qd_ste_bwd)


def quant_dense(x: jax.Array, w, *, transpose: bool = False,
                backend: str | None = None) -> jax.Array:
    """y = x · W (or x · Wᵀ) for a quantized weight, f32 result.

    ``w``: a :class:`QTensor` (codes stream through the kernel backend, the
    custom VJP keeps the backward in the code domain), a :class:`ShipWeight`
    (same, plus the straight-through master gradient), or a dense array
    (plain einsum — the unquantized path is untouched). Weight shape
    (*stack, K, N); x (*lead, *stack, M, K) — the stack dims cover MoE
    expert tables and unscanned stacked layers. ``transpose`` contracts
    against Wᵀ (tied unembed / the backward itself).
    """
    if isinstance(w, ShipWeight):
        return _qd_ste(x, w.master, w.qt, backend, transpose)
    if isinstance(w, QTensor):
        return _qd(x, w, backend, transpose)
    reg = _registry()
    return reg.einsum_f32(reg.matmul_eq(jnp.ndim(x), jnp.ndim(w), transpose),
                          x, w)


def quant_dense_q(x: jax.Array, w, key: jax.Array, *, bits: int = 8,
                  backend: str | None = None) -> QTensor:
    """``quant_dense`` with the fused quantize epilogue: returns the §2.2
    double-sampled row-scaled int-grid pair of the output activation as one
    QTensor (codes + codes2 + row scales) — the storage a quantized
    activation channel consumes — instead of the dense y. Forward-only (the
    consumer's VJP owns the backward; see act_quant.ds_dense)."""
    if isinstance(w, ShipWeight):
        w = w.qt
    if isinstance(w, QTensor):
        return _registry().resolve(backend).quant_dense_out_q(
            x, w, key, bits=bits)
    from . import ds_pair
    from .scheme import QScheme

    reg = _registry()
    y = reg.einsum_f32(reg.matmul_eq(jnp.ndim(x), jnp.ndim(w), False), x,
                       w).astype(x.dtype)
    return ds_pair(y, QScheme.int_symmetric(bits, scaling="row",
                                            rounding="ds"), key)
