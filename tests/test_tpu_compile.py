"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported casts, blocks off the (8, 128) tiling, VMEM overruns. These cases
hand each kernel entry point shapes at gemma-2b widths (M=256, K=2048,
N=16384; paged decode with H=8, Hkv=1, D=256, page 16, and once more at
granite-3-8b's serving cell: 48 slots, H=32, Hkv=8, D=128, 96 pages a slot)
placed on one chip of a described ``v5e:2x2`` topology, compile it with the
TPU compiler, and check that the compiled program holds the kernel
(``tpu_custom_call``). Nothing runs; no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import paged_attn as pa_mod
from repro.kernels import qmm as qmm_mod
from repro.kernels import qmm_bitplane as qbp_mod

M, K, N = 256, 2048, 16384          # gemma-2b: tokens × d_model × d_ff
H, HKV, D, PAGE = 8, 1, 256, 16     # gemma-2b MQA decode over a paged pool
B, MAXP, POOL = 8, 8, 64            # decode batch, pages per seq, pool pages


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A chip compile written to the persistent cache cannot be read back
    without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _qmm(packed):
    def build(s):
        codes = s((K, N // 2), jnp.uint8) if packed else s((K, N), jnp.int8)
        return (lambda x, c, sc: qmm_mod.qmm(x, c, sc, packed=packed),
                (s((M, K), jnp.bfloat16), codes, s((1, N), jnp.float32)))
    return build


def _qmm_t(packed):
    def build(s):
        codes = s((K, N // 2), jnp.uint8) if packed else s((K, N), jnp.int8)
        return (lambda g, c, sc: qmm_mod.qmm_t(g, c, sc, packed=packed),
                (s((M, N), jnp.bfloat16), codes, s((1, N), jnp.float32)))
    return build


def _qmm_qout(packed):
    # the fused epilogue holds a (bm, N) row in VMEM; N = d_model here (the
    # attention out-projection feeding a quantized activation channel)
    def build(s):
        codes = s((K, K // 2), jnp.uint8) if packed else s((K, K), jnp.int8)
        return (lambda x, c, sc, r: qmm_mod.qmm_qout(x, c, sc, r, qmax=127,
                                                     packed=packed),
                (s((M, K), jnp.bfloat16), codes, s((1, K), jnp.float32),
                 s((M, K), jnp.uint32)))
    return build


def _qmm_bitplane(s):
    return (qbp_mod.qmm_bitplane,
            (s((M, K), jnp.bfloat16), s((9, K, N // 32), jnp.uint32),
             s((1, N), jnp.float32)))


def _ds_quant(scale_axis):
    # row: scale=None runs the row_absmax reduction kernel, then the fused
    # pair draw; col: the data pipeline's column scales (the linear models)
    def build(s):
        if scale_axis == "row":
            return (lambda x, key: ops.ds_quantize(x, 7, key),
                    (s((4096, K), jnp.float32), s((2,), jnp.uint32)))
        return (lambda x, key, sc: ops.ds_quantize(x, 7, key, scale=sc),
                (s((4096, K), jnp.float32), s((2,), jnp.uint32),
                 s((K,), jnp.float32)))
    return build


def _qmv(s):
    return (qmm_mod.qmv, (s((4096, K), jnp.int8), s((K, 1), jnp.float32)))


def _paged_attn(kv_bits, b=B, h=H, hkv=HKV, d=D, page=PAGE, maxp=MAXP,
                pool=POOL):
    def build(s):
        dp = d // 2 if kv_bits == 4 else d
        dt = {0: jnp.bfloat16, 8: jnp.int8, 4: jnp.uint8}[kv_bits]
        sc = (pool, page, hkv, 1) if kv_bits else (1, 1, hkv, 1)
        return (lambda q, kp, vp, ks, vs, bt, ln: pa_mod.paged_decode_attn(
                    q, kp, vp, ks, vs, bt, ln, softmax_scale=d ** -0.5,
                    kv_bits=kv_bits),
                (s((b, h, d), jnp.bfloat16), s((pool, page, hkv, dp), dt),
                 s((pool, page, hkv, dp), dt), s(sc, jnp.float32),
                 s(sc, jnp.float32), s((b, maxp), jnp.int32),
                 s((b,), jnp.int32)))
    return build


def _quant_adamw(s):
    r, c = 2048, N

    def step(master, g, mc, ms, vc, vs, rand):
        return ops.quant_adamw_update(
            master, g, mc, ms, vc, vs, rand, qmax=127, b1=0.9, b2=0.999,
            eps=1e-8, wd=0.01, uclip=10.0, lr=1e-3, b1c=0.1, b2c=0.001,
            clip=1.0, finite=1.0)
    return (step, (s((r, c), jnp.float32), s((r, c), jnp.float32),
                   s((r, c), jnp.int8), s((c,), jnp.float32),
                   s((r, c), jnp.int8), s((c,), jnp.float32),
                   s((r, c), jnp.uint32)))


CASES = {
    "qmm_int8": _qmm(False),
    "qmm_int4": _qmm(True),
    "qmm_t_int8": _qmm_t(False),
    "qmm_t_int4": _qmm_t(True),
    "qmm_qout_int8": _qmm_qout(False),
    "qmm_qout_int4": _qmm_qout(True),
    "qmm_bitplane": _qmm_bitplane,
    "ds_quant_row": _ds_quant("row"),
    "ds_quant_col": _ds_quant("col"),
    "qmv": _qmv,
    "paged_attn_bf16": _paged_attn(0),
    "paged_attn_int8": _paged_attn(8),
    "paged_attn_int4": _paged_attn(4),
    # granite-3-8b's serving cell: 48 slots, GQA 32/8, D 128, 96 pages a slot
    "paged_attn_int8_granite": _paged_attn(8, b=48, h=32, hkv=8, d=128,
                                           maxp=96, pool=2475),
    "quant_adamw": _quant_adamw,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache,
                                 monkeypatch):
    monkeypatch.setenv("ZIPML_PALLAS_INTERPRET", "0")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[case](s)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
