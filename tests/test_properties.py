"""Hypothesis property-based tests for the system's core invariants.

Each property is the load-bearing guarantee of a subsystem:
  * quantizer unbiasedness & boundedness over arbitrary inputs/levels (C1)
  * the full repro.quant QScheme grid: round-trip error bound and
    stochastic/double-sampling unbiasedness over bits × tensor/row/column/
    channel scaling × nearest/stochastic/ds rounding, incl. packed int4
  * DP-optimal levels never lose to uniform, monotone in s (C4)
  * double-sampling estimator unbiasedness for arbitrary (a, x, b) (C2)
  * gradient compression roundtrip bound & error-feedback telescoping (C3)
  * sharding rules always produce divisible, mesh-valid specs
  * data pipeline determinism under arbitrary cursors
"""
import numpy as np
import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

import repro.core.quantize as qz
from repro import quant
from repro.core import optimal
from repro.core.double_sampling import (lsq_gradient_double_sampling,
                                        lsq_gradient_fullprec)
from repro.data.pipeline import Cursor, TokenStream, TokenStreamConfig
from repro.precision import gradcomp
from repro.quant import QScheme

SETTINGS = dict(max_examples=25, deadline=None)


@st.composite
def _vectors(draw, max_n=48):
    n = draw(st.integers(1, max_n))
    seed = draw(st.integers(0, 2**31 - 1))
    scale = draw(st.floats(1e-3, 1e3))
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, scale, n), jnp.float32)


class TestQuantizerProperties:
    @settings(**SETTINGS)
    @given(v=_vectors(), s=st.sampled_from([1, 2, 3, 7, 15, 127]),
           seed=st.integers(0, 2**31 - 1))
    def test_roundtrip_within_one_interval(self, v, s, seed):
        q = qz.stochastic_quantize(v, s, jax.random.PRNGKey(seed))
        width = qz.row_scale(v) / s
        assert float(jnp.max(jnp.abs(q - v))) <= float(width) + 1e-4

    @settings(**SETTINGS)
    @given(v=_vectors(max_n=16), s=st.sampled_from([1, 3, 7]),
           seed=st.integers(0, 2**31 - 1))
    def test_unbiased(self, v, s, seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), 1500)
        qs = jax.vmap(lambda k: qz.stochastic_quantize(v, s, k))(keys)
        se = qs.std(0) / np.sqrt(1500) + 1e-6
        assert (np.abs(np.asarray(qs.mean(0) - v)) < 6 * se
                + 1e-3 * float(qz.row_scale(v))).all()

    @settings(**SETTINGS)
    @given(v=_vectors(), s=st.sampled_from([1, 3, 31]))
    def test_variance_bound(self, v, s):
        n = v.shape[0]
        tv = float(qz.tv_variance(v, s, scale=qz.row_scale(v, "l2")))
        bound = min(n / s**2, np.sqrt(n) / s) * float(jnp.sum(v * v))
        assert tv <= bound + 1e-4 * bound + 1e-6


SCALINGS = ("tensor", "row", "column", "channel")


def _grid_matrix(seed, rows=4, cols=8, spread=True):
    rng = np.random.default_rng(seed)
    sd = rng.uniform(1e-2, 10.0) if spread else 1.0
    return jnp.asarray(rng.normal(0, sd, (rows, cols)), jnp.float32)


def _bcast_scale(qt, shape):
    return np.broadcast_to(np.asarray(qt.scale), shape)


class TestQSchemeGridProperties:
    """The repro.quant contract over the whole scheme grid: every
    (grid × bits × scaling × rounding) cell round-trips within one code step
    and the stochastic modes are unbiased — including the nibble-packed int4
    storage, which must be value-identical to unpacked int4."""

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), bits=st.sampled_from([2, 4, 8]),
           scaling=st.sampled_from(SCALINGS),
           rounding=st.sampled_from(["nearest", "stochastic", "ds"]))
    def test_int_grid_roundtrip_within_one_step(self, seed, bits, scaling,
                                                rounding):
        x = _grid_matrix(seed)
        sch = QScheme.int_symmetric(bits, scaling=scaling, rounding=rounding)
        qt = quant.encode(x, sch, key=jax.random.PRNGKey(seed))
        step = _bcast_scale(qt, x.shape)
        tol = step * (0.5 if rounding == "nearest" else 1.0) + 1e-5
        planes = [qt.decode()] + ([qt.decode2()] if rounding == "ds" else [])
        for deq in planes:
            err = np.abs(np.asarray(deq) - np.asarray(x))
            assert (err <= tol).all(), float((err - tol).max())

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), s=st.sampled_from([3, 7, 31]),
           scaling=st.sampled_from(["tensor", "row"]),
           rounding=st.sampled_from(["nearest", "stochastic", "ds"]))
    def test_zipml_grid_roundtrip_within_one_interval(self, seed, s, scaling,
                                                      rounding):
        x = _grid_matrix(seed)
        sch = QScheme.zipml(s, scaling=scaling, rounding=rounding)
        qt = quant.encode(x, sch, key=jax.random.PRNGKey(seed))
        width = _bcast_scale(qt, x.shape) / s
        tol = width * (0.5 if rounding == "nearest" else 1.0) + 1e-5
        planes = [qt.decode()] + ([qt.decode2()] if rounding == "ds" else [])
        for deq in planes:
            err = np.abs(np.asarray(deq) - np.asarray(x))
            assert (err <= tol).all(), float((err - tol).max())

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), bits=st.sampled_from([2, 4]),
           scaling=st.sampled_from(["tensor", "row"]))
    def test_stochastic_rounding_unbiased(self, seed, bits, scaling):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(0, 1, (12,)), jnp.float32)
        sch = QScheme.int_symmetric(bits, scaling=scaling)
        keys = jax.random.split(jax.random.PRNGKey(seed), 1500)
        deq = jax.vmap(lambda k: quant.encode(x, sch, key=k).decode())(keys)
        se = np.asarray(deq.std(0)) / np.sqrt(1500) + 1e-6
        bias = np.abs(np.asarray(deq.mean(0)) - np.asarray(x))
        smax = float(np.max(np.asarray(quant.compute_scale(x, sch))))
        assert (bias < 6 * se + 1e-3 * smax).all(), bias.max()

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), bits=st.sampled_from([2, 4]))
    def test_ds_planes_each_unbiased(self, seed, bits):
        """§2.2: both double-sampling planes are themselves unbiased draws
        (they share the scale and base level, not the up/down bits)."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(0, 1, (12,)), jnp.float32)
        sch = QScheme.int_symmetric(bits, rounding="ds")
        keys = jax.random.split(jax.random.PRNGKey(seed), 1500)
        smax = float(np.max(np.asarray(quant.compute_scale(x, sch))))
        for plane in ("decode", "decode2"):
            deq = jax.vmap(
                lambda k: getattr(quant.encode(x, sch, key=k), plane)())(keys)
            se = np.asarray(deq.std(0)) / np.sqrt(1500) + 1e-6
            bias = np.abs(np.asarray(deq.mean(0)) - np.asarray(x))
            assert (bias < 6 * se + 1e-3 * smax).all(), (plane, bias.max())

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), scaling=st.sampled_from(SCALINGS),
           rounding=st.sampled_from(["nearest", "stochastic"]))
    def test_packed_int4_value_identical(self, seed, scaling, rounding):
        """Nibble packing is a pure storage transform: same key ⇒ identical
        dequantized values, identical logical nbytes, half the physical
        code bytes."""
        x = _grid_matrix(seed, rows=6, cols=16)
        key = jax.random.PRNGKey(seed)
        qu = quant.encode(x, QScheme.int_symmetric(
            4, scaling=scaling, rounding=rounding), key=key)
        qp = quant.encode(x, QScheme.int_symmetric(
            4, scaling=scaling, rounding=rounding, packed=True), key=key)
        np.testing.assert_array_equal(np.asarray(qu.decode()),
                                      np.asarray(qp.decode()))
        assert qp.nbytes == qu.nbytes
        assert qp.codes.size * 2 == qu.codes.size
        assert qp.codes.dtype == jnp.uint8

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_pack_unpack_roundtrip_exact(self, seed):
        rng = np.random.default_rng(seed)
        codes = jnp.asarray(rng.integers(-7, 8, (5, 10)), jnp.int8)
        np.testing.assert_array_equal(
            np.asarray(quant.unpack_int4(quant.pack_int4(codes))),
            np.asarray(codes, np.float32))


class TestBitplaneProperties:
    """The any-precision contract of ``layout='bitplane'`` storage: the
    round-trip error bound holds at every bit width × scaling family, a
    top-k plane slice decodes EXACTLY like quantizing directly at k bits
    (scale is bits-independent and the magnitude truncation nests), and the
    physical bytes are linear in the planes kept."""

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), bits=st.integers(1, 8),
           scaling=st.sampled_from(SCALINGS))
    def test_roundtrip_within_one_step(self, seed, bits, scaling):
        x = _grid_matrix(seed)
        qt = quant.encode(x, QScheme.bitplane(bits, scaling=scaling))
        assert qt.codes.dtype == jnp.uint32
        assert qt.shape == x.shape
        step = _bcast_scale(qt, x.shape) * 2.0 ** -bits
        err = np.abs(np.asarray(qt.decode()) - np.asarray(x))
        # truncation: one full step, plus fp32 rounding of mag·scale·2^-bits
        # (relative to |x| ≈ step·2^bits, hence the 1e-4 headroom at bits=8)
        tol = step * (1 + 1e-4) + 1e-7
        assert (err <= tol).all(), float((err - tol).max())

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), k=st.sampled_from([1, 2, 4, 8]),
           scaling=st.sampled_from(SCALINGS))
    def test_plane_slice_equals_direct_encode(self, seed, k, scaling):
        """slice_planes(k) of the 8-bit artifact ≡ encoding at k bits — the
        MLWeaving any-precision invariant, exact (not approximate)."""
        x = _grid_matrix(seed)
        full = quant.encode(x, QScheme.bitplane(8, scaling=scaling))
        direct = quant.encode(x, QScheme.bitplane(k, scaling=scaling))
        sliced = full.slice_planes(k)
        np.testing.assert_array_equal(np.asarray(sliced.codes),
                                      np.asarray(direct.codes))
        np.testing.assert_array_equal(np.asarray(sliced.decode()),
                                      np.asarray(direct.decode()))
        assert sliced.scheme.bits == k

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1),
           scaling=st.sampled_from(["tensor", "channel"]))
    def test_nbytes_linear_in_planes(self, seed, scaling):
        """Code bytes of a k-bit slice are exactly (k+1)/(B+1) of the full
        artifact's — the byte-per-plane increment is constant."""
        x = _grid_matrix(seed)
        full = quant.encode(x, QScheme.bitplane(8, scaling=scaling))
        scale_b = np.asarray(full.scale).size * 4
        code_b = {k: full.slice_planes(k).nbytes - scale_b for k in range(1, 9)}
        per_plane = code_b[8] // 9
        assert code_b[8] == 9 * per_plane
        for k in range(1, 9):
            assert code_b[k] == (k + 1) * per_plane, (k, code_b)

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(1, 70))
    def test_pack_unpack_bitplanes_roundtrip(self, seed, d):
        rng = np.random.default_rng(seed)
        planes = jnp.asarray(rng.integers(0, 2, (3, 4, d)), jnp.uint32)
        words = quant.pack_bitplanes(planes)
        assert words.dtype == jnp.uint32
        assert words.shape == (3, 4, -(-d // 32))
        np.testing.assert_array_equal(
            np.asarray(quant.unpack_bitplanes(words, d)), np.asarray(planes))


class TestOptimalLevelProperties:
    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), s=st.sampled_from([2, 3, 7]),
           n=st.integers(10, 300))
    def test_never_worse_than_uniform(self, seed, s, n):
        xs = np.clip(np.random.default_rng(seed).beta(0.7, 2.0, n), 0, 1)
        lv = optimal.optimal_levels_discretized(xs, s, M=64)
        assert (optimal.mean_variance(xs, lv)
                <= optimal.mean_variance(xs, optimal.uniform_levels(s)) + 1e-12)

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_monotone_in_levels(self, seed):
        xs = np.random.default_rng(seed).uniform(0, 1, 200)
        mvs = [optimal.mean_variance(
            xs, optimal.optimal_levels_discretized(xs, s, M=64))
            for s in (2, 4, 8)]
        assert mvs[0] >= mvs[1] >= mvs[2] - 1e-12


class TestDoubleSamplingProperties:
    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 24),
           batch=st.integers(1, 8))
    def test_unbiased_for_any_instance(self, seed, n, batch):
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.normal(0, 1, (batch, n)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 2, n), jnp.float32)
        b = jnp.asarray(rng.normal(0, 1, batch), jnp.float32)
        truth = lsq_gradient_fullprec(x, a, b)
        keys = jax.random.split(jax.random.PRNGKey(seed), 2000)
        gs = jax.vmap(lambda k: lsq_gradient_double_sampling(x, a, b, 3, k))(keys)
        se = np.asarray(gs.std(0)) / np.sqrt(2000) + 1e-6
        assert (np.abs(np.asarray(gs.mean(0) - truth)) < 6 * se + 1e-2).all()


class TestGradCompressionProperties:
    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1), bits=st.sampled_from([2, 4, 8]),
           n=st.integers(1, 64))
    def test_roundtrip_bound(self, seed, bits, n):
        g = {"x": jnp.asarray(np.random.default_rng(seed).normal(0, 1, n),
                              jnp.float32)}
        comp, _ = gradcomp.compress_tree(g, bits, jax.random.PRNGKey(seed))
        deq = gradcomp.decompress_tree(comp)
        step = float(jnp.max(jnp.abs(g["x"]))) / (2 ** (bits - 1) - 1)
        assert float(jnp.max(jnp.abs(deq["x"] - g["x"]))) <= step + 1e-5

    @settings(**SETTINGS)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_error_feedback_residual_bounded(self, seed):
        rng = np.random.default_rng(seed)
        g = {"x": jnp.asarray(rng.normal(0, 0.01, 8), jnp.float32)}
        err = gradcomp.init_error_feedback(g)
        for i in range(30):
            _, err = gradcomp.compress_tree(g, 2, jax.random.PRNGKey(i), error=err)
        # EF residual stays bounded by one quantization step of the running sum
        assert float(jnp.max(jnp.abs(err["x"]))) < 1.0


class TestShardingRuleProperties:
    @settings(**SETTINGS)
    @given(arch=st.sampled_from(["gemma-2b", "mixtral-8x7b", "mamba2-780m",
                                 "zamba2-2.7b"]))
    def test_specs_divide_mesh(self, arch):
        """Every param spec must divide the production mesh axis sizes."""
        from repro import configs
        from repro.launch.sharding import param_spec, _path_str
        from repro.models import transformer as T
        sizes = {"data": 16, "model": 16}
        cfg = configs.get_config(arch)
        params = T.param_specs(cfg)

        def check(path, leaf):
            spec = param_spec(path, leaf)
            for dim, part in zip(leaf.shape, spec):
                if part is None:
                    continue
                parts = part if isinstance(part, tuple) else (part,)
                total = int(np.prod([sizes[p] for p in parts]))
                assert dim % total == 0, (_path_str(path), leaf.shape, spec)

        jax.tree_util.tree_map_with_path(check, params)


class TestPipelineProperties:
    @settings(**SETTINGS)
    @given(seed=st.integers(0, 1000), step=st.integers(0, 50),
           vocab=st.integers(10, 1000))
    def test_cursor_determinism(self, seed, step, vocab):
        cfg = TokenStreamConfig(vocab_size=vocab, seq_len=8, global_batch=2,
                                seed=seed)
        s1 = TokenStream(cfg)
        s1.skip_to(Cursor(step=step))
        b1 = s1.next_batch()
        s2 = TokenStream(cfg)
        s2.skip_to(Cursor(step=step))
        b2 = s2.next_batch()
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert b1["tokens"].max() < vocab
