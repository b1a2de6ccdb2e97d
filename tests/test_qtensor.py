"""QTensor as a first-class pytree: jit/vmap/scan round-trips, checkpoint
save/restore, the compressed psum under shard_map, and the storage
accounting (nbits/nbytes host-side, +1 bit for the DS pair)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import quant
from repro.ckpt.checkpoint import CheckpointManager
from repro.precision import gradcomp, qat
from repro.quant import PrecisionPlan, QScheme, QTensor

KEY = jax.random.PRNGKey(0)


def _qt(shape=(8, 16), bits=8, key=KEY):
    x = jax.random.normal(key, shape)
    return x, quant.encode(x, QScheme.int_symmetric(bits), key)


class TestPytree:
    def test_flatten_roundtrip(self):
        _, qt = _qt()
        leaves, treedef = jax.tree.flatten(qt)
        back = jax.tree.unflatten(treedef, leaves)
        assert isinstance(back, QTensor)
        assert back.scheme == qt.scheme
        np.testing.assert_array_equal(np.asarray(back.codes),
                                      np.asarray(qt.codes))

    def test_jit_through(self):
        x, qt = _qt()

        @jax.jit
        def f(q):
            return q.decode()

        np.testing.assert_array_equal(np.asarray(f(qt)),
                                      np.asarray(qt.decode()))

        @jax.jit
        def g(v, k):
            return quant.encode(v, QScheme.int_symmetric(8), k)

        out = g(x, KEY)
        assert isinstance(out, QTensor)
        np.testing.assert_array_equal(np.asarray(out.codes),
                                      np.asarray(qt.codes))

    def test_vmap_through(self):
        xs = jax.random.normal(KEY, (4, 8, 16))
        keys = jax.random.split(KEY, 4)

        def enc(v, k):
            return quant.encode(v, QScheme.int_symmetric(8), k)

        qts = jax.vmap(enc)(xs, keys)
        assert isinstance(qts, QTensor) and qts.codes.shape == (4, 8, 16)
        deq = jax.vmap(lambda q: q.decode())(qts)
        for i in range(4):
            want = enc(xs[i], keys[i]).decode()
            np.testing.assert_array_equal(np.asarray(deq[i]), np.asarray(want))

    def test_scan_carry(self):
        """A QTensor rides through lax.scan as the carry (the scheme is static
        aux data, so carry-in/carry-out structures match)."""
        x = jax.random.normal(KEY, (8, 16))
        scheme = QScheme.int_symmetric(8, rounding="nearest")
        qt = quant.encode(x, scheme)

        def body(carry, _):
            q = quant.encode(carry.decode(), scheme)  # re-encode: same scheme
            return q, q.decode().sum()

        final, ys = jax.lax.scan(body, qt, jnp.arange(3))
        assert isinstance(final, QTensor) and ys.shape == (3,)
        # int grid nearest re-encode of already-on-grid values is idempotent
        np.testing.assert_allclose(np.asarray(final.decode()),
                                   np.asarray(qt.decode()), rtol=1e-6)

    def test_optimal_levels_stacked_scans(self):
        """Regression (seed bug): the C4 level-table weight storage must ride
        lax.scan over stacked layers — the old splice format put a dim-less
        table next to (L, …) codes, which scan rejected."""
        w = jax.random.normal(KEY, (3, 8, 4))          # (L, d_in, d_out)
        qt = qat._optimal_quantize_weight(w, 4)
        assert qt.levels.shape[0] == 3 and qt.scale.shape == (3,)

        def body(c, layer_qt):
            return c + layer_qt.decode().sum(), None

        total, _ = jax.lax.scan(body, jnp.float32(0), qt)
        np.testing.assert_allclose(float(total), float(qt.decode().sum()),
                                   rtol=1e-5)

    def test_ds_pair_planes_and_grad_none_leaves(self):
        x = jax.random.normal(KEY, (8, 16))
        qt = quant.ds_pair(x, QScheme.zipml(7, rounding="ds"), KEY)
        assert qt.is_ds and qt.codes2.shape == x.shape
        # None children (levels) survive transformations
        out = jax.jit(lambda q: (q.decode() + q.decode2()) / 2)(qt)
        assert out.shape == x.shape


class TestEdgeCases:
    def test_wide_int_grid_uses_int32_codes(self):
        """bits > 8 must widen the code dtype, not saturate int8."""
        x = jax.random.normal(KEY, (64,))
        qt = quant.encode(x, QScheme.int_symmetric(16, rounding="nearest"))
        assert qt.codes.dtype == jnp.int32
        step = float(jnp.max(jnp.abs(x))) / (2**15 - 1)
        assert float(jnp.max(jnp.abs(qt.decode() - x))) <= step + 1e-7

    def test_ds_without_key_raises(self):
        x = jnp.ones((4, 4))
        with pytest.raises(ValueError, match="PRNG key"):
            quant.ds_pair(x, QScheme.zipml(7, rounding="ds"), None)
        with pytest.raises(ValueError, match="PRNG key"):
            quant.encode(x, QScheme.int_symmetric(8, rounding="ds"))

    def test_stochastic_without_key_raises(self):
        with pytest.raises(ValueError, match="PRNG key"):
            quant.encode(jnp.ones((4,)), QScheme.int_symmetric(8))


class TestAccounting:
    def test_nbits_host_side(self):
        """nbits must be a Python int computed without tracing (the old
        Quantized.nbits called jnp on a Python int)."""
        _, qt = _qt(bits=8)
        assert isinstance(qt.nbits, int) and qt.nbits == 8
        zq = quant.encode(jnp.ones((4, 4)), QScheme.zipml(7), KEY)
        assert zq.nbits == 3          # ceil(log2(7+1))
        dsq = quant.ds_pair(jnp.ones((4, 4)), QScheme.zipml(7, rounding="ds"),
                            KEY)
        assert dsq.nbits == 4         # +1 bit for the second DS plane (§2.2)

    def test_nbits_under_jit(self):
        _, qt = _qt()

        @jax.jit
        def f(q):
            return jnp.zeros((q.nbits,))  # host int → usable as a shape

        assert f(qt).shape == (8,)

    def test_nbytes(self):
        _, qt = _qt(shape=(16, 16), bits=8)
        # 256 int8 codes + 1 f32 scalar scale
        assert qt.nbytes == 256 + 4
        dsq = quant.ds_pair(jax.random.normal(KEY, (16, 16)),
                            QScheme.zipml(7, rounding="ds"), KEY)
        assert dsq.nbytes == (256 * 4 + 7) // 8 + 4   # 4 bits/coord + scale


class TestDot:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_dot_matches_decode(self, backend):
        x = jax.random.normal(KEY, (16, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 1), (32,))
        for scheme, scale in [
            (QScheme.zipml(7), None),
            (QScheme.zipml(15, scaling="column"), jnp.max(jnp.abs(x), axis=0)),
            (QScheme.int_symmetric(8), None),
        ]:
            qt = quant.encode(x, scheme, KEY, scale=scale)
            want = np.asarray(qt.decode() @ v)
            got = np.asarray(quant.dot(qt, v, backend=backend))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestBackendStructuralParity:
    def test_ds_pair_same_structure_both_backends(self):
        """ref- and pallas-produced QTensors must be interchangeable: same
        scale shape, same nbytes, same pytree structure (the pallas kernel's
        internal scale broadcast must not leak into the storage format)."""
        x = jax.random.normal(KEY, (8, 16))
        for scheme, scale in [
            (QScheme.zipml(15, rounding="ds"), None),
            (QScheme.zipml(15, scaling="column", rounding="ds"),
             jnp.max(jnp.abs(x), axis=0)),
        ]:
            qr = quant.ds_pair(x, scheme, KEY, scale=scale, backend="ref")
            qp = quant.ds_pair(x, scheme, KEY, scale=scale, backend="pallas")
            assert qr.scale.shape == qp.scale.shape, scheme
            assert qr.nbytes == qp.nbytes, scheme
            assert (jax.tree.structure(qr) == jax.tree.structure(qp))


class TestQuantizedConsumers:
    def test_moe_expert_weights_qtensor(self):
        """moe._wmat must read QTensor expert weights (int8 serving of MoE)."""
        from repro.models import moe as moe_mod

        spec = moe_mod.MoESpec(n_experts=4, top_k=2, d_model=16, d_ff=32,
                               dense_path_max_tokens=512)
        p = moe_mod.init_moe(KEY, spec, dtype=jnp.float32)
        x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 8, 16))
        y_ref = moe_mod.moe_block(p, x, spec)
        qp = qat.quantize_param_tree(p, bits=8)
        assert isinstance(qp["up"]["w"], QTensor)
        assert isinstance(qp["down"]["w"], QTensor)
        y_q = moe_mod.moe_block(qp, x, spec)
        rel = float(jnp.linalg.norm(y_q - y_ref) /
                    (jnp.linalg.norm(y_ref) + 1e-9))
        assert rel < 0.1, rel

    def test_param_spec_shards_qtensor_codes(self):
        """QTensor code planes must inherit the dense weight's sharding;
        scales/levels replicate (sharding rules see flat-index child paths)."""
        from repro.launch import sharding as sh

        params = {"mlp": {"up": {"w": quant.encode(
            jnp.zeros((512, 512)), QScheme.int_symmetric(
                8, scaling="channel", rounding="nearest"))}}}
        specs = jax.tree_util.tree_map_with_path(
            lambda pth, leaf: sh.param_spec(pth, leaf), params)
        qt_specs = specs["mlp"]["up"]["w"]
        assert qt_specs.codes == P("data", "model")       # like a dense 'w'
        assert qt_specs.scale == P(None, None)            # replicated


class TestCheckpoint:
    def test_save_restore_qtensor_tree(self, tmp_path):
        x = jax.random.normal(KEY, (16, 8))
        tree = {
            "dense": {"w": quant.encode(x, QScheme.int_symmetric(
                8, scaling="channel", rounding="nearest"))},
            "step_scale": jnp.float32(0.5),
        }
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, tree, blocking=True)
        template = {
            "dense": {"w": quant.encode(jnp.zeros_like(x), QScheme.int_symmetric(
                8, scaling="channel", rounding="nearest"))},
            "step_scale": jnp.float32(0.0),
        }
        restored, manifest = mgr.restore(template)
        assert manifest["step"] == 3
        got = restored["dense"]["w"]
        assert isinstance(got, QTensor)
        np.testing.assert_array_equal(np.asarray(got.codes),
                                      np.asarray(tree["dense"]["w"].codes))
        np.testing.assert_array_equal(np.asarray(got.decode()),
                                      np.asarray(tree["dense"]["w"].decode()))

    def test_quantized_param_tree_roundtrip(self, tmp_path):
        """The serving weight format (qat.quantize_param_tree) checkpoints."""
        params = {"mlp": {"up": {"w": jax.random.normal(KEY, (8, 16))},
                          "norm": {"g": jnp.ones((8,))}}}
        qparams = qat.quantize_param_tree(params, bits=8)
        assert isinstance(qparams["mlp"]["up"]["w"], QTensor)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, qparams, blocking=True)
        restored, _ = mgr.restore(jax.tree.map(jnp.zeros_like, qparams))
        np.testing.assert_array_equal(
            np.asarray(restored["mlp"]["up"]["w"].codes),
            np.asarray(qparams["mlp"]["up"]["w"].codes))


class TestShardMap:
    def test_compressed_psum_under_shard_map(self):
        mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
        psum = gradcomp.make_compressed_psum("pod", 8)
        g = {"a": jax.random.normal(KEY, (8, 4)),
             "b": jax.random.normal(jax.random.fold_in(KEY, 1), (16,))}
        f = jax.shard_map(psum, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                      check_vma=False)
        out = f(g, KEY)
        # single member ⇒ the mean equals the dequantized compression of g
        comp, _ = gradcomp.compress_tree(g, 8, KEY)
        want = gradcomp.decompress_tree(comp)
        for k in g:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(want[k]), rtol=1e-6)
            step = float(jnp.max(jnp.abs(g[k]))) / 127
            assert float(jnp.max(jnp.abs(out[k] - g[k]))) <= step + 1e-6


class TestPrecisionPlanRoundtrip:
    def test_to_from_dict(self):
        p = PrecisionPlan("e2e", sample_bits=6, model_bits=8, grad_bits=8,
                          kv_bits=4, model_storage="int")
        q = PrecisionPlan.from_dict(p.to_dict())
        assert p == q and hash(p) == hash(q)

    def test_legacy_kwargs_map_to_canonical(self):
        with pytest.warns(DeprecationWarning):
            p = PrecisionPlan(weight_bits=8, act_ds_bits=4,
                              weight_storage="int")
        assert (p.model_bits, p.act_bits, p.model_storage) == (8, 4, "int")

    def test_unknown_kwarg_rejected(self):
        with pytest.raises(TypeError):
            PrecisionPlan(frobnicate=3)

    def test_conflicting_legacy_and_canonical_kwargs_rejected(self):
        with pytest.raises(TypeError):
            PrecisionPlan(model_bits=4, weight_bits=8)
