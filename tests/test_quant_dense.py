"""quant_dense — the fused dequant-GEMM registry op family.

Pins the PR-level guarantees:

* the **ref backend is bit-exact** with the pre-op decode-then-einsum model
  numerics on every path (dense layer, MoE expert/router stacks, tied
  unembed transpose, level tables), forward AND backward — so routing the
  model through the op is a pure data-movement change;
* the **pallas backend matches the f32 decode path to ≤ 1e-5** relative
  error forward and backward (f32-accumulation associativity only), for
  int8 and nibble-packed int4 code planes;
* **ShipWeight** carries the straight-through master gradient while the
  matmul consumes codes, including over ``lax.scan``-stacked layers, and
  packed-int4 ship is value-identical to unpacked int4 (nibbles round-trip
  exactly);
* the **quantize epilogue** (quant_dense_q / act_quant.ds_project) equals
  matmul → cast → ds row-pair on the ref backend, and the fused Pallas
  kernel is bit-identical to the unfused kernel path given the same rand
  bits;
* the removed spliced weight formats raise, and interpret-mode selection
  resolves in one place (registry.interpret_default + env flag).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs, quant
from repro.kernels import registry
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.precision import act_quant, qat
from repro.quant import (PrecisionPlan, QScheme, QTensor, ShipWeight,
                         quant_dense, quant_dense_q)

KEY = jax.random.PRNGKey(0)


def _wq(shape, bits=8, packed=False, key=KEY):
    w = jax.random.normal(key, shape) * 0.05
    scheme = QScheme.int_symmetric(bits, scaling="channel", channel_axis=-2,
                                   rounding="nearest", packed=packed)
    return w, quant.encode(w, scheme)


# ---------------------------------------------------------------------------
# ref backend: bit-exact with the pre-op decode-then-einsum numerics
# ---------------------------------------------------------------------------

class TestRefBitExact:
    def test_dense_forward(self):
        _, qt = _wq((32, 24))
        x = jax.random.normal(KEY, (4, 6, 32)).astype(jnp.bfloat16)
        got = quant_dense(x, qt, backend="ref")
        want = jnp.einsum("...i,io->...o", x, qt.decode(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_dense_layer_forward(self):
        _, qt = _wq((32, 24))
        x = jax.random.normal(KEY, (2, 5, 32)).astype(jnp.bfloat16)
        got = L.dense({"w": qt}, x)
        want = jnp.einsum("...i,io->...o", x, qt.decode(jnp.bfloat16),
                          preferred_element_type=jnp.float32).astype(x.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_backward_matches_autodiff_through_decode(self):
        _, qt = _wq((32, 24))
        x = jax.random.normal(KEY, (4, 32)).astype(jnp.bfloat16)
        g1 = jax.grad(lambda x: quant_dense(x, qt, backend="ref").sum())(x)
        g2 = jax.grad(lambda x: jnp.einsum(
            "...i,io->...o", x, qt.decode(jnp.bfloat16),
            preferred_element_type=jnp.float32).sum())(x)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    def test_stacked_expert_forward(self):
        _, qt = _wq((3, 16, 8))
        assert qt.scale.shape == (3, 1, 8)     # per-expert channel scales
        x = jax.random.normal(KEY, (5, 3, 7, 16)).astype(jnp.bfloat16)
        got = quant_dense(x, qt, backend="ref")
        w = qt.decode(jnp.bfloat16)
        want = jnp.stack([jnp.einsum("gcd,df->gcf", x[:, e], w[e],
                                     preferred_element_type=jnp.float32)
                          for e in range(w.shape[0])], axis=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_unembed_transpose(self):
        _, qt = _wq((40, 32))
        x = jax.random.normal(KEY, (2, 3, 32)).astype(jnp.bfloat16)
        got = L.unembed({"table": qt}, x)
        want = jnp.einsum("...d,vd->...v", x, qt.decode(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_levels_grid_falls_back_to_decode(self):
        w = jax.random.normal(KEY, (16, 8)) * 0.1
        qt = qat._optimal_quantize_weight(w, 4)
        x = jax.random.normal(KEY, (4, 16)).astype(jnp.bfloat16)
        for be in ("ref", "pallas"):
            got = quant_dense(x, qt, backend=be)
            want = jnp.einsum("...i,io->...o", x, qt.decode(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def _trees(self, arch):
        cfg = configs.get_reduced(arch)
        params = T.init_params(KEY, cfg)
        qp = qat.quantize_param_tree(params, bits=8)
        dec = jax.tree.map(
            lambda l: l.decode(jnp.bfloat16) if isinstance(l, QTensor) else l,
            qp, is_leaf=lambda l: isinstance(l, QTensor))
        return cfg, qp, dec

    def test_int_storage_prefill_bit_exact_vs_decode_einsum(self):
        """Whole-model parity: serving at int8 storage produces logits
        bit-identical to dequantizing every weight up front (the pre-op
        semantics of layers.dense). Unrolled layers — XLA's scan-vs-unrolled
        bf16 fusion already differed at ~1e-3 BEFORE this op existed (the
        same two programs diverge identically on the pre-op code), so only
        the unrolled form is a same-program bit-level comparison."""
        cfg, qp, dec = self._trees("musicgen-medium")
        cfg = dataclasses.replace(cfg, scan_layers=False)
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
        lq, _ = T.prefill(qp, toks, cfg)
        ld, _ = T.prefill(dec, toks, cfg)
        np.testing.assert_array_equal(np.asarray(lq), np.asarray(ld))

    def test_int_storage_prefill_scanned_close(self):
        cfg, qp, dec = self._trees("musicgen-medium")
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
        lq, _ = T.prefill(qp, toks, cfg)
        ld, _ = T.prefill(dec, toks, cfg)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                                   rtol=0.05, atol=0.5)

    def test_moe_prefill_and_decode_bit_exact_vs_decode_einsum(self):
        cfg, qp, dec = self._trees("mixtral-8x7b")
        cfg = dataclasses.replace(cfg, scan_layers=False)
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
        lq, sq = T.prefill(qp, toks, cfg)
        ld, sd = T.prefill(dec, toks, cfg)
        np.testing.assert_array_equal(np.asarray(lq), np.asarray(ld))
        dq, _ = T.decode_step(qp, sq, toks[:, :1], cfg)
        dd, _ = T.decode_step(dec, sd, toks[:, :1], cfg)
        np.testing.assert_array_equal(np.asarray(dq), np.asarray(dd))

    def test_moe_dispatch_path_bit_exact(self):
        """The capacity-dispatch (training/prefill) MoE path with QTensor
        expert tables equals the decoded-weight path bit for bit."""
        spec = moe_mod.MoESpec(n_experts=4, top_k=2, d_model=16, d_ff=32,
                               dense_path_max_tokens=0)
        p = moe_mod.init_moe(KEY, spec)
        qp = qat.quantize_param_tree(p, bits=8)
        dec = jax.tree.map(
            lambda l: l.decode(jnp.bfloat16) if isinstance(l, QTensor) else l,
            qp, is_leaf=lambda l: isinstance(l, QTensor))
        x = jax.random.normal(KEY, (2, 40, 16)).astype(jnp.bfloat16)
        yq = moe_mod.moe_block(qp, x, spec)
        yd = moe_mod.moe_block(dec, x, spec)
        np.testing.assert_array_equal(np.asarray(yq), np.asarray(yd))


# ---------------------------------------------------------------------------
# pallas backend: streams codes, ≤ 1e-5 vs the f32 decode path
# ---------------------------------------------------------------------------

class TestPallasParity:
    @pytest.mark.parametrize("bits,packed", [(8, False), (4, True)])
    def test_forward_and_backward(self, bits, packed):
        _, qt = _wq((96, 40), bits=bits, packed=packed)
        x = jax.random.normal(KEY, (7, 96)).astype(jnp.bfloat16)
        g = jax.random.normal(KEY, (7, 40)).astype(jnp.bfloat16)
        wd = qt.decode()                                  # f32 decode path
        p = registry.get("pallas")
        y = p.quant_dense(x, qt)
        y_ref = jnp.einsum("...k,kn->...n", x.astype(jnp.float32), wd)
        assert float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max()) <= 1e-5
        dx = p.quant_dense(g, qt, transpose=True)
        dx_ref = jnp.einsum("...n,kn->...k", g.astype(jnp.float32), wd)
        assert float(jnp.abs(dx - dx_ref).max()
                     / jnp.abs(dx_ref).max()) <= 1e-5

    def test_stacked_and_lead_dims(self):
        _, qt = _wq((3, 32, 16))
        x = jax.random.normal(KEY, (2, 3, 5, 32)).astype(jnp.bfloat16)
        y = registry.get("pallas").quant_dense(x, qt)
        y_ref = jnp.einsum("gecd,edf->gecf", x.astype(jnp.float32),
                           qt.decode())
        assert float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max()) <= 1e-5

    def test_packed_prefill_value_identical_to_unpacked(self):
        """Packed int4 storage is the same VALUES as unpacked int4 (offset
        nibbles round-trip exactly) — whole-model logits agree bit for bit
        on the ref backend."""
        cfg = configs.get_reduced("musicgen-medium")
        params = T.init_params(KEY, cfg)
        qp_packed = qat.quantize_param_tree(params, bits=4)      # auto-packs
        qp_plain = qat.quantize_param_tree(params, bits=4, packed=False)
        packed_planes = [l for l in jax.tree.leaves(qp_packed)
                         if hasattr(l, "dtype") and l.dtype == jnp.uint8]
        assert packed_planes, "4-bit weights should auto-pack"
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
        lp, _ = T.prefill(qp_packed, toks, cfg)
        lu, _ = T.prefill(qp_plain, toks, cfg)
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lu))


# ---------------------------------------------------------------------------
# ShipWeight: STE master gradient + scanned stacked layers + packed int4
# ---------------------------------------------------------------------------

class TestShipWeight:
    def test_ste_gradient_reaches_master(self):
        w, qt = _wq((32, 24))
        x = jax.random.normal(KEY, (4, 32)).astype(jnp.bfloat16)
        g = jnp.ones((4, 24), jnp.float32)

        def loss(w_):
            return jnp.vdot(quant_dense(x, ShipWeight(w_, qt),
                                        backend="ref"), g)
        dw = jax.grad(loss)(w)
        want = jnp.einsum("...k,...n->kn", x, g.astype(x.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(w.dtype)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(want),
                                   rtol=1e-6)

    def _ship_loss(self, bits, scan_layers, packed=None):
        plan = PrecisionPlan(model_bits=bits, model_storage="ship")
        cfg = configs.get_reduced("musicgen-medium", precision=plan)
        cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
        params = T.init_params(KEY, cfg)
        from repro.train.channels import ModelChannel
        from repro.train.step import make_grads_fn
        ch = ModelChannel(plan, ship_min_size=0)
        if packed is not None:
            ch.apply = lambda p, s, k: (jax.tree_util.tree_map_with_path(
                lambda path, leaf: qat.ship_quant(leaf, bits, packed=packed)
                if qat._is_weight(path) and leaf.ndim >= 2 else leaf,
                p), s)
        grads_of = make_grads_fn(cfg, ch)
        batch = {"tokens": jax.random.randint(KEY, (2, 16), 0,
                                              cfg.vocab_size),
                 "targets": jax.random.randint(KEY, (2, 16), 0,
                                               cfg.vocab_size)}
        loss, grads = jax.jit(grads_of)(params, batch, KEY)
        return float(loss), grads

    def test_ship_int4_packed_equals_unpacked_under_scan(self):
        """QAT ship at 4-bit over lax.scan-stacked layers: the nibble-packed
        code plane must reproduce the unpacked decode path exactly — loss
        and master gradients bit-identical (ref backend)."""
        l_packed, g_packed = self._ship_loss(4, True, packed=True)
        l_plain, g_plain = self._ship_loss(4, True, packed=False)
        assert l_packed == l_plain
        for a, b in zip(jax.tree.leaves(g_packed), jax.tree.leaves(g_plain)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ship_scan_matches_unrolled(self):
        l_scan, _ = self._ship_loss(8, True)
        l_unroll, _ = self._ship_loss(8, False)
        assert np.isclose(l_scan, l_unroll, rtol=1e-5)


# ---------------------------------------------------------------------------
# quantize epilogue
# ---------------------------------------------------------------------------

class TestEpilogue:
    def test_ref_equals_unfused_ds_pair(self):
        _, qt = _wq((32, 24))
        x = jax.random.normal(KEY, (6, 32)).astype(jnp.bfloat16)
        got = quant_dense_q(x, qt, KEY, bits=8, backend="ref")
        y = quant_dense(x, qt, backend="ref").astype(x.dtype)
        from repro.quant.qtensor import ds_pair_jnp
        want = ds_pair_jnp(y, QScheme.int_symmetric(8, scaling="row",
                                                    rounding="ds"), KEY)
        np.testing.assert_array_equal(np.asarray(got.codes),
                                      np.asarray(want.codes))
        np.testing.assert_array_equal(np.asarray(got.codes2),
                                      np.asarray(want.codes2))
        np.testing.assert_array_equal(np.asarray(got.scale),
                                      np.asarray(want.scale))

    def test_fused_bit_exact_vs_unfused_kernel_path(self):
        """Same rand bits → the fused epilogue emits exactly the codes the
        unfused (qmm → astype → ds row-quantize) pipeline would."""
        _, qt = _wq((64, 40))
        x = jax.random.normal(KEY, (9, 64)).astype(jnp.bfloat16)
        fused = quant_dense_q(x, qt, KEY, bits=8, backend="pallas")
        rand = jax.random.bits(KEY, (9, 40), jnp.uint32)
        yb = registry.get("pallas").quant_dense(x, qt).astype(x.dtype) \
            .astype(jnp.float32)
        absmax = jnp.max(jnp.abs(yb), axis=1, keepdims=True)
        sc = jnp.where(absmax == 0, 1.0, absmax / 127)
        t = yb / sc
        base = jnp.floor(t)
        u1 = (rand >> 16).astype(jnp.float32) / (1 << 16)
        u2 = (rand & 0xFFFF).astype(jnp.float32) / (1 << 16)
        c1 = jnp.clip(base + (u1 < (t - base)), -127, 127).astype(jnp.int8)
        c2 = jnp.clip(base + (u2 < (t - base)), -127, 127).astype(jnp.int8)
        np.testing.assert_array_equal(np.asarray(fused.codes),
                                      np.asarray(c1))
        np.testing.assert_array_equal(np.asarray(fused.codes2),
                                      np.asarray(c2))
        np.testing.assert_allclose(np.asarray(fused.scale),
                                   np.asarray(sc), rtol=1e-6)

    def test_ds_project_unbiased(self):
        """E[decode(Q₁)] ≈ y — the epilogue pair stays an unbiased
        estimator of the activation it replaces."""
        _, qt = _wq((16, 8))
        x = jnp.ones((4, 16), jnp.bfloat16) * 0.3
        y = quant_dense(x, qt, backend="ref").astype(jnp.float32)
        acc = jnp.zeros_like(y)
        n = 200
        for i in range(n):
            pair = act_quant.ds_project(x, qt, jax.random.fold_in(KEY, i),
                                        bits=4, backend="ref")
            acc = acc + pair.decode()
        err = float(jnp.abs(acc / n - y).max())
        width = float(jnp.abs(y).max()) / 7                # one 4-bit step
        assert err < 0.25 * width, (err, width)

    def test_lead_dims_roundtrip(self):
        _, qt = _wq((32, 24))
        x = jax.random.normal(KEY, (2, 3, 32)).astype(jnp.bfloat16)
        for be in ("ref", "pallas"):
            out = quant_dense_q(x, qt, KEY, bits=8, backend=be)
            assert out.codes.shape == (2, 3, 24)
            assert out.scale.shape == (2, 3, 1)
            assert out.is_ds


# ---------------------------------------------------------------------------
# removed splice formats + interpret-mode selection
# ---------------------------------------------------------------------------

class TestRemovedSplices:
    def test_dense_raises_on_wq_splice(self):
        with pytest.raises(ValueError, match="QTensor"):
            L.dense({"w_q": jnp.zeros((4, 4), jnp.int8),
                     "w_scale": jnp.ones((1, 4))},
                    jnp.zeros((2, 4), jnp.bfloat16))

    def test_dense_raises_on_levels_splice(self):
        with pytest.raises(ValueError, match="QTensor"):
            L.dense({"w_lvl_codes": jnp.zeros((4, 4), jnp.int8),
                     "w_levels": jnp.zeros((16,))},
                    jnp.zeros((2, 4), jnp.bfloat16))

    def test_moe_raises_on_splice(self):
        with pytest.raises(ValueError, match="QTensor"):
            moe_mod._qeinsum("ecd,edf->ecf",
                             jnp.zeros((1, 2, 4), jnp.bfloat16),
                             {"w_q": jnp.zeros((1, 4, 4), jnp.int8)})
        with pytest.raises(ValueError, match="QTensor"):
            moe_mod._gq_einsum("gecd,edf->gecf",
                               jnp.zeros((1, 1, 2, 4), jnp.bfloat16),
                               {"w_lvl_codes": jnp.zeros((1, 4, 4),
                                                         jnp.int16)})

    def test_migrate_spliced_weights_roundtrip(self):
        """The migration shim the error message points at: splice dicts →
        QTensor leaves with identical decode values, consumable by dense."""
        w = jax.random.normal(KEY, (16, 8)) * 0.05
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127
        codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        lv_qt = qat._optimal_quantize_weight(
            jax.random.normal(jax.random.fold_in(KEY, 1), (4, 16, 8)) * 0.1,
            4)
        spliced = {
            "mlp": {"up": {"w_q": codes,
                           "w_scale": scale.astype(jnp.float32)}},
            # old stacked level splice: dim-less table next to stacked codes
            "stack": {"w_lvl_codes": lv_qt.codes,
                      "w_levels": lv_qt.levels[0]},
        }
        with pytest.raises(ValueError, match="migrate_spliced_weights"):
            L.dense(spliced["mlp"]["up"], jnp.zeros((2, 16), jnp.bfloat16))
        fixed = qat.migrate_spliced_weights(spliced)
        up = fixed["mlp"]["up"]["w"]
        assert isinstance(up, QTensor)
        np.testing.assert_array_equal(
            np.asarray(up.decode(jnp.bfloat16)),
            np.asarray(codes.astype(jnp.bfloat16)
                       * scale.astype(jnp.bfloat16)))
        stack = fixed["stack"]["w"]
        assert stack.levels.shape == (4, lv_qt.levels.shape[-1])
        np.testing.assert_array_equal(np.asarray(stack.decode()),
                                      np.asarray(lv_qt.decode()))
        x = jax.random.normal(KEY, (2, 16)).astype(jnp.bfloat16)
        y = L.dense(fixed["mlp"]["up"], x)
        want = jnp.einsum("...i,io->...o", x, up.decode(jnp.bfloat16),
                          preferred_element_type=jnp.float32).astype(x.dtype)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


class TestInterpretSelection:
    def test_env_flag_forces_interpret(self, monkeypatch):
        monkeypatch.setenv(registry.INTERPRET_ENV, "1")
        assert registry.interpret_default() is True
        monkeypatch.setenv(registry.INTERPRET_ENV, "0")
        assert registry.interpret_default() is False

    def test_default_tracks_backend(self, monkeypatch):
        monkeypatch.delenv(registry.INTERPRET_ENV, raising=False)
        want = jax.default_backend() != "tpu"
        assert registry.interpret_default() is want

    def test_no_kernel_entrypoint_defaults_interpret_true(self):
        """The satellite fix: no Pallas entry point may hardcode
        ``interpret=True`` as its default again."""
        import inspect
        from repro.kernels import paged_attn, qmm, quant_adamw, ssd, stoch_quant
        for mod in (qmm, stoch_quant, quant_adamw, paged_attn, ssd):
            for name, fn in vars(mod).items():
                if not callable(fn) or not hasattr(fn, "__wrapped__"):
                    continue
                sig = inspect.signature(fn.__wrapped__)
                p = sig.parameters.get("interpret")
                if p is not None:
                    assert p.default is None, f"{mod.__name__}.{name}"


# ---------------------------------------------------------------------------
# chunked attention odd-length fix (satellite)
# ---------------------------------------------------------------------------

class TestChunkedAttentionOddLengths:
    @pytest.mark.parametrize("s,cq,window", [(37, 8, 0), (100, 32, 16),
                                             (17, 16, 8)])
    def test_odd_tail_is_padded_not_collapsed(self, s, cq, window):
        """Lengths not divisible by q_chunk must keep query chunking (the
        old fallback silently went O(S²)) and match the single-block
        softmax exactly."""
        b, h, hkv, d = 2, 4, 2, 16
        q = jax.random.normal(KEY, (b, s, h, d)).astype(jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(KEY, 1),
                              (b, s, hkv, d)).astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(KEY, 2),
                              (b, s, hkv, d)).astype(jnp.bfloat16)
        spec = A.AttnSpec(h, hkv, d, q_chunk=cq, window=window)
        one = A.AttnSpec(h, hkv, d, q_chunk=s, window=window)
        out = A.chunked_attention(q, k, v, spec)
        ref = A.chunked_attention(q, k, v, one)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_odd_length_loss_runs_chunked(self):
        """A model loss at an odd sequence length exercises the padded-tail
        path end to end (was: silent single-block fallback)."""
        cfg = configs.get_reduced("musicgen-medium")
        cfg = dataclasses.replace(cfg, q_chunk=8)
        params = T.init_params(KEY, cfg)
        toks = jax.random.randint(KEY, (2, 13), 0, cfg.vocab_size)
        loss = T.loss_fn(params, toks, toks, cfg)
        assert np.isfinite(float(loss))
