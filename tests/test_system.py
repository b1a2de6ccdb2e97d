"""System-behaviour tests: checkpoint/restart/elastic, fault injection,
data-pipeline determinism, gradient compression, double-sampled activations,
quantized optimizer moments, end-to-end driver runs."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import Cursor, QuantizedSampleStore, TokenStream, TokenStreamConfig
from repro.precision import act_quant, gradcomp

KEY = jax.random.PRNGKey(0)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones(5)}}
        mgr.save(7, tree, extra={"cursor": {"step": 7, "epoch": 0}}, blocking=True)
        got, manifest = mgr.restore(jax.eval_shape(lambda: tree))
        assert manifest["step"] == 7
        np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))

    def test_keep_and_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"x": jnp.zeros(3)}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree, blocking=True)
        assert mgr.all_steps() == [3, 4]

    def test_incomplete_checkpoint_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.ones(2)}, blocking=True)
        # simulate a crash mid-save: directory without the commit marker
        os.makedirs(tmp_path / "step_000000009")
        assert mgr.latest_step() == 1

    def test_elastic_restore_resharded(self, tmp_path):
        """Checkpoint written unsharded restores onto a different mesh."""
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.arange(64.0).reshape(8, 8)}
        mgr.save(3, tree, blocking=True)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        shardings = {"w": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data", None))}
        got, _ = mgr.restore(jax.eval_shape(lambda: tree), shardings=shardings)
        np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
        assert got["w"].sharding.is_equivalent_to(shardings["w"], 2)

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"w": jnp.zeros((4, 4))}, blocking=True)
        with pytest.raises(ValueError):
            mgr.restore(jax.eval_shape(lambda: {"w": jnp.zeros((2, 2))}))


@pytest.mark.slow
class TestTrainSupervisor:
    def test_fault_injection_recovers(self, tmp_path):
        """Injected fault at step 12 → restore from step-10 checkpoint →
        training completes all steps with the same final cursor."""
        from repro.launch.train import train
        _, losses = train("musicgen-medium", steps=16, batch=2, seq=16,
                          ckpt_dir=str(tmp_path), ckpt_every=10, fail_at=12,
                          log_every=100)
        # 16 real steps recorded after replaying 12→10
        assert len(losses) >= 16
        assert np.isfinite(losses).all()

    def test_grad_compression_trains(self):
        from repro.launch.train import train
        _, losses = train("musicgen-medium", steps=12, batch=2, seq=16,
                          grad_bits=8, log_every=100)
        assert losses[-1] < losses[0]

    def test_quantized_moments_train(self):
        from repro.launch.train import train
        _, losses = train("musicgen-medium", steps=12, batch=2, seq=16,
                          moment_bits=8, log_every=100)
        assert losses[-1] < losses[0]

    def test_qat_trains(self):
        from repro.launch.train import train
        _, losses = train("musicgen-medium", steps=12, batch=2, seq=16,
                          weight_bits=8, log_every=100)
        assert losses[-1] < losses[0]


class TestDataPipeline:
    def test_deterministic_and_resumable(self):
        cfg = TokenStreamConfig(vocab_size=100, seq_len=16, global_batch=4)
        s1 = TokenStream(cfg)
        batches = [s1.next_batch() for _ in range(5)]
        s2 = TokenStream(cfg)
        s2.skip_to(Cursor(step=3))
        np.testing.assert_array_equal(s2.next_batch()["tokens"],
                                      batches[3]["tokens"])

    def test_host_sharding_disjoint(self):
        full = TokenStream(TokenStreamConfig(100, 16, 4, n_hosts=1, host_id=0))
        h0 = TokenStream(TokenStreamConfig(100, 16, 4, n_hosts=2, host_id=0))
        h1 = TokenStream(TokenStreamConfig(100, 16, 4, n_hosts=2, host_id=1))
        b0, b1 = h0.next_batch(), h1.next_batch()
        assert b0["tokens"].shape == (2, 16)
        assert not np.array_equal(b0["tokens"], b1["tokens"])

    def test_targets_shifted(self):
        s = TokenStream(TokenStreamConfig(100, 16, 2))
        b = s.next_batch()
        np.testing.assert_array_equal(b["targets"][:, :-1], b["tokens"][:, 1:])

    def test_quantized_store_bytes(self):
        rng = np.random.default_rng(0)
        a = rng.normal(0, 1, (100, 64))
        store = QuantizedSampleStore.build(a, rng.normal(size=100), bits=4)
        assert store.bytes_per_sample() < 64 * 4  # < fp32
        aa, bb = store.draw(0, 8)
        assert aa.shape == (8, 64) and bb.shape == (8,)
        # dequantized values stay within one level width of the column scale
        width = store.scale / store.s
        assert (np.abs(np.asarray(aa)) <= store.scale + width + 1e-6).all()


class TestGradCompression:
    def test_roundtrip_error_bounded(self):
        g = {"a": jax.random.normal(KEY, (64,)), "b": jax.random.normal(KEY, (8, 8))}
        comp, err = gradcomp.compress_tree(g, 8, KEY)
        deq = gradcomp.decompress_tree(comp)
        for k in g:
            step = float(jnp.max(jnp.abs(g[k]))) / 127
            assert float(jnp.max(jnp.abs(deq[k] - g[k]))) <= step + 1e-6

    def test_unbiased(self):
        g = {"a": jax.random.normal(KEY, (32,))}
        keys = jax.random.split(KEY, 4096)
        deqs = jax.vmap(lambda k: gradcomp.decompress_tree(
            gradcomp.compress_tree(g, 4, k)[0])["a"])(keys)
        se = deqs.std(0) / np.sqrt(len(keys)) + 1e-6
        np.testing.assert_array_less(np.abs(deqs.mean(0) - g["a"]), 6 * se + 1e-3)

    def test_error_feedback_telescopes(self):
        """With EF, the *accumulated* applied update converges to the
        accumulated true gradient (residual stays bounded)."""
        g = {"a": jnp.ones((16,)) * 0.01}  # tiny gradient ≪ one quant step of 2 bits
        err = gradcomp.init_error_feedback(g)
        applied = jnp.zeros((16,))
        for i in range(50):
            comp, err = gradcomp.compress_tree(g, 2, jax.random.fold_in(KEY, i),
                                               error=err)
            applied += gradcomp.decompress_tree(comp)["a"]
        true_sum = 0.01 * 50
        np.testing.assert_allclose(np.asarray(applied), true_sum, atol=0.02)

    def test_compression_ratio(self):
        assert gradcomp.compression_ratio(8) == 2.0
        assert gradcomp.compression_ratio(4) == 4.0


class TestActDoubleSampling:
    def test_forward_close(self):
        x = jax.random.normal(KEY, (32, 64))
        w = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 16)) * 0.1
        y = act_quant.ds_dense(x, w, KEY, 8)
        y_ref = x @ w
        rel = float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))
        assert rel < 0.02, rel

    def test_weight_grad_unbiased(self):
        """E[∂W] under double-sampled activations equals the exact ∂W."""
        x = jax.random.normal(KEY, (8, 16))
        w = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 4)) * 0.1

        def loss(w_, key):
            return jnp.sum(act_quant.ds_dense(x, w_, key, 4))

        exact = jax.grad(lambda w_: jnp.sum(x @ w_))(w)
        keys = jax.random.split(KEY, 8192)
        grads = jax.vmap(lambda k: jax.grad(loss)(w, k))(keys)
        se = grads.std(0) / np.sqrt(len(keys)) + 1e-6
        np.testing.assert_array_less(np.abs(grads.mean(0) - exact),
                                     6 * np.asarray(se) + 1e-3)

    def test_mlp_trains(self):
        p = {"gate": {"w": jax.random.normal(KEY, (16, 32)) * 0.25},
             "up": {"w": jax.random.normal(jax.random.fold_in(KEY, 1), (16, 32)) * 0.25},
             "down": {"w": jax.random.normal(jax.random.fold_in(KEY, 2), (32, 16)) * 0.25}}
        x = jax.random.normal(KEY, (64, 16))
        target = jnp.roll(x, 1, axis=1)

        def loss(pp, key):
            return jnp.mean((act_quant.ds_mlp(pp, x, key) - target) ** 2)

        l0 = float(loss(p, KEY))
        for i in range(60):
            g = jax.grad(loss)(p, jax.random.fold_in(KEY, i))
            p = jax.tree.map(lambda a, b: a - 0.3 * b, p, g)
        l1 = float(loss(p, jax.random.fold_in(KEY, 999)))
        assert l1 < l0 * 0.9


class TestElasticController:
    def _fleet(self, n_pods=2):
        from repro.launch.elastic import ElasticController, HOSTS_PER_POD
        c = ElasticController(n_pods, heartbeat_timeout=10, rejoin_patience=2)
        t = 1000.0
        for pod in range(n_pods):
            for h in range(HOSTS_PER_POD):
                c.heartbeat(pod * HOSTS_PER_POD + h, pod, now=t)
        return c, t

    def test_steady_state(self):
        c, t = self._fleet()
        d = c.decide(latest_checkpoint_step=100, now=t + 1)
        assert d.n_pods == 2 and d.mesh_shape == (2, 16, 16)
        assert d.restore_step is None and not d.evicted_pods
        assert len(d.shard_assignment) == 128

    def test_pod_failure_shrinks_and_restores(self):
        c, t = self._fleet()
        c.report_failure(5)  # host 5 (pod 0) dies
        d = c.decide(latest_checkpoint_step=100, now=t + 1)
        assert d.n_pods == 1 and d.mesh_shape == (16, 16)
        assert d.evicted_pods == [0]
        assert d.restore_step == 100
        # surviving hosts get contiguous shard ids
        assert sorted(d.shard_assignment.values()) == list(range(64))

    def test_flap_protection(self):
        from repro.launch.elastic import HOSTS_PER_POD
        c, t = self._fleet()
        c.report_failure(5)
        c.decide(100, now=t + 1)            # pod 0 evicted
        # pod 0 comes back: one healthy round is not enough to re-admit
        for h in range(HOSTS_PER_POD):
            c.heartbeat(h, 0, now=t + 2)
        d = c.decide(100, now=t + 2)
        assert d.n_pods == 1
        d = c.decide(100, now=t + 3)        # second healthy round → admitted
        assert d.n_pods == 2

    def test_heartbeat_timeout_evicts(self):
        c, t = self._fleet()
        d = c.decide(100, now=t + 60)       # all heartbeats stale
        assert d.n_pods == 0 and "halt" in d.reason

    def test_rollback_budget(self):
        from repro.launch.elastic import plan_rollback
        assert plan_rollback([10, 50, 90], failed_at_step=95) == 90
        import pytest as _pytest
        with _pytest.raises(RuntimeError):
            plan_rollback([10], failed_at_step=5000, max_rollback=100)
