"""Multi-device lane: these tests need ≥ 8 devices and run in CI as their own
job under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (locally:
``XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src
python -m pytest tests/test_multidevice.py``).

What they pin:
  * QTensor code planes survive ``shard_map`` — codes stay sharded, scales
    replicate, decode inside the mapped region equals global decode
    (dense int8, nibble-packed int4, and bit-plane uint32 layouts alike).
  * ``gradcomp.make_compressed_psum`` produces the exact mean of the
    per-member quantized terms across a real 8-way axis.
  * paged serve decode is batch-shardable: the paged-attention op under an
    8-way data sharding matches the single-device result, and the
    continuous-batching scheduler runs to completion (leak-free, output-
    identical) in a multi-device process.
  * the multi-replica driver: prefix-caching engine replicas pinned to
    distinct devices behind one shared queue finish a shared-prefix trace
    with balanced dispatch and leak-free pools — and with self-speculative
    decoding on under prefix-aware dispatch, token-identical to the
    non-speculative replica set.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import quant
from repro.precision import gradcomp
from repro.quant import QScheme

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices — run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

KEY = jax.random.PRNGKey(0)


def _mesh(axis="data"):
    return Mesh(np.array(jax.devices()[:8]), (axis,))


@needs8
class TestQTensorSharding:
    def test_code_plane_sharding_survives_shard_map(self):
        """Shard a QTensor's codes 8-way, map a decode over the shards: the
        output keeps the sharding and equals the global decode."""
        mesh = _mesh()
        x = jax.random.normal(KEY, (64, 16))
        qt = quant.encode(x, QScheme.int_symmetric(8, scaling="row"), KEY)
        qt_spec = jax.tree.unflatten(
            jax.tree.structure(qt), [P("data", None), P("data", None)])
        qt_sharded = jax.device_put(
            qt, jax.tree.map(lambda s: NamedSharding(mesh, s), qt_spec,
                             is_leaf=lambda s: isinstance(s, P)))
        shards = {s.device for s in qt_sharded.codes.addressable_shards}
        assert len(shards) == 8

        f = jax.shard_map(lambda q: q.decode(), mesh=mesh, in_specs=(qt_spec,),
                      out_specs=P("data", None), check_vma=False)
        out = jax.jit(f)(qt_sharded)
        assert out.sharding.spec == P("data", None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(qt.decode()),
                                   rtol=1e-6)

    def test_ds_pair_codes_shard_together(self):
        """Both double-sampling planes (codes + codes2) shard identically —
        the §2.2 pair is one storage object, not two tensors."""
        mesh = _mesh()
        x = jax.random.normal(KEY, (64, 16))
        qt = quant.ds_pair(x, QScheme.zipml(7, rounding="ds"), KEY)
        spec = jax.tree.unflatten(
            jax.tree.structure(qt), [P("data", None), P(), P("data", None)])
        qs = jax.device_put(qt, jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda s: isinstance(s, P)))
        f = jax.shard_map(lambda q: (q.decode() + q.decode2()) / 2, mesh=mesh,
                      in_specs=(spec,), out_specs=P("data", None),
                      check_vma=False)
        out = jax.jit(f)(qs)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray((qt.decode() + qt.decode2()) / 2), rtol=1e-6)


@needs8
class TestBitplaneSharding:
    def test_bitplane_code_planes_shard_over_rows(self):
        """A bit-plane weight shards 8-way over its contraction (row) axis —
        the tiny plane axis and the packed word axis stay whole. Decode
        inside shard_map equals global decode, and quant_dense over the
        sharded QTensor matches the f32 decode path on both backends."""
        from repro.quant import quant_dense

        mesh = _mesh()
        w = jax.random.normal(KEY, (64, 256)) * 0.1
        qt = quant.encode(w, QScheme.bitplane(4))
        assert qt.codes.shape == (5, 64, 8) and qt.codes.dtype == jnp.uint32
        spec = jax.tree.unflatten(
            jax.tree.structure(qt), [P(None, "data", None), P()])
        qs = jax.device_put(qt, jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda s: isinstance(s, P)))
        assert len({s.device for s in qs.codes.addressable_shards}) == 8

        f = jax.shard_map(lambda q: q.decode(), mesh=mesh, in_specs=(spec,),
                      out_specs=P("data", None), check_vma=False)
        out = jax.jit(f)(qs)
        assert out.sharding.spec in (P("data"), P("data", None))
        np.testing.assert_allclose(np.asarray(out), np.asarray(qt.decode()),
                                   rtol=1e-6)

        x = jax.random.normal(KEY, (16, 64)).astype(jnp.bfloat16)
        want = jnp.einsum("mk,kn->mn", x.astype(jnp.float32), qt.decode())
        with mesh:
            for be in ("ref", "pallas"):
                got = jax.jit(
                    lambda x, q: quant_dense(x, q, backend=be))(x, qs)
                # ref decodes through bf16 (one-epsilon per k-term); the
                # pallas kernel reconstructs in f32 and matches tightly
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want),
                    atol=2e-2 if be == "ref" else 1e-4,
                    rtol=5e-3 if be == "ref" else 1e-5)


@needs8
class TestPackedQuantDense:
    def test_packed_int4_code_plane_shards_and_matmuls(self):
        """A nibble-packed int4 weight plane shards 8-way over its (even)
        packed out-channel dim; quant_dense over the sharded QTensor equals
        the single-device f32 decode path (both backends)."""
        from repro.quant import quant_dense

        mesh = _mesh("model")
        w = jax.random.normal(KEY, (32, 128)) * 0.1
        qt = quant.encode(w, QScheme.int_symmetric(
            4, scaling="channel", channel_axis=-2, rounding="nearest",
            packed=True))
        assert qt.codes.dtype == jnp.uint8 and qt.codes.shape == (32, 64)
        spec = jax.tree.unflatten(
            jax.tree.structure(qt), [P(None, "model"), P(None, "model")])
        qs = jax.device_put(qt, jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda s: isinstance(s, P)))
        assert len({s.device for s in qs.codes.addressable_shards}) == 8
        x = jax.random.normal(KEY, (16, 32)).astype(jnp.bfloat16)
        want = jnp.einsum("mk,kn->mn", x.astype(jnp.float32), qt.decode())
        with mesh:
            for be in ("ref", "pallas"):
                got = jax.jit(
                    lambda x, q: quant_dense(x, q, backend=be))(x, qs)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), atol=1e-4,
                    rtol=5e-3 if be == "ref" else 1e-5)


@needs8
class TestCompressedPsum:
    def test_mean_of_quantized_members_8way(self):
        """The C3 compressed all-reduce over a real 8-member axis equals the
        exact mean of each member's dequantized quantization (and stays
        within one quantization step of the true mean)."""
        mesh = _mesh("pod")
        n_dev, n = 8, 64
        rng = np.random.default_rng(0)
        per_member = jnp.asarray(rng.normal(0, 1, (n_dev, n)), jnp.float32)
        psum = gradcomp.make_compressed_psum("pod", 8)

        def member(g_slice, key):
            # each mesh member quantizes its own gradient with its own key
            idx = jax.lax.axis_index("pod")
            return psum({"g": g_slice[0]}, jax.random.fold_in(key, idx))

        f = jax.shard_map(member, mesh=mesh, in_specs=(P("pod", None), P()),
                      out_specs=P(), check_vma=False)
        out = np.asarray(jax.jit(f)(per_member, KEY)["g"])

        # oracle: quantize each member with its folded key, average
        want = np.mean([np.asarray(gradcomp.decompress_tree(
            gradcomp.compress_tree({"g": per_member[i]}, 8,
                                   jax.random.fold_in(KEY, i))[0])["g"])
            for i in range(n_dev)], axis=0)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
        step = float(jnp.max(jnp.abs(per_member))) / 127
        true_mean = np.asarray(per_member.mean(0))
        assert np.max(np.abs(out - true_mean)) <= step + 1e-5


@needs8
class TestShardedServe:
    def _op_args(self, kv_bits):
        rng = np.random.default_rng(1)
        b, h, g, d, page, maxp, n_pages = 8, 4, 2, 16, 8, 3, 24
        q = jnp.asarray(rng.normal(0, 1, (b, h, d)), jnp.float32)
        lens = jnp.asarray(rng.integers(1, page * maxp, (b,)), jnp.int32)
        bt = jnp.asarray(rng.integers(1, n_pages, (b, maxp)), jnp.int32)
        kv = rng.normal(0, 1, (2, n_pages, page, g, d)).astype(np.float32)
        if kv_bits:
            from repro.serve.pages import kv_scheme
            qk = quant.encode(jnp.asarray(kv[0]), kv_scheme(kv_bits))
            qv = quant.encode(jnp.asarray(kv[1]), kv_scheme(kv_bits))
            return q, qk.codes, qv.codes, qk.scale, qv.scale, bt, lens
        return (q, jnp.asarray(kv[0], jnp.bfloat16),
                jnp.asarray(kv[1], jnp.bfloat16), None, None, bt, lens)

    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_paged_attention_batch_sharded(self, kv_bits):
        """The serve decode hot path under an 8-way batch sharding (pool
        replicated, per-sequence state sharded) matches single-device."""
        from repro.kernels import registry

        args = self._op_args(kv_bits)
        q, kp, vp, ks, vs, bt, lens = args
        want = registry.get("ref").paged_attention(
            q, kp, vp, ks, vs, bt, lens, softmax_scale=q.shape[-1] ** -0.5)

        mesh = _mesh()
        dp = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())

        def put(x, s):
            return None if x is None else jax.device_put(x, s)

        out = jax.jit(lambda *a: registry.get("ref").paged_attention(
            *a, softmax_scale=q.shape[-1] ** -0.5))(
            put(q, NamedSharding(mesh, P("data", None, None))),
            put(kp, rep), put(vp, rep), put(ks, rep), put(vs, rep),
            put(bt, NamedSharding(mesh, P("data", None))), put(lens, dp))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-3)

    def test_scheduler_runs_on_multidevice_host(self):
        """End-to-end continuous batching in an 8-device process: every
        request finishes, no pages leak, tokens match the device_count=1
        greedy semantics (determinism is device-layout independent)."""
        from repro import configs
        from repro.models import transformer as T
        from repro.quant import PrecisionPlan
        from repro.serve import Request, ServeEngine

        cfg = configs.get_reduced("qwen2.5-14b")
        params = T.init_params(KEY, cfg)
        rng = np.random.default_rng(2)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(3, 14))),
                        max_new_tokens=4) for i in range(8)]
        eng = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                          max_slots=4, page_size=8, max_seq_len=32)
        out = eng.run(reqs)
        assert sorted(out) == list(range(8))
        eng.allocator.check_leaks(0)
        solo = ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                           max_slots=1, page_size=8, max_seq_len=32)
        got = solo.run([reqs[3]])
        np.testing.assert_array_equal(got[3].tokens, out[3].tokens)

    def test_replica_set_prefix_sharing_device_pinned(self):
        """Two prefix-caching engine replicas pinned to distinct host
        devices behind one shared queue: a shared-system-prompt trace
        finishes completely, dispatch is balanced, at least one replica
        serves prefix hits, and both pools drain leak-free."""
        from benchmarks.bench_serve_engine import make_shared_trace
        from repro import configs
        from repro.launch.serve import ReplicaSet
        from repro.models import transformer as T
        from repro.quant import PrecisionPlan
        from repro.serve import ServeEngine

        cfg = configs.get_reduced("qwen2.5-14b")
        params = T.init_params(KEY, cfg)
        rs = ReplicaSet(
            lambda i: ServeEngine(params, cfg, plan=PrecisionPlan(kv_bits=8),
                                  max_slots=2, page_size=4, max_seq_len=32,
                                  prefix_cache=True, chunk_pages=2),
            2, devices=jax.devices()[:2])
        n = 12
        out = rs.run(make_shared_trace(n, cfg.vocab_size, page_size=4,
                                       sys_pages=2, max_new=4))
        assert sorted(out) == list(range(n))
        assert min(rs.dispatched) >= 2        # least-loaded spreads the work
        assert rs.stats_sum("prefix_hits") >= 1
        for eng in rs.engines:
            eng.release_prefix_cache()
            eng.allocator.check_leaks(0)

    def test_replica_set_spec_decode_prefix_dispatch(self):
        """Speculative decoding composes with the multi-replica front-end:
        two device-pinned replicas under **prefix-aware dispatch**, each
        drafting through the int4 bitplane view, finish a shared-prefix
        trace token-identical to a vanilla (non-speculative) replica set,
        with speculative windows on both replicas and leak-free pools."""
        from benchmarks.bench_serve_engine import make_shared_trace
        from repro import configs
        from repro.launch.serve import ReplicaSet
        from repro.models import transformer as T
        from repro.precision.qat import quantize_param_tree
        from repro.quant import PrecisionPlan
        from repro.serve import ServeEngine

        cfg = configs.get_reduced("qwen2.5-14b")
        params = quantize_param_tree(T.init_params(KEY, cfg), bits=8,
                                     layout="bitplane")

        def mk_set(spec):
            kw = dict(spec_decode=3, draft_bits=4) if spec else {}
            return ReplicaSet(
                lambda i: ServeEngine(params, cfg,
                                      plan=PrecisionPlan(kv_bits=8),
                                      max_slots=2, page_size=4,
                                      max_seq_len=32, prefix_cache=True,
                                      chunk_pages=2, **kw),
                2, devices=jax.devices()[:2], dispatch="prefix")

        n = 12
        trace = lambda: make_shared_trace(n, cfg.vocab_size, page_size=4,
                                          sys_pages=2, max_new=4)
        want = mk_set(spec=False).run(trace())
        rs = mk_set(spec=True)
        out = rs.run(trace())
        assert sorted(out) == list(range(n))
        assert rs.stats_sum("spec_steps") >= 2
        assert min(e.stats["spec_steps"] for e in rs.engines) >= 1
        for rid in want:
            np.testing.assert_array_equal(out[rid].tokens, want[rid].tokens)
        for eng in rs.engines:
            eng.release_prefix_cache()
            eng.allocator.check_leaks(0)


# one process with four forced host devices, whatever this process has
_PLACEMENT = """
import jax
from repro.launch.serve import serve_engine

devices = jax.devices()
assert len(devices) == 4, devices
rs, results = serve_engine(
    "gemma-2b", n_requests=8, max_new=4, kv_bits=8, weight_bits=8,
    page_size=8, max_slots=2, replicas=4, devices=devices)
assert sorted(results) == list(range(8)), sorted(results)
for i, eng in enumerate(rs.engines):
    want = {rs.device_of(i)}
    for leaf in jax.tree.leaves((eng.params, eng.pool)):
        assert leaf.devices() == want, (i, leaf.devices(), want)
    assert rs.dispatched[i] >= 1, rs.dispatched
assert len({rs.device_of(i) for i in range(4)}) == 4
assert rs.stats["deaths"] == rs.stats["step_failures"] == 0, rs.stats
print("placement ok")
"""


def test_replicas_hold_weights_and_pool_on_their_own_device():
    """Four replicas on four forced host devices: each replica's weights
    and KV pool sit on its own device (not device 0's copy), and every
    replica serves."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _PLACEMENT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "placement ok" in out.stdout
