"""Sequence-parallel ring attention + expert-parallel MoE on the 8-device
CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kserve_tpu.models.moe import MoEConfig, init_moe_params, moe_mlp, moe_param_pspecs
from kserve_tpu.ops.attention import causal_prefill_attention
from kserve_tpu.parallel.ring_attention import ring_attention


class TestRingAttention:
    @pytest.mark.parametrize("ring", [2, 4, 8])
    def test_matches_full_attention(self, ring):
        B, T, nq, nkv, d = 2, 32, 4, 2, 16
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, T, nq, d), jnp.float32)
        k = jnp.asarray(rng.randn(B, T, nkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(B, T, nkv, d), jnp.float32)
        valid = jnp.asarray([T, T - 5], jnp.int32)
        ref = causal_prefill_attention(q, k, v, valid)

        mesh = Mesh(np.asarray(jax.devices()[:ring]), ("seq",))
        seq_sharded = P(None, "seq", None, None)
        fn = jax.shard_map(
            lambda q, k, v, vl: ring_attention(q, k, v, vl, "seq"),
            mesh=mesh,
            in_specs=(seq_sharded, seq_sharded, seq_sharded, P(None)),
            out_specs=seq_sharded,
        )
        got = fn(q, k, v, valid)
        # padded rows (beyond valid) don't matter; compare valid positions
        np.testing.assert_allclose(
            np.asarray(got)[0], np.asarray(ref)[0], rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(got)[1, : T - 5], np.asarray(ref)[1, : T - 5], rtol=2e-5, atol=2e-5
        )


class TestMoE:
    def test_topk_routing_shapes_and_determinism(self):
        config = MoEConfig(n_experts=4, top_k=2, hidden_size=16, intermediate_size=32)
        params = init_moe_params(config, jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(2, 6, 16), jnp.float32)
        out = moe_mlp(params, x, config)
        assert out.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(moe_mlp(params, x, config)), rtol=1e-6
        )

    def test_single_expert_equals_dense(self):
        """top_k == n_experts == 1 reduces to a plain SwiGLU MLP."""
        config = MoEConfig(n_experts=1, top_k=1, hidden_size=16, intermediate_size=32)
        params = init_moe_params(config, jax.random.PRNGKey(1))
        x = jnp.asarray(np.random.RandomState(1).randn(2, 4, 16), jnp.float32)
        out = moe_mlp(params, x, config)
        gate = jax.nn.silu(x @ params["w_gate"][0])
        ref = (gate * (x @ params["w_up"][0])) @ params["w_down"][0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_expert_parallel_sharding(self):
        """EP over the model axis: sharded == replicated result."""
        config = MoEConfig(n_experts=8, top_k=2, hidden_size=16, intermediate_size=32)
        params = init_moe_params(config, jax.random.PRNGKey(2))
        x = jnp.asarray(np.random.RandomState(2).randn(2, 4, 16), jnp.float32)
        ref = moe_mlp(params, x, config)

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
        specs = moe_param_pspecs()
        sharded = {
            name: jax.device_put(arr, NamedSharding(mesh, specs[name]))
            for name, arr in params.items()
        }
        got = jax.jit(lambda p, x: moe_mlp(p, x, config))(sharded, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
