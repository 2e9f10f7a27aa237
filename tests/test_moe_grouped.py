"""The routed experts' ONE compute path (models/moe.routed_experts: pairs
ordered by expert, grouped matmuls over the rows that were routed) against
the all-experts-masked product it replaced, at float32, for both routers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    moe_mlp,
    route,
    routed_experts,
    shared_expert,
)

MIXTRAL = MoEConfig(n_experts=8, top_k=2, hidden_size=32, intermediate_size=48)
SIGMOID = MoEConfig(n_experts=8, top_k=3, hidden_size=32, intermediate_size=48,
                    router="sigmoid", scale=1.8, shared=True)
CONFIGS = {"mixtral": MIXTRAL, "sigmoid": SIGMOID}


def _params(config, seed=0):
    return init_moe_params(config, jax.random.PRNGKey(seed), scale=0.2)


def _masked(params, x, weights, selected, config):
    """Every token through every expert, combined by a mask: the product
    models/moe.py held before (a [N, E, f] array and E times the FLOPs)."""
    onehot = jax.nn.one_hot(selected, config.n_experts, dtype=jnp.float32)
    combine = jnp.einsum("nk,nke->ne", weights, onehot)
    gate = jax.nn.silu(jnp.einsum("nh,ehf->nef", x, params["w_gate"]))
    up = jnp.einsum("nh,ehf->nef", x, params["w_up"])
    out = jnp.einsum("nef,efh->neh", gate * up, params["w_down"])
    return jnp.einsum("neh,ne->nh", out, combine)


def _x(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 32), jnp.float32)


@pytest.mark.parametrize("family", sorted(CONFIGS))
@pytest.mark.parametrize("tokens", [1, 5, 24])
def test_grouped_equals_all_experts_masked(family, tokens):
    config = CONFIGS[family]
    params, x = _params(config), _x(tokens)
    weights, selected = route(params, x, config)
    out, rows = routed_experts(params, x, weights, selected, config.n_experts)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_masked(params, x, weights, selected, config)),
        rtol=1e-5, atol=1e-6)
    assert int(rows.sum()) == tokens * config.top_k
    np.testing.assert_array_equal(
        np.asarray(rows), np.bincount(np.asarray(selected).ravel(), minlength=8))


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_empty_experts_and_one_expert_taking_every_token(family):
    """Routers whose weights send every token to the same experts: the
    others get no row, multiply nothing and change nothing."""
    config = CONFIGS[family]
    params = dict(_params(config))
    router = np.zeros((32, 8), np.float32)
    router[:, 5] = 1.0  # expert 5 scores highest wherever sum(x) > 0
    params["router"] = jnp.asarray(router)
    x = jnp.abs(_x(12)) + 0.1
    weights, selected = route(params, x, config)
    out, rows = routed_experts(params, x, weights, selected, config.n_experts)
    rows = np.asarray(rows)
    assert rows[5] == 12 and (rows == 0).sum() >= 8 - config.top_k
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_masked(params, x, weights, selected, config)),
        rtol=1e-5, atol=1e-6)
    # an expert no token reached plays no part: other weights in it change
    # nothing (finite ones: the CPU's lowering multiplies masked zeros)
    empty = int(np.nonzero(rows == 0)[0][0])
    other = dict(params, w_down=params["w_down"].at[empty].set(1e3))
    again, _ = routed_experts(other, x, weights, selected, config.n_experts)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_rows_that_are_not_tokens_reach_no_expert(family):
    config = CONFIGS[family]
    params, x = _params(config), _x(10)
    valid = jnp.asarray([True] * 6 + [False] * 4)
    out, rows = moe_mlp(params, x, config, valid, with_rows=True)
    alone, rows_alone = moe_mlp(params, x[:6], config, with_rows=True)
    assert int(rows.sum()) == 6 * config.top_k
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_alone))
    np.testing.assert_allclose(np.asarray(out[:6]), np.asarray(alone),
                               rtol=1e-5, atol=1e-6)


def test_sigmoid_router_bias_chooses_and_does_not_weigh():
    """b moves the choice; the weights are the chosen scores renormalised
    and scaled, whatever b is."""
    params = dict(_params(SIGMOID))
    x = _x(7)
    w0, s0 = route(params, x, SIGMOID)
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 1.8, rtol=1e-6)
    bias = np.zeros(8, np.float32)
    bias[2] = 10.0  # expert 2 is now always chosen
    params["router_bias"] = jnp.asarray(bias)
    w1, s1 = route(params, x, SIGMOID)
    assert (np.asarray(s1) == 2).any(axis=-1).all()
    scores = np.asarray(jax.nn.sigmoid(x @ params["router"]))
    chosen = np.take_along_axis(scores, np.asarray(s1), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w1), 1.8 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    assert not np.array_equal(np.asarray(s0), np.asarray(s1))


def test_the_shared_expert_takes_every_token_beside_the_routed():
    params, x = _params(SIGMOID), _x(9)
    weights, selected = route(params, x, SIGMOID)
    routed, _ = routed_experts(params, x, weights, selected, 8)
    np.testing.assert_allclose(
        np.asarray(moe_mlp(params, x, SIGMOID)),
        np.asarray(routed + shared_expert(params, x)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_no_array_over_tokens_experts_and_width(family):
    """Nothing of [tokens, experts, width] (or its transposes) exists in
    the traced computation, and its matmuls' FLOPs follow the pairs."""
    config = CONFIGS[family]
    params, x = _params(config), _x(16)
    jaxpr = jax.make_jaxpr(lambda p, x: moe_mlp(p, x, config))(params, x)
    big = {16, 8, 48}
    shapes = [tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert not [s for s in shapes if len(s) == 3 and set(s) == big], shapes
    grouped = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name.startswith("ragged_dot")]
    assert len(grouped) == 3
    assert all(e.invars[0].aval.shape[0] == 16 * config.top_k for e in grouped)
