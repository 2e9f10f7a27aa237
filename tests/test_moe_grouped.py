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
    device_layout,
    hidden_body,
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


# ---- the device's layout along `hidden` (models/moe.device_layout) ----


def _wide(form, hidden, held=0):
    """Experts of a `hidden` around the grouped matmul's 512-tile, their
    width one such tile."""
    return MoEConfig(n_experts=8, top_k=3, hidden_size=hidden,
                     intermediate_size=512, router="sigmoid", scale=2.5,
                     form=form, held=held)


#: which pairs a call multiplies: every one, a chip's share of the experts,
#: the rows that are tokens, both
LAYOUT_CALLS = {
    "all": (None, False), "share": ((2, 4), False), "valid": (None, True),
    "share_valid": ((2, 4), True)}


@pytest.mark.parametrize("call", sorted(LAYOUT_CALLS))
@pytest.mark.parametrize("form", ["relu2", "gated"])
def test_the_device_layout_computes_what_the_canonical_tensors_compute(
        form, call):
    """hidden 640 = 512 + 128: body and rest give the sums the whole
    tensors give, to float32 rounding (a contraction split at a column is
    a sum of two, an output split at a column two outputs side by side)."""
    share, masked = LAYOUT_CALLS[call]
    config = _wide(form, 640, held=share[1] if share else 0)
    params = _params(config)
    laid = device_layout(params)
    assert laid is not params and sorted(laid) == sorted(params)
    x = jax.random.normal(jax.random.PRNGKey(4), (20, 640), jnp.float32)
    valid = jnp.arange(20) % 5 != 0 if masked else None
    weights, selected = route(params, x, config)
    args = (x, weights, selected, config.n_experts, valid, share, form)
    out, rows = routed_experts(params, *args)
    split, split_rows = jax.jit(routed_experts, static_argnums=(4, 6, 7))(
        laid, *args)
    np.testing.assert_array_equal(np.asarray(split_rows), np.asarray(rows))
    assert int(rows.sum()) < 20 * config.top_k or call == "all"
    size = float(jnp.abs(out).max())
    assert size > 0.1
    np.testing.assert_allclose(
        np.asarray(split), np.asarray(out), rtol=2e-5, atol=1e-6 * size)


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("hidden", [32, 512, 1024])
def test_the_device_layout_leaves_whole_tiles_and_small_layers_alone(
        hidden, form):
    """A `hidden` of whole 512-tiles (every accepted expert configuration
    but one: 2048, 4096) or under one tile (a test's): the SAME dict comes
    back, so the layer traces the program it traced.  So does a layer whose
    feed-forward is dense, or that has none."""
    assert hidden_body(hidden) == hidden
    config = _wide(form, hidden)
    params = jax.eval_shape(
        lambda: init_moe_params(config, jax.random.PRNGKey(0)))
    assert device_layout(params) is params
    dense = {"w_up": params["router"], "w_down": params["router"]}
    assert device_layout(dense) is dense
    mixer_only = {"in_proj": params["router"]}
    assert device_layout(mixer_only) is mixer_only


@pytest.mark.parametrize("form", ["relu2", "gated"])
@pytest.mark.parametrize("hidden, body", [(640, 512), (1152, 1024), (2688, 2560)])
def test_the_device_layout_holds_the_canonical_values_slice_for_slice(
        hidden, body, form):
    assert hidden_body(hidden) == body
    config = MoEConfig(n_experts=4, top_k=2, hidden_size=hidden,
                       intermediate_size=8, form=form)
    params = init_moe_params(config, jax.random.PRNGKey(2))
    laid = device_layout(params)
    routed = ("w_up", "w_down") + (("w_gate",) if form == "gated" else ())
    for name in sorted(params):
        if name not in routed:
            assert laid[name] is params[name]
            continue
        axis = 2 if name == "w_down" else 1
        parts = laid[name]
        assert isinstance(parts, tuple) and len(parts) == 2
        assert parts[0].shape[axis] == body
        assert parts[1].shape[axis] == hidden - body
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(parts, axis=axis)),
            np.asarray(params[name]))
