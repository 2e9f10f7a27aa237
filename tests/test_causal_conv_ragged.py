"""`ops/ssm.causal_conv_ragged` by itself: the packed convolution against
the one-step form run token by token over each lane's slice (output and new
tail), and what its lowering may not hold."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.ops import ssm

D = 24


def _layouts(K):
    """name -> (T, [(q_start, q_len, fresh) a lane]); a lane's slices never
    overlap, padding is every row no slice holds."""
    long = 5 * K + 3
    return {
        # slices of length 1, K-2, K-1, K and >> K, fresh and continuing in
        # one buffer, padding between them and at the buffer's end
        "lengths": (2 * long + 26, [
            (0, 1, False), (3, max(K - 2, 0), False), (8, K - 1, True),
            (13, K, False), (20, long, True), (20 + long + 2, long, False)]),
        # the cells' packed step: single-token lanes at 8-row alignment
        # beside one long chunk
        "packed_step": (96, [(0, 1, False), (8, 1, True), (16, 1, False),
                             (24, 1, False), (32, 1, False),
                             (40, 50, False)]),
        # a lane without a slice, among lanes that have one
        "q_len_zero": (32, [(0, 2, False), (5, 0, False), (0, 0, True),
                            (9, K + 2, False)]),
        # no padding anywhere: adjacent slices, the last ends on row T - 1
        "ends_on_last_row": (24, [
            (0, 1, False), (1, 1, True), (2, K - 1, False),
            (K + 1, 24 - (K + 1), False)]),
    }


def _inputs(K, T, lanes, x_dtype, tail_dtype, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    # x holds values the tail's type holds too: the one-step form rounds
    # the token to the tail's type before it multiplies, the packed one after
    x = (0.5 * jax.random.normal(keys[0], (T, D))).astype(jnp.bfloat16)
    tail = (0.5 * jax.random.normal(keys[1], (lanes, K - 1, D)))
    w = 0.5 * jax.random.normal(keys[2], (K, D))
    b = 0.5 * jax.random.normal(keys[3], (D,))
    return (x.astype(x_dtype), tail.astype(jnp.bfloat16).astype(tail_dtype),
            w.astype(jnp.float32), b.astype(jnp.float32))


def _pack(T, lanes):
    token_seq = np.full(T, -1, np.int32)
    token_off = np.zeros(T, np.int32)
    for lane, (start, length, _) in enumerate(lanes):
        assert (token_seq[start:start + length] == -1).all()
        token_seq[start:start + length] = lane
        token_off[start:start + length] = np.arange(length)
    q_start, q_len, fresh = (np.asarray(c) for c in zip(*lanes))
    return (jnp.asarray(token_seq), jnp.asarray(token_off),
            jnp.asarray(q_start, jnp.int32), jnp.asarray(q_len, jnp.int32),
            jnp.asarray(fresh, bool))


def _token_by_token(x, tail, w, b, lanes):
    """The one-step form over each lane's slice: rows of y by buffer row,
    and the tail each lane is left with."""
    rows, tails = {}, []
    for lane, (start, length, fresh) in enumerate(lanes):
        t = tail[lane:lane + 1]
        if fresh:
            t = jnp.zeros_like(t)
        for row in range(start, start + length):
            y, t = ssm.causal_conv_step(x[row:row + 1], t, w, b)
            rows[row] = np.asarray(y[0])
        tails.append(np.asarray(t[0].astype(jnp.float32)))
    return rows, np.stack(tails)


@pytest.mark.parametrize("bias", ["vector", "scalar"])
@pytest.mark.parametrize("layout", ["lengths", "packed_step", "q_len_zero",
                                    "ends_on_last_row"])
@pytest.mark.parametrize("x_dtype,tail_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.float32, jnp.bfloat16),
    (jnp.float32, jnp.float32)], ids=["bf16", "f32_bf16tail", "f32"])
@pytest.mark.parametrize("K", [2, 4])
def test_agrees_with_the_one_step_form(K, x_dtype, tail_dtype, layout, bias):
    T, lanes = _layouts(K)[layout]
    x, tail, w, b = _inputs(K, T, len(lanes), x_dtype, tail_dtype, seed=K)
    if bias == "scalar":  # the `kda` caller's: no bias, a zero without dims
        b = jnp.zeros((), jnp.float32)
    y, new_tail = jax.jit(ssm.causal_conv_ragged)(
        x, tail, w, b, *_pack(T, lanes))
    assert y.shape == (T, D) and y.dtype == jnp.float32
    assert new_tail.shape == tail.shape and new_tail.dtype == tail.dtype
    want_rows, want_tail = _token_by_token(x, tail, w, b, lanes)
    assert len(want_rows) == sum(length for _, length, _ in lanes)
    y = np.asarray(y)
    for row, want in want_rows.items():
        np.testing.assert_allclose(y[row], want, rtol=0, atol=1e-6,
                                   err_msg=f"row {row}")
    got_tail = np.asarray(new_tail.astype(jnp.float32))
    has_slice = np.asarray([length > 0 for _, length, _ in lanes])
    # a lane without a slice keeps its tail (the caller selects the old one)
    np.testing.assert_allclose(got_tail[has_slice], want_tail[has_slice],
                               rtol=0, atol=1e-6)


def test_no_gather_along_the_token_axis():
    """The taps are static shifts: the program holds no gather (and no
    matrix product) whose result has the buffer's T rows; the only gathers
    left fetch lanes x (K - 1) rows."""
    T, width, B, K = 512, 256, 48, 4
    shapes = (
        jax.ShapeDtypeStruct((T, width), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, K - 1, width), jnp.bfloat16),
        jax.ShapeDtypeStruct((K, width), jnp.bfloat16),
        jax.ShapeDtypeStruct((width,), jnp.bfloat16),
        jax.ShapeDtypeStruct((T,), jnp.int32),
        jax.ShapeDtypeStruct((T,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.bool_))
    lowered = jax.jit(ssm.causal_conv_ragged).lower(*shapes)
    for text in (lowered.as_text(), lowered.compile().as_text()):
        found = [line.strip() for line in text.splitlines() if re.search(
            r"\b(dynamic_)?gather\(|stablehlo\.(dynamic_)?gather|dot_general"
            r"|\bdot\(", line)]
        assert found, "the new tail and the slices' first rows are gathered"
        for line in found:
            result = re.search(r"-> tensor<([0-9x]+)x\w+>|= \w+\[([0-9,]+)\]",
                               line)
            assert result, line
            dims = [int(n) for n in re.split(r"[x,]", result.group(1)
                                             or result.group(2))]
            assert "dot" not in line.split("(")[0], line
            assert T not in dims, line
            assert dims[0] <= B * (K - 1), line
