"""engine/sampling.sample_tokens, held to a frozen copy of the body it had
when every batch ran the truncation (three sorts, two softmaxes, a
cumulative sum): whichever path a batch takes through the sampler, its
tokens are that body's, token for token.

Since PR 38 the truncation sorts nothing: it finds each cutoff by a
threshold search over the floats' key.  top-k and min-p are still the
frozen body's bit for bit.  The nucleus sums the mass above a threshold in
another order than a cumulative sum over a sorted row does, so it is held
to the ACCEPTANCE RULE below (`TAU`, `_oracle_nucleus`): off the nucleus's
edge it is the frozen body's bit for bit too, which is every row at V = 257.
"""

import asyncio
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prometheus_client import REGISTRY

from kserve_tpu.engine.sampling import (
    SAMPLER_PATHS,
    SamplingParams,
    SamplingState,
    _truncated,
    sample_tokens,
    sampler_top_k,
    sampler_truncates,
)

V = 257  # odd and small: ties in a sort and clipping at V - 1 both occur


def frozen_sample_tokens(logits, state, rng, counters=None):
    """The sampler as it stood before it chose a path (PR 29's
    engine/sampling.py:115-160), kept here unedited as the reference."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = logits / temp

    # top-k: mask logits below the k-th largest (k==0 disables)
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]  # desc
    k = jnp.clip(state.top_k, 0, V)
    kth_idx = jnp.clip(k - 1, 0, V - 1)
    kth_val = jnp.take_along_axis(sorted_logits, kth_idx[:, None], axis=1)
    topk_mask = jnp.where(
        (state.top_k > 0)[:, None], scaled < kth_val, jnp.zeros_like(scaled, bool)
    )
    scaled = jnp.where(topk_mask, -jnp.inf, scaled)

    # top-p (nucleus): keep smallest prefix of sorted probs with cumsum >= p
    probs_sorted = jax.nn.softmax(jnp.sort(scaled, axis=-1)[:, ::-1], axis=-1)
    cumprobs = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_count = jnp.sum(cumprobs - probs_sorted < state.top_p[:, None], axis=-1)
    cutoff_idx = jnp.clip(cutoff_count - 1, 0, V - 1)
    sorted_again = jnp.sort(scaled, axis=-1)[:, ::-1]
    cutoff_val = jnp.take_along_axis(sorted_again, cutoff_idx[:, None], axis=1)
    topp_mask = jnp.where(
        (state.top_p < 1.0)[:, None], scaled < cutoff_val, jnp.zeros_like(scaled, bool)
    )
    scaled = jnp.where(topp_mask, -jnp.inf, scaled)

    # min-p: drop tokens with prob < min_p * max_prob
    probs = jax.nn.softmax(scaled, axis=-1)
    max_prob = probs.max(axis=-1, keepdims=True)
    minp_mask = jnp.where(
        (state.min_p > 0.0)[:, None],
        probs < state.min_p[:, None] * max_prob,
        jnp.zeros_like(scaled, bool),
    )
    scaled = jnp.where(minp_mask, -jnp.inf, scaled)

    if counters is None:
        counters = jnp.zeros((B,), jnp.int32)
    batch_keys = jax.random.split(rng, B)
    seeded_keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c)
    )(jnp.maximum(state.seed, 0), counters)
    keys = jnp.where((state.seed >= 0)[:, None], seeded_keys, batch_keys)
    sampled = jax.vmap(lambda k, row: jax.random.categorical(k, row))(keys, scaled)
    return jnp.where(state.temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def _frozen_truncated(scaled, state):
    """The truncation's lines of the body above (top-k to min-p), copied a
    second time so that the array they leave can be compared, not only the
    token drawn from it."""
    V = scaled.shape[-1]
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]  # desc
    k = jnp.clip(state.top_k, 0, V)
    kth_idx = jnp.clip(k - 1, 0, V - 1)
    kth_val = jnp.take_along_axis(sorted_logits, kth_idx[:, None], axis=1)
    topk_mask = jnp.where(
        (state.top_k > 0)[:, None], scaled < kth_val, jnp.zeros_like(scaled, bool)
    )
    scaled = jnp.where(topk_mask, -jnp.inf, scaled)
    probs_sorted = jax.nn.softmax(jnp.sort(scaled, axis=-1)[:, ::-1], axis=-1)
    cumprobs = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_count = jnp.sum(cumprobs - probs_sorted < state.top_p[:, None], axis=-1)
    cutoff_idx = jnp.clip(cutoff_count - 1, 0, V - 1)
    sorted_again = jnp.sort(scaled, axis=-1)[:, ::-1]
    cutoff_val = jnp.take_along_axis(sorted_again, cutoff_idx[:, None], axis=1)
    topp_mask = jnp.where(
        (state.top_p < 1.0)[:, None], scaled < cutoff_val, jnp.zeros_like(scaled, bool)
    )
    scaled = jnp.where(topp_mask, -jnp.inf, scaled)
    probs = jax.nn.softmax(scaled, axis=-1)
    max_prob = probs.max(axis=-1, keepdims=True)
    minp_mask = jnp.where(
        (state.min_p > 0.0)[:, None],
        probs < state.min_p[:, None] * max_prob,
        jnp.zeros_like(scaled, bool),
    )
    return jnp.where(minp_mask, -jnp.inf, scaled)


def P(**kw) -> SamplingParams:
    return SamplingParams(**kw)


GREEDY = P(temperature=0.0)
#: what the planners seat in a lane no request holds
EMPTY_LANE = SamplingParams()
#: name -> (rows, the path the batch must take)
MIXES = {
    "all_greedy": ([GREEDY] * 6, "plain"),
    "greedy_carrying_top_p": (
        [GREEDY, P(temperature=0.0, top_p=0.5, top_k=3, min_p=0.2)] * 3,
        "plain"),
    "empty_lanes_only": ([EMPTY_LANE] * 6, "plain"),
    "greedy_and_temperature": (
        [GREEDY, P(temperature=0.7), P(), GREEDY, P(temperature=1.3), GREEDY],
        "plain"),
    "seeded_and_unseeded": (
        [P(seed=7), P(temperature=0.8), P(temperature=0.8, seed=7),
         P(seed=2**31 - 1), P(seed=0), GREEDY],
        "plain"),
    "temperature_beside_empty_lanes": (
        [P(temperature=0.9, seed=3), EMPTY_LANE, EMPTY_LANE, P(), EMPTY_LANE,
         EMPTY_LANE],
        "plain"),
    "greedy_top_p_beside_a_draw": (
        [P(temperature=0.0, top_p=0.3), P(temperature=0.6), GREEDY, P(seed=5),
         GREEDY, GREEDY],
        "plain"),
    "top_k": (
        [P(top_k=1), P(top_k=5, temperature=0.7), P(top_k=V + 9), P(top_k=40),
         GREEDY, P()],
        "truncate"),
    "top_p": (
        [P(top_p=0.9, temperature=0.7, seed=11), P(top_p=0.05), P(top_p=0.5),
         P(), GREEDY, P(top_p=0.999)],
        "truncate"),
    "min_p": (
        [P(min_p=0.05), P(min_p=0.5, temperature=1.5), P(min_p=1.0), P(),
         GREEDY, P(seed=4)],
        "truncate"),
    "one_truncating_row_among_empty_lanes": (
        [EMPTY_LANE, EMPTY_LANE, P(top_p=0.9, temperature=0.7), EMPTY_LANE,
         EMPTY_LANE, EMPTY_LANE],
        "truncate"),
    "everything_at_once": (
        [GREEDY, P(temperature=0.0, top_p=0.2), P(temperature=0.7),
         P(seed=9), P(top_k=7, top_p=0.8, min_p=0.1, temperature=0.6, seed=13),
         EMPTY_LANE, P(top_k=3), P(top_p=0.6, seed=1), P(min_p=0.3),
         P(temperature=2.0, top_k=50, top_p=0.95)],
        "truncate"),
    "top_k_and_top_p_on_one_row": (
        [P(top_k=5, top_p=0.5), P(top_k=40, top_p=0.9, temperature=0.7, seed=2),
         P(top_k=2, top_p=0.999), P(top_k=200, top_p=0.05, seed=8),
         P(top_k=1, top_p=0.01), P(top_k=3, top_p=0.7, min_p=0.2)],
        "truncate"),
    "top_k_at_and_past_the_vocabulary": (
        [P(top_k=V), P(top_k=V + 1, top_p=0.8), P(top_k=V - 1),
         P(top_k=2**31 - 1, temperature=0.7, seed=6), P(top_k=V, min_p=0.1),
         GREEDY],
        "truncate"),
    "top_p_at_and_below_zero": (
        [P(top_p=0.0), P(top_p=-0.5, temperature=0.7, seed=3), P(top_p=1e-30),
         P(top_p=0.0, top_k=4), P(top_p=0.0, min_p=0.3), GREEDY],
        "truncate"),
}
MIX_NAMES = sorted(MIXES)

#: logits that tie where a cutoff falls, and rows with nothing to keep
LOGITS = {
    # every value one of five: the k-th largest is tied many times over
    "ties": lambda rng, rows: rng.randint(-2, 3, (rows, V)).astype(np.float32),
    # one value in every column: any k, any nucleus keeps the whole row
    "flat": lambda rng, rows: np.zeros((rows, V), np.float32),
    # a penalised row can hold -inf alone (every other row is as drawn)
    "a_row_of_minus_inf": lambda rng, rows: np.where(
        np.arange(rows)[:, None] % 3 == 1, -np.inf,
        rng.randn(rows, V) * 3.0).astype(np.float32),
    # and -inf beside finite values, fewer of those than k
    "mostly_minus_inf": lambda rng, rows: np.where(
        rng.rand(rows, V) < 0.99, -np.inf, rng.randn(rows, V)).astype(np.float32),
    # -0.0 beside +0.0 at the top of the row: one value to every compare
    "signed_zeros": lambda rng, rows: np.where(
        rng.rand(rows, V) < 0.3, np.where(rng.rand(rows, V) < 0.5, -0.0, 0.0),
        -np.abs(rng.randn(rows, V))).astype(np.float32),
}
TRUNCATING = [name for name in MIX_NAMES if MIXES[name][1] == "truncate"]


def _inputs(rows: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(rows, V).astype(np.float32) * 3.0)
    counters = jnp.asarray(rng.randint(0, 500, rows), jnp.int32)
    return logits, counters, jax.random.PRNGKey(seed + 17)


def _scanned(fn):
    """`fn` as the programs call it: inside a `lax.scan` over per-step keys,
    the counters advancing, the sampling state fixed outside the loop."""
    def run(logits, state, rng, counters):
        def body(carry, step_rng):
            logits, counters = carry
            out = fn(logits, state, step_rng, counters)
            # the next step's logits depend on what was sampled
            logits = logits.at[jnp.arange(logits.shape[0]), out].add(-1.5)
            return (logits, counters + 1), out
        _, outs = jax.lax.scan(
            body, (logits, counters), jax.random.split(rng, 4))
        return outs
    return run


def _scanned_with_path(logits, state, rng, counters):
    """As engine/compiled.py does: the path decided once, outside the scan."""
    truncates = sampler_truncates(state)
    return _scanned(
        lambda lg, st, r, c: sample_tokens(lg, st, r, c, truncates)
    )(logits, state, rng, counters)


@pytest.mark.parametrize("how", ["jit", "scan", "scan_path_hoisted"])
@pytest.mark.parametrize("with_counters", [True, False], ids=["counters", "no_counters"])
@pytest.mark.parametrize("mix", MIX_NAMES)
def test_tokens_are_the_frozen_body_s(mix, with_counters, how):
    rows, _ = MIXES[mix]
    state = SamplingState.from_params(rows)
    logits, counters, rng = _inputs(len(rows), seed=MIX_NAMES.index(mix))
    if how == "jit":
        c = counters if with_counters else None
        new = jax.jit(sample_tokens)(logits, state, rng, c)
        old = jax.jit(frozen_sample_tokens)(logits, state, rng, c)
    else:
        if not with_counters:
            counters = jnp.zeros_like(counters)
        new_fn = _scanned_with_path if how == "scan_path_hoisted" else _scanned(sample_tokens)
        new = jax.jit(new_fn)(logits, state, rng, counters)
        old = jax.jit(_scanned(frozen_sample_tokens))(logits, state, rng, counters)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    assert new.dtype == jnp.int32


@pytest.mark.parametrize("how", ["jit", "scan"])
@pytest.mark.parametrize("logits_kind", sorted(LOGITS))
@pytest.mark.parametrize("mix", TRUNCATING)
def test_cutoffs_at_ties_and_empty_rows_are_the_frozen_body_s(
        mix, logits_kind, how):
    """Where the one sort could differ from the three: values tied at the
    k-th place or at the nucleus's edge, and rows the masks leave empty."""
    rows, _ = MIXES[mix]
    state = SamplingState.from_params(rows)
    _, counters, rng = _inputs(len(rows), seed=3)
    logits = jnp.asarray(
        LOGITS[logits_kind](np.random.RandomState(5), len(rows)))
    wrap = _scanned if how == "scan" else (lambda fn: fn)
    new = jax.jit(wrap(sample_tokens))(logits, state, rng, counters)
    old = jax.jit(wrap(frozen_sample_tokens))(logits, state, rng, counters)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("logits_kind", sorted(LOGITS))
@pytest.mark.parametrize("mix", TRUNCATING)
def test_truncated_logits_are_the_frozen_body_s_bit_for_bit(mix, logits_kind):
    """Not only the tokens: the array the draw reads is the one the three
    sorts left (what is -inf, and every kept value)."""
    rows, _ = MIXES[mix]
    state = SamplingState.from_params(rows)
    logits = jnp.asarray(
        LOGITS[logits_kind](np.random.RandomState(9), len(rows)))
    scaled = logits / jnp.maximum(state.temperature, 1e-6)[:, None]
    new = jax.jit(_truncated)(scaled, state)
    old = jax.jit(_frozen_truncated)(scaled, state)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("mix", MIX_NAMES)
def test_host_label_is_the_device_s_branch(mix):
    """engine_sampler_dispatches_total's label, computed at plan time from
    the params, names the branch the program takes for the same rows."""
    rows, expected = MIXES[mix]
    columns, label = SamplingState.planned(rows)
    on_device = int(jax.jit(sampler_truncates)(SamplingState(**columns)))
    assert SAMPLER_PATHS[on_device] == label == expected


def test_host_label_compares_what_the_device_holds():
    """A top_p that only float64 tells from 1 is 1.0 on the device: the
    host's predicate reads float32 too."""
    columns, label = SamplingState.planned([P(top_p=1.0 - 1e-12)])
    assert label == "plain"
    assert not bool(sampler_truncates(SamplingState(**columns)))
    assert SamplingState.planned([P(temperature=-1.0, top_k=4)])[1] == "plain"


def _count_primitives(jaxpr, names) -> int:
    """Primitives of `names` in a jaxpr, those of the jaxprs it calls (a
    branch, a loop's body) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_primitives(sub, names)
    return n


@pytest.mark.parametrize("path", SAMPLER_PATHS)
def test_neither_branch_sorts(path):
    """The traced sampler holds one conditional of two branches, and
    neither sorts, sums cumulatively or selects a top k: the truncating one
    searches (loops of compare-and-reduce)."""
    ordering = {"sort", "cumsum", "top_k", "approx_top_k"}
    rows = MIXES["everything_at_once"][0]
    logits, counters, rng = _inputs(len(rows))
    jaxpr = jax.make_jaxpr(sample_tokens)(
        logits, SamplingState.from_params(rows), rng, counters).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1 and len(conds[0].params["branches"]) == 2
    assert _count_primitives(jaxpr, ordering) == 0
    assert _count_primitives(jaxpr, {"cond"}) == 1  # none nested
    branch = conds[0].params["branches"][SAMPLER_PATHS.index(path)].jaxpr
    # top-k's search and the nucleus's, in the truncating branch alone
    assert _count_primitives(branch, {"while", "scan"}) == 2 * (
        path == "truncate")
    # the frozen body, for contrast, is what this test would catch
    frozen = jax.make_jaxpr(frozen_sample_tokens)(
        logits, SamplingState.from_params(rows), rng, counters).jaxpr
    assert _count_primitives(frozen, ordering) == 4


def test_top_k_is_searched_only_where_a_sampled_row_sets_it():
    """`sampler_top_k` is the truncation's second predicate: without it the
    search for the k-th values runs no pass (a greedy row's top_k is not
    worth 32 of them: its sampled value is discarded)."""
    rows = [P(top_k=3), P(temperature=0.0, top_k=3), P(top_p=0.9), P()]
    state = SamplingState.from_params(rows)
    assert bool(sampler_top_k(state))
    assert not bool(sampler_top_k(SamplingState.from_params(rows[1:])))
    assert bool(sampler_truncates(SamplingState.from_params(rows[1:])))
    scaled, _, _ = _inputs(len(rows))
    searched = np.asarray(jax.jit(_truncated)(scaled, state))
    np.testing.assert_array_equal(
        searched, np.asarray(jax.jit(_frozen_truncated)(scaled, state)))
    assert (np.isfinite(searched).sum(axis=1)[:2] == 3).all()
    skipped = np.asarray(jax.jit(_truncated)(scaled, state, jnp.bool_(False)))
    assert (np.isfinite(skipped).sum(axis=1)[:2] == V).all()
    np.testing.assert_array_equal(skipped[2:], searched[2:])


# -- the acceptance rule of the nucleus (ISSUE 38, written before the code) --
#
# Oracle: numpy float64 on the SAME float32 row after the top-k mask:
# p = softmax, E(v) = the mass STRICTLY above v, K(q) = {j : row[j] >= min{v :
# E(v) < q}}: the exact nucleus at mass q, equal values kept together.
#
# 1. every row's kept set K is a threshold set, holds the row's maximum, and
#    K(top_p - TAU) <= K <= K(top_p + TAU);
# 2. where K(top_p - TAU) == K(top_p + TAU) (no value's E within TAU of
#    top_p), K, the masked logits and the tokens are the frozen body's, bit
#    for bit;
# 3. top-k and min-p alone (top_p = 1) are the frozen body's bit for bit;
# 4. a row of -inf alone, fewer finite values than k, -0.0 beside +0.0, all
#    values equal, top_p <= 0: as the frozen body (LOGITS, MIXES above).

#: probability mass.  A float32 tree sum over 2e5 non-negative terms errs by
#: ~1e-6, the frozen body's own float32 cumulative sum by as much.
TAU = 1e-5
#: the vocabularies the benchmark's cells sample from
VOCABULARIES = (151936, 200064)
ROWS = 4


def _oracle_nucleus(row: np.ndarray, q: float) -> np.ndarray:
    """K(q) of one float32 row, as a mask."""
    x = row.astype(np.float64)
    top = x.max()
    if not np.isfinite(top):
        return np.ones(row.shape, bool)  # nothing to tell apart
    p = np.exp(x - top)
    p /= p.sum()
    order = np.argsort(-x, kind="stable")
    values, first = np.unique(-x[order], return_index=True)  # descending
    above = np.concatenate([[0.0], np.cumsum(p[order])])[first]  # E(v)
    inside = -values[above < q]
    # q <= 0: no value has E(v) < q, and the maximum is kept with its ties
    return x >= (inside.min() if inside.size else top)


BIG_LOGITS = {
    # the benchmark's: random weights give nearly flat rows, and the
    # nucleus keeps most of the vocabulary
    "near_flat": lambda rng, v: rng.randn(ROWS, v) * 0.7,
    # a trained model's: a few tokens hold the mass, the tail is long
    "peaked": lambda rng, v: rng.randn(ROWS, v) * 4.0 + 12.0 * (
        rng.rand(ROWS, v) < 5e-5),
    # few distinct values: every edge is a tie of thousands
    "tied": lambda rng, v: rng.randint(-3, 4, (ROWS, v)).astype(np.float64),
    # a penalised row: most of it -inf
    "mostly_minus_inf": lambda rng, v: np.where(
        rng.rand(ROWS, v) < 0.9, -np.inf, rng.randn(ROWS, v) * 2.0),
}


def _big(kind: str, vocab: int, seed: int = 0) -> jnp.ndarray:
    rng = np.random.RandomState(VOCABULARIES.index(vocab) * 100 + seed)
    return jnp.asarray(BIG_LOGITS[kind](rng, vocab).astype(np.float32))


def _after_top_k(scaled, state):
    """The rows the nucleus sees: the frozen body's top-k mask alone."""
    only_top_k = dataclasses.replace(
        state, top_p=jnp.ones_like(state.top_p),
        min_p=jnp.zeros_like(state.min_p))
    return np.asarray(jax.jit(_frozen_truncated)(scaled, only_top_k))


def _hold_to_the_rule(scaled, state):
    """Rules 1 and 2 for every row of one batch; the rows at the edge."""
    new = np.asarray(jax.jit(_truncated)(scaled, state))
    old = np.asarray(jax.jit(_frozen_truncated)(scaled, state))
    before = _after_top_k(scaled, state)
    at_the_edge = []
    for i, top_p in enumerate(np.asarray(state.top_p, np.float64)):
        row, kept = before[i], new[i] > -np.inf
        if not np.isfinite(row.max()):
            np.testing.assert_array_equal(new[i], old[i])
            continue
        # what is kept is kept unchanged, and what top-k dropped stays so
        np.testing.assert_array_equal(new[i][kept], row[kept])
        if top_p >= 1.0:
            np.testing.assert_array_equal(new[i], old[i])
            continue
        assert kept[row.argmax()]
        assert row[kept].min() > row[~kept].max(initial=-np.inf)  # a threshold
        inner = _oracle_nucleus(row, top_p - TAU)
        outer = _oracle_nucleus(row, top_p + TAU)
        assert (kept | ~inner).all() and (outer | ~kept).all()
        if (inner == outer).all():
            np.testing.assert_array_equal(new[i], old[i])
        else:
            at_the_edge.append(i)
    return at_the_edge


@pytest.mark.parametrize("top_k", [0, 50], ids=["no_top_k", "top_k_50"])
@pytest.mark.parametrize("top_p", [0.9, 0.95, 0.999])
@pytest.mark.parametrize("kind", sorted(BIG_LOGITS))
@pytest.mark.parametrize("vocab", VOCABULARIES)
def test_nucleus_is_within_tau_of_the_exact_one(vocab, kind, top_p, top_k):
    """Rules 1 and 2 at the benchmark's vocabularies."""
    temperatures = (0.6, 0.7, 1.0, 1.3)
    state = SamplingState.from_params([
        P(top_p=top_p, top_k=top_k, temperature=t) for t in temperatures])
    scaled = _big(kind, vocab) / state.temperature[:, None]
    _hold_to_the_rule(scaled, state)


#: the rows of the V = 257 cases that DO lie within TAU of the edge (rule 2
#: does not bind them; the cases above find them the frozen body's all the
#: same): P(top_p=0.999) of the mix "top_p", whose tail values hold ~1e-5
#: of the mass each
AT_THE_EDGE_257 = {("top_p", "drawn"): [5], ("top_p", "a_row_of_minus_inf"): [5]}


@pytest.mark.parametrize("logits_kind", ["drawn"] + sorted(LOGITS))
@pytest.mark.parametrize("mix", TRUNCATING)
def test_rows_at_257_lie_off_the_nucleus_s_edge(mix, logits_kind):
    """Rule 2's premise holds for the rows that the cases above compare
    with the frozen body at V = 257, but for the two named: so those cases
    hold the nucleus bit for bit."""
    rows, _ = MIXES[mix]
    state = SamplingState.from_params(rows)
    if logits_kind == "drawn":
        logits = _inputs(len(rows), seed=MIX_NAMES.index(mix))[0]
    else:
        logits = jnp.asarray(
            LOGITS[logits_kind](np.random.RandomState(9), len(rows)))
    scaled = logits / jnp.maximum(state.temperature, 1e-6)[:, None]
    assert _hold_to_the_rule(scaled, state) == AT_THE_EDGE_257.get(
        (mix, logits_kind), [])


@pytest.mark.parametrize("params", [
    dict(top_k=1), dict(top_k=50), dict(top_k=10**6), dict(min_p=0.05),
    dict(top_k=50, min_p=0.3),
], ids=lambda d: "-".join(f"{k}={v}" for k, v in d.items()))
@pytest.mark.parametrize("kind", sorted(BIG_LOGITS))
@pytest.mark.parametrize("vocab", VOCABULARIES)
def test_top_k_and_min_p_are_the_frozen_body_s_at_any_size(vocab, kind, params):
    """Rule 3: a count is exact, and min-p never searched."""
    state = SamplingState.from_params(
        [P(temperature=t, **params) for t in (0.6, 0.7, 1.0, 1.3)])
    scaled = _big(kind, vocab, seed=1) / state.temperature[:, None]
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_truncated)(scaled, state)),
        np.asarray(jax.jit(_frozen_truncated)(scaled, state)))


@pytest.mark.parametrize("q, kept", [
    (0.5, [0, 1]), (0.4, [0, 1]), (0.41, [0, 1]), (0.39, [0, 1]),
    (0.81, [0, 1, 2]), (0.79, [0, 1]), (1.0, [0, 1, 2, 3, 4]), (0.0, [0, 1]),
    (-1.0, [0, 1]),
])
def test_the_oracle_keeps_ties_and_the_strict_mass_above(q, kept):
    """The oracle itself, on a row whose masses are 0.4 0.4 0.1 0.05 0.05
    (the two largest tied): K(q) by hand."""
    row = np.log(np.asarray([0.4, 0.4, 0.1, 0.05, 0.05])).astype(np.float32)
    row[1] = row[0]
    assert np.flatnonzero(_oracle_nucleus(row, q)).tolist() == kept


def _engine(label, **overrides):
    from kserve_tpu.engine.engine import EngineConfig, LLMEngine
    from kserve_tpu.engine.tokenizer import ByteTokenizer
    from kserve_tpu.models.llama import LlamaConfig

    model_config = LlamaConfig.tiny(dtype="float32")
    cfg = dict(
        max_batch_size=4, page_size=8, num_pages=64, max_pages_per_seq=8,
        max_prefill_len=32, prefill_buckets=(16, 32), dtype="float32",
        use_pallas=False, steps_per_sync=4)
    cfg.update(overrides)
    return LLMEngine(model_config, EngineConfig(**cfg),
                     ByteTokenizer(model_config.vocab_size),
                     metrics_label=label)


async def _drain(stream):
    return [out.token_id async for out in stream]


def _sampler_dispatches(label):
    return {path: REGISTRY.get_sample_value(
        "engine_sampler_dispatches_total",
        {"model_name": label, "sampler_path": path}) or 0.0 for path in SAMPLER_PATHS}


def _dispatches(label):
    return sum(REGISTRY.get_sample_value(
        "engine_dispatches_total", {"model_name": label, "program": program})
        or 0.0 for program in ("mixed", "mixed_decode", "decode"))


@pytest.fixture(scope="module")
def planned():
    """The rows each planner built its SamplingState from while three
    requests (fewer than the four lanes) ran through the unified program,
    the legacy programs and a detached prefill: planner -> list of rows."""
    seen = {}
    real = SamplingState.planned

    def spy(params_list):
        caller = next(
            f.function for f in inspect.stack()[1:]
            if f.function not in ("from_params", "_sampling_state"))
        seen.setdefault(caller, []).append(list(params_list))
        return real(params_list)

    request = P(max_tokens=9, temperature=0.8, top_p=0.9, seed=1, ignore_eos=True)
    prompts = ([1, 2, 3], [4, 5, 6, 7, 8], list(range(9, 30)))

    async def main():
        for label, overrides in (("sampling-mixed", {}),
                                 ("sampling-legacy", {"use_ragged": False})):
            engine = _engine(label, **overrides)
            await engine.start()
            try:
                await asyncio.gather(
                    *[_drain(engine.generate(p, request)) for p in prompts])
                await asyncio.gather(
                    *[engine.prefill_detached(p, request) for p in prompts])
            finally:
                await engine.stop()

    SamplingState.planned = staticmethod(spy)
    try:
        asyncio.run(main())
    finally:
        SamplingState.planned = staticmethod(real)
    return request, seen


@pytest.mark.parametrize("planner", [
    "_prefill_detached_batch", "_admit_batch", "_dispatch_chunk", "_plan_ragged"])
def test_a_planner_s_empty_lanes_ask_for_no_sort(planned, planner):
    """An unseated lane's token is discarded: what a planner seats there
    must not put its batch on the truncating path."""
    request, seen = planned
    batches = seen[planner]
    assert any(EMPTY_LANE in rows for rows in batches)
    for rows in batches:
        assert len(rows) == 4  # three requests, padded to the lanes
        assert all(p is request or p == EMPTY_LANE for p in rows)
    assert SamplingState.planned([EMPTY_LANE] * 4)[1] == "plain"


@pytest.mark.parametrize("regime", ["mixed", "mixed_decode", "legacy"])
@pytest.mark.parametrize("request_params, path", [
    (P(temperature=0.0), "plain"),
    (P(temperature=0.0, top_p=0.5), "plain"),
    (P(temperature=0.7, seed=3), "plain"),
    (P(temperature=0.7, top_p=0.9, seed=3), "truncate"),
], ids=["greedy", "greedy_top_p", "temperature", "top_p"])
def test_counter_takes_every_dispatch_on_the_batch_s_path(
        regime, request_params, path):
    """engine_sampler_dispatches_total beside engine_dispatches_total: one
    request among empty lanes puts every dispatch on its own path."""
    label = (f"sampling-{regime}-{request_params.temperature}"
             f"-{request_params.top_p}")
    overrides = {"mixed": {}, "mixed_decode": {"spec_decode_k": 2},
                 "legacy": {"use_ragged": False}}[regime]
    params = dataclasses.replace(
        request_params, max_tokens=14, ignore_eos=True)

    async def main():
        engine = _engine(label, **overrides)
        await engine.start()
        try:
            return await _drain(engine.generate([5, 6, 7, 8, 9], params))
        finally:
            await engine.stop()

    assert len(asyncio.run(main())) == 14
    counts = _sampler_dispatches(label)
    assert counts[path] == _dispatches(label) > 0
    assert sum(counts.values()) == counts[path]
