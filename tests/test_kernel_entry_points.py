"""PR 45: a Pallas kernel's entry point is a jitted function whose
Python-level choices are static (ops/pallas_kv_write.py,
ops/pallas_paged_attention.py).  A model's layers are unrolled in Python, so
a program of several layers traces and lowers each entry point ONCE and
calls that one function from every layer, and what it computes is the plain
wrapper's bit for bit (interpret mode, on the CPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_tpu.ops import pallas_kv_write as kw
from kserve_tpu.ops import pallas_paged_attention as pk

PS, LANES, WIDTH, PAGES = 8, 4, 4, 17


def _f(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.randn(*shape), dtype)


def _i(values):
    return jnp.asarray(values, jnp.int32)


def _table(rng):
    """Every lane its own pages, none the null page."""
    return _i(rng.permutation(np.arange(1, PAGES))[:LANES * WIDTH].reshape(
        LANES, WIDTH))


def _cache(rng, heads=2, d=128, dtype=jnp.float32):
    return _f(rng, PAGES, 2, heads, PS, d, dtype=dtype)


def _decode(rng, **static):
    return ((_f(rng, LANES, 4, 128), _cache(rng), _table(rng),
             _i(rng.randint(1, WIDTH * PS, size=LANES))), static)


def _ragged(rng):
    """Two slices of a 32-token buffer at multiples of the kernel's block."""
    return ((_f(rng, 32, 4, 128), _cache(rng), _table(rng),
             _i([0, 16, 0, 0]), _i([11, 1, 0, 0]), _i([5, 20, 0, 0])), {})


def _latent(rng):
    return _f(rng, PAGES, 1, 1, PS, 128)


#: entry point -> (its arrays of one layer, its static choices)
CASES = {
    "append_rows": lambda rng: (
        (_cache(rng, dtype=jnp.bfloat16), _f(rng, LANES, 2, 128),
         _f(rng, LANES, 2, 128), _table(rng),
         _i(rng.randint(0, WIDTH * PS, size=LANES)),
         jnp.asarray([True, True, False, True])), {}),
    "write_runs": lambda rng: (
        (_cache(rng, dtype=jnp.bfloat16), _f(rng, 32, 2, 128),
         _f(rng, 32, 2, 128), _table(rng), _i([0, 1, 2]), _i([0, 16, 24]),
         _i([11, 1, 0]), _i([5, 20, 0])), {}),
    "paged_attention_pallas": _decode,
    # another label, another scale: a function of its own (models/hybrid.py)
    "paged_attention_pallas named": lambda rng: _decode(
        rng, scale=0.2, name="window_attention_decode"),
    "ragged_paged_attention_pallas": _ragged,
    "latent_attention_decode_pallas": lambda rng: (
        (_f(rng, LANES, 4, 128), _latent(rng), _table(rng),
         _i(rng.randint(1, WIDTH * PS, size=LANES))),
        dict(scale=0.125, value_dim=64)),
    "latent_attention_ragged_pallas": lambda rng: (
        (_f(rng, 32, 4, 128), _latent(rng), _table(rng), _i([0, 16, 0, 0]),
         _i([11, 1, 0, 0]), _i([5, 20, 0, 0])),
        dict(scale=0.125, value_dim=64)),
    # a ring of two pages a lane; a chunk over a wrapped ring beside a
    # decode lane
    "window_attention_ragged_pallas": lambda rng: (
        (_f(rng, 32, 4, 128), _f(rng, 32, 2, 128), _f(rng, 32, 2, 128),
         _cache(rng), _table(rng)[:, :2], _i([0, 16, 0, 0]),
         _i([13, 1, 0, 0]), _i([27, 9, 0, 0])),
        dict(scale=0.25, block=8)),
}


def _entry(case):
    name = case.split()[0]
    return name, getattr(kw if hasattr(kw, name) else pk, name)


def _program(fn, static):
    """Two layers: the second reads what the first made (a cache it wrote,
    or its rows folded into the next layer's first argument)."""
    def program(layers):
        outs = []
        for args in layers:
            first = args[0]
            if outs and outs[-1].shape == first.shape:
                first = (outs[-1] if outs[-1].dtype == jnp.bfloat16
                         else first + 0.5 * outs[-1].astype(first.dtype))
            outs.append(fn(first, *args[1:], interpret=True, **static))
        return outs
    return jax.jit(program)


@pytest.mark.parametrize("case", sorted(CASES))
def test_two_layers_lower_the_entry_point_once_and_call_it_twice(case):
    name, entry = _entry(case)
    rng = np.random.RandomState(sorted(CASES).index(case))
    (first, static), (second, _) = CASES[case](rng), CASES[case](rng)
    text = _program(entry, static).lower([first, second]).as_text()
    assert len(re.findall(rf"func\.func private @{name}(?:_\d+)?\(", text)) == 1
    assert len(re.findall(rf"call @{name}(?:_\d+)?\(", text)) == 2
    # ... and its kernel with it: one pallas_call a form, not one a layer
    plain = _program(entry.__wrapped__, static).lower([first, second]).as_text()
    assert f"@{name}" not in plain
    assert not re.findall(rf"func\.func private @{name}", plain)


@pytest.mark.parametrize("case", sorted(CASES))
def test_two_layers_compute_what_the_plain_wrapper_computes(case):
    """Bit for bit: the jitted entry point is the same function, reached
    through a call."""
    _, entry = _entry(case)
    rng = np.random.RandomState(100 + sorted(CASES).index(case))
    (first, static), (second, _) = CASES[case](rng), CASES[case](rng)
    got = _program(entry, static)([first, second])
    want = _program(entry.__wrapped__, static)([first, second])
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.isfinite(g.astype(np.float32)).all() and g.any()
        assert g.tobytes() == w.tobytes()


def test_a_python_level_choice_is_static_and_an_array_is_not():
    """A mode, a label or a scale that changed is another function; other
    values in the same arrays are the same one."""
    rng = np.random.RandomState(7)
    args, _ = _decode(rng)
    entry = pk.paged_attention_pallas
    entry.clear_cache()
    entry(*args, interpret=True)
    entry(*_decode(rng)[0], interpret=True)
    assert entry._cache_size() == 1
    entry(*args, interpret=True, name="shared_kv_attention_decode")
    entry(*args, interpret=True, scale=0.3)
    assert entry._cache_size() == 3
    with pytest.raises(ValueError, match="[Nn]on-hashable static"):
        entry(*args, interpret=True, scale=jnp.float32(0.3) * np.ones(1))
