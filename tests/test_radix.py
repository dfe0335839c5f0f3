"""ops/radix.py: the digit order must be lax.sort's order, key by key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops.radix import sort_by_keys, stable_argsort

RNG = np.random.default_rng(23)
N = 4096


def _column(dtype):
    if dtype == np.bool_:
        return RNG.random(N) < 0.5
    if np.issubdtype(dtype, np.floating):
        x = RNG.standard_normal(N).astype(dtype) * 1e3
        x[RNG.integers(0, N, 40)] = np.nan
        x[RNG.integers(0, N, 40)] = 0.0
        x[RNG.integers(0, N, 40)] = -0.0
        x[RNG.integers(0, N, 8)] = np.inf
        x[RNG.integers(0, N, 8)] = -np.inf
        return x
    info = np.iinfo(dtype)
    x = RNG.integers(info.min, info.max, N, dtype=dtype, endpoint=True)
    x[:4] = [info.min, info.max, 0, info.max]
    # few distinct values too, so that ties reach the next key
    return np.where(RNG.random(N) < 0.5, x, x[RNG.integers(0, 16, N)])


@pytest.mark.parametrize("dtype", [
    np.bool_, np.int8, np.int16, np.int32, np.int64, np.uint8, np.uint32,
    np.uint64, np.float32, np.float64])
def test_single_key_matches_lax_sort(dtype):
    key = jnp.asarray(_column(dtype))
    iota = jnp.arange(N, dtype=jnp.int32)
    want = jax.lax.sort([key, iota], num_keys=2)[1]
    assert np.array_equal(np.asarray(stable_argsort([key])),
                          np.asarray(want))


def test_mixed_keys_match_lax_sort():
    keys = [jnp.asarray(_column(d)) for d in
            (np.bool_, np.bool_, np.int64, np.bool_, np.float64, np.int32)]
    # coarse leading keys, so that every later key decides some pairs
    keys[2] = keys[2] % 5
    keys[4] = jnp.round(keys[4] / 500.0)
    iota = jnp.arange(N, dtype=jnp.int32)
    want = jax.lax.sort(keys + [iota], num_keys=len(keys) + 1)
    got_keys, perm = jax.jit(sort_by_keys)(keys)
    assert np.array_equal(np.asarray(perm), np.asarray(want[-1]))
    for g, w in zip(got_keys, want[:-1]):
        assert np.array_equal(np.asarray(g), np.asarray(w), equal_nan=True)
