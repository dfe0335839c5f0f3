"""ops/radix.py: the digit order must be lax.sort's order, key by key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops.radix import in_order, sort_by_keys, stable_argsort

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


@pytest.mark.parametrize("how", ["sorted", "one_swap", "tie_then_later_key",
                                 "as_drawn", "one_row"])
@pytest.mark.parametrize("dtypes", [
    (np.int64,), (np.float64,), (np.bool_, np.int32),
    (np.bool_, np.bool_, np.int64, np.float32)],
    ids=["int64", "float64", "flag_int32", "flags_int64_float32"])
def test_in_order_is_whether_the_sort_would_move_a_row(dtypes, how):
    """`in_order` reads the passes' own digits: true exactly where
    `stable_argsort` is the identity — NaN last, -0 tied with +0, a run of
    flags packed into one digit, a tie decided by the next key."""
    keys = [_column(d) for d in dtypes]
    if dtypes[-1] != np.bool_:
        with np.errstate(invalid="ignore"):     # inf // 64
            keys[-1] = np.where(np.isfinite(keys[-1].astype(np.float64)),
                                keys[-1] // 64, keys[-1])      # ties
    perm = np.asarray(stable_argsort([jnp.asarray(k) for k in keys]))
    if how != "as_drawn":
        keys = [k[perm] for k in keys]
    if how == "one_swap":
        # two adjacent rows that differ somewhere, the other way round
        digits = np.stack([np.nan_to_num(k.astype(np.float64), nan=1e300)
                           for k in keys])
        differ = np.flatnonzero((digits[:, 1:] != digits[:, :-1]).any(0))
        at = int(differ[len(differ) // 2])
        for k in keys:
            k[[at, at + 1]] = k[[at + 1, at]]
    elif how == "tie_then_later_key" and len(keys) > 1:
        keys[0][:] = keys[0][0]         # the first key says nothing
        keys[-1] = np.sort(keys[-1])[::-1].copy()
        keys[-1][-1] = keys[-1][0]      # the last row belongs first
    elif how == "one_row":
        keys = [k[:1] for k in keys]
    keys = [jnp.asarray(k) for k in keys]
    identity = np.array_equal(np.asarray(stable_argsort(keys)),
                              np.arange(keys[0].shape[0]))
    assert bool(jax.jit(in_order)(keys)) == identity
    assert identity == (how in ("sorted", "one_row") or (
        how == "tie_then_later_key" and len(dtypes) == 1))
