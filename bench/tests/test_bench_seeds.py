"""The training traffic comes from ``--seed`` alone: the same seed gives
the same batches, another seed other ones, every row differs, and seeds
past 32 bits stay distinct streams."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import numpy as np
import pytest

import path_train
from common import jax_key, np_rng

CELL = {"traffic": {"clients": 3, "local_steps": 4, "seq_len": 16},
        "config": {"batch_size": 2, "vocab_size": 50257}}


def test_same_seed_same_batches():
    a, b = (path_train.first_round_batches(CELL, 2**40 + 3) for _ in range(2))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].shape == (4, 3, 2, 16)


@pytest.mark.parametrize("seeds", [(1, 2), (5, 2**40 + 5)])
def test_other_seed_other_batches(seeds):
    a, b = (path_train.first_round_batches(CELL, s)[0] for s in seeds)
    assert not np.array_equal(a, b)


def test_rows_differ_and_labels_are_the_next_ids():
    toks, labels = path_train.first_round_batches(CELL, 2**33 + 1)
    rows = toks.reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)
    np.testing.assert_array_equal(toks[..., 1:], labels[..., :-1])


def test_large_seeds_are_distinct_streams():
    import jax
    assert not np.array_equal(np_rng(5, 1).integers(0, 2**31, 8),
                              np_rng(2**40 + 5, 1).integers(0, 2**31, 8))
    k1, k2 = jax_key(5, 0), jax_key(2**40 + 5, 0)
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
