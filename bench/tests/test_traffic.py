"""Traffic generators: the Kronecker pool and the lap stream."""
import numpy as np
import pytest

from bench.traffic import kronecker

SPEC = {"graph_scale": 8, "draws": 16 * 2**8, "initiator": [0.57, 0.19, 0.19, 0.05],
        "pool_seed": 3}


@pytest.fixture(scope="module")
def pool():
    return kronecker.pool(SPEC, cache=None)


def test_pool_is_deterministic_and_simple(pool):
    again = kronecker.pool(SPEC, cache=None)
    np.testing.assert_array_equal(pool, again)
    assert pool.dtype == np.int32 and pool.shape[1] == 2
    assert np.all(pool[:, 0] < pool[:, 1])  # canonical, so no self-loops
    keys = pool[:, 0].astype(np.int64) << 32 | pool[:, 1]
    assert len(np.unique(keys)) == len(keys)  # no duplicate edges
    assert pool.max() < 2 ** SPEC["graph_scale"]
    # the Kronecker skew: the busiest vertex has far more than the mean degree
    deg = np.bincount(pool.ravel(), minlength=2 ** SPEC["graph_scale"])
    assert deg.max() > 5 * deg.mean()


def test_pool_cache_round_trip(pool, tmp_path):
    first = kronecker.pool(SPEC, cache=tmp_path)
    assert len(list(tmp_path.glob("*.npy"))) == 1
    np.testing.assert_array_equal(first, pool)
    np.testing.assert_array_equal(kronecker.pool(SPEC, cache=tmp_path), pool)


def test_stream_is_a_seeded_permutation(pool):
    n = 2 ** SPEC["graph_scale"]
    a = kronecker.stream(pool, n, seed=2**31 + 11).take(0, len(pool))
    b = kronecker.stream(pool, n, seed=2**31 + 11).take(0, len(pool))
    c = kronecker.stream(pool, n, seed=2**31 + 12).take(0, len(pool))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # the same graph up to relabelling: same degree sequence
    deg = lambda e: np.sort(np.bincount(e.ravel(), minlength=n))
    np.testing.assert_array_equal(deg(a), deg(pool))
    np.testing.assert_array_equal(deg(c), deg(pool))


def test_laps_are_vertex_disjoint_copies(pool):
    n, size = 2 ** SPEC["graph_scale"], len(pool)
    st = kronecker.stream(pool, n, seed=5)
    lap0 = st.take(0, size)
    lap2 = st.take(2 * size, size)
    np.testing.assert_array_equal(lap2, lap0 + 2 * n)
    # a batch straddling a lap boundary continues in the next copy
    cross = st.take(size - 3, 6)
    np.testing.assert_array_equal(cross[:3], lap0[-3:])
    np.testing.assert_array_equal(cross[3:], lap0[:3] + n)
    whole = st.take(0, 3 * size)
    keys = np.minimum(whole[:, 0], whole[:, 1]).astype(np.int64) << 32 | np.maximum(
        whole[:, 0], whole[:, 1])
    assert len(np.unique(keys)) == len(keys)


def test_pool_is_a_prefix_at_the_stream_scale():
    # a short prefix of a large-scale stream: ids spread over 2^scale, with
    # far fewer edges than the whole stream's edge_factor * 2^scale
    spec = {**SPEC, "graph_scale": 20, "draws": 4096}
    pool = kronecker.pool(spec, cache=None)
    assert 0.9 * 4096 < len(pool) <= 4096
    assert pool.max() < 2**20 and pool.max() > 2**16


def test_ids_stay_int32(pool):
    st = kronecker.stream(pool, 2**20, seed=1)
    st.take(2047 * len(pool), 10)  # the last lap that fits
    with pytest.raises(ValueError):
        st.take(2048 * len(pool), 10)
