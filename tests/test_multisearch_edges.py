"""Multisearch edge-shape parity: the Pallas kernel's block boundaries,
empty inputs and INF64 sentinel queries; the XLA merge against
``np.searchsorted``.

Deliberately hypothesis-free (unlike tests/test_kernels.py, which gates on
the dev dep at module level): this is the regression coverage for the
``n == 0`` uninitialized-kernel-output bugfix, and it must run in a base
install — a container without requirements-dev must not silently skip it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import multisearch_counts_ref


class TestMultisearchEdgeShapes:
    # block-boundary sweep: q/n at exact multiples and +-1 of the blocks
    # (q_block=32, k_block=64 below), plus the empty-structure degenerate —
    # n == 0 used to return `lt` from a never-launched kernel uninitialized
    @pytest.mark.parametrize(
        "n,q",
        [
            (0, 1), (0, 5), (0, 33),   # empty keys: every count is 0
            (1, 0), (64, 0), (0, 0),   # empty queries: empty outputs
            (63, 31), (64, 32), (65, 33),      # exactly one block +-1
            (127, 63), (128, 64), (129, 65),   # two blocks +-1
            (64, 96), (192, 32),               # mixed multiples
        ],
    )
    def test_block_boundaries_and_empty(self, n, q):
        rng = np.random.default_rng(7 * n + q)
        keys = jnp.sort(jnp.asarray(rng.integers(0, 200, n), jnp.int64))
        qs = jnp.asarray(rng.integers(-5, 205, q), jnp.int64)
        lt, le = ops.multisearch_counts_op(keys, qs, q_block=32, k_block=64)
        elt, ele = multisearch_counts_ref(keys, qs)
        assert lt.shape == le.shape == (q,)
        np.testing.assert_array_equal(np.asarray(lt), np.asarray(elt))
        np.testing.assert_array_equal(np.asarray(le), np.asarray(ele))

    @pytest.mark.parametrize("n", [0, 63, 64, 65])
    def test_inf64_queries(self, n):
        """INF64 sentinel queries (the routed-multisearch padding value) must
        count key padding in neither bound: le clamps to n, and with n == 0
        the short-circuit keeps both counts zero instead of garbage."""
        inf64 = np.iinfo(np.int64).max
        rng = np.random.default_rng(n)
        keys = jnp.sort(jnp.asarray(rng.integers(0, 100, n), jnp.int64))
        qs = jnp.asarray(np.array([inf64, 0, inf64, 50], np.int64))
        lt, le = ops.multisearch_counts_op(keys, qs, q_block=32, k_block=64)
        elt, ele = multisearch_counts_ref(keys, qs)
        np.testing.assert_array_equal(np.asarray(lt), np.asarray(elt))
        np.testing.assert_array_equal(np.asarray(le), np.asarray(ele))
        assert int(le[0]) == n  # every real key is <= INF64


# -- the XLA multisearch: the merge -------------------------------------------
INF64 = np.iinfo(np.int64).max


def _case(name):
    """(sorted keys, queries) of one parity case: int64, with runs of equal
    values, negative values (pack2 of -1 endpoints) and INF64 sentinels."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "duplicates":
        keys = np.repeat(rng.integers(0, 12, 40), rng.integers(1, 6, 40))
        qs = rng.integers(-2, 14, 70)
    elif name == "inf64":
        keys = np.concatenate([rng.integers(-5, 50, 30), [INF64] * 7])
        qs = np.concatenate([rng.integers(-5, 55, 20), [INF64] * 4, [-(2**63)]])
    elif name == "empty_keys":
        keys, qs = np.zeros(0), rng.integers(-3, 3, 9)
    elif name == "empty_queries":
        keys, qs = rng.integers(0, 9, 17), np.zeros(0)
    elif name == "q_much_more_than_n":
        keys, qs = rng.integers(0, 6, 5), rng.integers(-1, 8, 500)
    elif name == "n_much_more_than_q":
        keys, qs = rng.integers(0, 1 << 40, 600), rng.integers(0, 1 << 40, 3)
    elif name == "packed":
        src, lo = rng.integers(0, 6, 90), rng.integers(0, 1 << 31, 90)
        keys = (src << 32) | lo
        qs = np.concatenate([keys[:40], keys[:20] + 1, (src[:20] << 32), [-1]])
    elif name == "runs_across_rows":
        # equal-value runs several times longer than the scans' 128-entry
        # rows, and n + q not a multiple of 128
        keys = np.repeat(rng.integers(0, 9, 6), rng.integers(100, 400, 6))
        qs = rng.integers(-1, 10, 301)
    keys = np.sort(np.asarray(keys, np.int64))
    return keys, np.asarray(qs, np.int64)


CASES = ["duplicates", "inf64", "empty_keys", "empty_queries",
         "q_much_more_than_n", "n_much_more_than_q", "packed",
         "runs_across_rows"]


class TestXlaMultisearch:
    """The merge gives ``np.searchsorted``'s insertion points exactly, on
    both sides, and ``multisearch_lt`` gives the left one."""

    @pytest.mark.parametrize("name", CASES)
    def test_parity(self, name):
        from repro.primitives.search import _merge_bounds

        keys, qs = _case(name)
        lt, le = _merge_bounds(jnp.asarray(keys), jnp.asarray(qs), le=True)
        assert lt.dtype == le.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(lt), np.searchsorted(keys, qs, "left"))
        np.testing.assert_array_equal(np.asarray(le), np.searchsorted(keys, qs, "right"))
        only = _merge_bounds(jnp.asarray(keys), jnp.asarray(qs), le=False)
        np.testing.assert_array_equal(np.asarray(only), np.asarray(lt))

    @pytest.mark.parametrize("name", ["duplicates", "runs_across_rows"])
    def test_entry_points(self, name):
        """``multisearch_bounds`` and ``multisearch_lt`` under jit, as the
        ingest programs call them."""
        from repro.primitives.search import multisearch_bounds, multisearch_lt

        keys, qs = _case(name)
        args = jnp.asarray(keys), jnp.asarray(qs)
        lt, le = jax.jit(multisearch_bounds)(*args)
        np.testing.assert_array_equal(np.asarray(lt), np.searchsorted(keys, qs, "left"))
        np.testing.assert_array_equal(np.asarray(le), np.searchsorted(keys, qs, "right"))
        np.testing.assert_array_equal(np.asarray(jax.jit(multisearch_lt)(*args)), lt)

    @pytest.mark.parametrize("tenants", [1, 3])
    def test_parity_vmapped(self, tenants):
        """A leading tenant axis, as the engine's banked plans vmap the
        update."""
        from repro.primitives.search import _merge_bounds

        rng = np.random.default_rng(5)
        keys = np.sort(rng.integers(0, 30, (tenants, 64)), axis=1).astype(np.int64)
        keys[-1, -9:] = INF64
        qs = rng.integers(-1, 32, (tenants, 200)).astype(np.int64)
        qs[0, :5] = INF64
        args = jnp.asarray(keys), jnp.asarray(qs)
        lt, le = map(np.asarray, jax.vmap(lambda k, x: _merge_bounds(k, x, le=True))(*args))
        only = jax.vmap(lambda k, x: _merge_bounds(k, x, le=False))(*args)
        np.testing.assert_array_equal(np.asarray(only), lt)
        for t in range(tenants):
            np.testing.assert_array_equal(lt[t], np.searchsorted(keys[t], qs[t], "left"))
            np.testing.assert_array_equal(le[t], np.searchsorted(keys[t], qs[t], "right"))

    @pytest.mark.parametrize("n,q", [(64, 4096), (1 << 16, 4)])
    def test_path_scope(self, n, q):
        """The search is named ``multisearch/merge``, where the chip trace's
        readers find it (``tests/test_tpu_compile.py`` checks that the TPU
        compiler keeps the scope on the search's ops)."""
        from repro.primitives.search import multisearch_bounds

        keys = jax.ShapeDtypeStruct((n,), jnp.int64)
        qs = jax.ShapeDtypeStruct((q,), jnp.int64)
        text = jax.jit(multisearch_bounds).lower(keys, qs).as_text(debug_info=True)
        assert "multisearch/merge/" in text
