"""The reference's handling of coin ties: what rests on a coin within the
division's error of its quotient is the platform's choice."""
import numpy as np

from bench.reference import nbsi


def state(r=4):
    st = nbsi.State.fresh(r)
    st.f1[:] = [[0, 1]] * r
    st.f2[:] = [[1, 2]] * r
    st.chi[:] = 3
    st.m_seen = 10
    return st


def test_unsure_estimators_are_left_out_of_f2_and_has_f3():
    ref = state()
    ref.unsure[1] = True
    prog = {f: np.copy(getattr(ref, f)) for f in nbsi.FIELDS}
    prog["f2"][1] = [1, 5]  # the platform's choice at a tie
    assert sum(nbsi.mismatches(prog, ref).values()) == 0
    prog["has_f3"][2] = True  # a sure estimator that differs
    prog["chi"][1] = 4  # chi never rests on the coin
    assert nbsi.mismatches(prog, ref) == {
        "f1": 0, "chi": 1, "f2": 0, "has_f3": 1, "m_seen": 0}


def test_settled_takes_only_unsure_fields_from_the_program():
    ref = state()
    ref.unsure[1] = True
    prog = {f: np.copy(getattr(ref, f)) for f in nbsi.FIELDS}
    prog["has_f3"][:] = True
    got = nbsi.settled(prog, ref)
    np.testing.assert_array_equal(got.has_f3, [False, True, False, False])


def test_ties_are_rare_and_reset_with_the_level1_edge():
    rng = np.random.default_rng(3)
    r, n = 4096, 256
    draws = nbsi.Draws(2**31 + 5, r)
    st = nbsi.State.fresh(r)
    for i in range(6):
        W = rng.integers(0, 40, (n, 2)).astype(np.int32)
        W = W[W[:, 0] != W[:, 1]]
        st = nbsi.update(st, W, draws, i)
    assert st.unsure.sum() < r // 100
