"""Property tests for Alg. 1 weighted interleaving and DWP scaling."""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import interleave


@st.composite
def weight_vectors(draw):
    n = draw(st.integers(min_value=1, max_value=12))
    w = draw(st.lists(st.floats(min_value=0.0, max_value=10.0,
                                allow_nan=False), min_size=n, max_size=n))
    if sum(w) <= 0:
        w[0] = 1.0
    return np.asarray(w)


@given(weight_vectors(), st.integers(min_value=64, max_value=8192))
@settings(max_examples=60, deadline=None)
def test_alg1_fractions_match_weights(w, pages):
    """Per-node page fractions reproduce the target weights (Alg. 1 claim)."""
    w = interleave.normalize(w)
    a = interleave.weighted_interleave(pages, w)
    frac = interleave.page_fractions(a, len(w))
    # accuracy is limited by round-robin granularity: one page per sub-range
    # boundary per node.
    tol = len(w) * 1.5 / pages + 1e-9
    np.testing.assert_allclose(frac, w, atol=tol)


@given(weight_vectors())
@settings(max_examples=40, deadline=None)
def test_alg1_zero_weight_nodes_get_no_pages(w):
    w = np.asarray(w)
    w[0] = 0.0
    if w.sum() <= 0:
        w[-1] = 1.0
    a = interleave.weighted_interleave(1024, w)
    assert not (a == 0).any() or w[0] > 0


def test_alg1_uniform_equals_round_robin():
    a = interleave.weighted_interleave(100, np.ones(4))
    frac = interleave.page_fractions(a, 4)
    np.testing.assert_allclose(frac, 0.25, atol=0.01)


@given(weight_vectors(), st.floats(min_value=0, max_value=1))
@settings(max_examples=60, deadline=None)
def test_dwp_weights_preserve_cluster_ratios(w, dwp):
    """DWP scaling preserves relative weights within worker/non-worker
    clusters (paper Observation 3)."""
    w = interleave.normalize(w)
    n = len(w)
    workers = list(range(max(1, n // 2)))
    out = interleave.dwp_weights(w, workers, dwp)
    assert abs(out.sum() - 1.0) < 1e-9
    # ratios inside the worker cluster preserved
    wi = [i for i in workers if w[i] > 1e-12 and out[i] > 1e-12]
    for a, b in zip(wi, wi[1:]):
        np.testing.assert_allclose(out[a] / out[b], w[a] / w[b], rtol=1e-6)
    nw = [i for i in range(n) if i not in workers
          and w[i] > 1e-12 and out[i] > 1e-12]
    for a, b in zip(nw, nw[1:]):
        np.testing.assert_allclose(out[a] / out[b], w[a] / w[b], rtol=1e-6)


def test_dwp_extremes():
    w = interleave.normalize(np.asarray([4.0, 3, 2, 1]))
    workers = [0, 1]
    w0 = interleave.dwp_weights(w, workers, 0.0)
    np.testing.assert_allclose(w0, w)
    w1 = interleave.dwp_weights(w, workers, 1.0)
    assert w1[2] == w1[3] == 0.0
    np.testing.assert_allclose(w1[:2].sum(), 1.0)
    np.testing.assert_allclose(w1[0] / w1[1], w[0] / w[1])


@given(weight_vectors(), st.floats(min_value=0.05, max_value=1))
@settings(max_examples=40, deadline=None)
def test_migration_plan_is_minimal_diff(w, dwp):
    w = interleave.normalize(w)
    if len(w) < 2:
        return
    workers = [0]
    a0 = interleave.weighted_interleave(
        2048, interleave.dwp_weights(w, workers, 0.0))
    plan = interleave.plan_migration(
        a0, interleave.dwp_weights(w, workers, dwp))
    # every move actually changes the node, and untouched pages are identical
    assert (plan.moves[:, 1] != plan.moves[:, 2]).all()
    untouched = np.setdiff1d(np.arange(2048), plan.moves[:, 0])
    np.testing.assert_array_equal(plan.old_assignment[untouched],
                                  plan.new_assignment[untouched])


def test_migration_moves_toward_workers_when_dwp_increases():
    w = interleave.normalize(np.asarray([3.0, 2.0, 1.0, 1.0]))
    workers = [0, 1]
    a0 = interleave.weighted_interleave(
        4096, interleave.dwp_weights(w, workers, 0.0))
    plan = interleave.plan_migration(
        a0, interleave.dwp_weights(w, workers, 0.4))
    frac0 = interleave.page_fractions(plan.old_assignment, 4)[:2].sum()
    frac1 = interleave.page_fractions(plan.new_assignment, 4)[:2].sum()
    assert frac1 > frac0


def test_capacity_capped_weights_waterfill():
    w = interleave.normalize(np.asarray([6.0, 3.0, 1.0]))
    cap = np.asarray([0.4, np.inf, np.inf])
    out = interleave.capacity_capped_weights(w, cap)
    assert out.sum() == pytest.approx(1.0)
    assert out[0] == pytest.approx(0.4)
    # excess redistributes proportionally to the unclamped weights (3:1)
    assert out[1] / out[2] == pytest.approx(3.0)
    # cascading clamp: redistribution may push another node over its cap
    out2 = interleave.capacity_capped_weights(
        w, np.asarray([0.4, 0.35, np.inf]))
    assert out2 == pytest.approx([0.4, 0.35, 0.25])
    # uncapped (all inf) is the identity
    np.testing.assert_allclose(
        interleave.capacity_capped_weights(w, np.full(3, np.inf)), w)
    # infeasible caps (sum < 1) degrade to the capacity shape
    out3 = interleave.capacity_capped_weights(w, np.asarray([0.2, 0.2, 0.1]))
    np.testing.assert_allclose(out3, np.asarray([0.4, 0.4, 0.2]))
    # every positive-weight node capped, excess landing on zero-weight
    # uncapped nodes: must water-fill evenly, not NaN (inf/inf)
    out4 = interleave.capacity_capped_weights(
        np.asarray([0.5, 0.5, 0.0]), np.asarray([0.3, 0.3, np.inf]))
    np.testing.assert_allclose(out4, np.asarray([0.3, 0.3, 0.4]))
