"""Property-based tests for the budget ledger (docs/ARCHITECTURE.md,
"Event lifecycle"): random charge/release/reset sequences must keep the
delta-updated usage exactly in step with an independent audit sweep,
residuals must never go negative, and the serving layer's slot sizing
must be monotone with its pow2 rounding pinned at bucket boundaries.
"""
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.ledger import BudgetLedger, slots_from_usage

NUM_LAYERS = 4


def _world(rng, X, Z, capacitated=True):
    topo = SimpleNamespace(
        num_servers=Z,
        r_capacity=(rng.uniform(5.0, 50.0, Z) if capacitated else None),
        B_capacity=(rng.uniform(5.0, 50.0, Z) if capacitated else None))
    fleet = SimpleNamespace(
        server=rng.integers(0, Z, X),
        split=np.full(X, NUM_LAYERS, np.int64),    # all start on-device
        r=np.zeros(X), B=np.zeros(X))
    return topo, fleet


def _mutate(rng, fleet, ledger, u):
    """One lifecycle event for user ``u``, applied to the fleet table
    and mirrored as ledger deltas — exactly the discipline the event
    pipeline follows (release old row, write row, charge new row)."""
    Z = ledger.topo.num_servers
    ledger.release_rows(fleet, [u], NUM_LAYERS)
    kind = rng.integers(3)
    if kind == 0:                                   # degrade to device
        fleet.split[u] = NUM_LAYERS
        fleet.r[u] = fleet.B[u] = 0.0
    else:                                           # (re)admit / move
        fleet.split[u] = int(rng.integers(0, NUM_LAYERS))
        fleet.server[u] = int(rng.integers(0, Z))
        fleet.r[u] = float(rng.uniform(0.0, 10.0))
        fleet.B[u] = float(rng.uniform(0.0, 10.0))
    offl = fleet.split[u] < NUM_LAYERS
    ledger.charge([fleet.server[u]],
                  [fleet.r[u] if offl else 0.0],
                  [fleet.B[u] if offl else 0.0])


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10 ** 6),
       X=st.integers(min_value=1, max_value=24),
       Z=st.integers(min_value=1, max_value=5),
       capacitated=st.booleans())
def test_ledger_deltas_never_drift_and_residuals_stay_nonnegative(
        seed, X, Z, capacitated):
    rng = np.random.default_rng(seed)
    topo, fleet = _world(rng, X, Z, capacitated)
    ledger = BudgetLedger(topo)
    ledger.reset_from_fleet(fleet, NUM_LAYERS)
    for _ in range(40):
        op = rng.integers(10)
        if op == 0:     # a static replan supersedes all prior deltas
            ledger.reset_from_fleet(fleet, NUM_LAYERS)
        else:
            _mutate(rng, fleet, ledger, int(rng.integers(X)))
        assert ledger.drift(fleet, NUM_LAYERS) < 1e-9
        r_res, B_res = ledger.residuals()
        if not capacitated:
            assert r_res is None and B_res is None
        else:
            assert np.all(r_res >= 0.0) and np.all(B_res >= 0.0)
            # float add/subtract noise can leave usage at ~-1e-16, so
            # the residual may top capacity by one ulp — never more
            assert np.all(r_res <= np.asarray(topo.r_capacity) + 1e-9)
    # full teardown returns usage to zero (no leaked charge)
    ledger.release_rows(fleet, np.arange(X), NUM_LAYERS)
    assert np.abs(ledger.r_used).max() < 1e-9
    assert np.abs(ledger.B_used).max() < 1e-9


def _pow2_ref(r, per, lo, hi):
    n = max(int(np.ceil(r / per)), lo)
    p = 1 << (n - 1).bit_length() if n > 1 else 1
    return min(p, hi)


@settings(max_examples=40, deadline=None)
@given(usage=st.lists(st.floats(min_value=0.0, max_value=500.0),
                      min_size=1, max_size=12),
       per=st.floats(min_value=0.25, max_value=16.0),
       lo=st.integers(min_value=1, max_value=8),
       hi=st.integers(min_value=8, max_value=128))
def test_slots_from_usage_monotone_and_pow2(usage, per, lo, hi):
    got = slots_from_usage(usage, per, min_slots=lo, max_slots=hi)
    # matches the scalar reference on every element
    ref = [_pow2_ref(r, per, lo, hi) for r in usage]
    np.testing.assert_array_equal(got, ref)
    # monotone: more admitted work never shrinks the pool
    order = np.argsort(usage)
    np.testing.assert_array_equal(np.asarray(got)[order],
                                  np.sort(got))
    # every count is a power of two unless clipped by max_slots
    for s in got:
        assert s == hi or (int(s) & (int(s) - 1)) == 0


@settings(max_examples=40, deadline=None)
@given(k=st.integers(min_value=1, max_value=64),
       per=st.floats(min_value=0.5, max_value=8.0))
def test_slots_pow2_pinned_at_bucket_boundaries(k, per):
    """r = k*per sits exactly on a bucket edge: ceil gives k, and the
    tiniest nudge past the edge moves up a bucket — the pow2 rounding
    must not blur the boundary."""
    at = slots_from_usage([k * per], per, min_slots=1, max_slots=4096)[0]
    assert at == _pow2_ref(k * per, per, 1, 4096)
    just_over = slots_from_usage([k * per * (1 + 1e-9) + 1e-9], per,
                                 min_slots=1, max_slots=4096)[0]
    assert just_over == _pow2_ref(k * per + 1e-6, per, 1, 4096)
    assert just_over >= at


def test_overloaded_flags_capacity_churn():
    topo = SimpleNamespace(num_servers=2,
                           r_capacity=np.asarray([10.0, 10.0]),
                           B_capacity=None)
    ledger = BudgetLedger(topo)
    ledger.charge([0, 1], [8.0, 8.0], [0.0, 0.0])
    assert not ledger.overloaded().any()
    topo.r_capacity = np.asarray([4.0, 10.0])   # fault shrank server 0
    np.testing.assert_array_equal(ledger.overloaded(), [True, False])
