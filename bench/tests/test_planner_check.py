"""The planner cells' check: sound runs pass it; the control (the
reference in bfloat16, in the program's place) and each fault planted
under the timed path fail it.  CPU, 3 000 users."""
import jax
import numpy as np
import pytest

from helpers import run_small

CELL = "plan.nin.vehicular"


def test_sound_run_is_correct():
    res = run_small(CELL)
    assert res["correct"], res["checks"]
    assert res["checks"]["rows_checked"]["value"] > 0
    # every program the window runs was built in set-up
    wc = res["window_compiles"]
    assert (wc["compiles"], wc["cache_loads"]) == (0, 0), wc


def test_control_fails():
    res = run_small(CELL, hooks={"control": True})
    assert not res["correct"], res["checks"]
    # the program's own numbers, read in the same run, pass
    prog = res["program_checks"]
    assert all(c["ok"] for c in prog.values()), prog


def _state_unchanged(session):
    session.fleet.scatter = lambda *a, **k: None


def _half_batch(session):
    real = session.fleet.scatter

    def half(users, server, res, R=None):
        k = len(users) // 2
        real(users[:k], np.asarray(server)[:k],
             jax.tree.map(lambda a: np.asarray(a)[:k], res), R)
    session.fleet.scatter = half


def _answer_altered(session):
    real = session.fleet.scatter
    fleet, M = session.fleet, session.profile.num_layers

    def altered(users, server, res, R=None):
        real(users, server, res, R)
        fleet.split[users] = (fleet.split[users] + 1) % (M + 1)
    session.fleet.scatter = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_fails(fault):
    res = run_small(CELL, hooks={"session": fault})
    assert not res["correct"], res["checks"]
