"""Shared fixtures.  NOTE: no XLA_FLAGS manipulation here — tests must see
the real single CPU device (the 512-device dry-run sets its own flags in
repro.launch.dryrun, run as a separate process)."""
import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="session")
def _x64_off():
    jax.config.update("jax_enable_x64", False)
    yield
