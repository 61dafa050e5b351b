"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see the host's single device."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
