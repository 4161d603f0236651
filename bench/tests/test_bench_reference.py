"""The reference's moments of the extra draws of a Poisson pair, against
the Poisson distribution summed term by term in float64."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference


def _exact(r: float):
    k = np.arange(0, 200)
    logpmf = k * math.log(r) - r - np.array([math.lgamma(v + 1) for v in k])
    pmf = np.exp(logpmf)
    d = np.maximum(k - 1, 0)
    mean = float(np.sum(pmf * d))
    return mean, float(np.sum(pmf * d * d)) - mean**2


@pytest.mark.parametrize("r", [1e-4, 3e-3, 9.9e-3, 1.01e-2, 0.1, 1.0, 5.0])
def test_dup_pair_moments_match_the_poisson_distribution(r):
    mean, var = reference.dup_pair_moments(jnp.float32(r))
    want_mean, want_var = _exact(r)
    assert float(mean) == pytest.approx(want_mean, rel=2e-3)
    assert float(var) == pytest.approx(want_var, rel=2e-3)
