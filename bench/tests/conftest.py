"""The benchmark's tests run its float32 path: float64 is switched off
for each test and restored after, whatever another conftest set in this
worker."""
import jax
import pytest


@pytest.fixture(autouse=True)
def float32_only():
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", was)
