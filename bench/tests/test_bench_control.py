"""The control of every cell's check: the plain reference in the program's
place at bfloat16, the precision below the configurations' float32, comes
out not correct; at float32 it comes out correct."""
import jax.numpy as jnp
import pytest

from bench import check, control, harness
from bench.tests import tiny


@pytest.mark.parametrize("cell", tiny.cells())
def test_control_fails_and_float32_passes(cell):
    limits = harness.load_cell(cell)[0]["limits"]
    over = tiny.overrides(cell)
    low = control.readings(cell, tiny.SEED, dtype=jnp.bfloat16, overrides=over)
    same = control.readings(cell, tiny.SEED, dtype=jnp.float32, overrides=over)
    assert not check.passed(check.compare(low, limits)), low
    assert check.passed(check.compare(same, limits)), same
