"""Compile rehearsals for the TPU v5e: the main path's Pallas kernel and
jitted loops, compiled for a described ``v5e:2x2`` topology that is not
attached. Nothing runs; the chip's compiler refuses what would not fit or
tile, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file. Kernels are compiled with ``interpret=False`` passed explicitly,
since the default picks interpret mode off the TPU. The persistent
compilation cache is off for this module: entries compiled for a described
chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.batch.problems import BatchedProblem
from repro.batch.solvers import (
    build_batched_log_sketch,
    get_batched_solver,
    sparse_log_potentials,
)
from repro.core import default_cap, s0
from repro.distributed.sharding import leading_axis_specs
from repro.kernels.ops import gathered_kernel
from repro.launch.serve_ot import _make_request_problems

N_LARGE = 2 ** 17
CAP_LARGE = 10_127_143  # default_cap(4 * s0(2^17))
KERNEL_TEMP_LIMIT = 2e9  # bytes; the (k, 128)-padded layout needed 19.32 GB
HBM_BYTES = 15.75e9  # what the v5e compiler offers a program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no description here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)  # the chip path runs float32
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _temp_bytes(compiled) -> int:
    return compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("cost", ["sqeuclidean", "wfr"])
def test_gathered_kernel_fits_v5e(one_chip, cost):
    """The lane-dense gathered kernel at the advertised size: a Pallas
    custom call, under 2 GB of temporaries."""
    assert default_cap(4 * s0(N_LARGE)) == CAP_LARGE
    pts = _spec((N_LARGE, 3), jnp.float32, one_chip)
    idx = _spec((CAP_LARGE,), jnp.int32, one_chip)
    fn = jax.jit(lambda x, r, c: gathered_kernel(
        x, x, r, c, eps=0.1, cost=cost, eta=0.5, interpret=False))
    compiled = fn.lower(pts, idx, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _temp_bytes(compiled) < KERNEL_TEMP_LIMIT, _temp_bytes(compiled)


def test_sparse_log_loop_compiles_v5e(one_chip):
    """The log-domain iteration behind ``spar_sink_mf(stabilize=True)`` at
    n = 2^17 with the advertised sketch capacity (B = 1)."""
    loop = jax.jit(sparse_log_potentials,
                   static_argnames=("n", "m", "tol", "max_iter"))
    ids = _spec((1, CAP_LARGE), jnp.int32, one_chip)
    vec = _spec((1, N_LARGE), jnp.float32, one_chip)
    one = _spec((1,), jnp.float32, one_chip)
    compiled = loop.lower(
        ids, ids, _spec((1, CAP_LARGE), jnp.float32, one_chip), ids, vec, vec,
        one, one, n=N_LARGE, m=N_LARGE, tol=1e-6, max_iter=1000,
    ).compile()
    assert _temp_bytes(compiled) < HBM_BYTES


def _batched_spar_sink_log_args(batch: int, n: int):
    """(BatchedProblem, sketch) of the serving traffic, built on the host:
    the executor's inputs for one ``spar_sink_log`` bucket."""
    problems = _make_request_problems(batch, (n,), seed=0)
    keys = [jax.random.PRNGKey(i) for i in range(batch)]
    bp = BatchedProblem.from_problems(problems, bucket=(n, n), materialize_cost=False)
    return bp, build_batched_log_sketch(problems, keys, 8.0 * s0(n))


def _compile_batched(bp, aux, shardings):
    solver = get_batched_solver("spar_sink_log")
    fn = jax.jit(lambda bp, aux: solver(bp, aux, max_iter=2000))
    specs = jax.tree_util.tree_map(
        lambda x, s: _spec(x.shape, x.dtype, s), (bp, aux), shardings
    )
    return fn.lower(*specs).compile()


def test_batched_spar_sink_log_compiles_v5e(one_chip):
    """The executor's batched ``spar_sink_log`` program at B = 64, n = 256."""
    bp, aux = _batched_spar_sink_log_args(64, 256)
    shardings = jax.tree_util.tree_map(lambda _: one_chip, (bp, aux))
    compiled = _compile_batched(bp, aux, shardings)
    assert _temp_bytes(compiled) < HBM_BYTES


def test_batched_spar_sink_log_compiles_v5e_four_chips(topo):
    """The same program with the batch axis split over a 4-chip ``data``
    mesh, as ``BucketedExecutor(mesh=...)`` places it: no leaf replicated."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    bp, aux = _batched_spar_sink_log_args(64, 256)
    specs = leading_axis_specs(mesh, (bp, aux))
    for spec in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)
    ):
        assert spec[0] is not None, spec
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
    )
    compiled = _compile_batched(bp, aux, shardings)
    assert _temp_bytes(compiled) < HBM_BYTES
