"""The queue kernels compile for a TPU v5e at the main path's geometries.

Each test compiles ahead of time for a described (not attached) v5e chip
and asserts that the Pallas kernel is in the compiled program.  The
topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
worker that runs this file keeps it.  The rule tests show that the
kernels' predicates (``kernels.ring_tile``) reject exactly what the TPU
compiler rejects.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.kernels.queue_push.kernel import ring_scatter, ring_slice
from repro.kernels.queue_steal.kernel import ring_gather
from repro.kernels.queue_transfer.kernel import ring_transfer
from repro.kernels.ring_tile import (block_rows_ok, cut_rows, narrow,
                                     supported_dtype, widen)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described chip's compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


I32 = jnp.int32
SCALAR = ((), I32)

# (op, capacity, width, bound, dtype) at the main path's real geometries.
MAIN_PATH = [
    # Fig. 9 DAG lanes: int32 node ids.
    ("scatter", 16384, 1, 256, I32),       # worker push of 64 x 4 children
    ("slice", 16384, 1, 64, I32),          # worker pop of 64
    ("gather", 16384, 1, 1024, I32),       # max_steal
    ("transfer", 16384, 1, 1024, I32),
    ("scatter", 16384, 1, 1, I32),         # the one-item seeding push
    # UTS T3 lanes: a node is six int32 words (digest, height).
    ("scatter", 16384, 6, 512, I32),       # worker push of 64 x 8 children
    ("slice", 16384, 6, 64, I32),          # worker pop of 64
    ("gather", 16384, 6, 8192, I32),       # max_steal
    ("transfer", 16384, 6, 8192, I32),
    ("scatter", 16384, 6, 2048, I32),      # the root's 2,000 children
    # fig9-pareto lanes: int32 node ids, 512 slots a lane.
    ("slice", 16384, 1, 512, I32),         # worker pop of the free slots
    ("scatter", 16384, 1, 2048, I32),      # worker push of 512 x 4 children
    ("gather", 16384, 1, 8192, I32),       # max_steal
    ("transfer", 16384, 1, 8192, I32),
    # DD solver items: pop 4, push batch x explore_width = 32.
    ("slice", 4096, 1, 4, I32),
    ("scatter", 4096, 1, 32, I32),
    # Decode request ring: max_prompt + 4 int32s, pop n_slots.
    ("slice", 128, 20, 4, I32),
    ("gather", 128, 20, 64, I32),
    ("transfer", 128, 20, 64, I32),
    # A bf16 payload.
    ("gather", 4096, 128, 256, jnp.bfloat16),
    ("scatter", 4096, 2, 8, jnp.bfloat16),
]


def _program(op, cap, width, bound, dtype, lanes=8):
    ring = ((cap, width), dtype)
    if op == "scatter":
        return (lambda buf, b, s, n: ring_scatter(buf, b, s, n),
                [ring, ((bound, width), dtype), SCALAR, SCALAR])
    if op == "slice":
        return (lambda buf, lo, sz, n: ring_slice(buf, lo, sz, n, bound),
                [ring, SCALAR, SCALAR, SCALAR])
    if op == "gather":
        return (lambda buf, lo, n: ring_gather(buf, lo, n, bound),
                [ring, SCALAR, SCALAR])
    return (lambda buf, g, h, s, n: ring_transfer(buf, g, h, s, n,
                                                  max_steal=bound),
            [ring, ((lanes * bound, width), dtype), SCALAR, SCALAR, SCALAR])


@pytest.mark.parametrize("case", MAIN_PATH,
                         ids=lambda c: "-".join(
                             map(str, c[:4] + (jnp.dtype(c[4]).name,))))
def test_queue_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = _program(*case)
    compiled = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def _compiles(one_chip, fn, *shapes) -> bool:
    try:
        _compile(one_chip, fn, *shapes)
    except Exception:  # noqa: BLE001 - any refusal by the compiler
        return False
    return True


def _copy(x, rows):
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    spec = pl.BlockSpec((rows, x.shape[1]), lambda i: (i, 0))
    return pl.pallas_call(kern, grid=(x.shape[0] // rows,), in_specs=[spec],
                          out_specs=spec,
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


# (block rows, array rows, dtype)
BLOCK_RULE = [
    (8, 64, I32), (16, 64, I32), (24, 48, I32), (4, 64, I32), (12, 24, I32),
    (4, 4, I32), (8, 64, jnp.bfloat16), (4, 64, jnp.bfloat16),
    (4, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("rows,full,dtype", BLOCK_RULE)
def test_block_rule_matches_compiler(one_chip, rows, full, dtype):
    fn = functools.partial(_copy, rows=rows)
    assert block_rows_ok(rows, full) == _compiles(one_chip, fn,
                                                  ((full, 20), dtype))


def _cut(s, a, b):
    def kern(s_ref, a_ref, b_ref, o_ref):
        o_ref[...] = narrow(cut_rows(widen(a_ref[...]), widen(b_ref[...]),
                                     s_ref[0]), o_ref.dtype)

    spec = pl.BlockSpec(a.shape, lambda i, s: (0, 0))
    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,), in_specs=[spec, spec],
            out_specs=spec))(s, a, b)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.uint32,
                                   jnp.bfloat16, jnp.int16, jnp.float16,
                                   jnp.int8])
def test_dtype_rule_matches_compiler(one_chip, dtype):
    tile = ((16, 130), dtype)
    assert supported_dtype(dtype) == _compiles(one_chip, _cut, ((1,), I32),
                                               tile, tile)
