"""Owner-side bulk ring ops as Pallas TPU kernels (push / pop hot path).

The paper's bulk push is a single splice of a pre-linked batch at the
owner end; its bulk pop detaches the newest suffix.  On the TPU ring
queue both are ring-buffer segment moves with a DYNAMIC cut point, the
mirror image of the steal-side gather (``kernels.queue_steal``):

``ring_scatter`` (push)
    Splices ``batch[i] -> buf[(start + i) % cap]`` for ``i < n`` with
    ``start = lo + size``.  The ring buffer is updated IN PLACE via
    ``input_output_aliases`` and the grid visits only the blocks the
    splice touches — cost is O(batch), constant per item and flat in the
    batch size (Fig. 6), never O(capacity).  Each output block straddles
    at most two aligned batch blocks; the true segment is cut out at
    ``block - start % block`` (``ring_tile.cut_rows``) and non-spliced
    rows pass the old ring contents through (read-modify-write of the
    aliased block).

``ring_slice`` (pop_bulk)
    Detaches the newest ``n`` rows, i.e. rows ``(lo + size - n + i) %
    cap`` for ``i < n`` (rows >= n zero-masked).  Structurally the
    steal-side gather with the cut at the OWNER end: the start offset is
    derived from three prefetched scalars (``lo``, ``size``, ``n``)
    inside the BlockSpec index maps, so the whole segment move is one
    kernel with no host-side cursor arithmetic.

Scalar cursors arrive via ``PrefetchScalarGridSpec`` so the input DMA
windows align to the dynamic cut before the kernel body runs.  A batch
shorter than a tile is padded up to one (``ring_tile.ring_geometry``):
padded push rows are never live, padded pop rows are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring_tile import (DEFAULT_BLOCK, cut_rows, narrow,
                                     ring_geometry, widen)

__all__ = [
    "ring_scatter",
    "ring_scatter_supported",
    "ring_slice",
    "ring_slice_supported",
    "DEFAULT_BLOCK",
]


# ---------------------------------------------------------------------------
# ring_scatter: bulk push splice
# ---------------------------------------------------------------------------


def ring_scatter_supported(capacity: int, max_push: int, *,
                           dtype=jnp.int32,
                           block: int = DEFAULT_BLOCK) -> bool:
    """Whether :func:`ring_scatter` admits this geometry
    (``ring_tile.ring_geometry`` with ``splice=True``: the splice span
    plus one straddle block must not lap the ring, so every grid step
    writes a DISTINCT ring block — the in-place splice would otherwise
    read a block another step already rewrote)."""
    return ring_geometry(capacity, max_push, dtype, block=block,
                         splice=True) is not None


def _scatter_kernel(start_ref, n_ref, prev_ref, cur_ref, buf_ref, o_ref, *,
                    block: int, max_push: int):
    i = pl.program_id(0)
    r = start_ref[0] % block
    n = jnp.minimum(n_ref[0], max_push)
    # Batch rows i*block - r + k, k in [0, block): cut one aligned window
    # out of the two candidate batch blocks.
    vals = cut_rows(widen(prev_ref[...]), widen(cur_ref[...]), block - r)
    off = i * block - r + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    live = (off >= 0) & (off < n)
    # Read-modify-write: rows outside the splice keep the old ring
    # contents (the output aliases the ring buffer input).
    o_ref[...] = narrow(jnp.where(live, vals, widen(buf_ref[...])),
                        o_ref.dtype)


def ring_scatter(buf: jnp.ndarray, batch: jnp.ndarray, start: jnp.ndarray,
                 n: jnp.ndarray, *, block: int = DEFAULT_BLOCK,
                 interpret: bool = False) -> jnp.ndarray:
    """buf: (cap, W), batch: (max_push, W); returns buf with rows
    ``(start + i) % cap = batch[i]`` for ``i < n``.

    Geometry must satisfy :func:`ring_scatter_supported`; the ring
    buffer argument is donated to the output (in-place splice).
    """
    cap, width = buf.shape
    max_push = batch.shape[0]
    geo = ring_geometry(cap, max_push, buf.dtype, block=block, splice=True)
    assert geo is not None, (cap, max_push, buf.dtype)
    block, span = geo
    if span != max_push:
        batch = jnp.pad(batch, ((0, span - max_push), (0, 0)))
    nb = cap // block
    bb = span // block

    kern = functools.partial(_scatter_kernel, block=block, max_push=max_push)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # bb batch blocks land on bb + 1 ring blocks (dynamic straddle).
        grid=(bb + 1,),
        in_specs=[
            pl.BlockSpec((block, width),
                         lambda i, s, n: ((i - 1) % bb, 0)),
            pl.BlockSpec((block, width),
                         lambda i, s, n: (i % bb, 0)),
            pl.BlockSpec((block, width),
                         lambda i, s, n: ((s[0] // block + i) % nb, 0)),
        ],
        out_specs=pl.BlockSpec((block, width),
                               lambda i, s, n: ((s[0] // block + i) % nb, 0)),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap, width), buf.dtype),
        # Inputs count scalar-prefetch args first: buf is operand 4.
        input_output_aliases={4: 0},
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(1),
      jnp.asarray(n, jnp.int32).reshape(1), batch, batch, buf)


# ---------------------------------------------------------------------------
# ring_slice: bulk pop detach
# ---------------------------------------------------------------------------


def ring_slice_supported(capacity: int, max_n: int, *, dtype=jnp.int32,
                         block: int = DEFAULT_BLOCK) -> bool:
    """Whether :func:`ring_slice` admits this geometry (the steal-side
    gather's rule, ``ring_tile.ring_geometry``)."""
    return ring_geometry(capacity, max_n, dtype, block=block) is not None


def _slice_kernel(lo_ref, size_ref, n_ref, a_ref, b_ref, o_ref, *,
                  block: int, cap: int):
    i = pl.program_id(0)
    n = n_ref[0]
    start = (lo_ref[0] + size_ref[0] - n) % cap
    seg = cut_rows(widen(a_ref[...]), widen(b_ref[...]), start % block)
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
    o_ref[...] = narrow(jnp.where(row < n, seg, jnp.zeros_like(seg)),
                        o_ref.dtype)


def ring_slice(buf: jnp.ndarray, lo: jnp.ndarray, size: jnp.ndarray,
               n: jnp.ndarray, max_n: int, *, block: int = DEFAULT_BLOCK,
               interpret: bool = False) -> jnp.ndarray:
    """buf: (cap, W); returns (max_n, W) = the newest ``n`` rows in queue
    order (oldest of the block first), rows >= ``n`` zeroed.  ``n`` must
    already be clamped to ``size``."""
    cap, width = buf.shape
    geo = ring_geometry(cap, max_n, buf.dtype, block=block)
    assert geo is not None, (cap, max_n, buf.dtype)
    block, span = geo
    nb = cap // block

    def _start_block(lo, size, n):
        return ((lo[0] + size[0] - n[0]) % cap) // block

    kern = functools.partial(_slice_kernel, block=block, cap=cap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(span // block,),
        in_specs=[
            pl.BlockSpec((block, width),
                         lambda i, lo, sz, n:
                         ((_start_block(lo, sz, n) + i) % nb, 0)),
            pl.BlockSpec((block, width),
                         lambda i, lo, sz, n:
                         ((_start_block(lo, sz, n) + i + 1) % nb, 0)),
        ],
        out_specs=pl.BlockSpec((block, width), lambda i, lo, sz, n: (i, 0)),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((span, width), buf.dtype),
        interpret=interpret,
    )(jnp.asarray(lo, jnp.int32).reshape(1),
      jnp.asarray(size, jnp.int32).reshape(1),
      jnp.asarray(n, jnp.int32).reshape(1), buf, buf)
    return out if span == max_n else out[:max_n]
