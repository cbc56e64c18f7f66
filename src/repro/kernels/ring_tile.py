"""Tiling rule and dynamic row cut shared by the ring-queue kernels.

Every queue kernel (``queue_steal``, ``queue_push``, ``queue_transfer``)
moves a ring segment whose start row is dynamic, so each block it writes
straddles two aligned blocks of its source.  The TPU compiler (Mosaic)
lowers neither ``lax.dynamic_slice`` on a loaded value nor a
dynamic-offset load along the sublane axis of a ref wider than one lane
tile, and it rotates only 32-bit data.  The cut is therefore two dynamic
sublane rotations and a select over 32-bit data (:func:`cut_rows`):
16-bit payloads are widened bit-exactly (bitcast to ``uint16``, then
zero-extended) and narrowed back.

Block rows follow the compiler's rule (:func:`block_rows_ok`): a multiple
of 8 rows, unless the block spans the whole array.  A bound (push batch,
pop or steal count) shorter than that is padded up to whole blocks; the
dead rows are masked by every kernel, so small batches stay on the
kernels (:func:`ring_geometry`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "DEFAULT_BLOCK",
    "SUBLANE_ROWS",
    "RingGeometry",
    "block_rows_ok",
    "cut_rows",
    "narrow",
    "ring_geometry",
    "supported_dtype",
    "widen",
]

DEFAULT_BLOCK = 128
# Rows of one (sublane x lane) tile as the compiler checks a BlockSpec.
SUBLANE_ROWS = 8


def supported_dtype(dtype) -> bool:
    """Whether the ring kernels can move ``dtype``: 32-bit data, or
    16-bit data other than float16 (which v5e cannot load)."""
    dt = jnp.dtype(dtype)
    return dt.itemsize == 4 or (dt.itemsize == 2 and dt != jnp.float16)


def block_rows_ok(rows: int, full_rows: int) -> bool:
    """The compiler's rule for a block's row count: a multiple of
    :data:`SUBLANE_ROWS`, or the whole array."""
    return rows > 0 and (rows % SUBLANE_ROWS == 0 or rows == full_rows)


class RingGeometry(NamedTuple):
    block: int  # rows per grid step
    span: int   # the bound padded to whole blocks


def ring_geometry(capacity: int, bound: int, dtype=jnp.int32, *,
                  block: int = DEFAULT_BLOCK,
                  splice: bool = False) -> Optional[RingGeometry]:
    """Block and padded span for moving up to ``bound`` rows of a
    ``capacity``-row ring, or None where the kernels cannot.

    The bound is padded to whole tiles; the block is the default block
    shrunk to that span and to the ring.  Ring and span must both be
    whole numbers of blocks, each block must satisfy
    :func:`block_rows_ok` against both arrays, and a ``splice`` (push,
    transfer) must not lap the ring: its span plus one straddle block
    fits, so every grid step writes a distinct ring block.
    """
    if not supported_dtype(dtype) or bound <= 0 or capacity <= 0:
        return None
    span = -(-bound // SUBLANE_ROWS) * SUBLANE_ROWS
    blk = min(block, span, capacity)
    if capacity % blk or span % blk:
        return None
    if not (block_rows_ok(blk, capacity) and block_rows_ok(blk, span)):
        return None
    if splice and span + blk > capacity:
        return None
    return RingGeometry(blk, span)


def widen(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-exact view of a tile as 32-bit data."""
    if x.dtype.itemsize == 4:
        return x
    return lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)


def narrow(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`widen`."""
    if jnp.dtype(dtype).itemsize == 4:
        return x
    return lax.bitcast_convert_type(x.astype(jnp.uint16), dtype)


def cut_rows(a: jnp.ndarray, b: jnp.ndarray, c) -> jnp.ndarray:
    """Rows ``[c, c + B)`` of ``concat(a, b)`` for two ``(B, W)`` 32-bit
    tiles and a dynamic ``c`` in ``[0, B]``: both tiles rotated up by
    ``c`` rows, then row ``k`` taken from ``a`` where ``k + c < B``."""
    rows = a.shape[0]
    shift = (rows - c) % rows
    k = lax.broadcasted_iota(jnp.int32, a.shape, 0)
    return jnp.where(k < rows - c, pltpu.roll(a, shift, 0),
                     pltpu.roll(b, shift, 0))
