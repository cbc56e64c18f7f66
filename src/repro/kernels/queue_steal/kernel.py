"""Bulk ring-segment move as a Pallas TPU kernel (the steal hot path).

The paper's steal is a single-cut detach of a contiguous suffix; on TPU
the payload move is a ring-buffer segment copy HBM->HBM staged through
VMEM.  The start offset ``lo`` is DYNAMIC, so a block of the output may
straddle two aligned blocks of the ring.  TPU-native approach:

  * ``lo`` arrives via scalar prefetch (PrefetchScalarGridSpec) so the
    BlockSpec index_map can align input DMA windows to it: output block
    ``i`` reads ring blocks ``a = (lo//BS + i) % nb`` and ``(a+1) % nb``.
  * In-kernel, the true segment is cut out of the two VMEM tiles at
    ``r = lo % BS`` (``ring_tile.cut_rows``: two dynamic sublane
    rotations and a select) — the same "sever at the cut point"
    structure as the paper's Listing 4, executed as vector moves instead
    of pointer chasing.
  * Rows past ``n`` (the stolen count) are zero-masked so the result can
    travel through summing collectives (see core.master).

A ``max_steal`` shorter than a tile is padded up to one
(``ring_tile.ring_geometry``) and the padding sliced off.

Cost: O(batch) vectorized copy, constant per item — the kernel-level
realization of the paper's flat bulk-op latency (Fig. 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring_tile import (DEFAULT_BLOCK, cut_rows, narrow,
                                     ring_geometry, widen)

__all__ = ["ring_gather", "ring_gather_supported", "DEFAULT_BLOCK"]


def ring_gather_supported(capacity: int, max_steal: int, *,
                          dtype=jnp.int32,
                          block: int = DEFAULT_BLOCK) -> bool:
    """Whether :func:`ring_gather` admits this geometry
    (``ring_tile.ring_geometry``).  Callers use this to fall back to the
    jnp oracle instead of tripping the kernel assert."""
    return ring_geometry(capacity, max_steal, dtype, block=block) is not None


def _kernel(lo_ref, n_ref, a_ref, b_ref, o_ref, *, block: int):
    i = pl.program_id(0)
    seg = cut_rows(widen(a_ref[...]), widen(b_ref[...]), lo_ref[0] % block)
    row = i * block + jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
    o_ref[...] = narrow(jnp.where(row < n_ref[0], seg, jnp.zeros_like(seg)),
                        o_ref.dtype)


def ring_gather(buf: jnp.ndarray, lo: jnp.ndarray, n: jnp.ndarray,
                max_steal: int, *, block: int = DEFAULT_BLOCK,
                interpret: bool = False) -> jnp.ndarray:
    """buf: (cap, W); returns (max_steal, W) = rows (lo+i) % cap, i < n.

    Geometry must satisfy :func:`ring_gather_supported`.
    """
    cap, width = buf.shape
    geo = ring_geometry(cap, max_steal, buf.dtype, block=block)
    assert geo is not None, (cap, max_steal, buf.dtype)
    block, span = geo
    nb = cap // block

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(span // block,),
        in_specs=[
            pl.BlockSpec((block, width),
                         lambda i, lo, n: ((lo[0] // block + i) % nb, 0)),
            pl.BlockSpec((block, width),
                         lambda i, lo, n: ((lo[0] // block + i + 1) % nb, 0)),
        ],
        out_specs=pl.BlockSpec((block, width), lambda i, lo, n: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((span, width), buf.dtype),
        interpret=interpret,
    )(jnp.asarray(lo, jnp.int32).reshape(1),
      jnp.asarray(n, jnp.int32).reshape(1), buf, buf)
    return out if span == max_steal else out[:max_steal]
