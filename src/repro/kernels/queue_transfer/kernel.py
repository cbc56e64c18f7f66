"""Fused exchange-side transfer as a Pallas TPU kernel (compact superstep).

In the compact collective superstep (``core.master``, ``exchange=
"compact"``) every lane all_gathers one raw ``(max_steal, ...)`` ring
window and the victim's "detach" is a pure cursor bump — no masked block
is ever materialized on the victim.  What remains is the thief side:
cut the victim's stolen segment out of the replicated ``(W * max_steal,
...)`` gathered buffer (the ``steal_exact`` gather, relocated to the
thief) and splice it into the thief's own ring at the owner end (the
bulk ``push``).  ``ring_transfer`` fuses those two data movements into
ONE kernel:

* the source row offset ``src_start = src_row * max_steal`` is DYNAMIC
  (which victim the replicated plan paired this thief with), so the
  input DMA windows are aligned to it via scalar prefetch — the
  ``(max_steal, ...)`` intermediate ``gathered[src]`` block that a
  select-then-push pipeline would materialize never exists;
* the splice start ``head = (lo + size) % cap`` is DYNAMIC too, exactly
  as in ``kernels.queue_push.ring_scatter``: each touched ring block
  straddles at most two aligned gathered blocks, the true segment is cut
  at ``block - head % block`` (``ring_tile.cut_rows``), and rows
  outside ``[0, n)`` pass the old ring contents through (read-modify-
  write of the aliased block — the ring buffer is updated IN PLACE via
  ``input_output_aliases``);
* the grid covers only the ``max_steal // block + 1`` ring blocks the
  splice touches — cost is O(max_steal), never O(capacity) and never
  O(W * max_steal).

Structurally this is ``ring_scatter`` generalized with a dynamic source
offset into a source buffer W times larger than the splice span.  A
span shorter than a tile is padded up to one per lane window
(``ring_tile.ring_geometry``); padded rows are never live.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring_tile import (DEFAULT_BLOCK, cut_rows, narrow,
                                     ring_geometry, widen)

__all__ = ["ring_transfer", "ring_transfer_supported", "DEFAULT_BLOCK"]


def ring_transfer_supported(capacity: int, max_steal: int, *,
                            dtype=jnp.int32,
                            block: int = DEFAULT_BLOCK) -> bool:
    """Whether :func:`ring_transfer` admits this geometry.  Same rule as
    the push-side ring-scatter (``ring_tile.ring_geometry`` with
    ``splice=True``); the gathered source is ``n_lanes`` windows of the
    padded span, so it is block-aligned whenever the span is."""
    return ring_geometry(capacity, max_steal, dtype, block=block,
                         splice=True) is not None


def _transfer_kernel(c_ref, prev_ref, cur_ref, buf_ref, o_ref, *,
                     block: int, max_steal: int):
    i = pl.program_id(0)
    head, n = c_ref[0], c_ref[2]
    r = head % block
    n = jnp.minimum(n, max_steal)
    # Gathered rows src_start + i*block - r + k, k in [0, block): cut one
    # aligned window out of the two candidate gathered blocks.
    vals = cut_rows(widen(prev_ref[...]), widen(cur_ref[...]), block - r)
    off = i * block - r + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    live = (off >= 0) & (off < n)
    # Read-modify-write: rows outside the splice keep the old ring
    # contents (the output aliases the ring buffer input).
    o_ref[...] = narrow(jnp.where(live, vals, widen(buf_ref[...])),
                        o_ref.dtype)


def ring_transfer(buf: jnp.ndarray, gathered: jnp.ndarray,
                  head: jnp.ndarray, src_start: jnp.ndarray,
                  n: jnp.ndarray, *, max_steal: int,
                  block: int = DEFAULT_BLOCK,
                  interpret: bool = False) -> jnp.ndarray:
    """buf: (cap, W), gathered: (S, W) with ``S = n_lanes * max_steal``;
    returns buf with rows ``(head + i) % cap = gathered[src_start + i]``
    for ``i < n`` (``n <= max_steal``).

    ``src_start`` must be a multiple of the span ``max_steal`` (it is
    ``src_row * max_steal``), which keeps the dynamic source windows
    block-aligned.  Geometry must satisfy
    :func:`ring_transfer_supported`; the ring buffer argument is donated
    to the output (in-place splice).
    """
    cap, width = buf.shape
    geo = ring_geometry(cap, max_steal, buf.dtype, block=block, splice=True)
    assert geo is not None, (cap, max_steal, buf.dtype)
    assert gathered.shape[0] % max_steal == 0
    block, span = geo
    src_start = jnp.asarray(src_start, jnp.int32)
    if span != max_steal:
        lanes = gathered.shape[0] // max_steal
        gathered = jnp.pad(gathered.reshape(lanes, max_steal, width),
                           ((0, 0), (0, span - max_steal), (0, 0)))
        gathered = gathered.reshape(lanes * span, width)
        src_start = src_start // max_steal * span
    nb = cap // block
    sb = gathered.shape[0] // block
    bb = span // block

    kern = functools.partial(_transfer_kernel, block=block,
                             max_steal=max_steal)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # bb gathered blocks land on bb + 1 ring blocks (dynamic straddle).
        grid=(bb + 1,),
        in_specs=[
            pl.BlockSpec((block, width),
                         lambda i, c: ((c[1] // block + (i - 1) % bb) % sb,
                                       0)),
            pl.BlockSpec((block, width),
                         lambda i, c: ((c[1] // block + i % bb) % sb, 0)),
            pl.BlockSpec((block, width),
                         lambda i, c: ((c[0] // block + i) % nb, 0)),
        ],
        out_specs=pl.BlockSpec((block, width),
                               lambda i, c: ((c[0] // block + i) % nb, 0)),
    )
    scalars = jnp.stack([jnp.asarray(head, jnp.int32), src_start,
                         jnp.asarray(n, jnp.int32)])
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap, width), buf.dtype),
        # Inputs count the scalar-prefetch arg first: buf is operand 3.
        input_output_aliases={3: 0},
        interpret=interpret,
    )(scalars, gathered, gathered, buf)
