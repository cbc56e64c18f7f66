"""Faults and controls planted under the timed path, for the checks that
the comparison with the reference catches them.

Each entry of :data:`PLANTS` is a context manager that patches the
program for the time it is entered and restores it on exit:

* ``state_unchanged`` — a fused dispatch returns the queues and the
  carry it was given, as if its rounds had run;
* ``half_batch`` — every bulk pop takes its items off the queue but
  hands only the first half of them on;
* ``exchange_dropped`` — the thief's splice of a stolen block does
  nothing, so the block the victim gave up is lost;
* ``answer_altered`` — the answer is changed where it is produced: the
  first item of every bulk push is incremented, and ``parallel_solve``'s
  optimum is off by one.

``exchange_dropped`` is also every cell's control: it breaks the
guarantee both configurations state, that no item or subproblem is lost.

The tests drive the harness with each plant at a size a CPU holds;
``control.py`` does the same on the chip at the cells' own sizes.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict


@contextlib.contextmanager
def _patched(owner, name: str, make: Callable):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def state_unchanged():
    from repro.runtime.executor import StealRuntime

    def make(orig):
        del orig

        def run_fused(self, k, worker_fn=None, carry=None, *,
                      until_drained=False):
            del worker_fn
            self.rounds_run += k
            return (carry, None, k) if until_drained else (carry, None)

        return run_fused

    return _patched(StealRuntime, "run_fused", make)


def half_batch():
    from repro.core.ops import BulkOps

    def make(orig):
        def pop_bulk(self, q, max_n, n, **kwargs):
            q, batch, got = orig(self, q, max_n, n, **kwargs)
            return q, batch, got // 2

        return pop_bulk

    return _patched(BulkOps, "pop_bulk", make)


def exchange_dropped():
    import jax.numpy as jnp

    from repro.core.ops import BulkOps

    def make(orig):
        del orig

        def transfer(self, q, gathered, src_row, n, **kwargs):
            del gathered, src_row, n, kwargs
            return q, jnp.int32(0)

        return transfer

    return _patched(BulkOps, "transfer", make)


@contextlib.contextmanager
def answer_altered():
    import jax

    from repro.core import ops
    from repro.core.dd import parallel

    def make_push(orig):
        def push(self, q, batch, n, **kwargs):
            batch = jax.tree_util.tree_map(lambda x: x.at[0].add(1), batch)
            return orig(self, q, batch, n, **kwargs)

        return push

    def make_solve(orig):
        def parallel_solve(inst, **kwargs):
            opt, stats = orig(inst, **kwargs)
            return opt + 1, stats

        return parallel_solve

    with _patched(ops.BulkOps, "push", make_push), \
            _patched(parallel, "parallel_solve", make_solve):
        yield


PLANTS: Dict[str, Callable] = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "exchange_dropped": exchange_dropped,
    "answer_altered": answer_altered,
}
