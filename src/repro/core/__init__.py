"""repro.core — the paper's contribution: lock-free bulk work-stealing.

Layers:
  ops           the BulkOps backend contract (reference / pallas / auto /
                relaxed) over the functional ring-deque: bulk push /
                pop / proportional bulk steal, one operation surface
  relaxed       the fence-free multiplicity-tolerant backend
                (Castañeda & Piña): optimistic full-window steal +
                posterior reconcile, registered as "relaxed"
  queue         QueueState re-exports + host paging (PagedQueue)
  policy        steal policies + the virtual master's transfer planner
  master        SPMD rebalancing supersteps (compact one-window
                all_gather exchange by default; dense all_to_all oracle)
  sharded_queue stacked per-worker queues, vmap/shard_map drivers
  host_queue    faithful host-threaded port of the paper's Listings 1-4,
                behind the HostQueue protocol
  dd            decision-diagram branch-and-bound solver (paper's application)
  uts           UTS binomial trees (SHA-1 spawning on the device) searched
                on the steal runtime, with a hashlib reference walk

(The pre-BulkOps ``use_kernel`` dialect — module-level queue ops and
their ``*_inplace`` variants — had its one deprecation release at PR 3
and is gone; construct a backend with :func:`make_ops`.)
"""

from repro.core.ops import (  # noqa: F401
    BulkOps,
    QueueState,
    available_backends,
    make_ops,
    make_queue,
    queue_size,
    register_backend,
    steal_counted,
)
from repro.core import relaxed as _relaxed  # noqa: F401  (registers "relaxed")
from repro.core.queue import (  # noqa: F401
    PagedQueue,
    pop,
)
from repro.core.policy import (  # noqa: F401
    StealPolicy,
    proportional,
    steal_half,
    adaptive_chunk,
    plan_transfers,
)
