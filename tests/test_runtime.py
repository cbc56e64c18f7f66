"""The unified runtime: executor conservation across adaptive rounds
(per-round, fused, and early-exit fused), backend-dispatch parity
(pallas-routed vs reference BulkOps for steal/push/pop on dynamic
cursors straddling block boundaries), and donate= vs pure equivalence.
Executor tests are parametrized over ``backend in ("reference", "auto",
"relaxed")`` — the oracle, the geometry-resolved routing and the
fence-free relaxed backend must be observationally identical."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops as bulk_ops
from repro.core.policy import StealPolicy
from repro.kernels.queue_push.kernel import ring_scatter, ring_slice
from repro.kernels.queue_push.ref import ring_scatter_ref, ring_slice_ref
from repro.kernels.queue_steal.kernel import DEFAULT_BLOCK
from repro.kernels.queue_steal.ops import steal_gather
from repro.kernels.queue_steal.ref import ring_gather_ref
from repro.runtime import AdaptiveConfig, StealRuntime

SPEC = jax.ShapeDtypeStruct((), jnp.int32)
BACKENDS = ("reference", "auto", "relaxed")
REF = bulk_ops.make_ops("reference")
PALLAS = bulk_ops.make_ops("pallas")


def _seed(rt, sizes):
    """Fill lane i with ``sizes[i]`` distinct ids; returns the id set."""
    nxt = 1
    for i, n in enumerate(sizes):
        if n:
            rt.push(i, jnp.arange(nxt, nxt + n, dtype=jnp.int32), n)
            nxt += n
    return set(range(1, nxt))


def _drained_ids(rt):
    return sorted(int(x) for lane in rt.drain() for x in lane)


# ------------------------------------------------------------- conservation


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sizes,rounds", [
    ([40, 0, 0, 0], 5),
    ([0, 17, 3, 25, 0, 9], 4),
    ([100, 0, 0, 0, 0, 0, 0, 0], 8),
])
def test_executor_conserves_tasks_across_adaptive_rounds(sizes, rounds,
                                                         backend):
    """No task lost or duplicated while the controller re-tunes the
    proportion every round (traced scalar => same compiled round) — for
    every backend."""
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt = StealRuntime(len(sizes), 128, SPEC, policy=pol, adaptive=True,
                      backend=backend)
    ids = _seed(rt, sizes)
    props = set()
    for _ in range(rounds):
        props.add(rt.proportion)
        rt.round()
    assert _drained_ids(rt) == sorted(ids)
    # the controller actually moved (imbalanced seed => feedback signal)
    assert len(rt.controller.history) == rounds + 1
    assert rt.telemetry.summary()["rounds"] == rounds


@pytest.mark.parametrize("backend", BACKENDS)
def test_executor_backends_agree(backend):
    """The full executor trajectory (sizes, telemetry, drained ids) is
    identical across backends — the cross-implementation contract."""
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt_ref = StealRuntime(4, 128, SPEC, policy=pol, backend="reference")
    rt_b = StealRuntime(4, 128, SPEC, policy=pol, backend=backend)
    ids = _seed(rt_ref, [40, 0, 3, 0])
    _seed(rt_b, [40, 0, 3, 0])
    for _ in range(5):
        rt_ref.round()
        rt_b.round()
    np.testing.assert_array_equal(rt_ref.sizes(), rt_b.sizes())
    assert rt_ref.telemetry.summary() == rt_b.telemetry.summary()
    assert _drained_ids(rt_ref) == _drained_ids(rt_b) == sorted(ids)


def test_executor_conserves_with_worker_body():
    """Conservation holds when a worker body pops/pushes between steals
    (ids are consumed exactly once across lanes)."""
    pol = StealPolicy(proportion=0.5, low_watermark=1, high_watermark=6,
                      max_steal=16)
    W = 4
    rt = StealRuntime(W, 128, SPEC, policy=pol)
    ids = _seed(rt, [30, 0, 0, 0])
    ops = rt.ops

    def body(q, carry):
        q, item, valid = ops.pop(q)
        carry = carry + jnp.where(valid, item, 0)
        return q, carry

    carry = jnp.zeros((W,), jnp.int32)
    for _ in range(60):
        carry, _ = rt.round(body, carry)
        if rt.total_size() == 0:
            break
    assert rt.total_size() == 0
    # sum of consumed ids == sum of produced ids (nothing lost/dup'd)
    assert int(jnp.sum(carry)) == sum(ids)


def test_executor_hierarchical_conserves():
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt = StealRuntime(8, 128, SPEC, policy=pol, pod_size=4)
    ids = _seed(rt, [50, 0, 0, 0, 0, 12, 0, 0])
    for _ in range(5):
        rt.round()
    assert _drained_ids(rt) == sorted(ids)


def test_executor_reports_exchange_payload():
    """bytes_moved telemetry: compact rounds that transfer report one
    max_steal window per lane; skipped rounds report zero; the dense
    exchange reports the W x payload every round — through both
    .round() and .run_fused()."""
    W, max_steal, item_bytes = 4, 32, 4  # SPEC is one int32 per item
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=max_steal)
    rt = StealRuntime(W, 128, SPEC, policy=pol, adaptive=False)
    _seed(rt, [40, 0, 0, 0])
    rt.round()
    assert rt.telemetry.rounds[-1].bytes_moved == max_steal * item_bytes
    rt.run_fused(3)
    active = [r.bytes_moved for r in rt.telemetry.rounds
              if r.n_transferred > 0]
    idle = [r.bytes_moved for r in rt.telemetry.rounds
            if r.n_transferred == 0]
    assert all(b == max_steal * item_bytes for b in active)
    assert all(b == 0 for b in idle)  # the lax.cond fast path
    assert rt.telemetry.summary()["bytes_moved"] == sum(
        r.bytes_moved for r in rt.telemetry.rounds)

    pol_d = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                        max_steal=max_steal, exchange="dense")
    rt_d = StealRuntime(W, 128, SPEC, policy=pol_d, adaptive=False)
    _seed(rt_d, [40, 0, 0, 0])
    rt_d.round()
    assert (rt_d.telemetry.rounds[-1].bytes_moved
            == W * max_steal * item_bytes)


def test_executor_exchange_payload_stays_per_lane_hierarchically():
    """Hierarchical bytes_moved is the busiest LANE's injection (intra +
    xpod), not a cluster sum: one compact window per level at most."""
    max_steal, item_bytes = 32, 4
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=max_steal)
    rt = StealRuntime(8, 128, SPEC, policy=pol, adaptive=False, pod_size=4)
    _seed(rt, [50, 0, 0, 0, 0, 12, 0, 0])  # both pods rebalance intra
    rt.round()
    window = max_steal * item_bytes
    # at most one window per level for the busiest lane, never 2 pods' sum
    assert 0 < rt.telemetry.rounds[-1].bytes_moved <= 2 * window


def test_executor_spreads_load():
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=64)
    rt = StealRuntime(8, 256, SPEC, policy=pol,
                      adaptive_config=AdaptiveConfig(gain=1.0))
    _seed(rt, [100, 0, 0, 0, 0, 0, 0, 0])
    for _ in range(6):
        rt.round()
    s = rt.sizes()
    assert s.sum() == 100
    assert (s > 0).sum() >= 4
    assert rt.telemetry.total_transferred > 0


# ----------------------------------------------- kernel path: block straddle


STRADDLE_CASES = [
    # (cap, width, max_steal, lo, n) — lo chosen to straddle the
    # DEFAULT_BLOCK-aligned DMA windows of the Pallas kernel
    (512, 8, 256, DEFAULT_BLOCK - 1, 200),
    (512, 8, 256, DEFAULT_BLOCK + 1, 256),
    (512, 8, 512, 2 * DEFAULT_BLOCK - 7, 300),   # wraps past cap
    (256, 4, 256, 255, 129),                      # full wrap from last row
    (1024, 16, 256, 3 * DEFAULT_BLOCK + 63, 255),
]


@pytest.mark.parametrize("case", STRADDLE_CASES)
def test_ring_gather_interpret_parity_straddling_blocks(case):
    cap, width, max_steal, lo, n = case
    buf = jax.random.normal(jax.random.PRNGKey(7), (cap, width), jnp.float32)
    out_k = steal_gather(buf, jnp.int32(lo), jnp.int32(n),
                         max_steal=max_steal, use_pallas=True,
                         interpret=True)
    out_r = ring_gather_ref(buf, lo, n, max_steal)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("lo,n", [(120, 60), (250, 200), (0, 0)])
def test_steal_exact_pallas_backend_matches_reference(lo, n):
    """The pallas-routed backend == the reference backend for dynamic lo
    (the dispatcher picks the kernel oracle on CPU, Pallas on TPU)."""
    cap, max_steal = 256, 128
    q = bulk_ops.QueueState(
        buf={"a": jnp.arange(cap, dtype=jnp.int32),
             "b": jnp.arange(cap * 2, dtype=jnp.float32).reshape(cap, 2)},
        lo=jnp.int32(lo), size=jnp.int32(min(cap, 220)))
    q1, b1, n1 = REF.steal_exact(q, jnp.int32(n), max_steal=max_steal)
    q2, b2, n2 = PALLAS.steal_exact(q, jnp.int32(n), max_steal=max_steal)
    assert int(n1) == int(n2)
    assert int(q1.lo) == int(q2.lo) and int(q1.size) == int(q2.size)
    for k in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(b1[k]), np.asarray(b2[k]))


def test_kernel_steal_available_geometry():
    assert bulk_ops.kernel_steal_available(512, 256)
    assert bulk_ops.kernel_steal_available(256, 128)
    assert bulk_ops.kernel_steal_available(64, 32)       # block shrinks to 32
    assert not bulk_ops.kernel_steal_available(500, 256)  # cap not aligned
    assert not bulk_ops.kernel_steal_available(512, 200)  # max_steal unaligned


# ------------------------------------- push/pop kernels: wraparound parity


SCATTER_CASES = [
    # (cap, width, max_push, start, n) — start chosen to straddle the
    # DEFAULT_BLOCK-aligned splice windows / wrap past the ring end
    (512, 8, 256, DEFAULT_BLOCK - 1, 200),
    (512, 8, 256, DEFAULT_BLOCK + 1, 256),
    (512, 4, 256, 512 - 7, 256),                 # wraps past cap
    (512, 8, 256, 0, 0),                          # n = 0: pure pass-through
    (256, 4, 128, 255, 128),                      # full wrap from last row
    (1024, 16, 512, 3 * DEFAULT_BLOCK + 63, 511),
    (64, 3, 32, 33, 32),                          # shrunken block (32)
    # batches shorter than a tile are padded to one block of 8 rows
    (64, 1, 1, 63, 1),                            # one item at the last row
    (64, 20, 4, 62, 4),                           # wraps past cap
    (64, 2, 8, 57, 8),
    (64, 2, 8, 3, 0),
]


@pytest.mark.parametrize("case", SCATTER_CASES)
def test_ring_scatter_interpret_parity_straddling_blocks(case):
    cap, width, max_push, start, n = case
    key = jax.random.PRNGKey(3)
    buf = jax.random.normal(key, (cap, width), jnp.float32)
    batch = jax.random.normal(jax.random.fold_in(key, 1),
                              (max_push, width), jnp.float32)
    out_k = ring_scatter(buf, batch, jnp.int32(start), jnp.int32(n),
                         interpret=True)
    out_r = ring_scatter_ref(buf, batch, start, n)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    # Untouched ring rows must be preserved bit-exactly.
    offs = (np.arange(cap) - start) % cap
    keep = offs >= n
    np.testing.assert_array_equal(np.asarray(out_k)[keep],
                                  np.asarray(buf)[keep])


SLICE_CASES = [
    # (cap, width, max_n, lo, size, n)
    (512, 8, 256, DEFAULT_BLOCK - 1, 300, 200),
    (512, 8, 512, 2 * DEFAULT_BLOCK - 7, 512, 512),   # n = capacity
    (512, 8, 256, 17, 40, 0),                          # n = 0
    (256, 4, 256, 255, 200, 129),                      # wraps from last row
    (1024, 16, 256, 3 * DEFAULT_BLOCK + 63, 900, 255),
    (64, 3, 32, 61, 40, 32),                           # shrunken block
    # pops shorter than a tile: padded to one block, padding sliced off
    (64, 1, 1, 63, 1, 1),
    (64, 20, 4, 61, 5, 4),                             # wraps past cap
    (64, 2, 8, 60, 20, 8),
    (64, 2, 4, 10, 3, 2),
]


@pytest.mark.parametrize("case", SLICE_CASES)
def test_ring_slice_interpret_parity_straddling_blocks(case):
    cap, width, max_n, lo, size, n = case
    buf = jax.random.normal(jax.random.PRNGKey(5), (cap, width), jnp.float32)
    out_k = ring_slice(buf, jnp.int32(lo), jnp.int32(size), jnp.int32(n),
                       max_n, interpret=True)
    out_r = ring_slice_ref(buf, lo, size, n, max_n)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("lo,size,n_push,n_pop", [
    (0, 0, 10, 4), (120, 60, 16, 16), (250, 4, 8, 12), (100, 200, 0, 0),
])
def test_push_pop_pallas_backend_matches_reference(lo, size, n_push, n_pop):
    """The pallas-routed backend == the reference backend for push and
    bulk pop on dynamic cursors (the dispatcher picks the kernel oracle
    on CPU, Pallas on TPU)."""
    cap, max_n = 256, 16
    q = bulk_ops.QueueState(
        buf={"a": jnp.arange(cap, dtype=jnp.int32),
             "b": jnp.arange(cap * 2, dtype=jnp.float32).reshape(cap, 2)},
        lo=jnp.int32(lo), size=jnp.int32(size))
    batch = {"a": jnp.arange(1, max_n + 1, dtype=jnp.int32),
             "b": jnp.ones((max_n, 2), jnp.float32)}
    q1, p1 = REF.push(q, batch, jnp.int32(n_push))
    q2, p2 = PALLAS.push(q, batch, jnp.int32(n_push))
    assert int(p1) == int(p2)
    for k in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(q1.buf[k]),
                                      np.asarray(q2.buf[k]))
    q1, b1, n1 = REF.pop_bulk(q1, max_n, jnp.int32(n_pop))
    q2, b2, n2 = PALLAS.pop_bulk(q2, max_n, jnp.int32(n_pop))
    assert int(n1) == int(n2)
    assert int(q1.size) == int(q2.size)
    for k in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(b1[k]), np.asarray(b2[k]))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("rows,start", [(1, 63), (4, 61), (8, 59)])
def test_ring_push_pop_small_batches_roundtrip(rows, start, dtype):
    """A push of 1, 4 or 8 rows that wraps the ring, then a pop of the
    same rows, through the interpret-mode kernels: both equal the
    oracles bit for bit, and the pop returns the pushed rows."""
    cap, width = 64, 3
    key = jax.random.PRNGKey(rows)
    buf = jax.random.normal(key, (cap, width), jnp.float32).astype(dtype)
    batch = jax.random.normal(jax.random.fold_in(key, 1), (rows, width),
                              jnp.float32).astype(dtype)
    pushed = ring_scatter(buf, batch, jnp.int32(start), jnp.int32(rows),
                          interpret=True)
    np.testing.assert_array_equal(
        np.asarray(pushed), np.asarray(ring_scatter_ref(buf, batch, start,
                                                        rows)))
    lo, size = start - 5, rows + 5
    popped = ring_slice(pushed, jnp.int32(lo), jnp.int32(size),
                        jnp.int32(rows), rows, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(popped),
        np.asarray(ring_slice_ref(pushed, lo, size, rows, rows)))
    np.testing.assert_array_equal(np.asarray(popped), np.asarray(batch))


def test_kernel_predicates_state_the_tiling_rule():
    """Block rows are a multiple of 8 or the whole array; a bound shorter
    than that is padded to one block; payloads are 32-bit or 16-bit
    (not float16).  The TPU compile tests check the rule itself against
    the compiler."""
    from repro.kernels.ring_tile import (block_rows_ok, ring_geometry,
                                         supported_dtype)

    assert block_rows_ok(8, 16384) and block_rows_ok(24, 48)
    assert block_rows_ok(4, 4)                          # whole array
    assert not block_rows_ok(4, 16384) and not block_rows_ok(12, 24)
    assert ring_geometry(16384, 1) == (8, 8)            # padded batch
    assert ring_geometry(4096, 4) == (8, 8)
    assert ring_geometry(16384, 1024) == (128, 1024)
    assert ring_geometry(128, 20) is None               # 24-row block
    assert ring_geometry(8, 1, splice=True) is None     # would lap
    assert supported_dtype(jnp.bfloat16) and supported_dtype(jnp.uint32)
    assert not supported_dtype(jnp.float16)
    assert not supported_dtype(jnp.int8)
    assert bulk_ops.kernel_pop_available(4096, 4)
    assert bulk_ops.kernel_push_available(16384, 1)
    assert bulk_ops.kernel_steal_available(4096, 256, jnp.bfloat16)
    assert not bulk_ops.kernel_steal_available(4096, 256, jnp.float16)
    assert not bulk_ops.kernel_transfer_available(4096, 256, jnp.int8)


def test_kernel_push_pop_available_geometry():
    assert bulk_ops.kernel_push_available(512, 256)
    assert bulk_ops.kernel_push_available(4096, 1024)
    assert not bulk_ops.kernel_push_available(500, 256)   # cap unaligned
    # splice span (max_push + one straddle block) must not lap the ring
    assert not bulk_ops.kernel_push_available(256, 256)
    assert bulk_ops.kernel_pop_available(512, 512)
    assert bulk_ops.kernel_pop_available(64, 32)
    assert not bulk_ops.kernel_pop_available(512, 200)    # max_n unaligned


# ------------------------------------------------------- fused supersteps


@pytest.mark.parametrize("sizes,k", [
    ([40, 0, 0, 0], 5),
    ([0, 17, 3, 25, 0, 9], 4),
])
def test_run_fused_conserves_and_matches_sequential_rounds(sizes, k):
    """ONE run_fused(k) dispatch conserves every task and follows the
    exact trajectory of k sequential round() calls — the on-device
    adaptive update is the same float32 computation the host controller
    runs, so sizes, telemetry and proportion history all agree."""
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt_seq = StealRuntime(len(sizes), 128, SPEC, policy=pol, adaptive=True)
    rt_fus = StealRuntime(len(sizes), 128, SPEC, policy=pol, adaptive=True)
    ids = _seed(rt_seq, sizes)
    _seed(rt_fus, sizes)
    for _ in range(k):
        rt_seq.round()
    rt_fus.run_fused(k)
    assert rt_fus.rounds_run == rt_seq.rounds_run == k
    np.testing.assert_array_equal(rt_fus.sizes(), rt_seq.sizes())
    assert rt_fus.controller.history == rt_seq.controller.history
    assert rt_fus.telemetry.summary() == rt_seq.telemetry.summary()
    assert _drained_ids(rt_fus) == sorted(ids)
    assert _drained_ids(rt_seq) == sorted(ids)


def test_run_fused_with_worker_body_conserves():
    """Fused rounds interleaving a pop/consume body with backend-routed
    rebalancing consume every id exactly once."""
    pol = StealPolicy(proportion=0.5, low_watermark=1, high_watermark=6,
                      max_steal=16)
    W = 4
    rt = StealRuntime(W, 128, SPEC, policy=pol, backend="pallas")
    ids = _seed(rt, [30, 0, 0, 0])
    ops = rt.ops

    def body(q, carry):
        q, item, valid = ops.pop(q)
        carry = carry + jnp.where(valid, item, 0)
        return q, carry

    carry = jnp.zeros((W,), jnp.int32)
    for _ in range(15):
        carry, _ = rt.run_fused(5, body, carry)
        if rt.total_size() == 0:
            break
    assert rt.total_size() == 0
    assert int(jnp.sum(carry)) == sum(ids)


# ------------------------------------------- early-exit fused (while_loop)


@pytest.mark.parametrize("sizes,k", [
    ([40, 0, 0, 0], 5),
    ([0, 17, 3, 25, 0, 9], 4),
])
def test_until_drained_matches_scan_when_not_draining(sizes, k):
    """With work left after k rounds, until_drained executes exactly k
    rounds with the identical trajectory as the scan path."""
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt_scan = StealRuntime(len(sizes), 128, SPEC, policy=pol)
    rt_wl = StealRuntime(len(sizes), 128, SPEC, policy=pol)
    ids = _seed(rt_scan, sizes)
    _seed(rt_wl, sizes)
    _, stats_scan = rt_scan.run_fused(k)
    _, stats_wl, rounds = rt_wl.run_fused(k, until_drained=True)
    assert rounds == k  # nothing drained: full block
    assert rt_wl.rounds_run == rt_scan.rounds_run == k
    np.testing.assert_array_equal(rt_wl.sizes(), rt_scan.sizes())
    assert rt_wl.controller.history == rt_scan.controller.history
    assert rt_wl.telemetry.summary() == rt_scan.telemetry.summary()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        stats_wl, stats_scan)
    assert _drained_ids(rt_wl) == sorted(ids)


def test_until_drained_early_exits_and_reports_rounds():
    """A consuming worker body drains the queues mid-block: the
    while_loop stops early, reports the executed count, and telemetry /
    rounds_run see only executed rounds."""
    pol = StealPolicy(proportion=0.5, low_watermark=1, high_watermark=6,
                      max_steal=16)
    W = 4
    rt = StealRuntime(W, 128, SPEC, policy=pol)
    ids = _seed(rt, [6, 0, 0, 0])
    ops = rt.ops

    def body(q, carry):
        q, item, valid = ops.pop(q)
        carry = carry + jnp.where(valid, item, 0)
        return q, carry

    carry = jnp.zeros((W,), jnp.int32)
    carry, stats, rounds = rt.run_fused(50, body, carry,
                                        until_drained=True)
    assert rounds < 50
    assert rt.total_size() == 0
    assert rt.rounds_run == rounds
    assert rt.telemetry.summary()["rounds"] == rounds
    assert np.asarray(stats.n_transferred).shape[0] == rounds
    assert int(jnp.sum(carry)) == sum(ids)
    # already drained: zero rounds execute, state untouched
    carry2, stats2, rounds2 = rt.run_fused(5, body, jnp.zeros((W,), jnp.int32),
                                           until_drained=True)
    assert rounds2 == 0
    assert rt.rounds_run == rounds
    assert int(jnp.sum(carry2)) == 0


def test_hierarchical_accounting_is_exact_not_replicated():
    """Per-level counters: seed so that NO intra-pod transfer is possible
    (lanes within each pod are balanced) and exactly one cross-pod steal
    happens.  Exact accounting reports that steal once; the former
    upper-bound accounting replicated the cross-pod share per pod and
    would have doubled it."""
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt = StealRuntime(8, 128, SPEC, policy=pol, pod_size=4, adaptive=False)
    ids = _seed(rt, [20, 20, 20, 20, 0, 0, 0, 0])
    rt.round()
    # Cross-pod: rep sizes (20, 0) -> one steal of floor(20 * 0.5) = 10.
    assert rt.telemetry.total_steals == 1
    assert rt.telemetry.total_transferred == 10
    # And the fused path reduces identically.
    rt2 = StealRuntime(8, 128, SPEC, policy=pol, pod_size=4, adaptive=False)
    _seed(rt2, [20, 20, 20, 20, 0, 0, 0, 0])
    rt2.run_fused(1)
    assert rt2.telemetry.summary() == rt.telemetry.summary()
    np.testing.assert_array_equal(rt2.sizes(), rt.sizes())
    for r in (rt, rt2):
        for _ in range(4):
            r.run_fused(2)
    assert _drained_ids(rt) == sorted(ids)


def test_run_fused_stacks_telemetry_rounds():
    pol = StealPolicy(proportion=0.5, low_watermark=2, high_watermark=8,
                      max_steal=32)
    rt = StealRuntime(4, 128, SPEC, policy=pol)
    _seed(rt, [40, 0, 0, 0])
    _, stats = rt.run_fused(3)
    # Stacked (k, ...) leaves, one telemetry record per fused round.
    assert np.asarray(stats.n_transferred).shape[0] == 3
    assert rt.telemetry.summary()["rounds"] == 3
    assert len(rt.controller.history) == 4


# ----------------------------------------------------------- adaptive servo


def test_adaptive_controller_tracks_imbalance():
    from repro.runtime.adaptive import AdaptiveController

    pol = StealPolicy(proportion=0.5, low_watermark=1, high_watermark=8)
    ctl = AdaptiveController(pol, AdaptiveConfig(gain=1.0))
    # many idle, one busy -> target rises toward max
    p_hungry = ctl.update(np.array([100, 0, 0, 0, 0, 0, 0, 0]))
    assert p_hungry > 0.5
    # one idle of many busy -> steal only a small slice per round
    p_calm = ctl.update(np.array([30, 30, 30, 30, 30, 30, 30, 0]))
    assert p_calm < p_hungry
    # balanced above watermarks -> no possible transfer -> hold
    held = ctl.update(np.array([10, 10, 10, 10, 10, 10, 10, 10]))
    assert held == p_calm
    # nothing stealable -> hold
    held2 = ctl.update(np.array([2, 2, 2, 2, 2, 2, 2, 2]))
    assert held2 == p_calm
