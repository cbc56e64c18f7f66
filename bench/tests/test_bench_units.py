"""Generators, references, the peaks table and the byte count."""

import numpy as np
import pytest

from bench import dag, jobs, kernel_bytes, knapsack, peaks


def test_dag_reference_counts_every_node_once():
    n = 2_500_000
    salt = dag.salt_for(2**31 + 5)
    ref = dag.reference(n, 4, salt)
    assert ref["explored"] == n
    assert ref["id_sum"] == (n * (n - 1) // 2) & 0xFFFFFFFF
    ids = np.arange(n, dtype=np.int64)
    assert ref["hash_sum"] == int(dag._hash_np(ids, salt).sum()) & 0xFFFFFFFF
    assert dag.reference(n, 4, salt) == ref


def test_dag_salt_is_drawn_from_the_seed():
    assert dag.salt_for(7) == dag.salt_for(7)
    assert len({dag.salt_for(s) for s in range(20)}) == 20
    assert 0 <= dag.salt_for(2**33 + 1) < 2**32


def test_device_body_checksums_match_the_numpy_hash():
    import jax.numpy as jnp

    from repro.core.ops import make_ops
    from repro.runtime import StealRuntime

    rt = StealRuntime(2, 256, jnp.zeros((), jnp.int32),
                      backend=make_ops("reference"), max_pop=8)
    salt = dag.salt_for(3)
    driver = jobs.load_driver("dag_drain")
    body = driver.make_body(200, 8, 4, salt, rt.ops)
    rt.push(0, jnp.zeros((1,), jnp.int32), 1)
    carry = rt.run(body, driver.zero_carry(2), max_rounds=1000)
    got = dag.totals(carry)
    ref = dag.reference(200, 4, salt)
    assert {k: got[k] for k in ref} == ref


POOL = dict(n=20, r=1000, h=50, big_h=100)


def test_knapsack_instances_are_deterministic_per_seed():
    a = knapsack.instance("strongly_correlated", seed=0, index=0, **POOL)
    assert a == knapsack.instance("strongly_correlated", seed=0, index=0,
                                  **POOL)
    assert a != knapsack.instance("strongly_correlated", seed=1, index=0,
                                  **POOL)
    assert all(p == w + 100 for w, p in zip(a.weights, a.profits))
    assert a.capacity == 50 * sum(a.weights) // 101
    u = knapsack.instance("uncorrelated", seed=2**31 + 9, index=3, **POOL)
    assert all(1 <= x <= 1000 for x in u.weights + u.profits)
    assert knapsack.order(5, 8) == knapsack.order(5, 8)
    assert sorted(knapsack.order(5, 8)) == list(range(8))
    with pytest.raises(ValueError):
        knapsack.instance("subset_sum", seed=0, index=0, **POOL)


def test_dp_reference_on_a_hand_solved_instance():
    # max 8x1+5x2+7x3+6x4  s.t. 3x1+2x2+4x3+6x4 <= 7: optimum 15.
    inst = knapsack.Instance((3, 2, 4, 6), (8, 5, 7, 6), 7)
    assert knapsack.dp_optimum(inst) == 15


@pytest.mark.parametrize("cls,seed,supersteps", [
    ("strongly_correlated", 1, 66),
    ("uncorrelated", 0, 8),
])
def test_solver_counts_on_the_cpu(cls, seed, supersteps):
    """Superstep counts do not depend on the device: these are the CPU
    counts the cells were sized from."""
    from repro.core.dd.knapsack import Knapsack
    from repro.core.dd.parallel import parallel_solve

    inst = knapsack.instance(cls, seed=seed, index=0, **POOL)
    opt, stats = parallel_solve(Knapsack(*inst))
    assert opt == knapsack.dp_optimum(inst)
    assert stats["supersteps"] == supersteps


def test_peaks_table():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("cpu")


def test_queue_kernel_bytes_on_a_hand_counted_round():
    # One round of 8 lanes: 8 x 64 nodes popped, 200 children pushed, and
    # one steal of 100 int32 items (the victim's window, the thief's
    # splice).  Each item is read once and written once.
    counters = {"popped": 512, "pushed": 200, "transferred": 100,
                "item_bytes": 4}
    every = kernel_bytes.queue_kernel_bytes(counters, kernel_bytes.KINDS)
    assert every == 2 * 4 * (512 + (200 + 100) + 100) == 7296
    assert kernel_bytes.queue_kernel_bytes(counters, ["pop"]) == 4096
    assert kernel_bytes.queue_kernel_bytes(counters, ["ring_write"]) == 2400
    assert kernel_bytes.queue_kernel_bytes(counters, []) == 0
    with pytest.raises(ValueError):
        kernel_bytes.queue_kernel_bytes(counters, ["gather"])
