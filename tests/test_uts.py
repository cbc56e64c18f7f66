"""UTS on the steal runtime (``repro.core.uts``): the device SHA-1 is
bit-exact against ``hashlib``, the child rule is UTS's float test in
integers, and a search through ``launch_runtime`` -> ``run_fused``
explores a small T3-rule tree exactly as the sequential ``hashlib``
walk does, on the reference and the kernel-routed backends."""

import hashlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import uts
from repro.core.ops import BackendFallbackWarning, reset_fallback_warnings
from repro.core.policy import StealPolicy

# T3's rules (UTS 2.1 sample_trees.sh: -t 1 -b 2000 -q 0.124875 -m 8
# -r 42) on a small root: b0 = 100, r = 7.  A hashlib walk gives 5,989
# nodes, 5,252 leaves and depth 63.
SMALL = uts.BinomialTree(b0=100, q=0.124875, m=8, root_seed=7)
SMALL_SHAPE = {"nodes": 5989, "leaves": 5252, "depth": 63}
# fig9-dag's policy, which the uts-t3 cell runs.
PAPER = StealPolicy(proportion=0.5, queue_limit=2, low_watermark=1,
                    high_watermark=8, max_steal=8192)
SALT = 0xDEADBEEF


def _bytes(words: np.ndarray) -> bytes:
    return np.asarray(words, np.uint32).astype(">u4").tobytes()


def test_spawn_is_bit_exact_against_hashlib():
    rng = np.random.default_rng(2)
    parents = rng.integers(0, 2**32, size=(2000, 5), dtype=np.uint64)
    parents = parents.astype(np.uint32)
    index = np.arange(2000, dtype=np.uint32)
    out = np.asarray(jax.jit(uts.rng_spawn)(parents, index))
    for p, i, got in zip(parents, index, out):
        want = hashlib.sha1(_bytes(p) + int(i).to_bytes(4, "big")).digest()
        assert _bytes(got) == want


@pytest.mark.parametrize("seed", [0, 7, 42, 2**31 - 1, 2**32 - 1])
def test_rng_init_is_bit_exact_against_hashlib(seed):
    want = hashlib.sha1(bytes(16) + seed.to_bytes(4, "big")).digest()
    assert _bytes(jax.jit(uts.rng_init, static_argnums=0)(seed)) == want


def test_child_rule_is_the_float_test_in_integers():
    thr = uts.BinomialTree(2000, 0.124875, 8, 42).threshold
    assert thr - 1 == 268_167_020
    for draw in (thr - 2, thr - 1, thr, thr + 1):
        assert (draw < thr) == (draw / 2147483648.0 < 0.124875)


def test_reference_walk_gives_the_known_small_tree():
    ref = uts.reference_search(SMALL, SALT)
    assert {k: ref[k] for k in SMALL_SHAPE} == SMALL_SHAPE


@pytest.mark.parametrize("backend", ["reference", "auto"])
def test_search_equals_the_reference(backend):
    got = uts.uts_search(SMALL, 8, ring_slots=16_384, pop_batch=64,
                         policy=PAPER, backend=backend, fused_rounds=8,
                         root_lane=3, salt=SALT)
    ref = uts.reference_search(SMALL, SALT)
    for key in ("nodes", "leaves", "depth", "hash_sum"):
        assert got[key] == ref[key], key
    assert got["final_sizes"] == [0] * 8
    assert got["rounds"] >= ref["depth"]


def test_t3_geometry_runs_every_queue_op_on_the_kernels():
    """At the cell's geometry (16,384-slot rings, pops of 64, pushes of
    512, max_steal 8,192, the 2,000-child seeding push) ``auto`` routes
    every op to the kernels and no call falls back."""
    reset_fallback_warnings()
    t3 = uts.BinomialTree(2000, 0.124875, 8, 42)
    with warnings.catch_warnings():
        warnings.simplefilter("error", BackendFallbackWarning)
        search = uts.UTSSearch(t3, 8, ring_slots=16_384, pop_batch=64,
                               policy=PAPER, backend="auto")
        assert search.rt.ops.resolved == "pallas"
        search.seed(5)
        search.rt.lower(search.body, search.zero_carry(), fused=8)
    sizes = search.rt.sizes()
    assert sizes[5] == 2000 and sizes.sum() == 2000
    # The seeded items are the root's children at height 1.
    lane = np.asarray(search.rt.lane(5).buf[:2000])
    assert (lane[:, 5] == 1).all()
    root = hashlib.sha1(bytes(16) + (42).to_bytes(4, "big")).digest()
    for i in (0, 1999):
        want = hashlib.sha1(root + i.to_bytes(4, "big")).digest()
        assert _bytes(lane[i, :5].view(np.uint32)) == want


def test_body_counts_and_pushes_children_of_parents_only():
    """One round of the worker body on one lane, against the rule."""
    from repro.core.ops import make_ops, make_queue

    ops = make_ops("reference")
    root = jax.jit(uts.rng_init, static_argnums=0)(7)
    kids = np.asarray(jax.jit(uts.rng_spawn)(
        jnp.broadcast_to(root, (10, 5)), jnp.arange(10, dtype=jnp.uint32)))
    items = np.concatenate([kids.view(np.int32),
                            np.full((10, 1), 3, np.int32)], axis=1)
    q, _ = ops.push(make_queue(256, uts.ITEM), jnp.asarray(items), 10)
    body = uts.make_body(SMALL, 16, SALT, ops)
    q, carry = jax.jit(body)(q, {k: v[0]
                                 for k, v in uts.zero_carry(1).items()})
    parents = int(((kids[:, 4] & 0x7FFFFFFF) < SMALL.threshold).sum())
    assert int(carry["explored"]) == 10
    assert int(carry["leaves"]) == 10 - parents
    assert int(carry["max_height"]) == 3
    assert int(q.size) == 8 * parents
    heights = np.asarray(q.buf[:int(q.size), 5])
    assert (heights == 4).all()
