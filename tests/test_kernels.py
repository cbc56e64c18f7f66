"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracle (assigned requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dd_expand.ops import expand_layer_bulk
from repro.kernels.dd_expand.ref import expand_ref
from repro.kernels.flash_attention.ops import mha
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.queue_steal.ops import steal_gather
from repro.kernels.queue_steal.ref import ring_gather_ref
from repro.kernels.queue_transfer.ops import transfer_splice
from repro.kernels.queue_transfer.ref import ring_transfer_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- flash

FLASH_CASES = [
    # (B, S, T, H, K, hd, causal, window, softcap, dtype)
    (2, 128, 128, 4, 2, 64, True, None, None, jnp.float32),
    (1, 256, 256, 4, 4, 64, True, 128, None, jnp.float32),
    (2, 128, 256, 8, 2, 32, True, None, 50.0, jnp.float32),
    (1, 128, 128, 2, 1, 128, False, None, None, jnp.float32),
    (1, 128, 128, 4, 4, 64, True, None, None, jnp.bfloat16),
    (2, 128, 256, 4, 2, 32, True, 64, 30.0, jnp.float32),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    B, S, T, H, K, hd, causal, window, cap, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, K, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, K, hd), jnp.float32).astype(dtype)
    out_k = mha(q, k, v, causal=causal, window=window, softcap=cap,
                interpret=True)
    ke = jnp.repeat(k, H // K, 2)
    ve = jnp.repeat(v, H // K, 2)
    out_r = attention_ref(q, ke, ve, causal=causal, window=window,
                          softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)


# ----------------------------------------------------------- queue_steal

STEAL_CASES = [
    (512, 8, 256, 0, 100, jnp.float32),
    (512, 8, 256, 500, 256, jnp.float32),     # wraps
    (1024, 16, 512, 777, 333, jnp.float32),
    (256, 4, 256, 255, 256, jnp.int32),       # full wrap, int payload
    (256, 4, 128, 13, 0, jnp.float32),        # empty steal
    (256, 128, 256, 100, 200, jnp.bfloat16),
    # batches shorter than a tile are padded to one; wrap past the end
    (512, 1, 1, 511, 1, jnp.int32),
    (512, 1, 4, 510, 4, jnp.int32),
    (512, 3, 8, 509, 8, jnp.int32),
    (256, 2, 4, 254, 3, jnp.bfloat16),
    (256, 8, 8, 250, 8, jnp.bfloat16),
]


@pytest.mark.parametrize("case", STEAL_CASES)
def test_queue_steal_matches_ref(case):
    cap, W, max_steal, lo, n, dtype = case
    if jnp.issubdtype(dtype, jnp.integer):
        buf = jax.random.randint(KEY, (cap, W), 0, 1000, dtype)
    else:
        buf = jax.random.normal(KEY, (cap, W), jnp.float32).astype(dtype)
    out_k = steal_gather(buf, jnp.int32(lo), jnp.int32(n),
                         max_steal=max_steal, interpret=True)
    out_r = ring_gather_ref(buf, lo, n, max_steal)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


# ----------------------------------------------------- queue_transfer

# (cap, W, n_lanes, max_steal, head, src_row, n, dtype)
TRANSFER_CASES = [
    (512, 8, 4, 256, 0, 0, 100, jnp.float32),
    (512, 8, 4, 256, 500, 3, 256, jnp.float32),   # splice wraps the ring
    (1024, 16, 8, 128, 777, 5, 33, jnp.float32),
    (256, 4, 4, 64, 255, 2, 64, jnp.int32),       # int payload, wrap
    (256, 4, 4, 64, 13, 1, 0, jnp.float32),       # empty transfer
    (256, 128, 2, 128, 100, 1, 77, jnp.bfloat16),
    # spans shorter than a tile are padded to one; wrap past the end
    (64, 1, 4, 1, 63, 3, 1, jnp.int32),
    (64, 20, 4, 4, 62, 2, 4, jnp.int32),
    (64, 3, 2, 8, 60, 1, 8, jnp.float32),
    (64, 2, 4, 4, 61, 0, 3, jnp.bfloat16),
]


@pytest.mark.parametrize("case", TRANSFER_CASES)
def test_queue_transfer_matches_ref(case):
    cap, W, n_lanes, max_steal, head, src_row, n, dtype = case
    ks = jax.random.split(KEY, 2)
    if jnp.issubdtype(dtype, jnp.integer):
        buf = jax.random.randint(ks[0], (cap, W), 0, 1000, dtype)
        gathered = jax.random.randint(ks[1], (n_lanes, max_steal, W), 0,
                                      1000, dtype)
    else:
        buf = jax.random.normal(ks[0], (cap, W), jnp.float32).astype(dtype)
        gathered = jax.random.normal(ks[1], (n_lanes, max_steal, W),
                                     jnp.float32).astype(dtype)
    out_k = transfer_splice(buf, gathered, jnp.int32(head),
                            jnp.int32(src_row), jnp.int32(n),
                            max_steal=max_steal, interpret=True)
    out_r = ring_transfer_ref(buf, gathered.reshape(-1, W),
                              head, src_row * max_steal, n)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_queue_transfer_equals_select_then_push():
    """The fused transfer must equal the two-step oracle: select the
    victim's window row, then ring-scatter it at the head."""
    from repro.kernels.queue_push.ref import ring_scatter_ref

    cap, W, n_lanes, max_steal = 512, 8, 4, 128
    ks = jax.random.split(KEY, 2)
    buf = jax.random.normal(ks[0], (cap, W), jnp.float32)
    gathered = jax.random.normal(ks[1], (n_lanes, max_steal, W), jnp.float32)
    for head, src_row, n in [(0, 0, 128), (450, 3, 100), (77, 2, 1)]:
        fused = transfer_splice(buf, gathered, jnp.int32(head),
                                jnp.int32(src_row), jnp.int32(n),
                                max_steal=max_steal, interpret=True)
        two_step = ring_scatter_ref(buf, gathered[src_row], head, n)
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(two_step))


# ----------------------------------------- UTS nodes at the cell's geometry

# (op, head or lo, n): six int32 words a node (digest bit patterns and
# height) in a 16,384-slot ring; pushes of up to 512 (64 parents x 8
# children) and the root's 2,000 children, pops of 64, windows of 8,192.
UTS_CASES = [
    ("push", 16_200, 488),     # wraps past the ring's end
    ("push", 3, 512),
    ("seed", 16_000, 2000),    # padded to 2,048 rows; wraps
    ("pop", 16_350, 64),       # the newest 64 straddle the end
    ("window", 16_300, 8192),
    ("transfer", 16_383, 8192),
]


@pytest.mark.parametrize("case", UTS_CASES, ids=lambda c: f"{c[0]}-{c[1]}")
def test_uts_geometry_matches_ref(case):
    """The kernels move 24-byte UTS nodes bit-exactly as the reference."""
    from repro.kernels.queue_push.kernel import ring_scatter, ring_slice
    from repro.kernels.queue_push.ref import ring_scatter_ref, ring_slice_ref
    from repro.kernels.queue_steal.kernel import ring_gather

    op, at, n = case
    cap, width = 16_384, 6
    ks = jax.random.split(KEY, 2)
    words = lambda k, shape: jax.random.randint(  # noqa: E731
        k, shape, jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max,
        jnp.int32)
    buf = words(ks[0], (cap, width))
    if op in ("push", "seed"):
        rows = 2048 if op == "seed" else 512
        batch = words(ks[1], (rows, width))
        out_k = ring_scatter(buf, batch, jnp.int32(at), jnp.int32(n),
                             interpret=True)
        out_r = ring_scatter_ref(buf, batch, at, n)
    elif op == "pop":
        size = 200
        out_k = ring_slice(buf, jnp.int32(at), jnp.int32(size), jnp.int32(n),
                           n, interpret=True)
        out_r = ring_slice_ref(buf, at, size, n, n)
    elif op == "window":
        out_k = ring_gather(buf, jnp.int32(at), jnp.int32(n), n,
                            interpret=True)
        out_r = ring_gather_ref(buf, at, n, n)
    else:
        gathered = words(ks[1], (2, n, width))
        out_k = transfer_splice(buf, gathered, jnp.int32(at), jnp.int32(1),
                                jnp.int32(n // 2), max_steal=n,
                                interpret=True)
        out_r = ring_transfer_ref(buf, gathered.reshape(-1, width), at, n,
                                  n // 2)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


# --------------------------------------------------------------- ssd_scan

SSD_CASES = [
    (2, 64, 4, 16, 32, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 256, 8, 64, 128, 128),
    (1, 64, 1, 8, 8, 64),       # single chunk (S == Q)
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_ref(case):
    B, S, nh, hd, ns, Q = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hd), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, ns)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, ns)) * 0.3
    D = jnp.ones((nh,))
    y_k, fin_k = ssd(x, dt, A, Bm, Cm, D, chunk=Q, interpret=True)
    y_r, fin_r = ssd_chunked(x, dt, A, Bm, Cm, D, Q)
    np.testing.assert_allclose(y_k, y_r, atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(fin_k, fin_r, atol=5e-5, rtol=5e-4)


def test_ssd_decode_consistency():
    """Chunked scan == running mamba_decode_step token by token (state)."""
    from repro.kernels.ssd_scan.ref import ssd_chunk_ref

    Q, hd, ns = 16, 8, 4
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Q, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Q,)))
    a = -jnp.exp(jax.random.normal(ks[2], ()) * 0.3)
    Bm = jax.random.normal(ks[3], (Q, ns)) * 0.3
    Cm = jax.random.normal(ks[4], (Q, ns)) * 0.3
    y, state = ssd_chunk_ref(x, dt, a, Bm, Cm, jnp.float32(0.0),
                             jnp.zeros((hd, ns)))
    # sequential recurrence oracle
    st = jnp.zeros((hd, ns))
    ys = []
    for t in range(Q):
        dA = jnp.exp(dt[t] * a)
        st = st * dA + dt[t] * jnp.outer(x[t], Bm[t])
        ys.append(st @ Cm[t])
    np.testing.assert_allclose(y, jnp.stack(ys), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(state, st, atol=1e-4, rtol=1e-3)


# -------------------------------------------------------------- dd_expand

@pytest.mark.parametrize("N", [256, 512, 1024])
@pytest.mark.parametrize("wp", [(3, 8), (50, 1), (0, 0)])
def test_dd_expand_matches_ref(N, wp):
    w, p = wp
    s = jax.random.randint(KEY, (N,), -1, 100, jnp.int32)
    v = jax.random.randint(KEY, (N,), 0, 50, jnp.int32)
    sk, vk = expand_layer_bulk(s, v, w, p, interpret=True)
    sr, vr = expand_ref(s, v, w, p)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
