"""Silent-downgrade observability: when ``auto`` routes an op to the
reference path (geometry predicate rejection) or REPRO_QUEUE_BACKEND
overrides an ``auto`` request, a one-shot BackendFallbackWarning names
the reason.  (The relaxed->fenced case is covered in test_relaxed.)"""

import warnings

import pytest

from repro.core import ops as bulk_ops


@pytest.fixture(autouse=True)
def _fresh():
    bulk_ops.reset_fallback_warnings()
    yield
    bulk_ops.reset_fallback_warnings()


def _fallback_msgs(rec):
    return [str(r.message) for r in rec
            if issubclass(r.category, bulk_ops.BackendFallbackWarning)]


def test_auto_geometry_rejection_warns_once_per_op():
    # capacity 100 with bound 24: 100 % block != 0 for every shrunken
    # block choice, so all kernel predicates reject.
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ops = bulk_ops.make_ops("auto", capacity=100, max_push=24,
                                max_steal=24)
        bulk_ops.make_ops("auto", capacity=100, max_push=24, max_steal=24)
    assert ops.resolved == "reference"
    msgs = _fallback_msgs(rec)
    assert msgs, "no fallback warning for a rejected auto geometry"
    assert all("auto" in m and "reference" in m for m in msgs)
    # one-shot: the repeat construction added nothing
    assert len(msgs) == len(set(msgs))


def test_auto_supported_geometry_is_silent():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ops = bulk_ops.make_ops("auto", capacity=256, max_push=128,
                                max_steal=128)
    assert ops.name == "auto"
    assert _fallback_msgs(rec) == []


def test_env_override_of_auto_warns(monkeypatch):
    monkeypatch.setenv(bulk_ops.BACKEND_ENV_VAR, "reference")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ops = bulk_ops.make_ops("auto", capacity=256, max_steal=128)
        bulk_ops.make_ops("auto", capacity=256, max_steal=128)
    assert ops.resolved == "reference"
    msgs = _fallback_msgs(rec)
    assert len(msgs) == 1
    assert bulk_ops.BACKEND_ENV_VAR in msgs[0]
    assert "reference" in msgs[0]


def test_explicit_backend_request_is_silent(monkeypatch):
    """Asking for 'reference' by name is not a downgrade."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        bulk_ops.make_ops("reference")
    assert _fallback_msgs(rec) == []


def test_per_call_payload_rejection_warns_once():
    """A kernel-routed op whose payload dtype the kernels cannot move
    (float16) runs on the reference path, with the same results, and says
    so once."""
    import jax.numpy as jnp
    import numpy as np

    pallas, ref = bulk_ops.make_ops("pallas"), bulk_ops.make_ops("reference")
    q = bulk_ops.make_queue(64, jnp.zeros((2,), jnp.float16))
    batch = jnp.ones((8, 2), jnp.float16)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        qp, _ = pallas.push(q, batch, 5)
        pallas.push(q, batch, 5)
    qr, _ = ref.push(q, batch, 5)
    np.testing.assert_array_equal(np.asarray(qp.buf), np.asarray(qr.buf))
    msgs = _fallback_msgs(rec)
    assert len(msgs) == 1
    assert "push runs on the reference path" in msgs[0]
    assert "float16" in msgs[0]
