"""Telemetry edge cases (ISSUE 10 satellite): empty-stream summary,
direct ``reduce_round_stats`` unit coverage (flat and hierarchical
xpod accounting), and degenerate percentile inputs."""

import numpy as np

from repro.runtime.telemetry import Telemetry, reduce_round_stats


class _Stats:
    """Minimal RebalanceStats stand-in: numpy leaves, leading axis =
    lanes, with the xpod fields the hierarchical reduction reads."""

    def __init__(self, n_steals, n_transferred, bytes_moved,
                 n_steals_xpod=None, n_transferred_xpod=None,
                 bytes_moved_xpod=None):
        self.n_steals = np.asarray(n_steals)
        self.n_transferred = np.asarray(n_transferred)
        self.bytes_moved = np.asarray(bytes_moved)
        if n_steals_xpod is not None:
            self.n_steals_xpod = np.asarray(n_steals_xpod)
            self.n_transferred_xpod = np.asarray(n_transferred_xpod)
            self.bytes_moved_xpod = np.asarray(bytes_moved_xpod)


def test_summary_on_empty_stream():
    tele = Telemetry()
    s = tele.summary()
    assert s["rounds"] == 0
    assert s["steals"] == 0
    assert s["proportion_mean"] == 0.0
    assert s["proportion_final"] == 0.0
    assert s["imbalance_final"] == 0.0
    assert s["straggler_steps"] == 0
    assert "waves" not in s and "requests" not in s and "faults" not in s


def test_reduce_round_stats_flat_reads_replicated_element():
    # Flat mode: counters are replicated across lanes — element 0 exact.
    stats = _Stats([7, 7, 7, 7], [30, 30, 30, 30], [120, 120, 120, 120])
    assert reduce_round_stats(stats, n_workers=4) == (7, 30, 120)


def test_reduce_round_stats_hierarchical_sums_intra_plus_xpod_once():
    # 2 pods x 2 lanes.  Intra-pod counters replicate WITHIN a pod
    # (lane (p, 0) carries pod p's share); the cross-pod share lives in
    # the *_xpod fields, replicated across lane-0 representatives.
    stats = _Stats(
        n_steals=[3, 3, 5, 5],            # pod0 intra=3, pod1 intra=5
        n_transferred=[12, 12, 20, 20],
        bytes_moved=[48, 48, 80, 80],
        n_steals_xpod=[2, 0, 2, 0],       # xpod share, counted ONCE
        n_transferred_xpod=[8, 0, 8, 0],
        bytes_moved_xpod=[32, 0, 32, 0],
    )
    n_steals, n_transferred, bytes_moved = reduce_round_stats(
        stats, n_workers=4, pod_size=2)
    assert n_steals == 3 + 5 + 2
    assert n_transferred == 12 + 20 + 8
    # bytes_moved is PER-LANE: the busiest lane's intra payload plus the
    # pod-level share — not a sum over pods.
    assert bytes_moved == 80 + 32


def test_reduce_round_stats_hierarchical_zero_xpod_round():
    stats = _Stats([4, 4, 6, 6], [16, 16, 24, 24], [64, 64, 96, 96],
                   n_steals_xpod=[0, 0, 0, 0],
                   n_transferred_xpod=[0, 0, 0, 0],
                   bytes_moved_xpod=[0, 0, 0, 0])
    assert reduce_round_stats(stats, n_workers=4, pod_size=2) \
        == (10, 40, 96)


def test_single_request_percentiles_collapse_to_its_values():
    tele = Telemetry()
    tele.record_request(rid=0, admit=2, first=5, finish=9, tokens=4)
    s = tele.summary()
    assert s["requests"] == 1
    # One sample: every percentile is that sample.
    assert s["ttft_p50"] == s["ttft_p95"] == s["ttft_p99"] == 3.0
    assert s["latency_p50"] == s["latency_p99"] == 7.0
    wave = tele.record_wave(loads=[1, 2], served=1)
    assert wave.ttft_p99 == 3.0 and wave.latency_p95 == 7.0


def test_wave_round_alignment_and_fault_log_stamps():
    tele = Telemetry()
    tele.record(sizes=np.asarray([3, 1]), n_steals=1, n_transferred=1,
                proportion=0.5)
    tele.record_fault("kill", lane=1)
    w = tele.record_wave(loads=[2, 2], served=0)
    assert w.round == 1                      # closed after round 0
    assert tele.fault_log == [("kill", 1, 1)]
    tele.record_fault("restart")             # not lane-attributed
    assert tele.fault_log[-1] == ("restart", -1, 1)
    assert tele.summary()["faults"] == {"kill": 1, "restart": 1}
