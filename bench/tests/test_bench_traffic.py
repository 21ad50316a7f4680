"""The general arrival generator and the committed traffic files."""

import json
import os

import numpy as np
import pytest

from bench import traffic

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def load(name):
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


def test_backlog_queues_every_acquisition_at_once():
    t = load("backlog")
    sched = traffic.schedule(t, 4, 10, 4)
    counts = [len(s) for s in sched]
    assert len(set(counts)) == 1 and counts[0] % 4 == 0
    assert sum(counts) >= 10 * t["window_acq_per_s"]
    assert sum(counts) < 10 * t["window_acq_per_s"] + 16
    assert max(max(s) for s in sched) < 0.005


def test_fleet_is_uniform_with_staggered_phases():
    t = load("fleet")
    seconds = 10
    sched = traffic.schedule(t, 4, seconds, 4)
    rate = t["offered_acq_per_s"]
    for i, s in enumerate(sched):
        assert s[0] == pytest.approx(i / rate)
        assert np.allclose(np.diff(s), 4 / rate)
    merged = np.sort(np.concatenate(sched))
    assert np.allclose(np.diff(merged), 1 / rate)
    assert merged[-1] == pytest.approx(seconds, abs=8 / rate)


def test_schedule_has_no_randomness():
    t = load("fleet")
    assert traffic.schedule(t, 4, 3, 4) == traffic.schedule(t, 4, 3, 4)


def test_bursts_and_rate_weights():
    t = {"offered_acq_per_s": 100.0, "window_acq_per_s": 80.0,
         "phase_share": 0.0, "burst": 3, "rate_weights": [3, 1]}
    fast, slow = traffic.schedule(t, 2, 1.2, 4)
    assert traffic.rates(t, 2) == [75.0, 25.0]
    assert fast[:3] == [0.0, 0.0, 0.0]
    assert fast[3] == pytest.approx(3 / 75.0)
    assert len(fast) == 3 * len(slow)


@pytest.mark.parametrize("bad", [
    {"offered_acq_per_s": 1.0, "window_acq_per_s": 1.0, "speed": 2},
    {"offered_acq_per_s": 0.0, "window_acq_per_s": 1.0},
    {"offered_acq_per_s": 1.0, "window_acq_per_s": 1.0, "phase_share": 1.0},
    {"offered_acq_per_s": 1.0, "window_acq_per_s": 1.0, "burst": 0},
    {"offered_acq_per_s": 1.0, "window_acq_per_s": 1.0,
     "rate_weights": [1.0]},
])
def test_malformed_traffic_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.schedule(bad, 2, 1.0, 4)
