"""One general generator of arrival schedules, read from a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

  ``offered_acq_per_s``   total arrival rate over all probes (acq/s);
  ``window_acq_per_s``    how much work one run holds: the run offers
                          ``--seconds`` x this many acquisitions, split
                          evenly over the probes and rounded up to a
                          multiple of the batch size per probe;
  ``phase_share``         probe i starts ``i * phase_share`` of its own
                          period late, so the probes' arrivals interleave
                          unaligned;
  ``burst``               (optional, default 1) arrivals that come
                          together: every burst/rate seconds, ``burst``
                          acquisitions of one probe arrive at once;
  ``rate_weights``        (optional, default equal) each probe's share of
                          the offered rate, normalised over the probes.

Within a probe, acquisition k arrives at
``phase_i + (k // burst) * burst / rate_i`` (the uniform open-loop
arithmetic of the program's own arrival processes). The schedule has no
randomness: every seed gets the same sizes and arrival times, and only
the RF content changes with the seed.
"""

from __future__ import annotations

import math
from typing import Dict, List

KEYS = {"offered_acq_per_s", "window_acq_per_s", "phase_share", "burst",
        "rate_weights", "why"}


def validate(traffic: Dict) -> None:
    unknown = set(traffic) - KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    for k in ("offered_acq_per_s", "window_acq_per_s"):
        if not traffic.get(k, 0) > 0:
            raise ValueError(f"traffic {k} must be > 0")
    if not 0 <= traffic.get("phase_share", 0) < 1:
        raise ValueError("traffic phase_share must be in [0, 1)")
    if int(traffic.get("burst", 1)) < 1:
        raise ValueError("traffic burst must be >= 1")


def frames_per_probe(traffic: Dict, n_probes: int, seconds: float,
                     max_batch: int) -> List[int]:
    """Acquisitions each probe offers in one run."""
    total = seconds * traffic["window_acq_per_s"]
    w = _weights(traffic, n_probes)
    return [max_batch * max(1, math.ceil(total * wi / max_batch))
            for wi in w]


def _weights(traffic: Dict, n_probes: int) -> List[float]:
    w = traffic.get("rate_weights") or [1.0] * n_probes
    if len(w) != n_probes or min(w) <= 0:
        raise ValueError(f"rate_weights {w} do not fit {n_probes} probes")
    return [wi / sum(w) for wi in w]


def rates(traffic: Dict, n_probes: int) -> List[float]:
    """Each probe's offered rate (acq/s)."""
    return [traffic["offered_acq_per_s"] * wi
            for wi in _weights(traffic, n_probes)]


def schedule(traffic: Dict, n_probes: int, seconds: float,
             max_batch: int) -> List[List[float]]:
    """Arrival times (s, from the window's start) of every acquisition,
    one list per probe."""
    validate(traffic)
    burst = int(traffic.get("burst", 1))
    out = []
    for i, (rate, n) in enumerate(zip(
            rates(traffic, n_probes),
            frames_per_probe(traffic, n_probes, seconds, max_batch))):
        step = burst / rate
        phase = i * traffic.get("phase_share", 0.0) * step
        out.append([phase + (k // burst) * step for k in range(n)])
    return out
