"""Steered plane-wave transmit: the one convention that the benchmark's RF
(bench/rf.py) and its reference (bench/reference.py) share.

A configuration's ``pipeline`` block may state ``n_angles`` (default 1)
and ``angle_max_deg`` (default 0.0). The steering angles are
``linspace(-angle_max_deg, +angle_max_deg, n_angles)``; a single angle is
0 degrees. An acquisition is int16 ``(n_l, n_c, n_f * n_angles)``, transmit
event ``e = f * n_angles + a``: the angles of one frame are consecutive,
in the order a scanner fires them.

The steered wavefront crosses the array centre (x = 0, z = 0) at t = 0,
so a point (z, x) is reached at ``tau_tx = (z cos(theta) + x sin(theta)) / c``;
the echo returns to the element at ``x_c`` after ``tau_rx = sqrt(z^2 +
(x - x_c)^2) / c``. The two-way delay is ``tau_tx + tau_rx``. There is no
transmit apodization.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

DEFAULTS = {"n_angles": 1, "angle_max_deg": 0.0}


def geometry(keys, pipeline: Dict) -> Dict:
    """``keys`` of a configuration's pipeline block, the steering keys
    at their defaults where the block leaves them out."""
    return {k: pipeline[k] if k in pipeline else DEFAULTS[k] for k in keys}


def angles(g: Dict) -> np.ndarray:
    """(n_angles,) steering angles in radians, float64."""
    n = int(g["n_angles"])
    if n < 1:
        raise ValueError(f"n_angles {n} is not a positive count")
    if n == 1:
        return np.zeros(1)
    m = float(g["angle_max_deg"])
    return np.deg2rad(np.linspace(-m, m, n))


def transmit_path(z, x, cos_t, sin_t):
    """The distance the wavefront travels from crossing the array centre
    to reaching (z, x): ``c * tau_tx``."""
    return z * cos_t + x * sin_t
