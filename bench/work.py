"""The least work of the algorithm, counted from a configuration's shapes,
and the chip peaks it is divided by.

The count is the same whichever formulation the program runs (explicit
gather, one-hot matmul, block-sparse): it is what the algorithm needs,
never the zeros a dense or blocked operator multiplies. A multiply, an
add, a square root and a division each count one operation.

Per acquisition (n_l samples, n_c channels, n_f frames of n_a steering
angles each, E = n_f n_a transmit events, n_s = n_l / decim IQ samples,
P = nz * nx pixels, k = lpf_taps):

  demod     mix: 2 n_l n_c E              (one multiply per I and Q)
            FIR at the decimated rate: 2 * 2k n_s n_c E
  beamform  per (pixel, channel, event): 16
              interpolation  3 per component: s0 + f (s1 - s0)     6
              rotation       complex multiply                       6
              apodized sum   2 multiplies + 2 adds                  4
  compound  per (pixel, frame), in re and im, n_a - 1 adds and one
              scaling by 1 / n_a: 2 P n_f n_a; none for one angle
  heads     per compounded frame, as for one angle:
            B-mode: per (pixel, frame) magnitude 4, max-normalise 1,
              16 square roots + 5 for the log, 3 for the range: 29
            color Doppler: wall filter 2 * 2w (n_f - w + 1) per pixel,
              lag-one autocorrelation 8 per pixel and frame pair,
              atan2 20, smooth 2 s^2 per pixel
            power Doppler: wall filter as above, R0 3 per pixel and
              frame, log 22 + range 3, smooth 2 s^2 per pixel
"""

from __future__ import annotations

import json
import os
from typing import Dict

from bench import steering

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def terms(g: Dict, modality: str) -> Dict[str, int]:
    """The operations of one acquisition by stage: demod, beamform,
    compound and head."""
    n_l, n_c, n_f = g["n_l"], g["n_c"], g["n_f"]
    n_a = g.get("n_angles", steering.DEFAULTS["n_angles"])
    events = n_f * n_a
    n_s = n_l // g["decim"]
    pix = g["nz"] * g["nx"]
    demod = 2 * n_l * n_c * events + 4 * g["lpf_taps"] * n_s * n_c * events
    beamform = 16 * pix * n_c * events
    compound = 2 * pix * n_f * n_a if n_a > 1 else 0
    w, s = g["wall_filter_taps"], g["smooth_kernel"]
    n_fw = n_f - w + 1
    wall = 4 * w * n_fw * pix
    if modality == "bmode":
        head = 29 * pix * n_f
    elif modality == "doppler":
        head = wall + 8 * (n_fw - 1) * pix + 20 * pix + 2 * s * s * pix
    elif modality == "power_doppler":
        head = wall + 3 * n_fw * pix + 25 * pix + 2 * s * s * pix
    else:
        raise ValueError(f"no operation count for modality {modality!r}")
    return {"demod": demod, "beamform": beamform, "compound": compound,
            "head": head}


def flops_per_acquisition(g: Dict, modality: str) -> int:
    return sum(terms(g, modality).values())


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE} (known: {sorted(table)})")
    return table[device_kind]
