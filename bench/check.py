"""The comparison that decides ``correct``: served images against the
plain reference, each number against its limit.

Per served image, ``d = served - reference`` over every pixel (and every
frame of a B-mode image):

  ``max_abs``        the largest |d|;
  ``mean_abs``       the mean |d|;
  ``far_share``      (B-mode) the share of pixels with |d| over ``FAR``.
                     The log compression (16 square roots, then
                     2^16 (y - 1)) rounds the image to steps of about
                     5.7e-4 of the 60 dB range, and a last-bit
                     difference anywhere upstream moves about a tenth of
                     the pixels by one step in a sound run; ``FAR`` lies
                     between one step and two, so this counts only the
                     pixels that an error larger than rounding moved;
  ``wrapped_share``  (color Doppler) the share of pixels whose |d| is
                     over half a step of 2 / s^2: a phase at the
                     aliasing boundary (+-pi) may flip by 2 pi under a
                     last-bit difference, and the s x s smooth spreads a
                     flip as steps of 2 / s^2. For color Doppler,
                     ``max_abs`` and ``mean_abs`` are taken after those
                     steps are removed.

A cell's number is the worst over its served images, per modality, and is
compared with the limit that the configuration file states for it under
``limits``. A missing image counts against ``missing_images``, whose
limit is 0.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

FAR = 1e-3


def image_numbers(modality: str, got, want, smooth_kernel: int) -> Dict:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return {"max_abs": float("inf"), "mean_abs": float("inf"),
                "wrapped_share": 1.0}
    d = got - want
    if modality == "doppler":
        q = 2.0 / smooth_kernel ** 2
        r = (d + q / 2) % q - q / 2
        return {"max_abs": float(np.abs(r).max()),
                "mean_abs": float(np.abs(r).mean()),
                "wrapped_share": float((np.abs(d) > q / 2).mean())}
    a = np.abs(d)
    return {"max_abs": float(a.max()), "mean_abs": float(a.mean()),
            "far_share": float((a > FAR).mean())}


def worst(per_image: List[Tuple[str, Dict]]) -> Dict[str, float]:
    """{"<modality>.<number>": worst over images}."""
    out: Dict[str, float] = {}
    for modality, nums in per_image:
        for k, v in nums.items():
            key = f"{modality}.{k}"
            out[key] = max(out.get(key, v), v)
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, Dict[str, float]],
          missing: int) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) for every number that has a
    limit; a modality that was served must have its limits stated."""
    compared = {"missing_images": {"value": missing, "limit": 0}}
    served = {k.split(".")[0] for k in numbers}
    for modality in sorted(served):
        if modality not in limits:
            raise KeyError(f"the configuration states no limits for "
                           f"{modality!r}")
        for k, lim in sorted(limits[modality].items()):
            compared[f"{modality}.{k}"] = {
                "value": numbers[f"{modality}.{k}"], "limit": lim}
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    return ok, compared
