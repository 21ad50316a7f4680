"""The algorithm's operation count and the table of peaks."""

import json
import os

import pytest

from bench import work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def pipeline(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["pipeline"]


def test_paper_bmode_count():
    g = pipeline("paper-bmode")
    n_s, pix = 1336 // 4, 128 * 128
    demod = 2 * 1336 * 64 * 32 + 4 * 31 * n_s * 64 * 32
    beamform = 16 * pix * 64 * 32
    head = 29 * pix * 32
    assert work.flops_per_acquisition(g, "bmode") == demod + beamform + head
    assert 0.6e9 < demod + beamform + head < 0.7e9


def test_count_ignores_the_formulation():
    g = dict(pipeline("paper-mixed"))
    counts = set()
    for variant in ("auto", "cnn", "dynamic", "sparse"):
        g["variant"] = variant
        counts.add(work.flops_per_acquisition(g, "doppler"))
    assert len(counts) == 1


def test_unknown_modality_has_no_count():
    with pytest.raises(ValueError):
        work.flops_per_acquisition(pipeline("paper-bmode"), "elastography")


def test_peaks_of_the_v5e_and_an_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
