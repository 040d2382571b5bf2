"""The correctness check on the CPU, with the timed path sound and broken
underneath: the harness runs everything but its look for a chip."""

import time

import numpy as np
import pytest

from bench import control, forest_ref, harness, serve_cell
from repro.online import server

CELL = "emr-13.serve-poisson"
SEED = 2**33 + 99


@pytest.fixture(autouse=True)
def cpu_as_the_chip(monkeypatch):
    """The harness's look for a chip, passed on the CPU."""
    def device_info(chips):
        import jax
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}
    monkeypatch.setattr(harness, "device_info", device_info)


def _run():
    return harness.run(CELL, SEED, 0.5, False, t_start=time.perf_counter())


def _params_dict(model):
    p = model.params
    return {"feat_idx": p.feat_idx, "thresholds": p.thresholds,
            "leaves": p.leaves}


def test_sound_path_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["checks"]["prob_max_gap"]["value"] == 0.0
    assert out["failed"] == 0


def test_one_altered_answer_is_caught(monkeypatch):
    real = server.score_groups
    calls = {"n": 0}

    def altered(groups, impl="numpy"):
        outs, n = real(groups, impl=impl)
        calls["n"] += 1
        if calls["n"] % 3 == 0:                # an answer, one ulp off
            outs[0] = outs[0].copy()
            outs[0][0] = np.nextafter(outs[0][0], np.float32(2))
        return outs, n

    monkeypatch.setattr(server, "score_groups", altered)
    out = _run()
    assert not out["correct"]
    assert 0 < out["checks"]["prob_max_gap"]["value"] < 1e-6


def test_lost_answers_are_caught(monkeypatch):
    real = server.score_groups
    calls = {"n": 0}

    def failing(groups, impl="numpy"):
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            raise RuntimeError("flush lost")
        return real(groups, impl=impl)

    monkeypatch.setattr(server, "score_groups", failing)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["unanswered"]["value"] > 0
    assert out["failed"] > 0


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    def lower_precision(groups, impl="numpy"):
        return [forest_ref.control_probs(_params_dict(m), np.asarray(X))
                for m, X in groups], 1

    monkeypatch.setattr(server, "score_groups", lower_precision)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["prob_max_gap"]["value"] > 1e-3


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**40 + 7])
def test_control_reading_fails_where_the_program_passes(seed):
    (row,) = control.readings(CELL, [seed], 0.5)
    assert row["program"]["prob_max_gap"] == 0.0
    assert row["control"]["prob_max_gap"] > serve_cell.LIMITS["prob_max_gap"]
