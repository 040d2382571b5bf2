"""Arrival schedules, the decision stream and each cell's traffic."""

import numpy as np
import pytest

from bench import harness, load, serve_cell

BIG_SEED = 2**33 + 12345


@pytest.mark.parametrize("arrivals", [{"process": "poisson"},
                                      {"process": "mmpp", "fast": 4.0,
                                       "slow": 0.4, "flip": 0.05}])
def test_schedule_is_seeded_and_bounded(arrivals):
    a = load.arrival_schedule(3.0, 500.0, arrivals, load.rng_for(BIG_SEED, 1))
    b = load.arrival_schedule(3.0, 500.0, arrivals, load.rng_for(BIG_SEED, 1))
    c = load.arrival_schedule(3.0, 500.0, arrivals, load.rng_for(BIG_SEED, 2))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.size > 100 and a[0] >= 0 and a[-1] < 3.0
    assert np.all(np.diff(a) > 0)


def test_mmpp_mean_rate_near_base():
    arr = {"process": "mmpp", "fast": 4.0, "slow": 0.4, "flip": 0.05}
    a = load.arrival_schedule(200.0, 100.0, arr, load.rng_for(7))
    # equal time-average of the two states' gaps: 2 / (1/4 + 1/0.4) x base
    assert 0.5 * 72.7 < a.size / 200.0 < 1.5 * 72.7


def test_schedule_rejects_unknown_process():
    with pytest.raises(ValueError):
        load.arrival_schedule(1.0, 10.0, {"process": "weibull"},
                              load.rng_for(0))


def test_cut_requests_keeps_kinds_apart_and_sizes_cycle():
    mx = np.arange(6 * 3, dtype=np.float32).reshape(6, 3)
    rx = -np.arange(3 * 3, dtype=np.float32).reshape(3, 3) - 1
    mix = [("map", 4, 2), ("map", 1, 3), ("reduce", 5, 1)]
    reqs = load.cut_requests(mx, rx, mix, load.rng_for(BIG_SEED, 1))
    other = load.cut_requests(mx, rx, mix, load.rng_for(BIG_SEED, 2))
    shape = lambda rs: sorted((k, X.shape[0]) for k, X in rs)
    # every seed replays the same multiset of kinds and sizes
    assert shape(reqs) == shape(other) == sorted(
        [("map", 4)] * 2 + [("map", 1)] * 3 + [("reduce", 5)])
    assert [X.shape[0] for _, X in reqs] != [X.shape[0] for _, X in other]
    for kind, X in reqs:
        assert np.all(X >= 0) if kind == "map" else np.all(X < 0)
    # a request takes the next rows of its kind, wrapping round the trace
    maps = np.concatenate([X for k, X in reqs if k == "map"])
    assert np.array_equal(maps[:, 0], np.tile(mx[:, 0], 2)[:11])


def test_a_kind_the_trace_never_ran_takes_the_other_kinds_rows():
    mx = np.ones((4, 3), np.float32)
    reqs = load.cut_requests(mx, np.zeros((0, 3), np.float32),
                             [("reduce", 2, 2)], load.rng_for(1))
    assert [(k, X.shape) for k, X in reqs] == [("reduce", (2, 3))] * 2


def test_plan_clients_is_seeded():
    traffic = {"clients": 4, "rate_rps": 400.0,
               "arrivals": {"process": "poisson"}}
    p1 = load.plan_clients(50, traffic, 1.0, BIG_SEED, 2)
    p2 = load.plan_clients(50, traffic, 1.0, BIG_SEED, 2)
    assert len(p1) == 4
    for (i1, o1), (i2, o2) in zip(p1, p2):
        assert np.array_equal(i1, i2) and np.array_equal(o1, o2)
        assert i1.max() < 50 and o1.max() < 1.0


@pytest.mark.parametrize("cell", ["hadoop-1000.serve-bursty",
                                  "emr-13.serve-poisson"])
def test_each_cell_builds_its_traffic(cell):
    _, entry, config, traffic = harness.cell_files(cell)
    sut = serve_cell.ServeCell(config, traffic, BIG_SEED)
    mix = load.size_mix(config)
    sizes = sorted((k, X.shape[0]) for k, X in sut.requests)
    assert sizes == sorted((k, n) for k, n, c in mix for _ in range(c))
    assert sut.max_rows == max(n for _, n, _ in mix)
    f = config["forest"]
    for kind, forest in sut.forests.items():
        assert forest["feat_idx"].shape == (f["n_trees"], f["depth"])
        assert forest["leaves"].shape == (f["n_trees"], 1 << f["depth"])
        assert forest["feat_idx"].max() < f["n_features"]
    assert {k for k, _ in sut.requests} == {"map", "reduce"}
    again = serve_cell.ServeCell(config, traffic, BIG_SEED)
    assert all(np.array_equal(a[1], b[1])
               for a, b in zip(sut.requests, again.requests))
