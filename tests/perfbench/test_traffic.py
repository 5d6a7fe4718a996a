"""A seed orders the work and never draws it: two seeds offer the same
multiset of sizes and the same number of arrivals, in another order."""

import collections
import json
import pathlib

import pytest

from perfbench import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[2] / "perfbench" / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
SEEDS = (0, 7, 2**31 + 12345, 4_000_000_001)   # the driver's are large


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def sizes(plan):
    """Every (length, outputs or examples) of the plan, in plan order."""
    if plan["kind"] == "open_loop":
        return [(r["length"], r["examples"]) for r in plan["requests"]]
    return [(s["length"], s["outputs"])
            for client in plan["clients"] for s in client["sessions"]]


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_under_every_seed(name):
    plans = [traffic.build_plan(load(name), seed, 40.0) for seed in SEEDS]
    # Input sizes and output sizes each keep their multiset; which input
    # meets which output length in a session is the seed's to order.
    for column in (0, 1):
        multisets = [collections.Counter(row[column] for row in sizes(p))
                     for p in plans]
        assert all(m == multisets[0] for m in multisets)
    assert len({len(sizes(p)) for p in plans}) == 1


@pytest.mark.parametrize("name", [m for m in MIXES
                                  if len(set(load(m)["input_length_grid"])) > 1])
def test_another_seed_is_another_order(name):
    a, b = (sizes(traffic.build_plan(load(name), seed, 40.0))
            for seed in SEEDS[:2])
    assert a != b


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_is_the_same_plan(name):
    assert (traffic.build_plan(load(name), SEEDS[2], 40.0)
            == traffic.build_plan(load(name), SEEDS[2], 40.0))


@pytest.mark.parametrize("name", [m for m in MIXES
                                  if load(m)["kind"] == "open_loop"])
def test_arrival_count_is_fixed_by_the_rate(name):
    mix = load(name)
    for seconds in (10.0, 40.0):
        counts = set()
        for seed in SEEDS:
            plan = traffic.build_plan(mix, seed, seconds)
            due = [r["due"] for r in plan["requests"]]
            in_window = [t for t in due if 0.0 <= t < seconds]
            lead = [t for t in due if t < 0.0]
            assert all(-mix["lead_in_s"] <= t for t in lead)
            assert due == sorted(due)
            counts.add((len(in_window), len(lead)))
        assert counts == {(round(mix["rate_per_s"] * seconds),
                           round(mix["rate_per_s"] * mix["lead_in_s"]))}


def test_gaps_differ_between_seeds_but_not_the_count():
    import numpy as np

    a = traffic.arrivals(500, 10.0, np.random.default_rng(1))
    b = traffic.arrivals(500, 10.0, np.random.default_rng(2))
    assert len(a) == len(b) == 500 and a != b
    assert 0.0 < a[0] and a[-1] < 10.0


@pytest.mark.parametrize("n", [1, 5, 63, 64, 65, 200])
def test_ordered_keeps_the_law_in_every_pass(n):
    import numpy as np

    grid = list(range(100, 164))
    out = [traffic.ordered(grid, n, np.random.default_rng(s))
           for s in (1, 2)]
    assert sorted(out[0]) == sorted(out[1]) and len(out[0]) == n
    for start in range(0, n - 63, 64):      # each whole pass is the grid
        assert sorted(out[0][start:start + 64]) == grid


def test_sessions_start_staggered_over_the_ramp_before_the_window():
    mix = load("sessions")
    plan = traffic.build_plan(mix, 5, 40.0)
    starts = [c["start"] for c in plan["clients"]]
    assert len(starts) == mix["clients"]
    assert starts[0] == -mix["ramp_s"] and starts == sorted(starts)
    assert all(s < 0.0 for s in starts)


def test_request_inputs_follow_the_configuration():
    import numpy as np

    spec = [{"name": "ids", "kind": "tokens", "pad_to": 16},
            {"name": "mask", "kind": "ones"}]
    got = traffic.request_inputs(spec, 5, 3, 100, np.random.default_rng(0))
    assert got["ids"].shape == (3, 16) and got["mask"].shape == (3, 5)
    assert (got["ids"][:, :5] >= 2).all() and (got["ids"][:, 5:] == 0).all()
    assert got["ids"].dtype == np.int32 and (got["mask"] == 1).all()


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError):
        traffic.build_plan({"kind": "poisson"}, 0, 1.0)
