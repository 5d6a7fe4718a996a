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


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("n, seconds", [(1, 4.0), (60, 4.0), (320, 40.0),
                                        (480, 40.0), (1040, 40.0),
                                        (2000, 120.0)])
def test_one_arrival_in_every_slot(n, seconds, seed):
    import numpy as np

    due = traffic.arrivals(n, seconds, np.random.default_rng(seed))
    slot = seconds / n
    assert len(due) == n and due == sorted(due)
    assert [int(t // slot) for t in due] == list(range(n))
    assert 0.0 <= due[0] and due[-1] < seconds
    # No clumps and no holes: two neighbours are never two slots apart.
    assert all(b - a < 2 * slot for a, b in zip(due, due[1:]))


def test_no_arrivals_for_none_asked():
    import numpy as np

    assert traffic.arrivals(0, 4.0, np.random.default_rng(0)) == []


@pytest.mark.parametrize("name", [m for m in MIXES
                                  if load(m)["kind"] == "open_loop"])
def test_a_seed_moves_every_arrival_and_keeps_the_lengths(name):
    mix = load(name)
    a, b = (traffic.build_plan(mix, seed, 40.0)["requests"]
            for seed in SEEDS[2:])
    assert len(a) == len(b)
    assert all(x["due"] != y["due"] for x, y in zip(a, b))
    assert collections.Counter(r["length"] for r in a) \
        == collections.Counter(r["length"] for r in b)
    assert [r["length"] for r in a] != [r["length"] for r in b]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", [m for m in MIXES
                                  if load(m)["kind"] == "open_loop"])
def test_the_lead_in_is_negative_and_slotted_too(name, seed):
    mix = load(name)
    slot = 1.0 / mix["rate_per_s"]
    due = [r["due"] for r in traffic.build_plan(mix, seed, 40.0)["requests"]]
    lead = [t for t in due if t < 0.0]
    assert len(lead) == round(mix["rate_per_s"] * mix["lead_in_s"])
    assert [int((t + mix["lead_in_s"]) // slot) for t in lead] \
        == list(range(len(lead)))
    # The window's own slots begin at its opening, whatever the lead-in.
    assert [int(t // slot) for t in due[len(lead):]] \
        == list(range(len(due) - len(lead)))


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


@pytest.mark.parametrize("seed, parents", [(7, "3cb57b36363f98ac"),
                                           (4_000_000_001, "f356dd3e262be8b0")])
def test_the_sessions_plan_is_the_parent_s_but_for_more_sessions_a_client(
        seed, parents):
    """PR 45 changed one number of the mix: a client may run 64 sessions,
    not 32 (the busiest started 20-24 of its 32 in the 80 s of ramp and
    window). With 32 the plan is the parent's to the letter (its digest,
    taken with the parent's code), and with 64 every client starts when
    it did and runs the same first 31 sessions."""
    import hashlib

    mix = load("sessions")
    assert mix["items_per_client"] == 64
    before = traffic.build_plan(dict(mix, items_per_client=32), seed, 40.0)
    digest = hashlib.sha256(
        json.dumps(before, sort_keys=True).encode()).hexdigest()
    assert digest.startswith(parents)
    now = traffic.build_plan(mix, seed, 40.0)
    for was, is_ in zip(before["clients"], now["clients"], strict=True):
        assert was["start"] == is_["start"]
        assert was["sessions"][:31] == is_["sessions"][:31]
        assert len(is_["sessions"]) == 64


@pytest.mark.parametrize("name", MIXES)
def test_a_longer_window_is_a_number_with_its_reason_beside_it(name):
    """Any mix, those that later PRs add too, may ask for a window of
    `window_scale` times --seconds (a cell of long requests needs it):
    the rule is only that it is a number of 1 or more, and that a mix
    which asks says why."""
    mix = load(name)
    scale = mix.get("window_scale", 1)
    assert isinstance(scale, (int, float)) and scale >= 1
    assert scale == 1 or mix.get("window_scale_why")


@pytest.mark.parametrize("name, scale", [("sessions", 2), ("generate", 1),
                                         ("mixed-generate", 1)])
def test_the_window_of_each_accepted_mix(name, scale):
    """By name: the three mixes whose bounds rest on sets at these
    lengths (PERF.md section 2). A new mix is none of this test's."""
    assert load(name).get("window_scale", 1) == scale


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
