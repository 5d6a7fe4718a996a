"""The one general traffic generator. A traffic mix is a data file of
parameters (perfbench/traffic/<name>.json); this code reads it.

What makes runs comparable: a seed ORDERS the work and never DRAWS it.
Each length law is a fixed grid of quantiles. The seed permutes the grid
(a fresh permutation per pass, so any 64 consecutive requests hold the
whole law, heavy tail included) and places each arrival of an open loop
inside its own slot of 1 / rate (no clumps: these are steady mixes); the
number of arrivals in the window is fixed by the rate. Two seeds offer
the same multiset of sizes and the same count, in another order.

Kinds (the loops are code, the files parameterise them):
  open_loop    arrivals on a schedule, whether or not earlier ones ended
  sessions     `clients` callers, each running decode sessions back to
               back: init, steps up to the drawn output length, close
"""

from __future__ import annotations

import math

import numpy as np

KINDS = ("open_loop", "sessions")
# --seed may pass 2**31; numpy takes any non-negative integer.


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def ordered(grid, n: int, rng: np.random.Generator) -> list[int]:
    """`n` values: whole passes over `grid`, each pass freshly permuted,
    then a remainder of evenly spaced quantiles (the same multiset under
    every seed), permuted too."""
    grid = sorted(int(v) for v in grid)
    out: list[int] = []
    for _ in range(n // len(grid)):
        out.extend(int(v) for v in rng.permutation(grid))
    rest = n - len(out)
    if rest:
        picks = [grid[int((j + 0.5) * len(grid) / rest)] for j in range(rest)]
        out.extend(int(v) for v in rng.permutation(picks))
    return out


def arrivals(n: int, seconds: float, rng: np.random.Generator) -> list[float]:
    """`n` due times in [0, seconds), one inside each slot of
    `seconds / n`: the i-th lies in [i, i + 1) slots, and the seed places
    it there. The count and the spacing are the rate's; the seed moves
    every arrival by up to one slot and never clumps them."""
    if n <= 0:
        return []
    slot = seconds / n
    return [float((i + u) * slot) for i, u in enumerate(rng.random(n))]


def build_plan(traffic: dict, seed: int, seconds: float,
               rate: float | None = None) -> dict:
    """The work of one run. Times are seconds relative to the window's
    opening; lead-in work has negative due times and is not counted."""
    kind = traffic["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}; known: {KINDS}")
    examples = int(traffic.get("examples_per_request", 1))
    if kind == "open_loop":
        rate = float(traffic["rate_per_s"] if rate is None else rate)
        lead = float(traffic.get("lead_in_s", 0.0))
        n = int(round(rate * seconds))
        n_lead = int(round(rate * lead))
        lengths = ordered(traffic["input_length_grid"], n + n_lead,
                          _rng(seed, 0))
        due = ([t - lead for t in arrivals(n_lead, lead, _rng(seed, 1))]
               + arrivals(n, seconds, _rng(seed, 2)))
        return {"kind": kind, "rate_per_s": rate,
                "requests": [{"id": i, "due": due[i], "length": lengths[i],
                              "examples": examples}
                             for i in range(n + n_lead)]}
    clients = int(traffic["clients"])
    per_client = int(traffic["items_per_client"])
    total = clients * per_client
    lengths = ordered(traffic["input_length_grid"], total, _rng(seed, 0))
    # Each client's FIRST session is no draw. The clients start staggered
    # over the ramp, and their first output lengths are evenly spaced
    # quantiles of the law dealt out by a fixed stride, the same under
    # every seed: the longest starts first, and long sessions start all
    # through the ramp. So from the window's opening on there is always
    # some session past any given length, as in the loop's steady state,
    # where the pool's table width (set by the longest live session)
    # stays put; a ramp that started all the long ones together would
    # leave a hole when they end, and the tick's cost would step. The
    # seed orders everything after.
    grid = sorted(int(v) for v in traffic["output_length_grid"])
    ranked = sorted((grid[int((c + 0.5) * len(grid) / clients)]
                     for c in range(clients)), reverse=True)
    stride = next(k for k in range(max(2, clients * 3 // 8), 2 * clients)
                  if math.gcd(k, clients) == 1)
    first = [ranked[(c * stride) % clients] for c in range(clients)]
    later = ordered(grid, total - clients, _rng(seed, 3))
    ramp = float(traffic["ramp_s"])
    return {"kind": kind, "ramp_s": ramp, "clients": [
        {"start": -ramp + ramp * c / clients,
         "sessions": [{"id": c + clients * j,
                       "length": lengths[c + clients * j],
                       "outputs": (first[c] if j == 0
                                   else later[c + clients * (j - 1)])}
                      for j in range(per_client)]}
        for c in range(clients)]}


def request_inputs(spec: list[dict], length: int, examples: int,
                   vocab_size: int, rng: np.random.Generator) -> dict:
    """The tensors of one request, by the configuration's `request_inputs`
    (name, kind, optional pad_to): `tokens` are ids in [2, vocab), `ones`
    is the attention mask of an unpadded request."""
    out = {}
    for item in spec:
        width = int(item.get("pad_to") or length)
        if item["kind"] == "tokens":
            arr = np.zeros((examples, width), np.int32)
            arr[:, :length] = rng.integers(2, vocab_size, (examples, length))
        elif item["kind"] == "ones":
            arr = np.zeros((examples, width), np.int32)
            arr[:, :length] = 1
        else:
            raise ValueError(f"unknown input kind {item['kind']!r}")
        out[item["name"]] = arr
    return out
