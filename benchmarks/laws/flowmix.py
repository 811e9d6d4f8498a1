"""Law ``flowmix``: packets drawn from flows, not from tuples.

A share of the frames (``live_share``) are ACKs on the configuration's
live set, the flow picked by rank from a Zipf law (``p(rank) ∝
rank**-zipf_s``; rank 0 is the heaviest flow). The rest are first packets
of flows that have never been seen, each sent once: ``new_allowed`` of them
to a port the plain reference admits, ``new_denied`` from a pod to a port
it does not, ``new_unknown`` from an address no identity covers. No flow
closes. The world draws the flows; this file only mixes them.

Every seed gets the same flows and the same frames, in another order: the
population (which flows live, which are new, how many frames each live
flow sends) is drawn from ``POPULATION_SEED``, and the run's seed only
permutes the schedule. Runs whose seeds drew their own flows differed by 4%
in ``verdict_p50_ms`` from seed to seed and by 0.2% between two runs of one
seed (PERF.md §6): which flows are the heavy ones, and where they sit in
the conntrack table, changes the work.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.frames import Flows, concat, take

KIND_LIVE, KIND_NEW_ALLOWED, KIND_NEW_DENIED, KIND_NEW_UNKNOWN = 0, 1, 2, 3

POPULATION_SEED = 1
LIVE_SPORTS = (20000, 40000)
NEW_SPORTS = (40000, 60000)


def _key64(flows: Flows) -> np.ndarray:
    """A 64-bit key per flow, for cheap deduplication. Two different flows
    may share one (one of them is then left out), the same flow never
    gets two."""
    src = flows["src"].astype(np.uint64)
    addr = (src[:, 0] * np.uint64(0x9E3779B1) ^ src[:, 1]
            * np.uint64(0x85EBCA6B) ^ src[:, 3]) & np.uint64(0x7FFFFFFF)
    return (addr << np.uint64(33)) \
        | (flows["sport"].astype(np.uint64) << np.uint64(17)) \
        | (flows["dport"].astype(np.uint64) << np.uint64(1)) \
        | (flows["proto"] == 17).astype(np.uint64)


def _distinct(draw, n: int) -> Flows:
    """``n`` distinct flows from ``draw(m)``, in the order drawn."""
    got = draw(n + n // 16 + 64)
    _, first = np.unique(_key64(got), return_index=True)
    first = np.sort(first)
    if first.size < n:
        raise ValueError(f"only {first.size} distinct flows of {n} wanted")
    return take(got, first[:n])


def generate(params: Dict, world, rng, n_live: int, n_frames: int) -> Dict:
    """→ flows (live set first, in rank order, then the new flows),
    ``kind`` per flow, and ``sched_flow`` [n_frames]: which flow each frame
    of the schedule belongs to."""
    live_share = float(params["live_share"])
    shares = np.array([params["new_allowed"], params["new_denied"],
                       params["new_unknown"]], dtype=np.float64)
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError("the new-flow shares must sum to 1")
    order_rng, rng = rng, np.random.default_rng(POPULATION_SEED)
    n_new = int(round((1.0 - live_share) * n_frames))
    u = rng.random(n_frames)
    is_new = u < np.partition(u, n_new)[n_new] if 0 < n_new < n_frames \
        else np.full((n_frames,), n_new > 0)
    n_kind = np.floor(shares * n_new).astype(np.int64)
    n_kind[0] += n_new - int(n_kind.sum())

    live = _distinct(lambda m: world.allowed_flows(rng, m, *LIVE_SPORTS),
                     n_live)
    new = concat([
        _distinct(lambda m: world.allowed_flows(rng, m, *NEW_SPORTS),
                  int(n_kind[0])),
        _distinct(lambda m: world.denied_flows(rng, m, *NEW_SPORTS),
                  int(n_kind[1])),
        _distinct(lambda m: world.unknown_flows(rng, m, *NEW_SPORTS),
                  int(n_kind[2]))])
    new_kind = np.repeat(np.array([KIND_NEW_ALLOWED, KIND_NEW_DENIED,
                                   KIND_NEW_UNKNOWN], np.uint8), n_kind)
    order = rng.permutation(n_new)
    new, new_kind = take(new, order), new_kind[order]

    # Zipf over ranks 1..n_live by inverse CDF
    weights = np.arange(1, n_live + 1, dtype=np.float64) \
        ** -float(params["zipf_s"])
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    n_ack = n_frames - n_new
    rank = np.minimum(np.searchsorted(cdf, rng.random(n_ack)), n_live - 1)

    sched = np.empty((n_frames,), dtype=np.uint32)
    sched[~is_new] = rank
    sched[is_new] = n_live + np.arange(n_new, dtype=np.uint32)
    sched = sched[order_rng.permutation(n_frames)]
    return {
        "flows": concat([live, new]),
        "kind": np.concatenate([np.full((n_live,), KIND_LIVE, np.uint8),
                                new_kind]),
        "n_live": n_live,
        "sched_flow": sched,
    }
