"""What decides ``correct``: the ring's verdicts against the plain
reference.

The plain reference is the world's table of what its rule documents admit
(``World.table`` / ``World.cells``), built with numpy from the rule
parameters. Nothing here reads a tensor, a snapshot or an oracle of the
program. From the program it takes only counters (how many inserts the
conntrack table refused, verdicts by drop reason) and the rows
``Engine.submit`` answers.

Three comparisons, each number printed beside its limit:

1. Every frame the ring accepted got exactly one verdict
   (``unverdicted``), and the loop's log did not overflow.
2. Verdicts are applied in arrival order, so after K verdicts the frames
   passed must be the reference's count of admitted frames among the first
   K accepted — less those the conntrack table refused a slot (CT_FULL: a
   flow the policy admits is dropped when its probe window is full, which
   the reference does not model and the program counts). nicgen's log
   gives (K, passed) at every point where the counters stood still; the
   worst excess over all of them is ``prefix_excess``. The end of the log
   gives the exact totals, and the program's verdicts-by-reason must match
   them.
3. After the window, a sample of the window's own flows, drawn from the
   seed, goes through ``Engine.submit`` in harvest-sized batches and is
   compared row for row: allow, drop reason, and conntrack status (a live
   or admitted flow must be ESTABLISHED, a refused one must have left no
   state and read NEW again).

Why a frame is refused is the world's to state (``refusal_reasons``): its
``reasons(flows)`` gives, for each flow, the drop reason the rule documents
give a frame of it that the table does not admit; a world without the
method means ``REASON_POLICY`` for every flow. The end totals are held
reason by reason (``REFUSAL_GAPS`` names each reason's number) and the
probe's rows each against its own flow's.

The control is the same comparison with the reference handed a wrong
table: one rule that the run's traffic exercised, and that alone admits
its cell, is taken out. It has to come out as not correct.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

REASON_OK = 0
REASON_POLICY = 130        # no rule admits the frame
REASON_CT_FULL = 137
REASON_POLICY_L7 = 180     # the port's HTTP rules refuse the request
#: the reasons a world may state for a refusal, and the compared number
#: that holds each one's end total
REFUSAL_GAPS = {REASON_POLICY: "reason_policy_gap",
                REASON_POLICY_L7: "reason_policy_l7_gap"}
STATUS_NEW = 0
STATUS_ESTABLISHED = 1
PROTO_TCP = 6

#: share of admitted frames the conntrack table may refuse; see PERF.md §2
#: for the readings it was set from
CT_FULL_SHARE_LIMIT = 0.01


def expected_allow(world, flows, table: Optional[np.ndarray] = None
                   ) -> np.ndarray:
    """[n_flows] bool: does the rule table admit each flow?"""
    if table is None:
        table, _ = world.table()
    cell = world.cells(flows)
    return np.where(cell >= 0, table[np.maximum(cell, 0)], False)


def refusal_reasons(world, flows) -> np.ndarray:
    """[n_flows] int64: the drop reason the rule documents give a frame of
    each flow if the table does not admit it."""
    n = flows["sport"].shape[0]
    if not hasattr(world, "reasons"):
        return np.full((n,), REASON_POLICY, np.int64)
    reasons = np.asarray(world.reasons(flows)).astype(np.int64)
    known = np.isin(reasons, list(REFUSAL_GAPS))
    if reasons.shape != (n,) or not known.all():
        raise ValueError(f"world.reasons: one of {sorted(REFUSAL_GAPS)} a "
                         f"flow, not {np.unique(reasons[~known]).tolist()} "
                         f"in shape {reasons.shape}")
    return reasons


def wrong_table(world, flows, frames_per_flow: np.ndarray, rng,
                min_frames: int = 16):
    """The control's table: the reference's, less one rule. The rule is
    drawn from those whose cell no other rule covers and on which the run
    sent at least ``min_frames`` frames. → (table, cell) or (None, None)
    when the traffic exercised no such rule."""
    table, cover = world.table()
    cell = world.cells(flows)
    ok = cell >= 0
    per_cell = np.bincount(cell[ok], weights=frames_per_flow[ok],
                           minlength=table.shape[0])
    cand = np.nonzero((cover == 1) & (per_cell >= min_frames))[0]
    if cand.size == 0:
        return None, None
    drop = int(cand[rng.integers(0, cand.size)])
    wrong = table.copy()
    wrong[drop] = False
    return wrong, drop


def prefix_check(allow_of_accepted: np.ndarray, log: Dict, base: Dict,
                 ct_full: int) -> Dict[str, int]:
    """Comparison 2 over nicgen's log. ``allow_of_accepted`` [n_accepted]
    is the reference's answer for each accepted frame, in ring order."""
    prefix = np.concatenate([[0], np.cumsum(allow_of_accepted,
                                            dtype=np.int64)])
    n = allow_of_accepted.shape[0]
    passed = log["log_passes"] + log["log_txfull"] \
        - base["verdict_passes"] - base["tx_full_drops"]
    done = passed + log["log_drops"] - base["verdict_drops"]
    st = log["log_stable"] & (done >= 0) & (done <= n)
    want = prefix[done[st]]
    got = passed[st]
    over = np.maximum(got - want, 0)            # passed what was denied
    under = np.maximum(want - got - ct_full, 0)  # dropped what was admitted
    return {
        "stable_points": int(st.sum()),
        "prefix_excess": int(max(over.max(initial=0),
                                 under.max(initial=0))),
        "done_end": int(done[-1]) if done.size else 0,
        "passed_end": int(passed[-1]) if passed.size else 0,
        "admitted_end": int(prefix[n]),
    }


def probe_check(world, flows, kind_live: np.ndarray, sent: np.ndarray,
                fill_refused: np.ndarray, out: Dict[str, np.ndarray],
                table: Optional[np.ndarray] = None) -> Dict[str, int]:
    """Comparison 3 over the rows ``Engine.submit`` answered for ``flows``.
    ``kind_live``: the flow is of the live set; ``sent``: its frame entered
    the ring before the probe; ``fill_refused``: set-up's fill was refused
    a slot for it."""
    want = expected_allow(world, flows, table)
    allow = np.asarray(out["allow"]).astype(bool)
    reason = np.asarray(out["reason"]).astype(np.int64)
    status = np.asarray(out["status"]).astype(np.int64)
    refused_now = np.asarray(out["ct_full"]).astype(bool)
    judged = ~refused_now
    bad_allow = judged & (allow != want)
    bad_reason = judged & (reason != np.where(
        want, REASON_OK, refusal_reasons(world, flows)))
    tcp = flows["proto"] == PROTO_TCP
    # a denied flow must have left no state behind
    bad_state = judged & ~want & (status != STATUS_NEW)
    # a live flow that set-up inserted must be found established
    lost = judged & want & tcp & kind_live & ~fill_refused \
        & (status != STATUS_ESTABLISHED)
    # an admitted new flow whose frame was served reads established unless
    # the window refused it a slot: counted apart, limited by the refusals
    reopened = judged & want & tcp & ~kind_live & sent \
        & (status != STATUS_ESTABLISHED)
    return {
        "probe_rows": int(want.shape[0]),
        "probe_mismatched": int((bad_allow | bad_reason | bad_state
                                 | lost).sum()),
        "probe_refused_now": int(refused_now.sum()),
        "probe_reopened": int(reopened.sum()),
    }


def verdict(numbers: List[Dict]) -> bool:
    """Every compared number within its limit."""
    return all(n["ok"] for n in numbers)


def compare(name: str, value, limit, how: str = "max") -> Dict:
    """One compared number beside its limit. ``how``: ``max`` (value may
    not pass the limit), ``min`` (may not fall under it), ``eq``."""
    ok = {"max": value <= limit, "min": value >= limit,
          "eq": value == limit}[how]
    return {"name": name, "value": value, "limit": limit, "how": how,
            "ok": bool(ok)}
