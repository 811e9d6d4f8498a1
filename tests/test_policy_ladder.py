"""``kernels/policy.policy_lookup_batch`` against a plain ladder, row by row, on
rows no sound batch holds: every index negative or past its table's end,
two endpoints, class tables whose own values leave the image, and an image
whose column count (131) is no multiple of the chip's 128-lane tile.

The ladder reads each placed table in the shape it is placed in, with every
index clamped first; the plain ladder below clamps the same way and reads
the image's flat form, so the two agree exactly when the clipped direct
gather names the flat form's cell. Held under ``jit``, for the lookup alone
and for the step's ladder, L7 match and verdict composition over it
(``kernels/classify.interior_pre_core``, ``compose_verdict``).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cilium_tpu.kernels.classify import compose_verdict, interior_pre_core
from cilium_tpu.kernels.policy import policy_lookup_batch
from cilium_tpu.utils import constants as C

N_EPS, N_IDS, N_ROWS, N_COLS = 2, 11, 7, 131


def tables():
    rng = np.random.default_rng(35)
    id_class_of = rng.integers(0, N_ROWS, N_IDS).astype(np.int32)
    port_class = rng.integers(
        0, N_COLS, (C.N_PROTO_FAMILIES, 65536)).astype(np.int32)
    # class values that leave the image on both sides, where the rows look
    id_class_of[[0, 3]] = -2, N_ROWS + 2
    port_class[:, 0], port_class[:, 80] = -3, N_COLS + 3
    return {
        "id_class_of": id_class_of,
        "proto_family": np.array([C.proto_family(p) for p in range(256)],
                                 np.int32),
        "port_class": port_class,
        "verdict": rng.integers(
            0, 1 << 16, (N_EPS, 2, N_ROWS, N_COLS)).astype(np.uint16),
        "enforced": np.array([[True, False], [False, True]]),
        # one empty L7 set, as a snapshot with no L7 rule places it
        "l7_methods": np.zeros((1, 1), np.uint8),
        "l7_valid": np.zeros((1, 1), bool),
        "l7_path_len": np.zeros((1, 1), np.int32),
        "l7_path": np.zeros((1, 1, 64), np.uint8),
    }


def rows():
    grid = itertools.product(
        (-3, 0, 1, 2, 7),                                  # ep_slot
        (-1, 0, 1, 2),                                     # direction
        (-5, 0, 3, N_IDS - 1, N_IDS, N_IDS + 9),           # id_index
        (-1, C.PROTO_TCP, C.PROTO_UDP, 1, 255, 256, 300),  # proto
        (-1, 0, 80, 65535, 65536, 70000))                  # dport
    return tuple(np.array(col, np.int32) for col in zip(*grid))


def plain_ladder(t, ep_slot, direction, id_index, proto, dport):
    """One row at a time with Python integers: clamp, then the flat form."""
    def clamp(x, n):
        return min(max(int(x), 0), n - 1)
    flat, enf = t["verdict"].reshape(-1), t["enforced"].reshape(-1)
    out = []
    for e, d, i, p, q in zip(ep_slot, direction, id_index, proto, dport):
        id_cls = int(t["id_class_of"][clamp(i, N_IDS)])
        fam = int(t["proto_family"][clamp(p, 256)])
        pcls = int(t["port_class"][fam, clamp(q, 65536)])
        ep, dd = clamp(e, N_EPS), clamp(d, 2)
        cell = int(flat[((ep * 2 + dd) * N_ROWS + clamp(id_cls, N_ROWS))
                        * N_COLS + clamp(pcls, N_COLS)])
        out.append((cell & C.VERDICT_DECISION_MASK,
                    cell >> C.VERDICT_L7_SHIFT, bool(enf[ep * 2 + dd]),
                    id_cls * N_COLS + pcls))
    decision, l7_id, enforced, matched = zip(*out)
    return (np.array(decision, np.int32), np.array(l7_id, np.int32),
            np.array(enforced), np.array(matched, np.int32))


@pytest.fixture(scope="module")
def case():
    """(tables on the device, row columns, the plain ladder's answer)."""
    t, cols = tables(), rows()
    want = plain_ladder(t, *cols)
    decision, _, enforced, _ = want
    # the rows reach every decision and both arms of `enforced`
    assert N_COLS % 128 and set(decision) == {0, 1, 2, 3}
    assert enforced.any() and not enforced.all()
    return ({k: jnp.asarray(v) for k, v in t.items()},
            tuple(map(jnp.asarray, cols)), want)


def test_the_clipped_direct_gather_reads_the_flat_forms_cell_under_jit(case):
    dev, cols, want = case
    got = jax.jit(policy_lookup_batch)(dev, *cols)
    for name, w, g in zip(("decision", "l7_id", "enforced", "matched_rule"),
                          want, got):
        np.testing.assert_array_equal(np.asarray(g), w, name)


def test_compose_verdict_over_every_combination_of_its_inputs():
    """``compose_verdict`` against a row at a time of plain ``if``s, over
    every combination of decision, ``enforced``, ``l7_fail``, probe class
    and ``valid``: the device-RSS exchange composes with est/reply pinned
    (a hit that is a DENY cell, a reply that fails its L7 set), rows that
    no random stream of the parity suites need ever draw."""
    ok, deny, policy, l7 = (int(C.DropReason.OK),
                            int(C.DropReason.POLICY_DENY),
                            int(C.DropReason.POLICY),
                            int(C.DropReason.POLICY_L7))
    grid = list(itertools.product(
        (C.VERDICT_MISS, C.VERDICT_ALLOW, C.VERDICT_DENY,
         C.VERDICT_REDIRECT),
        (False, True), (False, True), ("new", "est", "reply"),
        (False, True)))
    assert len({d for d, *_ in grid}) == 4
    want = []
    for decision, enforced, l7_fail, probe, valid in grid:
        if probe != "new":
            allow, reason = not l7_fail, l7 if l7_fail else ok
        elif decision == C.VERDICT_DENY:
            allow, reason = False, deny
        elif decision == C.VERDICT_MISS:
            allow, reason = not enforced, policy if enforced else ok
        else:
            allow, reason = not l7_fail, l7 if l7_fail else ok
        status = {"est": C.CTStatus.ESTABLISHED, "reply": C.CTStatus.REPLY,
                  "new": C.CTStatus.NEW}[probe]
        want.append((allow and valid, reason, int(status),
                     valid and decision == C.VERDICT_REDIRECT))
    decision, enforced, l7_fail, probe, valid = zip(*grid)
    decision = jnp.asarray(decision, jnp.int32)
    got = jax.jit(compose_verdict)(
        decision, jnp.asarray(enforced), decision == C.VERDICT_REDIRECT,
        jnp.asarray(l7_fail), jnp.asarray([p == "est" for p in probe]),
        jnp.asarray([p == "reply" for p in probe]), jnp.asarray(valid))
    for name, g, w in zip(("allow", "reason", "status", "redirect"), got,
                          zip(*want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


def test_and_the_steps_composition_over_it_judges_those_rows_alike(case):
    """The step's interior over the same rows, composed: of new, token-less,
    valid rows the verdict shows the cell's decision, ``enforced`` and the
    ladder's ``matched_rule``, and no row fails an L7 set it never named."""
    dev, cols, (decision, _, enforced, matched) = case
    n = cols[0].shape[0]
    no = jnp.zeros((n,), bool)

    @jax.jit
    def interior(dev, *cols):
        dec, enf, cell_redirect, l7_fail, mrule = interior_pre_core(
            dev, *cols, jnp.full((n,), C.HTTP_METHOD_ANY, jnp.int32),
            jnp.zeros((n, 64), jnp.uint8))
        return compose_verdict(dec, enf, cell_redirect, l7_fail, no, no,
                               ~no) + (l7_fail, jnp.where(enf, mrule, -1))
    allow, reason, status, redirect, l7_fail, mrule = interior(dev, *cols)
    assert not np.asarray(l7_fail).any()
    assert (np.asarray(status) == int(C.CTStatus.NEW)).all()
    np.testing.assert_array_equal(
        np.asarray(allow),
        np.where(decision == C.VERDICT_DENY, False,
                 np.where(decision == C.VERDICT_MISS, ~enforced, True)))
    np.testing.assert_array_equal(
        np.asarray(reason),
        np.where(decision == C.VERDICT_DENY, int(C.DropReason.POLICY_DENY),
                 np.where((decision == C.VERDICT_MISS) & enforced,
                          int(C.DropReason.POLICY), int(C.DropReason.OK))))
    np.testing.assert_array_equal(np.asarray(redirect),
                                  decision == C.VERDICT_REDIRECT)
    np.testing.assert_array_equal(np.asarray(mrule),
                                  np.where(enforced, matched, -1))
