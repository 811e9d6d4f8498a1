"""``kernels/policy.policy_core`` against a plain ladder, row by row, on
rows no sound batch holds: every index negative or past its table's end,
two endpoints, class tables whose own values leave the image, and an image
whose column count (131) is no multiple of the chip's 128-lane tile.

The ladder reads each placed table in the shape it is placed in, with every
index clamped first; the plain ladder below clamps the same way and reads
the image's flat form, so the two agree exactly when the clipped direct
gather names the flat form's cell. Held under ``jit`` and inside the
interpreted fused verdict kernel (``kernels/fused.policy_verdict_fused``),
which shares the one function.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cilium_tpu.kernels import fused as fk
from cilium_tpu.kernels.policy import policy_core
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
        # one empty L7 set: the fused kernel keeps these resident
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
    got = jax.jit(policy_core)(dev, *cols)
    for name, w, g in zip(("decision", "l7_id", "enforced", "matched_rule"),
                          want, got):
        np.testing.assert_array_equal(np.asarray(g), w, name)


def test_and_inside_the_interpreted_fused_kernel(case):
    """The fused kernel hands back the composed verdict; of new, token-less,
    valid rows it shows the cell's decision, ``enforced`` and the masked
    ``matched_rule``."""
    dev, cols, (decision, _, enforced, matched) = case
    n = cols[0].shape[0]
    no = jnp.zeros((n,), bool)
    allow, reason, _, redirect, mrule = fk.policy_verdict_fused(
        dev, *cols, jnp.full((n,), C.HTTP_METHOD_ANY, jnp.int32),
        jnp.zeros((n, 64), jnp.uint8), no, no, ~no, interpret=True)
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
