"""LPM property fuzz: the device walk (``kernels/lpm.lpm_lookup_prov_batch``)
against the host reference walk (``compile/lpm.lpm_lookup_host``, which
model.ipcache pins to oracle semantics) over random v4/v6 prefix sets:
identity index and match provenance ``(slot << 8) | plen``, mixed families,
one family alone, the empty table, 2,048 probes, a traced default index,
and the 16-level v6 walk at 100,000 prefixes (the scale ``lpm100k-zipf``
serves).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cilium_tpu.compile.lpm import lpm_lookup_host, lpm_lookup_host_prov
from cilium_tpu.kernels.lpm import lpm_lookup_prov_batch
from cilium_tpu.utils.ip import parse_addr
# every table here is built by the vectorised builder AND by the loop it
# replaced, and the two compared array for array (PR 53)
from tests.test_lpm_build import checked_build_lpm as build_lpm


def _random_prefix_set(rng, n_v4, n_v6, max_ident=50):
    entries = {}
    for _ in range(n_v4):
        plen = int(rng.choice([8, 12, 16, 20, 24, 28, 32]))
        addr = rng.integers(0, 1 << 32) & ((0xFFFFFFFF << (32 - plen))
                                           & 0xFFFFFFFF)
        prefix = (f"{(addr >> 24) & 0xFF}.{(addr >> 16) & 0xFF}."
                  f"{(addr >> 8) & 0xFF}.{addr & 0xFF}/{plen}")
        entries[prefix] = int(rng.integers(1, max_ident))
    for _ in range(n_v6):
        plen = int(rng.choice([16, 32, 48, 56, 64, 96, 128]))
        words = [int(rng.integers(0, 1 << 16)) for _ in range(8)]
        addr = ":".join(f"{w:x}" for w in words)
        entries[f"{addr}/{plen}"] = int(rng.integers(1, max_ident))
    return entries


def _fuzz_addresses(rng, entries, n):
    """Half the probe addresses land inside random prefixes from the set
    (bit-match pressure on every level), half are uniform random."""
    probes = []
    keys = list(entries)
    for i in range(n):
        if keys and i % 2 == 0:
            prefix = keys[int(rng.integers(0, len(keys)))]
            addr_s, plen_s = prefix.rsplit("/", 1)
            a16, is_v6 = parse_addr(addr_s)
            raw = bytearray(a16)
            plen = int(plen_s) + (0 if is_v6 else 96)
            for bit in range(plen, 128):      # randomize the host bits
                if rng.integers(0, 2):
                    raw[bit // 8] |= 1 << (7 - bit % 8)
                else:
                    raw[bit // 8] &= ~(1 << (7 - bit % 8))
            if not is_v6:                     # keep the v4-mapped prelude
                raw[:12] = a16[:12]
            probes.append((bytes(raw), is_v6))
        else:
            is_v6 = bool(rng.integers(0, 2))
            if is_v6:
                probes.append((rng.integers(0, 256, 16, dtype=np.uint8)
                               .tobytes(), True))
            else:
                probes.append((b"\x00" * 10 + b"\xff\xff"
                               + rng.integers(0, 256, 4, dtype=np.uint8)
                               .tobytes(), False))
    return probes


def _placed(entries, probes, default_index):
    """→ (tables, placed tries on the device, addr_words, is_v6)."""
    idents = sorted(set(entries.values()))
    identity_index = {i: n for n, i in enumerate(idents)}
    tables = build_lpm(entries, identity_index, default_index)
    addr = np.stack([np.frombuffer(a, dtype=">u4").astype(np.uint32)
                     for a, _ in probes])
    is_v6 = np.asarray([v6 for _, v6 in probes])
    return (tables, jnp.asarray(tables.v4_placed),
            jnp.asarray(tables.v6_placed), jnp.asarray(addr), is_v6)


def _lpm_parity(entries, probes, default_index=0):
    tables, v4n, v6n, addr, is_v6 = _placed(entries, probes, default_index)
    want = np.asarray([lpm_lookup_host(tables, a, v6) for a, v6 in probes],
                      dtype=np.int32)
    want_meta = np.asarray(
        [lpm_lookup_host_prov(tables, a, v6)[1] for a, v6 in probes],
        dtype=np.int32)
    got, got_meta = lpm_lookup_prov_batch(
        v4n, v6n, addr, jnp.asarray(is_v6), default_index)
    np.testing.assert_array_equal(np.asarray(got), want,
                                  "device walk != host walk")
    # match provenance ((slot<<8)|plen) rides the same walk: both must
    # name the same winning prefix
    np.testing.assert_array_equal(np.asarray(got_meta), want_meta,
                                  "device provenance != host provenance")
    if not is_v6.any():
        got4, got4_meta = lpm_lookup_prov_batch(
            v4n, v6n, addr, jnp.asarray(is_v6), default_index, v4_only=True)
        np.testing.assert_array_equal(np.asarray(got4), want,
                                      "v4_only walk != host")
        np.testing.assert_array_equal(np.asarray(got4_meta), want_meta,
                                      "v4_only provenance != host")


class TestLPMFuzzParity:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_mixed_family_sets(self, seed):
        rng = np.random.default_rng(seed)
        entries = _random_prefix_set(rng, n_v4=120, n_v6=80)
        probes = _fuzz_addresses(rng, entries, 256)
        _lpm_parity(entries, probes, default_index=int(rng.integers(0, 5)))

    def test_v4_only_sets(self):
        rng = np.random.default_rng(9)
        entries = _random_prefix_set(rng, n_v4=200, n_v6=0)
        probes = _fuzz_addresses(
            rng, entries, 128)
        probes = [p for p in probes if not p[1]]
        _lpm_parity(entries, probes)

    def test_v6_only_sets(self):
        """No v4 prefix at all: the v4 trie is a root and the dead
        sentinel, every v4 probe resolves the default, every v6 probe
        walks its sixteen levels."""
        rng = np.random.default_rng(10)
        entries = _random_prefix_set(rng, n_v4=0, n_v6=200)
        probes = _fuzz_addresses(rng, entries, 256)
        assert any(v6 for _, v6 in probes) \
            and not all(v6 for _, v6 in probes)
        _lpm_parity(entries, probes, default_index=3)

    def test_empty_table_resolves_default(self):
        _lpm_parity({}, _fuzz_addresses(np.random.default_rng(1), {}, 32),
                    default_index=7)

    def test_two_thousand_probes(self):
        """2,048 probes in one call: two of the serving path's buckets."""
        rng = np.random.default_rng(5)
        entries = _random_prefix_set(rng, n_v4=60, n_v6=40)
        probes = _fuzz_addresses(rng, entries, 2048)
        _lpm_parity(entries, probes)

    def test_a_traced_default_index_is_one_program_for_every_value(self):
        """The step hands the walk ``world_index`` as a traced scalar (it
        changes when the identity table grows): one trace serves every
        value, and a miss resolves the value of the call, not of the
        trace."""
        rng = np.random.default_rng(6)
        entries = _random_prefix_set(rng, n_v4=40, n_v6=40)
        probes = _fuzz_addresses(rng, entries, 128)
        tables, v4n, v6n, addr, is_v6 = _placed(entries, probes, 0)
        traces = []

        @jax.jit
        def walk(default_index):
            traces.append(1)
            return lpm_lookup_prov_batch(v4n, v6n, addr, jnp.asarray(is_v6),
                                         default_index)
        want_meta = np.asarray(
            [lpm_lookup_host_prov(tables, a, v6)[1] for a, v6 in probes])
        assert (want_meta < 0).any() and (want_meta >= 0).any()
        base = None
        for default in (0, 4, 41):
            idx, meta = walk(np.int32(default))
            np.testing.assert_array_equal(np.asarray(meta), want_meta)
            idx = np.asarray(idx)
            assert (idx[want_meta < 0] == default).all()
            if base is None:
                base = idx
            np.testing.assert_array_equal(idx[want_meta >= 0],
                                          base[want_meta >= 0])
        assert len(traces) == 1

    def test_v6_walk_at_100k_prefixes(self):
        """ROADMAP item 4c seed: the 16-level stride walk over a
        BGP-table-scale v6 set (100k distinct prefixes under a shared /32,
        bounding trie width like a real table's aggregation does)."""
        rng = np.random.default_rng(42)
        entries = {}
        while len(entries) < 100_000:
            b4, b5, b6 = (int(rng.integers(0, 256)),
                          int(rng.integers(0, 256)),
                          int(rng.integers(0, 256)))
            entries[f"2001:db8:{b4:02x}{b5:02x}:{b6:02x}00::/56"] = \
                int(rng.integers(1, 64))
        probes = _fuzz_addresses(rng, entries, 1024)
        probes = [p for p in probes if p[1]]
        _lpm_parity(entries, probes)
