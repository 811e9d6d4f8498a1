"""Compiles for a described TPU v5e, no chip attached: what the TPU's own
compiler makes of the main path's kernels at the benchmark's real sizes.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached (``/opt/skills/guides/on-chip-measurement``,
section 2). Nothing runs, so these cases say nothing of results or times;
they hold the *form* of the optimised program, which is where PR 35's and
PR 44's costs were: a table re-laid in every batch shows as a ``copy`` of
the table's size, and a chip trace is the dear way to find it.

The topology is described inside a fixture (never at import: only the
worker this file goes to loads the library), every case here skips with
its reason where it cannot be described, and none waits on a device.
Keep every such case in this file: a second file can go to another worker,
which cannot load the library while this one holds it.
"""

import re

import numpy as np
import pytest

ROWS = 1024
#: the v4 trie of ``lpm100k-zipf`` and ``node-mixed`` (122 MB), the v6 trie
#: of ``node-mixed``'s east-west plane (62 MB), and the eleven- and two-node
#: tries of the other configurations (ISSUE 44; ledger, PR 42 and 43)
V4_NODES, V6_NODES, SMALL_V4_NODES, SMALL_V6_NODES = 39667, 20084, 11, 2
#: a copy of this many elements or more is a table re-laid (2^20)
WHOLE_TABLE = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def compiled_walk(one_chip, table_shape, walk):
    """``walk(table, addr_words, is_v6)`` over a trie of ``table_shape``
    and 1,024 rows, compiled for the described chip → (optimised HLO
    text, memory analysis)."""
    import jax
    import jax.numpy as jnp

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(walk).lower(
        spec(table_shape, jnp.int32), spec((ROWS, 4), jnp.uint32),
        spec((ROWS,), jnp.bool_)).compile()
    return compiled.as_text(), compiled.memory_analysis()


def two_node_trie():
    """The other family's trie: a root and the dead sentinel, placed."""
    import jax.numpy as jnp
    return jnp.full((2 * 256, 3), -1, jnp.int32)


def table_copies(text):
    """Lines of the optimised program that copy or transpose an array of
    ``WHOLE_TABLE`` elements or more."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= \S*?\[([\d,]+)\]\S* (?:copy|transpose)\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) \
                >= WHOLE_TABLE:
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("family,nodes", [
    ("v4", V4_NODES), ("v6", V6_NODES),
    ("v4", SMALL_V4_NODES), ("v6", SMALL_V6_NODES)])
def test_the_walk_reads_the_placed_trie_where_it_lies(one_chip, family,
                                                      nodes):
    """Over the placed form (``[n * 256, 3]``) the walk compiles to its
    twenty gathers, and over a large trie the optimised program holds no
    copy or transpose of 2^20 elements and no temporary to speak of: the
    gathers read the parameter."""
    from cilium_tpu.kernels.lpm import lpm_lookup_prov_batch
    other = two_node_trie()

    def walk(table, addr_words, is_v6):
        v4, v6 = (table, other) if family == "v4" else (other, table)
        return lpm_lookup_prov_batch(v4, v6, addr_words, is_v6, 0)
    text, memory = compiled_walk(one_chip, (nodes * 256, 3), walk)
    assert len(re.findall(r" gather\(", text)) == 4 + 16
    assert table_copies(text) == []
    if nodes * 256 >= WHOLE_TABLE:
        # 16 bytes an entry on the chip (the tiles pad three words to
        # four), and temporaries under a fiftieth of that
        placed = nodes * 256 * 16
        assert placed <= memory.argument_size_in_bytes < placed + (1 << 20)
        assert memory.temp_size_in_bytes < placed // 50


def test_the_reader_sees_the_copy_in_the_form_that_had_one(one_chip):
    """The control: the walk as it stood until PR 44, handed the host form
    ``[n, 256, 3]`` and flattening it itself, compiles to a program that
    re-lays the whole trie (445 us of every batch on the chip)."""
    from cilium_tpu.kernels.lpm import lpm_lookup_prov_batch

    def walk(nodes, addr_words, is_v6):
        return lpm_lookup_prov_batch(nodes.reshape(-1, 3), two_node_trie(),
                                     addr_words, is_v6, 0)
    text, memory = compiled_walk(one_chip, (V4_NODES, 256, 3), walk)
    copies = table_copies(text)
    assert len(copies) == 1 and str(V4_NODES) in copies[0], copies
    assert memory.temp_size_in_bytes >= V4_NODES * 256 * 16
