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


# -- the LB step inside the whole classify program (PR 45) ----------------------
#: the load-balancer's tables of ``svc10k-maglev``: 10,000 Maglev rows of
#: 16,381 (655 MB), 14,001 frontends in 32,768 slots, 151,000 backends
LB_SHAPES = {
    "lb_maglev": (10000, 16381), "lb_tab_keys": (32768, 6),
    "lb_tab_val": (32768,), "lb_fe_service": (14001,),
    "lb_fe_rnat_id": (14001,), "lb_rnat_addr": (14001, 4),
    "lb_rnat_port": (14001,), "lb_rnat_valid": (14001,),
    "lb_be_addr": (151000, 4), "lb_be_port": (151000,)}


def spied_dispatches(eng, world, rows_list):
    """→ {rows: (the datapath's jitted step, the shapes of one real
    dispatch of that many rows of the world's allowed flows)}: the step
    spied on as ``tests/test_lpm100k_config.py`` does."""
    import jax
    from benchmarks.frames import columns_of
    dp, seen, out = eng.datapath, [], {}
    step = dp._classify

    def spy(*args):
        seen.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args))
        return step(*args)
    dp._classify = spy
    try:
        for rows in rows_list:
            flows = world.allowed_flows(np.random.default_rng(1), rows, 1,
                                        40000)
            eng.submit(columns_of(flows, world.ep_v4, world.ep_v6_words,
                                  0)).result(timeout=300)
            assert eng.drain(timeout=60)
            out[rows] = (step, seen[-1])
    finally:
        dp._classify = step
    return out


@pytest.fixture(scope="module")
def classify_program():
    """→ (the datapath's jitted step, the shapes of one real dispatch of
    1,024 rows): the tiny service world on the jitted datapath."""
    import json
    import os

    from benchmarks.worlds import svclb
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "configs", "tiny-svclb.json")) as f:
        world = svclb.build(json.load(f)["world"])
    cfg = DaemonConfig(auto_regen=False, ct_capacity=1 << 12, maglev_m=251,
                       lb_map_max=4096, batch_size=ROWS)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    try:
        world.load(eng)
        eng.regenerate()
        return spied_dispatches(eng, world, [ROWS])[ROWS]
    finally:
        eng.stop()


def at_the_deployments_size(shapes, one_chip, lb_shapes):
    import jax
    tensors = dict(shapes[0])
    assert set(lb_shapes) == {k for k in tensors if k.startswith("lb_")}
    for name, shape in lb_shapes.items():
        tensors[name] = jax.ShapeDtypeStruct(shape, tensors[name].dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (tensors,) + tuple(shapes[1:]))


def test_the_lb_step_reads_a_clusters_tables_where_they_lie(
        one_chip, classify_program):
    """The whole classify program, as the datapath dispatches it for 1,024
    rows, over the load-balancer's tables at ``svc10k-maglev``'s shapes: the
    optimised program holds no copy or transpose of 2^20 elements (no table
    re-laid a batch under ``lb.step``), reads the Maglev table as the
    parameter it is, and its temporaries are a thirtieth of the tables."""
    step, shapes = classify_program
    compiled = step.lower(*at_the_deployments_size(
        shapes, one_chip, LB_SHAPES)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert table_copies(text) == []
    assert "s32[10000,16381]{1,0:T(8,128)} parameter(" in text
    tables = 10000 * 16384 * 4          # a row padded to 128 lanes
    assert tables <= memory.argument_size_in_bytes < tables + (16 << 20)
    assert memory.temp_size_in_bytes < tables // 30


@pytest.mark.parametrize("form", ["trailing-axis", "transposed"])
def test_the_reader_sees_a_maglev_table_handed_over_in_another_form(
        one_chip, classify_program, form):
    """The control: the same program handed the Maglev table as ``[S, M,
    1]`` and dropping the axis itself re-lays 164 million entries a batch
    (the tiles of the last two axes pad the one to 128 lanes). Handed over
    transposed (``[M, S]``), as ISSUE 45 proposed for the control, it does
    not: the compiler reads the transposed table through the gather's own
    dimension numbers. Both are held, so that the reader is known to see
    the one and the other is known not to be one."""
    import jax
    step, shapes = classify_program
    inner = step.__wrapped__
    shape, back = {
        "trailing-axis": ((10000, 16381, 1),
                          lambda m: m.reshape(10000, 16381)),
        "transposed": ((16381, 10000), lambda m: m.T)}[form]

    def handed(tensors, *rest):
        return inner(dict(tensors, lb_maglev=back(tensors["lb_maglev"])),
                     *rest)
    compiled = jax.jit(handed).lower(*at_the_deployments_size(
        shapes, one_chip, dict(LB_SHAPES, lb_maglev=shape))).compile()
    copies = table_copies(compiled.as_text())
    if form == "transposed":
        assert copies == []
    else:
        assert len(copies) == 1 and "[10000,16381,1]" in copies[0], copies
        assert compiled.memory_analysis().temp_size_in_bytes \
            >= 10000 * 16381 * 4


# -- conntrack's key table in every program that owns it (PR 48) ---------------
#: the benchmark's table sizes: ``l7-http``; ``pods10k-dualstack``,
#: ``lpm100k-zipf``, ``node-mixed`` and ``svc10k-maglev``; one chip's shard of
#: ``ct1m-50k-mesh4``; ``ct1m-50k``
CT_CAPACITIES = (1 << 16, 1 << 18, 1 << 19, 1 << 21)
#: the programs that take ``JITDatapath._ct`` and hand it back: the serving
#: step's conntrack part at a full batch and at a mesh shard's 256 rows, the
#: GC tick (``sweep_step``'s own ``jax.jit``) and the whole-table ``sweep``
CT_OWNERS = ("serve-1024", "serve-256", "gc-tick", "sweep")


def conntrack_of_the_step(ct, fwd, rev, proto, tcp_flags, allow, rnat, now):
    """Steps 2 and 6 of ``classify_step``: both probes, then the insert,
    the eviction round and the update, through the stage the step calls."""
    import jax.numpy as jnp
    from cilium_tpu.kernels import conntrack as ctk
    from cilium_tpu.kernels.classify import ct_update_stage
    fwd_slot, rev_slot = ctk.ct_probe_pair(ct, fwd, rev, now)
    hit = (fwd_slot >= 0) | (rev_slot >= 0)
    new_ct, ct_full, entry_rnat, n_evicted = ct_update_stage(
        ct, fwd, proto, tcp_flags, hit,
        jnp.where(fwd_slot >= 0, fwd_slot, rev_slot),
        (fwd_slot < 0) & (rev_slot >= 0), ~hit, allow, rnat, now)
    return new_ct, (ct_full, entry_rnat, n_evicted)


def compiled_owner(one_chip, owner, cap):
    """One owner of the table, compiled for the described chip over a
    placed table of ``cap`` slots, donated as the datapath donates it."""
    import functools

    import jax
    import jax.numpy as jnp
    from cilium_tpu.compile.ct_layout import CT_PLACED_KEYS
    from cilium_tpu.kernels import conntrack as ctk

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    ct = {k: spec((cap,), jnp.uint32) for k in CT_PLACED_KEYS}
    now = spec((), jnp.uint32)
    if owner == "sweep":
        return jax.jit(ctk.ct_sweep).lower(ct, now).compile()
    if owner == "gc-tick":
        return jax.jit(
            functools.partial(ctk.ct_sweep_chunk, chunk_rows=cap // 16),
            donate_argnums=(0,)).lower(ct, now, now, count_now=now).compile()
    rows = int(owner.split("-")[1])
    return jax.jit(conntrack_of_the_step, donate_argnums=(0,)).lower(
        ct, spec((rows, 10), jnp.uint32), spec((rows, 10), jnp.uint32),
        spec((rows,), jnp.int32), spec((rows,), jnp.int32),
        spec((rows,), jnp.bool_), spec((rows,), jnp.int32), now).compile()


def re_laid(text):
    """``table_copies`` less the compiler's own staging: a ``copy`` whose
    operand has the result's shape and layout and lies in another memory
    space (``S(1)``, the chip's fast memory) moves one array back to HBM
    where the compiler chose to compute it there (ROADMAP S3 (b)'s kind:
    8 MB and ≈22 µs for one ``u32[2^21]`` column of the 1,024-row step);
    it re-lays nothing."""
    def form(type_text):
        return re.sub(r"S\(\d+\)", "", type_text)
    out = []
    for line in table_copies(text):
        m = re.match(r"%?\S+ = (\S+) copy\(%?([\w.\-]+)\)", line)
        src = m and re.search(
            r"^\s*(?:ROOT )?%?" + re.escape(m.group(2)) + r" = (\S+) ", text,
            re.M)
        if not (src and form(src.group(1)) == form(m.group(1))
                and src.group(1) != m.group(1)):
            out.append(line)
    return out


@pytest.mark.parametrize("cap", CT_CAPACITIES)
@pytest.mark.parametrize("owner", CT_OWNERS)
def test_every_owner_of_the_key_table_keeps_its_one_form(one_chip, owner,
                                                         cap):
    """Each program that owns the conntrack table takes every one of its
    arrays, the ten key planes among them, as ``u32[cap]`` in the layout
    the shape has by itself and returns it so: no program hands the next
    one a table to re-lay. None re-lays 2^20 elements (at most one column
    is staged through the fast memory, ``re_laid``), and none needs 16 MB
    of temporaries (the ``[cap, 10]`` matrix cost 137 MB of them at
    2^18)."""
    from cilium_tpu.compile.ct_layout import CT_PLACED_KEYS
    compiled = compiled_owner(one_chip, owner, cap)
    text = compiled.as_text()
    assert re_laid(text) == []
    assert len(table_copies(text)) <= 1
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    taken = compiled.input_formats[0][0]
    handed_back = compiled.output_formats[0]
    assert set(taken) == set(handed_back) == set(CT_PLACED_KEYS)
    layouts = {str(f.layout) for f in taken.values()} \
        | {str(f.layout) for f in handed_back.values()}
    assert len(layouts) == 1 and "major_to_minor=(0,)" in layouts.pop()


@pytest.mark.parametrize("cap", CT_CAPACITIES)
def test_the_reader_sees_the_key_table_re_laid_as_a_matrix(one_chip, cap):
    """The control: the table as it stood until PR 48, one ``[cap, 10]``
    matrix probed by row gathers and written by row scatters. At 2^18 the
    compiled program re-lays all of it before the gathers and back after
    the scatters (0.385 ms of every dispatch on the chip, and 134 MB of
    temporaries); at the other sizes the compiler picks one layout and
    the reader, rightly, sees none."""
    import jax
    import jax.numpy as jnp
    from cilium_tpu.kernels.hashing import hash_words_jnp

    def matrix_step(tab, expiry, keys, now):
        base = (hash_words_jnp(keys) & jnp.uint32(cap - 1)).astype(jnp.int32)
        found = jnp.full(base.shape, -1, jnp.int32)
        for i in range(8):
            s = (base + i) & (cap - 1)
            eq = jnp.all(tab[s] == keys, axis=-1) & (expiry[s] > now)
            found = jnp.where((found < 0) & eq, s, found)
        fresh = jnp.where(found < 0, base, cap)
        return tab.at[fresh].set(keys, mode="drop"), found

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(matrix_step, donate_argnums=(0,)).lower(
        spec((cap, 10), jnp.uint32), spec((cap,), jnp.uint32),
        spec((ROWS, 10), jnp.uint32), spec((), jnp.uint32)).compile()
    copies = table_copies(compiled.as_text())
    if cap == 1 << 18:
        assert len(copies) == 2 and all("[262144,10]" in c for c in copies)
        assert compiled.memory_analysis().temp_size_in_bytes >= cap * 128 * 4
    else:
        assert copies == []


# -- the walk over a whole routing table, both families (PR 53) --------------------
#: the tries of ``dfz-dualstack`` as the program builds them: 40,228 v4 nodes
#: (165 MB placed) and 188,035 v6 nodes (770 MB), where the cases above hold
#: the walk at 39,667 and 20,084
DFZ_V4_NODES, DFZ_V6_NODES = 40228, 188035
DFZ_ROWS = (1024, 256)


def test_the_walk_reads_a_whole_routing_table_where_it_lies(one_chip):
    """Both chains over tries of the deployment's sizes in one program:
    twenty gathers, no copy or transpose of 2^20 elements, the two tables
    taken as the parameters they are placed as (16 bytes an entry: 935 MB)
    and no temporary to speak of. ``node * 256 + byte`` stays in 31 bits."""
    import jax
    import jax.numpy as jnp
    from cilium_tpu.kernels.lpm import lpm_lookup_prov_batch

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def walk(v4, v6, addr_words, is_v6):
        return lpm_lookup_prov_batch(v4, v6, addr_words, is_v6, 0)
    assert DFZ_V6_NODES * 256 < 1 << 31
    for rows in DFZ_ROWS:
        compiled = jax.jit(walk).lower(
            spec((DFZ_V4_NODES * 256, 3), jnp.int32),
            spec((DFZ_V6_NODES * 256, 3), jnp.int32),
            spec((rows, 4), jnp.uint32), spec((rows,), jnp.bool_)).compile()
        text, memory = compiled.as_text(), compiled.memory_analysis()
        assert len(re.findall(r" gather\(", text)) == 4 + 16
        assert table_copies(text) == []
        placed = (DFZ_V4_NODES + DFZ_V6_NODES) * 256 * 16
        assert placed == 934_965_248
        assert placed <= memory.argument_size_in_bytes < placed + (1 << 20)
        assert memory.temp_size_in_bytes < placed // 200


@pytest.fixture(scope="module")
def dfz_program():
    """→ {rows: (the datapath's jitted step, the shapes of one real
    dispatch of that many rows)}: the tiny routing-table world on the
    jitted datapath, dual-stack frames on the wide wire."""
    import json
    import os

    from benchmarks.worlds import dfz
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "configs", "tiny-dfz.json")) as f:
        world = dfz.build(json.load(f)["world"])
    cfg = DaemonConfig(auto_regen=False, ct_capacity=1 << 12,
                       batch_size=ROWS)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    try:
        world.load(eng)
        eng.regenerate()
        return spied_dispatches(eng, world, DFZ_ROWS)
    finally:
        eng.stop()


def with_the_deployments_tries(shapes, one_chip):
    import jax
    tensors = dict(shapes[0])
    for name, nodes in (("lpm_v4", DFZ_V4_NODES), ("lpm_v6", DFZ_V6_NODES)):
        assert tensors[name].shape[1:] == (3,)
        tensors[name] = jax.ShapeDtypeStruct((nodes * 256, 3),
                                             tensors[name].dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (tensors,) + tuple(shapes[1:]))


@pytest.mark.parametrize("rows", DFZ_ROWS)
def test_the_whole_step_over_a_whole_routing_table(one_chip, dfz_program,
                                                   rows):
    """The whole classify program, as the datapath dispatches it for 1,024
    and for 256 rows on the wide wire, over the two tries at
    ``dfz-dualstack``'s sizes: **every** copy or transpose of 2^20 elements
    is listed, and none stands (``re_laid``'s rule: the compiler staging one
    array through its fast memory re-lays nothing; a trie is not even
    staged). Both tries reach the program as the 2-D parameters they are
    placed as, and the temporaries are a hundredth of them."""
    step, shapes = dfz_program[rows]
    compiled = step.lower(*with_the_deployments_tries(
        shapes, one_chip)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    copies = table_copies(text)
    assert re_laid(text) == [], copies
    assert not any(str(DFZ_V4_NODES * 256) in c or str(DFZ_V6_NODES * 256)
                   in c for c in copies), copies
    for nodes in (DFZ_V4_NODES, DFZ_V6_NODES):
        assert re.search(r"s32\[%d,3\]\{[^}]*\} parameter\(" % (nodes * 256),
                         text), nodes
    placed = (DFZ_V4_NODES + DFZ_V6_NODES) * 256 * 16
    assert placed <= memory.argument_size_in_bytes < placed + (64 << 20)
    assert memory.temp_size_in_bytes < placed // 100


def test_the_families_names_are_names_alone(one_chip, dfz_program,
                                            monkeypatch):
    """``lpm.walk.v4`` / ``lpm.walk.v6`` are metadata: the optimised
    program of the whole step with the two names blanked is, line for line,
    the optimised program of the same step traced without the scopes (the
    parent's), metadata and all."""
    import contextlib
    import types

    import jax
    from cilium_tpu.kernels import lpm
    step, shapes = dfz_program[ROWS]
    args = with_the_deployments_tries(shapes, one_chip)
    inner_step = step.__wrapped__
    texts = {}
    for scoped in (True, False):
        if not scoped:
            monkeypatch.setattr(lpm, "jax", types.SimpleNamespace(
                named_scope=lambda name: contextlib.nullcontext()))
        # a function of its own each time (a jit of the same one would hand
        # back the trace it has), lowered from this one line (the program's
        # text holds its call sites)
        texts[scoped] = jax.jit(lambda *a: inner_step(*a)).lower(
            *args).compile().as_text()
    named, bare = texts[True], texts[False]
    assert named.count("/lpm.walk/lpm.walk.v6/") >= 16 \
        and named.count("/lpm.walk/lpm.walk.v4/") >= 4
    assert "lpm.walk.v" not in bare and "/lpm.walk/" in bare
    blanked = named.replace("/lpm.walk.v4", "").replace("/lpm.walk.v6", "")
    assert blanked.splitlines() == bare.splitlines()
