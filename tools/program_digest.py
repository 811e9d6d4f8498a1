#!/usr/bin/env python3
"""Program identity by text, on the CPU: one line a served program.

    cd <tree> && JAX_PLATFORMS=cpu python3 tools/program_digest.py \\
        [--platform tpu] > digests.txt

(or this file run from another tree's root: it imports the ``cilium_tpu``
of the directory it is run from). For every builder that serves (the
one-chip step packed and slab, and in its column form; the host-steered
mesh step, rule-sharded too; the device-RSS exchange step; the GC tick) at
conntrack 2^16 / 2^18 / 2^21, v4-only and dual-stack, on the narrow, wide
and both request wires: ``<program> <geometry> <sha256 of
.lower(...).as_text()>``. Two trees that print the same lines hand XLA the
same programs; ``diff`` of two outputs is the proof, ``--text DIR`` keeps
the texts where a digest differs and one wants to see why.

Nothing is compiled or run: engines are built on a tiny world (a CIDR
policy, an HTTP rule set, a service frontend: every plane's tensors are
placed), the datapath's own jitted callables are taken as it built them
(``jax.jit`` is watched while it does), and each is lowered for the shapes
and shardings a dispatch of 1,024 rows hands it. ``--platform tpu`` lowers
with the TPU's lowering rules in place of the CPU's (no chip: the meshes
stay four virtual CPU devices).
"""

from __future__ import annotations

import argparse
import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import numpy as np  # noqa: E402

ROWS = 1024
CAPS = (1 << 16, 1 << 18, 1 << 21)
#: path dictionary of a request wire: rows x words (any power of two rows)
PATH_DICT = (64, 8)
POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8", "2001:db8::/32"]}],
    "egressDeny": [{"toCIDR": ["10.66.0.0/16"]}],
    "ingress": [{"fromEndpoints": [{"matchLabels": {"role": "fe"}}],
                 "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}],
                              "rules": {"http": [{"method": "GET",
                                                  "path": "/api"}]}}]}],
}]

made = []           # every callable ``jax.jit`` has handed the program
_jit = jax.jit
platform = "cpu"    # whose lowering rules (``--platform``)


def text_of(fn, *args, **kw):
    return fn.trace(*args, **kw).lower(
        lowering_platforms=(platform,)).as_text()


def watched_jit(fun, *args, **kw):
    fn = _jit(fun, *args, **kw)
    made.append(fn)
    return fn


def engine(cap, v4_only, **mesh):
    from cilium_tpu.model.services import Backend, Frontend, Service
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.utils import constants as C
    cfg = DaemonConfig(ct_capacity=cap, v4_only=v4_only, auto_regen=False,
                       batch_size=ROWS, flowlog_mode="none", **mesh)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
    eng.apply_policy(POLICY)
    eng.upsert_service(Service(
        name="api", namespace="prod",
        frontends=(Frontend("172.30.0.1", 443, C.PROTO_TCP),),
        lb_backends=(Backend("10.7.0.1", 443), Backend("10.7.0.2", 443))))
    eng.regenerate()
    return eng


def aval(x):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                sharding=getattr(x, "sharding", None))


def wires(dp, v4_only):
    """name → the batch argument of one dispatch on that wire."""
    from cilium_tpu.kernels import records as R
    rows = getattr(dp, "_batch_sharding", None)
    repl = getattr(dp, "_repl_sharding", None)

    def wire(words):
        return jax.ShapeDtypeStruct((ROWS, words), np.uint32, sharding=rows)
    path_dict = jax.ShapeDtypeStruct(PATH_DICT, np.uint32, sharding=repl)
    out = {"narrow": wire(R.PACK4_WORDS),
           "request-narrow": (wire(R.PACK4_L7_WORDS), path_dict)}
    if not v4_only:
        out["wide"] = wire(R.PACK_WORDS)
        out["request-wide"] = (wire(R.PACK_L7DICT_WORDS), path_dict)
    return out


def columns():
    """The column-dict batch (tests, ``zero_copy_ingest=False``)."""
    from cilium_tpu.kernels.records import empty_batch
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in empty_batch(ROWS).items()}


def step_args(eng, batch):
    dp, act = eng.datapath, eng.active
    return (jax.tree.map(aval, dict(act.tensors)),
            jax.tree.map(aval, dp._ct), batch,
            jax.ShapeDtypeStruct((), np.uint32),
            jax.ShapeDtypeStruct((), np.int32))


def lowered_step(eng, batch, jits):
    """The text of the program ``dp._classify`` runs for ``batch``. On one
    chip that callable is the jit; on a mesh it builds one jit a batch
    kind (wire, wire and dictionary, columns) on first use, so it is
    traced once (nothing compiles) and the jit it made, kept in ``jits``
    for the kind's other shapes, is the one lowered."""
    dp, args = eng.datapath, step_args(eng, batch)
    if hasattr(dp._classify, "lower"):
        return text_of(dp._classify, *args)
    kind = type(batch).__name__
    if kind not in jits:
        before = len(made)
        jax.eval_shape(dp._classify, *args)
        jits[kind], = made[before:]
    return text_of(jits[kind], *args)


def gc_tick(eng, cap):
    """The GC tick as ``sweep_step`` builds and calls it."""
    dp = eng.datapath
    before = len(made)
    dp.sweep_step(now=100, chunk_rows=min(cap, 1 << 16))
    fn, = made[before:]
    now = jax.ShapeDtypeStruct((), np.uint32)
    return text_of(fn, jax.tree.map(aval, dp._ct), now, now, count_now=now)


#: builder, its mesh ("" = one chip), the ``DaemonConfig`` fields that
#: select it, whether it also serves column-dict batches
#: (``zero_copy_ingest=False``; the one-chip column form is lowered apart)
BUILDERS = (
    ("make_classify_fn", "", {}, False),
    ("make_sharded_classify_fn", "[4x1]", {"n_shards": 4}, True),
    ("make_sharded_classify_fn", "[2x2]",
     {"n_shards": 2, "rule_shards": 2}, False),
    ("make_unsteered_classify_fn", "[4x1]",
     {"n_shards": 4, "rss_mode": "device"}, False),
)


def programs():
    """→ (program, geometry, text), every served program in turn."""
    from cilium_tpu.kernels.classify import make_classify_fn
    for builder, shape, mesh, takes_columns in BUILDERS:
        name = builder + shape
        for v4_only in (False, True):
            family = "v4-only" if v4_only else "dual-stack"
            for cap in CAPS:
                where = f"ct=2^{cap.bit_length() - 1},{family}"
                eng = engine(cap, v4_only, **mesh)
                try:
                    dp, jits = eng.datapath, {}
                    for wire, batch in wires(dp, v4_only).items():
                        yield (f"{name}(slab)", f"{where},{wire}",
                               lowered_step(eng, batch, jits))
                    if not mesh:
                        fn = make_classify_fn(
                            probe_depth=dp.config.probe_depth,
                            v4_only=v4_only)
                        yield (f"{name}(columns)", f"{where},dict",
                               text_of(fn, *step_args(eng, columns())))
                    elif takes_columns:
                        yield (f"{name}(slab)", f"{where},dict",
                               lowered_step(eng, columns(), jits))
                    if not v4_only:
                        yield ("gc_tick" + shape,
                               f"ct=2^{cap.bit_length() - 1}",
                               gc_tick(eng, cap))
                finally:
                    eng.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--text", help="directory to keep each lowered text in")
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    args = ap.parse_args(argv)
    global platform
    platform = args.platform
    jax.jit = watched_jit
    if args.text:
        os.makedirs(args.text, exist_ok=True)
    for n, (program, where, text) in enumerate(programs()):
        digest = hashlib.sha256(text.encode()).hexdigest()
        print(program, where, digest, flush=True)
        if args.text:
            with open(os.path.join(args.text, f"{n:03d}_{digest[:12]}.txt"),
                      "w") as f:
                f.write(f"# {program} {where}\n{text}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
