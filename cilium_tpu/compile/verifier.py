"""Compile-all-configs verifier (SURVEY.md §4: upstream compiles every bpf
object for all kernel/config combos in ``test/verifier`` CI and asserts
verifier acceptance — "analog: assert XLA compilation of every config combo,
HBM budget check").

Here the eBPF verifier's role is played by XLA: a datapath configuration is
"verifier-accepted" when its fused classify program lowers, compiles, and
fits the memory budget. ``verify_configs`` AOT-compiles the classify step
over the cross product of datapath shape knobs (address family, wire format,
L7, LB, CT geometry, rule-shard padding) on tiny worlds and reports
per-combo status + compiled memory use, failing loudly on any combo a code
change broke — BEFORE that combo is hit in production.

Run via ``cilium-tpu verify`` or pytest (tests/test_verifier.py).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass
class ComboReport:
    name: str
    ok: bool
    error: str = ""
    argument_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0


def _build_world(l7: bool, lb: bool, v6: bool):
    from cilium_tpu.compile.ct_layout import CTConfig
    from cilium_tpu.compile.snapshot import build_snapshot
    from cilium_tpu.model.endpoint import Endpoint
    from cilium_tpu.model.identity import IdentityAllocator
    from cilium_tpu.model.ipcache import IPCache
    from cilium_tpu.model.labels import Labels
    from cilium_tpu.model.rules import parse_rule
    from cilium_tpu.model.services import Service
    from cilium_tpu.policy import PolicyContext, Repository
    from cilium_tpu.policy.selectorcache import SelectorCache
    from cilium_tpu.model.services import ServiceRegistry

    alloc = IdentityAllocator()
    ctx = PolicyContext(allocator=alloc,
                        selector_cache=SelectorCache(alloc),
                        ipcache=IPCache(), services=ServiceRegistry())
    repo = Repository(ctx)
    lbls = Labels.parse(["k8s:app=web"])
    ident = alloc.allocate(lbls)
    ctx.ipcache.upsert("192.168.0.10/32", ident.id)
    ep = Endpoint(ep_id=1, labels=lbls, identity_id=ident.id)
    docs = [{"endpointSelector": {"matchLabels": {"app": "web"}},
             "egress": [{"toCIDR": ["10.0.0.0/8"],
                         "toPorts": [{"ports": [
                             {"port": "443", "protocol": "TCP"}]}]}]}]
    if v6:
        docs.append({"endpointSelector": {"matchLabels": {"app": "web"}},
                     "egress": [{"toCIDR": ["2001:db8::/32"]}]})
    if l7:
        docs.append({"endpointSelector": {"matchLabels": {"app": "web"}},
                     "ingress": [{"toPorts": [{
                         "ports": [{"port": "80", "protocol": "TCP"}],
                         "rules": {"http": [
                             {"method": "GET", "path": "/api"}]}}]}]})
    if lb:
        # a REAL frontend: the snapshot only carries LB tensors (and the
        # kernel only compiles the LB stage — frontend probe, Maglev,
        # rev-NAT) when one exists; a frontend-less service would make
        # every "+lb" combo compile the identical LB-free program
        from cilium_tpu.model.services import Backend, Frontend
        from cilium_tpu.utils import constants as CC
        ctx.services.upsert(Service(
            name="api", namespace="prod", backends=("10.3.0.1",),
            frontends=(Frontend("10.96.0.10", 443, CC.PROTO_TCP),),
            lb_backends=(Backend("10.3.0.1", 8443),)))
        docs.append({"endpointSelector": {"matchLabels": {"app": "web"}},
                     "egress": [{"toServices": [{"k8sService": {
                         "serviceName": "api", "namespace": "prod"}}]}]})
    repo.add([parse_rule(d) for d in docs])
    return build_snapshot(repo, ctx, [ep], CTConfig(capacity=1 << 10))


def memory_stats(compiled) -> Dict[str, int]:
    """Bytes a compiled XLA executable needs, via ``memory_analysis()`` —
    the machinery both the offline budget check here and the live HBM
    ledger (runtime/datapath.hbm_ledger, ISSUE 13) cite: argument bytes are
    the placed tensors the ledger accounts group by group; temp bytes are
    the compiler's scratch on top."""
    try:
        m = compiled.memory_analysis()
        return {
            "argument_bytes": int(getattr(m, "argument_size_in_bytes", 0)),
            "temp_bytes": int(getattr(m, "temp_size_in_bytes", 0)),
            "output_bytes": int(getattr(m, "output_size_in_bytes", 0)),
        }
    except Exception:
        return {"argument_bytes": 0, "temp_bytes": 0, "output_bytes": 0}


_memory_stats = memory_stats           # pre-ISSUE-13 private name


def budget_doc(reports: List[ComboReport],
               max_hbm_bytes: Optional[int] = None) -> Dict:
    """Summarize one verify sweep into the HBM budget report that
    ``status_doc`` embeds (ISSUE 13 satellite:
    offline ``--max-hbm-bytes`` verification and the live ledger citing
    the same numbers). Pure function of the reports — reusable on a sweep
    loaded back from a ``cilium-tpu verify --report`` file."""
    ok = [r for r in reports if r.ok]
    worst = max(ok, key=lambda r: r.argument_bytes + r.temp_bytes,
                default=None)
    return {
        "combos": len(reports),
        "accepted": len(ok),
        "rejected": [r.name for r in reports if not r.ok],
        "max_hbm_bytes": max_hbm_bytes,
        "worst_combo": worst.name if worst is not None else None,
        "worst_argument_bytes": worst.argument_bytes if worst else 0,
        "worst_temp_bytes": worst.temp_bytes if worst else 0,
        "worst_total_bytes": (worst.argument_bytes + worst.temp_bytes)
        if worst else 0,
    }


def verify_configs(batch: int = 256,
                   max_hbm_bytes: Optional[int] = None,
                   quick: bool = False) -> List[ComboReport]:
    """AOT-compile the classify step for every datapath shape combo.
    ``max_hbm_bytes`` bounds argument+temp memory per combo (HBM budget
    check; None = report only). ``quick`` drops the LB axis (the LB stage's
    program shape is covered by the full sweep in CI; quick keeps the
    family/wire/L7 axes that actually change lowering)."""
    import jax
    import jax.numpy as jnp
    from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
    from cilium_tpu.kernels.classify import make_classify_fn
    from cilium_tpu.kernels.records import (
        empty_batch, pack_batch, pack_batch_l7dict, pack_batch_v4)

    reports: List[ComboReport] = []
    wire_formats = ("dict", "v4", "full", "l7dict", "addr")
    lb_axis = (False,) if quick else (False, True)
    for v4_only, l7, lb, wire in itertools.product(
            (False, True), (False, True), lb_axis, wire_formats):
        if wire == "v4" and (l7 or not v4_only):
            continue                    # compact wire is v4/L7-free only
        if wire == "l7dict" and not l7:
            continue
        if wire == "addr" and (v4_only or lb):
            continue                    # one addr-dict combo per L7 state
        name = (f"{'v4only' if v4_only else 'dual'}"
                f"{'+l7' if l7 else ''}{'+lb' if lb else ''}+{wire}")
        try:
            snap = _build_world(l7=l7, lb=lb, v6=not v4_only)
            tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
            ct = {k: jnp.asarray(v) for k, v in make_ct_arrays(
                snap.ct_config).items()}
            b = empty_batch(batch)
            b["valid"][:] = True
            b["dst"][:, 2] = 0xFFFF
            b["dst"][:, 3] = 0x0A000001
            if l7:
                b["http_method"][:] = 0
                b["http_path"][:, 0] = ord("/")
            # packed wires compile in the form the one-chip datapath
            # serves them: results packed into one slab inside the jit
            fn = make_classify_fn(v4_only=v4_only, donate_ct=False,
                                  packed=wire != "dict",
                                  slab=wire != "dict")
            if wire == "dict":
                arg = {k: jnp.asarray(v) for k, v in b.items()}
            elif wire == "v4":
                arg = jnp.asarray(pack_batch_v4(b))
            elif wire == "l7dict":
                w, d = pack_batch_l7dict(b)
                arg = (jnp.asarray(w), jnp.asarray(d))
            elif wire == "addr":
                from cilium_tpu.kernels.records import pack_batch_addrdict
                arg = tuple(jnp.asarray(x)
                            for x in pack_batch_addrdict(b, l7=l7))
            else:
                arg = jnp.asarray(pack_batch(b, l7=l7))
            lowered = fn.lower(tensors, ct, arg, jnp.uint32(1000),
                               jnp.int32(snap.world_index))
            compiled = lowered.compile()
            stats = _memory_stats(compiled)
            reports.append(ComboReport(name=name, ok=True, **stats))
        except Exception as e:          # compile failure = verifier reject
            reports.append(ComboReport(name=name, ok=False, error=repr(e)))
    # the sharded program (rule-axis psum) is covered by dryrun_multichip;
    # here we additionally verify rule-padded single-device geometry
    try:
        from cilium_tpu.parallel.mesh import pad_snapshot_tensors
        snap = _build_world(l7=False, lb=False, v6=False)
        tensors_np = pad_snapshot_tensors(snap.tensors(), 4)
        tensors = {k: jnp.asarray(v) for k, v in tensors_np.items()}
        ct = {k: jnp.asarray(v) for k, v in make_ct_arrays(
            snap.ct_config).items()}
        b = empty_batch(batch)
        fn = make_classify_fn(v4_only=True, donate_ct=False)
        arg = {k: jnp.asarray(v) for k, v in b.items()}
        compiled = fn.lower(tensors, ct, arg, jnp.uint32(1000),
                            jnp.int32(snap.world_index)).compile()
        reports.append(ComboReport(name="rule-padded", ok=True,
                                   **_memory_stats(compiled)))
    except Exception as e:
        reports.append(ComboReport(name="rule-padded", ok=False,
                                   error=repr(e)))
    if max_hbm_bytes is not None:
        reports = apply_budget(reports, max_hbm_bytes)
    return reports


def apply_budget(reports: List[ComboReport],
                 max_hbm_bytes: int) -> List[ComboReport]:
    """Post-process a sweep's memory stats against an HBM budget — pure
    function of the reports, so one compile sweep serves any number of
    budget policies (CI reuses a single sweep)."""
    import dataclasses
    out = []
    for r in reports:
        total = r.argument_bytes + r.temp_bytes
        if r.ok and total > max_hbm_bytes:
            r = dataclasses.replace(
                r, ok=False,
                error=f"memory budget exceeded: {total} > {max_hbm_bytes}")
        else:
            r = dataclasses.replace(r)   # never alias the input reports
        out.append(r)
    return out
