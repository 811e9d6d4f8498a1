"""cilium_tpu — a TPU-native packet-classification framework.

A from-scratch reimplementation of the capabilities of Cilium's eBPF datapath
(reference: carlanton/cilium; see SURVEY.md — the reference mount was empty, so
SURVEY.md's reconstructed semantics + the in-repo oracle are the parity contract),
re-designed TPU-first:

- ``model/``    — labels, security identities, CNP-compatible rule schema, ipcache
                  (analog of upstream ``pkg/labels``, ``pkg/identity``,
                  ``pkg/policy/api``, ``pkg/ipcache``).
- ``policy/``   — Repository + SelectorCache + MapState computation
                  (analog of ``pkg/policy``).
- ``compile/``  — the "loader": MapState/ipcache/CT-config → dense device tensor
                  images (analog of ``pkg/datapath/loader`` — XLA replaces clang).
- ``kernels/``  — batched JAX datapath kernels: LPM gather, policy lookup,
                  conntrack probe, L7-lite match, fused classify step (analog of
                  ``bpf/``).
- ``runtime/``  — host engine: snapshot double-buffering with revision fencing,
                  update controller, checkpoint/resume, metrics, flow log (analog
                  of ``pkg/datapath``, ``pkg/endpoint`` regeneration, monitor/Hubble).
- ``parallel/`` — device mesh + shard_map strategies: batch DP with RSS-style CT
                  sharding, rule-space row sharding (analog of per-CPU maps / RSS).
- ``shim/``     — C++ AF_XDP front end + ctypes bindings (analog of ``bpf_xdp.c``
                  XDP hook, rebuilt as a userspace shim feeding the TPU).
- ``cli/``      — inspect/trace commands (analog of ``cilium-dbg``).
"""

__version__ = "0.1.0"
