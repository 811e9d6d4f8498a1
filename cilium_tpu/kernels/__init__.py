"""Batched JAX datapath kernels (analog of upstream ``bpf/`` — SURVEY.md §2
native checklist item 1: "JAX TPU kernels (LPM lookup, policy match,
conntrack probe, L7-lite token match) — device-native, not Python loops").

Everything here is shape-static, branch-free (masked select instead of
data-dependent control flow), and jit-compiled once per snapshot geometry.
"""
