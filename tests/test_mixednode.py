"""Tier-1's hold on the mixed node's tests (PR 42).

``benchmarks/tests/test_mixednode.py`` holds the plain reference of
``worlds/mixednode.py`` (the world ``node-mixed`` is built with) against a
loop over its documents' text, its table and reasons against the program's
oracle, every case in which planes meet by hand, frames of every kind
through the shim in one harvest, the tiny cell through ``run_cell`` on the
jitted datapath (a verdict flipped in one plane's rows included), and the
worlds ``build`` has to refuse. ``python -m pytest benchmarks/tests`` runs
them there; tier-1 collects ``tests/`` only. This file brings every one
under tier-1 as it stands, case for case, as ``tests/test_httprules.py``
does for PR 36's: pytest collects a test function (and a fixture) by the
name it finds in a module, wherever it was defined, and a ``parametrize``
mark rides on the function.

The benchmark's conftest is not loaded here, only imported for its
helpers; the native shim its session fixture builds is built by ``tests/
conftest.py``, and the load generator's by the runs themselves.
"""

from benchmarks.tests.test_mixednode import *  # noqa: F401,F403
