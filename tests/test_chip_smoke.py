"""chip_smoke.py and the start-up rules it leans on: no CPU mode for the
chip check, ``device="tpu"`` is a requirement, the compile cache has one
fixed place, and status says which device serves. The phases themselves run
here on a tiny world — the CPU proves their control flow, the chip run
proves the product."""

import os
import subprocess
import sys

import jax
import pytest

from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_has_no_cpu_mode():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "phase device FAILED" in proc.stderr
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_phases_on_a_tiny_world_and_a_failing_one_stops_the_run():
    import chip_smoke as cs
    from cilium_tpu.runtime.faults import FAULTS
    world = cs.World(n_ids=32, n_rules=256, port_span=128,
                     ct_capacity=1 << 15, batch_size=256, n_frames=3000,
                     collide_windows=4, parity_rows=256)
    run = cs.Run(world=world, seed=0)
    cs.phase_build(run)
    try:
        for name, phase in cs.PHASES:
            phase(run)
        assert run.ct_full > 0 and run.established.any()
        # nothing survives a failed phase: the auditor's own corruption
        # drill flips captured verdicts, and phase parity raises
        FAULTS.load_spec("audit.corrupt=fail")
        est = run.established
        cs.nic_serve(run, cs.frames_of(cs.columns(
            run.flows["src"][est][:512], run.flows["sport"][est][:512],
            run.flows["dport"][est][:512])), "serve2")
        with pytest.raises(SystemExit, match="phase parity FAILED"):
            cs.phase_parity(run)
    finally:
        FAULTS.reset()
        cs.shutdown(run)


def test_device_tpu_is_a_requirement():
    assert jax.default_backend() == "cpu"
    with pytest.raises(RuntimeError, match="device='tpu'"):
        Engine(DaemonConfig(device="tpu"))
    with pytest.raises(ValueError, match="device"):
        DaemonConfig(device="gpu")


def test_status_names_the_serving_device():
    from cilium_tpu.runtime.api import status_doc
    eng = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False))
    try:
        dev = status_doc(eng)["device"]
        d0 = jax.devices()[0]
        assert (dev["platform"], dev["device_kind"], dev["count"]) \
            == (d0.platform, d0.device_kind, len(jax.devices()))
        assert dev["configured"] == "auto" and dev["serving"] == 1
    finally:
        eng.stop()


def test_status_carries_no_kernel_selector(tmp_path, capsys):
    """One classify interior (PR 52): the status document and the CLI's
    ``status`` text of a live jitted engine name the serving device and
    carry no selector beside it."""
    from cilium_tpu.cli.main import main as cli_main
    from cilium_tpu.runtime.api import status_doc
    sock = str(tmp_path / "api.sock")
    eng = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False,
                              api_socket=sock))
    try:
        doc = status_doc(eng)
        assert doc["device"]["platform"] == "cpu"
        assert "fused_kernels" not in doc
        eng.start_background()
        assert cli_main(["status", "--api", sock]) == 0
        out = capsys.readouterr().out
        assert "Device:" in out and "fused" not in out.lower()
    finally:
        eng.stop()


def test_compile_cache_has_one_place(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.ENV_VAR)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache() \
        == os.path.join(ROOT, ".jax_cache") \
        == jax.config.jax_compilation_cache_dir
