"""The long-lived agent process (upstream: the ``cilium-agent`` daemon,
SURVEY.md §3.1): construct the Engine, restore state, serve the REST API +
background controllers, checkpoint on shutdown.

    cilium-tpu agent run [--config FILE] [--state-dir DIR] [--api-socket S]
                         [--fake-datapath] ...

Startup mirrors §3.1's sequence: config population (file < env < flags) →
state restore (endpoints/rules/identities/CT re-placed from the state dir) →
regenerate (the restored-endpoints full build) → controllers + API up. On
SIGTERM/SIGINT: final checkpoint (the pinned-maps analog — flows survive the
restart), API socket removed, controllers stopped.
"""

from __future__ import annotations

import logging
import os
import signal
import threading


def register(sub) -> None:
    p = sub.add_parser("agent", help="run the long-lived agent daemon")
    asub = p.add_subparsers(dest="subcmd", required=True)
    pr = asub.add_parser("run", help="start the agent (blocks until SIGTERM)")
    pr.add_argument("--config", help="DaemonConfig JSON file")
    pr.add_argument("--api-socket", help="REST unix socket path "
                                         "(overrides config)")
    pr.add_argument("--state-dir", help="checkpoint dir (overrides config)")
    pr.add_argument("--fake-datapath", action="store_true",
                    help="serve with the oracle-backed fake (no jax/device; "
                         "control-plane testing)")
    pr.add_argument("--checkpoint-interval-s", type=float, default=60.0,
                    help="periodic checkpoint cadence (0 = only on exit)")
    pr.add_argument("--oneshot", action="store_true",
                    help="start, regenerate, checkpoint, exit (smoke runs)")
    pr.set_defaults(func=cmd_agent_run)


def cmd_agent_run(args) -> int:
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime import checkpoint as ckpt

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    log = logging.getLogger("cilium_tpu.agent")

    overrides = []
    if args.api_socket:
        overrides += ["--api-socket", args.api_socket]
    if args.state_dir:
        overrides += ["--state-dir", args.state_dir]
    config = DaemonConfig.load(config_file=args.config, argv=overrides)

    datapath = None
    if args.fake_datapath:
        from cilium_tpu.runtime.datapath import FakeDatapath
        datapath = FakeDatapath(config)
    from cilium_tpu.runtime.engine import Engine
    engine = Engine(config, datapath=datapath)

    state_dir = config.state_dir
    restored = False
    if state_dir and os.path.exists(os.path.join(state_dir, "state.json")):
        try:
            # a corrupt checkpoint returns False (cold start) — only an
            # unexpected error (bad engine state, device failure) raises
            restored = ckpt.restore(engine, state_dir)
        except Exception:
            log.exception("state restore failed; starting empty")
        if restored:
            log.info("restored state from %s (revision %d, %d endpoints)",
                     state_dir, engine.repo.revision, len(engine.endpoints))
        else:
            log.warning("checkpoint at %s unusable; cold start", state_dir)
    engine.regenerate(force=True)
    # the handlers go in before the API socket answers: a client that sees
    # healthz 200 may send SIGTERM at once, and the default action kills
    # the agent without its final checkpoint
    stop = threading.Event()

    def _on_signal(signum, _frame):
        log.info("signal %d: shutting down", signum)
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    engine.start_background()
    if config.api_socket:
        log.info("api listening on %s", config.api_socket)

    def _checkpoint():
        if state_dir:
            ckpt.save(engine, state_dir)

    if state_dir and args.checkpoint_interval_s > 0:
        engine.controllers.update("checkpoint", _checkpoint,
                                  interval=args.checkpoint_interval_s)

    log.info("agent up (revision %d, restored=%s, enforcement=%s)",
             engine.repo.revision, restored, engine.ctx.enforcement_mode)
    if args.oneshot:
        stop.set()
    stop.wait()

    try:
        _checkpoint()
        if state_dir:
            log.info("final checkpoint written to %s", state_dir)
    finally:
        engine.stop()
    return 0
