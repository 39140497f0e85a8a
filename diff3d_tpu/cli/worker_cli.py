"""Fleet worker process: one replica pinned to a device slice.

Boots a single serving replica in THIS process, pinned to a disjoint
subset of the host's devices, and serves the framed socket protocol
(``serving/transport.py``) that ``serve_cli --workers`` fronts.  N
workers on one host split the device set instead of sharing it — on
the CPU test backend the 8 virtual devices split 2×4::

    python -m diff3d_tpu.cli.worker_cli --config test --init random \
        --devices 0-3 --port 0 --name w0 --host_device_count 8
    python -m diff3d_tpu.cli.worker_cli --config test --init random \
        --devices 4-7 --port 0 --name w1 --host_device_count 8

With ``--port 0`` the worker binds an ephemeral port and prints one
JSON ready line to stdout (``{"ready": true, "port": ..., "name":
..., "http_port": ...}``) so a supervisor can harvest the address.

``--hbm_budget_bytes`` arms the admission gate: requests whose
resident-records + program-peak arithmetic (the ``runs/memcheck/``
pins, see ``--memcheck_dir``) exceeds the slice budget are rejected at
the door with a typed ``ReplicaOverBudget``.  The persistent compile
cache is placed by ``diff3d_tpu.runtime.configure_compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``--compile_cache
DIR``, else the checkout's fixed directory — sibling workers and
blue/green restarts share it and skip cold compiles.

One process per chip: a TPU belongs to one process at a time, so on a
real host ONE worker process drives all local chips (in-process
``Replica``s behind the router are the supported single-host layout).
Several worker processes on one host, each with its own ``--devices``
slice, work on the virtual CPU mesh only — on a TPU host the second
process cannot open the device.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import threading

from diff3d_tpu.cli._common import (add_model_width_args,
                                    apply_model_width_overrides,
                                    build_abstract_state,
                                    load_eval_params)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default=None,
                   help="checkpoint directory; omit with --init random")
    p.add_argument("--init", choices=["checkpoint", "random"],
                   default="checkpoint")
    p.add_argument("--config", choices=["srn64", "srn128", "test"],
                   default="srn64")
    p.add_argument("--name", default=None,
                   help="replica name (fleet-wide identity; default "
                        "'w<pid>')")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address for the socket transport")
    p.add_argument("--port", type=int, default=0,
                   help="transport port (0 = ephemeral; the bound port "
                        "is printed on the JSON ready line)")
    p.add_argument("--http_port", type=int, default=None,
                   help="also serve the worker's own HTTP surface "
                        "(/healthz /metrics /stats) on this port "
                        "(0 = ephemeral)")
    p.add_argument("--devices", required=True,
                   help="device slice this replica owns: '0-3' "
                        "(inclusive range) or '0,2,4' (list); disjoint "
                        "across workers on one host")
    p.add_argument("--host_device_count", type=int, default=None,
                   help="force this many virtual host devices "
                        "(XLA_FLAGS, CPU backend) — set it identically "
                        "on every worker sharing a host so slices mean "
                        "the same thing")
    p.add_argument("--sampler", choices=["ancestral", "ddim"],
                   default="ancestral")
    p.add_argument("--sampler_steps", type=int, default=None,
                   help="reverse steps per view for the default sampler "
                        "(default: the config's dense grid)")
    p.add_argument("--schedules", default=None,
                   help="extra compiled schedules beyond the default, "
                        "'kind:steps,...' — same grammar as serve_cli "
                        "--schedules (no 'i@' prefix: one worker is one "
                        "replica)")
    p.add_argument("--scan_chunks", type=int, default=1)
    p.add_argument("--hbm_budget_bytes", type=int, default=0,
                   help="slice HBM budget for admission control "
                        "(0 disables): resident records + program peak "
                        "past it -> typed ReplicaOverBudget 503")
    p.add_argument("--memcheck_dir", default=None,
                   help="memcheck manifest dir with the program peak "
                        "pins (default: runs/memcheck)")
    p.add_argument("--compile_cache", default=None,
                   help="persistent XLA compile-cache dir shared "
                        "across workers/restarts (ignored where "
                        "JAX_COMPILATION_CACHE_DIR is set; default: the "
                        "checkout's .jax_cache)")
    p.add_argument("--shallow", action="store_true",
                   help="with --config test: shallow 2-level UNet")
    p.add_argument("--max_views", type=int, default=None)
    p.add_argument("--timeout_s", type=float, default=None)
    p.add_argument("--raw_params", action="store_true")
    add_model_width_args(p)
    return p


def parse_schedules(spec: str):
    scheds = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        kind, _, steps_s = entry.partition(":")
        try:
            scheds.append((kind, int(steps_s)))
        except ValueError:
            raise SystemExit(
                f"--schedules entry {entry!r}: expected 'kind:steps'")
    return scheds


def build_worker(args):
    """Config + params -> Worker (not started)."""
    import dataclasses

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.analysis import membudgets
    from diff3d_tpu.serving.worker import boot_worker, device_slice

    if args.config == "test":
        cfg = config_lib.test_config(
            imgsize=args.imgsize or 16,
            ch=args.ch or 8,
            shallow=args.shallow)
    else:
        cfg = {"srn64": config_lib.srn64_config,
               "srn128": config_lib.srn128_config}[args.config]()
        cfg = apply_model_width_overrides(cfg, args)
    over = {}
    if args.max_views is not None:
        over["max_views"] = args.max_views
    if args.timeout_s is not None:
        over["default_timeout_s"] = args.timeout_s
    if over:
        cfg = dataclasses.replace(
            cfg, serving=dataclasses.replace(cfg.serving, **over))
    cfg.validate()

    params, version = None, "random-init"
    if args.init == "checkpoint":
        if not args.model:
            raise SystemExit("--model is required unless --init random")
        try:
            step, params = load_eval_params(args.model,
                                            build_abstract_state(cfg),
                                            args.raw_params)
        except ValueError as e:
            raise SystemExit(str(e))
        version = f"{args.model}@step{step}"

    name = args.name or f"w{os.getpid()}"
    return boot_worker(
        cfg,
        name=name,
        devices=device_slice(args.devices),
        sampler_kind=args.sampler,
        steps=args.sampler_steps,
        extra_schedules=(parse_schedules(args.schedules)
                         if args.schedules else None),
        params=params,
        params_version=version,
        host=args.host,
        port=args.port,
        hbm_budget_bytes=args.hbm_budget_bytes,
        memcheck_dir=(args.memcheck_dir
                      or membudgets.DEFAULT_MANIFEST_DIR),
        scan_chunks=args.scan_chunks)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    # Must precede the first jax import anywhere in-process: the CPU
    # backend reads XLA_FLAGS once, at client init.
    if args.host_device_count:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.host_device_count}").strip()
    logging.basicConfig(level=logging.INFO)
    logging.getLogger("absl").setLevel(logging.WARNING)
    from diff3d_tpu.runtime import configure_compile_cache
    logging.info("compile cache: %s",
                 configure_compile_cache(args.compile_cache))

    worker = build_worker(args)
    worker.start(http_port=args.http_port)
    # Machine-readable ready line: supervisors (serve_cli --workers,
    # chaos_router --remote, the tests) harvest the ephemeral port.
    print(json.dumps({"ready": True, "name": worker.replica.name,
                      "port": worker.port,
                      "http_port": worker.http_port}), flush=True)
    logging.info("worker %s: transport on %s:%d",
                 worker.replica.name, args.host, worker.port)

    done = threading.Event()

    def _sig(signum, frame):
        logging.info("signal %d: shutting down", signum)
        done.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    try:
        done.wait()
    finally:
        worker.stop()
        logging.info("stopped")


if __name__ == "__main__":
    main()
