"""``python -m repro.serve``: run the prediction service.

Binds the ndjson-over-HTTP front end and serves until SIGINT/SIGTERM,
then drains gracefully (queued requests finish; new ones get 503).
``--warmup ARCH/KERNEL:N[/KERNEL:N...]`` precompiles the plans for a
scenario structure at the given ``--warmup-buckets`` so the first live
tick is a cache hit.

(The *model-decode* demo formerly reachable in this namespace lives at
:mod:`repro.launch.serve` / ``examples/serve_decode.py``.)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import signal
import sys

from .. import api
from ..core import backend as backend_mod
from .coalesce import ServeConfig
from .http import App


def _warmup_scenario(spec: str) -> "api.Scenario":
    """Parse ``ARCH/KERNEL:N[/KERNEL:N...]`` into a scenario."""
    arch, *groups = spec.split("/")
    if not groups:
        raise SystemExit(
            f"--warmup {spec!r}: expected ARCH/KERNEL:N[/KERNEL:N...]")
    sc = api.Scenario.on(arch)
    for g in groups:
        kernel, _, n = g.partition(":")
        sc = sc.run(kernel, int(n or 1))
    return sc


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="prediction-as-a-service over the bandwidth-sharing "
                    "model (ndjson over HTTP; see docs/serving.md)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8787,
                    help="0 picks a free port (printed on startup)")
    ap.add_argument("--tick-ms", type=float, default=1.0,
                    help="coalescing window (ms)")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-queue", type=int, default=1024)
    ap.add_argument("--deadline-ms", type=float, default=30000.0,
                    help="default per-request deadline (ms); requests "
                         "may override per line")
    ap.add_argument("--cache-entries", type=int, default=128,
                    help="plan-cache LRU capacity")
    ap.add_argument("--warmup", action="append", default=[],
                    metavar="ARCH/KERNEL:N[/KERNEL:N...]",
                    help="precompile plans for this structure "
                         "(repeatable)")
    ap.add_argument("--warmup-buckets", default="1,64",
                    help="comma-separated batch sizes to warm "
                         "(rounded up to power-of-two buckets)")
    args = ap.parse_args(argv)

    config = ServeConfig(
        tick_s=args.tick_ms / 1e3, max_batch=args.max_batch,
        max_queue=args.max_queue,
        default_deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms > 0 else None),
        cache_entries=args.cache_entries)
    backend_mod.enable_compile_cache()
    return asyncio.run(_serve(args, config))


async def _serve(args, config: ServeConfig) -> int:
    app = App(config)
    buckets = [int(b) for b in args.warmup_buckets.split(",") if b]
    for spec in args.warmup:
        built = app.cache.warmup(_warmup_scenario(spec), buckets=buckets)
        print(f"warmup {spec}: {built} plan(s) compiled", flush=True)
    port = await app.start(args.host, args.port)
    device = backend_mod.device_info()
    print(f"repro.serve: serving on http://{args.host}:{port} "
          f"(tick {config.tick_s * 1e3:g} ms, max_batch "
          f"{config.max_batch}, jax on {device['count']} "
          f"{device['platform']} device(s), {device['kind']})",
          flush=True)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:   # pragma: no cover - non-unix
            signal.signal(sig, lambda *_: stop.set())
    await stop.wait()
    print("repro.serve: draining...", flush=True)
    await app.shutdown(drain=True)
    stats = app.coalescer.stats()
    print("repro.serve: drained "
          + json.dumps({k: stats[k] for k in
                        ("accepted", "completed", "errors", "expired",
                         "rejected")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
