"""Serving smoke test over a real socket and process boundary.

Boots ``python -m repro.serve`` as a subprocess (warmup flags
included), waits for ``/healthz``, streams an ndjson workload through
``/v1/solve`` with the stdlib client, checks ``/statsz``, then sends
SIGTERM and requires a clean graceful-drain exit (code 0).  This is
what the CI ``serve-smoke`` job runs; locally::

    PYTHONPATH=src python benchmarks/serve_smoke.py
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

# Imports jax but never initialises a backend: the chip stays free for
# the server process.
from repro.serve import client

HOST = "127.0.0.1"


def wait_healthy(port: int, proc, timeout_s: float = 30.0) -> dict:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if proc.poll() is not None:
            raise SystemExit(f"server died early (exit {proc.returncode})")
        try:
            status, health = client.get_json(HOST, port, "/healthz")
        except OSError:
            time.sleep(0.1)
            continue
        assert status == 200 and health["ok"], health
        return health
    raise SystemExit("server never came up")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--port", type=int, default=8123)
    ap.add_argument("--n", type=int, default=32, help="workload lines")
    args = ap.parse_args(argv)

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--port", str(args.port),
         "--warmup", "CLX/DCOPY:12/DDOT2:8", "--warmup-buckets", "1,32"],
        env=env)
    try:
        wait_healthy(args.port, proc)

        rows = [{"id": k, "arch": "CLX",
                 "groups": [{"kernel": "DCOPY", "n": 1 + k % 19},
                            {"kernel": "DDOT2", "n": 20 - (1 + k % 19)}]}
                for k in range(args.n)]
        out = client.solve(HOST, args.port, rows)
        assert [r["id"] for r in out] == list(range(args.n)), \
            "response order must match request order"
        bad = [r for r in out if not r.get("ok")]
        assert not bad, bad
        assert all(r["total_bw"] > 0 for r in out)

        status, stats = client.get_json(HOST, args.port, "/statsz")
        assert status == 200
        co, pc = stats["coalescer"], stats["plan_cache"]
        assert co["completed"] == args.n, co
        assert pc["hits"] >= 1, f"warmed structure must hit: {pc}"
        print(f"smoke ok: {args.n} requests in {co['ticks']} ticks, "
              f"plan cache hits={pc['hits']} misses={pc['misses']}")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            code = proc.wait(timeout=60)
        else:
            code = proc.returncode
    assert code == 0, f"graceful drain must exit 0, got {code}"
    print("graceful shutdown ok (exit 0)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
