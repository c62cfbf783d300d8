"""Benchmark driver: one module per paper table/figure.

Default output is ``name,us_per_call,derived`` CSV on stdout:
    PYTHONPATH=src python -m benchmarks.run [--only fig8]

``--json`` aggregates every module's rows into one machine-readable
report (optionally written to ``--out``); rows are consumed from a
generator module by module, so the working set is one module's rows:
    PYTHONPATH=src python -m benchmarks.run --json --out report.json

``--ndjson`` is the fully streaming form — one JSON line per row,
written as it is produced through the facade's streaming writer
(:func:`repro.api.dump_dicts`), nothing accumulated; the right mode
when the row count is huge or a consumer tails the file live:
    PYTHONPATH=src python -m benchmarks.run --ndjson --out report.ndjson
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

from repro.api import dump_dicts
from repro.core import backend

from . import (analysis_accuracy, api_overhead, calibrate_roundtrip,
               desync_scaling, fig6_full_domain, fig7_symmetric, fig8_error,
               fig9_pairings, grad_calibration, hpcg_desync, obs_overhead,
               placement_scaling, plan_overhead, table2_kernels,
               tpu_overlap)

MODULES = {
    "analysis": analysis_accuracy,
    "table2": table2_kernels,
    "fig6": fig6_full_domain,
    "fig7": fig7_symmetric,
    "fig8": fig8_error,
    "fig9": fig9_pairings,
    "hpcg": hpcg_desync,
    "tpu_overlap": tpu_overlap,
    "desync_scaling": desync_scaling,
    "calibrate": calibrate_roundtrip,
    "api_overhead": api_overhead,
    "plan_overhead": plan_overhead,
    "placement_scaling": placement_scaling,
    "grad": grad_calibration,
    "obs": obs_overhead,
}


def iter_rows(keys, failures: dict[str, str]):
    """Yield ``(module_key, row_dict)`` as modules produce them; a
    module that raises records its traceback in ``failures`` and the
    stream moves on."""
    for key in keys:
        try:
            for name, us, derived in MODULES[key].rows():
                yield key, {"name": name, "us_per_call": round(us, 1),
                            "derived": derived}
        except Exception:  # noqa: BLE001
            failures[key] = traceback.format_exc(limit=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=sorted(MODULES), default=None)
    ap.add_argument("--json", action="store_true",
                    help="emit one aggregated JSON report instead of CSV")
    ap.add_argument("--ndjson", action="store_true",
                    help="stream one JSON line per row as produced "
                         "(never materializes the full row list)")
    ap.add_argument("--out", default=None,
                    help="with --json/--ndjson: write here instead of "
                         "stdout")
    args = ap.parse_args()
    keys = [args.only] if args.only else list(MODULES)
    backend.enable_compile_cache()

    if args.ndjson:
        failures: dict[str, str] = {}
        rows = ({"module": key, **row}
                for key, row in iter_rows(keys, failures))
        if args.out:
            with open(args.out, "w") as fh:
                n = dump_dicts(rows, fh)
            print(f"wrote {args.out}  (rows={n}, "
                  f"failures={len(failures)})")
        else:
            dump_dicts(rows, sys.stdout)
        for key, tb in failures.items():
            print(f"FAILED {key}: {tb}", file=sys.stderr)
        if failures:
            sys.exit(1)
        return

    if args.json:
        # Modules are atomic in the aggregate report: a module that
        # fails mid-iteration contributes its traceback, never a
        # partial row set that could be mistaken for real results.
        failures = {}
        results: dict[str, list[dict]] = {}
        for key in keys:
            module_failures: dict[str, str] = {}
            rows = [row for _, row in iter_rows([key], module_failures)]
            if module_failures:
                failures.update(module_failures)
            else:
                results[key] = rows
        report = {
            "benchmark": "benchmarks.run",
            "modules": results,
            "failures": failures,
            "n_rows": sum(len(r) for r in results.values()),
            "ok": not failures,
        }
        text = json.dumps(report, indent=2) + "\n"
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(text)
            print(f"wrote {args.out}  (modules={len(results)}, "
                  f"rows={report['n_rows']}, ok={report['ok']})")
        else:
            sys.stdout.write(text)
        if failures:
            sys.exit(1)
        return

    print("name,us_per_call,derived")
    failures = {}
    for key, row in iter_rows(keys, failures):
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
        sys.stdout.flush()
    for key, tb in failures.items():
        print(f"{key}/ERROR,0.0,{tb!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
