"""Readings from which the correctness limits are set: the program's
numbers and the control's, over many seeds, in one process.

    python bench/control.py --workload sweep.paper-table2.numbers \\
        --seconds 5 --seeds 11 12 13

For each seed it sets the cell up at its own size, runs a short window,
frees the program's state and compares twice: what the window produced
against the reference (the program's reading), and the reference
computed in float32, put in the program's place, against the same
reference (the control's reading).  One JSON line per seed.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = harness.find_cell(args.workload)
    device = harness.check_device(int(cell["workload"]["chips"]))
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core import backend
    backend.enable_compile_cache()
    traffic = cell["traffic"]
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{traffic['driver']}.py", "driver")
    for seed in args.seeds:
        subject = driver.Cell(cell["config"], traffic, seed)
        window = subject.window(args.seconds, annotate=False)
        subject.release()
        program, attempted, failed = subject.check(window)
        control, _, control_failed = subject.check(window, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": device["kind"], "program": program,
                          "control": control, "attempted": attempted,
                          "failed": failed,
                          "control_failed": control_failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
