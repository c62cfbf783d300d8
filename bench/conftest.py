"""Tiny sizes of the cells that ``bench/tests/test_bench_cells.py``'s
table does not list, registered beside it: that test runs every cell of
``BENCHMARK.json`` at the sizes its ``SIZES`` table gives, and a cell
added later brings its own here."""

import sys

import pytest

#: Sizes laid over each later cell's configuration and traffic.
SIZES = {
    "serve.paper-table2.batch-posts": {
        "traffic": {"min_lines": 8, "max_lines": 32, "posts": 6,
                    "max_groups": 2, "rate_lines_per_s": 400,
                    "lead_s": 0.2, "check_per_post": 32,
                    "serve": {"tick_s": 0.002, "max_batch": 32,
                              "max_queue": 256, "default_deadline_s": None,
                              "cache_entries": 64}}},
}


@pytest.hookimpl(tryfirst=True)
def pytest_collection_modifyitems(items):
    cells = sys.modules.get("bench.tests.test_bench_cells")
    if cells is not None:
        for name, sizes in SIZES.items():
            cells.SIZES.setdefault(name, sizes)
