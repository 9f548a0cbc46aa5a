"""The paper's analytic tables, one section each.

  PYTHONPATH=src python -m paper.run

Prints the design-space exploration (Table 1 / Figs 6-8), the
system-level Amdahl finding (section 8), the tiling fit (Fig 7b) with the
scratchpad sweep, and the dry run's roofline table. Every number here
comes from the cycle model (``repro.core.dse`` / ``repro.core.isa``) or
from compile-time cost analysis: none is a timing. Speed is measured only
on the chip, by ``bench/run.py``, and recorded in PERF.md and
PERF_LEDGER.jsonl.
"""

from __future__ import annotations

from paper import bench_dse, bench_roofline, bench_system_amdahl, bench_tiling

SECTIONS = (
    ("DSE (Table 1 / Figs 6-8)", bench_dse.main),
    ("System Amdahl (section 8 finding)", bench_system_amdahl.main),
    ("Tiling fit (Fig 7b) + scratchpad sweep", bench_tiling.main),
    ("Roofline table (dry-run artifacts)", bench_roofline.main),
)


def main() -> None:
    for title, fn in SECTIONS:
        print(f"\n===== {title} =====")
        fn()


if __name__ == "__main__":
    main()
