#!/usr/bin/env python3
"""Record the small trace `test_trace_reduce.py` checks the reduction on:
five requests of a cell traced on the chip, the `.xplane.pb` copied to the
path given. Run on the chip by hand:

    python3 benchmark/tests/record_trace.py <cell> <out.xplane.pb>
"""

import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main(cell: str, out: str) -> None:
    loaded = run.load_cell(cell)
    device = run.require_device(int(loaded["cell"]["chips"]))
    from opensearch_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    loaded["traffic"]["trace"] = {"min_requests": 5, "min_seconds": 0}
    out_dir = os.path.join(run.ROOT, "benchmark_out", "recorded")
    result = run.run_cell(loaded, 7, 1.0, True, device, run.CompileMeter(),
                          out_dir)
    print(result)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(run.find_xplane(os.path.join(out_dir, "trace")), out)


if __name__ == "__main__":
    main(*sys.argv[1:3])
