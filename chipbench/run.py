#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell.  It finds the cell's chips (and exits non-zero
with no result when JAX finds none), sets up, warms every shape the
cell's traffic uses, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object as
the last line of standard output.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.

Everything that belongs to one configuration, traffic mix, loop shape or
metric is a file of its own under this directory, found by the names in
``workloads/<cell>.json``.

Options for building the benchmark, never passed in a measured run:
``--rehearse`` runs on the CPU at the cell's ``rehearse`` size with
Pallas in interpret mode (its numbers are no device metric);
``--cache-dir`` moves the persistent compilation cache; ``--mode`` picks
a driver's tool mode (``readings``: the numbers ``correct`` compares, of
the program and of its control, on ``--count`` seeds from ``--seed`` on).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
# one process with few threads: the host's BLAS pools add run-to-run spread
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--cache-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--mode", default="run", help=argparse.SUPPRESS)
    ap.add_argument("--count", type=int, default=1, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from harness import (Cell, CompileCounter, Run, emit,
                         load_module, print_checks, require_devices, use_cache)

    cell = Cell.load(args.workload)
    devices = require_devices(cell.spec["chips"], rehearse=args.rehearse)
    use_cache(args.cache_dir)
    compiles = CompileCounter().install()
    driver = load_module("drivers", cell.driver)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), device=devices[0])
    run.notes["t_start"] = T_START
    run.notes["compiles"] = compiles
    run.notes["rehearse"] = args.rehearse
    if args.mode != "run":
        return driver.tool(run, args.mode, args.count)
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        trace_dir = Path(tdir) if args.trace else None
        driver.run(run, trace_dir)
        if args.trace:
            from trace_reduce import reduce_dir

            run.reduced = reduce_dir(trace_dir, cell.spec.get("trace_names", {}))
    if args.trace:
        names = [m for m in cell.spec["per_layer"]]
        readers = {m: load_module("metrics", m) for m in names}
    else:
        names = list(cell.spec["end_to_end"])
        readers = {}
    line = emit(run, names, readers)
    print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
