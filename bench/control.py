"""Readings that a serving cell's check limit is set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: the cell's set-up, one window at the
cell's own load, then the reference over the same sample of finished
requests a run checks.  Prints per seed the program's widest gap (a sound
run's reading) and the fp8 control's widest gap over the same prompts and
tokens (a reading the check has to fail).  The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import registry, run as bench_run, serve  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    dev = bench_run.device_info(int(cell.entry["chips"]), True)
    bench_run.use_compile_cache()
    for seed in args.seeds:
        t = time.perf_counter()
        rec = serve.window(cell, seed=seed, seconds=args.seconds,
                           trace=False, t_start=t, profile_dir=None)
        gc.collect()
        t_ref = time.perf_counter()
        checks = serve.check(rec, seed=seed, limits=cell.workload["check"],
                             control=True)
        print(json.dumps({
            "seed": seed, "program_gap": checks["served_gap_max"][0],
            "control_gap": checks["control_gap_max"][0],
            "served_tokens": checks["served_tokens"][0],
            "short_outputs": checks["short_outputs"][0],
            "check_s": time.perf_counter() - t_ref,
            "device": f"{dev['platform']} {dev['kind']} x{dev['count']}"}),
            flush=True)


if __name__ == "__main__":
    main()
