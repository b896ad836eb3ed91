"""Find a serving cell's knee: the same cell at several fixed rates.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1 1.5 2 2.5 3

One process sets the cell up once and measures one window per rate (the
engine stays warm between windows).  Each line gives the rate, how many
requests fell due and finished, the tails, and the backlog left when the
window closed: a rate the system sustains leaves a backlog of a few
requests, one above it leaves a queue that grew all window.  The benchmark
itself never searches for a rate; the knee found here is written into the
cell's file as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import registry, run as bench_run, serve, tails  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    dev = bench_run.device_info(int(cell.entry["chips"]), True)
    bench_run.use_compile_cache()
    st = serve.setup(cell, seed=args.seed, trace=False)
    for rate in args.rates:
        rec = serve.measure(st, {**cell.traffic, "rate": rate},
                            seed=args.seed, seconds=args.seconds)
        t0, t1 = rec["window"]
        due = [r for r in rec["records"] if r.due < t1]
        print(json.dumps({
            "rate": rate, "due": len(due),
            "finished": sum(r.state == "FINISHED" for r in due),
            "waiting_at_end": rec["waiting_at_end"],
            "ttft_p50_s": tails.percentile(tails.ttft(due, t0, t1), 50),
            "ttft_p90_s": tails.percentile(tails.ttft(due, t0, t1), 90),
            "itl_p95_ms": 1e3 * (tails.percentile(
                tails.itl(due, t0, t1), 95) or 0.0),
            "tok_s": tails.tokens_served(due, t0, t1) / (t1 - t0),
            "compiles_in_window": rec["compiles"],
            "device": f"{dev['platform']} {dev['kind']} x{dev['count']}"}),
            flush=True)


if __name__ == "__main__":
    main()
