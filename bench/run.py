"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (see ``bench/registry.py``),
makes weights and traffic from ``--seed``, warms up, measures for
``--seconds`` and checks what the measured window produced against the
plain reference.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` profiles the window and reports its per-layer metrics.  The
last line of stdout is one JSON object; the compared numbers and their
limits are the last lines of stderr.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402

from bench import peaks as P  # noqa: E402
from bench import registry, trace_reduce  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
PROFILE_DIR = ROOT / ".bench_profile"
DRIVERS = {"open_loop": "bench.serve", "backlog": "bench.serve"}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    keeping every program so a warm run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found {dev.platform} ({dev.device_kind}), not a "
                     f"TPU; nothing was run")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def read_trace(profile_dir: Path) -> dict:
    files = sorted(profile_dir.glob("**/*.xplane.pb"))
    if not files:
        return {}
    ops, spans = trace_reduce.read_xplane(str(files[-1]))
    win = [(s, e) for name, s, e in spans
           if name == trace_reduce.HOST_PREFIX + "window"]
    if not win:
        return {}
    return trace_reduce.reduce(ops, spans, win[-1])


def run(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
        compile_cache: bool = True, t_start: float = T_START) -> dict:
    """One run; returns the result line's object (and prints nothing).
    ``require_tpu`` and ``compile_cache`` are off only in CPU tests."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.load_cell(args.workload, root)
    device = device_info(int(cell.entry["chips"]), require_tpu)
    peak = P.peaks(device["kind"]) if require_tpu else \
        P.PEAKS.get(device["kind"])
    if compile_cache:
        use_compile_cache()
    driver = __import__(DRIVERS[cell.traffic["kind"]], fromlist=["run"])
    profile = None
    if args.trace:
        profile = Path(root) / PROFILE_DIR.name / args.workload
        shutil.rmtree(profile, ignore_errors=True)
    rec, checks, correct = driver.run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=t_start, profile_dir=str(profile) if profile else None)
    rec["peak"] = peak
    rec["trace"] = read_trace(profile) if profile else {}
    entries = cell.per_layer if args.trace else cell.end_to_end
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    out = {"correct": bool(correct), "attempted": rec["attempted"],
           "failed": rec["failed"],
           "metrics": registry.read_metrics(entries, rec, root),
           "device": device}
    if args.trace and rec["trace"]:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {k: rec["trace"][k]
                            for k in ("device_ops", "idle_gaps")}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["notes"] = {"setup_s": rec["setup_s"],
                    "compiles_in_window": rec["compiles"],
                    "compile_s_in_window": rec["compile_s"],
                    "generator_lag_s": rec["generator_lag_s"],
                    "waiting_at_end": rec["waiting_at_end"]}
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    dev = out["device"]
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    for k, v in out.pop("notes").items():
        print(f"{tag} {k}: {v}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"{tag} check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
