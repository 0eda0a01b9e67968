"""Readings for the limits of the correctness check, and the knee sweep.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--rates 0.5,1,2] [--control 1] [--faults 1]

Runs, in one process (set-up and warm-up once), one window per seed and
rate at the cell's own load, and prints one JSON line for each: the
window's end-to-end numbers and, unless ``--check 0``, the numbers that
decide ``correct`` (``run.check``) beside their limits and the verdict.
With ``--control 1`` it also reads the float8 control at the same
positions and passes it through the same verdict (``control_correct``,
which has to be false). With ``--faults 1`` the sampled requests take
turns between sound ones and each sampler fault of ``run.FAULTS``, and
each fault's readings pass through the verdict too (``<fault>_correct``).
The benchmark's own runs never run the control or plant a fault.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import loadgen, run, spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    base = loadgen.traffic_from_dict(cell.traffic)
    rates = [float(r) for r in args.rates.split(",") if r] or [base.rate]
    ses = run.Session(cell, base)
    for seed in [int(s) for s in args.seeds.split(",")]:
        for rate in rates:
            ses.traffic = loadgen.Traffic(**{**base.__dict__, "rate": rate})
            ses.load(seed)
            faults = tuple(run.FAULTS) if args.faults else ()
            sv = ses.serve(seed, args.seconds, False, faults)
            ttft, tpot = run.latency(sv.recs, sv.end)
            q = lambda v, p: (loadgen.quantile(v, p) * 1e3  # noqa: E731
                              if v else None)
            out = {"seed": seed, "rate": rate,
                   "attempted": len(sv.counted), "failed": sv.failed,
                   "output_tok_s": run.window_tokens(sv.steps, sv.w0, sv.w1)
                   / (sv.w1 - sv.w0),
                   "ttft_p50_ms": q(ttft, .5), "ttft_p90_ms": q(ttft, .9),
                   "tpot_p50_ms": q(tpot, .5), "tpot_p90_ms": q(tpot, .9),
                   "drain_s": sv.end - sv.w1,
                   "compiles_in_window": sv.compiles_in_window,
                   "memory_peak_bytes": (ses.dev.memory_stats() or {}).get(
                       "peak_bytes_in_use", 0)}
            ses.free()
            if args.check:
                t = time.perf_counter()
                tr = ses.cell.traffic
                prog = ses.check(sv, seed)
                out["program"] = run.limited(prog, tr)
                out["correct"] = run.verdict(out["program"])
                if args.control:
                    ctrl = ses.check(sv, seed, control=True,
                                     parts=("greedy",))
                    out["control_worst_gap"] = ctrl["worst_gap"]
                    out["control_correct"] = run.verdict(
                        run.limited({**prog, **ctrl}, tr))
                for f in faults:
                    got = ses.check(sv, seed, fault=f, parts=("sampled",))
                    out[f] = {k: got[k] for k in ("outside_nucleus",
                                                  "pit_dev", "sampled_tokens")}
                    out[f + "_correct"] = run.verdict(
                        run.limited({**prog, **out[f]}, tr))
                out["check_s"] = time.perf_counter() - t
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
