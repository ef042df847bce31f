#!/usr/bin/env python3
"""Find a cell's knee, and read the check's numbers over many seeds, in
one process (one set-up, programs compiled once).  Not part of a
benchmark run.

    python3 bench/sweep.py --workload <cell> --seconds <s> \
        --rates 0.1,0.2,0.3            # knee: the mix at each rate
    python3 bench/sweep.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control 1]    # check readings, seed by seed

Each line of standard output is one JSON object: the rate or seed, what
was attempted and failed, the cell's end-to-end metrics, how many turns
finished per second against how many were due, and the check's numbers
(with ``--control 1`` also those of the fp8 control and of the two
adapter faults, adapters dropped and slots swapped).  Exits 1 with no
result where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def summary(out: dict, seconds: float) -> dict:
    run = out.pop("run")
    due = len(run.turns)
    done_in_time = sum(1 for t in run.turns if t.done is not None
                       and t.done < run.window[1] + 5.0)
    return {"attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "turns_due_per_s": due / seconds,
            "turns_done_within_5s_of_close": done_in_time,
            "drain_s": run.end - run.window[1],
            "check": {k: v["value"] for k, v in out.get("check", {}).items()},
            "memory_peak_bytes": out["memory_peak_bytes"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    harness.enable_cache()
    spec = harness.load_spec()
    cell = harness.load_cell(spec, args.workload)
    base_mix = copy.deepcopy(cell.mix)
    first = True
    sysm = None
    for rate in [float(r) for r in args.rates.split(",") if r]:
        if sysm is None:
            sysm = harness.build_system(cell, args.seed, False,
                                          engine=False)
        cell.mix = copy.deepcopy(base_mix)
        cell.mix["arrival"]["rate_per_s"] = rate
        out = harness.run_cell(cell, args.seed, args.seconds, False,
                               t_process=time.perf_counter(), warm=first,
                               system=sysm, do_check=False)
        first = False
        print(json.dumps({"rate_per_s": rate, **summary(out, args.seconds)}),
              flush=True)
    cell.mix = base_mix
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        sysm = None
        gc.collect()
        sysm = harness.build_system(cell, seed, False, engine=False)
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_process=time.perf_counter(), warm=first,
                               system=sysm, control=bool(args.control))
        first = False
        print(json.dumps({"seed": seed, **summary(out, args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
