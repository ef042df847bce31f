#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix.  The run builds the serving engine on seeded weights, warms
every program the traffic can reach, plays the mix against the engine on
the wall clock for ``--seconds``, lets the requests due in that window
finish, and checks a sample of what they produced against the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics, from the engine's counters and a profiler
trace of a few steady seconds.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``check``, each compared number beside its limit;
the same numbers end standard error.  Exits 1 with no result when JAX
finds no TPU, or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def device_info(jax, n: int) -> dict:
    devs = jax.devices()[:n]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_peaks(kind: str) -> dict:
    with open(harness.BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell = harness.load_cell(spec, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu" \
            or len(jax.devices()) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s), JAX found "
              f"{jax.devices()}", file=sys.stderr)
        return 1
    dev = device_info(jax, cell.chips)
    peaks = load_peaks(dev["kind"])
    print(f"bench: {args.workload} seed {args.seed} on {dev}; compile "
          f"cache {harness.enable_cache()}", file=sys.stderr)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS, peaks=peaks)
    out.pop("run")
    dev["memory_peak_bytes"] = out.pop("memory_peak_bytes")
    if args.trace:
        dev["busy_s"] = out.pop("busy_s", 0.0)
        dev["window_s"] = out.pop("window_s", 0.0)
    chk = out.pop("check")
    result = {"correct": harness.is_correct(chk),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": out["metrics"], "device": dev}
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["check"] = chk
    for k, v in chk.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
