#!/usr/bin/env python3
"""Record the small profiler trace ``test_bench_phases.py`` reads.

    python3 bench/tests/record_phases.py [OUT_DIR]

Runs the fixture cell (``bench/tests/data/fixture``: a 2-layer model,
short turns) for a few traced seconds on the chip, writes the trace to
``OUT_DIR/trace_phases.xplane.pb`` (default ``bench/.out``) with the
host planes cut to the benchmark's annotations and the engine's phases
(``trim``), and prints its size and what ``bench/phases.py`` reduces it
to.  Exits 1 with no trace where JAX finds no TPU.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

T = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness, phases, trace  # noqa: E402

KEEP = (trace.ANNOTATION, phases.PHASE)


def trim(src, dst, span_s: float = 0.25) -> None:
    """Keep ``span_s`` seconds from the first ``bench.*`` annotation:
    there, every device event, and of the host planes only the
    benchmark's annotations and the engine's phases; drop the metadata
    nothing refers to."""
    xplane_pb2 = phases.xplane_module()
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())

    def t_ns(line, e):
        return line.timestamp_ns + e.offset_ps // 1000

    def name(plane, e):
        return plane.event_metadata[e.metadata_id].name

    w0 = min(t_ns(ln, e) for p in space.planes if p.name.startswith("/host")
             for ln in p.lines for e in ln.events
             if name(p, e).startswith(trace.ANNOTATION))
    w1 = w0 + int(span_s * 1e9)
    for plane in space.planes:
        host = plane.name.startswith("/host")
        for line in plane.lines:
            keep = [e for e in line.events if w0 <= t_ns(line, e) < w1
                    and (not host or name(plane, e).startswith(KEEP))]
            del line.events[:]
            line.events.extend(keep)
        kept = [ln for ln in plane.lines if ln.events]
        del plane.lines[:]
        plane.lines.extend(kept)
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]
    Path(dst).write_bytes(space.SerializeToString())


def main() -> int:
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_phases: needs a TPU", file=sys.stderr)
        return 1
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else harness.OUT
    root = HERE / "data" / "fixture"
    spec = harness.load_spec(root)
    cell = harness.load_cell(spec, "tiny.turns", root)
    cell.mix["trace_s"] = 1.0
    harness.run_cell(cell, 5, 3.0, True, t_process=T)
    path = trace.latest_xplane(str(harness.OUT / "trace"))
    out.mkdir(parents=True, exist_ok=True)
    dst = out / "trace_phases.xplane.pb"
    trim(path, dst)
    print(f"{dst}: {dst.stat().st_size} bytes")
    print(phases.reduce_file(str(dst)))
    print(trace.reduce_file(str(dst)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
