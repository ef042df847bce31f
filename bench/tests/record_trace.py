#!/usr/bin/env python3
"""Record the small profiler trace ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py [OUT_DIR]

Runs the fixture cell (``bench/tests/data/fixture``: a 2-layer model,
short turns) for a few traced seconds on the chip, writes the trace to
``OUT_DIR/trace_tiny.xplane.pb`` (default ``bench/.out``) with the host
planes cut to the benchmark's own annotations (``trim``), and prints the
planes and lines it holds and what ``bench/trace.py`` reduces it to.
Exits 1 with no trace where JAX finds no TPU.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

T = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness, trace  # noqa: E402


def trim(src, dst, span_s: float = 0.25) -> None:
    """Keep ``span_s`` seconds from the first ``bench.*`` annotation:
    there, every device event, and of the host planes only the
    benchmark's annotations; drop the metadata nothing refers to."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())

    def t_ns(line, e):
        return line.timestamp_ns + e.offset_ps // 1000

    def is_ours(plane, e):
        return plane.event_metadata[e.metadata_id].name.startswith(
            trace.ANNOTATION)

    w0 = min(t_ns(ln, e) for p in space.planes if p.name.startswith("/host")
             for ln in p.lines for e in ln.events if is_ours(p, e))
    w1 = w0 + int(span_s * 1e9)
    for plane in space.planes:
        host = plane.name.startswith("/host")
        for line in plane.lines:
            keep = [e for e in line.events if w0 <= t_ns(line, e) < w1
                    and (not host or is_ours(plane, e))]
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
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else harness.OUT
    root = HERE / "data" / "fixture"
    spec = harness.load_spec(root)
    cell = harness.load_cell(spec, "tiny.turns", root)
    cell.mix["trace_s"] = 1.0
    harness.run_cell(cell, 5, 3.0, True, t_process=T)
    path = trace.latest_xplane(str(harness.OUT / "trace"))
    out.mkdir(parents=True, exist_ok=True)
    dst = out / "trace_tiny.xplane.pb"
    trim(path, dst)
    from jax.profiler import ProfileData
    for p in ProfileData.from_file(str(dst)).planes:
        print("plane", p.name, [(ln.name, sum(1 for _ in ln.events))
                                for ln in p.lines])
    print(trace.reduce_file(str(dst)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
