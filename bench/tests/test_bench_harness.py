"""The harness: it finds a cell's pieces by name, refuses to run without a
TPU, and on the CPU drives a whole run of a small cell (the fixture under
``data/fixture``, granite-3.2-8b's reduced widths in bf16) through the
engine: sound, the check passes; with the timed path broken underneath,
it fails; and the fp8 control and the adapter faults fail it too."""
from __future__ import annotations

import dataclasses
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURE = BENCH / "tests" / "data" / "fixture"
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def fixture_cell():
    spec = harness.load_spec(FIXTURE)
    return harness.load_cell(spec, "tiny.turns", FIXTURE)


def test_finds_config_mix_and_metric_by_name():
    cell = fixture_cell()
    assert cell.config["name"] == "tiny"
    assert cell.mix["arrival"]["kind"] == "poisson"
    assert cell.bench == FIXTURE / "bench"
    assert [m["name"] for m in cell.per_layer] == ["fixture_steps",
                                                   "hit_share.adapter"]
    # the fixture's own reader, and one of the benchmark's
    run = dataclasses.make_dataclass("R", ["steps"])(steps=[1, 2, 3])
    assert harness.load_reader("fixture_steps", cell.bench)(run) == 3
    assert callable(harness.load_reader("itl_p95_ms", cell.bench))
    with pytest.raises(KeyError):
        harness.load_cell(harness.load_spec(FIXTURE), "no.such", FIXTURE)


def test_every_cell_of_the_benchmark_loads():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.load_cell(spec, w["name"])
        assert harness.model_config(cell.config).num_layers \
            == cell.config["num_hidden_layers"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_reader(m["name"], cell.bench))


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(["--workload", "granite8b-s0.pipeline", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout == ""


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run(["--workload", "granite8b-s0.pipeline", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


# ---------------------------------------------------------------------------
# whole runs on the CPU
# ---------------------------------------------------------------------------
def run_fixture(seed=3, fault=None, control=False):
    t = time.perf_counter()
    return harness.run_cell(fixture_cell(), seed, 1.5, False, t_process=t,
                            warm=False, control=control, fault=fault,
                            log=lambda s: None)


def test_a_sound_run_is_correct_and_its_control_is_not():
    out = run_fixture(control=True)
    chk = out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert chk["tokens"]["value"] >= 32
    assert harness.is_correct(chk)
    # fp8 weights in place of the model, and the adapters dropped or
    # their slots swapped: their first choices sit further below the
    # reference's best than the limit allows
    for k in ("control_gap_std", "adapters_dropped_gap_std",
              "slots_swapped_gap_std"):
        assert chk[k]["value"] > chk[k]["limit"], k


def alter_tokens(eng):
    """A token altered where it is produced: every sampled id + 1."""
    fetch = eng.runner.fetch_sampled
    V = eng.cfg.vocab_size
    eng.runner.fetch_sampled = lambda h: (fetch(h) + 1) % V


def drop_kv_writes(eng):
    """A step that returns its state unchanged: no K/V reaches the pool
    (every write goes to the reserved dump block)."""
    r = eng.runner
    assemble, dump = r._assemble_mixed, r.rcfg.num_blocks - 1
    r._assemble_mixed = lambda mb: assemble(dataclasses.replace(
        mb, write_bids=np.full_like(mb.write_bids, dump)))


def drop_adapters(eng):
    """The adapters dropped: every token's adapter index is the zero
    adapter's slot."""
    r = eng.runner
    assemble = r._assemble_mixed
    r._assemble_mixed = lambda mb: assemble(dataclasses.replace(
        mb, adapter_idx=np.zeros_like(mb.adapter_idx)))


def swap_adapter_slots(eng):
    """The adapter slots swapped: slot ``s`` serves as slot
    ``n + 1 - s`` of the pool's ``n`` (slot 0, the zero adapter, stays)."""
    r = eng.runner
    assemble, n = r._assemble_mixed, eng.adapter_pool.num_slots

    def swap(a):
        a = np.asarray(a)
        return np.where(a > 0, n + 1 - a, a).astype(a.dtype)

    r._assemble_mixed = lambda mb: assemble(dataclasses.replace(
        mb, adapter_idx=swap(mb.adapter_idx),
        active_slots=swap(mb.active_slots)))


@pytest.mark.parametrize("fault", [alter_tokens, drop_kv_writes,
                                   drop_adapters, swap_adapter_slots])
def test_a_broken_timed_path_is_not_correct(fault):
    out = run_fixture(fault=fault)
    assert out["check"]["tokens"]["value"] > 0
    assert not harness.is_correct(out["check"])


def test_the_sample_holds_every_kind_of_request():
    """The check's sample has the longest request and one of each kind the
    window finished, drawn from the seed, whatever the token target."""
    from bench.driver import Rec
    recs = [Rec(kind, None, 0.0, True, None, 100 + i, n, done=1.0)
            for i, (kind, n) in enumerate(
                [("base", 300)] * 6 + [("eval", 8)] * 6
                + [("direct", 20)] * 6)]
    recs.append(Rec("eval", None, 0.0, True, None, 1, 4))   # unfinished
    for seed in (1, 2, 2**31 + 5):
        out = harness.choose_sample(recs, seed, 1)
        assert out[0] is max(recs[:18], key=lambda r: r.prompt_len
                             + r.max_new)
        assert sorted(r.kind for r in out) == ["base", "direct", "eval"]
        assert all(r.done is not None for r in out)
