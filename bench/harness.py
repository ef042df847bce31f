"""One run of one cell: build the system under test from the cell's
configuration, warm it up, measure a window of its traffic, and check
what the window produced against the plain reference.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``bench/configs/<config>.json`` (sizes, KV pool, adapters, reference,
check limit), ``bench/traffic/<mix>.json`` (the generator's parameters)
and ``bench/metrics/<metric>.py`` (one reader per metric).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"                 # traces (listed in .gitignore)
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import driver, traffic  # noqa: E402


# ---------------------------------------------------------------------------
# finding the pieces by name
# ---------------------------------------------------------------------------
def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclass
class Cell:
    name: str
    config: dict                    # bench/configs/<config>.json
    mix: dict                       # bench/traffic/<traffic>.json
    chips: int
    end_to_end: List[dict]          # metric entries this cell reports
    per_layer: List[dict]
    bench: Path = BENCH             # where its files were found


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``spec`` with its files from ``root``."""
    w = find(spec["workloads"], name, "workload")
    c = find(spec["configs"], w["config"], "config")
    with open(root / c["file"]) as f:
        config = json.load(f)
    bench = root / spec["paths"][0]
    mix = traffic.load_mix(bench / "traffic" / f"{w['traffic']}.json")
    return Cell(name, config, mix, w["chips"],
                [m for m in spec["end_to_end"] if reports(m, name)],
                [m for m in spec["per_layer"] if reports(m, name)], bench)


def _module(kind: str, name: str, bench: Path):
    """``<bench>/<kind>/<name>.py``, else the benchmark's own."""
    path = bench / kind / f"{name}.py"
    if not path.exists():
        path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench: Path = BENCH) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    return _module("metrics", name, bench).read


def load_reference(name: str, bench: Path = BENCH):
    return _module("reference", name, bench)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
ACTIVATION = {"silu": "swiglu"}


def model_fields(conf: dict) -> dict:
    """The program's model settings from a configuration file's keys
    (the published names, at the values the cell runs)."""
    hf = conf
    d, H = hf["hidden_size"], hf["num_attention_heads"]
    return {
        "name": conf["name"],
        "num_layers": hf["num_hidden_layers"],
        "d_model": d,
        "num_heads": H,
        "num_kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim", d // H),
        "d_ff": hf["intermediate_size"],
        "vocab_size": hf["vocab_size"],
        "activation": ACTIVATION[hf["hidden_act"]],
        "tie_embeddings": hf["tie_word_embeddings"],
        "rope_theta": hf["rope_theta"],
        "sliding_window": hf.get("sliding_window") or 0,
        "norm_eps": hf.get("rms_norm_eps", hf.get("norm_epsilon", 1e-5)),
        "dtype": hf["torch_dtype"],
    }


def model_config(conf: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(arch_type="dense", **model_fields(conf))


class CompileLog:
    """Counts XLA backend compiles and their seconds (a persistent-cache
    hit is not a backend compile)."""

    def __init__(self):
        import jax
        self.n, self.secs, self.names = 0, 0.0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs
            self.names.append(str(kw.get("fun_name", "?")))


def enable_cache() -> str:
    """The program's persistent compile cache (a fixed path inside the
    checkout), caching small programs too."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@dataclass
class System:
    cfg: object                      # the program's ModelConfig
    model: dict                      # model_fields
    params: object
    adapters: List[object]           # per adapter, its weight tree
    names: List[str]
    inv: List[int]
    rank: int
    eng: object = None


def build_system(cell: Cell, seed: int, trace: bool,
                 engine: bool = True) -> System:
    """Seeded weights and adapters, and an engine serving them."""
    import jax
    from repro.core.alora import init_adapter_weights
    from repro.models import init_params

    from bench import weights
    conf = cell.config
    cfg = model_config(conf)
    serving = conf["serving"]
    rank, n_ad = serving["adapter_rank"], serving["adapters"]
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    params = weights.make_params(shapes, seed, conf["published"][
        "num_hidden_layers"], conf.get("init_std", 0.02))
    ashapes = jax.eval_shape(lambda k: init_adapter_weights(k, cfg, rank),
                             jax.random.key(0))
    ads = [weights.make_adapter(ashapes, seed, i, cfg.d_model, rank,
                                serving["adapter_gain"])
           for i in range(n_ad)]
    jax.block_until_ready((params, ads))
    sysm = System(cfg, model_fields(conf), params, ads,
                  [f"intrinsic{i}" for i in range(n_ad)],
                  list(serving["invocation_tokens"]), rank)
    if engine:
        sysm.eng = new_engine(cell, sysm, trace)
    return sysm


def new_engine(cell: Cell, sysm: System, trace: bool):
    """The engine as the program configures it, with the cell's KV pool
    and the benchmark's adapters registered."""
    from repro.core.alora import AdapterSpec
    from repro.serving import Engine, EngineConfig
    specs = [AdapterSpec(n, rank=sysm.rank,
                         invocation_tokens=tuple(sysm.inv))
             for n in sysm.names]
    ecfg = EngineConfig(num_blocks=cell.config["serving"]["num_blocks"],
                        trace=trace)
    return Engine(sysm.cfg, sysm.params,
                  adapters=list(zip(specs, sysm.adapters)), engine_cfg=ecfg)


# ---------------------------------------------------------------------------
# warm-up: every step program the traffic can reach, every prompt length
# ---------------------------------------------------------------------------
def pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def pow2_range(lo: int, hi: int) -> List[int]:
    out, v = [], pow2(lo)
    while v <= pow2(hi):
        out.append(v)
        v *= 2
    return out


def step_keys(eng, ctx_lo: int, ctx_hi: int, n_adapters: int) -> List[tuple]:
    """Every (tokens, requests, block-table, active-adapter) bucket a
    mixed step can take under the engine's own limits, for requests whose
    block tables span ``ctx_lo`` to ``ctx_hi`` tokens."""
    e, bs = eng.ecfg, eng.ecfg.block_size
    chunk = eng.runner.rcfg.chunk_tokens
    blocks = pow2_range(-(-ctx_lo // bs), -(-ctx_hi // bs))
    keys = []
    for T in pow2_range(1, e.max_batched_tokens):
        for R in pow2_range(1, e.max_running):
            # R rows need >= R tokens; fewer rows than the bucket's
            # lower edge cannot fill T tokens (one chunk per prefill row)
            r_lo = R // 2 + 1 if R > 1 else 1
            if r_lo > T or min(R, e.max_running) * chunk < T // 2 + 1:
                continue
            for A in pow2_range(1, max(1, min(R, n_adapters))):
                for nb in blocks:
                    keys.append((T, R, nb, A))
    return keys


def synthetic_batch(eng, T: int, R: int, nb: int, A: int):
    """A mixed batch of exactly these bucket sizes whose every write goes
    to the runner's reserved dump block and dump slot."""
    from repro.serving.runner import MixedBatch
    rc = eng.runner.rcfg
    dump_b, dump_s = rc.num_blocks - 1, rc.max_running - 1
    z = np.zeros
    return MixedBatch(
        tok_ids=z(T, np.int32),
        embeds=z((T, eng.cfg.d_model), np.float32),
        use_embeds=z(T, bool), from_buf=z(T, bool),
        positions=z(T, np.int32), adapter_idx=z(T, np.int32),
        req_rows=np.minimum(np.arange(T), R - 1).astype(np.int32),
        row_cols=z(T, np.int32),
        write_bids=np.full(T, dump_b, np.int32), write_offs=z(T, np.int32),
        block_tables=[[dump_b] * nb for _ in range(R)],
        out_rows=np.arange(R, dtype=np.int32),
        run_slots=np.full(R, dump_s, np.int32),
        snap_rows=z(0, np.int32),
        active_slots=np.arange(1, A + 1, dtype=np.int32))


def warm_steps(eng, keys: List[tuple], threads: int = 6) -> None:
    """Compile (or load from the persistent cache) every key's program,
    in parallel, then run each once through the engine's runner."""
    import jax
    runner = eng.runner
    # the compiler recurses deeply on a whole unrolled model; a worker
    # thread's default stack overflowed on a 30-layer step
    threading.stack_size(512 << 20)
    with ThreadPoolExecutor(threads) as pool:
        futs = [pool.submit(runner.lower_mixed(synthetic_batch(eng, *k))
                            .compile) for k in keys]
        for f in futs:
            f.result()
    for k in keys:
        h = runner.submit_batch(synthetic_batch(eng, *k))
        runner.fetch_sampled(h)
    jax.block_until_ready(runner.k_pool)


def warm_adapters(eng) -> None:
    """Install every adapter in a slot once (the install compiles a
    program per slot); with a slot for each adapter none is evicted."""
    pool = eng.adapter_pool
    for name in pool.registered:
        uid = pool.uid_of(name)
        pool.acquire(uid)
        pool.release(uid)


def warm_prompt_lengths(eng, lengths) -> None:
    """Admission turns a prompt into embeddings with a program per
    length: make each once."""
    for n in lengths:
        eng.runner.build_input_embeds([traffic.TOKEN_LO] * n, None)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclass
class Run:
    """What a metric reader sees."""
    window: tuple                    # (t0, t1) on the client's clock
    end: float                       # when the drain stopped
    setup_s: float
    recs: list                       # measured requests
    turns: list                      # measured turns
    all_recs: list
    steps: list
    counters: Dict[str, float]       # the obs counters' change over the window
    ledger: list                     # obs ledger rows of measured requests
    model: dict
    rank: int
    trace: object = None             # trace.Reduced of a traced run
    profile_steps: list = field(default_factory=list)   # the traced steps
    peak_flops: float = 0.0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, peaks: Optional[dict] = None,
             warm: bool = True, control: bool = False,
             system: Optional[System] = None, do_check: bool = True,
             log=lambda s: print(s, file=sys.stderr, flush=True),
             fault: Optional[Callable] = None) -> dict:
    """One run of one cell; returns the result object (without device).

    ``system`` reuses weights already made (a fresh engine serves them);
    ``fault`` (tests only) is called with the engine before the window,
    to break the timed path underneath."""
    import jax
    mix_p = cell.mix
    compiles = CompileLog()
    if system is None:
        sysm = build_system(cell, seed, trace)
    else:
        sysm = system
        sysm.eng = new_engine(cell, sysm, trace)
    eng = sysm.eng
    log(f"built {cell.config['name']}: weights and {len(sysm.adapters)} "
        f"adapters in {time.perf_counter() - t_process:.1f} s since start")
    mix = traffic.Mix.build(mix_p, seed, len(sysm.inv))
    warmup_s, drain_cap = mix_p["warmup_s"], mix_p["drain_cap_s"]
    sessions = mix.planned(warmup_s + seconds, n_closed=4096)
    lo, hi = traffic.context_bounds(sessions, len(sysm.inv))
    client = driver.Client(eng, mix, sysm.names, sysm.inv,
                           sysm.cfg.vocab_size, annotate=trace)
    if warm:
        t = time.perf_counter()
        keys = step_keys(eng, lo, hi, mix_p.get("adapters", 0))
        warm_steps(eng, keys)
        log(f"warm-up: {len(keys)} step programs (contexts {lo}-{hi} "
            f"tokens) in {time.perf_counter() - t:.1f} s; {compiles.n} "
            f"backend compiles so far")
        lens = traffic.length_support(mix)
        t = time.perf_counter()
        warm_adapters(eng)
        warm_prompt_lengths(eng, lens)
        log(f"warm-up: adapters and {len(lens)} prompt lengths in "
            f"{time.perf_counter() - t:.1f} s; {compiles.n} backend "
            f"compiles so far")
    if fault is not None:
        fault(eng)
    t_start = time.perf_counter()
    client.start(t_start, sessions)
    trace_s = mix_p.get("trace_s", 4.0) if trace else 0.0
    t0 = t_start + warmup_s
    t1 = t0 + seconds
    client.window, client.stop_at = (t0, t1), t1 + trace_s
    client.run(until=t0)
    setup_s = time.perf_counter() - t_process
    c0, n0 = dict(eng.tracer.counters), compiles.n
    client.run(until=t1)
    c1 = dict(eng.tracer.counters)
    profile = None
    if trace:
        # a few steady seconds right after the window, the traffic still
        # on: stopping the profiler stalls the loop for seconds, which
        # only the drain then sees
        shutil.rmtree(OUT / "trace", ignore_errors=True)
        OUT.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host: the annotations only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(OUT / "trace"), profiler_options=opts)
        p0 = time.perf_counter()
        client.run(until=p0 + trace_s)
        jax.profiler.stop_trace()
        profile = (p0, time.perf_counter())
    client.run(until=t1 + drain_cap, measured_only=True)
    end = time.perf_counter()
    in_window = compiles.n - n0
    log(f"window {seconds:.0f} s from {setup_s:.1f} s after start; drained "
        f"{end - t1:.1f} s; {in_window} backend compiles in the window "
        f"and drain {compiles.names[n0:]}")
    log(driver.lateness_line(client.lateness))
    recs, turns = client.measured(), client.measured_turns()
    ids = {r.req.req_id for r in recs}
    ledger = [row for row in eng.tracer.ledger if row[0] in ids]
    counters = {k: c1.get(k, 0.0) - c0.get(k, 0.0) for k in c1}
    run = Run((t0, t1), end, setup_s, recs, turns, client.recs,
              [s for s in client.steps if t0 <= s.t0 < t1], counters,
              ledger, sysm.model, sysm.rank,
              profile_steps=[s for s in client.steps if profile
                             and profile[0] <= s.t0 < profile[1]])
    failed = sum(1 for r in recs if r.done is None)
    out = {"attempted": len(recs), "failed": failed}
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    out["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    # the check's sample (from every request of the turns due in the
    # window, evaluations submitted after it too), then free the
    # program's state
    sample = choose_sample([r for t in turns for r in t.recs], seed,
                           cell.mix.get("check_tokens", 384))
    served = [(list(r.req.prompt), list(r.req.output_tokens),
               None if r.adapter is None else sysm.names.index(r.adapter))
              for r in sample]
    del client, eng
    sysm.eng = None
    gc.collect()
    if trace:
        from bench import trace as trace_mod
        path = trace_mod.latest_xplane(str(OUT / "trace"))
        run.trace = None if path is None else trace_mod.reduce_file(path)
    if peaks is not None:
        run.peak_flops = peaks["bf16_flops_per_s"]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"], cell.bench)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["metrics"] = metrics
    if trace and run.trace is not None:
        out["busy_s"] = run.trace.busy_s
        out["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           run.trace.top_ops],
                            "idle_gaps": [list(x) for x in
                                          run.trace.idle_gaps]}
    out["run"] = run
    if not do_check:
        return out
    t = time.perf_counter()
    out["check"] = check(sysm, served, cell.config, control, cell.bench)
    log(f"reference check of {len(served)} requests "
        f"({sum(len(o) for _, o, _ in served)} served tokens) in "
        f"{time.perf_counter() - t:.1f} s")
    return out


def choose_sample(recs, seed: int, target_tokens: int) -> list:
    """Finished requests drawn from the seed: the longest first,
    then one of each kind (base, evaluation, direct) the window finished
    that is not in yet, then others until ``target_tokens`` served
    tokens."""
    done = [r for r in recs if r.done is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + r.max_new)
    out = [longest]
    rest = [done[i] for i in traffic.rng_for(seed, 9).permutation(len(done))
            if done[i] is not longest]
    for r in rest:
        if r.kind not in {o.kind for o in out}:
            out.append(r)
    taken = {id(r) for r in out}
    n = sum(r.max_new for r in out)
    for r in rest:
        if n >= target_tokens:
            break
        if id(r) not in taken:
            out.append(r)
            n += r.max_new
    return out


def check(sysm: System, served, conf: dict, control: bool,
          cell_bench: Path = BENCH) -> dict:
    """Worst gap of a served token below the reference's best, in
    standard deviations of the reference row.

    With ``control``, also the worst gap of the token each stand-in puts
    first at the same positions: the control (the reference with its
    weights rounded to fp8) over every request, and two faults of the
    adapter path over the adapter requests: the adapters dropped, and
    their slots swapped (adapter ``i`` serves as ``n - 1 - i``)."""
    ref = load_reference(conf["reference"], cell_bench)
    n_ad = len(sysm.adapters)
    worst = 0.0
    stand_in = {"control_gap_std": 0.0, "adapters_dropped_gap_std": 0.0,
                "slots_swapped_gap_std": 0.0}

    def logits(ad, prompt, output, quant=None):
        return ref.sequence_logits(sysm.params, ad, sysm.model, prompt,
                                   output, sysm.inv, quant=quant)

    for prompt, output, a in served:
        ad = None if a is None else sysm.adapters[a]
        lg = logits(ad, prompt, output)
        worst = max(worst, float(ref.gaps(lg, output).max()))
        if not control:
            continue
        others = {"control_gap_std": logits(ad, prompt, output, "fp8")}
        if a is not None:
            others["adapters_dropped_gap_std"] = logits(None, prompt, output)
            others["slots_swapped_gap_std"] = logits(
                sysm.adapters[n_ad - 1 - a], prompt, output)
        for k, lq in others.items():
            stand_in[k] = max(stand_in[k],
                              float(ref.gaps(lg, lq.argmax(-1)).max()))
    limit = conf["check"]["max_gap_std"]
    res = {"worst_gap_std": {"value": worst, "limit": limit},
           "requests": {"value": len(served), "limit": 1},
           "tokens": {"value": sum(len(o) for _, o, _ in served),
                      "limit": 1}}
    if control:
        res.update({k: {"value": v, "limit": limit}
                    for k, v in stand_in.items()})
    return res


def is_correct(chk: dict) -> bool:
    return (chk["worst_gap_std"]["value"] <= chk["worst_gap_std"]["limit"]
            and chk["requests"]["value"] >= chk["requests"]["limit"])

