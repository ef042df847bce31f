"""Compiled-step HLO audit (Pass A of the invariant analyzer).

The serving engine's performance story rests on properties of ONE
compiled artifact: the mixed ragged step (``runner._mixed_impl``).  The
benchmarks measure those properties dynamically; this module verifies
them *statically*, on the post-optimization HLO of the exact lowering
production dispatches (``ModelRunner.lower_mixed`` lowers the same
argument tuple ``submit_batch`` executes).  For every config in
``repro.configs`` × mesh in {single-device, data=2/model=4} we check:

  A1  no host round-trips compiled into the step: no custom-call host
      callbacks (extend ``ALLOWED_CUSTOM_CALLS`` only with a reviewed
      reason), no infeed/outfeed.
  A2  host-bound payload is ids-only: every non-donated ROOT output is
      one of ``b_ssm``/``b_conv``/``sampled``; ``sampled`` is a 1-D s32
      of at most pow2(max_running) elements; no host-bound output has a
      vocab-sized dimension (a (R, vocab) logits output would silently
      multiply per-step D2H traffic by the vocab size).
  A3  pool donation: the K/V pools, the SSM live pools (when the arch
      has SSM layers) and ``tok_buf`` appear in ``input_output_alias``
      — and nothing else does.  Donation is what keeps the pools from
      doubling HBM residency every step.
  A4  collective fingerprint: per-(config, mesh) op counts and result
      bytes from ``parse_collectives`` must match the checked-in golden
      under ``analysis/goldens/`` — any drift (a new all-gather from a
      sharding regression, say) fails with a readable diff.
  A5  hygiene: no f32 ``convert`` of a bf16 param-sized (≥ d_model²
      elements) tensor; no dynamic-shape ops (bounded-dynamic ``[<=``,
      set-dimension-size, dynamic-reshape) — the step must stay fully
      static for the bucketed-shape recompile guarantees.

Async/sync equivalence: batches are captured from an engine running the
production default (async one-step-lookahead); a sync-flavored copy
(``from_buf=None``) must lower to the SAME module text — the two modes
are data, not program, so one compile covers both.  If a future change
ever makes them diverge, both get compiled and their collective
fingerprints must agree.

Import note: importing this module imports jax.  The CLI
(``python -m repro.analysis``) sets ``XLA_FLAGS`` for the 8-device host
platform BEFORE this import; do the same in any new entry point.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import all_configs, get_reduced
from repro.core.alora import AdapterSpec, init_adapter_weights
from repro.launch.hlo_analysis import parse_collectives
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.serving import Engine, EngineConfig
from repro.serving import runner as runner_mod
from repro.serving.runner import MixedBatch, next_pow2

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")
# mesh-name → (data, model) host-mesh axes; None = single device
MESHES: Dict[str, Optional[Tuple[int, int]]] = {"1x1": None, "2x4": (2, 4)}

# output tuple slots of _mixed_impl, in order; the ROOT tuple flattens
# these (None slots contribute no leaf, the scalar-0 SSM boundaries of
# attention-only archs contribute one each)
OUT_NAMES = ("k_pool", "v_pool", "live_ssm", "live_conv", "tok_buf",
             "b_ssm", "b_conv", "sampled")
# outputs allowed to stay host-fetchable (everything else must alias)
HOST_PAYLOAD = frozenset({"b_ssm", "b_conv", "sampled"})
# custom-call targets that are NOT host callbacks — any other custom
# call in the step is a finding until reviewed in here.
#   TopK: XLA's device-side top-k expansion (the MoE router's
#   jax.lax.top_k lowers to it on CPU); stays on-device, no host hop.
ALLOWED_CUSTOM_CALLS: Tuple[str, ...] = ("TopK",)
DYNAMIC_SHAPE_MARKERS = ("[<=", " set-dimension-size ",
                         " dynamic-reshape(", " dynamic-reshape ")

_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')
# `%x = f32[...] convert(bf16[...] %y)` — operand dtype may be inline or
# resolved through the def map when the printer omits operand shapes
_CONVERT_RE = re.compile(
    r"=\s*f32\[([0-9,]*)\]\S*\s+convert\(\s*"
    r"(?:(\w+)\[[0-9,]*\]\S*\s+)?%([\w.\-]+)\)")
_DEF_RE = re.compile(r"%([\w.\-]+)\s*=\s*(\w+)\[([0-9,]*)\]")
_ALIAS_ENTRY_RE = re.compile(r"\{\s*([0-9]+)[0-9,\s]*\}:\s*\((\d+)")


@dataclass
class AuditResult:
    arch: str
    mesh: str
    violations: List[str] = field(default_factory=list)
    fingerprint: Dict[str, Dict] = field(default_factory=dict)
    fingerprint_diff: str = ""
    donated: List[str] = field(default_factory=list)
    sync_async_identical: bool = True
    memory: Dict[str, float] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations and not self.fingerprint_diff

    def to_json(self) -> Dict:
        return {
            "kind": "analysis_audit", "arch": self.arch,
            "mesh": self.mesh, "ok": self.ok,
            "violations": list(self.violations),
            "fingerprint": self.fingerprint,
            "fingerprint_drift": bool(self.fingerprint_diff),
            "donated": list(self.donated),
            "sync_async_identical": self.sync_async_identical,
            "memory": dict(self.memory),
        }


# ---------------------------------------------------------------- text
def entry_body(hlo_text: str) -> str:
    """The ENTRY computation's body.  Inner computations (fusions,
    reducers) have their own ROOT lines — alias/payload checks must only
    ever look at the entry ROOT."""
    m = re.search(r"ENTRY [^{]+\{(.*?)\n\}", hlo_text, re.S)
    return m.group(1) if m else hlo_text


def check_host_callbacks(hlo_text: str) -> List[str]:
    out = []
    for tgt in sorted(set(_CUSTOM_CALL_RE.findall(hlo_text))):
        if tgt not in ALLOWED_CUSTOM_CALLS:
            out.append(f"host-callback: custom_call_target=\"{tgt}\" in "
                       "the compiled step (not in ALLOWED_CUSTOM_CALLS)")
    for marker in ("infeed(", "outfeed("):
        if marker in hlo_text:
            out.append(f"host-callback: {marker[:-1]} op in the compiled "
                       "step")
    return out


def check_dynamic_shapes(hlo_text: str) -> List[str]:
    return [f"dynamic-shape: marker '{m.strip()}' in the compiled step "
            "(bucketed shapes must stay fully static)"
            for m in DYNAMIC_SHAPE_MARKERS if m in hlo_text]


def check_bf16_upcasts(hlo_text: str, threshold_elems: int) -> List[str]:
    """f32 converts of bf16 tensors at/above param size (≥ d_model²
    elements) — a whole-matrix upcast doubles the bandwidth the bf16
    residency was supposed to save."""
    defs = {name: (dt, dims)
            for name, dt, dims in _DEF_RE.findall(hlo_text)}
    out = []
    for dims, op_dtype, op_name in _CONVERT_RE.findall(hlo_text):
        if op_dtype is None or op_dtype == "":
            op_dtype = defs.get(op_name, ("", ""))[0]
        if op_dtype != "bf16":
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n >= threshold_elems:
            out.append(f"bf16-upcast: f32[{dims}] convert of bf16 "
                       f"%{op_name} ({n} elems ≥ {threshold_elems}) — "
                       "param-sized tensors must stay bf16 in-step")
    return out


def parse_aliases(hlo_text: str) -> Dict[int, int]:
    """``input_output_alias`` header → {flat output index: param index}.
    The mixed step's ROOT is a flat tuple of arrays, so the alias
    ShapeIndex's leading element IS the flat output index."""
    i = hlo_text.find("input_output_alias={")
    if i < 0:
        return {}
    j, depth = i + len("input_output_alias={"), 1
    while j < len(hlo_text) and depth:
        depth += {"{": 1, "}": -1}.get(hlo_text[j], 0)
        j += 1
    body = hlo_text[i:j]
    return {int(o): int(p) for o, p in _ALIAS_ENTRY_RE.findall(body)}


# ----------------------------------------------------------- step args
def output_leaves(args: Tuple) -> List[Tuple[str, object]]:
    """(slot name, ShapeDtypeStruct) per flat ROOT output, in order."""
    fn = runner_mod._mixed_impl.__wrapped__
    outs = jax.eval_shape(partial(fn, args[0]), *args[1:])
    leaves: List[Tuple[str, object]] = []
    for name, slot in zip(OUT_NAMES, outs):
        for leaf in jax.tree_util.tree_leaves(slot):
            leaves.append((name, leaf))
    return leaves


def check_payload(leaves, aliases: Dict[int, int], cfg,
                  max_running: int) -> List[str]:
    out = []
    for idx, (name, leaf) in enumerate(leaves):
        if idx in aliases:
            continue
        if name not in HOST_PAYLOAD:
            out.append(f"payload: non-donated output #{idx} ({name}, "
                       f"{leaf.dtype}{list(leaf.shape)}) is not part of "
                       "the ids-only host payload")
        if cfg.vocab_size in leaf.shape:
            out.append(f"payload: host-bound output #{idx} ({name}) has "
                       f"a vocab-sized dim {list(leaf.shape)} — logits "
                       "must never leave the device")
        if name == "sampled":
            if str(leaf.dtype) != "int32" or len(leaf.shape) != 1 \
                    or leaf.shape[0] > next_pow2(max_running):
                out.append(f"payload: sampled is {leaf.dtype}"
                           f"{list(leaf.shape)}; expected 1-D int32 of "
                           f"≤ {next_pow2(max_running)} rows")
    return out


def check_donation(leaves, aliases: Dict[int, int],
                   has_ssm: bool) -> Tuple[List[str], List[str]]:
    """All pools aliased, nothing else.  Returns (violations, donated
    output names)."""
    expected = {"k_pool", "v_pool", "tok_buf"}
    if has_ssm:
        expected |= {"live_ssm", "live_conv"}
    out = []
    donated = sorted({leaves[i][0] for i in aliases if i < len(leaves)})
    by_name = {}
    for idx, (name, _) in enumerate(leaves):
        by_name.setdefault(name, []).append(idx)
    for name in sorted(expected):
        idxs = by_name.get(name, [])
        if not idxs:
            out.append(f"donation: expected pool output '{name}' absent "
                       "from the step's ROOT tuple")
        for idx in idxs:
            if idx not in aliases:
                out.append(f"donation: pool output #{idx} ({name}) is "
                           "not in input_output_alias — its HBM doubles "
                           "every step")
    for idx in sorted(aliases):
        name = leaves[idx][0] if idx < len(leaves) else "?"
        if name not in expected:
            out.append(f"donation: unexpected alias of output #{idx} "
                       f"({name}) — only the pools may donate")
    return out, donated


# -------------------------------------------------------- fingerprints
def golden_path(arch: str, mesh_name: str,
                golden_dir: str = GOLDEN_DIR) -> str:
    return os.path.join(golden_dir, f"{arch}__{mesh_name}.json")


def fingerprint_of(hlo_text: str) -> Dict[str, Dict]:
    stats = parse_collectives(hlo_text)
    return {"counts": {k: stats.counts[k] for k in sorted(stats.counts)},
            "result_bytes": {k: int(round(stats.by_kind[k]))
                             for k in sorted(stats.by_kind)}}


# the config knob most likely responsible when a collective kind
# drifts — turns a `--update-goldens` review from HLO archaeology into
# checking one setting
_DRIFT_KNOBS: Dict[str, str] = {
    "all-gather": "EngineConfig.data_shard_tokens / the mesh `data` "
                  "axis (token-axis sharding gathers)",
    "reduce-scatter": "the mesh `model` axis / StepShardings (TP "
                      "matmul partials)",
    "all-reduce": "the mesh `model` axis / StepShardings (TP matmul "
                  "partials)",
    "collective-permute": "StepShardings output layouts (resharding "
                          "between pinned layouts)",
    "all-to-all": "StepShardings output layouts / expert or head "
                  "re-partitioning",
}


def diff_fingerprint(arch: str, mesh_name: str, seen: Dict,
                     golden: Optional[Dict]) -> str:
    """Human-reviewable drift report, grouped per collective op: count
    and result-byte deltas side by side, plus the config knob most
    likely to have moved them."""
    if golden is None:
        return (f"{arch} [{mesh_name}]: no golden checked in at "
                f"{golden_path(arch, mesh_name)} — run "
                "`python -m repro.analysis --update-goldens`\n")
    if seen == golden:
        return ""
    lines = [f"{arch} [{mesh_name}]: collective fingerprint drift"]
    gc, sc = golden.get("counts", {}), seen.get("counts", {})
    gb, sb = golden.get("result_bytes", {}), seen.get("result_bytes", {})
    for kind in sorted(set(gc) | set(sc) | set(gb) | set(sb)):
        c0, c1 = gc.get(kind, 0), sc.get(kind, 0)
        b0, b1 = gb.get(kind, 0), sb.get(kind, 0)
        if c0 == c1 and b0 == b1:
            continue
        if c0 == 0 and b0 == 0:
            what, knob = "NEW op", ("a partitioner/StepShardings "
                                    "change introduced this collective")
        elif c1 == 0 and b1 == 0:
            what, knob = "GONE", ("a partitioner/StepShardings change "
                                  "removed this collective")
        else:
            what = "drifted"
            knob = _DRIFT_KNOBS.get(
                kind, "mesh shape / StepShardings for this op")
        lines.append(f"  {kind:20s} {what:8s} "
                     f"count {c0} -> {c1} ({c1 - c0:+d}), "
                     f"bytes {b0} -> {b1} ({b1 - b0:+d})")
        lines.append(f"  {'':20s} likely knob: {knob}")
    return "\n".join(lines) + "\n"


# ------------------------------------------------------- engine plumbing
def build_engine(arch: str, mesh_name: str) -> Engine:
    mesh = None
    axes = MESHES[mesh_name]
    if axes is not None:
        mesh = make_host_mesh(data=axes[0], model=axes[1])
    cfg = get_reduced(arch)
    params = init_params(jax.random.key(0), cfg)
    ads = [(AdapterSpec("ad0", rank=8, invocation_tokens=(7, 8, 9)),
            init_adapter_weights(jax.random.key(100), cfg, 8))]
    return Engine(cfg, params, adapters=ads,
                  engine_cfg=EngineConfig(max_running=4,
                                          max_batched_tokens=64,
                                          mesh=mesh))


def capture_batch(eng: Engine, n: int = 3, gen: int = 4,
                  plen: int = 24) -> MixedBatch:
    """Run a short production (async) serve and keep the richest
    submitted batch — prefer one mixing decode rows with prefill
    chunks, the shape the steady-state engine dispatches."""
    cfg = eng.cfg
    rng = np.random.RandomState(5)
    captured: List[MixedBatch] = []
    orig = eng.runner._assemble_mixed

    def cap(mb: MixedBatch):
        captured.append(mb)
        return orig(mb)

    eng.runner._assemble_mixed = cap  # type: ignore[method-assign]
    try:
        for i in range(n):
            kw = {}
            if cfg.is_encoder_decoder:
                kw = dict(frame_embeds=np.random.RandomState(7).randn(
                    cfg.encoder_seq_len, cfg.d_model).astype(np.float32))
            eng.submit(list(rng.randint(10, 500, plen)), gen,
                       adapter_name="ad0" if i % 2 else None,
                       arrival_time=1e-9 * i, **kw)
        steps = 0
        while (eng.pending or eng.waiting or eng.running) and steps < 60:
            eng.step()
            steps += 1
    finally:
        eng.runner._assemble_mixed = orig  # type: ignore[method-assign]
    if not captured:
        raise RuntimeError(f"no mixed batch captured for {cfg.name}")
    return max(captured,
               key=lambda mb: (bool(len(mb.block_tables)),
                               len(mb.tok_ids)))


# ------------------------------------------------------------ the audit
def audit_config(arch: str, mesh_name: str, *,
                 golden_dir: str = GOLDEN_DIR,
                 update_goldens: bool = False) -> AuditResult:
    """Compile the production mixed step for (arch, mesh) and run every
    static check.  With ``update_goldens`` the observed collective
    fingerprint is written as the new golden instead of diffed."""
    res = AuditResult(arch=arch, mesh=mesh_name)
    eng = build_engine(arch, mesh_name)
    runner = eng.runner
    mb = capture_batch(eng)

    args = runner._assemble_mixed(mb)
    lowered = runner_mod._mixed_impl.lower(*args)
    # async vs sync is data (from_buf mask), not program: the sync
    # flavor must lower to the identical module
    mb_sync = dataclasses.replace(mb, from_buf=None)
    lowered_sync = runner_mod._mixed_impl.lower(
        *runner._assemble_mixed(mb_sync))
    res.sync_async_identical = \
        lowered.as_text() == lowered_sync.as_text()

    compiled = lowered.compile()
    txt = compiled.as_text()

    res.violations += check_host_callbacks(txt)
    res.violations += check_dynamic_shapes(txt)
    res.violations += check_bf16_upcasts(
        txt, threshold_elems=eng.cfg.d_model * eng.cfg.d_model)

    leaves = output_leaves(args)
    # the alias table sits in the HloModule header (module scope), the
    # ROOT tuple in the ENTRY body — parse from the full text
    aliases = parse_aliases(txt)
    res.violations += check_payload(leaves, aliases, eng.cfg,
                                    runner.rcfg.max_running)
    dviol, res.donated = check_donation(leaves, aliases,
                                        has_ssm=bool(runner.Ls))
    res.violations += dviol

    res.fingerprint = fingerprint_of(txt)
    if not res.sync_async_identical:
        fp_sync = fingerprint_of(lowered_sync.compile().as_text())
        if fp_sync != res.fingerprint:
            res.violations.append(
                "sync-async: the sync-flavored step compiles to a "
                "different collective fingerprint than the async one")
    gp = golden_path(arch, mesh_name, golden_dir)
    if update_goldens:
        os.makedirs(golden_dir, exist_ok=True)
        with open(gp, "w") as f:
            json.dump({"arch": arch, "mesh": mesh_name,
                       **res.fingerprint}, f, indent=2, sort_keys=True)
            f.write("\n")
    else:
        golden: Optional[Dict] = None
        if os.path.exists(gp):
            with open(gp) as f:
                g = json.load(f)
            golden = {"counts": g.get("counts", {}),
                      "result_bytes": g.get("result_bytes", {})}
        res.fingerprint_diff = diff_fingerprint(arch, mesh_name,
                                                res.fingerprint, golden)

    try:
        ma = compiled.memory_analysis()
        res.memory = {
            "alias_size_bytes": float(ma.alias_size_in_bytes),
            "output_size_bytes": float(ma.output_size_in_bytes),
            "temp_size_bytes": float(ma.temp_size_in_bytes),
            "argument_size_bytes": float(ma.argument_size_in_bytes),
        }
    except Exception:        # backend without memory stats: non-fatal
        res.memory = {}
    return res


def audit_all(archs: Optional[List[str]] = None,
              mesh_names: Optional[List[str]] = None, *,
              golden_dir: str = GOLDEN_DIR,
              update_goldens: bool = False,
              progress=None) -> List[AuditResult]:
    archs = sorted(all_configs()) if archs is None else archs
    mesh_names = list(MESHES) if mesh_names is None else mesh_names
    results = []
    for arch in archs:
        for mesh_name in mesh_names:
            if progress:
                progress(f"auditing {arch} [{mesh_name}]")
            results.append(audit_config(arch, mesh_name,
                                        golden_dir=golden_dir,
                                        update_goldens=update_goldens))
    return results
