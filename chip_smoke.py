#!/usr/bin/env python3
"""Smoke test of the serving path on TPU at published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the TP-sharded step only

One chip (TPU v5e, 16 GB): granite-3.2-8b's one-chip share
(``configs.granite3_8b.chip_share``: 20 of 40 layers, every width as
published, bf16, seeded random weights) goes through the normal serving
path, ``launch/serve.py``'s ``build_engine`` -> ``Engine`` ->
``ModelRunner`` mixed step, running the paper's base->adapter pipeline
(``serving.pipelines.base_adapter``) with two aLoRA adapters.  Phases:

  1. kernels: the ragged paged-attention and grouped-LoRA Pallas
     kernels compile for the chip (``tpu_custom_call``) and match their
     jnp references at granite widths, and ragged SSD at mamba2-2.7b
     widths;
  2. serve: 4 pipelines of ~2k-token prompts; every request finishes
     with valid token ids, the adapter stage reuses the base stage's
     KV blocks (cache hit share > 0), and the drained engine holds no
     blocks;
  3. reference: every generated token is checked against
     ``models.model.forward_full`` on the same parameters (see
     ``LOGIT_TOL``); the adapter tokens must fail the same check
     against references that drop the adapters or swap their slots.

Four chips (``--chips 4``): the 20-layer share served tensor-parallel
over a (data=1, model=4) mesh (``EngineConfig.mesh``) against the same
share on one chip, then the published 40-layer model on that mesh; two
base requests and one aLoRA request each, every token checked against
``forward_full``.

Exits non-zero, printing no result, when JAX finds no TPU.  The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``.
Compiled programs persist in JAX's compile cache
(``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-3.2-8b"
SEED = 0
N_PIPELINES = 4
N_ADAPTERS = 2
# base prompt + 16 generated + 3 invocation + 16 evaluated tokens stay
# within 2,048 positions, so every block table fits one 128-block bucket
PROMPT_LEN = 1984
GEN_LEN = 16
EVAL_LEN = 16
# Reference check: the reference logit of each generated token is within
# LOGIT_TOL standard deviations (of that reference logit row) of the
# reference maximum.  Argmax equality is not required: the engine and
# forward_full both run the model in bf16 (8-bit mantissa), in different
# orders (paged K/V gathered per token and 128-token prefill chunks,
# against whole-sequence flash attention), and with random weights the
# top two logits are a median 0.15 std apart.  On a v5e that reordering
# put the worst token 0.05-0.12 std below the maximum (one chip, 192
# tokens a run) and the first token of each request, the prefill's, at
# 0.  Two controls, run with every adapter check, must fail it: the same
# tokens against a reference with the adapters dropped, and with their
# slots swapped.  Adapters at init_adapter_weights' scale left those
# controls only 0.27 and 0.63 std off; with launch/serve.py's
# ADAPTER_GAIN they sat 2.61 and 3.41 std off.
LOGIT_TOL = 0.25
GIB = 1 << 30


def check(ok, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_info(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileLog:
    """Counts XLA backend compiles and their seconds (persistent-cache
    hits skip the backend compile and are not counted)."""

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def line(self) -> str:
        return f"{self.n} backend compiles, {self.secs:.1f} s"


def peak_gib(jax) -> float:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"] / GIB


# ---------------------------------------------------------------------------
# phase 1: Pallas kernels at granite widths
# ---------------------------------------------------------------------------
def check_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import ragged_paged_attention
    from repro.kernels.ragged_lora import (ragged_grouped_lora_padded,
                                           ragged_grouped_lora_ref)
    from repro.kernels.ref import ragged_paged_attention_ref

    def compiled(f, *args):
        c = jax.jit(f).lower(*args).compile()
        check("tpu_custom_call" in c.as_text(), "kernel not in the program")
        return c

    def close(name, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        print(f"  {name}: max|kernel-ref| {err:.3e} (ref max {scale:.3e},"
              f" tol {tol * scale:.3e}) tpu_custom_call")
        check(np.isfinite(got).all() and err <= tol * scale, name)

    ks = jax.random.split(jax.random.key(SEED), 8)
    H, KV, hd, bs = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 16
    T, R, nb, NB = 128, 4, 128, 4 * 128 + 1
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (T, H, hd)).astype(bf)
    kp = jax.random.normal(ks[1], (NB, bs, KV, hd)).astype(bf)
    vp = jax.random.normal(ks[2], (NB, bs, KV, hd)).astype(bf)
    bt = jax.random.permutation(ks[3], NB - 1)[:R * nb].reshape(R, nb)
    rows = jnp.sort(jax.random.randint(ks[4], (T,), 0, R))
    qlens = jax.random.randint(ks[5], (T,), 1, nb * bs + 1)
    c = compiled(ragged_paged_attention, q, kp, vp, bt, rows, qlens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ragged_paged_attention_ref)(q, kp, vp, bt, rows,
                                                   qlens)
    # bf16 inputs; the kernel rounds the softmax weights to bf16 before
    # the PV matmul (2^-9 relative), the reference keeps them in f32
    close(f"ragged attention T={T} H={H} KV={KV} hd={hd} ctx={nb * bs}",
          c(q, kp, vp, bt, rows, qlens), want, 2e-2)

    d, r, n_slots = cfg.d_model, 32, 3
    for T in (8, 128):
        for out in (H * hd, KV * hd):
            x = jax.random.normal(ks[6], (T, d)).astype(bf)
            a = (jax.random.normal(ks[7], (n_slots, d, r)) * 0.02
                 ).at[0].set(0.0).astype(bf)
            b = (jax.random.normal(ks[5], (n_slots, r, out)) * 0.02
                 ).at[0].set(0.0).astype(bf)
            idx = jax.random.randint(ks[4], (T,), 0, n_slots)
            act = jnp.arange(1, n_slots, dtype=jnp.int32)
            c = compiled(ragged_grouped_lora_padded, x, a, b, idx, act)
            want = jax.jit(ragged_grouped_lora_ref)(x, a, b, idx, act)
            # the reference rounds x@A to bf16 like the kernel, and its
            # output too (2^-9 relative each)
            close(f"grouped LoRA T={T} d={d} out={out} rank={r}",
                  c(x, a, b, idx, act), want, 2e-2)

    # ragged SSD at mamba2-2.7b widths: 4 decode rows and two prefill
    # segments of 60 and 64 tokens in one 128-token packed batch
    from repro.configs import get_config
    from repro.kernels.ops import ragged_ssd_scan_op
    from repro.kernels.ref import ragged_ssd_scan_ref
    m = get_config("mamba2-2.7b")
    H, P, N = m.ssm.expand * m.d_model // m.ssm.head_dim, m.ssm.head_dim, \
        m.ssm.state_dim
    lens, n_slots = [1, 1, 1, 1, 60, 64], 9
    T = sum(lens)
    seg = np.repeat(np.arange(len(lens)), lens)
    starts = np.concatenate([[True], seg[1:] != seg[:-1]])
    slots = seg + 1
    ks = jax.random.split(ks[0], 6)
    dt = jax.random.uniform(ks[0], (T, H), minval=1e-3, maxval=0.1)
    args = (jax.random.normal(ks[1], (T, H, P)),
            jax.random.normal(ks[2], (T, H, N)),
            jax.random.normal(ks[3], (T, H, N)),
            -dt * jnp.arange(1, H + 1, dtype=jnp.float32), dt,
            jnp.asarray(seg, jnp.int32), jnp.asarray(starts),
            jnp.asarray(slots, jnp.int32),
            jax.random.normal(ks[4], (n_slots, H, N, P)) * 0.1)
    c = compiled(lambda *a: ragged_ssd_scan_op(*a, chunk=16,
                                               interpret=False), *args)
    y, st = c(*args)
    with jax.default_matmul_precision("highest"):
        y_r, st_r = jax.jit(ragged_ssd_scan_ref)(*args[:5], *args[6:])
    # float32 throughout; the kernel sums each state as one matmul over
    # its chunk instead of token by token, on an MXU that may round f32
    # operands to bf16 passes (2^-9 relative)
    close(f"ragged SSD T={T} H={H} P={P} N={N} chunk=16 (y)", y, y_r,
          2e-2)
    close("ragged SSD (post-token states)", st, st_r, 2e-2)


# ---------------------------------------------------------------------------
# phases 2-3: the serving path and its reference
# ---------------------------------------------------------------------------
def check_requests(eng, ids, n_new: int) -> None:
    """Every request finished with ``n_new`` valid token ids."""
    from repro.serving.request import State
    V = eng.cfg.vocab_size
    for rid in ids:
        req = eng.request(rid)
        out = req.output_tokens
        check(req.state == State.DONE and len(out) == n_new
              and all(0 <= t < V for t in out), f"request {rid}: {out}")
    print(f"  {len(ids)} requests finished with {n_new} token ids each, "
          f"all in [0, {V})")


def check_drained(eng) -> None:
    kv = eng.kv_mgr
    held = sum(m.ref for m in kv.meta)
    print(f"  drained: {kv.num_free()}/{eng.ecfg.num_blocks} blocks free, "
          f"{held} block refs held")
    check(kv.num_free() == eng.ecfg.num_blocks and held == 0, "KV leak")


def serve(cfg, params, engine_cfg, *, n_pipelines: int, prompt_len: int,
          gen_len: int, eval_len: int):
    """The base->adapter pipeline through ``launch/serve.py``'s builder,
    with its checks.  Returns ``(engine, [base ids, adapter ids])``."""
    from repro.launch.serve import build_engine
    from repro.serving import pipelines as P

    eng = build_engine(cfg, params, "alora", N_ADAPTERS,
                       engine_cfg=engine_cfg)
    names = [f"intrinsic{i}" for i in range(N_ADAPTERS)]
    t0 = time.perf_counter()
    res = P.base_adapter(eng, adapter_names=names, prompt_len=prompt_len,
                         gen_len=gen_len, eval_len=eval_len,
                         batch=n_pipelines, seed=SEED)
    print(f"  served {len(res.base_ids)} base + {len(res.eval_ids)} adapter"
          f" requests in {eng.runner.call_counts['mixed_step']} mixed steps,"
          f" {time.perf_counter() - t0:.1f} s wall (compiles included)")
    check_requests(eng, res.base_ids, gen_len)
    check_requests(eng, res.eval_ids, eval_len)
    hit = {st: res.stage_metrics(eng, st).means["cache_hit_frac"]
           for st in ("base", "eval")}
    print(f"  prefix-cache hit share of prompt tokens: base stage "
          f"{hit['base']:.4f}, adapter stage {hit['eval']:.4f}")
    check(hit["eval"] > 0, "adapter stage reused no base KV blocks")
    check_drained(eng)
    return eng, [res.base_ids, res.eval_ids]


def serve_batch(cfg, params, engine_cfg, *, prompt_len: int, gen_len: int):
    """Two base requests and one aLoRA request (its prompt ends in the
    invocation tokens) submitted together: one prefill step, then
    decode steps.  Returns ``(engine, [base ids, [adapter id]])``."""
    import numpy as np
    from repro.launch.serve import build_engine

    eng = build_engine(cfg, params, "alora", 1, engine_cfg=engine_cfg)
    rng = np.random.RandomState(SEED)
    prompts = [list(rng.randint(10, cfg.vocab_size, prompt_len))
               for _ in range(3)]
    inv = list(eng.adapters["intrinsic0"].spec.invocation_tokens)
    base = [eng.submit(p, gen_len) for p in prompts[:2]]
    alora = [eng.submit(prompts[2] + inv, gen_len,
                        adapter_name="intrinsic0")]
    t0 = time.perf_counter()
    eng.run_until_idle()
    print(f"  served 2 base + 1 adapter requests in "
          f"{eng.runner.call_counts['mixed_step']} mixed steps, "
          f"{time.perf_counter() - t0:.1f} s wall (compiles included)")
    check_requests(eng, base + alora, gen_len)
    check_drained(eng)
    return eng, [base, alora]


def token_gaps(lg, tokens):
    """``(ref max - ref[token]) / std`` of each reference logit row
    ``lg[j, k]`` (real vocabulary only) for the engine's ``tokens[j][k]``,
    and how many tokens are the row's argmax."""
    import numpy as np
    tok = np.asarray(tokens)
    picked = np.take_along_axis(lg, tok[..., None], -1)[..., 0]
    gaps = (lg.max(-1) - picked) / lg.std(-1)
    return gaps, int((lg.argmax(-1) == tok).sum())


def check_reference(eng, groups, cfg, params) -> None:
    """Teacher-force each request's prompt and generated tokens through
    ``forward_full`` on ``params`` and check every generated token
    (``LOGIT_TOL``); the first one is the prefill's.  ``groups``: lists
    of request ids whose prompt and output lengths agree (one forward
    batch each).  For adapter requests two controls show that the check
    sees the adapter: the same tokens against a reference that drops the
    adapters, and against one that swaps their slots, must fail it."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.activation_mask import find_invocation_start
    from repro.core.alora import PAPER_ALORA_RANK, stack_adapters
    from repro.launch.serve import build_adapters
    from repro.models.model import forward_full, logits_for

    adapters = build_adapters(cfg, "alora", N_ADAPTERS)
    stacked = stack_adapters(cfg, [w for _, w in adapters],
                             PAPER_ALORA_RANK)
    slot_of = {s.name: i + 1 for i, (s, _) in enumerate(adapters)}
    swapped = {s.name: N_ADAPTERS - i for i, (s, _) in enumerate(adapters)}

    @partial(jax.jit, static_argnums=4)
    def ref_logits(p, st, toks, aidx, n_last: int):
        with jax.default_matmul_precision("highest"):
            h, _, _ = forward_full(p, cfg, toks, adapters=st,
                                   adapter_idx=aidx)
            lg = logits_for(p, cfg, h[:, -n_last:])
            return lg[..., :cfg.vocab_size].astype(jnp.float32)

    for ids in groups:
        reqs = [eng.request(i) for i in ids]
        seqs = [r.prompt + r.output_tokens[:-1] for r in reqs]
        L = len(seqs[0])
        check(all(len(s) == L for s in seqs), "ragged reference batch")
        outs = [r.output_tokens for r in reqs]
        n = sum(map(len, outs))

        def gaps(st, slots):
            aidx = np.zeros((len(reqs), L), np.int32)
            for j, r in enumerate(reqs):
                if r.adapter is not None:
                    start = find_invocation_start(
                        r.prompt, r.adapter.invocation_tokens)
                    aidx[j, start:] = slots[r.adapter.name]
            aidx = None if st is None else jnp.asarray(aidx)
            lg = ref_logits(params, st, jnp.asarray(seqs), aidx,
                            len(outs[0]))
            return token_gaps(np.asarray(lg), outs)

        g, agree = gaps(stacked, slot_of)
        kind = "adapter" if reqs[0].adapter is not None else "base"
        print(f"  reference, {len(reqs)} {kind} requests: {n} generated "
              f"tokens; worst gap (ref max - ref[token]) {g.max():.4f} std, "
              f"first (prefill) tokens {g[:, 0].max():.4f} (tol "
              f"{LOGIT_TOL}); argmax equal on {agree}/{n}")
        check(g.max() <= LOGIT_TOL, f"{kind} token off the reference")
        if kind == "base":
            continue
        for what, st, slots in (("adapters dropped", None, slot_of),
                                ("adapter slots swapped", stacked, swapped)):
            g, agree = gaps(st, slots)
            print(f"    control, reference with {what}: worst gap "
                  f"{g.max():.4f} std; argmax equal on {agree}/{n}")
            check(g.max() > LOGIT_TOL,
                  f"the reference check cannot see the adapter ({what})")


def one_chip() -> None:
    import jax
    from repro.configs import get_chip_share
    from repro.configs.granite3_8b import CHIP_SHARE_REDUCED, DEPLOYMENT
    from repro.launch.serve import CHIP_ENGINE_CFG
    from repro.models import init_params

    cfg = get_chip_share(ARCH)
    print(f"config {cfg.name}: {cfg.num_layers} layers (cut: "
          f"{', '.join(CHIP_SHARE_REDUCED)}), d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}/{cfg.num_kv_heads} kv, head_dim {cfg.head_dim},"
          f" d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}")
    print(f"  deployment: {DEPLOYMENT}")

    print("phase 1: Pallas kernels")
    check_kernels(cfg)

    print("phase 2: serve")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(jax.random.key(SEED), cfg))
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    print(f"  params {n_bytes / GIB:.2f} GiB, init "
          f"{time.perf_counter() - t0:.1f} s, peak_bytes_in_use "
          f"{peak_gib(jax):.2f} GiB")
    eng, groups = serve(cfg, params, CHIP_ENGINE_CFG,
                        n_pipelines=N_PIPELINES, prompt_len=PROMPT_LEN,
                        gen_len=GEN_LEN, eval_len=EVAL_LEN)
    print("phase 3: reference")
    check_reference(eng, groups, cfg, params)


def four_chips() -> None:
    import gc

    import jax
    from repro.configs import get_chip_share, get_config
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params, param_specs
    from repro.serving import EngineConfig

    mesh = make_host_mesh(data=1, model=4)
    ecfg = EngineConfig(num_blocks=512, max_running=8,
                        max_batched_tokens=1024, mesh=mesh)
    sizes = dict(prompt_len=240, gen_len=8)

    print("phase 1: 20-layer share, tensor-parallel over 4 chips vs the "
          "same share on one chip")
    share = get_chip_share(ARCH)
    # the engine shards its own copy; the reference runs on this one, on
    # one chip: every token of the sharded engine (the first of each
    # request from its sharded prefill) is checked against one chip
    params = init_params(jax.random.key(SEED), share)
    eng, groups = serve_batch(share, params, ecfg, **sizes)
    check_reference(eng, groups, share, params)

    del eng, params
    gc.collect()

    print("phase 2: published 40-layer model, tensor-parallel over 4 chips")
    cfg = get_config(ARCH)
    named = shd.to_named(shd.param_specs_tree(cfg, param_specs(cfg),
                                              mesh=mesh), mesh)
    params = init_params(jax.random.key(SEED), cfg, named)
    eng, groups = serve_batch(cfg, params, ecfg, **sizes)
    check_reference(eng, groups, cfg, params)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    dev = device_info(jax)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev}", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {dev}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {dev}; compile cache {enable_compile_cache()}")
    compiles = CompileLog(jax)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)()
    print(f"done in {time.perf_counter() - t0:.1f} s; {compiles.line()}; "
          f"peak_bytes_in_use {peak_gib(jax):.2f} GiB (device 0)")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
