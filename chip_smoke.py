#!/usr/bin/env python3
"""Smoke run of the CrossPool serving engine on a TPU, at published widths.

    python chip_smoke.py                   # one chip
    python chip_smoke.py --chips 4         # disaggregated pools, four chips
    python chip_smoke.py --cpu-rehearsal   # the same phases, smoke widths, CPU

One engine colocates two cold models, so the shared paged KV pool (GQA and
MLA typing the same pages) and the shared weights arena both do real work:

* moonshot-v1-16b-a3b: d_model 2048, 16 heads x 128, 64 experts of d_ff
  1408 (top-6, all 64 held in bf16), vocab 163,840;
* minicpm3-4b: d_model 2560, 40 MLA heads (768/256/64/32/64), d_ff 6400,
  vocab 73,448;

both at published widths and cut to 4 layers, with random weights from
fixed seeds.  Eight requests (seeded prompts of 128-512 tokens, 32 new
tokens each) go through the engine's session API (``submit``/``step``) in
its default mode: pipeline on, fused lowering, one token per dispatch.

The run prints the device, time to first token (compiling included), the
steady decode time per step, tokens out, device memory and pool use.  It
then frees the engine and checks the first-token logits of one request
per model against the model's own unpooled ``forward`` in float32.  This
is a one-off smoke, not a benchmark.

``--chips 4`` runs only the disaggregated path -- a host-driven engine
with the KV pool and attention on chip 0 and the arena on chip 3 -- and,
for comparison, the one-chip fused engine on the same requests.  It checks
where the pools live and that the two engines' logits agree.

Any failure exits non-zero.  The last line of standard output, printed
only when every check passed, is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script fails, unless ``--cpu-rehearsal`` is given.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import EngineConfig, get_config, get_smoke_config
from repro.core.split_exec import split_params
from repro.launch.compile_cache import setup_compile_cache
from repro.models import build_model
from repro.models import transformer as tfm
from repro.runtime.engine import CrossPoolEngine, EngineMode
from repro.runtime.request import Request
from repro.runtime.session import HandleState

MODELS = ("moonshot-v1-16b-a3b", "minicpm3-4b")
N_LAYERS = 4
PAGE_BYTES = 64 * 1024          # 8 moonshot tokens a page (16 KiB holds 2)
KV_POOL_BYTES = 1 << 30
N_REQUESTS, MAX_NEW = 8, 32
MAX_BATCH, MAX_CTX = 4, 1024
PROMPT_LENS = (128, 512)
#: Prompt length of each model's checked request (request 0 and 1).  It is
#: a prefill bucket, so the engine's prompt pass is unpadded and routes its
#: MoE tokens with the same expert capacity as the reference forward.
CHECKED_LEN = {"moonshot-v1-16b-a3b": 512, "minicpm3-4b": 256}
WARMUP_STEPS = 3                # decode-only steps left out of the steady time
#: Relative L2 error allowed between the served first-token logits and the
#: model's own unpooled forward at the SAME precision (bf16 weights and
#: activations).  The math is the same, but the programs differ (per-layer
#: stages over pool pages and arena slabs vs one forward), and the TPU
#: compiler fuses them differently, so bf16 values round differently: on a
#: v5e the two are about 1% apart.  A wrong page or slab moves logits by
#: tens of percent: the negative control below measures how far.
SAME_PRECISION_RTOL = 2e-2
#: The same against the float32 reference (highest matmul precision), by
#: kind of FFN.  The served path is bf16: rounding to 2^-9 leaves a dense
#: model's logits about 1% from float32 over 4 layers (bound 3%).  A routed
#: MoE amplifies that rounding: it flips near-tied choices among the top 6
#: of 64 experts and shifts capacity slots, so some tokens take other
#: experts.  At d_model 256 with 64 experts that leaves 13.5% while the
#: same engine in float32 matches to 3e-7 (CPU); bound 20%.
FLOAT32_RTOL = {"dense": 0.03, "moe": 0.20}


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def make_requests(models, seed: int):
    """Eight requests alternating between the models; the first of each
    model keeps its first-token logits for the reference check."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_REQUESTS):
        name = MODELS[i % len(MODELS)]
        n = (CHECKED_LEN[name] if i < len(MODELS)
             else int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1)))
        ids = rng.integers(0, models[name].vocab_size, n).astype(np.int32)
        reqs.append(Request(i, name, n, MAX_NEW, 0.0, prompt_ids=ids,
                            keep_prompt_logits=i < len(MODELS)))
    return reqs


def reckon_peak(engine, models) -> int:
    """Device bytes the engine should peak at on one chip: while building,
    the KV pool, the params kept so far and one model's full tree; once
    built, pool + kept params + arena + one layer's slabs in upload."""
    pool = engine.virt.pool.nbytes
    kept, build = 0, 0
    for i, (name, cfg) in enumerate(models.items()):
        full = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(i))
        build = max(build, pool + kept + tree_bytes(full))
        kept += tree_bytes(split_params(full, cfg)[0])
    upload = max(s[0].nbytes for s in engine.arena.host_slabs.values())
    return max(build, pool + kept + engine.arena.device_bytes() + upload)


def serve(models, mode: EngineMode, reqs, *, page_budget: int, label: str):
    """Build an engine, serve ``reqs`` to completion through the session
    API and check its pools drained.  Returns the engine."""
    t = time.perf_counter()
    engine = CrossPoolEngine(models, page_budget=page_budget,
                             page_bytes=PAGE_BYTES, max_batch=MAX_BATCH,
                             max_ctx=MAX_CTX, config=EngineConfig(mode=mode))
    print(f"[{label}] engine built in {time.perf_counter() - t:.1f} s: "
          f"KV pool {gib(engine.virt.pool.nbytes)} ({page_budget} pages of "
          f"{PAGE_BYTES // 1024} KiB), arena {gib(engine.arena.device_bytes())}"
          f" ({engine.arena.slot_budget} slabs), attention-side params "
          f"{gib(engine.kv_pool.total_param_bytes())}")
    for name, view in engine.virt.views.items():
        print(f"[{label}]   {name}: {view.tokens_per_page} tokens/page, "
              f"{engine.arena.views[name].total_slabs} slabs")

    handles = [engine.submit(r) for r in reqs]
    refused = [h.request.request_id for h in handles
               if h.state is HandleState.REJECTED]
    if refused:
        fail(f"[{label}] admission rejected requests {refused}")
    t0 = time.perf_counter()
    first, decode_steps, n_steps = {}, [], 0
    while any(h.state is not HandleState.FINISHED for h in handles):
        n_steps += 1
        if n_steps > 10 * MAX_NEW:
            fail(f"[{label}] not drained after {n_steps} steps")
        ts = time.perf_counter()
        events = engine.step()
        dt = time.perf_counter() - ts
        for e in events:
            if e.first:
                first[e.request_id] = time.perf_counter() - t0
        if events and not any(e.first for e in events):
            decode_steps.append(dt)
    tokens = sum(len(r.output_ids) for r in reqs)
    if tokens != N_REQUESTS * MAX_NEW or engine.stats.tokens_out != tokens:
        fail(f"[{label}] {tokens} tokens out "
             f"(stats {engine.stats.tokens_out}), want {N_REQUESTS * MAX_NEW}")
    steady = decode_steps[WARMUP_STEPS:]
    if not steady:
        fail(f"[{label}] no steady decode steps to time")
    ttft = sorted(first.values())
    print(f"[{label}] time to first token (compiling included): "
          f"first {ttft[0]:.2f} s, last {ttft[-1]:.2f} s")
    print(f"[{label}] steady decode step (both models, B={MAX_BATCH} each): "
          f"median {statistics.median(steady) * 1e3:.2f} ms over "
          f"{len(steady)} steps, after {WARMUP_STEPS} warm-up steps")
    print(f"[{label}] tokens out: {tokens} in {n_steps} steps, "
          f"{time.perf_counter() - t0:.2f} s")
    util = engine.virt.utilization()
    print(f"[{label}] KV pool: mapped_pages {util['mapped_pages']}, "
          f"free_pages {util['free_pages']}, peak_mapped "
          f"{util['peak_mapped']} of {util['page_budget']}")
    arena = engine.arena.utilization()
    print(f"[{label}] arena: resident_slabs {arena['resident_slabs']} of "
          f"{arena['slot_budget']}, layer_uploads {arena['layer_uploads']}, "
          f"pinned_slabs {arena['pinned_slabs']}")
    if util["mapped_pages"] != 0:
        fail(f"[{label}] {util['mapped_pages']} pages still mapped after "
             f"the drain")
    if arena["pinned_slabs"] != 0:
        fail(f"[{label}] {arena['pinned_slabs']} slabs still pinned")
    return engine


def memory_line(label: str, reckoned: str = "") -> None:
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            print(f"[{label}] {d}: memory_stats not reported{reckoned}")
            continue
        peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
        print(f"[{label}] {d}: peak_bytes_in_use {gib(peak)}{reckoned}, "
              f"bytes_limit {gib(limit)}")
        if peak > limit:
            fail(f"[{label}] {d} peaked above its memory")


def swap_ffn_layers(params):
    """The FFN weights of layers 0 and 1 swapped: what the FFN stage would
    read through a slot table that points at the wrong layer's slabs."""
    def swap(path, leaf):
        if any(getattr(k, "key", None) in ("moe", "mlp") for k in path):
            return leaf.at[jnp.array([0, 1])].set(leaf[jnp.array([1, 0])])
        return leaf
    return {**params, "layers": jax.tree_util.tree_map_with_path(
        swap, params["layers"])}


def reference_check(models, reqs) -> None:
    """First-token logits of each checked request against the model's own
    unpooled forward, with the same weights (``PRNGKey(i)`` in model order,
    as the engine draws them): once at the served precision, once in
    float32 at highest matmul precision."""
    for i, (name, cfg) in enumerate(models.items()):
        req = next(r for r in reqs if r.model == name and r.keep_prompt_logits)
        got = req.prompt_logits
        if not np.isfinite(got).all():
            fail(f"{name}: served logits are not finite")
        tokens = jnp.asarray(req.prompt_ids)[None]
        fwd = jax.jit(lambda p, t: tfm.forward(p, cfg, t)[0][0, -1])
        params = build_model(cfg).init(jax.random.PRNGKey(i))
        same = np.asarray(fwd(params, tokens))
        wrong = np.asarray(fwd(swap_ffn_layers(params), tokens))
        # float32 activations from the first layer on: every weight is
        # promoted inside the op it feeds.  Expert stacks stay bf16 in
        # memory (their values are bf16 anyway); everything else is cast.
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: a if any(getattr(k, "key", None) == "moe"
                                  for k in p) else a.astype(jnp.float32),
            params)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fwd(params, tokens))
        del params
        tol32 = FLOAT32_RTOL["moe" if cfg.is_moe else "dense"]
        err, err32 = rel_err(got, same), rel_err(got, want)
        control = rel_err(wrong, same)
        print(f"[reference] {name} request {req.request_id} "
              f"({req.prompt_tokens} prompt tokens), relative L2 error of "
              f"the served first-token logits: {err:.5f} from the unpooled "
              f"{cfg.dtype} forward (tolerance {SAME_PRECISION_RTOL}), "
              f"{err32:.5f} from the float32 reference (tolerance {tol32}); "
              f"negative control, FFN slabs of layers 0/1 swapped: "
              f"{control:.5f}")
        if err > SAME_PRECISION_RTOL:
            fail(f"{name}: served logits are {err:.5f} from the unpooled "
                 f"forward")
        if err32 > tol32:
            fail(f"{name}: served logits are {err32:.5f} from the float32 "
                 f"reference")
        if control <= max(SAME_PRECISION_RTOL, tol32):
            fail(f"{name}: the tolerances cannot tell a wrong slab apart "
                 f"({control:.5f})")


def check_placement(engine, label: str, *, kv, weights) -> None:
    where = {
        "KV pool": engine.virt.pool,
        "arena": engine.arena.arena,
        **{f"{n} attention params": jax.tree.leaves(p)[0]
           for n, p in engine.kv_pool.attn_params.items()},
    }
    want = {"KV pool": kv, "arena": weights}
    for what, arr in where.items():
        dev = want.get(what, kv)
        print(f"[{label}] {what} on {sorted(arr.devices(), key=str)}")
        if arr.devices() != {dev}:
            fail(f"[{label}] {what} is on {arr.devices()}, want {dev}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run at smoke widths on the CPU (no TPU needed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = setup_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}; compile cache {cache_dir}")
    if not args.cpu_rehearsal and dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} but JAX found {len(devs)} devices")

    if args.cpu_rehearsal:
        models = {n: get_smoke_config(n) for n in MODELS}
        page_budget = (64 << 20) // PAGE_BYTES
    else:
        models = {n: get_config(n).replace(n_layers=N_LAYERS) for n in MODELS}
        page_budget = KV_POOL_BYTES // PAGE_BYTES
    for name, cfg in models.items():
        print(f"model {name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
              f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, experts "
              f"{cfg.n_experts} (top-{cfg.experts_per_token}), vocab "
              f"{cfg.vocab_size}, {cfg.dtype}")

    if args.chips == 4:
        host_reqs = make_requests(models, args.seed)
        engine = serve(models, EngineMode(lowering=False), host_reqs,
                       page_budget=page_budget, label="host-driven 2-pool")
        check_placement(engine, "host-driven 2-pool", kv=devs[0],
                        weights=devs[-1])
        del engine
        gc.collect()
        fused_reqs = make_requests(models, args.seed)
        engine = serve(models, EngineMode(), fused_reqs,
                       page_budget=page_budget, label="fused 1-chip")
        check_placement(engine, "fused 1-chip", kv=devs[0], weights=devs[0])
        del engine
        gc.collect()
        for a, b in zip(host_reqs, fused_reqs):
            if a.keep_prompt_logits:
                err = rel_err(a.prompt_logits, b.prompt_logits)
                print(f"[compare] {a.model} request {a.request_id}: host-"
                      f"driven vs fused first-token logits, relative L2 "
                      f"{err:.5f} (tolerance {SAME_PRECISION_RTOL})")
                if err > SAME_PRECISION_RTOL:
                    fail(f"{a.model}: host-driven and fused logits differ")
        memory_line("4 chips")
    else:
        reqs = make_requests(models, args.seed)
        engine = serve(models, EngineMode(), reqs, page_budget=page_budget,
                       label="fused")
        memory_line("fused",
                    f"; reckoned peak {gib(reckon_peak(engine, models))}")
        del engine
        gc.collect()
        reference_check(models, reqs)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
