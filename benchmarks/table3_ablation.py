"""Table 3: ablation of the layer-wise pipeline + control lowering.

Two complementary measurements:
  (a) REAL: the CrossPool engine serving the smoke-scale colocation trio
      (deepened to 8 layers so per-layer dispatch overhead is visible) on
      TWO forced host devices — the KV pool on device 0, the weights pool
      on device 1 with real inter-device hidden-state transfers.  Runs in a
      subprocess so the device-count flag never leaks into other benches.
      Wall-clock decode throughput across the four (pipeline x lowering)
      modes; warmup excluded.
  (b) SIM:  the paper-scale cost model at 0.5 RPS/model (as in Table 3).
"""
from __future__ import annotations

import copy
import os
import subprocess
import sys

from repro.configs import PAPER_COLOC_SET, get_config
from repro.runtime import observe as trace_mod
from repro.runtime.simulator import DecodeSimulator, paper_placements

MODES = [(False, False), (False, True), (True, False), (True, True)]

_REAL_SCRIPT = r"""
import time
import jax, jax.numpy as jnp, numpy as np
from repro.configs import PAPER_COLOC_SET, get_smoke_config
from repro.runtime.engine import CrossPoolEngine, EngineMode
from repro.runtime import observe as trace_mod

assert len(jax.devices()) == 2, jax.devices()
models = {n: get_smoke_config(n).replace(n_layers=8, dtype="float32")
          for n in PAPER_COLOC_SET}

def run_mode(pipeline, lowering):
    engine = CrossPoolEngine(models, page_budget=16384, page_bytes=4096,
                             max_batch=2, max_ctx=64,
                             mode=EngineMode(pipeline, lowering), seed=1)
    reqs = trace_mod.make_requests(list(models), rps_per_model=100.0,
                                   horizon_s=0.12, kind="sharegpt", seed=1,
                                   scale_tokens=0.05, max_new_cap=8)
    reqs = reqs[:9]
    for r in reqs:
        r.prompt_tokens = max(min(r.prompt_tokens, 16), 4)
        r.arrival_time = 0.0
    stats = engine.run(reqs)
    decode_steps = sum(len(v) for v in stats.step_times.values())
    decode_time = sum(sum(v) for v in stats.step_times.values())
    toks = stats.tokens_out
    return toks, decode_time

for pipeline, lowering in [(False, False), (False, True), (True, False),
                           (True, True)]:
    toks, dt = run_mode(pipeline, lowering)
    print(f"RESULT,{int(pipeline)},{int(lowering)},{toks},{dt:.4f}",
          flush=True)
"""


def run_real(csv=print) -> dict:
    env = dict(os.environ)
    # two forced host devices: the child runs on the CPU, never on a chip
    # the parent process (benchmarks/run.py) may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-c", _REAL_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1500)
    if r.returncode != 0:
        raise RuntimeError(f"real ablation failed:\n{r.stdout[-2000:]}\n"
                           f"{r.stderr[-2000:]}")
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT,"):
            _, p, l, toks, dt = line.split(",")
            tput = int(toks) / max(float(dt), 1e-9)
            out[(bool(int(p)), bool(int(l)))] = tput
    for (pipeline, lowering), tput in sorted(out.items()):
        csv(f"table3_real,pipeline={'On' if pipeline else 'Off'},"
            f"lowering={'On' if lowering else 'Off'},"
            f"decode_tok_s={tput:.2f}")
    base = out[(False, False)]
    csv(f"table3_real,lowering_gain,{out[(False, True)] / base:.2f}x")
    csv(f"table3_real,pipeline_gain,{out[(True, False)] / base:.2f}x")
    csv(f"table3_real,combined_gain,{out[(True, True)] / base:.2f}x")
    return out


def run_sim(csv=print, horizon_s: float = 90.0) -> dict:
    models = {n: get_config(n) for n in PAPER_COLOC_SET}
    proto = trace_mod.make_requests(
        list(models), rps_per_model=0.5, horizon_s=horizon_s,
        kind="sharegpt", seed=2)
    out = {}
    for pipeline, lowering in MODES:
        reqs = copy.deepcopy(proto)
        pl = paper_placements(models, "crosspool", pipelined=pipeline,
                              lowered=lowering)
        DecodeSimulator(models, pl).run(reqs)
        tok = sum(r.generated for r in reqs)
        span = max((r.finish_time for r in reqs if r.finish_time),
                   default=1.0)
        tput = tok / span
        out[(pipeline, lowering)] = tput
        csv(f"table3_sim,pipeline={'On' if pipeline else 'Off'},"
            f"lowering={'On' if lowering else 'Off'},"
            f"throughput_tok_s={tput:.2f}")
    base = out[(False, False)]
    both = out[(True, True)]
    csv(f"table3_sim,combined_gain,{both / base:.2f}x")
    assert both > out[(True, False)] and both > out[(False, True)] > base
    return out


def run(csv=print) -> dict:
    real = run_real(csv)
    sim = run_sim(csv)
    # directionality of the real measurement: fused control beats per-layer
    # host dispatch (the dominant effect at CPU scale)
    assert real[(False, True)] > real[(False, False)]
    return {"real": real, "sim": sim}


if __name__ == "__main__":
    run()
