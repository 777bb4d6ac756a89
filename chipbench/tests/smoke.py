"""A benchmark at smoke widths for the CPU tests: the same harness, the
repository's smoke presets of the two models, and small mixes."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
BENCH_DIR = HERE.parent
for p in (BENCH_DIR, BENCH_DIR.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

MOONSHOT = {"n_layers": 2, "d_model": 64, "vocab": 256, "attention": "gqa",
            "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "ffn": "moe",
            "d_ff": 32, "n_experts": 8, "experts_per_token": 2,
            "capacity_factor": 1.25, "rope_theta": 50000.0, "norm_eps": 1e-6}
MINICPM3 = {"n_layers": 2, "d_model": 64, "vocab": 256, "attention": "mla",
            "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "q_lora_rank": 32,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "ffn": "dense",
            "d_ff": 128, "rope_theta": 10000.0, "norm_eps": 1e-6}

CONFIG = {
    "repo_preset": "smoke",
    "models": {
        "moonshot-v1-16b-a3b": {"as_run": MOONSHOT, "checks": [{
            "name": "moonshot_mean_gap", "statistic": "mean", "limit": 0.5}]},
        "minicpm3-4b": {"as_run": MINICPM3, "checks": [{
            "name": "minicpm3_widest_gap", "statistic": "widest",
            "limit": 0.06}]},
    },
    # at these widths the program reads minicpm3_widest_gap 0 to 0.021
    # and the fp8 control 0.11 to 0.42 (seeds 1-5 of both mixes)
    "control": "fp8",
    "engine": {"lowering": True, "pipeline": True, "page_bytes": 4096,
               "kv_pool_bytes": 8 << 20, "max_batch": 4, "max_ctx": 64,
               "chips": 1},
}

TRAFFIC = {
    "decode": {"loop": "closed",
               "models": {"moonshot-v1-16b-a3b": {"clients": 1},
                          "minicpm3-4b": {"clients": 4}},
               "prompt": {"dist": "uniform", "min": 8, "max": 32},
               "output": {"dist": "uniform", "min": 4, "max": 12},
               "levels": 3, "warm_batch": 2, "check_requests": 2,
               "max_ctx": 64},
    "chat": {"loop": "open", "rate_per_s": 4.0, "lead_s": 0.5,
             "models": {"moonshot-v1-16b-a3b": {"share": 0.5},
                        "minicpm3-4b": {"share": 0.5}},
             "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                        "min": 8, "max": 40},
             "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "min": 3, "max": 12},
             "levels": 3, "warm_batch": 2, "check_requests": 2,
             "max_ctx": 64},
}


def make_root(tmp: Path) -> Path:
    """A directory holding a smoke BENCHMARK.json, config and mixes."""
    (tmp / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "traffic").mkdir(exist_ok=True)
    (tmp / "configs" / "smoke.json").write_text(json.dumps(CONFIG))
    for name, t in TRAFFIC.items():
        (tmp / "traffic" / f"{name}.json").write_text(json.dumps(t))
    real = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    bench = {
        "configs": [{"name": "smoke", "file": "configs/smoke.json"}],
        "workloads": [
            {"name": f"smoke.{t}", "config": "smoke", "traffic": t,
             "chips": 1} for t in TRAFFIC],
        "end_to_end": [dict(m, workloads=[f"smoke.{t}" for t in TRAFFIC])
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[f"smoke.{t}" for t in TRAFFIC])
                      for m in real["per_layer"]],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def args(workload: str, seed: int = 7, seconds: float = 2.0,
         trace: int = 0):
    return SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                           trace=trace, out="")


def harness():
    """``chipbench/run.py`` as a module."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(tmp: Path, workload: str, fault=None, controls=(), **kw) -> dict:
    bench_run = harness()
    root = make_root(tmp)
    return bench_run.run_cell(args(workload, **kw), require_tpu=False,
                              root=root, traffic_dir=root / "traffic",
                              fault=fault, controls=controls)
