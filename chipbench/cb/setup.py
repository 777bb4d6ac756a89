"""Build the system under test from a configuration file.

``chipbench/configs/<config>.json`` holds the published configuration of
the first model at its top level (with the keys changed for the run named
in ``BENCHMARK.json``'s ``reduced``), every colocated model under
``models`` with its sizes ``as_run``, and the engine's settings under
``engine``.  The harness checks that each ``as_run`` block is what the
repository's own model configuration runs, builds the engine, and hands
it the benchmark's seeded weights.
"""
from __future__ import annotations

import contextlib
import json
from pathlib import Path
from typing import Dict, Tuple

import jax

from cb.traffic import key_from_seed
from cb.weights import make_params
from cb.work import ModelShape

#: ``as_run`` keys and the repository ModelConfig fields they must equal.
_FIELDS = {
    "n_layers": "n_layers", "d_model": "d_model", "vocab": "vocab_size",
    "attention": "attention", "n_heads": "n_heads",
    "n_kv_heads": "n_kv_heads", "head_dim": "head_dim", "d_ff": "d_ff",
    "n_experts": "n_experts", "experts_per_token": "experts_per_token",
    "rope_theta": "rope_theta", "norm_eps": "norm_eps",
    "capacity_factor": "capacity_factor",
}
_MLA_FIELDS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim")


def load_config(path: Path) -> Dict:
    cfg = json.loads(Path(path).read_text())
    for key in ("models", "engine"):
        if key not in cfg:
            raise ValueError(f"{path}: no {key!r} block")
    return cfg


def shapes(cfg: Dict) -> Dict[str, ModelShape]:
    return {n: ModelShape.from_dict(n, m["as_run"])
            for n, m in cfg["models"].items()}


def repo_models(cfg: Dict):
    """The repository's ModelConfig of every model, cut to ``as_run``'s
    depth, after checking every size against the file."""
    from repro.configs import get_config, get_smoke_config
    preset = get_smoke_config if cfg.get("repo_preset") == "smoke" \
        else get_config
    out = {}
    for name, m in cfg["models"].items():
        run = m["as_run"]
        mc = preset(name).replace(n_layers=int(run["n_layers"]))
        over = dict(m.get("repo_overrides", {}))
        if "mla" in over:
            over["mla"] = type(mc.mla)(**over["mla"])
        if over:
            mc = mc.replace(**over)
        wrong = []
        for key, field in _FIELDS.items():
            if key in run and getattr(mc, field) != run[key]:
                wrong.append(f"{key}: file {run[key]!r}, program "
                             f"{getattr(mc, field)!r}")
        if run["attention"] == "mla":
            for key in _MLA_FIELDS:
                if getattr(mc.mla, key) != run[key]:
                    wrong.append(f"{key}: file {run[key]!r}, program "
                                 f"{getattr(mc.mla, key)!r}")
        want_ffn = "moe" if mc.is_moe else "dense"
        if run["ffn"] != want_ffn or mc.n_shared_experts or \
                mc.dtype != "bfloat16":
            wrong.append(f"ffn {run['ffn']} vs {want_ffn}, shared experts "
                         f"{mc.n_shared_experts}, dtype {mc.dtype}")
        if wrong:
            raise ValueError(f"{name}: the configuration file does not "
                             f"describe what runs: {'; '.join(wrong)}")
        out[name] = mc
    return out


def model_keys(cfg: Dict, seed: int) -> Dict[str, int]:
    return {n: key_from_seed(seed, salt=i)
            for i, n in enumerate(cfg["models"])}


def seeded_params(model_cfg, key_int: int, device=None):
    """One model's weights from the seed, in the engine's layout."""
    from repro.models import build_model
    return make_params(build_model(model_cfg).param_specs(), key_int, device)


@contextlib.contextmanager
def _weights_from_seed(models, keys: Dict[str, int], device):
    """The engine makes each model's weights through the model constructor
    it imports; while the engine is being built, that constructor's
    ``init`` returns the benchmark's seeded weights instead (one model at
    a time, so only one full tree is on the device at once)."""
    import repro.runtime.engine as engine_mod
    real = engine_mod.build_model

    class _Seeded:
        def __init__(self, mc):
            self.mc = mc

        def init(self, _key):
            return seeded_params(self.mc, keys[self.mc.name], device)

        def __getattr__(self, attr):
            return getattr(real(self.mc), attr)

    engine_mod.build_model = _Seeded
    try:
        yield
    finally:
        engine_mod.build_model = real


def build_engine(cfg: Dict, seed: int) -> Tuple[object, Dict]:
    """(engine, repository model configs) for one configuration file."""
    from repro.configs import EngineConfig
    from repro.runtime.engine import CrossPoolEngine, EngineMode
    from repro.runtime.observe import EngineObserver
    models = repo_models(cfg)
    eng = cfg["engine"]
    mode = EngineMode(pipeline=bool(eng["pipeline"]),
                      lowering=bool(eng["lowering"]))
    with _weights_from_seed(models, model_keys(cfg, seed),
                            jax.devices()[0]):
        engine = CrossPoolEngine(
            models, page_budget=int(eng["kv_pool_bytes"])
            // int(eng["page_bytes"]),
            page_bytes=int(eng["page_bytes"]),
            max_batch=int(eng["max_batch"]), max_ctx=int(eng["max_ctx"]),
            config=EngineConfig(mode=mode), observer=EngineObserver())
    return engine, models
