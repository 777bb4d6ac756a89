"""Build the system under test from a configuration file.

``chipbench/configs/<config>.json`` holds the published configuration of
the first model at its top level (with the keys changed for the run named
in ``BENCHMARK.json``'s ``reduced``), every colocated model under
``models`` with its sizes ``as_run``, and the engine's settings under
``engine``.  The harness checks that each ``as_run`` block describes all
that the repository's own model configuration runs, builds the engine,
and hands it the benchmark's seeded weights.

A model's entry may name the plain reference that computes it
(``reference``: a module under ``chipbench/cb/``, default
``cb.reference``) and the class that counts its work (``work``:
``<module>.<class>``, default ``cb.work.ModelShape``).  A model that
neither default describes brings its own as new files.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
from pathlib import Path
from typing import Dict, List, Tuple

import jax

from cb.traffic import key_from_seed
from cb.weights import make_params

DEFAULT_REFERENCE = "cb.reference"
DEFAULT_WORK = "cb.work.ModelShape"

#: ``as_run`` keys named otherwise than the ModelConfig field they give.
ALIASES = {"vocab": "vocab_size", "ffn": "family"}
#: ModelConfig fields a file need not give, each with why it does not
#: change the forward pass.
EXEMPT = {
    "name": "the model's key under ``models`` names it",
    "source": "a provenance note",
    "max_position": "the longest context the model may serve: RoPE is "
                    "computed from each position, and the engine's "
                    "``max_ctx`` bounds the positions that run",
}


def load_config(path: Path) -> Dict:
    cfg = json.loads(Path(path).read_text())
    for key in ("models", "engine"):
        if key not in cfg:
            raise ValueError(f"{path}: no {key!r} block")
    return cfg


def _benchmark_module(name: str):
    if not name.startswith("cb."):
        raise ValueError(f"{name!r}: a model's reference or work count is a "
                         f"module of the benchmark's own (cb.<name>)")
    return importlib.import_module(name)


def reference_module(model: Dict):
    """The module of one model's plain reference (``Reference``,
    ``served_gaps``, ``QUANTIZERS``, ``KEYS``: ``cb.reference`` describes
    them), after checking that it computes every key of ``as_run``."""
    mod = _benchmark_module(model.get("reference", DEFAULT_REFERENCE))
    unknown = sorted(set(model["as_run"]) - mod.KEYS)
    if unknown:
        raise ValueError(f"{mod.__name__} does not compute {unknown}")
    return mod


def work_class(model: Dict):
    """The class that counts one model's work: ``from_dict(name, as_run)``
    and ``decode_step(ctx) -> (bytes, operations)``."""
    module, _, cls = model.get("work", DEFAULT_WORK).rpartition(".")
    return getattr(_benchmark_module(module), cls)


def shapes(cfg: Dict) -> Dict:
    return {n: work_class(m).from_dict(n, m["as_run"])
            for n, m in cfg["models"].items()}


def _mismatches(given: Dict, obj, exempt=()) -> List[str]:
    """Every field of the dataclass ``obj`` that ``given`` (field name to
    value) states otherwise than ``obj`` holds it, or leaves out while
    ``obj`` holds it off its default."""
    wrong = []
    for f in dataclasses.fields(obj):
        if f.name in exempt:
            continue
        have, default = getattr(obj, f.name), f.default
        if f.name in given:
            if given[f.name] != have:
                wrong.append(f"{f.name}: file {given[f.name]!r}, program "
                             f"{have!r}")
        elif default is dataclasses.MISSING:
            wrong.append(f"{f.name}: not in the file, program {have!r}")
        elif have != default:
            wrong.append(f"{f.name}: not in the file, program {have!r} "
                         f"(default {default!r})")
    return wrong


def describes(run: Dict, mc) -> List[str]:
    """How ``as_run`` fails to describe the ModelConfig ``mc``: a key that
    names no field, a field given otherwise, or a field left out that the
    program runs off its default (``EXEMPT`` aside).  The keys of
    multi-head latent attention describe ``mc.mla``, field by field."""
    from repro.configs.base import MLAConfig
    names = {f.name for f in dataclasses.fields(mc)} - {"mla"}
    mla_names = {f.name for f in dataclasses.fields(MLAConfig)}
    given, mla, wrong = {}, {}, []
    for key, value in run.items():
        field = ALIASES.get(key, key)
        if field in mla_names:
            mla[field] = value
        elif field in names:
            given[field] = value
        else:
            wrong.append(f"{key}: names no field of the program's model "
                         f"configuration")
    wrong += _mismatches(given, mc, exempt=set(EXEMPT) | {"mla"})
    if mla and mc.mla is not None:
        wrong += _mismatches(mla, mc.mla)
    elif mla or mc.mla is not None:
        wrong.append(f"mla: file {sorted(mla) or None}, program {mc.mla!r}")
    return wrong


def repo_models(cfg: Dict):
    """The repository's ModelConfig of every model, cut to ``as_run``'s
    depth, after checking that ``as_run`` describes all of it."""
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
        wrong = describes(run, mc)
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
