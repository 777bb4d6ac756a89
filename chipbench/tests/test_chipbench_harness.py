"""The harness refuses to run without a TPU, or without the program, and
refuses a configuration file that does not describe what runs; a model's
entry may name its own reference and work count."""
import copy
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import smoke
from cb import check, setup
from cb.traffic import prompt_ids
from cb.work import ModelShape, decode_step_work

RUN = smoke.BENCH_DIR / "run.py"


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script), "--workload", "colo2-fused.decode",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(smoke.BENCH_DIR.parent, RUN)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(smoke.BENCH_DIR.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(smoke.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "chipbench" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_names_files_that_exist():
    bench = json.loads((smoke.BENCH_DIR.parent / "BENCHMARK.json")
                       .read_text())
    root = smoke.BENCH_DIR.parent
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for m in names:
        # a quantity split by cell is read by its base name's reader
        assert any((smoke.BENCH_DIR / "metrics" / f"{n}.py").exists()
                   for n in (m, m.split(".")[0])), m
    for c in bench["configs"]:
        assert (root / c["file"]).exists()
    for w in bench["workloads"]:
        assert (smoke.BENCH_DIR / "traffic" / f"{w['traffic']}.json")\
            .exists()


# ---------------------------------------------------------------------------
# a model's own reference and work count; as_run describes all that runs
# ---------------------------------------------------------------------------

MOONSHOT, MINICPM3 = list(smoke.CONFIG["models"])

STUB_REFERENCE = '''
import numpy as np

QUANTIZERS = {"fp8": None}
KEYS = frozenset({"n_layers", "d_model", "vocab", "attention", "n_heads",
                  "n_kv_heads", "head_dim", "ffn", "d_ff", "n_experts",
                  "experts_per_token", "capacity_factor", "rope_theta",
                  "norm_eps"})


class Reference:
    def __init__(self, m, length, max_ctx, mode="f32"):
        self.mode = mode


def served_gaps(ref, params, prompt, served, controls=()):
    return np.full((len(served), 1 + len(controls)), 7.0, np.float32)
'''

STUB_WORK = '''
class Count:
    def __init__(self, name):
        self.name = name

    @classmethod
    def from_dict(cls, name, as_run):
        return cls(name)

    def decode_step(self, ctx):
        return 1000.0 * len(ctx), 10.0 * len(ctx)
'''


def _smoke_config():
    return copy.deepcopy(smoke.CONFIG)


def _samples(cfg, seed=3):
    """Two requests per model with arbitrary served tokens."""
    out = {}
    for i, (name, m) in enumerate(cfg["models"].items()):
        vocab = m["as_run"]["vocab"]
        out[name] = [SimpleNamespace(
            prompt=prompt_ids(seed, 10 * i + j, 12 + 5 * j, vocab),
            tokens=[int(t) for t in prompt_ids(seed + 1, 10 * i + j, 6,
                                               vocab)])
            for j in range(2)]
    return out


@pytest.fixture
def own_modules(tmp_path, monkeypatch):
    """``cb.stub_reference`` and ``cb.stub_work``, as a configuration's own
    new files would add them."""
    (tmp_path / "cb").mkdir()
    (tmp_path / "cb" / "stub_reference.py").write_text(STUB_REFERENCE)
    (tmp_path / "cb" / "stub_work.py").write_text(STUB_WORK)
    monkeypatch.syspath_prepend(str(tmp_path))
    for mod in ("cb.stub_reference", "cb.stub_work"):
        monkeypatch.delitem(sys.modules, mod, raising=False)
    yield
    for mod in ("cb.stub_reference", "cb.stub_work"):
        sys.modules.pop(mod, None)


def test_a_model_is_judged_by_the_reference_its_entry_names(own_modules):
    cfg = _smoke_config()
    cfg["models"][MOONSHOT]["reference"] = "cb.stub_reference"
    readings = check.compare(cfg, setup.repo_models(cfg), _samples(cfg), 3)
    assert all(np.all(g == 7.0)
               for g in readings[MOONSHOT]["token_gaps"])
    assert not any(np.any(g == 7.0)
                   for g in readings[MINICPM3]["token_gaps"])
    checks, correct = check.judge(cfg, {n: rd["token_gaps"]
                                        for n, rd in readings.items()})
    assert checks["moonshot_mean_gap"]["value"] == pytest.approx(7.0)
    assert not correct


def test_the_default_reference_is_cb_reference_called_directly():
    import jax
    from cb.reference import Reference, served_gaps
    cfg = _smoke_config()
    seed = 5
    samples = _samples(cfg, seed)
    models = setup.repo_models(cfg)
    readings = check.compare(cfg, models, samples, seed)
    max_ctx = cfg["engine"]["max_ctx"]
    for name, reqs in samples.items():
        m = cfg["models"][name]["as_run"]
        params = setup.seeded_params(
            models[name], setup.model_keys(cfg, seed)[name],
            jax.devices()[0])
        ref = Reference(m, max_ctx, max_ctx, "f32")
        for r, got in zip(reqs, readings[name]["token_gaps"]):
            want = served_gaps(ref, params, r.prompt, r.tokens)[:, 0]
            assert np.array_equal(got, want)


def _shared_experts_unstated(cfg):
    cfg["models"][MOONSHOT]["repo_overrides"] = {"n_shared_experts": 1}


def _unknown_key(cfg):
    cfg["models"][MINICPM3]["as_run"]["router_scale"] = 2.5


def _latent_rank_left_out(cfg):
    del cfg["models"][MINICPM3]["as_run"]["q_lora_rank"]


def _latent_key_on_a_model_without(cfg):
    cfg["models"][MOONSHOT]["as_run"]["kv_lora_rank"] = 16


@pytest.mark.parametrize("edit, field", [
    (_shared_experts_unstated, "n_shared_experts"),
    (_unknown_key, "router_scale"),
    (_latent_rank_left_out, "q_lora_rank"),
    (_latent_key_on_a_model_without, "mla"),
])
def test_a_file_that_does_not_describe_what_runs_is_refused(
        edit, field, monkeypatch):
    import repro.runtime.engine as engine_mod

    def never(*a, **kw):
        raise AssertionError("the engine was built")
    monkeypatch.setattr(engine_mod, "CrossPoolEngine", never)
    cfg = _smoke_config()
    edit(cfg)
    with pytest.raises(ValueError, match=field):
        setup.build_engine(cfg, 1)


def test_a_stated_field_off_its_default_is_accepted():
    cfg = _smoke_config()
    cfg["models"][MOONSHOT]["repo_overrides"] = {"n_shared_experts": 1}
    cfg["models"][MOONSHOT]["as_run"]["n_shared_experts"] = 1
    assert setup.repo_models(cfg)[MOONSHOT].n_shared_experts == 1
    # the default reference and work count do not compute shared experts
    with pytest.raises(ValueError, match="n_shared_experts"):
        setup.shapes(cfg)
    with pytest.raises(ValueError, match="n_shared_experts"):
        setup.reference_module(cfg["models"][MOONSHOT])


def test_the_work_count_refuses_a_key_it_does_not_model():
    with pytest.raises(ValueError, match="n_shared_experts"):
        ModelShape.from_dict("m", smoke.MOONSHOT | {"n_shared_experts": 2})
    # keys that change no byte or operation pass
    assert ModelShape.from_dict("m", smoke.MOONSHOT).n_experts == 8


def test_a_model_is_counted_by_the_work_class_its_entry_names(own_modules):
    cfg = _smoke_config()
    cfg["models"][MINICPM3]["work"] = "cb.stub_work.Count"
    shapes = setup.shapes(cfg)
    assert type(shapes[MINICPM3]).__name__ == "Count"
    assert isinstance(shapes[MOONSHOT], ModelShape)
    nb, nf = decode_step_work(shapes, {MINICPM3: [3, 9, 27]})
    assert (nb, nf) == (3000.0, 30.0)
    both = decode_step_work(shapes, {MINICPM3: [3], MOONSHOT: [3]})
    assert both == tuple(a + b for a, b in zip(
        (1000.0, 10.0), shapes[MOONSHOT].decode_step([3])))


@pytest.mark.parametrize("entry", [
    {"reference": "os.path", "as_run": {}},
    {"work": "json.JSONDecoder", "as_run": {}}])
def test_only_the_benchmarks_own_modules_are_named(entry):
    with pytest.raises(ValueError, match="cb."):
        if "reference" in entry:
            setup.reference_module(entry)
        else:
            setup.work_class(entry)


_BENCH = json.loads((smoke.BENCH_DIR.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("conf", [c["file"] for c in _BENCH["configs"]])
def test_every_benchmark_configuration_describes_what_runs(conf):
    cfg = setup.load_config(smoke.BENCH_DIR.parent / conf)
    models = setup.repo_models(cfg)
    assert set(models) == set(cfg["models"])
    shapes = setup.shapes(cfg)
    for name, m in cfg["models"].items():
        assert shapes[name].decode_step([5])[0] > 0
        mod = setup.reference_module(m)
        mod.Reference(m["as_run"], 16, 16)
        assert cfg["control"] in mod.QUANTIZERS
