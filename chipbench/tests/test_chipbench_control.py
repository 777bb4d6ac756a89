"""The control of the correctness check, at smoke widths on the CPU.

Tokens that the float32 reference itself chose greedily read a gap of 0
against it; a lower-precision control, put in the program's place on the
same prompts and tokens, puts another token first somewhere and reads above
0.  Judged by the harness's own comparison in the program's place, the
control comes out as not correct while the program's run is correct.
"""
import jax
import numpy as np
import pytest

import smoke
from cb import setup
from cb.reference import Reference, served_gaps
from cb.traffic import prompt_ids

WIDE = {"d_model": 128, "vocab": 4096}


def _greedy(ref, params, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        x = ref.hidden(params, ids, len(prompt))
        ids.append(int(ref.argmax(params, x)[len(ids) - 1]))
    return ids[len(prompt):]


@pytest.mark.parametrize("name", list(smoke.CONFIG["models"]))
def test_reference_tokens_read_zero_and_the_control_does_not(name):
    m = dict(smoke.CONFIG["models"][name]["as_run"], **WIDE)
    cfg = {"repo_preset": "smoke", "engine": smoke.CONFIG["engine"],
           "models": {name: {"as_run": m, "repo_overrides": {
               "d_model": m["d_model"], "vocab_size": m["vocab"]}}}}
    mc = setup.repo_models(cfg)[name]
    ref = Reference(m, 64, 64, "f32")
    ctl = Reference(m, 64, 64, "fp8")
    control_max = []
    for seed in (1, 2, 3):
        params = setup.seeded_params(mc, setup.model_keys(cfg, seed)[name],
                                     jax.devices()[0])
        prompt = prompt_ids(seed, 0, 24, m["vocab"])
        served = _greedy(ref, params, prompt, 16)
        g = served_gaps(ref, params, prompt, served, [ctl])
        assert np.all(g[:, 0] == 0.0)
        control_max.append(float(g[:, 1].max()))
    assert max(control_max) > 0.0, control_max


def test_statistics_over_every_served_token():
    from cb.check import statistic
    gaps = [np.array([0.0, 0.4, 0.1]), np.array([0.2])]
    assert statistic("widest", gaps) == pytest.approx(0.4)
    assert statistic("mean", gaps) == pytest.approx(0.175)
    with pytest.raises(ValueError):
        statistic("median", gaps)


@pytest.mark.parametrize("workload", ["smoke.decode", "smoke.chat"])
def test_control_in_the_programs_place_is_not_correct(tmp_path, workload):
    out = smoke.run(tmp_path, workload, seconds=1.5, seed=11,
                    controls=(smoke.CONFIG["control"],))
    assert out["correct"], out["checks"]
    ctl = out["controls"][smoke.CONFIG["control"]]
    assert not ctl["correct"], ctl["checks"]
    assert set(ctl["checks"]) == set(out["checks"])


def test_fp8_rounding_is_float8_e4m3fn():
    import jax.numpy as jnp
    import ml_dtypes
    from cb.reference import _q_fp8
    rng = np.random.default_rng(0)
    a = (rng.standard_normal((16, 200))
         * np.exp(3 * rng.standard_normal((16, 1)))).astype(np.float32)
    a[0, :4] = [1e-9, -3e-7, 0.0, 2.0]
    s = np.abs(a).max(-1, keepdims=True) / 448.0
    want = (a / s).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * s
    assert np.array_equal(np.asarray(_q_fp8(jnp.asarray(a), -1)), want)
