"""The trace reduction: busy union, idle share, top ops, gaps by host."""
import json

import pytest

import smoke
from cb.trace import load_xplane, reduce_trace, trim

FIXTURE = smoke.BENCH_DIR / "fixtures" / "trace_v5e_decode.json"


def _trace(ops, host, window=(0, 100)):
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["cb.window", window[0], window[1] - window[0]]] + host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit_step", 0, 100]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["other", 0, 100]]}]},
    ]}


def test_busy_union_idle_and_gaps_by_hand():
    ops = [["fusion.1", 10, 20],     # 10-30
           ["copy.2", 25, 15],       # 25-40 overlaps: union 10-40
           ["fusion.1", 60, 10],     # 60-70
           ["copy.2", 95, 20]]       # 95-115, clipped to 95-100
    host = [["cb.step", 0, 50], ["cb.book", 40, 15], ["cb.idle", 55, 45]]
    r = reduce_trace(_trace(ops, host))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)          # 30 + 10 + 5
    assert r["idle_share"] == pytest.approx(0.55)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 30e-9, "copy.2": 20e-9})
    # gaps: 0-10 (step), 40-60 (mid 50: book, opened after step), 70-95
    # (idle)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"cb.step": 10e-9, "cb.book": 20e-9, "cb.idle": 25e-9})


def test_device_time_of_each_step_by_hand():
    ops = [["a", 10, 20], ["b", 25, 15], ["c", 60, 10], ["d", 95, 20]]
    host = [["cb.step", 5, 30], ["cb.book", 35, 5], ["cb.step", 50, 30],
            ["cb.step", 90, 10], ["cb.step", 100, 10]]   # last: after window
    r = reduce_trace(_trace(ops, host))
    # busy union 10-40, 60-70, 95-100: step 5-35 holds 25, 50-80 holds 10,
    # 90-100 holds 5
    assert r["steps"] == pytest.approx([25e-9, 10e-9, 5e-9])


def test_no_window_or_no_ops_gives_nothing():
    assert reduce_trace(_trace([], [])) is None
    t = _trace([["x", 1, 2]], [])
    t["planes"][0]["lines"][0]["events"] = []
    assert reduce_trace(t) is None


def test_trim_keeps_what_the_reduction_reads():
    ops = [[f"op.{i}", 10 + 3 * i, 2] for i in range(20)]
    t = trim(_trace(ops, [["cb.step", 0, 90]]), max_ops=5)
    r = reduce_trace(t)
    assert r["n_ops"] == 5
    assert r["busy_s"] == pytest.approx(10e-9)


def test_chip_fixture():
    """A trace recorded on a TPU v5e in a decode window, trimmed."""
    t = json.loads(FIXTURE.read_text())
    r = reduce_trace(t)
    exp = t["expected"]
    assert r["n_ops"] == exp["n_ops"]
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(exp["idle_share"], rel=1e-9)
    assert 0.0 < r["idle_share"] < 1.0
    assert r["device_ops"][0][0] == exp["top_op"]


def test_load_xplane_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("cb.window"):
        with TraceAnnotation("cb.step"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    from cb.trace import find_xplane
    t = load_xplane(find_xplane(str(tmp_path)))
    names = {e[0] for pl in t["planes"] for ln in pl["lines"]
             for e in ln["events"]}
    assert {"cb.window", "cb.step"} <= names
