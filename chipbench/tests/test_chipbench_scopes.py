"""Device self time by stage scope, the stage split of the work, and the
readers built on them, on synthetic traces and runs and on a trimmed chip
trace."""
import json

import pytest

import smoke
from cb import scopes
from cb.load import Req, Step
from cb.stage_work import decode_step_stage_bytes, stage_bytes
from cb.trace import reduce_trace
from cb.work import ModelShape, decode_step_work

FIXTURE = smoke.BENCH_DIR / "fixtures" / "trace_v5e_decode_scoped.json"
GQA = ModelShape.from_dict("m", smoke.MOONSHOT | {"n_experts": 8})
MLA = ModelShape.from_dict("c", smoke.MINICPM3)
PREFIX = "jit(decode_m)/loop/while/body/closed_call/"


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, smoke.BENCH_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace(ops, host, window=(0, 200)):
    """``ops``: (name, start, dur, op_name or None[, program])."""
    names = sorted({n for o in ops for n in o[3:] if n})
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": [
            ["cb.window", window[0], window[1] - window[0]]] + host}]},
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": [list(o[:3]) for o in ops],
            "op_names": names,
            "op_name_of": [names.index(o[3]) if o[3] else -1
                           for o in ops],
            "program_of": [names.index(o[4]) if o[4:] else -1
                           for o in ops]}]},
    ]}


def test_scope_of_names_the_stage_chain():
    assert scopes.scope_of(PREFIX + "attn/kv_write/scatter") == \
        "attn/kv_write"
    assert scopes.scope_of(PREFIX + "ffn/unpack/jit(_where)/select_n") == \
        "ffn/unpack"
    assert scopes.scope_of("jit(decode_m)/loop/while") == "loop"
    assert scopes.scope_of("jit(decode_m)/loop/head/argmax") == "head"
    assert scopes.scope_of("jit(prefill_attn_m)/prefill_attn/dot") == \
        "prefill_attn"
    assert scopes.scope_of("jit(decode_m)/copy") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def test_nested_loop_op_counts_self_time_only():
    # a layer loop 0-100 holding two body ops; a head op after it; an op
    # without metadata
    ops = [("%while.1", 0, 100, "jit(decode_m)/loop/while"),
           ("%fusion.2", 10, 20, PREFIX + "attn/kv_write/scatter"),
           ("%fusion.3", 40, 30, PREFIX + "ffn/unpack/gather"),
           ("%fusion.4", 110, 10, "jit(decode_m)/head/dot"),
           ("%copy.5", 130, 5, None)]
    host = [["cb.step", 0, 125], ["cb.step", 125, 50]]
    t = _trace(ops, host)
    got = scopes.step_scopes(t)
    assert got[0] == pytest.approx({"loop": 50e-9, "attn/kv_write": 20e-9,
                                    "ffn/unpack": 30e-9, "head": 10e-9})
    # the op with no op_name takes the scope of the op before it
    assert got[1] == pytest.approx({"head": 5e-9})
    assert scopes.step_scopes(t, inherit=False)[1] == \
        pytest.approx({scopes.UNSCOPED: 5e-9})
    # the scopes of each step sum to its busy time
    steps = reduce_trace(t)["steps"]
    assert [sum(s.values()) for s in got] == pytest.approx(steps)


def test_unnamed_op_inherits_within_its_own_program():
    # two programs interleave; the unnamed ops follow a named op of their
    # own program, or none
    ops = [("%copy.1", 0, 5, None, "jit_decode_b"),
           ("%fusion.2", 10, 10, PREFIX + "attn/kv_write/x", "jit_decode_a"),
           ("%fusion.3", 20, 10, "jit(decode_b)/head/dot", "jit_decode_b"),
           ("%copy.4", 30, 10, None, "jit_decode_a"),
           ("%copy.5", 40, 10, None, "jit_decode_b")]
    got = scopes.step_scopes(_trace(ops, [["cb.step", 0, 60]]))
    assert got == [pytest.approx({scopes.UNSCOPED: 5e-9,
                                  "attn/kv_write": 20e-9,
                                  "head": 20e-9})]


def test_self_segments_of_a_loop_in_a_loop():
    segs = scopes.self_segments([(0, 100, "loop"), (10, 60, "loop"),
                                 (20, 30, "attn"), (70, 80, "ffn")])
    assert segs == [(0, 10, "loop"), (10, 20, "loop"), (20, 30, "attn"),
                    (30, 60, "loop"), (60, 70, "loop"), (70, 80, "ffn"),
                    (80, 100, "loop")]


def test_step_clipped_to_the_window():
    ops = [("%fusion.1", 150, 100, PREFIX + "ffn/experts/dot")]
    got = scopes.step_scopes(_trace(ops, [["cb.step", 140, 200]]))
    assert got == [pytest.approx({"ffn/experts": 50e-9})]


def test_seconds_under_a_stage():
    sc = {"attn/kv_write": 1.0, "attn/kv_view": 2.0, "attn": 0.5,
          "ffn/unpack": 4.0, "loop": 8.0}
    assert scopes.seconds_under(sc, "attn") == 3.5
    assert scopes.seconds_under(sc, *scopes.RELAYOUT) == 3.0
    assert scopes.seconds_under(sc, "ffn") == 4.0


def test_trim_scoped_keeps_what_the_reduction_reads():
    ops = [("%while.1", 0, 100, "jit(decode_m)/loop/while"),
           ("%fusion.2 = bf16[8] fusion(x)", 10, 20,
            PREFIX + "attn/kv_write/scatter")]
    t = _trace(ops, [["cb.step", 0, 150], ["cp.step", 1, 140]])
    t["planes"][0]["lines"][0]["args"] = [{}, {}, {"step": "3"}]
    small = json.loads(json.dumps(scopes.trim_scoped(t)))
    assert scopes.step_scopes(small) == scopes.step_scopes(t)
    host = small["planes"][0]["lines"][0]
    assert host["args"][host["events"].index(["cp.step", 1, 140])] == \
        {"step": "3"}
    assert small["planes"][1]["lines"][0]["events"][1][0] == "%fusion.2"


def test_trim_scoped_keeps_whole_steps():
    ops = [(f"%fusion.{i}", 10 * i, 5, PREFIX + "ffn/experts/dot")
           for i in range(10)]
    host = [["cb.step", 0, 48], ["cb.step", 50, 48]]
    small = scopes.trim_scoped(_trace(ops, host), max_ops=7)
    # the 7 ops reach into the second step, which is left out whole
    assert small["planes"][0]["lines"][0]["events"][0] == ["cb.window", 0,
                                                           50]
    assert scopes.step_scopes(small) == [pytest.approx(
        {"ffn/experts": 25e-9})]


@pytest.mark.parametrize("shape", [GQA, MLA], ids=["gqa_moe", "mla_dense"])
@pytest.mark.parametrize("ctx", [[10], [10, 20, 300], []])
def test_stage_bytes_sum_to_the_step_bytes(shape, ctx):
    parts = stage_bytes(shape, ctx)
    assert sum(parts.values()) == pytest.approx(shape.decode_step(ctx)[0],
                                                rel=1e-12)
    shapes = {"m": GQA, "c": MLA}
    ctx_by = {"m": [5, 6], "c": ctx}
    both = decode_step_stage_bytes(shapes, ctx_by)
    assert sum(both.values()) == pytest.approx(
        decode_step_work(shapes, ctx_by)[0], rel=1e-12)


def test_stage_bytes_by_hand():
    # GQA MoE, one request at ctx 10: 2 layers; KV 2 x 4 x 16 bf16 a token
    kv_tok = 2 * 4 * 16 * 2
    parts = stage_bytes(GQA, [10])
    attn = 4 * 64 * 64
    assert parts["attn"] == 2 * (attn * 2 + 2 * 64 * 2 + 11 * kv_tok
                                 + kv_tok)
    assert parts["ffn"] == pytest.approx(2 * (64 * 8 * 4 + 2 * 3 * 64 * 32
                                              * 2))
    assert parts["head"] == 64 * 2 + 64 * 256 * 2 + 64 * 2


def _run(step_scopes, ctx=None):
    ctx = ctx or {"m": [10, 20], "c": [30]}
    steps = [Step(0.0, 1.0, False, ctx, 3, device_s=2e-3),
             Step(1.0, 2.0, True, ctx, 3, device_s=3e-3),     # a prompt pass
             Step(2.0, 3.0, False, ctx, 3, device_s=2e-3),
             Step(3.0, 4.0, False, ctx, 3)]                   # not traced
    return {"steps": steps, "window": (0.0, 4.0), "window_open_until": 4.0,
            "shapes": {"m": GQA, "c": MLA}, "chips_used": 1,
            "peaks": {"hbm_bytes_per_s": 1e12}, "step_scopes": step_scopes,
            "requests": []}


def test_readers_on_a_synthetic_run():
    sc = {"attn/kv_write": 0.4e-3, "attn/kv_view": 0.6e-3, "attn": 0.2e-3,
          "attn/attention": 0.1e-3, "ffn/unpack": 0.5e-3, "ffn": 0.1e-3,
          "head": 0.1e-3}
    prompt = {"prefill_attn": 3e-3}
    run = _run([sc, prompt, sc])
    assert _reader("kv_relayout_ms_per_step").read(run) == \
        pytest.approx(1.0)
    parts = decode_step_stage_bytes(run["shapes"], run["steps"][0].ctx)
    assert _reader("attention_hbm_roofline").read(run) == pytest.approx(
        100 * 2 * parts["attn"] / (2 * 1.3e-3 * 1e12))
    assert _reader("ffn_hbm_roofline").read(run) == pytest.approx(
        100 * 2 * parts["ffn"] / (2 * 0.6e-3 * 1e12))


def test_readers_without_scopes_read_nothing():
    for name in ("kv_relayout_ms_per_step", "attention_hbm_roofline",
                 "ffn_hbm_roofline"):
        assert _reader(name).read(_run(None)) is None
        assert _reader(name).read(_run([{}])) is None      # misaligned
    assert _reader("first_token_hold_p50_ms").read(_run(None)) is None


def test_first_token_hold_by_hand():
    meta = [{"name": "thread_name", "ph": "M", "tid": 1,
             "args": {"name": "engine/step-loop"}},
            {"name": "thread_name", "ph": "M", "tid": 2,
             "args": {"name": "model/m"}}]

    def span(name, ph, ts, tid=1, **kw):
        return {"name": name, "ph": ph, "ts": ts, "tid": tid, **kw}
    events = meta + [
        span("step", "B", 0), span("prefill", "X", 10, 2, dur=90,
                                   args={"rids": [1, 2]}),
        span("step", "E", 300),                  # holds 200 us
        span("step", "B", 400), span("prefill", "X", 410, 2, dur=40,
                                     args={"rids": [3]}),
        span("step", "E", 550),                  # holds 100 us
        span("step", "B", 600), span("prefill", "X", 600, 2, dur=50,
                                     args={"rids": [9]}),
        span("step", "E", 1650)]                 # rid 9: not in the window
    run = _run(None)
    run["engine_events"] = events
    run["requests"] = [Req(rid, "m", [1], 2, 0.0, times=[t])
                       for rid, t in ((1, 0.5), (2, 0.6), (3, 1.5),
                                      (9, 9.0))]
    # requests 1, 2 hold 0.2 ms, request 3 0.1 ms: median 0.2 ms
    assert _reader("first_token_hold_p50_ms").read(run) == \
        pytest.approx(0.2)


@pytest.fixture(scope="module")
def chip_trace():
    return json.loads(FIXTURE.read_text())


def test_fixture_scopes_sum_to_step_busy(chip_trace):
    per_step = scopes.step_scopes(chip_trace)
    busy = reduce_trace(chip_trace)["steps"]
    assert len(per_step) == len(busy) > 0
    for sc, b in zip(per_step, busy):
        assert sum(sc.values()) == pytest.approx(b, rel=0.01)


def test_fixture_names_the_decode_stages(chip_trace):
    total = {}
    for sc in scopes.step_scopes(chip_trace):
        for k, v in sc.items():
            total[k] = total.get(k, 0.0) + v
    stages = {k.split("/")[0] for k in total}
    assert {"attn", "ffn", "head"} <= stages
    assert scopes.seconds_under(total, *scopes.RELAYOUT) > 0
    assert total.get(scopes.UNSCOPED, 0.0) < 0.05 * sum(total.values())


def test_fixture_idle_gaps_go_to_engine_spans(chip_trace):
    red = reduce_trace(chip_trace, host_prefix=("cb.", "cp."))
    gaps = dict(red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert sum(v for k, v in gaps.items() if k.startswith("cp.")) > 0.9 * idle
    # the harness's own reduction still charges them to its step span
    assert dict(reduce_trace(chip_trace)["idle_gaps"]).get("cb.step", 0) > 0


def test_fixture_engine_spans_carry_their_stats(chip_trace):
    host = chip_trace["planes"][0]["lines"][0]
    spans = [(e[0], a) for e, a in zip(host["events"], host["args"])]
    steps = [a for n, a in spans if n == "cp.step"]
    assert steps and all("step" in a for a in steps)
    assert {"cp.dispatch", "cp.commit"} <= {n for n, _ in spans}


def test_program_op_names_from_a_compiled_text():
    import jax
    import jax.numpy as jnp

    def decode_m(x):
        with jax.named_scope("ffn"):
            with jax.named_scope("experts"):
                return jnp.tanh(x) @ x

    text = jax.jit(decode_m).lower(jnp.ones((8, 8))).compile().as_text()
    module, names = scopes.program_op_names(text)
    assert module == "jit_decode_m"
    assert names and all(k.startswith("%") for k in names)
    assert "ffn/experts" in {scopes.scope_of(v) for v in names.values()}
