"""Observability layer acceptance (ISSUE 7).

* metrics registry: counters/gauges/histograms with label sets, exact
  ``np.percentile`` quantiles, Prometheus text exposition and JSON
  snapshot, bounded structured-event log;
* span tracer: injected monotonic clock gives deterministic timestamps;
  emitted JSON is well-formed Chrome trace-event format (B/E balanced
  per track, one ``thread_name`` metadata event per tid);
* engine integration: deterministic span sequences for a
  queued→admitted→finished request and a cancelled-mid-decode request
  in BOTH lowering modes; exported counters/histograms match
  ``EngineStats`` exactly; observer disabled ⇒ bit-exact token streams;
* ``report()`` renders its last-N rebalance lines from the registry's
  event log;
* DemandTelemetry: empty-window and single-event EWMA edge cases, and
  gauge-fed EWMAs identical to direct pool sampling.
"""
import json
import re
from types import SimpleNamespace

import numpy as np
import pytest

from repro.configs import ElasticConfig, PAPER_COLOC_SET, get_smoke_config
from repro.core.weight_pool import slabs_for_config
from repro.runtime.engine import CrossPoolEngine, EngineMode
from repro.runtime.observe import (EngineObserver, MetricsRegistry,
                                   SpanTracer, percentile, summarize)
from repro.runtime.request import Request
from repro.runtime.telemetry import DemandTelemetry

MOE, MLA, MOON = "qwen3-moe-235b-a22b", "minicpm3-4b", "moonshot-v1-16b-a3b"


class FakeClock:
    """Deterministic monotonic clock: +1ms per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 0.001
        return self.t


def _models(names=PAPER_COLOC_SET):
    return {n: get_smoke_config(n).replace(dtype="float32") for n in names}


def _engine(names=PAPER_COLOC_SET, lowering=True, **kw):
    kw.setdefault("page_budget", 2048)
    kw.setdefault("page_bytes", 4096)
    kw.setdefault("slab_bytes", 4096)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_ctx", 64)
    kw.setdefault("seed", 0)
    return CrossPoolEngine(_models(names),
                           mode=EngineMode(pipeline=True, lowering=lowering),
                           **kw)


def _backpressure_engine(observer=None, lowering=True):
    """MOE + MLA with an arena sized for ONE model: the second submit
    queues on weight pressure (the queued→admitted drain path)."""
    models = _models((MOE, MLA))
    need = {n: slabs_for_config(c, 4096) for n, c in models.items()}
    return CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096,
        slot_budget=max(need.values()), slab_bytes=4096,
        max_batch=2, max_ctx=64,
        mode=EngineMode(pipeline=True, lowering=lowering),
        observer=observer)


def _lifecycle(tracer: SpanTracer, track: str):
    """The B/E/i sequence on one track (X slices carry durations, not
    lifecycle ordering — dropped here, asserted separately)."""
    return [(ph, n) for ph, n in tracer.span_names(track) if ph != "X"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_labels():
    m = MetricsRegistry()
    c = m.counter("req_total", "requests", ("model", "outcome"))
    c.labels("a", "ok").inc()
    c.labels("a", "ok").inc(2)
    c.labels("b", "err").inc()
    assert c.labels("a", "ok").value == 3
    assert c.value == 4                      # family total
    g = m.gauge("depth", "queue depth")
    g.set(7)
    g.set(3)
    assert g.value == 3
    # get-or-create shares one family; kind mismatch is a hard error
    assert m.counter("req_total", labelnames=("model", "outcome")) is c
    with pytest.raises(AssertionError):
        m.gauge("req_total")


def test_histogram_percentile_is_exactly_numpy():
    m = MetricsRegistry()
    h = m.histogram("lat", "latency", ("model",))
    rng = np.random.default_rng(0)
    samples = {"a": rng.uniform(0, 2, 101), "b": rng.uniform(0, 0.01, 7)}
    for name, vals in samples.items():
        child = h.labels(name)
        for v in vals:
            child.observe(v)
    everything = np.concatenate(list(samples.values()))
    for q in (50, 95, 99):
        assert h.percentile(q) == float(np.percentile(everything, q))
        assert h.labels("a").percentile(q) == \
            float(np.percentile(samples["a"], q))
    assert h.count == len(everything)
    assert np.isnan(percentile([], 99))      # empty window → NaN, no raise
    assert np.isnan(summarize([])["p50"])


def test_prometheus_text_exposition():
    m = MetricsRegistry()
    m.counter("c_total", "a counter", ("model",)).labels("x").inc(5)
    m.gauge("g", "a gauge").set(1.5)
    h = m.histogram("h_seconds", "a histogram", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = m.prometheus_text()
    assert "# HELP c_total a counter" in text
    assert "# TYPE c_total counter" in text
    assert 'c_total{model="x"} 5' in text
    assert "# TYPE g gauge" in text and "g 1.5" in text
    # cumulative buckets: 1 ≤ 0.1, 2 ≤ 1.0, 3 ≤ +Inf == _count
    assert 'h_seconds_bucket{le="0.1"} 1' in text
    assert 'h_seconds_bucket{le="1"} 2' in text
    assert 'h_seconds_bucket{le="+Inf"} 3' in text
    assert "h_seconds_count 3" in text
    assert "h_seconds_sum 2.55" in text
    # every sample line is NAME{LABELS}? VALUE
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.+einfa]+$')
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert line_re.match(line), line


def test_snapshot_is_jsonable_and_event_log_bounded():
    m = MetricsRegistry(event_log_size=4)
    m.histogram("h", "hist").observe(0.2)
    m.counter("c", "cnt").inc()
    snap = json.loads(json.dumps(m.snapshot()))
    assert snap["h"]["values"][0]["count"] == 1
    assert snap["h"]["values"][0]["p50"] == 0.2
    for i in range(10):
        m.log_event("rebalance", step=i)
    assert [e["step"] for e in m.recent_events("rebalance")] == [6, 7, 8, 9]
    assert [e["step"] for e in m.recent_events("rebalance", 2)] == [8, 9]
    assert m.recent_events("nope") == []


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_fake_clock_gives_deterministic_timestamps():
    tr = SpanTracer(clock=FakeClock())          # t0 = 1ms
    tr.begin("trk", "step")                     # reads 2ms → ts 1000us
    tr.instant("trk", "mark")                   # 3ms → 2000us
    tr.end("trk", "step")                       # 4ms → 3000us
    tr.complete("trk", "slice", dur_s=0.002)    # 5ms → ends at 4000us
    ev = tr.track_events("trk")
    assert [(e["ph"], e["ts"]) for e in ev] == [
        ("B", 1000.0), ("i", 2000.0), ("E", 3000.0), ("X", 2000.0)]
    assert ev[3]["dur"] == 2000.0
    # the metadata event named the track exactly once
    meta = [e for e in tr.events if e["ph"] == "M"]
    assert len(meta) == 1 and meta[0]["args"]["name"] == "trk"


def _validate_chrome_trace(trace: dict) -> None:
    """Schema check: the shape Perfetto/chrome://tracing ingests."""
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    assert trace["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]
    assert events, "empty trace"
    named_tids = set()
    depth: dict = {}
    for e in events:
        assert e["ph"] in {"B", "E", "X", "i", "M", "C"}, e
        assert e["pid"] == SpanTracer.PID and isinstance(e["tid"], int)
        assert isinstance(e["name"], str) and e["name"]
        if e["ph"] == "M":
            assert e["name"] == "thread_name"
            assert e["tid"] not in named_tids    # one metadata per track
            named_tids.add(e["tid"])
            continue
        assert e["tid"] in named_tids            # named before first use
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        elif e["ph"] == "i":
            assert e["s"] == "t"
        elif e["ph"] == "C":
            assert e["args"] and all(isinstance(v, float)
                                     for v in e["args"].values())
        elif e["ph"] == "B":
            depth[e["tid"]] = depth.get(e["tid"], 0) + 1
        elif e["ph"] == "E":
            depth[e["tid"]] = depth[e["tid"]] - 1
            assert depth[e["tid"]] >= 0, f"unbalanced E on tid {e['tid']}"
    assert all(d == 0 for d in depth.values()), f"unclosed spans: {depth}"


# ---------------------------------------------------------------------------
# engine integration: one run shared by the parity/schema tests
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def observed_run():
    obs = EngineObserver(clock=FakeClock())
    engine = _engine(observer=obs)
    reqs = [Request(0, MOE, 6, 3, 0.0), Request(1, MOE, 7, 3, 0.0),
            Request(2, MLA, 5, 3, 0.0), Request(3, MOON, 20, 3, 0.0)]
    stats = engine.run(reqs)
    return engine, reqs, stats, obs


def test_metrics_match_engine_stats(observed_run):
    engine, reqs, stats, obs = observed_run
    # token volume: the per-model counter family sums to EngineStats
    assert obs.tokens_total.value == stats.tokens_out
    # latency histograms hold EXACTLY the windowed EngineStats samples
    assert sorted(obs.tbt.all_samples()) == sorted(stats.tbt)
    assert sorted(obs.ttft.all_samples()) == sorted(stats.ttft)
    assert sorted(obs.prefill_batch.all_samples()) == \
        sorted(stats.prefill_batch_sizes)
    # admission verdicts per (model, outcome) match the controller
    adm = engine.admission.stats
    for (model, outcome), child in obs.admission_total.children.items():
        assert child.value == getattr(adm.per_model[model], outcome), \
            (model, outcome)
    assert obs.admission_total.value == \
        adm.admitted + adm.queued + adm.rejected
    # every request reached exactly one terminal outcome
    assert obs.requests_total.value == len(reqs)
    # arena/KV gauges mirror the live pools
    assert obs.kv_occupancy() == \
        engine.virt.mapped_pages / max(engine.virt.page_budget, 1)
    assert obs.slab_occupancy() == \
        engine.arena.resident_slabs / max(engine.arena.slot_budget, 1)


def test_prometheus_and_snapshot_outputs_parse(observed_run):
    _, _, _, obs = observed_run
    text = obs.metrics.prometheus_text()
    assert "# TYPE crosspool_ttft_seconds histogram" in text
    assert "crosspool_ttft_seconds_bucket" in text
    assert f'crosspool_admission_total{{model="{MOE}",outcome="admitted"}}' \
        in text
    json.loads(json.dumps(obs.metrics.snapshot()))


def test_chrome_trace_schema_and_request_span_trees(observed_run):
    _, reqs, _, obs = observed_run
    trace = json.loads(json.dumps(obs.tracer.chrome_trace()))
    _validate_chrome_trace(trace)
    # one COMPLETE span tree per request: submit → admitted → decode →
    # finished, all spans closed, ≥1 K-block slice inside decode
    for r in reqs:
        track = f"req/{r.model}#{r.request_id}"
        assert _lifecycle(obs.tracer, track) == [
            ("i", "submit"), ("B", "admitted"), ("E", "admitted"),
            ("B", "decode"), ("E", "decode"), ("i", "finished")]
        assert any(ph == "X" and name == "decode_block"
                   for ph, name in obs.tracer.span_names(track))
    # the step loop bracketed every step and its phases
    seq = obs.tracer.span_names(EngineObserver.ENGINE_TRACK)
    assert ("B", "step") in seq and ("E", "step") in seq
    assert ("B", "admission_drain") in seq and ("B", "batcher") in seq


def test_observer_disabled_streams_bit_exact(observed_run):
    _, ref_reqs, ref_stats, _ = observed_run
    engine = _engine()                      # observer=None: the fast path
    assert engine.observer is None
    reqs = [Request(0, MOE, 6, 3, 0.0), Request(1, MOE, 7, 3, 0.0),
            Request(2, MLA, 5, 3, 0.0), Request(3, MOON, 20, 3, 0.0)]
    stats = engine.run(reqs)
    assert stats.tokens_out == ref_stats.tokens_out
    for a, b in zip(reqs, ref_reqs):
        assert a.output_ids == b.output_ids, a.request_id


# ---------------------------------------------------------------------------
# deterministic span sequences, both lowering modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lowering", [True, False])
def test_span_sequence_queued_admitted_finished(lowering):
    """Arena backpressure queues the MLA submit; the queued span closes
    when the front door drains it, then the normal lifecycle follows."""
    obs = EngineObserver(clock=FakeClock())
    engine = _backpressure_engine(observer=obs, lowering=lowering)
    h_moe = engine.submit(Request(0, MOE, 8, 2, 0.0))
    h_mla = engine.submit(Request(1, MLA, 8, 2, 0.0))
    assert h_moe.admission == "admitted" and h_mla.admission == "queued"
    engine.drain()
    assert _lifecycle(obs.tracer, f"req/{MLA}#1") == [
        ("i", "submit"), ("B", "queued"), ("E", "queued"),
        ("B", "admitted"), ("E", "admitted"),
        ("B", "decode"), ("E", "decode"), ("i", "finished")]
    # metrics saw the same story: one queued verdict, one drain wait
    assert obs.admission_total.labels(MLA, "queued").value == 1
    assert obs.admission_total.labels(MLA, "admitted").value == 1
    wait = obs.metrics.get("crosspool_admission_wait_seconds")
    assert wait.labels(MLA).count == 1
    assert obs.requests_total.labels(MLA, "finished").value == 1


@pytest.mark.parametrize("lowering", [True, False])
def test_span_sequence_cancelled_mid_decode(lowering):
    obs = EngineObserver(clock=FakeClock())
    engine = _engine(names=(MOE, MLA), lowering=lowering, observer=obs)
    h = engine.submit(Request(0, MOE, 6, 50, 0.0))
    engine.submit(Request(1, MLA, 5, 3, 0.0))
    engine.step()
    engine.step()
    assert len(h.tokens) >= 2               # mid-decode, slot held
    assert engine.cancel(h)
    engine.drain()
    assert _lifecycle(obs.tracer, f"req/{MOE}#0") == [
        ("i", "submit"), ("B", "admitted"), ("E", "admitted"),
        ("B", "decode"), ("E", "decode"), ("i", "cancelled")]
    assert obs.requests_total.labels(MOE, "cancelled").value == 1
    assert obs.requests_total.labels(MLA, "finished").value == 1
    _validate_chrome_trace(obs.tracer.chrome_trace())


# ---------------------------------------------------------------------------
# report() renders rebalance lines from the registry event log
# ---------------------------------------------------------------------------

def test_report_rebalance_lines_come_from_registry():
    engine = _engine(names=(MOE, MLA), elastic=ElasticConfig())
    engine.metrics.log_event(
        "rebalance", step=5, time=1.0, page_budget=(8, 16),
        slot_budget=(4, 2), swapped_out=0, evicted_models=1,
        reason="kv_pressure")
    report = engine.report()
    assert "move @step 5: pages 8->16, slabs 4->2" in report
    assert "kv_pressure" in report and "evicted 1" in report


# ---------------------------------------------------------------------------
# DemandTelemetry EWMA edge cases + gauge feeding
# ---------------------------------------------------------------------------

def _fake_virt(mapped=0, budget=10):
    return SimpleNamespace(mapped_pages=mapped, page_budget=budget,
                           swapped_now=0)


def test_telemetry_empty_window():
    tel = DemandTelemetry(_models((MLA,)), ElasticConfig())
    tel.observe(0.0, _fake_virt(), arena=None, admission=None)
    assert tel.kv_occupancy_ewma == 0.0
    assert tel.slab_occupancy_ewma == 0.0
    assert tel.queue_depth_ewma == 0.0
    assert tel.arrival_rate(MLA, 0.0) == 0.0
    assert tel.window_specs(0.0) == []       # no signal → no specs
    assert tel.snapshot()["window_completions"] == 0.0


def test_telemetry_single_event_ewma():
    cfg = ElasticConfig()
    tel = DemandTelemetry(_models((MLA,)), cfg)
    tel.note_arrival(MLA, 0.0)
    tel.note_finish(MLA, prompt_tokens=8, output_tokens=4,
                    admit_time=0.0, finish_time=0.5)
    tel.observe(0.5, _fake_virt(mapped=5), arena=None, admission=None)
    # one sample folded from zero: ewma == alpha * x exactly
    assert tel.kv_occupancy_ewma == cfg.ewma_alpha * 0.5
    # sub-second window: the rate denominator floors at 1s (no n/epsilon)
    assert tel.arrival_rate(MLA, 0.5) == 1.0
    specs = tel.window_specs(0.5)
    assert len(specs) == 1 and specs[0].arrival_rate == 1.0
    assert specs[0].prompt_tokens.tolist() == [8.0]


def test_telemetry_gauge_fed_matches_direct_sampling():
    """With an observer attached the EWMAs fold the gauge values the
    registry exports — identical to direct pool sampling, by value."""
    cfg = ElasticConfig()
    direct = DemandTelemetry(_models((MLA,)), cfg)
    obs = EngineObserver(clock=FakeClock())
    fed = DemandTelemetry(_models((MLA,)), cfg, gauges=obs)
    admission = SimpleNamespace(queued_count=lambda: 3)
    for step in range(4):
        virt = _fake_virt(mapped=2 * step, budget=10)
        direct.observe(float(step), virt, arena=None, admission=admission)
        obs.sample(virt, None, admission, waiting=0)
        fed.observe(float(step), virt, arena=None, admission=admission)
    assert fed.kv_occupancy_ewma == direct.kv_occupancy_ewma
    assert fed.queue_depth_ewma == direct.queue_depth_ewma
    assert fed.last == direct.last


# ---------------------------------------------------------------------------
# SLO burn-rate monitor edge cases (ISSUE 10)
# ---------------------------------------------------------------------------

def _slo_monitor(threshold_ms=100.0, target=0.9, window_s=10.0,
                 short_window_s=2.0):
    from repro.configs.base import SLObjective, SLOConfig
    from repro.runtime.observe import SLOMonitor
    cfg = SLOConfig(
        objectives={"m": SLObjective(ttft_ms=threshold_ms, target=target)},
        window_s=window_s, short_window_s=short_window_s)
    return SLOMonitor(cfg)


def test_slo_empty_window_never_breaches():
    mon = _slo_monitor()
    assert mon.evaluate(0.0) == [] and mon.evaluate(1e9) == []
    st = mon.status(1e9)[("m", "ttft")]
    assert st["n"] == 0 and not st["breaching"]
    assert np.isnan(st["window_value"])
    assert mon.breach_count() == 0


def test_slo_single_sample_breach_edge():
    mon = _slo_monitor()
    mon.note("ttft", "m", 0.5, 1.0)            # 500ms against a 100ms SLO
    breaches = mon.evaluate(1.0)
    assert len(breaches) == 1
    b = breaches[0]
    assert (b.model, b.metric) == ("m", "ttft")
    assert b.long_burn == b.short_burn == pytest.approx(1.0 / 0.1)
    # edge-triggered: still breaching, but no NEW edge without recovery
    assert mon.evaluate(1.5) == []
    assert mon.breach_count() == 1
    # sample ages out of the window -> condition clears -> edge re-arms
    assert mon.evaluate(20.0) == []
    mon.note("ttft", "m", 0.5, 21.0)
    assert len(mon.evaluate(21.0)) == 1
    assert mon.breach_count() == 2


def test_slo_exact_threshold_is_within_slo():
    """A sample EQUAL to the objective does not burn budget (bad is
    strictly greater-than)."""
    mon = _slo_monitor(threshold_ms=100.0)
    for i in range(8):
        mon.note("ttft", "m", 0.1, float(i) * 0.1)
    assert mon.evaluate(0.8) == []
    st = mon.status(0.8)[("m", "ttft")]
    assert st["bad_fraction"] == 0.0 and st["long_burn"] == 0.0
    # one ulp above the threshold and the whole window burns
    mon.note("ttft", "m", np.nextafter(0.1, 1.0), 0.9)
    assert len(mon.evaluate(0.9)) == 1


def test_slo_reset_mid_window_drops_samples_and_rearms():
    """The ``engine.reset_stats()`` path: windows clear and the edge
    re-arms, so the same condition fires a fresh breach afterwards."""
    mon = _slo_monitor()
    mon.note("ttft", "m", 0.5, 1.0)
    assert len(mon.evaluate(1.0)) == 1
    mon.reset()
    st = mon.status(1.0)[("m", "ttft")]
    assert st["n"] == 0 and not st["breaching"]
    assert mon.evaluate(1.1) == []             # empty again, no breach
    mon.note("ttft", "m", 0.5, 1.2)
    assert len(mon.evaluate(1.2)) == 1         # re-armed edge fires
    assert mon.breach_count() == 2


def test_slo_window_value_matches_np_percentile_of_histogram():
    """Breach parity: the monitor's window quantile is EXACTLY
    ``np.percentile`` over the same raw samples a registry histogram
    holds (same linear interpolation, no bucketing error)."""
    mon = _slo_monitor(threshold_ms=100.0, target=0.95, window_s=100.0)
    hist = mon.metrics.histogram("ttft_seconds", "raw ttft", ("model",))
    rng = np.random.default_rng(3)
    samples = rng.uniform(0.0, 0.4, 64)
    for i, v in enumerate(samples):
        mon.note("ttft", "m", v, float(i))
        hist.labels("m").observe(v)
    mon.evaluate(float(len(samples) - 1))
    st = mon.status(float(len(samples) - 1))[("m", "ttft")]
    assert st["window_value"] == float(np.percentile(samples, 95.0))
    assert st["window_value"] == hist.labels("m").percentile(95.0)
    ev = mon.metrics.recent_events("slo_breach")
    assert ev and ev[-1]["window_value_ms"] == st["window_value"] * 1e3


def test_metrics_registry_counts_dropped_events():
    reg = MetricsRegistry(event_log_size=4)
    for i in range(7):
        reg.log_event("rebalance", step=i)
    assert reg.events_dropped("rebalance") == 3
    assert reg.events_dropped() == {"rebalance": 3}
    assert reg.events_dropped("slo_breach") == 0
    # the companion counter family is exported for scrapes
    ctr = reg.get("crosspool_events_dropped_total")
    assert ctr is not None and ctr.labels("rebalance").value == 3
    # the log still holds the most recent events only
    assert [e["step"] for e in reg.recent_events("rebalance")] == [3, 4, 5, 6]


# ---------------------------------------------------------------------------
# engine spans on the profiler's clock (cp.<span> TraceAnnotations)
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every span
    built, entered and exited."""

    enabled = False
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args
        self.log.append(("new", name))

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_profiler(monkeypatch):
    import repro.core.pools as pools
    import repro.runtime.observe as observe
    _FakeAnnotation.log = []
    _FakeAnnotation.enabled = False
    monkeypatch.setattr(observe, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(pools, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation


@pytest.mark.parametrize("lowering", [True, False])
def test_profiler_off_builds_no_annotation(fake_profiler, lowering):
    obs = EngineObserver(clock=FakeClock())
    engine = _engine(names=(MOE, MLA), lowering=lowering, observer=obs)
    engine.submit(Request(0, MOE, 6, 3, 0.0))
    engine.submit(Request(1, MLA, 5, 3, 0.0))
    engine.drain()
    assert fake_profiler.log == []
    assert ("B", "dispatch") in obs.tracer.span_names(
        EngineObserver.ENGINE_TRACK)


def test_profiler_on_engine_track_spans_nest(fake_profiler):
    fake_profiler.enabled = True
    tr = SpanTracer(clock=FakeClock(), profiled=("engine",))
    tr.begin("engine", "step", step=1)
    tr.begin("engine", "dispatch")
    tr.begin("req/a#0", "decode")          # not a profiled track
    tr.end("engine", "dispatch")
    tr.end("req/a#0", "decode")
    tr.end("engine", "step")
    assert fake_profiler.log == [
        ("new", "cp.step"), ("enter", "cp.step"),
        ("new", "cp.dispatch"), ("enter", "cp.dispatch"),
        ("exit", "cp.dispatch"), ("exit", "cp.step")]
    # the in-memory events are the same with the profiler on or off
    assert tr.span_names("engine") == [
        ("B", "step"), ("B", "dispatch"), ("E", "dispatch"), ("E", "step")]


def test_profiler_started_mid_span_closes_only_its_own(fake_profiler):
    tr = SpanTracer(clock=FakeClock(), profiled=("engine",))
    tr.begin("engine", "step")             # before the session
    fake_profiler.enabled = True
    tr.begin("engine", "commit")
    tr.end("engine", "commit")
    tr.end("engine", "step")               # never reported: nothing to close
    assert fake_profiler.log == [("new", "cp.commit"), ("enter", "cp.commit"),
                                 ("exit", "cp.commit")]


def test_prefill_span_carries_request_ids():
    obs = EngineObserver(clock=FakeClock())
    engine = _engine(names=(MOE, MLA), observer=obs)
    engine.submit(Request(0, MOE, 6, 2, 0.0))
    engine.submit(Request(1, MOE, 7, 2, 0.0))
    engine.submit(Request(2, MLA, 5, 2, 0.0))
    engine.drain()
    rids = [rid for track in (f"model/{MOE}", f"model/{MLA}")
            for e in obs.tracer.track_events(track) if e["name"] == "prefill"
            for rid in e["args"]["rids"]]
    assert sorted(rids) == [0, 1, 2]


def test_engine_step_writes_nested_cp_spans_into_the_xplane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    engine = _engine(names=(MOE, MLA), observer=EngineObserver())
    engine.submit(Request(0, MOE, 6, 4, 0.0))
    engine.submit(Request(1, MLA, 5, 4, 0.0))
    engine.step()                           # prompt passes, compiles
    with jax.profiler.trace(str(tmp_path)):
        engine.step()                       # one decode step, traced
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cp."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    (s0, s1, args), = spans["cp.step"]
    assert "step" in args                   # metadata rides as stats
    for inner in ("cp.dispatch", "cp.commit"):
        (a, b, _), = spans[inner]
        assert s0 <= a <= b <= s1
