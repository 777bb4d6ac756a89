#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``: the models and the engine's
settings) and a traffic mix (``chipbench/traffic/<mix>.json``).  The run
builds the engine with weights drawn from the seed, warms every program
the mix reaches, fills the slots (closed loop) or runs the arrivals of a
lead-in (open loop), then drives ``CrossPoolEngine.submit``/``step`` for
``--seconds``.  With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, from a profiler trace
of the window's first seconds, the engine's counters and the harness's
host spans.  Each metric is computed by ``chipbench/metrics/<name>.py``.

After the window the engine is freed and a sample of the finished
requests goes through each model's plain float32 reference (the module its
entry names, ``cb.reference`` by default);
``correct`` says whether each number the configuration compares over the
served tokens' gaps stays within its limit (``cb.check.judge``).  Without
a TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

#: The persistent compilation cache: a fixed directory in the checkout, so
#: only a cell's first run there compiles.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: Seconds of the window the traced run profiles.
TRACE_SECONDS = 8.0


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_reader(name: str):
    """``metrics/<name>.py``; a quantity split by cell (``<base>.<cell
    part>``) is read by ``metrics/<base>.py`` where it has no file of its
    own."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool):
    """Names of the metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class NoChip(SystemExit):
    pass


def check_device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"run.py: no TPU: JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"run.py: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


#: Histograms of the engine's registry that per-layer metrics read over
#: the window alone.
WINDOW_HISTOGRAMS = ("crosspool_prefill_dispatch_seconds",)


def registry_marks(reg, names) -> dict:
    """How many samples each labelled child of each histogram holds now."""
    return {n: {k: len(c.samples) for k, c in reg.get(n)._children.items()}
            for n in names if reg.get(n) is not None}


def samples_since(reg, name: str, marks: dict) -> list:
    """The samples of one histogram recorded after ``marks`` was taken."""
    h = reg.get(name)
    if h is None:
        return []
    seen = marks.get(name, {})
    return [v for k, c in h._children.items() for v in c.samples[seen.get(k, 0):]]


def count_compiles():
    """A counter of backend compilations and cache loads, from JAX's
    monitoring events."""
    import jax
    seen = {"compile": 0, "trace": 0}

    def on_duration(event, _secs, **_kw):
        if "backend_compile" in event:
            seen["compile"] += 1
        elif "jaxpr_trace" in event:
            seen["trace"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def run_cell(args, *, require_tpu: bool = True, root: Path = ROOT,
             traffic_dir: Path = HERE / "traffic", fault=None,
             controls=(), out_dir: str = "") -> dict:
    """One run of one cell.  ``fault`` breaks the built engine (tests);
    ``controls`` also reads each named lower-precision control at the
    served positions and judges it in the program's place, under the key
    ``controls``; ``out_dir`` receives per-request records, the served
    gaps and the trace (tests and ``tests/control_chip.py``; not a
    benchmark run)."""
    bench = load_bench(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"run.py: unknown workload {args.workload!r}")
    cell = cells[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    if args.seed < 0:
        raise SystemExit("run.py: --seed must be a whole number >= 0")

    import jax
    if require_tpu:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # the directory is the benchmark's own: a size limit set for a
        # shared cache would evict one cell's programs while another runs
        jax.config.update("jax_compilation_cache_max_size", -1)
    devs = check_device(int(cell["chips"]), require_tpu)

    from cb import check, setup
    from cb.load import Load
    from cb.measures import percentile
    from cb.peaks import peaks_for
    from cb.traffic import Traffic

    cfg = setup.load_config(root / conf["file"])
    # a model that its work count or its reference does not describe is
    # refused before anything is built (the program's side: build_engine)
    shapes = setup.shapes(cfg)
    for m in cfg["models"].values():
        setup.reference_module(m)
    # the precision the configuration states for the program's float32
    # products (on a TPU an unmarked float32 product runs in one bf16 pass)
    jax.config.update("jax_default_matmul_precision",
                      cfg["engine"].get("matmul_precision"))
    traffic = Traffic.from_file(traffic_dir / f"{cell['traffic']}.json")
    peaks = peaks_for(devs[0].device_kind) if require_tpu else \
        {"hbm_bytes_per_s": 1.0, "bf16_flops_per_s": 1.0}
    compiles = count_compiles()
    t = time.perf_counter()
    engine, repo_models = setup.build_engine(cfg, args.seed)
    if fault is not None:
        fault(engine)
    t_build = time.perf_counter() - t
    vocab = {n: m["as_run"]["vocab"] for n, m in cfg["models"].items()}
    drv = Load(engine, traffic, args.seed, vocab, annotate=bool(args.trace))
    t = time.perf_counter()
    drv.precompile(repo_models)
    log(f"[setup] build and compiled ahead: compiles {dict(compiles)}")
    drv.warm_up()
    t_warm = time.perf_counter() - t
    t = time.perf_counter()
    queues = traffic.closed_queues(args.seed, 4096)
    drv.lead_in(queues, float(traffic.raw.get("lead_s", 0.0)))
    t_lead = time.perf_counter() - t
    engine.reset_stats()
    n_passes = len(engine.stats.prefill_batch_sizes)
    marks = registry_marks(engine.metrics, WINDOW_HISTOGRAMS)
    set_up_compiles = dict(compiles)

    trace_state = {}
    if args.trace:
        from jax.profiler import ProfileOptions, TraceAnnotation
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        span = TraceAnnotation("cb.window")

        def close_trace(now):
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            trace_state["closed"] = now

        def tick(t0, now):
            if "open" not in trace_state:
                span.__enter__()
                trace_state["open"] = now
            elif "closed" not in trace_state and now - t0 >= TRACE_SECONDS:
                close_trace(now)
        drv.tick = tick

    setup_s = time.perf_counter() - T_PROCESS
    if traffic.loop == "closed":
        t_w0 = drv.run_closed(args.seconds)
    else:
        t_w0 = drv.run_open(traffic.open_schedule(args.seed, args.seconds),
                            args.seconds)
    t_open_until = t_w0 + args.seconds
    n_compiles = {k: v - set_up_compiles[k] for k, v in compiles.items()}
    passes = engine.stats.prefill_batch_sizes[n_passes:]
    if args.trace and "closed" not in trace_state:
        close_trace(time.perf_counter())
    win_steps = [s for s in drv.steps if t_w0 <= s.t0 < t_open_until]
    t_w1 = win_steps[-1].t1 if win_steps else t_open_until
    # the registry's samples recorded since the window opened
    since = {n: samples_since(engine.metrics, n, marks)
             for n in WINDOW_HISTOGRAMS}
    prefill_s = sum(since["crosspool_prefill_dispatch_seconds"])
    prefill_tok = sum(len(r.prompt) for r in drv.reqs.values()
                      if r.times and t_w0 <= r.times[0] <= t_w1)
    kv_mapped = [m for s, m in zip(drv.steps, drv.kv_mapped)
                 if t_w0 <= s.t0 < t_open_until]
    registry = {"prefill_seconds": prefill_s, "prefill_tokens": prefill_tok,
                "kv_mapped": kv_mapped,
                "page_budget": engine.virt.page_budget}
    backlog = sum(1 for r in drv.reqs.values() if r.phase == "window"
                  and r.due < t_open_until and not r.times)
    if traffic.loop == "open":
        drv.finish_first_tokens(t_w0, t_open_until)
    late = [r.submitted - r.due for r in drv.reqs.values()
            if r.phase == "window" and r.submitted is not None]

    # the chips the engine's work runs on: the KV pool's, and under the
    # host-driven pipeline (lowering off) the weights arena's too
    chips = list(dict.fromkeys(
        [engine.kv_device] if cfg["engine"]["lowering"]
        else [engine.kv_device, engine.w_device]))
    mem_peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        mem_peak = max(mem_peak, int(st.get("peak_bytes_in_use", 0)))

    reduced = None
    if args.trace:
        from cb.trace import find_xplane, load_xplane, reduce_trace
        path = find_xplane(str(TRACE_DIR))
        if path is not None:
            raw = load_xplane(path)
            reduced = reduce_trace(raw)
            if reduced is not None:
                # the first chip is the KV pool's, which reduced reads
                busy = [reduced["busy_s"]] + [
                    (reduce_trace(raw, device=f"/device:TPU:{d.id}")
                     or {"busy_s": 0.0})["busy_s"] for d in chips[1:]]
                log(f"[trace] busy seconds by chip used {busy}")
                reduced["busy_mean_s"] = sum(busy) / len(busy)
            traced = [s for s in drv.steps if trace_state.get(
                "open", np.inf) <= s.t0 < trace_state["closed"]]
            if reduced is not None and len(traced) == len(reduced["steps"]):
                for s, busy_s in zip(traced, reduced["steps"]):
                    s.device_s = busy_s
            log(f"[trace] {len(traced)} steps in the traced window, "
                f"{len(reduced['steps']) if reduced else 0} step spans")
            if out_dir:
                Path(out_dir).mkdir(parents=True, exist_ok=True)
                from cb.trace import trim
                (Path(out_dir) / "trace_trim.json").write_text(
                    json.dumps(trim(raw)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    run = {"requests": list(drv.reqs.values()), "steps": drv.steps,
           "window": (t_w0, t_w1), "window_open_until": t_open_until,
           "seconds": args.seconds, "setup_s": setup_s, "shapes": shapes,
           "peaks": peaks, "registry": registry, "trace": reduced,
           "chips_used": len(chips)}
    metrics = {}
    for m in cell_metrics(bench, cell["name"], bool(args.trace)):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    due = [r for r in drv.reqs.values() if r.phase == "window"
           and r.submitted is not None]
    attempted = len(due)
    failed = sum(1 for r in due if r.rejected)
    log(f"[memory] peak {mem_peak / 2**30:.3f} GiB")
    log(f"[setup] build {t_build:.2f} s, warm-up {t_warm:.2f} s, lead-in "
        f"{t_lead:.2f} s, setup_s {setup_s:.3f} s, compiles in set-up "
        f"{set_up_compiles}")
    log(f"[window] {len(win_steps)} steps, {t_w1 - t_w0:.3f} s, compiles "
        f"in window {n_compiles}, generator late p50/max "
        f"{(sorted(late)[len(late) // 2] if late else 0) * 1e3:.1f}/"
        f"{(max(late) if late else 0) * 1e3:.1f} ms, attempted {attempted}, "
        f"failed {failed}")
    log(f"[window] tpot_p50_ms {load_reader('tpot_p50_ms')(run)}, "
        f"tbt_p99_ms {load_reader('tbt_p99_ms')(run)}")
    log(f"[window] prompt passes by batch size "
        f"{dict(sorted(collections.Counter(passes).items()))}")

    def ms(values, q):
        return f"{percentile(values, q) * 1e3:.1f} ms" if values else "none"

    if traffic.loop == "open":
        ttft = [r.times[0] - r.due for r in drv.reqs.values()
                if r.phase == "window" and t_w0 <= r.due < t_open_until
                and r.times]
        half = [r.times[0] - r.due for r in drv.reqs.values()
                if r.phase == "window" and r.times
                and t_w0 + args.seconds / 2 <= r.due < t_open_until]
        log(f"[window] time to first token over {len(ttft)} requests: "
            f"p50 {ms(ttft, 50)}, p90 {ms(ttft, 90)}, max {ms(ttft, 100)}; "
            f"second half p50 {ms(half, 50)}; due without a first token at "
            f"the close {backlog}")
    samples = check.sample_requests(list(drv.reqs.values()), cfg["models"],
                                    traffic.check_requests, args.seed)
    if out_dir:
        dump_details(Path(out_dir), drv, run, samples)
    drv.engine = None
    del engine, drv
    gc.collect()
    t = time.perf_counter()
    readings = check.compare(cfg, repo_models, samples, args.seed,
                             controls)
    log(f"[check] reference over {sum(len(v) for v in samples.values())} "
        f"requests in {time.perf_counter() - t:.1f} s")
    gaps = {n: rd["token_gaps"] for n, rd in readings.items()}
    if out_dir:
        (Path(out_dir) / "gaps.json").write_text(json.dumps({
            n: {"program": [g.tolist() for g in rd["token_gaps"]],
                **{c: [g.tolist() for g in v]
                   for c, v in rd["controls"].items()}}
            for n, rd in readings.items()}))
    for name, rd in readings.items():
        for k, g in (("program", rd["token_gaps"]),
                     *rd["controls"].items()):
            g = np.concatenate(g)
            log(f"[check] {name} {k}: {len(g)} tokens of {rd['requests']} "
                f"requests, widest {g.max():.4f} p99 "
                f"{percentile(g, 99):.4f} mean {g.mean():.5f} share>0 "
                f"{np.mean(g > 0):.4f}")
    checks, correct = check.judge(cfg, gaps)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if reduced is not None:
        device["busy_s"] = reduced["busy_mean_s"]
        device["window_s"] = reduced["window_s"]
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    if controls:
        out["controls"] = {}
        for c in controls:
            c_checks, c_correct = check.judge(cfg, {
                n: rd["controls"][c] for n, rd in readings.items()})
            out["controls"][c] = {"correct": c_correct, "checks": c_checks}
    out["checks"] = checks
    return out


def dump_details(out: Path, drv, run, samples) -> None:
    """Per-request and per-step records of one run, for study."""
    out.mkdir(parents=True, exist_ok=True)
    t0 = run["window"][0]
    rec = {"window": [0.0, run["window"][1] - t0],
           "requests": [{"rid": r.rid, "model": r.model, "phase": r.phase,
                         "prompt": len(r.prompt), "max_new": r.max_new,
                         "due": r.due - t0, "n": len(r.tokens),
                         "first": (r.times[0] - t0) if r.times else None,
                         "last": (r.times[-1] - t0) if r.times else None}
                        for r in drv.reqs.values()],
           "steps": [[s.t0 - t0, s.t1 - t0, s.prefill, s.n_tokens,
                      {m: len(c) for m, c in s.ctx.items()}]
                     for s in drv.steps],
           "checked": {m: [r.rid for r in rs] for m, rs in samples.items()}}
    (out / "details.json").write_text(json.dumps(rec))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args)
    except NoChip as e:
        log(str(e))
        return 3
    for key, c in result["checks"].items():
        log(f"{key} {c['value']:.6f} limit {c['limit']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
