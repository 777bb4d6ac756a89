"""Serving launcher: the CrossPool engine over colocated cold models.

  python -m repro.launch.serve --rps 0.5 --horizon 20 --pipeline --lowering
  python -m repro.launch.serve --arch qwen3-14b --shape decode_32k --dry-run

Host-scale runs colocate the paper's model trio at smoke scale and report
decode TBT percentiles + pool statistics; --dry-run lowers the production
serve_step for an (arch x shape) cell instead.  ``--metrics-out`` /
``--trace-out`` attach an :class:`~repro.runtime.observe.EngineObserver`
and write Prometheus metrics / a Perfetto-loadable Chrome trace
(DESIGN.md §10) — CI's observability smoke step runs exactly that.
"""
from __future__ import annotations

import argparse
from typing import Optional

import numpy as np


def parse_slo_specs(specs: list, model_names: list):
    """``"model:ttft_ms=200,tbt_p99_ms=50"`` (repeatable) -> SLOConfig.
    Model ``*`` expands to every colocated model; later specs override."""
    from repro.configs.base import SLObjective, SLOConfig
    objectives = {}
    for spec in specs:
        model, _, body = spec.partition(":")
        if not body:
            raise SystemExit(f"--slo {spec!r}: expected model:k=v[,k=v...]")
        kwargs = {}
        for item in body.split(","):
            key, _, val = item.partition("=")
            try:
                kwargs[key.strip()] = float(val)
            except ValueError:
                raise SystemExit(f"--slo {spec!r}: bad value {item!r}")
        try:
            obj = SLObjective(**kwargs)
        except TypeError as err:
            raise SystemExit(f"--slo {spec!r}: {err}")
        for name in (model_names if model.strip() == "*" else [model.strip()]):
            if name not in model_names:
                raise SystemExit(f"--slo {spec!r}: unknown model {name!r} "
                                 f"(colocated: {model_names})")
            objectives[name] = obj
    return SLOConfig(objectives=objectives)


def main(argv: Optional[list] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="dry-run arch")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--strategy", default="crosspool",
                    choices=["crosspool", "monolithic"])
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    # engine options
    ap.add_argument("--rps", type=float, default=0.5)
    ap.add_argument("--horizon", type=float, default=10.0)
    ap.add_argument("--pipeline", action="store_true", default=True)
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false")
    ap.add_argument("--lowering", action="store_true", default=True)
    ap.add_argument("--no-lowering", dest="lowering", action="store_false")
    ap.add_argument("--page-budget", type=int, default=8192)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="K tokens committed per fused decode dispatch "
                         "(DESIGN.md §9; host-driven lowering clamps to 1)")
    ap.add_argument("--cache", action="store_true",
                    help="enable radix-tree prefix caching over the KV "
                         "pool (DESIGN.md §11): trace requests get real "
                         "prompt ids sharing a per-model system prefix, "
                         "and the cache snapshot is reported")
    ap.add_argument("--elastic", action="store_true",
                    help="enable the online KV<->weights rebalancer "
                         "(DESIGN.md §8)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write Prometheus-text metrics here after serving "
                         "(DESIGN.md §10)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write Chrome trace-event JSON here after serving "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--slo", action="append", default=None, metavar="SPEC",
                    help='per-model latency objective, repeatable: '
                         '"model:ttft_ms=200,tbt_p99_ms=50,'
                         'queue_wait_ms=500,target=0.99" — model "*" '
                         'applies to every colocated model (DESIGN.md §13)')
    ap.add_argument("--flight-record-out", default=None, metavar="PATH",
                    help="dump the flight record (full causal input "
                         "stream + pool snapshots) here after serving; "
                         "replay with `python -m repro.launch.replay PATH`")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.dry_run:
        from repro.launch import dryrun
        assert args.arch, "--arch required with --dry-run"
        rec = dryrun.run_cell(args.arch, args.shape,
                              multi_pod=args.multi_pod,
                              strategy_name=args.strategy)
        raise SystemExit(0 if rec.get("ok") else 1)

    from repro.configs import PAPER_COLOC_SET, get_smoke_config
    from repro.configs.base import (CacheConfig, ElasticConfig, EngineConfig,
                                    FlightRecorderConfig)
    from repro.runtime import observe as trace_mod
    from repro.runtime.engine import CrossPoolEngine, EngineMode
    from repro.runtime.observe import EngineObserver, percentile

    observer = (EngineObserver()
                if args.metrics_out or args.trace_out else None)
    models = {n: get_smoke_config(n) for n in PAPER_COLOC_SET}
    slo_cfg = parse_slo_specs(args.slo, list(models)) if args.slo else None
    rec_cfg = (FlightRecorderConfig(dump_path=args.flight_record_out)
               if args.flight_record_out else None)
    engine = CrossPoolEngine(
        models, page_budget=args.page_budget, max_batch=4, max_ctx=128,
        config=EngineConfig(
            mode=EngineMode(pipeline=args.pipeline, lowering=args.lowering,
                            decode_steps_per_dispatch=args.decode_steps),
            elastic=ElasticConfig() if args.elastic else None,
            cache=CacheConfig(enabled=args.cache),
            slo=slo_cfg, flightrec=rec_cfg),
        observer=observer)
    reqs = trace_mod.make_requests(
        list(models), rps_per_model=args.rps, horizon_s=args.horizon,
        kind="sharegpt", scale_tokens=0.1, max_new_cap=args.max_new)
    if args.cache:
        # synthetic trace counts are cache-ineligible by design; give each
        # request REAL ids whose head is a per-model "system prompt" so
        # same-bucket requests share a cacheable prefix
        rng = np.random.default_rng(0)
        system = {n: rng.integers(0, models[n].vocab_size, 64)
                  .astype(np.int32) for n in models}
        for r in reqs:
            n = r.prompt_tokens
            ids = np.concatenate([system[r.model][:n], rng.integers(
                0, models[r.model].vocab_size, max(0, n - 64))])
            r.prompt_ids = ids[:n].astype(np.int32)
    print(f"serving {len(reqs)} requests across {len(models)} cold models "
          f"(pipeline={args.pipeline}, lowering={args.lowering}, "
          f"decode_steps={args.decode_steps})")
    stats = engine.run(reqs)
    print(f"tokens out: {stats.tokens_out}  virtual wall: {stats.wall_s:.2f}s "
          f"throughput: {stats.throughput:.1f} tok/s")
    print(f"TBT p50/p95/p99: {percentile(stats.tbt, 50) * 1e3:.1f} / "
          f"{percentile(stats.tbt, 95) * 1e3:.1f} / "
          f"{percentile(stats.tbt, 99) * 1e3:.1f} ms")
    print(f"admission: {engine.admission.stats}")
    print(f"pool: {engine.virt.utilization()}")
    if engine.cache is not None:
        print(f"prefix cache: {engine.cache.snapshot()}")
    print(f"straggler steps flagged: {stats.slow_steps}")
    if engine.slo is not None:
        print(engine.slo.report_line(engine.now))
    if args.flight_record_out:
        engine.recorder.dump(args.flight_record_out)
        print(f"flight record -> {args.flight_record_out} "
              f"({len(engine.recorder.ring)} events, "
              f"{len(engine.recorder.snapshots)} snapshots); replay with "
              f"`python -m repro.launch.replay {args.flight_record_out}`")
    if observer is not None:
        if args.metrics_out:
            observer.metrics.write(args.metrics_out)
            print(f"metrics -> {args.metrics_out}")
        if args.trace_out:
            observer.tracer.write(args.trace_out)
            print(f"trace -> {args.trace_out} "
                  f"({len(observer.tracer.events)} events)")


if __name__ == "__main__":
    main()
