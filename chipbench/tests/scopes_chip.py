#!/usr/bin/env python3
"""Read a cell's device time by stage scope and its engine spans on the
chip, with the new per-layer readings, from one run of the cell.

    python3 chipbench/tests/scopes_chip.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> --out <dir> [--root <checkout>]

One run of the cell as ``chipbench/run.py`` makes it (``--root`` runs
another checkout's harness and program, as a parent commit's).  Besides
the run's result it writes to ``--out``:

* ``hlo.json``: per model, the instruction count of the compiled fused
  decode step and its program text (``decode_<model>.hlo.txt.gz``), for
  the shapes the run handed it (the texts also give the trace's ops
  their ``op_name``: ``cb.scopes.load_scoped_xplane``);
* ``details.json``, ``gaps.json``, ``trace_trim.json``: what
  ``run_cell``'s ``out_dir`` writes;
* with ``--trace 1``: ``trace_scoped.json``, a trimmed scoped trace
  (``cb.scopes.trim_scoped``); ``scopes.json``, the device self time by
  scope of the traced decode-only steps, the idle time by innermost
  ``cb.``/``cp.`` span, the readings of the readers ``run.py`` cannot feed
  yet (``kv_relayout_ms_per_step``, ``attention_hbm_roofline``,
  ``ffn_hbm_roofline``, ``first_token_hold_p50_ms``), and the tokens/s of
  the traced steps against the untraced steps of the same window.

It reaches into ``run.py`` at three points (the engine after it is
built, the trace file before it is deleted, the ``run`` the readers get),
so it serves the harness as it is.  Not a benchmark run.
"""
from __future__ import annotations

import argparse
import gzip
import importlib.util
import json
import re
import sys
import traceback
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+ = ", re.M)


def load_harness(root: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", root / "chipbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def record_fused_programs(engine, texts: dict) -> None:
    """Wrap each model's fused decode program so that its first call
    keeps the compiled program's text for the shapes it is handed."""
    import jax
    for name, runner in engine.runners.items():
        fused = getattr(runner, "fused", None)
        if fused is None:
            continue
        program = fused._step

        def first_call(*a, _program=program, _fused=fused, _name=name):
            avals = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding), a)
            texts[_name] = _program.lower(*avals).compile().as_text()
            _fused._step = _program
            return _program(*a)
        fused._step = first_call


def hlo_counts(texts: dict, out: Path) -> dict:
    counts = {}
    for name, text in texts.items():
        counts[name] = len(_INSTRUCTION.findall(text))
        with gzip.open(out / f"decode_{name}.hlo.txt.gz", "wt") as f:
            f.write(text)
    return counts


def study(run_mod, args, out: Path, state: dict, result: dict) -> dict:
    """The scope and span readings of one traced run."""
    from cb import scopes
    from cb.measures import window_steps
    from cb.trace import reduce_trace
    run = state["run"]
    raw = state.get("scoped")
    red = None if raw is None else reduce_trace(raw,
                                                host_prefix=("cb.", "cp."))
    report = {}
    if red is not None:
        run["step_scopes"] = scopes.step_scopes(raw)
        report["idle_gaps"] = red["idle_gaps"]
        report["busy_s"], report["window_s"] = red["busy_s"], red["window_s"]
        steps = scopes.decode_only_scopes(run)
        total = {}
        for _, sc in steps:
            for k, v in sc.items():
                total[k] = total.get(k, 0.0) + v
        busy = sum(s.device_s for s, _ in steps)
        report["decode_only_steps"] = len(steps)
        report["decode_only_busy_s"] = busy
        report["scope_s"] = dict(sorted(total.items(),
                                        key=lambda kv: -kv[1]))
        report["scope_sum_over_busy"] = sum(total.values()) / busy \
            if busy else None
        report["unscoped_share"] = total.get(scopes.UNSCOPED, 0.0) / busy \
            if busy else None
        # the share of that time in ops that carry no op_name themselves
        own = scopes.step_scopes(raw, inherit=False)
        traced = [s for s in run["steps"] if s.device_s is not None]
        keep = {id(s) for s, _ in steps}
        no_name = sum(sc.get(scopes.UNSCOPED, 0.0)
                      for s, sc in zip(traced, own) if id(s) in keep)
        report["no_op_name_share"] = no_name / busy if busy else None
        (out / "trace_scoped.json").write_text(
            json.dumps(scopes.trim_scoped(raw)))
    run["engine_events"] = state["tracer"].events
    names = ["kv_relayout_ms_per_step", "attention_hbm_roofline",
             "ffn_hbm_roofline", "first_token_hold_p50_ms"]
    report["readings"] = {n: run_mod.load_reader(n)(run) for n in names}
    win = window_steps(run)
    traced = [s for s in win if s.device_s is not None]
    rest = [s for s in win if s.device_s is None]

    def rate(ss):
        if len(ss) < 2:
            return None
        return sum(s.n_tokens for s in ss[1:]) / (ss[-1].t1 - ss[0].t1)
    report["tokens_per_s_traced"] = rate(traced)
    report["tokens_per_s_untraced"] = rate(rest)
    report["metrics"] = result.get("metrics")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=str(BENCH_DIR.parent))
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    run_mod = load_harness(Path(args.root).resolve())
    state, texts = {}, {}

    def on_engine(engine):
        state["tracer"] = engine.observer.tracer
        record_fused_programs(engine, texts)

    import cb.trace as cb_trace
    find = cb_trace.find_xplane

    def find_and_keep(trace_dir):
        path = find(trace_dir)
        if path is not None:
            try:
                from cb.scopes import load_scoped_xplane
                state["scoped"] = load_scoped_xplane(
                    path, programs=list(texts.values()))
            except Exception:  # noqa: BLE001 - the run goes on
                traceback.print_exc()
        return path
    cb_trace.find_xplane = find_and_keep

    load_reader = run_mod.load_reader

    def keep_run(name):
        read = load_reader(name)

        def read_and_keep(run):
            state["run"] = run
            return read(run)
        return read_and_keep
    run_mod.load_reader = keep_run

    try:
        result = run_mod.run_cell(args, fault=on_engine, out_dir=str(out))
    except run_mod.NoChip as e:
        run_mod.log(str(e))
        return 3
    run_mod.load_reader = load_reader
    (out / "result.json").write_text(json.dumps(result))
    try:
        counts = hlo_counts(texts, out)
        (out / "hlo.json").write_text(json.dumps(counts))
        run_mod.log(f"[hlo] instructions of the fused decode steps {counts}")
    except Exception:  # noqa: BLE001
        traceback.print_exc()
    if args.trace and "run" in state:
        try:
            report = study(run_mod, args, out, state, result)
            (out / "scopes.json").write_text(json.dumps(report, indent=1))
            run_mod.log(json.dumps(report))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
