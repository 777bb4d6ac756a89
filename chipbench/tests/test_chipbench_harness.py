"""The harness refuses to run without a TPU, or without the program."""
import os
import shutil
import subprocess
import sys

import smoke

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
    import json
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
