"""The whole run on the CPU at tiny widths, everything but the look for a
chip: every committed cell end to end, the refusals of the entry point,
and a new cell, configuration, mix and metric added by files alone."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from conftest import BENCH_DIR, CELLS, REPO, make_tiny_root

KIND = "TPU v5 lite"      # the peak table's key; the CPU has no entry


def run_cell(root, workload, *, seed=2**31 + 11, seconds=0.3):
    import jax
    import harness
    return harness.run_cell(Path(root), workload, seed=seed, seconds=seconds,
                            trace=False, devices=jax.devices(),
                            t_start=time.perf_counter(), kind=KIND)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end_and_is_correct(tiny_root, bench, workload):
    result = run_cell(tiny_root, workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["checks"]["token_deficit"]["value"] <= \
        result["checks"]["token_deficit"]["limit"]


def _entry(cwd, env_extra, argv=("--workload", CELLS[0], "--seed", "1", "--seconds", "1")):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, str(Path(cwd) / "benchmarks/chip/run.py"),
                           *argv], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_entry_refuses_the_cpu():
    p = _entry(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_entry_refuses_a_directory_without_the_program(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _entry(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""


def _digest(d: Path):
    return {str(f.relative_to(d)): hashlib.sha1(f.read_bytes()).hexdigest()
            for f in sorted(d.rglob("*")) if f.is_file() and "__pycache__" not in f.parts}


def test_new_cell_config_mix_and_metric_are_files_and_entries(tmp_path):
    import harness
    from reduction import reduce_file
    root = make_tiny_root(tmp_path)
    bdir = root / "benchmarks/chip"
    before = _digest(bdir)
    conf = json.loads((bdir / "configs/olmo-1b.json").read_text())
    # its own program module: a layout of the program's tree is a file too
    (bdir / "programs/tiny_gqa.py").write_text((bdir / "programs/dense.py").read_text())
    conf.update(name="tiny-gqa", num_key_value_heads=1, program="programs/tiny_gqa.py")
    (bdir / "configs/tiny-gqa.json").write_text(json.dumps(conf))
    (bdir / "traffic/lockstep.p4.o3.b2.json").write_text(json.dumps(
        {"engine": "engines/lockstep.py", "loop": "closed", "clients": 2,
         "prompt_len": 4, "output_len": 3, "max_len": 8, "check_requests": 2}))
    (bdir / "limits/tiny-gqa.short.json").write_text(
        (bdir / f"limits/{CELLS[0]}.json").read_text())
    (bdir / "metrics/decode_calls.py").write_text(
        "def read(run):\n    ms = run.device_ms('decode')\n"
        "    return len(ms) if ms else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-gqa", "source": "https://example.org/tiny",
                             "file": "benchmarks/chip/configs/tiny-gqa.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny-gqa.short", "config": "tiny-gqa",
                               "traffic": "lockstep.p4.o3.b2", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "decode_calls", "unit": "calls",
                               "better": "higher", "source": "device_trace",
                               "layer": "decode step", "moves": "output_tok_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result = run_cell(root, "tiny-gqa.short")
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"output_tok_s", "setup_s"}

    cell = harness.Cell(root, "tiny-gqa.short")
    assert "decode_calls" in [m["name"] for m in cell.metrics(trace=True)]
    calls = json.loads((BENCH_DIR / "tests/data/lockstep_tiny.calls.json").read_text())
    red = reduce_file(BENCH_DIR / "tests/data/lockstep_tiny.xplane.pb",
                      host_spans=cell.module(cell.traffic["engine"]).HOST_SPANS)
    run = harness.Run(cell, 0.0, 1.0, [], [tuple(c) for c in calls["calls"]], 0.0,
                      harness.peaks_for(bdir, KIND), cell.module(conf["counts"]), red)
    n_decode = sum(c[0] == "decode" for c in run.calls)
    assert cell.module("metrics/decode_calls.py").read(run) == n_decode
    after = _digest(bdir)
    assert {k: v for k, v in after.items() if k in before} == before
