"""Shared set-up of the benchmark's own tests (run them with
``python -m pytest benchmarks/chip/tests``).

``tiny_root`` is a checkout-shaped directory holding ``BENCHMARK.json`` and
a copy of the benchmark in which every configuration is cut to a width the
CPU runs in seconds and every mix to a few short requests.  Cells, names,
metrics and files are otherwise the committed ones.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]
for p in (BENCH_DIR, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
               "num_attention_heads": 4, "head_dim": 16, "vocab_size": 256}
TINY_MIX = {"clients": 4, "prompt_len": 8, "output_len": 6, "max_len": 16,
            "check_requests": 4}
# the smallest size at which float8's error, summed over the layers, reads
# as large against the logits' spread as it does at the cells' own size
CONTROL_WIDTHS = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 4,
                  "num_attention_heads": 4, "head_dim": 64, "vocab_size": 1024}
CONTROL_MIX = dict(TINY_MIX, output_len=16, max_len=32)
# every committed cell, by name
CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


def make_tiny_root(root: Path, widths=TINY_WIDTHS, mix=TINY_MIX) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copytree(BENCH_DIR, root / bench["paths"][0],
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for c in bench["configs"]:
        f = root / c["file"]
        conf = json.loads(f.read_text())
        kv_groups = conf["num_attention_heads"] // conf["num_key_value_heads"]
        conf.update(widths, num_key_value_heads=widths["num_attention_heads"]
                    // min(kv_groups, 2))
        f.write_text(json.dumps(conf))
    for t in (root / bench["paths"][0] / "traffic").glob("*.json"):
        t.write_text(json.dumps({**json.loads(t.read_text()), **mix}))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="session")
def control_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("control"), CONTROL_WIDTHS, CONTROL_MIX)


@pytest.fixture(scope="session")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())
