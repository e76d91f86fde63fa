"""run.py finds every committed cell's files by name, and refuses to
measure anything without a TPU."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests import helpers

RUN = os.path.join(helpers.ROOT, "bench", "run.py")
ARGS = ["--workload", "bitnet-0.73b.edge", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def call(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_nonzero_without_a_tpu():
    p = call(helpers.ROOT, RUN)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(helpers.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(helpers.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = call(tmp_path, str(tmp_path / "bench" / "run.py"))
    assert p.returncode != 0
    assert no_result(p.stdout)


with open(os.path.join(helpers.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_by_name(cell):
    """Each cell's configuration, mix, settings and metric readers are
    found by name, and it reports set-up, another end-to-end metric and a
    per-layer metric whose end-to-end metric it reports too."""
    spec = run.load_cell(cell)
    assert spec["cfg"]["name"] == spec["workload"]["config"]
    e2e = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert callable(importlib.import_module(
            f"bench.metrics.{m['name']}").read)
