"""The command line: no TPU, no result; the files a cell needs exist for
every cell of ``BENCHMARK.json``, and every metric has a reader."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-2b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_finds_its_files(cell):
    spec = harness.cell_spec(cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    family = harness.FAMILIES / f"{spec['config']['model_type']}.py"
    assert family.is_file()
    assert harness.family(spec["config"]).__file__ == str(family)
    assert spec["check"]["mean_logit_gap"] > 0
    assert NAME.match(cell)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"])
        assert set(m.get("workloads", [cell])) <= {
            w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.device_peaks("cpu")
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
