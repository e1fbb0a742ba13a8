"""Mini dry-run on 8 virtual host devices (subprocess — the device-count env
var must be set before jax initializes, and the main test process must keep
seeing 1 device)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.config import ShapeConfig
from repro.models import Model
from jax.sharding import AxisType
from repro.launch.steps import make_step
from repro.launch.dryrun import collective_stats

arch, kind, multipod = "%(arch)s", "%(kind)s", %(multipod)s
if multipod:
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
else:
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
cfg = get_smoke_config(arch)
model = Model(cfg)
shape = ShapeConfig("t", 64, 8, kind)
step, abstract_inputs = make_step(model, mesh, shape)
with mesh:
    lowered = step.lower(*abstract_inputs())
compiled = lowered.compile()
ca = compiled.cost_analysis()
coll = collective_stats(compiled.as_text())
print(json.dumps({"flops": ca.get("flops", 0.0),
                  "coll": coll["total_link_bytes"],
                  "mem": compiled.memory_analysis().argument_size_in_bytes}))
"""


def _run(arch, kind, multipod):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c",
                          SCRIPT % dict(arch=arch, kind=kind, multipod=multipod)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("arch,kind", [
    ("granite-3-2b", "train"),
    ("llama4-scout-17b-a16e", "train"),
    ("rwkv6-3b", "decode"),
    ("zamba2-2.7b", "prefill"),
])
def test_small_mesh_dryrun(arch, kind):
    r = _run(arch, kind, False)
    assert r["flops"] > 0
    assert r["coll"] > 0      # sharded step must communicate


@pytest.mark.slow
def test_small_mesh_multipod():
    r = _run("granite-3-2b", "train", True)
    assert r["flops"] > 0 and r["coll"] > 0
