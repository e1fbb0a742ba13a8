"""chip_smoke.py's body on the CPU (the rehearsal of the chip run).

* the reduced-config run completes every request, keeps every logits call
  finite, decodes through the cache to the one-shot forward pass's logits
  and through the arena to the serial step's, in float32 and in bfloat16;
  in float32 the serial replay's token streams equal the arena's (the
  decode-path contract) and the two steps' logits agree to f32 rounding;
* the full-width workload itself (timing only, no model) offloads every
  request to the edge and mixes exits, so the chip run exercises the
  multi-exit arena;
* ``main`` refuses to run without a TPU and prints no result.
"""
import dataclasses
import importlib.util
import os

import pytest

from repro.sim import Simulation

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_smoke_body_reduced_config(chip_smoke, dtype):
    rep = chip_smoke.run_smoke(
        chip_smoke.smoke_spec(full_width=False, dtype=dtype))
    for name, (passed, detail) in rep["checks"].items():
        assert passed, f"{name}: {detail}"
    assert set(rep["checks"]) == {"completion", "reference_logits",
                                  "arena_logits", "finite_logits"}
    assert rep["dtype"] == dtype
    assert rep["cfg"].num_layers == 4                 # the reduced preset
    warm, run = rep["runs"]["warmup"], rep["runs"]["run"]
    assert run["tokens"] == warm["tokens"] > 0
    assert run["compile_s"] == 0.0                    # warm-up compiled all
    assert rep["arena"]["calls"] > 0 and rep["jit_variants"]["arena"] > 1
    if dtype == "float32":                        # the pinned contract
        held, detail = rep["identity"]
        assert held, detail
        assert rep["arena_distance"] < 1e-5           # float32 rounding


def test_full_width_workload_offloads_and_mixes_exits(chip_smoke):
    spec = chip_smoke.smoke_spec()
    spec = dataclasses.replace(
        spec, engine=dataclasses.replace(spec.engine, real_decode=False))
    sim = Simulation(spec)
    summary = sim.run().summary()
    sc = sim.scenario
    assert sc.cfg.num_layers == 40 and sc.cfg.d_model == 2048
    assert sc.cfg.vocab_size == 49155
    assert len(sc.graph.branches) == 5                # 4 exits + final
    assert 12 <= len(sc.workload) <= 20
    assert {r.prompt_len for r in sc.workload} == {128}
    assert all(r.plan.partition > 0 for r in sc.workload)   # all at the edge
    assert len(summary["exit_histogram"]) > 1
    assert summary["requests"] == len(sc.workload)


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out
