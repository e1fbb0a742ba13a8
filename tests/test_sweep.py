"""repro.sim.sweep: grid expansion, JSONL rows, process-pool equivalence,
and the contract that sweep cells reproduce the fleet_scale benchmark
tables' numbers (each cell is just a spec — same spec, same metrics)."""
import json

import pytest

from repro.sim import (ScenarioSpec, Simulation, apply_overrides,
                       get_scenario, grid_cells, random_cells, run_sweep)
from repro.sim.sweep import main as sweep_main
from repro.sim.sweep import run_cell

SMALL = {"workload.horizon_s": 5.0, "topology.num_devices": 8}


def _base():
    return apply_overrides(get_scenario("smoke-lm"), SMALL)


def test_grid_cells_cartesian_order():
    cells = grid_cells(_base(), {"topology.num_devices": [4, 8],
                                 "router.name": ["rr", "jsq"]})
    combos = [(c.topology.num_devices, c.router.name) for c in cells]
    # row-major: later axes vary fastest
    assert combos == [(4, "rr"), (4, "jsq"), (8, "rr"), (8, "jsq")]
    # cells are independent specs; the base is untouched
    assert _base().topology.num_devices == 8


def test_grid_cells_reject_unknown_axis():
    with pytest.raises(ValueError):
        grid_cells(_base(), {"topology.nope": [1]})


def test_random_cells_deterministic_in_seed():
    axes = {"seed": [1, 2, 3, 4], "router.name": ["rr", "jsq"]}
    a = random_cells(_base(), axes, 6, seed=9)
    b = random_cells(_base(), axes, 6, seed=9)
    assert [c.to_dict() for c in a] == [c.to_dict() for c in b]
    assert len(a) == 6
    assert any(x.to_dict() != y.to_dict() for x, y in zip(a, a[1:]))


def test_run_sweep_rows_and_jsonl(tmp_path):
    out = tmp_path / "rows.jsonl"
    cells = grid_cells(_base(), {"router.name": ["rr", "jsq"]})
    rows = run_sweep(cells, out_path=str(out))
    assert [r["spec"]["router"]["name"] for r in rows] == ["rr", "jsq"]
    on_disk = [json.loads(line) for line in out.read_text().splitlines()]
    assert on_disk == json.loads(json.dumps(rows))  # canonical JSON rows
    for row in rows:
        # a row's spec re-runs to the identical metrics (reproducibility
        # contract: the JSONL is self-describing)
        again = Simulation(ScenarioSpec.from_dict(row["spec"])).run()
        assert again.summary() == row["metrics"]


def test_run_sweep_parallel_matches_inline():
    cells = grid_cells(_base(), {"router.name": ["rr", "jsq"],
                                 "seed": [0, 1]})
    inline = run_sweep(cells)
    pooled = run_sweep(cells, processes=2)
    strip = lambda rows: [{k: v for k, v in r.items() if k != "wall_s"}
                          for r in rows]                      # noqa: E731
    canon = lambda rows: json.loads(json.dumps(strip(rows)))  # noqa: E731
    assert canon(inline) == canon(pooled)


def test_run_sweep_refuses_pooled_real_decode():
    """Real decode runs the model, which belongs in the one process that
    holds the accelerator: a pool of workers must refuse it up front."""
    cells = grid_cells(_base(), {"seed": [0, 1]})
    cells[1] = apply_overrides(cells[1], {"engine.real_decode": True})
    with pytest.raises(ValueError, match="real_decode"):
        run_sweep(cells, processes=2)


def test_sweep_cell_reproduces_fleet_scale_table_cells():
    """The --coop / --mobility benchmark tables are sweeps now; their cells
    must equal a direct Simulation of the registered scenario (the pinned
    smoke numbers in fleet_scale's --smoke gates rest on this)."""
    import sys
    sys.path.insert(0, "benchmarks")
    try:
        from fleet_scale import SEED, lm_cell_spec, mobility_cell_spec
    finally:
        sys.path.pop(0)
    # --coop --smoke cell == registry "coop" scenario
    row = run_cell(lm_cell_spec(40, "joint", seed=SEED))
    assert row["metrics"] == Simulation(get_scenario("coop")).run().summary()
    # --mobility --smoke bocd cell == registry "smoke-mobility" scenario
    mob = get_scenario("smoke-mobility")
    row = run_cell(mobility_cell_spec(mob.topology.num_devices,
                                      mob.topology.speed, "bocd", seed=SEED))
    assert row["metrics"] == Simulation(mob).run().summary()


def test_sweep_cli_grid(tmp_path, capsys):
    out = tmp_path / "cli.jsonl"
    rc = sweep_main([
        "--scenario", "smoke-lm",
        "--set", "workload.horizon_s=4", "--set", "topology.num_devices=6",
        "--grid", 'router.name=["rr","jsq"]',
        "--out", str(out)])
    assert rc == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(rows) == 2
    assert {r["spec"]["router"]["name"] for r in rows} == {"rr", "jsq"}


def test_sweep_cli_rejects_bad_usage(tmp_path):
    with pytest.raises(ValueError):
        sweep_main(["--scenario", "smoke-lm", "--out",
                    str(tmp_path / "x.jsonl")])          # no --grid
    with pytest.raises(ValueError):
        sweep_main(["--out", str(tmp_path / "x.jsonl"),
                    "--grid", "seed=[1]"])               # no base spec
    with pytest.raises(ValueError):
        sweep_main(["--scenario", "smoke-lm", "--grid", "seed=1",
                    "--out", str(tmp_path / "x.jsonl")])  # not a list
