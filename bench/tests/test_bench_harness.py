"""Every cell of BENCHMARK.json resolves by name to its files, and the file
keeps to the benchmark's contract. Cells, mixes and metrics that later
changes add are checked by these same tests."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import e2e  # noqa: E402
import harness  # noqa: E402

SPEC = harness.load_benchmark()
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head)"
                   r"|(_dim|_rank)$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == c.workload["config"]
    assert c.traffic["loop"] in ("open", "closed")
    assert c.chips in (1, 4)
    assert c.config["entry"] in ("serve", "solve")
    assert c.config["dtype"] == "float32"
    assert set(c.config["limits"]) == {"f_gap", "x_gap", "iters_gap"}
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end:
        assert m["name"] in e2e.METRICS
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_lists_cells_that_report_what_it_moves(metric):
    [m] = [x for x in SPEC["per_layer"] if x["name"] == metric]
    moves = [x for x in SPEC["end_to_end"] if x["name"] == m["moves"]]
    assert moves, f"{metric} moves an unknown metric {m['moves']!r}"
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert harness.reports(moves[0], cell), \
            f"{cell} does not report {m['moves']}, which {metric} moves"


def test_contract_keys_names_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3" and len(SPEC["command"]) <= 32
    for p in SPEC["paths"]:
        assert (BENCH.parent / p).is_dir()
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"}}
    for group, want in keys.items():
        for e in SPEC[group]:
            assert set(e) == want
            assert NAME.match(e["name"]) and len(e["why"]) <= 200
    for e in SPEC["configs"]:
        assert e["file"].startswith("bench/configs/")
        assert len(e["source"]) <= 200 and not any(
            WIDTH.search(k) for k in e["reduced"])
        assert json.loads((BENCH.parent / e["file"]).read_text())[
            "reduced"] == e["reduced"]
    for e in SPEC["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in SPEC["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for e in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
    names = [e["name"] for g in ("end_to_end", "per_layer")
             for e in SPEC[g]]
    assert len(names) == len(set(names))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for e in SPEC["per_layer"]:
        assert "\n" not in e["layer"] and len(e["layer"]) <= 200
    assert (BENCH.parent / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("cell,chips", [("rastrigin40-dgo.solve-4chip", 4),
                                        ("rastrigin40-dgo.solve-1chip", 1)])
def test_solve_cells_resolve_to_one_configuration(cell, chips):
    c = harness.load_cell(cell)
    assert c.chips == chips
    assert c.config["entry"] == "solve" and len(c.config["problems"]) == 1
    assert c.traffic["loop"] == "closed" and c.traffic["in_flight"] == 1
    assert {m["name"] for m in c.end_to_end} == {"solve_ms", "solve_p95_ms",
                                                 "setup_s"}
    layer = {m["name"] for m in c.per_layer}
    assert {"device_idle_share.solve", "engine_roofline"} <= layer
    assert ("collective_exposed_share" in layer) == (chips == 4)


def test_a_missing_cell_or_file_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric")


# ---------------------------------------------------------------------------
# a configuration's check, found by the name under its "check" key
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_every_configuration_names_a_check_that_resolves(config):
    [entry] = [c for c in SPEC["configs"] if c["name"] == config]
    cfg = json.loads((BENCH.parent / entry["file"]).read_text())
    module = harness.check_module(cfg["check"])
    assert callable(module.check) and callable(module.control_numbers)


STUB_CHECK = '''
def check(config, run, seed):
    return config["verdict"], {"gap": (float(seed), 1.0)}


def control_numbers(cell, seed, seconds):
    return {"gap": 2.0 * seed}, {"gap": 1.0}
'''


def stub_checkout(root: Path, check: str = "stub") -> Path:
    """A checkout holding one cell whose configuration names ``check``,
    and the module ``bench/checks/stub.py``."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "checks").mkdir()
    (root / "bench" / "checks" / "stub.py").write_text(STUB_CHECK)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "entry": "stub", "check": check, "verdict": True,
         "reduced": []}))
    (root / "bench" / "traffic" / "once.json").write_text('{"loop": "open"}')
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.once", "config": "toy",
                       "traffic": "once", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    return root


@pytest.fixture
def stub_run(monkeypatch, tmp_path):
    """``run.execute`` on the stub checkout's cell, with a driver that
    returns a run of ``failed`` failures and runs no program."""
    from types import SimpleNamespace

    import drive
    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))

    def execute(cell, failed=0):
        def driver(cell, seed, seconds, trace, compiles, t_start):
            return drive.Run(window_s=seconds, setup_s=0.5, attempted=4,
                             failed=failed, completed_in_window=4,
                             latencies_s=None, answers=[])
        monkeypatch.setitem(run.DRIVERS, "stub", driver)
        return run.execute(cell, 7, 1.0, False,
                           SimpleNamespace(platform="cpu", device_kind="cpu"),
                           1, 0.0)
    return execute


@pytest.mark.parametrize("verdict", [True, False])
def test_a_run_takes_the_verdict_of_the_check_its_configuration_names(
        stub_run, tmp_path, verdict):
    cell = harness.load_cell("toy.once", stub_checkout(tmp_path / "co"))
    assert cell.root == tmp_path / "co"
    cell.config["verdict"] = verdict
    out = stub_run(cell)
    assert out["correct"] is verdict
    assert out["check"] == {"gap": {"value": 7.0, "limit": 1.0}}
    assert list(out)[-2:] == ["check", "_notes"]
    assert any(n.startswith("check_s ") for n in out["_notes"])


def test_a_run_with_a_failed_request_is_not_correct_whatever_the_check(
        stub_run, tmp_path):
    cell = harness.load_cell("toy.once", stub_checkout(tmp_path / "co"))
    out = stub_run(cell, failed=1)
    assert out["check"] == {"gap": {"value": 7.0, "limit": 1.0}}
    assert out["correct"] is False


def test_an_unknown_check_is_an_error(stub_run, tmp_path):
    with pytest.raises(FileNotFoundError, match="check"):
        harness.check_module("no_such_check")
    cell = harness.load_cell("toy.once",
                             stub_checkout(tmp_path / "co", "no_such_check"))
    with pytest.raises(FileNotFoundError, match="no_such_check"):
        stub_run(cell)


def test_the_control_is_the_check_modules_own(tmp_path):
    import control

    cell = harness.load_cell("toy.once", stub_checkout(tmp_path / "co"))
    assert control.control_numbers(cell, 3, 1.0) == ({"gap": 6.0},
                                                     {"gap": 1.0})
