"""BENCHMARK.json keeps the benchmark's contract, and every piece it
names has its file."""
import json
import re

import pytest

from chipbench.bench import BENCH_DIR, ROOT, load_json

M = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|^hidden_size$|intermediate_size|latent|"
                   r"state_size|proj|expan|experts_per_tok|head_size")
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = M["workloads"]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= M["run_seconds"] <= 51
    for p in M["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert (ROOT / p).is_dir()
    assert len(json.dumps(M)) < 64 * 1024


def test_a_full_check_of_24_cells_fits_its_time():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entries(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    for cell in m.get("workloads", []):
        assert cell in {c["name"] for c in CELLS}


def test_metric_names_are_unique():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))


def test_end_to_end_bounds():
    names = {m["name"] for m in M["end_to_end"]}
    assert "setup_s" in names
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entries(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert _line(m["layer"])
    moves = next(e for e in M["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", [c["name"] for c in CELLS]):
        # every cell that reports this metric reports what it moves
        assert "workloads" not in moves or cell in moves["workloads"]
    assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_every_kernel_roofline_has_a_step_mfu_beside_it():
    for m in M["per_layer"]:
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in M["per_layer"])


@pytest.mark.parametrize("c", CELLS, ids=lambda c: c["name"])
def test_cells(c):
    assert set(c) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(c["name"]) and NAME.match(c["traffic"])
    assert c["chips"] in (1, 4) and _line(c["why"])
    assert c["config"] in {g["name"] for g in M["configs"]}
    assert (BENCH_DIR / "traffic" / f"{c['traffic']}.json").is_file()
    assert (BENCH_DIR / "limits" / f"{c['name']}.json").is_file()

    def reports(m):
        return "workloads" not in m or c["name"] in m["workloads"]

    e2e = [m["name"] for m in M["end_to_end"] if reports(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m) for m in M["per_layer"])


def test_cells_are_unique_and_at_most_half_on_four_chips():
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(pairs) == len(set(pairs))
    assert len({c["name"] for c in CELLS}) == len(CELLS)
    four = sum(c["chips"] == 4 for c in CELLS)
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("g", M["configs"], ids=lambda g: g["name"])
def test_configs(g):
    assert set(g) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(g["name"]) and _line(g["why"]) and _line(g["source"])
    assert g["file"].startswith("chipbench/configs/")
    assert any(c["config"] == g["name"] for c in CELLS)
    cfg = load_json(ROOT / g["file"])
    assert len(g["reduced"]) <= 16
    assert sorted(g["reduced"]) == sorted(cfg["reduced"])
    for key in g["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key)
    assert len({x["file"] for x in M["configs"]}) == len(M["configs"])


@pytest.mark.parametrize("c", CELLS, ids=lambda c: c["name"])
def test_limits_files_hold_the_readings_they_came_from(c):
    limits = load_json(BENCH_DIR / "limits" / f"{c['name']}.json")
    for name, lim in limits.items():
        assert lim["lower"] < lim["limit"] < lim["upper"], name
        assert lim["from"]


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_list_their_cells(m):
    # a reader that finds nothing in a cell returns None, and a cell
    # whose --trace 1 line lacks a metric it should report is refused
    assert m["workloads"]


@pytest.mark.parametrize("c", CELLS, ids=lambda c: c["name"])
def test_a_cell_on_four_chips_runs_a_plan_over_them(c):
    from chipbench.bench import program_config
    from repro.parallel.plan import resolve_plan
    g = next(g for g in M["configs"] if g["name"] == c["config"])
    cfg = load_json(ROOT / g["file"])
    plan = cfg.get(cfg["kind"], {}).get("plan")
    if c["chips"] == 1:
        assert plan is None
    else:
        assert resolve_plan(plan, program_config(cfg),
                            chips=c["chips"]).chips == c["chips"]
