"""The benchmark is found from data: every cell loads by name, an unknown
name is refused, and ``BENCHMARK.json`` keeps the shape its readers
expect.  No chip and no program run here."""
import json
import os
import re

import numpy as np
import pytest

from harness import cells, systems, traffic

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark(ROOT)


def test_every_cell_loads_from_data(bench):
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], ROOT)
        assert cell.chips == w["chips"]
        assert traffic.validate(cell.traffic)["kind"] == "score_batches"
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "every cell reports a per-layer metric"
        for m in cell.per_layer:
            assert callable(m["read"])


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="unknown workload 'no.such.cell'"):
        cells.load_cell("no.such.cell", ROOT)


def test_unknown_metric_reader_is_refused():
    with pytest.raises(FileNotFoundError, match="no reader"):
        cells.load_reader("no_such_metric.score")


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in seen
            seen.add(entry["name"])
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("name,count", [("ffp_n11_lan", 271),
                                        ("joint_n11_wan", 404)])
def test_configurations_enumerate_their_systems(name, count):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    defs = systems.enumerate_systems(cfg)
    assert len(defs) == count
    assert len({d.label for d in defs}) == count
    members = systems.to_program(defs, cfg["n"])
    assert [m.label for m in members] == [d.label for d in defs]


def test_enumeration_refuses_a_wrong_count():
    with open(os.path.join(BENCH, "configs", "ffp_n11_lan.json")) as f:
        cfg = json.load(f)
    cfg["expected_systems"] = 270
    with pytest.raises(ValueError, match="enumerates 271 systems"):
        systems.enumerate_systems(cfg)


def test_joint_space_matches_its_parts():
    with open(os.path.join(BENCH, "configs", "joint_n11_wan.json")) as f:
        cfg = json.load(f)
    kinds = [d.kind for d in systems.enumerate_systems(cfg)]
    assert [kinds.count(k) for k in ("card", "relaxed", "grid",
                                     "weighted")] == [271, 125, 3, 5]


def test_reference_crash_budgets_match_the_program():
    with open(os.path.join(BENCH, "configs", "joint_n11_wan.json")) as f:
        cfg = json.load(f)
    defs = systems.enumerate_systems(cfg)
    ft = systems.reference_fault_tolerance(defs, cfg["n"])
    from repro.frontier.score import _as_masks, _fault_tolerance
    masks, native, _ = _as_masks(systems.to_program(defs, cfg["n"]), None)
    for i, (s, m) in enumerate(zip(native, masks)):
        f = _fault_tolerance(s, m)
        assert tuple(ft[i]) == (f["steady_state_fast"], f["phase1"],
                                f["phase2_classic"]), defs[i].label


def test_batch_seeds_are_deterministic_and_fit_32_bits():
    big = 2 ** 31 + 12345
    a = [traffic.batch_seed(big, b) for b in range(50)]
    assert a == [traffic.batch_seed(big, b) for b in range(50)]
    assert len(set(a)) == 50 and all(0 <= s < 2 ** 31 for s in a)
    assert a != [traffic.batch_seed(big + 1, b) for b in range(50)]
    picks = {traffic.checked_batch(s, 7) for s in range(200)}
    assert picks == set(range(1, 8))
    assert traffic.checked_batch(big, 7) == traffic.checked_batch(big, 7)


def test_traffic_validation_refuses_unknown_keys():
    with open(os.path.join(BENCH, "traffic", "score.json")) as f:
        t = json.load(f)
    with pytest.raises(ValueError, match="unknown traffic keys"):
        traffic.validate(dict(t, burst=3))
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic.validate(dict(t, kind="open_loop"))
    with pytest.raises(ValueError, match="trials must exceed chunk"):
        traffic.validate(dict(t, trials=100))


def test_reference_rows_pad_unused_rows_unreachable():
    with open(os.path.join(BENCH, "configs", "joint_n11_wan.json")) as f:
        cfg = json.load(f)
    rows = systems.reference_rows(systems.enumerate_systems(cfg), cfg["n"])
    w, t = rows["p1"]
    assert w.shape == (404, 9, 11)      # the 3x3 grid has 9 phase-1 rows
    assert np.isinf(t[0, 1:]).all() and t[0, 0] > 0
