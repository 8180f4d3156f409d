"""Configurations, traffic mixes and metric readers are found by name."""
import json

import pytest

from bench import run as harness


def test_every_name_in_the_benchmark_resolves():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"], 1, spec=spec)
        assert cell.config["pipeline"]["dim"] > 0
        assert cell.mix.get("ingest") or cell.mix.get("score")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]).read)


@pytest.mark.parametrize("kind,call", [
    ("workload", lambda: harness.Cell("no.such", 1)),
    ("traffic", lambda: harness.traffic("no_such_mix")),
    ("metric", lambda: harness.reader("no_such_metric")),
])
def test_unknown_name_is_an_error(kind, call):
    with pytest.raises(harness.BenchError, match=kind if kind != "workload"
                       else "unknown workload"):
        call()


def test_unknown_configuration_is_an_error():
    spec = harness.load_spec()
    spec = json.loads(json.dumps(spec))
    spec["workloads"][0]["config"] = "no_such_config"
    with pytest.raises(harness.BenchError, match="unknown configuration"):
        harness.Cell(spec["workloads"][0]["name"], 1, spec=spec)


def test_every_compared_number_has_a_limit():
    spec = harness.load_spec()
    need = {"ingest": {"thr_ulps", "center_ulps", "trained_gap", "mass_gap",
                       "window_short", "fed_gap"},
            "score": {"dist_ulps", "score_ulps", "argmin_bad", "flag_bad",
                      "missing"}}
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"], 1, spec=spec)
        for part, names in need.items():
            if cell.mix.get(part):
                assert names <= set(cell.config["limits"]), w["name"]
