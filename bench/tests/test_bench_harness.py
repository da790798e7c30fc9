"""The harness finds every piece by name, and refuses unknown names."""
import json

import pytest

from bench import harness


def catalog_with_new_pieces(root):
    """A catalog to which only files and entries were added: a new
    configuration, traffic mix and per-layer metric."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    (root / "bench" / "configs" / "cfg-new.json").write_text(
        json.dumps({"frame_hw": 32, "check_limits": {}}))
    (root / "bench" / "traffic" / "mix-new.json").write_text(
        json.dumps({"kind": "ingest_stream", "feed_rows": 64}))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(record):\n    return record['run']['x'] * 2\n")
    (root / "bench" / "metrics" / "silent.py").write_text(
        "def read(record):\n    return None\n")
    spec = {"paths": ["bench"],
            "configs": [{"name": "cfg-new",
                         "file": "bench/configs/cfg-new.json"}],
            "workloads": [{"name": "cell-new", "config": "cfg-new",
                           "traffic": "mix-new", "chips": 1}],
            "end_to_end": [{"name": "ingest_frames_per_s", "unit": "frames/s",
                            "workloads": ["cell-new"]},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "new_metric", "unit": "%",
                           "moves": "ingest_frames_per_s"},
                          {"name": "silent", "unit": "%",
                           "moves": "ingest_frames_per_s",
                           "workloads": ["cell-new"]},
                          {"name": "elsewhere", "unit": "%",
                           "moves": "query_latency_ms"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Catalog(root)


def test_finds_config_traffic_and_metric(tmp_path):
    cat = catalog_with_new_pieces(tmp_path)
    cell = cat.cell("cell-new")
    assert cat.config(cell["config"])["frame_hw"] == 32
    traffic = cat.traffic(cell["traffic"])
    assert cat.load_kind(traffic["kind"]).__name__ == "IngestStream"
    assert [m["name"] for m in cat.end_to_end("cell-new")] == \
        ["ingest_frames_per_s", "setup_s"]
    assert [m["name"] for m in cat.per_layer("cell-new")] == \
        ["new_metric", "silent"]
    assert cat.reader("new_metric")({"run": {"x": 21}}) == 42
    assert cat.reader("silent")({}) is None


def test_new_traffic_kind_from_its_own_file(tmp_path):
    cat = catalog_with_new_pieces(tmp_path)
    (tmp_path / "bench" / "traffic" / "bursts.py").write_text(
        "class Load:\n    pass\n")
    assert cat.load_kind("bursts").__name__ == "Load"


@pytest.mark.parametrize("lookup,name", [
    ("cell", "no-such-cell"), ("config", "no-such-config"),
    ("traffic", "no-such-mix"), ("reader", "no_such_metric"),
    ("load_kind", "no_such_kind")])
def test_refuses_unknown_names(tmp_path, lookup, name):
    cat = catalog_with_new_pieces(tmp_path)
    with pytest.raises(harness.UnknownName):
        getattr(cat, lookup)(name)


def test_no_benchmark_file_is_refused(tmp_path):
    with pytest.raises(harness.UnknownName):
        harness.Catalog(tmp_path)


def test_limits_are_required():
    with pytest.raises(harness.UnknownName):
        harness.checks_of({"gap_max": 0.0}, {})
    assert harness.checks_of({"m": 1}, {"m": 2}) == \
        {"m": {"value": 1, "limit": 2}}


def test_repo_catalog_is_complete():
    """Every cell of the real BENCHMARK.json resolves, and every metric
    it reports has a reader."""
    cat = harness.Catalog(harness.Path(__file__).resolve().parents[2])
    for cell in cat.spec["workloads"]:
        cat.config(cell["config"])
        cat.load_kind(cat.traffic(cell["traffic"])["kind"])
        for m in cat.per_layer(cell["name"]):
            cat.reader(m["name"])
