"""A configuration, a traffic mix, a cell and a per-layer metric are each
new files plus new entries: the rehearsal manifest adds one of each beside
the benchmark's own, and the harness finds them by name."""

import json
from pathlib import Path

from benchmark import run

REHEARSAL = Path(__file__).parent / "rehearsal" / "manifest.json"


def test_rehearsal_manifest_adds_entries_as_files_only():
    man = run.Manifest(REHEARSAL)
    cell = man.cell("tiny.toy_closed")
    assert man.config(cell["config"])["hidden_size"] == 64
    assert man.traffic(cell["traffic"])["callers"] == 3             # beside the manifest
    assert man.traffic("chat_closed")["callers"] == 8               # the benchmark's own
    names = [m["name"] for m in man.metrics("per_layer", "tiny.toy_closed")]
    assert "toy_requests_done" in names and "slots_busy_avg" in names
    only = dict(man.data["per_layer"][0], workloads=["another.cell"])
    man.data["per_layer"][0] = only                                 # a metric of other cells
    assert only["name"] not in [m["name"] for m in man.metrics("per_layer", "tiny.toy_closed")]
    mod = run.load_module(man.find("layer_metrics", "toy_requests_done", (".py",)))
    assert mod.read({"client": {"completed": 7}}) == 7.0


def test_benchmark_json_agrees_with_the_files_it_names():
    man = run.Manifest(run.ROOT / "BENCHMARK.json")
    for conf in man.data["configs"]:
        body = json.loads((run.ROOT / conf["file"]).read_text())
        assert body["source"] == conf["source"] and body["reduced"] == conf["reduced"]
        man.find("references", body["reference"], (".py",))
    cells = {w["name"] for w in man.data["workloads"]}
    e2e = {m["name"] for m in man.data["end_to_end"]}
    for w in man.data["workloads"]:
        man.traffic(w["traffic"])
        reported = {m["name"] for m in man.metrics("end_to_end", w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        for m in man.metrics("per_layer", w["name"]):
            assert m["moves"] in reported, (w["name"], m["name"])
    for m in man.data["per_layer"]:
        mod = run.load_module(man.find("layer_metrics", m["name"], (".py",)))
        assert {k: m[k] for k in mod.METRIC} == mod.METRIC
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells


def test_a_reader_that_finds_nothing_returns_nothing():
    man = run.Manifest(run.ROOT / "BENCHMARK.json")
    empty = {"client": {}, "records": [], "window": (0.0, 1.0), "samples": [],
             "stats_before": None, "stats_after": None, "trace": {}, "traffic": {"loop": "closed"},
             "engine": {}, "config": {}, "device": {}, "env": {}}
    for name in ("admit_wait_p50_ms", "kv_pool_used_share", "decode_step_roofline", "gap_p50_ms"):
        mod = run.load_module(man.find("layer_metrics", name, (".py",)))
        assert mod.read(empty) is None
