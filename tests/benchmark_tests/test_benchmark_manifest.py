"""BENCHMARK.json keeps to the contract's names, units and lengths, and the
harness is driven by data: a configuration, a mix and a per-layer metric
are added as new files, with no edit to a file that is there."""

import json
import os
import re
import shutil

import pytest
from bench_paths import BENCH, ROOT

from kbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer",
                             "trace_in_run"}
    assert MANIFEST["trace_in_run"] is True  # the harness takes --trace 2
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in MANIFEST["paths"])
    assert len(MANIFEST["command"]) <= 32
    assert all(one_line(w) for w in MANIFEST["command"])
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert one_line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    for cell in metric.get("workloads", ()):
        assert cell in CELLS


def test_names_are_unique_and_every_config_is_used():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in MANIFEST["workloads"]} == \
        {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and one_line(config["why"])
    assert one_line(config["source"]) and config["source"].startswith("https://")
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        data = json.load(f)
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    for key in ("hidden_size", "num_hidden_layers", "vocab_size",
                "num_attention_heads", "intermediate_size"):
        assert isinstance(data[key], int)
    assert data["deployment"]["chips"] in (1, 4)


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_what_it_reports(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    resolved = manifest.resolve_cell(cell["name"])
    assert resolved.chips == resolved.deployment["chips"]
    e2e = {m["name"] for m in resolved.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and resolved.per_layer
    # a per-layer metric's `moves` is reported in every cell where it is
    for m in resolved.per_layer:
        assert m["moves"] in e2e, (m["name"], cell["name"])


@pytest.mark.parametrize(
    "metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_reader_agrees_with_the_manifest(metric):
    reader = manifest.load_reader(metric["name"])
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        metric["layer"], metric["unit"], metric["source"], metric["moves"])
    assert reader.__doc__ and reader.__doc__.strip()


def test_files_under_paths_are_named_from_a_names_characters():
    for path in MANIFEST["paths"]:
        for base, _dirs, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert PATH.match(rel), rel


def test_unknown_device_is_an_error_not_a_default():
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError, match="no default"):
        manifest.load_peaks("TPU v9000")


def test_unknown_workload_and_missing_reader_are_named():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.resolve_cell("qwen3-4b.nope")
    with pytest.raises(manifest.ManifestError, match="no reader"):
        manifest.load_reader("nothing.here")


def test_a_config_a_mix_and_a_layer_metric_are_added_as_files_only(tmp_path):
    """Copy the benchmark, ADD a configuration file, a traffic file, a cell
    file, a reader and the BENCHMARK.json entries — and resolve the new
    cell through the unchanged harness.  No file that was there is
    edited (checked by hash)."""
    import hashlib

    import run as bench_run  # benchmark/run.py

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))

    def digest():
        out = {}
        for base, _d, files in os.walk(root / "benchmark"):
            for name in files:
                p = os.path.join(base, name)
                with open(p, "rb") as f:
                    out[p] = hashlib.sha256(f.read()).hexdigest()
        return out

    before = digest()
    bench = root / "benchmark"
    with open(bench / "configs" / "qwen3-4b.json") as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=12, source="https://example.org/new-model")
    (bench / "configs" / "new-model.json").write_text(json.dumps(cfg))
    with open(bench / "traffic" / "chat.json") as f:
        mix = json.load(f)
    mix["gaps"] = {"dist": "lognormal", "median": 1.0, "sigma": 1.5}
    (bench / "traffic" / "chat-burst.json").write_text(json.dumps(mix))
    with open(bench / "cells" / "qwen3-4b.chat.json") as f:
        pair = json.load(f)
    pair["rate"] = 2.5
    (bench / "cells" / "new-model.chat-burst.json").write_text(json.dumps(pair))
    (bench / "layer_metrics" / "engine.generated_tokens.py").write_text(
        '"""Generated tokens in the window."""\n'
        'LAYER, UNIT, SOURCE, MOVES = "scheduler", "tokens", '
        '"program_counter", "output_tok_s"\n\n\n'
        'def read(run):\n    return run["after"]["n"] - run["before"]["n"]\n')
    new = json.loads(json.dumps(MANIFEST))
    new["configs"].append({
        "name": "new-model", "source": "https://example.org/new-model",
        "file": "benchmark/configs/new-model.json",
        "reduced": ["num_hidden_layers"], "why": "a test"})
    new["workloads"].append({
        "name": "new-model.chat-burst", "config": "new-model",
        "traffic": "chat-burst", "chips": 1, "why": "a test"})
    new["per_layer"].append({
        "name": "engine.generated_tokens", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "output_tok_s", "workloads": ["new-model.chat-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    cell = manifest.resolve_cell(
        "new-model.chat-burst", root=str(root), bench_dir=str(bench))
    assert cell.hf_config["num_hidden_layers"] == 12
    assert "deployment" not in cell.hf_config
    assert cell.traffic["gaps"]["dist"] == "lognormal" and cell.pair["rate"] == 2.5
    assert "engine.generated_tokens" in {m["name"] for m in cell.per_layer}
    assert "engine.generated_tokens" not in {
        m["name"] for m in manifest.resolve_cell(
            "qwen3-4b.chat", root=str(root), bench_dir=str(bench)).per_layer}
    reader = manifest.load_reader("engine.generated_tokens", bench_dir=str(bench))
    assert reader.read({"before": {"n": 3}, "after": {"n": 10}}) == 7
    plan = bench_run.Plan(cell, rehearse=False)
    assert plan.rate == 2.5 and plan.open_loop
    assert plan.flags["max_model_len"] == 2048 and plan.flags["kv_pages"] == 2300
    assert digest().items() >= before.items()  # nothing that was there changed
