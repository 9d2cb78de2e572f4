"""The harness finds every part of a cell by name, from files alone, and
``BENCHMARK.json`` keeps to the format its readers rely on."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import spec
from bench.model import model_config

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def benchmark():
    return spec.load_benchmark(ROOT)


def test_every_cell_resolves(benchmark):
    for w in benchmark["workloads"]:
        cell = spec.load_cell(w["name"], ROOT, BENCH, benchmark)
        assert cell.rate > 0 and cell.chips == 1
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
        model_config(cell.config)             # the program accepts it


def test_names_units_and_keys(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in benchmark["end_to_end"]
             + benchmark["per_layer"]]
    names += [c["name"] for c in benchmark["configs"]]
    names += [w["name"] for w in benchmark["workloads"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    layers = {}
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if "layer" in m:
            assert m["moves"] in {e["name"] for e in benchmark["end_to_end"]}
            layers.setdefault(m["layer"], m["layer"])
    for c in benchmark["configs"]:
        cfg = spec.load_json(ROOT / c["file"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_configs_match_the_program_registry():
    """The files hold Mistral-NeMo-12B (cut to 10 layers) and, as test
    data for the recorded trace, the repository's zamba2-1.2b, as the
    program's own registry defines them."""
    import dataclasses

    from repro.configs import get_config

    z = model_config(spec.load_json(Path(__file__).resolve().parent / "data"
                                    / "zamba2-1.2b-repo.json"))
    ref = get_config("zamba2-1.2b")
    assert z.groups == ref.groups and z.d_model == ref.d_model
    assert z.vocab_size == ref.vocab_size and z.dtype == ref.dtype
    m = model_config(spec.load_json(
        BENCH / "configs" / "mistral-nemo-12b-10l.json"))
    ref = get_config("mistral-nemo-12b")
    cut = tuple(dataclasses.replace(g, repeats=10) for g in ref.groups)
    assert m.groups == cut and m.d_model == ref.d_model
    assert m.vocab_size == ref.vocab_size and ref.n_layers == 40


def test_a_new_cell_is_added_by_files_alone(tmp_path):
    """Copy the harness, then add a configuration, a traffic mix, a cell and
    a per-layer metric as new files and one new entry each in
    BENCHMARK.json: every existing file of the harness stays byte-identical
    and the harness finds all of it by name."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    data = Path(__file__).resolve().parent / "data"
    shutil.copy(data / "tiny-dense.json", bench / "configs" / "tiny-dense.json")
    shutil.copy(data / "tiny_mix.json", bench / "traffic" / "bursty_tiny.json")
    (bench / "cells" / "tiny-dense.bursty_tiny.json").write_text(
        json.dumps({"rate_per_s": 3.0}))
    (bench / "metrics" / "window.requests.py").write_text(
        "def read(ctx):\n    return len(ctx.window_requests()) or None\n")
    b = spec.load_benchmark(ROOT)
    b["configs"].append({"name": "tiny-dense", "source": "test",
                         "file": "bench/configs/tiny-dense.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-dense.bursty_tiny",
                           "config": "tiny-dense", "traffic": "bursty_tiny",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "window.requests", "unit": "req",
                           "better": "higher", "source": "host_clock",
                           "layer": "driver",
                           "moves": b["end_to_end"][0]["name"],
                           "workloads": ["tiny-dense.bursty_tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("tiny-dense.bursty_tiny", tmp_path, bench)
    assert cell.config["name"] == "tiny-dense" and cell.rate == 3.0
    assert [m["name"] for m in cell.per_layer] == ["window.requests"]
    read = spec.metric_reader("window.requests", bench)
    from types import SimpleNamespace
    ctx = SimpleNamespace(window_requests=lambda: [1, 2])
    assert read(ctx) == 2
    after = {p: p.read_bytes() for p in before}
    assert after == before
