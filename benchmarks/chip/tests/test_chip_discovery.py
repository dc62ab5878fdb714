"""Discovery by name: every cell, configuration, architecture module,
mix, limit and metric that BENCHMARK.json names is found as a file, and
a new one is added by adding files alone.  What a configuration states
of its model and its deployment reaches the program whole, or raises."""
import dataclasses
import json
import re

import pytest

from benchmarks.chip import cells, harness
from repro.configs import ARCH_IDS, get_arch

BENCH = cells.load_json(cells.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found(name):
    cell = cells.find_cell(name)
    assert cell.config["model"]["dtype"] == "bfloat16"
    assert all(callable(getattr(cell.arch, k)) for k in cells.ARCH_API)
    assert cell.mix["loop"] in ("open", "closed")
    assert cell.limits["mean_gap"]["limit"] > 0
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    # each per-layer metric moves an end-to-end metric this cell reports
    assert all(m["moves"] in reported for m in cell.per_layer)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    assert callable(cells.load_reader(metric))


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= set(CELLS)
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("name", sorted({c["name"] for c in
                                         BENCH["configs"]}))
def test_configuration_keeps_the_published_widths(name):
    from repro.configs import get_arch
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    m = cells.load_json(cells.ROOT / entry["file"])["model"]
    arch = get_arch(name)
    cfg = harness.arch_config(m)
    for key in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab", "act", "rope_theta"):
        assert getattr(cfg, key) == getattr(arch, key), key


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a mix, a limit and a metric reader that no file
    of the harness names are found once their files exist."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "limits").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "archs").mkdir()
    (tmp_path / "configs" / "new.json").write_text(
        '{"arch": "new-block", "model": {"dtype": "bfloat16"}, '
        '"deployment": {}}')
    (tmp_path / "archs" / "new-block.py").write_text("".join(
        f"def {k}(*args):\n    return '{k}'\n" for k in cells.ARCH_API))
    (tmp_path / "traffic" / "burst.json").write_text(
        '{"base": "chat", "rate_per_s": 9.0}')
    (tmp_path / "traffic" / "chat.json").write_text(
        '{"loop": "open", "rate_per_s": 1.0}')
    (tmp_path / "limits" / "new.burst.json").write_text(
        '{"mean_gap": {"limit": 0.5}}')
    (tmp_path / "metrics" / "hits.x.py").write_text(
        "def read(run):\n    return run * 2\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "new", "file": "configs/new.json"}],
        "workloads": [{"name": "new.burst", "config": "new",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "hits.x", "workloads": ["new.burst"]},
                      {"name": "other", "workloads": ["elsewhere"]}]}))
    cell = cells.find_cell("new.burst", root=tmp_path, here=tmp_path)
    assert cell.mix == {"loop": "open", "rate_per_s": 9.0}
    assert cell.arch.logits() == "logits"
    assert cell.limits["mean_gap"]["limit"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["hits.x"]
    assert cells.load_reader("hits.x", tmp_path / "metrics")(21) == 42
    with pytest.raises(KeyError):
        cells.find_cell("missing", root=tmp_path, here=tmp_path)


def test_peaks_refuse_an_unknown_chip():
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("cpu")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_config_passes_every_field(arch):
    cfg = get_arch(arch)
    assert harness.arch_config(dataclasses.asdict(cfg)) == cfg


def test_unknown_model_key_raises():
    m = dataclasses.asdict(get_arch("granite-3-2b"))
    with pytest.raises(ValueError, match="n_expert"):
        harness.arch_config({**m, "n_expert": 8})


def test_deployment_keys_reach_the_engine_or_raise():
    dep = cells.load_json(cells.ROOT / BENCH["configs"][0]["file"])[
        "deployment"]
    opts = harness.engine_options({**dep, "prefill_chunk_tokens": 64,
                                   "max_prefill_tokens_per_tick": 256})
    assert opts == {**{k: v for k, v in dep.items()
                       if k not in ("chips", "replicas")},
                    "prefill_chunk_tokens": 64,
                    "max_prefill_tokens_per_tick": 256, "eos_id": -1}
    # a fleet option only where there is a fleet
    fleet = {**dep, "replicas": 2, "tick_deadline_s": 5.0}
    assert harness.engine_options(fleet)["tick_deadline_s"] == 5.0
    for bad in ({"prefil_chunk_tokens": 64}, {"eos_id": 1},
                {"tick_deadline_s": 5.0}, {"devices": None}):
        with pytest.raises(ValueError, match=next(iter(bad))):
            harness.engine_options({**dep, **bad})


def test_configuration_without_arch_raises(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "c.json").write_text(
        '{"model": {}, "deployment": {}}')
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "configs/c.json"}],
        "workloads": [{"name": "c.x", "config": "c", "traffic": "x",
                       "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    with pytest.raises(ValueError, match="arch"):
        cells.find_cell("c.x", root=tmp_path, here=tmp_path)


def test_architecture_module_missing_a_name_raises(tmp_path):
    (tmp_path / "half.py").write_text("def shapes(m):\n    return {}\n")
    with pytest.raises(ValueError, match="logits"):
        cells.load_arch("half", tmp_path)
