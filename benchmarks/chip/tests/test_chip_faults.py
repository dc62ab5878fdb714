"""A whole run of the harness on the CPU, at a tiny size, without the
look for a chip: a sound run comes out correct, on one engine and on a
fleet of two replicas, and with a block from an architecture module
that no file of the harness names; a run whose engine alters the tokens
where it produces them comes out not correct.  Also the float8 control at that
size: it has to read above the limit that the program reads below."""
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import cells, harness, weights

TINY = Path(__file__).resolve().parent / "tiny"


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    # the harness keeps JAX's cache where this names; JAX read the
    # variable at import, so setting it now leaves the cache off
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def run(cell, seed, control=False):
    import time
    return harness.run(cell, seed, 2.0, False, t0=time.perf_counter(),
                       require_tpu=False, control=control, root=TINY,
                       here=TINY)


def test_sound_run_is_correct():
    r = run("tiny.closed", 2**31 + 99)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"


def test_fleet_of_replicas_is_served_and_correct():
    r = run("tiny-x2.chat", 11)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0


def test_block_from_a_test_local_module_is_served_and_correct():
    """``tiny-tied``'s configuration names ``"arch": "tied"``
    (``tiny/archs/tied.py``) and states ``tie_embeddings``: the weights
    hold no unembedding, so the engine serves only if the key reaches
    it, and the dense reference would fail on them."""
    cell = cells.find_cell("tiny-tied.closed", root=TINY, here=TINY)
    assert Path(cell.arch.__file__) == TINY / "archs" / "tied.py"
    assert "unembed" not in weights.make(cell.arch, cell.config["model"], 0)
    r = run("tiny-tied.closed", 2**31 + 7)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["tokens_checked"]["value"] >= 256


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.runtime.serve import NO_TOKEN, ServingEngine
    checked_row = ServingEngine._checked_row

    def altered(self, req, slot, row):
        row = np.array(checked_row(self, req, slot, row), copy=True)
        live = np.flatnonzero(np.asarray(row) != NO_TOKEN)
        if req.rid >= 0 and live.size:
            row[live[0]] = (row[live[0]] + 1) % self.cfg.vocab
        return row

    monkeypatch.setattr(ServingEngine, "_checked_row", altered)
    r = run("tiny.chat", 5)
    assert r["correct"] is False
    gap = r["checks"]["mean_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_where_the_program_passes():
    r = run("tiny-bf16.chat", 7, control=True)
    assert r["correct"] is True
    c = r["control"]
    assert c["correct"] is False
    assert c["checks"]["mean_gap"]["value"] > c["checks"]["mean_gap"]["limit"]
    assert c["checks"]["mean_gap"]["value"] >= \
        3 * r["checks"]["mean_gap"]["value"]
