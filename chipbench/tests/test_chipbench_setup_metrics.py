"""The set-up metrics read the program's own span and compile table
(``repro.runtime.obs``) after a cell's set-up: numbers after a toy
set-up on the CPU, None where the table is empty or the program has no
such table."""
from __future__ import annotations

import sys

import pytest

from chipbench import cells
from chipbench.tests.toy import toy_cell

METRICS = ["build_ms.setup", "compile_ms.setup", "cache_miss.setup"]


@pytest.fixture
def table(monkeypatch):
    from repro.runtime import obs
    monkeypatch.setenv("REPRO_TUNE_CACHE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    rec = obs.Recorder()
    monkeypatch.setattr(obs, "_REC", rec)
    return rec


@pytest.mark.parametrize("name", ["par512.fp_bp", "cone512.fp_bp",
                                  "par512.fbp"])
def test_readers_after_a_toy_setup(name, table):
    assert [m["name"] for m in cells.load(name).per_layer][-3:] == METRICS
    step = toy_cell(name).step(2 ** 40 + 3)
    step.setup()
    got = {m: cells.reader(m)(None) for m in METRICS}
    assert got["build_ms.setup"] > 0
    assert got["compile_ms.setup"] > 0
    assert isinstance(got["cache_miss.setup"], int)
    assert got["cache_miss.setup"] >= 0
    spans = table.snapshot()["spans"]
    assert ("fbp.tables" in spans) == name.endswith("fbp")
    assert ("ops.get_bundle" in spans) == name.endswith("fp_bp")


def test_readers_on_an_empty_table(table):
    assert all(cells.reader(m)(None) is None for m in METRICS)


def test_readers_on_a_program_without_the_table(monkeypatch):
    """The parent of the change that added the table: nothing to read, and
    no reader raises."""
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.runtime.obs", None)
    assert all(cells.reader(m)(None) is None for m in METRICS)
