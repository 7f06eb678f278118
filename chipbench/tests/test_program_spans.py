"""The readers of the program's own span records, on synthetic records."""
import importlib.util
import sys
from pathlib import Path

import pytest

from repro.core import spans

METRICS = {"weights_ms_per_place.batch": "weights",
           "candidates_ms_per_place.batch": "candidates",
           "refine_prepare_ms_per_place.batch": "refine.prepare",
           "refine_wait_ms_per_place.batch": "refine.wait"}


def _metric(name):
    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _place(**phases):
    return spans.Record("place", 1.0,
                        {k.replace("_", "."): (1, v)
                         for k, v in phases.items()})


# two warm-up records before the window's three placements
RECORDS = ([spans.Record("place", 9.0, {"weights": (1, 9.0)}),
            spans.Record("place_many", 9.0, {"place": (2, 9.0)})]
           + [_place(weights=0.5, candidates=0.03, refine_prepare=0.002,
                     refine_wait=0.06),
              _place(weights=0.7, candidates=0.06, refine_prepare=0.004,
                     refine_wait=0.03),
              _place(refine_prepare=0.003, refine_wait=0.06)])
WANT = {"weights": 400.0, "candidates": 30.0, "refine.prepare": 3.0,
        "refine.wait": 50.0}


@pytest.fixture
def records(monkeypatch):
    monkeypatch.setattr(spans, "recent", lambda: list(RECORDS))


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_averages_the_windows_records(records, name):
    got = _metric(name).read({"placements": 3})
    assert got == pytest.approx(WANT[METRICS[name]])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_none_without_enough_records(records, name):
    mod = _metric(name)
    assert mod.read({"placements": len(RECORDS) + 1}) is None
    assert mod.read({"placements": 0}) is None
    # the window reaches back to a record that is not a placement
    assert mod.read({"placements": 4}) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_none_without_the_program_spans(monkeypatch, name):
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert _metric(name).read({"placements": 3}) is None
