"""bench/metrics/jit_ms.train.py on a synthetic record: only JIT time
inside the window counts, nested traces once, per window epoch."""
import sys
import types

import pytest

from bench import harness

READ = harness.load_reader("jit_ms.train")


def _rec(epochs=4):
    # the window: first span start 10.0, last span end 20.0
    spans = [("step", 10.0, 12.0), ("validation", 12.0, 15.0),
             ("step", 15.0, 17.0), ("validation", 17.0, 20.0)]
    return {"spans": spans, "counters": {"epochs": epochs}}


def _with_events(monkeypatch, events):
    from repro import obs
    monkeypatch.setattr(obs, "jit_events", lambda: [
        obs.JitEvent(kind, "jit(f)", t0, t1) for kind, t0, t1 in events])


def test_events_outside_the_window_do_not_count(monkeypatch):
    _with_events(monkeypatch, [("compile", 1.0, 9.5),  # set-up
                               ("trace", 21.0, 30.0)])  # a reader's compile
    assert READ(_rec()) == 0.0


def test_clipped_and_merged_per_epoch(monkeypatch):
    _with_events(monkeypatch, [
        ("trace", 9.0, 10.5),  # straddles the start: 0.5 s inside
        ("trace", 13.0, 14.0),  # a trace ...
        ("trace", 13.2, 13.6),  # ... with a nested one: counted once
        ("lower", 14.0, 14.5),
        ("compile", 19.5, 25.0),  # straddles the end: 0.5 s inside
    ])
    assert READ(_rec(epochs=4)) == pytest.approx(1e3 * 2.5 / 4)


def test_no_record_reads_nothing(monkeypatch):
    # a program that predates repro.obs
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    assert READ(_rec()) is None
    # or one whose obs keeps no JIT record
    monkeypatch.setitem(sys.modules, "repro.obs", types.ModuleType("obs"))
    assert READ(_rec()) is None


def test_an_empty_window_reads_nothing(monkeypatch):
    _with_events(monkeypatch, [])
    assert READ({"spans": [], "counters": {"epochs": 0}}) is None
    assert READ(_rec()) == 0.0
