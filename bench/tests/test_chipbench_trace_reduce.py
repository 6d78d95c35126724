"""bench/trace_reduce.py: busy union, idle share and breakdown."""
import glob
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).parent / "data" / "small_trace.json"


@pytest.fixture
def small():
    t = json.loads(DATA.read_text())
    return ([tuple(e) for e in t["device_events"]],
            [tuple(s) for s in t["host_spans"]])


def test_busy_union_and_idle_share(small):
    out = trace_reduce.reduce(*small)
    # window [1000, 12000): busy [1000, 4500) + [8000, 9000) + [11000, 12000)
    assert out["window_s"] == pytest.approx(11000e-9)
    assert out["busy_s"] == pytest.approx((3500 + 1000 + 1000) * 1e-9)
    assert out["idle_share"] == pytest.approx(1 - 5500 / 11000)


def test_breakdown_names_ops_and_gaps(small):
    out = trace_reduce.reduce(*small)["breakdown"]
    ops = dict(out["device_ops"])
    # self time: fusion.1 counts (2000 - 1000 of fusion.2 in it) + 1500; the
    # one before the window not at all; while.3 is clipped to 1000, less
    # the 500 of fusion.3 in it
    assert ops["fusion.1"] == pytest.approx(2500e-9)
    assert ops["fusion.2"] == pytest.approx(1000e-9)
    assert ops["while.3"] == pytest.approx(500e-9)
    assert ops["fusion.3"] == pytest.approx(500e-9)
    assert out["device_ops"][0][0] == "fusion.1"
    gaps = out["idle_gaps"]
    # [4500, 8000) mid 6250: validation; [9000, 11000) mid 10000: query
    assert gaps[0] == ["validation", pytest.approx(3500e-9)]
    assert gaps[1] == ["query", pytest.approx(2000e-9)]
    assert len(gaps) == 2


def test_op_names_and_self_times():
    assert trace_reduce.op_name("%fusion.12 = f32[8]{0} fusion(...)") == \
        "fusion.12"
    assert trace_reduce.op_name("copy-start.3") == "copy-start.3"
    got = trace_reduce.self_times([("w", 0, 10), ("a", 2, 3), ("b", 6, 2),
                                   ("c", 6, 1)])
    assert dict((n, t) for n, t in got) == {"w": 5, "a": 3, "b": 1, "c": 1}


def test_innermost_span_names_a_gap():
    spans = [("query", 0, 100), ("query", 40, 20), ("window", 0, 100)]
    assert trace_reduce._innermost(spans, 50) == "query"
    assert trace_reduce._innermost(spans, 200) == "no span"


def test_reads_a_recorded_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX +
                                      trace_reduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "step"):
            jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    events, spans, layout = trace_reduce.read_xplane(path)
    names = {s[0] for s in spans}
    assert {"step", trace_reduce.WINDOW_SPAN} <= names
    assert any(p.startswith("/host:") for p in layout)
