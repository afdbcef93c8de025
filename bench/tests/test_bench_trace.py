"""The trace reduction: device busy time, idle share, per-program time and
the assignment of compiled variants to shapes, on a hand-made timeline and
on a small trace recorded on the chip (kept as a fixture)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "ingest_4096.xplane.pb"
MS = 1_000_000  # ns


def _raw():
    # window 100 ms starting at 50 ms; one program overlaps its start,
    # two overlap each other, one lies after the window
    return {"window": (50 * MS, 100 * MS), "devices": {
        "/device:TPU:0": [
            ("jit_chain(11)", 40 * MS, 20 * MS),      # 50..60 inside
            ("jit_chain(22)", 70 * MS, 10 * MS),      # 70..80
            ("jit__lockstep(7)", 75 * MS, 15 * MS),   # 75..90, overlaps
            ("jit_chain(11)", 160 * MS, 5 * MS),      # after the window
        ]}}


def test_busy_is_the_union_of_executions_clipped_to_the_window():
    red = devtrace.reduce(_raw())
    assert red["window_s"] == pytest.approx(0.1)
    # 50..60 and 70..90 -> 30 ms busy of 100
    assert red["busy_s"] == pytest.approx(0.030)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.7)
    assert red["gaps"] == [(pytest.approx(0.010), pytest.approx(0.020)),
                           (pytest.approx(0.040), pytest.approx(0.100))]
    times = devtrace.program_time(red, "jit_chain")
    assert {k: sum(v) for k, v in times.items()} == {
        "jit_chain(11)": pytest.approx(0.010),
        "jit_chain(22)": pytest.approx(0.010)}


def test_no_window_or_no_device_reads_nothing():
    raw = _raw()
    assert devtrace.reduce({**raw, "window": None}) is None
    assert devtrace.reduce({**raw, "devices": {}}) is None


def test_variants_pair_with_shapes_by_duration():
    groups = {"p(1)": [0.010, 0.012], "p(2)": [0.001], "p(3)": [0.004]}
    assert devtrace.assign_by_duration(groups, [5.0, 1.0, 2.0, 1.0]) == {
        "p(2)": 1.0, "p(3)": 2.0, "p(1)": 5.0}
    assert devtrace.assign_by_duration(groups, [1.0, 2.0]) is None


def test_breakdown_labels_gaps_with_the_host_span_that_covers_them():
    red = devtrace.reduce(_raw())
    spans = [{"name": "convert.entropy", "start": 1000.04, "end": 1000.2},
             {"name": "pipeline.convert", "start": 1000.0, "end": 1000.2}]
    bd = devtrace.breakdown(red, spans, t0=1000.0)
    assert bd["idle_gaps"][0] == ["convert.entropy", pytest.approx(0.06)]
    assert bd["idle_gaps"][1] == ["no span", pytest.approx(0.01)]
    assert bd["device_ops"][0][0] in ("jit_chain", "jit__lockstep")


def test_a_recorded_chip_trace_reduces():
    """A 30-s window of open-loop 4096^2 ingest traced on a v5e: 23
    pyramid executions and their upload concatenations; the run reported
    busy_s 0.3752553719999958 and window_s 30.000095226000003."""
    red = devtrace.reduce(devtrace.load(str(FIXTURE)))
    assert red["window_s"] == pytest.approx(30.000095226000003, abs=1e-9)
    assert red["busy_s"] == pytest.approx(0.3752553719999958, abs=1e-9)
    chain = devtrace.program_time(red, "jit_chain")
    assert [len(v) for v in chain.values()] == [23]
    assert sum(sum(v) for v in chain.values()) == pytest.approx(
        0.3614519839999902, abs=1e-9)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(0.9874915273043942, abs=1e-9)
    assert max(b - a for a, b in red["gaps"]) == pytest.approx(
        4.880926926, abs=1e-6)
