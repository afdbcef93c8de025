"""The host-plane reading of a trace: the program's spans and the named
kernels, on a hand-made timeline and on a small trace recorded on the
chip (kept as a fixture)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import hostplane  # noqa: E402

#: recorded by ``record_fixture.py`` on a v5e: one 1024^2 slide converted,
#: one tile encoded and one frame decoded, with named kernels and the
#: program's spans annotated into the host plane
NAMED = Path(__file__).resolve().parent / "fixtures" / "convert_named.xplane.pb"
MS = 1_000_000  # ns


def _raw(**extra):
    # devtrace's hand-made window: 100 ms from 50 ms, busy 50..60 and
    # 70..90, so the gaps are 60..70 and 90..150
    raw = {"window": (50 * MS, 100 * MS), "devices": {
        "/device:TPU:0": [
            ("jit_chain(11)", 40 * MS, 20 * MS),
            ("jit_chain(22)", 70 * MS, 10 * MS),
            ("jit__lockstep(7)", 75 * MS, 15 * MS),
            ("jit_chain(11)", 160 * MS, 5 * MS),
        ]}, "kernels": {}, "host": [], "ops": 0}
    return {**raw, **extra}


def test_innermost_leaf_takes_each_instant_of_its_thread():
    host = [("t1", "inference.score", 0.0, 10.0),
            ("t1", "decode.parse", 2.0, 5.0),
            ("t1", "decode.entropy", 6.0, 8.0),
            ("t2", "convert.encode", 1.0, 4.0)]
    got = {}
    for n, a, b in hostplane.innermost(host):
        got[n] = got.get(n, 0.0) + b - a
    assert got == {"inference.score": pytest.approx(5.0),
                   "decode.parse": pytest.approx(3.0),
                   "decode.entropy": pytest.approx(2.0),
                   "convert.encode": pytest.approx(3.0)}


def test_gaps_are_labelled_from_the_host_plane_summed_over_threads():
    """The gap 40..100 ms has decode.entropy on two threads (2 x 20 ms)
    against inference.score outside its decode (35 ms) and convert.encode
    (30 ms); nothing on the host plane covers the 10..20 ms gap."""
    red = hostplane.reduce(_raw(host=[
        ("/host:CPU/0", "inference.score", 85 * MS, 60 * MS),   # 35..95
        ("/host:CPU/0", "decode.entropy", 100 * MS, 20 * MS),   # 50..70
        ("/host:CPU/1", "decode.entropy", 120 * MS, 20 * MS),   # 70..90
        ("/host:CPU/2", "convert.encode", 100 * MS, 30 * MS),   # 50..80
        ("/host:CPU/2", "convert.fetch", 300 * MS, 10 * MS),    # after
    ]))
    assert [h[1] for h in red["host"]] == ["inference.score",
                                           "decode.entropy",
                                           "decode.entropy",
                                           "convert.encode"]
    assert hostplane.idle_gaps(red) == [
        ["decode.entropy", pytest.approx(0.06)],
        ["no span", pytest.approx(0.01)]]


def test_a_trace_without_program_spans_labels_no_gap():
    red = hostplane.reduce(_raw())
    assert [g[0] for g in hostplane.idle_gaps(red)] == ["no span"] * 2
    assert hostplane.reduce(_raw(window=None)) is None


def test_kernel_time_sums_named_kernels_in_the_window():
    red = hostplane.reduce(_raw(kernels={"/device:TPU:0": [
        ("jpeg_transform", 52 * MS, 4 * MS),
        ("jpeg_transform", 70 * MS, 2 * MS),
        ("downsample2x2", 148 * MS, 4 * MS),   # half inside
        ("jpeg_inverse", 400 * MS, 1 * MS),    # after
    ]}))
    assert hostplane.kernel_time(red) == {
        "jpeg_transform": pytest.approx(0.006),
        "downsample2x2": pytest.approx(0.002)}
    assert hostplane.kernel_time(hostplane.reduce(_raw())) == {}


@pytest.mark.parametrize("event,kernel", [
    ("%jpeg_transform.5 = s32[256,3,256,256]{3,2,1,0} custom-call(f32[256]"
     " %bitcast.1), custom_call_target=\"tpu_custom_call\"", "jpeg_transform"),
    ("%downsample2x2 = f32[3,256,256]{2,1,0} custom-call(%x.1)",
     "downsample2x2"),
    ("%_kernel.3 = f32[8] custom-call(%a)", "_kernel"),
    ("%slice-start.21 = ((f32[3,256,4096]), f32[1]) async-start(%x)",
     "slice-start"),
])
def test_instruction_names_parse_to_their_base(event, kernel):
    assert hostplane._INSTRUCTION.match(event).group(1) == kernel


def test_a_recorded_trace_holds_named_kernels_and_program_spans():
    """What ``record_fixture.py`` printed for this trace on the chip."""
    red = hostplane.reduce(hostplane.load(str(NAMED)))
    assert red["window_s"] == pytest.approx(0.122390883, abs=1e-9)
    assert red["busy_s"] == pytest.approx(0.0010345919999999661, abs=1e-9)
    assert hostplane.kernel_time(red) == {
        "jpeg_transform": pytest.approx(0.0005806070000000003, abs=1e-9),
        "downsample2x2": pytest.approx(0.000284775000000001, abs=1e-9),
        "dct8x8_quant": pytest.approx(5.585199999999513e-05, abs=1e-9),
        "jpeg_inverse": pytest.approx(2.4786999999998338e-05, abs=1e-9),
        "rgb2ycbcr": pytest.approx(1.4566999999993113e-05, abs=1e-9)}
    names = [n for _, n, _, _ in red["host"]]
    assert names == ["convert.upload", "convert.dispatch"] + [
        "convert.fetch", "convert.encode", "convert.wrap"] * 3 + [
        "convert.pack", "decode.parse", "decode.entropy", "decode.inverse"]
    assert len({th for th, _, _, _ in red["host"]}) == 1  # one thread ran it
    # the spans lie on the device clock: the level-0 fetch ends after the
    # pyramid program it waits for
    (chain,) = [b for n, _, b in red["events"]["/device:TPU:0"]
                if devtrace.program(n) == "jit_chain"]
    fetch0 = next(h for h in red["host"] if h[1] == "convert.fetch")
    assert fetch0[3] > chain
    gaps = hostplane.idle_gaps(red)
    assert gaps[0] == ["convert.encode", pytest.approx(0.069937503, abs=1e-6)]
    assert gaps[1][0] == "decode.entropy"


def test_the_command_prints_the_report(capsys):
    assert hostplane.main([str(NAMED)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["executions"]["jit_chain"] == 1
    assert out["spans"]["convert.fetch"] == 3
    assert set(out["kernels"]) == set(hostplane.KERNELS)
    assert out["idle_gaps"][0][0] == "convert.encode"
    assert hostplane.main([]) == 2
