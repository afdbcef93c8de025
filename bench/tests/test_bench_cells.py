"""Each cell end to end on the CPU at a tiny size, with the chip
requirement lifted in the test only; then the control and the planted
faults, each of which has to come out not correct."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SEED = 2**31 + 101

TINY = {
    "ingest.backfill": {"mix": {"sizes": [[512, 2], [1024, 1]], "pool": 2,
                                "check": {"slides": 2,
                                          "frames_per_level": 2}}},
    "export.studies": {"mix": {"sizes": [[1024, 1]],
                               "check": {"levels": 3,
                                         "frames_per_level": 2}}},
}

#: big enough for the control's few flipped roundings to show: a 2048^2
#: slide (or study) compared on every frame of every level
WIDE = {
    "ingest.backfill": {"mix": {"sizes": [[2048, 1]], "in_flight": 1,
                                "pool": 1,
                                "check": {"slides": 1,
                                          "frames_per_level": 64}}},
    "export.studies": {"mix": {"sizes": [[2048, 1]], "pool": 1,
                               "in_flight": 1,
                               "check": {"levels": 4,
                                         "frames_per_level": 64}}},
}


@pytest.fixture(autouse=True)
def _cpu_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _run(cell: str, seconds: float = 3.0, overrides=None, **kw) -> dict:
    return run.run(cell, SEED, seconds, False, require_tpu=False,
                   overrides=overrides or TINY[cell], log=lambda s: None,
                   **kw)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_runs_end_to_end_on_cpu(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = run.load_spec()
    want = {m["name"] for m in run.metrics_for(spec, cell, "end_to_end")}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "_check_detail" and "checks" in r


@pytest.mark.parametrize("cell,number", [
    ("ingest.backfill", "coef_mismatch_share"),
    ("export.studies", "pixel_mismatch_share"),
])
def test_control_is_not_correct(cell, number):
    """The reference one precision step lower (bfloat16 x3 products), put
    in the program's place, fails the limit that the program passes and
    makes the run not correct."""
    r = _run(cell, 2.0, WIDE[cell], control=True)
    assert not r["correct"], r["checks"]
    c = r["checks"][number]
    assert c["value"] > c["limit"], r["_check_detail"]
    assert r["_check_detail"][number]["control"]
    program = _run(cell, 2.0, WIDE[cell])
    assert program["correct"], program["checks"]
    assert program["checks"][number]["value"] <= c["limit"]


# ----------------------------------------------------------------- faults
def _alter_coefficient(monkeypatch):
    import repro.wsi.convert as conv

    real = conv.encode_coef_batch

    def altered(coef):
        coef = np.array(coef)
        coef[:, 0, 0, 1] += 1  # one AC coefficient of every tile
        return real(coef)

    monkeypatch.setattr(conv, "encode_coef_batch", altered)


def _drop_half_the_tiles(monkeypatch):
    import repro.wsi.convert as conv

    real = conv._level_chunks

    def half(batch, bh, bw):
        chunks = real(batch, bh, bw)
        return chunks[:max(1, len(chunks) // 2)]

    monkeypatch.setattr(conv, "_level_chunks", half)


def _skip_the_downsample(monkeypatch):
    """The downsample step hands its input on unfiltered (every other
    pixel): the pyramid's state passes through the step unchanged."""
    import repro.wsi.convert as conv

    monkeypatch.setattr(conv, "downsample2x2", lambda x: x[:, ::2, ::2])
    conv._pyramid_chain.cache_clear()


def _alter_pixel(monkeypatch):
    import repro.wsi.export as exp

    real = exp.decode_frames

    def altered(frames, **kw):
        rgb = np.array(real(frames, **kw))
        rgb[:, 0, 0, 0] ^= 1
        return rgb

    monkeypatch.setattr(exp, "decode_frames", altered)


def _decode_half_the_frames(monkeypatch):
    import repro.wsi.export as exp

    real = exp.decode_frames

    def half(frames, **kw):
        rgb = np.array(real(frames, **kw))
        rgb[len(rgb) // 2:] = 0
        return rgb

    monkeypatch.setattr(exp, "decode_frames", half)


@pytest.mark.parametrize("cell,fault", [
    ("ingest.backfill", _alter_coefficient),
    ("ingest.backfill", _drop_half_the_tiles),
    ("ingest.backfill", _skip_the_downsample),
    ("export.studies", _alter_pixel),
    ("export.studies", _decode_half_the_frames),
], ids=lambda p: getattr(p, "__name__", p))
def test_a_planted_fault_is_not_correct(cell, fault, monkeypatch):
    import repro.wsi.convert as conv

    fault(monkeypatch)
    try:
        r = _run(cell)
    finally:
        conv._pyramid_chain.cache_clear()
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("trace_s", [None, 1.0])
def test_a_traced_run_without_a_device_trace_reports_nothing(monkeypatch,
                                                             trace_s):
    """``--trace 1`` drives the profiler over the window, or over its first
    ``trace_s`` seconds; a CPU trace has no TPU plane, so the run ends
    without a result rather than report a device metric from the host."""
    monkeypatch.setattr(run, "peaks_for", lambda kind, root=None: {
        "flops_per_s": 1.0, "bytes_per_s": 1.0})
    over = {"mix": dict(TINY["ingest.backfill"]["mix"])}
    if trace_s is not None:
        over["mix"]["trace_s"] = trace_s
    logs = []
    with pytest.raises(RuntimeError, match="no device execution"):
        run.run("ingest.backfill", SEED, 2.0, True, require_tpu=False,
                overrides=over, log=logs.append)
    traced = [ln for ln in logs if ln.startswith("trace: ") and "of the" in ln]
    assert traced and float(traced[0].split()[1]) == pytest.approx(
        trace_s or 2.0, abs=0.5)
