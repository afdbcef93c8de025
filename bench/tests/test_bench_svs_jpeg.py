"""The JPEG SVS cell end to end on the CPU at a tiny size, with the chip
requirement lifted in the test only; then its control and planted faults,
each of which has to come out not correct."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import work  # noqa: E402
import work_svs  # noqa: E402

SEED = 2**31 + 211
CELL = "ingest.svs_jpeg"
TINY = {"mix": {"sizes": [[512, 2], [1024, 1]], "pool": 2,
                "check": {"slides": 2, "frames_per_level": 4}}}
#: big enough for the control's few flipped roundings to show
WIDE = {"mix": {"sizes": [[2048, 1]], "in_flight": 1, "pool": 1,
                "check": {"slides": 1, "frames_per_level": 64}}}


@pytest.fixture(autouse=True)
def _cpu_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _run(seconds: float = 3.0, overrides=None, **kw) -> dict:
    return run.run(CELL, SEED, seconds, False, require_tpu=False,
                   overrides=overrides or TINY, log=lambda s: None, **kw)


def test_cell_runs_end_to_end_on_cpu():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = run.load_spec()
    want = {m["name"] for m in run.metrics_for(spec, CELL, "end_to_end")}
    assert set(r["metrics"]) == want == {"convert_mpx_s", "setup_s"}
    assert set(r["checks"]) == {"missing_levels_or_frames",
                                "level0_scan_mismatch", "coef_mismatch_share"}
    detail = r["_check_detail"]
    assert detail["level0_scan_mismatch"]["frames"] > 0
    assert detail["coef_mismatch_share"]["compared"] > 0
    assert detail["coef_mismatch_share"]["left_out"] > 0


def test_control_is_not_correct():
    """The reference with every matrix product in three bfloat16 passes,
    decode and forward transform both, put in the program's place, fails
    the limit that the program passes."""
    r = _run(2.0, WIDE, control=True)
    assert not r["correct"], r["checks"]
    c = r["checks"]["coef_mismatch_share"]
    assert c["value"] > c["limit"], r["_check_detail"]
    program = _run(2.0, WIDE)
    assert program["correct"], program["checks"]
    assert program["checks"]["coef_mismatch_share"]["value"] <= c["limit"]


# ----------------------------------------------------------------- faults
def _reencode_level0(monkeypatch):
    """Level 0 decoded and re-encoded (4:4:4, Annex K) instead of kept."""
    from repro.wsi import jpeg
    from repro.wsi.formats import TiffSlideReader

    real = TiffSlideReader.jpeg_frames

    def reencoded(self):
        frames = real(self)
        return None if frames is None else [
            jpeg.encode_tile(jpeg.decode_tile(f)) for f in frames]

    monkeypatch.setattr(TiffSlideReader, "jpeg_frames", reencoded)


def _replicate_chroma(monkeypatch):
    """Chroma upsampled by replication in place of the triangle filter."""
    from repro.kernels import ops, ref

    def nearest(n_out, n_in):
        return np.eye(n_in, dtype=np.float32).repeat(n_out // n_in, axis=0)

    monkeypatch.setattr(ref, "upsample_matrix", nearest)
    ops._jpeg_inverse420_core.clear_cache()


def _alter_level1_coefficient(monkeypatch):
    import repro.wsi.convert as conv

    real = conv.encode_coef_batch

    def altered(coef):
        coef = np.array(coef)
        coef[:, 0, 0, 1] += 1  # one AC coefficient of every tile
        return real(coef)

    monkeypatch.setattr(conv, "encode_coef_batch", altered)


def _leave_a_level_out(monkeypatch):
    """From the window on (a warm-up slide missing a level would never
    finish), every study is converted one level short."""
    import repro.wsi.convert as conv
    from clients import svs_jpeg

    real, window = conv._pyramid_dims, svs_jpeg.Client.window

    def short(self):
        monkeypatch.setattr(conv, "_pyramid_dims",
                            lambda H, W, m: real(H, W, m)[:-1])
        return window(self)

    monkeypatch.setattr(svs_jpeg.Client, "window", short)


@pytest.mark.parametrize("fault,number", [
    (_reencode_level0, "level0_scan_mismatch"),
    (_replicate_chroma, "coef_mismatch_share"),
    (_alter_level1_coefficient, "coef_mismatch_share"),
    (_leave_a_level_out, "missing_levels_or_frames"),
], ids=lambda p: getattr(p, "__name__", p))
def test_a_planted_fault_is_not_correct(fault, number, monkeypatch):
    import repro.wsi.convert as conv
    from repro.kernels import ops

    fault(monkeypatch)
    try:
        # slides left unfinished by a fault are followed 2 s, not 90
        r = _run(overrides={"mix": {**TINY["mix"], "drain_s": 2}})
    finally:
        conv._pyramid_chain.cache_clear()
        ops._jpeg_inverse420_core.clear_cache()
    assert not r["correct"], r["checks"]
    c = r["checks"][number]
    assert c["value"] > c["limit"], r["_check_detail"]


def test_inverse420_work_at_one_known_shape():
    flops, nbytes = work_svs.inverse420(256 * 256)
    # 1.5 samples x (dequantise 1 + iDCT 32) + two chroma upsamples (4.5
    # each) + colour 9 + round/clip 3 x 3 = 76.5 flops per pixel
    assert flops == 256 * 256 * 76.5
    assert nbytes == 256 * 256 * 6  # 1.5 int16 samples in, RGB out
    t, roof = work.least_time(flops, nbytes, run.peaks_for("TPU v5 lite"))
    assert roof == "memory" and t == pytest.approx(nbytes / 819e9)
