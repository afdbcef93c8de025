"""Pipelined conversion engine: every level's frames against the plain
reference (``_convert_ref``), whole-tar determinism under shared UIDs,
manifest-resume determinism, and real-mode multi-slide concurrency."""
import json

import pytest

from _convert_ref import reference_level_frames, study_frames

from repro.core import ConversionPipeline, RealScheduler
from repro.core import clock
from repro.core.clock import wall_sleep
from repro.wsi import (ConvertOptions, SyntheticScanner,
                       convert_wsi_to_dicom, read_part10, study_levels)
from repro.wsi.dicom import new_uid


def _uids():
    return json.dumps([new_uid(), new_uid()])


def _convert(psv, *, uids, **kw):
    opt = ConvertOptions(manifest={"uids": uids}, **kw)
    return convert_wsi_to_dicom(psv, {"slide_id": "AB"}, options=opt), opt


# --------------------------------------------------------------------------
# byte identity: levels vs the plain reference, whole tars vs each other
# --------------------------------------------------------------------------
@pytest.mark.parametrize("hw,min_level", [
    ((512, 512), 256),
    ((1024, 512), 256),   # non-square, multi-level
    ((512, 512), 64),     # runs into sub-tile levels (0 full frames)
])
def test_pipelined_tar_identical_to_sync(hw, min_level):
    """Every level's frames equal the plain, strictly sequential reference
    (host pyramid, per-tile coder); two conversions that share UIDs give
    the same tar, so the wrap is deterministic too."""
    psv = SyntheticScanner(seed=11).scan(*hw, 256)
    uids = _uids()
    tar, _ = _convert(psv, uids=uids, min_level_size=min_level)
    again, _ = _convert(psv, uids=uids, min_level_size=min_level)
    assert again == tar  # shared UIDs → the same wrapped archive
    assert study_frames(tar) == reference_level_frames(psv, min_level)


def test_pipelined_levels_decode_and_cover_pyramid():
    psv = SyntheticScanner(seed=12).scan(1024, 1024, 256)
    tar, _ = _convert(psv, uids=_uids())
    lv = study_levels(tar)
    meta = json.loads(lv["study.json"])
    assert meta["levels"] == 3  # 1024 → 512 → 256
    for li, (total, frames) in enumerate([(1024, 16), (512, 4), (256, 1)]):
        ds, fr = read_part10(lv[f"level_{li}.dcm"])
        assert ds.get_int(0x0048, 0x0007) == total
        assert ds.get_int(0x0028, 0x0008) == frames
        assert len(fr) == frames


# --------------------------------------------------------------------------
# manifest resume reproduces a fresh conversion byte-for-byte
# --------------------------------------------------------------------------
def test_full_manifest_resume_tar_identical():
    psv = SyntheticScanner(seed=13).scan(512, 512, 256)
    tar1, opt1 = _convert(psv, uids=_uids())
    opt2 = ConvertOptions(manifest=dict(opt1.manifest))
    tar2 = convert_wsi_to_dicom(psv, {"slide_id": "AB"}, options=opt2)
    assert tar2 == tar1


def test_partial_manifest_resume_tar_identical():
    psv = SyntheticScanner(seed=13).scan(1024, 1024, 256)
    tar1, opt1 = _convert(psv, uids=_uids())
    # crashed after level 0: only level 0's bytes + the minted UIDs survive
    partial = {"uids": opt1.manifest["uids"], "0": opt1.manifest["0"]}
    opt2 = ConvertOptions(manifest=partial)
    tar2 = convert_wsi_to_dicom(psv, {"slide_id": "AB"}, options=opt2)
    assert tar2 == tar1


def test_pipelined_crash_mid_pyramid_checkpoints_finished_levels(monkeypatch):
    """A level is checkpointed into the manifest as soon as its last chunk
    is entropy-coded, so a crash mid-conversion resumes past it."""
    import repro.wsi.convert as cv

    psv = SyntheticScanner(seed=15).scan(512, 512, 256)  # 2 chunks + 1 chunk
    calls = []
    real = cv.encode_coef_batch

    def flaky(coef):
        calls.append(1)
        if len(calls) == 3:  # die on level 1's (only) chunk
            raise RuntimeError("killed")
        return real(coef)

    monkeypatch.setattr(cv, "encode_coef_batch", flaky)
    opt = ConvertOptions(manifest={"uids": _uids()})
    with pytest.raises(RuntimeError):
        convert_wsi_to_dicom(psv, {"slide_id": "AB"}, options=opt)
    assert "0" in opt.manifest and "1" not in opt.manifest

    monkeypatch.setattr(cv, "encode_coef_batch", real)
    level0 = opt.manifest["0"]
    tar = convert_wsi_to_dicom(psv, {"slide_id": "AB"}, options=opt)
    lv = study_levels(tar)
    assert lv["level_0.dcm"] == level0  # resumed, not recomputed
    # and the resumed tar matches an uninterrupted conversion bit-for-bit
    fresh = convert_wsi_to_dicom(
        psv, {"slide_id": "AB"},
        options=ConvertOptions(manifest={"uids": opt.manifest["uids"]}))
    assert tar == fresh


def test_clear_manifest_mints_fresh_uids():
    psv = SyntheticScanner(seed=14).scan(256, 256, 256)
    tar1, opt = _convert(psv, uids=_uids())
    opt.clear_manifest()
    assert opt.manifest == {}
    tar2 = convert_wsi_to_dicom(psv, {"slide_id": "AB"}, options=opt)
    ds1, _ = read_part10(study_levels(tar1)["level_0.dcm"])
    ds2, _ = read_part10(study_levels(tar2)["level_0.dcm"])
    assert ds1.get_str(0x0020, 0x000D) != ds2.get_str(0x0020, 0x000D)


# --------------------------------------------------------------------------
# real-mode concurrency: a multi-slide batch through the event-driven wiring
# --------------------------------------------------------------------------
def test_concurrent_real_mode_batch_matches_sequential():
    n = 4
    scanner = SyntheticScanner(seed=21)
    slides = {f"slides/s{i}.psv": scanner.scan(512, 512, 256)
              for i in range(n)}
    uids = {k: _uids() for k in slides}

    def convert(data, meta):
        opt = ConvertOptions(manifest={"uids": uids[meta["slide_id"]]})
        return convert_wsi_to_dicom(data, meta, options=opt)

    reference = {k: convert(v, {"slide_id": k}) for k, v in slides.items()}

    sched = RealScheduler(workers=8)
    pipe = ConversionPipeline(
        sched, convert=convert, max_instances=2, concurrency=2,
        cold_start=0.0, scale_down_delay=2.0,
    )
    outs = pipe.run_batch(slides, timeout=240.0)
    assert outs == reference
    # run_batch returns once the studies are stored (inside the handler);
    # the completion metric ticks in _finish after the handler returns
    deadline = clock.monotonic() + 30.0
    while pipe.done_count() < n and clock.monotonic() < deadline:
        wall_sleep(0.01)
    assert pipe.done_count() == n
    assert sorted(pipe.converted) == sorted(
        k.rsplit(".", 1)[0] + ".dcm" for k in slides)
    sched.shutdown()
