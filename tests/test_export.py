"""Export subsystem: event-driven DICOM → tiled-TIFF retrieval — QIDO/WADO
reads, deterministic TIFF output (repeat + crash-rebuild byte identity),
actionable DLQ reasons for corrupt frames, auto-export fan-out, and the
full-circle re-ingestion of an exported TIFF."""
import numpy as np
import pytest

from repro.core import ConversionPipeline, RealScheduler, SimScheduler
from repro.core.storage import ObjectStore
from repro.wsi import (ConvertOptions, DicomStoreService, ExportService,
                       SyntheticScanner, convert_wsi_to_dicom, decode_tile,
                       open_slide, study_levels, write_part10)
from repro.wsi.dicom import TS_JPEG_BASELINE
from repro.wsi.formats import TiffSlideReader


def _stored_study(hw=512, seed=3, **convert_kw):
    psv = SyntheticScanner(seed=seed).scan(hw, hw, 256)
    archive = convert_wsi_to_dicom(psv, {"slide_id": "exp"},
                                   options=ConvertOptions(**convert_kw))
    sched = SimScheduler()
    store = ObjectStore(sched)
    svc = DicomStoreService(store.bucket("dicom"), sched)
    svc.store_study_archive("studies/exp.tar", archive)
    (study,) = svc.search_studies()
    return psv, svc, store, study


def _derived_bytes(derived):
    return {k: derived.get(k).data for k in derived.list()}


# --------------------------------------------------------------------------
# the export itself
# --------------------------------------------------------------------------
def test_export_study_writes_reopenable_level_tiffs():
    _, svc, store, study = _stored_study()
    exporter = ExportService(svc, store.bucket("derived"))
    keys = exporter.export_study(study)
    assert keys == [f"{study}/level_0.tiff", f"{study}/level_1.tiff"]
    for li, key in enumerate(keys):
        rd = open_slide(store.bucket("derived").get(key).data)
        assert isinstance(rd, TiffSlideReader)
        assert (rd.H, rd.W, rd.tile) == (512 >> li, 512 >> li, 256)
        # provenance rides in the Aperio-style ImageDescription
        assert rd.metadata["vendor"] == "repro-dicom2tiff"
        assert rd.metadata["study"] == study
        assert rd.metadata["level"] == str(li)
    assert exporter.exported == [(study, tuple(keys))]


def test_exported_pixels_match_wado_frame_decode():
    """The TIFF tiles are exactly the decoded WADO frames, row-major."""
    _, svc, store, study = _stored_study()
    exporter = ExportService(svc, store.bucket("derived"))
    (key0, _) = exporter.export_study(study)
    rd = open_slide(store.bucket("derived").get(key0).data)
    meta = svc.search_instances(study)[0]
    sop = meta["sop_instance_uid"]
    bh, bw = rd.grid
    for r in range(bh):
        for c in range(bw):
            frame = svc.retrieve_frame(sop, r * bw + c)
            np.testing.assert_array_equal(rd.read_tile(r, c),
                                          decode_tile(frame))


def test_export_levels_are_spans_with_their_stages_as_children():
    from repro.core import tracing

    _, svc, store, study = _stored_study()
    exporter = ExportService(svc, store.bucket("derived"))
    with tracing.capture() as tracer:
        exporter.export_study(study)
    index = {sp.span_id: sp for sp in tracer.spans}
    (top,) = tracer.spans_named("export.study")
    (query,) = tracer.spans_named("export.query")
    assert query.parent_id == top.span_id
    levels = tracer.spans_named("export.level")
    assert [sp.parent_id for sp in levels] == [top.span_id] * 2
    assert [(sp.attrs["level"], sp.attrs["px"], sp.attrs["frames"])
            for sp in levels] == [(0, 512 * 512, 4), (1, 256 * 256, 1)]
    for sp in levels:
        kids = [k for k in tracer.spans if k.parent_id == sp.span_id]
        # the per-tile decoder of a single frame integrates as it decodes
        scatter = ["decode.scatter"] if sp.attrs["frames"] > 1 else []
        assert [k.name for k in kids] == [
            "export.wado", "decode.parse", "decode.entropy", *scatter,
            "decode.inverse", "export.tiff", "export.put"]
        assert all(k.status == "ok" for k in kids)
        wado = kids[0]
        sop = svc.search_instances(study)[sp.attrs["level"]][
            "sop_instance_uid"]
        assert wado.attrs["bytes"] == sum(
            len(svc.retrieve_frame(sop, i))
            for i in range(sp.attrs["frames"]))
    # the batched decoder serves the 4-frame level, the per-tile one the
    # single frame
    engines = [sp.attrs["engine"] for sp in tracer.spans_named(
        "decode.entropy")]
    assert engines[1] == "python" and engines[0] in ("jax", "numpy")
    assert all(index[k.parent_id].name == "export.level"
               for k in tracer.spans_named("decode.parse"))
    # the per-level events stay on the study span
    assert [n for _, n, _ in top.events] == ["export.level"] * 2


def test_export_bytes_identical_armed_vs_disarmed():
    from repro.core import tracing

    _, svc, store, study = _stored_study()
    plain = ExportService(svc, store.bucket("plain"))
    plain.export_study(study)
    traced = ExportService(svc, store.bucket("traced"))
    with tracing.capture() as tracer:
        traced.export_study(study)
    assert tracer.spans_named("export.level"), "tracer saw no export"
    a = _derived_bytes(store.bucket("plain"))
    b = _derived_bytes(store.bucket("traced"))
    assert len(a) == 2 and list(a.values()) == list(b.values())


def test_native_study_exports_lossless_pixels():
    """jpeg=False studies export through the native path — the TIFF pixels
    equal the original scan exactly (no transform loss anywhere)."""
    psv, svc, store, study = _stored_study(jpeg=False)
    exporter = ExportService(svc, store.bucket("derived"))
    keys = exporter.export_study(study)
    rd = open_slide(store.bucket("derived").get(keys[0]).data)
    src = open_slide(psv)
    for (rc, tile) in src.tiles():
        np.testing.assert_array_equal(rd.read_tile(*rc), tile)


def test_repeated_and_post_rebuild_exports_are_byte_identical():
    _, svc, store, study = _stored_study()
    exporter = ExportService(svc, store.bucket("derived"))
    exporter.export_study(study)
    clean = _derived_bytes(exporter.derived)

    # full re-derivation forced: the decode + write_tiff pipeline itself
    # must be deterministic (content-addressed no-op, no re-notify)
    exporter.export_study(study, skip_unchanged=False)
    assert _derived_bytes(exporter.derived) == clean
    assert store.metrics.get("bucket.derived.idempotent_skips") >= 2

    # default path short-circuits on the recorded content generation —
    # no WADO fetch, no decode (frames_decoded unchanged)
    before = svc.metrics.get("pipeline.export.frames_decoded")
    keys = exporter.export_study(study)
    assert svc.metrics.get("pipeline.export.levels_unchanged") == 2
    assert svc.metrics.get("pipeline.export.frames_decoded") == before
    assert keys == sorted(clean)  # skipped levels still report their keys

    # simulated crash: fresh service over the same bucket + rebuilt index
    svc2 = DicomStoreService(store.bucket("dicom"), svc.scheduler)
    svc2.rebuild_index()
    exporter2 = ExportService(svc2, store.bucket("derived2"))
    exporter2.export_study(study)
    assert _derived_bytes(exporter2.derived) == {
        k: v for k, v in clean.items()}


def test_sub_tile_levels_are_skipped_not_fatal():
    """A level smaller than one tile stores zero frames — export skips it
    (there are no pixels) and records the skip."""
    _, svc, store, study = _stored_study(min_level_size=128)
    exporter = ExportService(svc, store.bucket("derived"))
    keys = exporter.export_study(study)
    assert [k.rsplit("/", 1)[1] for k in keys] == \
        ["level_0.tiff", "level_1.tiff"]  # level_2 (128² < tile) skipped
    assert svc.metrics.get("pipeline.export.levels_skipped") == 1


def test_unknown_study_raises_key_error():
    _, svc, store, _ = _stored_study()
    exporter = ExportService(svc, store.bucket("derived"))
    with pytest.raises(KeyError, match="unknown study"):
        exporter.export_study("2.25.404")


# --------------------------------------------------------------------------
# the event-driven hop (pipeline wiring)
# --------------------------------------------------------------------------
def test_request_export_through_pipeline_topic():
    sched = SimScheduler()
    pipe = ConversionPipeline(sched)
    archive = convert_wsi_to_dicom(
        SyntheticScanner(seed=4).scan(512, 512, 256), {"slide_id": "s"})
    pipe.dicom.put("studies/s.dcm", archive)  # → store-ingest hop
    sched.run()
    (study,) = pipe.store_service.search_studies()
    assert pipe.derived.list() == []  # no auto-export by default

    pipe.request_export(study)
    sched.run()
    assert pipe.derived.list() == [f"{study}/level_0.tiff",
                                   f"{study}/level_1.tiff"]
    g = pipe.metrics.get
    assert g("pipeline.export.requests") == 1
    assert g("pipeline.export.frames_decoded") == 5  # 4 + 1 frames
    assert g("pipeline.export.bytes_written") > 0
    assert g("topic.export-request.published") == 1


def test_auto_export_triggers_on_instance_stored():
    sched = SimScheduler()
    pipe = ConversionPipeline(sched, auto_export=True)
    archive = convert_wsi_to_dicom(
        SyntheticScanner(seed=6).scan(512, 512, 256), {"slide_id": "s"})
    pipe.dicom.put("studies/s.dcm", archive)
    sched.run()
    (study,) = pipe.store_service.search_studies()
    # every stored instance republished the request; the repeats skip on
    # the recorded content generation instead of re-decoding every level
    assert pipe.derived.list() == [f"{study}/level_0.tiff",
                                   f"{study}/level_1.tiff"]
    assert pipe.metrics.get("pipeline.export.requests") == 2
    assert pipe.metrics.get("pipeline.export.frames_decoded") == 5
    assert pipe.metrics.get("pipeline.export.levels_unchanged") == 2


def test_corrupt_frame_dead_letters_with_actionable_reason():
    """A stored instance whose frame bytes rot into undecodable JPEG must
    exhaust export retries and land in the export DLQ carrying the
    decoder's corrupt-JPEG reason."""
    sched = SimScheduler()
    pipe = ConversionPipeline(sched, max_delivery_attempts=2,
                              min_backoff=0.1, max_backoff=0.1,
                              subscribers=False)
    # SOI marker present (so the deep-verify path keeps it) but garbage after
    bad = b"\xff\xd8" + b"\x99" * 40
    blob = write_part10(frames=[bad], rows=8, cols=8, total_rows=8,
                        total_cols=8, transfer_syntax=TS_JPEG_BASELINE,
                        study_uid="1.2.9", series_uid="1.2.9.1",
                        sop_instance_uid="1.2.9.1.1")
    pipe.store_service.store_instance(blob)
    pipe.request_export("1.2.9")
    sched.run()
    assert pipe.derived.list() == []
    assert pipe.metrics.get("pipeline.export.dead_lettered") == 1
    ((event, reason),) = pipe.export_dead_lettered
    assert event == {"study_uid": "1.2.9"}
    assert "corrupt JPEG" in reason


# --------------------------------------------------------------------------
# full circle: scan → convert → store → export → re-ingest
# --------------------------------------------------------------------------
def test_full_circle_export_reingests_through_sniffing_pipeline():
    sched = RealScheduler(workers=4)
    pipe = ConversionPipeline(
        sched, convert=lambda data, meta: convert_wsi_to_dicom(data, meta),
        max_instances=2, cold_start=0.0, scale_down_delay=2.0,
    )
    psv = SyntheticScanner(seed=11).scan(512, 512, 256)
    pipe.run_batch({"slides/circle.psv": psv}, timeout=240.0)
    sched.run(until=30.0)  # store ingest + subscriber fan-out
    (study,) = pipe.store_service.search_studies()
    pipe.request_export(study)
    sched.run(until=30.0)
    keys = pipe.derived.list()
    assert keys == [f"{study}/level_0.tiff", f"{study}/level_1.tiff"]

    # the exported level-0 TIFF goes back through the same sniffing
    # pipeline as any scanner upload and lands as a new study
    tif = pipe.derived.get(keys[0]).data
    out = pipe.run_batch({"slides/rescan.tiff": tif}, timeout=240.0)
    assert pipe.metrics.get("pipeline.format.tiff") >= 1
    levels = study_levels(out["slides/rescan.tiff"])
    assert sorted(k for k in levels if k.endswith(".dcm")) == \
        ["level_0.dcm", "level_1.dcm"]
    sched.run(until=30.0)
    assert len(pipe.store_service.search_studies()) == 2
    assert pipe.validator.quarantined == []
    sched.shutdown()


def test_export_of_a_transcoded_jpeg_svs_decodes_its_420_level0():
    """A study transcoded from a scanner's JPEG SVS keeps the scanner's
    4:2:0 tiles as level 0 (YBR_FULL_422); its export writes level 0's
    TIFF from those frames, decoded as the per-tile decoder decodes them."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    import scanner_jpeg

    _, svs = scanner_jpeg.scan(512, 512, 256, 4.25)
    sched = SimScheduler()
    store = ObjectStore(sched)
    svc = DicomStoreService(store.bucket("dicom"), sched)
    svc.store_study_archive("studies/svs.tar", convert_wsi_to_dicom(svs))
    (study,) = svc.search_studies()
    meta = svc.search_instances(study)[0]
    exporter = ExportService(svc, store.bucket("derived"))
    keys = exporter.export_study(study)
    assert len(keys) == 2
    rd = open_slide(store.bucket("derived").get(keys[0]).data)
    assert (rd.H, rd.W) == (512, 512)
    for i in range(4):
        frame = svc.retrieve_frame(meta["sop_instance_uid"], i)
        np.testing.assert_array_equal(rd.read_tile(*divmod(i, 2)),
                                      decode_tile(frame))
