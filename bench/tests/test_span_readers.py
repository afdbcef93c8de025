"""The readers of the layer metrics that read the program's spans, on
hand-made runs. CPU only."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def _span(sid, name, start, end, parent=None, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name,
            "start": start, "end": end, "attrs": attrs}


def _ingest_ctx():
    """Two slides (1 and 4 Mpx); the subscriber spans hang under the
    store's spans, whose nearest ``key`` is the landing key."""
    from clients.ingest import Slide

    slides = [Slide("landing/1.svs", 1000, 0, 3),
              Slide("landing/2.svs", 2000, 0, 4)]
    spans = [
        _span("a", "pipeline.convert", 0, 10, key="landing/1.svs"),
        _span("b", "convert.entropy", 1, 5, "a", level=0),
        _span("c", "convert.fetch", 1, 2, "b"),
        _span("d", "convert.encode", 2, 4.5, "b"),
        _span("e", "pipeline.convert", 0, 10, key="landing/2.svs"),
        _span("f", "convert.fetch", 1, 1.5, "e"),
        _span("g", "convert.encode", 2, 2.5, "e"),
        _span("h", "pipeline.store", 10, 11, key="landing/1.svs"),
        _span("i", "stow.archive", 12, 13, "h", archive="landing/1.dcm"),
        _span("j", "sub.dicom-validation.deliver", 14, 16, "i"),
        _span("k", "validate.verify", 14, 15, "j"),
        _span("l", "inference.score", 15, 15.5, "j"),
        _span("m", "validate.verify", 20, None, "j"),  # open: not counted
        _span("n", "convert.fetch", 1, 3),  # no slide: not counted
    ]
    return SimpleNamespace(spans=spans, client=SimpleNamespace(slides=slides),
                           tw0=None, tw1=None)


class _Exports:
    """A client that credits 2 Mpx to the traced part [10, 20]."""

    def __init__(self):
        self.asked = []

    def mpx_in(self, t0, t1):
        self.asked.append((t0, t1))
        return 2.0


def _export_ctx():
    spans = [
        _span("a", "export.study", 8, 22),
        _span("b", "export.level", 9, 19, "a", level=0),
        _span("c", "export.wado", 9, 11, "b", bytes=1),     # 1 s inside
        _span("d", "decode.parse", 11, 12, "b", frames=4),  # 1 s
        _span("s", "decode.scatter", 13, 13.5, "b"),        # 0.5 s
        _span("e", "export.tiff", 18, 21, "b"),             # 2 s inside
        _span("f", "inference.score", 12, 14),
        _span("g", "decode.parse", 12, 13, "f", frames=4),  # not exported
        _span("h", "export.wado", 21, 22, "a"),             # after: 0
    ]
    return SimpleNamespace(spans=spans, client=_Exports(), tw0=10.0,
                           tw1=20.0)


@pytest.mark.parametrize("name,ctx,want", [
    ("fetch_wait_ms_per_mpx", _ingest_ctx, (1000 + 500) / 5.0),
    ("encode_ms_per_mpx", _ingest_ctx, (2500 + 500) / 5.0),
    ("subscriber_ms_per_mpx", _ingest_ctx, (1000 + 500) / 1.0),
    ("wado_ms_per_mpx", _export_ctx, 1000 / 2.0),
    ("decode_host_ms_per_mpx", _export_ctx, (1000 + 500) / 2.0),
    ("tiff_ms_per_mpx", _export_ctx, 2000 / 2.0),
])
def test_span_metric_reads_a_hand_made_run(name, ctx, want):
    c = ctx()
    assert run.reader("per_layer", name)(c) == pytest.approx(want)
    if isinstance(c.client, _Exports):
        assert c.client.asked == [(10.0, 20.0)]
    # a program without the span (the parent of the change) reads nothing
    c.spans = [s for s in c.spans if s["name"] in ("pipeline.convert",
                                                   "export.study")]
    assert run.reader("per_layer", name)(c) is None


def test_existing_span_readers_read_what_they_did():
    """The accepted readers keep their values: ``entropy_ms_per_mpx`` is
    still the whole of ``convert.entropy``."""
    c = _ingest_ctx()
    assert run.reader("per_layer", "entropy_ms_per_mpx")(c) == \
        pytest.approx(4000 / 1.0)
    assert run.reader("per_layer", "upload_ms_per_mpx")(c) is None


def test_span_metrics_are_declared_for_the_cells_that_read_them():
    spec = run.load_spec()
    cells = {"fetch_wait_ms_per_mpx": "ingest.backfill",
             "encode_ms_per_mpx": "ingest.backfill",
             "subscriber_ms_per_mpx": "ingest.backfill",
             "wado_ms_per_mpx": "export.studies",
             "decode_host_ms_per_mpx": "export.studies",
             "tiff_ms_per_mpx": "export.studies"}
    for cell in set(cells.values()):
        names = [m["name"] for m in run.metrics_for(spec, cell, "per_layer")]
        assert {n for n, c in cells.items() if c == cell} <= set(names)
    declared = {m["name"]: m for m in spec["per_layer"]}
    for n in cells:
        assert declared[n]["source"] == "program_span"
        assert (BENCH / "layer_metrics" / f"{n}.py").is_file()
