"""Distributed-tracing invariants + latency-histogram accuracy.

The tentpole observability contracts, as tests:

* **Propagation** — one slide's journey through the event spine (publish →
  every delivery attempt incl. retries, hedges, budget-exempt requeues →
  fleet admission → conversion → store) lands as ONE span tree: exactly
  one root per slide, no orphaned parent references, hedge duplicates
  linked to their primary attempt, and the tree survives scripted broker
  faults and a mid-flight instance kill.
* **Determinism** — a tracer clocked by ``SimScheduler`` exports
  bit-identical span lists across identical runs.
* **Cost** — conversion bytes are identical with tracing armed vs
  disarmed (the instrumentation observes, never participates), and the
  disarmed entry points are true no-ops.
* **Histograms** — the log-bucketed percentiles respect the documented
  ~19% bucket-width error bound, and ``Metrics._now()`` keeps real
  timestamps without a scheduler (the PR-10 regression fix).
"""
import hashlib
import json

import pytest

from repro.core import (ConversionPipeline, DeliveryFaults, Metrics,
                        RealScheduler, SimScheduler, Subscription, Topic,
                        tracing)
from repro.core.dashboard import build_report, trace_problems
from repro.core.metrics import Histogram

ROOT = "topic.wsi-dicom-conversion.publish"


# ------------------------------------------------------- metrics regression
def test_metrics_now_without_scheduler_is_monotonic_not_zero():
    # regression: real-mode Metrics (no scheduler) stamped every sample 0.0
    m = Metrics()
    m.record("fig.t", 1.0)
    m.record("fig.t", 2.0)
    ts = [t for t, _ in m.timeseries("fig.t")]
    assert all(t > 0.0 for t in ts)
    assert ts == sorted(ts)
    m.log("boot")
    assert m.events[0][0] > 0.0


def test_metrics_now_prefers_scheduler_time():
    sched = SimScheduler()
    m = Metrics(sched)
    sched.schedule(7.0, lambda: m.record("fig.t", 1.0))
    sched.run()
    assert m.timeseries("fig.t") == [(7.0, 1.0)]


# ------------------------------------------------------- histogram accuracy
def test_histogram_percentiles_within_bucket_error_bound():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    s = h.snapshot()
    assert s["count"] == 100 and s["sum"] == 5050.0
    assert s["min"] == 1.0 and s["max"] == 100.0 and s["mean"] == 50.5
    # log2 buckets of width 0.25 → percentile is the bucket upper bound,
    # at most 2**0.25 (~19%) above the exact order statistic
    assert 50.0 <= s["p50"] <= 50.0 * 2 ** 0.25
    assert 95.0 <= s["p95"] <= 100.0  # clamped into [min, max]
    assert 99.0 <= s["p99"] <= 100.0


def test_histogram_zero_and_negative_values_bucket():
    h = Histogram()
    for v in (-1.0, 0.0, 4.0):  # sim queue waits are often exactly 0.0
        h.observe(v)
    assert h.zeros == 2
    assert h.percentile(0.50) == -1.0  # rank falls in the zeros bucket
    s = h.snapshot()
    assert s["min"] == -1.0 and s["max"] == 4.0 and s["count"] == 3


def test_metrics_observe_feeds_named_histogram():
    m = Metrics()
    for v in (1.0, 2.0, 4.0):
        m.observe("sub.x.latency", v)
    snap = m.histogram("sub.x.latency")
    assert snap["count"] == 3 and snap["sum"] == 7.0
    assert m.histogram("no.such")["count"] == 0
    assert "sub.x.latency" in m.summary()["histograms"]


# ---------------------------------------------------------- arming contract
def test_disarmed_entry_points_are_noops():
    assert tracing.current() is None
    assert tracing.start_span("a.b") is None
    tracing.end_span(None)  # must not raise
    tracing.add_event(None, "a.b")
    with tracing.span("a.b") as sp:
        assert sp is None
    attrs = {"k": "v"}
    tracing.inject(attrs)
    assert attrs == {"k": "v"}  # nothing written
    assert tracing.extract({"trace_id": "t", "span_id": "s"}) is None


def test_arm_twice_raises_and_capture_restores():
    tr = tracing.arm()
    try:
        try:
            tracing.arm()
            raise AssertionError("second arm() must raise")
        except RuntimeError:
            pass
        with tracing.capture() as shadow:
            assert tracing.current() is shadow
            with tracing.span("shadow.op"):
                pass
        assert tracing.current() is tr  # restored
        assert len(shadow.spans) == 1 and not tr.spans
    finally:
        assert tracing.disarm() is tr
    assert tracing.current() is None


# -------------------------------------------------- propagation invariants
def _assert_one_root_per_trace(tracer, n_expected, root_name=ROOT):
    traces = tracer.traces()
    assert len(traces) == n_expected
    for tid, spans in traces.items():
        roots = [sp for sp in spans if sp.parent_id is None]
        assert len(roots) == 1, f"{tid}: {len(roots)} roots"
        assert roots[0].name == root_name
        assert trace_problems(spans) == [], trace_problems(spans)
    return traces


def _scripted_fault_run(seed_spans=False):
    """The scripted drop/duplicate/delay scenario under a traced sim."""
    faults = (DeliveryFaults()
              .drop("s0", attempts=(1,))
              .duplicate("s1", lag=1.0)
              .delay("s2", by=200.0))  # past the 120 s ack deadline
    sched = SimScheduler()
    with tracing.capture(now=sched.now) as tracer:
        pipe = ConversionPipeline(
            sched, service_time=20.0, cold_start=5.0, max_instances=4,
            ack_deadline=120.0, min_backoff=5.0, subscribers=False,
            fleet={}, ordered_ingest=True, delivery_faults=faults)
        for i in range(4):
            pipe.ingest(f"scans/s{i}.psv", bytes([i + 1]) * 8)
        sched.run()
    return pipe, tracer


def _events(tracer, name):
    return [(sp, t, attrs) for sp in tracer.spans
            for t, n, attrs in sp.events if n == name]


def test_fault_gauntlet_one_connected_tree_per_slide():
    pipe, tracer = _scripted_fault_run()
    assert pipe.metrics.get("sub.wsi2dcm-push.acks") == 4
    traces = _assert_one_root_per_trace(tracer, 4)
    # faults are structured span events on the delivery they hit
    for ev in ("fault.drop", "fault.delay", "fault.duplicate"):
        hits = _events(tracer, ev)
        assert len(hits) == 1, f"{ev}: {hits}"
        assert hits[0][0].name == "sub.wsi2dcm-push.deliver"
    # the dropped delivery expired its deadline and retried IN THE SAME
    # trace: its span settles "deadline", the retry is a sibling attempt
    (drop_sp, _, _), = _events(tracer, "fault.drop")
    assert drop_sp.status == "deadline"
    assert any(n == "sub.retry" for _, n, _ in drop_sp.events)
    retried = [sp for sp in traces[drop_sp.trace_id]
               if sp.name == "sub.wsi2dcm-push.deliver"]
    assert len(retried) == 2  # dropped attempt + the redelivery
    assert {sp.parent_id for sp in retried} == {retried[0].parent_id}
    # the duplicated delivery deduped at fleet admission, visibly
    assert _events(tracer, "fleet.duplicate")


def test_trace_export_is_deterministic_across_runs():
    def normalized(tracer):
        # message/request ids come from process-global counters; the
        # determinism contract covers span ids, structure, and timings
        out = tracer.export()
        for sp in out:
            sp["attrs"].pop("message_id", None)
            sp["attrs"].pop("req_id", None)
            for ev in sp["events"]:
                ev["attrs"].pop("req_id", None)
        return out

    _, t1 = _scripted_fault_run()
    _, t2 = _scripted_fault_run()
    assert normalized(t1) == normalized(t2)


def test_hedge_span_links_primary_delivery():
    deliveries = []

    def ep(m, c):
        deliveries.append(c)
        if len(deliveries) == 1:
            return  # original hangs; the hedged duplicate wins
        c.ack()

    sched = SimScheduler()
    with tracing.capture(now=sched.now) as tracer:
        topic = Topic("t", sched)
        sub = Subscription(topic, "s", ep, hedge_after=20.0,
                           ack_deadline=1000.0, min_backoff=5.0)
        topic.publish({"i": 0})
        sched.run()
    assert sub.metrics.get("sub.s.hedge_acks") == 1
    (pub,) = tracer.spans_named("topic.t.publish")
    (orig,) = tracer.spans_named("sub.s.deliver")
    (hedge,) = tracer.spans_named("sub.s.hedge")
    # both race legs parent on the publish span, in one trace, and the
    # duplicate carries the hedge_of link back to the primary attempt
    assert orig.parent_id == pub.span_id
    assert hedge.parent_id == pub.span_id
    assert hedge.trace_id == orig.trace_id == pub.trace_id
    assert hedge.attrs["hedge_of"] == orig.span_id
    assert hedge.status == "acked" and orig.status == "acked"


def test_backpressure_requeues_stay_in_their_trace():
    sched = SimScheduler()
    n = 10
    with tracing.capture(now=sched.now) as tracer:
        pipe = ConversionPipeline(
            sched, service_time=30.0, cold_start=5.0, max_instances=2,
            min_backoff=5.0, max_delivery_attempts=3, subscribers=False,
            fleet=dict(shed_backlog=3), ordered_ingest=True)
        for i in range(n):
            pipe.ingest(f"burst/s{i:02d}.psv", bytes([i + 1]) * 8)
        sched.run()
    assert pipe.metrics.get("svc.wsi2dcm.shed") > 0
    traces = _assert_one_root_per_trace(tracer, n)
    shed = [sp for sp in tracer.spans if sp.status == "requeued"]
    assert shed, "overload never produced a requeued delivery span"
    for sp in shed:
        assert sp.name == "sub.wsi2dcm-push.deliver"
        assert any(n_ == "sub.requeue" for _, n_, _ in sp.events)
        # the budget-exempt redelivery landed in the SAME trace and
        # eventually acked — shed work is visible, never lost
        attempts = [s for s in traces[sp.trace_id]
                    if s.name == "sub.wsi2dcm-push.deliver"]
        assert len(attempts) >= 2
        assert attempts[-1].status == "acked"


def test_kill_mid_conversion_keeps_one_tree():
    sched = SimScheduler()
    with tracing.capture(now=sched.now) as tracer:
        pipe = ConversionPipeline(
            sched, service_time=50.0, cold_start=5.0, max_instances=1,
            min_backoff=5.0, subscribers=False, fleet={},
            ordered_ingest=True)
        pipe.ingest("scans/a.psv", b"aaaa")
        sched.schedule(20.0, pipe.service.kill_instance)  # mid-conversion
        sched.run()
    assert pipe.metrics.get("svc.wsi2dcm.killed") == 1
    traces = _assert_one_root_per_trace(tracer, 1)
    (spans,) = traces.values()
    handles = [sp for sp in spans if sp.name == "svc.wsi2dcm.handle"]
    # the serve attempt died with the instance; the requeued run finished.
    # Both live under ONE request span that records the kill_requeue hop
    assert sorted(sp.status for sp in handles) == ["killed", "ok"]
    (req,) = (sp for sp in spans if sp.name == "svc.wsi2dcm.request")
    assert req.status == "ok"
    assert any(n == "fleet.kill_requeue" for _, n, _ in req.events)
    assert {sp.parent_id for sp in handles} == {req.span_id}


# ------------------------------------------------- real-pipeline acceptance
def _pinned_convert(data, meta):
    from repro.wsi.convert import ConvertOptions, convert_wsi_to_dicom
    h = hashlib.sha256(meta["slide_id"].encode()).hexdigest()
    uids = ["2.25." + str(int(h[:24], 16)), "2.25." + str(int(h[24:48], 16))]
    return convert_wsi_to_dicom(
        data, meta, options=ConvertOptions(manifest={"uids": json.dumps(uids)}))


@pytest.fixture(scope="module")
def real_single_slide():
    """One slide through the real deployment (real scheduler, real
    converter, store + validation/inference subscribers + auto-export),
    traced: ``(pipe, tracer)``."""
    from repro.wsi import SyntheticScanner

    scanner = SyntheticScanner(seed=3)
    slides = {"scans/acc.psv": scanner.scan(256, 256, 256)}
    meta = {"scans/acc.psv": {"slide_id": "scans/acc.psv"}}
    sched = RealScheduler(workers=4)
    try:
        with tracing.capture(now=sched.now) as tracer:
            pipe = ConversionPipeline(
                sched, convert=_pinned_convert, cold_start=0.0,
                max_instances=2, fleet={}, ordered_ingest=True,
                store_shards=2, auto_export=True)
            pipe.run_batch(slides, meta, timeout=180.0)
            sched.run(until=60.0)  # drain store ingest + fan-out + export
    finally:
        sched.shutdown()
    return pipe, tracer


def test_real_single_slide_lands_as_one_span_tree(real_single_slide):
    """Acceptance: a single-slide real run (real scheduler, real
    converter, store + validation/inference subscribers + auto-export) is
    one connected trace covering every hop, and the dashboard's critical
    path accounts for its wall time within 5%."""
    pipe, tracer = real_single_slide
    traces = _assert_one_root_per_trace(tracer, 1)
    ((tid, spans),) = traces.items()
    names = {sp.name for sp in spans}
    for hop in (ROOT, "sub.wsi2dcm-push.deliver", "svc.wsi2dcm.request",
                "svc.wsi2dcm.handle", "pipeline.fetch", "pipeline.convert",
                "pipeline.store", "convert.slide", "convert.entropy",
                "convert.fetch", "convert.encode", "convert.wrap",
                "stow.archive", "validate.verify", "inference.score",
                "export.study", "export.query", "export.level",
                "export.wado", "decode.parse", "decode.entropy",
                "decode.inverse", "export.tiff", "export.put"):
        assert hop in names, f"missing hop {hop}: {sorted(names)}"
    events = {n for sp in spans for _, n, _ in sp.events}
    assert {"stow.instance", "validate.instance",
            "inference.instance"} <= events
    # critical-path attribution: queue + compute + store sums to the
    # trace's wall-clock window within the acceptance tolerance
    report = build_report(pipe.metrics, tracer, title="acceptance")
    (t,) = [x for x in report["traces"] if x["trace_id"] == tid]
    assert t["slide"] == "scans/acc.psv" and not t["problems"]
    covered = sum(t["attribution"].values())
    assert abs(covered - t["duration"]) <= 0.05 * max(t["duration"], 1e-9)
    assert t["attribution"]["compute"] > 0.0
    # the histogram migration: delivery latency lands in a bounded
    # histogram, not an unbounded series
    assert report["histograms"]["sub.wsi2dcm-push.latency"]["count"] >= 1


def _key_of(span, index):
    """The nearest ``key`` attribute on the span or its ancestors (the
    rule the chip benchmark's span readers resolve a slide by)."""
    while span is not None:
        if "key" in span.attrs:
            return span.attrs["key"]
        span = index.get(span.parent_id)
    return None


@pytest.mark.parametrize("name", ["validate.verify", "inference.score"])
def test_subscriber_spans_resolve_to_the_slide_key(real_single_slide, name):
    _, tracer = real_single_slide
    index = {sp.span_id: sp for sp in tracer.spans}
    subs = tracer.spans_named(name)
    # one per stored instance: the 256x256 slide stores one level
    assert len(subs) == 1 and subs[0].status == "ok"
    assert _key_of(subs[0], index) == "scans/acc.psv"
    if name == "inference.score":
        assert subs[0].attrs["frames"] == 1
        kids = {sp.name for sp in tracer.spans
                if index.get(sp.parent_id) in subs}
        assert kids == {"decode.parse", "decode.entropy", "decode.inverse"}


def test_entropy_span_children_cover_it():
    """Each level's ``convert.entropy`` holds one ``convert.fetch``, one
    ``convert.encode`` and one ``convert.wrap``; together they take at
    most its time and, over the slide, at least 95% of it. The slide span
    counts its own upload, dispatch and fetches."""
    from repro.wsi import SyntheticScanner

    psv = SyntheticScanner(seed=5).scan(1024, 1024, 256)
    meta = {"slide_id": "scans/cover.psv"}
    _pinned_convert(psv, meta)  # compile outside the capture
    with tracing.capture() as tracer:
        _pinned_convert(psv, meta)
    entropy = tracer.spans_named("convert.entropy")
    assert [sp.attrs["level"] for sp in entropy] == [0, 1, 2]
    parts = 0.0
    for sp in entropy:
        kids = [k for k in tracer.spans if k.parent_id == sp.span_id]
        assert [k.name for k in kids] == ["convert.fetch", "convert.encode",
                                          "convert.wrap"]
        took = sum(k.duration() for k in kids)
        assert took <= sp.duration()
        parts += took
        fetch, encode, _ = kids
        side = 1024 >> sp.attrs["level"]
        assert fetch.attrs == {"level": sp.attrs["level"]}  # waits only
        assert encode.attrs["frames"] == (side // 256) ** 2
        # chunks of ≤ 4 tiles: coded on the host from their int32 coefs
        assert encode.attrs["host_tiles"] == encode.attrs["frames"]
        assert encode.attrs["device_tiles"] == 0
        assert encode.attrs["bytes_in"] == side * side * 3 * 4
        assert encode.attrs["bytes_out"] > 0
    assert parts >= 0.95 * sum(sp.duration() for sp in entropy)
    (slide,) = tracer.spans_named("convert.slide")
    assert {k: slide.attrs[k] for k in ("uploads", "dispatches",
                                        "fetches", "levels", "host_tiles",
                                        "device_tiles")} == {
        "uploads": 1, "dispatches": 1, "fetches": 3, "levels": 3,
        "host_tiles": 21, "device_tiles": 0}


def test_slide_span_counts_only_its_own_transfers():
    """Two conversions at once under one tracer: each ``convert.slide``
    counts its own upload, dispatch and fetches, not its neighbour's."""
    from repro.analysis import racedep
    from repro.wsi import SyntheticScanner

    psv = SyntheticScanner(seed=6).scan(512, 512, 256)
    _pinned_convert(psv, {"slide_id": "scans/warm.psv"})
    with tracing.capture() as tracer:
        ths = [racedep.spawn(_pinned_convert, psv,
                             {"slide_id": f"scans/c{i}.psv"})
               for i in range(2)]
        for th in ths:
            th.join()
    slides = tracer.spans_named("convert.slide")
    assert len(slides) == 2
    for sp in slides:
        assert (sp.attrs["uploads"], sp.attrs["dispatches"],
                sp.attrs["fetches"]) == (1, 1, 2)


class _Annotation:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that records."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_annotate_hook_wraps_each_span_while_armed():
    _Annotation.log = []
    with tracing.span("a.b"):  # disarmed: never entered
        pass
    assert _Annotation.log == []
    tracer = tracing.arm(annotate=_Annotation)
    try:
        with tracing.span("outer.op"):
            with tracing.span("inner.op"):
                pass
        sp = tracing.start_span("manual.op")  # not a span block
        tracing.end_span(sp)
    finally:
        tracing.disarm()
    assert _Annotation.log == [("enter", "outer.op"), ("enter", "inner.op"),
                               ("exit", "inner.op"), ("exit", "outer.op")]
    assert [sp.name for sp in tracer.spans] == ["outer.op", "inner.op",
                                                "manual.op"]
    _Annotation.log = []
    with tracing.span("a.b"):
        pass
    assert _Annotation.log == []  # disarmed again
    tr = tracing.arm(annotate=_Annotation)
    try:
        try:
            with tracing.span("fail.op"):
                raise ValueError("boom")
        except ValueError:
            pass
    finally:
        tracing.disarm()
    assert _Annotation.log == [("enter", "fail.op"), ("exit", "fail.op")]
    assert tr.spans[0].status == "error"


def test_conversion_bytes_identical_armed_vs_disarmed():
    from repro.wsi import SyntheticScanner

    psv = SyntheticScanner(seed=5).scan(256, 256, 256)
    meta = {"slide_id": "scans/id.psv"}
    assert tracing.current() is None
    plain = _pinned_convert(psv, meta)
    with tracing.capture() as tracer:
        traced = _pinned_convert(psv, meta)
    assert tracer.spans_named("convert.slide"), "tracer saw no conversion"
    assert traced == plain, "tracing changed the produced DICOM bytes"
