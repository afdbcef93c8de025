"""Institutional-scale converter fleet benchmark (paper Figures 2-3) plus a
fault-injection gauntlet that proves the fleet's delivery guarantees.

Four sections, all written into ``BENCH_fleet.json``:

* **fig2** — serial vs 16-way parallel vs the event-driven *fleet* (per-
  instance queues, controller scaling, ordered ingest) at batches of
  1/10/50 slides. Asserts the paper's crossover: cold start makes the
  fleet LOSE at n=1 and WIN against both baselines at n>=10.
* **fig3** — average container instances per minute during a 50-slide
  burst through the fleet: ramp to a plateau that never exceeds
  ``max_instances``, then decay back to zero.
* **sharded_store** — study-UID-hash routing balance across bucket
  partitions, plus crash-a-shard → ``rebuild_index()`` → byte-identical
  QIDO/WADO (measured on the gauntlet's real studies).
* **lockdep_overhead** — the disarmed-fast-path gate. Benchmarks run with
  lockdep *disarmed* (only the pytest plugin arms it), so the gate proves
  the production configuration costs nothing: the same fleet simulation
  timed with bare ``threading.Lock`` delegation vs disarmed
  ``TrackedLock`` (one module-global read per operation), min-of-N,
  asserted < 10% apart. The fully-armed detector's ratio is reported as a
  diagnostic alongside.
* **fault_injection** — the deterministic gauntlet: real JPEG/DICOM
  conversion under ``SimScheduler`` with pinned study UIDs, while the
  broker drops, delays, and duplicates deliveries, an instance is killed,
  and a store shard crashes. Asserts zero lost and zero double-converted
  slides, no dead-letters, and study tars byte-identical to a serial
  (no-infrastructure) conversion of the same slides. A backpressure
  sub-scenario overloads a 2-instance fleet past ``shed_backlog`` and
  asserts shed work is requeued budget-exempt — never dead-lettered —
  until it completes.

``--fast`` shrinks the gauntlet workload and skips wall-clock calibration;
every assertion is identical (the CI smoke runs this mode).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time

from benchmarks import fig2_workflows as fig2
from benchmarks import fig3_autoscaling as fig3
from repro.analysis import lockdep, racedep
from repro.core import (ConversionPipeline, DeliveryFaults, SimScheduler,
                        dashboard, tracing)
from repro.launch.cache import enable_compile_cache

TAU = 90.0          # paper: ~90 s per gigapixel conversion on a 16-vCPU VM
COLD = 12.0         # paper: Cloud Run cold start
FLEET_BATCHES = (1, 10, 50)
FLEET_KW = dict(fleet={}, ordered_ingest=True)


def _uids_for(slide_id: str) -> list[str]:
    """Deterministic (study, series) UIDs so the fleet run and the serial
    baseline mint identical studies — byte-identity needs pinned UIDs."""
    h = hashlib.sha256(slide_id.encode()).hexdigest()
    return ["2.25." + str(int(h[:24], 16)),
            "2.25." + str(int(h[24:48], 16))]


def _pinned_convert():
    from repro.wsi.convert import ConvertOptions, convert_wsi_to_dicom

    def convert(data: bytes, meta: dict) -> bytes:
        opt = ConvertOptions(
            manifest={"uids": json.dumps(_uids_for(meta["slide_id"]))})
        return convert_wsi_to_dicom(data, meta, options=opt)

    return convert


# ---------------------------------------------------------------- fig 2
def _fig2_section(calibrate: bool) -> dict:
    rows = []
    if calibrate:
        tau_meas = fig2.measure_service_time()
        tau_scaled = tau_meas * (36_000 * 36_000) / (256 * 256)
        rows.append({"workflow": "calibration", "n": 1,
                     "seconds": round(tau_meas, 3),
                     "note": f"measured 256^2; "
                             f"gigapixel-scaled={tau_scaled:.0f}s"})
    for n in FLEET_BATCHES:
        rows.append({"workflow": "serial", "n": n,
                     "seconds": fig2.serial_time(n, TAU)})
        rows.append({"workflow": "parallel16", "n": n,
                     "seconds": fig2.parallel_time(n, TAU)})
        rows.append({"workflow": "event_driven_fleet", "n": n,
                     "seconds": round(fig2.autoscaling_time(
                         n, TAU, cold_start=COLD, **FLEET_KW), 1)})
    t = {(r["workflow"], r["n"]): r["seconds"] for r in rows
         if r["workflow"] != "calibration"}
    assert t[("event_driven_fleet", 1)] > t[("serial", 1)], \
        "cold start should make the fleet lose at n=1"
    for n in FLEET_BATCHES[1:]:
        assert t[("event_driven_fleet", n)] < t[("parallel16", n)] \
            < t[("serial", n)], f"fleet should win at n={n}"
    return {
        "tau_s": TAU, "cold_start_s": COLD, "rows": rows,
        "crossover": {
            "loses_at_1": t[("event_driven_fleet", 1)]
            > t[("serial", 1)],
            **{f"wins_at_{n}": t[("event_driven_fleet", n)]
               < t[("parallel16", n)] for n in FLEET_BATCHES[1:]},
        },
    }


# ---------------------------------------------------------------- fig 3
def _fig3_section() -> dict:
    max_instances = 100
    minutes, pipe = fig3.run(n=50, tau=TAU, cold_start=COLD,
                             max_instances=max_instances, **FLEET_KW)
    peak_avg = max(v for _, v in minutes)
    peak_inst = max(v for _, v in pipe.instance_series())
    assert peak_avg >= 45, f"should ramp to ~50 instances, got {peak_avg}"
    assert peak_inst <= max_instances, \
        f"instance count {peak_inst} exceeded max_instances"
    assert minutes[-1][1] == 0, "fleet should scale back to zero"
    return {
        "n_slides": 50, "max_instances": max_instances,
        "minutes": [[m, v] for m, v in minutes],
        "peak_avg_instances": peak_avg,
        "peak_instantaneous": peak_inst,
        "decays_to_zero": minutes[-1][1] == 0,
        "cold_starts": pipe.service.cold_starts,
    }


# ------------------------------------------------------- sharded store
def _hash_balance(n_shards: int = 4, n_uids: int = 2000) -> dict:
    from repro.wsi.store_service import ShardedDicomStore

    counts = [0] * n_shards
    for i in range(n_uids):
        uid = "2.25." + str(int(
            hashlib.sha256(f"study-{i}".encode()).hexdigest()[:24], 16))
        counts[ShardedDicomStore.shard_index_for_uid(uid, n_shards)] += 1
    lo, hi = min(counts), max(counts)
    assert hi <= 2 * lo, f"shard hash badly skewed: {counts}"
    return {"n_shards": n_shards, "n_uids": n_uids, "counts": counts,
            "max_over_min": round(hi / lo, 3)}


# -------------------------------------------------- fault-injection gauntlet
def _fault_gauntlet(n_slides: int, hw: int) -> dict:
    from repro.wsi import SyntheticScanner
    from repro.wsi.formats import sniff

    scanner = SyntheticScanner(seed=11)
    slides = {f"scans/s{i}.psv": scanner.scan(hw, hw, 256)
              for i in range(n_slides)}
    tenants = ("lab-a", "lab-b")
    meta = {k: {"slide_id": k, "tenant": tenants[i % 2]}
            for i, k in enumerate(slides)}
    convert = _pinned_convert()

    # serial baseline: plain function calls, no infrastructure, identical
    # metadata shape to what the pipeline's worker passes
    baseline = {}
    for k, d in slides.items():
        m = dict(meta[k])
        m.setdefault("format", sniff(d))
        baseline[k] = convert(d, m)

    faults = (DeliveryFaults()
              .drop("s0", attempts=(1,))          # lost push → redelivery
              .duplicate("s1", lag=1.0)           # double push → dedupe
              .delay("s2", by=200.0))             # arrives after deadline
    sched = SimScheduler()
    # traced on the sim clock: every slide's journey (faults, kill, shards
    # included) must land as one connected span tree
    with tracing.capture(now=sched.now) as tracer:
        pipe = ConversionPipeline(
            sched, convert=convert, cold_start=COLD, max_instances=4,
            ack_deadline=120.0, min_backoff=5.0,
            fleet=dict(instance_queue_depth=2), ordered_ingest=True,
            store_shards=4, delivery_faults=faults)
        for k, d in slides.items():
            pipe.ingest(k, d, meta[k])
        sched.schedule(5.0, pipe.service.kill_instance)  # churn mid-backlog
        sched.run()

    # --- zero lost, zero double-converted, nothing dead-lettered ---------
    assert pipe.dead_lettered == [], \
        f"work dead-lettered under faults: {pipe.dead_lettered}"
    out_keys = pipe.dicom.list()
    assert len(out_keys) == n_slides, \
        f"{len(out_keys)} studies for {n_slides} slides"
    writes = int(pipe.metrics.get("bucket.dicom-store.writes"))
    assert writes == n_slides, \
        f"{writes} study-tar writes for {n_slides} slides (double convert?)"

    # --- byte-identical to the serial baseline --------------------------
    from repro.core.pipeline import derive_out_key
    for k in slides:
        got = pipe.dicom.get(derive_out_key(k)).data
        assert got == baseline[k], f"fleet study tar differs for {k}"

    # --- the faults and the kill actually fired -------------------------
    assert faults.injected["drop"] >= 1 and faults.injected["duplicate"] >= 1 \
        and faults.injected["delay"] >= 1, dict(faults.injected)
    assert int(pipe.metrics.get("svc.wsi2dcm.killed")) == 1

    # --- one connected span tree per slide; attribution sums to the
    # --- trace window (the dashboard's 5% acceptance gate) --------------
    report = dashboard.build_report(pipe.metrics, tracer,
                                    title="fault gauntlet")
    assert len(report["traces"]) == n_slides, \
        f"{len(report['traces'])} traces for {n_slides} slides"
    for t in report["traces"]:
        assert not t["problems"], \
            f"trace {t['trace_id']} ({t['slide']}): {t['problems']}"
        total = sum(t["attribution"].values())
        assert abs(total - t["duration"]) <= 0.05 * max(t["duration"], 1e-9), \
            f"attribution {total} vs duration {t['duration']} for {t['slide']}"

    # --- crash a populated shard; rebuild serves identical QIDO/WADO ----
    ss = pipe.store_service
    studies = ss.search_studies()
    assert len(studies) == n_slides
    dist_before = ss.shard_distribution()
    uid = studies[0]
    shard_i = ss.shard_index_for(uid)
    qido_before = ss.search_instances(uid)
    wado_before = {m["sop_instance_uid"]: ss.retrieve(m["sop_instance_uid"])
                   for m in qido_before}
    ss.crash_shard(shard_i)
    assert ss.search_instances(uid) == [], \
        "crash_shard left index state behind"
    rebuilt = ss.rebuild_index()
    assert ss.search_instances(uid) == qido_before, \
        "post-rebuild QIDO differs"
    for sop, blob in wado_before.items():
        assert ss.retrieve(sop) == blob, f"post-rebuild WADO differs: {sop}"

    return {
        "n_slides": n_slides, "slide_hw": hw, "n_shards": 4,
        "faults_injected": dict(faults.injected),
        "instance_killed": True,
        "dead_lettered": 0,
        "study_tar_writes": writes,
        "byte_identical_to_serial": True,
        "shard_distribution": dist_before,
        "crashed_shard": shard_i,
        "rebuilt_instances": rebuilt,
        "crash_rebuild_identical": True,
        "deliveries": int(
            pipe.metrics.get("sub.wsi2dcm-push.deliveries")),
        "duplicates_deduped": int(
            pipe.metrics.get("svc.wsi2dcm.duplicates")),
        "completion_s": sched.now(),
        # the single dashboard, embedded: per-slide critical path + the
        # delivery-latency histogram percentiles
        "dashboard": {
            "traces": report["traces"],
            "histograms": report["histograms"],
        },
    }


# --------------------------------------------------------- lockdep overhead
def _lockdep_workload(n: int):
    """One lock-heavy fleet run: every ingest crosses the bucket, topic,
    subscription, fleet, and metrics locks several times."""
    sched = SimScheduler()
    pipe = ConversionPipeline(
        sched, service_time=TAU, cold_start=COLD, max_instances=8,
        min_backoff=5.0, fleet={}, ordered_ingest=True, subscribers=False)
    for i in range(n):
        pipe.ingest(f"bench/s{i:03d}.psv", bytes([i % 251]) * 32)
    sched.run()
    assert pipe.done_count() == n


def _lockdep_overhead_section(fast: bool) -> dict:
    import gc

    n, repeats = (120, 15) if fast else (200, 15)
    _lockdep_workload(n)  # warm-up: imports, bytecode, allocator

    def disarmed_run():
        _lockdep_workload(n)

    def bare_run():
        # bare baseline: every TrackedLock operation delegates straight to
        # the wrapped threading lock, skipping even the disarmed detector
        # check — what the tree would cost had the locks never been swapped
        TL = lockdep.TrackedLock
        orig = (TL.acquire, TL.release)
        TL.acquire = lambda self, blocking=True, timeout=-1: \
            self._lock.acquire(blocking, timeout)
        TL.release = lambda self: self._lock.release()
        try:
            _lockdep_workload(n)
        finally:
            TL.acquire, TL.release = orig

    def armed_run():
        with lockdep.capture(max_hold=30.0) as det:
            _lockdep_workload(n)
        assert det.violations == [], det.report()

    assert lockdep.current() is None, \
        "overhead baseline needs the disarmed fast path"
    # interleave the three variants so drift (thermal, scheduler, GC)
    # lands on all of them equally, then compare PAIRED per-round ratios:
    # each round times bare/disarmed/armed back-to-back, so slow spells
    # hit all three and cancel out of the ratio; the median round is the
    # gated statistic (robust to the odd descheduled round)
    times = {"bare": [], "disarmed": [], "armed": []}
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(repeats):
            for label, run in (("bare", bare_run),
                               ("disarmed", disarmed_run),
                               ("armed", armed_run)):
                gc.collect()
                t0 = time.perf_counter()
                run()
                times[label].append(time.perf_counter() - t0)
    finally:
        if gc_was_enabled:
            gc.enable()

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    bare = min(times["bare"])
    disarmed = min(times["disarmed"])
    armed = min(times["armed"])
    ratio = median(d / b for d, b in zip(times["disarmed"], times["bare"]))
    armed_ratio = median(a / b for a, b in zip(times["armed"],
                                               times["bare"]))
    assert ratio < 1.10, \
        f"disarmed lockdep overhead {ratio:.3f}x exceeds the 10% gate " \
        f"(bare {bare:.4f}s, disarmed {disarmed:.4f}s)"
    return {"n_slides": n, "repeats": repeats, "bare_s": round(bare, 4),
            "disarmed_s": round(disarmed, 4), "armed_s": round(armed, 4),
            "overhead_ratio": round(ratio, 4), "gate": 1.10,
            "armed_ratio": round(armed_ratio, 4)}


# --------------------------------------------------------- racedep overhead
def _racedep_overhead_section(fast: bool) -> dict:
    """Disarmed racedep instrumentation (Shared proxies on the spine's
    tracked structures, no detector armed) must cost <10% over an
    uninstrumented pipeline. Same paired-median methodology as the lockdep
    gate: bare (instrumentation kill-switch, raw containers), disarmed
    (proxies, one global read per access), armed (full vector-clock
    checking — diagnostic only)."""
    import gc

    n, repeats = (120, 15) if fast else (200, 15)
    _lockdep_workload(n)  # warm-up: imports, bytecode, allocator

    def bare_run():
        # uninstrumented baseline: objects constructed with instrumentation
        # off carry raw dicts/deques/lists — zero proxy indirection
        prev = racedep.set_instrumentation(False)
        try:
            _lockdep_workload(n)
        finally:
            racedep.set_instrumentation(prev)

    def disarmed_run():
        _lockdep_workload(n)

    def armed_run():
        with racedep.capture() as det:
            _lockdep_workload(n)
        assert det.violations == [], det.report()

    assert racedep.current() is None, \
        "overhead baseline needs the disarmed fast path"
    times = {"bare": [], "disarmed": [], "armed": []}
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(repeats):
            for label, run in (("bare", bare_run),
                               ("disarmed", disarmed_run),
                               ("armed", armed_run)):
                gc.collect()
                t0 = time.perf_counter()
                run()
                times[label].append(time.perf_counter() - t0)
    finally:
        if gc_was_enabled:
            gc.enable()

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    bare = min(times["bare"])
    disarmed = min(times["disarmed"])
    armed = min(times["armed"])
    ratio = median(d / b for d, b in zip(times["disarmed"], times["bare"]))
    armed_ratio = median(a / b for a, b in zip(times["armed"],
                                               times["bare"]))
    assert ratio < 1.10, \
        f"disarmed racedep overhead {ratio:.3f}x exceeds the 10% gate " \
        f"(bare {bare:.4f}s, disarmed {disarmed:.4f}s)"
    return {"n_slides": n, "repeats": repeats, "bare_s": round(bare, 4),
            "disarmed_s": round(disarmed, 4), "armed_s": round(armed, 4),
            "overhead_ratio": round(ratio, 4), "gate": 1.10,
            "armed_ratio": round(armed_ratio, 4)}


# --------------------------------------------------------- tracing overhead
def _tracing_overhead_section(fast: bool) -> dict:
    """Disarmed tracing (every instrumentation point bails on one
    module-global read) must cost <10% over a spine with the trace points
    compiled out. Same paired-median methodology as the lockdep/racedep
    gates: bare (tracing entry points monkeypatched to no-ops — what the
    spine would cost had it never been instrumented), disarmed (the
    shipped fast path), armed (full span capture — diagnostic only)."""
    import gc

    n, repeats = (120, 15) if fast else (200, 15)
    _lockdep_workload(n)  # warm-up: imports, bytecode, allocator

    def bare_run():
        t = tracing
        orig = (t.start_span, t.end_span, t.add_event, t.inject,
                t.extract, t.use_span, t.span, t.current_span)
        t.start_span = lambda name, **kw: None
        t.end_span = lambda sp, **kw: None
        t.add_event = lambda sp, name, **kw: None
        t.inject = lambda attributes, sp=None: None
        t.extract = lambda attributes: None
        t.use_span = lambda sp: t._NULL
        t.span = lambda name, **kw: t._NULL
        t.current_span = lambda: None
        try:
            _lockdep_workload(n)
        finally:
            (t.start_span, t.end_span, t.add_event, t.inject,
             t.extract, t.use_span, t.span, t.current_span) = orig

    def disarmed_run():
        _lockdep_workload(n)

    def armed_run():
        with tracing.capture() as tracer:
            _lockdep_workload(n)
        assert tracer.spans, "armed run recorded no spans"

    assert tracing.current() is None, \
        "overhead baseline needs the disarmed fast path"
    times = {"bare": [], "disarmed": [], "armed": []}
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(repeats):
            for label, run in (("bare", bare_run),
                               ("disarmed", disarmed_run),
                               ("armed", armed_run)):
                gc.collect()
                t0 = time.perf_counter()
                run()
                times[label].append(time.perf_counter() - t0)
    finally:
        if gc_was_enabled:
            gc.enable()

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    bare = min(times["bare"])
    disarmed = min(times["disarmed"])
    armed = min(times["armed"])
    ratio = median(d / b for d, b in zip(times["disarmed"], times["bare"]))
    armed_ratio = median(a / b for a, b in zip(times["armed"],
                                               times["bare"]))
    assert ratio < 1.10, \
        f"disarmed tracing overhead {ratio:.3f}x exceeds the 10% gate " \
        f"(bare {bare:.4f}s, disarmed {disarmed:.4f}s)"
    return {"n_slides": n, "repeats": repeats, "bare_s": round(bare, 4),
            "disarmed_s": round(disarmed, 4), "armed_s": round(armed, 4),
            "overhead_ratio": round(ratio, 4), "gate": 1.10,
            "armed_ratio": round(armed_ratio, 4)}


# ------------------------------------------------------------- backpressure
def _backpressure_section() -> dict:
    sched = SimScheduler()
    pipe = ConversionPipeline(
        sched, service_time=TAU, cold_start=COLD, max_instances=2,
        min_backoff=5.0, fleet=dict(shed_backlog=4), ordered_ingest=True,
        subscribers=False)
    n = 12
    for i in range(n):
        pipe.ingest(f"burst/s{i:02d}.psv", bytes([i]) * 32)
    sched.run()
    shed = int(pipe.metrics.get("svc.wsi2dcm.shed"))
    requeues = int(pipe.metrics.get("sub.wsi2dcm-push.requeues"))
    assert pipe.done_count() == n, \
        f"only {pipe.done_count()}/{n} completed under backpressure"
    assert shed > 0, "overload never shed"
    assert requeues >= shed, "sheds were not budget-exempt requeues"
    assert pipe.dead_lettered == [], "shed work dead-lettered"
    return {"n_slides": n, "max_instances": 2, "shed_backlog": 4,
            "shed": shed, "budget_exempt_requeues": requeues,
            "dead_lettered": 0, "completed": pipe.done_count(),
            "completion_s": sched.now()}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller gauntlet, no wall-clock "
                         "calibration, same assertions")
    args = ap.parse_args(argv)
    enable_compile_cache()

    result = {
        "config": {"tau_s": TAU, "cold_start_s": COLD,
                   "batches": list(FLEET_BATCHES), "fast": args.fast},
        "fig2": _fig2_section(calibrate=not args.fast),
        "fig3": _fig3_section(),
        "sharded_store": _hash_balance(),
        "lockdep_overhead": _lockdep_overhead_section(fast=args.fast),
        "racedep_overhead": _racedep_overhead_section(fast=args.fast),
        "tracing_overhead": _tracing_overhead_section(fast=args.fast),
        "fault_injection": _fault_gauntlet(
            n_slides=3 if args.fast else 6, hw=256),
        "backpressure": _backpressure_section(),
    }
    with open("BENCH_fleet.json", "w") as f:
        json.dump(result, f, indent=2)

    print("workflow,n_images,seconds")
    for r in result["fig2"]["rows"]:
        print(f"{r['workflow']},{r['n']},{r['seconds']}")
    print("# claims: fleet loses at n=1 (cold start), wins at n>=10 — OK")
    print("minute,avg_instances")
    for m, v in result["fig3"]["minutes"]:
        print(f"{m},{v}")
    fi = result["fault_injection"]
    print(f"faults,{sum(fi['faults_injected'].values())},"
          f"{fi['faults_injected']} + 1 instance kill + 1 shard crash")
    print(f"gauntlet,ok,{fi['n_slides']} slides byte-identical to serial, "
          f"0 lost, 0 double-converted, 0 dead-lettered")
    bp = result["backpressure"]
    print(f"backpressure,ok,{bp['shed']} sheds / "
          f"{bp['budget_exempt_requeues']} requeues, 0 dead-lettered, "
          f"{bp['completed']}/{bp['n_slides']} completed")
    lo = result["lockdep_overhead"]
    print(f"lockdep_overhead,ok,{lo['overhead_ratio']}x disarmed vs bare "
          f"(gate {lo['gate']}x; armed diagnostic {lo['armed_ratio']}x)")
    ro = result["racedep_overhead"]
    print(f"racedep_overhead,ok,{ro['overhead_ratio']}x disarmed vs bare "
          f"(gate {ro['gate']}x; armed diagnostic {ro['armed_ratio']}x)")
    to = result["tracing_overhead"]
    print(f"tracing_overhead,ok,{to['overhead_ratio']}x disarmed vs bare "
          f"(gate {to['gate']}x; armed diagnostic {to['armed_ratio']}x)")
    for t in fi["dashboard"]["traces"]:
        a = t["attribution"]
        print(f"trace,{t['slide']},total={t['duration']:.1f}s,"
              f"queue={a['queue']:.1f}s,compute={a['compute']:.1f}s,"
              f"store={a['store']:.1f}s,spans={t['n_spans']}")
    print("wrote BENCH_fleet.json")


if __name__ == "__main__":
    main()
