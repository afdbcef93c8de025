"""Ingest traffic: slides land in the landing bucket, the deployment's
event spine converts them, and a slide is done when QIDO lists every level
of its study.

The deployment is stood up the way the service runs: landing bucket ->
OBJECT_FINALIZE -> pub/sub -> autoscaling converter service (the
configuration's number of converter threads) -> DICOM bucket -> store
ingest subscription -> DICOM store -> the validation and ML-inference
subscribers. The benchmark lands slides in a closed loop, subscribes to the
store's ``dicom-instance-stored`` topic like any downstream consumer, and
on each stored instance asks QIDO whether the study is complete; that
moment, on the host clock, ends the slide.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

import reference
import scanner
import traffic

#: pool threads of the real scheduler: every subscription delivery and every
#: conversion runs on one, so there are more than the deployment ever uses
SCHEDULER_WORKERS = 32
#: threads that render and Deflate the seed's slides in set-up
RENDER_THREADS = 8
#: how long set-up waits for its warm-up conversions
WARM_LIMIT_S = 900.0


def window_share(a: float, b: float, t0: float, t1: float) -> float:
    """The share of the interval [a, b] that lies inside [t0, t1]."""
    if b <= a:
        return 1.0 if t0 <= b <= t1 else 0.0
    return max(0.0, min(b, t1) - max(a, t0)) / (b - a)


@dataclass
class Slide:
    key: str
    side: int
    pool: int  # which pool slide of this side
    levels: int
    t_put: float = 0.0
    t_done: float | None = None
    failed: str | None = None
    study: str | None = None
    in_window: bool = True

    @property
    def mpx(self) -> float:
        return self.side * self.side / 1e6

    def mpx_in(self, t0: float, t1: float) -> float:
        """Megapixels credited to [t0, t1]: a finished slide's level 0 in
        the share of its landing-to-QIDO interval that lies inside."""
        if not self.in_window or self.t_done is None:
            return 0.0
        return self.mpx * window_share(self.t_put, self.t_done, t0, t1)


@dataclass
class Check:
    """One compared number beside its limit."""
    name: str
    value: float
    limit: float
    detail: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def render_pool(mix: dict, seed: int, tile: int, level: int,
                threads: int) -> dict[int, list[tuple[np.ndarray, bytes]]]:
    """Per side, the pool of distinct slides: (pixels, TIFF bytes)."""
    return {side: [scanner.scan(side, side, tile, s, level=level,
                                threads=threads) for s in seeds]
            for side, seeds in traffic.scanner_seeds(mix, seed).items()}


def build_pipeline(cfg: dict):
    """The deployment the configuration describes, on real threads."""
    from repro.core import ConversionPipeline, RealScheduler
    from repro.wsi import ConvertOptions, convert_wsi_to_dicom

    def convert(data: bytes, meta: dict) -> bytes:
        return convert_wsi_to_dicom(data, meta, options=ConvertOptions(
            min_level_size=cfg["min_level_size"]))

    sched = RealScheduler(workers=SCHEDULER_WORKERS)
    pipe = ConversionPipeline(
        sched, convert=convert, max_instances=cfg["converter_threads"],
        concurrency=1, cold_start=0.0, scale_down_delay=3600.0,
        ack_deadline=3600.0, max_delivery_attempts=1)
    return sched, pipe


class Client:
    """Closed-loop ingest of the mix's slides: ``in_flight`` slides
    outstanding, the next landing as soon as one is done."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed, self.seconds = cfg, mix, seed, seconds
        self.slides: list[Slide] = []
        self._by_out: dict[str, Slide] = {}
        self._cond = threading.Condition()
        self._n = 0

    # ------------------------------------------------------------ set-up
    def setup(self) -> dict[str, float]:
        from repro.core.pubsub import Subscription

        t = time.monotonic()
        self.pool = render_pool(self.mix, self.seed, self.cfg["tile"],
                                self.cfg["deflate_level"], RENDER_THREADS)
        split = {"render_s": time.monotonic() - t}
        t = time.monotonic()
        self.sched, self.pipe = build_pipeline(self.cfg)
        self.store = self.pipe.store_service
        Subscription(self.store.topic, "bench-qido", self._on_stored)
        Subscription(self.pipe.dlq, "bench-dlq", self._on_dead)
        # every slide of the pool once, all at once: compiles (or loads
        # from the compile cache) and runs each program before the window,
        # the subscribers' frame decodes of each slide's content included
        warm = [self._land(side, k, in_window=False)
                for side, k in self._warm_slides()]
        self._wait(lambda: all(s.t_done or s.failed for s in warm),
                   WARM_LIMIT_S)
        bad = [s for s in warm if not s.t_done]
        if bad:
            raise RuntimeError(f"warm-up slide failed: {bad[0].failed}")
        # the window starts once the subscribers have seen every warm-up
        # instance, so none of their set-up work runs inside it
        n = sum(s.levels for s in warm)
        pipe = self.pipe
        if not self._wait(lambda: len(pipe.validator.checked) >= n and
                          len(pipe.ml_subscriber.predictions) >= n,
                          WARM_LIMIT_S):
            raise RuntimeError("the subscribers did not finish the warm-up "
                               "instances")
        split["warm_s"] = time.monotonic() - t
        return split

    def _warm_slides(self) -> list[tuple[int, int]]:
        """(side, pool index) of the slides converted in set-up: every
        slide of the pool, which are the only contents the window lands."""
        return [(side, k) for side, seeds in
                traffic.scanner_seeds(self.mix, self.seed).items()
                for k in range(len(seeds))]

    # ------------------------------------------------------- event hooks
    def _on_stored(self, msg, ctx) -> None:
        ctx.ack()
        meta = msg.data
        out_key = (meta.get("source") or "").rsplit("/", 1)[0]
        s = self._by_out.get(out_key)
        if s is None or s.t_done is not None:
            return
        if len(self.store.search_instances(meta["study_uid"])) >= s.levels:
            now = time.monotonic()
            with self._cond:
                if s.t_done is None:
                    s.t_done, s.study = now, meta["study_uid"]
                self._cond.notify_all()

    def _on_dead(self, msg, ctx) -> None:
        ctx.ack()
        key = msg.data.get("name")
        with self._cond:
            for s in self.slides:
                if s.key == key:
                    s.failed = msg.attributes.get("dlq_reason", "dead-lettered")
            self._cond.notify_all()

    def _land(self, side: int, pool: int, in_window: bool = True) -> Slide:
        self._n += 1
        key = f"landing/{self._n:05d}-{side}.svs"
        s = Slide(key, side, pool, len(reference.level_dims(
            side, self.cfg["min_level_size"])), in_window=in_window)
        with self._cond:
            self.slides.append(s)
            self._by_out[key[:-len(".svs")] + ".dcm"] = s
        s.t_put = time.monotonic()
        self.pipe.ingest(key, self.pool[side][pool][1], {"slide_id": key})
        return s

    def _wait(self, cond, limit: float) -> bool:
        deadline = time.monotonic() + limit
        with self._cond:
            while not cond():
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(min(left, 0.5))
        return True

    def _outstanding(self) -> int:
        return sum(1 for s in self.slides
                   if s.in_window and s.t_done is None and s.failed is None)

    # ------------------------------------------------------------ window
    def window(self) -> tuple[float, float]:
        """Run the mix for ``seconds``; returns the window (t0, t1)."""
        seq = traffic.sides(self.mix, self.seed)
        uses: dict[int, int] = {}

        def land() -> None:
            side = next(seq)
            k = uses.get(side, 0)
            uses[side] = k + 1
            self._land(side, k % int(self.mix["pool"]))

        t0 = time.monotonic()
        t1 = t0 + self.seconds
        n = int(self.mix["in_flight"])
        for _ in range(n):
            land()
        while True:
            self._wait(lambda: self._outstanding() < n
                       or time.monotonic() >= t1, t1 - time.monotonic())
            if time.monotonic() >= t1:
                break
            land()
        return t0, t1

    def drain(self) -> None:
        """Follow slides still in flight up to the mix's drain limit."""
        self._wait(lambda: self._outstanding() == 0, self.mix["drain_s"])
        with self._cond:
            for s in self.slides:
                if s.in_window and s.t_done is None and s.failed is None:
                    s.failed = f"not done {self.mix['drain_s']} s after the window"

    def close(self) -> None:
        if hasattr(self, "sched"):
            self.sched.shutdown()

    def mpx_in(self, t0: float, t1: float) -> float:
        """Level-0 megapixels converted in [t0, t1]: each finished slide
        credited with the share of its landing-to-QIDO interval inside."""
        return sum(s.mpx_in(t0, t1) for s in self.slides)

    def latencies(self, t0: float, t1: float) -> list[float]:
        """Landing-to-QIDO seconds of the slides landed in the window."""
        return [s.t_done - s.t_put for s in self.slides if s.in_window
                and s.t_done is not None and t0 <= s.t_put <= t1]

    def outcome(self, t0: float, t1: float) -> tuple[int, int]:
        """(slides landed in the window, slides that failed)."""
        ws = [s for s in self.slides if s.in_window]
        return len(ws), sum(1 for s in ws if s.failed)

    # ------------------------------------------------------ correctness
    def check(self, seed: int, control: bool = False) -> list[Check]:
        """QIDO/WADO completeness of every slide of the window (a slide that
        never finished misses all its levels), and the stored coefficients
        of a seeded sample of the finished ones against the reference.

        With ``control`` the reference computed one precision step lower
        stands in for the stored coefficients, through the same share and
        limit: the comparison has to find it not correct.
        """
        done = [s for s in self.slides if s.in_window and s.t_done]
        missing = sum(s.levels for s in self.slides
                      if s.in_window and not s.t_done)
        for s in done:
            metas = self.store.search_instances(s.study)
            dims = reference.level_dims(s.side, self.cfg["min_level_size"])
            tile = self.cfg["tile"]
            if len(metas) != len(dims):
                missing += abs(len(dims) - len(metas))
                continue
            for meta, d in zip(metas, dims):
                n = self.store.frame_index(meta["sop_instance_uid"]).n_frames
                if n != (d // tile) ** 2 or meta["total_rows"] != d:
                    missing += 1
        g = traffic.rng(seed, 4)
        spec = self.mix["check"]
        pick = g.choice(len(done), min(len(done), spec["slides"]),
                        replace=False) if done else []
        stats = {"compared": 0, "differ": 0, "undecodable": 0, "tiles": 0,
                 "control": control}
        pyramids: dict[tuple[int, int], list[np.ndarray]] = {}
        for i in sorted(pick):
            s = done[int(i)]
            key = (s.side, s.pool)
            if key not in pyramids:
                pyramids[key] = reference.pyramid(self.pool[s.side][s.pool][0],
                                                  s.levels)
            metas = self.store.search_instances(s.study)
            for meta, level in zip(metas, pyramids[key]):
                _compare_level(self.store, meta, level, self.cfg["tile"],
                               spec["frames_per_level"], g, stats, control)
        share = (stats["differ"] + stats["undecodable"] * 3 * self.cfg["tile"]
                 ** 2) / max(1, stats["compared"])
        return [Check("missing_levels_or_frames", missing, 0,
                      {"slides": len(done)}),
                Check("coef_mismatch_share", share if done else 1.0,
                      self.cfg["limits"]["coef_mismatch_share"], stats)]


def _compare_level(store, meta: dict, level: np.ndarray, tile: int,
                   k: int, g: np.random.Generator, stats: dict,
                   control: bool) -> None:
    sop = meta["sop_instance_uid"]
    per_row = level.shape[1] // tile
    n = per_row * (level.shape[0] // tile)
    for i in sorted(g.choice(n, min(n, k), replace=False)):
        r, c = divmod(int(i), per_row)
        pix = level[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile]
        want, amb = reference.forward(pix)
        keep = ~amb
        stats["tiles"] += 1
        stats["compared"] += int(keep.sum())
        if control:
            got, _ = reference.forward(pix, reference.matmul_bf16x3)
        else:
            try:
                got = reference.decode_coefficients(
                    store.retrieve_frame(sop, int(i)))
            except (ValueError, KeyError, IndexError):
                stats["undecodable"] += 1
                continue
        if got.shape != want.shape:
            stats["undecodable"] += 1
            continue
        stats["differ"] += int(((got != want) & keep).sum())
