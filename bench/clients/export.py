"""Export traffic: exporter threads re-export stored studies as tiled-TIFF
pyramids (dicom2tiff) in a closed loop.

Set-up converts the mix's studies through the ingest path of the same
deployment and exports each once. In the window each exporter calls
``ExportService.export_study(uid, skip_unchanged=False)`` on the next
study of a seeded cycle as soon as its last one returns. The service
exports a study's levels one after another; a level is done when its TIFF
lands in the derived bucket, and it began when the level before it (or the
call) ended.
"""
from __future__ import annotations

import threading
import time

import reference
import traffic
from clients.ingest import Check, Client as IngestClient, window_share


def level_of(key: str) -> int:
    """Pyramid level of a derived-bucket key (``<uid>/level_<n>.tiff``)."""
    return int(key.rsplit("level_", 1)[1].split(".")[0])


class Client(IngestClient):

    def setup(self) -> dict[str, float]:
        split = super().setup()  # renders the pool and converts all of it
        self.studies = {s.study: s.side for s in self.slides}  # uid -> side
        #: (level start, TIFF put, study uid, key, level-0 side)
        self.exports: list[tuple[float, float, str, str, int]] = []
        self._lock = threading.Lock()
        self._began = threading.local()
        self._put = self.pipe.derived.put
        self.pipe.derived.put = self._timed_put
        t = time.monotonic()
        self._loop(time.monotonic() + 1e9, list(self.studies), warm=True)
        split["warm_export_s"] = time.monotonic() - t
        with self._lock:
            self.exports.clear()
        return split

    def _timed_put(self, key, data, metadata=None, **kw):
        obj = self._put(key, data, metadata, **kw)
        now = time.monotonic()
        uid = (metadata or {}).get("study_uid")
        began = getattr(self._began, "t", now)
        self._began.t = now
        with self._lock:
            self.exports.append((began, now, uid, key,
                                 self.studies.get(uid, 0)))
        return obj

    def _loop(self, t1: float, order, warm: bool = False) -> None:
        svc = self.pipe.export_service
        errors: list[BaseException] = []
        it = iter(order)
        lock = threading.Lock()

        def worker() -> None:
            while time.monotonic() < t1:
                with lock:
                    uid = next(it, None)
                if uid is None:
                    return
                t = self._began.t = time.monotonic()
                try:
                    svc.export_study(uid, skip_unchanged=False)
                except Exception as exc:  # a failed export is a failed answer
                    errors.append(exc)
                    with self._lock:
                        self.failures.append(repr(exc))
                with self._lock:
                    self.calls.append((t, time.monotonic()))

        self.failures: list[str] = []
        self.calls: list[tuple[float, float]] = []  # export_study (start, end)
        threads = [threading.Thread(target=worker, name=f"exporter-{i}")
                   for i in range(int(self.mix["in_flight"]))]
        for th in threads:
            th.start()
        self._threads = threads
        if warm:
            for th in threads:
                th.join()
            if errors:
                raise RuntimeError(f"warm-up export failed: {errors[0]!r}")

    def window(self) -> tuple[float, float]:
        g = traffic.rng(self.seed, 5)

        def cycle():
            while True:
                order = list(self.studies)
                g.shuffle(order)
                yield from order

        t0 = time.monotonic()
        t1 = t0 + self.seconds
        self._loop(t1, cycle())
        time.sleep(max(0.0, t1 - time.monotonic()))
        return t0, t1

    def drain(self) -> None:
        deadline = time.monotonic() + self.mix["drain_s"]
        for th in self._threads:
            th.join(max(0.0, deadline - time.monotonic()))
        if any(th.is_alive() for th in self._threads):
            self.failures.append(
                f"an export was still running {self.mix['drain_s']} s after "
                "the window")

    def mpx_in(self, t0: float, t1: float) -> float:
        """Megapixels of levels exported in [t0, t1]: each level credited
        with the share of its start-to-put interval inside."""
        return sum((side >> level_of(key)) ** 2 / 1e6
                   * window_share(a, b, t0, t1)
                   for a, b, _, key, side in self.exports)

    def latencies(self, t0: float, t1: float) -> list[float]:
        """Seconds of the study exports started in the window."""
        return [b - a for a, b in self.calls if t0 <= a <= t1]

    def outcome(self, t0: float, t1: float) -> tuple[int, int]:
        """(study exports started in the window, exports that failed)."""
        return sum(1 for a, _ in self.calls if t0 <= a <= t1), \
            len(self.failures)

    # ------------------------------------------------------ correctness
    def check(self, seed: int, control: bool = False) -> list[Check]:
        """Exported TIFF pixels of a seeded sample of the levels exported in
        the window against the reference decode of the stored frames.

        With ``control`` the reference inverse computed one precision step
        lower stands in for the exported pixels, through the same share and
        limit: the comparison has to find it not correct.
        """
        tile = self.cfg["tile"]
        done = sorted({(uid, key, side) for _, _, uid, key, side
                       in self.exports})
        g = traffic.rng(seed, 6)
        spec = self.mix["check"]
        pick = g.choice(len(done), min(len(done), spec["levels"]),
                        replace=False) if done else []
        stats = {"compared": 0, "differ": 0, "unreadable": 0, "tiles": 0,
                 "control": control}
        wrong_geometry = 0
        for j in sorted(pick):
            uid, key, side = done[int(j)]
            li = level_of(key)
            d = reference.level_dims(side, self.cfg["min_level_size"])[li]
            meta = self.store.search_instances(uid)[li]
            tif = self.pipe.derived.get(key).data
            n = (d // tile) ** 2
            for i in sorted(g.choice(n, min(n, spec["frames_per_level"]),
                                     replace=False)):
                stats["tiles"] += 1
                coef = reference.decode_coefficients(
                    self.store.retrieve_frame(meta["sop_instance_uid"], int(i)))
                want, amb = reference.inverse(coef)
                keep = ~amb
                stats["compared"] += int(keep.sum())
                try:
                    got, hw = reference.tiff_tile(tif, int(i))
                except (ValueError, KeyError, IndexError):
                    stats["unreadable"] += 1
                    continue
                if hw != (d, d):
                    wrong_geometry += 1
                if control:
                    got, _ = reference.inverse(coef, reference.matmul_bf16x3)
                stats["differ"] += int(((got != want) & keep).sum())
        share = (stats["differ"] + stats["unreadable"] * 3 * tile * tile) \
            / max(1, stats["compared"])
        return [Check("failed_exports_or_geometry",
                      len(self.failures) + wrong_geometry, 0,
                      {"levels": len(done)}),
                Check("pixel_mismatch_share", share if done else 1.0,
                      self.cfg["limits"]["pixel_mismatch_share"], stats)]
