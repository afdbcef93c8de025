"""Ingest of JPEG SVS: the ``ingest`` client's deployment, closed loop,
event hooks, crediting and drain, with slides from the stand-in scanner
that writes JPEG tiles (``scanner_jpeg``) and a check of what the
transcoding owes them.

The check: every slide of the window complete in QIDO/WADO; sampled
level-0 frames carry the scanner's tile scans unchanged, and the tables of
``JPEGTables``; sampled frames of levels >= 1 hold the quantised
coefficients of the float64 reference chain (the scanner's own
coefficients decoded, the pyramid, the forward transform), a coefficient
left out where the stated float32 precision cannot decide a rounding on
the way (``reference_svs``).
"""
from __future__ import annotations

import struct
import time

import numpy as np

import reference
import reference_svs
import scanner_jpeg
import traffic
from clients import ingest
from clients.ingest import Check, RENDER_THREADS, WARM_LIMIT_S


def render_pool(mix: dict, seed: int, tile: int, threads: int
                ) -> dict[int, list[tuple[dict, bytes]]]:
    """Per side, the pool of distinct slides: (scanner record, SVS bytes)."""
    return {side: [scanner_jpeg.scan(side, side, tile, s, threads=threads)
                   for s in seeds]
            for side, seeds in traffic.scanner_seeds(mix, seed).items()}


class Client(ingest.Client):
    """Closed-loop ingest of JPEG SVS slides."""

    def setup(self) -> dict[str, float]:
        from repro.core.pubsub import Subscription

        t = time.monotonic()
        self.pool = render_pool(self.mix, self.seed, self.cfg["tile"],
                                RENDER_THREADS)
        split = {"render_s": time.monotonic() - t}
        t = time.monotonic()
        self.sched, self.pipe = ingest.build_pipeline(self.cfg)
        self.store = self.pipe.store_service
        Subscription(self.store.topic, "bench-qido", self._on_stored)
        Subscription(self.pipe.dlq, "bench-dlq", self._on_dead)
        warm = [self._land(side, k, in_window=False)
                for side, k in self._warm_slides()]
        self._wait(lambda: all(s.t_done or s.failed for s in warm),
                   WARM_LIMIT_S)
        bad = [s for s in warm if not s.t_done]
        if bad:
            raise RuntimeError(f"warm-up slide failed: {bad[0].failed}")
        n = sum(s.levels for s in warm)
        pipe = self.pipe
        if not self._wait(lambda: len(pipe.validator.checked) >= n and
                          len(pipe.ml_subscriber.predictions) >= n,
                          WARM_LIMIT_S):
            raise RuntimeError("the subscribers did not finish the warm-up "
                               "instances")
        split["warm_s"] = time.monotonic() - t
        return split

    def check(self, seed: int, control: bool = False) -> list[Check]:
        """QIDO/WADO completeness of every slide of the window; on a seeded
        sample of the finished ones, level 0's scans and tables against
        the scanner's, and the coefficients of levels >= 1 against the
        reference. With ``control`` the reference computed with every
        matrix product one precision step lower stands in for the stored
        coefficients of levels >= 1, through the same share and limit."""
        tile, min_side = self.cfg["tile"], self.cfg["min_level_size"]
        done = [s for s in self.slides if s.in_window and s.t_done]
        missing = sum(s.levels for s in self.slides
                      if s.in_window and not s.t_done)
        for s in done:
            metas = self.store.search_instances(s.study)
            dims = reference.level_dims(s.side, min_side)
            if len(metas) != len(dims):
                missing += abs(len(dims) - len(metas))
                continue
            for meta, d in zip(metas, dims):
                n = self.store.frame_index(meta["sop_instance_uid"]).n_frames
                if n != (d // tile) ** 2 or meta["total_rows"] != d:
                    missing += 1
        g = traffic.rng(seed, 4)
        spec = self.mix["check"]
        k = spec["frames_per_level"]
        pick = g.choice(len(done), min(len(done), spec["slides"]),
                        replace=False) if done else []
        stats = {"compared": 0, "differ": 0, "undecodable": 0, "tiles": 0,
                 "left_out": 0, "level0_frames": 0, "control": control}
        scan_bad = 0
        refs: dict[tuple[int, int], tuple] = {}
        for i in sorted(pick):
            s = done[int(i)]
            record = self.pool[s.side][s.pool][0]
            key = (s.side, s.pool)
            if key not in refs:
                refs[key] = _reference(record, s.levels, control)
            levels, amb, lower = refs[key]
            metas = self.store.search_instances(s.study)
            scan_bad += _compare_level0(self.store, metas[0], record, k, g,
                                        stats)
            for li in range(1, len(metas)):
                _compare_level(self.store, metas[li], levels[li], li, amb,
                               tile, k, g, stats,
                               lower[li] if lower else None)
        share = (stats["differ"] + stats["undecodable"] * 3 * tile ** 2) \
            / max(1, stats["compared"])
        return [Check("missing_levels_or_frames", missing, 0,
                      {"slides": len(done)}),
                Check("level0_scan_mismatch", scan_bad if done else 1, 0,
                      {"frames": stats["level0_frames"]}),
                Check("coef_mismatch_share", share if done else 1.0,
                      self.cfg["limits"]["coef_mismatch_share"], stats)]


def _reference(record: dict, n_levels: int, control: bool):
    """The reference pyramid of a pool slide, its level-0 samples the band
    leaves out, and with ``control`` the pyramid of the decode with every
    matrix product in three bfloat16 passes."""
    img, amb = reference_svs.scanner_decode(
        record, scanner_jpeg.Q_LUMA, scanner_jpeg.Q_CHROMA)
    lower = None
    if control:
        low, _ = reference_svs.scanner_decode(
            record, scanner_jpeg.Q_LUMA, scanner_jpeg.Q_CHROMA,
            reference.matmul_bf16x3)
        lower = reference.pyramid(low, n_levels)
    return reference.pyramid(img, n_levels), amb, lower


def _compare_level0(store, meta: dict, record: dict, k: int,
                    g: np.random.Generator, stats: dict) -> int:
    """Sampled level-0 frames whose entropy-coded data differs from the
    scanner tile's, or whose tables differ from ``JPEGTables``."""
    sop = meta["sop_instance_uid"]
    tiles = record["tiles"]
    tables = reference_svs.segments(record["tables"])[0]
    bad = 0
    for i in sorted(g.choice(len(tiles), min(len(tiles), k), replace=False)):
        stats["level0_frames"] += 1
        try:
            segs, scan = reference_svs.segments(
                store.retrieve_frame(sop, int(i)))
        except (ValueError, KeyError, IndexError, struct.error):
            bad += 1
            continue
        have = [seg for seg in segs if seg[0] in (0xDB, 0xC4)]
        if scan != reference_svs.segments(tiles[int(i)])[1] or have != tables:
            bad += 1
    return bad


def _compare_level(store, meta: dict, level: np.ndarray, li: int,
                   amb: np.ndarray, tile: int, k: int,
                   g: np.random.Generator, stats: dict,
                   lower: np.ndarray | None) -> None:
    """Sampled frames of level ``li`` >= 1 against the reference; with
    ``lower`` (the control's level) its bfloat16 x3 forward transform
    stands in for the stored frame."""
    sop = meta["sop_instance_uid"]
    per_row = level.shape[1] // tile
    n = per_row * (level.shape[0] // tile)
    foot = reference_svs.footprint(amb, li)
    nb = tile // 8
    for i in sorted(g.choice(n, min(n, k), replace=False)):
        r, c = divmod(int(i), per_row)
        pix = level[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile]
        want, band = reference.forward(pix)
        cover = foot[r * nb:(r + 1) * nb, c * nb:(c + 1) * nb]
        keep = ~band & ~np.repeat(np.repeat(cover, 8, 0), 8, 1)[None]
        stats["tiles"] += 1
        stats["compared"] += int(keep.sum())
        stats["left_out"] += int((~keep).sum())
        if lower is not None:
            got, _ = reference.forward(
                lower[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile],
                reference.matmul_bf16x3)
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
