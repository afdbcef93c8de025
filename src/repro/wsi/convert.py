"""The converter: any registered slide container → multi-level DICOM WSM study.

Per slide: sniff the container (``repro.wsi.formats.open_slide`` — PSV,
tiled TIFF/SVS, or any registered format), stream tiles through the
``SlideReader`` protocol, build the multi-resolution pyramid with the
Pallas downsample kernel, transform-code every tile (Pallas DCT/quant +
host Huffman), wrap each level in a DICOM Part-10 instance (TILED_FULL),
and bundle the study as a tar archive. The converter consumes only the
reader protocol, so identical pixel content produces byte-identical study
tars regardless of the source container (given the same manifest UIDs) —
asserted across PSV vs tiled-TIFF in tests and the benchmark.

Three compute paths (see DESIGN.md, "Whole-level batched dispatch" and
"Kernel roofline & sharding"), all emitting **byte-identical** study tars:

- **pipelined/fused** (default): the device-resident engine. Level-0 tile
  rows are uploaded to the device as the reader inflates them (no full
  host ``(H, W, 3)`` array), then the **entire pyramid** — every level's
  ``jpeg_transform`` and the ``downsample2x2`` chain between levels — is
  one jitted dispatch (``donate_argnums`` retires the pixel buffer on
  accelerators). The host consumes per-level coefficients behind async
  fetches (``copy_to_host_async``), entropy-coding level N while the
  device is still transforming levels > N. Exactly one host→device upload
  and one dispatch per slide (counted by ``TRANSFER_STATS``, asserted in
  the conversion bench).
- **batched sync** (``ConvertOptions(pipelined=False)``): level 0 is
  uploaded once; every further level is produced by chaining
  ``downsample2x2`` on device, and all tiles of a level are transform-coded
  by a single fused ``jpeg_transform`` dispatch followed by the vectorized
  host entropy coder — but each level's host work completes before the next
  level's device work is enqueued. Kept as the A/B baseline for the
  pipelined path.
- **per-tile** (``ConvertOptions(batched=False)``): the original path — host
  pyramid, ``[encode_tile(f) for f in frames]`` with 4 dispatches per tile.
  Kept for A/B benchmarking.

**Determinism**: the study/series UIDs are minted once and stored in the
manifest (key ``"uids"``), and every level's SOP instance UID is derived
from the series UID + instance number. Two conversions of the same slide
that share a manifest (or whose manifests were seeded with the same
``"uids"`` entry) therefore produce byte-identical study tars — this is
what the pipelined-vs-sync A/B asserts on whole archives, and what makes
manifest resume reproduce a fresh conversion exactly.

**Crash/resume**: ``ConvertOptions.manifest`` is the single store of
finished-level DICOM bytes (level index → Part-10 bytes). A converter
restarted against the same manifest skips completed levels (this backs the
checkpoint/restart fault-tolerance tests — at-least-once delivery plus this
idempotent resume gives effectively-once conversion). The study tar is
assembled directly from the manifest, so finished-level bytes are stored
exactly once; call ``ConvertOptions.clear_manifest()`` to release them once
the study archive has been durably stored.

**Thread safety**: ``convert_wsi_to_dicom`` shares no mutable module state
(the entropy coder's caches are lock-protected), so the real-mode pipeline
runs up to ``concurrency`` conversions in parallel worker threads — the
transform dispatch, the numpy entropy coder, and zlib inflation all release
the GIL for their heavy regions.
"""
from __future__ import annotations

import io
import json
import tarfile
from contextlib import nullcontext
from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import tracing
from repro.kernels import downsample2x2, jpeg_transform, ops as kernel_ops
from repro.wsi.dicom import (TS_EXPLICIT_LE, TS_JPEG_BASELINE, new_uid,
                             write_part10)
from repro.wsi.formats import SlideReader, open_slide
from repro.wsi.jpeg import encode_coef_batch, encode_tile

__all__ = ["convert_wsi_to_dicom", "study_levels", "ConvertOptions"]


class ConvertOptions:
    """Converter knobs.

    min_level_size
        Stop the pyramid once the next level's short edge would fall below
        this (pixels). Levels smaller than one tile emit zero full frames.
    jpeg
        ``True`` → encapsulated JPEG baseline transfer syntax; ``False`` →
        native (uncompressed) explicit-VR-LE pixel data. The batched/
        pipelined device paths only apply to JPEG output; ``jpeg=False``
        always runs the host per-tile wrap.
    manifest
        Resume checkpoint *and* the only copy of finished-level bytes held
        by the converter: maps level index (str) to that level's Part-10
        bytes, plus the ``"uids"`` entry (JSON ``[study_uid, series_uid]``)
        minted on first use so a resumed — or deliberately re-seeded —
        conversion reproduces the original bytes exactly. The output tar is
        written from the manifest directly.
    batched
        ``True`` (default): device-resident pyramid, one fused transform
        dispatch per level, vectorized host entropy coder. ``False``: the
        original per-tile path (4 dispatches + Python Huffman loop per
        tile), kept for A/B benchmarking.
    pipelined
        ``True`` (default): the fused device-resident engine — streamed
        level-0 upload, the whole pyramid (transforms + downsample chain)
        in one jitted dispatch, async per-level coefficient fetches.
        ``False``: strictly sequential per-level stages (the PR-1 batched
        path), kept as the byte-identity A/B baseline. Only effective when
        ``batched`` and ``jpeg`` are both ``True``.
    mesh
        Optional ``jax.sharding.Mesh`` with a ``"data"`` axis: scope the
        conversion's batched kernel dispatches to this mesh (level batches
        are split over the axis — see ``kernels.ops.use_mesh``). ``None``
        (default) uses the ambient mesh (all visible devices). Sharding
        never changes output bytes, only where tiles are computed.
    """

    def __init__(self, *, min_level_size: int = 256, jpeg: bool = True,
                 manifest: dict | None = None, batched: bool = True,
                 pipelined: bool = True, mesh=None):
        self.min_level_size = min_level_size
        self.jpeg = jpeg
        self.batched = batched
        self.pipelined = pipelined
        self.mesh = mesh
        self.manifest = manifest if manifest is not None else {}

    def clear_manifest(self) -> None:
        """Drop finished-level bytes (call after the study tar is stored).

        Also drops the stored study/series UIDs, so a conversion rerun
        against the cleared manifest mints fresh identifiers.
        """
        self.manifest.clear()


def _study_uids(opt: ConvertOptions) -> tuple[str, str]:
    """(study_uid, series_uid), minted once and persisted in the manifest."""
    raw = opt.manifest.get("uids")
    if raw is None:
        raw = json.dumps([new_uid(), new_uid()])
        opt.manifest["uids"] = raw
    study_uid, series_uid = json.loads(raw)
    return study_uid, series_uid


def _level_frames(img: np.ndarray, tile: int) -> tuple[list[np.ndarray], int, int]:
    """Tile a (H, W, 3) level into row-major frames."""
    H, W, _ = img.shape
    frames = []
    for r in range(H // tile):
        for c in range(W // tile):
            frames.append(img[r * tile:(r + 1) * tile,
                              c * tile:(c + 1) * tile])
    return frames, H // tile, W // tile


def _tile_batch(dev: jnp.ndarray, tile: int) -> jnp.ndarray:
    """(3, H, W) device level → (N, 3, tile, tile) row-major tile batch."""
    _, H, W = dev.shape
    bh, bw = H // tile, W // tile
    if bh == 0 or bw == 0:
        # level smaller than one tile: no full frames (matches the per-tile
        # path, whose _level_frames loop body never runs)
        return jnp.zeros((0, 3, tile, tile), dev.dtype)
    return (dev[:, :bh * tile, :bw * tile].reshape(3, bh, tile, bw, tile)
            .transpose(1, 3, 0, 2, 4).reshape(bh * bw, 3, tile, tile))


class TransferStats:
    """Host↔device traffic ledger for the fused engine.

    ``uploads`` counts streamed level-0 uploads (one per slide — the strip
    ``device_put`` calls of a single slide are one logical transfer),
    ``dispatches`` counts jitted pyramid-chain launches, and ``fetches``
    counts per-level coefficient downloads. The conversion bench resets
    this, converts a slide, and asserts ``uploads == 1`` and
    ``dispatches == 1`` — the "≤1 host↔device round trip per slide"
    acceptance gate. Counters are advisory (not thread-synchronized);
    reset + assert from a single thread.
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.uploads = 0
        self.dispatches = 0
        self.fetches = 0


TRANSFER_STATS = TransferStats()


def _upload_level0(rd: SlideReader, mesh) -> jnp.ndarray:
    """Stream level 0 to the mesh one tile row at a time.

    Each row strip is handed to ``jax.device_put`` as soon as its tiles are
    inflated, so the host↔device copy of row r overlaps the zlib inflation
    of row r+1; the full-resolution ``(H, W, 3)`` host array of the sync
    path is never materialized. The strips hold exact uint8 values in
    float32, so the device concatenation is bit-identical to a whole-level
    upload. When the tile rows divide evenly over the mesh's devices, each
    device receives its own band of rows (``P(None, "data")``, the layout
    the chain's downsample and tile batches split on); otherwise every
    device receives the whole level.
    """
    tile, W = rd.tile, rd.W
    bh, bw = rd.grid
    devices = list(mesh.devices.flat)
    per = bh // len(devices) if bh % len(devices) == 0 else None
    replicated = NamedSharding(mesh, P())
    TRANSFER_STATS.uploads += 1
    strips = []
    for r in range(bh):
        row = np.empty((3, tile, W), np.float32)
        for c in range(bw):
            row[:, :, c * tile:(c + 1) * tile] = \
                np.transpose(rd.read_tile(r, c), (2, 0, 1))
        strips.append(jax.device_put(
            row, replicated if per is None else devices[r // per]))
    if per is None:
        return jnp.concatenate(strips, axis=1)
    bands = [jnp.concatenate(strips[d * per:(d + 1) * per], axis=1)
             for d in range(len(devices))]
    return jax.make_array_from_single_device_arrays(
        (3, rd.H, W), NamedSharding(mesh, P(None, "data")), bands)


def _wrap_level(opt: ConvertOptions, li: int, frames: list[bytes], ts: str,
                tile: int, H: int, W: int, metadata: dict | None,
                study_uid: str, series_uid: str) -> None:
    """Wrap one finished level as Part-10 bytes into the manifest."""
    opt.manifest[str(li)] = write_part10(
        frames=frames, rows=tile, cols=tile,
        total_rows=H, total_cols=W, transfer_syntax=ts,
        study_uid=study_uid, series_uid=series_uid,
        sop_instance_uid=f"{series_uid}.{li + 1}",
        instance_number=li + 1,
        metadata={0: (metadata or {}).get("slide_id", "unknown"),
                  1: f"level={li}"},
    )


def _level_chunks(batch, bh: int, bw: int) -> list:
    """Split a level's (N, 3, T, T) coefficient batch into row-aligned
    chunks for the host entropy coder.

    Chunk boundaries sit on whole tile rows and each tile is entropy-coded
    as its own scan, so per-chunk encode emits exactly the frames of a
    whole-level encode, in the same row-major order. ~4 chunks per level
    keeps a crash between chunks cheap to resume (each finished level is
    checkpointed as soon as its last chunk is coded) without shrinking the
    vectorized encode batches too far.
    """
    rows_per = max(1, bh // 4)
    return [batch[r0 * bw:min(r0 + rows_per, bh) * bw]
            for r0 in range(0, bh, rows_per)]


def _pyramid_dims(H: int, W: int,
                  min_level_size: int) -> list[tuple[int, int]]:
    """Host-side geometry walk: (H, W) per pyramid level, same stopping
    rule as the sync engine's device walk."""
    dims = []
    while True:
        dims.append((H, W))
        if min(H, W) // 2 < min_level_size:
            return dims
        H, W = H // 2, W // 2


@lru_cache(maxsize=None)
def _pyramid_chain(n_levels: int, needed: tuple[int, ...], tile: int,
                   donate: bool, mesh):
    """One jitted dispatch for the whole pyramid.

    The traced graph chains ``downsample2x2`` level to level and emits
    ``jpeg_transform`` coefficients for every level in ``needed`` (levels
    already checkpointed in the manifest are skipped — their downsamples
    still run, because deeper levels derive from them). Fusing the chain
    means the pixel pyramid never leaves the device: the old engine's
    per-level dispatch + fetch round trips collapse to a single launch.
    ``donate=True`` (accelerators only; CPU warns and cannot donate) lets
    XLA retire the level-0 pixel buffer into the chain's scratch space.
    ``mesh`` (the ambient one) only keys the cache: the kernels' shard_map
    layouts are baked into the trace, so distinct meshes need distinct jits.
    """
    def chain(dev):
        outs = []
        for li in range(n_levels):
            if li in needed:
                outs.append(jpeg_transform(_tile_batch(dev, tile)))
            if li + 1 < n_levels:
                dev = jnp.clip(jnp.round(downsample2x2(dev)), 0, 255)
        return outs
    kw = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(chain, **kw)


def _convert_pipelined(rd: SlideReader, metadata: dict | None,
                       opt: ConvertOptions, study_uid: str,
                       series_uid: str) -> int:
    """The fused device-resident engine. Returns the number of levels.

    One streamed upload, one dispatch, ordered consumption:

    1. **Upload** — level-0 tile rows go to the device as the reader
       inflates them (``_upload_level0``); no full host pixel array.
    2. **Fused pyramid dispatch** — a single jitted call
       (``_pyramid_chain``) runs every level's ``jpeg_transform`` and the
       ``downsample2x2`` chain between levels in one traced graph. The
       dispatch returns immediately (JAX async dispatch); every level's
       coefficient fetch is started with ``copy_to_host_async`` so
       downloads overlap the remaining device work.
    3. **Ordered consume** — levels are entropy-coded and Part-10-wrapped
       in pyramid order, in row-aligned chunks (``_level_chunks``); each
       finished level is checkpointed into the manifest immediately, so a
       crash mid-pyramid resumes from every completed level. While the
       host codes level N, the device is still transforming levels > N.

    The per-tile math and emitted frame order are identical to the sync
    engine's per-level dispatch — fusion changes only where buffers live —
    so the output bytes are identical (asserted in tests and the bench).
    """
    tile = rd.tile
    dims = _pyramid_dims(rd.H, rd.W, opt.min_level_size)
    n_levels = len(dims)
    needed = tuple(li for li in range(n_levels)
                   if str(li) not in opt.manifest)
    if not needed:
        return n_levels

    mesh = kernel_ops.default_mesh()
    with tracing.span("convert.upload"):
        dev = _upload_level0(rd, mesh)
    donate = jax.default_backend() != "cpu"
    with tracing.span("convert.dispatch", levels=len(needed)):
        # async dispatch: the span covers trace/launch, not device time —
        # device work overlaps the per-level entropy spans below
        outs = _pyramid_chain(n_levels, needed, tile, donate, mesh)(dev)
    TRANSFER_STATS.dispatches += 1
    del dev  # donated / retired: the chain owns the pixel pyramid now
    for coef in outs:
        if hasattr(coef, "copy_to_host_async"):
            coef.copy_to_host_async()

    for li, coef_dev in zip(needed, outs):
        H, W = dims[li]
        with tracing.span("convert.entropy", level=li):
            coef = np.asarray(coef_dev)
            TRANSFER_STATS.fetches += 1
            bh, bw = H // tile, W // tile
            chunks = [coef] if (bh == 0 or bw == 0) \
                else _level_chunks(coef, bh, bw)
            frames: list[bytes] = []
            for ch in chunks:
                frames += encode_coef_batch(np.asarray(ch))
            _wrap_level(opt, li, frames, TS_JPEG_BASELINE, tile, H, W,
                        metadata, study_uid, series_uid)
            tracing.add_event(None, "convert.checkpoint", level=li,
                              frames=len(frames))
    return n_levels


def _convert_sync(rd: SlideReader, metadata: dict | None, opt: ConvertOptions,
                  study_uid: str, series_uid: str) -> int:
    """The strictly sequential engine (batched or per-tile). Returns the
    number of levels."""
    tile = rd.tile

    # level 0 assembled tile-by-tile (streaming); higher levels by 2× pooling
    H, W = rd.H, rd.W
    level = np.empty((H, W, 3), np.uint8)
    for (r, c), t in rd.tiles():
        level[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile] = t

    # batched path: the pyramid lives on device as float32 planes holding
    # exact uint8 values (downsample output is re-quantized on device), so
    # the transform input matches the per-tile uint8 path bit-for-bit
    dev = jnp.asarray(np.transpose(level, (2, 0, 1)).astype(np.float32)) \
        if opt.batched else None

    li = 0
    while True:
        if opt.batched:
            H, W = int(dev.shape[1]), int(dev.shape[2])
        else:
            H, W = level.shape[:2]
        if str(li) not in opt.manifest:
            if opt.jpeg and opt.batched:
                coef = np.asarray(jpeg_transform(_tile_batch(dev, tile)))
                frames = encode_coef_batch(coef)
                ts = TS_JPEG_BASELINE
            else:
                if opt.batched:
                    level = np.asarray(dev).transpose(1, 2, 0).astype(np.uint8)
                frames_rgb, _, _ = _level_frames(level, tile)
                if opt.jpeg:
                    frames = [encode_tile(f) for f in frames_rgb]
                    ts = TS_JPEG_BASELINE
                else:
                    frames = [np.ascontiguousarray(f).tobytes()
                              for f in frames_rgb]
                    ts = TS_EXPLICIT_LE
            _wrap_level(opt, li, frames, ts, tile, H, W, metadata,
                        study_uid, series_uid)
        if min(H, W) // 2 < opt.min_level_size:
            return li + 1
        if opt.batched:
            dev = jnp.clip(jnp.round(downsample2x2(dev)), 0, 255)
        else:
            chw = np.transpose(level, (2, 0, 1)).astype(np.float32)
            down = np.asarray(downsample2x2(chw))
            level = np.clip(np.round(np.transpose(down, (1, 2, 0))),
                            0, 255).astype(np.uint8)
        li += 1


def _pack_study(opt: ConvertOptions, n_levels: int, study_uid: str,
                tile: int) -> bytes:
    """Assemble the study tar directly from the manifest (deterministic:
    fixed member mtimes, levels in index order)."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        manifest = {"levels": n_levels, "study_uid": study_uid,
                    "tile": tile}
        mb = json.dumps(manifest).encode()
        info = tarfile.TarInfo("study.json")
        info.size = len(mb)
        tar.addfile(info, io.BytesIO(mb))
        for i in range(n_levels):
            blob = opt.manifest[str(i)]
            info = tarfile.TarInfo(f"level_{i}.dcm")
            info.size = len(blob)
            tar.addfile(info, io.BytesIO(blob))
    return buf.getvalue()


def convert_wsi_to_dicom(slide_bytes: bytes, metadata: dict | None = None,
                         options: ConvertOptions | None = None) -> bytes:
    """Full conversion of any registered container (sniffed by magic bytes).

    Returns a tar archive of per-level .dcm files. Raises an actionable
    ``ValueError`` for unknown/truncated containers (see
    ``repro.wsi.formats.sniff``)."""
    opt = options or ConvertOptions()
    rd = open_slide(slide_bytes)
    if rd.H % rd.tile or rd.W % rd.tile:
        raise ValueError(
            f"slide is {rd.H}x{rd.W} with {rd.tile}px tiles — the pyramid "
            "engine requires tile-aligned dimensions (pad the scan)")
    study_uid, series_uid = _study_uids(opt)
    ctx = kernel_ops.use_mesh(opt.mesh) if opt.mesh is not None \
        else nullcontext()
    stats0 = (TRANSFER_STATS.uploads, TRANSFER_STATS.dispatches,
              TRANSFER_STATS.fetches)
    with tracing.span("convert.slide",
                      slide=(metadata or {}).get("slide_id")) as sp:
        with ctx:
            if opt.pipelined and opt.batched and opt.jpeg:
                n_levels = _convert_pipelined(rd, metadata, opt, study_uid,
                                              series_uid)
            else:
                n_levels = _convert_sync(rd, metadata, opt, study_uid,
                                         series_uid)
        with tracing.span("convert.pack", levels=n_levels):
            out = _pack_study(opt, n_levels, study_uid, rd.tile)
        if sp is not None:
            # TRANSFER_STATS is advisory (not thread-synced): under
            # concurrent conversions the deltas may include a neighbour's
            # transfers — they annotate, they don't assert
            sp.attrs.update(
                levels=n_levels,
                uploads=TRANSFER_STATS.uploads - stats0[0],
                dispatches=TRANSFER_STATS.dispatches - stats0[1],
                fetches=TRANSFER_STATS.fetches - stats0[2])
    return out


def study_levels(study_tar: bytes) -> dict[str, bytes]:
    """Unpack a converted study archive (non-file members are skipped)."""
    out = {}
    with tarfile.open(fileobj=io.BytesIO(study_tar)) as tar:
        for m in tar.getmembers():
            f = tar.extractfile(m)
            if f is None:  # directory / link member
                continue
            out[m.name] = f.read()
    return out
