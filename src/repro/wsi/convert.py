"""The converter: any registered slide container → multi-level DICOM WSM study.

Per slide: sniff the container (``repro.wsi.formats.open_slide`` — PSV,
tiled TIFF/SVS, or any registered format), stream tiles through the
``SlideReader`` protocol, build the multi-resolution pyramid with the
Pallas downsample kernel, transform-code every tile (Pallas DCT/quant +
Huffman), wrap each level in a DICOM Part-10 instance (TILED_FULL),
and bundle the study as a tar archive. The converter consumes only the
reader protocol, so identical pixel content produces byte-identical study
tars regardless of the source container (given the same manifest UIDs) —
asserted across PSV vs tiled-TIFF in the tests.

The container picks the engine; nothing else does. Two engines emit JPEG
(see DESIGN.md, "Pipelined conversion" and "Transcoding a scanner's
JPEG"):

- **pipelined** (``_convert_pipelined``), for a container of raw pixels
  (PSV, tiled TIFF/SVS). Level-0 tile rows are uploaded to the device as
  the reader inflates them (no full host ``(H, W, 3)`` array), then the
  **entire pyramid** — every level's ``jpeg_transform`` and the
  ``downsample2x2`` chain between levels — is one jitted dispatch
  (``donate_argnums`` retires the pixel buffer on accelerators). The
  coefficients never come back to the host: each level is Huffman-coded
  on the device in row-aligned chunks (``jpeg.encode_coef_batch`` on a
  device array, ``wsi/entropy_encode_jax``) and only the packed scans are
  copied back, to be 0xFF-stuffed and wrapped; a level's device buffers
  are dropped once it is coded. A chunk too small to be worth a compile
  (the last levels' 1-, 2- and 4-tile chunks), or a tile the device coder
  flags (over its slab, or a category outside the baseline tables), is
  copied back and coded by the numpy coder — a rule on the input, not an
  option. Exactly one host→device upload and one pyramid dispatch per
  slide (the ``convert.slide`` span counts its ``convert.upload``/
  ``convert.dispatch``/``convert.fetch`` spans and sums the
  ``device_tiles``/``host_tiles`` its ``convert.encode`` spans coded).
- **transcoding** (``_convert_transcode``), for a container of JPEG tiles
  (a scanner's SVS): its tiles become level 0's frames unchanged, level 0
  is decoded on the device, and the pipelined engine's pyramid builds
  levels ≥ 1 from it.

Native output (``ConvertOptions(jpeg=False)``, explicit-VR-LE pixels) is a
transfer syntax, not an engine: ``_convert_native`` uploads level 0 the
same way, walks the same ``downsample2x2`` chain and copies each level's
raw frames back.

**Determinism**: the study/series UIDs are minted once and stored in the
manifest (key ``"uids"``), and every level's SOP instance UID is derived
from the series UID + instance number. Two conversions of the same slide
that share a manifest (or whose manifests were seeded with the same
``"uids"`` entry) therefore produce byte-identical study tars — this is
what makes manifest resume reproduce a fresh conversion exactly.

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
device dispatches and waits, the numpy entropy coder, and zlib inflation
all release the GIL for their heavy regions.
"""
from __future__ import annotations

import io
import json
import tarfile
from collections import Counter
from contextlib import nullcontext
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import tracing
from repro.kernels import downsample2x2, jpeg_transform, ops as kernel_ops
from repro.wsi import entropy_jax, jpeg
from repro.wsi.dicom import (TS_EXPLICIT_LE, TS_JPEG_BASELINE, new_uid,
                             write_part10)
from repro.wsi.formats import SlideReader, open_slide
from repro.wsi.jpeg import encode_coef_batch

__all__ = ["convert_wsi_to_dicom", "study_levels", "ConvertOptions"]


class ConvertOptions:
    """Converter knobs.

    min_level_size
        Stop the pyramid once the next level's short edge would fall below
        this (pixels). Levels smaller than one tile emit zero full frames.
    jpeg
        ``True`` → encapsulated JPEG baseline transfer syntax; ``False`` →
        native (uncompressed) explicit-VR-LE pixel data: the same device
        pyramid, with each level's raw frames copied back and wrapped.
    manifest
        Resume checkpoint *and* the only copy of finished-level bytes held
        by the converter: maps level index (str) to that level's Part-10
        bytes, plus the ``"uids"`` entry (JSON ``[study_uid, series_uid]``)
        minted on first use so a resumed — or deliberately re-seeded —
        conversion reproduces the original bytes exactly. The output tar is
        written from the manifest directly.
    mesh
        Optional ``jax.sharding.Mesh`` with a ``"data"`` axis: scope the
        conversion's batched kernel dispatches to this mesh (level batches
        are split over the axis — see ``kernels.ops.use_mesh``). ``None``
        (default) uses the ambient mesh (all visible devices). Sharding
        never changes output bytes, only where tiles are computed.
    """

    def __init__(self, *, min_level_size: int = 256, jpeg: bool = True,
                 manifest: dict | None = None, mesh=None):
        self.min_level_size = min_level_size
        self.jpeg = jpeg
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


def _tile_batch(dev: jnp.ndarray, tile: int) -> jnp.ndarray:
    """(3, H, W) device level → (N, 3, tile, tile) row-major tile batch."""
    _, H, W = dev.shape
    bh, bw = H // tile, W // tile
    if bh == 0 or bw == 0:
        # level smaller than one tile: no full frames
        return jnp.zeros((0, 3, tile, tile), dev.dtype)
    return (dev[:, :bh * tile, :bw * tile].reshape(3, bh, tile, bw, tile)
            .transpose(1, 3, 0, 2, 4).reshape(bh * bw, 3, tile, tile))


def _upload_level0(rd: SlideReader, mesh) -> jnp.ndarray:
    """Stream level 0 to the mesh one tile row at a time.

    Each row strip is handed to ``jax.device_put`` as soon as its tiles are
    inflated, so the host↔device copy of row r overlaps the zlib inflation
    of row r+1; the full-resolution ``(H, W, 3)`` host array is never
    materialized. The strips hold exact uint8 values in
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
                study_uid: str, series_uid: str,
                photometric: str | None = None) -> None:
    """Wrap one finished level as Part-10 bytes into the manifest."""
    opt.manifest[str(li)] = write_part10(
        photometric=photometric,
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
    chunks for the entropy coder.

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


#: what the entropy coder's ``jpeg.encode`` spans count, summed into
#: ``convert.encode``
_ENCODE_ATTRS = ("device_tiles", "host_tiles", "bytes_in")


def _summed(sp, name: str, attrs: tuple[str, ...]) -> dict[str, int]:
    """``attrs`` summed over the ``name`` spans below ``sp``."""
    below = [d for d in tracing.descendants(sp) if d.name == name]
    return {a: sum(d.attrs.get(a, 0) for d in below) for a in attrs}


def _pyramid_dims(H: int, W: int,
                  min_level_size: int) -> list[tuple[int, int]]:
    """Host-side geometry walk: (H, W) per pyramid level. The pyramid stops
    once the next level's short edge would fall below ``min_level_size``."""
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
        with jax.named_scope("pyramid"):
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
       dispatch returns immediately (JAX async dispatch).
    3. **Ordered consume** — levels are entropy-coded and Part-10-wrapped
       in pyramid order, in row-aligned chunks (``_level_chunks``) of the
       device-resident coefficients: each chunk is Huffman-coded on the
       device and only its packed scans come back (``convert.encode``
       records ``device_tiles``, ``host_tiles`` and ``bytes_in``). Each
       finished level is checkpointed into the manifest immediately, so a
       crash mid-pyramid resumes from every completed level, and its
       device coefficients are released.

    Each frame is the baseline JPEG that ``jpeg.encode_tile`` makes of the
    same tile of a pyramid built level by level — fusion changes only where
    buffers live (asserted against a plain numpy reference in the tests).
    """
    tile = rd.tile
    dims = _pyramid_dims(rd.H, rd.W, opt.min_level_size)
    needed = tuple(li for li in range(len(dims))
                   if str(li) not in opt.manifest)
    if not needed:
        return len(dims)

    mesh = kernel_ops.default_mesh()
    with tracing.span("convert.upload") as sp:
        dev = _upload_level0(rd, mesh)
        if sp is not None:
            sp.attrs["bytes"] = dev.nbytes
    _code_pyramid(dev, dims, needed, tile, mesh, opt, metadata, study_uid,
                  series_uid)
    return len(dims)


def _code_pyramid(dev, dims: list[tuple[int, int]], needed: tuple[int, ...],
                  tile: int, mesh, opt: ConvertOptions, metadata: dict | None,
                  study_uid: str, series_uid: str) -> None:
    """Steps 2 and 3 of the pipelined engine, from level 0's (3, H, W)
    device planes: the fused pyramid dispatch for the ``needed`` levels,
    then each level Huffman-coded, wrapped and checkpointed in order."""
    n_levels = len(dims)
    donate = jax.default_backend() != "cpu"
    with tracing.span("convert.dispatch", levels=len(needed)):
        # async dispatch: the span covers trace/launch, not device time —
        # device work overlaps the per-level entropy spans below
        outs = _pyramid_chain(n_levels, needed, tile, donate, mesh)(dev)
    if not dev.is_deleted():
        # not donated (the CPU; or level 0's transform skipped, so no
        # output can take its buffer): release it now, not when the
        # caller's reference goes after every level is coded
        dev.delete()
    levels = dict(zip(needed, outs))
    del outs  # each level's coefficients are freed once it is coded

    for li in needed:
        H, W = dims[li]
        coef = levels.pop(li)
        with tracing.span("convert.entropy", level=li):
            # the host blocked on the device: the rest of the chain up to
            # this level; nothing is copied
            with tracing.span("convert.fetch", level=li):
                coef.block_until_ready()
            bh, bw = H // tile, W // tile
            frames: list[bytes] = []
            with tracing.span("convert.encode") as sp:
                chunks = [coef] if (bh == 0 or bw == 0) \
                    else _level_chunks(coef, bh, bw)
                del coef
                while chunks:
                    frames += encode_coef_batch(chunks.pop(0))
                if sp is not None:
                    sp.attrs.update(_summed(sp, "jpeg.encode", _ENCODE_ATTRS),
                                    frames=len(frames),
                                    bytes_out=sum(map(len, frames)))
            with tracing.span("convert.wrap"):
                _wrap_level(opt, li, frames, TS_JPEG_BASELINE, tile, H, W,
                            metadata, study_uid, series_uid)
            tracing.add_event(None, "convert.checkpoint", level=li,
                              frames=len(frames))


@partial(jax.jit, static_argnums=(1, 2))
def _tiles_to_plane(tiles, bh: int, bw: int):
    """(N, 3, T, T) row-major tile batch → (3, bh·T, bw·T) level planes
    (the inverse of ``_tile_batch``)."""
    T = tiles.shape[-1]
    return (tiles.reshape(bh, bw, 3, T, T).transpose(2, 0, 3, 1, 4)
            .reshape(3, bh * T, bw * T))


def _decode_level0(frames: list[bytes], bh: int, bw: int,
                   mesh) -> tuple[jax.Array, dict]:
    """Level 0 of a JPEG-tiled container decoded on the device → its
    (3, H, W) float32 planes (exact uint8 values, the layout
    ``_upload_level0`` gives) and what ``convert.decode`` records: the
    bytes uploaded (``bytes_in``), the blocks decoded and, where traced,
    the lockstep loop's ``steps`` and ``lanes``.

    The host parses, unstuffs and packs the scans (``decode.parse``) and
    uploads them compressed (``convert.upload``); the device runs the
    lockstep entropy decoder (``decode.entropy``: only its error flags and
    trip count come back), integrates the DC terms and de-zigzags, and
    inverts every tile (``decode.inverse``: ``jpeg_inverse420``, the
    stream's tables, chroma upsampled). No coefficient or pixel of level 0
    comes back.
    """
    with tracing.span("decode.parse", frames=len(frames)):
        H, W, coding, scans = jpeg._parse_batch(frames)
        _, packed = jpeg._pack_scans(scans, H, W, "jax", coding)
    with tracing.span("convert.upload") as sp:
        packed = (*jax.device_put(packed[:4], mesh.devices.flat[0]),
                  packed[4])
        nbytes = sum(a.nbytes for a in packed[:4])
        if sp is not None:
            sp.attrs["bytes"] = nbytes
    with tracing.span("decode.entropy", engine="jax") as sp:
        zzf = entropy_jax.decode_packed(packed, H, W, coding)
    loop = {} if sp is None else {k: sp.attrs[k] for k in ("steps", "lanes")}
    with tracing.span("decode.inverse"):
        y, c = entropy_jax.coef_planes(zzf, n=len(frames), H=H, W=W,
                                       coding=coding)
        del zzf
        if mesh.devices.size > 1:
            y, c = jax.device_put((y, c), NamedSharding(mesh, P()))
        dev = _tiles_to_plane(
            kernel_ops.jpeg_inverse420(y, c, coding.qtables()), bh, bw)
    return dev, dict(bytes_in=nbytes, blocks=len(frames) * coding.units(H, W),
                     **loop)


def _convert_transcode(rd: SlideReader, frames: list[bytes],
                       metadata: dict | None, opt: ConvertOptions,
                       study_uid: str, series_uid: str) -> int:
    """The transcoding engine, for a container of JPEG tiles (a scanner's
    SVS). Returns the number of levels.

    Level 0's frames are the scanner's tiles with the shared tables merged
    in — its entropy-coded data unchanged, never re-encoded (that would add
    a second generation of JPEG loss to every pixel of the archive) — and
    are wrapped and checkpointed before any device work, as YBR_FULL_422
    where the chroma is subsampled. Level 0 is then decoded on the device
    (``convert.decode``: ``frames``, ``bytes_in`` uploaded, ``blocks``, the
    lockstep loop's ``steps`` and ``lanes``) and its planes feed the
    pipelined engine's pyramid with level 0's own transform skipped;
    levels ≥ 1 are coded and wrapped as from any other container.
    """
    tile = rd.tile
    dims = _pyramid_dims(rd.H, rd.W, opt.min_level_size)
    if "0" not in opt.manifest:
        with tracing.span("convert.wrap"):
            _wrap_level(opt, 0, frames, TS_JPEG_BASELINE, tile, rd.H, rd.W,
                        metadata, study_uid, series_uid,
                        photometric=jpeg.photometric(frames[0]))
        tracing.add_event(None, "convert.checkpoint", level=0,
                          frames=len(frames))
    needed = tuple(li for li in range(1, len(dims))
                   if str(li) not in opt.manifest)
    if not needed:
        return len(dims)
    mesh = kernel_ops.default_mesh()
    with tracing.span("convert.decode", frames=len(frames)) as sp:
        dev, attrs = _decode_level0(frames, *rd.grid, mesh)
        if sp is not None:
            sp.attrs.update(attrs)
    _code_pyramid(dev, dims, needed, tile, mesh, opt, metadata, study_uid,
                  series_uid)
    return len(dims)


def _convert_native(rd: SlideReader, metadata: dict | None,
                    opt: ConvertOptions, study_uid: str,
                    series_uid: str) -> int:
    """Native (explicit-VR-LE) output. Returns the number of levels.

    Level 0 is uploaded as the pipelined engine uploads it; each level
    still missing from the manifest comes back as uint8 row-major raw
    frames, and the next level is the same re-quantized ``downsample2x2``
    as ``_pyramid_chain``'s.
    """
    tile = rd.tile
    dims = _pyramid_dims(rd.H, rd.W, opt.min_level_size)
    with tracing.span("convert.upload"):
        dev = _upload_level0(rd, kernel_ops.default_mesh())
    for li, (H, W) in enumerate(dims):
        if str(li) not in opt.manifest:
            tiles = np.asarray(_tile_batch(dev, tile)).astype(np.uint8)
            frames = [t.transpose(1, 2, 0).tobytes() for t in tiles]
            _wrap_level(opt, li, frames, TS_EXPLICIT_LE, tile, H, W,
                        metadata, study_uid, series_uid)
        if li + 1 < len(dims):
            dev = jnp.clip(jnp.round(downsample2x2(dev)), 0, 255)
    return len(dims)


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
    # the container picks the engine: a scanner's JPEG tiles are kept as
    # level 0 when the output is JPEG
    frames = rd.jpeg_frames() if opt.jpeg and hasattr(rd, "jpeg_frames") \
        else None
    with tracing.span("convert.slide",
                      slide=(metadata or {}).get("slide_id")) as sp:
        with ctx:
            if frames is not None:
                n_levels = _convert_transcode(rd, frames, metadata, opt,
                                              study_uid, series_uid)
            elif opt.jpeg:
                n_levels = _convert_pipelined(rd, metadata, opt, study_uid,
                                              series_uid)
            else:
                n_levels = _convert_native(rd, metadata, opt, study_uid,
                                           series_uid)
        with tracing.span("convert.pack", levels=n_levels):
            out = _pack_study(opt, n_levels, study_uid, rd.tile)
        if sp is not None:
            # counted from this slide's own spans: exact under concurrent
            # conversions
            n = Counter(d.name for d in tracing.descendants(sp))
            sp.attrs.update(levels=n_levels, uploads=n["convert.upload"],
                            dispatches=n["convert.dispatch"],
                            fetches=n["convert.fetch"],
                            transcoded_frames=len(frames or ()),
                            **_summed(sp, "convert.encode",
                                      ("device_tiles", "host_tiles")))
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
