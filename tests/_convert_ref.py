"""A plain reference for the converter's per-level frames.

The tests hold ``convert_wsi_to_dicom`` to this: the same slide read
through the reader, a pyramid built in numpy, each level cut into
row-major tiles and each tile coded by the per-tile encoder. It uses
nothing of ``repro.wsi.convert``. Usage:

    from _convert_ref import reference_level_frames, study_frames

    assert study_frames(tar) == reference_level_frames(slide, 256)
"""
import io
import tarfile

import numpy as np

from repro.wsi import encode_tile, open_slide, read_part10


def _downsample(level: np.ndarray) -> np.ndarray:
    """(H, W, 3) uint8 → (H/2, W/2, 3) uint8: 2×2 mean in float32 (row pairs
    summed, then column pairs, ×0.25), rounded half to even and clipped."""
    H, W, _ = level.shape
    x = level[:H - H % 2, :W - W % 2].astype(np.float32)
    rows = x[0::2] + x[1::2]
    mean = (rows[:, 0::2] + rows[:, 1::2]) * np.float32(0.25)
    return np.clip(np.round(mean), 0, 255).astype(np.uint8)


def _frames(level: np.ndarray, tile: int) -> list[np.ndarray]:
    """Row-major full tiles of a level (none for a level under one tile)."""
    H, W, _ = level.shape
    return [level[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile]
            for r in range(H // tile) for c in range(W // tile)]


def _even(frame: bytes) -> bytes:
    """One 0x00 pads an odd frame, as DICOM encapsulation does."""
    return frame + b"\x00" if len(frame) % 2 else frame


def reference_level_frames(slide_bytes: bytes, min_level_size: int,
                           jpeg: bool = True) -> list[list[bytes]]:
    """Every pyramid level's frames as ``read_part10`` returns them: baseline
    JPEG (``encode_tile``, padded to even length) or, with ``jpeg=False``,
    the raw uint8 RGB bytes."""
    rd = open_slide(slide_bytes)
    tile = rd.tile
    level = np.empty((rd.H, rd.W, 3), np.uint8)
    for (r, c), t in rd.tiles():
        level[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile] = t
    out = []
    while True:
        frames = _frames(level, tile)
        out.append([_even(encode_tile(f)) for f in frames] if jpeg
                   else [np.ascontiguousarray(f).tobytes() for f in frames])
        H, W, _ = level.shape
        if min(H, W) // 2 < min_level_size:
            return out
        level = _downsample(level)


def study_frames(study_tar: bytes) -> list[list[bytes]]:
    """Every level's frames of a converted study tar, level 0 first."""
    with tarfile.open(fileobj=io.BytesIO(study_tar)) as tar:
        names = sorted((m.name for m in tar.getmembers()
                        if m.name.endswith(".dcm")),
                       key=lambda n: int(n[len("level_"):-len(".dcm")]))
        return [read_part10(tar.extractfile(n).read())[1] for n in names]
