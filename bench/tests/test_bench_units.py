"""Unit tests of the benchmark's yardstick: work counts, peaks, traffic,
the reference codec, the harness's lookups and its refusal to run without
a chip. CPU only."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import run  # noqa: E402
import scanner  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402


# ------------------------------------------------------------------- work
def test_work_counts_at_one_known_shape():
    flops, nbytes = work.transform(256 * 256)
    # colour 16 + per channel (DCT 32 + quantise 2): 118 flops per pixel
    assert flops == 256 * 256 * 118
    assert nbytes == 256 * 256 * 9  # uint8 RGB in, int16 x3 out
    f_inv, b_inv = work.inverse(256 * 256)
    assert b_inv == nbytes
    assert f_inv == 256 * 256 * (3 * (1 + 32) + 9 + 9)
    f_ds, b_ds = work.downsample(512 * 512)
    assert b_ds == 512 * 512 * 3 + 256 * 256 * 3
    pf, pb = work.pyramid([512, 256])
    assert pb == work.transform(512 * 512)[1] + b_ds \
        + work.transform(256 * 256)[1]
    assert pf == work.transform(512 * 512)[0] + f_ds + flops
    peaks = run.peaks_for("TPU v5 lite")
    t, roof = work.least_time(pf, pb, peaks)
    assert roof == "memory" and t == pytest.approx(pb / 819e9)


def test_peaks_refuse_an_unknown_device_kind():
    assert run.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run.peaks_for("TPU v99 imaginary")
    with pytest.raises(KeyError):
        run.peaks_for("cpu")


# ---------------------------------------------------------------- traffic
@pytest.mark.parametrize("name", ["backfill", "export"])
def test_traffic_is_fixed_by_the_seed(name):
    mix = traffic.load(name)
    seed, other = 2**31 + 5, 2**31 + 6

    def draw(s):
        seq = traffic.sides(mix, s)
        return [next(seq) for _ in range(40)], traffic.scanner_seeds(mix, s)

    a, b, c = draw(seed), draw(seed), draw(other)
    assert a == b
    assert a[1] != c[1]  # other slides for another seed
    # every seed draws the same work: the same multiset of sizes per deck
    n = len(traffic.deck(mix))
    assert sorted(a[0][:n]) == sorted(c[0][:n]) == sorted(traffic.deck(mix))


def test_a_deck_of_several_sizes_is_dealt_in_a_seeded_order():
    mix = {"sizes": [[512, 3], [1024, 2], [2048, 1]]}

    def first(seed, n=12):
        seq = traffic.sides(mix, seed)
        return [next(seq) for _ in range(n)]

    a = first(2**31 + 5)
    assert a == first(2**31 + 5)
    assert sorted(a[:6]) == sorted(a[6:]) == sorted(traffic.deck(mix))
    assert len({tuple(first(s)) for s in range(8)}) > 1


@pytest.mark.parametrize("a,b,share", [
    (0.0, 10.0, 0.5),    # half inside
    (6.0, 8.0, 1.0),     # wholly inside
    (12.0, 14.0, 0.0),   # wholly after
    (2.0, 7.0, 0.4),     # overlaps the start
    (8.0, 18.0, 0.2),    # overlaps the end
    (2.0, 12.0, 0.5),    # covers the window
    (7.0, 7.0, 1.0),     # an instant inside
])
def test_work_is_credited_by_the_share_inside_the_window(a, b, share):
    from clients.ingest import window_share

    assert window_share(a, b, 5.0, 10.0) == pytest.approx(share)


def test_slides_are_fixed_by_the_seed():
    px1, tif1 = scanner.scan(512, 512, 256, 3.5)
    px2, tif2 = scanner.scan(512, 512, 256, 3.5)
    px3, _ = scanner.scan(512, 512, 256, 4.5)
    assert tif1 == tif2 and np.array_equal(px1, px2)
    assert not np.array_equal(px1, px3)
    tile, hw = reference.tiff_tile(tif1, 3)
    assert hw == (512, 512) and np.array_equal(tile, px1[256:, 256:])


# -------------------------------------------------------------- reference
def test_reference_round_trip_and_control_precision():
    px, _ = scanner.scan(512, 512, 256, 9.0)
    coef, amb = reference.forward(px[:256, :256])
    assert coef.shape == (3, 256, 256) and amb.mean() < 1e-4
    back, _ = reference.inverse(coef)
    mse = np.mean((back.astype(float) - px[:256, :256]) ** 2)
    assert 10 * np.log10(255 ** 2 / mse) > 30
    a = np.random.default_rng(0).normal(size=(64, 8, 8)).astype(np.float32)
    exact = np.matmul(a.astype(np.float64), a.astype(np.float64))
    err3 = np.abs(reference.matmul_bf16x3(a, a) - exact).max()
    err32 = np.abs(np.matmul(a, a) - exact).max()
    assert err32 < err3 < 1e-3  # the control sits between f32 and bf16


def test_reference_decoder_reads_a_baseline_jpeg_it_did_not_write():
    from PIL import Image
    import io

    px, _ = scanner.scan(256, 256, 256, 2.0)
    buf = io.BytesIO()
    Image.fromarray(px).save(buf, "JPEG", quality=50, subsampling=0)
    coef = reference.decode_coefficients(buf.getvalue())
    want, amb = reference.forward(px)
    # an independent encoder (libjpeg, integer DCT) agrees to within 1
    assert np.abs(coef - want).max() <= 1
    assert (coef != want).mean() < 0.01


# ---------------------------------------------------------------- harness
def test_a_cell_added_from_files_alone_is_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "dummy.json").write_text(json.dumps(
        {"client": "ingest", "in_flight": 1,
         "sizes": [[512, 1]], "pool": 1, "drain_s": 5,
         "check": {"slides": 1, "frames_per_level": 1}}))
    (root / "bench" / "layer_metrics" / "dummy.count.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["workloads"].append({"name": "dummy.cell", "config": "svs_ingest",
                              "traffic": "dummy", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "dummy.count", "unit": "n", "better": "lower",
        "source": "program_counter", "layer": "event spine",
        "moves": "convert_mpx_s", "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    spec = run.load_spec(root)
    cell, cfg, mix = run.find_cell(spec, "dummy.cell", root)
    assert cfg["name"] == "svs_ingest" and mix["sizes"] == [[512, 1]]
    names = [m["name"] for m in run.metrics_for(spec, "dummy.cell",
                                                "per_layer")]
    assert names == ["dummy.count"]
    assert run.reader("per_layer", "dummy.count", root)(None) == 42.0
    with pytest.raises(KeyError):
        run.find_cell(spec, "no.such.cell", root)


def _bench(cwd: Path, *extra: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ingest.backfill",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_harness_exits_nonzero_without_a_tpu():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_harness_exits_nonzero_without_the_system_under_test(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
