"""Benchmark of the slide -> DICOM -> TIFF service on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Everything a cell needs is found by name:
its entry in ``BENCHMARK.json`` names a configuration file and a traffic
mix (``bench/traffic/<traffic>.json``), the mix names its client
(``bench/clients/<client>.py``), and every metric is read by a file of its
own (``bench/end_to_end/<name>.py``, ``bench/layer_metrics/<name>.py``)
with a ``read(ctx)`` that returns a number, or ``None`` where it finds
nothing to read.

A run: set-up (render the seed's slides, stand up the deployment, warm
every shape the cell uses), a window of ``--seconds`` of traffic, a drain
of work still in flight, then the comparison with the plain reference on a
seeded sample of what the window produced. ``--trace 1`` traces the window
with the profiler and reports the per-layer metrics instead of the
end-to-end ones. The last line of standard output is the result as JSON;
the numbers compared, each beside its limit, are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
#: nothing outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(Exception):
    pass


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str,
              root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix) of the cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, cfg, mix


def metrics_for(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


#: where the readers of each kind of metric live, under bench/
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def reader(kind: str, name: str, root: Path = ROOT):
    """The ``read`` function of the metric ``name`` of ``kind``
    (``end_to_end`` or ``per_layer``)."""
    path = root / "bench" / READERS[kind] / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    """Published peaks of the device kind; an unknown kind is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts lowerings (each new program the process builds, compiled or
    loaded from the cache) while ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.active and name == self.EVENT:
            self.count += 1


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, control: bool = False,
        overrides: dict | None = None, t_start: float | None = None,
        log=print) -> dict:
    """One run of a cell; returns the result dict (the JSON line).
    ``setup_s`` counts from ``t_start`` (default: this call)."""
    t_start = time.monotonic() if t_start is None else t_start
    spec = load_spec()
    cell, cfg, mix = find_cell(spec, workload)
    for part, vals in (overrides or {}).items():
        {"cfg": cfg, "mix": mix}[part].update(vals)
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no system under test at {ROOT / 'src'}")
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    cache = enable_compile_cache()
    devs = devices(cell["chips"], require_tpu)
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}; compile cache {cache}")
    peaks = peaks_for(dev.device_kind) if trace else None

    client = importlib.import_module(f"clients.{mix['client']}").Client(
        cfg, mix, seed, seconds)
    try:
        return _run(client, spec, cell, cfg, mix, devs, seed, seconds,
                    trace, control, peaks, t_start, log)
    finally:
        client.close()


def _run(client, spec, cell, cfg, mix, devs, seed, seconds, trace, control,
         peaks, t_start, log) -> dict:
    workload, dev = cell["name"], devs[0]
    split = client.setup()
    counter = CompileCounter()
    from repro.core import tracing

    setup_s = time.monotonic() - t_start
    log("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in split.items())
        + f"; total {setup_s:.3f} s")
    tracer = None
    trace_dir = ROOT / ".bench_trace" / f"{workload}-{seed}"
    traced: dict[str, float] = {}
    counter.active = True
    if trace:
        import jax
        import devtrace as tr

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=tr.profile_options())
        tracer = tracing.arm()
        trace_s = min(seconds, float(mix.get("trace_s", seconds)))

        def trace_window() -> None:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                traced["t0"] = time.monotonic()
                time.sleep(trace_s)
                traced["t1"] = time.monotonic()
            t = time.monotonic()
            jax.profiler.stop_trace()
            traced["stop_s"] = time.monotonic() - t

        th = threading.Thread(target=trace_window, name="bench-trace")
        th.start()
        t0, t1 = client.window()
        th.join()
        log(f"trace: {traced['t1'] - traced['t0']:.3f} s of the window, "
            f"stopped in {traced['stop_s']:.3f} s")
    else:
        t0, t1 = client.window()
    counter.active = False
    client.drain()
    if tracer is not None:
        tracing.disarm()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell["chips"]])
    log(f"compilations in the window: {counter.count}")

    ctx = SimpleNamespace(cell=workload, cfg=cfg, mix=mix, seconds=seconds,
                          t0=t0, t1=t1, client=client, setup_s=setup_s,
                          tw0=traced.get("t0"), tw1=traced.get("t1"),
                          spans=tracer.export() if tracer else [],
                          trace=None, peaks=peaks)
    result_metrics: dict = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    breakdown = None
    if trace:
        import devtrace as tr

        files = sorted(trace_dir.rglob("*.xplane.pb"))
        t = time.monotonic()
        red = tr.reduce(tr.load(str(files[-1]))) if files else None
        log(f"trace: {files[-1].stat().st_size if files else 0} B read in "
            f"{time.monotonic() - t:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is None:
            raise RuntimeError("the trace holds no device execution in the "
                               "window")
        ctx.trace = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = tr.breakdown(red, ctx.spans, ctx.tw0)
    kind = "per_layer" if trace else "end_to_end"
    for m in metrics_for(spec, workload, kind):
        v = reader(kind, m["name"])(ctx)
        if v is not None:
            result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    _log_slides(client, t0, t1, log)

    t_check = time.monotonic()
    checks = client.check(seed, control=control)
    log(f"comparison with the reference: {time.monotonic() - t_check:.3f} s")
    attempted, failed = client.outcome(t0, t1)
    result = {"correct": all(c.ok for c in checks),
              "attempted": attempted, "failed": failed,
              "metrics": result_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    result["_check_detail"] = {c.name: c.detail for c in checks}
    return result


def _log_slides(client, t0: float, t1: float, log) -> None:
    """The tail that no metric reports: p90 of per-request times with its
    sample count."""
    times = client.latencies(t0, t1)
    if len(times) >= 2:
        p90 = statistics.quantiles(times, n=10)[-1]
        log(f"request time: p50 {statistics.median(times):.4f} s, "
            f"p90 {p90:.4f} s over {len(times)} requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference computed one precision step "
                         "lower (the control) in the program's place in the "
                         "comparison; it has to come out not correct")
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), control=args.control, t_start=T_START,
                     log=lambda s: print(s, flush=True))
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    except Exception:  # a run that cannot finish prints no result
        traceback.print_exc()
        return 2
    detail = result.pop("_check_detail")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{detail[name]}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
