PYTHONPATH := src
export PYTHONPATH

.PHONY: test lint verify smoke bench race trace

# tier-1 verify (conftest arms lockdep AND racedep for the whole suite:
# any lock-order inversion / callback-under-lock / held-too-long /
# acquired-in-jit violation — or a data race on tracked shared state —
# fails the test that provoked it)
test:
	python -m pytest -x -q

# project AST lint rules (see src/repro/analysis/lint.py: bare-lock,
# bare-thread, wall-clock, unseeded-random, direct-pallas, counter-name,
# jit-global-mutation); exits nonzero on any finding
lint:
	python -m repro.analysis.lint src tests benchmarks

# same entry point, named the way the docs and CI refer to it
verify: lint test

# systematic schedule exploration (see src/repro/analysis/schedules.py):
# runs the sim fleet scenario and the real-bytes fleet scenario — synthetic
# slides through the real converter under drop/duplicate/delay faults and
# an instance kill — across N seeded event schedules plus legacy FIFO,
# asserting exactly-once settlement, cross-schedule byte-identical study
# tars, and zero data races (racedep armed). A failing schedule dumps its
# seed + trace under artifacts/ and prints a one-line replay command
race:
	python -m repro.analysis.schedules --explore sim --seeds 30
	python -m repro.analysis.schedules --explore realbytes --seeds 20

# instrumented observability smoke (see src/repro/core/dashboard.py):
# runs a small real-conversion batch on the wall-clock scheduler with the
# distributed tracer armed, delivery faults injected, and an instance
# killed mid-run; renders the single dashboard and writes
# artifacts/dashboard.json + artifacts/trace-sample.json (one slide's
# full span tree) — exits nonzero if any slide's trace is disconnected
trace:
	python -m repro.core.dashboard --smoke --out artifacts

# CPU smoke of the benches: the store benchmark asserts indexed-WADO byte
# identity + ≥10x plus re-STOW / crash-rebuild QIDO/WADO identity; the
# export benchmark asserts batched-decode pixel identity +
# coefficient-exact round-trip and a >1x decode speedup at EVERY batch-scaling point; the kernel
# benchmark asserts flat batch scaling (no small-batch recompile cliff)
# and pow2-bucket jit-cache reuse, and writes the roofline terms; the
# fleet benchmark asserts the Figure-2 crossover (fleet loses at n=1,
# wins at n>=10), the Figure-3 ramp/plateau/decay, and the full
# fault-injection gauntlet (drop/delay/duplicate deliveries + instance
# kill + shard crash -> zero lost/double-converted, study tars
# byte-identical to a serial conversion)
smoke:
	python -m benchmarks.store_bench --fast
	python -m benchmarks.export_bench --fast
	python -m benchmarks.kernels_bench --fast
	python -m benchmarks.fleet_bench --fast

# benchmark suite: paper figures + kernels + store + export + fleet hot
# paths (writes BENCH_*.json into the working directory)
bench:
	python -m benchmarks.run
	python -m benchmarks.store_bench
	python -m benchmarks.export_bench
	python -m benchmarks.kernels_bench
	python -m benchmarks.fleet_bench
