# Single-command entries the builder's verify recipe runs before the
# suite (see ROADMAP.md for the canonical tier-1 line).

.PHONY: lint lint-json tier1 chaos profile-report

# dslint: AST-level invariant checker (docs/LINT.md) — no jax needed
lint:
	python tools/dslint.py deepspeed_tpu tools

lint-json:
	python tools/dslint.py --json deepspeed_tpu tools

# newest continuous-profiler window + window-over-window regression
# verdict from the on-disk history ring (docs/OBSERVABILITY.md
# "Continuous profiling"; no jax needed — the ring is plain JSON)
profile-report:
	python tools/trace_report.py --history profile_history

# lint first (seconds), then the tier-1 suite as the driver runs it (a
# quarter of an hour): six workers, a file the unit of work.  The two files
# that compile for a described v5e may land on two workers, each of which
# loads the TPU's library: ALLOW_MULTIPLE_LIBTPU_LOAD lets them (ROADMAP D6)
tier1: lint
	env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest \
		tests/ -q -m 'not slow' --continue-on-collection-errors \
		-p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly

# the slow-marked chaos suites (outside tier-1): the mid-stream
# decode-replica kill in tests/unit/test_serving_chaos.py and the
# TRAINING matrix (tests/perf/test_train_chaos.py — randomized
# kill-sweep across an elastic 4->2->8->4 cycle, multi-round gradient
# bombs) at CPU smoke scale
chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m slow -k chaos \
		--continue-on-collection-errors -p no:cacheprovider \
		-p no:xdist -p no:randomly
