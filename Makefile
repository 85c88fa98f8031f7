# Convenience targets (the CI-role entry points — SURVEY §3.4).

.PHONY: test gate gate-fast native native-test lint lint-baseline shape-lint life-lint check check-baseline obs-smoke tune-smoke tune chaos-smoke train-chaos-smoke cluster-chaos-smoke aot-smoke locktrace-smoke shapetrace-smoke lifetrace-smoke

# graftlint: JAX-footgun static analysis (docs/LINT.md). Fails only on
# findings NOT grandfathered in lint_baseline.json. JAX_PLATFORMS=cpu so
# the registry-consistency rules can never hang on an unreachable TPU.
lint:
	JAX_PLATFORMS=cpu python tools/graftlint.py

# regenerate the baseline (after FIXING findings — the baseline only shrinks)
lint-baseline:
	JAX_PLATFORMS=cpu python tools/graftlint.py --write-baseline

# graftshape tier alone (docs/LINT.md § graftshape): jit-signature &
# recompile-discipline rules GS001-GS005. Already part of `make lint` —
# this target is the fast loop while working on shape discipline.
shape-lint:
	JAX_PLATFORMS=cpu python tools/graftlint.py --rules GS001,GS002,GS003,GS004,GS005

# graftlife tier alone (docs/LINT.md § graftlife): resource-lifecycle &
# exactly-once rules GR001-GR005. Already part of `make lint` — this
# target is the fast loop while working on ownership discipline.
life-lint:
	JAX_PLATFORMS=cpu python tools/graftlint.py --rules GR001,GR002,GR003,GR004,GR005

# graftcheck: abstract shape/dtype verification of the SameDiff fixture
# zoo (docs/ANALYSIS.md). Build-only — no jit, no device. Fails only on
# findings NOT grandfathered in check_baseline.json (committed empty:
# the fixtures must stay clean).
check:
	JAX_PLATFORMS=cpu python tools/graftcheck.py

check-baseline:
	JAX_PLATFORMS=cpu python tools/graftcheck.py --write-baseline

# observability smoke (docs/OBSERVABILITY.md): run the obsreport demo
# workload on CPU and emit ONE JSON line — fails unless train steps,
# recompile-ledger events, and serving percentiles all came out nonzero.
obs-smoke:
	JAX_PLATFORMS=cpu python tools/obsreport.py --json

# kernel-autotuner smoke (docs/KERNELS.md): tiny-shape tune on CPU — must
# exit 0 anywhere, produce a valid tuning table in the (throwaway by
# default) cache dir, and PROVE via the dispatch counters that resolve
# honors the tuned flash_min_t (XLA below, Pallas above). ONE JSON line
# like lint/check. The throwaway dir matters: smoke thresholds are
# interpret-mode noise and must never sit beside a real measured table
# (set DL4J_TPU_TUNING_DIR yourself to keep the smoke table).
tune-smoke:
	JAX_PLATFORMS=cpu \
	DL4J_TPU_TUNING_DIR=$${DL4J_TPU_TUNING_DIR:-$$(mktemp -d -t dl4j_tune_smoke.XXXXXX)} \
	python tools/tune.py --smoke --json

# full-ladder autotune — run ON THE TARGET CHIP; writes the measured table
# for this device kind to DL4J_TPU_TUNING_DIR, or to the git-ignored
# .tuning/ of the checkout when that is unset. Dispatch reads it only once a
# copy is committed as deeplearning4j_tpu/ops/tuning_tables/<kind>.json
tune:
	python tools/tune.py

# chaos smoke (docs/ROBUSTNESS.md): generative serving + checkpoints under
# an injected fault schedule (page OOM, decode crash, worker death, torn
# checkpoint write) — every request must reach a terminal finish reason,
# the supervisor must restart within its cap with ZERO new_shape ledger
# events, and restore() must fall back to the last intact checkpoint.
# ONE JSON line like lint/check/obs.
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos.py --json

# preemption-proof-training smoke (docs/ROBUSTNESS.md § Preemption-proof
# training): a supervised MLN fit under torn checkpoint writes, an
# async-writer death, and hard preemption kills — fails unless the
# resumed loss/param trajectory is BIT-EXACT vs the uninterrupted
# oracle with zero new_shape recompiles, >=1 intact checkpoint, and
# every-step ASYNC checkpointing costs < 10% of the synchronous-save
# baseline per step. ONE JSON line like lint/check/obs/chaos.
train-chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos.py --json --leg training

# cluster-failure-domain smoke (docs/ROBUSTNESS.md § Cluster failure
# domains): three engines behind the ClusterRouter under a past-capacity
# burst, one hard-killed mid-flight by engine_death — fails unless every
# request reaches a terminal state, >= 1 in-flight request migrates with
# its greedy output token-for-token identical to the single-engine
# oracle, goodput degrades no worse than proportionally to the capacity
# lost, and survivors show zero new_shape ledger events. ONE JSON line
# like lint/check/obs/chaos.
cluster-chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos.py --json --leg cluster

# locktrace smoke (docs/LINT.md § graftlock): runtime shadow-lock
# cross-validation of the static lock-order graph — fails unless the
# static graph is acyclic, every lock-order edge observed under the
# threaded serving + checkpoint workload is inside its transitive
# closure, and the combined graph stays acyclic.
# ONE JSON line like lint/check/obs/chaos.
locktrace-smoke:
	JAX_PLATFORMS=cpu python tools/locktrace.py

# shapetrace smoke (docs/LINT.md § graftshape): runtime cross-validation
# of the static jit-site inventory against the RecompileLedger — drives a
# randomized-shape serving replay (prefix cache + speculation on) plus a
# checkpoint-resumed training leg, then fails unless every recompile
# event attributes to a statically ledgered callsite and every new_shape
# event lands in a statically flagged hazard module.
# ONE JSON line like lint/check/obs/chaos/locktrace.
shapetrace-smoke:
	JAX_PLATFORMS=cpu python tools/shapetrace.py

# lifetrace smoke (docs/LINT.md § graftlife): runtime cross-validation of
# the static ownership inventory against live allocators — wraps the real
# paged-KV caches of a 3-engine prefix cluster in recording proxies,
# drives a faults-armed workload (page_oom mid-prefix-admission, decode
# crashes, one engine death) plus an async-checkpoint training leg with a
# worker death MID-WRITE, then fails unless pages end rc-clean, every
# request terminal counted exactly once, no thread leaked, and every
# observed acquire/release callsite lies inside the static inventory.
# ONE JSON line like lint/check/obs/chaos/locktrace/shapetrace.
lifetrace-smoke:
	JAX_PLATFORMS=cpu python tools/lifetrace.py

# AOT warm-boot smoke (docs/SERVING.md § AOT warm boot): three fresh
# processes replay the identical randomized-shape request mix with the
# persistent export cache off, populating, and warm — fails unless the
# warm restart pays ZERO serving first_compile ledger events (every
# dispatched fn arrives as cache_hit), its greedy outputs are
# bit-identical to the cache-off leg, and zero new_shape events were paid.
# ONE JSON line like lint/check/obs/chaos.
aot-smoke:
	JAX_PLATFORMS=cpu python tools/aot.py --json

# DL4J_TPU_REQUIRE_NATIVE=1: a missing native lib FAILS the ctypes tests
# instead of silently exercising the numpy fallback (SURVEY §5.3)
test: native-test
	DL4J_TPU_REQUIRE_NATIVE=1 python -m pytest tests/ -q

native-test: native
	ctest --test-dir native/build --output-on-failure

# full pre-snapshot gate (tools/gate.py): pytest + every smoke stage, all on
# the CPU. Run before any round-end commit. The chip is reached only through
# `python chip_smoke.py`, where a TPU is attached.
gate:
	python tools/gate.py

gate-fast:
	python tools/gate.py --fast

native:
	cmake -S native -B native/build && cmake --build native/build -j
