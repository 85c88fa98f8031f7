#!/usr/bin/env python
"""Pre-snapshot gate — the CI role (SURVEY §3.4).

Round-2 shipped a red snapshot because nothing stood between `git commit`
and a failing gradcheck; this gate is that something. Run before ANY
snapshot/round-end commit:

    python tools/gate.py            # full: pytest + every smoke stage
    python tools/gate.py --fast     # pytest only (pre-commit speed)

This is a CPU correctness gate: every stage runs with ``JAX_PLATFORMS=cpu``
(kernels in interpret mode), and whatever a stage times is a CPU-harness
number, never a device speed. The chip is reached only through ``python
chip_smoke.py`` (``--consistency`` adds the CPU-vs-TPU suite), one process,
run where a TPU is attached.

Stages:
  1. native: cmake build + ctest, then an ASAN(-DSANITIZE=ON) build + ctest
     (the libnd4j tests_cpu CI stage — SURVEY §5.3, §6.2)
  2. full pytest suite on the 8-device CPU harness with
     DL4J_TPU_REQUIRE_NATIVE=1 (a missing .so fails ctypes tests loudly)
  3. multichip dryrun (virtual 8-device CPU mesh via __graft_entry__; a
     failed stage fails the gate)
  4. obs smoke: tools/obsreport.py --json must report nonzero train steps,
     recompile-ledger events, and serving percentiles (docs/OBSERVABILITY.md)
  5. tune smoke: tiny-shape autotune into a throwaway cache dir must
     produce a loadable tuning table and prove measured dispatch via the
     helper-dispatch counters (docs/KERNELS.md)
  6. chaos smoke: tools/chaos.py under an injected fault schedule — every
     request must reach a terminal finish reason, the supervisor must
     restart within its cap with zero new_shape ledger events, and
     restore() must fall back past a torn checkpoint (docs/ROBUSTNESS.md)
  7. trainchaos smoke: tools/chaos.py --leg training — training killed
     mid-fit by injected faults must resume BIT-EXACT vs the
     uninterrupted oracle with zero new_shape, and async checkpointing's
     per-step overhead must be < 10% of the synchronous-save baseline
     (docs/ROBUSTNESS.md § Preemption-proof training)
  8. locktrace smoke: tools/locktrace.py shadow-lock cross-validation —
     the graftlock static lock-order graph must be acyclic, every
     lock-order edge observed under the threaded serving + checkpoint
     workload must lie inside its transitive closure, and the combined
     graph must stay acyclic (docs/LINT.md § graftlock)
  9. shapetrace smoke: tools/shapetrace.py recompile-ledger
     cross-validation — every CompileEvent recorded under the
     randomized-shape serving replay + checkpoint-resumed training
     workload must attribute to a statically known registration span,
     every new_shape must land in a statically flagged hazard module,
     and both legs must themselves observe zero new_shape
     (docs/LINT.md § graftshape)
 10. lifetrace smoke: tools/lifetrace.py runtime resource-lifecycle
     cross-validation — the faults-armed prefix cluster + async
     checkpoint workload must end with rc-clean pages, exactly one
     terminal count per request, zero leaked threads, every observed
     acquire/release callsite inside graftlife's static ownership
     inventory, and zero new_shape (docs/LINT.md § graftlife)
 11. aot smoke: tools/aot.py cold-restart warm boot — a fresh process
     restoring from the persistent export cache must pay zero serving
     first_compile events (cache_hit only) and emit outputs bit-identical
     to the cache-off leg (docs/SERVING.md § AOT warm boot)

Exit code 0 = snapshot allowed; anything else = fix first.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(name: str, cmd, env=None, timeout=3600) -> bool:
    print(f"== gate: {name} ==", flush=True)
    e = dict(os.environ)
    if env:
        e.update(env)
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=e, timeout=timeout,
                              capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        print(f"   FAIL ({name}: timeout after {timeout}s)")
        return False
    if proc.returncode != 0:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-25:])
        print(f"   FAIL ({name}, exit {proc.returncode})\n{tail}")
        return False
    print(f"   ok ({name})")
    return True


def native_stage() -> bool:
    """Build the native lib + run ctest, then an ASAN build + ctest
    (SURVEY §5.3/§6.2 — the libnd4j tests_cpu CI stage)."""
    steps = [
        ("cmake configure", ["cmake", "-S", "native", "-B", "native/build"]),
        ("cmake build", ["cmake", "--build", "native/build", "-j"]),
        ("ctest", ["ctest", "--test-dir", "native/build",
                   "--output-on-failure"]),
        ("cmake configure (ASAN)",
         ["cmake", "-S", "native", "-B", "native/build-asan",
          "-DSANITIZE=ON"]),
        ("cmake build (ASAN)", ["cmake", "--build", "native/build-asan",
                                "-j"]),
        ("ctest (ASAN)", ["ctest", "--test-dir", "native/build-asan",
                          "--output-on-failure"]),
    ]
    for name, cmd in steps:
        if not run(f"native: {name}", cmd, timeout=600):
            return False
    return True


def _baselined_tool_stage(tool: str, script: str, label: str) -> bool:
    """Shared stage driver for the baselined static-analysis tools
    (graftlint / graftcheck): run the script with --json, echo its ONE
    JSON summary line into the gate log so driver artifacts stay
    diagnosable, fail on any finding beyond the tool's shrink-only
    baseline."""
    print(f"== gate: {tool} ({label}) ==", flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, script, "--json"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print(f"   FAIL ({tool} timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL ({tool} exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    print(f"   ok ({tool}: {rec['total']} findings, "
          f"{rec['baselined']} grandfathered, {rec['new']} new)")
    return True


def lint_stage() -> bool:
    """graftlint over the whole repo (docs/LINT.md), vs
    lint_baseline.json."""
    return _baselined_tool_stage("graftlint", "tools/graftlint.py",
                                 "static analysis")


def check_stage() -> bool:
    """graftcheck over the fixture zoo (docs/ANALYSIS.md), vs
    check_baseline.json."""
    return _baselined_tool_stage("graftcheck", "tools/graftcheck.py",
                                 "graph shape/dtype verification")


def obs_stage() -> bool:
    """observability smoke (docs/OBSERVABILITY.md): the obsreport demo
    workload on CPU must report nonzero train steps, recompile-ledger
    events, and serving latency percentiles — one JSON line, like
    lint/check."""
    print("== gate: obs-smoke (obsreport demo workload) ==", flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "tools/obsreport.py", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print("   FAIL (obs-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (obs-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    ok = bool(rec.get("ok"))
    print(f"   {'ok' if ok else 'FAIL'} (obs-smoke: "
          f"{rec.get('train_steps')} steps, {rec.get('recompiles')} "
          f"recompiles, {rec.get('serving_requests')} serving requests)")
    return ok


def tune_stage() -> bool:
    """Autotuner smoke (docs/KERNELS.md): tiny-shape tune into a THROWAWAY
    cache dir must produce a loadable table and prove — via the
    dl4j_tpu_helper_dispatch_total counters — that small-shape attention
    dispatches to the XLA generic below the tuned threshold and to the
    Pallas helper above it. One JSON line, like lint/check/obs."""
    import tempfile

    print("== gate: tune-smoke (autotuner + measured dispatch) ==",
          flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DL4J_TPU_TUNING_DIR=tempfile.mkdtemp(prefix="gate_tune_"))
    env.pop("DL4J_TPU_FLASH_MIN_T", None)  # env override would mask the
    try:                                   # tuned-table dispatch proof
        proc = subprocess.run(
            [sys.executable, "tools/tune.py", "--smoke", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        print("   FAIL (tune-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (tune-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    verify = rec.get("verify") or {}
    ok = (bool(rec.get("ok")) and rec.get("table_path")
          and verify.get("below_dispatch") == "xla"
          and verify.get("above_dispatch") == "pallas")
    print(f"   {'ok' if ok else 'FAIL'} (tune-smoke: "
          f"{rec.get('measurements')} candidates, flash_min_t="
          f"{verify.get('flash_min_t')}, below->{verify.get('below_dispatch')}"
          f", above->{verify.get('above_dispatch')})")
    return bool(ok)


def chaos_stage() -> bool:
    """Robustness smoke (docs/ROBUSTNESS.md): the chaos harness must
    report ok — faults fired > 0 (all required points), unresolved
    requests == 0, restarts within cap, zero new_shape events, checkpoint
    fallback intact. One JSON line, like lint/check/obs.

    The full composite deliberately includes the (cheap, ~10s) training
    leg even though trainchaos_stage re-runs it: `make chaos-smoke` must
    stay the one-command proof of the WHOLE failure surface in one
    process, and the trainchaos stage owns the (expensive) overhead
    measurement the composite skips."""
    print("== gate: chaos-smoke (fault injection + supervised recovery) ==",
          flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DL4J_TPU_FAULTS", None)  # an ambient schedule would double-
    try:                              # inject on top of the harness's own
        proc = subprocess.run(
            [sys.executable, "tools/chaos.py", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        print("   FAIL (chaos-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (chaos-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    srv = rec.get("serving") or {}
    ok = (bool(rec.get("ok"))
          and (rec.get("faults_injected_total") or 0) > 0
          and srv.get("unresolved") == 0)
    print(f"   {'ok' if ok else 'FAIL'} (chaos-smoke: "
          f"{rec.get('faults_injected_total')} faults, "
          f"{srv.get('submitted')} submitted -> reasons {srv.get('reasons')}"
          f", {srv.get('restarts')} restarts, checkpoint fallback "
          f"{(rec.get('checkpoint') or {}).get('fallback_ok')})")
    return bool(ok)


def aot_stage() -> bool:
    """AOT warm-boot smoke (docs/SERVING.md § AOT warm boot): three
    fresh processes replay the identical randomized-shape request mix —
    compile cache off, populating, and warm. The warm restart must pay
    ZERO serving first_compile ledger events (everything it dispatches
    arrives as cache_hit), produce outputs bit-identical to the
    cache-off leg and observe zero new_shape. One JSON line, like
    lint/check/obs/chaos."""
    print("== gate: aot-smoke (cold-restart warm boot, cache off/on) ==",
          flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DL4J_TPU_FAULTS", None)   # ambient faults / cache state would
    env.pop("DL4J_TPU_COMPILE_CACHE", None)  # change what the legs compare
    try:
        proc = subprocess.run(
            [sys.executable, "tools/aot.py", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        print("   FAIL (aot-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (aot-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    ok = (bool(rec.get("ok"))
          and rec.get("warm_first_compile_keys") == []
          and len(rec.get("warm_cache_hit_keys") or []) > 0
          and rec.get("outputs_identical")
          and rec.get("new_shape_events") == 0)
    print(f"   {'ok' if ok else 'FAIL'} (aot-smoke: warm first_compiles="
          f"{rec.get('warm_first_compile_keys')}, cache_hits="
          f"{rec.get('warm_cache_hit_keys')}, "
          f"identical={rec.get('outputs_identical')})")
    return bool(ok)


def trainchaos_stage() -> bool:
    """Preemption-proof-training smoke (docs/ROBUSTNESS.md §
    Preemption-proof training): training killed mid-fit by injected
    faults (torn checkpoint write + async-writer death + hard
    preemption) must resume to a BIT-EXACT loss/param trajectory vs the
    uninterrupted oracle with zero new_shape recompiles, every on-disk
    checkpoint intact or detectably corrupt, and every-step async
    checkpointing's per-step overhead < 10% of the synchronous-save
    baseline. One JSON line, like lint/check/obs/chaos."""
    print("== gate: train-chaos-smoke (preemption-proof training) ==",
          flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DL4J_TPU_FAULTS", None)  # an ambient schedule would double-
    try:                              # inject on top of the harness's own
        proc = subprocess.run(
            [sys.executable, "tools/chaos.py", "--json", "--leg",
             "training"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        print("   FAIL (train-chaos-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (train-chaos-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    tr = rec.get("training") or {}
    ovh = rec.get("overhead") or {}
    ok = (bool(rec.get("ok"))
          and tr.get("trajectory_bit_exact")
          and tr.get("params_bit_exact")
          and tr.get("new_shape_events") == 0
          and (tr.get("resumes") or 0) >= 1
          and bool(ovh.get("ok")))
    print(f"   {'ok' if ok else 'FAIL'} (train-chaos-smoke: "
          f"{tr.get('steps')} steps, {tr.get('resumes')} resumes, fired "
          f"{tr.get('fired')}, bit-exact={tr.get('trajectory_bit_exact')}"
          f", async overhead {ovh.get('async_overhead_ms')}ms vs sync "
          f"{ovh.get('sync_overhead_ms')}ms "
          f"(ratio {ovh.get('overhead_ratio')}))")
    return bool(ok)


def cluster_stage() -> bool:
    """Cluster-failure-domain smoke (docs/ROBUSTNESS.md § Cluster
    failure domains): three engines behind the ClusterRouter under a
    past-capacity burst, one hard-killed mid-flight by ``engine_death``
    — fails unless every request reaches a terminal state on both legs,
    at least one in-flight request migrates with its greedy output
    token-for-token identical to the single-engine oracle, goodput
    degrades no worse than proportionally to the capacity lost, and
    survivors show zero ``new_shape`` ledger events. One JSON line,
    like lint/check/obs/chaos."""
    print("== gate: cluster-chaos-smoke (kill one engine, migrate) ==",
          flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DL4J_TPU_FAULTS", None)  # an ambient schedule would double-
    try:                              # inject on top of the harness's own
        proc = subprocess.run(
            [sys.executable, "tools/chaos.py", "--json", "--leg",
             "cluster"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    except subprocess.TimeoutExpired:
        print("   FAIL (cluster-chaos-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (cluster-chaos-smoke exit {proc.returncode})\n"
              f"{tail}")
        return False
    rec = json.loads(line)
    cl = rec.get("cluster") or {}
    kd = cl.get("killed") or {}
    ok = (bool(rec.get("ok"))
          and kd.get("deaths") == 1
          and (kd.get("migrations") or 0) >= 1
          and kd.get("bit_exact")
          and kd.get("unresolved") == 0
          and kd.get("new_shape_events") == 0
          and cl.get("goodput_proportional_ok"))
    full = cl.get("full") or {}
    print(f"   {'ok' if ok else 'FAIL'} (cluster-chaos-smoke: "
          f"{kd.get('submitted')} submitted, {kd.get('deaths')} death, "
          f"{kd.get('migrations')} migrated, bit-exact="
          f"{kd.get('bit_exact')}, goodput "
          f"{kd.get('goodput_tokens_per_sec')} vs full "
          f"{full.get('goodput_tokens_per_sec')} tok/s, new_shape "
          f"{kd.get('new_shape_events')})")
    return bool(ok)


def locktrace_stage() -> bool:
    """Locktrace smoke (docs/LINT.md § graftlock): runtime shadow-lock
    cross-validation of the static lock-order graph — fails if the
    static graph has a cycle, any observed runtime edge falls outside
    its transitive closure (an analyzer blind spot), the combined graph
    is cyclic, or the threaded workload leaves unresolved work. One
    JSON line, like lint/check/obs/chaos."""
    print("== gate: locktrace-smoke (shadow-lock vs static order) ==",
          flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "tools/locktrace.py"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print("   FAIL (locktrace-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (locktrace-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    ok = (bool(rec.get("ok"))
          and rec.get("static_acyclic")
          and not rec.get("unknown_edges")
          and rec.get("combined_cycle") is None
          and len(rec.get("observed_edges") or []) > 0)
    print(f"   {'ok' if ok else 'FAIL'} (locktrace-smoke: "
          f"{rec.get('static_edges')} static edges, "
          f"{len(rec.get('observed_edges') or [])} observed, "
          f"{len(rec.get('unknown_edges') or [])} outside closure, "
          f"combined cycle {rec.get('combined_cycle')})")
    return bool(ok)


def shapetrace_stage() -> bool:
    """Shapetrace smoke (docs/LINT.md § graftshape): runtime
    recompile-ledger cross-validation of the static jit-boundary
    inventory — fails if any ledger event recorded under the
    randomized-shape serving + resumed-training workload is
    unattributed (callsite outside every statically known registration
    span), any new_shape lands in a statically clean module, either leg
    itself pays a new_shape, or the window saw no ledger traffic at
    all. One JSON line, like lint/check/obs/chaos/locktrace."""
    print("== gate: shapetrace-smoke (recompile ledger vs static "
          "jit inventory) ==", flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "tools/shapetrace.py"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print("   FAIL (shapetrace-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (shapetrace-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    ok = (bool(rec.get("ok"))
          and not rec.get("unattributed")
          and not rec.get("new_shape_unexplained")
          and (rec.get("events") or 0) > 0)
    print(f"   {'ok' if ok else 'FAIL'} (shapetrace-smoke: "
          f"{rec.get('events')} ledger events, "
          f"{len(rec.get('unattributed') or [])} unattributed, "
          f"{rec.get('new_shape_total')} new_shape / "
          f"{len(rec.get('new_shape_unexplained') or [])} unexplained)")
    return bool(ok)


def lifetrace_stage() -> bool:
    """Lifetrace smoke (docs/LINT.md § graftlife): runtime
    resource-lifecycle cross-validation of the static ownership
    inventory — fails unless the faults-armed cluster + checkpoint
    workload ends rc-clean (observed acquires - releases == live
    refcount mass, allocator invariants hold), every tracked request
    terminal is counted exactly once, no thread leaks, every observed
    acquire/release callsite lies inside a static inventory span, and
    the recoveries paid zero new_shape. One JSON line, like
    lint/check/obs/chaos/locktrace/shapetrace."""
    print("== gate: lifetrace-smoke (resource tracer vs static "
          "ownership inventory) ==", flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "tools/lifetrace.py"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print("   FAIL (lifetrace-smoke timeout)")
        return False
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("{") and '"tool"' in l), None)
    if line:
        print(f"   {line}")
    if proc.returncode != 0 or line is None:
        tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-15:])
        print(f"   FAIL (lifetrace-smoke exit {proc.returncode})\n{tail}")
        return False
    rec = json.loads(line)
    pages = rec.get("pages") or {}
    terms = rec.get("terminals") or {}
    ok = (bool(rec.get("ok"))
          and pages.get("rc_balanced")
          and not pages.get("invariant_errors")
          and terms.get("exactly_once")
          and not (rec.get("threads") or {}).get("leaked")
          and not (rec.get("callsites") or {}).get("unknown")
          and (rec.get("new_shape_events") or 0) == 0)
    print(f"   {'ok' if ok else 'FAIL'} (lifetrace-smoke: "
          f"{pages.get('acquires')} acquires / {pages.get('releases')} "
          f"releases, live {pages.get('live_refs')}, terminals "
          f"{terms.get('counted')}/{terms.get('tracked')}, "
          f"{len((rec.get('callsites') or {}).get('unknown') or [])} "
          f"unknown callsites, new_shape {rec.get('new_shape_events')})")
    return bool(ok)


def multichip_stage() -> bool:
    """Multichip dryrun on eight virtual CPU devices: a stage that fails or
    hangs fails the gate (__graft_entry__.dryrun_multichip raises)."""
    # outer timeout must exceed the THREE per-stage worker watchdogs
    # (3 × 600s default) so a hung stage is reported by name, not killed
    # from outside
    return run("multichip dryrun (8 virtual CPU devices)",
               [sys.executable, "-c",
                "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
               timeout=2100)


def main() -> int:
    fast = "--fast" in sys.argv
    results = {}

    # static analysis runs in BOTH modes: it is the cheapest stage and the
    # one that catches the hang class before anything can hang
    results["lint"] = lint_stage()
    # graph verification also runs in BOTH modes: build-only (no jit), so
    # it is nearly free and catches importer/optimizer shape regressions
    # before the pytest stage spends minutes compiling them
    results["check"] = check_stage()

    if not fast:  # --fast stays "pytest only" (pre-commit speed)
        results["native"] = native_stage()

    # DL4J_TPU_REQUIRE_NATIVE: under the gate, a missing .so FAILS the
    # ctypes tests instead of silently exercising the numpy fallback
    results["pytest"] = run(
        "pytest (CPU harness)",
        [sys.executable, "-m", "pytest", "tests/", "-q", "-x"],
        env={"DL4J_TPU_REQUIRE_NATIVE": "1"},
        timeout=2400)

    if not fast:
        results["obs"] = obs_stage()
        results["tune"] = tune_stage()
        results["chaos"] = chaos_stage()
        results["trainchaos"] = trainchaos_stage()
        results["cluster"] = cluster_stage()
        results["locktrace"] = locktrace_stage()
        results["shapetrace"] = shapetrace_stage()
        results["lifetrace"] = lifetrace_stage()
        results["aot"] = aot_stage()
        results["multichip"] = multichip_stage()

    failed = [k for k, v in results.items() if not v]
    if failed:
        print(f"\nGATE RED: {failed} — fix before snapshotting")
        return 1
    print("\nGATE GREEN: snapshot allowed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
