"""Cluster-router tests (serving/cluster.py — docs/ROBUSTNESS.md
§ Cluster failure domains).

The properties under test mirror the ``cluster`` gate stage:
  * routing follows prefix affinity and load/health, deterministically;
  * whole-engine death migrates in-flight retryable work to survivors at
    queue FRONT with the ORIGINAL submit time and priority — deadlines
    and ``peek_best_pending`` ordering never invert across a migration;
  * a migrated greedy generation is bit-identical to the single-engine
    oracle, with zero ``new_shape`` ledger events on survivors;
  * every request reaches exactly one labelled terminal state;
  * the frontend's circuit breaker is per-engine: one dead/thrashing
    engine never fast-fails admissions a healthy sibling could serve.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu import faults, observe
from deeplearning4j_tpu.faults import InjectedFault
from deeplearning4j_tpu.models.gpt import (
    GptConfig, GptModel, reference_generate,
)
from deeplearning4j_tpu.serving import (
    ClusterRouter, GenerativeEngine, SLOFrontend,
)
from deeplearning4j_tpu.serving.overload import _serving_new_shape_count

CFG = GptConfig.tiny()
MODEL = GptModel(CFG, seed=1)

PROMPTS = [np.array([3, 5, 7, 9], np.int32),
           np.array([11, 2], np.int32),
           np.array([42, 43, 44, 45, 46, 47], np.int32)]


def make_engine(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 6)
    kw.setdefault("max_prompt", 16)
    kw.setdefault("seed", 3)
    kw.setdefault("restart_backoff_s", 0.0)
    return GenerativeEngine(MODEL, **kw)


def make_router(n=2, router_kw=None, **ekw):
    engines = [make_engine(**ekw) for _ in range(n)]
    return ClusterRouter(engines, **(router_kw or {}))


def evicted_counts():
    out = {}
    for inst in observe.metrics().instruments():
        if inst.name == "dl4j_tpu_serving_evicted_total" and inst.labels:
            out[dict(inst.labels)["reason"]] = int(inst.value)
    return out


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# routing: load, affinity, health
# ---------------------------------------------------------------------------


class TestRouting:
    def test_engines_renumbered_and_least_loaded_wins(self):
        r = make_router(2)
        assert [e.engine_id for e in r.engines] == [0, 1]
        # two queued requests make engine 0 the loaded one
        r.engines[0].submit(PROMPTS[0], max_new_tokens=4)
        r.engines[0].submit(PROMPTS[1], max_new_tokens=4)
        r.submit(PROMPTS[2], max_new_tokens=4)
        assert len(r.engines[1].scheduler.pending) == 1
        assert len(r.engines[0].scheduler.pending) == 2

    def test_prefix_affinity_routes_to_cached_engine(self):
        r = make_router(2, prefix_pages=8)
        shared = np.arange(1, 13, dtype=np.int32)          # 12 tokens
        # warm ONLY engine 1's radix tree; loads stay equal (drained)
        r.engines[1].generate([shared], max_new_tokens=1, eos_token=-1)
        m = observe.metrics()
        before = m.counter("dl4j_tpu_cluster_routed_total",
                           engine="1", reason="affinity").value
        prompt = np.concatenate([shared, [99, 100]]).astype(np.int32)
        r.submit(prompt, max_new_tokens=4)
        assert len(r.engines[1].scheduler.pending) == 1
        assert len(r.engines[0].scheduler.pending) == 0
        assert m.counter("dl4j_tpu_cluster_routed_total",
                         engine="1", reason="affinity").value == before + 1

    def test_affinity_yields_to_load_imbalance(self):
        """Cache locality must not pile work onto a drowning engine: past
        ``affinity_max_imbalance`` waves of extra load the cached engine
        loses to the idle one."""
        r = make_router(2, prefix_pages=8,
                        router_kw=dict(affinity_max_imbalance=2.0))
        shared = np.arange(1, 13, dtype=np.int32)
        r.engines[1].generate([shared], max_new_tokens=1, eos_token=-1)
        for _ in range(5):  # 5 queued / 2 slots = 2.5 waves > 2.0
            r.engines[1].submit(PROMPTS[0], max_new_tokens=4)
        prompt = np.concatenate([shared, [99, 100]]).astype(np.int32)
        r.submit(prompt, max_new_tokens=4)
        assert len(r.engines[0].scheduler.pending) == 1

    def test_dead_engines_excluded_until_none_left(self):
        r = make_router(2)
        r._on_engine_death(r.engines[0], RuntimeError("boom"))
        assert [e.engine_id for e in r.live_engines()] == [1]
        r.submit(PROMPTS[0], max_new_tokens=4)
        assert len(r.engines[1].scheduler.pending) == 1
        assert len(r.engines[0].scheduler.pending) == 0
        r._on_engine_death(r.engines[1], RuntimeError("boom"))
        with pytest.raises(RuntimeError, match="no live engine"):
            r.submit(PROMPTS[0], max_new_tokens=4)

    def test_restart_thrash_quarantines_but_never_strands(self):
        r = make_router(2, router_kw=dict(quarantine_restarts=2,
                                          quarantine_window_s=60.0,
                                          quarantine_cooldown_s=30.0))
        q0 = observe.metrics().counter(
            "dl4j_tpu_cluster_quarantined_total").value
        r.engines[0].restarts = 2       # a thrash burst inside the window
        r.submit(PROMPTS[0], max_new_tokens=4)
        assert len(r.engines[1].scheduler.pending) == 1
        assert observe.metrics().counter(
            "dl4j_tpu_cluster_quarantined_total").value == q0 + 1
        # quarantine deprioritises, never strands: with the healthy
        # sibling dead, the quarantined engine still serves
        r._on_engine_death(r.engines[1], RuntimeError("boom"))
        r.submit(PROMPTS[1], max_new_tokens=4)
        assert len(r.engines[0].scheduler.pending) >= 1


# ---------------------------------------------------------------------------
# the engine_death fault point (satellite)
# ---------------------------------------------------------------------------


class TestEngineDeathFault:
    def test_engine_death_is_hard_and_unrestartable(self):
        """Without a router, engine_death spends the restart budget and
        fails every submitted future — loudly, not as a hang."""
        faults.arm("engine_death", prob=1.0, max_fires=1)
        eng = make_engine()
        eng.start()
        futs = [eng.submit(p, max_new_tokens=4) for p in PROMPTS]
        with pytest.raises(InjectedFault, match="engine_death"):
            for f in futs:
                f.result(timeout=60)
        assert eng.restarts == eng.max_restarts
        assert isinstance(eng._error, InjectedFault)
        assert eng._error.point == "engine_death"
        assert observe.metrics().counter(
            "dl4j_tpu_faults_injected_total", point="engine_death").value >= 1
        eng.stop()


# ---------------------------------------------------------------------------
# cross-engine migration
# ---------------------------------------------------------------------------


class TestMigration:
    def test_kill_one_engine_mid_flight_bit_identical_to_oracle(self):
        """The tentpole property, end to end: one engine hard-killed
        mid-flight, every request terminal, >= 1 in-flight migration, the
        greedy outputs token-for-token the single-engine oracle's, and
        zero ``new_shape`` on the survivor."""
        r = make_router(2)
        prompts = [np.arange(1, 9, dtype=np.int32) + i for i in range(6)]
        for e in r.engines:  # compile before the clock starts
            e.generate([prompts[0]], max_new_tokens=2, eos_token=-1)
        before_terminal = sum(evicted_counts().values())
        new_shape0 = _serving_new_shape_count()
        r.start()
        faults.arm("slow_decode", prob=1.0)       # keep work in flight
        faults.arm("engine_death", prob=1.0, after_n=4, max_fires=1)
        futs = [r.submit(p, max_new_tokens=6, eos_token=-1, max_retries=3)
                for p in prompts]
        res = [f.result(timeout=120) for f in futs]
        faults.reset()
        assert r.deaths == 1 and len(r.live_engines()) == 1
        assert r.migrations >= 1
        assert all(x.finish_reason == "length" for x in res)
        for p, x in zip(prompts, res):
            np.testing.assert_array_equal(
                x.tokens, reference_generate(MODEL.params, CFG, p, 6))
        assert _serving_new_shape_count() == new_shape0
        # terminal vocabulary: every request exactly one labelled counter
        assert (sum(evicted_counts().values())
                == before_terminal + len(prompts))
        r.check_invariants()
        r.stop()

    def test_migrated_request_keeps_submit_time_and_priority(self):
        """The bugfix satellite: migration re-admits with the ORIGINAL
        submit time and priority, so the request expires at the same wall
        deadline it would have on its first engine and the pending order
        never inverts."""
        r = make_router(2)
        e0, e1 = r.engines
        fut = e0.submit(PROMPTS[0], max_new_tokens=64, eos_token=-1,
                        deadline_s=0.25, max_retries=2, priority=2)
        e0.step()                       # admit + first token: IN FLIGHT
        (slot,) = e0.scheduler.active_slots()
        st = e0.scheduler.slots[slot]
        orig_submit_t = st.submit_t
        r._on_engine_death(e0, RuntimeError("boom"))
        (item,) = e1.scheduler.pending_snapshot()
        req, mig_fut, submit_t = item
        assert mig_fut is fut               # the SAME future, not a chain
        assert submit_t == orig_submit_t    # deadline keeps counting
        assert req.priority == 2            # ordering never inverts
        assert req.retries_used == 1        # migration charged one retry
        # the deadline is measured from the ORIGINAL submit: once that
        # wall instant passes, the survivor's sweep retires it
        while time.perf_counter() - orig_submit_t <= 0.25:
            time.sleep(0.01)
        e1.step()
        assert fut.result(timeout=10).finish_reason == "deadline"

    def test_in_flight_without_retry_budget_fails_terminally(self):
        r = make_router(2)
        e0, e1 = r.engines
        before = evicted_counts().get("error", 0)
        fut = e0.submit(PROMPTS[0], max_new_tokens=64, eos_token=-1,
                        max_retries=0)
        e0.step()
        r._on_engine_death(e0, RuntimeError("boom"))
        assert fut.result(timeout=10).finish_reason == "error"
        assert e1.scheduler.pending_snapshot() == []
        assert evicted_counts().get("error", 0) == before + 1

    def test_pending_migrates_in_order_without_retry_charge(self):
        r = make_router(2)
        e0, e1 = r.engines
        futs = [e0.submit(p, max_new_tokens=4) for p in PROMPTS]
        r._on_engine_death(e0, RuntimeError("boom"))
        items = e1.scheduler.pending_snapshot()
        assert [it[1] for it in items] == futs     # order preserved
        assert all(it[0].retries_used == 0 for it in items)

    def test_no_survivors_every_request_terminal_error(self):
        r = make_router(1)
        futs = [r.engines[0].submit(p, max_new_tokens=4) for p in PROMPTS]
        r._on_engine_death(r.engines[0], RuntimeError("boom"))
        assert all(f.result(timeout=10).finish_reason == "error"
                   for f in futs)

    def test_pinned_prefix_rewarms_on_destination(self):
        """A pinned per-class prefix lost with the dead engine is carried
        back onto the destination behind the migrated work."""
        r = make_router(2, prefix_pages=8)
        e0, e1 = r.engines
        shared = np.arange(1, 13, dtype=np.int32)
        r.prewarm_prefix(shared, pin=True)
        e1.prefix.clear()               # cold destination (intents survive)
        fut = e0.submit(PROMPTS[0], max_new_tokens=4, eos_token=-1,
                        max_retries=2)
        e0.step()
        r._on_engine_death(e0, RuntimeError("boom"))
        while e1.scheduler.has_work():  # drain migrated + re-warm work
            e1.step()
        assert fut.result(timeout=10).finish_reason == "length"
        probe = np.concatenate([shared, [99, 100]]).astype(np.int32)
        m = e1.prefix.match(probe)
        assert m is not None and m.matched >= 8
        assert e1.prefix.pinned_pages >= 1


# ---------------------------------------------------------------------------
# lifecycle + SLO frontend composition
# ---------------------------------------------------------------------------


class TestLifecycleAndFrontend:
    def test_threaded_lifecycle(self):
        r = make_router(2).start()
        r.start()                                        # idempotent
        fut = r.submit(PROMPTS[0], max_new_tokens=3, eos_token=-1)
        assert fut.result(timeout=60).finish_reason == "length"
        r.stop()
        r.stop()                                         # idempotent
        assert all(e.stopped_cleanly for e in r.engines)
        with pytest.raises(RuntimeError):
            r.submit(PROMPTS[0], max_new_tokens=3)

    def test_frontend_breaker_is_per_engine(self):
        """One thrashing engine must not fast-fail admissions the healthy
        sibling could serve; only ALL engines open fast-fails."""
        r = make_router(2)
        fe = SLOFrontend(r, breaker_restarts=2, breaker_window_s=60.0,
                         breaker_cooldown_s=600.0)
        r.engines[0].restarts = 2
        fut = fe.submit(PROMPTS[0], max_new_tokens=4)
        assert not fut.done()            # admitted (queued), NOT fast-failed
        assert fe.breaker_opens == 1 and not fe.breaker_open
        assert observe.metrics().gauge(
            "dl4j_tpu_slo_breaker_open").value == 0.5
        r.engines[1].restarts = 2
        fut2 = fe.submit(PROMPTS[1], max_new_tokens=4)
        assert fut2.result(timeout=10).finish_reason == "error"
        assert fe.breaker_open and fe.breaker_opens == 2
        assert observe.metrics().gauge(
            "dl4j_tpu_slo_breaker_open").value == 1.0

    def test_frontend_single_engine_breaker_regression(self):
        """The pre-cluster path: a single engine's open breaker still
        fast-fails with the historical gauge values."""
        eng = make_engine()
        fe = SLOFrontend(eng, breaker_restarts=2, breaker_window_s=60.0,
                         breaker_cooldown_s=600.0)
        eng.restarts = 2
        fut = fe.submit(PROMPTS[0], max_new_tokens=4)
        assert fut.result(timeout=10).finish_reason == "error"
        assert fe.breaker_open and fe.breaker_opens == 1
        assert observe.metrics().gauge(
            "dl4j_tpu_slo_breaker_open").value == 1.0

    def test_frontend_capacity_tracks_live_engines(self):
        """The frontend's wave estimates see the cluster's LIVE capacity:
        a death shrinks ``max_slots`` so the ladder degrades
        proportionally instead of pretending dead slots exist."""
        r = make_router(2)
        assert r.scheduler.max_slots == 4
        r._on_engine_death(r.engines[0], RuntimeError("boom"))
        assert r.scheduler.max_slots == 2
