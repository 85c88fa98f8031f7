"""SLO admission frontend tests (docs/SERVING.md § SLO admission
frontend).

The properties under test mirror the ``slo`` gate stage:
  * admission control is a POLICY, not an accident: token buckets,
    concurrency caps, per-class queue bounds and predictive early shed
    each deny for their own counted reason, and every denial is a
    TERMINAL result — never an exception, never a hang;
  * the pending queue is priority-ordered and shed-lowest-first, and
    supervisor retries preserve class/priority/submit time;
  * the degradation ladder escalates immediately, de-escalates with
    hysteresis, trims only degradable classes, and records the trim on
    the result;
  * the circuit breaker fast-fails admissions while the engine thrashes
    and re-admits after the cooldown;
  * every terminal path — engine retires, queue fails, frontend sheds —
    increments the ONE ``dl4j_tpu_serving_evicted_total{reason}`` family
    exactly once with a reason from ``FINISH_REASONS``;
  * a threaded mixed-class overload run ends with every request
    terminal, interactive p99 TTFT inside its SLO while batch sheds, and
    ZERO ``new_shape`` ledger events across all ladder transitions.
"""

import threading
import time
import types

import numpy as np
import pytest

from deeplearning4j_tpu import faults, observe
from deeplearning4j_tpu.serving import (
    ClassPolicy, GenerationRequest, LadderThresholds, SLOFrontend,
)
from deeplearning4j_tpu.serving.scheduler import (
    FINISH_REASONS, SlotScheduler, count_terminal,
)

PROMPT = np.array([3, 5, 7], np.int32)


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class StubEngine:
    """The engine surface the frontend touches, minus the device: a real
    SlotScheduler (pure host-side), a restarts attr, and a
    submit_request that queues without serving."""

    def __init__(self, max_slots: int = 2):
        self.scheduler = SlotScheduler(max_slots)
        self.restarts = 0
        self.cfg = types.SimpleNamespace(eos_token=-1, vocab_size=64)
        self.default_deadline_s = None
        self.submitted = []

    def validate_request(self, req):
        pass  # the real engine's prompt-bucket/vocab checks

    def submit_request(self, req):
        self.submitted.append(req)
        return self.scheduler.submit(req)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
    faults.reset()
    observe.reset()
    yield
    faults.reset()
    observe.reset()


def evicted_counts():
    out = {}
    for inst in observe.metrics().instruments():
        if inst.name == "dl4j_tpu_serving_evicted_total" and inst.labels:
            out[dict(inst.labels)["reason"]] = int(inst.value)
    return out


def slo_shed_counts():
    out = {}
    for inst in observe.metrics().instruments():
        if inst.name == "dl4j_tpu_slo_shed_total" and inst.labels:
            lbl = dict(inst.labels)
            out[(lbl["class"], lbl["reason"])] = int(inst.value)
    return out


# ---------------------------------------------------------------------------
# admission control: buckets, caps, predictive shed
# ---------------------------------------------------------------------------


class TestAdmissionControl:
    def test_unknown_class_raises(self):
        fe = SLOFrontend(StubEngine())
        with pytest.raises(ValueError, match="unknown SLO class"):
            fe.submit(PROMPT, slo_class="platinum")

    def test_token_bucket_rate_limit(self):
        clock = FakeClock()
        classes = {"standard": ClassPolicy("standard", priority=1,
                                           rate=1.0, burst=2)}
        fe = SLOFrontend(StubEngine(), classes=classes, clock=clock)
        futs = [fe.submit(PROMPT) for _ in range(3)]
        # burst of 2 admitted, the third shed terminally as rate_limit
        assert not futs[0].done() and not futs[1].done()
        res = futs[2].result(timeout=0)
        assert res.finish_reason == "shed"
        assert slo_shed_counts()[("standard", "rate_limit")] == 1
        # the bucket refills with (fake) time — one token per second
        clock.t += 1.0
        assert not fe.submit(PROMPT).done()
        res = fe.submit(PROMPT).result(timeout=0)
        assert res.finish_reason == "shed"

    def test_concurrency_cap(self):
        classes = {"batch": ClassPolicy("batch", priority=2,
                                        max_concurrent=2)}
        eng = StubEngine()
        fe = SLOFrontend(eng, classes=classes)
        f1 = fe.submit(PROMPT, slo_class="batch")
        fe.submit(PROMPT, slo_class="batch")
        shed = fe.submit(PROMPT, slo_class="batch")
        assert shed.result(timeout=0).finish_reason == "shed"
        assert slo_shed_counts()[("batch", "concurrency")] == 1
        # completing one in-flight request frees a slot in the cap
        eng.scheduler.fail_pending(RuntimeError("drain"), reason="error")
        assert f1.done()
        assert not fe.submit(PROMPT, slo_class="batch").done()

    def test_predictive_shed_on_hopeless_deadline(self):
        eng = StubEngine(max_slots=2)
        fe = SLOFrontend(eng, est_tokens_per_request=16.0)
        fe._rolling.p50 = 0.1  # 100ms/step signal
        # queue 10 deep ahead of us -> ~8 waves x 16 tokens x 100ms >> 0.5s
        for _ in range(10):
            eng.scheduler.submit(GenerationRequest(prompt=PROMPT))
        fut = fe.submit(PROMPT, deadline_s=0.5)
        assert fut.result(timeout=0).finish_reason == "shed"
        assert slo_shed_counts()[("standard", "predicted_deadline")] == 1

    def test_no_predictive_shed_without_latency_signal(self):
        """Cold start: no decode histogram samples and no prior — the
        frontend must never early-shed blind."""
        eng = StubEngine(max_slots=1)
        fe = SLOFrontend(eng)
        for _ in range(50):
            eng.scheduler.submit(GenerationRequest(prompt=PROMPT))
        assert fe.estimate_ttft_s() is None
        assert not fe.submit(PROMPT, deadline_s=0.001).done()

    def test_priority_aware_estimate(self):
        """An interactive arrival jumps the queue — its TTFT estimate
        counts only same-or-better-priority work ahead."""
        eng = StubEngine(max_slots=2)
        fe = SLOFrontend(eng)
        fe._rolling.p50 = 0.1
        for _ in range(10):
            eng.scheduler.submit(
                GenerationRequest(prompt=PROMPT, priority=2))
        est_batch = fe.estimate_ttft_s(priority=2)
        est_interactive = fe.estimate_ttft_s(priority=0)
        assert est_interactive < est_batch


# ---------------------------------------------------------------------------
# priority ordering + shed-lowest-first
# ---------------------------------------------------------------------------


class TestPriorityQueue:
    def test_best_pending_orders_by_priority_then_fifo(self):
        sched = SlotScheduler(2)
        batch = GenerationRequest(prompt=PROMPT, priority=2)
        std1 = GenerationRequest(prompt=PROMPT, priority=1)
        std2 = GenerationRequest(prompt=PROMPT, priority=1)
        sched.submit(batch)
        sched.submit(std1)
        sched.submit(std2)
        item = sched.peek_best_pending()
        assert item[0] is std1  # best priority, earliest submit
        assert sched.remove_pending(item)
        assert sched.peek_best_pending()[0] is std2
        assert not sched.remove_pending(item)  # already gone

    def test_steal_lowest_pending(self):
        sched = SlotScheduler(2)
        b1 = GenerationRequest(prompt=PROMPT, priority=2)
        b2 = GenerationRequest(prompt=PROMPT, priority=2)
        s1 = GenerationRequest(prompt=PROMPT, priority=1)
        for r in (b1, s1, b2):
            sched.submit(r)
        # nothing strictly lower-priority than batch itself
        assert sched.steal_lowest_pending(2) is None
        # an interactive arrival displaces the NEWEST worst-class item
        victim = sched.steal_lowest_pending(0)
        assert victim[0] is b2
        assert len(sched.pending) == 2

    def test_queue_bound_sheds_lowest_class_first(self):
        eng = StubEngine()
        fe = SLOFrontend(eng, max_queue_total=2)
        batch_fut = fe.submit(PROMPT, slo_class="batch")
        fe.submit(PROMPT, slo_class="standard")
        # the queue is full; an interactive arrival displaces batch
        inter_fut = fe.submit(PROMPT, slo_class="interactive")
        assert batch_fut.result(timeout=0).finish_reason == "shed"
        assert batch_fut.result(timeout=0).slo_class == "batch"
        assert not inter_fut.done()
        assert slo_shed_counts()[("batch", "queue_full")] == 1
        # a batch arrival with nothing worse queued sheds ITSELF
        fut = fe.submit(PROMPT, slo_class="batch")
        assert fut.result(timeout=0).finish_reason == "shed"

    def test_per_class_queue_bound(self):
        classes = {"batch": ClassPolicy("batch", priority=2, max_queued=2)}
        fe = SLOFrontend(StubEngine(), classes=classes)
        fe.submit(PROMPT, slo_class="batch")
        fe.submit(PROMPT, slo_class="batch")
        fut = fe.submit(PROMPT, slo_class="batch")
        assert fut.result(timeout=0).finish_reason == "shed"
        assert slo_shed_counts()[("batch", "queue_full")] == 1


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------


class TestLadder:
    def _fe(self, q_p99):
        fe = SLOFrontend(StubEngine(), thresholds=LadderThresholds(
            degraded_queue=8, shedding_queue=16,
            degraded_p99_s=0.5, shedding_p99_s=2.0))
        fe._signals = lambda: q_p99[0]  # noqa: test hook
        return fe

    def test_escalation_and_hysteresis(self):
        sig = [(0, None)]
        fe = self._fe(sig)
        fe._update_state(0.0)
        assert fe.state == "ok"
        sig[0] = (20, None)  # past the shedding enter threshold
        fe._update_state(0.0)
        assert fe.state == "shedding"
        # inside the hysteresis band: 9 < 16 but > 0.5 * 16 — stays
        sig[0] = (9, None)
        fe._update_state(0.0)
        assert fe.state == "shedding"
        # below the exit band: one level at a time
        sig[0] = (7, None)
        fe._update_state(0.0)
        assert fe.state == "degraded"
        sig[0] = (7, None)  # above degraded exit (4) — stays degraded
        fe._update_state(0.0)
        assert fe.state == "degraded"
        sig[0] = (2, None)
        fe._update_state(0.0)
        assert fe.state == "ok"
        assert fe.states_visited == {"ok", "degraded", "shedding"}
        # transitions were counted and the gauge tracks the level
        trans = {dict(i.labels).get("to"): int(i.value)
                 for i in observe.metrics().instruments()
                 if i.name == "dl4j_tpu_slo_transitions_total" and i.labels}
        assert trans == {"shedding": 1, "degraded": 1, "ok": 1}
        assert observe.metrics().gauge("dl4j_tpu_slo_state").value == 0.0

    def test_p99_signal_escalates(self):
        sig = [(0, 3.0)]  # rolling decode p99 of 3s
        fe = self._fe(sig)
        fe._update_state(0.0)
        assert fe.state == "shedding"

    def test_degraded_trims_low_classes_only(self):
        eng = StubEngine()
        fe = SLOFrontend(eng, degraded_max_new_tokens=4)
        fe._signals = lambda: (100, None)  # force shedding-level pressure
        fe.submit(PROMPT, slo_class="standard", max_new_tokens=32,
                  top_k=40, top_p=0.9)
        req = eng.submitted[-1]
        assert req.degraded and req.max_new_tokens == 4
        assert req.top_k == 0 and req.top_p == 1.0
        # interactive is not degradable in the default ladder
        fe.submit(PROMPT, slo_class="interactive", max_new_tokens=32,
                  top_k=40, top_p=0.9)
        req = eng.submitted[-1]
        assert not req.degraded and req.max_new_tokens == 32
        assert req.top_k == 40
        deg = int(observe.metrics().family_total(
            "dl4j_tpu_slo_degraded_total"))
        assert deg == 1

    def test_shedding_rejects_batch_outright(self):
        eng = StubEngine()
        fe = SLOFrontend(eng)
        fe._signals = lambda: (100, None)
        fut = fe.submit(PROMPT, slo_class="batch")
        assert fut.result(timeout=0).finish_reason == "shed"
        assert slo_shed_counts()[("batch", "shedding_state")] == 1
        # interactive still admits in shedding
        assert not fe.submit(PROMPT, slo_class="interactive").done()

    def test_degraded_flag_propagates_to_result(self):
        eng = StubEngine()
        fe = SLOFrontend(eng)
        fe._signals = lambda: (100, None)
        fut = fe.submit(PROMPT, slo_class="standard")
        item = eng.scheduler.peek_best_pending()
        eng.scheduler.remove_pending(item)
        eng.scheduler.admit(0, item[0], item[1], item[2], first_token=1,
                            now=item[2])
        res = eng.scheduler.retire(0, "length")
        assert res.degraded and res.slo_class == "standard"
        assert fut.result(timeout=0).degraded


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_opens_on_restart_rate_and_cools_down(self):
        clock = FakeClock()
        eng = StubEngine()
        fe = SLOFrontend(eng, breaker_window_s=60.0, breaker_restarts=3,
                         breaker_cooldown_s=5.0, clock=clock)
        assert not fe.submit(PROMPT).done()
        eng.restarts = 3  # supervisor thrash
        fut = fe.submit(PROMPT)
        res = fut.result(timeout=0)
        assert res.finish_reason == "error"  # fast-fail, not shed
        assert fe.breaker_open and fe.breaker_opens == 1
        assert slo_shed_counts()[("standard", "circuit_open")] == 1
        assert observe.metrics().gauge(
            "dl4j_tpu_slo_breaker_open").value == 1.0
        # still open inside the cooldown
        clock.t += 4.0
        assert fe.submit(PROMPT).done()
        # past the cooldown with no NEW restarts: admissions resume
        clock.t += 2.0
        assert not fe.submit(PROMPT).done()
        assert observe.metrics().gauge(
            "dl4j_tpu_slo_breaker_open").value == 0.0

    def test_reopens_only_on_new_restarts(self):
        clock = FakeClock()
        eng = StubEngine()
        fe = SLOFrontend(eng, breaker_restarts=2, breaker_cooldown_s=1.0,
                         clock=clock)
        eng.restarts = 2
        assert fe.submit(PROMPT).done()
        clock.t += 2.0
        assert not fe.submit(PROMPT).done()  # old thrash burst consumed
        eng.restarts = 4
        assert fe.submit(PROMPT).done()
        assert fe.breaker_opens == 2


# ---------------------------------------------------------------------------
# one terminal vocabulary (satellite)
# ---------------------------------------------------------------------------


class TestTerminalVocabulary:
    def test_count_terminal_rejects_unknown_reasons(self):
        with pytest.raises(ValueError, match="unknown finish reason"):
            count_terminal("vibes")

    def test_frontend_sheds_count_exactly_once(self):
        fe = SLOFrontend(StubEngine(), max_queue_total=0)
        before = evicted_counts()
        fut = fe.submit(PROMPT, slo_class="batch")
        assert fut.result(timeout=0).finish_reason == "shed"
        after = evicted_counts()
        assert after.get("shed", 0) - before.get("shed", 0) == 1
        assert sum(after.values()) - sum(before.values()) == 1

    def test_breaker_error_counts_exactly_once(self):
        eng = StubEngine()
        fe = SLOFrontend(eng, breaker_restarts=1)
        eng.restarts = 1
        before = evicted_counts()
        fe.submit(PROMPT)
        after = evicted_counts()
        assert after.get("error", 0) - before.get("error", 0) == 1
        assert sum(after.values()) - sum(before.values()) == 1

    def test_fail_pending_and_fail_all_label_reasons(self):
        sched = SlotScheduler(2)
        sched.submit(GenerationRequest(prompt=PROMPT))
        before = evicted_counts()
        sched.fail_pending(RuntimeError("stop hung"), reason="stopped")
        after = evicted_counts()
        assert after.get("stopped", 0) - before.get("stopped", 0) == 1
        from concurrent.futures import Future
        fut: "Future" = Future()
        sched.admit(0, GenerationRequest(prompt=PROMPT), fut, 0.0, 1, 0.0)
        sched.submit(GenerationRequest(prompt=PROMPT))
        before = evicted_counts()
        sched.fail_all(RuntimeError("died"))
        after = evicted_counts()
        assert after.get("error", 0) - before.get("error", 0) == 2

    def test_already_done_futures_not_double_counted(self):
        sched = SlotScheduler(2)
        fut = sched.submit(GenerationRequest(prompt=PROMPT))
        item = sched.peek_best_pending()
        # frontend-style displacement completes the future first...
        stolen = sched.steal_lowest_pending(0)
        assert stolen is item
        from deeplearning4j_tpu.serving.scheduler import GenerationResult
        fut.set_result(GenerationResult(
            tokens=np.zeros((0,), np.int32), finish_reason="shed",
            prompt_len=0, ttft_s=None, intertoken_s=[]))
        before = evicted_counts()
        sched.fail_pending(RuntimeError("x"))  # queue already empty
        assert evicted_counts() == before

    def test_all_reason_labels_are_in_finish_reasons(self):
        """Every reason label the counter family has ever seen must come
        from the shared vocabulary."""
        fe = SLOFrontend(StubEngine(), max_queue_total=0)
        fe.submit(PROMPT, slo_class="batch")
        sched = SlotScheduler(1)
        sched.submit(GenerationRequest(prompt=PROMPT))
        sched.fail_pending(RuntimeError("x"), reason="stopped")
        for reason in evicted_counts():
            assert reason in FINISH_REASONS


# ---------------------------------------------------------------------------
# burst_arrival fault hook
# ---------------------------------------------------------------------------


class TestBurstArrival:
    def test_burst_injects_tracked_lowest_class_arrivals(self):
        eng = StubEngine(max_slots=2)
        fe = SLOFrontend(eng, burst_size=3)
        faults.arm("burst_arrival", prob=1.0, max_fires=1)
        fe.submit(PROMPT, slo_class="interactive")
        assert len(fe.burst_futures) == 3
        # injected arrivals are LOWEST class and pass through admission
        # (here: queued on the stub, ready to shed/serve like any other)
        burst_reqs = [r for r in eng.submitted if r.slo_class == "batch"]
        assert len(burst_reqs) == 3
        fired = int(observe.metrics().counter(
            "dl4j_tpu_faults_injected_total", point="burst_arrival").value)
        assert fired == 1
        # one fire only — the next submit injects nothing more
        fe.submit(PROMPT, slo_class="interactive")
        assert len(fe.burst_futures) == 3

    def test_burst_point_is_registered(self):
        assert "burst_arrival" in faults.FAULT_POINTS


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------


class TestSummary:
    def test_slo_section_in_summary(self):
        fe = SLOFrontend(StubEngine(), max_queue_total=0)
        fe.submit(PROMPT, slo_class="standard")  # sheds (queue bound 0)
        s = observe.summary()
        assert "slo" in s
        assert s["slo"]["state"] in (0, 1, 2)
        assert s["slo"]["shed"].get("standard/queue_full") == 1
        assert "breaker_open" in s["slo"]

    def test_eagerly_registered_metric_names(self):
        rendered = observe.metrics().render_prometheus()
        for name in ("dl4j_tpu_slo_state", "dl4j_tpu_slo_breaker_open",
                     "dl4j_tpu_slo_admitted_total", "dl4j_tpu_slo_shed_total",
                     "dl4j_tpu_slo_degraded_total",
                     "dl4j_tpu_slo_transitions_total"):
            assert name in rendered


# ---------------------------------------------------------------------------
# GL010 hygiene (satellite): serving timing is monotonic-only
# ---------------------------------------------------------------------------


class TestWallClockHygiene:
    def test_serving_sources_never_call_wall_clock(self):
        """``time.time()`` anywhere in serving/ would let a wall-clock
        jump expire deadlines or corrupt TTFT — the timing contract is
        perf_counter only (scheduler docstring, graftlint GL010)."""
        import deeplearning4j_tpu.serving as serving_pkg
        import glob
        import os
        pkg_dir = os.path.dirname(serving_pkg.__file__)
        for path in sorted(glob.glob(os.path.join(pkg_dir, "*.py"))):
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            assert "time.time(" not in src, (
                f"{os.path.basename(path)} uses wall-clock time.time(); "
                f"serving timing must be time.perf_counter (GL010)")

    def test_serving_is_gl010_lint_clean(self):
        """The real linter, rule GL010 only, over the serving package —
        a regression reintroducing wall-clock durations fails here
        without waiting for the repo-wide lint gate."""
        from deeplearning4j_tpu.lint.core import lint_paths
        import deeplearning4j_tpu
        import os
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(deeplearning4j_tpu.__file__)))
        findings = lint_paths(["deeplearning4j_tpu/serving"], repo_root,
                              rules=["GL010"])
        assert not findings, [f"{f.path}:{f.line} {f.message}"
                              for f in findings]


# ---------------------------------------------------------------------------
# integration: real engine behind the frontend
# ---------------------------------------------------------------------------


class TestFrontendEngineIntegration:
    @staticmethod
    def _engine(**kw):
        from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
        from deeplearning4j_tpu.serving import GenerativeEngine
        model = GptModel(GptConfig.tiny(), seed=1)
        kw.setdefault("max_slots", 2)
        kw.setdefault("page_size", 8)
        kw.setdefault("max_pages_per_seq", 6)
        kw.setdefault("max_prompt", 16)
        kw.setdefault("seed", 3)
        kw.setdefault("restart_backoff_s", 0.0)
        return GenerativeEngine(model, **kw)

    def test_priority_admission_order(self):
        """With one slot, a later-submitted interactive request admits
        BEFORE earlier batch requests — the pending queue is
        priority-ordered, not FIFO."""
        eng = self._engine(max_slots=1)
        fe = SLOFrontend(eng)
        done_order = []
        futs = []
        for i, cls in enumerate(["batch", "batch", "interactive"]):
            fut = fe.submit(PROMPT, slo_class=cls, max_new_tokens=2,
                            eos_token=-1)
            fut.add_done_callback(
                lambda _f, i=i: done_order.append(i))
            futs.append(fut)
        while eng.scheduler.has_work():
            eng.step()
        assert all(f.result(timeout=0).finish_reason == "length"
                   for f in futs)
        assert done_order[0] == 2  # interactive finished first

    def test_retry_preserves_class_and_submit_time(self):
        """A supervisor crash-retry re-queues the SAME request object:
        class, priority and submit time survive, so recovery re-admits
        it AHEAD of younger work and the result still carries its
        class."""
        eng = self._engine()
        fe = SLOFrontend(eng)
        # warm the compiled paths so the armed crash hits generation
        eng.generate([PROMPT[:2]], max_new_tokens=2, eos_token=-1)
        faults.arm("decode_step_error", prob=1.0, after_n=1, max_fires=1)
        eng.start()
        try:
            fut = fe.submit(PROMPT, slo_class="interactive",
                            max_new_tokens=6, eos_token=-1, max_retries=2)
            res = fut.result(timeout=600)
        finally:
            eng.stop()
        assert res.finish_reason == "length"
        assert res.slo_class == "interactive"
        assert eng.restarts == 1

    def test_threaded_overload_mixed_classes(self):
        """Satellite: saturate a tiny engine with mixed-class traffic.
        (a) every request reaches a terminal state; (b) interactive p99
        TTFT stays under its SLO while batch sheds; (c) ZERO new_shape
        recompiles across all degradation transitions."""
        eng = self._engine(max_slots=2)
        fe = SLOFrontend(
            eng,
            thresholds=LadderThresholds(degraded_queue=3, shedding_queue=8),
            max_queue_total=8,
            degraded_max_new_tokens=2,
            classes={
                "interactive": ClassPolicy("interactive", priority=0,
                                           degradable=False),
                "batch": ClassPolicy("batch", priority=2, max_queued=4,
                                     reject_in_shedding=True),
            })
        eng.generate([PROMPT[:2]], max_new_tokens=2, eos_token=-1)  # warm
        new_shape_before = sum(
            1 for e in observe.ledger().events()
            if e.graph == "serving" and e.cause == "new_shape")
        eng.start()
        inter_futs, batch_futs = [], []
        stop_flood = threading.Event()

        def flood_batch():
            r = np.random.RandomState(7)
            while not stop_flood.is_set():
                p = r.randint(1, 50, size=3).astype(np.int32)
                batch_futs.append(
                    fe.submit(p, slo_class="batch", max_new_tokens=8,
                              eos_token=-1))
                time.sleep(0.002)

        flooder = threading.Thread(target=flood_batch, daemon=True)
        try:
            flooder.start()
            r = np.random.RandomState(11)
            for _ in range(12):
                p = r.randint(1, 50, size=3).astype(np.int32)
                inter_futs.append(
                    fe.submit(p, slo_class="interactive", max_new_tokens=4,
                              eos_token=-1))
                time.sleep(0.05)
            stop_flood.set()
            flooder.join(timeout=30)
            inter_res = [f.result(timeout=600) for f in inter_futs]
            batch_res = [f.result(timeout=600) for f in batch_futs]
        finally:
            stop_flood.set()
            eng.stop()
        # (a) every request terminal
        assert all(f.done() for f in inter_futs + batch_futs)
        assert all(r.finish_reason in FINISH_REASONS
                   for r in inter_res + batch_res)
        # (b) interactive served within SLO while batch shed under
        # pressure; interactive is never degraded
        ttfts = sorted(r.ttft_s for r in inter_res if r.ttft_s is not None)
        assert len(ttfts) == len(inter_res), \
            "an interactive request was shed"
        p99 = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
        assert p99 < 2.0, f"interactive p99 TTFT {p99:.3f}s blew the SLO"
        assert not any(r.degraded for r in inter_res)
        shed_batch = sum(1 for r in batch_res if r.finish_reason == "shed")
        assert shed_batch > 0, "batch flood never shed — not overloaded"
        # the ladder actually moved
        assert "degraded" in fe.states_visited
        # (c) zero new_shape across every transition the run produced
        new_shape_after = sum(
            1 for e in observe.ledger().events()
            if e.graph == "serving" and e.cause == "new_shape")
        assert new_shape_after - new_shape_before == 0

    def test_invalid_arrival_never_displaces_a_victim(self):
        """Validation runs BEFORE the shed-lowest-first steal: an
        over-long prompt raises to its caller without destroying the
        queued batch request it would have displaced."""
        eng = self._engine(max_prompt=16)
        fe = SLOFrontend(eng, max_queue_total=1)
        batch_fut = fe.submit(PROMPT, slo_class="batch", eos_token=-1)
        with pytest.raises(ValueError, match="max_prompt"):
            fe.submit(np.arange(1, 30, dtype=np.int32),
                      slo_class="interactive", eos_token=-1)
        assert not batch_fut.done()  # the victim survived
        assert len(eng.scheduler.pending) == 1

    def test_breaker_threshold_scales_to_engine_restart_budget(self):
        """A fixed threshold above engine.max_restarts would be dead code
        — the supervisor fail_alls before the breaker could ever open."""
        eng = self._engine(max_restarts=3)
        fe = SLOFrontend(eng)
        assert fe.breaker_restarts == 3

    def test_engine_submit_accepts_class_kwargs(self):
        """Plain engine.submit carries class labels through to results
        (the frontend-free path keeps the vocabulary)."""
        eng = self._engine()
        res = eng.generate([PROMPT], max_new_tokens=2, eos_token=-1,
                           slo_class="batch", priority=2)
        assert res[0].slo_class == "batch"
        assert res[0].finish_reason == "length"
