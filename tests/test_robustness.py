"""Supervised-recovery tests (docs/ROBUSTNESS.md): the GenerativeEngine
under injected faults, the death paths of the serving stack, and the
ParallelInference crash contract.

The properties under test mirror the chaos gate stage:
  * crash recovery is CORRECT — a retried greedy generation emits exactly
    the oracle tokens, as if the crash never happened;
  * recovery never recompiles — zero ``new_shape`` ledger events across
    restarts (the compile-once property survives the supervisor);
  * every submitted request reaches a terminal state — shed, deadline,
    error and oom are results, not hangs;
  * death paths stay loud — unsupervised engines and exhausted retry
    budgets propagate to blocked callers instead of wedging them.
"""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import faults, nn, observe
from deeplearning4j_tpu.faults import InjectedFault
from deeplearning4j_tpu.models.gpt import (
    GptConfig, GptModel, reference_generate,
)
from deeplearning4j_tpu.serving import GenerativeEngine
from deeplearning4j_tpu.serving.scheduler import (
    FINISH_REASONS, GenerationRequest, SlotScheduler,
)

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
    kw.setdefault("restart_backoff_s", 0.0)  # tests need no pacing
    return GenerativeEngine(MODEL, **kw)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# supervised crash recovery
# ---------------------------------------------------------------------------


class TestSupervisedRecovery:
    def test_inline_decode_crash_recovers_to_oracle(self):
        """One injected decode crash mid-generation: the supervisor
        re-admits and the final greedy output is EXACTLY the oracle's —
        recovery is invisible in the tokens."""
        faults.arm("decode_step_error", prob=1.0, after_n=1, max_fires=1)
        eng = make_engine()
        res = eng.generate(PROMPTS, max_new_tokens=5)
        for p, r in zip(PROMPTS, res):
            assert r.finish_reason == "length"
            np.testing.assert_array_equal(
                r.tokens, reference_generate(MODEL.params, CFG, p, 5))
        assert eng.restarts == 1
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_recovery_never_recompiles(self):
        """Compile-once survives the supervisor: crash + KV-buffer
        reallocation + re-admission produce ZERO new_shape events."""
        observe.reset()
        faults.arm("decode_step_error", prob=1.0, after_n=2, max_fires=2)
        eng = make_engine()
        eng.generate(PROMPTS, max_new_tokens=4)
        assert eng.restarts == 2
        serving = [e for e in observe.ledger().events()
                   if e.graph == "serving"]
        assert serving, "expected serving compile events"
        assert not any(e.cause == "new_shape" for e in serving)
        by_key = {}
        for ev in serving:
            by_key.setdefault(ev.key, []).append(ev.cause)
        assert by_key["decode"] == ["first_compile"], by_key

    def test_restart_counter_and_metric(self):
        observe.reset()
        faults.arm("decode_step_error", prob=1.0, max_fires=1)
        eng = make_engine()
        eng.generate([PROMPTS[0]], max_new_tokens=3)
        assert eng.restarts == 1
        assert observe.metrics().counter(
            "dl4j_tpu_serving_engine_restarts_total").value >= 1
        assert observe.metrics().counter(
            "dl4j_tpu_serving_retries_total").value >= 1

    def test_retry_budget_exhausted_is_error_result(self):
        """A request whose slot dies more often than max_retries completes
        terminally as 'error' — no exception, no hang."""
        faults.arm("decode_step_error", prob=1.0, max_fires=2)
        eng = make_engine(max_slots=1)
        res = eng.generate([PROMPTS[0]], max_new_tokens=4, max_retries=1)[0]
        assert res.finish_reason == "error"
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_restart_budget_exhausted_raises_inline(self):
        """Past max_restarts the supervisor gives up LOUDLY: inline
        generate() re-raises the original fault."""
        faults.arm("decode_step_error", prob=1.0)  # crash every step
        eng = make_engine(max_restarts=2)
        with pytest.raises(InjectedFault, match="decode_step_error"):
            eng.generate([PROMPTS[0]], max_new_tokens=4, max_retries=100)
        assert eng.restarts == 2

    def test_unsupervised_engine_keeps_old_contract(self):
        """supervise=False: the first crash propagates (inline) — the
        pre-robustness behavior stays reachable."""
        faults.arm("decode_step_error", prob=1.0, max_fires=1)
        eng = make_engine(supervise=False)
        with pytest.raises(InjectedFault):
            eng.generate([PROMPTS[0]], max_new_tokens=4)
        assert eng.restarts == 0

    def test_threaded_worker_death_restarts_and_serves(self):
        """worker_death kills the serving thread; a REPLACEMENT thread
        finishes the request correctly and stop() joins cleanly."""
        faults.arm("worker_death", prob=1.0, max_fires=1)
        eng = make_engine().start()
        ident0 = eng._worker.ident
        try:
            fut = eng.submit(PROMPTS[0], max_new_tokens=4)
            res = fut.result(timeout=120)
            np.testing.assert_array_equal(
                res.tokens,
                reference_generate(MODEL.params, CFG, PROMPTS[0], 4))
        finally:
            eng.stop()
        assert eng.restarts == 1
        assert eng._worker is None and eng.stopped_cleanly
        assert ident0 is not None  # the original worker existed and died

    def test_threaded_unsupervised_crash_propagates_to_callers(self):
        """Satellite: engine-thread exception propagation — a blocked
        submit() caller gets the worker's exception, and later submits
        are rejected with the death cause chained."""
        faults.arm("decode_step_error", prob=1.0, max_fires=1)
        eng = make_engine(supervise=False).start()
        fut = eng.submit(PROMPTS[0], max_new_tokens=8)
        with pytest.raises(InjectedFault):
            fut.result(timeout=120)
        # the engine is dead: new submissions refuse loudly
        with pytest.raises(RuntimeError, match="died"):
            for _ in range(100):
                eng.submit(PROMPTS[1])
                time.sleep(0.01)
        eng.stop()


# ---------------------------------------------------------------------------
# deadlines, shedding, injected pool pressure
# ---------------------------------------------------------------------------


class TestDeadlinesAndShedding:
    def test_pending_deadline_expires_without_slot(self):
        eng = make_engine(max_slots=1)
        fut = eng.submit(PROMPTS[0], max_new_tokens=4, deadline_s=0.0)
        time.sleep(0.005)
        eng.step()
        res = fut.result(timeout=0)
        assert res.finish_reason == "deadline"
        assert res.tokens.size == 0

    def test_active_deadline_retires_with_partial_tokens(self):
        faults.arm("slow_decode", prob=1.0)  # +50ms per decode step
        eng = make_engine(max_slots=1)
        fut = eng.submit(PROMPTS[0], max_new_tokens=50, deadline_s=0.12)
        while eng.scheduler.has_work():
            eng.step()
        res = fut.result(timeout=0)
        assert res.finish_reason == "deadline"
        # partial output is the oracle prefix — the deadline lost time,
        # not correctness
        assert res.tokens.size >= 1
        np.testing.assert_array_equal(
            res.tokens,
            reference_generate(MODEL.params, CFG, PROMPTS[0],
                               len(res.tokens)))
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_default_deadline_applies_to_submit(self):
        eng = make_engine(default_deadline_s=0.0)
        fut = eng.submit(PROMPTS[0])
        time.sleep(0.005)
        eng.step()
        assert fut.result(timeout=0).finish_reason == "deadline"

    def test_bounded_queue_sheds_with_terminal_reason(self):
        observe.reset()
        eng = make_engine(max_slots=1, max_queue=2)
        futs = [eng.submit(p, max_new_tokens=2) for p in PROMPTS]
        shed = [f for f in futs if f.done()
                and f.result().finish_reason == "shed"]
        assert len(shed) == 1  # queue bound 2, third submission shed
        assert observe.metrics().counter(
            "dl4j_tpu_serving_evicted_total", reason="shed").value == 1
        # the queued ones still complete normally
        while eng.scheduler.has_work():
            eng.step()
        reasons = sorted(f.result(timeout=0).finish_reason for f in futs)
        assert reasons == ["length", "length", "shed"]

    def test_injected_page_oom_is_terminal_oom(self):
        faults.arm("page_oom", prob=1.0, max_fires=1)
        eng = make_engine(max_slots=1)
        res = eng.generate([PROMPTS[0]], max_new_tokens=6)[0]
        assert res.finish_reason == "oom"
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_deadline_validation(self):
        with pytest.raises(ValueError, match="deadline_s"):
            GenerationRequest(prompt=PROMPTS[0], deadline_s=-1.0)
        with pytest.raises(ValueError, match="max_retries"):
            GenerationRequest(prompt=PROMPTS[0], max_retries=-1)

    def test_prefill_crash_does_not_strand_request(self, monkeypatch):
        """A crash inside prefill hits AFTER the request left the pending
        queue but BEFORE it owns a slot — recovery must re-queue it (front,
        original submit time) instead of stranding its future forever."""
        eng = make_engine(max_slots=1)
        real = eng._prefill_into
        calls = {"n": 0}

        def flaky(slot, req):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected prefill crash")
            return real(slot, req)

        monkeypatch.setattr(eng, "_prefill_into", flaky)
        res = eng.generate([PROMPTS[0]], max_new_tokens=3)[0]
        assert res.finish_reason == "length"
        np.testing.assert_array_equal(
            res.tokens, reference_generate(MODEL.params, CFG, PROMPTS[0], 3))
        assert eng.restarts == 1
        eng.cache.check_invariants()
        assert eng.cache.free_pages == eng.cache.num_pages

    def test_wall_clock_jump_cannot_expire_deadlines(self, monkeypatch):
        """GL010 satellite: deadline bookkeeping runs on perf_counter.
        A wall-clock jump (NTP step, manual reset) mid-generation must
        NOT spuriously expire a request whose monotonic budget is fine —
        here the wall clock leaps a full year and everything still
        finishes as 'length'."""
        real_time = time.time
        monkeypatch.setattr(time, "time",
                            lambda: real_time() + 365 * 24 * 3600.0)
        eng = make_engine(max_slots=1)
        res = eng.generate([PROMPTS[0]], max_new_tokens=4,
                           deadline_s=120.0)[0]
        assert res.finish_reason == "length"
        assert res.tokens.size == 4


# ---------------------------------------------------------------------------
# death paths of the existing stack (satellite)
# ---------------------------------------------------------------------------


class TestDeathPaths:
    def test_fail_all_drains_pending_submits(self):
        """SlotScheduler.fail_all completes EVERY queued future — pending
        submissions cannot hang across an engine death."""
        sched = SlotScheduler(max_slots=2)
        futs = [sched.submit(GenerationRequest(prompt=p)) for p in PROMPTS]
        exc = RuntimeError("engine died")
        sched.fail_all(exc)
        assert not sched.pending and not sched.slots
        for f in futs:
            with pytest.raises(RuntimeError, match="engine died"):
                f.result(timeout=0)

    def test_fail_pending_leaves_active_slots_alone(self):
        sched = SlotScheduler(max_slots=2)
        from concurrent.futures import Future
        active_fut: "Future" = Future()
        sched.admit(0, GenerationRequest(prompt=PROMPTS[0]), active_fut,
                    submit_t=0.0, first_token=1, now=0.0)
        queued = sched.submit(GenerationRequest(prompt=PROMPTS[1]))
        sched.fail_pending(RuntimeError("stop hung"))
        with pytest.raises(RuntimeError):
            queued.result(timeout=0)
        assert not active_fut.done()  # the (possibly stuck) worker owns it
        assert 0 in sched.slots

    def test_stop_detects_hung_worker(self):
        """Satellite: a worker that outlives the join timeout is detected
        — logged, stopped_cleanly False, gauge 0 — and stop() returns
        instead of silently continuing (or raising mid-shutdown)."""
        observe.reset()
        eng = make_engine().start()
        release = threading.Event()

        def stuck_step(ahead):
            release.wait(5.0)
            return 0

        # the loop picks it up on the next iteration
        eng._iterate = stuck_step
        fut = eng.submit(PROMPTS[0], max_new_tokens=4)
        time.sleep(0.05)  # let the loop enter the stuck step
        eng.stop(timeout=0.2)
        assert eng.stopped_cleanly is False
        assert observe.metrics().gauge(
            "dl4j_tpu_serving_stopped_cleanly").value == 0.0
        assert eng._worker is not None  # deliberately NOT nulled
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit(PROMPTS[1])
        # the queued request was failed so nothing hangs...
        with pytest.raises(RuntimeError):
            fut.result(timeout=0)
        release.set()  # ...and the stuck worker is released for teardown
        eng._worker.join(timeout=10)

    def test_clean_stop_sets_gauge_one(self):
        observe.reset()
        eng = make_engine().start()
        eng.stop()
        assert eng.stopped_cleanly is True
        assert observe.metrics().gauge(
            "dl4j_tpu_serving_stopped_cleanly").value == 1.0

    def test_parallel_inference_worker_raise_fails_batch_not_loop(self):
        """Satellite: a backend worker raising mid-batch fails THAT
        batch's futures and the serving loop keeps serving."""
        from tests._helpers import _mln, _rng
        from deeplearning4j_tpu.parallel.mesh import ParallelInference

        net = _mln([
            nn.DenseLayer(n_out=16, activation="relu"),
            nn.OutputLayer(n_out=3, activation="softmax", loss="mcxent"),
        ], nn.InputType.feed_forward(6))
        pi = ParallelInference(net, max_batch=8, window_ms=1.0).start()
        try:
            x = _rng(0).randn(6).astype(np.float32)
            ref = pi.predict(x)  # warm + healthy
            faults.arm("backend_init_fail", prob=1.0, max_fires=1)
            with pytest.raises(InjectedFault, match="backend_init_fail"):
                pi.predict(x)
            # fault exhausted: the SAME loop serves the next request
            np.testing.assert_allclose(pi.predict(x), ref, atol=1e-6)
        finally:
            pi.stop()

    def test_parallel_inference_start_fails_loudly(self):
        from tests._helpers import _mln
        from deeplearning4j_tpu.parallel.mesh import ParallelInference

        net = _mln([
            nn.OutputLayer(n_out=3, activation="softmax", loss="mcxent"),
        ], nn.InputType.feed_forward(6))
        faults.arm("backend_init_fail", prob=1.0, max_fires=1)
        pi = ParallelInference(net, max_batch=4)
        with pytest.raises(InjectedFault):
            pi.start()

    def test_finish_reasons_superset(self):
        """The terminal-state vocabulary the SLO frontend consumes."""
        assert set(FINISH_REASONS) >= {"eos", "length", "overflow", "oom",
                                       "stopped", "shed", "deadline",
                                       "error"}
