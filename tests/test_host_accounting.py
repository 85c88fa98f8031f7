"""The host's accounting on the spans that own its time, and the
collector's hook (docs/OBSERVABILITY.md § Host accounting): what a pass
leaves in the tracer and the registry, and that the hook takes no lock."""

import gc
import glob
import os
import threading
import time

import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.observe import host, registry, tracing

ACCOUNTING = ("cpu_ms", "gc_ms")


@pytest.fixture(autouse=True)
def fresh_observe():
    observe.install_gc_hook()
    observe.reset()
    yield
    observe.reset()


def host_gc(parent=None):
    return [e for e in observe.tracer().to_dict()["traceEvents"]
            if e["name"] == "host_gc"
            and (parent is None or e["args"]["parent"] == parent)]


def passes(generation):
    return observe.metrics().histogram("dl4j_tpu_gc_pause_seconds",
                                       generation=str(generation)).count


def test_a_span_keeps_the_hosts_accounting_only_when_asked():
    tr = observe.tracer()
    with tr.span("plain") as plain:
        pass
    t0 = time.perf_counter()
    with tr.span("owner", host=True) as owner:
        while time.perf_counter() - t0 < 0.02:  # on a core
            pass
        time.sleep(0.02)  # off it
    assert set(plain.args) == {"id", "parent"}
    args = owner.args
    assert set(args) == {"id", "parent", *ACCOUNTING}
    ev = next(e for e in tr.events if e["name"] == "owner")
    wall_ms = ev["dur"] / 1e3
    assert 10 <= args["cpu_ms"] <= wall_ms + 1e-3
    assert wall_ms - args["cpu_ms"] >= 10  # the sleep is no CPU time
    assert args["gc_ms"] >= 0


def test_a_full_pass_inside_a_span_is_a_host_gc_span():
    before = passes(2)
    with observe.tracer().span("owner", host=True) as owner:
        gc.collect(2)
    (pass_,) = [e for e in host_gc(owner.id)
                if e["args"]["generation"] == 2]
    assert pass_["cat"] == "host"
    assert pass_["args"]["thread"] == threading.current_thread().name
    assert pass_["args"]["collected"] >= 0
    assert passes(2) == before + 1
    # the span's gc_ms covers the pass (rounded to the microsecond)
    assert owner.args["gc_ms"] >= pass_["dur"] / 1e3 - 1e-3
    owner_ev = next(e for e in observe.tracer().events
                    if e["name"] == "owner")
    assert owner_ev["ts"] <= pass_["ts"]
    assert pass_["ts"] + pass_["dur"] <= owner_ev["ts"] + owner_ev["dur"]


def test_a_pass_is_timed_without_the_other_callbacks():
    """jax's own ``gc.callbacks`` entry runs XLA's ``collect_garbage`` at a
    pass's start and stop: that is not the collector, and goes to the
    ``host_gc`` span's ``callbacks_ms``, not to ``gc_ms``."""

    def slow(phase, info):
        time.sleep(0.05)

    gc.callbacks.append(slow)  # as jax's is, before the hook comes in
    try:
        observe.install_gc_hook()
        with observe.tracer().span("owner", host=True) as owner:
            t0 = time.perf_counter()
            gc.collect(2)
            wall_ms = 1e3 * (time.perf_counter() - t0)
    finally:
        gc.callbacks.remove(slow)
    (pass_,) = [e for e in host_gc(owner.id)
                if e["args"]["generation"] == 2]
    others = pass_["args"]["callbacks_ms"]
    assert others >= 100  # at start and at stop
    # the pass and the callbacks around it share the collect's wall time
    assert pass_["dur"] / 1e3 + others <= wall_ms + 1e-3
    assert pass_["dur"] / 1e3 + others >= wall_ms - 20
    assert pass_["dur"] / 1e3 - 1e-3 <= owner.args["gc_ms"] <= wall_ms - others


def test_the_hook_stays_around_the_callbacks_added_after_it():
    def later(phase, info):
        pass

    gc.callbacks.append(later)
    try:
        observe.install_gc_hook()
        observe.install_gc_hook()
        first, last = host._first, host._last
        assert gc.callbacks[0] is first and gc.callbacks[-1] is last
        assert gc.callbacks.count(first) == gc.callbacks.count(last) == 1
    finally:
        gc.callbacks.remove(later)


def test_a_young_pass_adds_gc_ms_and_no_span():
    gc.disable()  # so that the young generation holds what is made here
    try:
        junk = [[i] for i in range(20000)]
        before = passes(0)
        with observe.tracer().span("owner", host=True) as owner:
            gc.collect(0)
    finally:
        gc.enable()
    del junk
    assert owner.args["gc_ms"] > 0
    assert passes(0) >= before + 1
    assert not host_gc(owner.id)


def test_the_hook_takes_no_lock():
    """A full pass while this thread holds the tracer's lock, the
    registry's and both accessors': a callback that took one would never
    return."""
    tr, reg = observe.tracer(), observe.metrics()
    done = threading.Event()

    def collect_under_the_locks():
        with tracing._DEFAULT_LOCK, registry._DEFAULT_LOCK, tr._lock, \
                reg._lock:
            gc.collect(2)
        done.set()

    t = threading.Thread(target=collect_under_the_locks, daemon=True)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and done.is_set()
    # written at the next read, on this thread
    assert [e["args"]["thread"] for e in host_gc()
            if e["args"]["generation"] == 2] == [t.name]
    (pass_,) = [e for e in host_gc() if e["args"]["generation"] == 2]
    assert pass_["tid"] == t.ident % 1_000_000


def test_an_instant_comes_after_the_passes_before_it():
    """A reader that takes the newest event as its clock mark (the
    benchmark's ``bench_sync``) finds it last."""
    tr = observe.tracer()
    gc.collect(2)
    tr.instant("mark")
    events = tr.to_dict()["traceEvents"]
    assert events[-1]["name"] == "mark"
    assert any(e["name"] == "host_gc" for e in events[:-1])


def test_a_pass_is_a_host_gc_annotation_under_a_profiler_session(tmp_path):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with observe.tracer().span("owner", host=True):
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert "dl4j/host_gc" in names and "dl4j/owner" in names
