"""The readers of the host's accounting (``step_max_ms.serve``,
``step_host_cpu_pct.serve``, ``step_gc_pct.serve``, ``call_max_ms.train``)
on hand-made spans: nothing from a program whose spans carry no accounting,
and the arithmetic on a window with one stalled step or call."""

import time

import pytest

import common
from deeplearning4j_tpu import observe


@pytest.fixture(autouse=True)
def fresh_tracer():
    observe.reset()
    yield
    observe.reset()


def reader(name):
    return common.module("layer_metrics", name).read


def span(name, start, seconds, sid, parent=None, **args):
    return {"name": name, "start": start, "seconds": seconds,
            "args": dict(args, id=sid, parent=parent)}


def usage(cpu_ms, gc_ms=0.0):
    return dict(cpu_ms=cpu_ms, gc_ms=gc_ms)


def window():
    """Three steps: two of 10 ms (6 of them a read) and one stalled step of
    400 ms whose read took 20 and whose host part lost 300 ms to a full
    pass of the collector; a pass on another thread beside it."""
    spans = []
    t, sid = 100.0, 1
    for n, (wall, read, cpu, read_cpu, gc_ms) in enumerate(
            [(0.010, 0.006, 3.0, 0.5, 0.1), (0.400, 0.020, 70.0, 1.0, 300.0),
             (0.010, 0.006, 3.5, 0.5, 0.0)]):
        step = sid
        spans.append(span("serving_step", t, wall, step, step=n + 1,
                          **usage(cpu, gc_ms)))
        spans.append(span("serving_decode", t + 0.001, wall - 0.002,
                          step + 1, step))
        spans.append(span("serving_decode_read", t + 0.002, read, step + 2,
                          step + 1, **usage(read_cpu)))
        if gc_ms:
            spans.append(span("host_gc", t + 0.003, gc_ms / 1e3, step + 3,
                              step, generation=2 if gc_ms > 1 else 1,
                              collected=5, thread="worker",
                              callbacks_ms=0.2))
        t += wall
        sid += 4
    spans.append(span("host_gc", 100.2, 0.05, 99, None, generation=1,
                      collected=0, thread="MainThread", callbacks_ms=0.1))
    return spans


def ctx_of(spans):
    return {"spans": spans, "window": (100.0, 100.5),
            "traced": (100.3, 100.5),
            "trace": {"busy_s": 0.1, "window_s": 0.2}}


def test_nothing_from_a_program_without_the_accounting():
    bare = [dict(s, args={k: v for k, v in s["args"].items()
                          if k in ("id", "parent", "step")})
            for s in window()]
    for name in ("step_max_ms.serve", "step_host_cpu_pct.serve",
                 "step_gc_pct.serve"):
        assert reader(name)(ctx_of(bare)) is None, name
        assert reader(name)({"spans": []}) is None, name
        assert reader(name)({}) is None, name
    observe.tracer().dropped = 2
    for name in ("step_max_ms.serve", "step_host_cpu_pct.serve",
                 "step_gc_pct.serve"):
        assert reader(name)(ctx_of(window())) is None, name


def test_the_longest_step_and_what_was_beside_it(capsys):
    assert reader("step_max_ms.serve")(ctx_of(window())) == pytest.approx(
        400.0)
    err = capsys.readouterr().err
    assert "step=2 400.0 ms at 0.010 s into the window, 40.0x the median" \
        in err
    assert "cpu_ms 70.0 gc_ms 300.0" in err
    assert "outside the traced seconds" in err
    assert ("host_gc 300.0 ms (generation 2 thread worker collected 5 "
            "callbacks_ms 0.2)") in err
    assert "host_gc 50.0 ms (generation 1 thread MainThread" in err
    assert "serving_decode_read 20.0 ms (cpu_ms 1.0" in err
    assert "1 over ten times the median" in err


def test_the_host_part_on_a_core():
    # (3 - 0.5) + (70 - 1) + (3.5 - 0.5) ms of CPU over (10 - 6) + (400 -
    # 20) + (10 - 6) ms of host part
    got = reader("step_host_cpu_pct.serve")(ctx_of(window()))
    assert got == pytest.approx(100 * 74.5 / 388.0)


def test_the_collectors_share_of_the_steps(capsys):
    got = reader("step_gc_pct.serve")(ctx_of(window()))
    assert got == pytest.approx(100 * 300.1 / 420.0)
    err = capsys.readouterr().err
    assert "generation 2 on worker: 1 (300.000 ms)" in err
    assert "generation 1 on MainThread: 1 (50.000 ms)" in err
    assert "longest host_gc 300.0 ms" in err


def fill_calls(tr, t0, durations, accounting=True):
    """One ``fit_scanned`` span a call with its two stages, back to back:
    dispatch takes 4 ms, the read the rest."""
    t = t0
    for n, d in enumerate(durations):
        extra = usage(1e3 * d / 2) if accounting else {}
        cid = tr.complete_between("fit_scanned", t, t + d, model="bert",
                                  steps=1, call=n + 1, **extra)
        tr.complete_between("fit_scanned_dispatch", t, t + 0.004, parent=cid,
                            **(usage(3.9) if accounting else {}))
        tr.complete_between("fit_scanned_read", t + 0.004, t + d, parent=cid,
                            **(usage(0.2) if accounting else {}))
        t += d
    return t


def test_the_longest_train_call(capsys):
    read = reader("call_max_ms.train")
    tr = observe.tracer()
    t0 = time.perf_counter()
    fill_calls(tr, t0, [0.15] * 4, accounting=False)
    assert read({"call_s": [0.15] * 4}) is None  # the parent's spans
    observe.reset()
    tr = observe.tracer()
    t = fill_calls(tr, t0, [0.5] * 3 + [0.15, 0.15, 3.5, 0.15, 0.15])
    tr.complete_between("host_gc", t - 3.9, t - 3.4, generation=2,
                        collected=1, thread="MainThread", callbacks_ms=1800.0)
    ctx = {"call_s": [0.15] * 5, "cell": {"mix": {"trace_seconds": 1}},
           "trace": {"busy_s": 0.9, "window_s": 1.0}}
    assert read(ctx) == pytest.approx(3500.0)
    err = capsys.readouterr().err
    assert "call=6 3500.0 ms, 23.3x the median 150.0 ms" in err
    assert "fit_scanned_read 3496.0 ms (cpu_ms 0.2 gc_ms 0.0)" in err
    assert ("host_gc 500.0 ms (generation 2 thread MainThread collected 1 "
            "callbacks_ms 1800.0)") in err
    assert "full passes in the window: host_gc 500.0 ms" in err
    assert "calls (ms @ call of the window): 3500.0 @ 3," in err
    assert "of 5; 1 over ten times the median" in err
    assert read({"call_s": [0.1] * 12}) is None  # more calls than spans
    tr.dropped = 1
    assert read(ctx) is None
