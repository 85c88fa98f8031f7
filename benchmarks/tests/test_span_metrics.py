"""The readers of the program's stage spans (PR 25), each on a hand-made
``ctx`` or a hand-filled tracer, and the idle-gap labelling they rely on."""

import time

import pytest

import common
import trace_reduce
from deeplearning4j_tpu import observe


@pytest.fixture(autouse=True)
def fresh_tracer():
    observe.reset()
    yield
    observe.reset()


def span(name, start, seconds, sid, parent=None, **args):
    return {"name": name, "start": start, "seconds": seconds,
            "args": dict(args, id=sid, parent=parent)}


def test_step_host_ms_takes_the_reads_off_each_step():
    read = common.module("layer_metrics", "step_host_ms.serve").read
    spans = [
        # a step of 100 ms with two admissions (reads 2 + 3) and a decode
        # whose read took 60: the host had 35 ms of its own
        span("serving_step", 0.0, 0.100, 1),
        span("serving_admit", 0.001, 0.010, 2, 1),
        span("serving_prefill", 0.002, 0.008, 3, 2),
        span("serving_prefill_read", 0.006, 0.002, 4, 3),
        span("serving_admit", 0.012, 0.010, 5, 1),
        span("serving_prefill", 0.013, 0.008, 6, 5),
        span("serving_prefill_read", 0.016, 0.003, 7, 6),
        span("serving_decode", 0.030, 0.065, 8, 1),
        span("serving_decode_launch", 0.030, 0.004, 9, 8),
        span("serving_decode_read", 0.034, 0.060, 10, 8),
        # and two steps with a decode only: 80 less 70, 90 less 70
        span("serving_step", 0.100, 0.080, 11),
        span("serving_decode_read", 0.105, 0.070, 12, 11),
        span("serving_step", 0.180, 0.090, 13),
        span("serving_decode_read", 0.185, 0.070, 14, 13),
        # a read under no step of the window is nobody's
        span("serving_decode_read", 0.300, 0.500, 15, 999),
    ]
    assert read({"spans": spans}) == pytest.approx(20.0)
    assert read({"spans": []}) is None
    assert read({"spans": [s for s in spans
                           if s["name"] != "serving_step"]}) is None
    observe.tracer().dropped = 3
    assert read({"spans": spans}) is None


def test_queue_wait_p95_over_the_windows_submissions():
    read = common.module("layer_metrics", "queue_wait_p95_ms.serve").read
    tr = observe.tracer()
    t0 = time.perf_counter()
    assert read({"window": (t0, t0 + 10)}) is None  # no spans yet
    for i in range(101):  # waits of 0..100 ms, submitted inside the window
        tr.async_between("serving_queue_wait", t0 + 1 + i * 0.01,
                         t0 + 1 + i * 0.01 + i * 1e-3, key=i, request=i)
    # outside the window, and a thread span of the same name: neither counts
    tr.async_between("serving_queue_wait", t0 - 5, t0 - 4, key=500)
    tr.complete_between("serving_queue_wait", t0 + 1, t0 + 9)
    assert read({"window": (t0, t0 + 10)}) == pytest.approx(95.0, abs=1e-6)
    assert read({}) is None  # a loop with no window (training)
    tr.dropped = 1
    assert read({"window": (t0, t0 + 10)}) is None


def fill_calls(tr, t0, durations):
    """One ``fit_scanned`` span a call with its two children, back to back:
    dispatch takes 4 ms, the read the rest."""
    t = t0
    for n, d in enumerate(durations):
        cid = tr.complete_between("fit_scanned", t, t + d, model="bert",
                                  steps=1, call=n + 1)
        tr.complete_between("fit_scanned_dispatch", t, t + 0.004, parent=cid)
        tr.complete_between("fit_scanned_read", t + 0.004, t + d, parent=cid)
        t += d
    return t


def test_dispatch_ms_reads_the_windows_last_calls(capsys):
    read = common.module("layer_metrics", "dispatch_ms_per_call.train").read
    tr = observe.tracer()
    assert read({"call_s": [0.1] * 3}) is None  # the program has no spans
    t0 = time.perf_counter()
    # six calls of set-up, then a window of five whose third stalls in its
    # dispatch, with a compile inside it
    t = fill_calls(tr, t0, [0.5] * 6)
    for n, dispatch in enumerate((0.004, 0.005, 3.0, 0.004, 0.003)):
        cid = tr.complete_between("fit_scanned", t, t + dispatch + 0.15,
                                  model="bert", steps=1, call=7 + n)
        did = tr.complete_between("fit_scanned_dispatch", t, t + dispatch,
                                  parent=cid)
        if dispatch > 1:
            tr.complete_between("xla_compile", t + 0.1, t + 2.9, parent=did,
                                cached=False)
        tr.complete_between("fit_scanned_read", t + dispatch,
                            t + dispatch + 0.15, parent=cid)
        t += dispatch + 0.15
    assert read({"call_s": [0.15] * 5}) == pytest.approx(4.0)
    err = capsys.readouterr().err
    assert "call=9" in err and "1 xla_compile inside it (2800.0 ms)" in err
    assert "dispatch 3000.0 + read 150.0" in err
    assert read({"call_s": [0.1] * 12}) is None  # more calls than spans
    assert read({"call_s": []}) is None
    tr.dropped = 1
    assert read({"call_s": [0.15] * 5}) is None


def test_programs_compiled_counts_up_to_the_windows_end(capsys):
    read = common.module("layer_metrics", "programs_compiled").read
    tr = observe.tracer()
    built = observe.metrics().counter("dl4j_tpu_xla_programs_total")
    t0 = time.perf_counter()

    def build(at, parent=None, cached=False):
        built.inc()
        tr.complete_between("xla_compile", at, at + 0.5, category="compile",
                            parent=parent, cached=cached)

    assert read({"window": (t0 + 10, t0 + 20)}) is None  # nothing built
    for k in range(7):                 # set-up
        build(t0 + k, cached=k % 2 == 0)
    tr.clear()                         # the serving loop clears at the start
    admit = tr.complete_between("serving_admit", t0 + 12, t0 + 14)
    build(t0 + 12.5, parent=admit)     # one inside the window: a fault
    for k in range(3):                 # the reference, after the window
        build(t0 + 21 + k)
    assert read({"window": (t0 + 10, t0 + 20)}) == 8.0
    err = capsys.readouterr().err
    assert "8 before the window's end, 1 inside the window" in err
    assert "under serving_admit" in err
    tr.dropped = 2
    assert read({"window": (t0 + 10, t0 + 20)}) is None


def test_programs_compiled_in_a_training_window():
    read = common.module("layer_metrics", "programs_compiled").read
    tr = observe.tracer()
    built = observe.metrics().counter("dl4j_tpu_xla_programs_total")
    t0 = time.perf_counter()
    assert read({"call_s": [0.5] * 4}) is None  # no fit_scanned spans
    for k in range(5):
        built.inc()
        tr.complete_between("xla_compile", t0 + k * 0.1, t0 + k * 0.1 + 0.05,
                            cached=True)
    end = fill_calls(tr, t0 + 1, [0.5] * 10)
    for k in range(2):  # the reference's, after the last call
        built.inc()
        tr.complete_between("xla_compile", end + 1 + k, end + 1.5 + k,
                            cached=False)
    assert read({"call_s": [0.5] * 4}) == 5.0


def test_a_gap_inside_a_stage_takes_the_stages_label():
    host = [("bench_window", 0, 10_000_000),
            ("bench_serving_step", 1_000_000, 5_000_000),
            ("bench_serving_decode", 2_000_000, 3_500_000),
            ("bench_serving_decode_launch", 2_000_000, 400_000),
            ("bench_serving_decode_read", 2_400_000, 3_100_000)]
    label = trace_reduce.label_at
    assert label(host, 2_500_000, 2_900_000, "x") == "bench_serving_decode_read"
    assert label(host, 2_050_000, 2_350_000, "x") == "bench_serving_decode_launch"
    # a gap that crosses two stages goes to what covers most of it: their
    # parent (PERF.md, Open questions)
    assert label(host, 2_300_000, 2_600_000, "x") == "bench_serving_decode"
    assert label(host, 7_000_000, 8_000_000, "x") == "x"


# ---- the readers on the program's own spans: tiny cells, on the CPU (counts
# ---- and coverage only; a time from here is no device number)


def _loop_ctx(cell, seconds):
    import jax

    loop = common.module("loops", cell["mix"]["loop"])
    res = loop.run(cell, seed=7, seconds=seconds, trace=False,
                   devs=jax.devices())
    assert res["checks"].correct, res["checks"].compared()
    return res["ctx"]


def test_serving_readers_on_a_tiny_cell(capsys):
    import tiny

    ctx = _loop_ctx(tiny.serve_cell(), 2.0)
    spans = ctx["spans"]
    steps = [s for s in spans if s["name"] == "serving_step"]
    assert len(steps) > 10
    # the direct children of a step cover nearly all of it
    kids = common.module("layer_metrics", "program_spans").children_of(spans)
    covered = sum(c["seconds"] for s in steps
                  for c in kids.get(s["args"]["id"], []))
    assert covered / sum(s["seconds"] for s in steps) > 0.9
    # no request's span reached the thread spans that label idle gaps
    assert not [s for s in spans if s["name"] in ("serving_queue_wait",
                                                  "serving_request")]
    host = common.module("layer_metrics", "step_host_ms.serve").read(ctx)
    wait = common.module("layer_metrics", "queue_wait_p95_ms.serve").read(ctx)
    built = common.module("layer_metrics", "programs_compiled").read(ctx)
    step_ms = 1e3 * common.median([s["seconds"] for s in steps])
    assert 0 < host < step_ms and wait > 0
    assert built >= 3  # prefill, write_prompt, decode at the least
    err = capsys.readouterr().err
    assert " 0 inside the window" in err and "serving_decode_read" in err
    # the spans PR 24's metrics read kept their names and arguments
    assert common.module("layer_metrics", "slot_occupancy_pct.serve").read(
        ctx) > 50


def test_training_readers_on_a_tiny_cell(capsys):
    import tiny

    ctx = _loop_ctx(tiny.train_cell(), 0.5)
    ms = common.module("layer_metrics", "dispatch_ms_per_call.train").read(ctx)
    built = common.module("layer_metrics", "programs_compiled").read(ctx)
    assert 0 < ms < 1e3 * common.median(ctx["call_s"])
    assert built >= 1
    err = capsys.readouterr().err
    assert "longest train call: call=" in err
    assert " 0 inside the window" in err
