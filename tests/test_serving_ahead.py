"""The serving loop one decode step ahead of the tokens' read
(docs/SERVING.md § The loop): a started engine serves, token for token, what
an engine driven by ``step()`` serves; its spans show the launch of a step
before the read of the step before it; a step in flight survives ``stop()``,
a crash and a deadline; and the tracer's ring holds a window of it."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import faults, observe
from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
from deeplearning4j_tpu.models.longcat import LongcatModel
from deeplearning4j_tpu.observe import host, tracing
from deeplearning4j_tpu.serving import GenerativeEngine
from tests.test_longcat import bench_cfg, program_cfg, ref

SLOTS = 2
RNG = np.random.default_rng(7)
PROMPTS = [RNG.integers(1, 60, n).astype(np.int32)
           for n in (4, 2, 6, 9, 3, 7, 5)]
_MODELS: dict = {}
_FNS: dict = {}


def _model(name):
    if name not in _MODELS:
        if name == "gpt":
            _MODELS[name] = (GptModel(GptConfig.tiny(), seed=1), dict(
                page_size=8, max_pages_per_seq=8, max_prompt=16))
        else:
            cfg = bench_cfg()
            weights = ref.make_weights(cfg, 5, jnp.float32)
            _MODELS[name] = (
                LongcatModel(program_cfg(cfg), params=weights),
                dict(page_size=4, max_pages_per_seq=16, max_prompt=24))
    return _MODELS[name]


def make_engine(name, **kw):
    """A fresh engine; the jitted programs (functions of the geometry
    alone) are those of the first engine of this model, so that a test pays
    for its steps and not for another compile."""
    model, geo = _model(name)
    eng = GenerativeEngine(model, max_slots=SLOTS, seed=3, **geo, **kw)
    for attr, fn in _FNS.get(name, {}).items():
        setattr(eng, attr, fn)
    return eng


def keep_programs(name, eng):
    _FNS.setdefault(name, {}).update(
        {a: getattr(eng, a) for a in ("_prefill_fn", "_write_fn",
                                      "_decode_fn")
         if getattr(eng, a) is not None})


def serve(name, requests, started, **engine_kw):
    """Every request is queued before the first iteration, so both engines
    see the same queue; returns (tokens, finish_reason, prompt_len) each."""
    eng = make_engine(name, **engine_kw)
    futs = [eng.submit(p, **kw) for p, kw in requests]
    if started:
        eng.start()
        try:
            results = [f.result(timeout=600) for f in futs]
        finally:
            eng.stop()
        assert eng.stopped_cleanly
    else:
        while eng.scheduler.has_work():
            eng.step()
        results = [f.result(timeout=0) for f in futs]
    keep_programs(name, eng)
    eng.check_invariants()
    assert eng.cache.free_pages == eng.cache.num_pages
    assert eng._flying is None and not eng._landing
    assert not eng.scheduler.leaving
    return [(r.tokens.tolist(), r.finish_reason, r.prompt_len)
            for r in results]


@pytest.fixture(autouse=True)
def fresh():
    observe.reset()
    faults.reset()
    yield
    faults.reset()
    observe.reset()


@pytest.fixture(scope="module", params=["gpt", "longcat"])
def name(request):
    return request.param


_STREAMS: dict = {}


def greedy_streams(name):
    """What the synchronous engine serves each prompt greedily, 12 tokens."""
    if name not in _STREAMS:
        got = serve(name, [(p, dict(max_new_tokens=12, eos_token=-1))
                           for p in PROMPTS], started=False)
        _STREAMS[name] = [toks for toks, _reason, _plen in got]
    return _STREAMS[name]


def first_seen(stream, start):
    """The first index >= start whose token has not occurred before it."""
    for i in range(start, len(stream)):
        if stream[i] not in stream[:i]:
            return i
    return None


# (a) ------------------------------------------------------------------------


def requests_of(case, name):
    streams = greedy_streams(name)
    if case == "length":
        return [(p, dict(max_new_tokens=5 + i, eos_token=-1))
                for i, p in enumerate(PROMPTS[:SLOTS])]
    if case == "refill":  # more requests than slots, staggered lengths
        return [(p, dict(max_new_tokens=3 + 2 * (i % 3), eos_token=-1))
                for i, p in enumerate(PROMPTS)]
    if case == "one-token":
        return [(p, dict(max_new_tokens=1 if i % 2 == 0 else 4,
                         eos_token=-1)) for i, p in enumerate(PROMPTS)]
    if case == "eos-first":  # the prefill's own token ends the sequence
        return [(p, dict(max_new_tokens=8, eos_token=streams[i][0]))
                for i, p in enumerate(PROMPTS[:3])]
    if case == "eos-in-flight":
        # a token of a decode step: the started engine reads it after it
        # has launched the next step with that slot active. The tiny models'
        # greedy streams repeat one token, so these requests sample; as many
        # as there are slots, so that the keys' order does not depend on
        # when a slot comes free
        hot = [(p, dict(max_new_tokens=12, temperature=1.5))
               for p in PROMPTS[:SLOTS]]
        free = serve(name, [(p, dict(kw, eos_token=-1)) for p, kw in hot],
                     started=False)
        ends = [first_seen(toks, 1) for toks, _reason, _plen in free]
        assert all(at is not None and at < 11 for at in ends), free
        return [(p, dict(kw, eos_token=toks[at])) for (p, kw), at, (
            toks, _reason, _plen) in zip(hot, ends, free)]
    assert case == "sampled"  # all by length: the launch order is fixed
    knobs = [dict(temperature=0.9), dict(temperature=0.7, top_k=5),
             dict(temperature=1.1, top_p=0.9), dict()]
    return [(p, dict(max_new_tokens=3 + i % 4, eos_token=-1,
                     **knobs[i % len(knobs)]))
            for i, p in enumerate(PROMPTS)]


@pytest.mark.parametrize("case", ["length", "refill", "one-token",
                                  "eos-first", "eos-in-flight", "sampled"])
def test_started_engine_serves_what_step_serves(name, case):
    requests = requests_of(case, name)
    inline = serve(name, requests, started=False)
    started = serve(name, requests, started=True)
    assert started == inline
    reasons = {reason for _toks, reason, _plen in inline}
    if case.startswith("eos"):
        assert reasons == {"eos"}
        if case == "eos-first":
            assert all(toks == [] for toks, _r, _p in inline)
        else:
            assert all(1 <= len(toks) < 11 for toks, _r, _p in inline)
    else:
        assert reasons == {"length"}
        assert [len(t) for t, _r, _p in inline] == [
            kw["max_new_tokens"] for _p, kw in requests]


# (b) ------------------------------------------------------------------------


def events(name=None, phases=("X",)):
    evs = [e for e in observe.tracer().to_dict()["traceEvents"]
           if e["ph"] in phases]
    return [e for e in evs if name is None or e["name"] == name]


def end(e):
    return e["ts"] + e["dur"]


def test_spans_show_the_launch_before_the_read_of_the_step_before():
    requests = [(p, dict(max_new_tokens=6 + 3 * (i % 3), eos_token=-1))
                for i, p in enumerate(PROMPTS)]
    served = serve("gpt", requests, started=True)
    assert all(reason == "length" for _t, reason, _p in served)
    decodes = sorted(events("serving_decode"), key=lambda e: e["ts"])
    # the queue was full before the first iteration and never ran dry: only
    # the first step found nothing in flight
    assert [e["args"]["ahead"] for e in decodes] == [0] + [1] * (
        len(decodes) - 1)
    assert len(decodes) > 10
    kids = {}
    for e in events():
        kids.setdefault(e["args"]["parent"], []).append(e)
    reads = sorted(events("serving_decode_read"), key=lambda e: e["ts"])
    assert len(reads) == len(decodes)  # every step is read once
    for i, dec in enumerate(decodes):
        mine = {k["name"]: k for k in kids[dec["args"]["id"]]}
        launch = mine["serving_decode_launch"]
        if dec["args"]["ahead"]:
            # the read inside this step's span is the step before's: it
            # starts after this step's launch has returned
            read = mine["serving_decode_read"]
            assert read is reads[i - 1]
            assert end(launch) <= read["ts"]
        else:
            assert "serving_decode_read" not in mine
    # the last step is read with nothing left to launch: under its step
    assert reads[-1]["args"]["parent"] in {
        e["args"]["id"] for e in events("serving_step")}

    def below(span):
        out, todo = [], [span]
        while todo:
            for k in kids.get(todo.pop()["args"]["id"], []):
                out.append(k)
                todo.append(k)
        return out

    for step in events("serving_step"):
        inside = below(step)
        launches = [e for e in inside if e["name"].endswith("_launch")]
        blocked = [e for e in inside if e["name"].endswith("_read")]
        if launches and blocked:
            # between an iteration's first launch and its last the host
            # reads nothing from the device
            assert max(end(e) for e in launches) <= min(
                e["ts"] for e in blocked)
    m = observe.metrics()
    counted = [m.counter("dl4j_tpu_serving_decode_launches_total",
                         ahead=a).value for a in ("0", "1")]
    assert counted == [1, len(decodes) - 1]
    # a request's first token lands where its serving_prefill ends: behind
    # its iteration's decode launch, inside the stage that tiles the step
    prefills = events("serving_prefill")
    assert len(prefills) == len(PROMPTS)
    stages = events("serving_first_tokens")
    for read in events("serving_prefill_read"):
        assert any(st["ts"] <= read["ts"] and end(read) <= end(st) + 1.0
                   for st in stages)
        (pre,) = [p for p in prefills
                  if p["args"]["id"] == read["args"]["parent"]]
        assert abs(end(pre) - end(read)) <= 1.0
    for step in events("serving_step"):
        direct = kids.get(step["args"]["id"], [])
        assert sum(k["dur"] for k in direct) <= step["dur"] + 1e-3
    ttft = observe.metrics().histogram("dl4j_tpu_serving_ttft_seconds")
    assert ttft.count == len(PROMPTS)


def test_a_decode_step_counts_the_groups_the_paged_kernel_walks(
        name, monkeypatch):
    """``serving_decode``'s ``attn_groups`` and the counter
    ``dl4j_tpu_paged_decode_groups_total``: the groups of pages the paged
    kernel walks in a step, ``ceil(length / (group * page))`` over the
    active slots' lengths with the step's token, where the pool holds K/V
    rows. A latent pool's steps carry neither."""
    from deeplearning4j_tpu.ops import pallas_attention

    # a group of one page, so that these short sequences cross groups
    monkeypatch.setattr(pallas_attention, "_PAGED_GROUP_PAGES", 1)
    eng = make_engine(name)
    page = eng.cache.page_size
    want = []
    step_decode = eng._step_decode

    def spy(active, ahead):
        want.append(sum(-(-(int(eng.cache.seq_lens[s]) + 1) // page)
                        for s in active))
        return step_decode(active, ahead)

    eng._step_decode = spy
    futs = [eng.submit(p, max_new_tokens=10, eos_token=-1)
            for p in PROMPTS[:3]]
    while eng.scheduler.has_work():
        eng.step()
    assert all(f.result(timeout=0).finish_reason == "length" for f in futs)
    keep_programs(name, eng)
    got = [e["args"].get("attn_groups")
           for e in sorted(events("serving_decode"), key=lambda e: e["ts"])]
    counted = observe.metrics().counter(
        "dl4j_tpu_paged_decode_groups_total").value
    assert len(got) == len(want) > 5
    if name == "gpt":
        assert got == want and len(set(want)) > 2
        assert counted == sum(want)
    else:
        assert got == [None] * len(want) and counted == 0


def test_inline_steps_and_a_speculating_engine_never_run_ahead():
    from deeplearning4j_tpu.models.gpt import draft_config_for

    served = serve("gpt", [(p, dict(max_new_tokens=5, eos_token=-1))
                           for p in PROMPTS[:3]], started=False)
    assert all(reason == "length" for _t, reason, _p in served)
    decodes = events("serving_decode")
    assert decodes and {e["args"]["ahead"] for e in decodes} == {0}
    observe.reset()
    model, geo = _model("gpt")
    draft = GptModel(draft_config_for(model.cfg), seed=2)
    eng = GenerativeEngine(model, max_slots=SLOTS, seed=3, spec_k=2,
                           draft_model=draft, **geo)
    # one sampled request keeps the plain decode step on the path
    futs = [eng.submit(PROMPTS[0], max_new_tokens=6, eos_token=-1),
            eng.submit(PROMPTS[1], max_new_tokens=6, eos_token=-1,
                       temperature=0.8)]
    eng.start()
    try:
        got = [f.result(timeout=600) for f in futs]
    finally:
        eng.stop()
    assert [r.finish_reason for r in got] == ["length", "length"]
    assert got[0].tokens.tolist() == greedy_streams("gpt")[0][:6]
    decodes = events("serving_decode")
    assert decodes and {e["args"]["ahead"] for e in decodes} == {0}
    assert events("serving_verify")
    eng.check_invariants()


# (c) ------------------------------------------------------------------------


def run_until(eng, predicate, timeout=120.0):
    t0 = time.perf_counter()
    while not predicate():
        assert time.perf_counter() - t0 < timeout, "the engine made no way"
        time.sleep(0.002)


def checked(eng):
    """``check_invariants`` after every iteration of the worker's loop."""
    real, seen = eng._iterate, {"n": 0, "in_flight": 0}

    def iterate(ahead):
        produced = real(ahead)
        eng.check_invariants()
        seen["n"] += 1
        seen["in_flight"] += eng._flying is not None
        return produced

    eng._iterate = iterate
    return seen


def test_stop_with_a_step_in_flight_returns_what_landed(name):
    streams = greedy_streams(name)
    eng = make_engine(name)
    seen = checked(eng)
    futs = [eng.submit(p, max_new_tokens=12, eos_token=-1)
            for p in PROMPTS[:SLOTS]]
    eng.start()
    run_until(eng, lambda: seen["in_flight"] >= 3)
    eng.stop()
    assert eng.stopped_cleanly
    assert eng._flying is None and not eng._landing
    for fut, stream in zip(futs, streams):
        res = fut.result(timeout=0)
        # 12 tokens may all have landed before stop() was seen
        assert res.finish_reason in ("stopped", "length")
        toks = res.tokens.tolist()
        assert 1 <= len(toks) and toks == stream[:len(toks)]
    eng.check_invariants()
    assert eng.cache.free_pages == eng.cache.num_pages
    assert seen["in_flight"] >= 3


def test_a_crash_with_a_step_in_flight_recovers_the_same_tokens(name):
    streams = greedy_streams(name)
    eng = make_engine(name, restart_backoff_s=0.0)
    seen = checked(eng)
    # the fourth iteration dies after its admissions and before its launch,
    # the third iteration's step unread
    faults.arm("decode_step_error", after_n=3, max_fires=1)
    futs = [eng.submit(p, max_new_tokens=9, eos_token=-1, max_retries=1)
            for p in PROMPTS[:SLOTS + 1]]
    eng.start()
    try:
        got = [f.result(timeout=600) for f in futs]
    finally:
        eng.stop()
    assert eng.restarts == 1
    for res, stream in zip(got, streams):
        assert res.finish_reason == "length"
        assert res.tokens.tolist() == stream[:9]
    assert seen["in_flight"] >= 3
    eng.check_invariants()
    assert eng.cache.free_pages == eng.cache.num_pages


def test_a_deadline_with_a_step_in_flight_keeps_the_landed_tokens():
    streams = greedy_streams("gpt")
    eng = make_engine("gpt")
    seen = checked(eng)
    faults.arm("slow_decode")  # 50 ms a step: the deadline falls mid-way
    doomed = eng.submit(PROMPTS[0], max_new_tokens=12, eos_token=-1,
                        deadline_s=0.4)
    free = eng.submit(PROMPTS[1], max_new_tokens=12, eos_token=-1)
    eng.start()
    try:
        res = doomed.result(timeout=600)
        other = free.result(timeout=600)
    finally:
        eng.stop()
    assert res.finish_reason == "deadline"
    toks = res.tokens.tolist()
    assert 1 <= len(toks) < 12 and toks == streams[0][:len(toks)]
    assert other.finish_reason == "length"
    assert other.tokens.tolist() == streams[1]
    assert seen["in_flight"] >= 3
    assert eng.cache.free_pages == eng.cache.num_pages


def test_a_dying_engine_hands_over_the_sequence_that_left_its_slot():
    """An unrecoverable crash between a sequence's leaving its slot (complete
    by its count) and its last token's landing: the sequence goes back to
    the queue, where a cluster's hook finds it, and fails with the rest."""
    eng = make_engine("gpt", supervise=False)
    # the second iteration dies after its schedule stage and before its launch
    faults.arm("decode_step_error", after_n=1, max_fires=1)
    short = eng.submit(PROMPTS[0], max_new_tokens=2, eos_token=-1)
    long_ = eng.submit(PROMPTS[1], max_new_tokens=8, eos_token=-1)
    seen = {}

    def hook(exc):
        seen["queued"] = [item[1] for item in
                          eng.scheduler.pending_snapshot()]
        seen["active"] = [st.future for st in eng.scheduler.slots.values()]
        seen["retries"] = short_request.retries_used

    short_request = eng.scheduler.pending[0][0]
    eng.on_unrecoverable = hook
    eng.start()
    try:
        for fut in (short, long_):
            with pytest.raises(faults.InjectedFault):
                fut.result(timeout=600)
    finally:
        eng.stop()
    assert seen == {"queued": [short], "active": [long_], "retries": 1}
    assert not eng.scheduler.has_work() and eng._flying is None


# (d) ------------------------------------------------------------------------


def test_the_ring_holds_a_window_of_the_fastest_predicted_cell():
    """A started engine under a closed loop's load (a full bank, a request
    submitted when one returns) for a few hundred steps drops no event, and
    the ring holds what the comment above ``_MAX_EVENTS`` says it was sized
    for, at this loop's own events a step and a request."""
    eng = make_engine("gpt")
    lens = [5, 9, 14, 7, 11, 16]
    lock, state = threading.Lock(), {"left": 60, "done": 0, "tokens": 0}
    finished = threading.Event()

    def submit(i):
        fut = eng.submit(PROMPTS[i % len(PROMPTS)],
                         max_new_tokens=lens[i % len(lens)], eos_token=-1)
        fut.add_done_callback(returned)

    def returned(fut):
        with lock:
            state["done"] += 1
            state["tokens"] += len(fut.result().tokens)
            nxt = state["left"] = state["left"] - 1
            if state["done"] == 60 + SLOTS:
                finished.set()
        if nxt >= 0:
            submit(nxt)

    eng.start()
    try:
        for i in range(SLOTS):
            submit(i)
        assert finished.wait(600)
    finally:
        eng.stop()
    tr = observe.tracer()
    steps = len(events("serving_decode"))
    requests = len(events("serving_request", phases=("b",)))
    assert steps >= 200 and requests == 60 + SLOTS
    assert tr.dropped == 0
    # with the collector's hook in, whose passes of generations 1 and 2 are
    # spans of this ring too
    assert tracing.flush_host is host.flush
    per_request = 4 + 5  # two async pairs; admit, prefill and its two parts, a key
    per_step = (len(tr.events) - per_request * requests) / steps
    assert 8 <= per_step <= 13, per_step
    # the comment's arithmetic: 1.3 x 5000 tokens/s on 32 slots of requests
    # of 64 tokens, a 40 s window and the 10 s its drain may take
    rate, slots, new_tokens, seconds = 1.3 * 5000, 32, 64, 40 + 10
    need = seconds * (rate / slots * per_step
                      + rate / new_tokens * per_request)
    assert need < tracing._MAX_EVENTS == 262144, need
    assert tr.events.maxlen == tracing._MAX_EVENTS
