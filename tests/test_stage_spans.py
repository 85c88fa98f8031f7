"""Stage spans (docs/OBSERVABILITY.md § Span catalogue): ids, parents and
request ids in the tracer, the serving step and the scanned trainers from
the inside, and every program XLA builds counted where it is built."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.models.bert import BertConfig, BertModel
from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
from deeplearning4j_tpu.observe.tracing import SpanTracer
from deeplearning4j_tpu.serving import GenerativeEngine
from deeplearning4j_tpu.serving.sampling import SAMPLER_PATHS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = GptModel(GptConfig.tiny(), seed=1)
PROMPTS = [np.array([3, 5, 7, 9], np.int32), np.array([11, 2], np.int32),
           np.array([42, 43, 44, 45, 46, 47], np.int32)]


@pytest.fixture(autouse=True)
def fresh_observe():
    observe.reset()
    yield
    observe.reset()


def spans(name=None):
    """Thread spans ('X') and request spans (the 'b' of an async pair)."""
    evs = [e for e in observe.tracer().to_dict()["traceEvents"]
           if e["ph"] in ("X", "b")]
    return [e for e in evs if name is None or e["name"] == name]


def make_engine(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 6)
    kw.setdefault("max_prompt", 16)
    kw.setdefault("seed", 3)
    return GenerativeEngine(MODEL, **kw)


# ---------------------------------------------------------------- the tracer


class TestSpanIds:
    def test_ids_and_parents_nest_per_thread(self):
        tr = SpanTracer()
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                assert tr.current() == inner.id
            other = {}

            def work():
                with tr.span("elsewhere") as sp:
                    other["parent"], other["id"] = sp.parent, sp.id

            t = threading.Thread(target=work)
            t.start()
            t.join()
        assert inner.parent == outer.id and outer.parent is None
        assert other["parent"] is None  # another thread, another stack
        assert len({outer.id, inner.id, other["id"]}) == 3
        ev = {e["name"]: e["args"] for e in tr.events}
        assert ev["inner"] == {"id": inner.id, "parent": outer.id}
        assert tr.current() is None

    def test_parent_survives_an_exception_in_the_body(self):
        tr = SpanTracer()
        with tr.span("outer") as outer:
            with pytest.raises(ValueError):
                with tr.span("fails"):
                    raise ValueError("boom")
            with tr.span("after") as after:
                pass
        assert after.parent == outer.id
        assert [e["name"] for e in tr.events] == ["fails", "after", "outer"]

    def test_body_adds_args_before_the_span_closes(self):
        tr = SpanTracer()
        with tr.span("step", k=1) as sp:
            sp.set(produced=7)
        assert tr.events[-1]["args"]["produced"] == 7
        assert tr.events[-1]["args"]["k"] == 1

    def test_complete_between_takes_a_parent(self):
        tr = SpanTracer()
        t0 = time.perf_counter()
        with tr.span("outer") as outer:
            sid = tr.complete_between("late", t0, t0 + 0.5, parent=outer.id)
            orphan = tr.complete_between("orphan", t0, t0 + 0.5)
        ev = {e["name"]: e for e in tr.events}
        assert ev["late"]["args"] == {"id": sid, "parent": outer.id}
        assert ev["orphan"]["args"] == {"id": orphan, "parent": None}
        assert ev["late"]["ts"] == pytest.approx(
            (t0 - tr.perf_origin) * 1e6)

    def test_dropped_counts_evictions_and_clear_resets(self):
        tr = SpanTracer(max_events=4)
        for _ in range(6):
            with tr.span("x"):
                pass
        assert tr.dropped == 2 and len(tr.events) == 4
        tr.clear()
        assert tr.dropped == 0 and len(tr.events) == 0
        unbounded = SpanTracer(max_events=None)
        for _ in range(6):
            unbounded.instant("m")
        assert unbounded.dropped == 0

    def test_span_is_a_trace_annotation_under_a_profiler_session(
            self, monkeypatch):
        """With jax imported the span's interval is also open as a
        ``dl4j/<name>`` TraceAnnotation: one clock with the device trace."""
        from deeplearning4j_tpu.observe import tracing

        seen = []

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(tracing, "_ANNOTATION", Fake)
        with SpanTracer().span("serving_step"):
            seen.append(("body", None))
        assert seen == [("enter", "dl4j/serving_step"), ("body", None),
                        ("exit", "dl4j/serving_step")]

    def test_tracing_alone_leaves_jax_out(self):
        code = (
            "import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('t', sys.argv[1])\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "with m.SpanTracer().span('x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "print('ok')\n")
        path = os.path.join(ROOT, "deeplearning4j_tpu", "observe",
                            "tracing.py")
        out = subprocess.run([sys.executable, "-c", code, path],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"


# ------------------------------------------------------- the serving engine


EPS = 1.0  # microseconds, for the float arithmetic behind the timestamps

STAGES = ("serving_schedule", "serving_admit", "serving_next_key",
          "serving_prefill", "serving_prefill_launch", "serving_prefill_read",
          "serving_decode_upload", "serving_decode", "serving_decode_launch",
          "serving_decode_read", "serving_commit")


def sampler_steps():
    m = observe.metrics()
    return {p: m.counter("dl4j_tpu_serving_sampler_steps_total",
                         path=p).value for p in SAMPLER_PATHS}


@pytest.fixture
def served():
    eng = make_engine()
    futs = [eng.submit(p, max_new_tokens=4) for p in PROMPTS]
    while eng.scheduler.has_work():
        eng.step()
    return eng, futs, [f.result() for f in futs]


class TestServingStageSpans:
    def test_every_stage_has_a_step_ancestor(self, served):
        by_id = {e["args"]["id"]: e for e in spans()}
        for name in STAGES:
            found = spans(name)
            assert found, name
            for e in found:
                anc = e
                while anc["args"]["parent"] is not None:
                    anc = by_id[anc["args"]["parent"]]
                assert anc["name"] == "serving_step", (name, anc["name"])

    def test_children_never_sum_to_more_than_their_step(self, served):
        steps = spans("serving_step")
        assert [s["args"]["step"] for s in steps] == list(
            range(1, len(steps) + 1))
        for step in steps:
            kids = [e for e in spans()
                    if e["args"]["parent"] == step["args"]["id"]]
            assert kids
            assert sum(k["dur"] for k in kids) <= step["dur"] + 1e-3
            assert {"pending", "active", "admitted", "produced"} <= set(
                step["args"])
        assert sum(s["args"]["admitted"] for s in steps) == len(PROMPTS)
        assert sum(s["args"]["produced"] for s in steps) == sum(
            len(r.tokens) - 1 for r in served[2])

    def test_old_spans_keep_their_args(self, served):
        assert all(1 <= e["args"]["slots"] <= 2
                   for e in spans("serving_decode"))
        assert sorted(e["args"]["prompt_len"]
                      for e in spans("serving_prefill")) == sorted(
            p.size for p in PROMPTS)

    def test_greedy_requests_count_only_the_greedy_sampler(self, served):
        decodes = spans("serving_decode")
        assert decodes
        assert {e["args"]["sampler"] for e in decodes} == {"greedy"}
        assert sampler_steps() == {"greedy": len(decodes), "plain": 0,
                                   "filter": 0}

    @pytest.mark.parametrize("knobs,path", [
        ({"temperature": 0.8}, "plain"),
        ({"temperature": 0.8, "top_k": 3}, "filter"),
        ({"temperature": 0.8, "top_p": 0.9}, "filter"),
    ])
    def test_a_sampled_request_moves_the_steps_it_is_active_in(self, knobs,
                                                               path):
        """One sampled request beside a greedy one: the decode steps it is
        active in (a token each after its prefill's) take the body its
        knobs ask for, the others stay greedy, and the counter agrees with
        the spans."""
        eng = make_engine()
        greedy = eng.submit(PROMPTS[0], max_new_tokens=8)
        sampled = eng.submit(PROMPTS[1], max_new_tokens=3, **knobs)
        while eng.scheduler.has_work():
            eng.step()
        assert len(greedy.result().tokens) > len(sampled.result().tokens)
        by_path = {p: 0 for p in SAMPLER_PATHS}
        for e in spans("serving_decode"):
            by_path[e["args"]["sampler"]] += 1
        active = len(sampled.result().tokens) - 1
        assert active >= 1
        assert by_path[path] == active
        assert by_path["greedy"] == sum(by_path.values()) - active >= 1
        assert sampler_steps() == by_path

    def test_one_request_shares_its_id_and_its_ttft_adds_up(self, served):
        _eng, _futs, results = served
        waits = {e["args"]["request"]: e for e in spans("serving_queue_wait")}
        admits = {e["args"]["request"]: e for e in spans("serving_admit")
                  if e["args"]["admitted"]}
        prefills = {e["args"]["request"]: e for e in spans("serving_prefill")}
        done = {e["args"]["request"]: e for e in spans("serving_request")}
        assert len(waits) == len(PROMPTS)
        assert set(waits) == set(admits) == set(prefills) == set(done)
        # a request's spans are async pairs keyed by the request, with no
        # parent: they belong to no thread's stack
        assert all(w["args"]["parent"] is None and w["ph"] == "b"
                   and w["id"] == rid for rid, w in waits.items())
        ends = [e for e in observe.tracer().events if e["ph"] == "e"]
        assert len(ends) == len(waits) + len(done)
        by_len = {r.prompt_len: r for r in results}
        for rid, req in done.items():
            res = by_len[req["args"]["prompt_len"]]
            assert req["args"]["reason"] == res.finish_reason
            assert req["args"]["tokens"] == len(res.tokens)
            assert prefills[rid]["args"]["parent"] == admits[rid]["args"]["id"]
            # queue wait + the admission up to the first token = TTFT, from
            # the spans' own clock (microseconds; EPS for their rounding):
            # the wait ends where the admission that took the request
            # starts, the prefill is launched inside that admission, and
            # the first token lands where ``serving_prefill`` ends: one
            # clock reading is the span's end and the token's stamp. No
            # wall-clock tolerance: under other workers any stretch between
            # two reads of the clock can take milliseconds.
            wait, adm, pre = waits[rid], admits[rid], prefills[rid]
            end = lambda e: e["ts"] + e["dur"]  # noqa: E731
            assert abs(end(wait) - adm["ts"]) <= EPS
            assert adm["ts"] <= pre["ts"]
            assert abs(end(pre) - wait["ts"] - res.ttft_s * 1e6) <= EPS
            # inline, the admission reads its token before it closes (a
            # started engine reads it after its decode step's launch)
            assert end(pre) <= end(adm) + EPS
            launch, read = (
                next(e for e in spans("serving_prefill" + part)
                     if e["args"]["parent"] == pre["args"]["id"])
                for part in ("_launch", "_read"))
            assert launch["ts"] == pre["ts"] and end(launch) <= read["ts"]
            assert abs(end(read) - end(pre)) <= EPS
        wait_h = observe.metrics().histogram(
            "dl4j_tpu_serving_queue_wait_seconds")
        assert wait_h.count == len(PROMPTS)

    def test_a_retried_request_keeps_its_id(self):
        from deeplearning4j_tpu import faults

        eng = make_engine(restart_backoff_s=0.0)
        fut = eng.submit(PROMPTS[0], max_new_tokens=4, max_retries=2)
        rid = eng.scheduler.pending[0][0].request_id
        assert rid is not None
        eng.step()  # admitted, one token decoded
        faults.arm("decode_step_error", max_fires=1)
        try:
            with pytest.raises(faults.InjectedFault) as err:
                eng.step()
        finally:
            faults.reset()
        assert eng._recover(err.value)
        assert eng.scheduler.pending[0][0].request_id == rid
        while eng.scheduler.has_work():
            eng.step()
        assert fut.result().finish_reason == "length"
        assert [e["args"]["request"] for e in spans("serving_queue_wait")] \
            == [rid, rid]
        (req,) = spans("serving_request")
        assert req["args"]["request"] == rid
        assert req["args"]["retries_used"] == 1

    def test_an_idle_engine_writes_one_idle_span(self):
        eng = make_engine().start()
        try:
            time.sleep(0.05)
            eng.submit(PROMPTS[0], max_new_tokens=2).result(timeout=300)
        finally:
            eng.stop()
        idle = spans("serving_idle")
        assert 1 <= len(idle) <= 2  # before the request; maybe once after
        assert idle[0]["dur"] >= 0.04e6
        # step + idle tile the worker's time: the first step starts where
        # the idle span ends
        first = min(spans("serving_step"), key=lambda e: e["ts"])
        assert first["ts"] - (idle[0]["ts"] + idle[0]["dur"]) < 5e3


# ------------------------------------------------------ the scanned trainers


def bert_batch(b=2, t=8, vocab=64):
    rng = np.random.default_rng(0)
    return {"ids": rng.integers(0, vocab, (b, t)).astype(np.int32),
            "segments": np.zeros((b, t), np.int32),
            "mask": np.ones((b, t), np.int32),
            "mlm_labels": rng.integers(0, vocab, (b, t)).astype(np.int32),
            "mlm_mask": (rng.random((b, t)) < 0.3).astype(np.float32)}


def tiny_bert():
    return BertModel(BertConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                                intermediate=32, max_position=16), seed=0)


def programs_total():
    return observe.metrics().counter("dl4j_tpu_xla_programs_total").value


class TestScannedTrainerSpans:
    def test_bert_scanned_call_records_itself(self):
        model = tiny_bert()
        model.fit_mlm_scanned(bert_batch(), 3)
        (call,) = spans("fit_scanned")
        assert call["args"]["model"] == "bert" and call["args"]["steps"] == 3
        kids = {e["name"]: e for e in spans()
                if e["args"]["parent"] == call["args"]["id"]}
        assert {"fit_scanned_dispatch", "fit_scanned_read"} <= set(kids)
        assert kids["fit_scanned_dispatch"]["ts"] < kids[
            "fit_scanned_read"]["ts"]
        m = observe.metrics()
        assert m.counter("dl4j_tpu_train_steps_total",
                         model="bert").value == 3
        assert m.counter("dl4j_tpu_train_examples_total",
                         model="bert").value == 6
        assert observe.summary()["train"]["steps"] == 3
        led = [e for e in observe.ledger().events() if e.graph == "bert"]
        assert [e.key for e in led] == ["mlm_scanned"]
        model.fit_mlm_scanned(bert_batch(), 3)
        calls = [e["args"]["call"] for e in spans("fit_scanned")]
        assert calls[1] == calls[0] + 1

    def test_bert_per_batch_loop_counts_and_writes_no_spans(self):
        tiny_bert().fit_mlm([bert_batch(), bert_batch()], epochs=2)
        m = observe.metrics()
        assert m.counter("dl4j_tpu_train_steps_total",
                         model="bert").value == 4
        assert m.counter("dl4j_tpu_train_examples_total",
                         model="bert").value == 8
        assert not spans("fit_scanned")

    def test_programs_are_counted_where_xla_builds_them(self):
        model = tiny_bert()
        model.fit_mlm_scanned(bert_batch(), 2)
        built = programs_total()
        assert built >= 1
        model.fit_mlm_scanned(bert_batch(), 2)
        assert programs_total() == built  # a second identical call: none
        before = {e["args"]["id"] for e in spans("xla_compile")}
        model.fit_mlm_scanned(bert_batch(b=3), 2)  # a new batch shape
        assert programs_total() >= built + 1
        dispatch = spans("fit_scanned_dispatch")[-1]
        new = [e for e in spans("xla_compile")
               if e["args"]["id"] not in before]
        assert new and any(
            e["args"]["parent"] == dispatch["args"]["id"] for e in new)
        assert all(e["cat"] == "compile" for e in new)

    @pytest.mark.parametrize("model", ["mln", "graph"])
    def test_mln_and_graph_keep_the_same_record(self, model):
        from deeplearning4j_tpu import nn
        from deeplearning4j_tpu.nn import graph as G

        if model == "mln":
            net = nn.MultiLayerNetwork(
                nn.builder().seed(1).list()
                .layer(nn.DenseLayer(n_out=8, activation="tanh"))
                .layer(nn.OutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(nn.InputType.feed_forward(4)).build()).init()
        else:
            b = (G.graph_builder().seed(1).add_inputs("in")
                 .set_input_types(**{"in": nn.InputType.feed_forward(4)}))
            b.add_layer("fc", nn.DenseLayer(n_out=8, activation="tanh"), "in")
            b.add_layer("out", nn.OutputLayer(n_out=2, activation="softmax",
                                              loss="mcxent"), "fc")
            b.set_outputs("out")
            net = G.ComputationGraph(b.build()).init()
        x = np.random.default_rng(0).random((6, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[[0, 1, 0, 1, 0, 1]]
        losses = net.fit_scanned(x, y, steps=2)
        assert losses.shape == (2,) and net.last_batch_size == 6
        (call,) = spans("fit_scanned")
        assert call["args"]["model"] == model and call["args"]["steps"] == 2
        names = {e["name"] for e in spans()
                 if e["args"]["parent"] == call["args"]["id"]}
        assert {"fit_scanned_dispatch", "fit_scanned_read"} <= names
        m = observe.metrics()
        assert m.counter("dl4j_tpu_train_steps_total",
                         model=model).value == 2
        assert m.counter("dl4j_tpu_train_examples_total",
                         model=model).value == 12
        # the device-resident-epoch mode: a leading [steps, batch, ...] axis
        net.fit_scanned(np.stack([x, x, x]), np.stack([y, y, y]))
        assert spans("fit_scanned")[-1]["args"]["steps"] == 3
        assert m.counter("dl4j_tpu_train_examples_total",
                         model=model).value == 12 + 18
