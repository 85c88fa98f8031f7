"""observe/ — unified runtime telemetry (docs/OBSERVABILITY.md).

Covers the metric model (counters/gauges/histograms + streaming
percentiles, thread safety, Prometheus rendering), the span tracer (ONE
trace format shared with utils/profiling.py), the recompile ledger through
real SameDiff / MultiLayerNetwork jit caches (same-shape refit → no event;
new batch shape → new_shape; constant rebind → constant_rebind), the
ParallelInference serving metrics under multithreaded client load, and the
JSONL event log."""

import json
import threading

import numpy as np
import pytest

from deeplearning4j_tpu import observe


@pytest.fixture(autouse=True)
def fresh_observe():
    """Isolate every test from telemetry recorded by earlier tests (and by
    the fixture-owning test itself from later ones)."""
    observe.reset()
    yield
    observe.reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_and_gauge(self):
        m = observe.metrics()
        c = m.counter("t_total")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert m.counter("t_total") is c  # create-or-get
        with pytest.raises(ValueError):
            c.inc(-1)
        g = m.gauge("t_depth")
        g.set(7)
        g.dec(2)
        assert g.value == 5

    def test_labels_are_distinct_instruments(self):
        m = observe.metrics()
        m.counter("t_steps", model="mln").inc(3)
        m.counter("t_steps", model="graph").inc(4)
        assert m.counter("t_steps", model="mln").value == 3
        assert m.family_total("t_steps") == 7

    def test_kind_conflict_raises(self):
        m = observe.metrics()
        m.counter("t_thing")
        with pytest.raises(TypeError):
            m.histogram("t_thing")

    def test_histogram_percentiles(self):
        h = observe.metrics().histogram("t_lat")
        for v in [0.001] * 98 + [0.5, 1.0]:
            h.observe(v)
        assert h.count == 100
        # p50 lands in the bucket containing 1ms; p99 near the 0.5-1.0 tail
        assert h.quantile(0.50) < 0.01
        assert h.quantile(0.99) > 0.1
        assert h.min == 0.001 and h.max == 1.0
        pct = h.percentiles()
        assert set(pct) == {"p50", "p95", "p99"}

    def test_histogram_empty(self):
        h = observe.metrics().histogram("t_empty")
        assert h.quantile(0.5) is None and h.mean is None

    def test_merged_histogram_across_labels(self):
        m = observe.metrics()
        m.histogram("t_step", model="a").observe(0.01)
        m.histogram("t_step", model="b").observe(0.01)
        merged = m.merged_histogram("t_step")
        assert merged.count == 2

    def test_thread_safety_exact_counts(self):
        m = observe.metrics()
        c = m.counter("t_conc_total")
        h = m.histogram("t_conc_lat")

        def worker(seed):
            r = np.random.RandomState(seed)
            for _ in range(1000):
                c.inc()
                h.observe(float(r.rand()) * 0.01)

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert h.count == 8000

    def test_prometheus_rendering(self):
        m = observe.metrics()
        m.counter("t_req_total", model="mln").inc(2)
        h = m.histogram("t_req_seconds")
        h.observe(0.003)
        text = m.render_prometheus()
        assert "# TYPE t_req_total counter" in text
        assert 't_req_total{model="mln"} 2' in text
        assert "# TYPE t_req_seconds histogram" in text
        assert "t_req_seconds_count 1" in text
        assert "t_req_seconds_sum 0.003" in text
        assert 'le="+Inf"} 1' in text
        # cumulative buckets are monotonically non-decreasing
        cums = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
                if l.startswith("t_req_seconds_bucket")]
        assert cums == sorted(cums) and cums[-1] == 1
        # the eagerly registered core catalog is always present
        assert "dl4j_tpu_recompiles_total" in text
        assert "dl4j_tpu_serving_request_seconds" in text


# ---------------------------------------------------------------------------
# span tracer — one trace format
# ---------------------------------------------------------------------------


class TestSpanTracer:
    def test_nested_spans_and_export(self, tmp_path):
        tr = observe.tracer()
        with tr.span("outer", category="test", k=1):
            with tr.span("inner", category="test"):
                pass
            tr.instant("mark", note="x")
        names = [e["name"] for e in tr.events]
        assert names == ["inner", "mark", "outer"]  # inner completes first
        ev = {e["name"]: e for e in tr.events}
        assert ev["outer"]["ph"] == "X" and ev["outer"]["dur"] >= 0
        assert ev["outer"]["args"]["k"] == 1
        # every span carries its id and its parent's (tests/test_stage_spans.py)
        assert ev["inner"]["args"]["parent"] == ev["outer"]["args"]["id"]
        p = str(tmp_path / "trace.json")
        tr.write(p)
        data = json.load(open(p))
        assert data["displayTimeUnit"] == "ms"
        assert len(data["traceEvents"]) == 3

    def test_complete_between_perf_counter(self):
        import time

        tr = observe.tracer()
        t0 = time.perf_counter()
        t1 = t0 + 0.25
        tr.complete_between("window", t0, t1, category="test")
        ev = tr.events[-1]
        assert abs(ev["dur"] - 0.25e6) < 1.0  # microseconds

    def test_chrome_trace_writer_is_the_same_format(self, tmp_path):
        """utils/profiling.ChromeTraceWriter IS a SpanTracer now — the
        profiling artifact and the telemetry spans share one format."""
        from deeplearning4j_tpu.observe.tracing import SpanTracer
        from deeplearning4j_tpu.utils.profiling import (ChromeTraceWriter,
                                                        ProfileAnalyzer)

        w = ChromeTraceWriter()
        assert isinstance(w, SpanTracer)
        with w.span("step", category="train_step"):
            pass
        p = str(tmp_path / "t.json")
        w.write(p)
        agg = ProfileAnalyzer.load(p)
        assert "train_step" in agg

    def test_profiling_listener_still_writes(self, tmp_path):
        from deeplearning4j_tpu.utils.profiling import ProfilingListener

        p = str(tmp_path / "prof.json")
        pl = ProfilingListener(p)
        pl.on_epoch_start(model=None)
        pl.iteration_done(None, 1, 0, 0.5)
        pl.iteration_done(None, 2, 0, 0.4)
        pl.on_epoch_end(model=None)
        data = json.load(open(p))
        assert any(e.get("cat") == "train_step"
                   for e in data["traceEvents"])


# ---------------------------------------------------------------------------
# recompile ledger — SameDiff jit cache
# ---------------------------------------------------------------------------


def _linreg_sd(with_const=False):
    from deeplearning4j_tpu import nn
    from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig

    sd = SameDiff.create()
    x = sd.placeholder("x", shape=(None, 4))
    labels = sd.placeholder("labels", shape=(None, 1))
    w = sd.var("w", np.zeros((4, 1), np.float32))
    pred = x.mmul(w)
    if with_const:
        scale = sd.constant("scale", np.float32(1.0))
        pred = pred * scale
    sd.loss.mean_squared_error(pred, labels).rename("loss")
    sd.set_training_config(TrainingConfig(
        updater=nn.Sgd(learning_rate=0.01),
        data_set_feature_mapping=["x"], data_set_label_mapping=["labels"],
        loss_variables=["loss"]))
    return sd


def _fit(sd, n=32, epochs=1):
    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)

    r = np.random.RandomState(0)
    xs = r.randn(n, 4).astype(np.float32)
    ys = (xs @ np.array([[1.0], [2.0], [0.5], [-1.0]], np.float32))
    sd.fit(ListDataSetIterator(DataSet(xs, ys), batch_size=n), epochs=epochs)


def _events(graph=None, key=None):
    evs = observe.ledger().events()
    return [e for e in evs
            if (graph is None or e.graph == graph)
            and (key is None or e.key == key)]


class TestRecompileLedgerSameDiff:
    def test_same_shape_refit_exactly_one_compile_event(self):
        sd = _linreg_sd()
        _fit(sd, n=32, epochs=2)
        _fit(sd, n=32, epochs=3)   # same shapes: cached step fn, no event
        evs = _events("samediff", "train")
        assert len(evs) == 1
        assert evs[0].cause == "first_compile"
        assert "[32,4]" in evs[0].signature

    def test_new_batch_shape_one_new_event(self):
        sd = _linreg_sd()
        _fit(sd, n=32)
        _fit(sd, n=48)             # new feed signature on the cached fn
        evs = _events("samediff", "train")
        assert [e.cause for e in evs] == ["first_compile", "new_shape"]
        assert "[48,4]" in evs[1].signature

    def test_constant_rebind_cause(self):
        sd = _linreg_sd(with_const=True)
        _fit(sd, n=32)
        sd.set_arr("scale", np.float32(2.0))   # CONSTANT rebind: cache wiped
        _fit(sd, n=32)
        evs = _events("samediff", "train")
        assert [e.cause for e in evs] == ["first_compile", "constant_rebind"]

    def test_output_path_new_shape(self):
        from deeplearning4j_tpu.autodiff import SameDiff

        sd2 = SameDiff.create()
        x = sd2.placeholder("x", shape=(None, 3))
        w = sd2.var("w", np.ones((3, 2), np.float32))
        x.mmul(w).rename("out")
        sd2.output({"x": np.zeros((4, 3), np.float32)}, "out")
        sd2.output({"x": np.zeros((4, 3), np.float32)}, "out")  # cache hit
        sd2.output({"x": np.zeros((6, 3), np.float32)}, "out")  # retrace
        evs = _events("samediff", "exec")
        assert [e.cause for e in evs] == ["first_compile", "new_shape"]
        # the exec path's stats carry the measured trace/compile split
        assert evs[0].stats is not None
        assert evs[0].stats.trace_seconds is not None

    def test_graph_mutation_cause(self):
        from deeplearning4j_tpu.autodiff import SameDiff

        sd = SameDiff.create()
        x = sd.placeholder("x", shape=(None, 3))
        w = sd.var("w", np.ones((3, 3), np.float32))
        h = x.mmul(w)
        h.rename("out")
        feeds = {"x": np.zeros((2, 3), np.float32)}
        sd.output(feeds, "out")
        sd.math.tanh(h).rename("out2")   # mutation AFTER a compile
        # the PREVIOUSLY-compiled key rebuilt → graph_mutation; a key never
        # compiled before ("out2") is a first_compile even post-mutation
        sd.output(feeds, "out")
        sd.output(feeds, "out2")
        evs = _events("samediff", "exec")
        assert [e.cause for e in evs] == [
            "first_compile", "graph_mutation", "first_compile"]

    def test_recompile_counters(self):
        sd = _linreg_sd()
        _fit(sd, n=32)
        _fit(sd, n=16)
        m = observe.metrics()
        assert m.counter("dl4j_tpu_recompiles_total").value >= 2
        assert m.counter("dl4j_tpu_recompile_cause_total",
                         cause="new_shape").value >= 1


class TestRecompileLedgerNetworks:
    def test_mln_fit_first_compile_then_new_shape(self):
        from deeplearning4j_tpu import nn

        net = nn.MultiLayerNetwork(
            nn.builder().seed(0).updater(nn.Sgd(learning_rate=0.1)).list()
            .layer(nn.DenseLayer(n_out=4, activation="tanh"))
            .layer(nn.OutputLayer(n_out=2, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.feed_forward(3)).build()).init()
        r = np.random.RandomState(0)
        x = r.randn(16, 3).astype(np.float32)
        y = np.eye(2)[r.randint(0, 2, 16)].astype(np.float32)
        net.fit(x, y, batch_size=16)
        net.fit(x, y, batch_size=16)   # same shape: no new event
        net.fit(x[:8], y[:8], batch_size=8)
        evs = _events("mln", "train_step")
        assert [e.cause for e in evs] == ["first_compile", "new_shape"]
        m = observe.metrics()
        assert m.counter("dl4j_tpu_train_steps_total", model="mln").value == 3
        assert m.counter("dl4j_tpu_train_examples_total",
                         model="mln").value == 40
        assert m.merged_histogram("dl4j_tpu_train_step_seconds").count == 3


# ---------------------------------------------------------------------------
# ParallelInference serving metrics under concurrent clients
# ---------------------------------------------------------------------------


class TestServingMetrics:
    def test_multithreaded_clients_counters_and_percentiles(self):
        from deeplearning4j_tpu import nn
        from deeplearning4j_tpu.parallel.mesh import ParallelInference

        net = nn.MultiLayerNetwork(
            nn.builder().seed(0).updater(nn.Sgd(learning_rate=0.1)).list()
            .layer(nn.DenseLayer(n_out=8, activation="relu"))
            .layer(nn.OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(nn.InputType.feed_forward(5)).build()).init()
        # max_batch=8: divisible by the 8-device virtual CPU mesh
        pi = ParallelInference(net, max_batch=8, window_ms=2.0).start()
        errors = []
        try:
            def client(seed):
                r = np.random.RandomState(seed)
                try:
                    for _ in range(10):
                        out = pi.predict(r.randn(5).astype(np.float32))
                        assert out.shape == (1, 3)
                except Exception as e:  # surfaced below
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(s,))
                       for s in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            pi.stop()
        assert not errors, errors
        m = observe.metrics()
        # counters consistent: every request counted once, each row served
        assert m.counter("dl4j_tpu_serving_requests_total").value == 40
        assert m.counter("dl4j_tpu_serving_rows_total").value == 40
        batches = m.counter("dl4j_tpu_serving_batches_total").value
        assert 5 <= batches <= 40  # batched (>=5 at max_batch=8) but every
        #                            request still individually served
        lat = m.histogram("dl4j_tpu_serving_request_seconds")
        assert lat.count == 40
        pct = lat.percentiles()
        assert pct["p50"] is not None and pct["p99"] is not None
        assert 0 < pct["p50"] <= pct["p99"]
        wait = m.histogram("dl4j_tpu_serving_queue_wait_seconds")
        assert wait.count == 40
        occ = m.histogram("dl4j_tpu_serving_batch_occupancy")
        assert occ.count == batches
        assert 0 < occ.mean <= 1.0
        # summary() carries the serving section
        s = observe.summary()
        assert s["serving"]["requests"] == 40
        assert s["serving"]["p99_ms"] is not None


# ---------------------------------------------------------------------------
# JSONL event log
# ---------------------------------------------------------------------------


class TestJsonlEventLog:
    def test_events_append_when_env_set(self, tmp_path, monkeypatch):
        path = str(tmp_path / "obs.jsonl")
        monkeypatch.setenv(observe.OBS_LOG_ENV, path)
        observe.ledger().record(graph="samediff", key="train",
                                signature="x:f32[4,2]", cause="new_shape")
        observe.log_event("train_epoch", model="mln", epoch=1, steps=7)
        lines = [json.loads(l) for l in open(path).read().splitlines()]
        assert [l["kind"] for l in lines] == ["recompile", "train_epoch"]
        assert lines[0]["cause"] == "new_shape"
        assert lines[1]["steps"] == 7
        assert all("ts" in l for l in lines)

    def test_noop_without_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv(observe.OBS_LOG_ENV, raising=False)
        observe.log_event("train_epoch", steps=1)  # must not raise

    def test_obsreport_log_mode(self, tmp_path, monkeypatch, capsys):
        import sys

        from deeplearning4j_tpu.autodiff.optimize import OptimizeStats

        path = str(tmp_path / "obs.jsonl")
        monkeypatch.setenv(observe.OBS_LOG_ENV, path)
        st = OptimizeStats()
        st.record_fusion("attention", 12)
        st.record_fusion("epilogue", 72)
        observe.ledger().record(graph="mln", key="train_step",
                                signature="s", cause="first_compile",
                                stats=st)
        observe.log_event("serving_batch", rows=6, requests=3,
                          batch_seconds=0.004)
        monkeypatch.delenv(observe.OBS_LOG_ENV)

        sys.path.insert(0, "tools")
        try:
            import obsreport
        finally:
            sys.path.pop(0)
        rc = obsreport._summarize_log(path, json_mode=True)
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip())
        assert out["by_kind"] == {"recompile": 1, "serving_batch": 1}
        assert out["recompile_causes"] == {"first_compile": 1}
        assert out["serving_rows"] == 6
        # fusion hits ride the recompile event into the post-hoc summary
        assert out["fusion_hits"] == {"attention": 12, "epilogue": 72}


# ---------------------------------------------------------------------------
# ledger unit behavior
# ---------------------------------------------------------------------------


class TestLedgerUnit:
    def test_unknown_cause_rejected(self):
        with pytest.raises(ValueError):
            observe.ledger().record(graph="g", key="k", signature="s",
                                    cause="cosmic_rays")

    def test_bounded(self):
        led = observe.RecompileLedger(max_events=5)
        for i in range(9):
            led.record(graph="g", key="k", signature=f"s{i}",
                       cause="new_shape")
        assert len(led) == 5
        assert led.events()[0].signature == "s4"  # oldest dropped

    def test_summary_by_cause(self):
        led = observe.ledger()
        led.record(graph="g", key="k", signature="a", cause="first_compile")
        led.record(graph="g", key="k", signature="b", cause="new_shape")
        s = led.summary()
        assert s["total"] == 2
        assert s["by_cause"] == {"first_compile": 1, "new_shape": 1}
