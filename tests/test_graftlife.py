"""graftlife: per-rule fixture tests (positive + negative per rule,
including ownership-transfer-via-call and raise-path negatives), the
justified-suppression contract, shrink-only baseline mechanics over the
new tier, the repo-wide zero-unbaselined assertion, the static ownership
inventory in span units, the live lifetrace-vs-inventory consistency
check, and regression tests for the real findings the tier convicted
(the engine-step admission unwind, the hub's torn manifest, the UI
server's unjoined worker, the prefetch iterator's worker, the async
checkpoint writer's orphaned tmps).

The whole-repo gate run lives in test_graftlint.py (GR001-GR005 ride the
same registry, so ``test_repo_has_no_new_findings`` already covers the
new tier); this file owns everything graftlife-specific.
"""

import glob
import os
import tempfile
import textwrap
import threading
import time
import types

import numpy as np
import pytest

from deeplearning4j_tpu.lint import Finding, lint_paths, lint_source, \
    write_baseline
from deeplearning4j_tpu.lint.rules_lifecycle import (
    GR_RULES, OwnershipInventory, static_ownership_inventory,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, rules=None):
    return lint_source(textwrap.dedent(src), path="fixture.py", rules=rules)


def _rules_hit(findings):
    return {f.rule for f in findings}


def _fake_net(value: float):
    r = np.random.RandomState(0)
    net = types.SimpleNamespace()
    net.params = {"W": (r.randn(4, 4) * 0 + value).astype(np.float32)}
    net.opt_state = {"W": np.zeros((4, 4), np.float32)}
    net.net_state = {}
    net.iteration_count = int(value)
    net.epoch_count = 0
    return net


# ---------------------------------------------------------------------------
# GR001 — unbalanced page ownership
# ---------------------------------------------------------------------------


class TestGR001PageOwnership:
    def test_true_positive_leak_via_early_return(self):
        fs = _lint("""
            class Pool:
                def grab(self):
                    p = self.cache.alloc_page()
                    if self.full:
                        return None
                    self.cache.release(p)
                    return True
        """, rules=["GR001"])
        assert _rules_hit(fs) == {"GR001"}
        assert "'p'" in fs[0].message and "return" in fs[0].message

    def test_true_positive_leak_via_raise(self):
        fs = _lint("""
            def grab(cache, check):
                p = cache.alloc_page()
                if check():
                    raise RuntimeError("bad state")
                cache.release(p)
        """, rules=["GR001"])
        assert _rules_hit(fs) == {"GR001"}
        assert "raise" in fs[0].message

    def test_negative_released_on_every_path(self):
        fs = _lint("""
            def grab(cache):
                p = cache.alloc_page()
                if p is None:
                    return "oom"
                cache.release(p)
                return "ok"
        """, rules=["GR001"])
        assert fs == []

    def test_negative_none_guard_raise_path(self):
        # the allocator's None-on-exhaustion contract: the failure branch
        # holds nothing, so raising there is not a leak
        fs = _lint("""
            def grab(cache):
                p = cache.alloc_page()
                if p is None:
                    raise RuntimeError("pool exhausted")
                cache.release(p)
        """, rules=["GR001"])
        assert fs == []

    def test_negative_try_finally_discharges(self):
        fs = _lint("""
            def grab(cache, work):
                p = cache.alloc_page()
                try:
                    work(slot=3)
                finally:
                    cache.release(p)
        """, rules=["GR001"])
        assert fs == []

    def test_negative_free_slot_discharges_everything(self):
        fs = _lint("""
            def grow(cache, slot, check):
                p = cache.alloc_page()
                q = cache.alloc_page()
                if check():
                    cache.free_slot(slot)
                    raise RuntimeError("unwound")
                cache.release(p)
                cache.release(q)
        """, rules=["GR001"])
        assert fs == []

    def test_negative_handoff_to_radix_tree(self):
        # tree.insert retains what it keeps — the documented handoff
        fs = _lint("""
            def publish(cache, tree, key):
                p = cache.alloc_page()
                q = cache.alloc_page()
                tree.insert(key, [p, q])
                return key
        """, rules=["GR001"])
        assert fs == []

    def test_negative_ownership_transfer_via_call(self):
        # passing the held ref to ANY callee transfers ownership — the
        # intra-module helper that releases its parameter now owns it
        fs = _lint("""
            class Pool:
                def _give_back(self, page):
                    self.cache.release(page)

                def grab(self, check):
                    p = self.cache.alloc_page()
                    if check():
                        self._give_back(p)
                        raise RuntimeError("unwound")
                    self._give_back(p)
        """, rules=["GR001"])
        assert fs == []

    def test_negative_ownership_transfer_via_return(self):
        fs = _lint("""
            def grab(cache):
                p = cache.alloc_page()
                return p
        """, rules=["GR001"])
        assert fs == []

    def test_negative_stored_into_container(self):
        fs = _lint("""
            def grab(cache, owned, slot):
                p = cache.cow_page(slot, 0)
                owned[slot] = p
        """, rules=["GR001"])
        assert fs == []

    def test_call_graph_arm_positive(self):
        # the engine-step shape: the prefill path has raise-unwind
        # protection, the acquiring admission call does not
        fs = _lint("""
            class Engine:
                def _admit(self, slot):
                    self.cache.map_shared(slot, 0, 1)

                def step(self, slot):
                    self._admit(slot)
                    try:
                        self._prefill(slot)
                    except Exception:
                        self.cache.free_slot(slot)
                        raise
        """, rules=["GR001"])
        assert _rules_hit(fs) == {"GR001"}
        assert "_admit" in fs[0].message and "outside" in fs[0].message

    def test_call_graph_arm_negative_protected(self):
        fs = _lint("""
            class Engine:
                def _admit(self, slot):
                    self.cache.map_shared(slot, 0, 1)

                def step(self, slot):
                    try:
                        self._admit(slot)
                        self._prefill(slot)
                    except Exception:
                        self.cache.free_slot(slot)
                        raise
        """, rules=["GR001"])
        assert fs == []

    def test_not_applied_to_tools(self):
        src = textwrap.dedent("""
            def grab(cache, check):
                p = cache.alloc_page()
                if check():
                    raise RuntimeError("bad")
                cache.release(p)
        """)
        assert lint_source(src, path="tools/bench.py",
                           rules=["GR001"]) == []


# ---------------------------------------------------------------------------
# GR002 — double-release hazard
# ---------------------------------------------------------------------------


class TestGR002DoubleRelease:
    def test_true_positive_second_release(self):
        fs = _lint("""
            def unwind(cache):
                p = cache.alloc_page()
                cache.release(p)
                cache.release(p)
        """, rules=["GR002"])
        assert _rules_hit(fs) == {"GR002"}
        assert "released twice" in fs[0].message

    def test_true_positive_two_loops_same_list(self):
        fs = _lint("""
            def drain(cache, pages):
                for p in pages:
                    cache.release(p)
                for p in pages:
                    cache.release(p)
        """, rules=["GR002"])
        assert _rules_hit(fs) == {"GR002"}
        assert "two separate loops" in fs[0].message

    def test_negative_single_release(self):
        fs = _lint("""
            def unwind(cache):
                p = cache.alloc_page()
                cache.release(p)
        """, rules=["GR002"])
        assert fs == []

    def test_negative_release_on_disjoint_branches(self):
        fs = _lint("""
            def unwind(cache, fast):
                p = cache.alloc_page()
                if fast:
                    cache.release(p)
                else:
                    cache.release(p)
        """, rules=["GR002"])
        assert fs == []

    def test_negative_two_loops_different_lists(self):
        fs = _lint("""
            def drain(cache, owned, shared):
                for p in owned:
                    cache.release(p)
                for p in shared:
                    cache.release(p)
        """, rules=["GR002"])
        assert fs == []

    def test_negative_reacquired_then_released(self):
        fs = _lint("""
            def churn(cache):
                p = cache.alloc_page()
                cache.release(p)
                p = cache.alloc_page()
                cache.release(p)
        """, rules=["GR002"])
        assert fs == []


# ---------------------------------------------------------------------------
# GR003 — terminal-vocabulary exactly-once
# ---------------------------------------------------------------------------


class TestGR003TerminalExactlyOnce:
    def test_true_positive_completer_without_funnel(self):
        fs = _lint("""
            def finish(fut, result):
                fut.set_result(result)
        """, rules=["GR003"])
        assert _rules_hit(fs) == {"GR003"}
        assert "count_terminal" in fs[0].message

    def test_true_positive_deferred_lambda_completer(self):
        fs = _lint("""
            def finish_later(fut, pool):
                pool.defer(lambda: fut.set_exception(RuntimeError("x")))
        """, rules=["GR003"])
        assert _rules_hit(fs) == {"GR003"}

    def test_true_positive_double_count_straight_line(self):
        fs = _lint("""
            def retire(fut, count_terminal):
                fut.set_result(1)
                count_terminal("done")
                count_terminal("done")
        """, rules=["GR003"])
        assert _rules_hit(fs) == {"GR003"}
        assert "twice" in fs[0].message

    def test_negative_completer_with_funnel(self):
        fs = _lint("""
            def finish(fut, result, count_terminal):
                fut.set_result(result)
                count_terminal("done")
        """, rules=["GR003"])
        assert fs == []

    def test_negative_module_local_funnel_helper(self):
        # counting() fixpoint: _note reaches count_terminal, so calling
        # _note IS routing through the funnel
        fs = _lint("""
            def _note(reason):
                count_terminal(reason)

            def finish(fut, result):
                fut.set_result(result)
                _note("done")
        """, rules=["GR003"])
        assert fs == []

    def test_negative_known_funnel_helpers(self):
        fs = _lint("""
            class Engine:
                def crash(self, req, fut):
                    self._finish_unslotted(req, fut, "oom")
                    fut.set_exception(RuntimeError("oom"))
        """, rules=["GR003"])
        assert fs == []

    def test_negative_counts_on_separate_branches(self):
        fs = _lint("""
            def retire(fut, ok, count_terminal):
                fut.set_result(1)
                if ok:
                    count_terminal("done")
                else:
                    count_terminal("error")
        """, rules=["GR003"])
        assert fs == []


# ---------------------------------------------------------------------------
# GR004 — unstoppable thread
# ---------------------------------------------------------------------------


class TestGR004UnstoppableThread:
    def test_true_positive_local_never_joined(self):
        fs = _lint("""
            import threading

            def run(work):
                t = threading.Thread(target=work)
                t.start()
        """, rules=["GR004"])
        assert _rules_hit(fs) == {"GR004"}

    def test_true_positive_anonymous_start(self):
        fs = _lint("""
            import threading

            def run(work):
                threading.Thread(target=work).start()
        """, rules=["GR004"])
        assert _rules_hit(fs) == {"GR004"}
        assert "never be joined" in fs[0].message

    def test_true_positive_daemon_does_not_exempt(self):
        fs = _lint("""
            import threading

            def run(work):
                threading.Thread(target=work, daemon=True).start()
        """, rules=["GR004"])
        assert _rules_hit(fs) == {"GR004"}
        assert "daemon=True needs a written justification" in fs[0].message

    def test_true_positive_self_stored_in_non_joining_class(self):
        fs = _lint("""
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    pass
        """, rules=["GR004"])
        assert _rules_hit(fs) == {"GR004"}

    def test_negative_local_joined_in_function(self):
        fs = _lint("""
            import threading

            def run(work):
                t = threading.Thread(target=work)
                t.start()
                t.join(timeout=5.0)
        """, rules=["GR004"])
        assert fs == []

    def test_negative_self_stored_with_joining_stop(self):
        fs = _lint("""
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def stop(self):
                    self._t.join(timeout=5.0)

                def _run(self):
                    pass
        """, rules=["GR004"])
        assert fs == []


# ---------------------------------------------------------------------------
# GR005 — non-atomic durable write
# ---------------------------------------------------------------------------


class TestGR005AtomicDurableWrite:
    def test_true_positive_open_w(self):
        fs = _lint("""
            import json

            def save(path, obj):
                with open(path, "w") as f:
                    json.dump(obj, f)
        """, rules=["GR005"])
        assert _rules_hit(fs) == {"GR005"}
        assert "os.replace" in fs[0].message

    def test_true_positive_mode_kwarg(self):
        fs = _lint("""
            def save(path, text):
                with open(path, mode="w") as f:
                    f.write(text)
        """, rules=["GR005"])
        assert _rules_hit(fs) == {"GR005"}

    def test_true_positive_np_save_direct_path(self):
        fs = _lint("""
            import numpy as np

            def save(path, arr):
                np.save(path + ".npy", arr)
        """, rules=["GR005"])
        assert _rules_hit(fs) == {"GR005"}

    def test_negative_tmp_plus_replace(self):
        fs = _lint("""
            import json
            import os

            def save(path, obj):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(obj, f)
                os.replace(tmp, path)
        """, rules=["GR005"])
        assert fs == []

    def test_negative_read_mode(self):
        fs = _lint("""
            import json

            def load(path):
                with open(path, "r") as f:
                    return json.load(f)
        """, rules=["GR005"])
        assert fs == []

    def test_negative_np_savez_into_handle(self):
        # np.savez(f) into an open()-produced handle is the open's
        # business — only direct-path saves are the durable write
        fs = _lint("""
            import numpy as np

            def save(f, arr):
                np.savez(f, W=arr)
        """, rules=["GR005"])
        assert fs == []


# ---------------------------------------------------------------------------
# justified-suppression mechanics
# ---------------------------------------------------------------------------

_GR005_BAIT = """
    def save(path, text):  {marker}
        with open(path, "w") as f:  {inline}
            f.write(text)
"""


class TestJustified:
    def test_same_line_with_reason_suppresses(self):
        fs = _lint("""
            def save(path, text):
                with open(path, "w") as f:  # graftlife: justified(GR005): caller-owned scratch file
                    f.write(text)
        """, rules=["GR005"])
        assert fs == []

    def test_reason_is_mandatory(self):
        fs = _lint("""
            def save(path, text):
                with open(path, "w") as f:  # graftlife: justified(GR005):
                    f.write(text)
        """, rules=["GR005"])
        assert _rules_hit(fs) == {"GR005"}

    def test_wrong_rule_id_does_not_suppress(self):
        fs = _lint("""
            def save(path, text):
                with open(path, "w") as f:  # graftlife: justified(GR001): wrong rule
                    f.write(text)
        """, rules=["GR005"])
        assert _rules_hit(fs) == {"GR005"}

    def test_comment_block_above_suppresses(self):
        # real reasons run to multiple comment lines — the marker may sit
        # anywhere in the contiguous block directly above the finding
        fs = _lint("""
            def save(path, text):
                # caller-owned export path, not repo durable state —
                # graftlife: justified(GR005): a torn export is visibly
                # truncated and simply re-exported
                with open(path, "w") as f:
                    f.write(text)
        """, rules=["GR005"])
        assert fs == []

    def test_detached_comment_does_not_suppress(self):
        fs = _lint("""
            def save(path, text):
                # graftlife: justified(GR005): too far away

                with open(path, "w") as f:
                    f.write(text)
        """, rules=["GR005"])
        assert _rules_hit(fs) == {"GR005"}


# ---------------------------------------------------------------------------
# shrink-only baseline over the new tier
# ---------------------------------------------------------------------------


class TestBaselineShrinkOnly:
    def test_fresh_write_then_growth_refused(self):
        f1 = Finding("a.py", 3, "GR001", "error", "leak one")
        f2 = Finding("b.py", 9, "GR004", "error", "unstoppable")
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "baseline.json")
            # fresh write: everything grandfathered, nothing refused
            assert write_baseline(path, [f1]) == {}
            # atomic write (the GR005 fix in core.py): no tmp left behind
            assert glob.glob(os.path.join(d, "*.tmp")) == []
            # regenerating with MORE findings refuses the growth
            refused = write_baseline(path, [f1, f2])
            assert refused == {f2.key: 1}
            # the explicit escape hatch admits the new rule's findings
            assert write_baseline(path, [f1, f2], allow_growth=True) == {}


# ---------------------------------------------------------------------------
# the repo itself is clean under the new tier
# ---------------------------------------------------------------------------


class TestRepoWideClean:
    def test_zero_unbaselined_gr_findings(self):
        # acceptance bar: the first repo-wide run's real findings are
        # FIXED (not baselined) and the justified sites carry reasons,
        # so the GR tier contributes zero findings and zero baseline debt
        findings = lint_paths(["deeplearning4j_tpu", "tools", "examples"],
                              REPO, rules=list(GR_RULES))
        assert findings == [], [f"{f.path}:{f.line} {f.rule} {f.message}"
                                for f in findings]


# ---------------------------------------------------------------------------
# the static ownership inventory (span units)
# ---------------------------------------------------------------------------


class TestOwnershipInventory:
    def test_spans_and_callsite_attribution(self):
        with tempfile.TemporaryDirectory() as d:
            os.makedirs(os.path.join(d, "pkg"))
            src = textwrap.dedent("""
                class Pool:
                    def grab(self):
                        p = self.cache.alloc_page()
                        self.cache.release(p)
                        return True

                def unrelated():
                    return 1
            """)
            with open(os.path.join(d, "pkg", "mod.py"), "w") as f:
                f.write(src)
            inv = static_ownership_inventory(d, roots=("pkg",))
            assert [s["qualname"] for s in inv.spans] == ["grab"]
            assert inv.op_count() == 2
            span = inv.spans[0]
            assert span["path"] == os.path.join("pkg", "mod.py")
            # a callsite inside grab() attributes; one in unrelated()
            # (or outside any span) does not
            assert inv.attributes_callsite(span["path"], span["start"] + 1)
            assert not inv.attributes_callsite(span["path"], span["end"] + 3)
            assert not inv.attributes_callsite("pkg/other.py",
                                               span["start"] + 1)
            assert inv.as_dict()["ops"] == 2

    def test_lock_free_helpers_excluded(self):
        # release() without an argument is a lock idiom, not the page
        # vocabulary — it must not mint an inventory span
        with tempfile.TemporaryDirectory() as d:
            os.makedirs(os.path.join(d, "pkg"))
            with open(os.path.join(d, "pkg", "mod.py"), "w") as f:
                f.write("def f(lock):\n    lock.release()\n")
            inv = static_ownership_inventory(d, roots=("pkg",))
            assert inv.spans == []

    def test_repo_inventory_covers_the_allocator(self):
        inv = static_ownership_inventory(REPO)
        assert inv.op_count() > 0
        paths = {s["path"] for s in inv.spans}
        assert any(p.endswith(os.path.join("serving", "cache.py"))
                   for p in paths), sorted(paths)
        assert any(p.endswith(os.path.join("serving", "engine.py"))
                   for p in paths), sorted(paths)


# ---------------------------------------------------------------------------
# regression: the engine-step admission unwind (the GR001 conviction)
# ---------------------------------------------------------------------------


class TestAdmissionUnwindRegression:
    def test_step_crash_mid_admission_releases_and_requeues(self):
        from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
        from deeplearning4j_tpu.serving import GenerativeEngine
        from deeplearning4j_tpu.serving.scheduler import GenerationRequest

        cfg = GptConfig.tiny(vocab_size=64)
        eng = GenerativeEngine(GptModel(cfg, seed=0), max_slots=2,
                               page_size=4, max_pages_per_seq=4,
                               max_prompt=12, seed=0)
        prompt = np.arange(1, 6, dtype=np.int32)
        fut = eng.submit_request(GenerationRequest(
            prompt=prompt, max_new_tokens=3, eos_token=-1))

        orig = eng._admit_pages
        state = {"armed": True}

        def bomb(slot, req, match):
            # run the REAL admission (pages get mapped to the slot), then
            # die — the exact window the step() unwind must cover
            out = orig(slot, req, match)
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected admission crash")
            return out

        eng._admit_pages = bomb
        with pytest.raises(RuntimeError, match="injected admission crash"):
            eng.step()
        # the unwind: every page the admission mapped is back in the
        # pool, the allocator invariants hold, and the request is
        # re-queued (not stranded) with its future still open
        assert eng.cache.free_pages == eng.cache.num_pages
        eng.cache.check_invariants(
            eng.prefix.page_refs() if eng.prefix is not None else None)
        assert not fut.done()
        assert eng.scheduler.has_work()
        # the retry path completes the request normally
        while eng.scheduler.has_work():
            eng.step()
        res = fut.result(timeout=10)
        assert res.finish_reason == "length"
        assert len(res.tokens) == 3


# ---------------------------------------------------------------------------
# regression: the hub's torn manifest (the GR005 conviction)
# ---------------------------------------------------------------------------


class TestHubAtomicManifest:
    def _net(self):
        from deeplearning4j_tpu import nn
        conf = (nn.builder().seed(3).updater(nn.Sgd(learning_rate=0.1))
                .list()
                .layer(nn.DenseLayer(n_out=4, activation="tanh"))
                .layer(nn.OutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(nn.InputType.feed_forward(3)).build())
        return nn.MultiLayerNetwork(conf).init()

    def test_publish_leaves_no_tmp(self, tmp_path):
        from deeplearning4j_tpu.models.hub import ModelHub
        hub = ModelHub(root=str(tmp_path))
        hub.publish("m", self._net(), metadata={"v": 1})
        assert glob.glob(str(tmp_path / "m" / "*.tmp")) == []

    def test_crash_mid_manifest_write_keeps_old_entry(self, tmp_path,
                                                      monkeypatch):
        # load() checksum-verifies against the manifest, so the old code
        # (open(manifest, "w") in place) truncated the entry the moment a
        # re-publish crashed mid-dump — the whole model bricked. The
        # atomic tmp + os.replace publish must keep v1 loadable.
        import json as json_mod
        from deeplearning4j_tpu.models import hub as hub_mod
        hub = hub_mod.ModelHub(root=str(tmp_path))
        hub.publish("m", self._net(), metadata={"v": 1})

        real_dump = json_mod.dump

        def torn_dump(obj, fh, **kw):
            fh.write('{"torn":')  # a few bytes land, then the crash
            raise IOError("disk full")

        monkeypatch.setattr(hub_mod.json, "dump", torn_dump)
        with pytest.raises(IOError, match="disk full"):
            hub.publish("m", self._net(), metadata={"v": 2})
        monkeypatch.setattr(hub_mod.json, "dump", real_dump)
        # the published entry is untouched: manifest intact, model loads
        assert hub.manifest("m")["metadata"] == {"v": 1}
        hub.load("m")


# ---------------------------------------------------------------------------
# regression: joinable workers (the GR004 convictions)
# ---------------------------------------------------------------------------


class TestWorkerThreadsJoin:
    def test_ui_server_stop_joins_its_thread(self):
        from deeplearning4j_tpu.ui.server import UIServer
        srv = UIServer(port=0).start()
        t = srv._thread
        assert t is not None and t.is_alive()
        srv.stop()
        assert not t.is_alive()
        assert srv._thread is None

    def test_async_iterator_worker_exits_with_the_epoch(self):
        from deeplearning4j_tpu.datasets.dataset import AsyncDataSetIterator

        class _ListIter:
            batch_size = 2

            def __init__(self, items):
                self._items = items

            def __iter__(self):
                return iter(self._items)

            def reset(self):
                pass

        before = {id(t) for t in threading.enumerate()}
        it = AsyncDataSetIterator(_ListIter(list(range(7))), prefetch=2)
        assert list(it) == list(range(7))
        leaked = [t for t in threading.enumerate()
                  if id(t) not in before and t.is_alive()]
        assert leaked == []


# ---------------------------------------------------------------------------
# regression: the async checkpoint writer's orphaned tmps (satellite)
# ---------------------------------------------------------------------------


class TestCheckpointOrphanTmps:
    def test_restart_sweeps_preexisting_orphans(self):
        from deeplearning4j_tpu.parallel.checkpoint import \
            TrainingCheckpointer
        with tempfile.TemporaryDirectory() as d:
            orphan = os.path.join(d, "step_7.npz.tmp")
            with open(orphan, "w") as f:
                f.write("half a checkpoint")
            ck = TrainingCheckpointer(d, use_orbax=False)
            try:
                assert not os.path.exists(orphan)
            finally:
                ck.close()

    def test_writer_death_mid_write_is_swept_and_surfaced(self):
        from deeplearning4j_tpu import faults
        from deeplearning4j_tpu.parallel.checkpoint import \
            TrainingCheckpointer
        with tempfile.TemporaryDirectory() as d:
            ck = TrainingCheckpointer(d, keep_last=None, use_orbax=False,
                                      max_queue=2, overflow="block")
            # the 2nd async write dies between fsync and the publishing
            # rename — exactly the orphaned-tmp window
            faults.arm("worker_death", prob=1.0, after_n=1, max_fires=1)
            try:
                for step in range(3):
                    ck.save_async(step, _fake_net(float(step)))
                assert ck.wait_until_finished(timeout=60)
            finally:
                faults.reset()
            # the failure surfaces, the orphan does not survive the drain
            assert len(ck.drain_failures()) == 1
            assert glob.glob(os.path.join(d, "step_*.npz.tmp")) == []
            # durability restored by a compensating sync save
            ck.save(3, _fake_net(3.0))
            assert ck.restore(_fake_net(-1.0)) == 3
            ck.close()


# ---------------------------------------------------------------------------
# live lifetrace-vs-inventory consistency (the cross-validation, small)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestLifetraceConsistency:
    def test_live_workload_matches_static_inventory(self):
        """Run a real 2-engine cluster workload under the tracer and hold
        it to the full contract: rc-clean pages, exactly-once terminals,
        no leaked threads, and every observed acquire/release callsite
        inside the static ownership inventory."""
        from deeplearning4j_tpu.models.gpt import GptConfig, GptModel
        from deeplearning4j_tpu.serving import ClusterRouter, \
            GenerativeEngine
        from deeplearning4j_tpu.testing.lifetrace import ResourceTracer

        cfg = GptConfig.tiny()
        model = GptModel(cfg, seed=0)
        engines = [GenerativeEngine(model, max_slots=2, page_size=8,
                                    max_pages_per_seq=6, max_prompt=16,
                                    seed=3, restart_backoff_s=0.0)
                   for _ in range(2)]
        tracer = ResourceTracer()
        for i, e in enumerate(engines):
            tracer.attach_engine(e, name=f"engine{i}")
        router = ClusterRouter(engines)
        router.start()
        try:
            r = np.random.RandomState(0)
            futs = [router.submit(
                r.randint(1, cfg.vocab_size, size=5).astype(np.int32),
                max_new_tokens=4, eos_token=-1) for _ in range(4)]
            for f in futs:
                f.result(timeout=300)
        finally:
            router.stop()
        report = tracer.check(repo_root=REPO)
        assert report["ok"], report
        assert report["terminals"]["tracked"] >= 4
        assert report["callsites"]["observed"] > 0
        assert report["callsites"]["validated"]
        assert report["callsites"]["unknown"] == []
