"""``correct`` has to come out false when it should: the control (the
reference in the precision below, in the program's place) and each fault a
cell can have, planted under the timed path. Tiny sizes, on the CPU; the
harness's look for a chip is skipped and the rest of a run is driven."""

import io
import json

import jax
import jax.numpy as jnp
import pytest

import common
import run as bench_run
import tiny


def _line(cell, seed=7, seconds=0.5):
    out, err = io.StringIO(), io.StringIO()
    rc = bench_run.run_cell(cell, jax.devices(), seed=seed, seconds=seconds,
                            trace=False, out=out, err=err)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert "compared" in err.getvalue()
    return line


# ------------------------------------------------------------------ training


def test_train_sound_run_is_correct():
    line = _line(tiny.train_cell())
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0


def _stand_ins(cell, ctx, seed=7):
    """Each stand-in through the family's ``verify`` with the cell's
    limits, as calibrate.py does on the chip: tag -> Checks."""
    family = common.module("families", cell["cfg"]["family"])
    out = {}
    for tag, readings, kw in family.stand_ins(cell["cfg"], cell["mix"], seed,
                                              ctx):
        out[tag] = common.Checks()
        family.verify(cell["cfg"], cell["mix"], seed, readings, out[tag],
                      **kw)
    return out


def test_train_controls_and_planted_faults_are_not_correct():
    """The reference with its matmuls in 8 bits (operands rounded going
    forward, gradients going backward), and the faults planted in the
    reference, each in the program's place."""
    got = _stand_ins(tiny.train_cell(), None)
    assert set(got) == {"control_fp8", "control_int8", "fault_half_batch",
                        "fault_state_unchanged"}
    for tag, checks in got.items():
        assert checks.correct is False, (tag, checks.compared())
    for tag in ("control_fp8", "control_int8"):
        row = got[tag].compared()["grad_diff"]
        assert row["value"] > row["limit"], (tag, row)
    assert got["fault_state_unchanged"].compared()[
        "change_norm_gap"]["value"] == pytest.approx(1.0)


def test_reference_follows_the_programs_dropout_masks():
    """In float32 the program (flash kernel's masks, the feed-forward's
    bernoulli masks, three steps of the key chain) and the reference agree
    to rounding: the reference draws the same masks from the seed."""
    cell = tiny.train_cell()
    assert cell["cfg"]["attention_probs_dropout_prob"] == 0.1
    cell["cfg"]["param_dtype"] = "float32"
    line = _line(cell, seed=2**31 + 5)
    assert line["correct"] is True
    for name in ("loss_gap", "grad_diff", "change_norm_gap"):
        assert line["compared"][name]["value"] < 1e-4, line["compared"]


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.models.bert import BertModel

    real = BertModel.fit_mlm_scanned

    def unchanged(self, batch, steps):
        keep = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        losses = real(self, batch, steps)
        self.params, self.opt_state = keep
        return losses

    monkeypatch.setattr(BertModel, "fit_mlm_scanned", unchanged)
    line = _line(tiny.train_cell())
    assert line["correct"] is False
    assert line["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_the_batch_left_out_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.models.bert import BertModel

    real = BertModel.fit_mlm_scanned

    def half(self, batch, steps):
        n = batch["ids"].shape[0] // 2
        return real(self, {k: v[:n] for k, v in batch.items()}, steps)

    monkeypatch.setattr(BertModel, "fit_mlm_scanned", half)
    line = _line(tiny.train_cell())
    assert line["correct"] is False, line["compared"]


# ------------------------------------------------------------------- serving


def test_serve_sound_run_is_correct():
    line = _line(tiny.serve_cell(), seconds=2.0)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == set()  # the tiny cell declares none


@pytest.mark.parametrize("control", ["bfloat16", "int8"])
def test_serve_control_is_not_correct(control):
    """On a fixed sample (which requests a window finishes hangs on the
    host's threads): a control is read at each position of the prompts and
    tokens it is given, whatever they are."""
    import numpy as np

    cell = tiny.serve_cell()
    family = common.module("families", "gpt")
    rng = np.random.default_rng(3)
    sample = [{"prompt": rng.integers(1, 256, 40, dtype=np.int32),
               "tokens": rng.integers(1, 256, 24, dtype=np.int32)}
              for _ in range(32)]
    tags = [tag for tag, _, kw in family.stand_ins(
        cell["cfg"], cell["mix"], 7, {"sample": sample})
        if kw == {"control": control}]
    assert tags == ["control_" + control]
    checks = common.Checks()
    family.verify(cell["cfg"], cell["mix"], 7, sample, checks,
                  control=control)
    assert checks.correct is False, checks.compared()


def test_serve_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.serving import engine

    real = engine.sample_tokens

    def altered(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", altered)
    line = _line(tiny.serve_cell(), seconds=2.0)
    assert line["correct"] is False, line["compared"]
