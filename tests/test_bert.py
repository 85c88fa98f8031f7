"""BERT path tests — BASELINE config[3] gate: wordpiece, BertIterator,
fine-tune convergence, MLM step (reference BertIterator + SameDiff-BERT
workload, SURVEY §4.3)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nlp import BertWordPieceTokenizer, BertIterator, build_vocab
from deeplearning4j_tpu.models.bert import (
    BertConfig, BertModel, bert_encoder, init_bert_params,
    classification_logits, mlm_logits,
)
from deeplearning4j_tpu import nn

import jax


CORPUS = [
    "the good movie was great and fun",
    "a terrible film bad and boring",
    "great acting wonderful story good",
    "awful plot bad acting boring waste",
    "fun and wonderful a great time",
    "boring terrible waste of time bad",
] * 8


def corpus_labels():
    return [1, 0, 1, 0, 1, 0] * 8


class TestWordPiece:
    def test_build_vocab_has_specials(self):
        v = build_vocab(CORPUS)
        for sp in ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]:
            assert sp in v

    def test_tokenize_known_words(self):
        v = build_vocab(CORPUS)
        t = BertWordPieceTokenizer(v)
        assert t.tokenize("good movie") == ["good", "movie"]

    def test_wordpiece_fallback_to_chars(self):
        v = build_vocab(CORPUS)
        t = BertWordPieceTokenizer(v)
        pieces = t.tokenize("goodmovie")  # unseen compound → greedy pieces
        assert len(pieces) >= 2
        # round trip through ids
        ids = t.encode("good movie")
        assert t.decode(ids) == "good movie"

    def test_greedy_longest_match(self):
        v = {"[PAD]": 0, "[UNK]": 1, "[CLS]": 2, "[SEP]": 3, "[MASK]": 4,
             "un": 5, "##able": 6, "##a": 7, "##b": 8, "##l": 9, "##e": 10,
             "u": 11, "##n": 12}
        t = BertWordPieceTokenizer(v)
        assert t.tokenize("unable") == ["un", "##able"]


class TestBertIterator:
    def test_classification_batches(self):
        v = build_vocab(CORPUS)
        it = BertIterator(BertWordPieceTokenizer(v), CORPUS, corpus_labels(),
                          num_classes=2, max_len=16, batch_size=8)
        b = next(iter(it))
        assert b["ids"].shape == (8, 16)
        assert b["labels"].shape == (8, 2)
        assert b["mask"].max() == 1
        # CLS at position 0 everywhere
        assert (b["ids"][:, 0] == v["[CLS]"]).all()

    def test_mlm_batches(self):
        v = build_vocab(CORPUS)
        it = BertIterator(BertWordPieceTokenizer(v), CORPUS, task="unsupervised",
                          max_len=16, batch_size=8, seed=3)
        b = next(iter(it))
        assert b["mlm_mask"].sum() > 0  # some positions masked
        sel = b["mlm_mask"] > 0
        # labels hold the ORIGINAL ids at masked positions
        assert (b["mlm_labels"][sel] > 0).all()


class TestBertModel:
    def test_encoder_shapes(self):
        cfg = BertConfig.tiny()
        params = init_bert_params(jax.random.key(0), cfg)
        ids = np.zeros((2, 10), np.int32)
        seq, pooled = bert_encoder(params, ids, np.zeros_like(ids),
                                   np.ones_like(ids), cfg)
        assert seq.shape == (2, 10, cfg.hidden)
        assert pooled.shape == (2, cfg.hidden)

    def test_mask_blocks_attention(self):
        """Padding must not change unmasked outputs (attention mask works)."""
        cfg = BertConfig.tiny(dropout=0.0)
        params = init_bert_params(jax.random.key(0), cfg)
        rng = np.random.RandomState(0)
        ids8 = rng.randint(5, 50, (1, 8)).astype(np.int32)
        mask8 = np.ones((1, 8), np.int32)
        ids12 = np.concatenate([ids8, np.zeros((1, 4), np.int32)], axis=1)
        mask12 = np.concatenate([mask8, np.zeros((1, 4), np.int32)], axis=1)
        seq8, _ = bert_encoder(params, ids8, np.zeros_like(ids8), mask8, cfg)
        seq12, _ = bert_encoder(params, ids12, np.zeros_like(ids12), mask12, cfg)
        np.testing.assert_allclose(np.asarray(seq12)[:, :8], np.asarray(seq8),
                                   rtol=1e-4, atol=1e-5)

    def test_fine_tune_converges(self):
        """config[3] gate (tiny scale): sentiment fine-tune reaches high
        train accuracy."""
        v = build_vocab(CORPUS)
        tok = BertWordPieceTokenizer(v)
        cfg = BertConfig.tiny(vocab_size=len(v), num_labels=2, dropout=0.0)
        model = BertModel(cfg, seed=1, updater=nn.Adam(learning_rate=1e-3))
        it = BertIterator(tok, CORPUS, corpus_labels(), num_classes=2,
                          max_len=16, batch_size=16, seed=1)
        hist = model.fit_classifier(it, epochs=12)
        assert hist[-1] < hist[0] * 0.3, hist
        # accuracy on the training sentences
        b = next(iter(BertIterator(tok, CORPUS, corpus_labels(), num_classes=2,
                                   max_len=16, batch_size=48, seed=9)))
        pred = model.predict(b["ids"], b["segments"], b["mask"]).argmax(-1)
        acc = (pred == b["labels"].argmax(-1)).mean()
        assert acc > 0.9, acc

    def test_mlm_step_runs(self):
        v = build_vocab(CORPUS)
        tok = BertWordPieceTokenizer(v)
        cfg = BertConfig.tiny(vocab_size=len(v), dropout=0.0)
        model = BertModel(cfg, seed=2, updater=nn.Adam(learning_rate=1e-3))
        it = BertIterator(tok, CORPUS, task="unsupervised", max_len=16,
                          batch_size=16, seed=2)
        hist = model.fit_mlm(it, epochs=3)
        assert np.isfinite(hist).all()
        assert hist[-1] < hist[0], hist

    def test_kept_hidden_mask_gives_the_inline_draws_loss_and_grads(self):
        """The feed-forward's keep mask is drawn once and stored; the step
        must see the very bits of the plain inline draw (same key chain,
        same shape, same scaling), so loss and every gradient agree."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.models.bert import _attention, _layer_norm
        from deeplearning4j_tpu.nn import dtype as DT

        cfg = BertConfig.tiny(dropout=0.1)
        params = init_bert_params(jax.random.key(3), cfg)
        rs = np.random.RandomState(3)
        ids = rs.randint(5, cfg.vocab_size, (2, 16)).astype(np.int32)
        segments = np.zeros_like(ids)
        mask = np.ones_like(ids)
        labels = rs.randint(5, cfg.vocab_size, ids.shape).astype(np.int32)
        weight = (rs.rand(*ids.shape) < 0.3).astype(np.float32)
        rng = jax.random.key(11)

        def nll(logits):
            logp = jax.nn.log_softmax(logits)
            picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
            return -jnp.sum(picked[..., 0] * weight) / np.sum(weight)

        def kept(p):
            return nll(mlm_logits(p, ids, segments, mask, cfg, train=True,
                                  rng=rng))

        def inline(p):
            eps, emb = cfg.layer_norm_eps, p["embeddings"]
            with DT.precision_scope("float32"):
                x = (emb["word"][ids] + emb["position"][jnp.arange(16)][None]
                     + emb["token_type"][segments])
                x = _layer_norm(x, emb["ln_gamma"], emb["ln_beta"], eps)
                rngs = jax.random.split(rng, cfg.layers * 2)
                for i, blk in enumerate(p["encoder"]):
                    a = _attention(blk["attn"], x, mask, cfg, train=True,
                                   rng=rngs[2 * i])
                    x = _layer_norm(x + a, blk["attn"]["ln_gamma"],
                                    blk["attn"]["ln_beta"], eps)
                    f = blk["ffn"]
                    hdn = jax.nn.gelu(x @ f["W1"] + f["b1"])
                    keep = jax.random.bernoulli(rngs[2 * i + 1],
                                                1 - cfg.dropout, hdn.shape)
                    hdn = jnp.where(keep, hdn / (1 - cfg.dropout), 0.0)
                    x = _layer_norm(x + hdn @ f["W2"] + f["b2"],
                                    f["ln_gamma"], f["ln_beta"], eps)
                m = p["mlm"]
                h = _layer_norm(jax.nn.gelu(x @ m["W"] + m["b"]),
                                m["ln_gamma"], m["ln_beta"], eps)
                return nll(h @ emb["word"].T + m["bias"])

        loss, grads = jax.jit(jax.value_and_grad(kept))(params)
        want, want_grads = jax.jit(jax.value_and_grad(inline))(params)
        assert jnp.dtype(loss.dtype) == jnp.float32
        np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6,
                                       err_msg=jax.tree_util.keystr(path))
        # and dropout does act: another key draws other masks
        assert float(nll(mlm_logits(params, ids, segments, mask, cfg,
                                    train=True, rng=jax.random.key(12)))) \
            != float(loss)

    @pytest.mark.parametrize("train,dropout", [(False, 0.1), (True, 0.0)])
    def test_no_mask_is_drawn_or_kept_without_dropout(self, train, dropout):
        """``predict`` and a dropout-free step trace no draw and no barrier."""
        cfg = BertConfig.tiny(dropout=dropout)
        params = init_bert_params(jax.random.key(0), cfg)
        ids = np.zeros((2, 8), np.int32)
        jaxpr = str(jax.make_jaxpr(
            lambda p: mlm_logits(p, ids, ids, np.ones_like(ids), cfg,
                                 train=train, rng=jax.random.key(1)))(params))
        assert "optimization_barrier" not in jaxpr
        assert "random_bits" not in jaxpr and "threefry" not in jaxpr

    def test_param_count_base_is_bertbase_sized(self):
        cfg = BertConfig.base()
        params = init_bert_params(jax.random.key(0), cfg)
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        # BERT-base ≈ 110M (+MLM head)
        assert 100_000_000 < n < 135_000_000, n
