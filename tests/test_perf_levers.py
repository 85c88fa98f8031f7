"""Round-5 perf lever: s2d stem exactness.

The lever must be a *mathematically exact* rewrite — every test here checks
the optimized path against the canonical one, not against golden numbers.
"""

import numpy as np

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import nn

from tests._helpers import _mln, _rng


class TestS2DStem:
    """ConvolutionLayer(s2d_stem=True): 7×7/2 'same' conv lowered over a 2×2
    space-to-depth input (MLPerf ResNet stem trick) must match the plain
    lowering bit-for-bit up to fp reassociation."""

    def _nets(self, h=32, w=32):
        def mk(s2d):
            return _mln([
                nn.ConvolutionLayer(n_out=16, kernel=(7, 7), stride=(2, 2),
                                    convolution_mode="same", has_bias=False,
                                    activation="identity", s2d_stem=s2d),
                nn.GlobalPoolingLayer(pooling_type="avg"),
                nn.OutputLayer(n_out=5, activation="softmax", loss="mcxent"),
            ], nn.InputType.convolutional(h, w, 3))
        a, b = mk(False), mk(True)
        b.params = jax.tree.map(jnp.array, a.params)  # copy (donation-safe)
        return a, b

    def test_forward_matches_plain_conv(self):
        a, b = self._nets()
        x = _rng(0).randn(4, 32, 32, 3).astype(np.float32)
        np.testing.assert_allclose(a.output(x), b.output(x), atol=1e-5)

    def test_train_step_matches_plain_conv(self):
        a, b = self._nets()
        r = _rng(1)
        x = r.randn(4, 32, 32, 3).astype(np.float32)
        y = np.eye(5)[r.randint(0, 5, 4)].astype(np.float32)
        a.fit(x, y)
        b.fit(x, y)
        diffs = jax.tree.map(
            lambda p, q: float(jnp.max(jnp.abs(p - q))), a.params, b.params)
        assert jax.tree.reduce(max, diffs) < 1e-5

    def test_odd_input_falls_back(self):
        # odd spatial dims can't space-to-depth; the layer must fall back to
        # the plain conv path rather than mis-shape
        a, b = self._nets(h=31, w=31)
        x = _rng(2).randn(2, 31, 31, 3).astype(np.float32)
        np.testing.assert_allclose(a.output(x), b.output(x), atol=1e-5)

    def test_json_roundtrip(self):
        lc = nn.ConvolutionLayer(n_out=8, kernel=(7, 7), stride=(2, 2),
                                 convolution_mode="same", s2d_stem=True)
        from deeplearning4j_tpu.nn import conf as C
        d = lc.to_dict()
        back = C.LayerConf.from_dict(d)
        assert back.s2d_stem is True
