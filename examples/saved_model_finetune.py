"""Import a TF SavedModel with its trained weights and fine-tune it
(TFGraphMapper checkpoint-restore role): the imported variables are
trainable SDVariables, so a TrainingConfig fit starts from the pretrained
point rather than random init."""

import os
import tempfile

import numpy as np

import tensorflow as tf

from deeplearning4j_tpu import nn
from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.imports.tf_import import import_saved_model


def main():
    rng = np.random.RandomState(0)

    # --- "pretrained" TF model (stands in for a downloaded SavedModel) ---
    class M(tf.Module):
        def __init__(self):
            super().__init__()
            self.w = tf.Variable(rng.randn(8, 3).astype(np.float32) * 0.5,
                                 name="w")
            self.b = tf.Variable(np.zeros(3, np.float32), name="b")

        @tf.function(input_signature=[tf.TensorSpec([None, 8], tf.float32)])
        def __call__(self, x):
            return tf.nn.softmax(x @ self.w + self.b)

    m = M()
    path = os.path.join(tempfile.mkdtemp(), "saved_model")
    tf.saved_model.save(m, path)

    # --- import: weights land as VARIABLE-role SDVariables ---
    sd = import_saved_model(path)
    x = rng.randn(5, 8).astype(np.float32)
    got = sd.output({sd.graph_inputs[0]: x},
                    sd.graph_outputs[0])[sd.graph_outputs[0]]
    np.testing.assert_allclose(got, m(tf.constant(x)).numpy(),
                               rtol=1e-4, atol=1e-5)
    print("imported outputs match TF: True")

    # --- fine-tune on new labels ---
    steps = int(os.environ.get("EXAMPLE_MAX_BATCHES", "20"))
    xs = rng.randn(256, 8).astype(np.float32)
    ys = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 256)]
    labels = sd.placeholder("labels", shape=(None, 3))
    out_var = sd._vars[sd.graph_outputs[0]]
    sd.loss.mean_squared_error(out_var, labels).rename("ft_loss")
    sd.set_training_config(TrainingConfig(
        updater=nn.Adam(learning_rate=0.05),
        data_set_feature_mapping=[sd.graph_inputs[0]],
        data_set_label_mapping=["labels"],
        loss_variables=["ft_loss"]))
    w_name = next(n for n, v in sd._vars.items() if v.vtype == "VARIABLE"
                  and np.asarray(sd.get_arr(n)).shape == (8, 3))
    before = np.asarray(sd.get_arr(w_name)).copy()
    hist = sd.fit(ListDataSetIterator(DataSet(xs, ys), batch_size=64),
                  epochs=max(steps // 4, 1))
    after = np.asarray(sd.get_arr(w_name))
    print(f"fine-tune loss: {hist[0]:.4f} -> {hist[-1]:.4f}")
    print("weights moved from the pretrained point:",
          bool(not np.allclose(before, after)))


if __name__ == "__main__":
    main()
