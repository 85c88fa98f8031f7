"""Hyperparameter search with the arbiter module (arbiter-core role):
random search over learning rate + width for a small classifier, grid
refinement around the winner."""

import os

import numpy as np

from deeplearning4j_tpu import nn
from deeplearning4j_tpu.arbiter import (
    ContinuousParameterSpace, DiscreteParameterSpace,
    GridSearchCandidateGenerator, IntegerParameterSpace,
    LocalOptimizationRunner, RandomSearchGenerator)
from deeplearning4j_tpu.arbiter import test_set_loss_score as loss_score
from deeplearning4j_tpu.datasets.dataset import DataSet


def make_data(seed, n=256):
    r = np.random.RandomState(seed)
    x = r.randn(n, 8).astype(np.float32)
    w = r.randn(8, 3).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[(x @ w).argmax(axis=1)]
    return [DataSet(x, y)]


def build(params):
    return nn.MultiLayerNetwork(
        nn.builder().seed(7)
        .updater(nn.Adam(learning_rate=params["lr"])).list()
        .layer(nn.DenseLayer(n_out=params["width"], activation="relu"))
        .layer(nn.OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(nn.InputType.feed_forward(8)).build()).init()


def main():
    budget = int(os.environ.get("EXAMPLE_MAX_BATCHES", "6"))
    train, heldout = make_data(0), make_data(1)

    # stage 1: random exploration
    explore = LocalOptimizationRunner(
        build,
        RandomSearchGenerator({"lr": ContinuousParameterSpace(1e-4, 0.3,
                                                              log=True),
                               "width": IntegerParameterSpace(4, 64)},
                              seed=0),
        train_data=train, score_data=heldout, score_fn=loss_score,
        epochs=10, max_candidates=budget)
    best = explore.execute()
    print(f"random search best: lr={best.parameters['lr']:.4g} "
          f"width={best.parameters['width']} loss={best.score:.4f}")

    # stage 2: grid around the winner's learning rate
    lo, hi = best.parameters["lr"] / 3, best.parameters["lr"] * 3
    refine = LocalOptimizationRunner(
        build,
        GridSearchCandidateGenerator(
            {"lr": ContinuousParameterSpace(lo, hi, log=True),
             "width": best.parameters["width"]}, discretization=3),
        train_data=train, score_data=heldout, score_fn=loss_score,
        epochs=10, max_candidates=3)
    refined = refine.execute()
    print(f"grid refinement best: lr={refined.parameters['lr']:.4g} "
          f"loss={refined.score:.4f}")
    print(f"search ok: {len(explore.results) + len(refine.results)} trials")


if __name__ == "__main__":
    main()
