"""Hyperparameter search (ref: arbiter BasicHyperparameterOptimizationExample):
random search over learning rate and hidden width, scored by validation loss.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.arbiter import (
    ContinuousParameterSpace, IntegerParameterSpace, MaxCandidatesCondition,
    OptimizationConfiguration, OptimizationRunner, RandomSearchGenerator)
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.train import Adam

rng = np.random.RandomState(0)
X = rng.rand(256, 8).astype(np.float32)
Y = np.eye(3, dtype=np.float32)[(X.sum(1) * 2).astype(int) % 3]
Xv = rng.rand(64, 8).astype(np.float32)
Yv = np.eye(3, dtype=np.float32)[(Xv.sum(1) * 2).astype(int) % 3]

space = {
    "lr": ContinuousParameterSpace(1e-4, 1e-1, log_uniform=True),
    "hidden": IntegerParameterSpace(8, 64),
}


def build(hp):
    conf = (NeuralNetConfiguration.Builder().seed(5)
            .updater(Adam(hp["lr"])).list()
            .layer(DenseLayer(nOut=int(hp["hidden"]), activation="RELU"))
            .layer(OutputLayer(nOut=3, lossFunction="MCXENT"))
            .setInputType(InputType.feedForward(8)).build())
    net = MultiLayerNetwork(conf).init()
    net.fit(DataSet(X, Y), epochs=15)
    return net


def score(net, hp):
    return net.score(DataSet(Xv, Yv))


runner = OptimizationRunner(OptimizationConfiguration(
    candidate_generator=RandomSearchGenerator(space, seed=9),
    model_builder=build, score_function=score,
    termination_conditions=[MaxCandidatesCondition(8)]))
best = runner.execute()
print(f"tried {len(runner.results)} candidates")
print(f"best: lr={best.candidate.hyperparameters['lr']:.2e} "
      f"hidden={best.candidate.hyperparameters['hidden']} "
      f"val loss={best.score:.4f}")
assert best.score is not None
