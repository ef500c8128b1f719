"""Early stopping (ref: dl4j-examples EarlyStoppingMNIST): stop when the
validation score stops improving, keep the best model.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.earlystopping import (
    DataSetLossCalculator, EarlyStoppingConfiguration, EarlyStoppingTrainer,
    InMemoryModelSaver, MaxEpochsTerminationCondition,
    ScoreImprovementEpochTerminationCondition)
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.train import Adam

rng = np.random.RandomState(0)
X = rng.rand(512, 10).astype(np.float32)
Y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 512)]
Xv = rng.rand(128, 10).astype(np.float32)
Yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 128)]

conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2)).list()
        .layer(DenseLayer(nOut=32, activation="RELU"))
        .layer(OutputLayer(nOut=4, lossFunction="MCXENT"))
        .setInputType(InputType.feedForward(10)).build())

esc = EarlyStoppingConfiguration(
    epochTerminationConditions=[
        MaxEpochsTerminationCondition(40),
        ScoreImprovementEpochTerminationCondition(maxEpochsWithNoImprovement=5)],
    scoreCalculator=DataSetLossCalculator(
        ListDataSetIterator(DataSet(Xv, Yv).batchBy(128))),
    modelSaver=InMemoryModelSaver(),
    evaluateEveryNEpochs=1)

trainer = EarlyStoppingTrainer(
    esc, MultiLayerNetwork(conf).init(),
    ListDataSetIterator(DataSet(X, Y).batchBy(64)))
result = trainer.fit()
print("termination:", result.terminationReason, "| details:", result.terminationDetails)
print(f"best epoch {result.bestModelEpoch} score {result.bestModelScore:.4f} "
      f"(of {result.totalEpochs} epochs)")
assert result.bestModel is not None
