"""LeNet on MNIST (ref: dl4j-examples LeNetMNIST).

Uses the real IDX files when cached under ~/.deeplearning4j_tpu, else a
deterministic synthetic surrogate with the same shapes (documented in
data/fetchers.py). One fused XLA step per iteration.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

from deeplearning4j_tpu.data.fetchers import MnistDataSetIterator
from deeplearning4j_tpu.eval import Evaluation
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    ConvolutionLayer, DenseLayer, OutputLayer, SubsamplingLayer)
from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener
from deeplearning4j_tpu.train import Adam

conf = (NeuralNetConfiguration.Builder()
        .seed(123)
        .updater(Adam(1e-3))
        .list()
        .layer(ConvolutionLayer(nOut=20, kernelSize=(5, 5), activation="RELU"))
        .layer(SubsamplingLayer(poolingType="MAX", kernelSize=(2, 2), stride=(2, 2)))
        .layer(ConvolutionLayer(nOut=50, kernelSize=(5, 5), activation="RELU"))
        .layer(SubsamplingLayer(poolingType="MAX", kernelSize=(2, 2), stride=(2, 2)))
        .layer(DenseLayer(nOut=128, activation="RELU"))
        .layer(OutputLayer(nOut=10, lossFunction="MCXENT"))
        .setInputType(InputType.convolutionalFlat(28, 28, 1))
        .build())

net = MultiLayerNetwork(conf).init()
net.setListeners(ScoreIterationListener(50))

train = MnistDataSetIterator(batch_size=128, train=True, num_examples=1920)
test = MnistDataSetIterator(batch_size=256, train=False, num_examples=1000)

net.fit(train, epochs=1)

ev: Evaluation = net.evaluate(test)
print(ev.stats())
assert ev.accuracy() > 0.9
