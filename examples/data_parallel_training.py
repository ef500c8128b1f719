"""Single-node data parallelism (ref: dl4j-examples ParallelWrapper usage,
SURVEY §3.4): the reference spawns a thread + replica per device and
averages parameters; here sharded jit runs ONE lockstep step with the
gradient psum compiled in.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import jax
import numpy as np

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import ParallelWrapper
from deeplearning4j_tpu.train import Adam

print("devices:", jax.device_count())

conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2)).list()
        .layer(DenseLayer(nOut=64, activation="RELU"))
        .layer(OutputLayer(nOut=5, lossFunction="MCXENT"))
        .setInputType(InputType.feedForward(20)).build())
net = MultiLayerNetwork(conf).init()

rng = np.random.RandomState(0)
X = rng.rand(1024, 20).astype(np.float32)
Y = np.eye(5, dtype=np.float32)[rng.randint(0, 5, 1024)]

pw = ParallelWrapper(net, workers=jax.device_count())
pw.fit(DataSet(X, Y), epochs=5)
print("score after DP fit:", round(net.score(), 4))
assert np.isfinite(net.score())
