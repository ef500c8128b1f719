"""Save / restore / continue training (ref: dl4j-examples
SaveLoadMultiLayerNetwork): ModelSerializer round-trips configuration,
parameters, AND updater state, so resumed training is exactly the run that
never stopped.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.train import Adam
from deeplearning4j_tpu.util import ModelSerializer

rng = np.random.RandomState(0)
X = rng.rand(256, 6).astype(np.float32)
Y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 256)]
ds = DataSet(X, Y)

conf = (NeuralNetConfiguration.Builder().seed(21).updater(Adam(1e-2)).list()
        .layer(DenseLayer(nOut=16, activation="RELU"))
        .layer(OutputLayer(nOut=2, lossFunction="MCXENT"))
        .setInputType(InputType.feedForward(6)).build())

# --- reference run: 20 epochs straight through
ref = MultiLayerNetwork(conf).init()
ref.fit(ds, epochs=20)

# --- checkpointed run: 10 epochs, save, restore, 10 more
net = MultiLayerNetwork(conf).init()
net.fit(ds, epochs=10)
path = "/tmp/model_checkpoint.zip"
ModelSerializer.writeModel(net, path, saveUpdater=True)
restored = ModelSerializer.restoreMultiLayerNetwork(path)
restored.fit(ds, epochs=10)

print(f"straight-through score: {ref.score():.6f}")
print(f"resume-exact score:     {restored.score():.6f}")
np.testing.assert_allclose(ref.score(), restored.score(), rtol=1e-5)
print("resumed run matches the uninterrupted run")
