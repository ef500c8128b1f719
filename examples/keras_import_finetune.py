"""Keras h5 import + fine-tune (ref: dl4j-examples Keras import examples).
Requires tensorflow (present in this environment); the import path converts
NHWC/HWIO layouts to NCHW/OIHW and verifies numerically against Keras.
"""
import sys

import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

try:
    import tensorflow as tf
except ImportError:
    print("tensorflow not installed — skipping")
    sys.exit(0)

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.modelimport.keras import KerasModelImport

tf.keras.utils.set_random_seed(2)
m = tf.keras.Sequential([
    tf.keras.layers.Input((8, 8, 1)),
    tf.keras.layers.Conv2D(8, 3, activation="relu", padding="same"),
    tf.keras.layers.MaxPooling2D(2),
    tf.keras.layers.Flatten(),
    tf.keras.layers.Dense(16, activation="relu"),
    tf.keras.layers.Dense(3, activation="softmax"),
])
m.save("/tmp/keras_cnn.h5")

net = KerasModelImport.importKerasSequentialModelAndWeights("/tmp/keras_cnn.h5")

x = np.random.RandomState(0).rand(16, 8, 8, 1).astype(np.float32)
ref = np.asarray(m(x))
got = np.asarray(net.output(np.transpose(x, (0, 3, 1, 2))))
print("import parity max|diff|:", np.abs(got - ref).max())
assert np.abs(got - ref).max() < 1e-4

# fine-tune the imported model here
y = np.eye(3, dtype=np.float32)[np.random.RandomState(1).randint(0, 3, 16)]
net.fit(DataSet(np.transpose(x, (0, 3, 1, 2)), y), epochs=10)
print("fine-tuned score:", round(net.score(), 4))
