"""SameDiff: declarative graph + whole-graph-compiled training (ref:
nd4j samediff examples / SURVEY §3.2 — the op-by-op JVM interpreter is
replaced by ONE XLA executable for forward+backward+updater).
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.train import Adam

rng = np.random.RandomState(0)

sd = SameDiff.create()
x = sd.placeHolder("x", shape=(None, 4))
y = sd.placeHolder("y", shape=(None, 3))
w1 = sd.var("w1", rng.normal(0, 0.3, (4, 16)).astype(np.float32))
b1 = sd.var("b1", np.zeros(16, np.float32))
w2 = sd.var("w2", rng.normal(0, 0.3, (16, 3)).astype(np.float32))
b2 = sd.var("b2", np.zeros(3, np.float32))

h = sd.math.tanh(x.mmul(w1) + b1)
logits = h.mmul(w2) + b2
probs = sd.nn.softmax(logits).rename("probs")
loss = sd.loss.mcxent(y, probs).rename("loss")
sd.setLossVariables("loss")

sd.setTrainingConfig(TrainingConfig(
    updater=Adam(0.05),
    dataSetFeatureMapping=["x"], dataSetLabelMapping=["y"]))

X = rng.rand(256, 4).astype(np.float32)
labels = (X @ np.array([[1, -1, 0.5, 0.2]]).T > 0.8).astype(int)[:, 0] \
    + (X[:, 0] > 0.7).astype(int)
Y = np.eye(3, dtype=np.float32)[np.clip(labels, 0, 2)]

hist = sd.fit(DataSet(X, Y), epochs=60)
print("loss:", round(hist[0], 4), "->", round(hist[-1], 4))
assert hist[-1] < hist[0]

out = sd.output({"x": X[:8]}, "probs")["probs"].toNumpy()
print("probs row sums:", np.asarray(out).sum(1).round(3))
