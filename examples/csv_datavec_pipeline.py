"""CSV ETL pipeline (ref: dl4j-examples BasicDataVecExample + IrisClassifier):
CSV file -> Schema -> TransformProcess (categorical to integer, normalize-ish
math op) -> RecordReaderDataSetIterator -> train -> evaluate.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.datavec import (
    CSVRecordReader, CollectionRecordReader, FileSplit, MathOp,
    RecordReaderDataSetIterator, Schema, TransformProcess)
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.train import Adam

# ---- make a little CSV (sepal-ish data, 3 classes)
rng = np.random.RandomState(0)
path = "/tmp/flowers.csv"
kinds = ["setosa", "versicolor", "virginica"]
with open(path, "w") as f:
    for i in range(300):
        k = i % 3
        a, b = rng.normal(3 + k, 0.3), rng.normal(1 + 0.7 * k, 0.3)
        f.write(f"{a:.3f},{b:.3f},{kinds[k]}\n")

# ---- schema + transform: categorical label -> integer, scale features
schema = (Schema.Builder()
          .addColumnsDouble("sepal_len", "petal_len")
          .addColumnCategorical("species", *kinds)
          .build())
tp = (TransformProcess.Builder(schema)
      .categoricalToInteger("species")
      .doubleMathOp("sepal_len", MathOp.Multiply, 0.25)
      .build())

reader = CSVRecordReader().initialize(FileSplit(path))
rows = [r for r in reader]
transformed = tp.execute(rows)
print("final schema:", tp.getFinalSchema().getColumnNames())

it = RecordReaderDataSetIterator(
    CollectionRecordReader(transformed), batchSize=32, labelIndex=2, numClasses=3)

conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(5e-2)).list()
        .layer(DenseLayer(nOut=16, activation="TANH"))
        .layer(OutputLayer(nOut=3, lossFunction="MCXENT"))
        .setInputType(InputType.feedForward(2)).build())
net = MultiLayerNetwork(conf).init()
net.fit(it, epochs=30)

it.reset()
ev = net.evaluate(it)
print(ev.stats())
assert ev.accuracy() > 0.9
