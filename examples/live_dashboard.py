"""Live training dashboard (ref: dl4j-examples UIExample):
UIServer + StatsListener — browse http://127.0.0.1:9000 while training runs:
/ (overview: score, lr, update:param ratio), /model (layer graph with
per-layer param/grad series + histograms), /system (host/device memory,
step timing). Also renders the static HTML report at the end.
"""
import os

import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.train import Adam
from deeplearning4j_tpu.ui import (
    InMemoryStatsStorage, StatsListener, UIServer, render_report)

server = UIServer.getInstance(port=int(os.environ.get("UI_PORT", "9000")))
storage = InMemoryStatsStorage()
server.attach(storage)
print("dashboard:", server.url)

conf = (NeuralNetConfiguration.Builder().seed(11).updater(Adam(5e-3)).list()
        .layer(DenseLayer(nOut=48, activation="RELU"))
        .layer(DenseLayer(nOut=24, activation="RELU"))
        .layer(OutputLayer(nOut=4, lossFunction="MCXENT"))
        .setInputType(InputType.feedForward(12)).build())
net = MultiLayerNetwork(conf).init()
listener = StatsListener(storage, frequency=1)
net.setListeners(listener)

rng = np.random.RandomState(0)
X = rng.rand(512, 12).astype(np.float32)
Y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 512)]
net.fit(DataSet(X, Y), epochs=40)

reports = storage.getUpdates(listener.sessionId, "StatsListener", "worker_0")
print(f"{len(reports)} stats reports collected; "
      f"last update:param ratios: { {k: round(v, 5) for k, v in list(reports[-1]['updateRatios'].items())[:2]} }")
path = render_report(storage, listener.sessionId, "/tmp/training_report.html")
print("static report:", path)
server.stop()
