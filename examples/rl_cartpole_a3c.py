"""Vectorized A3C on CartPole (ref: rl4j A3CCartpole). The reference's async
worker threads become N lockstep envs with one batched policy eval + one
fused update per rollout (rl/nstep_q.py module docstring).
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import numpy as np

from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.rl import A3CConfiguration, A3CDiscreteDense, CartPole
from deeplearning4j_tpu.train import Adam


def pi_conf():
    return (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3)).list()
            .layer(DenseLayer(nOut=64, activation="TANH"))
            .layer(OutputLayer(nOut=2, lossFunction="MCXENT"))
            .setInputType(InputType.feedForward(4)).build())


def v_conf():
    return (NeuralNetConfiguration.Builder().seed(1).updater(Adam(3e-3)).list()
            .layer(DenseLayer(nOut=64, activation="TANH"))
            .layer(OutputLayer(nOut=1, activation="IDENTITY", lossFunction="MSE"))
            .setInputType(InputType.feedForward(4)).build())


cfg = A3CConfiguration(seed=0, gamma=0.99, nStep=16, numEnvs=8,
                       maxStep=24000, maxEpochStep=300)
learner = A3CDiscreteDense(lambda: CartPole(seed=np.random.randint(1 << 30)),
                           pi_conf(), v_conf(), cfg)
rewards = learner.train()
k = max(len(rewards) // 5, 1)
print(f"episodes={len(rewards)}  first 20%: {np.mean(rewards[:k]):.1f}  "
      f"last 20%: {np.mean(rewards[-k:]):.1f}")
print("greedy episode:", learner.play(300))
assert np.mean(rewards[-k:]) > np.mean(rewards[:k])
