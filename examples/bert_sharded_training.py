"""Flagship transformer on a (data, model) device mesh (SURVEY §2.9 P8 —
beyond-reference tensor parallelism): Megatron-style PartitionSpecs, batch
sharded over 'data', attention heads + MLP over 'model', ONE donated pjit
executable per step. On CPU this runs on a virtual 8-device mesh; on a TPU
slice the identical code spans real chips.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import jax
import jax.numpy as jnp
import numpy as np

if jax.default_backend() == "cpu" and jax.device_count() < 8:
    print("re-run with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
          "for the full mesh demo; continuing single-device")

from deeplearning4j_tpu.models import TransformerConfig, init_params, make_train_step
from deeplearning4j_tpu.models.bert import place_params
from deeplearning4j_tpu.parallel.mesh import make_mesh

cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2, heads=4,
                        mlp_dim=256, max_seq=64,
                        dtype=jnp.float32 if jax.default_backend() == "cpu"
                        else jnp.bfloat16,
                        remat=False)

n = jax.device_count()
mesh = make_mesh({'data': max(n // 2, 1), 'model': min(2, n)})
print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)))

params = place_params(init_params(jax.random.PRNGKey(0), cfg), cfg, mesh)
init_state, step = make_train_step(cfg, mesh=mesh, learning_rate=3e-4)
opt = init_state(params)

rng = np.random.default_rng(0)
B, T = 16, 64
batch = {
    "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
    "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
    "weights": jnp.ones((B, T), jnp.float32),
}

losses = []
for i in range(20):
    params, opt, loss = step(params, opt, batch)
    losses.append(float(loss))
print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")
assert losses[-1] < losses[0]

# the qkv kernel really is sharded over 'model'
qkv = params["blocks"][0]["qkv"]["kernel"]
print("qkv sharding:", qkv.sharding.spec)

# ---- multi-host input sharding (round 5) -----------------------------
# On a real multi-host slice each process reads a DISJOINT shard of the
# input stream with one wrapper — shard() defaults to this process's
# jax.process_index()/process_count(), shown here with explicit indices
# to simulate two hosts in one process:
from deeplearning4j_tpu.data import DataSet, ListDataSetIterator, shard

stream = [DataSet(rng.normal(size=(4, 8)).astype(np.float32),
                  rng.normal(size=(4, 2)).astype(np.float32))
          for _ in range(6)]
host0 = list(shard(ListDataSetIterator(stream), index=0, count=2))
host1 = list(shard(ListDataSetIterator(stream), index=1, count=2))
assert len(host0) == len(host1) == 3
# step s global batch = concat(host shards at step s), in stream order
for s, (a, b) in enumerate(zip(host0, host1)):
    assert a is stream[2 * s] and b is stream[2 * s + 1]
print("shard(): 6-batch stream -> 2 hosts x 3 disjoint batches, "
      "global order preserved")
