"""Long-context training (SURVEY §5.7 beyond-parity): the reference's only
answer to long sequences was truncated BPTT; here a causal LM trains on
full 8192-token sequences in ONE fused step, two ways:

1. Single-chip: ``attention_impl='flash'`` — the streamed Pallas flash
   kernels (O(T) memory fwd AND bwd; measured 25 ms/layer fwd+bwd at
   T=8192 on v5e, round-5 block sweep). On one real chip this config
   sustains ~51k tok/s end to end (B=4, no remat).
2. Sequence-parallel: the same model over a mesh with a 'context' axis —
   each device holds T/n_ctx of the sequence, K/V blocks ride the ring
   (``ring_flash_attention``: per-pair Pallas kernels, second-ring-pass
   backward, O(T_local) memory both directions).

On CPU this demo shrinks the shapes and runs the identical code on a
virtual 8-device mesh; on a TPU slice it spans real chips unchanged.
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models import (TransformerConfig, init_params,
                                       make_train_step)
from deeplearning4j_tpu.models.bert import batch_pspec, place_params
from deeplearning4j_tpu.parallel.mesh import make_mesh

on_tpu = jax.default_backend() == "tpu"
if on_tpu:
    T, B, layers, hidden, heads, mlp = 8192, 2, 4, 768, 12, 3072
    dtype = jnp.bfloat16
else:
    T, B, layers, hidden, heads, mlp = 2048, 1, 2, 64, 4, 128
    dtype = jnp.float32

# ---- 1. single-chip streamed-kernel training --------------------------------
cfg = TransformerConfig(vocab_size=1024, hidden=hidden, layers=layers,
                        heads=heads, mlp_dim=mlp, max_seq=T, causal=True,
                        dtype=dtype, remat=False, attention_impl="flash")
params = init_params(jax.random.PRNGKey(0), cfg)
init_state, step = make_train_step(cfg, learning_rate=3e-4)
opt = init_state(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
         "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
         "weights": jnp.ones((B, T), jnp.float32)}
losses = []
for i in range(4):
    params, opt, loss = step(params, opt, batch)
    losses.append(float(loss))
print(f"single-chip T={T}: losses {['%.3f' % l for l in losses]}")
assert losses[-1] < losses[0], "loss should fall on the memorizable batch"

# ---- 2. the same model sequence-parallel over a 'context' mesh --------------
n = jax.device_count()
ctx = min(4, n)
if ctx > 1:
    mesh = make_mesh({"data": 1, "context": ctx})
    cfg_sp = TransformerConfig(vocab_size=1024, hidden=hidden, layers=layers,
                               heads=heads, mlp_dim=mlp, max_seq=T,
                               causal=True, dtype=dtype, remat=False,
                               attention_impl="ring")
    params_sp = place_params(init_params(jax.random.PRNGKey(0), cfg_sp),
                             cfg_sp, mesh)
    init_sp, step_sp = make_train_step(cfg_sp, mesh=mesh, learning_rate=3e-4)
    opt_sp = init_sp(params_sp)
    from jax.sharding import NamedSharding
    bsh = NamedSharding(mesh, batch_pspec(mesh))
    sp_batch = {k: jax.device_put(v, bsh) for k, v in batch.items()}
    losses_sp = []
    for i in range(4):
        params_sp, opt_sp, loss = step_sp(params_sp, opt_sp, sp_batch)
        losses_sp.append(float(loss))
    print(f"ring SP over {ctx} context shards: losses "
          f"{['%.3f' % l for l in losses_sp]}")
    # same init, same data, exact attention: trajectories agree closely
    assert abs(losses_sp[0] - losses[0]) < 0.05, (losses_sp[0], losses[0])

    # ---- 2b. the load-BALANCED causal ring (zigzag layout) ------------------
    # a plain causal ring leaves early devices idle; the zigzag layout
    # gives every device constant work. The convenience API owns the
    # sequence permutation — drop-in for standalone attention calls:
    from deeplearning4j_tpu.parallel import (reference_attention,
                                             zigzag_ring_self_attention)
    rng2 = np.random.default_rng(1)
    # reduced length for the oracle check only: reference_attention
    # materializes (T_zz, T_zz) scores, which is exactly what the demo's
    # training legs avoid
    T_zz = min(T, 1024)
    qkv = [jnp.asarray(rng2.normal(size=(1, heads, T_zz, 64)) * 0.2,
                       jnp.float32) for _ in range(3)]
    zz = zigzag_ring_self_attention(mesh, *qkv)
    ref = reference_attention(*qkv, causal=True)
    err = float(jnp.max(jnp.abs(zz - ref)))
    print(f"zigzag balanced causal ring vs oracle: max err {err:.2e}")
    assert err < 1e-3
else:
    print("single device only - skipping the context-mesh leg "
          "(run with JAX_PLATFORMS=cpu for the virtual 8-device mesh "
          "demo, or on a multi-chip TPU slice)")
print("done")
