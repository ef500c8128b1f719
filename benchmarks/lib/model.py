"""From a configuration file to the program's model: sizes, the program's
``TransformerConfig``, and weights made on the device from ``--seed`` in one
jitted call."""
from __future__ import annotations

import importlib

# the program's TransformerConfig fields the configuration files may set
_FIELDS = ("vocab_size", "hidden", "layers", "heads", "mlp_dim", "max_seq",
           "causal", "remat", "attention_impl")


def sizes(config: dict, tiny: bool) -> dict:
    """The sizes as they are run: each program field read from the
    source's key that ``maps_to`` names, then the ``program`` settings, and
    with ``tiny`` the CPU rehearsal's overrides on top."""
    out = {field: config[key] for field, key in config["maps_to"].items()}
    out.update(config["program"])
    if tiny:
        out.update(config["tiny"])
    return out


def transformer_config(sz: dict):
    from deeplearning4j_tpu.models import TransformerConfig

    return TransformerConfig(**{k: sz[k] for k in _FIELDS})


def key_for(seed: int):
    """A PRNG key from a seed of any size (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) >> 31),
                              int(seed) & 0x7FFFFFFF)


def make_weights(cfg, seed: int):
    """The program's ``init_params`` as one jitted call, so the weights are
    made on the device and not leaf by leaf."""
    import jax
    from deeplearning4j_tpu.models import init_params

    return jax.jit(init_params, static_argnums=1)(key_for(seed), cfg)


def reference(config: dict):
    """The configuration's plain reference, found by name."""
    return importlib.import_module(
        f"benchmarks.references.{config['reference']}")
