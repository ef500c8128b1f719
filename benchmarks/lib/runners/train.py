"""Runner ``train``: the program's train step under a steady input stream.

It copies the calls ``chip_smoke.py`` proved: ``TransformerConfig`` ->
``init_params`` -> ``make_train_step`` (with ``place_params`` and a mesh when
the configuration's deployment names one), and feeds it a ring of seeded host
batches, one ``device_put`` per step, so the input path runs. One step is kept
in flight ahead of the one being waited for, as a training loop does.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.lib import device, flops, model, traffic, watch
from benchmarks.lib.observe import Observed


def _planned_bytes(compiled):
    """What the compiler plans for one call of the step: arguments, outputs
    that are not donated arguments, and temporaries."""
    m = compiled.memory_analysis()
    if m is None:
        return 0, 0
    whole = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return int(whole), int(m.temp_size_in_bytes)


def _check_split(params, cfg, mesh):
    """Every parameter lives on all the mesh's devices in shards of the
    shape its PartitionSpec asks for (``chip_smoke.assert_really_split``)."""
    import jax
    from jax.sharding import PartitionSpec
    from deeplearning4j_tpu.models.bert import param_pspecs

    specs = jax.tree.leaves(param_pspecs(cfg),
                            is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = jax.tree.leaves(params)
    split = 0
    for leaf, spec in zip(leaves, specs):
        want, ways = list(leaf.shape), 1
        for dim, axis in enumerate(spec):
            if axis in mesh.axis_names:
                want[dim] //= mesh.shape[axis]
                ways *= mesh.shape[axis]
        shards = leaf.addressable_shards
        if {s.device for s in shards} != set(mesh.devices.flat) \
                or any(s.data.shape != tuple(want) for s in shards) \
                or len({str(s.index) for s in shards}) != ways:
            return False
        split += ways > 1
    return len(specs) == len(leaves) and split > 0


def _agrees_with_reference(cell, cfg, mesh, params, sizes, notes) -> bool:
    """Outside the window: the system's loss and logits on a seeded sample
    of sequences against the configuration's plain float32 reference."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import forward
    from deeplearning4j_tpu.models.bert import lm_loss

    ref = model.reference(cell.config)
    tol = cell.config["tolerances"]
    sample = dict(cell.traffic, batch=int(tol["sample_sequences"]))
    batch = traffic.train_batches(sample, sizes, 1, cell.seed + 1)[0]
    T = batch["tokens"].shape[1]
    rng = traffic.rng_for(cell.seed, "reference_positions")
    at = np.sort(rng.choice(T, size=min(T, int(tol["sample_positions"])),
                            replace=False))
    at = np.broadcast_to(at[None], (batch["tokens"].shape[0], at.size))
    got_loss = float(jax.jit(lambda p, b: lm_loss(p, b, cfg, mesh))(
        params, batch))
    got_logits = jax.jit(lambda p, t, a: jnp.take_along_axis(
        forward(p, t, cfg, mesh), a[:, :, None], axis=1))(
            params, batch["tokens"], at)
    want_loss = float(ref.loss(params, batch, sizes))
    want_logits = ref.logits_at(params, batch["tokens"], jnp.asarray(at),
                                sizes)
    loss_rel = abs(got_loss - want_loss) / abs(want_loss)
    logit_abs = float(jnp.max(jnp.abs(got_logits - want_logits)))
    notes["reference"] = {"loss": got_loss, "reference_loss": want_loss,
                          "loss_rel": loss_rel, "logit_abs": logit_abs}
    return loss_rel <= tol["loss_rtol"] and logit_abs <= tol["logit_abs"]


def run(cell):
    import jax
    from jax.sharding import NamedSharding
    from deeplearning4j_tpu.models import make_train_step
    from deeplearning4j_tpu.models.bert import batch_pspec, place_params

    sizes = cell.sizes
    cfg = model.transformer_config(sizes)
    tr = cell.traffic
    B, T = int(tr["batch"]), int(tr["seq_len"])
    mesh_shape = cell.config["deployment"].get("mesh")
    mesh = None
    if mesh_shape:
        from deeplearning4j_tpu.parallel import make_mesh

        mesh = make_mesh(dict(mesh_shape), cell.devices)
    params = model.make_weights(cfg, cell.seed)
    put = jax.device_put
    if mesh is not None:
        params = place_params(params, cfg, mesh)
        sharding = NamedSharding(mesh, batch_pspec(mesh))
        put = lambda b: {k: jax.device_put(v, sharding)   # noqa: E731
                         for k, v in b.items()}
    init_state, step = make_train_step(
        cfg, mesh, learning_rate=float(tr["learning_rate"]))
    opt_state = init_state(params)
    ring = traffic.train_batches(tr, sizes, int(tr["ring"]), cell.seed)
    compiled = step.lower(params, opt_state, put(ring[0])).compile()
    planned, planned_temp = _planned_bytes(compiled)

    losses = []
    for i in range(int(tr["warmup_steps"])):
        params, opt_state, loss = compiled(params, opt_state,
                                           put(ring[i % len(ring)]))
        losses.append(float(jax.block_until_ready(loss)))

    obs = Observed()
    compiles_before = cell.compiles.count

    def steps_for(seconds, max_steps=None):
        """Dispatch steps until ``seconds`` have passed, one in flight ahead
        of the one waited for; returns the window start, the times at which
        steps completed, and the host spans of the loop."""
        nonlocal params, opt_state
        done, spans, pending = [], [], None
        t0 = time.perf_counter()
        i = 0
        while True:
            a = time.perf_counter()
            batch = put(ring[i % len(ring)])
            b = time.perf_counter()
            params, opt_state, loss = compiled(params, opt_state, batch)
            c = time.perf_counter()
            spans += [("in_input", a, b), ("in_step_call", b, c)]
            if pending is not None:
                losses.append(float(jax.block_until_ready(pending)))
                done.append(time.perf_counter())
                spans.append(("in_wait_for_step", c, done[-1]))
            pending, i = loss, i + 1
            if time.perf_counter() - t0 >= seconds \
                    or (max_steps and i >= max_steps):
                break
        losses.append(float(jax.block_until_ready(pending)))
        done.append(time.perf_counter())
        return t0, done, spans

    t0, done, _ = steps_for(cell.seconds)
    setup_seconds = t0 - cell.t_start
    inside = [t for t in done if t - t0 <= cell.seconds] or done[:1]
    steps, last = len(inside), inside[-1] - t0
    compiled_inside = cell.compiles.count - compiles_before
    obs.window = (t0, t0 + cell.seconds)
    tokens_per_s = steps * B * T / last
    obs.facts.update({
        "setup_seconds": setup_seconds, "window_s": last, "steps": steps,
        "tokens": steps * B * T, "tokens_per_s": tokens_per_s,
        "step_ms": 1e3 * last / steps, "chips": len(cell.devices),
        "flops_per_token": flops.train_flops_per_token(sizes, T),
        "compiled_inside_window": compiled_inside,
        "planned_bytes": planned, "planned_temp_bytes": planned_temp,
    })
    if not cell.tiny:
        obs.facts["peak_flops_per_s"] = device.peak(
            cell.devices[0], "bf16_flops_per_s")

    if cell.trace:
        logdir = os.path.join(cell.scratch, "trace")
        with watch.device_trace(logdir) as tr_out:
            _, _, spans = steps_for(1e9, max_steps=int(tr["trace_steps"]))
        obs.facts["trace_steps"] = int(tr["trace_steps"])
        if tr_out["planes"] is not None:
            from benchmarks.lib import xplane

            obs.trace = xplane.reduce(tr_out["planes"], spans,
                                      between="between_steps")
            cell.keep_trace(tr_out)

    notes = {"losses_first_last": [losses[0], losses[-1]],
             "memory_stats": cell.devices[0].memory_stats()}
    correct = bool(np.all(np.isfinite(losses))) and compiled_inside == 0
    correct = _agrees_with_reference(cell, cfg, mesh, params, sizes,
                                     notes) and correct
    if mesh is not None:
        correct = _check_split(params, cfg, mesh) and correct
    return {"correct": correct, "attempted": steps, "failed": 0,
            "observed": obs,
            "memory_peak_bytes": device.memory_peak_bytes(cell.devices),
            "notes": notes}
