"""Runner ``train_causal``: next-token training of a model family the
configuration file names, under the steady input stream of ``train``.

The window, the in-flight loop and the facts are ``train``'s (a ring of
seeded host batches, one ``device_put`` a step, one step in flight ahead of
the one waited for). What differs:

- the program's configuration is built from **every** key of the
  configuration file's ``maps_to`` + ``program`` by the class it names
  (``program_class``, looked up in ``deeplearning4j_tpu.models``), so a
  family with fields ``lib/model.py`` does not list needs no edit there;
- targets are the next token and the last position carries no loss, made
  from ``traffic.train_batches``' tokens;
- operations per token and the kernels' operations and bytes come from the
  flops module the configuration names (``flops``, under ``lib/``);
- a step may return counters beside the loss (the routed-expert layer's
  rows per held expert): those of the last step land in the facts;
- the optimizer state is freed before the reference check, so that the
  float32 reference fits beside the weights, and the check knows that a
  top-k router's choice may flip where it was close (``tolerances``:
  ``margin_max``, ``flipped_share_max``, ``near``).

One chip: a configuration whose deployment names a mesh belongs to ``train``.
"""
from __future__ import annotations

import importlib
import os
import time

import numpy as np

from benchmarks.lib import device, model, traffic, watch
from benchmarks.lib.observe import Observed
from benchmarks.lib.runners.train import _planned_bytes


def _next_token(batch):
    """``train_batches``' batch as a next-token batch: position t is scored
    on token t+1, and the last position on nothing."""
    tokens, weights = batch["tokens"], batch["weights"].copy()
    weights[:, -1] = 0.0
    return {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1),
            "weights": weights}


def _routing_facts(counters) -> dict:
    """The routed-expert layer's counters of one step, as plain numbers."""
    rows = np.asarray(counters["rows_per_expert"], np.float64)
    tokens_out = np.asarray(counters["tokens_without_expert"], np.float64)
    return {"experts_load_max_over_mean": float(rows.max() / rows.mean()),
            "experts_rows_per_step": float(rows.sum()),
            "experts_rows_min": float(rows.min()),
            "experts_rows_max": float(rows.max()),
            "tokens_without_expert_per_layer": float(tokens_out.mean())}


def _agrees_with_reference(cell, cfg, params, sizes, notes) -> bool:
    """Outside the window, at the timed sizes, on the weights the window
    left: loss over every position and logits at a seeded sample of
    positions against the plain float32 reference.

    A family that routes tokens to experts reports the experts each token
    chose (``chosen`` among its counters), and so does its reference, with
    each position's margin. A top-k choice is a discontinuity, so two
    precisions may differ where the choice was close: a position whose
    choices differ in some layer is **flipped**. Every flipped position's
    margin must be under ``margin_max`` (a differing choice that was not
    close is a fault), their share of all positions must stay under
    ``flipped_share_max``, and they are left out of the logit comparison,
    and only of that; so are the positions before ``near`` that can see a
    flipped one, where a single earlier position carries more than 1/near
    of the attention. The loss is compared over all positions."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import family_of, forward

    ref = model.reference(cell.config)
    tol = cell.config["tolerances"]
    sample = dict(cell.traffic, batch=int(tol["sample_sequences"]))
    batch = _next_token(traffic.train_batches(sample, sizes, 1,
                                              cell.seed + 1)[0])
    B, T = batch["tokens"].shape
    rng = traffic.rng_for(cell.seed, "reference_positions")
    at = np.sort(rng.choice(T, size=min(T, int(tol["sample_positions"])),
                            replace=False))
    at = np.broadcast_to(at[None], (B, at.size))
    got_loss, counters = jax.jit(
        lambda p, b: family_of(cfg).loss_and_aux(p, b, cfg, None))(
            params, batch)
    got_loss = float(got_loss)
    got_logits = jax.jit(lambda p, t, a: jnp.take_along_axis(
        forward(p, t, cfg), a[:, :, None], axis=1))(
            params, batch["tokens"], at)
    want = ref.check(params, {k: jnp.asarray(v) for k, v in batch.items()},
                     jnp.asarray(at), sizes)
    want_loss = float(want["loss"])
    loss_rel = abs(got_loss - want_loss) / abs(want_loss)
    gap = np.asarray(jnp.max(jnp.abs(got_logits - want["logits"]), axis=-1))
    report = {"loss": got_loss, "reference_loss": want_loss,
              "loss_rel": loss_rel,
              "logit_spread": float(jnp.std(want["logits"]))}
    ok = loss_rel <= tol["loss_rtol"]
    left_out = np.zeros((B, T), bool)
    if counters and "chosen" in counters and "chosen" in want:
        got_chosen = np.asarray(counters["chosen"]).reshape(
            np.asarray(want["chosen"]).shape)
        flipped = (got_chosen != np.asarray(want["chosen"])).any((0, 3))
        margin = np.asarray(want["margin"])
        worst = float(margin[flipped].max()) if flipped.any() else 0.0
        near = int(tol["near"])
        sees_flip = np.cumsum(flipped, axis=1) > 0
        left_out = flipped | (sees_flip & (np.arange(T)[None] < near))
        report.update(flipped_share=float(flipped.mean()),
                      flipped_margin_max=worst,
                      flipped_margin_quantiles=np.quantile(
                          margin[flipped], [0.5, 0.9, 0.99]).tolist()
                      if flipped.any() else [])
        ok = ok and worst < tol["margin_max"] \
            and flipped.mean() <= tol["flipped_share_max"]
    clear = ~np.take_along_axis(left_out, at, axis=1)
    logit_abs = float(gap[clear].max()) if clear.any() else float("inf")
    report.update(
        logit_abs=logit_abs, sampled_left_out=int((~clear).sum()),
        logit_abs_left_out=float(gap[~clear].max()) if (~clear).any()
        else 0.0)
    notes["reference"] = report
    return bool(ok and logit_abs <= tol["logit_abs"])


def run(cell):
    import jax
    from deeplearning4j_tpu import models

    sizes = cell.sizes
    cfg = getattr(models, cell.config["program_class"])(**sizes)
    flops = importlib.import_module(
        "benchmarks.lib." + cell.config["flops"])
    tr = cell.traffic
    B, T = int(tr["batch"]), int(tr["seq_len"])
    params = model.make_weights(cfg, cell.seed)
    init_state, step = models.make_train_step(
        cfg, None, learning_rate=float(tr["learning_rate"]))
    opt_state = init_state(params)
    ring = [_next_token(b) for b in traffic.train_batches(
        tr, sizes, int(tr["ring"]), cell.seed)]
    compiled = step.lower(params, opt_state,
                          jax.device_put(ring[0])).compile()
    planned, planned_temp = _planned_bytes(compiled)

    losses, counters = [], None

    def call(batch):
        """Dispatch one step on a device batch; returns its loss."""
        nonlocal params, opt_state, counters
        params, opt_state, loss, *rest = compiled(params, opt_state, batch)
        counters = rest[0] if rest else None
        return loss

    for i in range(int(tr["warmup_steps"])):
        losses.append(float(jax.block_until_ready(
            call(jax.device_put(ring[i % len(ring)])))))

    obs = Observed()
    compiles_before = cell.compiles.count

    def steps_for(seconds, max_steps=None):
        """``train``'s loop: dispatch steps until ``seconds`` have passed,
        one in flight ahead of the one waited for."""
        done, spans, pending = [], [], None
        t0 = time.perf_counter()
        i = 0
        while True:
            a = time.perf_counter()
            batch = jax.device_put(ring[i % len(ring)])
            b = time.perf_counter()
            loss = call(batch)
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
    obs.facts.update({
        "setup_seconds": setup_seconds, "window_s": last, "steps": steps,
        "tokens": steps * B * T, "tokens_per_s": steps * B * T / last,
        "step_ms": 1e3 * last / steps, "chips": len(cell.devices),
        "flops_per_token": flops.train_flops_per_token(sizes, T),
        "compiled_inside_window": compiled_inside,
        "planned_bytes": planned, "planned_temp_bytes": planned_temp,
    })
    if not cell.tiny:
        for fact, what in (("peak_flops_per_s", "bf16_flops_per_s"),
                           ("peak_hbm_bytes_per_s", "hbm_bytes_per_s")):
            obs.facts[fact] = device.peak(cell.devices[0], what)

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
    routing = _routing_facts(jax.device_get(counters)) if counters else {}
    obs.facts.update(routing, **flops.kernels_per_step(
        sizes, B, T, routing.get("experts_rows_per_step")))

    notes = {"losses_first_last": [losses[0], losses[-1]],
             "memory_stats": cell.devices[0].memory_stats()}
    memory_peak = device.memory_peak_bytes(cell.devices)
    # the optimizer's two moments go before the float32 reference comes
    for leaf in jax.tree.leaves(opt_state):
        leaf.delete()
    del opt_state, compiled
    correct = bool(np.all(np.isfinite(losses))) and compiled_inside == 0
    correct = _agrees_with_reference(cell, cfg, params, sizes,
                                     notes) and correct
    return {"correct": correct, "attempted": steps, "failed": 0,
            "observed": obs, "memory_peak_bytes": memory_peak,
            "notes": notes}
