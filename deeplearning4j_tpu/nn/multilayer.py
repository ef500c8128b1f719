"""MultiLayerNetwork (ref: org.deeplearning4j.nn.multilayer.MultiLayerNetwork,
~5k LoC) — the sequential network runtime.

Architectural shift vs the reference (SURVEY.md §3.1): the reference's fit loop
makes dozens of JNI op calls per step (per-layer forward, per-layer backward,
per-block updater). Here **one training step = one XLA executable**: forward +
loss + regularization + backward (jax.grad) + optimizer update are traced
together and jit-compiled with donated param/opt-state buffers — the
whole-graph execution model SameDiff gestured at but never realized natively.

The reference's workspace machinery (LayerWorkspaceMgr, WS_* scopes) is
deleted: XLA buffer assignment owns activation memory. Flat-parameter-vector
semantics (paramsFlattened) are preserved at the API boundary via
params()/setParams() for serializer/averaging parity.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.eval import Evaluation, RegressionEvaluation
from deeplearning4j_tpu.ndarray.array import NDArray, _unwrap
from deeplearning4j_tpu.nn.conf.builder import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    BaseOutputLayer, BaseRecurrentLayer, Bidirectional, ConvolutionLayer, FeedForwardLayer,
    GlobalPoolingLayer, LastTimeStep, Layer, LossLayer, RnnOutputLayer, SubsamplingLayer,
)
from deeplearning4j_tpu.data.dataset import DataSet, DataSetIterator, ListDataSetIterator


def _as_jnp(x, dtype=None):
    x = _unwrap(x)
    if isinstance(x, np.ndarray) or not isinstance(x, jax.Array):
        x = jnp.asarray(x)
    return x.astype(dtype) if dtype is not None else x


def _clip_grads(grads, mode: Optional[str], threshold: float):
    """Gradient normalization (ref: org.deeplearning4j.nn.conf.GradientNormalization)."""
    if mode is None:
        return grads
    if mode == "ClipElementWiseAbsoluteValue":
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, -threshold, threshold), grads)
    if mode in ("ClipL2PerLayer", "ClipL2PerParamType"):
        def clip_layer(layer_grads):
            return {k: _clip_l2(v, threshold) for k, v in layer_grads.items()} \
                if isinstance(layer_grads, dict) else layer_grads
        if mode == "ClipL2PerParamType":
            return [clip_layer(g) for g in grads]
        out = []
        for g in grads:
            leaves = jax.tree_util.tree_leaves(g)
            if not leaves:
                out.append(g)
                continue
            norm = jnp.sqrt(sum(jnp.sum(l * l) for l in leaves))
            scale = jnp.where(norm > threshold, threshold / (norm + 1e-12), 1.0)
            out.append(jax.tree_util.tree_map(lambda l: l * scale, g))
        return out
    if mode == "RenormalizeL2PerLayer":
        out = []
        for g in grads:
            leaves = jax.tree_util.tree_leaves(g)
            if not leaves:
                out.append(g)
                continue
            norm = jnp.sqrt(sum(jnp.sum(l * l) for l in leaves))
            out.append(jax.tree_util.tree_map(lambda l: l / (norm + 1e-12), g))
        return out
    raise ValueError(f"unknown gradientNormalization: {mode}")


def _clip_l2(g, threshold):
    norm = jnp.sqrt(jnp.sum(g * g))
    return g * jnp.where(norm > threshold, threshold / (norm + 1e-12), 1.0)


def _stack_batches(items):
    """Stack K minibatches into one (K, ...) array with a SINGLE host->device
    transfer when the sources are host arrays (the common iterator case)."""
    raw = [_unwrap(i) for i in items]
    if all(isinstance(r, np.ndarray) for r in raw):
        return jnp.asarray(np.stack(raw))
    return jnp.stack([_as_jnp(i) for i in items])


class _DeviceCache:
    """Identity-keyed host->device transfer cache (bounded FIFO).

    Re-transferring the same minibatch host->device every epoch is a
    large share of a small training step. Training
    loops that revisit the same host arrays (fit(ds, epochs=N), epoch
    iterators over in-memory data) hit this cache and transfer once — the
    TPU answer to the reference's workspace-pinned device buffers
    (ref: MemoryWorkspace / AsyncDataSetIterator prefetch-to-GPU).

    Safety/limits (round-4 advisor findings):
    - **In-place mutation IS observed**: every hit verifies the current
      host bytes against a snapshot taken at insert (np.array_equal — a
      host memcmp is far cheaper than a re-transfer) and rebuilds
      on mismatch, so pipelines that refill a preallocated batch buffer
      train on the fresh data.
    - **Byte-bounded**, not entry-bounded: entries evict FIFO once the
      summed host-array bytes (a proxy for the pinned device copies)
      exceed ``max_bytes``.
    - **Streaming detection**: after ``_STREAM_MISSES`` consecutive
      misses the cache stops inserting (it would only pin HBM for batches
      that never repeat); a hit re-arms it.
    Disable entirely with ``enabled = False`` (networks expose
    ``setHostTransferCache``)."""

    _STREAM_MISSES = 16

    def __init__(self, max_bytes: int = 2 << 30):
        self.max_bytes = max_bytes
        self.enabled = True
        self._d: dict = {}
        self._bytes = 0
        self._consec_misses = 0

    def _evict_to_fit(self):
        while self._bytes > self.max_bytes and self._d:
            _, snaps = self._d.pop(next(iter(self._d)))  # FIFO (insert order)
            self._bytes -= sum(s.nbytes for s in snaps)

    def get_or_put(self, raws, build):
        if not self.enabled:
            return build()
        key = tuple(id(r) for r in raws)
        hit = self._d.get(key)
        if hit is not None:
            value, snaps = hit
            if all(np.array_equal(r, s) for r, s in zip(raws, snaps)):
                self._consec_misses = 0
                return value
            # host buffer was mutated in place: rebuild and re-snapshot
            # (still a key hit — re-arm streaming detection)
            self._consec_misses = 0
            value = build()
            self._bytes -= sum(s.nbytes for s in snaps)
            new_snaps = [np.array(r, copy=True) for r in raws]
            self._bytes += sum(s.nbytes for s in new_snaps)
            self._d[key] = (value, new_snaps)
            self._evict_to_fit()
            return value
        value = build()
        self._consec_misses += 1
        if self._consec_misses > self._STREAM_MISSES:
            return value  # streaming workload: don't pin HBM for one-shots
        snaps = [np.array(r, copy=True) for r in raws]
        self._bytes += sum(s.nbytes for s in snaps)
        self._d[key] = (value, snaps)
        self._evict_to_fit()
        return value


import functools as _functools


@_functools.partial(jax.jit, static_argnums=1)
def _chain_split(key, k: int):
    """k sequential ``key, sub = jax.random.split(key)`` draws in ONE
    dispatch (lax.scan). Returns (advanced_key, (k, ...) stacked subs) with
    values IDENTICAL to the per-step loop — so the fused multi-step path
    consumes the RNG stream exactly like the single-step path and the same
    seed yields the same trajectory regardless of fusing (round-3 advisor
    finding)."""

    def body(c, _):
        ks = jax.random.split(c)
        return ks[0], ks[1]

    return jax.lax.scan(body, key, None, length=k)


def _chunk_limit(listeners, iteration: int, fuse_k: int) -> int:
    """Steps the fused fit may scan from ``iteration`` before some listener
    needs the live model (1 = no fusing right now). Shared by
    MultiLayerNetwork and ComputationGraph."""
    k = fuse_k
    for lst in listeners:
        req = getattr(lst, "requiresModelAtIteration", lambda it: True)
        for j in range(1, k + 1):
            if req(iteration + j):
                k = j
                break
    return k


class _ReplayQueue:
    """Lagged, batched listener replay for the fused fit paths (round 5,
    shared by MultiLayerNetwork and ComputationGraph — same design as
    SameDiff.fit's drain_pending). Completed chunks' device losses queue
    here; score-only listener callbacks replay up to ``listenerReplayLag``
    chunks behind the dispatch head, and each drain moves ALL drained
    chunks' losses device->host in ONE transfer — a host read waits for
    the device, so per-chunk syncing serializes the scan pipeline.
    ``dispatched`` tracks the dispatch head for _chunk_limit; the net's
    ``_iteration`` advances only at replay (so listeners see exact
    per-step iteration numbers)."""

    def __init__(self, net, replay=None):
        self.net = net
        # replay(losses, k): fire one chunk's worth of per-step callbacks.
        # Default is the MLN/CG _replay_chunk; SameDiff.fit passes its own
        # (history-indexed iteration numbers) so all three fit paths share
        # THIS queue/transfer logic instead of three hand-rolled copies.
        self.replay = replay or (lambda losses, k: _replay_chunk(net, losses, k))
        self.pending: list = []
        self.dispatched = getattr(net, "_iteration", 0)

    def push(self, losses, k: int):
        self.dispatched += k
        self.pending.append((k, losses))
        need_model = any(
            getattr(l, "requiresModelAtIteration", lambda it: True)(
                self.dispatched) for l in self.net.listeners)
        if need_model or not self.net.listeners:
            # boundary listeners must observe the model exactly as of this
            # chunk end (before anything newer overwrites it); without
            # listeners the replay is free bookkeeping — keep it current
            self.drain()
        else:
            self.drain(keep=max(
                int(getattr(self.net, "listenerReplayLag", 16)), 0))

    def drain(self, keep: int = 0):
        if len(self.pending) <= keep:
            return
        take = self.pending[:len(self.pending) - keep]
        self.pending = self.pending[len(self.pending) - keep:]
        if self.net.listeners:
            flat = np.asarray(jnp.concatenate(
                [jnp.ravel(l) for _, l in take])).astype(float)
            off = 0
            for k, _ in take:
                self.replay(flat[off:off + k], k)
                off += k
        else:
            for k, losses in take:
                self.replay(losses, k)


def _replay_chunk(net, losses, k: int):
    """Replay k buffered per-step losses to listeners after a fused chunk —
    the same callback sequence the per-step path fires, with the model
    synced at chunk end (= every requiresModelAtIteration boundary).
    ``losses`` arrive already host-converted from _ReplayQueue.drain's
    single bulk transfer when listeners are attached; the conversion here
    covers direct callers only."""
    if net.listeners and not isinstance(losses, np.ndarray):
        losses = np.asarray(losses).astype(float)
    for j in range(k):
        net._score = losses[j]
        net._iteration += 1
        for lst in net.listeners:
            lst.iterationDone(net, net._iteration, net._epoch)


def _zero_frozen(tree_list, frozen):
    """Zero per-layer grad/update entries for frozen layers (ref: FrozenLayer)."""
    if not any(frozen):
        return tree_list
    return [jax.tree_util.tree_map(jnp.zeros_like, t) if frozen[i] else t
            for i, t in enumerate(tree_list)]


class MultiLayerNetwork:
    """Sequential network over a MultiLayerConfiguration."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self._params: Optional[list] = None
        self._state: Optional[list] = None
        self._opt_state = None
        self._tx: Optional[optax.GradientTransformation] = None
        self._iteration = 0
        self._epoch = 0
        self._score = float("nan")
        self.listeners: List[Any] = []
        self._jit_cache: dict = {}
        self._dev_cache = _DeviceCache()
        self._rng_key = jax.random.key(conf.seed)
        self._dtype = jnp.float32 if conf.dataType == "FLOAT" else (
            jnp.float64 if conf.dataType == "DOUBLE" else jnp.bfloat16)

    # ------------------------------------------------------------------ init
    def init(self):
        """Initialize params/state deterministically from conf.seed (ref:
        MultiLayerNetwork.init + param initializers)."""
        key = jax.random.key(self.conf.seed)
        keys = jax.random.split(key, max(len(self.layers), 1))
        self._params = [l.init_params(keys[i], self._dtype) for i, l in enumerate(self.layers)]
        self._state = [l.init_state(self._dtype) for l in self.layers]
        self._tx = self.conf.updater.to_optax()
        self._opt_state = self._tx.init(self._params)
        return self

    # -------------------------------------------------------------- forward
    def _adapt_input(self, x):
        it = self.conf.inputType
        if it is not None and it.kind == "cnnflat" and x.ndim == 2:
            x = x.reshape(x.shape[0], it.channels, it.height, it.width)
        # HALF/DOUBLE nets: float inputs join the conf dtype (convs reject
        # mixed operands). Integer inputs (embedding token ids) must NOT
        # round-trip through bf16 — ids > 256 would silently collide.
        if self._dtype != jnp.float32 and jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(self._dtype)
        return x

    def _forward(self, params, state, x, *, training, rng, mask=None, rnn_states=None):
        """Full forward pass; returns (output, new_states, new_rnn_states).
        Auto-inserts the CNN->FF flatten the reference handles via
        InputPreProcessors. When ``rnn_states`` is given, recurrent layers run
        from that state and report their final state (ref:
        rnnActivateUsingStoredState — the tBPTT/streaming path)."""
        x = self._adapt_input(x)
        new_states, new_rnn = [], []
        n = len(self.layers)
        rngs = jax.random.split(rng, n) if rng is not None else [None] * n
        from deeplearning4j_tpu.nn.conf.layers import needs_flatten
        for i, layer in enumerate(self.layers):
            # preprocessor-equivalent: flatten NCHW/NCDHW into (B, -1) for FF layers
            if needs_flatten(layer, x.ndim):
                x = x.reshape(x.shape[0], -1)
            # dl4j conf-level dropout: applied to the layer INPUT during training
            if training and layer.dropOut is not None and not isinstance(layer, _DropoutLike):
                from deeplearning4j_tpu.nn.conf.dropout import apply_dropout
                if rngs[i] is not None:
                    x = apply_dropout(layer.dropOut,
                                      jax.random.fold_in(rngs[i], 7), x)
            if rnn_states is not None and isinstance(layer, BaseRecurrentLayer) \
                    and rnn_states[i]:
                kwargs = {"mask": mask} if mask is not None else {}
                x, rs = layer.apply_rnn(params[i], x, rnn_states[i], **kwargs)
                new_rnn.append(rs)
                new_states.append(state[i] if state[i] else {})
                continue
            kwargs = {}
            if isinstance(layer, (BaseRecurrentLayer, Bidirectional, LastTimeStep,
                                  GlobalPoolingLayer)) and mask is not None:
                kwargs["mask"] = mask
            x, st = layer.apply(params[i], x, training=training, rng=rngs[i],
                                state=state[i] if state[i] else None, **kwargs)
            new_states.append(st if st is not None else {})
            new_rnn.append({})
        return x, new_states, new_rnn

    # ----------------------------------------------------------- jitted fns
    def _loss_for(self, params, state, x, y, rng, fmask, lmask):
        out, new_states, _ = self._forward(params, state, x, training=True, rng=rng, mask=fmask)
        out_layer = self.layers[-1]
        from deeplearning4j_tpu.nn.conf.layers import CenterLossOutputLayer
        if isinstance(out_layer, CenterLossOutputLayer):
            loss = out_layer.compute_loss_ext(params[-1], y, out,
                                              new_states[-1]["features"], lmask)
            # the features were an aux channel for THIS loss only — strip
            # them so a batch of activations is never persisted as model
            # state (it would pin device memory and retrace on batch change)
            new_states = new_states[:-1] + [{}]
        elif hasattr(out_layer, "loss_with_params"):  # OCNN: loss needs own params
            loss = out_layer.loss_with_params(params[-1], y, out, lmask)
        elif hasattr(out_layer, "compute_loss"):  # output/loss/yolo layers
            loss = out_layer.compute_loss(y, out, lmask if lmask is not None else
                                          (fmask if isinstance(out_layer, RnnOutputLayer) else None))
        else:
            loss = jnp.mean((out - y) ** 2)
        # regularization (ref: BaseLayer.calcRegularizationScore summed into score)
        for reg in self.conf.regularization:
            for i, layer in enumerate(self.layers):
                for k in layer.regularizable():
                    if k in params[i]:
                        loss = loss + reg.penalty(params[i][k])
        return loss, new_states

    def _build_step(self, with_stats: bool = False):
        """One XLA executable: grad → clip → update. ``with_stats`` variants
        also return the gradient and applied-update trees for listeners
        advertising requiresGradients/requiresUpdates (StatsListener,
        panic-mode ProfilingListener); params are then NOT donated since the
        returned trees alias them."""
        conf = self.conf

        frozen = [getattr(l, "frozen", False) for l in self.layers]

        def step(params, state, opt_state, x, y, rng, fmask, lmask):
            (loss, new_states), grads = jax.value_and_grad(
                self._loss_for, has_aux=True)(params, state, x, y, rng, fmask, lmask)
            grads = _zero_frozen(grads, frozen)
            grads = _clip_grads(grads, conf.gradientNormalization,
                                conf.gradientNormalizationThreshold)
            updates, opt_state = self._tx.update(grads, opt_state, params)
            # zero the UPDATES too: decoupled weight decay (AdamW) would
            # otherwise mutate frozen params despite zero grads (ref:
            # FrozenLayer applies no update at all)
            updates = _zero_frozen(updates, frozen)
            new_params = optax.apply_updates(params, updates)
            if with_stats:
                return new_params, new_states, opt_state, loss, grads, updates
            return new_params, new_states, opt_state, loss

        return jax.jit(step, donate_argnums=() if with_stats else (0, 2))

    def _stats_requested(self) -> bool:
        return any(getattr(l, "requiresGradients", False)
                   or getattr(l, "requiresUpdates", False)
                   for l in self.listeners)

    # Steps fused into one executable by fit()'s multi-step path. 8 amortizes
    # per-dispatch host latency on small steps without inflating compile
    # time.
    fuseSteps: int = 8
    # How many fused chunks score-only listener callbacks may lag the
    # dispatch head before a forced batched replay (see _ReplayQueue;
    # 0 = replay right after every chunk, paying one host round trip each)
    listenerReplayLag: int = 16

    def _build_multi_step(self):
        """``fuseSteps`` training steps in ONE XLA executable: lax.scan over
        stacked minibatches, params/opt-state carried on device. This is the
        de-dispatch move one level up from the per-step fusion — the
        reference's per-op JNI dispatch disease (SURVEY §3.1) reappears as
        per-STEP Python dispatch on small models; the scan deletes it.
        Used by fit() when no listener/mask/tBPTT forces host hops."""
        conf = self.conf
        frozen = [getattr(l, "frozen", False) for l in self.layers]

        def body(carry, inp):
            params, state, opt_state = carry
            x, y, rng = inp
            (loss, new_states), grads = jax.value_and_grad(
                self._loss_for, has_aux=True)(params, state, x, y, rng,
                                              None, None)
            grads = _zero_frozen(grads, frozen)
            grads = _clip_grads(grads, conf.gradientNormalization,
                                conf.gradientNormalizationThreshold)
            updates, opt_state = self._tx.update(grads, opt_state, params)
            updates = _zero_frozen(updates, frozen)
            params = optax.apply_updates(params, updates)
            return (params, new_states, opt_state), loss

        def multi(params, state, opt_state, xs, ys, rngs):
            (params, state, opt_state), losses = jax.lax.scan(
                body, (params, state, opt_state), (xs, ys, rngs))
            # full per-step losses: fit() replays them to listeners after
            # the chunk (one host sync per chunk at most, not per step)
            return params, state, opt_state, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def _build_infer(self):
        def infer(params, state, x, fmask):
            out, _, _ = self._forward(params, state, x, training=False, rng=None, mask=fmask)
            return out

        return jax.jit(infer)

    def _get_jitted(self, kind):
        if kind not in self._jit_cache:
            builders = {"step": self._build_step, "infer": self._build_infer,
                        "step_stats": lambda: self._build_step(with_stats=True),
                        "multi": self._build_multi_step}
            self._jit_cache[kind] = builders[kind]()
        return self._jit_cache[kind]

    # ---------------------------------------------- rnn state (tBPTT/stream)
    def _rnn_format(self) -> str:
        """Time-axis layout of this net's sequence data: 'NWC' (B,T,F) or the
        reference's 'NCW' (B,F,T), taken from the first recurrent layer."""
        for l in self.layers:
            if isinstance(l, BaseRecurrentLayer):
                return l.rnnDataFormat
        return "NWC"

    def _init_rnn_states(self, batch: int) -> list:
        return [l.init_rnn_state(batch, self._dtype)
                if isinstance(l, BaseRecurrentLayer) else {}
                for l in self.layers]

    def _build_tbptt_step(self):
        conf = self.conf
        frozen = [getattr(l, "frozen", False) for l in self.layers]

        def loss_fn(params, state, x, y, rng, fmask, lmask, rnn_states):
            out, new_states, new_rnn = self._forward(
                params, state, x, rnn_states=rnn_states, training=True, rng=rng, mask=fmask)
            out_layer = self.layers[-1]
            if hasattr(out_layer, "compute_loss_ext") or hasattr(out_layer, "loss_with_params"):
                # center-loss/OCNN heads have no tBPTT semantics in the
                # reference either — refuse rather than silently drop terms
                raise NotImplementedError(
                    f"{type(out_layer).__name__} is not supported under TruncatedBPTT")
            if hasattr(out_layer, "compute_loss"):
                loss = out_layer.compute_loss(y, out, lmask if lmask is not None else
                                              (fmask if isinstance(out_layer, RnnOutputLayer) else None))
            else:
                loss = jnp.mean((out - y) ** 2)
            for reg in conf.regularization:
                for i, layer in enumerate(self.layers):
                    for k in layer.regularizable():
                        if k in params[i]:
                            loss = loss + reg.penalty(params[i][k])
            return loss, (new_states, new_rnn)

        def step(params, state, opt_state, x, y, rng, fmask, lmask, rnn_states):
            (loss, (new_states, new_rnn)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, x, y, rng, fmask, lmask, rnn_states)
            grads = _zero_frozen(grads, frozen)
            grads = _clip_grads(grads, conf.gradientNormalization,
                                conf.gradientNormalizationThreshold)
            updates, opt_state = self._tx.update(grads, opt_state, params)
            updates = _zero_frozen(updates, frozen)
            params = optax.apply_updates(params, updates)
            # state entering the next segment is a constant (ref: tBPTT detaches)
            new_rnn = jax.lax.stop_gradient(new_rnn)
            return params, new_states, opt_state, loss, new_rnn

        return jax.jit(step, donate_argnums=(0, 2))

    def _fit_tbptt(self, ds):
        """One DataSet fitted by truncated BPTT (ref: MultiLayerNetwork.
        doTruncatedBPTT): time axis sliced into fwdLength segments, recurrent
        state carried (detached) across segments within the batch."""
        if "tbptt" not in self._jit_cache:
            self._jit_cache["tbptt"] = self._build_tbptt_step()
        step = self._jit_cache["tbptt"]
        x_all = _as_jnp(ds.features)
        y_all = _as_jnp(ds.labels)
        fmask_all = _as_jnp(ds.features_mask) if ds.features_mask is not None else None
        lmask_all = _as_jnp(ds.labels_mask) if ds.labels_mask is not None else None
        taxis = 2 if self._rnn_format() == "NCW" else 1  # NCW = (B,F,T)
        T = x_all.shape[taxis]
        k = self.conf.tbpttFwdLength
        rnn_states = self._init_rnn_states(x_all.shape[0])

        def tslice(arr, sl):
            return arr[:, :, sl] if taxis == 2 else arr[:, sl]

        for t0 in range(0, T, k):
            sl = slice(t0, min(t0 + k, T))
            self._rng_key, sub = jax.random.split(self._rng_key)
            self._params, self._state, self._opt_state, loss, rnn_states = step(
                self._params, self._state, self._opt_state,
                tslice(x_all, sl), tslice(y_all, sl), sub,
                None if fmask_all is None else fmask_all[:, sl],  # masks are (B,T)
                None if lmask_all is None else lmask_all[:, sl],
                rnn_states)
            self._score = loss  # device scalar; score() syncs on demand
            self._iteration += 1
            for lst in self.listeners:
                lst.iterationDone(self, self._iteration, self._epoch)

    def rnnTimeStep(self, x) -> NDArray:
        """Streaming inference with stored state (ref: MultiLayerNetwork.
        rnnTimeStep). x: (B,F) one step, or a full sequence in the net's
        rnnDataFormat ((B,T,F) NWC / (B,F,T) NCW)."""
        xv = _as_jnp(x)
        ncw = self._rnn_format() == "NCW"
        single = xv.ndim == 2
        if single:
            xv = xv[:, :, None] if ncw else xv[:, None, :]
        if getattr(self, "_stream_rnn", None) is None or \
                jax.tree_util.tree_leaves(self._stream_rnn) and \
                jax.tree_util.tree_leaves(self._stream_rnn)[0].shape[0] != xv.shape[0]:
            self._stream_rnn = self._init_rnn_states(xv.shape[0])
        if "rnn_step" not in self._jit_cache:
            def fwd(params, state, x, rnn_states):
                out, _, new_rnn = self._forward(params, state, x, rnn_states=rnn_states,
                                                training=False, rng=None)
                return out, new_rnn
            self._jit_cache["rnn_step"] = jax.jit(fwd)
        out, self._stream_rnn = self._jit_cache["rnn_step"](
            self._params, self._state, xv, self._stream_rnn)
        if single and out.ndim == 3:
            out = out[:, :, 0] if ncw else out[:, 0]
        return NDArray(out)

    def rnnClearPreviousState(self):
        """(ref: rnnClearPreviousState)."""
        self._stream_rnn = None

    def rnnGetPreviousState(self, layer_idx: int) -> dict:
        st = getattr(self, "_stream_rnn", None)
        return {} if st is None else {k: NDArray(v) for k, v in st[layer_idx].items()}

    # ------------------------------------------------------------- pretrain
    def pretrainLayer(self, layer_idx: int, data, epochs: int = 1):
        """Layer-wise unsupervised pretraining for AutoEncoder/VAE layers
        (ref: MultiLayerNetwork.pretrainLayer): features forward through the
        preceding layers (inference), then the layer's pretrain_loss is
        minimized — feature extraction + loss + update in ONE jitted step."""
        from deeplearning4j_tpu.data.dataset import DataSet, ListDataSetIterator
        layer = self.layers[layer_idx]
        if not hasattr(layer, "pretrain_loss"):
            return self  # non-pretrainable layers are skipped (ref behavior)
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])

        key = ("pretrain", layer_idx)
        if key not in self._jit_cache:
            tx = self.conf.updater.to_optax()

            def step(lp, all_params, state, opt_state, x, rng):
                from deeplearning4j_tpu.nn.conf.layers import needs_flatten
                feats = self._adapt_input(x)
                for i in range(layer_idx):
                    if needs_flatten(self.layers[i], feats.ndim):
                        feats = feats.reshape(feats.shape[0], -1)
                    feats, _ = self.layers[i].apply(
                        all_params[i], feats, training=False,
                        state=state[i] if state[i] else None)
                loss, g = jax.value_and_grad(layer.pretrain_loss)(lp, feats, rng)
                updates, opt_state = tx.update(g, opt_state, lp)
                return optax.apply_updates(lp, updates), opt_state, loss

            # no donation: lp aliases all_params[layer_idx] in the call
            self._jit_cache[key] = (jax.jit(step), tx)
        step, tx = self._jit_cache[key]
        lp = self._params[layer_idx]
        opt_state = tx.init(lp)
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                self._rng_key, sub = jax.random.split(self._rng_key)
                lp, opt_state, loss = step(lp, self._params, self._state,
                                           opt_state, _as_jnp(ds.features), sub)
                self._score = loss  # device scalar; score() syncs on demand
                self._iteration += 1
        self._params = list(self._params)
        self._params[layer_idx] = lp
        return self

    def pretrain(self, data, epochs: int = 1):
        """Pretrain every pretrainable layer in order (ref: MultiLayerNetwork.
        pretrain)."""
        for i in range(len(self.layers)):
            self.pretrainLayer(i, data, epochs)
        return self

    # ------------------------------------------------------------------ fit
    def fit(self, data, labels=None, epochs: int = 1):
        """fit(DataSetIterator), fit(DataSet), or fit(features, labels)
        (ref: MultiLayerNetwork.fit overloads). A crash during training
        writes a diagnostic dump (ref: CrashReportingUtil), then re-raises."""
        try:
            return self._fit_impl(data, labels, epochs)
        except Exception as e:  # dump-and-reraise; reporting never masks the error
            from deeplearning4j_tpu.util import crash_reporting
            if not getattr(e, "_control_flow", False):  # early-stop signals etc.
                crash_reporting.writeMemoryCrashDump(self, e)
            raise

    def _fit_impl(self, data, labels=None, epochs: int = 1):
        if labels is not None:
            data = ListDataSetIterator([DataSet(data, labels)])
        elif isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        tbptt = self.conf.backpropType == "TruncatedBPTT"
        stats = self._stats_requested()
        kind = "step_stats" if stats else "step"
        step = None if tbptt else self._get_jitted(kind)
        # De-dispatch path: steps buffer into fuseSteps-sized lax.scan
        # chunks (one dispatch each). Listeners no longer disable it
        # (round-3 verdict #3): chunks are cut so the scan flushes exactly
        # at iterations where a listener needs the LIVE model
        # (requiresModelAtIteration — e.g. CheckpointListener save points),
        # and the buffered per-step losses are replayed to listeners after
        # each chunk. Only stats-requesting listeners and tBPTT force the
        # true per-step path.
        fuse_k = 0 if (tbptt or stats) else self.fuseSteps
        buf: list = []  # (features, labels) pairs of identical shape
        rq = _ReplayQueue(self)

        def run_single(ds):
            nonlocal step
            rq.drain()   # callback order: buffered chunks before this step
            raw_f, raw_y = _unwrap(ds.features), _unwrap(ds.labels)
            if isinstance(raw_f, np.ndarray) and isinstance(raw_y, np.ndarray):
                x, y = self._dev_cache.get_or_put(
                    [raw_f, raw_y], lambda: (_as_jnp(raw_f), _as_jnp(raw_y)))
            else:
                x, y = _as_jnp(ds.features), _as_jnp(ds.labels)
            fmask = _as_jnp(ds.features_mask) if ds.features_mask is not None else None
            lmask = _as_jnp(ds.labels_mask) if ds.labels_mask is not None else None
            self._rng_key, sub = jax.random.split(self._rng_key)
            if step is None:
                step = self._get_jitted(kind)
            if stats:
                (self._params, self._state, self._opt_state, loss,
                 self._last_grads, self._last_updates) = step(
                    self._params, self._state, self._opt_state, x, y, sub, fmask, lmask)
            else:
                self._params, self._state, self._opt_state, loss = step(
                    self._params, self._state, self._opt_state, x, y, sub, fmask, lmask)
            self._score = loss  # device scalar; score() syncs on demand
            self._iteration += 1
            rq.dispatched += 1
            for lst in self.listeners:
                lst.iterationDone(self, self._iteration, self._epoch)

        def drain(buf):
            for f, y in buf:  # singles reuse the already-compiled step
                run_single(DataSet(f, y))
            return []

        def flush(buf):
            while buf:
                k = _chunk_limit(self.listeners, rq.dispatched, fuse_k)
                if k <= 1:
                    # a listener needs the live model at the very next
                    # iteration: run it as a single (exact semantics)
                    f, y = buf[0]
                    run_single(DataSet(f, y))
                    buf = buf[1:]
                    continue
                if len(buf) < k:
                    break
                chunk, buf = buf[:k], buf[k:]
                raws = [_unwrap(f) for f, _ in chunk] + \
                       [_unwrap(y) for _, y in chunk]
                if all(isinstance(r, np.ndarray) for r in raws):
                    xs, ys = self._dev_cache.get_or_put(
                        raws, lambda: (_stack_batches([f for f, _ in chunk]),
                                       _stack_batches([y for _, y in chunk])))
                else:
                    xs = _stack_batches([f for f, _ in chunk])
                    ys = _stack_batches([y for _, y in chunk])
                # RNG stream identical to k single steps (_chain_split)
                self._rng_key, rngs = _chain_split(self._rng_key, k)
                multi = self._get_jitted("multi")
                (self._params, self._state, self._opt_state,
                 losses) = multi(self._params, self._state,
                                 self._opt_state, xs, ys, rngs)
                rq.push(losses, k)
            return buf

        try:
            for _ in range(epochs):
                for ds in data:
                    if tbptt and np.ndim(ds.features) == 3:
                        # NB fuse_k is 0 whenever tbptt is set, so buf/rq
                        # are necessarily empty here — nothing to drain
                        self._fit_tbptt(ds)
                        continue
                    if fuse_k > 1 and ds.features_mask is None \
                            and ds.labels_mask is None:
                        if buf and (np.shape(buf[0][0]) != np.shape(ds.features)
                                    or np.shape(buf[0][1]) != np.shape(ds.labels)):
                            buf = drain(buf)  # shape change: drain as singles
                        buf.append((ds.features, ds.labels))
                        buf = flush(buf)
                    else:
                        # masked/ineligible batch: buffered earlier steps must
                        # apply FIRST (sequential SGD order, round-3 advisor)
                        buf = drain(buf)
                        run_single(ds)
                # epoch boundary: apply leftovers so epoch listeners see a
                # fully-stepped model, then fire onEpochEnd
                buf = drain(buf)
                rq.drain()
                self._epoch += 1
                for lst in self.listeners:
                    if hasattr(lst, "onEpochEnd"):
                        lst.onEpochEnd(self)
        except BaseException:
            # an exception mid-fit must not lose completed chunks'
            # callbacks; never mask the original error with a replay failure
            try:
                rq.drain()
            except Exception:
                pass
            raise
        return self

    # ------------------------------------------------------------- inference
    def output(self, x, train: bool = False, features_mask=None) -> NDArray:
        """(ref: MultiLayerNetwork.output)."""
        infer = self._get_jitted("infer")
        fmask = _as_jnp(features_mask) if features_mask is not None else None
        return NDArray(infer(self._params, self._state, _as_jnp(x), fmask))

    def warmup(self, example_row, batch_sizes=(1,)) -> "MultiLayerNetwork":
        """Pre-compile the inference executable for the given batch sizes.
        ``example_row`` is ONE row (feature shape, no batch dim); each size
        runs a throwaway forward so jit's shape-specialized cache is hot
        before real traffic — the serving registry's warmup-on-deploy hook
        (serving/registry.py) and a useful standalone latency tool."""
        ex = np.asarray(example_row)
        for b in batch_sizes:
            np.asarray(self.output(np.broadcast_to(ex, (b,) + ex.shape).copy()).jax)
        return self

    def feedForward(self, x) -> List[NDArray]:
        """Per-layer activations list, input first (ref: feedForward)."""
        from deeplearning4j_tpu.nn.conf.layers import needs_flatten
        acts = [NDArray(_as_jnp(x))]
        xv = self._adapt_input(_as_jnp(x))
        cur = xv
        for i, layer in enumerate(self.layers):
            if needs_flatten(layer, cur.ndim):
                cur = cur.reshape(cur.shape[0], -1)
            cur, _ = layer.apply(self._params[i], cur, training=False,
                                 state=self._state[i] if self._state[i] else None)
            acts.append(NDArray(cur))
        return acts

    def predict(self, x) -> np.ndarray:
        """Class indices (ref: MultiLayerNetwork.predict)."""
        return np.asarray(jnp.argmax(self.output(x).jax, axis=-1))

    # ---------------------------------------------------------------- score
    def score(self, dataset: Optional[DataSet] = None) -> float:
        """Last-minibatch loss, or loss on a provided DataSet (ref: score())."""
        if dataset is None:
            return float(self._score)
        x = _as_jnp(dataset.features)
        y = _as_jnp(dataset.labels)
        loss, _ = self._loss_for(self._params, self._state, x, y, None,
                                 _as_jnp(dataset.features_mask) if dataset.features_mask is not None else None,
                                 _as_jnp(dataset.labels_mask) if dataset.labels_mask is not None else None)
        return float(loss)

    # ----------------------------------------------------------- evaluation
    def evaluate(self, iterator: DataSetIterator, num_classes: Optional[int] = None) -> Evaluation:
        """(ref: MultiLayerNetwork.evaluate)."""
        ev = Evaluation(num_classes)
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, out.toNumpy(), mask=ds.labels_mask)
        return ev

    def evaluateRegression(self, iterator: DataSetIterator) -> RegressionEvaluation:
        ev = RegressionEvaluation()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(ds.labels, out.toNumpy())
        return ev

    # ---------------------------------------------------- flat param surface
    def params(self) -> NDArray:
        """Flat parameter vector, layer order, sorted-key tree order within a
        layer (ref: MultiLayerNetwork.params / paramsFlattened). tree_flatten
        handles nested param dicts (e.g. Bidirectional's {'fwd','bwd'})."""
        leaves = [jnp.ravel(l) for l in jax.tree_util.tree_leaves(self._params)]
        if not leaves:
            return NDArray(jnp.zeros((0,)))
        return NDArray(jnp.concatenate(leaves))

    def setParams(self, flat):
        """(ref: MultiLayerNetwork.setParams) — inverse of params()."""
        flat = _as_jnp(flat).ravel()
        leaves, treedef = jax.tree_util.tree_flatten(self._params)
        pos, new = 0, []
        for l in leaves:
            n = int(np.prod(l.shape))
            new.append(flat[pos:pos + n].reshape(l.shape).astype(l.dtype))
            pos += n
        self._params = jax.tree_util.tree_unflatten(treedef, new)

    def numParams(self) -> int:
        return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(self._params)))

    def getParam(self, layer_idx: int, key: str) -> NDArray:
        return NDArray(self._params[layer_idx][key])

    def setParam(self, layer_idx: int, key: str, value):
        self._params[layer_idx] = dict(self._params[layer_idx])
        self._params[layer_idx][key] = _as_jnp(value)

    # ------------------------------------------------------------- listeners
    def setListeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def addListeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def setHostTransferCache(self, enabled: bool):
        """Toggle the host->device minibatch transfer cache (on by default;
        mutation-safe — see _DeviceCache). Off = every fit() batch is
        re-transferred."""
        self._dev_cache.enabled = enabled
        return self

    def getIterationCount(self) -> int:
        return self._iteration

    def getEpochCount(self) -> int:
        return self._epoch

    # ----------------------------------------------------------------- misc
    def clone(self) -> "MultiLayerNetwork":
        other = MultiLayerNetwork(self.conf)
        if self._params is not None:
            other._params = jax.tree_util.tree_map(lambda a: a, self._params)
            other._state = jax.tree_util.tree_map(lambda a: a, self._state)
            other._tx = self.conf.updater.to_optax()
            other._opt_state = other._tx.init(other._params)
        return other

    def summary(self) -> str:
        """(ref: MultiLayerNetwork.summary)."""
        rows = [("idx", "type", "nParams", "shape")]
        total = 0
        for i, layer in enumerate(self.layers):
            p = self._params[i] if self._params else {}
            n = int(sum(np.prod(v.shape) for v in p.values()))
            total += n
            shapes = ", ".join(f"{k}:{list(v.shape)}" for k, v in sorted(p.items()))
            rows.append((str(i), type(layer).__name__, str(n), shapes))
        widths = [max(len(r[c]) for r in rows) for c in range(4)]
        lines = ["  ".join(r[c].ljust(widths[c]) for c in range(4)) for r in rows]
        lines.append(f"Total params: {total}")
        return "\n".join(lines)


class _DropoutLike:
    pass


from deeplearning4j_tpu.nn.conf.layers import DropoutLayer as _DL  # noqa: E402

_DropoutLike = _DL
