"""Data-parallel training & inference (ref: deeplearning4j-parallel-wrapper
ParallelWrapper / ParallelInference, SURVEY.md §2.9 P2/P3/P7 and §3.4).

The reference spawns one thread + model replica per device, round-robins
batches, and periodically averages parameters (or asynchronously shares
threshold-encoded gradients). Here the whole mechanism collapses into sharded
jit: parameters live replicated on a Mesh, batches are sharded over the
``data`` axis, and XLA's SPMD partitioner emits the psum gradient sync inside
the *same* fused step — exact lockstep DP, semantically the reference's
averagingFrequency=1 (strictly stronger than both its modes; the async
staleness of gradient sharing is deliberately NOT reproduced — see
gradient_sharing.py for the compression-hook parity).

Multi-host: identical code — initialize jax.distributed (see multihost.py) and
the same Mesh spans all hosts' devices; ICI collectives within a slice, DCN
across slices, still zero framework networking code.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.data.dataset import DataSet, DataSetIterator, ListDataSetIterator
from deeplearning4j_tpu.ndarray.array import NDArray
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, batch_sharding, make_mesh


class ParallelWrapper:
    """Data-parallel trainer for a MultiLayerNetwork (ref: ParallelWrapper.Builder
    surface: workers(n) ≙ mesh size; averaging/gradient-sharing modes are both
    subsumed by exact per-step psum)."""

    def __init__(self, model, mesh: Optional[Mesh] = None, workers: Optional[int] = None):
        self.model = model
        if mesh is None:
            devs = jax.devices()
            if workers is not None:
                devs = devs[:workers]
            mesh = make_mesh({DATA_AXIS: len(devs)}, devs)
        self.mesh = mesh
        self._n = mesh.shape[DATA_AXIS]
        self._placed = False

    class Builder:
        """Fluent parity shim (ref: ParallelWrapper.Builder)."""

        def __init__(self, model):
            self._model = model
            self._workers = None

        def workers(self, n: int):
            self._workers = n
            return self

        def averagingFrequency(self, n: int):
            return self  # subsumed: exact sync every step

        def prefetchBuffer(self, n: int):
            return self  # jax async dispatch already overlaps host/device

        def trainingMode(self, mode: str):
            return self  # AVERAGING and SHARED_GRADIENTS both -> exact psum

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._model, workers=self._workers)

    # ------------------------------------------------------------------ fit
    def _place_params(self):
        rep = NamedSharding(self.mesh, P())
        m = self.model
        m._params = jax.tree_util.tree_map(lambda a: jax.device_put(a, rep), m._params)
        m._state = jax.tree_util.tree_map(lambda a: jax.device_put(a, rep), m._state)
        m._opt_state = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, rep) if isinstance(a, jax.Array) else a, m._opt_state)
        self._placed = True

    def _shard_batch(self, arr):
        arr = np.asarray(arr)
        n = self._n
        b = arr.shape[0]
        if b % n:  # pad final partial batch by cycling rows (reference drops/round-robins)
            arr = arr[np.resize(np.arange(b), b + n - (b % n))]
        return jax.device_put(arr, batch_sharding(self.mesh, rank=arr.ndim))

    def fit(self, data, epochs: int = 1):
        """Sharded lockstep DP fit (ref: ParallelWrapper.fit)."""
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        if not self._placed:
            self._place_params()
        m = self.model
        step = m._get_jitted("step")
        with jax.set_mesh(self.mesh):
            for _ in range(epochs):
                for ds in data:
                    x = self._shard_batch(ds.features)
                    y = self._shard_batch(ds.labels)
                    fmask = self._shard_batch(ds.features_mask) if ds.features_mask is not None else None
                    lmask = self._shard_batch(ds.labels_mask) if ds.labels_mask is not None else None
                    m._rng_key, sub = jax.random.split(m._rng_key)
                    m._params, m._state, m._opt_state, loss = step(
                        m._params, m._state, m._opt_state, x, y, sub, fmask, lmask)
                    m._score = float(loss)
                    m._iteration += 1
                    for lst in m.listeners:
                        lst.iterationDone(m, m._iteration, m._epoch)
                for lst in m.listeners:
                    if hasattr(lst, "onEpochEnd"):
                        lst.onEpochEnd(m)
                m._epoch += 1
        return self.model

    def shutdown(self):
        pass  # no worker threads to stop — parity no-op


class ParallelInference:
    """Sharded batch inference (ref: deeplearning4j-parallel-wrapper
    ParallelInference: per-device replicas + dynamic batching observables).
    Here: one replicated jit executable; arbitrary batches are padded, sharded
    over the data axis, and de-padded — XLA splits the work across devices.

    Batch sizes are padded UP to a geometric ladder of multiples of the
    mesh size (n, 2n, 4n, ...) rather than merely to the next multiple of
    n: jit specializes per shape, so the old padding still compiled a
    fresh executable per novel ``ceil(b/n)`` while the ladder bounds live
    signatures to log2(max batch seen). The reference's BATCHED inference
    mode (cross-caller coalescing + admission control) lives in
    :mod:`deeplearning4j_tpu.serving`; :meth:`engine` bridges to it."""

    def __init__(self, model, mesh: Optional[Mesh] = None, workers: Optional[int] = None,
                 batchLimit: int = 0):
        self.model = model
        if mesh is None:
            devs = jax.devices()
            if workers is not None:
                devs = devs[:workers]
            mesh = make_mesh({DATA_AXIS: len(devs)}, devs)
        self.mesh = mesh
        self._n = mesh.shape[DATA_AXIS]
        self.batchLimit = batchLimit

    class Builder:
        def __init__(self, model):
            self._model = model
            self._workers = None
            self._batch_limit = 0
            self._mode = "INPLACE"

        def workers(self, n: int):
            self._workers = n
            return self

        def batchLimit(self, n: int):
            self._batch_limit = n
            return self

        def inferenceMode(self, mode: str):
            self._mode = mode  # INPLACE/SEQUENTIAL ≙ direct; BATCHED -> .engine()
            return self

        def build(self) -> "ParallelInference":
            return ParallelInference(self._model, workers=self._workers,
                                     batchLimit=self._batch_limit)

    def _bucket(self, b: int) -> int:
        """Smallest n * 2^k >= b — the compiled-signature ladder."""
        s = self._n
        while s < b:
            s *= 2
        return s

    def output(self, x) -> NDArray:
        arr = np.asarray(x)
        b = arr.shape[0]
        padded = self._bucket(b)
        if padded != b:
            arr = np.concatenate(
                [arr, np.zeros((padded - b,) + arr.shape[1:], arr.dtype)], axis=0)
        xs = jax.device_put(arr, batch_sharding(self.mesh, rank=arr.ndim))
        with jax.set_mesh(self.mesh):
            out = self.model.output(xs)
        return NDArray(out.jax[:b]) if padded != b else out

    def engine(self, **engine_kwargs):
        """The reference's BATCHED inference mode: an
        :class:`~deeplearning4j_tpu.serving.InferenceEngine` coalescing
        concurrent callers over this wrapper's model and mesh."""
        from deeplearning4j_tpu.serving import InferenceEngine

        if self.batchLimit and "max_batch_size" not in engine_kwargs:
            engine_kwargs["max_batch_size"] = self.batchLimit
        return InferenceEngine(self.model, mesh=self.mesh, **engine_kwargs)
