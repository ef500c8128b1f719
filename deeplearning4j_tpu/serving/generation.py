"""Continuous-batching generation engine: ORCA-style iteration-level
scheduling over the slot-based KV cache in ``models/bert.py``.

The request-level batching in ``serving/engine.py`` is wrong for
autoregressive decode: batching whole GENERATIONS means a 4-token reply
waits for the 400-token reply it was co-batched with (head-of-line
blocking), and every (prompt len, output len) pair is a fresh jit
signature. ORCA (Yu et al., OSDI '22) moves the scheduling decision to
the ITERATION: every loop turn the scheduler (1) admits queued prompts
into free cache slots (prefill, padded to a prompt-length bucket ladder),
(2) runs ONE ``decode_step`` for all occupied slots, (3) streams each new
token to its caller, and (4) retires EOS/max-token slots immediately so
their slots are free for the next admission — a short request enters and
leaves mid-flight of a long one. vLLM (Kwon et al., SOSP '23) showed the
cache layout is the other half of the lever: by default the cache is now
PAGED — a shared pool of fixed-size blocks addressed through per-slot
block tables (``serving/paging.py`` owns the host-side free-list
allocator with refcounts; ``models/bert.py`` the block-table gather
executables) — so a stream only consumes the blocks its actual length
touches, admission is gated on free BLOCKS rather than worst-case slots,
and a shared prefix (``submit(prefix_id=...)``) is prefilled once with
its blocks pinned and referenced by every stream that names it,
copy-on-write on the first write into a partially-filled shared block.
Either layout compiles exactly ONE decode executable plus one prefill
per bucket for the engine's whole lifetime (the block table is a
fixed-shape gather index and the CoW copy rides the decode step's
``cow_src``/``cow_dst`` arguments — no third executable).

Determinism: sampling is gumbel-max under a per-request PRNG key folded
with the token index, and every per-slot computation is row-wise — so a
stream is bitwise-identical whether it decodes alone or co-scheduled with
arbitrary neighbors (asserted by the tier-1 determinism test).

Admission control reuses :class:`AdmissionController` with slot-unit
accounting: one queued request will occupy one cache slot, so the queue
is bounded in REQUESTS (``rows=1`` each) and deadline shedding drops
prompts that waited too long before ever touching a slot.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from deeplearning4j_tpu.profiler import OpProfiler
from deeplearning4j_tpu.serving.admission import (
    AdmissionController, HostDrainingError, KVBlocksExhaustedError,
    PreemptedError, RejectedError, Request,
)
from deeplearning4j_tpu.serving.engine import bucket_ladder
from deeplearning4j_tpu.serving.faults import inject
from deeplearning4j_tpu.serving.ledger import track_engine
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.paging import (
    BlockAllocator, BlockSwapStore, PrefixCache, SharedPrefix, SwapEntry,
    blocks_for_tokens, kv_bytes_per_token,
)
from deeplearning4j_tpu.serving.qos import (
    PRIORITIES, SloBurnGovernor, SpecAcceptanceGovernor, resolve_qos,
)
from deeplearning4j_tpu.serving.resilience import (
    CircuitBreaker, ResilientEngineMixin, RetryPolicy, WatchdogTimeoutError,
)
from deeplearning4j_tpu.serving.tracing import terminal_reason

_DONE = object()
_UNSET = object()   # submit()'s "use the engine default" eos sentinel


def prefill_buckets(max_len: int, min_bucket: int = 8) -> Tuple[int, ...]:
    """Prompt-length bucket ladder: geometric like the batch ladder, but
    CLAMPED to ``max_len`` (a prefill longer than the cache cannot be
    written), so the top rung may be a non-power-of-two."""
    return tuple(sorted({min(b, max_len)
                         for b in bucket_ladder(max_len,
                                                min_bucket=min(min_bucket,
                                                               max_len))}))


@dataclasses.dataclass
class GenerationRequest:
    """One queued generation (rides ``Request.x`` through admission)."""

    prompt: np.ndarray              # (n,) int32
    max_new_tokens: int
    temperature: float
    top_k: int
    eos_id: Optional[int]
    key: np.ndarray                 # (2,) uint32 base PRNG key
    prefix_id: Optional[str] = None  # shared-prefix reference (paged only)
    handle: "GenerationHandle" = None
    # ---- preemption / recompute-on-resume (allocate="on_demand") --------
    # set when this stream was evicted to reclaim KV blocks: the tokens
    # it had generated (appended to the prompt on the recompute prefill)
    # and the index its next sample resumes at — per-request keys fold
    # the token index, so the resumed draws are position-stable and the
    # resumed stream is bitwise the unpreempted one
    resume_tokens: Optional[np.ndarray] = None
    resume_step: int = 0
    preemptions: int = 0
    # swap-to-host (paging.BlockSwapStore): the key of this stream's
    # parked KV entry when its preemption swapped out instead of
    # discarding — a valid key re-seats via device_put with NO prefill;
    # a miss (LRU-evicted, invalidated, or swap-in failure) falls back
    # to the recompute path above
    swap_key: Optional[int] = None
    # ---- cross-host KV page migration (serving/disagg.py) ---------------
    # capture_pages asks the retire tail to export this stream's written
    # KV block pages (values + int8 scales + lengths + stream state) as
    # a SwapEntry stashed on captured_entry BEFORE the terminal is
    # delivered — the disaggregation orchestrator ships it to the
    # decode-class host, which re-seats via the swap-in device_put path.
    # A failed export leaves captured_entry None: the orchestrator
    # degrades to recompute on the decode host, never sheds.
    capture_pages: bool = False
    captured_entry: Optional[SwapEntry] = None


class GenerationHandle:
    """Per-request streaming surface. ``result()`` blocks for the full
    token list; ``stream()`` yields tokens as the scheduler emits them
    (single consumer); ``future`` is the underlying admission future, so
    shedding/shutdown surface as :class:`RejectedError` here too."""

    def __init__(self, request: Request, prompt_len: int,
                 on_token: Optional[Callable[[int], None]] = None):
        self._req = request
        self.prompt_len = prompt_len
        self.finish_reason: Optional[str] = None   # 'eos' | 'max_tokens'
        self._tokens: List[int] = []
        self._lock = threading.Lock()
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._on_token = on_token
        # tokens are pushed before the future resolves, so _DONE always
        # trails the last token (and any exception) in the stream queue
        request.future.add_done_callback(lambda _f: self._q.put(_DONE))

    @property
    def future(self) -> Future:
        return self._req.future

    def tokens_so_far(self) -> List[int]:
        with self._lock:
            return list(self._tokens)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Generated token ids (prompt excluded; EOS included when hit)."""
        return self._req.future.result(timeout)

    def stream(self, timeout: Optional[float] = None):
        """Yield tokens as they are generated; raises the request's error
        (shed, shutdown, model failure) at the point it occurred."""
        while True:
            item = self._q.get(timeout=timeout)
            if item is _DONE:
                exc = self._req.future.exception()
                if exc is not None:
                    raise exc
                return
            yield item

    # ------------------------------------------------- scheduler-side hooks
    def _push(self, token: int) -> Optional[BaseException]:
        """Deliver one token. Returns the consumer callback's exception
        when a broken ``on_token`` failed this stream — the scheduler then
        retires the slot and records the outcome; the error must not reach
        the scheduler loop itself, where it would be treated as a device
        failure (co-tenants failed, cache rebuilt)."""
        with self._lock:
            self._tokens.append(token)
        self._q.put(token)
        if self._on_token is not None:
            try:
                self._on_token(token)
            except BaseException as e:
                if self._fail(e):
                    return e
        return None

    def _finish(self, reason: str) -> bool:
        self.finish_reason = reason
        try:
            self._req.future.set_result(self.tokens_so_far())
            return True
        except InvalidStateError:
            return False   # caller cancelled while queued/running

    def _fail(self, exc: BaseException) -> bool:
        """True iff this call delivered the terminal — False when the
        watchdog/a zombie/a cancel got there first. Callers use the
        return to record each request's SLO outcome exactly once."""
        try:
            self._req.future.set_exception(exc)
            return True
        except InvalidStateError:
            return False


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding engine mode (Leviathan et al., ICML'23):
    a small DRAFT model proposes ``k`` tokens per scheduler turn and the
    target model verifies all of them in ONE fixed-shape
    ``make_verify_step`` executable, committing the longest proposal
    prefix that matches the target's own deterministic samples.

    Because every token of a stream is already a pure function of
    (request key, token index), the verify step computes the TARGET's
    samples at the k+1 scored positions and acceptance only decides how
    MANY commit per turn — a speculative stream is bitwise the
    non-speculative one at any temperature (``speculative=None`` and any
    ``SpecConfig`` emit identical tokens; only throughput differs).

    ``draft_params``/``draft_cfg`` are the draft model (must share the
    target's vocab and cover the engine's ``max_len`` positions);
    ``k`` is the proposals per turn (the verify executable scores k+1
    positions). ``min_acceptance`` > 0 arms the per-tenant
    :class:`~deeplearning4j_tpu.serving.qos.SpecAcceptanceGovernor`:
    a tenant whose observed draft-acceptance rate stays below it after
    ``min_proposed`` proposals is demoted to k=0 (plain per-turn
    advancement) instead of paying verify overhead its traffic keeps
    rejecting."""

    draft_params: Any
    draft_cfg: Any
    k: int = 4
    min_acceptance: float = 0.0
    min_proposed: int = 256

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(
                f"SpecConfig.k must be >= 1 (k == 0 IS plain decode — "
                f"pass speculative=None), got {self.k}")


@dataclasses.dataclass
class _Slot:
    """Scheduler-side state of one occupied cache slot."""

    greq: GenerationRequest
    request: Request
    n_generated: int = 0
    last_token: int = 0
    # ---- paged-cache bookkeeping (None/empty on the contiguous path) ----
    length: int = 0                  # tokens whose K/V are in the cache
    blocks: Optional[List[int]] = None   # every block this stream refs
    prefix_len: int = 0              # shared-prefix tokens (block-aligned
    #                                  part lives in shared blocks)
    # prompt tokens still to feed through decode steps (prefix streams
    # skip prefill: the suffix rides the decode executable one token per
    # iteration, attending to the shared prefix's pinned blocks)
    pending: Optional[Deque[int]] = None
    # one-shot copy-on-write for the first write into a partially-filled
    # shared block: (src physical block, dst physical block)
    cow: Optional[Tuple[int, int]] = None
    # table-row entries mapped so far (shared + fresh). Under
    # allocate="reserve" this covers the worst case at seating; under
    # "on_demand" it grows one block per boundary crossing
    n_entries: int = 0
    # recompute-on-resume seating: TTFT/prefix-hit accounting already
    # happened on the first seating and must not double-count
    resumed: bool = False
    # speculative decoding: count of stream positions whose K/V the
    # DRAFT cache holds valid. The slot is draft-WARM (eligible to
    # speculate) iff draft_len == length at turn start; -1 marks
    # draft-cold (never draft-seated, draft crashed, or the stream
    # advanced through a plain turn) — cold slots still ride spec turns
    # correctly, their garbage proposals just never match
    draft_len: int = -1


class GenerationEngine(ResilientEngineMixin):
    """Iteration-level scheduler over one causal LM and one KV cache.

    ``submit(prompt)`` returns a :class:`GenerationHandle`; a background
    scheduler thread runs the admit → decode → stream → retire loop.
    ``slots`` bounds concurrent generations, ``max_len`` is the per-slot
    cache capacity (prompt + generated tokens must fit), and the compiled
    footprint over the engine's lifetime is ``len(self.buckets)`` prefill
    executables + ONE decode executable, asserted by
    :meth:`compiled_signatures`. ``tracer`` opts requests into
    request-scoped tracing (serving/tracing.py — slot assignment, prefill,
    every decode-step participation, retries, retirement);
    ``screen_outputs`` is the cheap poisoned-result guard on sampled
    tokens (NaN/inf or out-of-vocab ids fail the iteration typed).

    ``paged=True`` (the default) stores K/V in a shared block pool
    (``block_size`` tokens per block, ``num_blocks`` total — default
    matches the contiguous footprint) addressed through per-slot block
    tables: admission is gated on free BLOCKS (typed
    'kv_blocks_exhausted' shed when a request can never fit), each
    stream reserves only ``ceil((len + max_new)/block_size)`` blocks
    instead of ``max_len`` rows, and :meth:`register_prefix` /
    ``submit(prefix_id=...)`` share one prefilled prefix across any
    number of streams with copy-on-write. ``paged=False`` keeps the PR 2
    contiguous layout (the bitwise-parity reference).

    ``kv_dtype`` selects the pool's storage: ``"float32"`` (default —
    full precision in the cache dtype, the bitwise pre-int8 behavior) or
    ``"int8"`` (quantize-on-write / dequant-on-read with per-token
    scales; ~4x smaller KV stream at bf16-free shapes, so >=2x resident
    streams at a fixed HBM budget — paged only). ``paged_attention``
    selects the decode attention read: ``"gather"`` (default; XLA
    materializes the block gather — bitwise-stable vs PR 6) or
    ``"fused"`` (the Pallas paged-attention kernel streams blocks
    through VMEM, never materializing the (slots, L) view in HBM;
    fp-tolerance-equivalent, the decode-speed route on TPU). Both knobs
    keep the ONE-donated-executable signature bound.

    ``qos`` (serving/qos.py ``QosPolicy``) swaps admission's FIFO for
    priority-strict weighted-fair queueing (cost = 1 request) with
    per-tenant quotas + SLO-burn shedding; ``retry_budget``
    (resilience.RetryBudget) bounds retry-storm amplification. Both
    default to off — the bitwise-identical pre-QoS path.

    ``allocate`` selects the block allocator's discipline (paged only):

    - ``"reserve"`` (default): a stream's whole worst-case
      ``ceil((len + max_new)/block_size)`` footprint is taken at seating
      — the pre-existing behavior, bitwise-inert, zero mid-stream
      surprises, but every unwritten generation tail sits idle in the
      pool (the ``kv_reservation_slack`` gauge).
    - ``"on_demand"`` (vLLM SOSP'23 §4.5): seating takes only the
      PROMPT's blocks; the decode loop allocates one block per
      block-boundary crossing, and when the pool is dry it preempts the
      lowest-QoS-class resident streams (largest footprint, latest
      arrival first; ``TenantPolicy.preemptible=False`` exempts a
      tenant) and requeues them for recompute-on-resume through the
      prefill path — the resumed stream is bitwise the unpreempted one
      (per-request keys fold the token index). ``kv_blocks_exhausted``
      becomes a mid-stream condition too; a victim that can no longer
      ever be resumed sheds typed ``'preempted'``.

    ``speculative`` (a :class:`SpecConfig`; paged only) turns each
    scheduler turn into draft×k + ONE k+1-position verify: the draft
    model proposes, the target commits the prefix matching its own
    deterministic samples, and per-slot lengths advance by the accepted
    count — bitwise identical streams at any k and temperature, faster
    exactly when drafts are accepted. The draft has its own breaker:
    draft faults DEGRADE the turn to plain decode (never shed, never
    stall), and ``min_acceptance`` > 0 demotes low-acceptance tenants to
    k=0 via the qos acceptance governor. Executable bound grows to
    ``len(self.buckets) + 2`` target-side plus ``len(self.buckets) + 1``
    draft-side. Default None — the exact plain path.

    ``prefix_cache_blocks`` > 0 (paged only) enables the AUTOMATIC
    prefix cache (SGLang RadixAttention's policy): retired streams'
    full blocks are retained in a bounded LRU (at most this many
    blocks) and a later prompt sharing a block-aligned token prefix
    references them directly — shared system prompts hit with no API
    opt-in (``register_prefix`` remains the pinned, never-evicted
    route). Cached blocks are reclaimed on demand, so they never gate
    admission. Default 0 — off, bitwise-inert.
    """

    _COMPONENT = "serving.GenerationEngine"
    _FAILURE_NOUN = "prefill/decode"

    def __init__(self, params, cfg, *, mesh=None, slots: int = 8,
                 max_len: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 cache_dtype: Any = None,
                 paged: bool = True,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 kv_dtype: str = "float32",
                 paged_attention: str = "gather",
                 allocate: str = "reserve",
                 prefix_cache_blocks: int = 0,
                 swap_threshold_blocks: Optional[int] = None,
                 swap_capacity_blocks: Optional[int] = None,
                 queue_capacity: int = 64,
                 default_timeout_ms: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 profiler: Optional[OpProfiler] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 retry_budget=None, qos=None,
                 speculative: Optional[SpecConfig] = None,
                 watchdog_timeout_ms: Optional[float] = None,
                 tracer=None, recorder=None, screen_outputs: bool = True,
                 name: str = "generation"):
        from deeplearning4j_tpu.models.bert import (
            grow_block_table, init_kv_cache, make_decode_step,
            make_paged_decode_step, make_paged_prefill, make_prefill,
            place_kv_cache, place_params)

        self._grow_table = grow_block_table

        if not cfg.causal:
            raise ValueError(
                "GenerationEngine needs a causal LM: TransformerConfig("
                "causal=True) — a bidirectional encoder has no decode order")
        if slots <= 0:
            raise ValueError("slots must be positive")
        self.cfg = cfg
        self.mesh = mesh
        self.slots = slots
        self.max_len = max_len if max_len is not None else cfg.max_seq
        self.buckets = (tuple(sorted(set(int(b) for b in buckets)))
                        if buckets else prefill_buckets(self.max_len))
        if self.buckets[-1] > self.max_len:
            raise ValueError(f"prefill buckets {self.buckets} exceed "
                             f"max_len {self.max_len}")
        self.eos_id = eos_id
        self.name = name
        self.metrics = metrics or ServingMetrics()
        self.profiler = profiler or OpProfiler.getInstance()
        # passes of the scheduler loop since start: the ``step`` every
        # phase span of one pass carries, so its phases can be joined
        self._iteration = 0
        if mesh is not None:
            params = place_params(params, cfg, mesh)
        self.params = params
        self.paged = paged
        if paged:
            from deeplearning4j_tpu.models.bert import (
                validate_block_size, validate_kv_dtype)

            if block_size is None:
                # default: 16-token blocks, degrading to the largest
                # power of two that fits a tiny max_len
                block_size = 16
                while block_size > self.max_len:
                    block_size //= 2
            self.block_size = validate_block_size(block_size, self.max_len)
            self.kv_dtype = validate_kv_dtype(kv_dtype, self.block_size)
            self.paged_attention = paged_attention
            if allocate not in ("reserve", "on_demand"):
                raise ValueError(
                    f"allocate must be 'reserve' or 'on_demand', got "
                    f"{allocate!r}")
            if prefix_cache_blocks < 0:
                raise ValueError(
                    f"prefix_cache_blocks must be >= 0, got "
                    f"{prefix_cache_blocks}")
            self.allocate = allocate
            self.prefix_cache_blocks = int(prefix_cache_blocks)
            self.max_blocks_per_slot = blocks_for_tokens(self.max_len,
                                                         self.block_size)
            self.num_blocks = (slots * self.max_blocks_per_slot + 1
                               if num_blocks is None else int(num_blocks))
            if swap_threshold_blocks is not None \
                    and swap_threshold_blocks < 0:
                raise ValueError(
                    f"swap_threshold_blocks must be >= 0 (a victim whose "
                    f"footprint EXCEEDS it swaps to host RAM), got "
                    f"{swap_threshold_blocks}")
            if swap_capacity_blocks is not None \
                    and swap_threshold_blocks is None:
                raise ValueError(
                    "swap_capacity_blocks requires swap_threshold_blocks "
                    "— the store only fills from preemption swap-outs")
            self.swap_threshold_blocks = swap_threshold_blocks
            # bounded host-RAM parking lot for preempted streams' KV
            # (vLLM §4.5 swap-vs-recompute): default None keeps the
            # recompute-only PR 13 behavior, bitwise-inert
            self._swap_store = BlockSwapStore(
                int(swap_capacity_blocks) if swap_capacity_blocks
                is not None else self.num_blocks) \
                if swap_threshold_blocks is not None else None
            self._prefill = make_paged_prefill(cfg, self.block_size, mesh,
                                               kv_dtype=self.kv_dtype)
            self._decode = make_paged_decode_step(
                cfg, self.block_size, mesh, kv_dtype=self.kv_dtype,
                paged_attention=paged_attention)
        else:
            from deeplearning4j_tpu.models.bert import validate_kv_dtype

            # int8 storage is a block-pool concept (per-block scale
            # tensors, dequant in the block read): validate against the
            # contiguous layout's absent block size so the error names it
            validate_kv_dtype(kv_dtype, None)
            if allocate != "reserve":
                raise ValueError(
                    f"allocate={allocate!r} requires the paged KV cache "
                    "(GenerationEngine(paged=True)) — the contiguous "
                    "layout reserves whole rows, there is nothing to "
                    "allocate on demand")
            if prefix_cache_blocks:
                raise ValueError(
                    "prefix_cache_blocks requires the paged KV cache "
                    "(GenerationEngine(paged=True)) — the automatic "
                    "prefix cache holds retired streams' blocks")
            if swap_threshold_blocks is not None \
                    or swap_capacity_blocks is not None:
                raise ValueError(
                    "swap_threshold_blocks requires the paged KV cache "
                    "(GenerationEngine(paged=True)) — swap-to-host parks "
                    "block K/V, and the contiguous layout has no blocks")
            self.allocate = "reserve"
            self.prefix_cache_blocks = 0
            self.swap_threshold_blocks = None
            self._swap_store = None
            if paged_attention != "gather":
                raise ValueError(
                    f"paged_attention={paged_attention!r} requires the "
                    "paged KV cache (GenerationEngine(paged=True)) — the "
                    "contiguous layout has no block table to fuse over")
            self.kv_dtype = kv_dtype
            self.paged_attention = "gather"
            self.block_size = None
            self.num_blocks = None
            self._prefill = make_prefill(cfg, mesh)
            self._decode = make_decode_step(cfg, mesh)
        self._cache_dtype = cache_dtype
        self._place_kv_cache = place_kv_cache
        self._init_kv_cache = init_kv_cache
        # shared-prefix registry (paged only): id -> SharedPrefix, plus a
        # scheduler-drained prefill queue — prefix prefills must run on
        # the scheduler thread because they donate the same cache the
        # decode loop donates
        self._prefixes: Dict[str, SharedPrefix] = {}
        self._prefix_lock = threading.Lock()
        self._pending_prefix: Deque[Tuple[str, Optional[Future]]] = deque()
        self._prefix_ids = itertools.count()
        self._prefix_busy = False
        self._allocator: Optional[BlockAllocator] = None
        self._tables: Optional[np.ndarray] = None
        # automatic prefix cache (paging.PrefixCache; scheduler-thread
        # single-writer) — rebuilt with the pool in _reset_cache.
        # _cache_bypass suspends MATCHING (warmup: a rung probe hitting
        # an earlier rung's retired blocks would ride the feed path and
        # skip its prefill compile — live traffic would then pay XLA
        # inline, the exact thing warmup exists to prevent)
        self._prefix_cache: Optional[PrefixCache] = None
        self._cache_bypass = False
        # block-wait reservation (scheduler thread only): the dequeued
        # request currently waiting for KV blocks, as (request, demand,
        # priority). Under FIFO nothing can overtake a requeued head, so
        # freed blocks always accumulated toward it; under a QosPolicy
        # same-class arrivals DO overtake (weighted fairness), and
        # without this reservation their trickle could consume every
        # freed block and starve a feasible waiter forever (the
        # stream-side analogue of PR 6's _pending_prefix_demand). The
        # reservation binds same-or-lower classes only — see _plan_blocks
        self._block_waiter: Optional[Tuple[Request, int, str]] = None
        # speculative decoding (SpecConfig): draft executables + THE
        # verify step, a draft-only breaker (degrade-to-plain, never
        # shed), and the per-tenant acceptance governor. speculative=None
        # keeps the exact plain path — bitwise-inert by construction
        # (verify commits only the target's own samples), guarded by the
        # parity suite
        self._spec = speculative
        self._spec_force_plain = False   # warmup: compile the fallback
        if speculative is not None:
            from deeplearning4j_tpu.models.bert import (
                init_draft_kv_cache, make_draft_prefill, make_draft_step,
                make_verify_step, place_draft_kv_cache)

            if not self.paged:
                raise ValueError(
                    "speculative decoding requires the paged KV cache "
                    "(GenerationEngine(paged=True)) — the verify step is "
                    "a paged executable")
            dcfg = speculative.draft_cfg
            if not dcfg.causal:
                raise ValueError(
                    "the draft model must be causal: TransformerConfig("
                    "causal=True)")
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} — proposals are fed to the target "
                    "as token ids, the vocabularies must be shared")
            if dcfg.max_seq < self.max_len:
                raise ValueError(
                    f"draft max_seq {dcfg.max_seq} < engine max_len "
                    f"{self.max_len} — the draft must cover every prompt "
                    "bucket position")
            dparams = speculative.draft_params
            if mesh is not None:
                from deeplearning4j_tpu.models.bert import place_params
                dparams = place_params(dparams, dcfg, mesh)
            self._draft_params = dparams
            self._draft_cfg = dcfg
            # the draft writes K/V up to position length + k - 1; clamp
            # to its positional table (near-the-end proposals degrade to
            # garbage → acceptance 0, never wrong tokens)
            self._draft_max_len = min(self.max_len + speculative.k,
                                      dcfg.max_seq)
            self._init_draft_cache = init_draft_kv_cache
            self._place_draft_cache = place_draft_kv_cache
            self._draft_prefill = make_draft_prefill(dcfg, mesh)
            self._draft_step = make_draft_step(dcfg, mesh)
            self._verify = make_verify_step(
                cfg, self.block_size, speculative.k, mesh,
                kv_dtype=self.kv_dtype, paged_attention=paged_attention)
            self._draft_breaker = CircuitBreaker(name=f"{name}.draft")
            self._spec_governor = SpecAcceptanceGovernor(
                speculative.min_acceptance, speculative.min_proposed)
        self._slots: List[Optional[_Slot]] = [None] * slots
        self._reset_cache()
        # multi-tenant QoS (serving/qos.py): policy -> weighted-fair
        # multi-queue + quotas + SLO-burn governor; None keeps the exact
        # FIFO path (bitwise-identical, guarded by test)
        self.qos = qos
        self._qos_governor = SloBurnGovernor(qos, self.metrics) \
            if qos is not None else None
        # slot-unit admission: one request == one future slot (rows=1)
        self._admission = AdmissionController(
            capacity_rows=queue_capacity,
            default_timeout_ms=default_timeout_ms, unit="requests",
            policy=qos)
        self._admission.on_shed = self._count_shed
        self._admission.on_close_reject = self._count_close_reject
        self._admission.on_cancelled = self._count_cancelled
        self._draining = False
        self._stop = threading.Event()
        self.screen_outputs = screen_outputs
        # resilience + observability scaffolding is the shared mixin
        # (serving/resilience.py). Note the retry-safety property is
        # generation-specific: injected/tagged-transient prefill and
        # decode failures raise BEFORE the donated call executes, so
        # retrying them re-uses the intact cache; everything else still
        # takes the fail-tenants + rebuild path from PR 2.
        self._init_resilience(retry_policy=retry_policy, breaker=breaker,
                              retry_budget=retry_budget,
                              tracer=tracer, recorder=recorder)
        self._inflight_prefill: Optional[Request] = None
        self._thread = threading.Thread(
            target=self._loop, args=(0,),
            name=f"generation-scheduler[{self.name}]", daemon=True)
        self._thread.start()
        if watchdog_timeout_ms is not None:
            self.arm_watchdog(watchdog_timeout_ms)
        track_engine(self)   # weak: the zero-leak ledger's registry

    # ------------------------------------------------------------ lifecycle
    def __enter__(self) -> "GenerationEngine":
        return self

    def __exit__(self, *exc):
        self.shutdown()

    def shutdown(self, wait: bool = True):
        """Idempotent: stop the scheduler; queued AND in-flight requests
        are rejected ('shutdown') — partial streams surface what they have
        via :meth:`GenerationHandle.tokens_so_far`."""
        self._shutdown_resilience()   # watchdog off, breaker detached
        self._stop.set()
        self._admission.close()
        with self._prefix_lock:
            pending, self._pending_prefix = list(self._pending_prefix), deque()
        for _pid, fut in pending:   # waiting register_prefix() callers
            if fut is None:
                continue
            try:
                # analysis: ok terminal-exactly-once — prefix rendezvous
                # future (register_prefix blocks on it), not a request
                # terminal: no SLO/trace/tenant accounting applies
                fut.set_exception(RejectedError(
                    "engine shut down before the prefix was prefilled",
                    "shutdown"))
            except InvalidStateError:
                pass
        self._recorder.record("engine.shutdown", engine=self.name)
        if wait and self._thread.is_alive():
            self._thread.join(timeout=30.0)

    # ----------------------------------------------------------------- drain
    def drain(self, timeout: Optional[float] = None,
              release_prefixes: bool = True) -> bool:
        """Graceful drain (the host-leave protocol's engine half): stop
        admitting — new submits shed typed ``host_draining`` — finish
        every queued and RESIDENT stream (the scheduler keeps running:
        queued prompts still seat and decode to completion; the shared
        mixin ``_drain_wait``), then release every shared-prefix pin so
        the pool's blocks return to the free list. Returns True when
        fully drained within ``timeout`` (None = wait forever); on
        timeout the engine stays draining (admission stays closed) but
        explicit pins are kept — the caller decides whether to force
        ``shutdown()``. The AUTOMATIC prefix cache is released on BOTH
        exits: admission is closed, so no future stream can match it —
        a timed-out drain that parked those reclaimable blocks until
        shutdown would advertise less free capacity than the host
        actually has (any in-flight match holds its own refs, so the
        release is safe against still-resident streams)."""
        ok = self._drain_wait(timeout)
        if release_prefixes and self._prefix_cache is not None:
            before = len(self._prefix_cache)
            self._prefix_cache.release_all()
            if before:
                self.metrics.prefix_cache_evictions_total.inc(before)
                self._update_block_gauges()
        if not ok:
            return False
        if release_prefixes:
            with self._prefix_lock:
                pids = list(self._prefixes)
            for pid in pids:
                self.release_prefix(pid)
        return True

    # --------------------------------------------------------------- submit
    def submit(self, prompt, *, max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0,
               eos_id: Any = _UNSET, seed: int = 0,
               timeout_ms: Optional[float] = None,
               prefix_id: Optional[str] = None,
               tenant: Optional[str] = None,
               priority: Optional[str] = None,
               on_token: Optional[Callable[[int], None]] = None,
               resume_tokens=None, resume_step: int = 0,
               capture_pages: bool = False,
               swap_key: Optional[int] = None,
               trace_link: Optional[str] = None,
               trace_parent: Optional[str] = None) -> GenerationHandle:
        """Queue one prompt. Greedy by default; ``temperature`` > 0 samples,
        ``top_k`` > 0 restricts sampling to the k highest-probability
        tokens, ``seed`` fixes the stream's
        PRNG key (a fixed seed gives a bitwise-reproducible stream
        regardless of co-scheduling). ``eos_id`` defaults to the engine's;
        pass ``eos_id=None`` to disable EOS retirement for this request.
        ``timeout_ms`` bounds QUEUE time: prompts shed on deadline never
        occupy a slot. ``prefix_id`` (paged cache only) names a prefix
        previously registered with :meth:`register_prefix`: the stream's
        logical sequence is ``prefix + prompt``, the prefix's pinned
        blocks are REFERENCED (not recomputed — its prefill happened
        once), and only the prompt suffix is fed through the decode
        executable, so thousands of concurrent streams share one
        prefill. ``tenant`` / ``priority`` attribute the request for QoS
        (serving/qos.py) — without a ``qos=`` policy they are accounting
        labels only and the queue stays FIFO.

        ``resume_tokens``/``resume_step`` seat this stream at a RESUME
        point instead of token 0 — the cross-host half of PR 13's
        recompute-on-resume (serving/rpc.py forwards them off the wire
        when a front door re-dispatches a lost stream): the already-
        delivered tokens ride the prompt through ONE recompute prefill
        and the next sample is drawn at index ``resume_step``, so the
        recovered stream is bitwise the uninterrupted one and re-decodes
        nothing it already delivered. ``resume_step`` must equal
        ``len(resume_tokens)`` — the resume point IS the delivery
        watermark.

        ``capture_pages`` (paged only) marks this stream for KV page
        export at retirement: its written block pages are stashed as a
        :class:`SwapEntry` retrievable via :meth:`take_captured_pages`
        — the prefill half of cross-host disaggregation
        (serving/disagg.py runs such a stream with
        ``max_new_tokens=1``). ``swap_key`` names an entry previously
        seated by :meth:`import_pages`: admission re-seats the stream
        from those pages with NO prefill, falling back to the ordinary
        resume recompute on any miss — the decode half of the same
        migration (requires ``resume_tokens``, the degrade path's
        delivery watermark).

        ``trace_link``/``trace_parent`` attach this stream's trace to a
        cross-host parent (the wire-v3 trace context serving/rpc.py
        forwards): the engine's RequestTrace stays a full local timeline
        but records which front-door trace it is a child leg of, so the
        cluster aggregator can stitch the legs. Default None — a local
        root, bitwise the pre-v3 behavior."""
        tenant, priority = resolve_qos(self.qos, tenant, priority)
        toks = np.ascontiguousarray(np.asarray(prompt, np.int32).ravel())
        if toks.size == 0:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens <= 0:
            raise ValueError("max_new_tokens must be positive")
        if resume_tokens is not None:
            resume_tokens = np.ascontiguousarray(
                np.asarray(resume_tokens, np.int32).ravel())
            if int(resume_step) != int(resume_tokens.size):
                raise ValueError(
                    f"resume_step ({resume_step}) must equal "
                    f"len(resume_tokens) ({resume_tokens.size}) — the "
                    "resume point is the delivery watermark")
            if resume_step >= max_new_tokens:
                raise ValueError(
                    f"resume_step ({resume_step}) must be < "
                    f"max_new_tokens ({max_new_tokens}) — a finished "
                    "stream has nothing to resume")
        elif resume_step:
            raise ValueError(
                f"resume_step ({resume_step}) requires resume_tokens — "
                "the delivered prefix the recompute prefill replays")
        if capture_pages and not self.paged:
            raise ValueError(
                "capture_pages requires the paged KV cache "
                "(GenerationEngine(paged=True)) — page export gathers "
                "block rows, and the contiguous layout has no blocks")
        if swap_key is not None and resume_tokens is None:
            raise ValueError(
                "swap_key requires resume_tokens — an imported stream "
                "needs its delivery watermark so a swap-in miss can "
                "degrade to the recompute path without re-decoding "
                "delivered tokens")
        prefix_len = 0
        if prefix_id is not None:
            if not self.paged:
                raise ValueError(
                    "prefix_id requires the paged KV cache "
                    "(GenerationEngine(paged=True))")
            with self._prefix_lock:
                prefix = self._prefixes.get(prefix_id)
            if prefix is None:
                raise KeyError(
                    f"prefix_id {prefix_id!r} is not registered — call "
                    "register_prefix() first")
            prefix_len = prefix.length
        total = prefix_len + toks.size + max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"prefix ({prefix_len}) + prompt ({toks.size}) + "
                f"max_new_tokens ({max_new_tokens}) exceeds the cache "
                f"capacity max_len={self.max_len}")
        if prefix_id is None and toks.size > self.buckets[-1]:
            # prefix streams skip prefill entirely (the suffix rides the
            # decode executable), so the bucket ladder does not bound them
            raise ValueError(
                f"prompt ({toks.size}) exceeds the top prefill bucket "
                f"{self.buckets[-1]} — extend `buckets` up to max_len")
        greq = GenerationRequest(
            prompt=toks, max_new_tokens=max_new_tokens,
            temperature=float(temperature), top_k=int(top_k),
            eos_id=self.eos_id if eos_id is _UNSET else eos_id,
            key=np.asarray(jax.random.PRNGKey(seed)), prefix_id=prefix_id,
            resume_tokens=resume_tokens, resume_step=int(resume_step),
            capture_pages=bool(capture_pages), swap_key=swap_key)
        trace = self._tracer.begin(self.name, "generate",
                                   link=trace_link,
                                   parent_span=trace_parent,
                                   prompt_len=int(toks.size),
                                   max_new_tokens=max_new_tokens,
                                   tenant=tenant)
        if resume_tokens is not None:
            # a wire-resume landed here instead of a full replay: count
            # it and mark the trace — the kill-mid-stream acceptance
            # test asserts exactly one of these per recovery
            self.metrics.stream_resumes_total.inc()
            trace.event("stream.resume", resume_step=int(resume_step))
        req = Request(x=greq, rows=1, trace=trace, tenant=tenant,
                      priority=priority)
        greq.handle = GenerationHandle(req, toks.size, on_token=on_token)
        self._count_request()
        if self._draining:
            # drain outranks every other gate: the host is leaving and
            # the router should place this stream elsewhere
            e = HostDrainingError(
                f"engine[{self.name}] is draining — admission closed "
                "ahead of a graceful leave; route to another host")
            self._reject_submit(trace, e, tenant=tenant)
            raise e
        self._breaker_gate(trace, tenant=tenant)
        if self._qos_governor is not None:
            e = self._qos_governor.gate(priority)
            if e is not None:
                self._reject_submit(trace, e, tenant=tenant)
                raise e
        if self.paged:
            # structural shed: a reservation the pool can never satisfy
            # (capacity minus prefix pins) fails typed NOW, not after a
            # queue wait that cannot end any other way
            needed = self._fresh_blocks_needed(prefix_len, int(toks.size),
                                               max_new_tokens)
            usable = self._usable_blocks()
            if needed > usable:
                e = KVBlocksExhaustedError(
                    f"request needs {needed} KV blocks but the pool can "
                    f"free at most {usable} of {self._allocator.capacity} "
                    f"(block_size={self.block_size}; shared-prefix pins "
                    f"excluded) — shrink the request or grow num_blocks",
                    needed=needed, usable=usable,
                    capacity=self._allocator.capacity)
                self._reject_submit(trace, e, tenant=tenant)
                raise e
        try:
            self._admission.admit(req, timeout_ms=timeout_ms)
        except RejectedError as e:
            self._reject_submit(trace, e, tenant=tenant)
            raise
        self.metrics.queue_depth.set(self._admission.depth_requests)
        return greq.handle

    def generate(self, prompt, timeout: Optional[float] = None,
                 **kwargs) -> List[int]:
        """Blocking submit: the full generated-token list."""
        return self.submit(prompt, **kwargs).result(timeout=timeout)

    # ------------------------------------------------------ shared prefixes
    def register_prefix(self, tokens, prefix_id: Optional[str] = None,
                        timeout: Optional[float] = 300.0) -> str:
        """Prefill a shared prefix ONCE and pin its blocks; returns the
        id to pass as ``submit(prefix_id=...)``. The prefill runs on the
        scheduler thread (it donates the same cache the decode loop
        donates) — this call blocks until the prefix is resident. After a
        cache rebuild (device failure / watchdog restart) the pinned K/V
        is gone; the registration survives and the next stream naming it
        triggers a lazy re-prefill from the retained tokens."""
        if not self.paged:
            raise ValueError("register_prefix requires the paged KV cache "
                             "(GenerationEngine(paged=True))")
        if self._draining:
            raise HostDrainingError(
                f"engine[{self.name}] is draining — it releases its "
                "prefix pins and takes no new ones; register elsewhere")
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32).ravel())
        if toks.size == 0:
            raise ValueError("prefix must contain at least one token")
        if toks.size > self.buckets[-1]:
            raise ValueError(
                f"prefix ({toks.size}) exceeds the top prefill bucket "
                f"{self.buckets[-1]} — extend `buckets` up to max_len")
        if toks.size >= self.max_len:
            raise ValueError(
                f"prefix ({toks.size}) leaves no room to generate within "
                f"max_len={self.max_len}")
        nb = blocks_for_tokens(int(toks.size), self.block_size)
        fut: Future = Future()
        with self._prefix_lock:
            if self._stop.is_set():
                raise RejectedError("engine is shut down", "shutdown")
            # capacity gate under the lock, counting BOTH prefilled pins
            # and not-yet-prefilled registrations' worst cases — two
            # concurrent registrations must not both pass and over-commit
            # the pool (the loser would wedge the prefill queue forever)
            reserved = sum(
                len(p.blocks) if p.blocks
                else blocks_for_tokens(p.length, self.block_size)
                for p in self._prefixes.values())
            usable = self._allocator.capacity - reserved
            if nb > usable:
                raise KVBlocksExhaustedError(
                    f"prefix needs {nb} KV blocks but only {usable} of "
                    f"{self._allocator.capacity} can ever be pinned "
                    "(other prefixes hold the rest)",
                    needed=nb, usable=usable,
                    capacity=self._allocator.capacity)
            if prefix_id is None:
                prefix_id = f"prefix-{next(self._prefix_ids)}"
            if prefix_id in self._prefixes:
                raise ValueError(
                    f"prefix_id {prefix_id!r} is already registered")
            self._prefixes[prefix_id] = SharedPrefix(prefix_id, toks)
            self._pending_prefix.append((prefix_id, fut))
        try:
            fut.result(timeout)
        except BaseException:
            # timeout / prefill failure / shutdown: withdraw the
            # registration so its worst-case reservation doesn't shrink
            # the pool (and gate stream admission) forever. A prefill
            # already in flight copes: on finding the id unregistered it
            # frees its blocks instead of publishing them.
            with self._prefix_lock:
                p = self._prefixes.get(prefix_id)
                if p is not None and not p.ready:
                    del self._prefixes[prefix_id]
                    self._pending_prefix = deque(
                        (pid, f) for pid, f in self._pending_prefix
                        if pid != prefix_id)
            raise
        return prefix_id

    def release_prefix(self, prefix_id: str) -> bool:
        """Drop a shared prefix's pin. Its blocks return to the free list
        once the last live stream referencing them retires; queued streams
        naming the id will fail at admission. Returns False for an
        unknown id (already released)."""
        with self._prefix_lock:
            prefix = self._prefixes.pop(prefix_id, None)
            if prefix is None:
                return False
            blocks, prefix.blocks = prefix.blocks, None
            if blocks:
                # under _prefix_lock so a concurrent cache rebuild (which
                # clears prefix.blocks and replaces the allocator, also
                # under this lock) cannot interleave a double free
                self._allocator.free(blocks)
        self._recorder.record("prefix.release", engine=self.name,
                              prefix_id=prefix_id)
        return True

    def _usable_blocks(self, excluding: Optional[str] = None) -> int:
        """Blocks a request could EVER get: pool capacity minus
        shared-prefix pins (live streams' blocks come back at retire;
        pins do not). A REGISTERED prefix that has not prefilled yet
        (queued, or awaiting lazy re-prefill after a rebuild) reserves
        its worst case too — otherwise two concurrent registrations
        could both pass the gate and over-commit the pool.
        ``excluding`` names a prefix whose own reservation should not
        count against itself (the drain's can-this-ever-fit check)."""
        with self._prefix_lock:
            pinned = sum(
                len(p.blocks) if p.blocks
                else blocks_for_tokens(p.length, self.block_size)
                for pid, p in self._prefixes.items() if pid != excluding)
        return self._allocator.capacity - pinned

    # ------------------------------------------------------------ scheduler
    def _live_count(self) -> int:
        return sum(s is not None for s in self._slots)

    def _reset_cache(self):
        """(Re)allocate the KV cache. Called at construction AND after any
        prefill/decode failure: both jitted calls DONATE the cache, so an
        exception raised after dispatch leaves ``self._cache`` bound to
        deleted buffers — without a rebuild every later call would die with
        'Array has been deleted' while submit() kept accepting work.

        On the paged path the block pool, allocator and block tables are
        rebuilt together (one consistent empty state — a fresh allocator
        also makes any straggling zombie free a harmless no-op against a
        dead object), and every registered prefix is invalidated: its K/V
        died with the pool, so ``blocks`` drops to None and the next
        stream naming it re-prefills lazily from the retained tokens."""
        cache = self._init_kv_cache(self.cfg, self.slots, self.max_len,
                                    dtype=self._cache_dtype,
                                    block_size=self.block_size,
                                    num_blocks=self.num_blocks,
                                    kv_dtype=self.kv_dtype)
        self._cache = self._place_kv_cache(cache, self.cfg, self.mesh) \
            if self.mesh is not None else cache
        if self.paged:
            self._block_waiter = None   # demand was against the old pool
            if self._prefix_cache is not None:
                # the old pool's K/V died with its allocator: the cached
                # references are void and must NOT be freed into the
                # fresh allocator (the PR 6 _clear_slot discipline,
                # extended to cache entries)
                self._prefix_cache.invalidate()
            if self._swap_store is not None:
                # swapped-out entries carry the epoch they were captured
                # under and would be rejected at swap-in anyway; dropping
                # them here returns the host RAM immediately
                self._swap_store.invalidate()
                self.metrics.kv_swapped_blocks_held.set(0)
            with self._prefix_lock:
                self._allocator = BlockAllocator(self.num_blocks, reserved=1)
                self._tables = np.zeros(
                    (self.slots, self.max_blocks_per_slot), np.int32)
                for p in self._prefixes.values():
                    p.blocks = None
            self._prefix_cache = (
                PrefixCache(self._allocator, self.block_size,
                            self.prefix_cache_blocks)
                if self.prefix_cache_blocks else None)
            self.metrics.kv_blocks_total.set(self._allocator.capacity)
            self.metrics.kv_block_bytes.set(self.kv_block_bytes)
            self.metrics.kv_pool_hbm_bytes.set(
                self.num_blocks * self.kv_block_bytes)
            self._update_block_gauges()
        if self._spec is not None:
            # the draft cache rides the same rebuild: its contents only
            # described the (now failed) tenants, and a fresh empty cache
            # is one consistent state for the replacement scheduler
            self._reset_draft_cache()

    def _reset_draft_cache(self):
        """(Re)allocate the speculative DRAFT model's contiguous KV cache
        — called at construction, with every target-cache rebuild, and
        after any draft-leg failure (draft calls donate this cache too).
        Existing slots become draft-cold; the caller marks them."""
        cache = self._init_draft_cache(self._draft_cfg, self.slots,
                                       self._draft_max_len)
        self._draft_cache = self._place_draft_cache(
            cache, self._draft_cfg, self.mesh) \
            if self.mesh is not None else cache

    @property
    def kv_block_bytes(self) -> int:
        """HBM bytes of one KV block across all layers — dtype-aware
        (paging.kv_bytes_per_token): int8 pools count their 1-byte values
        plus fp32 scales, fp/bf pools the cache dtype's width. Paged
        engines only — a contiguous cache has rows, not blocks."""
        import jax.numpy as jnp

        if not self.paged:
            raise ValueError(
                "kv_block_bytes is a paged-layout property (this engine "
                "runs the contiguous cache: paged=False); a contiguous "
                "stream's footprint is max_len * "
                "paging.kv_bytes_per_token(...)")
        itemsize = jnp.dtype(self._cache_dtype if self._cache_dtype
                             is not None else self.cfg.dtype).itemsize
        return self.block_size * kv_bytes_per_token(
            self.cfg.layers, self.cfg.heads, self.cfg.head_dim,
            self.kv_dtype, itemsize)

    def _update_block_gauges(self):
        """Block-pool occupancy / pin / fragmentation gauges (paged only).
        Occupancy counts RESERVED blocks (the admission view — worst-case
        reservations included). Fragmentation is the share of TOUCHED
        block capacity holding no token — the tail waste of
        partially-filled blocks, bounded by (block_size-1)/block_size per
        stream — NOT the unwritten generation headroom, which is
        reservation slack, not block-granularity waste (shared prefix
        tokens counted once, via each stream's block-aligned shared
        span)."""
        alloc = self._allocator
        if alloc is None:
            return
        in_use = alloc.in_use
        B = self.block_size
        with self._prefix_lock:
            pinned = sum(len(p.blocks) for p in self._prefixes.values()
                         if p.blocks)
            prefix_tokens = sum(p.length for p in self._prefixes.values()
                                if p.blocks)
            touched = sum(blocks_for_tokens(p.length, B)
                          for p in self._prefixes.values() if p.blocks)
        tokens = prefix_tokens
        slack = 0
        for st in list(self._slots):
            if st is not None:
                aligned_shared = (st.prefix_len // B) * B
                local = max(0, st.length - aligned_shared)
                tokens += local
                touched += blocks_for_tokens(local, B)
                # reserved-but-unwritten blocks: row entries past the
                # stream's written positions — the worst-case generation
                # tail allocate="reserve" holds idle (on_demand keeps at
                # most ~1 slack block per stream, the next write target)
                slack += max(0, (st.n_entries - st.prefix_len // B)
                             - blocks_for_tokens(local, B))
        self.metrics.kv_blocks_in_use.set(in_use)
        self.metrics.kv_blocks_pinned.set(pinned)
        self.metrics.kv_hbm_bytes_in_use.set(in_use * self.kv_block_bytes)
        cap = alloc.capacity
        self.metrics.kv_block_occupancy.set(in_use / cap if cap else 0.0)
        self.metrics.kv_fragmentation.set(
            max(0.0, 1.0 - tokens / (touched * B)) if touched else 0.0)
        self.metrics.kv_reservation_slack.set(slack)
        self.metrics.prefix_cache_blocks.set(
            self._prefix_cache.total_blocks
            if self._prefix_cache is not None else 0)
        self.metrics.kv_swapped_blocks_held.set(
            self._swap_store.blocks_held
            if self._swap_store is not None else 0)

    def _loop(self, epoch: int):
        """Scheduler loop for one epoch. The watchdog bumps ``_epoch`` on
        restart: this (possibly wedged) thread then exits at its next
        check, and any state it computes afterwards is dropped by the
        epoch guards instead of corrupting its replacement's cache."""
        # decode-step staging buffers are allocated ONCE per scheduler
        # thread and refilled in place every iteration (the old per-step
        # np.zeros churn was ~10 allocations per decode turn). Owned by
        # THIS epoch's thread: a watchdog replacement runs its own _loop
        # and therefore its own buffers, so a zombie wedged in a device
        # call can never race the replacement over shared staging memory.
        buf = self._make_step_buffers()
        try:
            while not self._stop.is_set() and self._epoch == epoch:
                if self._watchdog is not None:
                    self._watchdog.beat()
                self._iteration += 1
                if self.paged and self._pending_prefix:
                    with self._phase("serving.prefix_drain"):
                        self._drain_prefix_queue(epoch)
                if not self._live_count() \
                        and not self._admission.wait_for_request(0.05):
                    continue   # idle and nothing queued
                with self._phase("serving.admit") as did:
                    did["admitted"] = self._admit(epoch)
                    did["queue_depth"] = self._admission.depth_requests
                if self._live_count() and self._epoch == epoch:
                    try:
                        self._decode_iteration(epoch, buf)
                    except BaseException as e:   # fail tenants, keep thread
                        # a speculative verify failure stamps its own
                        # fault point — the crash dump must name the
                        # executable that actually died
                        self._on_device_failure(
                            e, epoch,
                            point=getattr(e, "fault_point",
                                          "generation.decode_step"))
        finally:
            # queued requests are failed by _admission.close() itself;
            # current-epoch thread only — a staled zombie must not fail
            # the replacement scheduler's live tenants
            if self._stop.is_set() and self._epoch == epoch:
                self._fail_live(RejectedError(
                    "engine shut down mid-generation", "shutdown"),
                    epoch=epoch)

    def _phase(self, name: str, parent: Optional[str] = None, **args):
        """A scheduler-phase span: an ``OpProfiler`` span that names what
        caused it — the enclosing span (``parent``; none for the phases
        that make up an iteration) and the iteration (``step``)."""
        if parent is not None:
            args["parent"] = parent
        return self.profiler.span(name, step=self._iteration, **args)

    def _on_device_failure(self, exc: BaseException, epoch: int, point: str):
        """Shared failure tail for prefill/decode: the failed call may have
        consumed the donated cache, and with it every live tenant's K/V —
        fail them and rebuild. Epoch-guarded so a zombie observing its own
        (post-restart) failure cannot rebuild the replacement's cache."""
        self._breaker.record_failure()
        if not getattr(exc, "injected", False) \
                and not isinstance(exc, RejectedError):
            # injected faults and typed serving errors (poison screens)
            # already flight-recorded themselves at the raise site;
            # recorded BEFORE the dump so the dump's snapshot has it
            self._recorder.record("device.failure", engine=self.name,
                                  point=point, error=type(exc).__name__)
        self._maybe_crash_dump(exc, point=point)
        with self._wd_lock:
            current = self._epoch == epoch
        if current:
            self._fail_live(exc, epoch=epoch)
            self._reset_cache()

    def _admit(self, epoch: int):
        """Fill free slots from the queue. Never waits (an idle scheduler
        waits for work in ``_loop``): admission is opportunistic, so decode
        cadence never stalls on an empty queue. Expired prompts
        are shed even under FULL occupancy (no free slot -> no ``take()``
        -> lazy head-shedding alone would let dead prompts hold queue
        budget and mask the queue-full backpressure signal).

        Paged: admission is gated on free BLOCKS, not just a free slot —
        the head request's worst-case reservation is planned first; a
        demand the pool can never satisfy sheds typed
        ('kv_blocks_exhausted'), a demand that merely exceeds the
        CURRENTLY free blocks requeues at the head and waits for
        retirements (FIFO preserved, deadline shedding still applies).

        Returns how many requests it took a slot for (seated, prefilled
        or failed in prefill; shed, requeued and cancelled ones are not
        counted)."""
        self._admission.expire_queued()
        admitted = 0
        for i in range(self.slots):
            if self._stop.is_set() or self._epoch != epoch:
                return admitted
            if self._slots[i] is not None:
                continue
            req = self._admission.take(1, timeout=0.0)
            self.metrics.queue_depth.set(self._admission.depth_requests)
            if req is None:
                continue
            prefix = cached = None
            if self.paged:
                verdict, prefix, cached = self._plan_blocks(req)
                if verdict == "shed":
                    continue   # head disposed of typed; slot stays free
                if verdict == "wait":
                    self._admission.requeue_head(req)
                    # FIFO: nothing overtakes the requeued head. QoS:
                    # higher-priority arrivals MAY overtake, but the
                    # _block_waiter reservation keeps them from eating
                    # the freed blocks the waiter is accumulating
                    return admitted
            greq: GenerationRequest = req.x
            resumed = greq.resume_tokens is not None
            if not req.future.running():
                if not req.future.set_running_or_notify_cancel():
                    if cached is not None:
                        # the plan's match refs must not outlive the
                        # request: leaked refcounts would keep evicted
                        # cache blocks off the free list forever
                        self._allocator.free(cached[2])
                    self._discard_swap(greq)
                    self._finish_request(req.trace, "cancelled",
                                         tenant=req.tenant)
                    continue     # caller cancelled while queued
            admitted += 1
            if not resumed:
                qw = (time.perf_counter() - req.submit_t) * 1e3
                self.metrics.observe_queue_wait_class(req.priority, qw)
                req.trace.event("queue.wait", queue_wait_ms=round(qw, 3))
            if greq.swap_key is not None and self.paged:
                # swap-to-host victim: try the block copy-back first —
                # cheaper than recompute above the crossover. Any miss
                # falls through to the ordinary resume paths below.
                if self._swap_in_seat(i, req, epoch):
                    continue
            if prefix is not None or cached is not None:
                # shared-prefix / automatic-cache-hit stream: no prefill
                # at all — reference the shared blocks and feed the
                # remaining prompt through decode steps
                self._seat_stream(i, req, prefix, cached, epoch)
                continue
            if resumed and int(greq.prompt.size) \
                    + int(greq.resume_tokens.size) > self.buckets[-1]:
                # the recompute prompt outgrew the prefill ladder (custom
                # short buckets): rebuild the K/V through the decode-feed
                # path instead — slower, but always available
                self._seat_stream(i, req, None, None, epoch)
                continue
            with self._wd_lock:  # visible to the watchdog while on-device
                self._inflight_prefill = req
            try:
                self._prefill_into(i, req, epoch)
            except BaseException as e:
                self.metrics.failed_total.inc()
                req.trace.event("prefill.failed", error=type(e).__name__)
                # outcome recorded only by the terminal's winner: if the
                # watchdog already failed this request, its "watchdog"
                # outcome stands and this late failure must not re-count
                if req.x.handle._fail(e):
                    self._finish_request(
                        req.trace, terminal_reason(e),
                        latency_ms=(time.perf_counter() - req.submit_t) * 1e3,
                        tenant=req.tenant)
                self._on_device_failure(e, epoch, point="generation.prefill")
            finally:
                with self._wd_lock:
                    if self._inflight_prefill is req:
                        self._inflight_prefill = None
        return admitted

    # ------------------------------------------------- paged block planning
    def _fresh_blocks_needed(self, prefix_len: int, n_prompt: int,
                             max_new: int, admit: bool = False) -> int:
        """THE block-demand formula — fresh blocks a stream must
        allocate: its footprint minus the prefix's FULLY-filled shared
        blocks (a partially-filled shared tail block is copy-on-written
        into a fresh block, so it is not deducted). Shared by the
        submit-time gate, the scheduler's plan, and the seating path so
        the three can never disagree.

        ``admit=False`` is the WORST CASE (prompt + every token the
        stream may ever generate) — the structural can-this-ever-fit
        bound, and the reservation ``allocate="reserve"`` takes at
        seating. ``admit=True`` is the demand seating actually pays:
        identical under "reserve", but under "on_demand" only the
        PROMPT's positions (plus one, the first generated token's write
        target — a seated stream can always emit at least one token);
        the generation tail allocates one block per boundary crossing
        in the decode loop instead of sitting idle in the pool."""
        total = prefix_len + n_prompt + max_new
        if admit and self.allocate == "on_demand":
            total = prefix_len + n_prompt + 1
        return blocks_for_tokens(total, self.block_size) \
            - prefix_len // self.block_size

    def _blocks_needed(self, greq: GenerationRequest,
                       prefix: Optional[SharedPrefix],
                       admit: bool = False) -> int:
        """A request's fresh-block demand. A preemption-resumed request
        recomputes its generated-so-far tokens through the prompt, so
        they count as prompt positions and its remaining budget shrinks
        by the same amount — the worst case is unchanged from the
        original admission."""
        n = int(greq.prompt.size)
        if greq.resume_tokens is not None:
            n += int(greq.resume_tokens.size)
        return self._fresh_blocks_needed(
            prefix.length if prefix is not None else 0,
            n, greq.max_new_tokens - greq.resume_step, admit=admit)

    def _plan_blocks(self, req: Request):
        """Dispose of the dequeued head: ('ok', prefix-or-None,
        cache-hit-or-None) when its seat demand fits the free pool,
        ('wait', None, None) when it must wait for retirements (or for a
        lazy prefix re-prefill), ('shed', None, None) when it was failed
        typed right here. The cache hit is ``(entry, m)`` — the
        automatic prefix cache's longest block-aligned match, consumed
        by the seating path (:meth:`_seat_stream`).

        Two demands: the WORST CASE gates structurally (a stream whose
        whole footprint exceeds what the pool can ever free can never
        complete, whichever allocator runs), the SEAT demand (prompt
        blocks only under ``allocate="on_demand"``) gates against the
        currently-free pool — the on-demand win is exactly this gap."""
        greq: GenerationRequest = req.x
        prefix = None
        if greq.prefix_id is not None:
            with self._prefix_lock:
                prefix = self._prefixes.get(greq.prefix_id)
            if prefix is None:
                # the caller released the prefix with requests still
                # queued against it: a client lifecycle mistake, labeled
                # 'client_error' (not model_error — the model is fine)
                e = RuntimeError(
                    f"shared prefix {greq.prefix_id!r} was released while "
                    "this request was queued")
                if greq.handle._fail(e):
                    self._finish_request(req.trace, "client_error",
                                         tenant=req.tenant)
                return "shed", None, None
            if not prefix.ready:
                # K/V lost to a cache rebuild (or registration raced the
                # queue): schedule the lazy re-prefill, wait our turn
                self._queue_prefix_prefill(greq.prefix_id)
                return "wait", None, None
        needed_worst = self._blocks_needed(greq, prefix)
        usable = self._usable_blocks()
        waiter = self._block_waiter
        if waiter is not None and (waiter[0] is req
                                   or waiter[0].future.done()):
            # the waiter is being re-planned right now, or reached a
            # terminal elsewhere (deadline shed, cancel): its
            # reservation must not throttle anyone anymore
            self._block_waiter = waiter = None
        if needed_worst > usable:
            if greq.resume_tokens is not None:
                # a preemption victim whose footprint can no longer ever
                # fit (shared-prefix pins grew under it after its blocks
                # were freed): the resume is impossible — typed
                # 'preempted', the caller resubmits the whole request
                self._discard_swap(greq)
                self._shed_typed(req, PreemptedError(
                    f"stream was preempted after {greq.resume_step} "
                    f"token(s) and its resume needs {needed_worst} KV "
                    f"blocks but the pool can free at most {usable} of "
                    f"{self._allocator.capacity} — resubmit",
                    tokens_generated=greq.resume_step))
                return "shed", None, None
            self._shed_typed(req, KVBlocksExhaustedError(
                f"request needs {needed_worst} KV blocks but the pool "
                f"can free at most {usable} of "
                f"{self._allocator.capacity} (shared-prefix pins "
                "excluded)",
                needed=needed_worst, usable=usable,
                capacity=self._allocator.capacity))
            return "shed", None, None
        # automatic prefix cache: longest block-aligned token-prefix
        # match over retired streams' full blocks — a hit seats like a
        # (block-aligned) shared prefix, no API opt-in. match_and_ref
        # takes this planner's OWN allocator refs atomically with the
        # match, so a concurrent release (warmup/drain) or eviction
        # cannot free the matched blocks before seating; every non-seat
        # exit below must free them. Resumed streams skip the match:
        # their recompute must rebuild the exact state the unpreempted
        # run had, through the same prefill route.
        cached = None
        if (self._prefix_cache is not None and prefix is None
                and greq.resume_tokens is None and not self._cache_bypass):
            cached = self._prefix_cache.match_and_ref(greq.prompt)
        if cached is not None:
            m = cached[1]
            needed = self._fresh_blocks_needed(
                m * self.block_size,
                int(greq.prompt.size) - m * self.block_size,
                greq.max_new_tokens, admit=True)
        else:
            needed = self._blocks_needed(greq, prefix, admit=True)
        # two reservations are off limits: blocks a queued-but-unprefilled
        # prefix still needs (the drain runs first each turn, but without
        # this sustained stream traffic would consume every freed block
        # and starve the waiting prefix prefill forever), and the current
        # block-waiter's demand — freed blocks accumulate toward the
        # waiter instead of being consumed by overtaking (QoS) arrivals.
        # The waiter reservation binds SAME-OR-LOWER priority classes
        # only: strict priority stays the top rule (interactive traffic
        # may outrun a batch waiter indefinitely, exactly as queue
        # selection itself allows). Any request that must wait TAKES OVER
        # the slot: a planned "wait" head is by construction the request
        # selection keeps picking, so the reservation always belongs to
        # the stable head — a recorded waiter that selection no longer
        # favors (a smaller-tag same-class arrival, a higher class)
        # would otherwise pin a reservation nobody can clear and
        # livelock the scheduler against an idle pool. Fairness is not
        # lost: a displaced waiter's fixed finish tag guarantees WFQ
        # re-selects it once the newcomers' tags grow past it.
        rank = PRIORITIES.index(req.priority)
        reserved = 0
        if waiter is not None and rank >= PRIORITIES.index(waiter[2]):
            reserved = waiter[1]
        avail = self._allocator.free_count \
            - self._pending_prefix_demand() - reserved
        if needed > avail and self._prefix_cache is not None \
                and len(self._prefix_cache):
            # the automatic prefix cache is reclaimable-on-demand by
            # design: evict LRU entries (never the one just matched)
            # before making anyone wait
            self._cache_evict(needed - avail,
                              protect=cached[0] if cached else None)
            avail = self._allocator.free_count \
                - self._pending_prefix_demand() - reserved
        if needed > avail:
            if cached is not None:
                # not seating this turn: return the planner's match refs
                # (the cache entry keeps its own; the next plan
                # re-matches against whatever still exists)
                self._allocator.free(cached[2])
            self._block_waiter = (req, needed, req.priority)
            return "wait", None, None
        return "ok", prefix, cached

    def _pending_prefix_demand(self) -> int:
        """Worst-case blocks the QUEUED unprefilled prefixes still need
        (reserved ahead of stream admission so retirements accumulate
        toward the prefill instead of being re-tenanted instantly)."""
        with self._prefix_lock:
            pending = {pid for pid, _ in self._pending_prefix}
            return sum(blocks_for_tokens(p.length, self.block_size)
                       for pid, p in self._prefixes.items()
                       if pid in pending and not p.ready)

    def _queue_prefix_prefill(self, prefix_id: str):
        with self._prefix_lock:
            if any(pid == prefix_id for pid, _ in self._pending_prefix):
                return
            self._pending_prefix.append((prefix_id, None))

    def _drain_prefix_queue(self, epoch: int):
        """Prefill pending shared prefixes (scheduler thread only — these
        donate the same cache the decode loop donates). A prefix whose
        blocks are not free yet stays at the head and is retried next
        iteration: retirements free blocks, so this converges whenever
        the pin fits ``_usable_blocks`` (which register_prefix checked)."""
        while not self._stop.is_set() and self._epoch == epoch:
            with self._prefix_lock:
                if not self._pending_prefix:
                    return
                pid, fut = self._pending_prefix[0]
                prefix = self._prefixes.get(pid)
            if prefix is not None and not prefix.ready:
                nb = blocks_for_tokens(prefix.length, self.block_size)
                if nb > self._usable_blocks(excluding=pid):
                    # can NEVER fit (other prefixes' pins/reservations own
                    # the pool): unregister + fail typed instead of
                    # wedging the queue head forever — every later
                    # registration and lazy re-prefill sits behind it
                    with self._prefix_lock:
                        self._prefixes.pop(pid, None)
                    self._pop_prefix_head(pid)
                    if fut is not None:
                        try:
                            fut.set_exception(KVBlocksExhaustedError(
                                f"prefix {pid!r} needs {nb} KV blocks the "
                                "pool can never free (pinned by other "
                                "prefixes)", needed=nb,
                                usable=self._usable_blocks(),
                                capacity=self._allocator.capacity))
                        except InvalidStateError:
                            pass
                    continue
                if nb > self._allocator.free_count:
                    return   # wait for retirements to free blocks
                try:
                    if not self._prefill_prefix(prefix, epoch):
                        return   # zombie: the new epoch owns the queue
                except BaseException as e:
                    self._pop_prefix_head(pid)
                    if fut is not None:
                        try:
                            fut.set_exception(e)
                        except InvalidStateError:
                            pass
                    self._on_device_failure(e, epoch,
                                            point="generation.prefill")
                    return
            self._pop_prefix_head(pid)
            if fut is None:
                continue
            try:
                if prefix is None:
                    fut.set_exception(RuntimeError(
                        f"prefix {pid!r} was released before its prefill"))
                else:
                    fut.set_result(pid)
            except InvalidStateError:
                pass

    def _pop_prefix_head(self, pid: str):
        with self._prefix_lock:
            if self._pending_prefix and self._pending_prefix[0][0] == pid:
                self._pending_prefix.popleft()

    def _prefill_prefix(self, prefix: SharedPrefix, epoch: int) -> bool:
        """Run the ONE prefill a shared prefix ever gets (per pool
        lifetime): allocate its blocks, write its K/V through the normal
        bucketed prefill executable (sampled token 0 discarded), publish
        ``prefix.blocks`` on success. Returns False when a watchdog
        restart staled this epoch mid-call — the replacement scheduler's
        drain re-runs it against the rebuilt pool."""
        alloc = self._allocator
        n = prefix.length
        nb = blocks_for_tokens(n, self.block_size)
        blocks = alloc.alloc(nb)
        bucket = self._bucket_for(n)
        row = np.zeros(blocks_for_tokens(bucket, self.block_size), np.int32)
        row[:nb] = blocks
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prefix.tokens
        with self._wd_lock:
            self._prefix_busy = True
        t0 = time.perf_counter()
        try:
            with self.profiler.span("serving.prefix_prefill",
                                    engine=self.name,
                                    prefix=prefix.prefix_id, tokens=n):
                def call():
                    return self._donated_call(
                        "generation.prefill", self._prefill,
                        self.params, self._cache, padded, row, np.int32(n),
                        np.asarray(jax.random.PRNGKey(0)), np.float32(0.0),
                        np.int32(0), np.int32(0))

                raw = self._retry_call(call)
                new_cache, _tok0 = raw
        except BaseException:
            alloc.free(blocks)   # captured allocator: a stale one is inert
            raise
        finally:
            with self._wd_lock:
                self._prefix_busy = False
        with self._wd_lock:
            current = self._epoch == epoch
            if current:
                self._cache = new_cache
        if not current:
            return False
        self._breaker.record_success()
        with self._prefix_lock:
            registered = self._prefixes.get(prefix.prefix_id) is prefix
            if registered:
                prefix.blocks = blocks
        if not registered:      # released while we were prefilling
            alloc.free(blocks)
            return True
        self.metrics.prefix_prefills_total.inc()
        self.metrics.prefill_ms.observe((time.perf_counter() - t0) * 1e3)
        self._recorder.record("prefix.prefill", engine=self.name,
                              prefix_id=prefix.prefix_id, tokens=n,
                              blocks=nb)
        self._update_block_gauges()
        return True

    def _seat_stream(self, i: int, req: Request,
                     prefix: Optional[SharedPrefix], cached, epoch: int):
        """Seat a stream WITHOUT a prefill — the decode-feed path. Three
        flavors share it:

        - **explicit shared prefix**: the table references the prefix's
          pinned blocks (refcount++), a partially-filled shared tail
          block is held read-only and copy-on-written by the slot's
          first decode step (``_Slot.cow``);
        - **automatic prefix-cache hit** (``cached=(entry, m)``): the
          table references the entry's first ``m`` blocks (refcount++) —
          entries hold FULL blocks only, so there is never a CoW tail;
        - **bare feed** (no shared blocks): a preemption-resumed stream
          whose recompute prompt outgrew the prefill ladder rebuilds its
          K/V one token per decode iteration from position 0.

        Fresh blocks cover the rest of the seat demand (worst case under
        ``allocate="reserve"``, prompt-only under ``"on_demand"``), and
        the un-prefilled tokens — prompt suffix plus, for a resumed
        stream, its generated-so-far tokens — ride the decode executable
        one token per iteration with mid-feed samples discarded; the
        final feed's sample is token ``resume_step`` (0 for a fresh
        stream), exactly the index the request key folds."""
        greq: GenerationRequest = req.x
        B = self.block_size
        alloc = self._allocator
        resumed = greq.resume_tokens is not None
        feed = [int(t) for t in greq.prompt]
        if resumed:
            feed += [int(t) for t in greq.resume_tokens]
        cow = None
        cow_src = None
        part = None            # partially-filled shared tail ref (prefix)
        owned = []             # refs this planner ALREADY holds (a cache
        #                        hit's match_and_ref took them atomically)
        try:
            if prefix is not None:
                P = prefix.length
                pblocks = prefix.blocks
                if pblocks is None:
                    raise RuntimeError(
                        f"shared prefix {greq.prefix_id!r} was "
                        "invalidated while this request was being "
                        "seated; resubmit")
                shared = list(pblocks[:P // B])
                # a partially-filled shared tail block is referenced too
                # (it must stay alive until the CoW copy reads it), but
                # never enters the table: the table entry points at the
                # CoW dst
                part = [pblocks[P // B]] if P % B else []
                cow_src = pblocks[P // B] if P % B else None
                nfresh = self._blocks_needed(greq, prefix, admit=True)
            elif cached is not None:
                _entry, m, owned = cached
                P = m * B
                shared = list(owned)
                part = []
                nfresh = self._fresh_blocks_needed(
                    P, len(feed) - P, greq.max_new_tokens
                    - greq.resume_step, admit=True)
                feed = feed[m * B:]
            else:
                P = 0
                shared, part = [], []
                nfresh = self._fresh_blocks_needed(
                    0, len(feed), greq.max_new_tokens - greq.resume_step,
                    admit=True)
            fresh = alloc.alloc(nfresh)
            refs = part if owned else shared + part
            try:
                alloc.incref(refs)   # all-or-nothing
            except ValueError as e:
                alloc.free(fresh)
                raise RuntimeError(
                    f"shared prefix {greq.prefix_id!r} was released while "
                    "this request was being seated; resubmit") from e
            held = shared + part + fresh
            if cow_src is not None:
                cow = (cow_src, fresh[0])
        except BaseException as e:
            if owned:
                alloc.free(owned)   # the match refs must not leak
            # release_prefix racing the seating — client lifecycle, same
            # 'client_error' label as the queued-release shed above
            if greq.handle._fail(e):
                self._finish_request(req.trace, "client_error",
                                     tenant=req.tenant)
            return
        n_shared = len(shared)
        n_entries = n_shared + len(fresh)
        row = np.zeros(self.max_blocks_per_slot, np.int32)
        row[:n_shared] = shared
        row[n_shared:n_entries] = fresh
        st = _Slot(greq=greq, request=req,
                   n_generated=greq.resume_step, last_token=0,
                   length=P, blocks=held, prefix_len=P,
                   pending=deque(feed), cow=cow, n_entries=n_entries,
                   resumed=resumed)
        with self._wd_lock:
            seated = self._epoch == epoch and not self._stop.is_set()
            if seated:
                self._tables[i] = row
                self._slots[i] = st
        if not seated:
            alloc.free(held)     # captured allocator: stale one is inert
            if greq.handle._fail(WatchdogTimeoutError(
                    f"engine[{self.name}] restarted while this prompt was "
                    "being seated; resubmit")):
                self._finish_request(req.trace, "watchdog",
                                     tenant=req.tenant)
            return
        if prefix is not None and not resumed:
            prefix.hits += 1
            self.metrics.prefix_hits_total.inc()
        if cached is not None:
            self.metrics.prefix_cache_hits_total.inc()
        if cow is not None:
            self.metrics.kv_cow_copies_total.inc()
        req.trace.event("slot.assign", slot=i, prefix_id=greq.prefix_id,
                        shared_blocks=n_shared + (1 if cow else 0),
                        fresh_blocks=len(fresh),
                        cached_tokens=P if cached is not None else 0,
                        resumed=resumed)
        self._update_block_gauges()

    def _swap_in_seat(self, i: int, req: Request, epoch: int) -> bool:
        """Re-seat a swap-to-host preemption victim by copying its
        captured KV blocks back into freshly-allocated pool blocks
        (device_put scatter + table rebuild) — NO prefill, no decode
        feed: the slot resumes exactly where the eviction froze it
        (``n_generated``/``last_token``/``length`` from the snapshot)
        and the next decode step continues the stream bitwise. Returns
        False on ANY miss — key already dropped (LRU eviction, watchdog
        invalidation), epoch mismatch, pool refusal, or a seeded
        ``kv.swap_in`` fault — and the caller falls through to the
        recompute-on-resume path; a swap failure never sheds."""
        greq: GenerationRequest = req.x
        key, greq.swap_key = greq.swap_key, None   # one shot either way
        store = self._swap_store
        entry = store.take(key) if store is not None \
            and key is not None else None
        if entry is None:
            return False
        self.metrics.kv_swapped_blocks_held.set(store.blocks_held)
        if entry.epoch != epoch:
            return False   # captured against a pre-restart pool
        alloc = self._allocator
        # same demand formula _plan_blocks just verified (swapped
        # victims are prefix-less by the swap-out gate, so prefix=None
        # is exact, and it covers the snapshot's blocks: used =
        # ceil(length/B) <= ceil((prompt+resume+1)/B) <= nfresh)
        nfresh = self._blocks_needed(greq, None, admit=True)
        try:
            blocks = alloc.alloc(nfresh)
        except KVBlocksExhaustedError:
            return False
        used = entry.used_blocks
        rows = np.asarray(blocks[:used], np.int32)
        try:
            def copy_in():
                # scatter the host snapshot into the allocated rows of
                # every leaf (values and int8 scales alike); .at[].set
                # builds a NEW pytree, assigned only under the epoch
                # check below — a watchdog restart in between drops it
                layers = [
                    {k: leaf.at[rows].set(data[k])
                     for k, leaf in layer.items()}
                    for layer, data in zip(self._cache["layers"],
                                           entry.payload)]
                out = dict(self._cache)
                out["layers"] = layers
                return out
            new_cache = inject("kv.swap_in", copy_in)
        except Exception as e:
            alloc.free(blocks)
            req.trace.event("kv.swap", direction="in", slot=i,
                            failed=type(e).__name__)
            return False
        row = np.zeros(self.max_blocks_per_slot, np.int32)
        row[:nfresh] = blocks
        st = _Slot(greq=greq, request=req,
                   n_generated=entry.n_generated,
                   last_token=entry.last_token, length=entry.length,
                   blocks=blocks, prefix_len=0, n_entries=nfresh,
                   resumed=True)
        with self._wd_lock:
            seated = self._epoch == epoch and not self._stop.is_set()
            if seated:
                self._cache = new_cache
                self._tables[i] = row
                self._slots[i] = st
        if not seated:
            alloc.free(blocks)   # captured allocator: stale one is inert
            return False         # the recompute path owns the terminal
        self.metrics.kv_swap_bytes_in.inc(entry.nbytes)
        req.trace.event("kv.swap", direction="in", slot=i,
                        blocks=used, bytes=entry.nbytes)
        req.trace.event("slot.assign", slot=i, swapped_in=True,
                        resumed=True)
        self._update_block_gauges()
        return True

    # --------------------------------- on-demand growth + QoS preemption
    def _grow_block_tables(self, epoch: int) -> bool:
        """Map a fresh block into every live slot whose NEXT write (at
        position ``st.length``) falls past its mapped entries — the
        on-demand allocator's per-iteration work, host-side only: the
        fixed-width table row grows an entry, the donated decode
        signature is untouched. Returns False when a watchdog restart
        staled this epoch (the caller abandons the iteration)."""
        B = self.block_size
        while True:
            needy = None
            with self._wd_lock:
                if self._epoch != epoch or self._stop.is_set():
                    return False
                for i, st in enumerate(self._slots):
                    if st is None:
                        continue
                    if blocks_for_tokens(st.length + 1, B) > st.n_entries:
                        needy = (i, st)
                        break
            if needy is None:
                return True
            if not self._grow_slot(needy[0], needy[1], epoch):
                return False

    def _grow_slot(self, i: int, st: _Slot, epoch: int) -> bool:
        """Allocate ONE block for slot ``i``'s boundary crossing,
        reclaiming — automatic-prefix-cache eviction first, then
        QoS-aware preemption — when the pool is dry. Returns False only
        when the epoch staled; a self-preempted slot returns True and
        the caller's re-scan finds it gone."""
        while True:
            alloc = self._allocator
            try:
                blocks = alloc.alloc(1)
            except KVBlocksExhaustedError:
                blocks = None
            if blocks is not None:
                with self._wd_lock:
                    current = self._epoch == epoch
                    seated = current and self._slots[i] is st
                    if seated:
                        st.n_entries = self._grow_table(
                            self._tables, i, st.n_entries, blocks[0])
                        st.blocks.append(blocks[0])
                if not seated:
                    # slot re-tenanted (restart) or stream gone: return
                    # the block — captured allocator, stale one is inert
                    alloc.free(blocks)
                return current if not seated else True
            # pool dry: unpinned cache entries are the cheap reclaim
            if self._prefix_cache is not None and len(self._prefix_cache):
                if self._cache_evict(1):
                    continue
            outcome = self._preempt_for(i, st, epoch)
            if outcome == "stale":
                return False
            if outcome == "self":
                return True      # slot i was evicted; caller re-scans
            # outcome == "freed": retry the allocation

    def _try_swap_out(self, j: int, vst: _Slot, epoch: int):
        """Copy victim slot ``j``'s written KV blocks (values AND int8
        scales) to the host swap store. Caller holds ``_wd_lock`` with
        the epoch verified and has NOT yet freed the victim's blocks —
        the device_get must finish before ``free_batch`` can recycle
        them under another stream. Returns ``(key, blocks, bytes)`` on
        success, ``(None, 0, 0)`` when the victim is below the
        crossover, structurally ineligible (pending CoW destination or
        mid-feed rows whose K/V is not yet complete), the bounded store
        cannot fit it, or the copy fails (seeded ``kv.swap_out`` fault
        point) — every miss degrades to the recompute path."""
        store = self._swap_store
        if store is None or vst.blocks is None \
                or self.swap_threshold_blocks is None:
            # threshold None with a live store: the store was created
            # lazily by import_pages (cross-host migration) — migration
            # must not change preemption behavior, so victims keep the
            # recompute-only path
            return None, 0, 0
        if len(vst.blocks) <= self.swap_threshold_blocks:
            return None, 0, 0
        if vst.cow is not None or vst.pending:
            # a pending copy-on-write destination still holds garbage
            # rows, and a mid-feed slot's cache is not yet complete:
            # neither snapshot would reproduce the stream
            return None, 0, 0
        if vst.prefix_len != 0 or vst.greq.prefix_id is not None:
            # shared-span victims (explicit prefix / automatic cache
            # hit) take the recompute path: their shared blocks outlive
            # the eviction anyway, so the swap win is the private tail
            # only — not worth duplicating pinned K/V into host RAM and
            # re-deriving the plan's shared-block discount at re-seat
            return None, 0, 0
        used = blocks_for_tokens(vst.length, self.block_size)
        if used <= 0 or used > vst.n_entries:
            return None, 0, 0
        rows = np.asarray(self._tables[j][:used], np.int32)
        try:
            # gather the used rows ON DEVICE, then one host transfer of
            # just those blocks (not the whole pool)
            payload = inject(
                "kv.swap_out",
                lambda: jax.device_get(
                    [{k: leaf[rows] for k, leaf in layer.items()}
                     for layer in self._cache["layers"]]))
        except Exception:
            return None, 0, 0
        nbytes = sum(int(a.nbytes) for layer in payload
                     for a in layer.values())
        entry = SwapEntry(payload=payload, used_blocks=used,
                          length=vst.length, n_generated=vst.n_generated,
                          last_token=int(vst.last_token),
                          prefix_len=vst.prefix_len, epoch=epoch,
                          nbytes=nbytes)
        key = store.put(entry)
        if key is None:
            return None, 0, 0
        return key, used, nbytes

    def _discard_swap(self, greq: "GenerationRequest"):
        """Drop a requeued stream's swapped-out entry (terminal shed or
        capacity refusal: the blocks will never be swapped back in)."""
        if greq.swap_key is not None:
            if self._swap_store is not None:
                self._swap_store.discard(greq.swap_key)
                self.metrics.kv_swapped_blocks_held.set(
                    self._swap_store.blocks_held)
            greq.swap_key = None

    # queued-request disposal hooks (AdmissionController callbacks): a
    # preemption victim requeued WITH a swap entry can die in the queue
    # too — shutdown's close(), a caller cancel, a deadline shed. The
    # shared-mixin accounting alone leaked the parked SwapEntry on all
    # three paths (host RAM held until engine GC; the ISSUE 18 ledger's
    # swap-store-empty-at-shutdown law caught it), so the generation
    # engine layers the discard on before counting the terminal.
    def _count_close_reject(self, req):
        self._discard_swap(req.x)
        super()._count_close_reject(req)

    def _count_cancelled(self, req):
        self._discard_swap(req.x)
        super()._count_cancelled(req)

    def _count_shed(self, req):
        self._discard_swap(req.x)
        super()._count_shed(req)

    # ------------------------------- cross-host KV page migration (disagg)
    def _capture_pages(self, req: Request, rows: np.ndarray, length: int,
                       n_generated: int, last_token: int, epoch: int):
        """Export a retiring ``capture_pages`` stream's written KV block
        pages (values AND int8 scales, every leaf) as a
        :class:`SwapEntry` on ``greq.captured_entry`` — the prefill half
        of cross-host migration. Caller holds ``_wd_lock`` with the
        epoch verified and the blocks still referenced, the same
        discipline as :meth:`_try_swap_out` (the device_get must finish
        before the rows can be recycled under another stream). Any
        failure — including the seeded ``kv.migrate.export`` fault
        point — leaves ``captured_entry`` None: the orchestrator
        degrades to recompute on the decode host, never sheds."""
        greq: GenerationRequest = req.x
        try:
            payload = inject(
                "kv.migrate.export",
                lambda: jax.device_get(
                    [{k: leaf[rows] for k, leaf in layer.items()}
                     for layer in self._cache["layers"]]))
        except Exception as e:
            req.trace.event("kv.migrate", direction="export",
                            failed=type(e).__name__)
            return
        nbytes = sum(int(a.nbytes) for layer in payload
                     for a in layer.values())
        greq.captured_entry = SwapEntry(
            payload=payload, used_blocks=int(rows.size),
            length=int(length), n_generated=int(n_generated),
            last_token=int(last_token), prefix_len=0, epoch=epoch,
            nbytes=nbytes)
        self.metrics.kv_migrate_bytes_out.inc(nbytes)
        req.trace.event("kv.migrate", direction="export",
                        blocks=int(rows.size), bytes=nbytes)

    def take_captured_pages(self, handle: GenerationHandle
                            ) -> Optional[SwapEntry]:
        """One-shot retrieval of a ``capture_pages`` stream's exported
        pages (None when the export failed or never ran — the caller
        degrades to recompute). Call after the handle's future resolved:
        the capture happens before the terminal is delivered, so a
        resolved future means the entry is either set or never will
        be."""
        greq = handle._req.x
        if greq is None:
            return None
        entry, greq.captured_entry = greq.captured_entry, None
        return entry

    def import_pages(self, entry: SwapEntry) -> Optional[int]:
        """Seat migrated KV pages in this engine's swap store and return
        the key to pass as ``submit(swap_key=...)`` — the decode half of
        cross-host migration rides PR 15's swap-in device_put path
        unchanged. The entry is re-stamped with THIS engine's current
        epoch (it crossed hosts; the exporter's epoch is meaningless
        here) under ``_wd_lock``, so a restart between import and
        admission invalidates it exactly like a native swap entry.
        Returns None when the store refuses it or the seeded
        ``kv.migrate.import`` fault point fires — the caller submits
        without ``swap_key`` and the decode host recomputes."""
        if not self.paged:
            raise ValueError(
                "import_pages requires the paged KV cache "
                "(GenerationEngine(paged=True)) — migrated pages re-seat "
                "through the block pool")
        with self._wd_lock:
            if self._swap_store is None:
                # lazy store for migration-only engines (no
                # swap_threshold_blocks): preemption behavior is
                # unchanged — _try_swap_out gates on the threshold, not
                # the store
                self._swap_store = BlockSwapStore(self.num_blocks)
            store = self._swap_store
            entry = dataclasses.replace(entry, epoch=self._epoch)
        try:
            key = inject("kv.migrate.import", store.put, entry)
        except Exception:
            return None
        if key is not None:
            self.metrics.kv_migrate_bytes_in.inc(entry.nbytes)
            self.metrics.kv_swapped_blocks_held.set(store.blocks_held)
        return key

    def discard_imported(self, key: int):
        """Drop an :meth:`import_pages` entry whose stream never reached
        admission (the migrate endpoint's follow-up submit was rejected):
        the key is one-shot and nothing will ever take it, so the parked
        bytes must come back now, not at shutdown."""
        with self._wd_lock:
            store = self._swap_store
        if store is not None:
            store.discard(key)
            self.metrics.kv_swapped_blocks_held.set(store.blocks_held)

    def _preempt_for(self, needy_i: int, needy_st: _Slot,
                     epoch: int) -> str:
        """The pool cannot serve slot ``needy_i``'s next block: evict ONE
        resident stream and requeue it — swapping its written blocks to
        host RAM when it sits above the recompute-vs-copy crossover
        (``swap_threshold_blocks``), else for recompute-on-resume (vLLM
        §4.5). Victim policy — QoS-aware, strict priority first: only
        same-or-LOWER classes than the needy stream are eligible (a
        batch stream never evicts interactive work), non-``preemptible``
        tenants are exempt, and within the eligible set the lowest
        class, then the largest block footprint, then the latest arrival
        goes first (one eviction frees the most for the least recompute
        debt). With no eligible victim the needy stream preempts ITSELF
        and waits in queue as the block-waiter. Returns 'freed' (a
        victim's blocks are back), 'self' (the needy slot was evicted),
        or 'stale' (watchdog restart owns the table)."""
        needy_rank = PRIORITIES.index(needy_st.request.priority)
        victim = None
        with self._wd_lock:
            if self._epoch != epoch:
                return "stale"
            best = None
            for j, st in enumerate(self._slots):
                if st is None or st is needy_st:
                    continue
                if st.request.future.done():
                    continue   # terminal delivered; retire tail owns it
                rank = PRIORITIES.index(st.request.priority)
                if rank < needy_rank:
                    continue   # never evict a higher class
                if self.qos is not None and not self.qos.tenant(
                        st.request.tenant).preemptible:
                    continue
                key = (rank, len(st.blocks or ()), st.request.submit_t)
                if best is None or key > best[0]:
                    best = (key, j, st)
            if best is not None:
                victim = (best[1], best[2])
            else:
                victim = (needy_i, needy_st)
            j, vst = victim
            # swap-to-host (vLLM §4.5): a victim above the
            # recompute-vs-copy crossover copies its written blocks to
            # host RAM BEFORE they are freed — once free_batch runs the
            # pool can hand those blocks to another stream, so the
            # device_get must complete under the same lock that frees
            # them. Any failure degrades to the recompute path (the
            # entry simply isn't stored); it never sheds the stream.
            # analysis: ok lock-discipline — the device_get must finish
            # before free_batch hands these blocks to another stream;
            # the copy is bounded (a victim's few KV blocks) and atomic
            # with the table teardown under the same epoch lock every
            # slot mutation takes. Moving it outside would race the
            # pool reusing (and overwriting) the blocks mid-copy.
            swap_key, swap_blocks, swap_bytes = self._try_swap_out(
                j, vst, epoch)
            self._slots[j] = None
            self._tables[j] = 0
            blocks, vst.blocks = vst.blocks, None
            if blocks:
                self._allocator.free_batch([blocks])
        greq = vst.greq
        req = vst.request
        greq.resume_tokens = np.asarray(greq.handle.tokens_so_far(),
                                        np.int32)
        greq.resume_step = vst.n_generated
        greq.swap_key = swap_key
        greq.preemptions += 1
        self.metrics.preemptions_total.inc()
        if swap_key is not None:
            self.metrics.kv_swapped_blocks.inc(swap_blocks)
            self.metrics.kv_swap_bytes_out.inc(swap_bytes)
            self.metrics.kv_swapped_blocks_held.set(
                self._swap_store.blocks_held)
            req.trace.event("kv.swap", direction="out", slot=j,
                            blocks=swap_blocks, bytes=swap_bytes)
        req.trace.event("preempt", slot=j,
                        tokens_generated=vst.n_generated,
                        blocks_freed=len(blocks or ()),
                        swapped=swap_key is not None,
                        self_preempted=vst is needy_st)
        self._recorder.record("stream.preempt", engine=self.name,
                              slot=j, tenant=req.tenant,
                              tokens_generated=vst.n_generated,
                              blocks=len(blocks or ()))
        # deadline bounded QUEUE time and this stream already served it:
        # the recompute requeue must not convert a long generation into
        # a 'deadline' shed (see MIGRATING.md)
        req.deadline_t = None
        if self._stop.is_set():
            self._discard_swap(greq)
            self._shed_typed(req, PreemptedError(
                f"stream preempted after {vst.n_generated} token(s) "
                "while the engine was shutting down — resubmit",
                tokens_generated=vst.n_generated))
        else:
            self._admission.requeue_head(req)
            self.metrics.queue_depth.set(self._admission.depth_requests)
        return "self" if vst is needy_st else "freed"

    def _maybe_cache_retired(self, i: int, st: _Slot):
        """Offer a normally-retired stream's FULL blocks to the
        automatic prefix cache instead of freeing them (caller holds
        ``_wd_lock`` with the epoch verified — the decode retire tail).
        Only the block-aligned span whose K/V the table actually holds
        is kept (``st.length`` positions: the retiring token's own K/V
        was never written), covered by the stream's prompt + generated
        tokens; explicit-prefix streams are skipped (their shared span
        is already pinned and the pin owns its lifecycle)."""
        cache = self._prefix_cache
        if cache is None or st.greq.prefix_id is not None \
                or st.blocks is None:
            return
        B = self.block_size
        m = st.length // B
        if m <= 0 or st.n_entries < m:
            return
        gen = st.greq.handle.tokens_so_far()
        seq = np.concatenate([np.asarray(st.greq.prompt, np.int32),
                              np.asarray(gen, np.int32)])
        if seq.size < m * B:
            return   # bookkeeping mismatch: freeing normally is safe
        row = [int(b) for b in self._tables[i][:m]]
        try:
            self._allocator.incref(row)   # the cache's own reference
        except ValueError:
            return   # shouldn't happen (stream holds refs); stay safe
        before = len(cache)
        kept = cache.insert(seq[:m * B], row)
        if kept:
            self.metrics.prefix_cache_inserts_total.inc()
        evicted = before + (1 if kept else 0) - len(cache)
        if evicted > 0:
            self.metrics.prefix_cache_evictions_total.inc(evicted)

    def _cache_evict(self, need_blocks: int, protect=None) -> int:
        """Evict LRU automatic-prefix-cache entries (scheduler thread
        only), counting evictions into metrics. Returns the references
        released."""
        cache = self._prefix_cache
        before = len(cache)
        released = cache.evict(need_blocks, protect=protect)
        evicted = before - len(cache)
        if evicted > 0:
            self.metrics.prefix_cache_evictions_total.inc(evicted)
        return released

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _donated_call(self, point: str, fn, *args):
        """Run a DONATED jitted call under the ``point`` fault hook, and
        stamp any exception that escapes after the call started executing
        with ``donated_state_consumed=True``: injected faults raise before
        execution (retry-safe, cache intact), but a real failure from the
        call itself may have consumed the donated buffers — the retry
        classifier refuses those and the fail-tenants-and-rebuild path
        takes over."""
        started = False

        def run(*a):
            nonlocal started
            started = True
            return fn(*a)

        try:
            return inject(point, run, *args)
        except BaseException as e:
            if started:
                try:
                    e.donated_state_consumed = True
                except Exception:
                    pass   # exotic __slots__ exception: stays conservative
            raise

    # ------------------------------------------------- poisoned-result screen
    def _screen_token_ids(self, toks, point: str, live=None):
        """Cheap poisoned-result guard on sampled tokens: NaN/inf (a
        poison rule can mutate the host copy to float) or ids outside
        [0, vocab) fail the iteration typed. Dead slots compute masked
        garbage by design, so only ``live`` entries are screened."""
        a = np.asarray(toks)
        if live is not None:
            a = a[np.asarray(live)]
        if a.size == 0:
            return
        if np.issubdtype(a.dtype, np.inexact) \
                and not bool(np.all(np.isfinite(a))):
            self._poisoned(point, "non-finite sampled token values")
        bad = (a < 0) | (a >= self.cfg.vocab_size)
        if bool(np.any(bad)):
            self._poisoned(
                point, f"{int(np.count_nonzero(bad))} sampled token id(s) "
                       f"outside [0, {self.cfg.vocab_size})")

    def _prefill_into(self, slot: int, req: Request, epoch: int):
        greq: GenerationRequest = req.x
        resumed = greq.resume_tokens is not None
        toks = greq.prompt
        if resumed:
            # recompute-on-resume (the vLLM §4.5 policy): the victim's
            # generated-so-far tokens ride the prompt through ONE
            # prefill, and the trailing sample is drawn at its next
            # token index (the `step` argument) — position-stable keys
            # make the resumed stream bitwise the unpreempted one
            toks = np.concatenate(
                [greq.prompt, np.asarray(greq.resume_tokens, np.int32)])
        n = int(toks.size)
        bucket = self._bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = toks
        req.trace.event("slot.assign", slot=slot, bucket=bucket,
                        resumed=resumed)
        alloc = blocks = row = None
        nb_total = 0
        if self.paged:
            # reservation gated by _plan_blocks: under "reserve" every
            # block this stream can ever touch is taken up front (decode
            # never hits mid-stream exhaustion); under "on_demand" only
            # the prompt's blocks (plus the first write target) — the
            # decode loop allocates one block per boundary crossing and
            # preempts when the pool is dry
            alloc = self._allocator
            nb_total = self._blocks_needed(greq, None, admit=True)
            blocks = alloc.alloc(nb_total)
            row = np.zeros(self.max_blocks_per_slot, np.int32)
            row[:nb_total] = blocks
        t0 = time.perf_counter()
        try:
            with self.profiler.span("serving.prefill", engine=self.name,
                                    slot=slot, bucket=bucket, prompt=n,
                                    step=self._iteration,
                                    parent="serving.admit"):
                def call():
                    # self._cache re-read per attempt: a retryable fault
                    # raises BEFORE the donated call runs (enforced by
                    # _donated_call's consumed-stamp), so the cache is
                    # intact and the retry re-binds the same live buffers
                    if self.paged:
                        return self._donated_call(
                            "generation.prefill", self._prefill,
                            self.params, self._cache, padded,
                            np.ascontiguousarray(row[:blocks_for_tokens(
                                bucket, self.block_size)]),
                            np.int32(n), greq.key,
                            np.float32(greq.temperature),
                            np.int32(greq.top_k),
                            np.int32(greq.resume_step))
                    return self._donated_call(
                        "generation.prefill", self._prefill,
                        self.params, self._cache, padded, np.int32(slot),
                        np.int32(n), greq.key, np.float32(greq.temperature),
                        np.int32(greq.top_k))

                with self._phase("serving.prefill.dispatch",
                                 "serving.prefill"):
                    new_cache, tok = self._retry_call(call)
                with self._phase("serving.prefill.readback",
                                 "serving.prefill"):
                    tok = np.asarray(tok)   # the host waits for the device
                if self.screen_outputs:
                    self._screen_token_ids(tok, "generation.prefill")
                tok = int(tok)
        except BaseException:
            if blocks is not None:
                alloc.free(blocks)   # captured allocator: stale one inert
            raise
        with self._wd_lock:
            current = self._epoch == epoch
            if current:
                self._cache = new_cache
        if not current:
            # the watchdog restarted the engine while this (zombie) prefill
            # was on-device: its write landed in an abandoned cache — fail
            # the request typed rather than leave its future hanging
            if blocks is not None:
                alloc.free(blocks)
            req.trace.event("watchdog.restart", stale=True)
            if greq.handle._fail(WatchdogTimeoutError(
                    f"engine[{self.name}] restarted while this prompt was "
                    f"in prefill; resubmit")):
                self._finish_request(req.trace, "watchdog",
                                     tenant=req.tenant)
            # else: the watchdog delivered (and recorded) the terminal —
            # this zombie must not double-count the outcome
            return
        self._breaker.record_success()
        now = time.perf_counter()
        req.trace.event("prefill", dur_ms=round((now - t0) * 1e3, 3),
                        slot=slot, bucket=bucket, prompt=n)
        self.metrics.prefill_ms.observe((now - t0) * 1e3)
        self.metrics.prefill_wall_ms.inc((now - t0) * 1e3)
        if greq.resume_step == 0:
            # this IS the stream's first token — including a victim
            # preempted before it ever emitted one (resume_step 0):
            # its preemption-inflated TTFT is exactly what the
            # histogram must see. A resume_step > 0 stream's TTFT was
            # recorded at its original first token; never re-count.
            self.metrics.ttft_ms.observe((now - req.submit_t) * 1e3)
        self.metrics.prefills_total.inc()
        self.metrics.generated_tokens_total.inc()
        state = _Slot(greq=greq, request=req,
                      n_generated=greq.resume_step + 1, last_token=tok,
                      length=n, blocks=blocks, n_entries=nb_total,
                      resumed=resumed)
        if greq.capture_pages and blocks is not None \
                and self._retire_reason(state, tok) is not None:
            # page export for a stream retiring AT its first token (the
            # disaggregation prefill stage runs max_new_tokens=1): must
            # happen BEFORE _push/_maybe_retire resolve the future — the
            # orchestrator reads captured_entry the moment result()
            # returns
            used = blocks_for_tokens(n, self.block_size)
            if 0 < used <= nb_total:
                with self._wd_lock:
                    if self._epoch == epoch:
                        # analysis: ok lock-discipline — the device_get
                        # must finish before the retire tail frees these
                        # blocks to another stream (same contract as the
                        # swap-out copy); the read is bounded (one
                        # stream's used blocks) and epoch-atomic
                        self._capture_pages(
                            req, np.asarray(blocks[:used], np.int32),
                            n, state.n_generated, tok, epoch)
        err = greq.handle._push(tok)
        if err is not None:
            # broken on_token consumer failed its own stream at token 0:
            # the handle delivered the terminal — record it (client_error:
            # the caller's callback raised, not the model), never tenant
            req.trace.event("on_token.failed", error=type(err).__name__)
            self._finish_request(req.trace, "client_error",
                                 tenant=req.tenant)
            if blocks is not None:
                alloc.free(blocks)
                state.blocks = None
            return
        if not self._maybe_retire(state, tok):
            registered = False
            with self._wd_lock:
                # re-check: a restart between the cache writeback and here
                # reset the cache, so this tenant's K/V no longer exists —
                # registering it would decode garbage. The watchdog already
                # failed its handle (it was the in-flight prefill).
                if self._epoch == epoch:
                    if self.paged:
                        self._tables[slot] = row
                    self._slots[slot] = state
                    registered = True
            if not registered and blocks is not None:
                alloc.free(blocks)
                state.blocks = None
            if registered and self._spec is not None:
                # warm the DRAFT cache for the freshly seated stream
                # (scheduler thread — the draft prefill donates the draft
                # cache like the decode loop donates the target's).
                # DEGRADE contract: failure leaves the slot draft-cold
                # (acceptance-zero speculation), never fails the stream
                self._draft_seat(slot, state, padded, epoch)
        elif blocks is not None:
            # retired at token 0 (EOS / max_new_tokens=1): the slot was
            # never seated, return its reservation now
            alloc.free(blocks)
            state.blocks = None
        if self.paged:
            self._update_block_gauges()

    def _draft_seat(self, slot: int, state: _Slot, padded: np.ndarray,
                    epoch: int):
        """Draft-prefill a just-seated stream's prompt into the draft
        cache (speculative engines only). Any failure takes the DEGRADE
        path: the draft cache is rebuilt (the donated call may have
        consumed it), every live slot goes draft-cold, and the stream
        itself proceeds at plain speed — a dead draft never sheds."""
        if padded.shape[1] > self._draft_cfg.max_seq:
            return   # bucket exceeds the draft's positional table: cold
        dcache = self._draft_cache
        try:
            new = self._donated_call(
                "generation.draft_prefill", self._draft_prefill,
                self._draft_params, dcache, padded, np.int32(slot))
        except BaseException as e:
            self._draft_breaker.record_failure()
            self.metrics.spec_fallbacks_total.inc()
            self._recorder.record("spec.draft_failure", engine=self.name,
                                  point="generation.draft_prefill",
                                  error=type(e).__name__)
            with self._wd_lock:
                if self._epoch == epoch:
                    self._reset_draft_cache()
                    for st in self._slots:
                        if st is not None:
                            st.draft_len = -1
            return
        with self._wd_lock:
            if self._epoch != epoch:
                return   # zombie: the replacement rebuilt its own cache
            self._draft_cache = new
            state.draft_len = state.length

    def _make_step_buffers(self) -> Dict[str, np.ndarray]:
        """Preallocate one scheduler thread's decode-step staging arrays
        — every per-slot argument the fixed-shape decode executable takes,
        shaped by engine config (slots), never by any request. Refilled
        in place each iteration by :meth:`_decode_iteration`."""
        S = self.slots
        buf = {"tokens": np.zeros(S, np.int32),
               "live": np.zeros(S, bool),
               "keys": np.zeros((S, 2), np.uint32),
               "steps": np.zeros(S, np.int32),
               "temps": np.zeros(S, np.float32),
               "top_ks": np.zeros(S, np.int32),
               "lengths": np.zeros(S, np.int32),
               "cow_src": np.zeros(S, np.int32),
               "cow_dst": np.zeros(S, np.int32)}
        if self.paged:
            buf["tables"] = np.zeros((S, self.max_blocks_per_slot),
                                     np.int32)
        if self._spec is not None:
            buf["spec_tokens"] = np.zeros((S, self._spec.k + 1), np.int32)
            buf["draft_feed"] = np.zeros(S, np.int32)
        return buf

    def _decode_iteration(self, epoch: int, buf: Dict[str, np.ndarray]):
        """One scheduler turn: a single fixed-shape decode_step over ALL
        slots, then stream/retire per live slot. Paged additions: host
        block tables + lengths ride in as the gather index, a pending CoW
        copy runs inside the executable via cow_src/cow_dst (cleared
        after the step lands), and shared-prefix streams still feeding
        their prompt suffix get the NEXT suffix token embedded — their
        mid-prompt samples are discarded until the suffix is consumed,
        at which point the step's sample is generated token 0.

        ``buf`` is the calling scheduler thread's preallocated staging
        set (:meth:`_make_step_buffers`): zeroed and refilled in place —
        the previous step's dispatch completed when its sampled tokens
        were read back, so the arrays are free to reuse."""
        S = self.slots
        if self.paged and self.allocate == "on_demand":
            # on-demand block growth: every live slot whose next write
            # crosses a block boundary gets one fresh block mapped into
            # its (fixed-width) table row — preempting residents when
            # the pool is dry. Runs BEFORE the slot snapshot: a stream
            # preempted here must not be staged into this step.
            if not self._grow_block_tables(epoch):
                return   # epoch staled mid-growth: the restart owns it
        tokens, live, keys = buf["tokens"], buf["live"], buf["keys"]
        steps, temps, top_ks = buf["steps"], buf["temps"], buf["top_ks"]
        lengths = buf["lengths"]
        cow_src, cow_dst = buf["cow_src"], buf["cow_dst"]
        with self._phase("serving.decode.stage"):
            for a in (tokens, live, keys, steps, temps, top_ks, lengths,
                      cow_src, cow_dst):
                a.fill(0)
            n_live = 0
            # snapshot the slot table: after a watchdog restart the live
            # list belongs to the replacement scheduler (possibly
            # re-tenanted), and this thread must only ever touch the
            # tenants IT dispatched
            states = list(self._slots)
            for i, st in enumerate(states):
                if st is None:
                    continue
                n_live += 1
                tokens[i] = st.pending[0] if st.pending else st.last_token
                live[i] = True
                keys[i] = st.greq.key
                steps[i] = st.n_generated
                temps[i] = st.greq.temperature
                top_ks[i] = st.greq.top_k
                lengths[i] = st.length
                if st.cow is not None:
                    cow_src[i], cow_dst[i] = st.cow
            self.metrics.slot_occupancy.set(n_live / S)
            # snapshot the cache binding: if the watchdog restarts the
            # engine mid-step, this (zombie) call must keep donating the
            # OLD cache — re-reading self._cache after a restart would
            # consume the replacement scheduler's live buffers. The
            # block-table snapshot rides beside it for the same reason
            # (copied into this thread's own staging buffer: self._tables
            # is replaced on rebuild, and the replacement scheduler
            # mutates only ITS buffer set).
            cache = self._cache
            tables = None
            if self.paged:
                tables = buf["tables"]
                np.copyto(tables, self._tables)
        if self._spec is not None and not self._spec_force_plain \
                and self._spec_turn(epoch, buf, states, n_live):
            return
        t0 = time.perf_counter()
        with self.profiler.span("serving.decode_step", engine=self.name,
                                live=n_live, slots=S, step=self._iteration):
            def call():
                if self.paged:
                    return self._donated_call(
                        "generation.decode_step", self._decode,
                        self.params, cache, tables, lengths, tokens, keys,
                        steps, temps, top_ks, cow_src, cow_dst)
                return self._donated_call(
                    "generation.decode_step", self._decode,
                    self.params, cache, tokens, live, keys, steps,
                    temps, top_ks)

            with self._phase("serving.decode.dispatch",
                             "serving.decode_step"):
                new_cache, toks = self._retry_call(call)
            with self._phase("serving.decode.readback",
                             "serving.decode_step"):
                toks = np.asarray(toks)   # the host waits for the device
            if self.screen_outputs:
                # raises BEFORE the cache writeback: a poisoned iteration
                # takes the fail-tenants + rebuild path, never re-tenants
                # over the (possibly poisoned) cache
                self._screen_token_ids(toks, "generation.decode_step",
                                       live=live)
        with self._wd_lock:
            current = self._epoch == epoch
            if current:
                self._cache = new_cache
        if not current:
            return   # zombie: tenants were already failed typed on restart
        self._breaker.record_success()
        now = time.perf_counter()
        dt_ms = (now - t0) * 1e3
        with self._phase("serving.decode.commit") as did:
            self.metrics.decode_step_ms.observe(dt_ms)
            self.metrics.decode_wall_ms.inc(dt_ms)
            self.metrics.decode_steps_total.inc()
            self.metrics.live_slot_steps_total.inc(n_live)
            emitted = retired = 0
            for i, st in enumerate(states):
                if st is None:
                    continue
                res = self._commit_sampled(i, st, int(toks[i]), epoch, dt_ms,
                                           now)
                if res == "stale":
                    return
                emitted += res != "fed"
                retired += res in ("retired", "client_error")
            self.metrics.generated_tokens_total.inc(emitted)
            # re-read after retirement so an engine that drains to idle
            # shows its true occupancy instead of the pre-retire value
            # forever
            self.metrics.slot_occupancy.set(self._live_count() / S)
            if self.paged:
                self._update_block_gauges()
            did["emitted"], did["retired"] = emitted, retired

    def _commit_sampled(self, i: int, st: _Slot, tok: int, epoch: int,
                        dt_ms: float, now: float) -> str:
        """Commit ONE sampled token to slot ``i`` — the per-slot tail of
        :meth:`_decode_iteration`, split out so the speculative commit
        walk can apply it once per ACCEPTED token with identical
        semantics (length/pending/retire accounting, page capture,
        tracing, stream push). Returns ``"stale"`` (epoch moved — the
        caller must abandon the whole iteration), ``"fed"`` (mid-suffix
        prompt feed, sample discarded), ``"ok"``, ``"retired"``, or
        ``"client_error"`` (the last three all emitted the token; the
        last two vacated the slot — a speculative walk must stop)."""
        reason = None
        fed_only = first_token = False
        with self._wd_lock:
            # serialize each slot-table touch with _watchdog_stall's
            # epoch bump (taken under this lock): the instant the
            # epoch moves, the replacement scheduler owns the table —
            # a re-tenanted slot i must not receive this step's token
            if self._epoch != epoch:
                return "stale"
            st.length += 1
            st.cow = None          # the copy landed with this step
            if st.pending:
                st.pending.popleft()
                if st.pending:
                    fed_only = True   # mid-suffix: discard the sample
                else:
                    first_token = True
            if not fed_only:
                st.n_generated += 1
                st.last_token = tok
                reason = self._retire_reason(st, tok)
                if reason is not None:
                    if st.greq.capture_pages and st.blocks is not None:
                        # decode-feed retirement (prefix/cache-hit
                        # seat, EOS at token 0): export the written
                        # pages while the blocks are still
                        # referenced, under the same epoch lock that
                        # frees them (st.length counts written
                        # positions; the retiring token's K/V was
                        # never written — swap-out semantics)
                        used = blocks_for_tokens(st.length,
                                                 self.block_size)
                        if 0 < used <= st.n_entries:
                            # analysis: ok lock-discipline — the
                            # device_get must finish before
                            # _clear_slot frees these blocks to
                            # another stream (swap-out's contract);
                            # bounded read, epoch-atomic
                            self._capture_pages(
                                st.request,
                                np.asarray(self._tables[i][:used],
                                           np.int32),
                                st.length, st.n_generated, tok, epoch)
                    self._maybe_cache_retired(i, st)
                    self._clear_slot(i, st)  # freed for NEXT admission
        if fed_only:
            st.request.trace.event("prompt.feed", slot=i,
                                   remaining=len(st.pending))
            return "fed"
        if first_token and st.greq.resume_step == 0:
            # prefix/feed streams have no prefill: token 0 lands
            # here — including a victim preempted mid-feed before
            # any token (resume_step 0), whose preemption-inflated
            # TTFT must still be observed exactly once. A
            # resume_step > 0 feed's "first" token is mid-stream;
            # its TTFT was recorded at the original first token.
            self.metrics.ttft_ms.observe(
                (now - st.request.submit_t) * 1e3)
        st.request.trace.event("decode.step", step=st.n_generated - 1,
                               dur_ms=round(dt_ms, 3), slot=i, token=tok)
        err = st.greq.handle._push(tok)
        if err is not None:
            # broken on_token consumer: the handle delivered the
            # terminal — retire the slot now (no point decoding a dead
            # stream) and record the one outcome
            st.request.trace.event("on_token.failed",
                                   error=type(err).__name__)
            if reason is None:
                with self._wd_lock:
                    if self._epoch == epoch and self._slots[i] is st:
                        self._clear_slot(i, st)
            self._finish_request(st.request.trace, "client_error",
                                 tenant=st.request.tenant)
            return "client_error"
        if reason is not None:
            self._finish_stream(st, reason)
            return "retired"
        return "ok"

    # ------------------------------------------------- speculative decoding
    def _spec_turn(self, epoch: int, buf: Dict[str, np.ndarray],
                   states: List[Optional[_Slot]], n_live: int) -> bool:
        """One speculative scheduler turn: draft×k then ONE verify over
        all slots, committing each slot's accepted prefix. Returns True
        when this turn was handled (the caller skips the plain step);
        False degrades the turn to plain decode — draft breaker open, no
        draft-warm eligible slot, or the draft leg failed (the DEGRADE
        contract: a dead draft costs throughput, never correctness, and
        never sheds or stalls a stream).

        Eligibility is per slot: draft-WARM (``draft_len == length``), no
        pending prompt feed, and the tenant not k=0-demoted by the
        acceptance governor. Ineligible live slots still ride the
        fixed-shape verify — their proposal columns are garbage the
        exact-match acceptance never commits, so they advance exactly one
        token, like a plain turn. The commit walk reuses
        :meth:`_commit_sampled` per accepted token, so every stream is
        bitwise the plain-decode stream regardless of k.

        The verify dispatch is retried like decode (injected faults raise
        before the donated call); a real verify failure propagates to the
        loop stamped ``fault_point='generation.verify_step'`` and takes
        the fail-tenants + rebuild path."""
        spec = self._spec
        k = spec.k
        elig = [st is not None and not st.pending
                and st.draft_len == st.length
                and not self._spec_governor.demoted(st.request.tenant)
                for st in states]
        if not any(elig):
            return False
        if not self._draft_breaker.allow():
            self.metrics.spec_fallbacks_total.inc()
            return False
        # ---- draft leg: k proposals per slot, one executable call each.
        # NOT retried — the draft is optional work, and the degrade path
        # is strictly cheaper than a retry storm on a sick draft
        dtoks = buf["spec_tokens"]
        dtoks[:, 0] = buf["tokens"]
        feed = buf["draft_feed"]
        np.copyto(feed, buf["tokens"])
        dcache = self._draft_cache
        try:
            with self.profiler.span("serving.draft_step",
                                    engine=self.name, live=n_live, k=k):
                for j in range(k):
                    dcache, props = self._donated_call(
                        "generation.draft_step", self._draft_step,
                        self._draft_params, dcache, feed,
                        buf["lengths"] + np.int32(j), buf["keys"],
                        buf["steps"] + np.int32(j), buf["temps"],
                        buf["top_ks"])
                    props = np.asarray(props)
                    if self.screen_outputs:
                        self._screen_token_ids(
                            props, "generation.draft_step",
                            live=np.asarray(elig))
                    dtoks[:, j + 1] = props
                    np.copyto(feed, props)
        except BaseException as e:
            self._draft_breaker.record_failure()
            self.metrics.spec_fallbacks_total.inc()
            self._recorder.record("spec.draft_failure", engine=self.name,
                                  point="generation.draft_step",
                                  error=type(e).__name__)
            with self._wd_lock:
                if self._epoch == epoch:
                    # the failed call may have consumed the donated draft
                    # cache; rebuild it and mark every stream cold — they
                    # keep decoding at plain speed
                    self._reset_draft_cache()
                    for st in states:
                        if st is not None:
                            st.draft_len = -1
            return False
        with self._wd_lock:
            if self._epoch != epoch:
                return True   # zombie: replacement owns its own caches
            self._draft_cache = dcache
        self._draft_breaker.record_success()
        # ---- verify leg: ONE fixed-shape executable scores k+1
        # positions per slot and counts each accepted prefix on device
        t0 = time.perf_counter()
        cache = self._cache
        tables = buf["tables"]   # this pass's snapshot (serving.decode.stage)
        try:
            with self.profiler.span("serving.verify_step",
                                    engine=self.name, live=n_live,
                                    slots=self.slots, k=k):
                def call():
                    return self._donated_call(
                        "generation.verify_step", self._verify,
                        self.params, cache, tables, buf["lengths"], dtoks,
                        buf["keys"], buf["steps"], buf["temps"],
                        buf["top_ks"], buf["cow_src"], buf["cow_dst"])

                new_cache, samples, accepted = self._retry_call(call)
                samples = np.asarray(samples)
                accepted = np.asarray(accepted)
                if self.screen_outputs:
                    self._screen_token_ids(samples,
                                           "generation.verify_step",
                                           live=buf["live"])
        except BaseException as e:
            try:
                e.fault_point = "generation.verify_step"
            except Exception:
                pass   # exotic __slots__ exception: generic dump label
            raise
        with self._wd_lock:
            current = self._epoch == epoch
            if current:
                self._cache = new_cache
        if not current:
            return True   # zombie: tenants already failed on restart
        self._breaker.record_success()
        now = time.perf_counter()
        dt_ms = (now - t0) * 1e3
        self.metrics.decode_step_ms.observe(dt_ms)
        self.metrics.decode_wall_ms.inc(dt_ms)
        self.metrics.decode_steps_total.inc()
        self.metrics.live_slot_steps_total.inc(n_live)
        # ---- commit walk: per slot, apply the plain-decode tail once
        # per accepted token. The commit count is capped by (a) the
        # device acceptance + 1 (the target's own next sample), (b) k
        # (sample k+1's K/V was never drafted — recomputed identically
        # next turn), and (c) the slot's VALIDLY WRITTEN positions
        # (writes past the mapped block entries or max_seq were
        # scratch-routed; committing them would stand on garbage)
        B = self.block_size
        emitted = 0
        for i, st in enumerate(states):
            if st is None:
                continue
            if elig[i]:
                cap = max(1, min(st.n_entries * B, self.cfg.max_seq)
                          - st.length)
                c = min(int(accepted[i]) + 1, k, cap)
                self.metrics.record_spec_outcome(
                    st.request.tenant, k, int(accepted[i]))
                self._spec_governor.record(
                    st.request.tenant, k, int(accepted[i]))
            else:
                c = 1   # cold/demoted/pending: exactly a plain turn
            res = "ok"
            for j in range(c):
                res = self._commit_sampled(i, st, int(samples[i, j]),
                                           epoch, dt_ms, now)
                if res == "stale":
                    return True
                if res != "fed":
                    emitted += 1
                if res in ("retired", "client_error"):
                    break
            if elig[i] and res == "ok":
                # the draft wrote positions length..length+k-1 this turn
                # and we committed c <= k of them: its cache is exactly
                # as long as the stream again — still warm
                with self._wd_lock:
                    if self._epoch == epoch and self._slots[i] is st:
                        st.draft_len = st.length
        self.metrics.generated_tokens_total.inc(emitted)
        self.metrics.slot_occupancy.set(self._live_count() / self.slots)
        self._update_block_gauges()
        return True

    def _retire_reason(self, st: _Slot, tok: int) -> Optional[str]:
        """Pure retirement decision — EOS or the token budget — split from
        the side effects so the decode tail can take it under _wd_lock."""
        if st.greq.eos_id is not None and tok == st.greq.eos_id:
            return "eos"
        if st.n_generated >= st.greq.max_new_tokens:
            return "max_tokens"
        return None

    def _finish_stream(self, st: _Slot, reason: str):
        delivered = st.greq.handle._finish(reason)
        self.metrics.generations_completed.inc()
        lat = (time.perf_counter() - st.request.submit_t) * 1e3
        self.metrics.latency_ms.observe(lat)
        st.request.trace.event("stream.finish", finish_reason=reason,
                               tokens=st.n_generated)
        if delivered:
            self._finish_request(st.request.trace, "ok", latency_ms=lat,
                                 tenant=st.request.tenant)
        else:
            # the terminal was already delivered elsewhere (watchdog win,
            # broken on_token) and its outcome recorded there — just make
            # sure the trace closes, labeled by the actual terminal
            try:
                exc = st.request.future.exception(timeout=0)
            except BaseException:
                exc = None   # cancelled future: exception() raises
            st.request.trace.finish(
                "cancelled" if exc is None else terminal_reason(exc),
                latency_ms=lat)

    def _maybe_retire(self, st: _Slot, tok: int) -> bool:
        """Retire a finished stream immediately — EOS or the token budget —
        so a long co-tenant never holds its slot hostage."""
        reason = self._retire_reason(st, tok)
        if reason is None:
            return False
        self._finish_stream(st, reason)
        return True

    def _release_blocks(self, st: _Slot):
        """Return a retired/failed stream's block references to the free
        list (paged only; idempotent — ``st.blocks`` is nulled). Callers
        on the decode/retire path hold ``_wd_lock`` with the epoch
        verified current, so a zombie's stale retire tail can never free
        a re-tenanted stream's blocks — it bails on the epoch check
        before reaching here (and after a rebuild the allocator object
        itself is fresh, so even a missed guard would hit a dead
        allocator, not live accounting)."""
        if not self.paged or st.blocks is None:
            return
        blocks, st.blocks = st.blocks, None
        self._allocator.free(blocks)

    def _clear_slot(self, i: int, st: _Slot):
        """Vacate slot ``i``: remove its tenant, free its blocks, and —
        critically — point its block-table row back at the scratch block.
        A dead slot still participates in every decode step (fixed-shape
        executable) and its write lands wherever its table row says: a
        stale row would aim that garbage write at freed blocks, which the
        very next admission may hand to a NEW stream. Caller holds
        ``_wd_lock`` with the epoch verified current."""
        self._slots[i] = None
        if self.paged:
            self._tables[i] = 0
        self._release_blocks(st)

    def _fail_live(self, exc: BaseException, epoch: Optional[int] = None):
        """Fail every live tenant typed and vacate their slots. Each slot
        is cleared under ``_wd_lock`` with the epoch re-verified: this
        runs OUTSIDE the lock (after _on_device_failure's check), so a
        watchdog restart can interleave — a stale walk must not evict the
        replacement scheduler's re-tenanted slot nor free old-pool block
        ids into the fresh allocator. Futures resolve outside the lock
        (set_exception runs done-callbacks synchronously)."""
        reason = terminal_reason(exc)
        victims: List[_Slot] = []
        for i in range(self.slots):
            with self._wd_lock:
                if epoch is not None and self._epoch != epoch:
                    break   # the restart owns the table; its stall hook
                    #         failed these tenants already
                st = self._slots[i]
                if st is None:
                    continue
                self._clear_slot(i, st)
            victims.append(st)
        for st in victims:
            if st.greq.handle._fail(exc):
                self._finish_request(st.request.trace, reason,
                                     tenant=st.request.tenant)

    # ------------------------------------------- ResilientEngineMixin hooks
    def _retry_traces(self):
        with self._wd_lock:
            if self._inflight_prefill is not None:
                return (self._inflight_prefill.trace,)
        return tuple(s.request.trace for s in list(self._slots)
                     if s is not None)

    def _crash_dump_model(self):
        return self.params

    def _crash_dump_context(self) -> dict:
        ctx = {"slots": self.slots, "live_slots": self._live_count()}
        if self.paged and self._allocator is not None:
            ctx.update(kv_blocks=self._allocator.num_blocks,
                       kv_blocks_free=self._allocator.free_count,
                       block_size=self.block_size)
        return ctx

    # ------------------------------------------------------------- watchdog
    def _watchdog_busy(self) -> bool:
        with self._wd_lock:
            if self._inflight_prefill is not None or self._prefix_busy:
                return True
        with self._prefix_lock:
            if self._pending_prefix:
                return True
        return self._live_count() > 0 or self._admission.depth_requests > 0

    def _watchdog_stall(self):
        """Recovery hook: the scheduler stopped heartbeating with work
        outstanding (wedged in a device call). Fail the in-prefill request
        and every live slot typed, rebuild the donated cache (the wedged
        call's eventual write is epoch-staled), and start a fresh
        scheduler over the preserved admission queue."""
        with self._wd_lock:
            self._epoch += 1
            epoch = self._epoch
            pre, self._inflight_prefill = self._inflight_prefill, None
        exc = WatchdogTimeoutError(
            f"engine[{self.name}] scheduler missed its heartbeat for "
            f">{self._watchdog.timeout_s * 1e3:.0f} ms; live generations "
            f"failed, scheduler restarted")
        failed = 0
        if pre is not None:
            pre.trace.event("watchdog.restart", epoch=epoch, in_prefill=True)
            if pre.x.handle._fail(exc):
                self._finish_request(pre.trace, "watchdog",
                                     tenant=pre.tenant)
            failed += 1
        for i, st in enumerate(self._slots):
            if st is not None:
                st.request.trace.event("watchdog.restart", epoch=epoch,
                                       slot=i)
                if st.greq.handle._fail(exc):
                    self._finish_request(st.request.trace, "watchdog",
                                         tenant=st.request.tenant)
                self._slots[i] = None
                # blocks are not individually freed here: _reset_cache
                # below rebuilds the whole allocator (and block tables)
                # into one consistent empty state; nulling the refs keeps
                # any straggling release idempotent
                if st.blocks is not None:
                    st.blocks = None
                failed += 1
        if failed:
            self.metrics.failed_total.inc(failed)
        self.metrics.watchdog_restarts.inc()
        self.metrics.record_rejection("watchdog")
        self._recorder.record("watchdog.restart", engine=self.name,
                              epoch=epoch, victims=failed)
        self.metrics.slot_occupancy.set(0.0)
        self._breaker.record_failure()
        self._reset_cache()
        self._thread = threading.Thread(
            target=self._loop, args=(epoch,),
            name=f"generation-scheduler[{self.name}]#{epoch}", daemon=True)
        self._thread.start()

    # -------------------------------------------------------------- insight
    def compiled_signatures(self) -> int:
        """Live compiled-executable count across the whole generation path:
        bounded by ``len(self.buckets) + 1`` (prefill ladder + the single
        decode step) for the engine's lifetime — ``+ 2`` when
        ``speculative`` is set (the single verify step rides beside the
        decode fallback; the draft model's own executables are counted
        separately by :meth:`draft_compiled_signatures`)."""
        from deeplearning4j_tpu.serving.registry import _jit_cache_size

        return (_jit_cache_size(self._prefill) or 0) + \
            (_jit_cache_size(self._decode) or 0) + \
            ((_jit_cache_size(self._verify) or 0)
             if self._spec is not None else 0)

    def draft_compiled_signatures(self) -> int:
        """DRAFT-side compiled-executable count (0 for non-speculative
        engines): bounded by ``len(self.buckets) + 1`` — the draft
        prefill ladder (compiled lazily per bucket as streams seat) plus
        THE single draft step, mirroring the target's own bound."""
        if self._spec is None:
            return 0
        from deeplearning4j_tpu.serving.registry import _jit_cache_size

        return (_jit_cache_size(self._draft_prefill) or 0) + \
            (_jit_cache_size(self._draft_step) or 0)

    @property
    def queue_depth(self) -> int:
        return self._admission.depth_requests

    @property
    def live_slots(self) -> int:
        return self._live_count()

    def ledger_stats(self) -> dict:
        """Point-in-time resource accounting for the zero-leak ledger
        (serving/ledger.py): every countable thing this engine can hold
        — resident slots, queued requests, KV blocks by attribution
        (free / explicit pins / automatic cache), swap-store residency.
        Reads only; each lock is taken briefly on its own (leaf-lock
        hygiene), so the soak orchestrator can poll this under load."""
        stats = {"name": self.name,
                 "live_slots": self._live_count(),
                 "queue_depth": self._admission.depth_requests}
        with self._wd_lock:
            alloc = self._allocator
            store = self._swap_store
            cache = self._prefix_cache
        if alloc is not None:
            stats["kv_capacity_blocks"] = alloc.capacity
            stats["kv_free_blocks"] = alloc.free_count
            stats["kv_blocks_in_use"] = alloc.in_use
        if store is not None:
            stats["swap_entries"] = len(store)
            stats["swap_blocks_held"] = store.blocks_held
        if cache is not None:
            stats["kv_prefix_cache_blocks"] = cache.total_blocks
        with self._prefix_lock:
            stats["pinned_prefixes"] = len(self._prefixes)
            stats["kv_pinned_blocks"] = sum(
                len(p.blocks) for p in self._prefixes.values() if p.blocks)
        return stats

    def warmup(self) -> "GenerationEngine":
        """Compile every prefill bucket + the decode executable up front by
        generating one short throwaway stream per bucket (token id 0
        prompts) — after warmup, live traffic never pays XLA compilation
        inline. Each rung is probed with the SHORTEST prompt that maps to
        it, so even a top rung that only admits near-max_len prompts (no
        room for 2 generated tokens) still compiles, via a 1-token
        stream."""
        prev = 0
        self._cache_bypass = True   # every rung must actually PREFILL —
        #   an automatic-prefix-cache hit on an earlier rung's retired
        #   blocks would route the probe through the decode-feed path
        #   and leave that rung's prefill uncompiled
        try:
            for b in self.buckets:
                n, prev = prev + 1, b
                new = min(2, self.max_len - n)
                if new < 1:
                    continue   # rung admits no prompt at all (n == max_len)
                # eos_id=None: an engine-level eos_id matching the warmup
                # continuation would retire every stream at prefill and
                # leave the decode executable uncompiled
                self.generate(np.zeros(n, np.int32), max_new_tokens=new,
                              eos_id=None, timeout=300.0)
        finally:
            self._cache_bypass = False
            if self._prefix_cache is not None:
                # drop the probes' retired blocks: zero-token warmup
                # prompts must not squat the bounded LRU (or match real
                # traffic). The cache locks internally, and a racing
                # match_and_ref holds its own block refs — no torn state
                self._prefix_cache.release_all()
        if self._spec is not None and self.max_len >= 2:
            # speculative engines compiled draft prefill/step + verify
            # through the rungs above, but never the PLAIN decode
            # fallback — and a draft breaker opening under live load must
            # not pay XLA inline at the worst possible moment. One
            # forced-plain probe compiles it now.
            self._spec_force_plain = True
            try:
                self.generate(np.zeros(1, np.int32),
                              max_new_tokens=min(2, self.max_len - 1),
                              eos_id=None, timeout=300.0)
            finally:
                self._spec_force_plain = False
        return self


def client_stream_handle(prompt_len: int,
                         on_token: Optional[Callable[[int], None]] = None,
                         tenant: str = None) -> GenerationHandle:
    """A :class:`GenerationHandle` backed by NO local scheduler — the
    client half of a cross-host stream bridge (serving/rpc.py and the
    front door's hedging supervisor in serving/cluster.py). The bridge
    delivers through the same scheduler-side hooks the engine uses —
    ``_push`` per token, ``_finish``/``_fail`` exactly-once at the
    terminal — so ``result()``/``stream()``/``tokens_so_far()``/
    ``on_token`` behave identically whether the tokens were decoded in
    this process or long-polled off a remote host. The underlying
    admission Request exists only to carry the future and tenant label;
    it never enters a queue."""
    from deeplearning4j_tpu.serving.admission import DEFAULT_TENANT

    req = Request(x=None, rows=1,
                  tenant=tenant if tenant is not None else DEFAULT_TENANT)
    return GenerationHandle(req, prompt_len, on_token=on_token)


__all__ = ["GenerationEngine", "GenerationHandle", "GenerationRequest",
           "SpecConfig", "client_stream_handle", "prefill_buckets"]
