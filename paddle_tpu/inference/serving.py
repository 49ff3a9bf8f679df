"""Continuous-batching serving engine over the paged KV cache.

reference capability: the serving loop the reference builds around
block_multihead_attention (paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu + incubate/nn/functional/
block_multihead_attention.py): block tables, iteration-level scheduling,
in-flight admission of new sequences while others decode.

TPU-native design (round 9: fused multi-token decode): TWO compiled
program families serve every request mix.

  - chunked prefill: a prompt is split into fixed-width chunks; each
    chunk forward writes its K/V into the paged pool (multi-token
    scatter) and attends over all previously cached positions, so a
    1024-token prompt interleaves with decode steps instead of
    head-of-line-blocking every active lane. One compiled program per
    chunk width.
  - fused K-step decode: ONE `lax.scan` advances all lanes
    `decode_steps` tokens per dispatch — on-device greedy argmax (and
    on-device per-lane categorical sampling for sampled lanes),
    on-device paged-cache writes, on-device EOS/length masking —
    returning a [B, K] token tile instead of one token per host
    round-trip.

Lane state (block tables, seq lens, next-token ids, alive mask, sampling
knobs) is DEVICE-RESIDENT: uploaded only when lane membership changes
(admission / retire / shed), never rebuilt from numpy in the steady
state (`serving_lane_state_uploads_total` counts refreshes). Dispatch is
double-buffered: tile N+1 is enqueued before tile N's tokens are read
back, so host bookkeeping overlaps device compute
(`serving_dispatch_ahead_depth`, `serving_hostsync_seconds`).

Memory is allocated in block_size granules from one (L, num_blocks, ...)
pool — no per-sequence max-length reservation, exactly the property the
reference's block attention exists for.
"""

from __future__ import annotations

import os
import time
import warnings
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from ..generation import (_llama_layer_prefill_chunk, _llama_mlp, _rms,
                          _rope)
from .adapters import AdapterLoadError
from ..observability import span as _span
from ..observability.catalog import metric as _metric
from ..observability.metrics import get_registry as _get_registry
from ..observability.recorder import get_recorder as _get_recorder
from ..observability.tracing import LANE_TID_BASE
from ..observability.tracing import get_tracer as _get_tracer
from ..observability.tracing import new_trace_id as _new_trace_id
from ..ops.paged_attention import (KVBlockFormat, kv_rollback_tokens,
                                   kv_write_token, kv_write_tokens,
                                   paged_attention_decode_inner,
                                   paged_attention_verify, write_to_cache)
from ..profiler.phases import get_phase_accountant as _get_phases
from ..resilience.faults import FaultInjected, fault_point
from .prefix_cache import PrefixCacheIndex
from .scheduler import PRIORITY_CLASSES, SLOScheduler

__all__ = ["ContinuousBatchingEngine", "Request", "BackpressureError",
           "KVPoolExhaustedError"]

# exception classes that mean "transient trouble, retry next step" when
# they surface from an admission / prefill-chunk / host-sync seam
_TRANSIENT_ERRORS = (TimeoutError, ConnectionError, OSError, FaultInjected)


class BackpressureError(RuntimeError):
    """add_request refused: the admission queue is at max_queue. The
    caller (gateway/load balancer) should retry later or route away —
    that is the backpressure signal, instead of unbounded queueing."""


class KVPoolExhaustedError(MemoryError):
    """The paged KV pool has no free block for a reservation. A typed
    MemoryError subclass so the existing shed/defer-on-MemoryError paths
    keep working while callers (and the metrics catalog:
    serving_pool_exhausted_total) can tell pool pressure apart from a
    real device OOM."""


class Request:
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "generated", "done", "do_sample", "temperature", "top_k",
                 "top_p", "rng", "sample_seed", "t_arrival", "deadline_s",
                 "t_deadline", "finish_reason", "shed_count", "trace_id",
                 "tenant", "priority", "t_first", "adapter", "adapter_id")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 seed=None, deadline_s=None, tenant="-",
                 priority="interactive", adapter=None):
        self.rid = rid
        # named LoRA adapter (round 22) — None/"" = the base model.
        # adapter_id is the device pool slot, bound at admission by the
        # engine's AdapterStore (0 = base, an exact-zeros delta).
        self.adapter = str(adapter) if adapter else None
        self.adapter_id = 0
        # per-tenant telemetry label; "-" = unattributed (the default
        # keeps every pre-tenant caller's label sets unchanged)
        self.tenant = str(tenant) if tenant else "-"
        # scheduling class (closed registry: scheduler.PRIORITY_CLASSES);
        # validated at add_request, defaulted here so direct Request
        # construction in tests keeps working
        self.priority = priority
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.generated: list[int] = []
        self.done = False
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        # None -> OS entropy: concurrent sampled requests must differ by
        # default; a fixed seed is the explicit-reproducibility opt-in.
        # The same seed feeds the host RandomState (first token, sampled
        # at prefill) and the device per-lane PRNG lane key (decode
        # tokens, folded with the absolute position so the stream is
        # identical no matter how decode steps are tiled).
        self.rng = np.random.RandomState(seed)
        self.sample_seed = (np.uint32(seed & 0xFFFFFFFF)
                            if seed is not None else
                            np.uint32(int.from_bytes(os.urandom(4),
                                                     "little")))
        self.t_arrival = time.perf_counter()   # TTFT anchor
        self.t_first = None                    # first-token wall time
        # degraded completions are distinguishable: finish_reason is one
        # of eos / length / timeout / shed / rejected (None while live)
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.t_deadline = (None if deadline_s is None
                           else self.t_arrival + float(deadline_s))
        self.finish_reason = None
        self.shed_count = 0
        # joins this request's spans, histogram exemplars, and flight-
        # recorder events; generated unconditionally (one f-string) so a
        # request is correlatable even if tracing turns on mid-flight
        self.trace_id = _new_trace_id("req-")

    def choose(self, logits: np.ndarray) -> int:
        """Per-request next-token choice on the host — used for the
        FIRST token only (sampled once per request at prefill; decode
        tokens are chosen on device inside the fused scan). Semantics:
        temperature -> top-k -> nucleus filter -> categorical."""
        if not self.do_sample:
            return int(np.argmax(logits))
        z = logits.astype(np.float64) / max(self.temperature, 1e-6)
        if self.top_k > 0:
            kth = np.sort(z)[-min(self.top_k, z.size)]
            z = np.where(z < kth, -np.inf, z)
        if self.top_p < 1.0:
            p = np.exp(z - np.max(z))
            p /= p.sum()
            order = np.argsort(-p)
            cum = np.cumsum(p[order])
            keep_sorted = (cum - p[order]) < self.top_p
            keep_sorted[0] = True  # top_p=0 must still keep the argmax
            keep = np.zeros_like(keep_sorted)
            keep[order] = keep_sorted
            z = np.where(keep, z, -np.inf)
        p = np.exp(z - np.max(z))
        p /= p.sum()
        return int(self.rng.choice(p.size, p=p))


class _LayeredBlockPool:
    """Block allocator over a (L, num_blocks, block_size, KVH, D) pool.
    One block-id table per sequence, shared by all layers.

    Round 18: blocks are REFCOUNTED. A block's references are (a) each
    request whose table holds it and (b) an optional prefix-cache pin
    (`pin`/`unpin`) that keeps a prompt-prefix block resident after its
    request retires. `release` decrements instead of freeing, so two
    requests sharing a system-prompt prefix return the block exactly
    once — when the last holder lets go. Shared blocks are only ever
    READ (prompt positions are immutable after prefill; decode and
    speculative writes land at positions >= the prompt length, i.e. in
    later, private blocks); the one write that can land inside a shared
    block — the >=1-token prefill tail of a block-aligned full-prefix
    match — goes through `fork_cow` first."""

    def __init__(self, num_layers, num_blocks, block_size, kv_heads,
                 head_dim, dtype, fmt=None):
        self.block_size = block_size
        self.num_blocks = num_blocks
        # storage format of the blocks (round 11): quantized formats hold
        # int8/fp8 payloads plus a parallel per-(token, head) scale pool;
        # passthrough formats ARE the pre-round-11 pool, byte-identical
        self.fmt = fmt if fmt is not None else KVBlockFormat(
            "native", native_dtype=dtype)
        store = self.fmt.store_dtype if self.fmt.quantized else dtype
        shape = (num_layers, num_blocks, block_size, kv_heads, head_dim)
        self.k = jnp.zeros(shape, store)
        self.v = jnp.zeros(shape, store)
        if self.fmt.quantized:
            sshape = (num_layers, num_blocks, block_size, kv_heads)
            self.k_scale = jnp.zeros(sshape, self.fmt.scale_dtype)
            self.v_scale = jnp.zeros(sshape, self.fmt.scale_dtype)
        else:
            self.k_scale = self.v_scale = None
        # the LAST block is the scratch target for inactive decode lanes:
        # every lane writes its token's K/V unconditionally inside the
        # compiled step (no data-dependent skips), so masked lanes must
        # scribble somewhere no live sequence owns
        self.scratch_block = num_blocks - 1
        self._free = list(range(num_blocks - 2, -1, -1))
        self.tables: dict[int, list[int]] = {}
        # block id -> reference count; absent == free (on self._free)
        self._ref: dict[int, int] = {}

    def blocks_needed(self, n_tokens):
        return (n_tokens + self.block_size - 1) // self.block_size

    def can_fit(self, n_tokens, have=0):
        return len(self._free) >= self.blocks_needed(n_tokens) - have

    def _deref(self, b):
        n = self._ref.get(b, 1) - 1
        if n <= 0:
            self._ref.pop(b, None)
            self._free.append(b)
        else:
            self._ref[b] = n

    def ensure(self, rid, n_tokens):
        table = self.tables.setdefault(rid, [])
        need = self.blocks_needed(n_tokens)
        while len(table) < need:
            if not self._free:
                _metric("serving_pool_exhausted_total").inc()
                raise KVPoolExhaustedError("paged KV pool exhausted")
            b = self._free.pop()
            self._ref[b] = 1
            table.append(b)
        return table

    def release(self, rid):
        for b in self.tables.pop(rid, []):
            self._deref(b)

    # --- cross-request prefix sharing (round 18) --------------------------
    def adopt(self, rid, blocks):
        """Start rid's table with already-resident shared blocks (a
        prefix-cache hit): each gains a reference; the tail of the table
        is filled by the usual ensure()."""
        table = self.tables.setdefault(rid, [])
        if table:
            raise ValueError(f"adopt on rid {rid} with a non-empty table")
        for b in blocks:
            self._ref[b] = self._ref.get(b, 0) + 1
            table.append(int(b))
        return table

    def pin(self, b):
        """Prefix-cache reference: keeps the block resident after its
        request retires."""
        self._ref[b] = self._ref.get(b, 0) + 1

    def unpin(self, b):
        """Drop a prefix-cache reference (index eviction / clear). The
        block frees only when no request still holds it."""
        self._deref(b)

    def shared_count(self, rid):
        """How many of rid's blocks are shared (refcount > 1) — the
        handoff manifest's shared-block marker."""
        return sum(1 for b in self.tables.get(rid, ())
                   if self._ref.get(b, 1) > 1)

    def fork_cow(self, rid, idx):
        """Copy-on-write: give rid a PRIVATE copy of table[idx] before a
        write lands in it. Device-copies the stored payload (and scales)
        byte-for-byte into a fresh block, swaps the table entry, and
        drops the old reference. No-op when the block is already
        private. Raises KVPoolExhaustedError when no free block exists
        (callers treat it like any reservation failure)."""
        old = self.tables[rid][idx]
        if self._ref.get(old, 1) <= 1:
            return old
        if not self._free:
            _metric("serving_pool_exhausted_total").inc()
            raise KVPoolExhaustedError(
                "paged KV pool exhausted (copy-on-write fork)")
        new = self._free.pop()
        self._ref[new] = 1
        self.k = self.k.at[:, new].set(self.k[:, old])
        self.v = self.v.at[:, new].set(self.v[:, old])
        if self.fmt.quantized:
            self.k_scale = self.k_scale.at[:, new].set(self.k_scale[:, old])
            self.v_scale = self.v_scale.at[:, new].set(self.v_scale[:, old])
        self.tables[rid][idx] = new
        self._deref(old)
        return new


class _PrefillTask:
    """A prompt being prefilled chunk-by-chunk: `pieces` is the
    precomputed (start, width) plan; the task owns its lane (the lane is
    occupied but NOT decode-active until the final chunk completes)."""

    __slots__ = ("req", "lane", "pieces", "idx")

    def __init__(self, req, lane, pieces):
        self.req = req
        self.lane = lane
        self.pieces = pieces
        self.idx = 0


class _Inflight:
    """One dispatched-but-unread decode tile: the [B, K] token tile
    future plus the lane snapshot (request refs + lane epochs) needed to
    credit tokens only to lanes whose occupancy did not change while the
    tile was in flight."""

    __slots__ = ("tile", "t_dispatch", "reqs", "epochs", "k", "covers_all",
                 "tile_id", "spec", "key")

    def __init__(self, tile, t_dispatch, reqs, epochs, k, covers_all,
                 tile_id=0, spec=False, key=None):
        self.tile = tile
        self.t_dispatch = t_dispatch
        self.reqs = reqs
        self.epochs = epochs
        self.k = k
        self.covers_all = covers_all
        self.tile_id = tile_id
        # speculative tiles are (tokens [B, K, D+1], counts [B, K]) pairs
        # instead of a [B, K] array; per-tile, not per-engine, so tiles
        # dispatched before a speculation-off degradation drain correctly
        self.spec = spec
        self.key = key      # compile_reports key of the dispatched program


class ContinuousBatchingEngine:
    """Iteration-level scheduler: admit -> fused decode tile -> retire.

    model: LlamaForCausalLM. Per-request decoding knobs (greedy default;
    do_sample with temperature/top_k/top_p + per-request seed) ride the
    device-resident lane state — mixed greedy/sampled lanes share one
    compiled fused decode step.

    Tuning knobs (PERF.md "Fused multi-token serving decode"):
      decode_steps: tokens every lane advances per dispatch (the K of
        the fused scan). 1 reproduces the old step-per-token engine.
      prefill_chunk: max prompt tokens per prefill chunk (default: the
        largest prefill bucket — prompts beyond it now chunk instead of
        being rejected).
      prefill_chunks_per_step: chunks advanced per engine step while
        decode lanes are active (back-to-back when none are).
      compat_step_loop: reproduce the pre-fused host-bound loop —
        decode_steps forced to 1, lane state rebuilt from numpy and
        re-uploaded EVERY step, every tile drained synchronously (no
        dispatch-ahead). The reference the fused-decode tests compare
        against, and a fully-synchronous debug mode (nothing in flight
        between steps).

    Round-11 knobs (PERF.md "Speculative decode + quantized KV"):
      speculative_decode: each fused scan step proposes draft_depth
        tokens from the drafter, verifies them in ONE batched forward
        and commits the accepted run plus a correction token — up to
        K*(draft_depth+1) tokens per dispatch, greedy streams
        byte-identical to the non-speculative path.
      draft_depth: draft tokens per scan step (clamped to block_size-1
        so one step's writes never alias within a block).
      draft_ngram: context length of the built-in n-gram/prompt-lookup
        drafter.
      drafter: pluggable draft hook `fn(hist, lens, toks, depth) ->
        [B, depth] int32`, traced inside the compiled program (a cheap
        draft model goes here); None = the built-in n-gram drafter.
      kv_cache_dtype: paged-pool block format — "bf16"/"native" (store
        the model dtype; the PR-5-identical pool), "int8", "fp8_e4m3",
        "fp8_e5m2" (quantized payloads + per-(token, head) scales,
        dequant fused into the attention reads).
      kv_pool_bytes: size the pool by HBM budget instead of num_blocks —
        int8 fits ~2x the lanes of bf16 in the same bytes (test-pinned
        >=1.9x).

    Round-14 knob (RESILIENCE.md "Overload runbook"):
      scheduler: the closed-loop SLO scheduler (scheduler.SLOScheduler)
        — priority classes with decode-lane preemption, per-tenant DRR
        fairness + lane quotas, and the reversible brownout ladder.
        None (default) = plain FIFO admission, exactly the
        pre-scheduler engine; True = an SLOScheduler with defaults; or
        pass a configured instance.

    Round-18 knobs (PERF.md "Prefix cache"):
      prefix_cache: cross-request prompt-prefix sharing (off by
        default — the pre-round-18 engine). Admission
        resolves the prompt's leading block-aligned chunks against a
        chained-hash index of already-resident paged-KV blocks; prefill
        runs only on the unmatched tail, shared blocks are refcounted,
        and the one write that could land in a shared block (the tail
        of a block-aligned full match) forks a private copy first
        (COW). Greedy/sampled streams are byte-identical with the
        cache on or off (test-pinned). Index failures degrade to a
        cache miss (serve.prefix_match fault site) — never a wrong
        stream.
      prefix_cache_blocks: optional cap on indexed blocks (LRU-evicted
        past it). None = bounded only by pool pressure: admission
        evicts LRU index entries before deferring on a full pool.
    """

    def __init__(self, model, num_blocks=256, block_size=16, max_batch=8,
                 max_blocks_per_seq=64,
                 prefill_buckets=(64, 128, 256, 512, 1024),
                 max_queue=None, max_sheds=2, decode_steps=4,
                 prefill_chunk=None, prefill_chunks_per_step=1,
                 compat_step_loop=False, speculative_decode=False,
                 draft_depth=2, draft_ngram=3, drafter=None,
                 kv_cache_dtype="bf16", kv_pool_bytes=None,
                 scheduler=None, prefix_cache=False,
                 prefix_cache_blocks=None, adapters=None):
        config = model.config
        self.cfg = dict(eps=config.rms_norm_eps, theta=config.rope_theta,
                        heads=config.num_attention_heads,
                        kv_heads=config.num_key_value_heads,
                        head_dim=(config.hidden_size //
                                  config.num_attention_heads))
        state = {k: v._data for k, v in model.state_dict().items()}
        from ..parallel.functional import split_stacked_layer_params
        self.stacked, other = split_stacked_layer_params(state)
        self.embed_w = other["llama.embed_tokens.weight"]
        self.norm_w = other["llama.norm.weight"]
        self.head_w = other.get("lm_head.weight")  # None == tied
        # tied models: transpose ONCE — passing embed_w.T per call would
        # re-materialize a (hidden, vocab) device array every token
        self._out_w = self.head_w if self.head_w is not None \
            else jnp.asarray(self.embed_w).T
        L = config.num_hidden_layers
        fmt = KVBlockFormat(kv_cache_dtype, native_dtype=self.embed_w.dtype)
        if kv_pool_bytes is not None:
            # size the pool by byte budget: blocks = budget / bytes-per-
            # block (k AND v, all layers, payload + scales) — the knob
            # that makes int8's ~2x lane capacity a measurable contract
            per_block = (L * block_size * 2 *
                         fmt.bytes_per_token(self.cfg["kv_heads"],
                                             self.cfg["head_dim"]))
            num_blocks = max(2, int(kv_pool_bytes) // per_block)
        self.pool = _LayeredBlockPool(L, num_blocks, block_size,
                                      self.cfg["kv_heads"],
                                      self.cfg["head_dim"],
                                      self.embed_w.dtype, fmt=fmt)
        self.max_batch = int(max_batch)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.buckets = tuple(sorted(prefill_buckets))
        self.compat_step_loop = bool(compat_step_loop)
        self.decode_steps = (1 if self.compat_step_loop
                             else max(1, int(decode_steps)))
        # speculative decode rides the fused scan; the compat loop is by
        # definition the pre-fused engine, so it never speculates
        self.spec = bool(speculative_decode) and not self.compat_step_loop
        # depth cap: one step writes draft_depth+1 contiguous slots per
        # lane; keeping that <= block_size guarantees the write and its
        # rollback never alias within a block
        self.draft_depth = max(1, min(int(draft_depth), block_size - 1))
        self.draft_ngram = max(2, int(draft_ngram))
        self._drafter = drafter
        self.chunk = int(prefill_chunk or self.buckets[-1])
        self.prefill_chunks_per_step = max(1, int(prefill_chunks_per_step))
        # chunk widths a prefill piece may compile at: every bucket that
        # fits inside a chunk, plus the chunk width itself (the tail
        # piece pads to the smallest width that fits)
        self._chunk_widths = sorted(
            {b for b in self.buckets if b <= self.chunk} | {self.chunk})
        # the largest width's prefill attention decision, kept for audit;
        # each width is decided by the same rule at prefill trace time
        from ..ops.pallas.attention_router import route
        self.attention_route = route(
            self.cfg["heads"], self._chunk_widths[-1],
            self._chunk_widths[-1], self.cfg["head_dim"],
            self.embed_w.dtype, True)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.max_sheds = int(max_sheds)
        self.lanes: list[Request | None] = [None] * self.max_batch
        self.lane_len = np.zeros(self.max_batch, np.int64)  # tokens in cache
        self.lane_tok = np.zeros(self.max_batch, np.int64)  # next to write
        # occupancy epoch per lane: bumped on every retire/shed/assign so
        # an in-flight tile can never credit tokens across an occupancy
        # change (the lane snapshot carries the epochs it was dispatched
        # under)
        self._lane_epoch = np.zeros(self.max_batch, np.int64)
        self.queue: deque[Request] = deque()
        self.finished: dict[int, Request] = {}
        self._next_rid = 0
        self._prefill_jit = {}                 # chunk width -> pir_jit
        self._prefill_tasks: dict[int, _PrefillTask] = {}
        self._decode_jit = {}                  # variant -> pir_jit
        # device-resident lane state (toks/lens/alive/rem/eos/tables +
        # sampling knobs); rebuilt from the host mirrors ONLY when
        # membership changes (self._dirty)
        self._dev = None
        self._dirty = True
        self._inflight: deque[_Inflight] = deque()
        # PIR compile pipeline reports per program (prefill.b<width> /
        # decode[.sampled]): cache hit/miss + pass stats — the engine
        # warm-start evidence tests read
        self.compile_reports: dict[str, object] = {}
        # observability handles bound ONCE (catalog names; no-op when the
        # layer is disabled — each call is a single flag check)
        self._m_ttft = _metric("serving_ttft_seconds")
        self._m_tpot = _metric("serving_tpot_seconds")
        self._m_prefill = _metric("serving_prefill_seconds")
        self._m_queue = _metric("serving_queue_depth")
        self._m_occ = _metric("serving_batch_occupancy")
        self._m_free = _metric("serving_kv_free_blocks")
        self._m_admitted = _metric("serving_admitted_total")
        self._m_retired = _metric("serving_retired_total")
        self._m_tokens = _metric("serving_tokens_total")
        self._m_uploads = _metric("serving_lane_state_uploads_total")
        self._m_dispatches = _metric("serving_decode_dispatches_total")
        self._m_ahead = _metric("serving_dispatch_ahead_depth")
        self._m_hostsync = _metric("serving_hostsync_seconds")
        self._m_hostsync_retries = _metric("serving_hostsync_retries_total")
        self._m_chunks = _metric("serving_prefill_chunks_total")
        self._m_draft = _metric("serving_draft_tokens_total")
        self._m_accept = _metric("serving_accepted_tokens_total")
        self._m_accept_rate = _metric("serving_spec_acceptance_rate")
        self._m_tok_disp = _metric("serving_tokens_per_dispatch")
        _metric("serving_preempted_total")  # incremented by _try_preempt
        # request-scoped telemetry handles, bound once; every hot-path
        # use is guarded by a single `.enabled` attribute check so the
        # disabled engine pays no allocation (kwargs pack at call sites)
        self._tracer = _get_tracer()
        self._reg = _get_registry()
        self._rec = _get_recorder()
        self._tile_seq = 0              # decode tile ids for span links
        # per-phase wall-time accountant (profiler/phases.py): every
        # mutation is disabled-noop, so the engine marks unconditionally
        self._phases = _get_phases()
        # bounded-cardinality tenant label set: past the cap new tenants
        # collapse to "overflow" so a label-per-user bug cannot blow up
        # the registry (MAX_LABEL_SETS)
        self._tenants: set[str] = set()
        self._max_tenants = 32
        # cost-model calibration: raw roofline seconds are TPU-ledger
        # priced; the first measured dispatch fixes the platform +
        # overhead scale so later predicted-vs-measured ratios are
        # relative-accuracy signals on any backend
        self._cost_scale = None
        self._m_cost_err = _metric("pir_cost_model_error")
        # round 14: the closed-loop SLO scheduler. Base knob values are
        # captured here so the brownout ladder's degradations are
        # REVERSIBLE (level 0 restores them); _spec_allowed separates
        # the reversible brownout switch from the permanent
        # draft_verify-fault degradation.
        self._base_decode_steps = self.decode_steps
        self._base_draft_depth = self.draft_depth
        self._base_chunk = self.chunk
        self._mnt_cap = None
        self._spec_allowed = self.spec
        # rid -> (request, cached length, next token): decode lanes
        # parked by preemption. Pool blocks stay allocated — resuming is
        # a lane-state re-upload, not a re-prefill.
        self._preempted: dict[int, tuple[Request, int, int]] = {}
        # round 16 (mesh disaggregation): a prefill-pool worker sets
        # this to a callable; the final prefill chunk then serializes
        # the request's paged-KV state through export_kv and hands the
        # record to the sink INSTEAD of activating a local decode lane.
        # None (default) = the single-process engine, byte-identical to
        # every earlier round.
        self.prefill_sink = None
        # arrival timestamps (trailing window) — the scheduler's offered-
        # rate estimate, independent of any load harness
        self._arrivals: deque[float] = deque(maxlen=256)
        if scheduler is True:
            scheduler = SLOScheduler()
        self.scheduler = scheduler
        # round 17 (observability plane): an attached MetricsSampler is
        # ticked once per step (deterministic step-count clock). None
        # (default) = no sampler, zero overhead; a sampler that fails
        # degrades ITSELF (obs.sample site) — never the engine.
        self.sampler = None
        # round 18: the cross-request prefix index. The identity string
        # is folded into every chain key, so entries can never resolve
        # across a block-format or geometry change (the kv_dequant
        # degradation additionally clears the index outright).
        if prefix_cache:
            ident = (f"{self.pool.fmt.name}:{block_size}:"
                     f"{self.cfg['kv_heads']}x{self.cfg['head_dim']}:"
                     f"{np.dtype(self.embed_w.dtype).name}")
            self._prefix = PrefixCacheIndex(ident, block_size,
                                            max_blocks=prefix_cache_blocks)
        else:
            self._prefix = None
        # rid -> tokens resolved from the index at admission (handoff
        # manifests + tests read this; entries drop at finish)
        self._prefix_matched: dict[int, int] = {}
        self._m_pfx_hits = _metric("serving_prefix_hits_total")
        self._m_pfx_miss = _metric("serving_prefix_misses_total")
        self._m_pfx_saved = _metric("serving_prefix_tokens_saved_total")
        self._m_pfx_shared = _metric("serving_prefix_shared_blocks")
        self._m_pfx_evict = _metric("serving_prefix_evictions_total")
        self._m_pfx_cow = _metric("serving_prefix_cow_forks_total")
        # round 22: the multi-adapter (LoRA) store. None (default) keeps
        # the engine EXACTLY the storeless engine — no extra program
        # inputs, no adapter math in the compiled scans, byte-identical
        # everything. With a store attached, lanes carry an adapter_id
        # and the decode/prefill programs gather per-lane A/B factors
        # from the store's device pools (slot 0 = base, zeros).
        if adapters is not None:
            nh, nkv, hd = (self.cfg["heads"], self.cfg["kv_heads"],
                           self.cfg["head_dim"])
            H = nh * hd
            if (adapters.num_layers != L or adapters.hidden != H
                    or adapters.q_out != nh * hd
                    or adapters.v_out != nkv * hd):
                raise ValueError(
                    "AdapterStore dimensions do not match this model: "
                    f"store (L={adapters.num_layers}, H={adapters.hidden},"
                    f" q={adapters.q_out}, v={adapters.v_out}) vs model "
                    f"(L={L}, H={H}, q={nh * hd}, v={nkv * hd})")
        self.adapters = adapters

    # --- public API -------------------------------------------------------
    def add_request(self, prompt, max_new_tokens=32, eos_token_id=None,
                    do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                    seed=0, deadline_s=None, tenant="-",
                    priority="interactive", adapter=None):
        """Queue a request. `deadline_s` is a per-request wall-clock
        budget from arrival: once exceeded the request finishes with
        whatever it has and finish_reason='timeout'. `tenant` labels the
        request's per-tenant telemetry (bounded cardinality; unknown
        tenants past the cap collapse to 'overflow'). `priority` is the
        scheduling class (closed registry scheduler.PRIORITY_CLASSES:
        interactive / batch / best_effort) — only consulted when the
        engine has a scheduler. `adapter` names a LoRA adapter in the
        engine's AdapterStore (None = base model); a name the store
        cannot make resident at admission is a typed rejection
        (finish_reason='rejected'), never a base-weights fallback.
        Raises BackpressureError when the admission queue is at
        max_queue."""
        if priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"unknown priority class {priority!r}; registered: "
                f"{list(PRIORITY_CLASSES)}")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            _metric("serving_backpressure_total").inc()
            if self._rec.enabled:
                self._rec.record("backpressure", queue=len(self.queue),
                                 max_queue=self.max_queue)
            raise BackpressureError(
                f"admission queue full ({len(self.queue)}/{self.max_queue}); "
                "retry later")
        tenant = str(tenant) if tenant else "-"
        if tenant != "-" and tenant not in self._tenants:
            if len(self._tenants) >= self._max_tenants:
                tenant = "overflow"
            else:
                self._tenants.add(tenant)
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens, eos_token_id,
                      do_sample, temperature, top_k, top_p,
                      seed, deadline_s, tenant=tenant, priority=priority,
                      adapter=adapter)
        self.queue.append(req)
        self._arrivals.append(req.t_arrival)
        if self._tracer.enabled:
            # root of the request's span tree (instant: arrival moment)
            self._tracer.add_span("request.admit",
                                  int(req.t_arrival * 1e9), 0,
                                  trace_id=req.trace_id, args={"rid": rid})
        return rid

    def adopt_identity(self, rid, trace_id, t_arrival=None):
        """Adopt a mesh-level identity onto a still-queued request:
        spans, exemplars, and any handoff manifest join the mesh trace,
        and TTFT/deadline accounting stays anchored at TRUE arrival
        (router admission time, not replica enqueue time). Returns False
        when the rid already left the queue."""
        for req in self.queue:
            if req.rid == rid:
                req.trace_id = str(trace_id)
                if t_arrival is not None:
                    req.t_arrival = float(t_arrival)
                    if req.deadline_s is not None:
                        req.t_deadline = req.t_arrival + req.deadline_s
                return True
        return False

    def has_work(self):
        return (bool(self.queue) or any(r is not None for r in self.lanes)
                or bool(self._inflight) or bool(self._preempted))

    def run(self, max_steps=10_000):
        """Drive to completion; returns {rid: [generated tokens]}."""
        steps = 0
        while self.has_work() and steps < max_steps:
            self.step()
            steps += 1
        return {rid: r.generated for rid, r in self.finished.items()}

    # --- scheduling -------------------------------------------------------
    def step(self):
        ph = self._phases
        ph.begin_step()
        with _span("serving.step"):
            self._expire_deadlines()
            self._m_queue.set(len(self.queue))
            if self.scheduler is not None:
                # the closed-loop decision (brownout ladder + at most
                # one preemption); its wall time lands in the "admit"
                # phase — it IS admission policy
                self.scheduler.on_step(self)
            self._admit()
            ph.mark("admit")
            self._run_prefill_tasks()
            self._decode_phase()
            self._m_occ.set(sum(r is not None for r in self.lanes)
                            / self.max_batch)
            self._m_free.set(len(self.pool._free))
        ph.end_step()
        if self.sampler is not None:
            self.sampler.sample()

    def _decode_active(self):
        """Lanes the fused decode advances: occupied AND past prefill."""
        return [i for i, r in enumerate(self.lanes)
                if r is not None and i not in self._prefill_tasks]

    # --- graceful degradation --------------------------------------------
    def _finish(self, req, reason):
        # THE one finish path: req.finish_reason, the
        # serving_finished_total{reason} counter, the request.finish
        # span, and the flight-recorder event all derive from the same
        # `reason` argument here — they cannot disagree (test-pinned)
        req.done = True
        req.finish_reason = reason
        self._prefix_matched.pop(req.rid, None)
        self.finished[req.rid] = req
        _metric("serving_finished_total", reason=reason).inc()
        _metric("serving_tenant_finished_total",
                tenant=req.tenant, reason=reason).inc()
        if self._tracer.enabled:
            self._tracer.add_span("request.finish",
                                  time.perf_counter_ns(), 0,
                                  trace_id=req.trace_id,
                                  args={"rid": req.rid, "reason": reason,
                                        "tokens": len(req.generated)})
        if self._rec.enabled:
            self._rec.record("finish", rid=req.rid, reason=reason,
                             tokens=len(req.generated))

    def _adapter_release(self, req):
        """Drop the request's adapter reference (idempotent). The ref
        lifecycle mirrors the pool blocks exactly: acquired at
        admission, held across preempt/park (blocks stay resident),
        dropped wherever pool.release retires the request or a requeue
        will re-acquire at the next admission."""
        if self.adapters is not None and req.adapter_id:
            self.adapters.release(req.adapter_id)
        req.adapter_id = 0

    def _retire_lane(self, lane, reason):
        req = self.lanes[lane]
        self._prefill_tasks.pop(lane, None)
        self.pool.release(req.rid)
        self._adapter_release(req)
        self.lanes[lane] = None
        self.lane_len[lane] = 0
        self._lane_epoch[lane] += 1
        self._dirty = True
        self._m_retired.inc()
        self._finish(req, reason)

    def _expire_deadlines(self):
        """Per-request deadlines: an expired queued request finishes
        empty; an expired decoding (or prefilling) lane finishes with the
        tokens it has (a degraded-but-distinguishable completion) and its
        pool blocks are released."""
        now = time.perf_counter()
        if any(r.t_deadline is not None and now >= r.t_deadline
               for r in self.queue):
            kept = deque()
            for req in self.queue:
                if req.t_deadline is not None and now >= req.t_deadline:
                    _metric("serving_timeouts_total", where="queue").inc()
                    if self._rec.enabled:
                        self._rec.record("timeout", rid=req.rid,
                                         where="queue")
                    self._finish(req, "timeout")
                else:
                    kept.append(req)
            self.queue = kept
        for lane, req in enumerate(self.lanes):
            if (req is not None and req.t_deadline is not None
                    and now >= req.t_deadline):
                _metric("serving_timeouts_total", where="decode").inc()
                if self._rec.enabled:
                    self._rec.record("timeout", rid=req.rid, where="decode")
                self._retire_lane(lane, "timeout")
        # parked (preempted) requests keep their deadline: one that
        # expires before a lane frees up finishes with the tokens it has
        # and releases its still-resident pool blocks
        for rid in [rid for rid, (req, _ln, _tok)
                    in self._preempted.items()
                    if req.t_deadline is not None
                    and now >= req.t_deadline]:
            req, _ln, _tok = self._preempted.pop(rid)
            self.pool.release(rid)
            self._adapter_release(req)
            _metric("serving_timeouts_total", where="preempted").inc()
            if self._rec.enabled:
                self._rec.record("timeout", rid=rid, where="preempted")
            self._m_retired.inc()
            self._finish(req, "timeout")

    def cancel(self, rid):
        """Withdraw one request wherever it lives (queued, decoding, or
        parked) WITHOUT producing a finished record: the caller already
        has the stream's outcome from somewhere else (a hedge sibling
        that committed first, or an RPC the client gave up on before the
        reply landed). Pool blocks release; nothing reaches `finished`,
        so the router's commit map never sees a duplicate. Returns
        whether anything was withdrawn."""
        rid = int(rid)
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                break
        else:
            for lane, req in enumerate(self.lanes):
                if req is not None and req.rid == rid:
                    self._prefill_tasks.pop(lane, None)
                    self.pool.release(rid)
                    self._adapter_release(req)
                    self.lanes[lane] = None
                    self.lane_len[lane] = 0
                    self._lane_epoch[lane] += 1
                    self._dirty = True
                    break
            else:
                if rid not in self._preempted:
                    return False
                req, _ln, _tok = self._preempted.pop(rid)
                self.pool.release(rid)
                self._adapter_release(req)
        self._prefix_matched.pop(rid, None)
        if self._rec.enabled:
            self._rec.record("sched", action="cancel", rid=rid)
        return True

    def _shed(self, active):
        """Decode OOM: preempt the lane with the least work done (fewest
        generated tokens), release its blocks, and requeue the request at
        the FRONT of the queue for a fresh prefill. A request shed more
        than max_sheds times finishes degraded (finish_reason='shed')
        instead of thrashing the pool forever."""
        self._dirty = True
        if not active:
            return
        victim = max(active,
                     key=lambda i: (-len(self.lanes[i].generated), i))
        req = self.lanes[victim]
        self.pool.release(req.rid)
        self._adapter_release(req)
        self.lanes[victim] = None
        self.lane_len[victim] = 0
        self._lane_epoch[victim] += 1
        req.shed_count += 1
        _metric("serving_shed_total").inc()
        if self._rec.enabled:
            self._rec.record("shed", rid=req.rid, lane=victim,
                             sheds=req.shed_count)
        if req.shed_count > self.max_sheds:
            self._m_retired.inc()
            self._finish(req, "shed")
            return
        # restart from the prompt next admission: the KV blocks are gone,
        # and greedy decode reproduces the same prefix deterministically
        # (sampled lanes re-derive the same stream from (seed, position))
        req.generated = []
        self.queue.appendleft(req)

    # --- priority preemption (round 14) ----------------------------------
    def _try_preempt(self, lane, why="slo"):
        """Park a decode-active lane so a higher-priority request can
        take it. Unlike _shed, the paged-KV blocks STAY resident and the
        host decode cursor (lane_len / lane_tok) is saved: resuming is a
        lane-state re-upload through the membership-change path, so the
        stream continues byte-identically (greedy is deterministic;
        sampled lanes key the device PRNG on absolute position). Any
        tokens of the lane still in a dropped in-flight tile are
        regenerated identically after resume — the epoch bump below
        prevents double-crediting. Returns False when the lane is not
        preemptible (empty / still prefilling) or the serve.preempt
        fault site fires: a failed preemption aborts cleanly and the
        victim keeps decoding."""
        req = self.lanes[lane]
        if req is None or lane in self._prefill_tasks:
            return False
        try:
            fault_point("serve.preempt", rid=req.rid, lane=lane)
        except _TRANSIENT_ERRORS:
            _metric("serving_deferred_total", reason="preempt_fault").inc()
            return False
        self._preempted[req.rid] = (req, int(self.lane_len[lane]),
                                    int(self.lane_tok[lane]))
        self.lanes[lane] = None
        self.lane_len[lane] = 0
        self._lane_epoch[lane] += 1
        self._dirty = True
        _metric("serving_preempted_total").inc()
        _metric("serving_preemptions_total",
                **{"class": req.priority}).inc()
        if self._rec.enabled:
            self._rec.record("sched", action="preempt", rid=req.rid,
                             lane=lane, why=why,
                             tokens=len(req.generated))
        if self._tracer.enabled:
            self._tracer.add_span("request.preempt",
                                  time.perf_counter_ns(), 0,
                                  trace_id=req.trace_id,
                                  args={"rid": req.rid, "why": why})
        return True

    def _resume_preempted(self):
        """Re-admit parked requests into free lanes (oldest first). The
        pool blocks never left, so this is just the host mirror restore
        + an epoch bump; the next _decode_phase re-uploads lane state
        and the stream picks up exactly where it was parked."""
        for rid in list(self._preempted):
            lane = next((i for i, r in enumerate(self.lanes)
                         if r is None and i not in self._prefill_tasks),
                        None)
            if lane is None:
                return
            req, lane_len, lane_tok = self._preempted.pop(rid)
            self.lanes[lane] = req
            self.lane_len[lane] = lane_len
            self.lane_tok[lane] = lane_tok
            self._lane_epoch[lane] += 1
            self._dirty = True
            if self._rec.enabled:
                self._rec.record("sched", action="resume", rid=rid,
                                 lane=lane, tokens=len(req.generated))

    # --- disaggregated paged-KV handoff (round 16) -----------------------
    def export_kv(self, req, first_tok):
        """Handoff record for a just-prefilled request: the prompt's
        paged-KV blocks in the pool's RAW storage representation
        (payload + scales when quantized) plus everything the decode
        side needs to continue the stream byte-identically. Copying
        stored bytes — not dequantized values — makes the round trip
        exact for native and quantized block formats alike; the device
        PRNG keys on (sample_seed, absolute position), so sampled
        streams survive the hop too."""
        s = int(req.prompt.size)
        nb = self.pool.blocks_needed(s)
        ids = jnp.asarray(self.pool.tables[req.rid][:nb], jnp.int32)
        rec = {
            "version": 1,
            "fmt": self.pool.fmt.name,
            "prompt": np.asarray(req.prompt, np.int32),
            "first_token": int(first_tok),
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": req.eos_token_id,
            "do_sample": bool(req.do_sample),
            "temperature": float(req.temperature),
            "top_k": int(req.top_k),
            "top_p": float(req.top_p),
            "sample_seed": int(req.sample_seed),
            "tenant": req.tenant,
            "priority": req.priority,
            "trace_id": req.trace_id,
            "t_arrival": float(req.t_arrival),
            "t_first": None if req.t_first is None else float(req.t_first),
            "deadline_s": req.deadline_s,
            # prefix-cache manifest (round 18): how much of this prompt
            # was resolved from the sender's index and how many of the
            # exported blocks are refcount-shared there. The payload
            # below is a COPY either way — the receiver re-owns (and
            # re-indexes) the blocks privately.
            "prefix_matched_tokens": int(
                self._prefix_matched.get(req.rid, 0)),
            "prefix_shared_blocks": int(self.pool.shared_count(req.rid)),
            # adapter identity rides the record as scalar meta (round 22):
            # the importer must bind the SAME adapter or reject the
            # handoff — silently continuing on base weights would change
            # the stream mid-request.
            "adapter": req.adapter,
            "k": np.asarray(self.pool.k[:, ids]),
            "v": np.asarray(self.pool.v[:, ids]),
        }
        if self.pool.fmt.quantized:
            rec["k_scale"] = np.asarray(self.pool.k_scale[:, ids])
            rec["v_scale"] = np.asarray(self.pool.v_scale[:, ids])
        return rec

    def import_kv(self, record):
        """Install a handed-off prefill on THIS engine: reserve the full
        sequence footprint, write the stored block payload verbatim, and
        park the request through the preemption path — resuming is the
        same lane-state re-upload as a preempt/resume, so the stream
        continues exactly where the prefill worker left it (no
        re-prefill, no host recompute). Returns the local rid. Raises
        ValueError on a block-format mismatch and KVPoolExhaustedError
        (via pool.ensure) when the blocks do not fit — callers treat
        both as a failed handoff and fall back to re-prefill."""
        if record["fmt"] != self.pool.fmt.name:
            raise ValueError(
                f"handoff block format {record['fmt']!r} != pool format "
                f"{self.pool.fmt.name!r}; mesh replicas must share "
                "kv_cache_dtype")
        adapter = record.get("adapter") or None
        if adapter is not None and (
                self.adapters is None
                or not self.adapters.can_serve(adapter)):
            # rides the failed-handoff fallback (ValueError): the router
            # re-prefills on a replica that CAN serve the adapter rather
            # than silently continuing the stream on base weights
            raise ValueError(
                f"handoff names adapter {adapter!r} which this engine "
                "cannot serve (no store or unregistered adapter)")
        prompt = np.asarray(record["prompt"], np.int32).reshape(-1)
        s = int(prompt.size)
        total = s + int(record["max_new_tokens"])
        if total > self.max_blocks_per_seq * self.pool.block_size:
            raise ValueError("handoff exceeds the per-sequence block "
                             "budget of the receiving engine")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, record["max_new_tokens"],
                      record["eos_token_id"], record["do_sample"],
                      record["temperature"], record["top_k"],
                      record["top_p"], seed=None,
                      tenant=record["tenant"],
                      priority=record["priority"],
                      adapter=adapter)
        # stream identity crosses the hop unchanged: trace id (span
        # joins), PRNG lane key (sampled decode continuity), arrival +
        # deadline anchors (TTFT/e2e stay measured from true arrival)
        req.trace_id = record["trace_id"]
        req.sample_seed = np.uint32(record["sample_seed"] & 0xFFFFFFFF)
        req.t_arrival = record["t_arrival"]
        req.t_first = record.get("t_first")
        if record.get("deadline_s") is not None:
            req.deadline_s = float(record["deadline_s"])
            req.t_deadline = req.t_arrival + req.deadline_s
        first_tok = int(record["first_token"])
        req.generated = [first_tok]
        self._m_admitted.inc()
        self._m_tokens.inc()        # the handed-off first token
        if (req.eos_token_id is not None and first_tok == req.eos_token_id) \
                or req.max_new_tokens <= 1:
            # the prefill worker's first token already ended the stream:
            # nothing to decode, no blocks needed
            reason = ("eos" if req.eos_token_id is not None
                      and first_tok == req.eos_token_id else "length")
            self._m_retired.inc()
            self._finish(req, reason)
            return rid
        self.pool.ensure(rid, total)
        if req.adapter:
            try:
                req.adapter_id = self.adapters.acquire(req.adapter)
            except (AdapterLoadError,) + _TRANSIENT_ERRORS as e:
                # treated like any other failed handoff: give the blocks
                # back and let the caller fall back to re-prefill
                self.pool.release(rid)
                raise ValueError(
                    f"handoff adapter {req.adapter!r} failed to "
                    f"hot-load on the receiving engine: {e}") from e
        nb = self.pool.blocks_needed(s)
        ids = jnp.asarray(self.pool.tables[rid][:nb], jnp.int32)
        self.pool.k = self.pool.k.at[:, ids].set(
            jnp.asarray(record["k"], self.pool.k.dtype))
        self.pool.v = self.pool.v.at[:, ids].set(
            jnp.asarray(record["v"], self.pool.v.dtype))
        if self.pool.fmt.quantized:
            self.pool.k_scale = self.pool.k_scale.at[:, ids].set(
                jnp.asarray(record["k_scale"], self.pool.k_scale.dtype))
            self.pool.v_scale = self.pool.v_scale.at[:, ids].set(
                jnp.asarray(record["v_scale"], self.pool.v_scale.dtype))
        # a handed-off prompt seeds THIS engine's prefix index too: the
        # next local request with the same prefix shares these blocks.
        # Same degrade-to-unindexed contract as the prefill-side insert.
        if self._prefix is not None:
            try:
                fault_point("serve.prefix_match", rid=rid)
                for b in self._prefix.insert(prompt,
                                             self.pool.tables[rid]):
                    self.pool.pin(b)
                for b in self._prefix.trim():
                    self.pool.unpin(b)
                    self._m_pfx_evict.inc()
                self._m_pfx_shared.set(len(self._prefix))
            except _TRANSIENT_ERRORS:
                _metric("serving_runtime_degradations_total",
                        what="prefix_miss").inc()
        # park exactly like a preempted lane: (req, cached length, next
        # token). _resume_preempted + the next lane-state upload then
        # continue decode with no further handoff-specific machinery.
        self._preempted[rid] = (req, s, first_tok)
        self._dirty = True
        return rid

    # --- admission / chunked prefill -------------------------------------
    def _admit(self):
        """Reserve lanes + pool blocks for queued requests; the prompts
        themselves prefill chunk-by-chunk in _run_prefill_tasks so a long
        admission never head-of-line-blocks the decode lanes. With a
        scheduler attached, parked (preempted) requests resume first
        when the scheduler allows, and queue order comes from its
        priority-class + tenant-DRR pick instead of FIFO."""
        if self._preempted and (self.scheduler is None
                                or self.scheduler.should_resume(self)):
            self._resume_preempted()
        while self.queue:
            free_lanes = [i for i, r in enumerate(self.lanes) if r is None]
            if not free_lanes:
                return
            if self.scheduler is not None:
                idx = self.scheduler.pick_index(self)
                if idx is None:
                    return
            else:
                idx = 0
            req = self.queue[idx]
            if (self.scheduler is not None
                    and self.scheduler.shed_best_effort
                    and req.priority == "best_effort"):
                # deepest brownout rung: best_effort is not served at
                # all; a typed, counted shed — not a silent drop
                del self.queue[idx]
                req.generated = []
                _metric("serving_shed_total").inc()
                if self._rec.enabled:
                    self._rec.record("sched", action="shed_best_effort",
                                     rid=req.rid)
                self._finish(req, "shed")
                continue
            if self._mnt_cap is not None \
                    and req.max_new_tokens > self._mnt_cap:
                # cap_max_new_tokens rung: reshape the admitted budget —
                # the stream still serves, just shorter. Capped at
                # admission so already-running streams keep theirs, and
                # a request admitted under brownout keeps the cap even
                # after recovery (budget decisions are admission-final).
                req.max_new_tokens = self._mnt_cap
                if self._rec.enabled:
                    self._rec.record("sched", action="cap_max_new_tokens",
                                     rid=req.rid, cap=self._mnt_cap)
            total = req.prompt.size + req.max_new_tokens
            if total > self.max_blocks_per_seq * self.pool.block_size:
                # cannot ever serve: reject with an empty result instead
                # of crashing the engine mid-step (prompts longer than
                # the largest bucket are now served via chunking; only
                # the per-sequence block budget is a hard wall)
                del self.queue[idx]
                req.generated = []
                self._finish(req, "rejected")
                _metric("serving_rejected_total", reason="oversized").inc()
                continue
            if req.max_new_tokens <= 0:
                del self.queue[idx]
                self._finish(req, "length")
                continue
            # prefix-cache lookup (round 18): resolve the prompt's
            # leading block-aligned chunks to already-resident shared
            # blocks. ANY index failure is a plain cache miss — full
            # prefill, byte-identical stream, never a wrong answer
            # (the serve.prefix_match contract, chaos-drilled).
            matched, m_tok = [], 0
            s = int(req.prompt.size)
            if self._prefix is not None:
                try:
                    fault_point("serve.prefix_match", rid=req.rid)
                    matched, m_tok = self._prefix.lookup(req.prompt)
                except _TRANSIENT_ERRORS:
                    matched, m_tok = [], 0
                    _metric("serving_runtime_degradations_total",
                            what="prefix_miss").inc()
                    if self._rec.enabled:
                        self._rec.record("degrade", what="prefix_miss",
                                         rid=req.rid)
            # a block-aligned FULL-prompt match must still prefill the
            # final position (the first token samples from full-prompt
            # logits) — that one write lands inside the last shared
            # block, so the admission below forks it (copy-on-write)
            need_fork = matched and m_tok >= s
            if need_fork:
                m_tok = s - 1
            # admit only if the WHOLE sequence fits: no mid-flight
            # eviction of LIVE requests (the reference engine preempts;
            # we keep the no-surprise contract) — but index-only blocks
            # are reclaimable cache, so LRU-evict those before deferring
            have = len(matched) - (1 if need_fork else 0)

            def _fits():
                # 1-arg call when nothing matched: the pre-round-18
                # can_fit signature is a test-pinned monkeypatch seam
                return (self.pool.can_fit(total, have) if have
                        else self.pool.can_fit(total))

            if not _fits() and self._prefix is not None:
                protect = frozenset(matched)
                while not _fits():
                    b = self._prefix.evict(protect)
                    if b is None:
                        break
                    self.pool.unpin(b)
                    self._m_pfx_evict.inc()
                self._m_pfx_shared.set(len(self._prefix))
            if not _fits():
                _metric("serving_deferred_total", reason="pool_full").inc()
                return
            del self.queue[idx]
            # adapter binding (round 22): make the named adapter
            # resident and validate the slot the lanes will gather from
            # before the pool reservation. ANY store failure — unknown
            # name, slots pinned, injected serve.adapter_load /
            # serve.adapter_gather fault — is a typed rejection: the
            # one forbidden outcome is serving the stream with the
            # wrong weights. Other lanes never notice (their slots are
            # untouched).
            req.adapter_id = 0
            if req.adapter:
                try:
                    fault_point("serve.adapter_load", rid=req.rid,
                                adapter=req.adapter)
                    if self.adapters is None:
                        raise AdapterLoadError(
                            f"request names adapter {req.adapter!r} but "
                            "the engine has no AdapterStore attached")
                    req.adapter_id = self.adapters.acquire(req.adapter)
                    fault_point("serve.adapter_gather", rid=req.rid,
                                slot=req.adapter_id)
                    self.adapters.check_resident(req.adapter_id)
                except (AdapterLoadError,) + _TRANSIENT_ERRORS:
                    self._adapter_release(req)
                    req.generated = []
                    self._finish(req, "rejected")
                    _metric("serving_rejected_total",
                            reason="adapter").inc()
                    _metric("serving_adapter_load_failures_total").inc()
                    if self._rec.enabled:
                        self._rec.record("adapter", action="reject",
                                         rid=req.rid,
                                         adapter=req.adapter)
                    continue
            lane = free_lanes[0]
            try:
                fault_point("serve.admit", rid=req.rid)
                # reserve the FULL footprint now — lazy per-step
                # allocation could exhaust the pool mid-decode across
                # admitted sequences, which the can_fit gate above
                # promised cannot happen. Matched prefix blocks are
                # adopted (refcount +1) ahead of the fresh-tail ensure.
                if matched:
                    self.pool.adopt(req.rid, matched)
                    if need_fork:
                        self.pool.fork_cow(req.rid, len(matched) - 1)
                        self._m_pfx_cow.inc()
                self.pool.ensure(req.rid, total)
            except MemoryError:
                # pool exhausted despite the can_fit gate (e.g. blocks
                # held by an out-of-band allocation): surface as a counted
                # deferral, give back any partial reservation, and leave
                # the request AT THE FRONT of the queue — never let the
                # scheduler step die mid-flight
                self.pool.release(req.rid)
                self._adapter_release(req)
                self.queue.appendleft(req)
                _metric("serving_deferred_total",
                        reason="pool_exhausted").inc()
                return
            except _TRANSIENT_ERRORS:
                # transient admission failure (store/IO blip or injected
                # fault): same counted-deferral contract — requeued at
                # the front, retried next step, scheduler stays alive
                self.pool.release(req.rid)
                self._adapter_release(req)
                self.queue.appendleft(req)
                _metric("serving_deferred_total",
                        reason="admit_fault").inc()
                return
            if self._prefix is not None:
                if m_tok > 0:
                    self._m_pfx_hits.inc()
                    self._m_pfx_saved.inc(m_tok)
                    self._prefix_matched[req.rid] = m_tok
                    if self._rec.enabled:
                        self._rec.record("prefix_hit", rid=req.rid,
                                         tokens=m_tok,
                                         blocks=len(matched))
                else:
                    self._m_pfx_miss.inc()
            self.lanes[lane] = req
            self._lane_epoch[lane] += 1
            # prefill covers ONLY the unmatched tail: the chunk plan
            # starts at the first token the index could not resolve
            self._prefill_tasks[lane] = _PrefillTask(
                req, lane, self._chunk_plan(req.prompt.size, m_tok))
            if self._tracer.enabled:
                t0 = int(req.t_arrival * 1e9)
                self._tracer.add_span(
                    "request.queued", t0, time.perf_counter_ns() - t0,
                    trace_id=req.trace_id, tid=LANE_TID_BASE + lane,
                    tid_name=f"lane {lane}", args={"rid": req.rid})
            if self._rec.enabled:
                self._rec.record("admit", rid=req.rid, lane=lane,
                                 epoch=int(self._lane_epoch[lane]))

    def _chunk_plan(self, s, start=0):
        """(start, width) pieces covering tokens [start, s) of a prompt:
        full chunks, then a tail padded to the smallest chunk width that
        fits. A non-zero start is a prefix-cache hit — the matched head
        is already resident and never recomputed."""
        pieces = []
        while s - start > self.chunk:
            pieces.append((start, self.chunk))
            start += self.chunk
        rem = s - start
        width = next(w for w in self._chunk_widths if w >= rem)
        pieces.append((start, width))
        return pieces

    def _run_prefill_tasks(self):
        """Advance every in-flight prefill by up to
        prefill_chunks_per_step chunks (all remaining chunks when no
        lane is decoding — there is no one to block)."""
        if not self._prefill_tasks:
            return
        decode_busy = bool(self._decode_active())
        for lane in sorted(self._prefill_tasks):
            task = self._prefill_tasks.get(lane)
            if task is None:
                continue
            budget = (self.prefill_chunks_per_step if decode_busy
                      else len(task.pieces) - task.idx)
            try:
                with _span("serving.prefill", rid=task.req.rid,
                           prompt=int(task.req.prompt.size)):
                    for _ in range(max(1, budget)):
                        if self._prefill_one_chunk(task):
                            break
            except MemoryError:
                self._abort_prefill(task, "prefill_oom")
                return
            except _TRANSIENT_ERRORS:
                self._abort_prefill(task, "prefill_fault")
                return

    def _abort_prefill(self, task, reason):
        """A chunk failed: give back the blocks + lane and requeue the
        request at the front for a fresh prefill next step."""
        self.pool.release(task.req.rid)
        self._adapter_release(task.req)
        self.lanes[task.lane] = None
        self.lane_len[task.lane] = 0
        self._lane_epoch[task.lane] += 1
        self._prefill_tasks.pop(task.lane, None)
        self.queue.appendleft(task.req)
        _metric("serving_deferred_total", reason=reason).inc()

    def _prefill_one_chunk(self, task):
        """Run one chunk forward; on the final chunk, sample the first
        token and activate the lane. Returns True when the task is
        done."""
        req = task.req
        start, width = task.pieces[task.idx]
        s = req.prompt.size
        fault_point("serve.prefill_chunk", rid=req.rid, start=start)
        fn = self._prefill_jit.get(width)
        if fn is None:
            # engine warm-start: prefill programs compile through the PIR
            # pipeline — pattern-rewritten pre-XLA and, with
            # FLAGS_compile_cache_dir set, warm-loaded from the
            # persistent compile cache instead of paying the cold XLA
            # compile
            from ..pir import pir_jit
            fn = pir_jit(self._make_prefill_chunk(),
                         name=f"serving.prefill.b{width}",
                         extra_key=({"lora": self.adapters.program_key}
                                    if self.adapters is not None else None))
            self._prefill_jit[width] = fn
            self.compile_reports[f"prefill.b{width}"] = None
            # program construction counts as a retrace: the hot-swap
            # contract pins this counter's delta to 0 across adapter churn
            _metric("jit_retrace_total").inc()
        cold = fn._compiled is None     # first call traces + compiles
        n_real = min(width, s - start)
        ids = np.zeros((1, width), np.int32)
        ids[0, :n_real] = req.prompt[start:start + n_real]
        table = np.full(self.max_blocks_per_seq, self.pool.scratch_block,
                        np.int32)
        t = self.pool.tables[req.rid]
        table[:len(t)] = t
        is_final = task.idx == len(task.pieces) - 1
        last_idx = (s - 1 - start) if is_final else 0
        args = [self.stacked, self.embed_w, self.norm_w, self._out_w,
                self.pool.k, self.pool.v]
        if self.pool.fmt.quantized:
            args += [self.pool.k_scale, self.pool.v_scale]
        args += [jnp.asarray(ids), jnp.int32(start), jnp.int32(last_idx),
                 jnp.asarray(table)]
        if self.adapters is not None:
            ad = self.adapters
            args += [ad.A_q, ad.B_q, ad.A_v, ad.B_v,
                     jnp.int32(req.adapter_id)]
        t0 = time.perf_counter()
        out = fn(*args)
        if self.pool.fmt.quantized:
            (logits, self.pool.k, self.pool.v,
             self.pool.k_scale, self.pool.v_scale) = out
        else:
            logits, self.pool.k, self.pool.v = out
        dt = time.perf_counter() - t0
        self._m_prefill.observe(dt)
        self._m_chunks.inc()
        if self._phases.enabled:
            self._phases.mark("compile" if cold else "prefill.chunk",
                              tenant=req.tenant)
        if not cold:        # a cold call's wall is compile, not the program
            self._cost_observe(f"prefill.b{width}", dt)
        if self._tracer.enabled:
            self._tracer.add_span(
                "request.prefill.chunk", int(t0 * 1e9), int(dt * 1e9),
                trace_id=req.trace_id, tid=LANE_TID_BASE + task.lane,
                tid_name=f"lane {task.lane}",
                args={"rid": req.rid, "chunk": task.idx, "width": width})
        if self.compile_reports.get(f"prefill.b{width}") is None:
            self.compile_reports[f"prefill.b{width}"] = \
                getattr(fn, "report", None)
        task.idx += 1
        if not is_final:
            return False
        # final chunk: first token on the host (once per request), lane
        # becomes decode-active -> membership change
        first_tok = req.choose(np.asarray(logits).reshape(-1))
        lane = task.lane
        self._prefill_tasks.pop(lane, None)
        # the exemplar ties this observation's bucket to the exact trace
        # that produced it (bad p99 -> exact request)
        ttft = time.perf_counter() - req.t_arrival
        req.t_first = req.t_arrival + ttft
        self._m_ttft.observe(ttft, exemplar=req.trace_id)
        _metric("serving_tenant_ttft_seconds",
                tenant=req.tenant).observe(ttft)
        if self.adapters is not None:
            _metric("serving_adapter_ttft_seconds",
                    adapter=req.adapter or "base").observe(ttft)
        if self.scheduler is not None:
            self.scheduler.note_ttft(ttft)
        # index the request's full-prompt blocks for the NEXT sharer
        # (before the sink path below releases the request's own refs —
        # the index pin is what keeps a prefix resident). Failures
        # degrade to "not indexed": streams are never affected.
        if self._prefix is not None:
            try:
                fault_point("serve.prefix_match", rid=req.rid)
                for b in self._prefix.insert(req.prompt,
                                             self.pool.tables[req.rid]):
                    self.pool.pin(b)
                for b in self._prefix.trim():
                    self.pool.unpin(b)
                    self._m_pfx_evict.inc()
                self._m_pfx_shared.set(len(self._prefix))
            except _TRANSIENT_ERRORS:
                _metric("serving_runtime_degradations_total",
                        what="prefix_miss").inc()
                if self._rec.enabled:
                    self._rec.record("degrade", what="prefix_miss",
                                     rid=req.rid)
        if self.prefill_sink is not None:
            # disaggregated prefill worker: serialize the prompt's KV
            # state and hand the stream to the decode pool. The lane +
            # blocks free immediately; admitted/token accounting happens
            # exactly once mesh-wide, on the decode engine's import.
            record = self.export_kv(req, first_tok)
            if self._phases.enabled:   # export = device->host KV readback
                self._phases.mark("hostsync", tenant=req.tenant)
            self.pool.release(req.rid)
            self._adapter_release(req)
            self._prefix_matched.pop(req.rid, None)
            self.lanes[lane] = None
            self.lane_len[lane] = 0
            self._lane_epoch[lane] += 1
            self._dirty = True
            self.prefill_sink(record)
            return True
        self.lane_len[lane] = s
        self.lane_tok[lane] = first_tok
        self._dirty = True
        self._m_admitted.inc()
        self._emit(lane, first_tok)
        return True

    def _emit(self, lane, token):
        req = self.lanes[lane]
        req.generated.append(int(token))
        self._m_tokens.inc()
        if (req.eos_token_id is not None
                and int(token) == req.eos_token_id):
            self._retire_lane(lane, "eos")
        elif len(req.generated) >= req.max_new_tokens:
            self._retire_lane(lane, "length")

    # --- fused decode: dispatch / overlap / drain -------------------------
    def _decode_phase(self):
        """Double-buffered fused decode: dispatch tile N+1, then read
        back + book-keep tile N while the device computes. Membership
        changes force a drain + lane-state re-upload (the only time
        numpy touches the device state)."""
        if self.compat_step_loop:
            self._dirty = True      # pre-fused loop: re-upload every step
        # round-11 degradation sites fire BEFORE the drain/upload
        # decision so the membership machinery below drains any in-flight
        # tile (under its dispatch-time variant) before the lane-state
        # re-upload switches programs — a mid-flight rewind would
        # double-emit the tile's tokens
        if self.spec:
            try:
                fault_point("serve.draft_verify", depth=self.draft_depth)
            except _TRANSIENT_ERRORS:
                self._disable_spec("draft_verify_fault")
        if self.pool.fmt.quantized:
            try:
                fault_point("serve.kv_dequant", fmt=self.pool.fmt.name)
            except _TRANSIENT_ERRORS:
                self._degrade_kv_to_bf16()
        active = self._decode_active()
        if not active:
            if self._inflight:
                self._drain_all()
            return
        if self._inflight and (self._dirty
                               or self._inflight[-1].covers_all
                               or len(self._inflight) >= 2):
            if not self._drain_all():
                return                 # transient host-sync fault: retry
            active = self._decode_active()
            if not active:
                return
        if self._dirty or self._dev is None:
            self._upload_lane_state(active)
            self._phases.mark("lane_upload")
        t0 = time.perf_counter()
        try:
            fault_point("serve.decode_oom", active=len(active))
            with _span("serving.decode_step", active=len(active),
                       k=self.decode_steps):
                tile = self._dispatch()
        except MemoryError:
            # device OOM (or the serve.decode_oom fault site): shed one
            # lane and requeue it rather than killing every in-flight
            # request; the remaining lanes decode on the next step
            self._shed(active)
            return
        except Exception as e:  # noqa: BLE001 — XLA OOM is backend-typed
            if "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e):
                self._shed(active)
                return
            raise
        self._m_dispatches.inc()
        self._m_ahead.set(len(self._inflight))
        K = self.decode_steps
        prev_reqs = self._inflight[-1].reqs if self._inflight else None
        covers_all = all(
            (self.lanes[i].max_new_tokens - len(self.lanes[i].generated)
             - (K if prev_reqs is not None and prev_reqs[i]
                is self.lanes[i] else 0)) <= K
            for i in active)
        # snapshot only DECODE-ACTIVE lanes: a lane that is occupied but
        # still prefilling was masked dead on device — its tile row is
        # filler and must never be credited
        active_set = set(active)
        snap = [self.lanes[i] if i in active_set else None
                for i in range(self.max_batch)]
        tile_id = self._tile_seq
        self._tile_seq += 1
        d_variant = self._dev["variant"]
        key = ("decode" + (".sampled" if d_variant.startswith("sampled")
                           else "") + (".spec" if d_variant.endswith(".spec")
                                       else ""))
        self._inflight.append(_Inflight(
            tile, t0, snap, self._lane_epoch.copy(), K, covers_all,
            tile_id, spec=isinstance(tile, tuple), key=key))
        if self._rec.enabled:
            self._rec.record("dispatch", tile=tile_id, lanes=list(active),
                             epochs=[int(self._lane_epoch[i])
                                     for i in active], k=K)
        # overlapped host bookkeeping: process the PREVIOUS tile while
        # the device runs this one (compat mode drains its own tile too:
        # the old engine blocked on every token)
        keep = 0 if self.compat_step_loop else 1
        while len(self._inflight) > keep:
            if not self._drain_one():
                break

    def _disable_spec(self, why):
        """serve.draft_verify degradation: permanently fall back to the
        non-speculative fused decode. Streams continue byte-identically
        (speculation never changes the committed tokens); only the
        tokens-per-dispatch multiplier is lost. Unlike the brownout
        ladder's reversible switch, this is permanent: _spec_allowed
        goes False so a later brownout recovery cannot re-enable a
        faulted drafter."""
        self.spec = False
        self._spec_allowed = False
        _metric("serving_runtime_degradations_total",
                what="speculation_off").inc()
        if self._rec.enabled:
            self._rec.record("degrade", what="speculation_off", why=why)
        # _decode_phase drains in-flight tiles (flagged spec per-tile)
        # before honoring _dirty, so no committed token is re-emitted
        self._dirty = True

    def _degrade_kv_to_bf16(self):
        """serve.kv_dequant degradation: dequantize the WHOLE pool to the
        native dtype once (timed into serving_kv_dequant_seconds) and
        drop the quantized block format for the engine's lifetime. Every
        compiled program embedded the quantized pool dtypes, so the jit
        caches are cleared and programs recompile against the bf16 pool."""
        t0 = time.perf_counter()
        fmt = self.pool.fmt
        self.pool.k = fmt.decode(self.pool.k, self.pool.k_scale)
        self.pool.v = fmt.decode(self.pool.v, self.pool.v_scale)
        self.pool.k_scale = self.pool.v_scale = None
        self.pool.fmt = KVBlockFormat("native",
                                      native_dtype=self.embed_w.dtype)
        # the prefix index promised the OLD byte layout: every entry is
        # stale the instant the pool re-encodes, so drop them all (the
        # blocks free once no resident request still holds them)
        if self._prefix is not None:
            for b in self._prefix.clear():
                self.pool.unpin(b)
                self._m_pfx_evict.inc()
            self._m_pfx_shared.set(0)
        self._prefill_jit.clear()
        self._decode_jit.clear()
        _metric("serving_kv_dequant_seconds").observe(
            time.perf_counter() - t0)
        _metric("serving_runtime_degradations_total", what="kv_bf16").inc()
        if self._rec.enabled:
            self._rec.record("degrade", what="kv_bf16", fmt=fmt.name)

    # --- brownout knobs (round 14) ---------------------------------------
    # The ladder's setters are REVERSIBLE, unlike the fault degradations
    # above: they only flip the knob and mark lane state dirty. The
    # membership machinery drains any in-flight tile under its dispatch-
    # time program before the next dispatch compiles/reuses the new
    # (variant, K, D)-keyed program — so a mid-flight knob change can
    # never double-emit or drop a token, and byte-identity is exactly
    # the already-pinned across-K stream invariance.
    def _set_decode_steps(self, k):
        k = 1 if self.compat_step_loop else max(1, int(k))
        if k == self.decode_steps:
            return
        self.decode_steps = k
        self._dirty = True

    def _set_draft_depth(self, d):
        d = max(1, min(int(d), self.pool.block_size - 1))
        if d == self.draft_depth:
            return
        self.draft_depth = d
        self._dirty = True

    def _set_speculation(self, on):
        want = bool(on) and self._spec_allowed \
            and not self.compat_step_loop
        if want == self.spec:
            return
        self.spec = want
        self._dirty = True

    def _set_prefill_chunk_small(self, on):
        # force_small_prefill_chunk rung: future admissions plan their
        # prefill at the smallest compiled chunk width so each piece
        # holds the dispatch for the shortest possible time. No _dirty:
        # chunk planning is host-side at admission and every width in
        # _chunk_widths is already a compiled bucket. Plans already
        # issued are unchanged (admission-scoped, like every knob).
        self.chunk = self._chunk_widths[0] if on else self._base_chunk

    def _set_mnt_cap(self, cap):
        # cap_max_new_tokens rung: requests admitted while engaged are
        # clamped to `cap` generated tokens (reshaped, not shed). None
        # restores uncapped admission.
        self._mnt_cap = None if cap is None else max(1, int(cap))

    def _dispatch(self):
        d = self._dev
        variant = d["variant"]
        spec = variant.endswith(".spec")
        sampled = variant.startswith("sampled")
        quant = self.pool.fmt.quantized
        # the compiled program closes over K (decode_steps) and D
        # (draft_depth) at make time, so the cache key carries them:
        # a brownout transition swaps programs without clearing the
        # cache, and recovery swaps straight back to the warm base one
        jit_key = (variant, self.decode_steps,
                   self.draft_depth if spec else 0)
        fn = self._decode_jit.get(jit_key)
        cold = fn is None or fn._compiled is None
        if fn is None:
            # decode keeps donation (the KV pools must not double-buffer),
            # so the pipeline runs but the artifact store is bypassed
            # (pir reports cache="bypass:donate")
            from ..pir import pir_jit
            name = ("serving.decode" + (".sampled" if sampled else "")
                    + (".spec" if spec else ""))
            maker = self._make_decode_spec if spec else self._make_decode
            fn = pir_jit(maker(sampled), name=name,
                         donate_argnums=(4, 5, 6, 7) if quant else (4, 5),
                         extra_key=({"lora": self.adapters.program_key}
                                    if self.adapters is not None else None))
            self._decode_jit[jit_key] = fn
            # program construction counts as a retrace: the hot-swap
            # contract pins this counter's delta to 0 across adapter churn
            _metric("jit_retrace_total").inc()
        args = [self.stacked, self.embed_w, self.norm_w, self._out_w,
                self.pool.k, self.pool.v]
        if quant:
            args += [self.pool.k_scale, self.pool.v_scale]
        args += [d["toks"], d["lens"], d["alive"], d["rem"], d["eos"],
                 d["tables"]]
        if spec:
            args.append(d["hist"])
        if sampled:
            args += [d["seeds"], d["do_sample"], d["temp"], d["top_k"],
                     d["top_p"]]
        if self.adapters is not None:
            # adapter pool + per-lane slot ids ride at the very END so
            # the donated KV-pool argnums above never shift
            ad = self.adapters
            args += [ad.A_q, ad.B_q, ad.A_v, ad.B_v, d["adapter_ids"]]
        out = fn(*args)
        if spec:
            (tile, counts, d["toks"], d["lens"], d["alive"], d["rem"],
             d["hist"]) = out[:7]
            rest = out[7:]
            tile = (tile, counts)
        else:
            tile, d["toks"], d["lens"], d["alive"], d["rem"] = out[:5]
            rest = out[5:]
        if quant:
            (self.pool.k, self.pool.v,
             self.pool.k_scale, self.pool.v_scale) = rest
        else:
            self.pool.k, self.pool.v = rest
        key = ("decode" + (".sampled" if sampled else "")
               + (".spec" if spec else ""))
        if self.compile_reports.get(key) is None:
            rep = getattr(fn, "report", None)
            self.compile_reports[key] = rep
            if rep is not None and rep.fallback == "verify":
                # the IR verifier statically rejected the decode program
                # (donation-alias or a structural rule): the engine keeps
                # serving on plain jax.jit, but donation safety of the
                # pool buffers is no longer *proven* — loud, not silent
                warnings.warn(
                    f"decode program {key!r} was rejected by the PIR "
                    f"verifier and fell back to plain jax.jit; see "
                    f"pir_verify_failures_total{{rule}} for the rule",
                    RuntimeWarning, stacklevel=2)
        self._phases.mark("compile" if cold else "decode.dispatch")
        return tile

    def _drain_all(self):
        while self._inflight:
            if not self._drain_one():
                return False
        return True

    def _drain_one(self):
        """Read back the oldest in-flight tile and run host bookkeeping.
        Returns False on a transient host-sync fault (tile kept, retried
        next step)."""
        infl = self._inflight[0]
        try:
            fault_point("serve.hostsync_read")
            t0 = time.perf_counter()
            self._phases.mark("decode.readback")
            if infl.spec:
                arr = (np.asarray(infl.tile[0]), np.asarray(infl.tile[1]))
            else:
                arr = np.asarray(infl.tile)
        except MemoryError:
            self._inflight.popleft()
            self._shed(self._decode_active())
            return True
        except _TRANSIENT_ERRORS:
            self._m_hostsync_retries.inc()
            return False
        except Exception as e:  # noqa: BLE001 — XLA OOM is backend-typed
            if "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e):
                self._inflight.popleft()
                self._shed(self._decode_active())
                return True
            raise
        t1 = time.perf_counter()
        self._inflight.popleft()
        self._m_hostsync.observe(t1 - t0)
        self._phases.mark("hostsync")
        self._cost_observe(infl.key, t1 - infl.t_dispatch)
        # one fused dispatch advances every active lane K tokens, so the
        # dispatch->readback wall time over K IS the per-token latency.
        # Exemplar: the first live lane's trace id stands for the tile
        # (one tile serves many lanes; the span links carry all of them)
        ex = None
        if self._reg.enabled:
            for r in infl.reqs:
                if r is not None and not r.done:
                    ex = r.trace_id
                    break
        if not infl.spec:
            per_tok = (t1 - infl.t_dispatch) / infl.k
            self._m_tpot.observe(per_tok, exemplar=ex)
            if self.scheduler is not None:
                self.scheduler.note_tpot(per_tok)
            for t in sorted({r.tenant for r in infl.reqs
                             if r is not None and not r.done}):
                _metric("serving_tenant_tpot_seconds",
                        tenant=t).observe(per_tok)
            if self.adapters is not None:
                for a in sorted({(r.adapter or "base") for r in infl.reqs
                                 if r is not None and not r.done}):
                    _metric("serving_adapter_tpot_seconds",
                            adapter=a).observe(per_tok)
        if self._rec.enabled:
            self._rec.record("readback", tile=infl.tile_id,
                             wait_ms=round((t1 - t0) * 1e3, 3))
        if self._tracer.enabled:
            self._trace_tile(infl, t1)
        if infl.spec:
            self._process_tile_spec(arr[0], arr[1], infl, t1, ex)
        else:
            self._process_tile(arr, infl)
        ph = self._phases
        if ph.enabled:
            # token crediting/emission since the hostsync mark is the
            # commit phase; the tile's device time splits evenly across
            # the tenants it served (one dispatch advances all lanes)
            ph.mark("commit")
            tenants = sorted({r.tenant for r in infl.reqs if r is not None})
            ph.credit_tenants(tenants, t1 - infl.t_dispatch)
        return True

    def _trace_tile(self, infl, t1):
        """Span-link a drained tile: one engine-side serving.decode_tile
        span linking every request it advanced, plus a request-side
        request.decode.tile span in each lane's trace group (the lanes
        here match _process_tile's crediting rules exactly)."""
        t0_ns = int(infl.t_dispatch * 1e9)
        dur_ns = int((t1 - infl.t_dispatch) * 1e9)
        links = []
        for lane, req in enumerate(infl.reqs):
            if (req is None or req.done
                    or self.lanes[lane] is not req
                    or self._lane_epoch[lane] != infl.epochs[lane]):
                continue
            links.append(req.trace_id)
            self._tracer.add_span(
                "request.decode.tile", t0_ns, dur_ns,
                trace_id=req.trace_id, tid=LANE_TID_BASE + lane,
                tid_name=f"lane {lane}",
                args={"rid": req.rid, "tile": infl.tile_id, "k": infl.k})
        self._tracer.add_span(
            "serving.decode_tile", t0_ns, dur_ns,
            args={"tile": infl.tile_id, "k": infl.k},
            links=links or None)

    # --- static cost model (pir/analysis.py CostModel) --------------------
    def _cost_observe(self, key, dt):
        """Predicted-vs-measured cost of one dispatch of the program
        compile_reports[key]. The FIRST measured dispatch calibrates the
        platform scale (its ratio is 1.0 by construction); every later
        one updates the per-program ratio gauge and the pooled error
        histogram whose exemplar carries the worst-predicted program."""
        rep = self.compile_reports.get(key)
        cost = getattr(rep, "cost", None)
        if cost is None or cost.raw_seconds <= 0 or dt <= 0:
            return
        if self._cost_scale is None:
            self._cost_scale = dt / cost.raw_seconds
        ratio = dt / (cost.raw_seconds * self._cost_scale)
        _metric("pir_cost_ratio", program=key).set(ratio)
        self._m_cost_err.observe(ratio, exemplar=key)

    def predicted_costs(self):
        """{program key: {flops, bytes, raw_seconds, seconds}} for every
        compiled program with a stamped ProgramCost; `seconds` is the
        calibrated prediction (None until a dispatch calibrated the
        scale). The loadgen harness derives its slo_headroom capacity
        signal from this."""
        out = {}
        for key, rep in self.compile_reports.items():
            cost = getattr(rep, "cost", None)
            if cost is None:
                continue
            out[key] = {"flops": cost.flops, "bytes": cost.bytes,
                        "raw_seconds": cost.raw_seconds,
                        "seconds": (cost.raw_seconds * self._cost_scale
                                    if self._cost_scale else None)}
        return out

    def predicted_service_seconds(self, output_tokens=32):
        """Calibrated engine seconds one request of `output_tokens`
        consumes: its share of the fused decode dispatches (a tile
        advances all max_batch lanes together) plus one prefill chunk.
        None until the cost model is calibrated — callers fall back to
        measured throughput."""
        if self._cost_scale is None:
            return None
        costs = self.predicted_costs()
        decode = next((c for k, c in sorted(costs.items())
                       if k.startswith("decode")), None)
        if decode is None or decode["seconds"] is None:
            return None
        # priced against the BASE decode program (the calibrated report
        # belongs to it): the estimate stays a stable capacity signal
        # for the undegraded engine even while the brownout ladder has
        # decode_steps temporarily shrunk
        t = (output_tokens / self._base_decode_steps) \
            * decode["seconds"] / self.max_batch
        prefill = next((c for k, c in sorted(costs.items())
                        if k.startswith("prefill")), None)
        if prefill is not None and prefill["seconds"] is not None:
            t += prefill["seconds"]
        return t

    def _process_tile(self, tile, infl):
        """Credit a [B, K] token tile: walk each lane's K tokens with the
        SAME eos/length rules the device applied, so host mirrors and
        device carry stay in lockstep without reading lens/alive back."""
        credited = 0
        for lane in range(self.max_batch):
            req = infl.reqs[lane]
            if (req is None or req.done
                    or self.lanes[lane] is not req
                    or self._lane_epoch[lane] != infl.epochs[lane]):
                continue            # occupancy changed while in flight
            for k in range(infl.k):
                self.lane_len[lane] += 1
                tok = int(tile[lane, k])
                self.lane_tok[lane] = tok
                credited += 1
                self._emit(lane, tok)
                if req.done or self.lanes[lane] is not req:
                    break
        self._m_tok_disp.set(credited)

    def _process_tile_spec(self, tile, counts, infl, t1, ex):
        """Credit a speculative tile: tokens [B, K, D+1] + counts [B, K].
        Row k of a lane commits its first counts[lane, k] tokens (the
        accepted draft run plus one correction token); counts drops to 0
        the step after the lane died on device. The host walk applies
        the same eos/length rules as the device, and the draft/accept
        accounting plus the acceptance-rate exemplar (worst-accepting
        request in the tile) are credited here, once per drained tile."""
        D = tile.shape[2] - 1
        credited = 0
        lanes_credited = 0
        drafted = accepted = 0
        worst = None
        for lane in range(self.max_batch):
            req = infl.reqs[lane]
            if (req is None or req.done
                    or self.lanes[lane] is not req
                    or self._lane_epoch[lane] != infl.epochs[lane]):
                continue            # occupancy changed while in flight
            lanes_credited += 1
            lane_drafted = lane_accepted = 0
            for k in range(infl.k):
                c = int(counts[lane, k])
                if c <= 0:
                    break
                lane_drafted += D
                lane_accepted += c - 1
                for i in range(c):
                    self.lane_len[lane] += 1
                    tok = int(tile[lane, k, i])
                    self.lane_tok[lane] = tok
                    credited += 1
                    self._emit(lane, tok)
                    if req.done or self.lanes[lane] is not req:
                        break
                if req.done or self.lanes[lane] is not req:
                    break
            drafted += lane_drafted
            accepted += lane_accepted
            if lane_drafted:
                rate = lane_accepted / lane_drafted
                if worst is None or rate < worst[0]:
                    worst = (rate, req.trace_id)
        if drafted:
            self._m_draft.inc(drafted)
            self._m_accept.inc(accepted)
            self._m_accept_rate.observe(
                accepted / drafted, exemplar=worst[1] if worst else None)
        self._m_tok_disp.set(credited)
        # effective per-token latency: the dispatch->readback wall over
        # the tokens one lane actually committed (> K with acceptance)
        eff = credited / max(1, lanes_credited)
        per_tok = (t1 - infl.t_dispatch) / max(1.0, eff)
        self._m_tpot.observe(per_tok, exemplar=ex)
        if self.scheduler is not None:
            self.scheduler.note_tpot(per_tok)
        for t in sorted({r.tenant for r in infl.reqs
                         if r is not None and not r.done}):
            _metric("serving_tenant_tpot_seconds", tenant=t).observe(per_tok)
        if self.adapters is not None:
            for a in sorted({(r.adapter or "base") for r in infl.reqs
                             if r is not None and not r.done}):
                _metric("serving_adapter_tpot_seconds",
                        adapter=a).observe(per_tok)

    # --- device-resident lane state ---------------------------------------
    def _upload_lane_state(self, active):
        """Rebuild the device lane state from the host mirrors — called
        ONLY on membership change (admission / retire / shed / recovery),
        never in the steady state. Counted so the A/B evidence can show
        uploads << dispatches."""
        B, MB = self.max_batch, self.max_blocks_per_seq
        tables = np.full((B, MB), self.pool.scratch_block, np.int32)
        lens = np.zeros(B, np.int32)
        toks = np.zeros(B, np.int32)
        alive = np.zeros(B, bool)
        rem = np.zeros(B, np.int32)
        eos = np.full(B, -1, np.int32)
        sampled = any(self.lanes[i].do_sample for i in active)
        if sampled:
            seeds = np.zeros(B, np.uint32)
            do_s = np.zeros(B, bool)
            temp = np.ones(B, np.float32)
            top_k = np.zeros(B, np.int32)
            top_p = np.ones(B, np.float32)
        for i in active:
            r = self.lanes[i]
            t = self.pool.tables[r.rid]
            tables[i, :len(t)] = t
            lens[i] = self.lane_len[i]
            toks[i] = self.lane_tok[i]
            alive[i] = True
            rem[i] = r.max_new_tokens - len(r.generated)
            if r.eos_token_id is not None:
                eos[i] = r.eos_token_id
            if sampled and r.do_sample:
                do_s[i] = True
                seeds[i] = r.sample_seed
                temp[i] = max(r.temperature, 1e-6)
                top_k[i] = r.top_k
                top_p[i] = r.top_p
        variant = ("sampled" if sampled else "greedy") + \
            (".spec" if self.spec else "")
        dev = dict(variant=variant,
                   toks=jnp.asarray(toks), lens=jnp.asarray(lens),
                   alive=jnp.asarray(alive), rem=jnp.asarray(rem),
                   eos=jnp.asarray(eos), tables=jnp.asarray(tables))
        if self.spec:
            # device-resident token history per lane (prompt + committed
            # tokens up to the cached length) — the drafter's lookup
            # corpus; extended ON DEVICE inside the scan, so like the
            # rest of the lane state it is only rebuilt here on
            # membership change
            hmax = self.max_blocks_per_seq * self.pool.block_size
            hist = np.zeros((B, hmax), np.int32)
            for i in active:
                r = self.lanes[i]
                seq = (np.concatenate([r.prompt,
                                       np.asarray(r.generated[:-1],
                                                  np.int32)])
                       if r.generated else r.prompt)
                n = min(seq.size, hmax)
                hist[i, :n] = seq[:n]
            dev["hist"] = jnp.asarray(hist)
        if sampled:
            dev.update(seeds=jnp.asarray(seeds), do_sample=jnp.asarray(do_s),
                       temp=jnp.asarray(temp), top_k=jnp.asarray(top_k),
                       top_p=jnp.asarray(top_p))
        if self.adapters is not None:
            # per-lane adapter slot ids: slot 0 (the reserved all-zero
            # adapter) for empty lanes and base-weight requests, so the
            # gathered low-rank delta is exactly 0 there
            aids = np.zeros(B, np.int32)
            for i in active:
                aids[i] = self.lanes[i].adapter_id
            dev["adapter_ids"] = jnp.asarray(aids)
        self._dev = dev
        self._dirty = False
        self._m_uploads.inc()
        if self._rec.enabled:
            self._rec.record("membership", active=list(active),
                             variant=dev["variant"])

    # --- compiled programs ------------------------------------------------
    def _make_prefill_chunk(self):
        cfg = self.cfg
        fmt = self.pool.fmt
        quant = fmt.quantized
        lora = self.adapters is not None

        def run(stacked, embed_w, norm_w, head_w, kpool, vpool, *rest):
            rest = list(rest)
            if lora:
                # adapter pools ride at the END of the arg list (after
                # every positional the storeless program takes) so the
                # two programs share their leading signature
                aq_p, bq_p, av_p, bv_p, aid = rest[-5:]
                rest = rest[:-5]
            if quant:
                kspool, vspool, ids, start, last_idx, table_row = rest
            else:
                ids, start, last_idx, table_row = rest
            with jax.named_scope("pt.embed"):
                h = jnp.take(embed_w, ids, axis=0)       # (1, C, H)

            def layer(hh, xs):
                if lora:
                    aq_l, bq_l, av_l, bv_l = xs[-4:]
                    xs = xs[:-4]
                    # single lane per prefill call: one scalar adapter id
                    # gathers this layer's (A, B) factors from the pool
                    delta = (aq_l[aid], bq_l[aid], av_l[aid], bv_l[aid])
                else:
                    delta = None
                if quant:
                    lp, kc, vc, ks, vs = xs
                    hh, pools = _llama_layer_prefill_chunk(
                        lp, hh, kc, vc, table_row, start, cfg,
                        fmt=fmt, kc_scale=ks, vc_scale=vs, lora=delta)
                else:
                    lp, kc, vc = xs
                    hh, pools = _llama_layer_prefill_chunk(
                        lp, hh, kc, vc, table_row, start, cfg, lora=delta)
                return hh, pools

            xs = ((stacked, kpool, vpool, kspool, vspool) if quant
                  else (stacked, kpool, vpool))
            if lora:
                xs = xs + (aq_p, bq_p, av_p, bv_p)
            h, pools = jax.lax.scan(layer, h, xs)
            h_last = h[0, last_idx]     # dynamic index: traced position
            with jax.named_scope("pt.head"):
                logits = (_rms(h_last, norm_w, cfg["eps"]) @ head_w
                          ).astype(jnp.float32)
            return (logits,) + tuple(pools)

        return run

    def _make_decode(self, sampled: bool):
        cfg = self.cfg
        K = self.decode_steps
        scratch = self.pool.scratch_block
        fmt = self.pool.fmt
        quant = fmt.quantized
        lora = self.adapters is not None

        def run(stacked, embed_w, norm_w, head_w, kpool, vpool, *rest):
            rest = list(rest)
            if lora:
                # adapter pools + per-lane slot ids ride at the very END
                # (after sampling state) so the donated KV argnums and
                # the storeless signature prefix never shift
                aq_p, bq_p, av_p, bv_p, aids = rest[-5:]
                rest = rest[:-5]
            if quant:
                (kspool, vspool, toks, lens, alive, rem, eos_ids, tables,
                 *sample_state) = rest
            else:
                toks, lens, alive, rem, eos_ids, tables, *sample_state = \
                    rest
                kspool = vspool = None
            eps, theta = cfg["eps"], cfg["theta"]
            nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
            B = toks.shape[0]
            if sampled:
                seeds, do_sample, temp, top_k, top_p = sample_state

            def step(carry, _):
                if quant:
                    (toks, lens, alive, rem, kpool, vpool,
                     kspool, vspool) = carry
                else:
                    toks, lens, alive, rem, kpool, vpool = carry
                    kspool = vspool = None
                with jax.named_scope("pt.embed"):
                    h = jnp.take(embed_w, toks[:, None], axis=0)  # (B, 1, H)
                pos = lens[:, None]                            # write pos

                def layer(hh, xs):
                    if lora:
                        aq_l, bq_l, av_l, bv_l = xs[-4:]
                        xs = xs[:-4]
                    if quant:
                        lp, kc, vc, ks, vs = xs
                    else:
                        lp, kc, vc = xs
                        ks = vs = None
                    with jax.named_scope("pt.attn"):
                        x = _rms(hh, lp["input_layernorm.weight"], eps)
                        q_lin = x @ lp["self_attn.q_proj.weight"]
                        v_lin = x @ lp["self_attn.v_proj.weight"]
                        if lora:
                            # per-lane batched low-rank delta: gather each
                            # lane's (A, B) factors by slot id, one einsum
                            # over the whole tile. Slot 0 is all-zeros, so
                            # base lanes add exactly 0.
                            aq = jnp.take(aq_l, aids, axis=0)   # (B, H, r)
                            bq = jnp.take(bq_l, aids, axis=0)   # (B, r, Dq)
                            q_lin = q_lin + jnp.einsum(
                                "bch,bhr,brd->bcd", x,
                                aq.astype(x.dtype), bq.astype(x.dtype))
                            av = jnp.take(av_l, aids, axis=0)
                            bv = jnp.take(bv_l, aids, axis=0)
                            v_lin = v_lin + jnp.einsum(
                                "bch,bhr,brd->bcd", x,
                                av.astype(x.dtype), bv.astype(x.dtype))
                        q = q_lin.reshape(B, 1, nh, hd)
                        k = (x @ lp["self_attn.k_proj.weight"]
                             ).reshape(B, 1, nkv, hd)
                        v = v_lin.reshape(B, 1, nkv, hd)
                        q = _rope(q, pos, theta)[:, 0]
                        k = _rope(k, pos, theta)[:, 0]
                        v = v[:, 0]
                        # passthrough formats route through write_to_cache
                        # with the exact pre-round-11 ops (byte-identical
                        # trace); quantized formats also update the scales
                        kc, vc, ks, vs = kv_write_token(
                            fmt if quant else None, kc, vc, ks, vs, k, v,
                            tables, lens, active=alive, scratch_block=scratch)
                        attn = paged_attention_decode_inner(
                            q, kc, vc, tables, lens + 1,
                            scale=1.0 / (hd ** 0.5),
                            fmt=fmt if quant else None,
                            k_scale_cache=ks, v_scale_cache=vs)
                        hh = hh + (attn.reshape(B, 1, nh * hd)
                                   @ lp["self_attn.o_proj.weight"])
                    hh = _llama_mlp(lp, hh, eps)
                    return hh, ((kc, vc, ks, vs) if quant else (kc, vc))

                xs = ((stacked, kpool, vpool, kspool, vspool) if quant
                      else (stacked, kpool, vpool))
                if lora:
                    xs = xs + (aq_p, bq_p, av_p, bv_p)
                h, pools = jax.lax.scan(layer, h, xs)
                if quant:
                    kpool, vpool, kspool, vspool = pools
                else:
                    kpool, vpool = pools
                with jax.named_scope("pt.head"):
                    logits = (_rms(h[:, 0], norm_w, eps) @ head_w).astype(
                        jnp.float32)
                with jax.named_scope("pt.serve.sample"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    if sampled:
                        samp = _device_sample(logits, seeds, lens, temp,
                                              top_k, top_p)
                        nxt = jnp.where(do_sample, samp, nxt)
                # frozen lanes re-emit their last token (never credited:
                # the host walk stops at the same eos/length boundary)
                nxt = jnp.where(alive, nxt, toks)
                rem = rem - alive.astype(rem.dtype)
                alive_next = alive & (nxt != eos_ids) & (rem > 0)
                lens = lens + alive.astype(lens.dtype)
                out = (nxt, lens, alive_next, rem, kpool, vpool)
                if quant:
                    out = out + (kspool, vspool)
                return out, nxt

            carry0 = (toks, lens, alive, rem, kpool, vpool)
            if quant:
                carry0 = carry0 + (kspool, vspool)
            carry, tile = jax.lax.scan(step, carry0, None, length=K)
            toks, lens, alive, rem = carry[:4]
            return (jnp.moveaxis(tile, 0, 1), toks, lens, alive, rem
                    ) + tuple(carry[4:])

        return run

    def _make_decode_spec(self, sampled: bool):
        """The speculative fused decode program: each of the K scan steps
        proposes draft_depth tokens from the drafter, verifies the step
        token + drafts in ONE batched forward (C = draft_depth+1 queries
        per lane against the paged pool), accepts the leading run of
        drafts that match what the sequential policy would emit, rolls
        back the rejected slots' cache writes, and commits the accepted
        run plus one correction token — up to K*(draft_depth+1) tokens
        per dispatch, with the committed stream exactly equal to the
        non-speculative path (greedy by argmax equality; sampled lanes
        by the position-keyed PRNG, which makes the sequential sample at
        every position a pure function of (seed, position))."""
        cfg = self.cfg
        K = self.decode_steps
        D = self.draft_depth
        C = D + 1
        scratch = self.pool.scratch_block
        fmt = self.pool.fmt
        quant = fmt.quantized
        hmax = self.max_blocks_per_seq * self.pool.block_size
        drafter = self._drafter
        ngram = self.draft_ngram
        lora = self.adapters is not None

        def run(stacked, embed_w, norm_w, head_w, kpool, vpool, *rest):
            rest = list(rest)
            if lora:
                # same tail contract as the base decode program: adapter
                # state last, donated argnums untouched
                aq_p, bq_p, av_p, bv_p, aids = rest[-5:]
                rest = rest[:-5]
            if quant:
                (kspool, vspool, toks, lens, alive, rem, eos_ids, tables,
                 hist, *sample_state) = rest
            else:
                (toks, lens, alive, rem, eos_ids, tables, hist,
                 *sample_state) = rest
                kspool = vspool = None
            eps, theta = cfg["eps"], cfg["theta"]
            nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
            B = toks.shape[0]
            rows = jnp.arange(B)
            if sampled:
                seeds, do_sample, temp, top_k, top_p = sample_state

            def step(carry, _):
                if quant:
                    (toks, lens, alive, rem, hist, kpool, vpool,
                     kspool, vspool) = carry
                else:
                    toks, lens, alive, rem, hist, kpool, vpool = carry
                    kspool = vspool = None
                # record the step token into the running history (dead
                # lanes scatter out of bounds, which JAX drops)
                hidx = jnp.where(alive, lens, hmax)
                hist = hist.at[rows, hidx].set(toks)
                if drafter is not None:
                    drafts = drafter(hist, lens, toks, D).astype(jnp.int32)
                else:
                    drafts = _ngram_draft(hist, lens, toks, D, ngram)
                u = jnp.concatenate([toks[:, None], drafts], axis=1)
                didx = jnp.where(alive[:, None],
                                 lens[:, None] + 1 + jnp.arange(D)[None, :],
                                 hmax)
                hist = hist.at[rows[:, None], didx].set(drafts)
                with jax.named_scope("pt.embed"):
                    h = jnp.take(embed_w, u, axis=0)           # (B, C, H)
                pos = lens[:, None] + jnp.arange(C)[None, :]   # (B, C)

                def layer(hh, xs):
                    if lora:
                        aq_l, bq_l, av_l, bv_l = xs[-4:]
                        xs = xs[:-4]
                    if quant:
                        lp, kc, vc, ks, vs = xs
                    else:
                        lp, kc, vc = xs
                        ks = vs = None
                    with jax.named_scope("pt.attn"):
                        x = _rms(hh, lp["input_layernorm.weight"], eps)
                        q_lin = x @ lp["self_attn.q_proj.weight"]
                        v_lin = x @ lp["self_attn.v_proj.weight"]
                        if lora:
                            # x is (B, C, H) here — the same batched einsum
                            # covers all C verify positions of every lane
                            aq = jnp.take(aq_l, aids, axis=0)
                            bq = jnp.take(bq_l, aids, axis=0)
                            q_lin = q_lin + jnp.einsum(
                                "bch,bhr,brd->bcd", x,
                                aq.astype(x.dtype), bq.astype(x.dtype))
                            av = jnp.take(av_l, aids, axis=0)
                            bv = jnp.take(bv_l, aids, axis=0)
                            v_lin = v_lin + jnp.einsum(
                                "bch,bhr,brd->bcd", x,
                                av.astype(x.dtype), bv.astype(x.dtype))
                        q = q_lin.reshape(B, C, nh, hd)
                        k = (x @ lp["self_attn.k_proj.weight"]
                             ).reshape(B, C, nkv, hd)
                        v = v_lin.reshape(B, C, nkv, hd)
                        q = _rope(q, pos, theta)
                        k = _rope(k, pos, theta)
                        # kv.write effect scope (stamped inside the callee):
                        # the verify-write must stay ordered before the
                        # rollback below — the PIR effect-order rule rejects
                        # any pass that migrates one past the other
                        kc, vc, ks, vs, saved = kv_write_tokens(
                            fmt if quant else None, kc, vc, ks, vs, k, v,
                            tables, lens, active=alive, scratch_block=scratch)
                        attn = paged_attention_verify(
                            q, kc, vc, tables, lens, scale=1.0 / (hd ** 0.5),
                            fmt=fmt if quant else None,
                            k_scale_cache=ks, v_scale_cache=vs)
                        hh = hh + (attn.reshape(B, C, nh * hd)
                                   @ lp["self_attn.o_proj.weight"])
                    hh = _llama_mlp(lp, hh, eps)
                    out = (kc, vc, ks, vs) if quant else (kc, vc)
                    return hh, (out, saved)

                xs = ((stacked, kpool, vpool, kspool, vspool) if quant
                      else (stacked, kpool, vpool))
                if lora:
                    xs = xs + (aq_p, bq_p, av_p, bv_p)
                h, (pools, saved) = jax.lax.scan(layer, h, xs)
                with jax.named_scope("pt.head"):
                    logits = (_rms(h, norm_w, eps) @ head_w).astype(
                        jnp.float32)                           # (B, C, V)
                # g[:, i] is the token the sequential policy emits at
                # position lens+i+1 GIVEN the drafts up to i were right —
                # so the committed tokens are exactly a prefix of g
                with jax.named_scope("pt.serve.sample"):
                    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    if sampled:
                        samp = jnp.stack(
                            [_device_sample(logits[:, i], seeds, lens + i,
                                            temp, top_k, top_p)
                             for i in range(C)], axis=1)
                        g = jnp.where(do_sample[:, None], samp, g)
                # leading-run acceptance; +1 = the correction token
                matches = (drafts == g[:, :D]).astype(jnp.int32)
                n_acc = jnp.cumprod(matches, axis=1).sum(axis=1)
                commits = jnp.minimum(n_acc + 1, rem)
                iseos = g == eos_ids[:, None]
                eos_clip = jnp.where(iseos.any(axis=1),
                                     jnp.argmax(iseos, axis=1) + 1, C)
                commits = jnp.minimum(commits, eos_clip)
                commits = jnp.where(alive, commits, 0)
                # roll back the rejected slots' writes layer by layer
                # (kept and dead-lane restores are routed to scratch)
                keep = ((jnp.arange(C)[None, :] < commits[:, None])
                        & alive[:, None])

                def restore(_, xs):
                    if quant:
                        (kc, vc, ks, vs), sv = xs
                    else:
                        (kc, vc), sv = xs
                        ks = vs = None
                    kc, vc, ks, vs = kv_rollback_tokens(
                        fmt if quant else None, kc, vc, ks, vs, sv,
                        tables, lens, keep, active=alive,
                        scratch_block=scratch)
                    return None, ((kc, vc, ks, vs) if quant
                                  else (kc, vc))

                _, pools = jax.lax.scan(restore, None, (pools, saved))
                if quant:
                    kpool, vpool, kspool, vspool = pools
                else:
                    kpool, vpool = pools
                last = jnp.clip(commits - 1, 0, C - 1)
                g_last = g[rows, last]
                toks_next = jnp.where(alive, g_last, toks)
                ended_eos = alive & (commits > 0) & (g_last == eos_ids)
                rem = rem - commits
                alive_next = alive & ~ended_eos & (rem > 0)
                lens = lens + commits
                out = (toks_next, lens, alive_next, rem, hist,
                       kpool, vpool)
                if quant:
                    out = out + (kspool, vspool)
                return out, (g, commits.astype(jnp.int32))

            carry0 = (toks, lens, alive, rem, hist, kpool, vpool)
            if quant:
                carry0 = carry0 + (kspool, vspool)
            carry, (tile, counts) = jax.lax.scan(step, carry0, None,
                                                 length=K)
            toks, lens, alive, rem, hist = carry[:5]
            return (jnp.moveaxis(tile, 0, 1), jnp.moveaxis(counts, 0, 1),
                    toks, lens, alive, rem, hist) + tuple(carry[5:])

        return run


def _ngram_draft(hist, lens, toks, depth, ngram):
    """Default self-drafter: prompt-lookup decoding. For each lane, find
    the most recent earlier occurrence of the trailing `ngram`-token
    suffix of (history + step token) and propose the `depth` tokens that
    followed it; lanes with no match propose `depth` copies of the step
    token (a valid — if rarely accepted — draft). Pure jnp over the
    device-resident history buffer, so it traces into the fused scan."""
    hmax = hist.shape[1]
    cand = jnp.arange(hmax)

    def one(h, n, t):
        # h[n] is the step token (scattered by the caller); compare the
        # ngram ending at each candidate position against the one at n.
        # Candidates must leave the whole continuation in the PAST
        # (cand + depth < n): a more recent match would read positions
        # >= n, which hold the previous step's rejected-draft leftovers
        ok = (cand >= ngram - 1) & (cand + depth < n)
        for gback in range(ngram):
            ok &= (h[jnp.clip(cand - gback, 0, hmax - 1)]
                   == h[jnp.clip(n - gback, 0, hmax - 1)])
        j = jnp.max(jnp.where(ok, cand, -1))
        cont = h[jnp.clip(j + 1 + jnp.arange(depth), 0, hmax - 1)]
        return jnp.where(j >= 0, cont, jnp.full((depth,), t))

    return jax.vmap(one)(hist, lens, toks).astype(jnp.int32)


def _device_sample(logits, seeds, lens, temperature, top_k, top_p):
    """Per-lane on-device sampling: temperature -> top-k -> nucleus ->
    categorical, all vectorized over lanes. Randomness comes from
    fold_in(key(lane_seed), absolute_position), so a lane's stream is a
    pure function of (seed, position) — byte-identical no matter how the
    decode steps are tiled (decode_steps=1 vs K)."""
    B, V = logits.shape
    z = logits / jnp.maximum(temperature, 1e-6)[:, None]
    svals = jnp.sort(z, axis=-1)[:, ::-1]               # descending
    idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(svals, idx[:, None], axis=-1)
    z = jnp.where((top_k > 0)[:, None] & (z < kth), -jnp.inf, z)
    probs = jax.nn.softmax(z, axis=-1)
    order = jnp.argsort(-probs, axis=-1)
    sp = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(sp, axis=-1)
    keep_sorted = (cum - sp) < top_p[:, None]
    keep_sorted = keep_sorted.at[:, 0].set(True)  # top_p=0 keeps argmax
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(B)[:, None], order].set(keep_sorted)
    z = jnp.where((top_p < 1.0)[:, None] & ~keep, -jnp.inf, z)
    keys = jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.key(s), p))(seeds, lens)
    return jax.vmap(jax.random.categorical)(keys, z).astype(jnp.int32)
