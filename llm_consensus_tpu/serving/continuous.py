"""Continuous batching: token-level request interleaving on one chip.

The :class:`llm_consensus_tpu.serving.scheduler.BatchScheduler` batches
whole requests (a batch runs to completion before the next starts); this
module admits and retires requests at *decode-step* granularity, vLLM
style, re-founded on XLA's compile-once constraint:

- One jitted, donated decode-step program over a fixed ``max_slots``-wide
  paged cache (:mod:`llm_consensus_tpu.models.paged_cache`): shapes never
  change, so the hot loop never recompiles. Admission/retirement mutate
  page tables and lengths — data, not shapes.
- **Chunked prefill interleaved with decode** (PR 2): prompts prefill in
  fixed-size chunks scheduled as work units BETWEEN decode steps
  (compile-once per (chunk width, lanes) pair, paged K/V scatter per
  chunk — :func:`llm_consensus_tpu.models.transformer.prefill_chunk_paged`),
  so running slots keep decoding while new prompts fill; the ready
  chunks of several prefilling sequences share one program, a lane each
  (PR 31: a weight read serves up to L chunks). A mid-prefill
  sequence's device table row stays NULL (the decode program never sees
  it); the chunk program writes through an explicit host-side table.
- **Copy-on-write shared prefixes**: admission hashes the prompt's
  page-aligned prefix into a per-shard
  :class:`~llm_consensus_tpu.models.paged_cache.PrefixRegistry`; full
  pages of an already-resident prefix are refcount-mapped into the new
  sequence's table instead of re-prefilled (the consensus panel's N
  personas over one question prefill the common header ONCE), and a
  partially-matching boundary page is copied
  (:func:`~llm_consensus_tpu.models.paged_cache.copy_page`), never
  shared — decode writes land only in private pages. Registration
  happens at admission, gated by per-page readiness flags, so a burst
  of same-prefix requests dedups against the first request's in-flight
  prefill instead of racing it.
- **Host-RAM offload tier** (PR 4, :mod:`llm_consensus_tpu.serving.
  offload`): with ``host_cache_bytes > 0``, prefix-registry eviction
  DEMOTES ready pages to a byte-budgeted host LRU store instead of
  dropping them, and admission falls through registry-miss → host-hit,
  restoring pages via ``device_put`` + install scheduled between
  decode steps exactly like prefill chunks. Restored pages re-register
  under the same per-page readiness gates, so a same-prefix burst
  dedups against an in-flight restore like an in-flight prefill — and
  a restored prefix is byte-identical to a re-prefilled one (tested).
- A host thread drives: admit waiting requests into free slots, run at
  most one restore or chunk program, run one decode step for all
  slots, sample,
  retire EOS/length-capped slots, resolve futures. Inactive slots decode
  into the reserved NULL page and their outputs are discarded (the cost
  of a dead slot is one row of an already-batched matmul — negligible
  next to recompilation or bubbles).
- **Pipelined decode dispatch** (PR 6, ``pipeline_depth``, default 2):
  the host loop is a software pipeline, not a dispatch→sync→bookkeep
  lockstep — program *n+1* is enqueued before program *n*'s tokens are
  fetched, fed from *n*'s device-resident token output, so all host
  work (stop scans, retirement, group bookkeeping, chunked-prefill
  admission, host-tier restores) happens while the device is already
  running the next program. Retirement lags by the in-flight depth
  (overshoot tokens are discarded on fetch and pre-budgeted into page
  reservations); restores and CoW boundary copies drain the pipeline
  first (``gateway_pipeline_flushes_total``). Depth 1 is the
  serialized loop; outputs are byte-identical at every depth (tested).

- **Mesh-native hot path** (PR 13): pass ``mesh=`` and the WHOLE stack
  shards — pool pages and slot blocks over ``data`` (one host
  allocator + prefix registry per data shard, so every row's table is
  shard-local), kv heads over ``model``, params via ``shard_params``,
  the draft pool with the target's — and every feature above plus
  fused dispatch, grouped prefix attention, multi-round decode, spec
  decode, and the host tier ENGAGES, serving byte-identical text to
  the single-chip batcher (tests/test_mesh_serving.py parity grid;
  README Serving engage matrix). The Pallas ragged kernel runs under
  shard_map with per-shard page-id rebasing; configs it can't shard
  (``transformer.ragged_mesh_shardable``) take the GSPMD-sharded XLA
  reference instead — the one remaining kernel-level fallback.

Pages for the whole request (prompt + max_new_tokens) are reserved at
admission; requests wait while the pool is exhausted (no mid-flight
growth/preemption in v1 — simpler, and cannot deadlock; prefix-registry
pages held by nobody else are evicted on demand first).

The reference processes requests strictly one-question-at-a-time with
unbounded per-call HTTP concurrency (``src/main.rs:101,156,182``); this
is the TPU-native throughput-serving counterpart.
"""

from __future__ import annotations

import base64
import functools
import hashlib
import itertools
import logging
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass, field, replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from llm_consensus_tpu.backends import base as _backend_base
from llm_consensus_tpu.engine.engine import _next_bucket
from llm_consensus_tpu.engine.sampler import (
    SamplerConfig,
    sample_token_per_request,
    stop_scan_hit,
)
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from llm_consensus_tpu.utils.compile_cache import StoredJit
from llm_consensus_tpu.utils.stops import (
    VisibleIdFilter,
    derived_stop_screen,
    earliest_stop_cut,
    stop_tail_window,
)
from llm_consensus_tpu.models.configs import ModelConfig
from llm_consensus_tpu.models.paged_cache import (
    NULL_PAGE,
    DecodeGroupArrays,
    GroupTracker,
    PagedKVCache,
    PagePool,
    PrefixRegistry,
    StatePool,
    copy_page,
    install_page,
    install_pages,
    install_seq,
    release_seq,
)
from llm_consensus_tpu.engine import diffusion as _diffusion
from llm_consensus_tpu.engine.accept import verify_tokens
from llm_consensus_tpu.ops.pallas.quant_matmul import _MAX_M as _QMM_MAX_ROWS
from llm_consensus_tpu.serving import flight as _flight
from llm_consensus_tpu.serving.offload import HostPageStore
from llm_consensus_tpu.models.transformer import (
    decode_step_paged,
    denoise_step_paged,
    fused_step_paged,
    kv_plane_token_bytes,
    model_param_bytes,
    prefill_chunk_paged,
    program_hbm_cost,
    unembed_rows,
    verify_step_paged,
)
from llm_consensus_tpu.models.transformer import (
    ragged_mesh_shardable as _ragged_mesh_shardable,
)
from llm_consensus_tpu.ops.kernels import (
    on_tpu,
    resolve_kernels,
    single_device,
)
from llm_consensus_tpu.server.metrics import (
    ATTENTION_PAGES_READ as _M_ATTN_PAGES_READ,
    ATTENTION_TOKENS_READ as _M_ATTN_TOKENS_READ,
)
from llm_consensus_tpu.server.metrics import (
    MOE_ASSIGNMENTS as _M_MOE_ASSIGNMENTS,
)
from llm_consensus_tpu.server.metrics import (
    MOE_EXPERTS_TOUCHED as _M_MOE_EXPERTS,
)
from llm_consensus_tpu.server.metrics import (
    MOE_LAYER_PROGRAMS as _M_MOE_LAYER_PROGRAMS,
)
from llm_consensus_tpu.server.metrics import (
    PREFILL_STALL_SECONDS as _M_PREFILL_STALL,
)
from llm_consensus_tpu.server.metrics import (
    PREFIX_HITS as _M_PREFIX_HITS,
)
from llm_consensus_tpu.server.metrics import (
    PREFIX_LOOKUPS as _M_PREFIX_LOOKUPS,
)
from llm_consensus_tpu.server.metrics import (
    PREFIX_PAGES_COPIED as _M_PREFIX_COPIED,
)
from llm_consensus_tpu.server.metrics import (
    PREFIX_PAGES_SHARED as _M_PREFIX_SHARED,
)
from llm_consensus_tpu.server.metrics import (
    DECODE_GROUP_SIZE as _M_GROUP_SIZE,
)
from llm_consensus_tpu.server.metrics import (
    SHARED_KV_BYTES_SAVED as _M_KV_SAVED,
)
from llm_consensus_tpu.server.metrics import (
    KV_OFFLOAD_DEMOTED as _M_OFF_DEMOTED,
)
from llm_consensus_tpu.server.metrics import (
    KV_OFFLOAD_DROPPED as _M_OFF_DROPPED,
)
from llm_consensus_tpu.server.metrics import (
    KV_OFFLOAD_RESTORED as _M_OFF_RESTORED,
)
from llm_consensus_tpu.server.metrics import (
    KV_HOST_TIER_BYTES as _M_OFF_HOST_BYTES,
)
from llm_consensus_tpu.server.metrics import (
    KV_RESTORE_SECONDS as _M_RESTORE_SECONDS,
)
from llm_consensus_tpu.server.metrics import (
    DECODE_STEP_SECONDS as _M_STEP_SECONDS,
)
from llm_consensus_tpu.server.metrics import (
    SCHED_OVERHEAD_SECONDS as _M_SCHED_OVERHEAD,
)
from llm_consensus_tpu.server.metrics import (
    PIPELINE_FLUSHES as _M_PIPELINE_FLUSHES,
)
from llm_consensus_tpu.server.metrics import (
    DISPATCH_INFLIGHT as _M_DISPATCH_INFLIGHT,
)
from llm_consensus_tpu.server.metrics import (
    DEVICE_PROGRAMS as _M_DEVICE_PROGRAMS,
)
from llm_consensus_tpu.server.metrics import (
    BATCHER_PHASE_SECONDS as _M_PHASE_SECONDS,
)
from llm_consensus_tpu.server.metrics import (
    GENERATED_TOKENS as _M_GENERATED,
)
from llm_consensus_tpu.server.metrics import (
    PREFILL_TOKENS as _M_PREFILL_TOKENS,
)
from llm_consensus_tpu.server.metrics import (
    CHUNK_LANES as _M_CHUNK_LANES,
)
from llm_consensus_tpu.server.metrics import (
    DEVICE_MEMORY_BYTES as _M_DEVICE_MEMORY,
)
from llm_consensus_tpu.server.metrics import (
    REGISTRY as _M_REGISTRY,
)
from llm_consensus_tpu.server.metrics import (
    RAGGED_ROWS as _M_RAGGED_ROWS,
)
from llm_consensus_tpu.server.metrics import (
    DECODE_ROUNDS_PER_PROGRAM as _M_DECODE_ROUNDS,
)
from llm_consensus_tpu.server.metrics import (
    DEVICE_ROUNDS as _M_DEVICE_ROUNDS,
)
from llm_consensus_tpu.server.metrics import (
    SPEC_DRAFT_TOKENS as _M_SPEC_DRAFTED,
)
from llm_consensus_tpu.server.metrics import (
    SPEC_ACCEPTED_TOKENS as _M_SPEC_ACCEPTED,
)
from llm_consensus_tpu.server.metrics import (
    SPEC_ACCEPTANCE as _M_SPEC_ACCEPTANCE,
)
from llm_consensus_tpu.server.metrics import (
    SPEC_VERIFIED_TOKENS as _M_SPEC_VERIFIED,
)
from llm_consensus_tpu.server.metrics import (
    SPEC_XMODEL_ACCEPTED_TOKENS as _M_SPEC_XMODEL,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_ACTIVE as _M_ACTIVE,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_COMPLETED as _M_COMPLETED,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_OCCUPANCY as _M_OCCUPANCY,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_STEPS as _M_STEPS,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_SUBMITTED as _M_SUBMITTED,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_TOKENS as _M_TOKENS,
)
from llm_consensus_tpu.server.metrics import (
    SERVING_WAITING as _M_WAITING,
)
from llm_consensus_tpu.server.metrics import (
    TBT_SECONDS as _M_TBT,
)
from llm_consensus_tpu.server.metrics import (
    PROGRAM_MBU as _M_PROGRAM_MBU,
)
from llm_consensus_tpu.server.metrics import (
    MESH_SHARDS as _M_MESH_SHARDS,
)
from llm_consensus_tpu.server.metrics import (
    PREFIX_TOKENS_RECOMPUTED as _M_PREFIX_RECOMPUTED,
)
from llm_consensus_tpu.server.metrics import (
    SSM_TOKENS as _M_SSM_TOKENS,
)
from llm_consensus_tpu.server.metrics import (
    STATE_SLOTS as _M_STATE_SLOTS,
)
from llm_consensus_tpu.server.metrics import (
    STATE_SNAPSHOTS as _M_STATE_SNAPSHOTS,
)
from llm_consensus_tpu.server.metrics import (
    KV_PREFETCH as _M_PREFETCH,
)
from llm_consensus_tpu.server.metrics import (
    PIPELINE_DRAINS as _M_PIPELINE_DRAINS,
)
from llm_consensus_tpu.server.metrics import (
    SETUP_SECONDS as _M_SETUP_SECONDS,
)
from llm_consensus_tpu.server.metrics import (
    ATTENTION_PAIRS as _M_ATTN_PAIRS,
    DENOISE_STEPS as _M_DENOISE_STEPS,
    DIFFUSION_BLOCKS as _M_DIFF_BLOCKS,
    DIFFUSION_TOKENS as _M_DIFF_TOKENS,
)
from llm_consensus_tpu.utils import tracing as _tracing

log = logging.getLogger(__name__)

# Process-wide request-id stream: ids key the (process-global)
# RequestLog, so two batchers in one process must not collide.
_RID = itertools.count(1)

# Width of the per-row device stop screen (PR 12): a request's derived
# candidate-id set rides the multi-round program as one -1-padded
# [max_slots, _SCREEN_W] data row. STATIC — widening it per request
# would make screen size a compiled shape. Requests whose screen
# doesn't fit bound the window to 1 round instead (derived_stop_screen
# returns None past the cap).
_SCREEN_W = 8

# Bound on the per-batcher derived-screen memo (stop tuples are
# client-supplied; see _screen_cache).
_SCREEN_CACHE_MAX = 512

# The batcher thread's phases: the labels of
# gateway_batcher_phase_seconds_total, and "batcher.<phase>" on the
# profiler's host plane.
_PHASES = ("admit", "restore", "dispatch", "device_wait", "retire", "idle")

# What emptied the dispatch window before a program was enqueued to an
# idle device while rows were decoding: the labels of
# gateway_pipeline_drains_total. ``first_token`` — the fetch that ended
# a prompt left nothing in flight — reads 0 at ``pipeline_depth`` >= 2
# since PR 36, and is where a wait put back on that path would show.
_DRAINS = ("first_token", "standalone_chunk", "flush", "other")

# A row's entry in the patch of the device page tables (``_RowPatch``).
_ROW_KEEP, _ROW_INSTALL, _ROW_RELEASE = 0, 1, 2


def _step_program(name: str, fn):
    """``fn`` under the fixed function name ``name``: ``jax.jit`` names
    the XLA module after the function it is given (``jit_<name>``, and
    ``PjitFunction(<name>)`` on the profiler's host plane), and a bound
    method or a ``functools.partial`` would read ``jit__decode_sample``
    or ``jit__unknown``. The name is what a trace reduction finds a
    step program by, whatever its bucket: keep it when the body
    changes."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return program


def apply_rows(caches, ops, tables, lengths, states):
    """The row changes of one fetch, for the target pool and (where
    there is one) the draft's, as ONE device program: ``ops`` [pools,
    slots] says for each pool's row whether it keeps what it has, is
    installed (``tables`` [slots, pages_per_seq], ``lengths`` and
    ``states`` [slots]: :func:`install_seq` for that row) or released
    (:func:`release_seq`). Both run over every row and a select keeps
    each row's own. ``caches`` come without their pools
    (:func:`_rows_of`): a program that took them would have to be
    given them for good, and a reader on another thread (a test, an
    export) would find its pool deleted."""

    def patched(cache, op):
        rows = jnp.arange(cache.max_seqs)
        installed = install_seq(cache, rows, tables, lengths, states)
        released = release_seq(cache, rows)

        def pick(kept, inst, rel):
            o = op.reshape(op.shape + (1,) * (kept.ndim - 1))
            return jnp.where(
                o == _ROW_INSTALL, inst, jnp.where(o == _ROW_RELEASE, rel, kept)
            )

        state = cache.state
        if state is not None:
            state = replace(
                state,
                slot=pick(state.slot, installed.state.slot, released.state.slot),
            )
        return replace(
            cache,
            page_table=pick(
                cache.page_table, installed.page_table, released.page_table
            ),
            length=pick(cache.length, installed.length, released.length),
            state=state,
        )

    return tuple(patched(cache, ops[n]) for n, cache in enumerate(caches))


def _rows_of(cache: PagedKVCache) -> PagedKVCache:
    """``cache``'s per-row leaves alone — page tables, lengths, state
    slots — as a cache whose pools are absent (None is no leaf)."""
    state = cache.state
    if state is not None:
        state = replace(state, s=None, conv=None)
    return replace(cache, k=None, v=None, state=state)


def _with_rows(cache: PagedKVCache, rows: PagedKVCache) -> PagedKVCache:
    """``cache``'s pools under the per-row leaves of ``rows``."""
    state = cache.state
    if state is not None:
        state = replace(state, slot=rows.state.slot)
    return replace(
        cache, page_table=rows.page_table, length=rows.length, state=state
    )


class _RowPatch:
    """The changes one retire phase makes to the device's page-table
    rows, gathered on the host and applied by ONE :func:`apply_rows`
    program at its end. The later entry of a row wins: a row installed
    and released in one fetch (a first token that ends its request) is
    released. Fresh arrays a patch — the program reads them after the
    call returns (the snapshot rule of ``_dispatch.rows``)."""

    def __init__(self, pools: int, slots: int, pages_per_seq: int):
        self.ops = np.full((pools, slots), _ROW_KEEP, np.int32)
        self.tables = np.full((slots, pages_per_seq), NULL_PAGE, np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self.states = np.zeros((slots,), np.int32)


def _abstract(x):
    """Shape, dtype and (where the array was placed on purpose) sharding
    of ``x``, for compiling ahead; anything that is no array as it is."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None,
        weak_type=x.weak_type,
    )


def _fill_device_memory() -> None:
    """gateway_device_memory_bytes from the allocator's own numbers:
    the largest value over the local devices. A render hook of the
    metrics registry (installed by the first batcher, so the backend is
    up): it runs when ``/metrics`` is asked for, never in the loop."""
    largest: dict[str, int] = {}
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        for kind, key in (
            ("in_use", "bytes_in_use"),
            ("peak", "peak_bytes_in_use"),
            ("limit", "bytes_limit"),
        ):
            if key in stats:
                largest[kind] = max(largest.get(kind, 0), int(stats[key]))
    for kind, value in largest.items():
        _M_DEVICE_MEMORY.labels(kind=kind).set(value)

# Cap on prefix_probe's host-tier extension walk (PR 14): each probed
# page hashes a fresh chain-prefix tuple (O(chain) per lookup — the
# store key is the full flat chain), so an unbounded walk is quadratic
# in prompt length on the per-request routing hot path. Host tokens
# only break ties between replicas' registry matches, and the signal
# saturates after a few pages; past the cap the router still routes
# correctly, it just stops counting deeper host residency.
_PROBE_HOST_PAGES = 8


def _weights_fingerprint(params) -> tuple:
    """A cheap, deterministic identity for a parameter tree: leaf
    count plus a hash over the first 4 elements of EVERY leaf (one
    concatenated device fetch at construction — a single leaf would
    not do: norm scales initialize to ones and embeddings can tie
    across checkpoints, so the sample must span the tree). Two
    batchers loaded from the same checkpoint (or sharing one tree,
    shard_params included — resharding moves bytes, not values)
    fingerprint equal; different weights differ with overwhelming
    probability. The host-tier store scope includes this (PR 14): a
    KV page's bytes are a function of the weights that wrote it, so
    replicas serving different checkpoints of one config must never
    cross-restore through a shared store."""
    import hashlib

    import jax.numpy as _jnp

    leaves = jax.tree_util.tree_leaves(params)
    sample = np.asarray(
        _jnp.concatenate(
            [
                _jnp.ravel(leaf)[:4].astype(_jnp.float32)
                for leaf in leaves
            ]
        )
    ).tobytes()
    return (len(leaves), hashlib.sha1(sample).hexdigest())


@dataclass
class ContinuousConfig:
    max_slots: int = 8
    page_size: int = 64
    n_pages: int = 512  # pool size (excl. semantics: page 0 is reserved)
    pages_per_seq: int = 32  # table width = max seq len / page_size
    max_new_tokens: int = 256
    seq_buckets: tuple[int, ...] = (64, 128, 256, 512)
    sampler: SamplerConfig | None = None
    poll_interval_s: float = 0.001
    # Over-long prompts: left-truncate to the largest bucket (keeping the
    # question tail) with a warning, or reject when False.
    truncate_prompts: bool = True
    # Prefill-chunk width in tokens, >= 1. A prompt prefills in chunks
    # of min(prefill_chunk, its seq bucket), one chunk of a sequence a
    # loop iteration; the ready chunks of up to L sequences share that
    # iteration's program (L from the slots, this width and the int8
    # matmul kernel's row cap: ContinuousBatcher._lanes_for), riding the
    # decode dispatch when rows are decoding: a decoding row waits for
    # one program of up to L chunks (at most 256 rows), never for a
    # whole prompt. Chunked prefill is the only prefill path.
    prefill_chunk: int = 64
    # Map page-aligned shared prompt prefixes out of the PrefixRegistry
    # instead of re-prefilling them, and read a shared run once a group
    # through the grouped attention kernel where that kernel runs.
    # Stays an option: a deployment may refuse to share pages across
    # requests; False is also the ungrouped reference tests compare
    # against.
    share_prefix: bool = True
    # Byte budget of the host-RAM tier under the prefix registry. > 0:
    # registry eviction demotes ready prefix pages to host buffers and
    # admission restores them between decode steps instead of
    # re-prefilling. 0: eviction destroys. Requires share_prefix (a
    # restore re-registers its pages under the registry's readiness
    # gates).
    host_cache_bytes: int = 0
    # Decode programs in flight at once: program n+1 is enqueued before
    # program n's tokens are fetched, so stop scans, retirement,
    # admission and restores run while the device works. A finished row
    # overshoots by up to pipeline_depth * (tokens a program advances)
    # - 1 tokens, discarded on fetch and budgeted into its pages.
    # Restores and CoW boundary copies drain the pipeline first
    # (gateway_pipeline_flushes_total). Text is byte-identical at every
    # depth. Stays an option: an adaptive controller steers the live
    # depth inside [1, pipeline_depth], so depth 1 is a path that runs.
    pipeline_depth: int = 2
    # A ready prefill chunk rides the decode dispatch as one more row
    # of the ragged attention kernel (one device program an iteration)
    # instead of running as a program of its own between decode steps.
    # Read per loop iteration; text is identical either way. Stays
    # until the mesh parity grids that use False as their second axis
    # get another (ROADMAP D2).
    ragged_attention: bool = True
    # Draft tokens proposed per speculative round. With spec_k > 0 AND
    # a draft model (``ContinuousBatcher(draft=(cfg, params))``), a
    # round is one device program: spec_k + 1 greedy draft steps on the
    # draft's mirror of the page pool (one shared stream per
    # shared-prefix group), the target's verify rows over all drafts,
    # and the accept / rollback rule on device. Greedy text is
    # byte-identical to spec-off for any draft; sampled rows use the
    # exact residual rule (engine/accept.py). Sizes every admission's
    # page-overshoot budget, so it must not change on a live batcher.
    spec_k: int = 0
    # Whether a configured draft speculates, read per loop iteration: a
    # flip drains the dispatch pipeline and rows that decoded meanwhile
    # replay through the draft (``_spec_catch_up``). The adaptive
    # controller's spec gate takes the same path. No effect without
    # spec_k > 0 and a draft model.
    spec_decode: bool = True
    # Decode rounds per dispatched program — the one way to put several
    # decode steps in a program. R > 1 scans the masked decode body R
    # times with sampling, stop checks and emit counts on device: a row
    # that samples EOS, a screened stop candidate or its last budgeted
    # token FREEZES (K/V writes go to the NULL page, its PRNG stops
    # folding, its length stops advancing) while its neighbours go on,
    # and the host fetches once a window. Text is byte-identical to
    # R = 1: EOS and budgets are exact on device, stop strings freeze
    # through a derived byte screen and the host's check at the fetch
    # stays authoritative; a request whose stops admit no bounded
    # screen holds its window to one round. While speculation is
    # engaged the verify round is the multi-token step and R applies to
    # the plain windows. Sizes the page-overshoot budget like spec_k.
    decode_rounds: int = 1
    # The device's peak HBM bandwidth in GB/s (~819 for a v5e). > 0:
    # every fetched program sets gateway_program_mbu{kind} = modelled
    # HBM bytes (models.transformer.program_hbm_cost) / measured wall
    # time / peak. 0: no gauge; the modelled bytes and measured seconds
    # still accumulate per kind in stats() (mbu_* keys).
    hbm_gbps: float = 0.0
    # Slots of the recurrent-state pool, for a model with state-space
    # layers (a size, as n_pages is): one a live sequence, the rest
    # registry snapshots, slot 0 the empty state. 0: 4 a decode slot.
    # A model without such layers has no state pool.
    state_slots: int = 0

    def __post_init__(self):
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (got {self.prefill_chunk}): "
                "chunked prefill is the only prefill path"
            )


class BatcherFailed(_backend_base.BackendError):
    """What a request's future raises when the batcher's worker loop
    died under it. A ``BackendError``, so every backend seam over a
    batcher relays it to the gateway as a 502 carrying the message."""


@dataclass
class ServeResult:
    """What a :meth:`ContinuousBatcher.submit` future resolves to."""

    text: str
    num_tokens: int  # generated tokens incl. EOS
    # Per-request serving timeline (PR 10): the same summary dict the
    # RequestLog retains for /debug/requests — TTFT, inter-token-gap
    # percentiles, spec tokens accepted per round, restored-vs-prefilled
    # header pages. Rides the gateway response as "meta". Excluded from
    # equality: two identical generations NEVER share wall-clock stamps,
    # and result comparison means "same text/tokens" everywhere
    # (parity tests compare whole ServeResults).
    timing: dict | None = field(default=None, compare=False)
    # ``submit(logits=n)``: float32 [<= n, V], the logits of the first
    # generated positions exactly as the step programs handed them to
    # the sampler (row 0 from the prefill's unembed, the rest from the
    # decode or fused steps the row rode). None unless asked.
    logits: np.ndarray | None = field(default=None, compare=False)
    # A block-diffusion model's read-out: ``logits`` holds B rows a
    # denoising step, and this the block's input ids at each step.
    logit_ids: list | None = field(default=None, compare=False)


@dataclass
class _Request:
    prompt_ids: np.ndarray
    max_new_tokens: int
    temperature: float
    seed: int
    future: Future
    # Per-request sampler settings ride as decode-step DATA (arrays),
    # never as compiled constants — a request with new settings joining
    # the batch must not recompile the hot loop.
    top_k: int = 0
    top_p: float = 1.0
    # Stop sequences (engine contract): text trims at the earliest
    # occurrence; the host loop sees every sampled token, so multi-token
    # stops end decoding immediately (no overshoot to EOS/length).
    stop: tuple[str, ...] = ()
    # Tail-window width for the per-token stop check, precomputed once
    # at submit (stop strings are immutable for the request's life —
    # re-encoding them per sampled token would put tokenizer calls on
    # the thread pacing device steps).
    stop_window: int = 0
    # Device stop screen for multi-round decode (PR 12), derived once
    # at submit (memoized per stop tuple): () = no stops (never screen-
    # freezes), a tuple of <= _SCREEN_W candidate ids, or None = stops
    # with no bounded screen — this row bounds any multi-round window
    # it rides to 1 round (host-checked cadence).
    stop_screen: tuple[int, ...] | None = ()
    # Request-scoped trace captured from the submitter's context: the
    # worker thread attaches prefill-chunk/decode-step/restore spans to
    # it explicitly (contextvars do not cross the thread boundary).
    trace: object | None = None
    # Flight-recorder identity + timeline origin (PR 10): rid keys the
    # RequestLog summary; t_submit (perf_counter) anchors TTFT and the
    # request's Chrome-export track.
    rid: str = ""
    t_submit: float = 0.0
    # ``"logits": n`` read-out: the sampler's logits rows of the first
    # ``logits_n`` generated positions, fetched for this row alone.
    logits_n: int = 0
    logit_rows: list = field(default_factory=list)
    logit_ids: list = field(default_factory=list)


@dataclass
class _Slot:
    request: _Request
    pages: list[int]  # every table page this sequence holds one ref on
    generated: list[int]
    prompt_len: int
    # "prefill" until the last chunk lands (device table row stays NULL
    # and the decode loop ignores the row), then "decode".
    phase: str = "decode"
    # -- chunked-prefill state (phase == "prefill") --------------------
    table: np.ndarray | None = None  # host-side table (device sees NULL)
    next_pos: int = 0  # absolute position of the next chunk's first token
    chunk: int = 0  # this request's chunk width
    padded_ids: np.ndarray | None = None  # prompt ids padded to chunk grid
    s_bucket: int = 0  # prompt's seq bucket (program-family key)
    # Registry nodes whose page CONTENT this sequence reads (shared
    # prefix pages written by another in-flight prefill): chunks wait
    # until every dep is ready.
    deps: list = field(default_factory=list)
    # Nodes THIS sequence registered, with the prompt position whose
    # write completes them: [(node, end_pos)].
    reg_nodes: list = field(default_factory=list)
    # Tokens the TARGET committed through plain decode programs that
    # the draft mirror never saw (spec_decode flipped off mid-decode
    # with a draft configured). The next spec engagement replays them
    # through the draft before dispatching (:meth:`_spec_catch_up`) —
    # without the replay the draft would write this row's next K/V at
    # stale positions and its proposals would silently stop accepting.
    draft_lag: int = 0
    # -- per-request token timeline (PR 10) -----------------------------
    # First-token stamp (perf_counter; TTFT = t_first - t_submit), the
    # previous token-arrival stamp, and the observed inter-token gaps
    # (one per token past the first; tokens landing in the same program
    # fetch record 0 past the first — the bursty arrival a streaming
    # client sees). Retirement folds these into the RequestLog summary.
    t_first: float | None = None
    t_last_tok: float = 0.0
    gaps: list = field(default_factory=list)
    # Speculative per-request tallies: verify rounds this row rode and
    # draft tokens those rounds accepted for it.
    spec_rounds: int = 0
    spec_accepted_toks: int = 0
    # Header provenance: full prefix pages mapped from the registry at
    # admission vs restored from the host tier (each page is page_size
    # prompt tokens this request never re-prefilled).
    pages_shared_n: int = 0
    pages_restored_n: int = 0
    # -- recurrent state (a model with state-space layers) --------------
    # The sequence's own state slot, held from admission to retirement;
    # the slot its NEXT chunk starts from (0: an empty state; a registry
    # snapshot's for the first chunk after a shared prefix; its own from
    # then on); the snapshot's node while its content is still to be
    # written; the registry nodes of the prompt's full pages, by page;
    # and how many pages the registry matched (``pages_shared_n`` is
    # how many of them a snapshot made usable).
    state_slot: int = 0
    state_src: int = 0
    state_dep: object = None
    chain: list = field(default_factory=list)
    pages_matched_n: int = 0
    # -- block diffusion -------------------------------------------------
    # ``prompt_len`` is what chunked prefill covers, the prompt's last
    # whole block; ``tail`` is the rest of the prompt, which rides the
    # row's first block (empty once that block is dispatched). ``length``
    # mirrors the row's committed device length at its next dispatch;
    # ``planned`` counts the tokens its dispatched blocks give at most.
    tail: np.ndarray | None = None
    length: int = 0
    planned: int = 0


class _LaneArgs(NamedTuple):
    """What one chunk program is given for its L lanes
    (``ContinuousBatcher._lane_args``). All numpy, made for the one
    call and never written again: the programs take them as they are."""

    lanes: int  # the program's width: 1, or ``_lanes_for``
    ids: np.ndarray  # [L, C] chunk token ids
    tables: np.ndarray  # [L, P]; a dead lane's is all NULL pages
    starts: np.ndarray  # [L] chunk start positions
    lasts: np.ndarray  # [L] last prompt positions
    done: np.ndarray  # [L] bool: the lane ends its prompt here
    # (seeds, temperatures, top_k, top_p), [L] each: the first token's
    # draw for a lane that ends its prompt.
    sampler: tuple
    filters: bool  # a lane that ends needs the sampler's filters
    ext: list  # cost extents (end, width) of the live lanes
    state_kw: dict  # ``chunk_state`` [L, 4] for a recurrent model

    @property
    def program_args(self) -> tuple:
        """The lanes' arguments behind a step program's own."""
        return (
            self.ids, self.tables, self.starts, self.lasts, self.done,
            self.sampler,
        )


@dataclass
class _InflightChunk:
    """A prefill chunk riding an in-flight FUSED program (PR 8).

    The chunk's device work (K/V writes, ragged attention, the final
    chunk's first token sampled from its last position's logits) is
    already ordered on the stream; what waits for the fetch is the HOST
    bookkeeping — chunk accounting, the final chunk's activation and
    its row's entry in the patch. ``slot`` is the identity guard,
    exactly like ``_Inflight.rows``.
    """

    idx: int  # slot index
    slot: _Slot
    done: bool  # this program wrote the chunk covering the prompt end
    lane: int  # its lane: the row of the program's ``chunk_first``
    # device [V] last-real-position logits (done only), fetched for a
    # ``"logits": n`` request alone
    logits: object
    pos: int  # chunk start position (trace span meta)
    width: int  # chunk width


@dataclass
class _Inflight:
    """One dispatched, not-yet-fetched decode program (PR 6).

    ``rows`` snapshots the (slot index, slot object) pairs that were
    decoding at dispatch time: the fetch credits tokens ONLY to rows
    whose slot object is still in place, so a slot retired — or retired
    and re-admitted to a new request — while this program was in flight
    never receives a stale program's output.
    """

    tokens: object  # device [slots, k] sampled tokens (the fetch target)
    next_input: object  # device [slots] final token (next dispatch's input)
    t0: float  # host dispatch stamp (perf_counter)
    k: int  # decode steps folded into this program
    rows: list  # [(slot_idx, _Slot)] decoding at dispatch
    # Fused prefill chunks (PR 8), one a live lane (PR 31), and the
    # program's device [L] first tokens of the lanes that ended their
    # prompts: fetched with ``tokens`` when one did.
    chunks: list = field(default_factory=list)
    chunk_first: object = None
    # -- speculative round (PR 9) --------------------------------------
    # ``tokens`` is then the [slots, spec_k + 1] emit buffer; only
    # ``emit_cnt`` leading tokens per row are real. ``counts_out`` is
    # the device-resident post-round PRNG index row the NEXT spec
    # dispatch consumes (counts become data-dependent under
    # accept/rollback, so the host mirror syncs at fetch, not at
    # dispatch).
    spec: bool = False
    spec_k: int = 0
    emit_cnt: object = None  # device [slots] emitted-token counts
    counts_out: object = None  # device [slots] post-round PRNG counts
    # -- multi-round decode (PR 12) --------------------------------------
    # > 0: this program ran through the multi-round machinery (that
    # many masked decode rounds — possibly 1 when a stop-bound
    # collapsed the window); its per-row yield is data-dependent like
    # a spec round's (``emit_cnt`` leading tokens real, ``counts_out``
    # device-resident, host count/draft-lag mirrors sync at fetch).
    # 0: a one-step program whose host mirrors advanced at dispatch.
    rounds: int = 0
    # Whether this window's length was the rounds controller's CHOICE
    # (PR 15) rather than forced by a near-stop cap or an
    # unscreenable-stop collapse — only chosen windows feed the
    # per-arm measured-rate EWMAs (a forced tail window would
    # attribute its frozen rows' starvation to an arm that never
    # chose it).
    rounds_clean: bool = False
    # -- flight recorder + roofline attribution (PR 10) -----------------
    # The "program" flight event recorded at dispatch: the fetch fills
    # its (t0, dur) window in place once the true device window is
    # known. ``cost`` is the static HBM/FLOPs model for this program
    # (program_hbm_cost output), accumulated per kind at fetch time
    # against the measured duration.
    flight: object = None
    cost: dict | None = None
    # What the program returned beside its tokens (``_step_aux``): the
    # last step's logits [slots, V], left on the device unless a row
    # asked for them, and a dropless-MoE model's routing counts, which
    # the fetch brings home with the tokens.
    aux: tuple | None = None
    # A block-diffusion program (``_denoise_sample``): ``tokens`` are
    # each row's committed block, generated positions first, of which
    # ``emit_cnt`` belong to the request; ``aux`` is (logits [T, slots,
    # B, V] and block ids [T, slots, B] of its T denoising steps,
    # *routing counts).
    denoise: bool = False


class ContinuousBatcher:
    """Token-level continuous batching over one model's weights."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        tokenizer: Tokenizer | None = None,
        config: ContinuousConfig | None = None,
        mesh=None,
        draft: tuple[ModelConfig, dict] | None = None,
        draft_map=None,
        host_store: HostPageStore | None = None,
        host_store_scope: tuple | None = None,
        controller=None,
    ):
        # Kernel choice is observed here, once (ops.kernels): a TPU
        # compiles the Pallas path — under shard_map on a mesh — and
        # anything else runs the jnp references.
        cfg = resolve_kernels(cfg, mesh, shard_mapped=True)
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer or ByteTokenizer()
        self.config = config or ContinuousConfig()
        c = self.config
        # Roofline-adaptive runtime control (PR 15,
        # serving/control.py): an AdaptiveController closing the PR-10
        # cost model into a feedback loop — effective spec_k per
        # dispatch from measured per-group acceptance, adaptive-R
        # window caps, chunk/depth steering from un-overlapped
        # overhead and modeled MBU, restore pacing for the fleet's
        # preempt hook. None (default) = every knob stays its static
        # config value. Bound below once the modeled terms exist.
        self.controller = controller
        # Speculative draft model (PR 9): the draft decodes against its
        # OWN pool mirroring the target's page geometry — same page
        # ids, same host-side tables/allocator, so prefix sharing, CoW
        # copies, and host-tier restores cover both pools with one set
        # of bookkeeping. Draft prefill rides every prompt whenever the
        # draft exists, so flipping ``spec_decode`` mid-serve never
        # leaves a prompt without draft context.
        self._draft_cfg: ModelConfig | None = None
        self._draft_params: dict | None = None
        self.draft_cache = None
        # Cross-model vocab remap (PR 18, serving/vocab_align.py):
        # ``draft_map`` carries the exact-match d2t/t2d tables when the
        # draft speaks a DIFFERENT tokenizer. All carried token state —
        # committed streams, spec_fill, the verify drafts — stays in
        # TARGET vocab; t2d applies only at the draft model's input
        # boundary (its decode scan and prefill mirrors), d2t only at
        # its argmax output. An identity map (or None with equal
        # vocabs) keeps the PR-9 single-tokenizer fast path: no gather
        # in any trace.
        self._vocab_map = draft_map
        self._t2d = None
        self._d2t = None
        if draft is not None:
            dcfg, dparams = draft
            if c.spec_k <= 0:
                raise ValueError(
                    "a draft model needs spec_k > 0 (spec_k sizes the "
                    "page-overshoot budget and the verify program)"
                )
            if draft_map is not None and not draft_map.identity:
                if len(draft_map.d2t) != dcfg.vocab_size or len(
                    draft_map.t2d
                ) != cfg.vocab_size:
                    raise ValueError(
                        f"draft_map shape mismatch: d2t[{len(draft_map.d2t)}]"
                        f" vs draft vocab {dcfg.vocab_size}, t2d"
                        f"[{len(draft_map.t2d)}] vs target vocab "
                        f"{cfg.vocab_size}"
                    )
                # Tiny int32 tables captured as jit constants — one
                # device copy, every spec/prefill trace closes over it.
                self._t2d = jnp.asarray(draft_map.t2d, jnp.int32)
                self._d2t = jnp.asarray(draft_map.d2t, jnp.int32)
            elif dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} — cross-model speculation needs a "
                    "vocab alignment map (serving.vocab_align."
                    "align_vocabs) or one shared tokenizer"
                )
            self._draft_cfg = resolve_kernels(dcfg, mesh, shard_mapped=True)
            self._draft_params = dparams
        # ``mesh``: run the serving hot loop sharded — slots (the decode
        # batch axis) and the page pool's page axis over ``data``, kv
        # heads over ``model``, params via ``shard_params`` (tp over
        # ``model``, replicated over ``data``). Slot->page affinity
        # below keeps each slot's pages on its own data shard so page
        # reads/writes stay shard-local on real hardware.
        self.mesh = mesh
        self._dp = 1
        self._mp = 1
        self._row_sharding = None
        # Positions a decode row advances by: 1, or a block-diffusion
        # model's block (its rows are blocks, denoised in place).
        self._block = cfg.attn_block if cfg.is_diffusion else 1
        self._refuse_unsupported(cfg, self._draft_cfg, mesh, host_store)
        # What this batcher's programs read besides their arguments: the
        # store of executables keys every one of them on it.
        self._key = self._program_key()
        # The ragged attention kernel runs compiled (or interpreted) iff
        # the config asks for it and, on a mesh, the mesh can shard it.
        attn_kernel = bool(cfg.use_pallas) and (
            mesh is None
            or _ragged_mesh_shardable(cfg, mesh, c.max_slots, c.n_pages)
        )
        if mesh is not None:
            from llm_consensus_tpu.parallel.partitioning import shard_params

            dp = int(mesh.shape.get("data", 1))
            if c.max_slots % dp or c.n_pages % dp:
                raise ValueError(
                    f"max_slots ({c.max_slots}) and n_pages ({c.n_pages}) "
                    f"must be multiples of the mesh data axis ({dp})"
                )
            self._dp = dp
            self._mp = int(mesh.shape.get("model", 1))
            self.params = shard_params(self.params, mesh)
            if self._draft_params is not None:
                # The draft shards exactly like the target (PR 13): tp
                # over ``model``, replicated over ``data`` — the spec
                # program's draft scan and verify rows run on the same
                # mesh as the plain decode step.
                self._draft_params = shard_params(self._draft_params, mesh)
            self._row_sharding = self._named(("data",))
            if cfg.use_pallas and not attn_kernel:
                # Every serving feature still ENGAGES — this is purely
                # the kernel-vs-reference choice inside the one
                # attention seam (models.transformer._attn_paged).
                log.warning(
                    "Pallas ragged kernel cannot shard over this mesh "
                    "(n_kv_heads=%d %% model=%d, or slots/pages %% "
                    "data=%d, indivisible): paged attention runs the "
                    "XLA reference under GSPMD instead — outputs "
                    "identical, kernel bandwidth shaping lost",
                    cfg.n_kv_heads,
                    self._mp,
                    self._dp,
                )
        _M_MESH_SHARDS.labels(axis="data").set(self._dp)
        _M_MESH_SHARDS.labels(axis="model").set(self._mp)
        # Which attention path the paged programs trace — logged once
        # and exported by heartbeat() (the gateway's /readyz) next to
        # the device, so "the kernel engaged" is read, never assumed.
        if not attn_kernel:
            self.kernels = "reference"
        else:
            self.kernels = "pallas" if on_tpu() else "pallas-interpret"
            if not single_device(mesh):
                self.kernels += "/shard_map"
        self._state_slots = c.state_slots or 4 * c.max_slots + 1
        self.cache = self._create_pool(cfg)
        # The device as JAX reports it to this process, and which of
        # its devices hold this batcher's pool.
        devices = sorted(self.cache.k.devices(), key=lambda d: d.id)
        self.device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "pool_on": [d.id for d in devices],
        }
        log.info(
            "continuous batcher: %s, pool on device(s) %s of %d x %s (%s), "
            "attention kernels: %s",
            cfg.name,
            self.device["pool_on"],
            self.device["count"],
            self.device["kind"],
            self.device["platform"],
            self.kernels,
        )
        if self._draft_cfg is not None:
            # The draft pool: same n_pages/page_size/table geometry as
            # the target's, its own [L_d, n, page, Hkv_d, D_d] planes.
            # page_table/length are maintained in LOCKSTEP with the
            # target cache at every install/release/assign site, so one
            # host allocator serves both pools. On a mesh it takes the
            # same placement as the target's (pages over ``data``,
            # heads over ``model`` where they divide).
            self.draft_cache = self._create_pool(self._draft_cfg)
        # Host-side refcounted page allocator; page 0 is the NULL page.
        # On a mesh, one pool (and one prefix registry) per data shard:
        # slot s (slots shard in contiguous blocks) draws only from its
        # own shard's page range, so a sequence's table always points at
        # shard-local pages — and prefix sharing only ever maps pages
        # within one shard.
        pages_per_shard = c.n_pages // self._dp
        self._shard_of_slot = [
            s * self._dp // c.max_slots for s in range(c.max_slots)
        ]
        self._pools = [
            PagePool(
                p
                for p in range(j * pages_per_shard, (j + 1) * pages_per_shard)
                if p != NULL_PAGE
            )
            for j in range(self._dp)
        ]
        # Recurrent state (PR 32): the second kind of state in the one
        # cache manager. One StatePool (a recurrent model serves on one
        # device); the registry's nodes hold snapshots out of it.
        self._states = (
            StatePool(self._state_slots) if cfg.is_recurrent else None
        )
        self._registries = [
            PrefixRegistry(pool, c.page_size, states=self._states)
            for pool in self._pools
        ]
        self._state_events = dict.fromkeys(
            ("saved", "restored", "missed", "evicted"), 0
        )
        self._prefix_recomputed = 0
        self._miss_depths: list[int] = []  # _remember_miss_depth
        self._ssm_tokens = dict.fromkeys(
            ("fused", "decode", "prefill", "spec"), 0
        )
        # Host-RAM offload tier (PR 4; mesh-native since PR 13).
        # Engages only with prefix sharing (restores re-register
        # under the registry's readiness gates). On a mesh
        # the demote ``device_get`` assembles the page's sharded plane
        # slices into one host buffer and the restore ``install_page``
        # scatters it back through the pool's NamedSharding — the
        # round trip is bit-identical either way (tested); per-shard
        # streaming of the slices is a chip-transport optimization the
        # correctness contract doesn't depend on.
        self._offload: HostPageStore | None = None
        # Store-key scope (PR 14): with a FLEET-SHARED store, every key
        # must carry the identity of the function that wrote the page —
        # config dims, page size, pool dtype, the weights fingerprint,
        # and the draft's equivalents (draft planes travel in the same
        # entries) — so heterogeneous replicas can never cross-restore.
        # A private (per-batcher) store pays the same prefix for free.
        self._store_scope: tuple = ()
        # Chain-scope doc for /debug/chains (PR 18): which model's
        # weights wrote the chains this batcher counts. Lazy — the
        # weights fingerprint walks every param leaf, a cost the first
        # debug probe pays once, not construction.
        self._probe_scope: dict | None = None
        if c.host_cache_bytes > 0 and c.share_prefix:
            self._offload = (
                host_store
                if host_store is not None
                else HostPageStore(c.host_cache_bytes)
            )
            if host_store_scope is not None:
                # A sibling replica already computed the scope over the
                # SAME cfg/params/store (ReplicaSet passes replica 0's
                # down) — the weights fingerprint walks every param
                # leaf, and K identical walks at fleet construction
                # would be pure redundant startup latency.
                self._store_scope = host_store_scope
            elif host_store is None:
                # PRIVATE store: nobody else can ever write or read
                # it, so keys only need internal consistency — the
                # empty scope keeps the pre-fleet behavior without
                # paying the per-leaf fingerprint walk at every
                # single-batcher `serve --host-cache-mb` start.
                self._store_scope = ()
            else:
                scope = (
                    cfg.name,
                    cfg.n_layers,
                    cfg.n_kv_heads,
                    cfg.head_dim,
                    c.page_size,
                    str(self.cache.k.dtype),
                    _weights_fingerprint(self.params),
                )
                if self._draft_cfg is not None:
                    scope += (
                        self._draft_cfg.name,
                        self._draft_cfg.n_layers,
                        self._draft_cfg.n_kv_heads,
                        self._draft_cfg.head_dim,
                        _weights_fingerprint(self._draft_params),
                    )
                    if self._vocab_map is not None:
                        # The draft planes a restore installs were
                        # written through THIS remap; a different map
                        # means different draft inputs for the same
                        # target chain.
                        scope += self._vocab_map.scope_key()
                self._store_scope = scope
            for reg in self._registries:
                reg.on_evict = self._demote_nodes
        elif host_store is not None:
            raise ValueError(
                "a shared host_store needs the offload tier engaged: "
                "host_cache_bytes > 0 and share_prefix"
            )
        # Fleet hooks (PR 14): router-requested preemption (demote
        # reclaimable registry chains to the host tier NOW, freeing
        # pool pages for the overload storm instead of shedding 429s)
        # and chain exports (spill a resident chain's ready pages to
        # the shared store WITHOUT evicting, so another replica can
        # restore it — the rebalance transport). Both are REQUESTS
        # enqueued from router/gateway threads and executed by the
        # worker loop: the demote path's device_get must never race
        # the worker's dispatch-time buffer donation.
        self._preempt_req = 0
        self._preempted_pages = 0
        # Fleet-steered group-formation cap (PR 19): the fleet
        # controller resizes GroupTracker.max_groups from fleet-level
        # sharing pressure. The tracker is worker-owned state, so the
        # resize is an enqueued REQUEST applied at the top of the
        # worker loop, exactly like preempts. None = no change pending.
        self._group_cap_req: int | None = None
        # Export queue entries are mutable [ids, done, stream_until,
        # spilled_pages]: a STREAMED export (PR 17) re-arms itself
        # after each spill until the chain's usable pages are all out
        # or the deadline passes, so transport overlaps the prefill
        # still computing the later pages.
        self._exports: deque = deque()
        self._exported_pages = 0
        # Route-driven restore prefetch (PR 17): a bounded host-side
        # cache of chain pages pulled from the (remote) store AHEAD of
        # admission, filled by a side thread so the store round trip
        # never rides the worker loop or the admission lock. Admission
        # consumes it in front of the store probe, shrinking a restore
        # flush to a local install. Lock order: self._lock before
        # _prefetch_lock, everywhere.
        self._prefetched: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._prefetch_lock = threading.Lock()
        self._prefetch_q: deque = deque()
        self._prefetch_have = threading.Event()
        self._prefetch_thread: threading.Thread | None = None
        # Entries, not bytes: a chain is at most pages_per_seq pages,
        # so this holds a few routed-but-not-yet-admitted chains.
        self._prefetch_cap = max(16, 4 * c.pages_per_seq)
        self._prefetch_fetched = 0
        self._prefetch_hits = 0
        self._prefetch_expired = 0
        # Pending page restores: (registry node, host planes). Filled at
        # admission, drained one page per loop iteration between decode
        # steps (the same bounded-stall discipline as prefill chunks);
        # the node's readiness gate holds dependent prefills until the
        # install lands.
        self._restores: deque = deque()
        self._offload_restored = 0
        # Group-aware decode attention: derive per-step groups from
        # shared prefix page runs. The ragged kernel handles groups,
        # sliding windows, and mixed rows in one program, and since
        # PR 13 meshes too (shard_map with groups riding their
        # members' data shard), so the only engage conditions are the
        # kernel and prefix sharing (README Serving engage matrix).
        # Grouping is per data shard
        # by construction: pages share only within one shard's
        # registry, so a group's members always land on one shard. On
        # a mesh the KERNEL must actually be shardable: the XLA
        # reference fallback ignores groups, so building them would
        # only accrue shared-KV "savings" that never happen (and pay
        # the per-iteration tracker work) — telemetry must not claim
        # reads the program still performs.
        self._group_decode = c.share_prefix and attn_kernel
        self._groups = GroupTracker(c.max_slots, c.page_size)
        # KV bytes one token costs per read across all layers (k + v,
        # pool dtype) — the unit of gateway_shared_kv_bytes_saved_total
        # AND the cost model's KV term (one formula, transformer.py).
        self._kv_token_bytes = kv_plane_token_bytes(cfg, self.cache.k.dtype)
        self._kv_bytes_saved = 0
        # Roofline attribution (PR 10): the static per-program cost
        # model's weight term is the parameter tree as it actually sits
        # in HBM (post-shard on a mesh — leaf sizes are global either
        # way), measured once; per-kind accumulators mirror the
        # gateway_program_mbu gauge into stats().
        self._weight_bytes, self._weight_params = model_param_bytes(
            self.params
        )
        self._draft_weight_bytes = self._draft_weight_params = 0
        self._draft_kv_token_bytes = 0
        if self._draft_cfg is not None:
            self._draft_weight_bytes, self._draft_weight_params = (
                model_param_bytes(self._draft_params)
            )
            self._draft_kv_token_bytes = kv_plane_token_bytes(
                self._draft_cfg, self.draft_cache.k.dtype
            )
        if self.controller is not None:
            # Static modeled terms the controller's roofline clauses
            # read: the weight tree as it sits in HBM, the KV
            # byte-per-token unit (cost-dict KV splits), the
            # configured peak, and the host tier's budget (restore-
            # pacing debt cap).
            self.controller.bind(
                hbm_gbps=c.hbm_gbps,
                weight_bytes=self._weight_bytes,
                kv_token_bytes=self._kv_token_bytes,
                host_budget_bytes=(
                    c.host_cache_bytes if self._offload is not None else 0
                ),
            )
        self._mbu = {
            kind: {
                "hbm_bytes": 0,
                "flops": 0,
                "kv_read_tokens": 0,
                "kv_write_tokens": 0,
                "attn_pages_read": 0,
                "seconds": 0.0,
                "programs": 0,
            }
            for kind in ("fused", "decode", "prefill", "spec", "denoise")
        }
        # Per-request token timeline (PR 10): stats() mirrors of the
        # gateway_ttft-equivalent (submit -> first token, batcher side)
        # and gateway_tbt_seconds observations — one site, two surfaces.
        self._ttft_sum = 0.0
        self._ttft_count = 0
        self._tbt_sum = 0.0
        self._tbt_count = 0
        # Flight-recorder change detectors: the last spec engage state
        # (flip events record transitions, not steady state) and each
        # row's last draft-stream donor (stream events record donor
        # changes/divergences, not every round's plan).
        self._spec_flip_prev: bool | None = None
        self._stream_src_prev: dict[int, int] = {}
        self._slots: list[_Slot | None] = [None] * c.max_slots
        self._waiting: deque[_Request] = deque()
        self._last_tokens = np.zeros((c.max_slots,), np.int32)
        # Pipelined decode dispatch (PR 6): programs dispatched but not
        # yet fetched (oldest first; bounded by pipeline_depth), and the
        # rows whose next input token must come from the HOST mirror
        # instead of the previous program's device output (rows
        # (re)activated since the last dispatch — their first token was
        # sampled from prefill logits, not decoded in flight).
        self._inflight: deque[_Inflight] = deque()
        self._tok_dirty = np.zeros((c.max_slots,), bool)
        self._pipeline_flushes = 0
        # What last emptied the window (a label of ``_DRAINS``; None:
        # nothing has since the last dispatch), and the drains counted
        # by it: the observations behind gateway_pipeline_drains_total.
        self._drained_by: str | None = None
        self._pipeline_drains = dict.fromkeys(_DRAINS, 0)
        # Fused scheduler step (PR 8): device programs by kind plus the
        # ragged-row occupancy — the same observations behind
        # gateway_device_programs_total / gateway_ragged_rows_per_program
        # — and the count of loop iterations that ran any program (the
        # denominator of "device programs per scheduler iteration").
        self._programs = {
            "fused": 0, "decode": 0, "prefill": 0, "spec": 0, "draft": 0,
            "denoise": 0,
        }
        # Block diffusion: row-forwards, tokens committed to requests,
        # blocks committed (the gateway_denoise_steps / diffusion_*
        # families' mirrors), and attention pairs by kind.
        self._diffusion = dict.fromkeys(("steps", "tokens", "blocks"), 0)
        self._attn_pairs: dict[str, int] = {}
        self._ragged_rows_sum = 0
        self._ragged_rows_count = 0
        self._work_iterations = 0
        # Chunk programs by (kind, lanes filled): the observations
        # behind gateway_chunk_lanes_total (PR 31).
        self._chunk_lanes_n: dict[tuple[str, int], int] = {}
        # Multi-round decode (PR 12): total decode rounds dispatched
        # and the per-program round-count observations — the same
        # numbers behind gateway_device_rounds_total /
        # gateway_decode_rounds_per_program (lockstep tested).
        self._device_rounds = 0
        self._decode_rounds_sum = 0
        self._decode_rounds_count = 0
        # perf_counter stamp of the previous fetch's completion: deeper
        # than depth 1 a program starts on device when its predecessor
        # finishes, not at its own dispatch — the step histogram uses
        # max(dispatch, previous fetch) as the start approximation.
        self._last_fetch_end: float | None = None
        # CoW boundary copy staged by _admit_chunked under the lock,
        # dispatched by _admit's post-lock epilogue (the copy wants a
        # pipeline flush first, and the flush's fetch bookkeeping takes
        # the same lock).
        self._pending_copy: tuple[int, int] | None = None
        # Per-slot PRNG state: requests own their stream (seed, token
        # index), so sampling is reproducible regardless of batch-mates.
        self._seeds = np.zeros((c.max_slots,), np.int32)
        self._counts = np.zeros((c.max_slots,), np.int32)
        # Per-slot sampler settings (data, not compiled constants).
        dflt = c.sampler or SamplerConfig()
        self._topks = np.full((c.max_slots,), dflt.top_k, np.int32)
        self._topps = np.full((c.max_slots,), dflt.top_p, np.float32)
        self._completed = 0
        self._generated_tokens = 0
        self._decode_steps = 0
        self._prefill_chunks = 0
        # Span-derived step telemetry (PR 5): the SAME observations feed
        # the Prometheus histograms and these accumulators, so stats()
        # and /metrics cannot drift. _last_step_end is the perf_counter
        # stamp of the previous decode step's host fetch; None = the
        # loop idled since (idle waits are not scheduling overhead).
        self._decode_step_sum = 0.0
        self._decode_step_count = 0
        self._sched_overhead_sum = 0.0
        self._sched_overhead_count = 0
        self._last_step_end: float | None = None
        # Loop phases (PR 26; worker thread only): ``self._phase(name)``
        # opens one, and the loop flushes their seconds into
        # gateway_batcher_phase_seconds_total once an iteration.
        self._phases = _tracing.PhaseClock(
            "batcher.", _M_PHASE_SECONDS, _PHASES
        )
        self._phase = self._phases.phase
        # Liveness heartbeat: stamped at the top of every host-loop
        # iteration (the idle loop ticks at >= 10 Hz), and after each
        # decode step. The gateway's readiness probe compares the tick
        # age against its stall threshold.
        self._hb_tick = time.monotonic()
        self._hb_step: float | None = None
        # "Type: message" of the exception that ended the worker loop.
        self._failed: str | None = None
        self._vis_filter = VisibleIdFilter(
            self.tokenizer, skip_ids=(self.tokenizer.eos_id,)
        )
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._work = threading.Event()
        # params ride as a jit argument (not a closure constant) so the
        # weights aren't baked into the executable.
        self._jit_decode = StoredJit(
            _step_program("decode_step", self._decode_sample),
            key=self._key,
            donate_argnums=(1,),
            static_argnums=(8,),
        )
        # Multi-round decode program (PR 12): rounds is static (the
        # scan length; two cached traces per variant — R, and the
        # stop-bound 1), filters_active as in _jit_decode.
        self._jit_rounds = StoredJit(
            _step_program("rounds_step", self._rounds_sample),
            key=self._key,
            donate_argnums=(2,),
            static_argnums=(0, 9),
        )
        # Derived stop screens memoized per stop tuple: the derivation
        # scans the vocabulary once, and submit() runs on caller
        # threads that must not repay it per request. BOUNDED
        # (evict-oldest past _SCREEN_CACHE_MAX) like every other
        # long-lived store here — stop tuples are client-supplied, so
        # an unbounded memo is a slow leak under per-request-unique
        # stops; a cycling adversary re-pays only the capped
        # (max_vocab_scan decodes) derivation on its own thread.
        self._screen_cache: dict[tuple, tuple[int, ...] | None] = {}
        # The chunk programs, keyed by what changes their HLO
        # (:meth:`_chunk_key`): (chunk width, lanes, MoE pin).
        self._jit_chunk = {}  # key -> compiled chunk prefill
        self._jit_fused = {}  # key -> compiled fused step
        self._jit_denoise = {}  # (chunk, lanes) -> block-diffusion step
        # Keys whose programs were built ahead, and the shapes of a
        # plain dispatch's arguments to build them from
        # (:meth:`_build_fused_ahead`).
        self._fused_ahead: set[tuple] = set()
        self._plain_shapes: tuple | None = None
        self._jit_copy_page = StoredJit(copy_page, donate_argnums=(0,))
        self._jit_install_page = StoredJit(install_page, donate_argnums=(0,))
        # Batched restore install (PR 17): one scatter per restore
        # BATCH — one executable per batch size actually seen
        # (1 and the controller's restore_batch, in practice).
        self._jit_install_pages = StoredJit(
            install_pages, donate_argnums=(0,)
        )
        # The row patch of a fetch (PR 36): page tables, lengths and
        # state slots in and out, what the host gathered as numpy.
        self._jit_apply_rows = StoredJit(
            _step_program("apply_rows", apply_rows)
        )
        self._row_patch: _RowPatch | None = None
        # Speculative state (PR 9). _spec_cfg pins the MoE dispatch of
        # the k+1-token verify rows to the plain decode step's choice,
        # exactly as engine/speculative.py pins its verify chunk.
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_xmodel_accepted = 0
        self._spec_shared_rows = 0
        self._spec_acc_sum = 0.0
        self._spec_acc_count = 0
        self._spec_verified_last = 0
        if self._draft_cfg is not None:
            self._spec_cfg = cfg.moe_pin_for(
                c.max_slots, c.max_slots * (c.spec_k + 1)
            )
            self._jit_spec = StoredJit(
                _step_program("verify_step", self._spec_sample),
                key=self._key,
                static_argnums=(0, 11, 12),
                donate_argnums=(3, 4),
            )
            self._jit_chunk_d = {}  # (chunk, s_bucket) -> draft chunk
            # Draft-pool copy/install ride _jit_copy_page /
            # _jit_install_page: one executable per input shape, so the
            # draft planes just add a second one.
        # Round-robin pointer over prefilling slots (fairness when
        # several prompts fill concurrently).
        self._prefill_rr = 0
        _M_REGISTRY.add_render_hook(_fill_device_memory)
        self._thread = threading.Thread(
            target=self._run, name="continuous-batcher", daemon=True
        )
        self._thread.start()

    def _program_key(self) -> tuple:
        """What the step programs read besides their arguments, as text:
        the model (kernel choice resolved), the batcher's configuration,
        the draft, the mesh and its devices, the tokenizer's EOS id and
        the cross-model remap tables (trace constants)."""
        mesh = self.mesh
        tables = [
            None if t is None
            else hashlib.sha256(np.asarray(t).tobytes()).hexdigest()
            for t in (self._t2d, self._d2t)
        ]
        return (
            repr(self.cfg), repr(self.config), repr(self._draft_cfg),
            None if mesh is None else (repr(mesh), mesh.device_ids.tolist()),
            self.tokenizer.eos_id, tables,
        )

    def _named(self, spec) -> "object":
        """NamedSharding over this batcher's mesh for an axis tuple."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self.mesh, P(*spec))

    def _refuse_unsupported(self, cfg, draft_cfg, mesh, host_store) -> None:
        """A latent-attention (MLA) model, and a model with recurrent
        (state-space) layers, serve through the chunked, fused and
        grouped programs of one device. What has no latent path or
        moves pages only (a state is no page) refuses here, by name,
        rather than fall back or serve wrong state."""
        models = [m for m in (cfg, draft_cfg) if m is not None]
        recurrent = any(m.is_recurrent for m in models)
        c = self.config
        if any(m.is_diffusion for m in models):
            self._refuse_for_diffusion(cfg, draft_cfg, mesh, host_store)
        if not (recurrent or any(m.is_mla for m in models)):
            return
        why = None
        if draft_cfg is not None:
            why = "a draft model (the draft/verify lane" + (
                " rewinds positions, and a recurrent state cannot be "
                "rolled back)" if recurrent else ")"
            )
        elif not single_device(mesh):
            why = (
                "a mesh (the latent pool and the state pool have no "
                "partitioning yet)"
            )
        elif c.host_cache_bytes > 0 or host_store is not None:
            why = "the host tier / a remote page store" + (
                " / handoff export (they move pages only, and a page "
                "without its state cannot be continued from)"
                if recurrent else ""
            )
        elif recurrent and c.decode_rounds > 1:
            why = (
                "decode_rounds > 1 (jit_rounds_step freezes and rewinds "
                "rows inside a window; a state has no rollback)"
            )
        elif recurrent and any(
            self._chunk_width(b) % c.page_size and b > c.page_size
            for b in c.seq_buckets
        ):
            why = (
                f"prefill_chunk {c.prefill_chunk} / seq_buckets "
                f"{c.seq_buckets} whose chunks are no multiple of the page "
                f"size {c.page_size} (a state snapshot is saved where a "
                "chunk ends on a page boundary)"
            )
        if why:
            kind = (
                "models with recurrent (state-space) layers" if recurrent
                else "latent-attention (MLA) models"
            )
            raise ValueError(f"{cfg.name}: {kind} do not serve with {why} yet")

    def _refuse_for_diffusion(self, cfg, draft_cfg, mesh, host_store) -> None:
        """A block-diffusion model serves through the chunked, fused
        and grouped programs of one device, greedily; what would need
        its own block path refuses here, by name."""
        c = self.config
        b = cfg.block_length
        why = None
        if draft_cfg is not None:
            why = "a draft model (a block is no draft to verify)"
        elif not single_device(mesh):
            why = "a mesh"
        elif c.host_cache_bytes > 0 or host_store is not None:
            why = "the host tier / a remote page store"
        elif c.decode_rounds > 1:
            why = "decode_rounds > 1 (a program already holds a block)"
        elif any(self._chunk_width(k) % b for k in c.seq_buckets):
            why = (
                f"prefill_chunk {c.prefill_chunk} / seq_buckets "
                f"{c.seq_buckets} whose chunks are no multiple of the block "
                f"length {b} (chunks end on block boundaries)"
            )
        elif c.page_size % b:
            why = f"page_size {c.page_size}, no multiple of the block {b}"
        if why:
            raise ValueError(
                f"{cfg.name}: block-diffusion models do not serve with "
                f"{why} yet"
            )

    def _create_pool(self, cfg: ModelConfig) -> PagedKVCache:
        """An empty pool for ``cfg`` at this batcher's geometry. On a
        mesh it is born in its sharding — every device allocates its
        own shard and none ever holds the whole pool (4.3 GB at the
        mistral-7b defaults, beside the weights)."""
        c = self.config
        create = partial(
            PagedKVCache.create,
            cfg, c.n_pages, c.page_size, c.max_slots, c.pages_per_seq,
            state_slots=self._state_slots if cfg.is_recurrent else 0,
        )
        if self.mesh is None:
            return create()
        return StoredJit(
            create, program="create_pool", key=(self._key, repr(cfg)),
            out_shardings=self._pool_sharding_for(cfg),
        )()

    def _pool_sharding_for(self, cfg: ModelConfig) -> PagedKVCache:
        """Placement of one paged pool on the mesh (PR 13): pages over
        ``data`` (each data shard holds exactly the page range its
        slots allocate from — the host allocator's affinity), kv heads
        over ``model`` when they divide (a draft whose Hkv < mp
        replicates its heads — tiny planes, correctness first), page
        tables and lengths row-sharded over ``data``."""
        head = "model" if cfg.n_kv_heads % self._mp == 0 else None
        plane = self._named((None, "data", None, head, None))
        return PagedKVCache(
            k=plane,
            v=plane,
            page_table=self._named(("data", None)),
            length=self._named(("data",)),
        )

    @property
    def _depth(self) -> int:
        """Decode programs allowed in flight (>= 1). Read per loop
        iteration, so a depth change between bursts takes effect
        without restarting the batcher. With an adaptive controller
        the effective depth steers within
        [1, pipeline_depth] from the un-overlapped overhead signal
        (PR 15) — outputs are depth-invariant by the PR-6 contract,
        so steering can never change text."""
        d = max(1, self.config.pipeline_depth)
        if self.controller is not None:
            d = max(1, min(d, self.controller.depth_for(d)))
        return d

    @property
    def _spec_ok(self) -> bool:
        """Whether decode rounds run the speculative draft/verify
        program (PR 9). Read per loop iteration: ``spec_decode`` may
        flip on a live batcher."""
        return (
            self._draft_cfg is not None
            and self.config.spec_k > 0
            and self.config.spec_decode
        )

    @property
    def _rounds(self) -> int:
        """Decode rounds folded into one PLAIN (non-spec) dispatch
        (PR 12): ``decode_rounds``, at least 1. Meshes included since
        PR 13: a frozen row's NULL-page write is one more row of the
        same sharded scatter every live row rides, and the stop screen
        / budgets / emit counts are per-row data sharded over ``data``
        like every other row array. Read per loop iteration; while > 1
        every non-spec dispatch runs the multi-round machinery — even
        a stop-bound 1-round window — so a pipeline window never mixes
        host- and device-advanced PRNG counts."""
        return max(1, self.config.decode_rounds)

    @property
    def _round_tokens(self) -> int:
        """Worst-case tokens ONE dispatched program advances a row by —
        the page-overshoot unit. Plain decode: the decode_rounds
        window (PR 12) — counted from the CONFIG regardless of live
        engagement, exactly like spec_k, so in-flight admissions stay
        budgeted across a flip. With a draft configured: spec_k + 1
        verify tokens. Block diffusion: a block."""
        rt = self._rounds
        if self._draft_cfg is not None:
            rt = max(rt, self.config.spec_k + 1)
        return max(rt, self._block)

    # -- device programs ------------------------------------------------

    def _decode_sample(
        self,
        params,
        cache,
        tokens,
        seeds,
        counts,
        temps,
        topks,
        topps,
        filters_active,
        groups=None,
    ):
        """One decode+sample step for every slot as ONE device program.

        Returns ``([slots, 1] tokens, [slots, 1] logprobs, cache,
        [slots] final token, aux)`` — the final-token row is what a
        pipelined dispatch feeds the NEXT program without a host round
        trip; ``aux`` is the step's logits [slots, V] as the sampler
        got them (``"logits": n`` requests read their row; nothing
        else fetches it) and, for a dropless-MoE model, its routing
        counts. The step folds ``(seed, count)`` into the per-slot
        PRNG, so a request's stream is addressed by its output index
        whatever program carries it (tested).

        ``groups`` (DecodeGroupArrays or None): per-step decode-group
        metadata — shared prefix pages read once per group through the
        grouped kernel. None compiles/runs the plain program (the two
        variants are separate cached traces; membership CHANGES within
        a variant are pure data and never recompile).
        """
        # One application of the step, under a scan of length 1 ON
        # PURPOSE. XLA deletes the loop (the compiled program is the
        # same HLO with and without it), but on the chip's host every
        # process's warm-up of the chat cell took ~2 s longer without
        # it, 7 runs of 7 (PERF.md, Findings PR 30): take it out only
        # with `setup_s` of `mistral-7b.chat` measured beside it.
        def body(carry, _):
            cache, tok, cnt = carry
            next_tok, logp, cache, extra = self._decode_step(
                params, cache, tok, cnt, seeds, temps, topks, topps,
                filters_active, groups,
            )
            return (cache, next_tok, cnt + 1), (next_tok, logp, extra)

        (cache, tok_end, _), (toks, logps, extra) = jax.lax.scan(
            body, (cache, tokens, counts), None, length=1
        )
        return toks.T, logps.T, cache, tok_end, self._step_aux(extra)

    def _decode_step(
        self,
        params,
        cache,
        tok,
        cnt,
        seeds,
        temps,
        topks,
        topps,
        filters_active,
        groups,
        alive=None,
    ):
        """The decode+sample step the one-step program applies once
        and the multi-round scan body applies per round, so the two
        cannot drift: the layer pass over every slot's newest token (``alive``
        rows alone write K/V and advance; None = all), the ``(seed,
        count)`` PRNG fold, the sampler. Returns ``(next_tok, logp,
        cache, (logits, *routing counts))``."""
        logits, cache, *moe = decode_step_paged(
            self.cfg, params, tok[:, None], cache, groups=groups,
            write_mask=alive, mesh=self.mesh,
        )
        next_tok, logp = self._sample_rows(
            logits, seeds, cnt, temps, topks, topps, filters_active
        )
        return next_tok, logp, cache, (logits, *moe)

    @staticmethod
    def _sample_rows(logits, seeds, cnt, temps, topks, topps, filters_active):
        """Every slot's next token from its logits row, drawn with the
        row's ``(seed, count)`` key — the one sampling site of the
        decode, multi-round and fused programs. ``filters_active`` is
        STATIC (two cached programs): the all-defaults workload —
        every active request with top_k=0, top_p=1.0 — never pays the
        filters' full-vocab sort."""
        keys = jax.vmap(
            lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c)
        )(seeds, cnt)
        return sample_token_per_request(
            logits, keys, temps, topks, topps,
            filters_active=filters_active,
        )

    def _decode_body(
        self,
        params,
        seeds,
        temps,
        topks,
        topps,
        filters_active,
        groups,
        budgets,
        screen,
    ):
        """The early-exit-masked decode round as a scan body (PR 12),
        shared by the multi-round program and the fused step's
        multi-round tail. Carry ``(cache, tok, cnt, alive, emitted)``:
        a live row decodes exactly :meth:`_decode_step` (same K/V
        write, same (seed, count) PRNG fold, same sampler), then
        :func:`stop_scan_hit` freezes it on EOS, a screened stop
        candidate, or its emit budget. A frozen row stops writing K/V
        (decode_step_paged's write_mask), stops folding its PRNG
        (count invariance vs R = 1), holds its last token (the emit
        buffer past ``emitted`` is that stale token — the host reads
        only the real prefix), and stays frozen for the window's
        remainder (freezing is monotone, so the real tokens are always
        a prefix)."""

        def body(carry, _):
            cache, tok, cnt, alive, emitted = carry
            next_tok, logp, cache, extra = self._decode_step(
                params, cache, tok, cnt, seeds, temps, topks, topps,
                filters_active, groups, alive=alive,
            )
            next_tok = jnp.where(alive, next_tok, tok)
            adv = alive.astype(cnt.dtype)
            cnt = cnt + adv
            emitted = emitted + adv
            hit = stop_scan_hit(
                next_tok, self.tokenizer.eos_id, screen, emitted, budgets
            )
            alive = alive & ~hit
            return (
                (cache, next_tok, cnt, alive, emitted),
                (next_tok, logp, extra),
            )

        return body

    @staticmethod
    def _step_aux(extra):
        """What a program hands back beside its tokens, from its
        scan's stacked ``extra``: the LAST step's logits [slots, V] as
        the sampler got them (``"logits": n`` requests read their row
        of the one-step program's; ``submit`` refuses them where a
        program is several rounds) and, for a dropless-MoE model, the
        routing counts summed over the steps."""
        logits, *moe = extra
        return (logits[-1], *(m.sum(axis=0) for m in moe))

    def _rounds_sample(
        self,
        rounds,
        params,
        cache,
        tokens,
        seeds,
        counts,
        temps,
        topks,
        topps,
        filters_active,
        budgets,
        screen,
        groups=None,
    ):
        """Up to ``rounds`` decode rounds as ONE device program (PR 12)
        — the multi-round counterpart of :meth:`_decode_sample`: a scan
        of the same step with the early-exit mask threaded through the
        carry (:meth:`_decode_body`).

        counts: [B] device-resident per-row PRNG indices (the yield is
        data-dependent once rows can freeze mid-window, so counts
        thread program-to-program like the spec path's — the host
        mirror syncs at fetch); budgets: [B] max tokens each row may
        emit this window (its remaining max-new-tokens at dispatch);
        screen: [B, _SCREEN_W] -1-padded candidate stop ids. Every row
        enters alive, so each dispatched row emits >= 1 token — the
        invariant ``next_in`` (the final carry token, held through
        frozen rounds) relies on.

        Returns ``(emit [B, R], logps [B, R], cache, next_in [B],
        counts_out [B], emit_cnt [B], aux)`` — only each row's leading
        ``emit_cnt`` tokens are real, the spec program's contract.
        """
        alive0 = jnp.ones(tokens.shape, dtype=bool)
        emitted0 = jnp.zeros_like(counts)
        body = self._decode_body(
            params, seeds, temps, topks, topps, filters_active, groups,
            budgets, screen,
        )
        (cache, tok_end, cnt_out, _, emitted), (toks, logps, extra) = (
            jax.lax.scan(
                body, (cache, tokens, counts, alive0, emitted0), None,
                length=rounds,
            )
        )
        aux = self._step_aux(extra)
        return toks.T, logps.T, cache, tok_end, cnt_out, emitted, aux

    def _fused_sample(
        self,
        cfg_chunk,
        params,
        cache,
        tokens,
        seeds,
        counts,
        temps,
        topks,
        topps,
        filters_active,
        groups,
        chunk_tokens,
        chunk_table,
        chunk_start,
        chunk_last,
        chunk_done,
        chunk_sampler,
        stop_rounds=0,
        budgets=None,
        screen=None,
        chunk_state=None,
    ):
        """The fused scheduler step: one decode+sample step AND the
        next prefill chunk of up to L sequences as ONE device program
        (PR 8; L lanes since PR 31). ``chunk_tokens`` [L, C],
        ``chunk_table`` [L, P], ``chunk_start`` / ``chunk_last`` /
        ``chunk_done`` [L]; a lane with an all-NULL table is dead.
        ``chunk_sampler``: the lanes' (seeds, temperatures, top_k,
        top_p), [L] each, for the first token of a lane that ends its
        prompt here. ``chunk_state`` [L, 4] (a model with recurrent
        layers): each lane's state slots and real tokens, as
        ``prefill_chunk_paged`` takes them.

        ``stop_rounds`` (STATIC, PR 12): > 0 makes this the MULTI-ROUND
        fused step — the chunk rides round 1 exactly as before (every
        row enters alive, so the first step needs no mask), then
        ``stop_rounds - 1`` early-exit-masked rounds follow via the
        shared stop body, and the returns grow by ``(emit_cnt,
        counts_out)`` with only each row's leading ``emit_cnt`` emit
        tokens real — the chunk keeps riding the decode dispatch under
        ``decode_rounds`` without a pipeline flush per admission.
        0 = the one-step fused program and its return shape.

        The chunk rides the decode step's layer pass
        (:func:`~llm_consensus_tpu.models.transformer.fused_step_paged`
        — shared token axis, one K/V scatter, the ragged attention
        kernel). Returns the plain program's outputs plus
        ``(chunk_first, chunk_logits)`` (:meth:`_lane_first`): the [L]
        first tokens of the lanes that end their prompts here, and a
        tuple of L [V] logits rows they were sampled from.
        """
        logits, hidden, cache, *moe = fused_step_paged(
            self.cfg,
            params,
            tokens[:, None],
            cache,
            chunk_tokens,
            chunk_table,
            chunk_start,
            groups=groups,
            cfg_chunk=cfg_chunk,
            mesh=self.mesh,
            chunk_state=chunk_state,
        )
        tok1, logp1 = self._sample_rows(
            logits, seeds, counts, temps, topks, topps, filters_active
        )
        chunk_out = self._lane_first(
            params, hidden, chunk_start, chunk_last, chunk_done,
            chunk_sampler, filters_active,
        )
        if stop_rounds:
            # Multi-round tail (PR 12): round 1 was the fused step
            # above (all rows alive by the dispatch invariant); apply
            # its freeze decision, then scan the masked body for the
            # window's remainder. Same (seed, count + j) folds as
            # _rounds_sample — the chunk lane never perturbs a decode
            # row's PRNG stream.
            emitted = jnp.ones_like(counts)
            alive = ~stop_scan_hit(
                tok1, self.tokenizer.eos_id, screen, emitted, budgets
            )
            if stop_rounds > 1:
                body = self._decode_body(
                    params, seeds, temps, topks, topps, filters_active,
                    groups, budgets, screen,
                )
                (cache, tok_end, cnt_out, _, emitted), (toks, logps, extra) = (
                    jax.lax.scan(
                        body,
                        (cache, tok1, counts + 1, alive, emitted),
                        None,
                        length=stop_rounds - 1,
                    )
                )
                toks = jnp.concatenate([tok1[:, None], toks.T], axis=1)
                logps = jnp.concatenate([logp1[:, None], logps.T], axis=1)
                # aux: the FIRST round's logits (the fused step's own)
                # with the tail rounds' routing counts added.
                tail = self._step_aux(extra)[1:]
                aux = (logits, *(m + t for m, t in zip(moe, tail)))
                return (
                    toks, logps, cache, tok_end, chunk_out, emitted,
                    cnt_out, aux,
                )
            return (
                tok1[:, None], logp1[:, None], cache, tok1, chunk_out,
                emitted, counts + 1, (logits, *moe),
            )
        return (
            tok1[:, None], logp1[:, None], cache, tok1, chunk_out,
            (logits, *moe),
        )

    def _lane_first(
        self, params, hidden, chunk_start, chunk_last, chunk_done,
        chunk_sampler, filters_active,
    ):
        """The first generated token of each chunk lane that ends its
        prompt in this program, sampled where its logits are: lane l's
        last prompt position ``chunk_last[l]`` of ``hidden`` [L, C, D]
        is unembedded and drawn with the ``(seed, 0)`` key through the
        programs' one sampling site. Returns ``(first [L] int32, a
        tuple of L [V] logits rows)`` — a row a lane, so a
        ``"logits": n`` request's fetch slices nothing.

        ``chunk_done`` is traced, under a ``lax.cond`` taken when any
        lane ends its prompt: a program of non-final chunks skips the
        full-vocab unembed and the sampler at run time (its outputs are
        zeros nobody reads) without being a program of its own — a
        program costs seconds to trace and load in every process, and
        a last-chunk variant first met under load would stall every
        row for them."""
        lanes, c = hidden.shape[:2]
        seeds, temps, topks, topps = chunk_sampler

        def ended(h):
            logits = unembed_rows(self.cfg, params, h, mesh=self.mesh)
            first, _ = self._sample_rows(
                logits, seeds, jnp.zeros_like(seeds), temps, topks, topps,
                filters_active,
            )
            return first, logits

        first, logits = jax.lax.cond(
            jnp.any(chunk_done),
            ended,
            lambda h: (
                jnp.zeros((lanes,), jnp.int32),
                jnp.zeros((lanes, self.cfg.vocab_size), jnp.float32),
            ),
            hidden[
                jnp.arange(lanes), jnp.clip(chunk_last - chunk_start, 0, c - 1)
            ],
        )
        return first, tuple(logits[lane] for lane in range(lanes))

    def _chunk_sample(
        self, cfg_chunk, params, tokens, table, start, cache, chunk_last,
        chunk_done, chunk_sampler, filters_active, chunk_state=None,
    ):
        """The standalone chunk program: one prompt chunk for each of L
        lanes (:func:`prefill_chunk_paged`), and the first token of a
        lane that ends its prompt (:meth:`_lane_first`, as the fused
        step takes it). Returns ``((first, logits rows), cache,
        *routing counts)``."""
        hidden, cache, *moe = prefill_chunk_paged(
            cfg_chunk, params, tokens, table, start, cache, mesh=self.mesh,
            chunk_state=chunk_state,
        )
        if self._block > 1:
            # A block-diffusion prompt ends in its first block, which
            # the step program denoises: no first token, no unembed.
            lanes = tokens.shape[0]
            return (
                (jnp.zeros((lanes,), jnp.int32), (None,) * lanes), cache,
                *moe,
            )
        chunk_out = self._lane_first(
            params, hidden, start, chunk_last, chunk_done, chunk_sampler,
            filters_active,
        )
        return (chunk_out, cache, *moe)

    def _denoise_sample(
        self,
        params,
        cache,
        block,
        live,
        groups=None,
        chunk_tokens=None,
        chunk_table=None,
        chunk_start=None,
    ):
        """One BLOCK for every row of a block-diffusion model as ONE
        device program, plus optionally the next prefill chunk of up to
        L other sequences riding its first forward.

        ``block`` [slots, B]: each row's block as it starts — the
        prompt's tail, if the row has one still to ride, then masks;
        ``live`` [slots] bool: the rows that need a block (a row whose
        blocks in flight already cover its request writes nothing, as
        an idle one). The program runs the model's ``denoising_steps``
        forwards of the blocks against their rows' caches
        (:func:`~llm_consensus_tpu.models.transformer.denoise_step_paged`),
        each followed by the unmask rule (:func:`~llm_consensus_tpu.
        engine.diffusion.unmask`), then one forward of the finished
        blocks that writes their K/V (SDAR's commit: no block reaches the
        cache with masks in it), and advances each live row's length by
        B. Nothing leaves the device between the steps.

        Returns ``(tokens [slots, B], emit_cnt [slots], cache, aux)``:
        each row's generated positions first, of which ``emit_cnt``
        belong to the request (up to and including an EOS); ``aux`` =
        (logits [T, slots, B, V] float32 and the blocks' ids [T, slots,
        B] at each step — a ``"logits": n`` request's read-out, left on
        the device otherwise —, *routing counts summed over the
        forwards)."""
        cfg = self.cfg
        steps = cfg.denoising_steps
        masked0 = jnp.sum(block == cfg.mask_token_id, axis=1).astype(
            jnp.int32
        )
        live = live & (cache.page_table[:, 0] != NULL_PAGE)

        def forward(x, cache, *lanes, unembed=True):
            logits, _, cache, *moe = denoise_step_paged(
                cfg, params, x, cache, groups, *lanes, write_mask=live,
                unembed=unembed,
            )
            return logits, cache, moe

        logits, cache, moe = forward(
            block, cache, chunk_tokens, chunk_table, chunk_start
        )
        x = _diffusion.unmask(cfg, logits, block, masked0, 0)
        logits_all, ids_all = logits[None], block[None]
        if steps > 1:

            def body(carry, t):
                cache, x, moe = carry
                logits, cache, more = forward(x, cache)
                x_next = _diffusion.unmask(cfg, logits, x, masked0, t)
                moe = [a + b for a, b in zip(moe, more)]
                return (cache, x_next, moe), (logits, x)

            (cache, x, moe), (logits_t, ids_t) = jax.lax.scan(
                body, (cache, x, moe), jnp.arange(1, steps)
            )
            logits_all = jnp.concatenate([logits_all, logits_t])
            ids_all = jnp.concatenate([ids_all, ids_t])
        _, cache, more = forward(x, cache, unembed=False)
        moe = [a + b for a, b in zip(moe, more)]
        cache = replace(
            cache,
            length=cache.length + live.astype(cache.length.dtype) * x.shape[1],
        )
        new, emit = _diffusion.emitted(
            cfg, x, masked0, self.tokenizer.eos_id
        )
        return new, emit, cache, (logits_all, ids_all, *moe)

    def _spec_sample(
        self,
        spec_k,
        params,
        dparams,
        cache,
        dcache,
        tokens,
        seeds,
        counts,
        temps,
        topks,
        topps,
        filters_active,
        all_greedy,
        groups,
        draft_src,
        spec_fill,
        spec_off,
    ):
        """One speculative round — draft, verify, accept — as ONE
        device program (PR 9).

        tokens: [B] each row's newest committed token (its K/V not yet
        written — the same invariant as the plain decode step's input);
        counts: [B] device-resident per-row PRNG indices (data-
        dependent under accept/rollback, so they thread program-to-
        program like the cache instead of advancing on the host at
        dispatch). Shared draft streams: ``draft_src`` [B] is each
        row's stream donor (its own index = independent); a mate at
        ``spec_off[i]`` tokens behind its donor takes its first
        ``spec_off`` proposals from ``spec_fill`` [B, K] (the donor's
        already-committed suffix — host-known, certain-accept while
        the mate keeps agreeing) and the rest from the donor's fresh
        proposals, and its draft-cache writes consume exactly that
        stream, so its draft context stays consistent with what gets
        verified.

        The draft runs spec_k + 1 greedy steps (the +1 writes the last
        proposal's K/V — on full acceptance the bonus token's next
        round needs it; its own proposal is discarded, exactly like
        ``speculative_generate``'s extra step). The target verifies
        through :func:`verify_step_paged`'s ragged rows; the accept
        rule is :func:`llm_consensus_tpu.engine.accept.verify_tokens`
        — greedy rows byte-identical to plain decode, sampled rows the
        exact one-hot residual rule. Both caches' ``length`` rewinds
        to ``old + emit_cnt`` (count bookkeeping is the WHOLE
        rollback: decode rows write only private pages, so a rejected
        tail never touches registered/shared pages and simply gets
        overwritten).

        Returns (emit [B, K+1], emit_cnt [B], cache, dcache, next_in
        [B], counts_out [B]).
        """
        k = spec_k
        b = tokens.shape[0]
        dcfg = self._draft_cfg
        # Cross-model remap (PR 18): carried state (tokens, hist,
        # spec_fill, drafts) is TARGET vocab; the draft model's inputs
        # gather through t2d and its argmax lifts through d2t. Both
        # tables are trace constants; the identity case compiles with
        # no gather at all (self._t2d is None).
        t2d, d2t = self._t2d, self._d2t

        def dbody(carry, j):
            dc, tok, hist = carry
            din = tok if t2d is None else t2d[tok]
            lg, dc, *_ = decode_step_paged(
                dcfg, dparams, din[:, None], dc, mesh=self.mesh
            )
            prop = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # [B]
            if d2t is not None:
                prop = d2t[prop]
            hist = hist.at[:, j].set(prop)
            # Next input = each row's stream token j: donor committed
            # fill while j < spec_off, else the donor's proposal
            # j - spec_off (already in hist — a mate only ever lags).
            from_donor = jnp.take_along_axis(
                hist[draft_src], jnp.clip(j - spec_off, 0, k)[:, None], axis=1
            )[:, 0]
            nxt = jnp.where(
                j < spec_off,
                spec_fill[:, jnp.minimum(j, k - 1)],
                from_donor,
            )
            return (dc, nxt, hist), None

        hist0 = jnp.zeros((b, k + 1), jnp.int32)
        (dcache, _, hist), _ = jax.lax.scan(
            dbody, (dcache, tokens, hist0), jnp.arange(k + 1)
        )
        j_idx = jnp.arange(k)[None, :]
        from_donor = jnp.take_along_axis(
            hist[draft_src],
            jnp.clip(j_idx - spec_off[:, None], 0, k),
            axis=1,
        )
        drafts = jnp.where(
            j_idx < spec_off[:, None], spec_fill, from_donor
        )  # [B, K] each row's verified proposals == its draft-fed stream

        vtok = jnp.concatenate([tokens[:, None], drafts], axis=1)
        logits, cache, *_ = verify_step_paged(
            self._spec_cfg, params, vtok, cache, groups=groups,
            mesh=self.mesh,
        )  # [B, K+1, V] fp32

        def row_keys(s, c):
            base = jax.random.PRNGKey(s)
            # Key j = the (seed, output-index) fold the plain sampler
            # burns for generated token counts + j: per-request streams
            # stay (seed, index)-addressed regardless of speculation.
            return jax.vmap(lambda j: jax.random.fold_in(base, c + j))(
                jnp.arange(k + 1)
            )

        keys = jax.vmap(row_keys)(seeds, counts)
        # all_greedy STATIC: the per-position PRNG folds above become
        # dead code on the greedy trace and jit erases them with the
        # leviathan machinery.
        emit, emit_cnt = verify_tokens(
            logits, drafts, temps, topks, topps, keys,
            filters_active=filters_active, all_greedy=all_greedy,
        )
        new_len = cache.length + emit_cnt
        cache = PagedKVCache(
            k=cache.k, v=cache.v, page_table=cache.page_table, length=new_len
        )
        # Draft-length invariant: committed - 1 == the target's length,
        # for every row alike (the draft's next round re-consumes the
        # newest committed token at that position).
        dcache = PagedKVCache(
            k=dcache.k,
            v=dcache.v,
            page_table=dcache.page_table,
            length=new_len,
        )
        next_in = jnp.take_along_axis(
            emit, (emit_cnt - 1)[:, None], axis=1
        )[:, 0]
        return emit, emit_cnt, cache, dcache, next_in, counts + emit_cnt

    def _spec_stream_plan(self, rows_now, k: int | None = None):
        """Host-side shared-draft-stream planning for one round.
        ``k``: this dispatch's EFFECTIVE spec window (PR 15's
        controller may shrink it below config.spec_k; the fill matrix
        and offsets size to what the program will actually verify).

        Per shared-prefix bucket (GroupTracker first-page buckets — the
        panel over one header), the member with the LONGEST committed
        text is the donor; every mate whose generated tokens are a
        prefix of the donor's rides the donor's stream (src -> donor,
        fill = the donor's committed suffix, off = how far behind).
        A mate that has diverged — different token anywhere — simply
        stays its own stream; the comparison re-runs per round, so
        divergence needs no sticky state and a retired donor just
        stops being chosen. Returns (src [S], fill [S, K], off [S],
        streams, shared_rows).

        Pipeline staleness rule: with a spec program still in flight
        (depth >= 2), ``generated`` lags the device by that program's
        data-dependent emissions, so a donor-suffix FILL (off > 0)
        built from the mirror would verify at shifted device positions
        and mostly reject — worse than the mate drafting for itself.
        The lagging-mate catch-up therefore only plans over an empty
        pipeline window (depth 1, or right after a flush). The off ==
        0 path stays allowed in flight: equal mirrors + one shared
        greedy stream emit identically on device, so live equality is
        preserved (a sampled mate can diverge invisibly for one round
        and re-drafts alone the moment the mirror syncs — rejects for
        a round, never wrong output).
        """
        c = self.config
        if k is None:
            k = c.spec_k
        n = c.max_slots
        src = np.arange(n, dtype=np.int32)
        off = np.zeros((n,), np.int32)
        fill = np.zeros((n, k), np.int32)
        decoding = {i for i, _ in rows_now}
        mirror_authoritative = not self._inflight
        shared = 0
        if c.share_prefix:
            for bucket in self._groups.stream_buckets():
                members = [i for i in bucket if i in decoding]
                if len(members) < 2:
                    continue
                donor = max(
                    members,
                    key=lambda i: (len(self._slots[i].generated), -i),
                )
                dgen = self._slots[donor].generated
                for i in members:
                    if i == donor:
                        continue
                    gen = self._slots[i].generated
                    m = len(gen)
                    if gen != dgen[:m]:
                        continue  # diverged from the donor's stream
                    delta = len(dgen) - m
                    if delta > 0 and not mirror_authoritative:
                        continue  # stale fill — see staleness rule
                    src[i] = donor
                    off[i] = min(delta, k)
                    tail = dgen[m : m + k]
                    if tail:
                        fill[i, : len(tail)] = tail
                    shared += 1
        streams = len({int(src[i]) for i in decoding})
        return src, fill, off, streams, shared

    def _lanes_for(self, chunk: int) -> int:
        """Chunk lanes of the WIDE chunk programs at this chunk width:
        what fits, beside every slot's decode row (a block of B rows
        for a block-diffusion model), on the token axis
        the int8 matmul kernel takes (``quant_matmul._MAX_M`` rows: past
        it every matrix leaves the kernel, and a decoding row's wait
        stops buying throughput well before) — and never more lanes
        than slots. A program is static in its lanes, so there are two
        widths: this one, and 1 for the iteration that finds one slot
        ready (a lone prompt pays for no dead lane).

        One lane where a path takes one: the mesh kernel resolves the
        lane on its owner shard, a draft mirrors a chunk through its
        own one-lane program, and a capacity-dispatch MoE pins its path
        (and counts its capacity) by one sequence's chunk."""
        c = self.config
        if (
            self.mesh is not None
            or self._draft_cfg is not None
            or (self.cfg.is_moe and self.cfg.moe_capacity_factor > 0)
        ):
            return 1
        return max(1, min(
            c.max_slots, (_QMM_MAX_ROWS - c.max_slots * self._block) // chunk
        ))

    def _chunk_key(self, chunk: int, lanes: int, s_bucket: int):
        """(program key, pinned config) of a chunk program. The bucket
        only pins the chunk side's MoE dispatch path to the choice a
        one-shot [1, s_bucket] prefill would trace — a chunk below the
        dense-fallback threshold must not diverge from the engine's
        whole-prompt prefill it is parity-tested against — and the pin
        is ``self.cfg`` itself for every dense and every dropless
        configuration: their buckets share one program, and lanes of
        different buckets ride it together."""
        cfg = self.cfg.moe_pin_for(s_bucket, chunk)
        return (chunk, lanes, cfg.moe_dense_decode_tokens), cfg

    def _chunk_fn(self, chunk: int, lanes: int, s_bucket: int):
        """Jitted per :meth:`_chunk_key`: one paged prefill chunk for
        each of ``lanes`` sequences (:meth:`_chunk_sample`;
        ``filters_active`` static as in the step programs, over the
        lanes that end their prompts).

        Compile-once: chunk widths come from
        ``min(config.prefill_chunk, s_bucket)`` and lanes are 1 or
        :meth:`_lanes_for`, so the program family is bounded by the
        seq-bucket list.
        """
        key, cfg = self._chunk_key(chunk, lanes, s_bucket)
        if key not in self._jit_chunk:
            self._jit_chunk[key] = StoredJit(
                _step_program(
                    "prefill_chunk", partial(self._chunk_sample, cfg)
                ),
                key=(self._key, key),
                donate_argnums=(4,),
                static_argnums=(8,),
            )
        return self._jit_chunk[key]

    def _chunk_fn_d(self, chunk: int, s_bucket: int):
        """Jitted per (chunk, prompt-bucket): the DRAFT model's paged
        prefill chunk — same tokens/table/start as the target's chunk,
        its own pool. Runs whenever a draft is configured (even with
        spec_decode flipped off) so every admitted prompt has draft
        context by the time speculation engages."""
        key = (chunk, s_bucket)
        if key not in self._jit_chunk_d:
            dcfg = self._draft_cfg.moe_pin_for(s_bucket, chunk)
            t2d = self._t2d

            def prefill_chunk_draft(params, tokens, table, pos, dcache):
                # Cross-model remap (PR 18): the chunk arrives in
                # TARGET ids (the one prompt tokenization both pools
                # share); the draft model reads its t2d image. The
                # identity case traces with no gather.
                if t2d is not None:
                    tokens = t2d[tokens]
                return prefill_chunk_paged(
                    dcfg, params, tokens, table, pos, dcache,
                    mesh=self.mesh,
                )[:2]

            self._jit_chunk_d[key] = StoredJit(
                prefill_chunk_draft, key=(self._key, key),
                donate_argnums=(4,),
            )
        return self._jit_chunk_d[key]

    def _draft_prefill_chunk(self, slot: _Slot, chunk_ids, pos: int) -> None:
        """Run the draft's mirror of one prefill chunk (stream-ordered
        behind whatever program carries the target's chunk)."""
        self._count_program("draft")
        _, self.draft_cache = self._chunk_fn_d(slot.chunk, slot.s_bucket)(
            self._draft_params,
            jnp.asarray(chunk_ids[None]),
            jnp.asarray(slot.table),
            jnp.int32(pos),
            self.draft_cache,
        )

    def _spec_catch_up(self) -> None:
        """Replay plain-decoded tokens through the draft before a spec
        dispatch, for every row that decoded while ``spec_decode`` was
        flipped off.

        Plain decode programs advance only the target cache; the draft
        mirror's length and K/V for the window's tokens go stale
        (tracked per row in ``_Slot.draft_lag``). Without the replay
        the next spec round's draft scan would write this row's K/V at
        the stale positions — wrong RoPE, wrong span — and the row's
        proposals would silently stop accepting for the rest of its
        life. Greedy text stays correct either way (verify is exact);
        what this protects is the speedup the flip is supposed to
        resume.

        The replay runs the draft's own chunk program over the missing
        committed positions ``[tlen - lag, tlen)`` — all >= prompt_len,
        so every write lands in the row's PRIVATE decode pages, never a
        refcount-shared prefix page — in ``slot.chunk``-wide windows
        (the admission traces, already compiled) plus width-1 steps for
        the tail, then re-installs the row's draft length. A flip is a
        between-bursts event; rows alive across one are the edge case.
        """
        lagging = [
            (i, s)
            for i, s in enumerate(self._slots)
            if s is not None and s.phase == "decode" and s.draft_lag > 0
        ]
        if not lagging:
            return
        # Host mirror (generated tokens) must be current: drain any
        # window the lag accumulated under.
        if self._inflight:
            self._flush_pipeline()
            lagging = [
                (i, s)
                for i, s in lagging
                if self._slots[i] is s and s.phase == "decode"
            ]
        for idx, slot in lagging:
            _flight.flight_recorder().record(
                "spec_catch_up",
                time.perf_counter(),
                trace_id=_tracing.trace_id_of(slot.request.trace),
                slot=idx,
                lag=slot.draft_lag,
            )
            # Newest committed token's K/V is pending in BOTH caches
            # (the round input), so the draft must cover [dlen, tlen).
            tlen = slot.prompt_len + len(slot.generated) - 1
            dlen = tlen - slot.draft_lag
            table_dev = jnp.asarray(slot.table)
            gen = np.asarray(slot.generated, np.int32)
            cur = dlen
            while cur < tlen:
                width = slot.chunk if slot.chunk and tlen - cur >= slot.chunk else 1
                toks = gen[cur - slot.prompt_len : cur - slot.prompt_len + width]
                self._count_program("draft")
                _, self.draft_cache = self._chunk_fn_d(width, slot.s_bucket)(
                    self._draft_params,
                    jnp.asarray(toks[None]),
                    table_dev,
                    jnp.int32(cur),
                    self.draft_cache,
                )
                cur += width
            # An install is idempotent on the (unchanged) table row;
            # what this fixes is the row's draft length.
            self._row_install(idx, slot, tlen, draft_only=True)
            slot.draft_lag = 0
        self._apply_row_patch()

    def _fused_fn(self, chunk: int, lanes: int, s_bucket: int):
        """Jitted per :meth:`_chunk_key`: the fused scheduler step
        (:meth:`_fused_sample`) with ``lanes`` chunk lanes. The pin is
        the chunk side's MoE dispatch path exactly as :meth:`_chunk_fn`
        has it — the fused program must stay output-identical to the
        split programs it replaces (tested with ``ragged_attention`` on
        and off)."""
        key, cfg_chunk = self._chunk_key(chunk, lanes, s_bucket)
        if key not in self._jit_fused:
            self._jit_fused[key] = StoredJit(
                _step_program(
                    "fused_step", partial(self._fused_sample, cfg_chunk)
                ),
                key=(self._key, key),
                donate_argnums=(1,),
                static_argnums=(8, 16),
            )
        return self._jit_fused[key]

    def _denoise_fn(self, chunk: int, lanes: int):
        """Jitted per (chunk width, lanes): the block-diffusion step
        (:meth:`_denoise_sample`), with ``lanes`` chunk lanes riding
        its first forward (0: none). Module ``jit_denoise_step`` either
        way."""
        key = (chunk, lanes)
        if key not in self._jit_denoise:
            self._jit_denoise[key] = StoredJit(
                _step_program("denoise_step", self._denoise_sample),
                key=(self._key, "denoise", key),
                donate_argnums=(1,),
            )
        return self._jit_denoise[key]

    def _build_fused_ahead(self, chunk: int, s_bucket: int) -> None:
        """Trace and compile the chunk programs a decoding row could
        meet, once: the fused step at both widths (one lane and
        :meth:`_lanes_for`), with ungrouped rows and — where rows group
        — with grouped ones, the wide standalone chunk, and the row
        patch of a fetch (:func:`apply_rows`).

        Called before a chunk runs alone because nothing decodes, so no
        decoding row waits for the builds: the prompt being prefilled
        does, seconds, once a process (and not the process's first,
        which comes before the first plain dispatch, whose arguments
        give the shapes). Without it a fused step is first built when a
        chunk first rides a dispatch, or when two slots are first ready
        at once, or rows first group, with every decoding row stalled
        behind it; a chunk width or MoE pin first met under load is
        still built there. Building from shapes gives the executable its
        call takes (from the store of executables where an earlier
        process built it), and runs nothing.

        The builds are one ``build_ahead`` span: an annotation on this
        thread, inside the ``batcher.dispatch`` that called it (whose
        seconds it leaves alone), and seconds towards
        ``gateway_setup_seconds_total{phase="build_ahead"}``; the compile
        spans it causes nest inside it."""
        ahead, _ = self._chunk_key(chunk, 0, s_bucket)
        if ahead in self._fused_ahead or self._plain_shapes is None:
            return
        self._fused_ahead.add(ahead)
        span = _tracing.PhaseClock("", _M_SETUP_SECONDS, ("build_ahead",))
        with span.phase("build_ahead"):
            widths = self._build_ahead(chunk, s_bucket)
        log.info(
            "fused step programs for chunks of %d, lanes %s, grouped and "
            "not: built in %.1f s", chunk, widths, span.seconds["build_ahead"],
        )
        span.flush()

    def _build_ahead(self, chunk: int, s_bucket: int) -> list[int]:
        """The builds of :meth:`_build_fused_ahead`; returns the lane
        widths built."""
        c = self.config
        i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        f32 = partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        grouped = [None]
        if self._group_decode:
            rows, gm = i32((c.max_slots,)), i32((self._groups.max_groups,))
            grouped.append(DecodeGroupArrays(rows, gm, gm, rows))
        widths = sorted({1, self._lanes_for(chunk)})
        if self._block > 1:
            # Block diffusion: the block program without lanes, grouped
            # (the plain one is what gave the shapes), and with each
            # width of lanes, grouped and not.
            for groups in grouped[1:]:
                self._denoise_fn(0, 0).build(*self._plain_shapes, groups)
        for lanes in widths:
            lane = (
                i32((lanes, chunk)), i32((lanes, c.pages_per_seq)),
                i32((lanes,)),
            )
            if self._block > 1:
                for groups in grouped:
                    self._denoise_fn(chunk, lanes).build(
                        *self._plain_shapes, groups, *lane
                    )
                if lanes > 1:
                    self._chunk_fn(chunk, lanes, s_bucket).build(
                        self._plain_shapes[0], *lane, self._plain_shapes[1],
                        i32((lanes,)),
                        jax.ShapeDtypeStruct((lanes,), jnp.bool_),
                        (i32((lanes,)), f32((lanes,)), i32((lanes,)),
                         f32((lanes,))),
                        False,
                    )
                continue
            # Last positions, done, and the lanes' sampler rows.
            ends = (
                i32((lanes,)), jax.ShapeDtypeStruct((lanes,), jnp.bool_),
                (i32((lanes,)), f32((lanes,)), i32((lanes,)), f32((lanes,))),
            )
            state = (
                {"chunk_state": i32((lanes, 4))}
                if self._states is not None else {}
            )
            for groups in grouped:
                self._fused_fn(chunk, lanes, s_bucket).build(
                    *self._plain_shapes, groups, *lane, *ends, **state,
                )
            if lanes > 1:
                self._chunk_fn(chunk, lanes, s_bucket).build(
                    self._plain_shapes[0], *lane, self._plain_shapes[1],
                    *ends, self._plain_shapes[8], **state,
                )
        pools = (_rows_of(self._plain_shapes[1]),) + (
            (jax.tree.map(_abstract, _rows_of(self.draft_cache)),)
            if self.draft_cache is not None else ()
        )
        self._jit_apply_rows.build(
            pools, i32((len(pools), c.max_slots)),
            i32((c.max_slots, c.pages_per_seq)), i32((c.max_slots,)),
            i32((c.max_slots,)),
        )
        return widths

    @property
    def _fused_ok(self) -> bool:
        """Whether a ready chunk may ride the decode dispatch this
        iteration (PR 8; mesh-native since PR 13). On a mesh the fused
        program's concat [B + C] token axis is laid out by GSPMD from
        the operands' shardings — decode rows over ``data``, the chunk
        lane riding replicated with its K/V scatter landing on the
        owner shard's page range — and the attention read goes through
        the same one kernel seam as the plain step, so ONE device
        program per scheduler iteration holds on every topology. Read
        per iteration: ``config.ragged_attention`` may flip between
        bursts on one batcher."""
        return self.config.ragged_attention

    # -- public API -----------------------------------------------------

    def submit(
        self,
        prompt: str,
        *,
        max_new_tokens: int | None = None,
        temperature: float = 0.0,
        seed: int = 0,
        top_k: int | None = None,
        top_p: float | None = None,
        stop: list[str] | tuple[str, ...] | None = None,
        prompt_ids=None,
        logits: int = 0,
    ) -> Future:
        """Enqueue a request; Future resolves to a :class:`ServeResult`.

        ``logits`` = n > 0 also returns the float32 logits of the first
        n generated positions (``ServeResult.logits``), as the timed
        programs computed them: no second forward pass. Greedy requests
        only, and only where a program is one decode step (no
        ``decode_rounds`` or draft): elsewhere a program keeps only its
        last step's logits. For a block-diffusion model n counts
        denoising steps: B rows of logits a step, the block's input ids
        at each in ``ServeResult.logit_ids``.

        ``top_k``/``top_p``: ``None`` inherits the batcher's
        config-level sampler; any EXPLICIT value is authoritative —
        including 0 / 1.0, which mean *disabled* exactly as in
        ``SamplingParams`` (so a protocol request with default params
        samples unfiltered on this backend just like on LocalBackend,
        and "no top_k" is expressible on a batcher configured with
        one). ``stop`` follows the engine's stop-sequence contract —
        text trimmed at the earliest stop (stop removed), and the row
        retires as soon as the stop appears (every token is
        host-checked, so multi-token stops end decoding immediately).
        ``prompt_ids``: the prompt's already-encoded token ids — the
        fleet router tokenizes once for routing and passes them
        through (PR 14), so the common panel header is not encoded
        twice per request. Must be THIS tokenizer's encoding of
        ``prompt``; the same largest-bucket truncation applies."""
        if self._stop.is_set():
            raise RuntimeError(
                f"serving loop failed: {self._failed}"
                if self._failed
                else "batcher stopped"
            )
        c = self.config
        if max_new_tokens is None:
            max_new_tokens = c.max_new_tokens
        if max_new_tokens <= 0:
            raise ValueError(f"max_new_tokens must be > 0, got {max_new_tokens}")
        if self._block > 1 and temperature > 0:
            raise ValueError(
                f"{self.cfg.name}: block diffusion serves greedy requests "
                "(temperature 0) only yet"
            )
        if logits:
            if temperature > 0:
                raise ValueError("'logits' is for greedy requests only")
            if c.decode_rounds > 1 or self._draft_cfg is not None:
                raise ValueError(
                    "'logits' needs one decode step a program: no "
                    "decode_rounds or draft model"
                )
        full_ids = (
            prompt_ids
            if prompt_ids is not None
            else self.tokenizer.encode(prompt)
        )
        cap = c.seq_buckets[-1]
        if len(full_ids) > cap:
            if not c.truncate_prompts:
                raise ValueError(
                    f"prompt is {len(full_ids)} tokens but the largest "
                    f"sequence bucket is {cap} (set truncate_prompts=True "
                    "to left-truncate instead)"
                )
            log.warning(
                "prompt of %d tokens left-truncated to %d (largest bucket)",
                len(full_ids),
                cap,
            )
        ids = np.asarray(full_ids[-cap:], np.int32)
        if len(ids) < self._block:
            raise ValueError(
                f"{self.cfg.name}: a prompt of {len(ids)} tokens is shorter "
                f"than one block ({self._block}): block diffusion prefills "
                "whole blocks"
            )
        dflt = c.sampler or SamplerConfig()
        stop = tuple(stop or ())
        window = stop_tail_window(self.tokenizer, stop)
        # Multi-round decode (PR 12): the device stop screen, derived
        # once per distinct stop tuple (the derivation scans the
        # vocabulary; this thread must not repay it per request).
        if stop in self._screen_cache:
            screen = self._screen_cache[stop]
        else:
            screen = derived_stop_screen(
                self.tokenizer, stop, max_ids=_SCREEN_W
            )
            with self._lock:
                while len(self._screen_cache) >= _SCREEN_CACHE_MAX:
                    self._screen_cache.pop(
                        next(iter(self._screen_cache))
                    )
                self._screen_cache[stop] = screen
        req = _Request(
            prompt_ids=ids,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            seed=seed,
            future=Future(),
            top_k=dflt.top_k if top_k is None else top_k,
            top_p=dflt.top_p if top_p is None else top_p,
            stop=stop,
            stop_window=window,
            stop_screen=screen,
            trace=_tracing.current_trace(),
            rid=f"req-{next(_RID)}",
            t_submit=time.perf_counter(),
            logits_n=max(0, int(logits)),
        )
        with self._lock:
            self._waiting.append(req)
            _M_WAITING.set(len(self._waiting))
        _M_SUBMITTED.inc()
        self._work.set()
        return req.future

    def heartbeat(self) -> dict:
        """Host-loop liveness: seconds since the last loop tick and the
        last decode step. The loop ticks at >= 10 Hz even when idle, so
        a large ``last_tick_age_s`` means the worker is wedged (stuck
        device call, deadlock) — the gateway's ``/readyz`` probe flips
        to 503 past its stall threshold."""
        now = time.monotonic()
        alive = self._thread.is_alive() and not self._stop.is_set()
        return {
            "alive": alive,
            # Lifecycle state (PR 19): a standalone batcher is simply
            # serving or stopped; a fleet overlays "draining"/"retired"
            # on its replicas during elastic scale-down so /readyz can
            # tell a deliberate drain from a wedged loop.
            "state": "serving" if alive else "stopped",
            "last_tick_age_s": now - self._hb_tick,
            "last_step_age_s": (
                now - self._hb_step if self._hb_step is not None else None
            ),
            # Where the pool lives and which attention path its
            # programs trace, as observed at construction.
            "device": self.device,
            "kernels": self.kernels,
            # Set once, by the worker loop's own failure (_run).
            **({"failed": self._failed} if self._failed else {}),
        }

    # -- fleet surface (PR 14) ------------------------------------------
    # Everything the replica router/gateway threads call on a batcher:
    # read-only probes under the admission lock, plus preempt/export
    # REQUESTS the worker loop executes (the demote path's device_get
    # must never race the worker's dispatch-time buffer donation).

    def prefix_probe(self, ids) -> dict:
        """How much of this prompt's page-aligned prefix chain is
        already resident here: ``registry_tokens`` (device pages — the
        affinity signal; restore-free) and ``host_tokens`` (the host
        tier's extension past the registry match — restorable at
        device_put latency; capped at ``_PROBE_HOST_PAGES`` pages —
        it only breaks ties). Read-only: no refcounts, ticks, or
        counters move (PrefixRegistry.probe), so the router can probe
        every replica per request. Unready (in-flight-prefill) nodes
        count — a burst's mates must probe the donor's replica as a
        match while its prefill is still running."""
        c = self.config
        pg = c.page_size
        usable_full = (len(ids) - 1) // pg
        if usable_full <= 0 or not c.share_prefix:
            return {
                "registry_tokens": 0,
                "host_tokens": 0,
                "scope": self.chain_scope(),
            }
        chain = tuple(int(t) for t in ids[: usable_full * pg])
        best = (0, 0)
        with self._lock:
            for registry in self._registries:
                _, t = registry.probe(ids)
                k = t // pg
                h = 0
                if self._offload is not None:
                    # One batched run_len probe per registry (PR 17):
                    # over the remote store this is a single RTT for
                    # the whole capped extension walk instead of up to
                    # _PROBE_HOST_PAGES sequential __contains__ calls.
                    cap = min(usable_full - k, _PROBE_HOST_PAGES)
                    if cap > 0:
                        keys = [
                            self._store_key(chain[: (k + j + 1) * pg])
                            for j in range(cap)
                        ]
                        rl = getattr(self._offload, "run_len", None)
                        if rl is not None:
                            h = rl(keys)
                        else:
                            for key in keys:
                                if key not in self._offload:
                                    break
                                h += 1
                best = max(best, (t, h * pg))
        return {
            "registry_tokens": best[0],
            "host_tokens": best[1],
            "scope": self.chain_scope(),
        }

    def chain_scope(self) -> dict:
        """WHOSE chains this batcher's probe counts (PR 18): the model
        name and a weights-fingerprint prefix (plus the draft pairing
        when one is mounted). A heterogeneous fleet's front tier
        aggregates residency across members whose caches are mutually
        unrestorable — without the scope, ``/debug/chains`` counts
        them as one anonymous pool. Fingerprint computed lazily once:
        it walks every param leaf (the PR-14 store-key walk), a debug
        cost the first probe pays, never construction or serving."""
        if self._probe_scope is None:
            doc = {
                "model": self.cfg.name,
                "weights": _weights_fingerprint(self.params)[1][:12],
            }
            if self._draft_cfg is not None:
                doc["draft_model"] = self._draft_cfg.name
                if self._vocab_map is not None:
                    doc["draft_vocab_coverage"] = round(
                        self._vocab_map.coverage, 4
                    )
            self._probe_scope = doc
        return dict(self._probe_scope)

    def load_cost(self) -> float:
        """Modeled outstanding HBM bytes of this replica's admitted
        work — the router's least-loaded signal (PR 14): the KV terms
        of :meth:`_program_cost` integrated over each admitted
        request's remaining schedule (remaining prefill writes, plus
        every remaining decode step reading the whole committed
        context and writing one token), per slot and per waiting
        request. Weight reads amortize over whatever batch each
        request joins and are identical across replicas, so they
        cancel out of a load COMPARISON and are left out. A
        32k-context request weighs what it costs, not one unit of
        queue depth."""
        kvb = self._kv_token_bytes + self._draft_kv_token_bytes
        total = 0
        with self._lock:
            for s in self._slots:
                if s is None:
                    continue
                done = len(s.generated)
                rem = max(0, s.request.max_new_tokens - done)
                L = s.prompt_len + done
                if s.phase == "prefill":
                    total += max(0, s.prompt_len - s.next_pos)
                    rem = s.request.max_new_tokens
                    L = s.prompt_len
                total += rem * L + rem * (rem - 1) // 2 + rem
            for r in self._waiting:
                # A waiting request's whole schedule: the SAME tokens
                # modeled_request_cost charges at the admission door
                # (one formula, two surfaces — the unit-normalization
                # contract of PR 15's cost-budget admission).
                total += self._cost_tokens(len(r.prompt_ids), r.max_new_tokens)
        return float(total * kvb)

    @staticmethod
    def _cost_tokens(L: int, rem: int) -> int:
        """KV-token units of one not-yet-started request's whole
        schedule: L prefill writes, then rem decode steps each reading
        the full committed context (L + j at step j) and writing one
        token — THE formula load_cost integrates and
        modeled_request_cost prices, kept in one place so the router
        and the admission bound can never drift units."""
        return L + rem * L + rem * (rem - 1) // 2 + rem

    def modeled_request_cost(
        self, prompt_tokens: int, max_new_tokens: int | None = None
    ) -> float:
        """Modeled HBM bytes of one request's whole schedule — the
        cost-budget admission unit (PR 15). Deliberately the same
        KV-term formula and byte unit as :meth:`load_cost`, so the
        gateway queue bound, the overflow hard cap, and the fleet
        router's least-loaded comparison all speak modeled bytes: a
        32k-context request weighs what it costs, not one unit of
        queue depth."""
        c = self.config
        if max_new_tokens is None:
            max_new_tokens = c.max_new_tokens
        L = max(1, min(int(prompt_tokens), c.seq_buckets[-1]))
        kvb = self._kv_token_bytes + self._draft_kv_token_bytes
        return float(self._cost_tokens(L, int(max_new_tokens)) * kvb)

    def waiting_depth(self) -> int:
        """Requests admitted to this batcher but not yet slotted — the
        router's congestion signal for rebalancing (cheap; stats()
        walks the registries and is too heavy per routed request)."""
        with self._lock:
            return len(self._waiting)

    def device_programs_total(self) -> int:
        """All device programs this batcher has dispatched (the
        per-replica split of the process-global
        gateway_device_programs_total)."""
        with self._lock:
            return sum(self._programs.values())

    def prefix_hit_rate(self) -> float:
        """Committed prefix-registry hit rate (hits / lookups; 0.0
        before the first lookup)."""
        with self._lock:
            lookups = sum(r.lookups for r in self._registries)
            hits = sum(r.hits for r in self._registries)
        return hits / max(1, lookups)

    def cached_chain_pages(self) -> int:
        """ALL registry-resident chain pages, pinned-by-live-slots
        included (cheap — a node count, no tree walk). The fleet
        hook's is-there-anything-to-preserve signal: pinned chains
        become demotable as their slots retire, so a non-zero count
        means overload admission degrades to restore latency; zero
        means the offered traffic registers nothing shareable and
        classic shedding is the only backpressure left."""
        with self._lock:
            return sum(r.cached_pages for r in self._registries)

    @property
    def host_page_bytes(self) -> int:
        """Approximate host-tier bytes one demoted page occupies
        (target + draft planes; int8 pools' scale planes add a few
        percent on top) — the router's store-headroom unit."""
        return (
            self._kv_token_bytes + self._draft_kv_token_bytes
        ) * self.config.page_size

    def request_preempt(self, n_pages: int) -> None:
        """Ask the worker to demote up to ``n_pages`` reclaimable
        registry pages to the host tier NOW — the fleet's
        preempt-instead-of-shed lever: an overload storm frees device
        pages at restore-latency cost instead of 429ing. Enqueued;
        the worker's next iteration executes it (callable from any
        thread). The backlog is the MAX of outstanding requests, not
        the sum: a storm can call this hundreds of times between two
        worker ticks, and summing would wipe the victim's entire
        prefix cache in one giant evict walk + device_get under the
        admission lock — one bounded demotion per worker iteration
        while overflow persists is the intent."""
        if self._offload is None or n_pages <= 0:
            return
        with self._lock:
            self._preempt_req = max(self._preempt_req, int(n_pages))
        self._work.set()

    def request_group_cap(self, n: int) -> None:
        """Ask the worker to resize the shared-prefix group-formation
        cap (``GroupTracker.max_groups`` — how many prefix groups the
        grouped decode program batches per dispatch) at its next
        iteration. The fleet controller (PR 19) sizes this from
        fleet-level sharing pressure; the tracker itself is
        worker-owned, so the change rides the same enqueued-request
        discipline as preempts. Clamped to [1, max_slots]."""
        n = max(1, min(int(n), self.config.max_slots))
        with self._lock:
            self._group_cap_req = n
        self._work.set()

    def group_cap(self) -> int:
        """Current shared-prefix group-formation cap (steered value
        once a ``request_group_cap`` has been applied)."""
        return int(self._groups.max_groups)

    def active_requests(self) -> int:
        """Admitted-but-unfinished requests on this batcher: waiting +
        slotted. The elastic-retire drain barrier — a draining replica
        is closeable once this reaches zero (cheap: two length reads
        under the admission lock)."""
        with self._lock:
            return len(self._waiting) + sum(
                1 for s in self._slots if s is not None
            )

    def request_export(
        self, ids, stream_until: float | None = None
    ) -> threading.Event:
        """Ask the worker to spill the READY resident pages of this
        prompt's registered prefix chain to the (shared) host store
        WITHOUT evicting them — the rebalance transport: the chain
        stays hot here and becomes restorable on any replica sharing
        the store. Returns an Event set when the spill has run (set
        immediately when the tier is off — nothing to do).

        With ``stream_until`` (a ``time.monotonic`` deadline) the
        export STREAMS (PR 17): each worker iteration spills the pages
        that became ready since the last pass — so while a chunked
        prefill is still computing the chain's tail, the head is
        already crossing the wire — and the export re-arms itself
        until every usable chain page is out (then the event sets) or
        the deadline passes (the event sets with whatever made it;
        the coordinator's own wait bounds the handoff either way)."""
        done = threading.Event()
        if self._offload is None:
            done.set()
            return done
        with self._lock:
            self._exports.append(
                [np.asarray(ids, np.int32), done, stream_until, 0]
            )
        self._work.set()
        return done

    # -- route-driven restore prefetch (PR 17) --------------------------
    # When the fleet router picks THIS replica as a request's
    # destination, the chain's host-store pages are known before the
    # request clears the gateway queue + admission. prefetch_chain()
    # pulls them store -> local staging (the expensive remote hop) on a
    # side thread so admission's restore plan starts from staged planes
    # instead of a cold round trip; the device_put half still happens
    # on the worker (restore discipline unchanged). Wrong-guess safety:
    # entries are chain-keyed (content deterministic in the key), so a
    # stale or evicted guess can never corrupt — it just falls through
    # to get_run/recompute. The staging dict is byte-bounded by entry
    # COUNT (a few chains' worth) and LRU-evicts, counted as "expired".

    def prefetch_chain(self, ids) -> bool:
        """Queue a speculative store->host pull of this prompt's chain
        (gateway/router thread; non-blocking). Returns False when
        there is nothing to prefetch (no offload tier, sharing off,
        sub-page prompt, or the queue is saturated)."""
        c = self.config
        if self._offload is None or not c.share_prefix:
            return False
        if (len(ids) - 1) // c.page_size <= 0:
            return False
        with self._prefetch_lock:
            if len(self._prefetch_q) >= 32:
                return False  # saturated: drop, never block the router
            self._prefetch_q.append(np.asarray(ids, np.int32))
            if self._prefetch_thread is None:
                self._prefetch_thread = threading.Thread(
                    target=self._prefetch_loop,
                    name="kv-prefetch",
                    daemon=True,
                )
                self._prefetch_thread.start()
        self._prefetch_have.set()
        return True

    def _prefetch_loop(self) -> None:
        while not self._stop.is_set():
            self._prefetch_have.wait(timeout=0.2)
            while True:
                with self._prefetch_lock:
                    if not self._prefetch_q:
                        self._prefetch_have.clear()
                        break
                    ids = self._prefetch_q.popleft()
                if self._stop.is_set():
                    return
                try:
                    self._prefetch_one(ids)
                except Exception:  # noqa: BLE001 — advisory path
                    log.exception("kv prefetch failed (ignored)")

    def _prefetch_one(self, ids) -> None:
        """Pull one chain's restorable pages store -> staging. Probes
        the registries first so device-resident pages aren't refetched;
        skips keys already staged; stages the contiguous run the store
        holds past that point."""
        c = self.config
        pg = c.page_size
        usable_full = (len(ids) - 1) // pg
        chain = tuple(int(t) for t in ids[: usable_full * pg])
        with self._lock:
            k = 0
            for reg in self._registries:
                _, t = reg.probe(ids)
                k = max(k, t // pg)
        keys = [
            self._store_key(chain[: (j + 1) * pg])
            for j in range(k, usable_full)
        ]
        with self._prefetch_lock:
            while keys and keys[0] in self._prefetched:
                self._prefetched.move_to_end(keys[0])
                keys.pop(0)
        if not keys:
            return
        store = self._offload
        gr = getattr(store, "get_run", None)
        if gr is not None:
            run = gr(keys)
        else:
            run = []
            for key in keys:
                planes = store.get(key)
                if planes is None:
                    break
                run.append(planes)
        if not run:
            return
        expired = 0
        with self._prefetch_lock:
            for key, planes in zip(keys, run):
                self._prefetched[key] = planes
                self._prefetched.move_to_end(key)
            while len(self._prefetched) > self._prefetch_cap:
                self._prefetched.popitem(last=False)
                expired += 1
            self._prefetch_fetched += len(run)
            self._prefetch_expired += expired
        _M_PREFETCH.labels(event="fetched").inc(len(run))
        if expired:
            _M_PREFETCH.labels(event="expired").inc(expired)
        _flight.flight_recorder().record(
            "prefetch", time.perf_counter(), pages=len(run),
            expired=expired,
        )

    def _prefetch_take(self, keys: list) -> list:
        """Consume the staged contiguous prefix of ``keys`` (admission
        path, caller holds ``self._lock`` — lock order is always
        _lock -> _prefetch_lock). Taken entries leave the staging dict:
        their planes transfer to the restore plan."""
        out: list = []
        with self._prefetch_lock:
            for key in keys:
                planes = self._prefetched.pop(key, None)
                if planes is None:
                    break
                out.append(planes)
            if out:
                self._prefetch_hits += len(out)
        if out:
            _M_PREFETCH.labels(event="hit").inc(len(out))
        return out

    def _prefetch_stats(self) -> dict:
        """Stats()-shaped prefetch counters (lock order: the caller
        holds ``self._lock``; _prefetch_lock nests inside it)."""
        with self._prefetch_lock:
            return {
                "prefetch_fetched_pages": self._prefetch_fetched,
                "prefetch_hit_pages": self._prefetch_hits,
                "prefetch_expired_pages": self._prefetch_expired,
                "prefetch_staged_pages": len(self._prefetched),
            }

    def _steer_step(self) -> None:
        """Worker-side application of a queued group-cap resize (PR
        19). The tracker re-forms its group view lazily, so the new
        cap takes effect at the next grouped-decode array build."""
        if self._group_cap_req is None:
            return
        with self._lock:
            n, self._group_cap_req = self._group_cap_req, None
        if n is not None and n != self._groups.max_groups:
            self._groups.max_groups = n
            self._groups._dirty = True

    def _preempt_step(self) -> None:
        """Worker-side execution of queued preempt requests: one
        registry evict walk whose on_evict hook demotes the victims
        (the PR-4 path — preemption IS eviction pointed at the host
        tier, requested by the router instead of by a short pool)."""
        if not self._preempt_req:
            return
        with self._lock:
            n, self._preempt_req = self._preempt_req, 0
            freed = 0
            for reg in self._registries:
                if freed >= n:
                    break
                freed += reg.evict(n - freed)
            self._preempted_pages += freed
        if freed:
            if self.controller is not None:
                # Restore-pacing debt (PR 15): preempt-demoted bytes
                # that the restore path will have to repay.
                self.controller.note_preempt_demote(
                    freed * self.host_page_bytes
                )
            _flight.flight_recorder().record(
                "preempt", time.perf_counter(), pages=freed
            )

    def _export_step(self) -> None:
        """Worker-side execution of ONE queued chain export per loop
        iteration (the same bounded-stall discipline as restores):
        probe the registries for the chain's resident nodes, spill the
        ready ones the store doesn't already hold.

        STREAMING exports (PR 17, ``stream_until`` set) spill only the
        DELTA of pages that became ready since their last pass, then
        re-arm at the back of the queue until the whole usable chain is
        out or the deadline passes — overlapping the wire transfer with
        the chunked prefill that is still computing the chain's tail.
        Re-arming deliberately does NOT set ``_work``: the worker's
        idle tick (the 0.1 s ``_work.wait`` timeout) repolls a pending
        stream without busy-spinning an otherwise idle loop."""
        if not self._exports:
            return
        streaming = False
        with self._lock:
            if not self._exports:
                return
            entry = self._exports.popleft()
            ids, done, stream_until, spilled = entry
            nodes: list = []
            for reg in self._registries:
                cand, _ = reg.probe(ids)
                if len(cand) > len(nodes):
                    nodes = cand
            ready = [n for n in nodes if n.ready]
            fetched = 0
            if len(ready) > spilled:
                # Delta-spill: earlier passes of this streamed export
                # already pushed ready[:spilled] (ready order is chain
                # order — pages become ready root-first).
                fetched, _ = self._spill_nodes(ready[spilled:])
                entry[3] = len(ready)
            self._exported_pages += fetched
            if stream_until is not None:
                expected = (len(ids) - 1) // self.config.page_size
                if (
                    len(ready) < expected
                    and time.monotonic() < stream_until
                ):
                    streaming = True
                    self._exports.append(entry)
        if fetched or not streaming:
            # Quiet re-poll passes (streamed export waiting on prefill
            # progress) don't spam the flight ring.
            _flight.flight_recorder().record(
                "export", time.perf_counter(), pages=fetched,
                resident=len(ready), streaming=streaming,
            )
        if not streaming:
            done.set()

    def stats(self) -> dict:
        """Live serving counters — a consistent snapshot (the worker
        mutates slots/pages/counters under the same lock).

        ``free_pages`` counts reclaimable prefix-registry pages (held by
        nobody but the registry — evicted on demand at admission) as
        free: they are available capacity, exactly like OS page-cache
        memory. ``cached_pages`` reports the registry-resident total.
        """
        with self._lock:
            regs = self._registries
            return {
                "active_slots": self._decoding(),
                "prefilling_slots": sum(
                    s is not None and s.phase == "prefill"
                    for s in self._slots
                ),
                "max_slots": self.config.max_slots,
                "waiting": len(self._waiting),
                "free_pages": sum(p.available for p in self._pools)
                + sum(r.reclaimable_pages() for r in regs),
                "total_pages": self.config.n_pages - 1,
                "cached_pages": sum(r.cached_pages for r in regs),
                "completed_requests": self._completed,
                "generated_tokens": self._generated_tokens,
                "decode_steps": self._decode_steps,
                "prefill_chunks": self._prefill_chunks,
                "prefix_lookups": sum(r.lookups for r in regs),
                "prefix_hits": sum(r.hits for r in regs),
                "prefix_pages_shared": sum(r.pages_shared for r in regs),
                "prefix_pages_copied": sum(r.pages_copied for r in regs),
                "prefix_evictions": sum(r.evictions for r in regs),
                # Group-aware decode attention (PR 3): KV bytes the
                # grouped kernel did not re-read, the largest active
                # group right now (0 = ungrouped program), and the
                # lifetime peak group size.
                "shared_kv_bytes_saved": self._kv_bytes_saved,
                "decode_group_size": self._groups.largest_group,
                "decode_group_peak": self._groups.peak_group,
                # Host-RAM offload tier (PR 4). Demoted counts every
                # eviction that reached the host store (including
                # refreshes of already-spilled chains); restored counts
                # pages promoted back instead of re-prefilled — each
                # one is page_size prompt tokens the chip never
                # recomputed; dropped is LRU pressure within the host
                # budget.
                "offload_demoted_pages": (
                    self._offload.demoted_pages if self._offload else 0
                ),
                "offload_restored_pages": self._offload_restored,
                "offload_dropped_pages": (
                    self._offload.dropped_pages if self._offload else 0
                ),
                "offload_host_bytes": (
                    self._offload.bytes_used if self._offload else 0
                ),
                "offload_host_pages": (
                    len(self._offload) if self._offload else 0
                ),
                # Fleet hooks (PR 14): pages demoted by router-
                # requested preemption (a subset of offload_demoted),
                # and ready chain pages spilled by rebalance exports
                # (resident here AND restorable fleet-wide).
                "preempted_pages": self._preempted_pages,
                "exported_pages": self._exported_pages,
                # Route-driven restore prefetch (PR 17): pages staged
                # store->host ahead of admission, staged pages the
                # restore planner consumed, and staged pages the LRU
                # cap expired unconsumed (mirrors of
                # gateway_kv_prefetch_total, lockstep tested). Wire
                # bytes mirror the remote store client's own counters
                # (0 for an in-process tier — no wire).
                **self._prefetch_stats(),
                "offload_wire_tx_bytes": (
                    getattr(self._offload, "tx_bytes", 0)
                    if self._offload
                    else 0
                ),
                "offload_wire_rx_bytes": (
                    getattr(self._offload, "rx_bytes", 0)
                    if self._offload
                    else 0
                ),
                # Span-derived step telemetry (PR 5): the same
                # observations that feed gateway_decode_step_seconds /
                # gateway_sched_overhead_seconds — one instrumentation
                # site, two surfaces (lockstep tested).
                "decode_step_seconds_sum": self._decode_step_sum,
                "decode_step_seconds_count": self._decode_step_count,
                "sched_overhead_seconds_sum": self._sched_overhead_sum,
                "sched_overhead_seconds_count": self._sched_overhead_count,
                # Pipelined decode dispatch (PR 6): programs currently
                # dispatched-not-fetched, and drains forced by
                # stable-cache operations (restores, CoW copies) — the
                # same observations behind
                # gateway_dispatch_inflight /
                # gateway_pipeline_flushes_total (lockstep tested).
                "dispatch_inflight": len(self._inflight),
                "pipeline_flushes": self._pipeline_flushes,
                # Mirror of gateway_pipeline_drains_total{after}.
                **{
                    f"pipeline_drains_{after}": n
                    for after, n in self._pipeline_drains.items()
                },
                # Fused scheduler step (PR 8): device programs by kind
                # (fused = decode rows + a prefill chunk in ONE
                # program), ragged-row occupancy, and the count of loop
                # iterations that ran any program — programs/iteration
                # == 1 is the fusion working; the same observations
                # behind gateway_device_programs_total /
                # gateway_ragged_rows_per_program (lockstep tested).
                "device_programs_fused": self._programs["fused"],
                "device_programs_decode": self._programs["decode"],
                "device_programs_prefill": self._programs["prefill"],
                "device_programs_spec": self._programs["spec"],
                "device_programs_draft": self._programs["draft"],
                "device_programs_denoise": self._programs["denoise"],
                # Block diffusion: the mirrors of
                # gateway_denoise_steps_total (summed over kinds),
                # gateway_diffusion_tokens_total and
                # gateway_diffusion_blocks_total, and of
                # gateway_attention_pairs_total{kind}.
                **{f"diffusion_{k}": n for k, n in self._diffusion.items()},
                **{
                    f"attention_pairs_{k}": n
                    for k, n in self._attn_pairs.items()
                },
                "ragged_rows_sum": self._ragged_rows_sum,
                "ragged_rows_count": self._ragged_rows_count,
                # Chunk programs by how many lanes carried a chunk
                # (gateway_chunk_lanes_total{kind,lanes}): over a kind
                # they sum to its chunk programs.
                **{
                    f"chunk_lanes_{kind}_{n}": v
                    for (kind, n), v in self._chunk_lanes_n.items()
                },
                "work_iterations": self._work_iterations,
                # Recurrent state (PR 32): the mirrors of
                # gateway_state_snapshots_total{event},
                # gateway_prefix_tokens_recomputed_total,
                # gateway_ssm_tokens_total{kind} and gateway_state_slots
                # — absent for a model without state-space layers.
                **(
                    {
                        **{
                            f"state_snapshots_{e}": n
                            for e, n in self._state_events.items()
                        },
                        "prefix_tokens_recomputed": self._prefix_recomputed,
                        **{
                            f"ssm_tokens_{k}": n
                            for k, n in self._ssm_tokens.items()
                        },
                        "state_slots_free": self._states.available,
                        "state_slots_held": self._states.held,
                    }
                    if self._states is not None
                    else {}
                ),
                # Multi-round on-device decode (PR 12) — the same
                # observations behind gateway_device_rounds_total /
                # gateway_decode_rounds_per_program (lockstep tested):
                # total decode rounds dispatched, and the histogram's
                # sum/count over decode-advancing programs
                # (decode/fused pass their window, spec passes 1 —
                # rounds count once per PROGRAM, not per row).
                # device_rounds_total / decode_rounds_count is the
                # realized rounds per program, and device programs per
                # generated token drops ~R× at R for a fixed batch
                # shape (tests/test_decode_rounds.py).
                "device_rounds_total": self._device_rounds,
                "decode_rounds_sum": self._decode_rounds_sum,
                "decode_rounds_count": self._decode_rounds_count,
                # Mesh topology (PR 13) — the same numbers behind
                # gateway_mesh_shards{axis} (lockstep tested): 1 on a
                # single chip; the serving features engage either way
                # (README engage matrix).
                "mesh_data_shards": self._dp,
                "mesh_model_shards": self._mp,
                # Speculative decoding (PR 9) — the same observations
                # behind gateway_spec_draft_tokens_total /
                # gateway_spec_accepted_tokens_total /
                # gateway_spec_acceptance / gateway_spec_verified_tokens
                # (lockstep tested). drafted counts k per STREAM per
                # round (one shared stream per agreeing panel group);
                # shared_draft_rows counts row-rounds that reused a
                # donor stream — per-sequence drafting would have
                # drafted for those rows too, so this is the panel
                # amortization realized.
                "spec_draft_tokens": self._spec_drafted,
                "spec_accepted_tokens": self._spec_accepted,
                "spec_cross_model_accepted_tokens": (
                    self._spec_xmodel_accepted
                ),
                "spec_acceptance_sum": self._spec_acc_sum,
                "spec_acceptance_count": self._spec_acc_count,
                "spec_verified_tokens_last": self._spec_verified_last,
                "spec_shared_draft_rows": self._spec_shared_rows,
                # Per-request token timeline (PR 10) — the same
                # observations behind gateway_tbt_seconds (lockstep
                # tested); ttft here is the batcher's submit-to-first-
                # token (the gateway's gateway_ttft_seconds keeps its
                # arrival-to-first-byte view; both move once per
                # request).
                "ttft_seconds_sum": self._ttft_sum,
                "ttft_seconds_count": self._ttft_count,
                "tbt_seconds_sum": self._tbt_sum,
                "tbt_seconds_count": self._tbt_count,
                # Roofline attribution (PR 10): per-program-kind sums
                # of the static cost model (modeled HBM bytes, FLOPs,
                # target-pool KV tokens touched) next to the measured
                # program seconds — gateway_program_mbu's inputs, so
                # MBU is derivable offline against any peak bandwidth.
                **{
                    f"mbu_{key}_{kind}": m[key]
                    for kind, m in self._mbu.items()
                    for key in (
                        "hbm_bytes",
                        "flops",
                        "kv_read_tokens",
                        "kv_write_tokens",
                        "attn_pages_read",
                        "seconds",
                        "programs",
                    )
                },
                # Adaptive control (PR 15): the controller's own
                # mirrors of gateway_autotune_value/_decisions_total —
                # absent without a controller (the knobs are static
                # config then, and a missing key is honest about it).
                **(
                    self.controller.stats()
                    if self.controller is not None
                    else {}
                ),
            }

    def close(self) -> None:
        self._stop.set()
        self._work.set()
        self._prefetch_have.set()  # wake the prefetch loop to exit
        self._thread.join(timeout=10)
        if self._prefetch_thread is not None:
            self._prefetch_thread.join(timeout=5)
        self._release_waiters(RuntimeError("batcher stopped"))

    def _release_waiters(self, exc: Exception) -> None:
        """Resolve everything still waiting on this batcher with
        ``exc``: queued and admitted requests' futures, and pending
        rebalance exports (which never run now)."""
        with self._lock:
            for _, ev, *_rest in self._exports:
                ev.set()
            self._exports.clear()
            for req in self._waiting:
                if not req.future.done():
                    req.future.set_exception(exc)
            for slot in self._slots:
                if slot and not slot.request.future.done():
                    slot.request.future.set_exception(exc)

    # -- host loop ------------------------------------------------------

    def _decoding(self) -> int:
        """Slots currently in the decode phase — THE definition of
        "active" every surface (gauge, stats, step accounting) shares."""
        return sum(
            s is not None and s.phase == "decode" for s in self._slots
        )

    @staticmethod
    def _group_key(slot: _Slot) -> int:
        """A slot's shared-prefix group identity for the adaptive
        controller (PR 15): its FIRST table page — panel mates mapping
        one registered header share it (the GroupTracker bucket key's
        first element), unique prompts each own theirs. Page-id
        recycling can alias groups across time; the acceptance EWMA is
        advisory, so staleness costs one wrong-k window, never
        correctness."""
        return int(slot.pages[0]) if slot.pages else -1

    def _bucket(self, n: int) -> int:
        return _next_bucket(n, self.config.seq_buckets)

    def _chunk_width(self, bucket: int) -> int:
        """Per-request prefill-chunk width: the largest divisor of the
        prompt bucket <= ``config.prefill_chunk`` (power-of-two buckets
        keep it at prefill_chunk). Dividing the bucket makes an
        UNSHARED chunked prefill cover exactly [0, bucket): the page
        footprint :meth:`_pages_needed` checks admission against."""
        chunk = min(self.config.prefill_chunk, bucket)
        while bucket % chunk:
            chunk -= 1
        return chunk

    def _pages_needed(self, req: _Request) -> int:
        """Table width in pages for an UNSHARED admission — the
        admit-ever feasibility bound (a request that only fits via
        sharing must not wait forever on an empty registry)."""
        bucket = self._bucket(len(req.prompt_ids))
        return self._table_pages(bucket, bucket, req)

    def _table_pages(self, bucket: int, prefill_end: int, req: _Request) -> int:
        # + depth * round_tokens - 1: a row finishing mid-window keeps
        # writing K/V until the window's last round, and under
        # pipelined dispatch its retirement lags up to depth - 1 MORE
        # already-enqueued programs (all those tokens are discarded on
        # host); its pages must absorb the full overshoot. Under
        # speculative decoding a round writes up to spec_k + 1 K/V
        # positions of which a rejected tail is rewound — the same
        # budget covers it (_round_tokens). depth 1, one round, spec off
        # reduces this to the classic + 0.
        # prefill_end: last position (+1) the chunked prefill may touch
        # — a shared-prefix start off the chunk grid can overhang the
        # bucket by up to chunk-1 positions of masked padding garbage.
        # Depth counts from the CONFIG, not the adaptive effective
        # depth (PR 15): a row admitted while the controller steered
        # depth low must stay budgeted when it steers back up —
        # exactly the live-flip rule _round_tokens already applies to
        # spec_k and decode_rounds.
        total = (
            max(bucket, prefill_end)
            + req.max_new_tokens
            + max(1, self.config.pipeline_depth) * self._round_tokens
            - 1
        )
        pg = self.config.page_size
        return -(-total // pg)

    def _admit(self) -> None:
        c = self.config
        while self._waiting:
            with self._lock:
                if not self._waiting:
                    return
                req = self._waiting[0]
                n_pages = self._pages_needed(req)
                # Largest shard-local pool that can EVER hold the
                # request: page 0 (the reserved NULL page) lives in
                # shard 0's range, so only the dp=1 pool loses it from
                # the max.
                per_shard = c.n_pages // self._dp
                fits_ever = min(
                    c.pages_per_seq,
                    per_shard - (1 if self._dp == 1 else 0),
                )
                if n_pages > fits_ever:
                    self._waiting.popleft()
                    req.future.set_exception(
                        ValueError(
                            f"request needs {n_pages} pages but the "
                            f"configuration caps a sequence at {fits_ever} "
                            f"(pages_per_seq={c.pages_per_seq}, usable "
                            f"per-shard pool="
                            f"{per_shard - (1 if self._dp == 1 else 0)})"
                        )
                    )
                    continue
                if not self._admit_chunked(req):
                    return  # no slot/pages; retry after retirements
                self._waiting.popleft()
                _M_WAITING.set(len(self._waiting))
            if self._pending_copy is not None:
                # The admission staged a CoW boundary copy: dispatch it
                # outside the lock (flush-then-copy; _flush_pipeline's
                # fetch bookkeeping takes the admission lock).
                self._boundary_copy_pending()

    # -- admission: chunked prefill + prefix sharing ---------------------

    def _admit_chunked(self, req: _Request) -> bool:
        """Claim a slot + pages for ``req`` and stage it as a prefilling
        slot (caller holds the lock). Returns False when nothing fits.

        Per candidate slot (= per data shard): match the prompt against
        the shard's prefix registry, size the table from the true chunk
        coverage, evict registry-only pages if the free list falls
        short, allocate, optionally copy the boundary page, and
        register this prompt's own full pages for successors.

        A block-diffusion prompt prefills its whole blocks alone (the
        tail rides the first block), so that is the prompt matched,
        copied and registered here: a page that will hold an unfinished
        block is never offered to successors.
        """
        c = self.config
        ids = req.prompt_ids
        ids = ids[: len(ids) - len(ids) % self._block]
        L = len(ids)
        pg = c.page_size
        bucket = self._bucket(L)
        chunk = self._chunk_width(bucket)
        if self.controller is not None:
            # Chunk steering (PR 15): the effective width for THIS
            # admission, from the menu {chunk, chunk/2} (chunk_for
            # guarantees the half still divides the bucket — the
            # unshared-footprint invariant — so at most ONE extra
            # compiled (chunk, bucket) trace per bucket can ever
            # exist: the no-recompile-storm bound).
            chunk = min(chunk, max(1, self.controller.chunk_for(bucket, chunk)))

        # One candidate slot per SHARD: every slot of a shard draws on
        # the same pool/registry, so retrying a failed plan on a
        # sibling slot would redo identical match/evict work for the
        # same answer.
        seen_shards: set[int] = set()
        for i in range(c.max_slots):
            if self._slots[i] is not None:
                continue
            shard = self._shard_of_slot[i]
            if shard in seen_shards:
                continue
            seen_shards.add(shard)
            pool = self._pools[shard]
            registry = self._registries[shard]
            # Plan A shares the registered prefix; plan B admits
            # unshared (exactly _pages_needed's footprint) when the shared
            # table would overhang the page budget — a prefix start off
            # the chunk grid pads the final chunk past the bucket, up
            # to chunk-1 positions.
            # Pages the registry matched for a recurrent model, kept
            # across the plans: plan B of a match no snapshot covers is
            # that admission's miss.
            matched_n = 0
            for use_share in (True, False) if c.share_prefix else (False,):
                match = None
                shared_pages: list[int] = []
                start0 = 0
                boundary = 0
                restore_plan: list = []
                state_node = None
                if use_share:
                    depth = None
                    if self._states is not None:
                        # A recurrent model continues from a page only
                        # where a snapshot holds the state after it.
                        depth, state_node, matched_n = self._state_depth(
                            registry, ids
                        )
                    # Boundary copies must beat recompute: a whole-page
                    # device copy for a trivial overlap (every prompt
                    # shares BOS) is pure overhead.
                    match = registry.match(
                        ids, min_boundary=max(2, c.page_size // 4),
                        depth=depth,
                    )
                    _M_PREFIX_LOOKUPS.inc()
                    shared_pages = match.pages
                    start0 = match.shared_tokens
                    if match.boundary_page is not None:
                        # A block's K/V depend on the whole block: a
                        # copy ends on a block boundary.
                        boundary = match.boundary_common
                        boundary -= boundary % self._block
                        if boundary < max(2, c.page_size // 4):
                            boundary = 0
                            match.boundary_page = None
                            match.boundary_common = 0
                    # Fall through registry-miss -> host-hit (PR 4):
                    # extend the matched chain through pages the
                    # offload tier still holds. Each hit is page_size
                    # prompt tokens promoted back by a device_put
                    # instead of recomputed; full-page restores
                    # supersede the partial boundary copy (their
                    # ranges would overlap).
                    if self._offload is not None:
                        k = start0 // pg
                        usable_full = (L - 1) // pg
                        if k < usable_full:
                            # One int conversion for the whole probe
                            # range; per-page keys are O(1) slices of
                            # it, not per-iteration re-tuplings.
                            chain = tuple(
                                int(t) for t in ids[: usable_full * pg]
                            )
                            keys = [
                                self._store_key(chain[: (j + 1) * pg])
                                for j in range(k, usable_full)
                            ]
                            # Route-driven prefetch hits first (PR 17):
                            # planes the prefetch loop already pulled
                            # store->host for this chain are consumed
                            # here without touching the store again.
                            restore_plan = self._prefetch_take(keys)
                            if len(restore_plan) < len(keys):
                                # One batched get_run for the rest —
                                # over the remote transport the whole
                                # restore plan is a single round trip
                                # (scatter-gather reply), not one RTT
                                # per page.
                                gr = getattr(self._offload, "get_run", None)
                                if gr is not None:
                                    restore_plan.extend(
                                        gr(keys[len(restore_plan):])
                                    )
                                else:
                                    for key in keys[len(restore_plan):]:
                                        planes = self._offload.get(key)
                                        if planes is None:
                                            break
                                        restore_plan.append(planes)
                        if restore_plan:
                            # Full-page restores supersede the partial
                            # boundary ON THE MATCH TOO: record_commit
                            # reads match.boundary_common, and the
                            # stats()/Prometheus hit counters must
                            # agree (PR 2 contract).
                            boundary = 0
                            match.boundary_page = None
                            match.boundary_common = 0
                    if not shared_pages and not boundary and not restore_plan:
                        continue  # registry miss: plan B is identical
                start = start0 + len(restore_plan) * pg + boundary
                end = start + -(-(L - start) // chunk) * chunk
                total = self._table_pages(bucket, end, req)
                need_new = total - len(shared_pages)
                # Infeasibility first: evicting cached prefixes to make
                # room for a plan the NEXT check rejects anyway would
                # self-destroy the registry this feature depends on.
                if total > c.pages_per_seq:
                    for p in shared_pages:
                        pool.release(p)
                    continue
                if pool.available < need_new:
                    registry.evict(need_new - pool.available)
                if pool.available < need_new:
                    # Give the refs back; plan B (or another slot's
                    # shard, or a later retirement) may fit.
                    for p in shared_pages:
                        pool.release(p)
                    continue
                own_state = 0
                if self._states is not None:
                    # The snapshot this admission starts from is shared
                    # BEFORE its own slot is found: that search may
                    # drop the least recently used idle snapshot.
                    if state_node is not None:
                        self._states.share(state_node.state)
                    own_state = registry.alloc_state()
                    if own_state is None:
                        if state_node is not None:
                            self._states.release(state_node.state)
                        for p in shared_pages:
                            pool.release(p)
                        continue
                    self._count_state_admission(
                        state_node, matched_n, len(shared_pages)
                    )
                if use_share:
                    registry.record_commit(match, copied=bool(boundary))
                    if shared_pages or boundary:
                        # record_commit's definition of a hit: a pure
                        # host-tier restore is counted by the offload
                        # families, not the registry's — the two
                        # surfaces must agree (PR 2 contract).
                        _M_PREFIX_HITS.inc()
                    _M_PREFIX_SHARED.inc(len(shared_pages))
                new_pages = pool.alloc(need_new)
                pages = shared_pages + new_pages
                table = np.full((c.pages_per_seq,), NULL_PAGE, np.int32)
                table[: len(pages)] = pages
                if boundary:
                    # Copy-on-write: the donor's boundary page extends
                    # our prefix mid-page; copy its content into our
                    # first private page and resume prefill after the
                    # common run. The device copy is STAGED here and
                    # dispatched by _admit's post-lock epilogue (after
                    # a pipeline flush — a stable-cache operation);
                    # this slot's first chunk cannot run before it
                    # (same worker thread, _prefill_step comes later).
                    _M_PREFIX_COPIED.inc()
                    self._pending_copy = (match.boundary_page, new_pages[0])
                # Offer our own full prompt pages to successors
                # (pending until our prefill writes past each page) —
                # unless sharing is off: a registry nobody consults
                # must not pin retired requests' pages either.
                reg_nodes = (
                    registry.register(ids, pages) if c.share_prefix else []
                )
                restore_nodes: list = []
                if restore_plan:
                    # Pages the host tier is about to repopulate:
                    # register() just created their nodes (the match
                    # walk stopped exactly where the tree thinned out),
                    # unready until the install lands. They leave
                    # reg_nodes — THIS prefill starts past them and
                    # never writes them — and gate both our own first
                    # chunk and any same-prefix burst-mate, exactly
                    # like an in-flight prefill.
                    restore_nodes = [
                        n for n, end_pos in reg_nodes if end_pos <= start
                    ]
                    reg_nodes = [
                        (n, e) for n, e in reg_nodes if e > start
                    ]
                    assert len(restore_nodes) == len(restore_plan)
                    for node, planes in zip(restore_nodes, restore_plan):
                        self._restores.append((node, planes, req.trace))
                padded = np.full((end,), self.tokenizer.pad_id, np.int32)
                padded[:L] = ids
                deps = restore_nodes + [
                    n
                    for n in (match.nodes if match else [])
                    if not n.ready
                ]
                chain: list = []
                if self._states is not None and c.share_prefix:
                    # Snapshots are wanted where a later prompt can
                    # continue from: the last full page (a later turn)
                    # and the last one an exact copy may map.
                    chain = registry.probe(ids, whole=True)[0]
                    for k in {(L - 1) // pg, L // pg}:
                        if 0 < k <= len(chain):
                            chain[k - 1].want_state = True
                self._slots[i] = _Slot(
                    request=req,
                    pages=pages,
                    generated=[],
                    prompt_len=L,
                    phase="prefill",
                    table=table,
                    next_pos=start,
                    chunk=chunk,
                    padded_ids=padded,
                    s_bucket=bucket,
                    deps=deps,
                    reg_nodes=reg_nodes,
                    pages_shared_n=len(shared_pages),
                    pages_restored_n=len(restore_plan),
                    state_slot=own_state,
                    state_src=state_node.state if state_node else 0,
                    state_dep=state_node,
                    chain=chain,
                    pages_matched_n=matched_n,
                    tail=req.prompt_ids[L:] if self._block > 1 else None,
                )
                _flight.flight_recorder().record(
                    "admit",
                    time.perf_counter(),
                    trace_id=_tracing.trace_id_of(req.trace),
                    id=req.rid,
                    slot=i,
                    prompt_tokens=L,
                    pages_shared=len(shared_pages),
                    pages_restored=len(restore_plan),
                    boundary_copy=bool(boundary),
                )
                return True
        return False

    # -- recurrent state beside the pages (PR 32) -------------------------

    def _state_depth(self, registry, ids):
        """How deep a recurrent model's admission may map ``ids``'
        registered pages: (pages, the node whose snapshot it starts
        from or None, pages the registry matched). The deepest matched
        node now WANTS a snapshot; it gets one promised at once if the
        prefill that registered its page has yet to finish it on a
        chunk end (the panel's mappers arrive while the donor is at
        page 0). Otherwise the deepest node that has one decides; none
        at all is a prefill from token 0 — slower, never different."""
        nodes, _ = registry.probe(ids)
        if nodes:
            last = nodes[-1]
            last.want_state = True
            if last.state is None and self._writer_reaches(last, len(nodes)):
                registry.promise_state(last)
        for k in range(len(nodes), 0, -1):
            if nodes[k - 1].state is not None:
                return k, nodes[k - 1], len(nodes)
        return 0, None, len(nodes)

    def _writer_reaches(self, node, k: int) -> bool:
        """Whether a prefilling sequence will end a chunk exactly at
        the end of page ``k`` (1-based), which ``node`` is and which it
        registered: only such a chunk's program can save the state."""
        end = k * self.config.page_size
        return any(
            s is not None and s.phase == "prefill" and s.next_pos < end
            and (end - s.next_pos) % s.chunk == 0
            and any(n is node for n, _ in s.reg_nodes)
            for s in self._slots
        )

    def _count_state_admission(self, state_node, matched: int, used: int):
        """One committed admission of a recurrent model: it restores a
        snapshot or not, and its page match ran deeper than the
        snapshot or not."""
        events = []
        if state_node is not None:
            events.append("restored")
        if matched > used:
            events.append("missed")
            self._remember_miss_depth(matched)
            lost = (matched - used) * self.config.page_size
            _M_PREFIX_RECOMPUTED.inc(lost)
            self._prefix_recomputed += lost
        self._state_event(*events)

    # Distinct page depths of recent snapshot misses that are kept.
    _MISS_DEPTHS_KEPT = 4

    def _remember_miss_depth(self, depth: int) -> None:
        """A match ended ``depth`` pages in on a node without a snapshot.

        Templated traffic branches at the same DEPTH on chain after
        chain, though each chain comes once: a panel's refine prompt
        leaves its question's evaluate chain 4-5 pages in, after that
        chain's prefill is over, so the rule "a node wants a snapshot
        once a match ended at it" always learns too late. Every prefill
        therefore also saves a snapshot as it passes a depth where a
        match lately missed (:meth:`_snapshot_slot`). The newest
        ``_MISS_DEPTHS_KEPT`` distinct depths are kept, so one that
        stops recurring is forgotten; the snapshots compete for slots
        LRU like any other. Worth 5% of a panel question and most of
        its run-to-run spread (PERF.md, Findings PR 32)."""
        kept = [d for d in self._miss_depths if d != depth]
        self._miss_depths = [*kept[-(self._MISS_DEPTHS_KEPT - 1):], depth]

    def _state_event(self, *events: str) -> None:
        """Count snapshot events, fold in what the registry evicted
        since, and refresh the slots gauge (caller holds the lock or is
        the worker thread; plain ints)."""
        evicted = sum(r.snapshots_evicted for r in self._registries)
        new = evicted - self._state_events["evicted"]
        for event in (*events, *(["evicted"] * new)):
            self._state_events[event] += 1
            _M_STATE_SNAPSHOTS.labels(event=event).inc()
        live = sum(s is not None for s in self._slots)
        for state, n in (
            ("live", live),
            ("snapshot", self._states.held - live),
            ("free", self._states.available),
        ):
            _M_STATE_SLOTS.labels(state=state).set(n)

    def _snapshot_slot(self, slot: _Slot, end: int) -> int:
        """The state slot the chunk of ``slot`` that ends at ``end``
        must copy its state into, or 0: a chunk that ends a full page
        of real tokens whose registry node wants a snapshot (or was
        promised one, or lies at a depth where matches lately missed:
        :meth:`_remember_miss_depth`) and has none written yet."""
        node = self._node_ending_at(slot, end)
        if node is None or node.state_ready:
            return 0
        depth = end // self.config.page_size
        if node.state is None and (
            node.want_state or depth in self._miss_depths
        ):
            self._registries[0].promise_state(node)
        return node.state or 0

    def _node_ending_at(self, slot: _Slot, end: int):
        """The registry node of the full page of REAL prompt tokens that
        ends at position ``end`` of ``slot``'s prompt, if ``end`` is
        such a page's end and the node is still in the tree."""
        pg = self.config.page_size
        k = end // pg
        if end > slot.prompt_len or end % pg or not 0 < k <= len(slot.chain):
            return None
        node = slot.chain[k - 1]
        return None if node.evicted else node

    def _boundary_copy_pending(self) -> None:
        """Dispatch the CoW boundary copy staged by :meth:`_admit_chunked`
        (outside the admission lock). Flushes the decode pipeline first:
        the copy is a stable-cache operation, and draining also settles
        retirement bookkeeping before the copy + first-chunk sequence
        occupies the device queue."""
        src, dst = self._pending_copy
        self._pending_copy = None
        self._flush_pipeline()
        _flight.flight_recorder().record(
            "cow_copy", time.perf_counter(), src=int(src), dst=int(dst)
        )
        self.cache = self._jit_copy_page(
            self.cache, jnp.int32(src), jnp.int32(dst)
        )
        if self.draft_cache is not None:
            # The draft pool shares the page geometry: its boundary
            # page carries the draft's K/V for the same tokens and
            # must CoW with the target's.
            self.draft_cache = self._jit_copy_page(
                self.draft_cache, jnp.int32(src), jnp.int32(dst)
            )

    def _flush_pipeline(self) -> None:
        """Drain every in-flight decode program (fetch + bookkeeping).

        The flush points are the operations that want a stable cache
        and settled host bookkeeping underneath them: host-tier page
        restores (install_page) and CoW boundary copies. Each drain of
        a non-empty pipeline counts once in
        ``gateway_pipeline_flushes_total`` — the price the pipeline
        pays to keep those paths simple. (Registry demotions read
        pages with ``device_get``, which already blocks on the
        dispatched stream and needs no flush.) Must be called WITHOUT
        the admission lock: fetch bookkeeping takes it.
        """
        if not self._inflight:
            return
        _M_PIPELINE_FLUSHES.inc()
        _flight.flight_recorder().record(
            "flush", time.perf_counter(), inflight=len(self._inflight)
        )
        with self._lock:
            self._pipeline_flushes += 1
        while self._inflight:
            self._fetch_one()
        self._drained_by = "flush"

    def _store_key(self, chain: tuple) -> tuple:
        """Host-tier key for a token chain: the batcher's store scope
        (config/weights identity — see __init__) prepended, so a
        fleet-shared store never cross-restores between heterogeneous
        replicas. Private stores pay the same prefix harmlessly."""
        return (self._store_scope, chain)

    def _spill_nodes(self, nodes) -> tuple[int, int]:
        """Spill the given registry nodes' pages to the host tier:
        ONE batched device_get covers every page the store doesn't
        already hold — a spill burst costs one host transfer, not N
        sequential round trips stalling the decode loop. Chains that
        round-tripped before skip the fetch entirely (recency refresh
        only; a refresh that LOSES the race with a concurrent LRU drop
        falls through to the fetch — the fleet-shared store's touch()
        says which happened). Returns (pages fetched+put, refreshed).

        The Prometheus families move by the STORE's own deltas, so a
        put() the budget refuses (oversize page) never counts as a
        demotion on either surface — and on a SHARED store the deltas
        are this call's own (computed around our puts; concurrent
        replicas' puts land in their own deltas).

        Worker thread only (both callers — the evict hook and the
        export step — run there): the device_get must not race a
        dispatch-time buffer donation.
        """
        store = self._offload
        keys = [
            self._store_key(PrefixRegistry.chain_tokens(node))
            for node in nodes
        ]
        refreshed = demoted = dropped = 0
        # Batched recency probe (PR 17): over the remote transport
        # touch_many is ONE round trip for the whole spill plan instead
        # of a serial RTT per chain. In-process stores answer the same
        # surface; a store without it falls back to the per-key loop.
        tm = getattr(store, "touch_many", None)
        touched = (
            tm(keys) if tm is not None else [store.touch(k) for k in keys]
        )
        fetch: list[tuple[tuple, int]] = []
        for key, node, hit in zip(keys, nodes, touched):
            if hit:
                refreshed += 1
                demoted += 1
            else:
                fetch.append((key, node.page))
        if fetch:
            pages = jnp.asarray([p for _, p in fetch], jnp.int32)
            planes_dev = [self.cache.k[:, pages], self.cache.v[:, pages]]
            if self.draft_cache is not None:
                # Demote the draft pool's planes for the same pages in
                # the SAME batched device_get: a restored prefix then
                # comes back with its draft context (PR 9) — the store
                # budget accounts all four planes' bytes.
                planes_dev += [
                    self.draft_cache.k[:, pages],
                    self.draft_cache.v[:, pages],
                ]
            got = jax.device_get(tuple(planes_dev))  # [L, n, page, Hkv, Dh]
            # Contiguous copies: a view into the batch buffer would
            # pin the whole [L, n, ...] fetch alive in the store.
            items = [
                (
                    key,
                    tuple(np.ascontiguousarray(pl[:, i]) for pl in got),
                )
                for i, (key, _) in enumerate(fetch)
            ]
            # One put_many per spill burst: remotely that's one frame
            # carrying every page's planes scatter-gathered (the v2
            # batched put), locally it loops put_counted under the hood.
            pm = getattr(store, "put_many", None)
            deltas = (
                pm(items)
                if pm is not None
                else [store.put_counted(k, p) for k, p in items]
            )
            for _, d, dr in deltas:
                demoted += d
                dropped += dr
        if demoted:
            _M_OFF_DEMOTED.inc(demoted)
        if dropped:
            _M_OFF_DROPPED.inc(dropped)
        _M_OFF_HOST_BYTES.set(store.bytes_used)
        return len(fetch), refreshed

    def _demote_nodes(self, nodes) -> None:
        """PrefixRegistry.on_evict hook: spill an evict() walk's ready
        victims to the host tier instead of losing them (worker thread,
        inside the admission lock — evictions happen at admission and
        in the fleet's preempt step, both worker-side)."""
        fetched, refreshed = self._spill_nodes(nodes)
        _flight.flight_recorder().record(
            "demote",
            time.perf_counter(),
            pages=fetched,
            refreshed=refreshed,
        )

    def _restore_step(self) -> bool:
        """Promote queued host-tier pages back into the device pool.

        The restore counterpart of :meth:`_prefill_step`: a bounded
        BATCH of ``device_put`` + installs runs between decode steps,
        so running slots pay a bounded stall — and each readiness flip
        releases every admission gated on that page (the admitting
        slot's first chunk, plus any same-prefix burst-mate that
        deduped against the in-flight restore). The batch size comes
        from :meth:`AdaptiveController.restore_batch` — the pipeline
        flush below is paid ONCE per call, so a host-bound loop drains
        more pages per flush while a saturated decode lane stays at
        the historical one page per iteration (the controller-less
        fallback). Returns True when at least one page was restored.
        """
        if not self._restores:
            return False
        batch = (
            self.controller.restore_batch()
            if self.controller is not None
            else 1
        )
        # Stable-cache operation: drain in-flight decode programs
        # before installing host content into pool pages (once for the
        # whole batch — the amortization restore_batch sizes).
        self._flush_pipeline()
        group: list = []
        while self._restores and len(group) < batch:
            group.append(self._restores.popleft())
        # Batched install (PR 17, the page_planes docstring's demote
        # symmetry): ONE stacked device_put + scatter covers the whole
        # group instead of a dispatch per page — restore bursts (a
        # handoff's chain, a promote-back after preemption) cost one
        # transfer the way a demote burst costs one device_get.
        t0 = time.perf_counter()
        pages = jnp.asarray([int(n.page) for n, _, _ in group], jnp.int32)
        self.cache = self._jit_install_pages(
            self.cache,
            pages,
            jnp.asarray(np.stack([p[0] for _, p, _ in group], axis=1)),
            jnp.asarray(np.stack([p[1] for _, p, _ in group], axis=1)),
        )
        draft_idx = [
            i for i, (_, p, _) in enumerate(group) if len(p) >= 4
        ]
        if self.draft_cache is not None and draft_idx:
            # Draft planes demoted alongside the target's (PR 9):
            # the restored prefix keeps its draft context, so
            # acceptance doesn't silently collapse after an
            # eviction round trip.
            self.draft_cache = self._jit_install_pages(
                self.draft_cache,
                pages[jnp.asarray(draft_idx, jnp.int32)],
                jnp.asarray(
                    np.stack([group[i][1][2] for i in draft_idx], axis=1)
                ),
                jnp.asarray(
                    np.stack([group[i][1][3] for i in draft_idx], axis=1)
                ),
            )
        # The install must COMPLETE before readers are released (same
        # contract as a prefill chunk's block). The histogram stays a
        # per-PAGE promotion latency: the batch's wall time amortizes
        # evenly over its pages (dur/n observed n times), keeping the
        # family's count == restored-pages lockstep with
        # offload_restored_total.
        with self._phase("device_wait"):
            jax.block_until_ready(self.cache.length)
        dur = time.perf_counter() - t0
        per = dur / len(group)
        for i, (node, _, trace) in enumerate(group):
            ti = t0 + i * per
            _M_RESTORE_SECONDS.observe(per)
            if trace is not None:
                trace.add_span("kv_restore", ti, per, page=int(node.page))
            _flight.flight_recorder().record(
                "restore",
                ti,
                per,
                trace_id=_tracing.trace_id_of(trace),
                page=int(node.page),
            )
            node.ready = True
            _M_OFF_RESTORED.inc()
            if self.controller is not None:
                self.controller.note_restore(self.host_page_bytes)
        with self._lock:
            self._offload_restored += len(group)
        return True

    def _count_program(
        self,
        kind: str,
        rows: int | None = None,
        rounds: int | None = None,
    ):
        """One device program dispatched by the scheduler loop: feed
        the Prometheus families, the stats() mirrors, AND the flight
        recorder from the same site (lockstep — the Chrome export's
        device track reconstructs exactly the programs this counted).
        ``rows``: ragged-row occupancy for fused/decode programs
        (decode rows + chunk lanes). ``rounds`` (PR 12): decode rounds
        this program folds — decode/fused pass their window (R under
        decode_rounds, else 1), spec
        passes 1 (the verify round IS the multi-token step), prefill/
        draft pass None (they advance no decode row) — feeding
        gateway_device_rounds_total + the per-program histogram and
        riding the PROGRAM flight event's meta so the Chrome export's
        device track stays count-exact at R > 1 (one slice still means
        one program, its ``rounds`` arg says how much decoding it
        held). Returns the flight event (None when recording is off)
        so pipelined callers can fill in the true device window in
        place once the fetch lands."""
        _M_DEVICE_PROGRAMS.labels(kind=kind).inc()
        with self._lock:
            self._programs[kind] += 1
            if rows is not None:
                self._ragged_rows_sum += rows
                self._ragged_rows_count += 1
            if rounds is not None:
                self._device_rounds += rounds
                self._decode_rounds_sum += rounds
                self._decode_rounds_count += 1
        if rows is not None:
            _M_RAGGED_ROWS.observe(rows)
        if rounds is not None:
            _M_DEVICE_ROUNDS.inc(rounds)
            _M_DECODE_ROUNDS.observe(rounds)
        meta = {"kind": kind}
        if rows is not None:
            meta["rows"] = rows
        if rounds is not None:
            meta["rounds"] = rounds
        if kind == "draft":
            # Draft mirror programs are dispatched async and never
            # individually fetched (their completion is implied by
            # stream order behind the carrying program) — their event
            # is a dispatch-stamp annotation, not a measured window.
            meta["untimed"] = 1
        return _flight.flight_recorder().record(
            "program", time.perf_counter(), meta=meta
        )

    def _program_cost(
        self,
        kind: str,
        rows_now: list,
        k: int,
        chunk_ext: list[tuple[int, int]] | tuple = (),
        streams: int = 0,
    ) -> dict:
        """Static HBM/FLOPs model for ONE dispatched program (PR 10).

        ``kv_read/write_tokens`` count the TARGET pool only and mirror
        what the program actually touches: a decode row at committed
        length L reads L + j positions at step j (k steps per
        program); a speculative verify row reads its pages ONCE for
        all k+1 queries (the ragged kernel folds each page one time —
        the reason a spec program's KV read equals a plain decode
        program's over the same rows) and writes k+1 positions of
        which a rejected tail is rewound (written traffic either way);
        a chunk lane (one ``(read_end, width)`` of ``chunk_ext`` a live
        lane) reads the pages covering [0, read_end) and writes its
        width. Group-
        shared prefix reads are deducted exactly as
        :meth:`_dispatch_tail` counts them saved — the two accountings
        cannot drift apart without a test noticing. The draft side of
        a spec program adds k+1 reads of the draft tree plus the
        streams' draft KV to hbm_bytes/flops only (the kv_*_tokens
        fields stay target-pool so the spec-on/off write-parity
        invariant is assertable).

        ``attn_pages_read`` (PR 29) is the same reads in the unit the
        ragged kernel walks: the pages it folds a layer, summed over
        the program's k kernel calls — each row's own pages under its
        fill, a group's shared run once (from the same
        ``saved_tokens_per_step``, which is whole pages), the chunk
        lane's up to its end. Like ``kv_read_tokens`` it does not
        deduct what a sliding window skips.

        A ``"denoise"`` program (block diffusion) is ``denoising_steps
        + 1`` forwards of each row's block of B at its committed length
        L — each reads the row's L + B positions, rewrites the block and
        reads the weights — with the chunk lanes in the first.
        ``attn_pairs`` counts the (query, key) pairs attention computes
        a layer under the model's rule, summed over the forwards.
        """
        pg = self.config.page_size
        kv_read = kv_write = tokens = pages = pairs = 0
        lengths = []
        passes = 1
        if kind == "denoise":
            b = self._block
            passes = self.cfg.denoising_steps + 1
            for _, s in rows_now:
                L = s.length
                kv_read += passes * (L + b)
                kv_write += passes * b
                tokens += passes * b
                pages += passes * -(-(L + b) // pg)
                pairs += passes * b * (L + b)
        else:
            for _, s in rows_now:
                L = s.prompt_len + len(s.generated)
                lengths.append(L)
                if kind == "spec":
                    kv_read += L + k
                    kv_write += k + 1
                    tokens += k + 1
                    pages += -(-(L + k) // pg)
                    pairs += (k + 1) * (L + 1) + k * (k + 1) // 2
                else:
                    kv_read += k * L + k * (k - 1) // 2
                    kv_write += k
                    tokens += k
                    pages += sum(-(-(L + j) // pg) for j in range(k))
                    pairs += k * (L + 1) + k * (k - 1) // 2
        if self._group_decode and rows_now:
            shared_steps = 1 if kind == "spec" else k * passes
            saved = self._groups.saved_tokens_per_step * shared_steps
            kv_read -= min(kv_read, saved)
            pages -= min(pages, saved // pg)
        for read_end, width in chunk_ext:
            kv_read += read_end
            kv_write += width
            tokens += width
            pages += -(-read_end // pg)
            # Queries at [end - width, end), each seeing through the
            # last slot of its block (its own under the causal rule).
            b = self._block
            n0, n1 = (read_end - width) // b, read_end // b
            pairs += b * b * (n1 * (n1 + 1) - n0 * (n0 + 1)) // 2
        cost = program_hbm_cost(
            self.cfg,
            weight_bytes=self._weight_bytes * passes,
            weight_params=self._weight_params,
            kv_token_bytes=self._kv_token_bytes,
            kv_read_tokens=kv_read,
            kv_write_tokens=kv_write,
            tokens=tokens,
        )
        if kind == "spec":
            mean_len = sum(lengths) // max(1, len(lengths))
            d_tokens = (k + 1) * max(1, streams)
            d = program_hbm_cost(
                self._draft_cfg,
                # The draft scan streams the draft tree once per step.
                weight_bytes=(k + 1) * self._draft_weight_bytes,
                weight_params=self._draft_weight_params,
                kv_token_bytes=self._draft_kv_token_bytes,
                kv_read_tokens=d_tokens * mean_len,
                kv_write_tokens=d_tokens,
                tokens=d_tokens,
            )
            cost["hbm_bytes"] += d["hbm_bytes"]
            cost["flops"] += d["flops"]
        cost["attn_pages_read"] = pages
        cost["attn_pairs"] = pairs
        return cost

    def _mbu_account(self, kind: str, cost: dict | None, dur: float) -> None:
        """Fold one fetched program's modeled cost + measured duration
        into the per-kind accumulators and — with a configured peak
        bandwidth — the gateway_program_mbu{kind} gauge. One site,
        two surfaces (stats mbu_* mirrors; lockstep tested)."""
        if self.controller is not None:
            # Roofline-position feed (PR 15): modeled weight fraction
            # + decode-MBU EWMAs come from the same (cost, dur) pairs
            # the gauge and stats sums fold.
            self.controller.note_program(kind, cost, dur)
        if cost is None:
            return
        pages = cost["attn_pages_read"]
        if self._states is not None:
            _M_SSM_TOKENS.labels(kind=kind).inc(cost["tokens"])
            self._ssm_tokens[kind] += cost["tokens"]
        _M_ATTN_TOKENS_READ.labels(kind=kind).inc(cost["kv_read_tokens"])
        _M_ATTN_PAGES_READ.labels(kind=kind).inc(pages)
        _M_ATTN_PAIRS.labels(kind=kind).inc(cost["attn_pairs"])
        with self._lock:
            m = self._mbu[kind]
            m["hbm_bytes"] += cost["hbm_bytes"]
            m["flops"] += cost["flops"]
            m["kv_read_tokens"] += cost["kv_read_tokens"]
            m["kv_write_tokens"] += cost["kv_write_tokens"]
            m["attn_pages_read"] += pages
            self._attn_pairs[kind] = (
                self._attn_pairs.get(kind, 0) + cost["attn_pairs"]
            )
            m["seconds"] += dur
            m["programs"] += 1
        peak = self.config.hbm_gbps * 1e9
        if peak > 0 and dur > 0:
            _M_PROGRAM_MBU.labels(kind=kind).set(
                cost["hbm_bytes"] / dur / peak
            )

    def _pick_prefill_slots(self) -> list[int]:
        """The ready prefilling slots whose next chunks share this
        iteration's program, up to :meth:`_lanes_for` of them — deps
        satisfied and chunks still to run (a slot whose FINAL chunk is
        already in flight under the fused path waits for its fetch-side
        activation), all of the first one's chunk width. Round-robin
        for fairness; advances the pointer past the last, so callers
        must run every returned slot's next chunk. Empty when nothing
        is ready.

        Readiness is read for all of them before any is dispatched: a
        slot whose deps another lane of the same program would only
        then write is not ready yet, and rides a later one."""
        n = self.config.max_slots
        picked: list[int] = []
        for off in range(n):
            i = (self._prefill_rr + off) % n
            s = self._slots[i]
            if (
                s is not None
                and s.phase == "prefill"
                and s.next_pos < s.prompt_len
                and all(node.ready for node in s.deps)
                and (s.state_dep is None or s.state_dep.state_ready)
                and (not picked or s.chunk == self._slots[picked[0]].chunk)
            ):
                picked.append(i)
                if len(picked) == self._lanes_for(s.chunk):
                    break
        if picked:
            self._prefill_rr = (picked[-1] + 1) % n
        return picked

    def _lane_args(self, idxs: list[int]) -> _LaneArgs:
        """The lane arguments of one chunk program carrying the next
        chunk of each slot of ``idxs``. One ready slot takes the
        one-lane program; more take the wide one, the lanes past
        ``idxs`` dead — an all-NULL table, as an idle slot's row."""
        c = self.config
        first = self._slots[idxs[0]]
        lanes = 1 if len(idxs) == 1 else self._lanes_for(first.chunk)
        ids = np.zeros((lanes, first.chunk), np.int32)
        tables = np.full((lanes, c.pages_per_seq), NULL_PAGE, np.int32)
        starts = np.zeros((lanes,), np.int32)
        lasts = np.zeros((lanes,), np.int32)
        done = np.zeros((lanes,), bool)
        seeds = np.zeros((lanes,), np.int32)
        temps = np.zeros((lanes,), np.float32)
        topks = np.zeros((lanes,), np.int32)
        topps = np.ones((lanes,), np.float32)
        filters = False
        state = np.zeros((lanes, 4), np.int32)
        ext = []
        for lane, idx in enumerate(idxs):
            slot = self._slots[idx]
            req = slot.request
            end = slot.next_pos + slot.chunk
            ids[lane] = slot.padded_ids[slot.next_pos : end]
            tables[lane] = slot.table
            starts[lane] = slot.next_pos
            lasts[lane] = slot.prompt_len - 1
            done[lane] = end >= slot.prompt_len
            seeds[lane], temps[lane] = req.seed, req.temperature
            topks[lane], topps[lane] = req.top_k, req.top_p
            if done[lane] and (req.top_k != 0 or req.top_p != 1.0):
                filters = True
            ext.append((end, slot.chunk))
            if self._states is not None:
                state[lane] = (
                    slot.state_src, slot.state_slot,
                    self._snapshot_slot(slot, end),
                    min(end, slot.prompt_len) - slot.next_pos,
                )
        return _LaneArgs(
            lanes, ids, tables, starts, lasts, done,
            (seeds, temps, topks, topps), filters, ext,
            {"chunk_state": state} if self._states is not None else {},
        )

    def _count_lanes(self, kind: str, live: int) -> None:
        """One chunk program of ``kind`` with ``live`` lanes filled."""
        _M_CHUNK_LANES.labels(kind=kind, lanes=str(live)).inc()
        with self._lock:
            key = (kind, live)
            self._chunk_lanes_n[key] = self._chunk_lanes_n.get(key, 0) + 1

    def _chunk_dispatched(self, slot: _Slot) -> None:
        """A chunk of ``slot`` is on the device stream: count its real
        tokens, flip the registry nodes it completes and move the slot
        on. The pages it covers are written by an ALREADY-DISPATCHED
        program, and every consumer is either a later program on the
        same stream (dependent chunks, decode reads) or a host
        operation that flushes the pipeline first (restore installs,
        CoW copies, demotion device_gets block on the stream)."""
        written_end = slot.next_pos + slot.chunk
        written_real = min(written_end, slot.prompt_len)
        _M_PREFILL_TOKENS.inc(written_real - slot.next_pos)
        for node, end_pos in slot.reg_nodes:
            if not node.ready and end_pos <= written_real:
                node.ready = True
        if self._states is not None:
            self._state_chunk_dispatched(slot, written_end)
        slot.next_pos = written_end

    def _state_chunk_dispatched(self, slot: _Slot, end: int) -> None:
        """The recurrent half of :meth:`_chunk_dispatched`: the chunk's
        program has read the slot it started from, so a snapshot's hold
        goes back and the next chunk starts from the sequence's own
        slot; and the snapshot that program saved (:meth:`_snapshot_slot`
        named it) is written as far as any later program can tell."""
        if slot.state_src != slot.state_slot:
            if slot.state_src:
                self._states.release(slot.state_src)
            slot.state_src = slot.state_slot
            slot.state_dep = None
        node = self._node_ending_at(slot, end)
        if node is not None and node.state is not None and not node.state_ready:
            node.state_ready = True
            with self._lock:
                self._state_event("saved")

    def _prefill_step(self, idxs: list[int]) -> bool:
        """Run the next prefill chunk of each slot of ``idxs`` as ONE
        STANDALONE device program (the pre-fusion path, and still the
        path when no decode batch exists to ride or ``ragged_attention``
        is off).

        The unit of decode stall under chunked prefill: between any two
        decode steps at most one of these runs, so admission latency
        costs running requests one bounded program, never a whole
        prompt.
        """
        slots = [self._slots[i] for i in idxs]
        head = slots[0]
        decoding = bool(self._decoding())
        if self._inflight:
            # Let in-flight decode work clear the device queue so the
            # stall histogram times ONLY this chunk. A device-order
            # wait, NOT a flush: the pending fetches stay pipelined
            # and cost ~nothing afterwards.
            with self._phase("device_wait"):
                jax.block_until_ready(self.cache.length)
        with self._phase("dispatch", kind="prefill"):
            if self._fused_ok and not self._decoding():
                self._build_fused_ahead(head.chunk, head.s_bucket)
            t0 = time.perf_counter()
            ev = self._count_program("prefill")
            self._count_lanes("prefill", len(idxs))
            la = self._lane_args(idxs)
            # A lane that ends its prompt has its first token sampled
            # in the program, from the last REAL position's hidden
            # state (a [D] gather + D x V unembed a lane — never a
            # [C, V] logits buffer per chunk).
            (first, chunk_logits), self.cache, *moe = self._chunk_fn(
                head.chunk, la.lanes, head.s_bucket
            )(
                self.params, la.ids, la.tables, la.starts, self.cache,
                la.lasts, la.done, la.sampler, la.filters, **la.state_kw,
            )
            if self.draft_cache is not None:
                for lane, slot in enumerate(slots):
                    self._draft_prefill_chunk(
                        slot, la.ids[lane], slot.next_pos
                    )
        # The device work above must COMPLETE before (a) the stall
        # histogram records it and (b) successors read the pages this
        # chunk wrote.
        with self._phase("device_wait"):
            jax.block_until_ready(self.cache.length)
            first_np = np.asarray(first) if la.done.any() else None
            moe_np = np.asarray(moe[0]) if moe else None
        if decoding:
            # Rows decode and the device has just run dry under them.
            self._drained_by = "standalone_chunk"
        # What the fetch does for a fused chunk: credit it, and on the
        # last one activate the row.
        with self._phase("retire"):
            dur = time.perf_counter() - t0
            if moe_np is not None:
                self._count_moe("prefill", moe_np)
            if ev is not None:
                # Standalone chunk programs are host-blocking: the
                # device window IS [t0, t0 + dur] — fill the flight
                # event now. Meta is REPLACED, not mutated: a concurrent
                # /debug/flight export may be iterating the old dict.
                ev.t0 = t0
                ev.dur = dur
                ev.meta = {
                    **ev.meta, "slot": idxs[0], "pos": head.next_pos,
                    "width": head.chunk, "lanes": len(idxs),
                }
            self._mbu_account(
                "prefill",
                self._program_cost("prefill", [], 0, chunk_ext=la.ext),
                dur,
            )
            for lane, (idx, slot) in enumerate(zip(idxs, slots)):
                # The program stalled the decode loop once: the lanes
                # past the first rode along (0, as a fused chunk's) —
                # count-lockstep with prefill_chunks.
                _M_PREFILL_STALL.observe(dur if lane == 0 else 0.0)
                trace = slot.request.trace
                if trace is not None:
                    trace.add_span(
                        "prefill_chunk", t0, dur,
                        pos=slot.next_pos, chunk=slot.chunk,
                    )
                self._chunk_dispatched(slot)
                with self._lock:
                    self._prefill_chunks += 1
                if not la.done[lane]:
                    continue
                self._prompt_ended(
                    idx, slot,
                    None if self._block > 1 else int(first_np[lane]),
                    chunk_logits[lane],
                )
            self._apply_row_patch()
            return True

    def _prompt_ended(
        self, idx: int, slot: _Slot, first: int | None, logits
    ) -> None:
        """A slot's last chunk has landed, with its first token sampled
        by the program that carried it (a standalone chunk or a fused
        step): make the row visible to the decode program — table, true
        length and state slot, in the target pool and the draft's (whose
        committed-minus-one invariant starts in sync with the target's),
        as one entry of the fetch's patch — and flip it to decoding.
        ``logits``: the device [V] row the token was sampled from,
        fetched for a ``"logits": n`` request alone. A block-diffusion
        prompt ends with no token (``first`` None): its row starts
        decoding with its first block."""
        req = slot.request
        if first is not None and req.logits_n:
            with self._phase("device_wait"):
                req.logit_rows.append(np.asarray(logits))
        self._row_install(idx, slot, slot.prompt_len)
        self._activate(idx, slot, first)

    def _row_install(
        self, idx: int, slot: _Slot, length: int, draft_only: bool = False
    ) -> None:
        """Enter row ``idx``'s install (``slot``'s table and state slot,
        ``length`` tokens) into the pending patch: for the target's pool
        and the draft's, or the draft's alone."""
        patch = self._patch()
        patch.ops[int(draft_only):, idx] = _ROW_INSTALL
        patch.tables[idx] = slot.table
        patch.lengths[idx] = length
        patch.states[idx] = slot.state_slot

    def _patch(self) -> _RowPatch:
        """The pending patch (pool 0 the target's, 1 the draft's)."""
        if self._row_patch is None:
            c = self.config
            self._row_patch = _RowPatch(
                1 + (self.draft_cache is not None), c.max_slots,
                c.pages_per_seq,
            )
        return self._row_patch

    def _apply_row_patch(self) -> None:
        """Apply what the retire phase gathered, as ONE program, where
        it gathered anything: behind the programs in flight and before
        the next dispatch, where the eager scatters it replaces ran, so
        nothing that takes ``self.cache`` sees an unpatched one."""
        patch, self._row_patch = self._row_patch, None
        if patch is None:
            return
        pools = (self.cache,) + (
            (self.draft_cache,) if self.draft_cache is not None else ()
        )
        rows, *draft = self._jit_apply_rows(
            tuple(_rows_of(pool) for pool in pools),
            patch.ops, patch.tables, patch.lengths, patch.states,
        )
        self.cache = _with_rows(self.cache, rows)
        if draft:
            self.draft_cache = _with_rows(self.draft_cache, draft[0])

    def _activate(self, idx: int, slot: _Slot, first: int | None) -> None:
        """Flip a slot to decoding with its first sampled token — or,
        for a block-diffusion model (``first`` None), with its first
        block still to denoise."""
        req = slot.request
        if first is not None:
            slot.generated.append(first)
            _M_GENERATED.inc()
        slot.phase = "decode"
        slot.deps = []
        # First generated token: the request's TTFT anchor (batcher
        # side — submit to first token; the gateway's
        # gateway_ttft_seconds keeps its arrival-to-first-byte view)
        # and the origin of the inter-token-gap timeline.
        now = time.perf_counter()
        if first is not None:
            slot.t_first = now
        slot.t_last_tok = now
        if self._group_decode or self.draft_cache is not None:
            # The row's prompt-prefix page run (full pages only — the
            # boundary page takes decode writes and must stay suffix).
            # Same page ids across rows == same tokens (sharing happens
            # only through the registry), so the tracker groups rows by
            # common run prefix: the panel's donor AND its mappers.
            # With a draft configured the tracker ALSO runs on
            # non-Pallas backends: its first-page buckets are the
            # shared-draft-stream candidate sets (the grouped KERNEL
            # still engages only under _group_decode — arrays() is
            # consulted only there).
            self._groups.add(
                idx, slot.pages[: slot.prompt_len // self.config.page_size]
            )
            _flight.flight_recorder().record(
                "group",
                now,
                trace_id=_tracing.trace_id_of(req.trace),
                slot=idx,
                largest=self._groups.largest_group,
            )
        with self._lock:
            _M_ACTIVE.set(self._decoding())
            if first is None:
                slot.length = slot.prompt_len
                return
            self._ttft_sum += now - req.t_submit
            self._ttft_count += 1
        self._last_tokens[idx] = first
        # The next dispatch must feed THIS row from the host mirror:
        # its first token came from prefill logits, not from the
        # in-flight program's output row (which is stale or garbage
        # for a freshly (re)activated slot).
        self._tok_dirty[idx] = True
        self._seeds[idx] = req.seed
        self._counts[idx] = 1  # token 0 sampled from prefill
        self._topks[idx] = req.top_k
        self._topps[idx] = req.top_p
        if (
            first == self.tokenizer.eos_id
            or req.max_new_tokens <= 1
            or self._hit_stop(slot)
        ):
            self._retire(idx)

    def _decoded_text(self, slot: _Slot) -> str:
        ids = [t for t in slot.generated if t != self.tokenizer.eos_id]
        return self.tokenizer.decode(ids)

    def _hit_stop(self, slot: _Slot) -> bool:
        """True when any stop sequence appears in the decoded text so
        far. Host-checked after EVERY sampled token — multi-token stops
        terminate immediately, with no overshoot to EOS/length (the
        engine's batch path can only device-stop single-token stops).

        Window sizing, visible-token filtering, and the full-decode
        confirm on candidate hits all live in
        :meth:`utils.stops.VisibleIdFilter.confirmed_stop_hit` — the
        one copy the engine's ``_chunked_stop_decode`` shares, so the
        two retiring surfaces cannot drift.
        """
        return self._vis_filter.confirmed_stop_hit(
            slot.generated,
            slot.request.stop,
            slot.request.stop_window,
            lambda: self._decoded_text(slot),
        )

    def _request_summary(self, slot: _Slot) -> dict:
        """The per-request serving timeline (PR 10): TTFT, inter-token
        gap percentiles, speculation tallies, and header-page
        provenance. Retained in the process RequestLog (served at
        ``GET /debug/requests``) and attached to the ServeResult so the
        gateway can surface it as response meta."""
        req = slot.request
        end = time.perf_counter()
        gaps = slot.gaps
        return {
            "id": req.rid,
            "trace_id": _tracing.trace_id_of(req.trace),
            "prompt_tokens": len(req.prompt_ids),
            "new_tokens": len(slot.generated),
            "ttft_s": (
                slot.t_first - req.t_submit
                if slot.t_first is not None
                else None
            ),
            "duration_s": end - req.t_submit,
            "tbt_p50_s": _flight.percentile(gaps, 50),
            "tbt_p99_s": _flight.percentile(gaps, 99),
            "tbt_max_s": max(gaps) if gaps else 0.0,
            "tbt_count": len(gaps),
            "spec_rounds": slot.spec_rounds,
            "spec_accepted_tokens": slot.spec_accepted_toks,
            "spec_accepted_per_round": (
                slot.spec_accepted_toks / slot.spec_rounds
                if slot.spec_rounds
                else 0.0
            ),
            "header_pages_shared": slot.pages_shared_n,
            # What the registry matched; a recurrent model could use
            # ``header_pages_shared`` of them (a snapshot's depth).
            "header_pages_matched": max(
                slot.pages_matched_n, slot.pages_shared_n
            ),
            "header_pages_restored": slot.pages_restored_n,
            "finished_at": time.time(),
        }

    def _retire(self, idx: int) -> None:
        slot = self._slots[idx]
        assert slot is not None
        # Groups shrink incrementally as members retire; a group left
        # with one member stops emitting (its row falls back to the
        # plain per-row walk — nothing left to dedup).
        self._groups.remove(idx)
        self._stream_src_prev.pop(idx, None)
        self._patch().ops[:, idx] = _ROW_RELEASE
        pool = self._pools[self._shard_of_slot[idx]]
        with self._lock:
            # Refcounted release: private pages return to the free
            # list; prefix-shared pages stay resident for their other
            # readers (and the registry's own hold keeps a retired
            # donor's prefix warm for future admissions).
            for p in slot.pages:
                pool.release(p)
            self._slots[idx] = None
            if self._states is not None:
                self._states.release(slot.state_slot)
                self._state_event()
            self._completed += 1
            self._generated_tokens += len(slot.generated)
            _M_ACTIVE.set(self._decoding())
        _M_COMPLETED.inc()
        _M_TOKENS.inc(len(slot.generated))
        text = self._decoded_text(slot)
        # Engine stop contract: trim at the earliest occurrence of any
        # stop, removing the stop itself. num_tokens keeps the honest
        # decoded count (here at most the stop's own tokens past the cut).
        cut = earliest_stop_cut(text, slot.request.stop)
        if cut >= 0:
            text = text[:cut]
        summary = self._request_summary(slot)
        _flight.request_log().add(summary)
        # The Chrome export's per-request track: one slice spanning
        # submit to retirement, joined to /debug/traces by trace id.
        _flight.flight_recorder().record(
            "request",
            slot.request.t_submit,
            summary["duration_s"],
            trace_id=summary.get("trace_id"),
            id=summary["id"],
            tokens=len(slot.generated),
        )
        if not slot.request.future.done():
            slot.request.future.set_result(
                ServeResult(
                    text=text,
                    num_tokens=len(slot.generated),
                    timing=summary,
                    logits=(
                        np.concatenate([
                            np.atleast_2d(r) for r in
                            slot.request.logit_rows[: slot.request.logits_n]
                        ])
                        if slot.request.logit_rows
                        else None
                    ),
                    logit_ids=(
                        slot.request.logit_ids[: slot.request.logits_n]
                        or None
                    ),
                )
            )

    def _stop_plan(
        self, rows_now: list, R: int
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Per-row device stop data for ONE multi-round dispatch
        (PR 12): emit budgets (each row's remaining max-new-tokens in
        the HOST mirror — exact at depth 1, an over-allowance under
        retirement lag, where the fetch's host trim discards the
        overshoot exactly as it always has), the -1-padded
        [max_slots, _SCREEN_W] stop-candidate screen, and the window's
        effective round count: R, or 1 when any decoding row's stop
        sequences admit no bounded screen — those stops need the
        host's byte-level look at every token, so the window collapses
        to the pre-PR-12 cadence until the row retires (stop sequences
        BOUND R; they never break text parity either way)."""
        c = self.config
        budgets = np.full((c.max_slots,), R, np.int32)
        screen = np.full((c.max_slots, _SCREEN_W), -1, np.int32)
        r_eff = R
        for i, s in rows_now:
            budgets[i] = max(
                1, s.request.max_new_tokens - len(s.generated)
            )
            scr = s.request.stop_screen
            if scr is None:
                r_eff = 1
            elif scr:
                screen[i, : len(scr)] = scr
        return budgets, screen, r_eff

    def _counts_device_arg(self, dirty_np, rows):
        """Device-resident PRNG-count input for a data-dependent
        dispatch (spec or multi-round): the previous program's
        ``counts_out`` with (re)activated rows patched from the host
        mirror exactly like their input token, or the mirror itself
        over an empty window. ONE copy for both branches — this is
        race-sensitive bookkeeping (the snapshot rule of ``rows()``),
        and the two callers drifting is how the PR-8 class of bug
        comes back."""
        if self._inflight:
            counts_dev = self._inflight[-1].counts_out
            if dirty_np.any():
                counts_dev = jnp.where(
                    jnp.asarray(dirty_np),
                    jnp.asarray(np.array(self._counts)),
                    counts_dev,
                )
            return counts_dev
        return rows(self._counts)

    def _note_dispatch_gap(self, t0: float) -> None:
        """A program is about to be enqueued at ``t0``: observe the
        un-overlapped host time before it and, where rows decode on a
        device that holds nothing, the drain that left it so."""
        # Un-overlapped host time: the gap since the pipeline drained
        # (retirement, admission, prefill chunks, group rebuilds that
        # no in-flight program hid). A dispatch issued with a program
        # still in flight spent its host time in that program's shadow
        # and observes 0, keeping depth-1 and depth-2 distributions
        # count-comparable; idle waits reset _last_step_end and never
        # count.
        overhead = None
        if self._last_step_end is not None:
            overhead = t0 - self._last_step_end
        elif self._inflight:
            overhead = 0.0
        if self._last_step_end is not None or (
            self._drained_by == "standalone_chunk"
        ):
            # Rows were decoding and the device holds nothing: this
            # program is one it had to wait for.
            after = self._drained_by or "other"
            _M_PIPELINE_DRAINS.labels(after=after).inc()
            with self._lock:
                self._pipeline_drains[after] += 1
        self._drained_by = None
        if overhead is not None:
            _M_SCHED_OVERHEAD.observe(overhead)
            if self.controller is not None:
                # Chunk/depth steering signal: the same
                # un-overlapped observation the histogram gets.
                self.controller.note_overhead(overhead)
            with self._lock:
                self._sched_overhead_sum += overhead
                self._sched_overhead_count += 1
            if overhead > 0 and self._last_step_end is not None:
                # The Chrome export's host track: un-overlapped
                # scheduler work between the pipeline draining and this
                # dispatch (overlapped dispatches observe 0 and emit
                # nothing — the track shows exactly the time the device
                # sat idle waiting on the host).
                _flight.flight_recorder().record(
                    "host", self._last_step_end, overhead
                )
        self._last_step_end = None

    def _dispatch(
        self,
        chunk_idxs: list[int] | None = None,
        spec: bool = False,
        rounds: int = 1,
        rounds_choice: bool = False,
    ) -> None:
        """Enqueue ONE decode program for the current decode batch.

        In pipelined mode (``pipeline_depth > 1``) this runs BEFORE the
        previous program's tokens reach the host: the input token row is
        the device-resident final-token output of the previous dispatch
        (no host->device round trip on the input side; the cache already
        flows through ``donate_argnums``), so the host's fetch and
        bookkeeping for program *n* overlap program *n+1*'s device
        execution. Rows (re)activated since the previous dispatch are
        patched in from the host mirror (``_tok_dirty``).

        ``chunk_idxs`` (PR 8; a list since PR 31): ready prefilling
        slots whose next chunks ride THIS program (the fused scheduler
        step), a lane each, instead of running standalone. A chunk's
        device work is ordered on the stream at dispatch — its registry
        nodes flip ready HERE, since every consumer is a later program
        on the same stream or a flush-first host operation — while its
        host bookkeeping (activation with the first token the program
        sampled, its row's entry in the fetch's patch) happens at the
        fetch, inside the pipeline's overlap window.

        ``spec`` (PR 9): dispatch the speculative draft/verify program
        instead — one device program whose per-row token yield is
        data-dependent (accepted drafts + 1). It rides the SAME
        pipeline: the emit buffer is the fetch target, the last
        emitted token the next dispatch's input, and the PRNG counts
        thread device-resident program-to-program (the host mirror
        syncs at fetch). Mutually exclusive with ``chunk_idxs`` —
        chunks run standalone while speculation is engaged.

        ``rounds`` (PR 12): the multi-round engage state from _run's
        once-per-iteration read (1 = the one-step program; _run passes
        1 whenever ``spec`` is set). > 1 dispatches the R-round masked
        program — :meth:`_rounds_sample`, or the fused step's
        multi-round tail when a chunk rides — with the same
        device-resident count threading as a spec round; the
        per-dispatch effective window may still collapse to 1
        (:meth:`_stop_plan`) without leaving the rounds counts-mode.

        ``rounds_choice`` (PR 15): this dispatch's ``rounds`` was the
        adaptive controller's FREE regime choice (not a near-stop
        force) — such windows are evidence for the two-arm rate
        arbitration. An adaptive arm-1 window is a PLAIN one-step
        dispatch (``rounds == 1``): the masked 1-round program would
        pay the masking machinery + an extra emit-count host fetch
        the plain program doesn't, and the whole point of the arm is
        to measure what single-round dispatch really costs — the
        mode-flush rules above already drain the pipeline on the
        counts-mode change.
        """
        c = self.config
        k = 1  # decode rounds this program holds
        temps = np.zeros((c.max_slots,), np.float32)
        rows_now: list[tuple[int, _Slot]] = []
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.phase == "decode":
                temps[i] = slot.request.temperature
                rows_now.append((i, slot))
        # Static (two cached programs): over the decode rows and the
        # chunk lanes that end their prompts in this program.
        filters_active = any(
            s.request.top_k != 0 or s.request.top_p != 1.0
            for _, s in rows_now
        )
        if chunk_idxs:
            la = self._lane_args(chunk_idxs)
            filters_active = filters_active or la.filters

        def rows(x):
            # SNAPSHOT (np.array copies) before device_put: jax's CPU
            # runtime zero-copies suitably-aligned numpy buffers, so
            # handing it the live mutable array lets the post-dispatch
            # host mutations (the += k counter advance below, fetch-time
            # _last_tokens updates) race the async program's read —
            # observed as a dispatched program folding count+k into the
            # PRNG and re-sampling an already-emitted index. Alignment
            # made the old code's luck allocation-dependent.
            arr = jnp.asarray(np.array(x))
            if self._row_sharding is not None:
                arr = jax.device_put(arr, self._row_sharding)
            return arr

        groups = self._groups.arrays() if self._group_decode else None
        t0 = time.perf_counter()
        self._note_dispatch_gap(t0)
        # Snapshot rule as rows(): _tok_dirty is reset and _last_tokens
        # mutated right after this dispatch; the spec branch reuses the
        # same snapshot for its counts patch.
        dirty_np = np.array(self._tok_dirty)
        if self._inflight:
            tokens = self._inflight[-1].next_input
            if dirty_np.any():
                tokens = jnp.where(
                    jnp.asarray(dirty_np),
                    jnp.asarray(np.array(self._last_tokens)),
                    tokens,
                )
        else:
            tokens = rows(self._last_tokens)
        self._tok_dirty[:] = False
        if spec:
            # Effective spec window (PR 15): the controller shrinks k
            # within [1, spec_k] from per-group measured acceptance —
            # menu {1, spec_k}, so the _jit_spec trace family stays
            # two entries. Everything downstream (stream plan, cost
            # model, drafted counter, the _Inflight record the fetch's
            # acceptance accounting divides by) uses THIS k.
            k_spec = c.spec_k
            if self.controller is not None:
                k_spec = max(
                    1,
                    min(
                        c.spec_k,
                        self.controller.spec_k_for(
                            [self._group_key(s) for _, s in rows_now],
                            c.spec_k,
                        ),
                    ),
                )
            # Device-resident PRNG counts: the previous spec program's
            # counts_out (data-dependent — the host can't advance them
            # at dispatch), with (re)activated rows patched from the
            # host mirror exactly like their input token. A mode flip
            # drains the pipeline first (_run), so a spec window only
            # ever chains spec outputs.
            counts_dev = self._counts_device_arg(dirty_np, rows)
            src, fill, off, streams, shared = self._spec_stream_plan(
                rows_now, k_spec
            )
            # Flight events for stream-plan CHANGES only (the plan
            # itself re-runs every round): a mate picking up a new
            # donor, or falling back to drafting for itself (diverge).
            for i, _ in rows_now:
                cur = int(src[i])
                prev = self._stream_src_prev.get(i)
                if prev is not None and prev != cur:
                    _flight.flight_recorder().record(
                        "stream_donor",
                        t0,
                        slot=i,
                        donor=cur,
                        prev=prev,
                        diverged=cur == i,
                    )
                self._stream_src_prev[i] = cur
            emit, emit_cnt, self.cache, self.draft_cache, next_in, cnt_out = (
                self._jit_spec(
                    k_spec,
                    self.params,
                    self._draft_params,
                    self.cache,
                    self.draft_cache,
                    tokens,
                    rows(self._seeds),
                    counts_dev,
                    rows(temps),
                    rows(self._topks),
                    rows(self._topps),
                    filters_active,
                    all(
                        s.request.temperature <= 0.0 for _, s in rows_now
                    ),
                    groups,
                    rows(src),
                    rows(fill),
                    rows(off),
                )
            )
            # rounds=1: the verify round IS the multi-token step — one
            # decode-advancing round per spec program (the
            # device-rounds algebra the decode_rounds leg gates on).
            ev = self._count_program("spec", rows=len(rows_now), rounds=1)
            cost = self._program_cost(
                "spec", rows_now, k_spec, streams=streams
            )
            drafted = k_spec * streams
            _M_SPEC_DRAFTED.inc(drafted)
            with self._lock:
                self._spec_drafted += drafted
                self._spec_shared_rows += shared
            # Host counts do NOT advance here (the plain path's += k):
            # the yield is data-dependent; _fetch_one syncs the mirror.
            rec = _Inflight(
                tokens=emit,
                next_input=next_in,
                t0=t0,
                k=1,
                rows=rows_now,
                spec=True,
                spec_k=k_spec,
                emit_cnt=emit_cnt,
                counts_out=cnt_out,
                flight=ev,
                cost=cost,
            )
            return self._dispatch_tail(rec, groups, k)
        # Multi-round window (PR 12): like the spec branch, the yield
        # is data-dependent once rows can freeze mid-window, so PRNG
        # counts thread device-resident program-to-program (host
        # mirror syncs at fetch), with (re)activated rows patched from
        # the mirror exactly like their input token. A window only
        # ever chains programs of one mode (_run drains on change) —
        # ``rounds`` is threaded from _run's one read of the engage
        # state per iteration, exactly like ``spec``, so a live
        # config flip between the mode check and this dispatch cannot
        # split the two decisions.
        R = rounds
        rounds_now = 0
        counts_arg = None
        budgets_dev = screen_dev = None
        emit_cnt = cnt_out = None
        if self.controller is not None and self._draft_cfg is not None:
            # Probe clock for a disengaged spec controller: plain
            # windows counted at the dispatch site (idle loop
            # iterations must not advance it).
            self.controller.note_plain_window()
        rounds_clean = rounds_choice and rounds == 1
        if R > 1:
            counts_arg = self._counts_device_arg(dirty_np, rows)
            budgets_np, screen_np, rounds_now = self._stop_plan(rows_now, R)
            if rounds_choice:
                # Chosen full window (PR 15): clean unless the stop
                # plan collapsed it (an unscreenable stop is forced,
                # not evidence about the window arms). The regime
                # choice itself happened in _run, at the same
                # once-per-iteration altitude as the engage state.
                rounds_clean = rounds_now == R
            budgets_dev = jnp.asarray(budgets_np)
            screen_dev = jnp.asarray(screen_np)
            k = rounds_now
        else:
            counts_arg = rows(self._counts)
        args = (
            self.params,
            self.cache,
            tokens,
            rows(self._seeds),
            counts_arg,
            rows(temps),
            rows(self._topks),
            rows(self._topps),
            filters_active,
            groups,
        )
        if self._plain_shapes is None and self._fused_ok and not rounds_now:
            self._plain_shapes = jax.tree.map(_abstract, args[:9])
        chunk_recs: list[_InflightChunk] = []
        chunk_first = None
        if not chunk_idxs:
            if rounds_now:
                # Same prepared device args as the one-step program
                # (args[9] is groups — _rounds_sample takes it after
                # the stop data).
                (
                    next_tok, _, self.cache, next_in, cnt_out, emit_cnt,
                    aux,
                ) = self._jit_rounds(
                    rounds_now, *args[:9], budgets_dev, screen_dev, args[9],
                )
            else:
                next_tok, _, self.cache, next_in, aux = self._jit_decode(
                    *args
                )
            ev = self._count_program(
                "decode", rows=len(rows_now), rounds=k
            )
            cost = self._program_cost("decode", rows_now, k)
        else:
            head = self._slots[chunk_idxs[0]]
            out = self._fused_fn(head.chunk, la.lanes, head.s_bucket)(
                *args, *la.program_args,
                *(
                    (rounds_now, budgets_dev, screen_dev)
                    if rounds_now
                    else ()
                ),
                **la.state_kw,
            )
            if rounds_now:
                (
                    next_tok, _, self.cache, next_in, chunk_out,
                    emit_cnt, cnt_out, aux,
                ) = out
            else:
                next_tok, _, self.cache, next_in, chunk_out, aux = out
            chunk_first, chunk_logits = chunk_out
            ev = self._count_program(
                "fused", rows=len(rows_now) + len(chunk_idxs), rounds=k
            )
            self._count_lanes("fused", len(chunk_idxs))
            cost = self._program_cost("fused", rows_now, k, chunk_ext=la.ext)
            for lane, idx in enumerate(chunk_idxs):
                slot = self._slots[idx]
                _flight.flight_recorder().record(
                    "chunk",
                    t0,
                    trace_id=_tracing.trace_id_of(slot.request.trace),
                    slot=idx,
                    pos=slot.next_pos,
                    width=slot.chunk,
                    fused=1,
                )
                if self.draft_cache is not None:
                    # The draft's mirror of the riding chunk — its own
                    # small program right behind the fused dispatch (the
                    # two touch disjoint pools; stream order is
                    # irrelevant between them, only their fetch/flush
                    # consumers care).
                    self._draft_prefill_chunk(
                        slot, la.ids[lane], slot.next_pos
                    )
                chunk_recs.append(
                    _InflightChunk(
                        idx=idx,
                        slot=slot,
                        done=bool(la.done[lane]),
                        lane=lane,
                        logits=chunk_logits[lane],
                        pos=slot.next_pos,
                        width=slot.chunk,
                    )
                )
                self._chunk_dispatched(slot)
        # Host counters track the DEVICE stream at dispatch: the
        # program advances every participating row by k regardless of
        # what the fetch later keeps, so a surviving row's next
        # dispatch folds the right PRNG indices. With a draft
        # configured, a plain program also widens the row's draft lag
        # (the mirror never saw these tokens — _spec_catch_up replays
        # them when speculation re-engages). A MULTI-ROUND program's
        # advance is data-dependent (frozen rows stop folding), so
        # both mirrors sync at fetch instead — the spec discipline.
        if not rounds_now:
            for i, s in rows_now:
                self._counts[i] += k
                if self.draft_cache is not None:
                    s.draft_lag += k
        rec = _Inflight(
            tokens=next_tok, next_input=next_in, t0=t0, k=k,
            rows=rows_now, chunks=chunk_recs, chunk_first=chunk_first,
            rounds=rounds_now,
            rounds_clean=rounds_clean,
            emit_cnt=emit_cnt, counts_out=cnt_out, flight=ev, cost=cost,
            aux=aux,
        )
        self._dispatch_tail(rec, groups, k)

    def _block_rows(self) -> list[tuple[int, _Slot]]:
        """Decoding rows of a block-diffusion model that need another
        block: those whose blocks in flight do not already cover their
        ``max_new_tokens`` (an EOS still ends one early)."""
        return [
            (i, s) for i, s in enumerate(self._slots)
            if s is not None and s.phase == "decode"
            and s.planned < s.request.max_new_tokens
        ]

    def _dispatchable(self) -> int:
        """Rows the next step program would advance: every decoding
        row, or for block diffusion :meth:`_block_rows`."""
        if self._block == 1:
            return self._decoding()
        return len(self._block_rows())

    def _dispatch_denoise(self, chunk_idxs: list[int] | None = None) -> None:
        """Enqueue ONE block-diffusion program (:meth:`_denoise_sample`)
        for the rows that need a block, with the ready chunks of
        ``chunk_idxs`` riding its first forward, a lane each, as the
        fused step takes them. Its input is host data alone — a row's
        block starts as its prompt's tail, once, then as masks — so a
        program n+1 enqueued before program n's fetch needs nothing of
        n but the cache it donated."""
        c = self.config
        b = self._block
        rows_now = self._block_rows()
        block = np.full((c.max_slots, b), self.cfg.mask_token_id, np.int32)
        live = np.zeros((c.max_slots,), bool)
        for i, s in rows_now:
            live[i] = True
            block[i, : len(s.tail)] = s.tail
        groups = self._groups.arrays() if self._group_decode else None
        t0 = time.perf_counter()
        self._note_dispatch_gap(t0)
        args = (self.params, self.cache, jnp.asarray(block), jnp.asarray(live))
        if self._plain_shapes is None:
            self._plain_shapes = jax.tree.map(_abstract, args)
        chunk_recs: list[_InflightChunk] = []
        if chunk_idxs:
            la = self._lane_args(chunk_idxs)
            head = self._slots[chunk_idxs[0]]
            new, emit, self.cache, aux = self._denoise_fn(
                head.chunk, la.lanes
            )(*args, groups, la.ids, la.tables, la.starts)
            kind = "fused"
            self._count_lanes(kind, len(chunk_idxs))
            for lane, idx in enumerate(chunk_idxs):
                slot = self._slots[idx]
                _flight.flight_recorder().record(
                    "chunk", t0,
                    trace_id=_tracing.trace_id_of(slot.request.trace),
                    slot=idx, pos=slot.next_pos, width=slot.chunk, fused=1,
                )
                chunk_recs.append(_InflightChunk(
                    idx=idx, slot=slot, done=bool(la.done[lane]), lane=lane,
                    logits=None, pos=slot.next_pos, width=slot.chunk,
                ))
                self._chunk_dispatched(slot)
        else:
            la = None
            new, emit, self.cache, aux = self._denoise_fn(0, 0)(*args, groups)
            kind = "denoise"
        ev = self._count_program(
            kind, rows=len(rows_now) + len(chunk_recs), rounds=1
        )
        cost = self._program_cost(
            "denoise", rows_now, 1, chunk_ext=la.ext if la else ()
        )
        for _, s in rows_now:
            s.length += b
            s.planned += b - len(s.tail)
            s.tail = s.tail[:0]
        rec = _Inflight(
            tokens=new, next_input=None, t0=t0, k=1, rows=rows_now,
            chunks=chunk_recs, emit_cnt=emit, flight=ev, cost=cost, aux=aux,
            denoise=True,
        )
        self._dispatch_tail(rec, groups, self.cfg.denoising_steps + 1)

    def _dispatch_tail(self, rec: "_Inflight", groups, k: int) -> None:
        """Enqueue the dispatched program and account the window —
        shared by the spec and plain branches so the bookkeeping
        cannot drift. ``k`` is the steps this program reads the shared
        prefix (a spec program passes 1: its verify round reads the
        group's shared pages once)."""
        self._inflight.append(rec)
        _M_DISPATCH_INFLIGHT.set(len(self._inflight))
        _M_GROUP_SIZE.set(
            self._groups.largest_group if groups is not None else 0
        )
        if groups is not None:
            # Shared pages read once per group instead of once per
            # member: count the reads this program skips.
            saved = (
                self._groups.saved_tokens_per_step * self._kv_token_bytes * k
            )
            _M_KV_SAVED.inc(saved)
            with self._lock:
                self._kv_bytes_saved += saved

    def _fetch_one(self) -> None:
        """Fetch the OLDEST in-flight program's tokens and run its host
        bookkeeping — stop scans, retirement, future resolution.

        Retirement necessarily lags dispatch by the in-flight depth: a
        row that finished in program *n* keeps decoding through the
        already-enqueued programs *n+1..n+depth-1*. Those tokens are
        discarded here — rows are credited by _Slot IDENTITY, so a slot
        retired (or retired and re-admitted) since dispatch never sees
        a stale program's output, and the stop-trim semantics stay
        byte-identical to depth 1 — and the page overshoot is
        pre-budgeted by :meth:`_table_pages`.
        """
        rec = self._inflight.popleft()
        with self._phase("device_wait"):
            next_np = np.asarray(rec.tokens)  # [slots, k] — THE host sync
            cnt_np = (
                np.asarray(rec.emit_cnt)
                if (rec.spec or rec.rounds or rec.denoise)
                else None
            )
            # A denoise program's aux holds its steps' logits and ids
            # before the routing counts.
            at = 2 if rec.denoise else 1
            moe_np = (
                np.asarray(rec.aux[at])
                if rec.aux is not None and len(rec.aux) > at
                else None
            )
            # The first tokens of the lanes that ended their prompts:
            # an output of the program that has just ended, as the
            # tokens are — no wait on the one behind it.
            first_np = (
                np.asarray(rec.chunk_first)
                if rec.chunk_first is not None
                and any(ch.done for ch in rec.chunks)
                else None
            )
        with self._phase("retire"):
            forwards = rec.k
            if rec.denoise:
                forwards = self.cfg.denoising_steps + 1
                self._count_blocks(self._kind_of(rec), len(rec.rows))
            if moe_np is not None:
                self._count_moe(self._kind_of(rec), moe_np, forwards)
            self._credit_fetched(rec, next_np, cnt_np, first_np)
            self._apply_row_patch()

    def _kind_of(self, rec: "_Inflight") -> str:
        """A fetched program's kind, as its dispatch counted it."""
        if rec.spec:
            return "spec"
        if rec.chunks:
            return "fused"
        return "denoise" if rec.denoise else "decode"

    def _count_blocks(self, kind: str, rows: int) -> None:
        """A retired block-diffusion program committed ``rows`` blocks,
        each after the model's denoising steps and one commit forward."""
        steps = rows * (self.cfg.denoising_steps + 1)
        _M_DENOISE_STEPS.labels(kind=kind).inc(steps)
        _M_DIFF_BLOCKS.inc(rows)
        with self._lock:
            self._diffusion["steps"] += steps
            self._diffusion["blocks"] += rows

    def _read_denoise_logits(self, rec: "_Inflight", i: int, req) -> None:
        """A ``"logits": n`` request's read-out from the block program
        of row ``i``: each denoising step's B rows of logits and the
        block's ids at that step, until n steps are in."""
        steps = min(
            req.logits_n - len(req.logit_rows), self.cfg.denoising_steps
        )
        if steps <= 0:
            return
        with self._phase("device_wait"):
            logits = np.asarray(rec.aux[0][:steps, i])
            ids = np.asarray(rec.aux[1][:steps, i])
        req.logit_rows.extend(logits)
        req.logit_ids.extend(ids.tolist())

    def _count_moe(self, kind: str, moe_np, steps: int = 1) -> None:
        """A retired program's expert-routing counts (int32 [experts
        reached, assignments], summed over its expert layers and
        steps), as the program returned them."""
        _M_MOE_EXPERTS.labels(kind=kind).inc(int(moe_np[0]))
        _M_MOE_ASSIGNMENTS.labels(kind=kind).inc(int(moe_np[1]))
        _M_MOE_LAYER_PROGRAMS.labels(kind=kind).inc(
            steps * self.cfg.n_moe_layers
        )

    def _credit_fetched(
        self, rec: "_Inflight", next_np, cnt_np, first_np
    ) -> None:
        """The host bookkeeping of one fetched program (the ``retire``
        phase): step telemetry, crediting its tokens to the rows still
        alive, stop scans, retirement, and a fused chunk's deferred
        activation. Row installs and releases go into the patch the
        caller applies (:meth:`_apply_row_patch`); nothing here waits
        on the device."""
        step_end = time.perf_counter()
        # Device-step latency: at depth 1 the program started at its
        # own dispatch; deeper, it started when its predecessor
        # finished — approximated from the host side by the previous
        # fetch's completion.
        start = rec.t0
        if self._last_fetch_end is not None:
            start = max(start, self._last_fetch_end)
        dur = step_end - start
        self._last_fetch_end = step_end
        # The pipeline drained: host time from here to the next
        # dispatch is un-overlapped. With programs still in flight the
        # gap is hidden and the next dispatch observes 0.
        self._last_step_end = None
        if not self._inflight:
            self._last_step_end = step_end
            self._drained_by = (
                "first_token" if first_np is not None else "other"
            )
        self._hb_step = time.monotonic()
        _M_STEP_SECONDS.observe(dur)
        if rec.flight is not None:
            # Fill the dispatch-time flight event with the TRUE device
            # window (same correction _M_STEP_SECONDS uses): the Chrome
            # export's device track is these windows back to back.
            rec.flight.t0 = start
            rec.flight.dur = dur
        self._mbu_account(self._kind_of(rec), rec.cost, dur)
        _M_DISPATCH_INFLIGHT.set(len(self._inflight))
        alive = [(i, s) for i, s in rec.rows if self._slots[i] is s]
        with self._lock:
            self._decode_steps += rec.k
            self._decode_step_sum += dur
            self._decode_step_count += 1
        # One "decode_step" span per DISTINCT trace among the program's
        # surviving participants: a batched step belongs to every
        # request it advanced (the per-trace span budget bounds long
        # decodes; retired requests take no post-retirement spans).
        step_traces: dict[int, object] = {}
        for _, slot in alive:
            if slot.request.trace is not None:
                step_traces[id(slot.request.trace)] = slot.request.trace
        for tr in step_traces.values():
            # Same window as _M_STEP_SECONDS: [start, step_end], where
            # start is the corrected dispatch/predecessor-fetch stamp.
            tr.add_span(
                "decode_step", start, dur, active=len(rec.rows), k=rec.k
            )
        _M_STEPS.inc(rec.k)
        if rec.rows:
            _M_OCCUPANCY.observe(len(rec.rows))
        if rec.spec:
            # Sync the host PRNG-count mirror (the spec program's yield
            # is data-dependent, so dispatch couldn't advance it), and
            # feed the speculation metrics from one site. Rows whose
            # slot was retired/reused mid-flight are skipped exactly
            # like their tokens; a reused slot's activation reset its
            # count and marked it dirty, so the mirror stays right.
            emitted = 0
            accepted = 0
            accept_samples = []
            for i, s in alive:
                n = int(cnt_np[i])
                self._counts[i] += n
                emitted += n
                accepted += n - 1
                # Per-request speculation tallies (the "spec tokens
                # accepted per round" line of the request summary).
                s.spec_rounds += 1
                s.spec_accepted_toks += n - 1
                accept_samples.append(
                    (self._group_key(s), n - 1, rec.spec_k)
                )
            if self.controller is not None and accept_samples:
                # Per-group acceptance EWMAs (PR 15) — fed from the
                # SAME per-row counts gateway_spec_acceptance's
                # fraction aggregates, keyed by the GroupTracker
                # bucket identity.
                self.controller.note_spec_round(accept_samples)
            if alive:
                _M_SPEC_ACCEPTED.inc(accepted)
                frac = accepted / (rec.spec_k * len(alive))
                _M_SPEC_ACCEPTANCE.observe(frac)
                _M_SPEC_VERIFIED.set(emitted)
                xmodel = (
                    self._vocab_map is not None
                    and not self._vocab_map.identity
                )
                if xmodel and accepted > 0:
                    # Cross-model speculation (PR 18): these accepts
                    # crossed a tokenizer boundary through the vocab
                    # remap; the flight event is their witness.
                    _M_SPEC_XMODEL.inc(accepted)
                    _flight.flight_recorder().record(
                        "spec_xmodel_accept",
                        time.perf_counter(),
                        accepted=accepted,
                        rows=len(alive),
                        spec_k=rec.spec_k,
                    )
                with self._lock:
                    self._spec_accepted += accepted
                    if xmodel:
                        self._spec_xmodel_accepted += accepted
                    self._spec_acc_sum += frac
                    self._spec_acc_count += 1
                    self._spec_verified_last = emitted
        if rec.rounds and not rec.spec:
            # Multi-round program (PR 12): sync the host PRNG-count
            # mirror by each surviving row's real yield (frozen rounds
            # folded nothing), and widen the draft lag by the same —
            # the spec discipline, minus the speculation metrics. Rows
            # whose slot was retired/reused mid-flight are skipped
            # exactly like their tokens (a reused slot's activation
            # reset its count and marked it dirty).
            for i, s in alive:
                n = int(cnt_np[i])
                self._counts[i] += n
                if self.draft_cache is not None:
                    s.draft_lag += n
        emitted_total = 0
        tbt_sum, tbt_count = 0.0, 0
        for i, slot in alive:
            done = False
            n_emit = int(cnt_np[i]) if cnt_np is not None else rec.k
            want = slot.request.logits_n
            if rec.denoise:
                self._read_denoise_logits(rec, i, slot.request)
            elif want > len(slot.request.logit_rows) and rec.aux is not None:
                # One step a program (``submit`` saw to it): this row of
                # the step's logits is the token credited just below.
                slot.request.logit_rows.append(np.asarray(rec.aux[0][i]))
            for j in range(n_emit):
                tok = int(next_np[i, j])
                slot.generated.append(tok)
                self._last_tokens[i] = tok
                emitted_total += 1
                if slot.t_first is None:
                    # A block-diffusion row's first token comes with its
                    # first block: the request's TTFT anchor.
                    slot.t_first = slot.t_last_tok = step_end
                    with self._lock:
                        self._ttft_sum += step_end - slot.request.t_submit
                        self._ttft_count += 1
                else:
                    # Token-timeline stamp: tokens surface at
                    # the fetch — the first of this fetch carries the
                    # gap since the row's previous token, the rest
                    # arrived with it (gap 0), which is exactly what a
                    # streaming client observes. One observation per
                    # generated token past the request's first (that
                    # one is TTFT's).
                    gap = step_end - slot.t_last_tok if j == 0 else 0.0
                    slot.t_last_tok = step_end
                    slot.gaps.append(gap)
                    _M_TBT.observe(gap)
                    tbt_sum += gap
                    tbt_count += 1
                done = (
                    tok == self.tokenizer.eos_id
                    or len(slot.generated) >= slot.request.max_new_tokens
                    or self._hit_stop(slot)
                )
                if done:
                    # Tokens past this point were decoded on device
                    # but never belonged to the request.
                    break
            if done:
                self._retire(i)
        if emitted_total:
            _M_GENERATED.inc(emitted_total)
            if rec.denoise:
                _M_DIFF_TOKENS.inc(emitted_total)
                with self._lock:
                    self._diffusion["tokens"] += emitted_total
        if tbt_count:
            with self._lock:
                self._tbt_sum += tbt_sum
                self._tbt_count += tbt_count
        if (
            self.controller is not None
            and not rec.spec
            and rec.rows
            and self.config.decode_rounds > 1
        ):
            # Two-arm rounds feed (PR 15): this window's realized
            # emissions, attributed to the running regime (a plain
            # window is the arm-1 regime while the controller
            # arbitrates; rec.rounds_clean says whether the length
            # was chosen or forced).
            self.controller.note_rounds_window(
                rec.rounds if rec.rounds else 1,
                emitted_total,
                clean=rec.rounds_clean,
            )
        if rec.flight is not None:
            # Replace, never mutate: a concurrent export may hold the
            # old meta dict.
            rec.flight.meta = {**rec.flight.meta, "tokens": emitted_total}
        for ch in rec.chunks:
            if self._slots[ch.idx] is not ch.slot:
                continue
            # Fused prefill chunk (PR 8): host bookkeeping deferred to
            # the fetch — its device work completed with the program
            # whose tokens we just pulled. The chunk did not stall the
            # decode loop (it rode the dispatch), so the stall
            # histogram observes 0 — count-lockstep with
            # prefill_chunks, value-honest about the fusion.
            slot = ch.slot
            _M_PREFILL_STALL.observe(0.0)
            with self._lock:
                self._prefill_chunks += 1
            trace = slot.request.trace
            if trace is not None:
                trace.add_span(
                    "prefill_chunk", start, dur,
                    pos=ch.pos, chunk=ch.width, fused=1,
                )
            if ch.done:
                # Final chunk: the fused program sampled the first token
                # from the logits it computed (same PRNG draw, same
                # unembed as the standalone path) — a block-diffusion
                # program none: its prompt ends in a block.
                self._prompt_ended(
                    ch.idx, slot,
                    None if first_np is None else int(first_np[ch.lane]),
                    ch.logits,
                )

    def _run(self) -> None:
        """The worker thread. A device program that raises (a kernel
        that does not lower, an OOM, a runtime fault) ends serving on
        this batcher — a failed dispatch may already have donated the
        pool — but it must end LOUDLY: every request waiting on the
        loop gets the error (a 502 with the message at the gateway, not
        a hang), new submits are refused, and heartbeat() reports
        ``failed`` so /readyz turns 503."""
        try:
            self._serve_loop()
        except Exception as e:  # noqa: BLE001 - thread boundary: report
            log.exception("continuous batcher worker failed")
            self._failed = f"{type(e).__name__}: {e}"
            self._stop.set()
            self._prefetch_have.set()
            self._release_waiters(
                BatcherFailed(f"serving loop failed: {self._failed}")
            )

    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            self._hb_tick = time.monotonic()
            self._phases.flush()
            # Fleet requests first (PR 14): preemption frees pages the
            # admission below may need; exports are bounded spills.
            with self._phase("admit"):
                self._steer_step()
                self._preempt_step()
                self._export_step()
                self._admit()
            progress = False
            ran_program = False
            # At most ONE prefill work unit per iteration — a host-tier
            # page restore (which unblocks gated prefills) or one chunk
            # program (the next chunk of each ready slot it has a lane
            # for): running slots pay a bounded stall per admission
            # instead of a whole prompt's prefill.
            chunk_idxs: list[int] = []
            if self._restores:
                with self._phase("restore"):
                    progress = self._restore_step()
            if not progress:
                chunk_idxs = self._pick_prefill_slots()
            # Speculative decoding (PR 9): read the engage state once
            # per iteration (config.spec_decode may flip between
            # bursts). While speculation is on, chunks run standalone —
            # the verify program IS the decode dispatch, and a chunk
            # lane on it is future work.
            spec_now = self._spec_ok
            if spec_now and self.controller is not None:
                # Adaptive spec gate (PR 15): the controller may
                # DISENGAGE speculation when every decoding group's
                # measured acceptance sits below the floor (and
                # re-probe periodically). The flip takes the same
                # drain + catch-up path as a live spec_decode flip —
                # the PR-9 rules this composes with.
                spec_now = self.controller.spec_gate(
                    [
                        self._group_key(s)
                        for s in self._slots
                        if s is not None and s.phase == "decode"
                    ]
                )
            # Multi-round engage state, read ONCE per iteration next to
            # spec_now and threaded into _dispatch the same way: the
            # mode-flush decision and the dispatched program must come
            # from the same read, or a live decode_rounds flip between
            # the two would chain a counts-mode mismatch into the
            # window (a flip is a between-bursts event, but the
            # scheduler must stay correct if one lands mid-burst).
            rounds_now = 1 if spec_now else self._rounds
            rounds_choice = False
            if rounds_now > 1 and self.controller is not None:
                # Roofline-adaptive R (PR 15): the controller's
                # two-arm regime choice over {plain 1-round, R-round
                # window}, consulted at the same once-per-iteration
                # altitude as the engage state itself — an arm-1
                # choice dispatches PLAIN programs (the mode flush
                # below drains on the transition, bounded by the
                # stretch cadence), an arm-R choice keeps the masked
                # window, and a batch about to retire forces 1 (the
                # masked tail rounds would decode nothing). Byte
                # parity vs any fixed R is the PR-12 masking
                # contract; the {1, R} menu adds ZERO compiled
                # traces.
                max_rem = max(
                    (
                        s.request.max_new_tokens - len(s.generated)
                        for s in self._slots
                        if s is not None and s.phase == "decode"
                    ),
                    default=0,
                )
                cap = max(
                    1,
                    min(
                        rounds_now,
                        self.controller.rounds_cap(max_rem, rounds_now),
                    ),
                )
                rounds_choice = max_rem >= rounds_now
                rounds_now = cap
            if self._draft_cfg is not None:
                # Flight event on TRANSITIONS only (spec_decode is read
                # per iteration; steady state records nothing).
                if (
                    self._spec_flip_prev is not None
                    and self._spec_flip_prev != spec_now
                ):
                    _flight.flight_recorder().record(
                        "spec_flip", time.perf_counter(), on=spec_now
                    )
                self._spec_flip_prev = spec_now
            # The fused scheduler step (PR 8): the ready chunks ride the
            # decode dispatch as more ragged-kernel rows — ONE device
            # program per iteration instead of chunk-then-decode. With
            # no decode batch to ride (or fusion off) they run
            # standalone, still one program this iteration.
            fused = (
                bool(chunk_idxs)
                and self._fused_ok
                and self._dispatchable()
                and not spec_now
            )
            if chunk_idxs and not fused:
                self._prefill_step(chunk_idxs)
                progress = True
                ran_program = True
                if self._fused_ok:
                    # A standalone chunk only runs under fusion when
                    # the decode batch was EMPTY; if its final chunk
                    # just activated the slot, dispatching in the same
                    # pass would make this the one iteration that runs
                    # two programs. Defer to the next pass (the loop
                    # spins straight back) — one program per iteration
                    # stays exact (tests/test_ragged_attention.py,
                    # tests/test_mesh_serving.py).
                    with self._lock:
                        self._work_iterations += 1
                    continue
            if self._dispatchable():
                # Software pipeline: enqueue the next program FIRST,
                # then fetch the oldest once the window is full — the
                # fetch's host sync lands while the newer program(s)
                # run. depth 1 reduces to dispatch -> fetch -> bookkeep
                # (the serialized parity baseline); the while also
                # drains excess depth after a live depth reduction.
                if self._inflight:
                    # A plain program feeds the next dispatch from
                    # host-advanced counts; spec and multi-round
                    # programs from their device counts_out. Mixing
                    # modes in one window would desync the PRNG
                    # mirror — drain first (a flip is a between-bursts
                    # event, never hot-path). Multi-round flush
                    # semantics extend unchanged otherwise: an R-round
                    # window drains like any other (its programs'
                    # fetches credit data-dependent yields), so every
                    # stable-cache operation keeps working under R.
                    tail = self._inflight[-1]
                    tail_mode = (
                        "spec"
                        if tail.spec
                        else ("rounds" if tail.rounds else "plain")
                    )
                    mode_now = (
                        "spec"
                        if spec_now
                        else ("rounds" if rounds_now > 1 else "plain")
                    )
                    if tail_mode != mode_now:
                        self._flush_pipeline()
                with self._phase(
                    "dispatch",
                    kind=(
                        "spec"
                        if spec_now
                        else "denoise" if self._block > 1
                        else ("fused" if fused else "decode")
                    ),
                    rows=self._decoding(),
                ):
                    if self._block > 1:
                        self._dispatch_denoise(chunk_idxs if fused else None)
                    else:
                        if spec_now:
                            # Rows that decoded through an off window
                            # need their draft mirror replayed first —
                            # no-op in the steady state (every lag-free
                            # iteration).
                            self._spec_catch_up()
                        self._dispatch(
                            chunk_idxs if fused else None,
                            spec=spec_now,
                            rounds=rounds_now,
                            rounds_choice=rounds_choice,
                        )
                while len(self._inflight) >= self._depth:
                    self._fetch_one()
                progress = True
                ran_program = True
            else:
                if self._inflight:
                    # The decode batch went empty (every known row
                    # retired) with programs still in flight: drain
                    # them — late retirements and futures resolve here.
                    self._fetch_one()
                    progress = True
                if not self._decoding():
                    # No device step pending: the gap to the next one
                    # is not scheduling overhead.
                    self._last_step_end = None
            if ran_program:
                # Denominator of "device programs per scheduler
                # iteration".
                with self._lock:
                    self._work_iterations += 1
            if not progress:
                self._last_step_end = None
                with self._phase("idle"):
                    self._work.wait(timeout=0.1)
                self._work.clear()


def _meta_with_logits(
    timing: dict | None, logits, block_ids: list | None = None
) -> dict | None:
    """The response's ``meta``: the request's summary, and for a
    ``"logits": n`` request a COPY of it (the summary ring keeps the
    original) with the rows as base64 of little-endian float32 — and,
    for a block-diffusion model, each denoising step's block ids."""
    if logits is None:
        return timing
    rows = np.ascontiguousarray(logits, dtype="<f4")
    doc = {
        "positions": int(rows.shape[0]),
        "vocab": int(rows.shape[1]),
        "dtype": "float32",
        "b64": base64.b64encode(rows.tobytes()).decode("ascii"),
    }
    if block_ids is not None:
        doc["block_ids"] = block_ids
    return {**(timing or {}), "logits": doc}


class ContinuousBackend(_backend_base.Backend):
    """Backend seam over a :class:`ContinuousBatcher`.

    The Coordinator's panel fan-out (``generate_batch``) rides token-level
    continuous batching: each request joins the running decode batch at
    step granularity instead of waiting for a whole-batch program. This
    closes the reference's L1 seam (``call_gemini``, src/main.rs:82-86)
    over the throughput-serving path.
    """

    def __init__(self, batcher: ContinuousBatcher):
        self.batcher = batcher

    async def generate_batch(self, requests):
        import asyncio

        BackendError = _backend_base.BackendError
        GenerationResult = _backend_base.GenerationResult

        # Per-request top_k/top_p/stop ride as decode-step data
        # (sample_token_per_request + host stop checks), so the full
        # SamplingParams surface passes through — protocol-identical
        # behavior to LocalBackend.
        futs = []
        try:
            for r in requests:
                futs.append(
                    self.batcher.submit(
                        r.prompt,
                        max_new_tokens=r.params.max_new_tokens,
                        temperature=r.params.temperature,
                        seed=r.params.seed,
                        top_k=r.params.top_k,
                        top_p=r.params.top_p,
                        stop=r.params.stop,
                        logits=r.params.logits,
                    )
                )
        except (RuntimeError, ValueError) as e:
            # A mid-batch submit failure (stopped batcher, rejected
            # prompt) leaves earlier futures in flight: cancel the ones
            # still waiting so their device work isn't silently orphaned
            # (_admit/_retire skip done futures).
            for f in futs:
                f.cancel()
            raise BackendError(f"continuous submit failed: {e}") from e
        outs = await asyncio.gather(*(asyncio.wrap_future(f) for f in futs))
        return [
            GenerationResult(
                text=o.text, num_tokens=o.num_tokens,
                meta=_meta_with_logits(o.timing, o.logits, o.logit_ids),
            )
            for o in outs
        ]

    def health(self) -> dict:
        """Gateway readiness probe surface: the batcher heartbeat."""
        return self.batcher.heartbeat()

    @property
    def tokenizer(self):
        """The batcher tokenizer — the gateway's ``/debug/chains``
        handler encodes ``?prompt=`` probes with it (PR 16)."""
        return self.batcher.tokenizer

    def prefix_probe(self, ids) -> dict:
        """``/debug/chains`` probe surface: how much of this prompt's
        prefix chain is resident here (PR 16 peer routing)."""
        return self.batcher.prefix_probe(ids)

    def prefetch(self, prompt: str) -> bool:
        """Gateway enqueue-time prefetch hook (PR 17): the single-
        replica deployment's destination is always THIS batcher, so
        the admission-queue wait is free overlap — stage the prompt's
        host-store pages now and the restore plan at admission finds
        them staged. Non-blocking (a queue append); advisory (a wrong
        guess falls through to get_run/recompute)."""
        ids = self.batcher.tokenizer.encode(prompt)
        return self.batcher.prefetch_chain(
            ids[-self.batcher.config.seq_buckets[-1]:]
        )

    def request_cost(self, prompt: str, max_new_tokens: int) -> float:
        """Modeled bytes of one request's whole schedule — the
        gateway's cost-budget admission consults this (PR 15) so its
        queue bound counts the same unit the router's load_cost
        compares. Tokenizes once (ByteTokenizer is O(len) on the
        event loop; the submit path re-encodes — correctness over a
        cached double-encode here)."""
        return self.batcher.modeled_request_cost(
            len(self.batcher.tokenizer.encode(prompt)), max_new_tokens
        )

    async def close(self) -> None:
        self.batcher.close()
