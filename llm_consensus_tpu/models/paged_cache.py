"""Paged KV cache: fixed page pool + per-sequence page tables.

The dense :class:`llm_consensus_tpu.models.cache.KVCache` allocates
``B x max_len`` up front — fine for uniform self-consistency fan-out,
wasteful for a serving mix of short and long requests. The paged layout
(vLLM-style, re-founded on XLA static shapes) keeps one global pool of
fixed-size pages; each sequence owns an ordered list of page ids. All
shapes are static: admission/eviction mutate *data* (page tables,
lengths), never shapes, so the decode program compiles exactly once.

The reference has no KV cache (no model code at all, SURVEY.md §0); this
is infrastructure for the serving path the build adds (SURVEY.md §7
step 5-6, BASELINE.json throughput targets).

Layout:
- pool k/v: ``[L, n_pages, page_size, Hkv, Dh]``; for a latent-attention
  (MLA) model ONE latent plane ``k``: ``[L, n_pages, page_size,
  kv_lora_rank + qk_rope_head_dim]`` (the compressed latent, then the
  shared rotary key: 576 lanes for DeepSeek-V2-Lite, zero-padded to
  whole 128-lane tiles, 640: ``ModelConfig.latent_pool_dim``), and ``v``
  an empty ``[L, n_pages, page_size, 0]`` plane — the value is the first
  ``kv_lora_rank`` lanes of the key, read from the same page. The page
  movers below index ``[:, page]`` and never look past that axis, so
  they carry latent pages as they carry K/V pairs.
- page_table: ``[max_seqs, pages_per_seq]`` int32 page ids (unused
  entries can hold any valid id; masking is by ``length``).
- length: ``[max_seqs]`` tokens written per sequence.

Page 0 is reserved as the "null" page so freshly-reset tables are valid.

A model with recurrent (state-space) layers keeps a SECOND kind of state
in the same cache (:class:`SsmState`): a pool of state slots ``[state
layer, slot, ...]`` — a Mamba-2 layer's ``S`` [H, P, N] float32 and the
last K - 1 rows of its convolution's input — one slot a live sequence
and one a registry snapshot, handed out by a :class:`StatePool` as
pages are by a :class:`PagePool`. Slot 0 is the empty state, as page 0
is the null page. The K/V pool then has a plane for each ATTENTION
layer only, indexed by a layer's index among its kind.

Two host-side structures complete the picture (PR 2):

- :class:`PagePool` — refcounted page allocator. A page mapped into N
  live page tables (plus optionally the prefix registry) carries
  refcount N(+1) and returns to the free list only when the last holder
  releases it, which is what makes COPY-ON-WRITE page sharing safe:
  full pages of a common prompt prefix are *mapped*, never rewritten
  (decode writes only at positions >= prompt_len, i.e. never into a
  fully-shared prefix page), and any page that WOULD be written —
  the partially-filled boundary page — is copied, never shared.
- :class:`PrefixRegistry` — a radix tree of page-aligned prompt
  prefixes keyed by page-sized token runs, so the consensus panel's N
  requests over one question prefill the shared header once and every
  later admission maps the already-resident pages.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field, replace
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from llm_consensus_tpu.models.configs import ModelConfig

NULL_PAGE = 0


def prefix_chain_key(
    ids: Sequence[int], page_size: int
) -> tuple[tuple[int, ...], ...]:
    """A prompt's page-aligned prefix-chain fingerprint: the tuple of
    page-sized token runs that key both the :class:`PrefixRegistry`
    radix walk and the host tier's chain keys — capped at the USABLE
    full pages (at least the last prompt token is always recomputed,
    so a prompt's final partial/whole page never participates in
    sharing; the same ``usable_full`` cap :meth:`PrefixRegistry.match`
    applies).

    Exported for the replica fleet (PR 14): the router fingerprints a
    request ONCE and compares it against every replica's resident
    chains — "requests sharing a radix-registry chain land where the
    pages already live" needs exactly this identity, computed the same
    way the registry computes it.
    """
    usable_full = (len(ids) - 1) // page_size
    return tuple(
        tuple(int(t) for t in ids[k * page_size : (k + 1) * page_size])
        for k in range(usable_full)
    )


NULL_SLOT = 0


@jax.tree_util.register_dataclass
@dataclass
class SsmState:
    """Recurrent state beside the pages: ``s`` and ``conv`` are pools
    over (state layer, slot); ``slot`` is each decode row's slot, kept
    in step with the row's page-table row (0 while it is idle or still
    prefilling: the chunk programs take a lane's slots as arguments)."""

    s: jnp.ndarray  # [Ls, n_slots, H, P, N] float32
    conv: jnp.ndarray  # [Ls, n_slots, K - 1, conv_dim]
    slot: jnp.ndarray  # [max_seqs] int32

    @property
    def n_slots(self) -> int:
        return self.s.shape[1]


@jax.tree_util.register_dataclass
@dataclass
class PagedKVCache:
    k: jnp.ndarray  # [L, n_pages, page_size, Hkv, Dh]; MLA: [.., latent]
    v: jnp.ndarray  # as k; MLA: [L, n_pages, page_size, 0] (empty)
    page_table: jnp.ndarray  # [max_seqs, pages_per_seq] int32
    length: jnp.ndarray  # [max_seqs] int32
    # None for a model without recurrent layers: no leaf, and the step
    # programs of such a model are what they were.
    state: SsmState | None = None

    @staticmethod
    def create(
        cfg: ModelConfig,
        n_pages: int,
        page_size: int,
        max_seqs: int,
        pages_per_seq: int,
        dtype=jnp.bfloat16,
        state_slots: int = 0,
    ) -> "PagedKVCache":
        if cfg.is_mla:
            lead = (cfg.n_layers, n_pages, page_size)
            k_shape, v_shape = lead + (cfg.latent_pool_dim,), lead + (0,)
        else:
            k_shape = v_shape = (
                cfg.n_attn_layers, n_pages, page_size, cfg.n_kv_heads,
                cfg.head_dim,
            )
        state = None
        if cfg.is_recurrent:
            if state_slots < 2:
                raise ValueError(
                    f"{cfg.name} keeps recurrent state: the cache needs "
                    f"state_slots >= 2 (slot 0 is the empty state), got "
                    f"{state_slots}"
                )
            lead = (cfg.n_ssm_layers, state_slots)
            state = SsmState(
                s=jnp.zeros(
                    lead + (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32,
                ),
                conv=jnp.zeros(
                    lead + (cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype
                ),
                slot=jnp.full((max_seqs,), NULL_SLOT, jnp.int32),
            )
        return PagedKVCache(
            k=jnp.zeros(k_shape, dtype),
            v=jnp.zeros(v_shape, dtype),
            page_table=jnp.full((max_seqs, pages_per_seq), NULL_PAGE, jnp.int32),
            length=jnp.zeros((max_seqs,), jnp.int32),
            state=state,
        )

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def max_seqs(self) -> int:
        return self.page_table.shape[0]

    @property
    def pages_per_seq(self) -> int:
        return self.page_table.shape[1]


def gather_seq_kv(
    cache: PagedKVCache, seq_ids: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize contiguous [L, B, pages_per_seq*page, Hkv, Dh] K/V for
    the given sequences (the jnp reference path; a Pallas kernel can read
    through the table instead)."""
    tables = cache.page_table[seq_ids]  # [B, P]
    k = cache.k[:, tables]  # [L, B, P, page, Hkv, Dh]
    v = cache.v[:, tables]
    L, b, p, pg, h, d = k.shape
    return k.reshape(L, b, p * pg, h, d), v.reshape(L, b, p * pg, h, d)


def write_decode_kv(
    cache: PagedKVCache,
    seq_ids: jnp.ndarray,  # [B]
    k_new: jnp.ndarray,  # [L, B, Hkv, Dh]
    v_new: jnp.ndarray,
) -> PagedKVCache:
    """Write one token's K/V for each sequence at its current length."""
    pos = cache.length[seq_ids]  # [B]
    page_idx = pos // cache.page_size
    offset = pos % cache.page_size
    pages = cache.page_table[seq_ids, page_idx]  # [B]
    k = cache.k.at[:, pages, offset].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[:, pages, offset].set(v_new.astype(cache.v.dtype))
    length = cache.length.at[seq_ids].add(1)
    return replace(cache, k=k, v=v, length=length)


def write_prefill_kv(
    cache: PagedKVCache,
    seq_id: jnp.ndarray,  # scalar int32
    k_seq: jnp.ndarray,  # [L, S, Hkv, Dh] (S = padded prompt bucket)
    v_seq: jnp.ndarray,
    length: jnp.ndarray,  # scalar true prompt length
) -> PagedKVCache:
    """Scatter one prefilled sequence's K/V into its assigned pages.

    S must be a multiple of page_size; slots past ``length`` hold padding
    garbage, masked out of attention by ``length`` exactly as the dense
    cache masks by ``valid_len``.
    """
    L, s, h, d = k_seq.shape
    pg = cache.page_size
    if s % pg:
        raise ValueError(f"prefill length {s} not a multiple of page {pg}")
    n = s // pg
    pages = jax.lax.dynamic_slice_in_dim(
        cache.page_table[seq_id], 0, n
    )  # [n]
    k_pages = k_seq.reshape(L, n, pg, h, d).astype(cache.k.dtype)
    v_pages = v_seq.reshape(L, n, pg, h, d).astype(cache.v.dtype)
    k = cache.k.at[:, pages].set(k_pages)
    v = cache.v.at[:, pages].set(v_pages)
    new_len = cache.length.at[seq_id].set(length.astype(jnp.int32))
    return replace(cache, k=k, v=v, length=new_len)


def assign_pages(
    cache: PagedKVCache, seq_id: jnp.ndarray, pages: jnp.ndarray
) -> PagedKVCache:
    """Install a page list (padded with NULL_PAGE) for one sequence."""
    table = cache.page_table.at[seq_id].set(pages.astype(jnp.int32))
    return replace(cache, page_table=table)


def release_seq(cache: PagedKVCache, seq_id: jnp.ndarray) -> PagedKVCache:
    """Clear a sequence's table/length (page recycling is host-side)."""
    table = cache.page_table.at[seq_id].set(NULL_PAGE)
    length = cache.length.at[seq_id].set(0)
    state = cache.state
    if state is not None:
        state = replace(state, slot=state.slot.at[seq_id].set(NULL_SLOT))
    return replace(cache, page_table=table, length=length, state=state)


def install_seq(
    cache: PagedKVCache,
    seq_id: jnp.ndarray,
    pages: jnp.ndarray,
    length: jnp.ndarray,
    slot: jnp.ndarray | None = None,
) -> PagedKVCache:
    """Install table AND length for one sequence in one pass — the
    moment a chunk-prefilled sequence (whose pages were written through
    an explicit host-side table, invisible to the decode program)
    becomes a live decode row. ``slot``: its state slot, where the
    model keeps recurrent state."""
    table = cache.page_table.at[seq_id].set(pages.astype(jnp.int32))
    new_len = cache.length.at[seq_id].set(length.astype(jnp.int32))
    state = cache.state
    if state is not None:
        state = replace(
            state, slot=state.slot.at[seq_id].set(slot.astype(jnp.int32))
        )
    return replace(cache, page_table=table, length=new_len, state=state)


def copy_page(
    cache: PagedKVCache, src: jnp.ndarray, dst: jnp.ndarray
) -> PagedKVCache:
    """Copy one page's K/V across all layers (``src`` -> ``dst``).

    The copy-on-write primitive: when an admission's prompt shares a
    registered prefix that ends INSIDE a page, that boundary page's
    already-computed K/V is copied into a freshly-allocated private
    page — sharing it would let this sequence's later prefill/decode
    writes corrupt every other reader.
    """
    k = cache.k.at[:, dst].set(cache.k[:, src])
    v = cache.v.at[:, dst].set(cache.v[:, src])
    return replace(cache, k=k, v=v)


def install_page(
    cache: PagedKVCache,
    page: jnp.ndarray,
    k_page: jnp.ndarray,  # [L, page_size, Hkv, Dh]
    v_page: jnp.ndarray,
) -> PagedKVCache:
    """Write one page's K/V across all layers from host-side planes.

    The offload tier's promote primitive
    (:mod:`llm_consensus_tpu.serving.offload`): a page demoted to host
    RAM comes back through this op verbatim — same dtype, same bytes —
    so a restored prefix is indistinguishable from one that never left
    the pool.
    """
    k = cache.k.at[:, page].set(k_page.astype(cache.k.dtype))
    v = cache.v.at[:, page].set(v_page.astype(cache.v.dtype))
    return replace(cache, k=k, v=v)


def install_pages(
    cache: PagedKVCache,
    pages: jnp.ndarray,  # [N]
    k_pages: jnp.ndarray,  # [L, N, page_size, Hkv, Dh]
    v_pages: jnp.ndarray,
) -> PagedKVCache:
    """:func:`install_page` for N pages in one scatter — the restore
    half of the batching contract the demote side already keeps (one
    ``device_get`` per evict walk): one host->device transfer and one
    program launch per restore BATCH instead of per page. ``pages``
    must be distinct (restore plans are, by construction: each page is
    a different chain prefix)."""
    k = cache.k.at[:, pages].set(k_pages.astype(cache.k.dtype))
    v = cache.v.at[:, pages].set(v_pages.astype(cache.v.dtype))
    return replace(cache, k=k, v=v)


# ---------------------------------------------------------------------------
# Host-side allocation: refcounted pages + prefix radix tree
# ---------------------------------------------------------------------------


class PagePool:
    """Refcounted host-side page allocator over a fixed id range.

    Callers hold pages by id; a page is free exactly when its refcount
    is zero. Fresh allocations start at refcount 1; mapping an existing
    page into another sequence's table goes through :meth:`share`;
    every holder (sequences AND the prefix registry) pairs its hold
    with exactly one :meth:`release`. Not thread-safe — callers
    serialize under their own lock (the continuous batcher's worker
    owns its pools).
    """

    def __init__(self, page_ids: Iterable[int]):
        self._free: deque[int] = deque(page_ids)
        self._rc: dict[int, int] = {}

    @property
    def available(self) -> int:
        """Pages allocatable right now (excludes shared/cached pages)."""
        return len(self._free)

    @property
    def held(self) -> int:
        return len(self._rc)

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {len(self._free)}"
            )
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._rc[p] = 1
        return pages

    def share(self, page: int) -> None:
        if page not in self._rc:
            raise ValueError(f"page {page} is not allocated")
        self._rc[page] += 1

    def release(self, page: int) -> None:
        rc = self._rc.get(page)
        if rc is None:
            raise ValueError(f"page {page} is not allocated")
        if rc == 1:
            del self._rc[page]
            self._free.append(page)
        else:
            self._rc[page] = rc - 1


class StatePool(PagePool):
    """Refcounted allocator of recurrent-state slots, exactly a
    :class:`PagePool` over slot ids: a live sequence holds its slot from
    admission to retirement, a registry snapshot holds one, and an
    admission that will start from a snapshot shares it until its first
    chunk is on the stream. Slot 0 (the empty state) is never handed
    out."""

    def __init__(self, n_slots: int):
        super().__init__(range(1, n_slots))


@dataclass
class _PrefixNode:
    """One page-sized token run in the prefix radix tree."""

    tokens: tuple[int, ...]
    page: int
    parent: "_PrefixNode | None"
    children: dict[tuple[int, ...], "_PrefixNode"] = field(
        default_factory=dict
    )
    # Content of ``page`` is fully written (the registering sequence's
    # prefill has passed this page's end). Readers — a matching
    # admission's chunk prefill, the boundary-page copy — must wait for
    # this flag; the page ids themselves are safe to map immediately.
    ready: bool = False
    # LRU tick for eviction (registry-maintained).
    last_used: int = 0
    # Recurrent models: a snapshot of the state after this page's last
    # token (a slot of the state pool), and whether it has been written
    # (a promised snapshot has its slot before its content). A page
    # without one cannot be continued from: the state inside it exists
    # nowhere. ``want_state``: some admission's match ended here, or a
    # prompt's last full page does — whoever next finishes this page on
    # a chunk end saves one. ``evicted``: dropped from the tree while a
    # prefilling sequence still held the node.
    state: int | None = None
    state_ready: bool = False
    want_state: bool = False
    evicted: bool = False


@dataclass
class PrefixMatch:
    """What an admission gets back from :meth:`PrefixRegistry.match`."""

    pages: list[int]  # full shared pages, prefix order (refs bumped)
    nodes: list[_PrefixNode]  # their nodes (readiness gates)
    shared_tokens: int  # len(pages) * page_size
    # Boundary page eligible for copy-on-write: its first
    # ``boundary_common`` tokens extend this prompt's prefix past the
    # full-page match. None when no partially-matching sibling exists
    # or its content is not ready yet (copying garbage helps nobody).
    boundary_page: int | None = None
    boundary_common: int = 0


class PrefixRegistry:
    """Radix tree of page-aligned prompt prefixes over one PagePool.

    Nodes are keyed by the exact token tuple of each page-sized run, so
    lookup is a dict walk (no hashing subtleties — the token run IS the
    key). The registry holds one refcount on every node's page; match
    bumps refcounts for the caller (caller releases per page on
    retirement, exactly like privately-allocated pages).

    Registration happens at ADMISSION (before content exists) so that a
    burst of same-prefix requests — the consensus panel — dedups
    against the FIRST request's in-flight prefill instead of racing it;
    ``_PrefixNode.ready`` gates content readers.
    """

    def __init__(
        self, pool: PagePool, page_size: int, states: StatePool | None = None
    ):
        self.pool = pool
        self.page_size = page_size
        # The state slots snapshots live in; None for a model whose
        # every layer keeps pages.
        self.states = states
        self.snapshots_evicted = 0
        self._root = _PrefixNode(tokens=(), page=NULL_PAGE, parent=None)
        self._nodes = 0
        self._tick = 0
        # Monotonic counters (the serving layer exports these).
        self.lookups = 0
        self.hits = 0
        self.pages_shared = 0
        self.pages_copied = 0
        self.evictions = 0
        # Offload tier (PR 4): called ONCE per evict() walk with the
        # list of READY victim nodes, turning eviction from destruction
        # into demotion — the callback spills the pages' content to
        # host RAM keyed by :meth:`chain_tokens`, in one batched host
        # transfer (a per-victim hook would stall admission on N
        # sequential device_gets). None = plain eviction.
        self.on_evict = None

    def __len__(self) -> int:
        return self._nodes

    @property
    def cached_pages(self) -> int:
        return self._nodes

    def reclaimable_pages(self) -> int:
        """Registry pages held by nobody else AND actually freeable via
        :meth:`evict`.

        evict() only ever drops leaves, so an interior node's page is
        reclaimable only when its whole subtree is: a registry-only
        parent above a child some live sequence still maps (refcount
        > 1) can never be reached by eviction and must not be counted —
        counting every refcount-1 node would overstate free capacity
        and break the pool invariant ``available + pinned + reclaimable
        == total`` (evict(∞) frees exactly this number; tested).
        """

        def subtree(node: _PrefixNode) -> tuple[int, bool]:
            total, children_ok = 0, True
            for child in node.children.values():
                n, ok = subtree(child)
                total += n
                children_ok = children_ok and ok
            ok = children_ok and self.pool.refcount(node.page) == 1
            return total + (1 if ok else 0), ok

        return sum(subtree(c)[0] for c in self._root.children.values())

    def _walk(self):
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            yield node

    def match(
        self, ids: Sequence[int], min_boundary: int = 1,
        depth: int | None = None,
    ) -> PrefixMatch:
        """Longest registered page-aligned prefix of ``ids``.

        ``depth`` (recurrent models): map at most that many pages — as
        deep as a state snapshot lets the caller continue from — and
        offer no boundary page (no state exists inside a page).

        Sharing is capped at ``len(ids) - 1`` tokens: at least the last
        prompt token must be (re)computed so the admission has a hidden
        state to sample the first token from. Matched pages' refcounts
        are bumped FOR THE CALLER (release per page on retirement).
        The boundary page (a sibling run extending the match part-way)
        is reported for copy-on-write but NOT ref-bumped — the caller
        copies content, so it allocates its own destination page.

        ``min_boundary``: smallest common run worth a page copy —
        below it the caller recomputes those tokens anyway, and a
        trivial overlap (every prompt shares BOS) must not trigger a
        copy per admission.
        """
        pg = self.page_size
        self.lookups += 1
        self._tick += 1
        node = self._root
        pages: list[int] = []
        nodes: list[_PrefixNode] = []
        # Only prefixes strictly shorter than the prompt are usable.
        usable_full = (len(ids) - 1) // pg
        if depth is not None:
            usable_full = min(usable_full, depth)
        k = 0
        while k < usable_full:
            key = tuple(int(t) for t in ids[k * pg : (k + 1) * pg])
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._tick
            self.pool.share(child.page)
            pages.append(child.page)
            nodes.append(child)
            node = child
            k += 1
        match = PrefixMatch(
            pages=pages,
            nodes=nodes,
            shared_tokens=k * pg,
        )
        # Boundary: a child run whose first tokens extend our prefix
        # but diverge (or run past our prompt) before the page ends.
        rem = tuple(int(t) for t in ids[k * pg :])
        cap = len(rem) - 1  # leave >= 1 token to prefill
        if cap > 0 and depth is None:
            best, best_child = 0, None
            for key, child in node.children.items():
                if not child.ready:
                    continue
                common = 0
                for a, b in zip(key, rem):
                    if a != b:
                        break
                    common += 1
                if common > best:
                    best, best_child = common, child
            if best_child is not None and min(best, cap) >= min_boundary:
                best_child.last_used = self._tick
                match.boundary_page = best_child.page
                match.boundary_common = min(best, cap)
        return match

    def probe(
        self, ids: Sequence[int], whole: bool = False
    ) -> tuple[list[_PrefixNode], int]:
        """Read-only longest-prefix walk: which registered nodes cover
        this prompt's page-aligned prefix, and how many tokens they
        span. NO side effects — no refcount bumps, no LRU ticks, no
        hit/lookup counters — so the fleet router (PR 14) can probe
        every replica per request without perturbing the eviction
        order or the admission-committed hit statistics that
        :meth:`match` + :meth:`record_commit` own.

        Unready nodes COUNT: their page identity is established at
        admission (PR 2), so a concurrent same-prefix burst probes the
        donor's replica as a match while the donor's prefill is still
        in flight — exactly the affinity the router needs.

        ``whole``: walk every FULL page of ``ids``, the last one too
        (the chain a prefilling sequence writes through, as
        :meth:`register` walks it).
        """
        pg = self.page_size
        node = self._root
        nodes: list[_PrefixNode] = []
        usable_full = (len(ids) - (0 if whole else 1)) // pg
        k = 0
        while k < usable_full:
            key = tuple(int(t) for t in ids[k * pg : (k + 1) * pg])
            child = node.children.get(key)
            if child is None:
                break
            nodes.append(child)
            node = child
            k += 1
        return nodes, k * pg

    def record_commit(self, match: PrefixMatch, copied: bool) -> None:
        """Count a match the caller actually ADMITTED on. Kept separate
        from :meth:`match` so a plan that rolls back (pool too full,
        table overflow) never inflates hits/pages_shared — the numbers
        stats() reports must agree with the Prometheus counters,
        which also count only committed admissions."""
        if match.pages or match.boundary_common:
            self.hits += 1
        self.pages_shared += len(match.pages)
        if copied:
            self.pages_copied += 1

    def register(
        self, ids: Sequence[int], pages: Sequence[int]
    ) -> list[tuple[_PrefixNode, int]]:
        """Offer a sequence's full prompt pages to the tree.

        ``pages[i]`` must hold tokens ``ids[i*pg : (i+1)*pg]`` (or be
        about to — see readiness). Runs already present are skipped (the
        existing node keeps its page; ours stays private). Returns the
        [(node, end_position)] list of NEWLY created nodes the caller
        must mark ready (:meth:`mark_ready`) as its prefill writes past
        each ``end_position``.
        """
        pg = self.page_size
        self._tick += 1
        node = self._root
        created: list[tuple[_PrefixNode, int]] = []
        full = min(len(ids) // pg, len(pages))
        for k in range(full):
            key = tuple(int(t) for t in ids[k * pg : (k + 1) * pg])
            child = node.children.get(key)
            if child is None:
                self.pool.share(pages[k])  # the registry's own hold
                child = _PrefixNode(
                    tokens=key, page=pages[k], parent=node
                )
                node.children[key] = child
                self._nodes += 1
                created.append((child, (k + 1) * pg))
            child.last_used = self._tick
            node = child
        return created

    @staticmethod
    def mark_ready(node: _PrefixNode) -> None:
        node.ready = True

    # -- state snapshots (recurrent models) -------------------------------

    def snapshot_nodes(self) -> list[_PrefixNode]:
        return [n for n in self._walk() if n.state is not None]

    def alloc_state(self) -> int | None:
        """One free state slot, dropping the least recently used
        snapshot nobody is waiting on if the pool is empty; None when
        every slot is a live sequence's or a snapshot in use."""
        if not self.states.available and not self.evict_states(1):
            return None
        return self.states.alloc(1)[0]

    def promise_state(self, node: _PrefixNode) -> int | None:
        """Give ``node`` a snapshot slot whose content a prefill in
        flight will write (``state_ready`` stays False until then)."""
        slot = self.alloc_state()
        if slot is not None:
            node.state, node.state_ready = slot, False
        return slot

    def drop_state(self, node: _PrefixNode) -> None:
        if node.state is not None:
            self.states.release(node.state)
            node.state, node.state_ready = None, False
            self.snapshots_evicted += 1

    def evict_states(self, n: int) -> int:
        """Release up to ``n`` written snapshots that only the registry
        holds, least recently used first. Returns how many."""
        idle = sorted(
            (
                node for node in self.snapshot_nodes()
                if node.state_ready and self.states.refcount(node.state) == 1
            ),
            key=lambda node: node.last_used,
        )
        for node in idle[:n]:
            self.drop_state(node)
        return min(n, len(idle))

    @staticmethod
    def chain_tokens(node: _PrefixNode) -> tuple[int, ...]:
        """Every token from the prefix root through ``node``'s page —
        the offload tier's key. A page's K/V content is a function of
        the WHOLE token chain above it (attention reads every earlier
        position), so the page run alone is not a sound identity; the
        full chain is.
        """
        runs: list[tuple[int, ...]] = []
        while node is not None and node.parent is not None:
            runs.append(node.tokens)
            node = node.parent
        return tuple(t for run in reversed(runs) for t in run)

    def evict(self, n_pages: int) -> int:
        """Free up to ``n_pages`` registry-only pages (LRU leaves first).

        Only leaves whose page nobody else holds are dropped — evicting
        a page mapped into a live sequence would free nothing and
        forfeit future sharing. One tree walk total (this runs inside
        the batcher's admission lock): eligible leaves are collected
        once into an LRU heap, and a parent enters the heap only when
        evicting its last child exposes it. Returns pages freed.

        With :attr:`on_evict` set (the offload tier), the READY victims
        are offered to the callback — once, as a batch — before evict()
        returns: demotion, not destruction. Their pages are back on the
        free list by then, but nothing re-WRITES a page until a later
        alloc+prefill/copy enqueues work, and the callback completes
        its host fetch synchronously first. Unready victims — their
        prefill/restore never completed — hold garbage and are dropped
        without a callback.
        """
        import heapq

        heap = [
            (node.last_used, id(node), node)
            for node in self._walk()
            if not node.children and self.pool.refcount(node.page) == 1
        ]
        heapq.heapify(heap)
        freed = 0
        demote: list[_PrefixNode] = []
        while heap and freed < n_pages:
            _, _, victim = heapq.heappop(heap)
            parent = victim.parent
            if self.on_evict is not None and victim.ready:
                demote.append(victim)
            del parent.children[victim.tokens]
            victim.evicted = True
            if victim.state is not None:
                # A snapshot goes with its page (an unready one is
                # shared by the admission waiting on it, whose matched
                # pages also keep this node off the heap).
                self.drop_state(victim)
            self.pool.release(victim.page)
            self._nodes -= 1
            self.evictions += 1
            freed += 1
            if (
                parent is not self._root
                and not parent.children
                and self.pool.refcount(parent.page) == 1
            ):
                heapq.heappush(heap, (parent.last_used, id(parent), parent))
        if demote:
            # Unlinked nodes keep their parent/tokens attrs, so
            # chain_tokens still resolves the full key here.
            self.on_evict(demote)
        return freed


# ---------------------------------------------------------------------------
# Decode groups: which resident sequences share a prefix page run
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class DecodeGroupArrays:
    """Device-side group metadata for the group-aware decode kernel
    (:func:`llm_consensus_tpu.ops.pallas.paged_decode_attention_grouped`).

    All int32. ``group_id`` [max_seqs]: group per row, -1 ungrouped;
    ``group_rep`` [Gm]: a member row whose page table holds the group's
    shared run; ``group_pages`` [Gm]: pages in that run (0 = padding
    slot); ``shared_start`` [max_seqs]: tokens the shared phase covers
    per row (page-aligned; 0 for ungrouped rows).
    """

    group_id: jnp.ndarray
    group_rep: jnp.ndarray
    group_pages: jnp.ndarray
    shared_start: jnp.ndarray


class GroupTracker:
    """Host-side decode-group metadata over shared prefix page runs.

    Every decoding sequence registers its PREFIX RUN — the page ids
    covering its prompt's full pages, in table order. Two runs that
    begin with the same page ids hold the same tokens by construction
    (pages are shared exclusively through the :class:`PrefixRegistry`'s
    refcount mapping, and decode never writes into a full prompt page),
    so sequences are grouped by the longest common prefix of their
    runs: a consensus panel's donor (which allocated and REGISTERED the
    header pages) and its N-1 mappers land in one group even though the
    donor's own run extends past the header. The grouped kernel then
    reads each group's common run once per step; members' remaining
    pages are their suffix.

    Membership updates incrementally at activation/retirement (O(1)
    dict ops); the device arrays rebuild lazily on the next
    :meth:`arrays` after a change. Grouping is one level (common prefix
    per first-page bucket, not a full trie): nested sharing patterns
    degrade to the bucket-wide common run, never to wrong output.
    Single-member buckets emit nothing (reading a run once for one
    reader is what the ungrouped kernel already does) and only the
    ``max_groups`` largest groups emit — overflow rows simply stay
    ungrouped, correct either way.

    Not thread-safe: the continuous batcher's worker owns it, exactly
    like the pools/registries.
    """

    def __init__(
        self, max_seqs: int, page_size: int, max_groups: int | None = None
    ):
        self.max_seqs = max_seqs
        self.page_size = page_size
        self.max_groups = max_groups or max(1, max_seqs // 2)
        self._run_of_seq: dict[int, tuple[int, ...]] = {}
        self._dirty = True
        self._cached: DecodeGroupArrays | None = None
        # Step stats for the arrays most recently built: tokens of KV
        # the grouped read dedups per decode step, and the largest
        # group's member count (the observability satellites).
        # ``peak_group`` is the lifetime high-water mark — the number a
        # post-burst stats() read still sees after every member retired.
        self.saved_tokens_per_step = 0
        self.largest_group = 0
        self.peak_group = 0

    def add(self, seq_id: int, prefix_run: Sequence[int]) -> None:
        """Register a decoding sequence's prompt prefix page run (no-op
        for an empty run — a sub-page prompt stays ungrouped)."""
        run = tuple(int(p) for p in prefix_run)
        self.remove(seq_id)
        if not run:
            return
        self._run_of_seq[seq_id] = run
        self._dirty = True

    def remove(self, seq_id: int) -> None:
        if self._run_of_seq.pop(seq_id, None) is not None:
            self._dirty = True

    def stream_buckets(self) -> list[list[int]]:
        """Registered seqs bucketed by shared FIRST prefix page — the
        candidate sets for panel-shared draft streams (PR 9: members of
        one bucket decode over one prompt header, so a donor's
        committed-suffix + fresh-draft stream is reusable by any mate
        whose committed text still agrees). First-page granularity like
        :meth:`arrays`' grouping; only >= 2-member buckets return."""
        buckets: dict[int, list[int]] = {}
        for seq, run in self._run_of_seq.items():
            buckets.setdefault(run[0], []).append(seq)
        return [sorted(s) for s in buckets.values() if len(s) >= 2]

    @staticmethod
    def _common_prefix(runs: list[tuple[int, ...]]) -> int:
        k = 0
        for pages in zip(*runs):
            if any(p != pages[0] for p in pages[1:]):
                break
            k += 1
        return k

    def arrays(self) -> DecodeGroupArrays | None:
        """Current group metadata as device arrays, or None when no
        group has >= 2 members (the caller then runs the plain
        ungrouped program — the automatic fallback)."""
        if not self._dirty:
            return self._cached
        self._dirty = False
        pg = self.page_size
        buckets: dict[int, list[int]] = {}
        for seq, run in self._run_of_seq.items():
            buckets.setdefault(run[0], []).append(seq)
        groups: list[tuple[int, list[int]]] = []  # (lcp_pages, members)
        for seqs in buckets.values():
            if len(seqs) < 2:
                continue
            lcp = self._common_prefix([self._run_of_seq[s] for s in seqs])
            if lcp > 0:
                groups.append((lcp, sorted(seqs)))
        groups.sort(key=lambda g: -(g[0] * len(g[1])))
        groups = groups[: self.max_groups]
        if not groups:
            self._cached = None
            self.saved_tokens_per_step = 0
            self.largest_group = 0
            return None
        gid = np.full((self.max_seqs,), -1, np.int32)
        rep = np.zeros((self.max_groups,), np.int32)
        gpages = np.zeros((self.max_groups,), np.int32)
        start = np.zeros((self.max_seqs,), np.int32)
        saved = 0
        largest = 0
        for g, (lcp, members) in enumerate(groups):
            rep[g] = members[0]
            gpages[g] = lcp
            largest = max(largest, len(members))
            saved += (len(members) - 1) * lcp * pg
            for s in members:
                gid[s] = g
                start[s] = lcp * pg
        self.saved_tokens_per_step = saved
        self.largest_group = largest
        self.peak_group = max(self.peak_group, largest)
        self._cached = DecodeGroupArrays(
            group_id=jnp.asarray(gid),
            group_rep=jnp.asarray(rep),
            group_pages=jnp.asarray(gpages),
            shared_start=jnp.asarray(start),
        )
        return self._cached
