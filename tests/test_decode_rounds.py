"""Multi-round on-device decode (PR 12).

Contract layers:

- STEP MASKING: ``decode_step_paged(write_mask=...)`` freezes a row —
  no K/V lands in its real pages, its length holds — while neighbors
  step normally.
- STOP MACHINERY: ``utils.stops.derived_stop_screen`` yields a bounded
  conservative candidate set (or None when none exists), and
  ``single_token_stop_ids`` is the engine's shared exact-terminator
  derivation.
- BATCHER: with ``decode_rounds`` R > 1, ONE device program runs up to
  R decode rounds (stop scan + sampling + emit/length bookkeeping on
  device; early-exit masking) and the host fetches once per window —
  text BYTE-IDENTICAL to R = 1 across pipeline depths, prefill-chunk
  widths, staggered panel retirement, stop tokens and max-tokens
  budgets landing mid-window (with no K/V written past the stop),
  eviction + host-tier restores with multi-round programs in flight,
  speculation composed and flipped live, and sampled (PRNG-addressed)
  rows — plus metrics/flight lockstep and the dispatch count the
  feature exists for: device programs per generated token drop >= 3x
  at R = 4.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import NULL_PAGE, PagedKVCache
from llm_consensus_tpu.models.transformer import (
    decode_step_paged,
    init_params,
)
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.utils.stops import (
    derived_stop_screen,
    single_token_stop_ids,
)

CFG = get_config("test-tiny")

_HEADER = "Panel shared header for every persona, forty ch: "

_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=96,
    pages_per_seq=10,
    max_new_tokens=8,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _serve(batcher, prompts, **kw):
    futs = [batcher.submit(p, **kw) for p in prompts]
    return [f.result(timeout=180) for f in futs]


def _quiesce(batcher, timeout=20.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        s = batcher.stats()
        if (
            s["active_slots"] == 0
            and s["prefilling_slots"] == 0
            and s["dispatch_inflight"] == 0
            and s["waiting"] == 0
        ):
            return s
        time.sleep(0.01)
    raise AssertionError(f"batcher did not quiesce: {batcher.stats()}")


def _burst(params, rounds, prompts, cfgkw=None, submit_kw=None):
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(
            **(cfgkw or _CCFG), decode_rounds=rounds
        ),
    )
    try:
        outs = _serve(b, prompts, **(submit_kw or {}))
        _quiesce(b)
        return [(o.text, o.num_tokens) for o in outs], b.stats()
    finally:
        b.close()


def _real_page_writes(batcher):
    """Set of non-NULL (page, offset) positions holding any K/V, and
    the full non-NULL planes — the KV footprint assertions compare
    these between R values (the NULL page is the sanctioned garbage
    sink for inactive and frozen rows and is excluded)."""
    k = np.asarray(batcher.cache.k)
    v = np.asarray(batcher.cache.v)
    nz = (np.abs(k[:, 1:]).sum(axis=(0, 3, 4)) > 0) | (
        np.abs(v[:, 1:]).sum(axis=(0, 3, 4)) > 0
    )
    return nz, k[:, 1:], v[:, 1:]


# ---------------------------------------------------------------------------
# Step masking (models/transformer.py)
# ---------------------------------------------------------------------------


def test_write_mask_freezes_row(params):
    """A frozen row's real pages and length are untouched by a masked
    decode step; live rows write and advance exactly as unmasked."""
    cache = PagedKVCache.create(CFG, n_pages=8, page_size=4, max_seqs=2,
                                pages_per_seq=2)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    cache = PagedKVCache(
        k=cache.k, v=cache.v, page_table=table,
        length=jnp.asarray([2, 3], jnp.int32),
    )
    toks = jnp.asarray([[5], [6]], jnp.int32)
    mask = jnp.asarray([True, False])
    _, out = decode_step_paged(CFG, params, toks, cache, write_mask=mask)
    assert out.length.tolist() == [3, 3]  # row 1 frozen
    k = np.asarray(out.k)
    # Row 0 wrote position 2 -> page 1 offset 2; row 1's would-be write
    # (page 3 offset 3) was redirected to the NULL page.
    assert np.abs(k[:, 1, 2]).sum() > 0
    assert np.abs(k[:, 3, 3]).sum() == 0
    assert np.abs(k[:, NULL_PAGE, 3]).sum() > 0


# ---------------------------------------------------------------------------
# Derived-stop machinery (utils/stops.py)
# ---------------------------------------------------------------------------


def test_derived_stop_screen_byte_tokenizer():
    tok = ByteTokenizer()
    assert derived_stop_screen(tok, ()) == ()
    scr = derived_stop_screen(tok, ("ab",))
    assert scr is not None
    # The completing byte's id must be screened (conservatively).
    (b_id,) = tok.encode("b", add_bos=False)
    assert b_id in scr
    # The non-final byte's id need not be.
    (a_id,) = tok.encode("a", add_bos=False)
    assert a_id not in scr
    # Ids that decode to nothing alone (specials) stay screened: their
    # contribution is invisible to the per-id byte check.
    assert all(tok.decode([i]) == "" or i == b_id for i in scr)


def test_derived_stop_screen_bounds():
    tok = ByteTokenizer()
    # Many distinct final bytes blow the max_ids cap -> None (the
    # batcher then bounds the window to 1 round).
    many = tuple("stop" + c for c in "abcdefghij")
    assert derived_stop_screen(tok, many, max_ids=8) is None

    class _Huge:
        vocab_size = 1 << 20

    assert derived_stop_screen(_Huge(), ("x",)) is None


def test_single_token_stop_ids_shared_with_engine():
    tok = ByteTokenizer()
    assert single_token_stop_ids(tok, ("a",)) == tuple(
        tok.encode("a", add_bos=False)
    )
    # Multi-token stops are not exact device terminators.
    assert single_token_stop_ids(tok, ("ab",)) == ()
    from llm_consensus_tpu.engine.engine import InferenceEngine

    assert InferenceEngine._stop_ids.__doc__  # the engine shares it


# ---------------------------------------------------------------------------
# Byte parity: R x depth x chunk grid over a staggered panel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [16, 32])
def test_parity_grid(params, chunk):
    """R in {1, 2, 4} x depth in {1, 2}: the shared-prefix panel with
    STAGGERED caps (members retire at different windows from the
    lagged mirror, shrinking the decode group mid-flight) serves
    byte-identical text and token counts everywhere."""
    prompts = [_HEADER + f"persona {i} answers" for i in range(4)]
    caps = [2, 7, 5, 8]
    cfgkw = dict(_CCFG, prefill_chunk=chunk)

    def run(rounds, depth):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(
                **cfgkw, decode_rounds=rounds, pipeline_depth=depth
            ),
        )
        try:
            futs = [
                b.submit(p, max_new_tokens=c)
                for p, c in zip(prompts, caps)
            ]
            return [
                (f.result(timeout=180).text,
                 f.result(timeout=180).num_tokens)
                for f in futs
            ]
        finally:
            b.close()

    want = run(1, 1)
    for rounds in (2, 4):
        for depth in (1, 2):
            assert run(rounds, depth) == want, (rounds, depth)


def test_prng_count_invariance_sampled(params):
    """Sampled rows: per-request streams are (seed, output-index)
    addressed, and a frozen row folds nothing — so the emitted token
    sequence is R-invariant even at temperature > 0."""
    prompts = [_HEADER + f"sampled {i}" for i in range(4)]
    kw = dict(temperature=0.9, seed=11, top_k=7)
    want, _ = _burst(params, 1, prompts, submit_kw=kw)
    got, _ = _burst(params, 4, prompts, submit_kw=kw)
    assert got == want


# ---------------------------------------------------------------------------
# Early-exit masking: stop / max-tokens mid-window, no KV past the stop
# ---------------------------------------------------------------------------


def _footprint_run(params, rounds, submit_kw):
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(
            **dict(_CCFG, max_new_tokens=16),
            decode_rounds=rounds,
            pipeline_depth=1,
        ),
    )
    try:
        [out] = _serve(b, [_HEADER + "stop probe"], **submit_kw)
        _quiesce(b)
        nz, k, v = _real_page_writes(b)
        return (out.text, out.num_tokens), nz, k, v
    finally:
        b.close()


def test_stop_token_mid_window_freezes_and_writes_no_kv(params):
    """A stop sequence hit inside an R=4 window: the row freezes on
    device (conservative screen + host byte confirm), the text is
    byte-identical to R=1, and the REAL-page KV footprint — positions
    and values — is exactly the R=1 footprint: nothing written past
    the stop."""
    (free, _), _, _, _ = _footprint_run(params, 1, {})
    assert len(free) >= 4
    mid = len(free) // 2
    stop = free[mid : mid + 2]
    want, nz1, k1, v1 = _footprint_run(params, 1, dict(stop=[stop]))
    got, nz4, k4, v4 = _footprint_run(params, 4, dict(stop=[stop]))
    assert got == want
    assert want[1] < 16  # the stop really ended decoding early
    assert np.array_equal(nz1, nz4)
    assert np.array_equal(k1, k4) and np.array_equal(v1, v4)


def test_max_tokens_mid_window_freezes_and_writes_no_kv(params):
    """max_new_tokens reached mid-window: same contract as a stop —
    identical text and identical real-page KV writes vs R=1 (the
    budget check is exact on device at depth 1)."""
    want, nz1, k1, v1 = _footprint_run(
        params, 1, dict(max_new_tokens=3)
    )
    got, nz4, k4, v4 = _footprint_run(
        params, 4, dict(max_new_tokens=3)
    )
    assert got == want and want[1] == 3
    assert np.array_equal(nz1, nz4)
    assert np.array_equal(k1, k4) and np.array_equal(v1, v4)


def test_unscreenable_stop_bounds_window_to_one_round(params):
    """A request whose stops admit no bounded screen collapses every
    window it rides to ONE round (host-checked cadence) — and text
    parity holds regardless."""
    prompts = [_HEADER + "unscreenable"]
    stop = ("\x7fnever-hit\x7f",)

    def run(rounds):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**_CCFG, decode_rounds=rounds),
        )
        try:
            # Poison the memoized screen: stand-in for a tokenizer
            # whose vocabulary admits no bounded candidate set.
            b._screen_cache[stop] = None
            outs = _serve(b, prompts, stop=list(stop))
            s = _quiesce(b)
            return [(o.text, o.num_tokens) for o in outs], s
        finally:
            b.close()

    want, _ = run(1)
    got, st = run(4)
    assert got == want
    # Every decode-advancing window the row rode collapsed to 1 round.
    assert st["decode_rounds_count"] > 0
    assert st["decode_rounds_sum"] == st["decode_rounds_count"]


def test_screen_cache_bounded(params):
    """The derived-screen memo is evict-oldest bounded: stop tuples
    are client-supplied, so per-request-unique stops must not grow a
    long-running batcher without bound."""
    from llm_consensus_tpu.serving import continuous as C

    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        for i in range(C._SCREEN_CACHE_MAX):
            b._screen_cache[(f"synthetic-{i}",)] = ()
        b.submit(
            _HEADER + "cache probe", max_new_tokens=2, stop=["zz"]
        ).result(timeout=120)
        assert len(b._screen_cache) <= C._SCREEN_CACHE_MAX
        assert ("zz",) in b._screen_cache
        assert ("synthetic-0",) not in b._screen_cache  # oldest evicted
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Interactions: host-tier round trip, speculation, live flips
# ---------------------------------------------------------------------------


def test_eviction_and_host_restore_with_rounds_in_flight(params):
    """Demote/restore under multi-round windows: the panel's header
    pages round-trip through the host tier while R=4 programs are in
    flight, with text parity and the same restore count as R=1."""
    cfgkw = dict(
        max_slots=2,
        page_size=16,
        n_pages=17,  # 16 usable vs a 2x8-page unshared working set
        pages_per_seq=10,
        max_new_tokens=6,
        seq_buckets=(16, 32, 64),
        prefill_chunk=16,
        share_prefix=True,
        host_cache_bytes=8 << 20,
    )
    rounds_bursts = [
        [_HEADER + f"p{i} proposes" for i in range(2)],
        [
            f"{i} unique filler storm with plenty of padding text {i}"
            for i in range(4)
        ],
        [_HEADER + f"r{i} re-votes" for i in range(2)],
    ]

    def run(rounds):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**cfgkw, decode_rounds=rounds),
        )
        try:
            texts = []
            for burst in rounds_bursts:
                texts.append([x.text for x in _serve(b, burst)])
            return texts, b.stats()
        finally:
            b.close()

    want, st1 = run(1)
    got, st4 = run(4)
    assert got == want
    assert st4["offload_restored_pages"] >= 1
    assert st4["offload_restored_pages"] == st1["offload_restored_pages"]


def test_spec_compose_and_live_flips(params):
    """decode_rounds and spec decode configured together: spec windows
    keep one verify round per dispatch, plain windows run R rounds,
    and live spec_decode flips drain the pipeline between modes —
    text identical to the no-draft R=1 baseline in every phase."""
    prompts = [_HEADER + f"member {i}" for i in range(4)]
    base = dict(_CCFG, n_pages=128, pages_per_seq=12)
    want, _ = _burst(params, 1, prompts, cfgkw=base)

    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**base, spec_k=3, decode_rounds=4),
        draft=(CFG, params),  # self-draft: high acceptance
    )
    try:
        for spec_on in (True, False, True):
            b.config.spec_decode = spec_on
            outs = _serve(b, prompts)
            assert [(o.text, o.num_tokens) for o in outs] == want, spec_on
        s = _quiesce(b)
    finally:
        b.close()
    # Both program families ran; every decode-advancing program
    # observed its rounds (spec = 1 per verify round).
    assert s["device_programs_spec"] > 0
    assert s["device_programs_decode"] > 0
    assert s["decode_rounds_count"] == (
        s["device_programs_spec"]
        + s["device_programs_decode"]
        + s["device_programs_fused"]
    )


# ---------------------------------------------------------------------------
# Metrics + flight lockstep
# ---------------------------------------------------------------------------


def test_rounds_metrics_prometheus_stats_lockstep(params):
    """gateway_device_rounds_total / gateway_decode_rounds_per_program
    move by the batcher's own stats() deltas — one site, two
    surfaces."""
    from llm_consensus_tpu.server.metrics import (
        DECODE_ROUNDS_PER_PROGRAM,
        DEVICE_ROUNDS,
    )

    before = (
        DEVICE_ROUNDS.value,
        DECODE_ROUNDS_PER_PROGRAM.count,
        DECODE_ROUNDS_PER_PROGRAM.sum,
    )
    _, st = _burst(
        params, 4, [_HEADER + f"lockstep {i}" for i in range(3)]
    )
    assert DEVICE_ROUNDS.value - before[0] == st["device_rounds_total"]
    assert (
        DECODE_ROUNDS_PER_PROGRAM.count - before[1]
        == st["decode_rounds_count"]
    )
    assert DECODE_ROUNDS_PER_PROGRAM.sum - before[2] == pytest.approx(
        st["decode_rounds_sum"]
    )
    # A round emits at most one
    # token per row, and a window folds up to R rounds per program.
    assert st["device_rounds_total"] >= st["decode_rounds_count"]
    assert st["decode_rounds_sum"] <= 4 * st["decode_rounds_count"]


def test_flight_program_events_carry_rounds_and_stay_count_exact(params):
    """PROGRAM flight events for multi-round programs carry ``rounds``
    in meta, and the Chrome device track still holds exactly the
    programs gateway_device_programs_total counted at R > 1."""
    import json

    from llm_consensus_tpu.server.metrics import REGISTRY
    from llm_consensus_tpu.serving import flight

    def programs_total():
        return sum(
            v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("gateway_device_programs_total")
        )

    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**_CCFG, decode_rounds=4)
    )
    try:
        _serve(b, [_HEADER + "warm"], max_new_tokens=4)
        _quiesce(b)
        flight.flight_recorder().clear()
        before = programs_total()
        _serve(b, [_HEADER + f"flight {i}" for i in range(3)])
        _quiesce(b)
        delta = programs_total() - before
    finally:
        b.close()
    evs = flight.flight_recorder().events()
    prog = [e for e in evs if e.kind == "program"]
    assert len(prog) == delta > 0
    dec = [e for e in prog if e.meta.get("kind") in ("decode", "fused")]
    assert dec and all("rounds" in e.meta for e in dec)
    assert any(e.meta["rounds"] == 4 for e in dec)
    doc = json.loads(json.dumps(flight.to_chrome(evs)))
    dev = [
        e
        for e in doc["traceEvents"]
        if e.get("cat") == "device" and e["ph"] == "X"
    ]
    # Count-exact at R > 1: one slice still means one program; its
    # ``rounds`` arg says how much decoding it held.
    assert len(dev) == delta
    assert any(e["args"].get("rounds") == 4 for e in dev)


# ---------------------------------------------------------------------------
# The dispatch count: programs per generated token, R = 1 against R = 4
# ---------------------------------------------------------------------------


def test_programs_per_token_drop_3x_at_r4_on_one_batcher(params):
    """The same greedy panel burst through ONE batcher with
    ``decode_rounds`` flipped 4 <-> 1 between bursts: byte-identical
    text, and device programs per generated token (every kind: the
    prefill chunks both sides pay are in both counts) at least 3x lower
    at R = 4."""
    kinds = ("fused", "decode", "prefill", "spec", "draft")
    prompts = [_HEADER + f"persona {i}" for i in range(4)]
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(
            **dict(_CCFG, prefill_chunk=64, max_new_tokens=64),
            decode_rounds=4,
        ),
    )

    def burst(rounds):
        b.config.decode_rounds = rounds
        s0 = _quiesce(b)
        outs = _serve(b, prompts)
        s1 = _quiesce(b)
        programs = sum(
            s1[f"device_programs_{k}"] - s0[f"device_programs_{k}"]
            for k in kinds
        )
        tokens = s1["generated_tokens"] - s0["generated_tokens"]
        assert tokens == sum(o.num_tokens for o in outs) > 0
        return [(o.text, o.num_tokens) for o in outs], programs / tokens

    try:
        # Both program families built first; the registry then holds
        # the header for every measured burst alike.
        for rounds in (4, 1):
            b.config.decode_rounds = rounds
            _serve(b, [_HEADER + f"warm {rounds}"], max_new_tokens=6)
        text4, ppt4 = burst(4)
        text1, ppt1 = burst(1)
    finally:
        b.close()
    assert text4 == text1
    assert ppt1 / ppt4 >= 3.0, (ppt1, ppt4)
