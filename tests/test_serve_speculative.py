"""Speculative decoding inside the continuous batcher (PR 9).

Contract layers:

- ACCEPT RULE: `engine.accept.verify_row` / `verify_tokens` pin to the
  standalone `engine.speculative.speculative_generate` decisions — the
  parity oracle (same greedy-match / leviathan one-hot math, one shared
  `leviathan_accept`).
- KERNEL/REFERENCE: the ragged attention verify lane ([B, NQ, H, D]
  decode rows) matches the XLA reference on mixed shapes.
- BATCHER: with a draft model + `spec_k > 0`, each round dispatches ONE
  draft/verify/accept device program emitting a ragged budget of
  verified tokens per slot. Greedy text is BYTE-IDENTICAL to spec-off
  for any draft — across pipeline depths, chunk widths, spec_k,
  sliding windows, rollbacks landing on page boundaries, panel members
  diverging from a shared draft stream, eviction + host-tier restores
  in flight, and a zero-acceptance draft (which degrades to plain
  decode progress, never a livelock).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine.accept import (
    leviathan_accept,
    verify_row,
    verify_tokens,
)
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

CFG = get_config("test-tiny")
DCFG = get_config("test-tiny-draft")

_CCFG = dict(
    max_slots=4,
    page_size=16,
    n_pages=96,
    pages_per_seq=10,
    max_new_tokens=10,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)

_HEADER = "Panel shared header for every persona, forty ch: "


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def dparams():
    # Random draft weights: proposes garbage, accepts ~nothing — the
    # adversarial draft. Correctness must not depend on acceptance.
    return init_params(DCFG, jax.random.PRNGKey(1), dtype=jnp.float32)


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
    return batcher.stats()


def _burst(params, draft, spec_k, depth=2, chunk=16, cfg=CFG, cfgkw=None,
           prompts=None, **submit_kw):
    ccfg = dict(_CCFG, prefill_chunk=chunk)
    ccfg.update(cfgkw or {})
    b = ContinuousBatcher(
        cfg,
        params,
        config=ContinuousConfig(
            **ccfg, pipeline_depth=depth, spec_k=spec_k
        ),
        draft=draft,
    )
    prompts = prompts or [
        _HEADER + "alpha tail one",
        _HEADER + "beta tail two",
        "unrelated prompt entirely",
        _HEADER + "gamma tail three",
    ]
    try:
        return [r.text for r in _serve(b, prompts, **submit_kw)], b.stats()
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Accept rule: pinned to the standalone oracle's decisions
# ---------------------------------------------------------------------------


def _oracle_row(logits, drafts, temperature, keys):
    """The standalone ``speculative_generate`` verify math for one row,
    re-composed from its building blocks (decode_chunk's argmax chain +
    the one-hot leviathan call) — the decisions `verify_row` must pin
    to exactly."""
    k = drafts.shape[0]
    v = logits.shape[-1]
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy = temperature <= 0.0
    if greedy:
        match = drafts == targets[:k]
        fix_of = targets
    else:
        p = jax.nn.softmax(logits / jnp.maximum(temperature, 1e-6), axis=-1)
        q = jnp.concatenate(
            [jax.nn.one_hot(drafts, v, dtype=p.dtype), jnp.zeros((1, v))]
        )
        d_pad = jnp.pad(drafts, (0, 1))
        coin, corr = jax.vmap(leviathan_accept)(p, q, d_pad, keys)
        match = coin[:k]
        fix_of = corr
    acc = jnp.cumprod(match.astype(jnp.int32))
    n_acc = int(jnp.sum(acc))
    emit = list(np.asarray(drafts[:n_acc])) + [int(fix_of[n_acc])]
    return emit, n_acc + 1


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_verify_row_pins_to_standalone_accept(temperature):
    rng = np.random.default_rng(7)
    k, v = 4, 48
    for trial in range(8):
        logits = jnp.asarray(rng.standard_normal((k + 1, v)), jnp.float32)
        # Mix of agreeing and disagreeing drafts.
        greedy_t = np.asarray(jnp.argmax(logits, axis=-1))
        drafts = np.where(
            rng.random(k) < 0.5, greedy_t[:k], rng.integers(0, v, k)
        ).astype(np.int32)
        keys = jax.vmap(
            lambda j: jax.random.fold_in(jax.random.PRNGKey(trial), j)
        )(jnp.arange(k + 1))
        emit, cnt = verify_row(
            logits, jnp.asarray(drafts), jnp.float32(temperature), keys
        )
        want_emit, want_cnt = _oracle_row(
            logits, jnp.asarray(drafts), temperature, keys
        )
        assert int(cnt) == want_cnt
        assert list(np.asarray(emit[:cnt])) == want_emit


def test_verify_tokens_greedy_is_argmax_chain_and_filter_invariant():
    """Greedy rows: the emitted chain equals the per-position argmax,
    and top-k/top-p filters (which keep the argmax) cannot change it."""
    rng = np.random.default_rng(8)
    b, k, v = 3, 3, 32
    logits = jnp.asarray(rng.standard_normal((b, k + 1, v)), jnp.float32)
    greedy_t = np.asarray(jnp.argmax(logits, axis=-1))
    drafts = jnp.asarray(greedy_t[:, :k]).at[1, 1].add(1)  # row 1 rejects @1
    temps = jnp.zeros((b,), jnp.float32)
    keys = jnp.broadcast_to(
        jax.random.PRNGKey(0), (b, k + 1, 2)
    )
    for fa, tk in ((False, 0), (True, 5)):
        for ag in (False, True):
            # all_greedy=True is the batcher's static fast path (no
            # leviathan machinery) — bit-identical to the general path
            # on greedy rows.
            emit, cnt = verify_tokens(
                logits, drafts, temps,
                jnp.full((b,), tk, jnp.int32),
                jnp.full((b,), 0.9, jnp.float32),
                keys, filters_active=fa, all_greedy=ag,
            )
            assert list(np.asarray(cnt)) == [k + 1, 2, k + 1]
            for i in range(b):
                n = int(cnt[i])
                assert list(np.asarray(emit[i, :n])) == list(greedy_t[i, :n])


# ---------------------------------------------------------------------------
# Ragged verify lane: kernel vs XLA reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [0, 9])
def test_ragged_verify_rows_match_reference(window):
    """[B, NQ, H, D] verify rows — with a chunk lane riding along and a
    sliding window — match the reference's chunk_decode_attention rule
    per row (the kernel's nq > 1 decode lane, PR 9). Fills >= nq,
    mid-block."""
    from llm_consensus_tpu.ops.pallas import parity

    errs = parity.ragged_attention_error(
        seed=11, pg=8, hkv=2, g=3, d=32, p_per=6, n_pages=40,
        valid_len=[13, 5, 40, 23], nq=3, cq=8, chunk_start=11,
        window=window, interpret=True,
    )
    for lane in ("decode", "chunk"):
        parity.check(lane, errs[lane], parity.ATTENTION_TOL)


@pytest.mark.parametrize("window", [0, 9])
def test_ragged_verify_rows_grouped_match_reference(window):
    """Verify rows through the GROUP phase: every member query stacks
    against one read of the shared run; output equals the ungrouped
    reference."""
    from llm_consensus_tpu.ops.pallas import parity

    errs = parity.ragged_attention_error(
        seed=12, pg=8, hkv=2, g=3, d=32, p_per=6, n_pages=40,
        valid_len=[13, 9, 40, 23], nq=3, group_rows=(0, 2, 3),
        window=window, interpret=True,
    )
    parity.check("decode", errs["decode"], parity.ATTENTION_TOL)


# ---------------------------------------------------------------------------
# Batcher: spec-on vs spec-off byte parity
# ---------------------------------------------------------------------------


def test_spec_text_parity_grid(params, dparams):
    """THE acceptance contract: greedy text byte-identical spec-on vs
    spec-off across pipeline depth {1,2} x chunk {16,32} x spec_k
    {2,4}, with the adversarial (random-weights) draft — the draft
    only ever affects speed."""
    want, _ = _burst(params, None, 0, depth=1, chunk=16)
    for depth in (1, 2):
        for chunk in (16, 32):
            for spec_k in (2, 4):
                got, st = _burst(
                    params, (DCFG, dparams), spec_k, depth=depth, chunk=chunk
                )
                assert got == want, (depth, chunk, spec_k)
                assert st["device_programs_spec"] >= 1


def test_spec_parity_high_acceptance_self_draft(params):
    """Self-draft (target as its own draft): acceptance near 1, rounds
    emit multiple tokens per program, text still byte-identical."""
    want, _ = _burst(params, None, 0, depth=1)
    got, st = _burst(params, (CFG, params), 4)
    assert got == want
    # Multi-token rounds actually happened: fewer spec programs than
    # generated tokens, and a healthy acceptance mean.
    toks = st["generated_tokens"]
    assert st["device_programs_spec"] < toks
    acc = st["spec_acceptance_sum"] / max(1, st["spec_acceptance_count"])
    assert acc > 0.5
    assert st["spec_accepted_tokens"] > 0


def test_spec_sliding_window_config_parity(params, dparams):
    """The windowed config rides the same verify lane (per-query window
    edges inside the ragged mask) — parity must hold there too."""
    wcfg = CFG.with_(sliding_window=24)
    want, _ = _burst(params, None, 0, depth=1, cfg=wcfg)
    for draft in ((DCFG, dparams), (CFG, params)):
        got, _ = _burst(params, draft, 3, cfg=wcfg)
        assert got == want


def test_spec_rollback_on_page_boundary(params, dparams):
    """Zero-acceptance rollback landing exactly on page boundaries:
    page_size 16 prompts sized to put early verify rounds astride a
    boundary — the rejected tail's K/V crosses into the next private
    page and is rewound by count bookkeeping alone. Parity is the
    proof the garbage never leaks into attention."""
    # prompt of exactly 15/16/17 tokens: first verify rounds write
    # across position 16 (the page-1 edge) in every alignment.
    prompts = ["x" * 15, "y" * 16, "z" * 17, _HEADER + "boundary"]
    want, _ = _burst(params, None, 0, depth=1, prompts=prompts)
    for spec_k in (2, 4):
        got, st = _burst(
            params, (DCFG, dparams), spec_k, prompts=prompts
        )
        assert got == want, spec_k
        # The adversarial draft really was rejected ~always.
        acc = st["spec_acceptance_sum"] / max(1, st["spec_acceptance_count"])
        assert acc < 0.5


def test_spec_zero_acceptance_never_livelocks(params, dparams):
    """A draft that accepts nothing degrades to >= plain-decode
    progress: every round still emits the correction token, so the
    burst completes (within the future timeout) with byte-identical
    text and every request retired."""
    want, _ = _burst(params, None, 0, depth=1)
    got, st = _burst(params, (DCFG, dparams), 4)
    assert got == want
    assert st["active_slots"] == 0 and st["waiting"] == 0
    assert st["completed_requests"] >= 4
    # Progress floor: one spec program never emits fewer tokens than a
    # plain decode step would have.
    assert st["generated_tokens"] >= st["device_programs_spec"]


def test_spec_shared_stream_and_mid_group_divergence(params):
    """The panel amortization: members over one header share the
    donor's draft stream while their committed texts agree, and a
    member that diverges (different tail -> different greedy output)
    drops back to its own stream while the group keeps decoding.
    Greedy parity holds throughout; the unique-prompt control run
    shares nothing."""
    # Three members share the whole prompt (identical committed text —
    # greedy mates agree forever, staggered activations catch up via
    # the donor's committed-suffix fill); the fourth carries its own
    # tail, so its greedy output diverges from the donor's committed
    # text and it drafts for itself while the group keeps decoding.
    panel = [_HEADER + "same question"] * 3 + [_HEADER + "diverging tail"]
    want, _ = _burst(params, None, 0, depth=1, prompts=panel)
    got, st = _burst(params, (CFG, params), 4, prompts=panel)
    assert got == want
    assert st["spec_shared_draft_rows"] > 0
    # Distinct from byte 0 — with page_size 16, prompts differing only
    # mid-string would still share their first page (and legitimately
    # group); the control must not.
    unique = [f"{i} <- unique prompt with its own header" for i in range(4)]
    _, st_u = _burst(params, (CFG, params), 4, prompts=unique)
    assert st_u["spec_shared_draft_rows"] == 0
    # Sharing reduced draft tokens per generated token vs the
    # per-sequence control (the ISSUE's amortization gate, CPU-sized).
    rate_panel = st["spec_draft_tokens"] / max(1, st["generated_tokens"])
    rate_unique = st_u["spec_draft_tokens"] / max(1, st_u["generated_tokens"])
    assert rate_panel < rate_unique


def test_spec_eviction_and_host_restore_in_flight(params, dparams):
    """Host-tier demote/restore with speculation engaged: the draft
    pool's planes travel with the target's (4-plane store entries), the
    restored prefix keeps draft context, and text parity holds across
    the eviction round trip."""
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
    rounds = [
        [_HEADER + f"p{i} proposes" for i in range(2)],
        [
            f"{i} unique filler storm with plenty of padding text {i}"
            for i in range(4)
        ],
        [_HEADER + f"r{i} re-votes" for i in range(2)],
    ]

    def run(draft, spec_k):
        b = ContinuousBatcher(
            CFG,
            params,
            config=ContinuousConfig(**cfgkw, spec_k=spec_k),
            draft=draft,
        )
        try:
            texts = []
            for r in rounds:
                texts.append([x.text for x in _serve(b, r)])
            return texts, b.stats()
        finally:
            b.close()

    want, st_off = run(None, 0)
    got, st_on = run((DCFG, dparams), 3)
    assert got == want
    assert st_on["offload_restored_pages"] >= 1
    assert st_on["offload_restored_pages"] == st_off["offload_restored_pages"]


def test_spec_flip_on_one_batcher(params):
    """config.spec_decode is the live A/B lever: one batcher serves a
    spec-on burst then a spec-off burst, both byte-identical to the
    no-draft baseline (the flip drains the pipeline, so plain and spec
    programs never share a window)."""
    prompts = [_HEADER + "flip one", _HEADER + "flip two"]
    want, _ = _burst(params, None, 0, depth=1, prompts=prompts)
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**_CCFG, spec_k=3),
        draft=(CFG, params),
    )
    try:
        on = [r.text for r in _serve(b, prompts)]
        _quiesce(b)
        st_on = b.stats()
        b.config.spec_decode = False
        off = [r.text for r in _serve(b, prompts)]
        _quiesce(b)
        st_off = b.stats()
    finally:
        b.close()
    assert on == want and off == want
    assert st_on["device_programs_spec"] >= 1
    assert st_off["device_programs_spec"] == st_on["device_programs_spec"]
    assert st_off["device_programs_decode"] > st_on["device_programs_decode"]


def test_spec_flip_mid_decode_replays_draft_mirror(params):
    """spec_decode flipped OFF with rows mid-decode and back ON: the
    plain window advances only the target cache, so each surviving
    row's draft mirror goes stale (``_Slot.draft_lag``). Re-engaging
    must replay the window through the draft (chunk-wide + width-1
    catch-up programs) and re-install the row's draft length BEFORE
    the next spec dispatch — a stale mirror would write the row's next
    draft K/V at shifted positions and collapse the self draft's
    acceptance for the rest of the row's life (text parity would still
    hold; what dies is the speedup the flip is supposed to resume)."""
    cfgkw = dict(_CCFG, max_new_tokens=32)
    # Unique prompts: no shared-prefix group, every row drafts for
    # itself — acceptance isolates the mirror's health from the
    # shared-stream machinery.
    prompts = [
        "unrelated prompt one entirely",
        "second distinct prompt here",
    ]
    want, _ = _burst(
        params, None, 0, depth=1, cfgkw=dict(max_new_tokens=32),
        prompts=prompts, max_new_tokens=32,
    )
    b = ContinuousBatcher(
        CFG, params,
        config=ContinuousConfig(**cfgkw, spec_k=3),
        draft=(CFG, params),
    )
    b.config.spec_decode = False  # rows enter decode under PLAIN rounds
    state = {"plain": 0, "draft_at_flip": None}
    real_dispatch = b._dispatch

    def flipping_dispatch(chunk_idx=None, spec=False, **kw):
        if not spec:
            state["plain"] += 1
            # Past one full chunk width of lag (20 > prefill_chunk 16):
            # the replay exercises the chunk-wide window AND the
            # width-1 tail.
            if state["plain"] == 20:
                state["draft_at_flip"] = b.stats()["device_programs_draft"]
                b.config.spec_decode = True
        real_dispatch(chunk_idx, spec=spec, **kw)

    b._dispatch = flipping_dispatch
    try:
        texts = [r.text for r in _serve(b, prompts, max_new_tokens=32)]
        st = _quiesce(b)
    finally:
        b.close()
    assert texts == want
    assert state["plain"] >= 20 and st["device_programs_spec"] >= 1
    # The replay ran: catch-up draft programs beyond the admission
    # chunk mirrors.
    assert st["device_programs_draft"] > state["draft_at_flip"]
    # ...and restored the mirror: the self draft proposes the target's
    # own greedy chain again, so post-flip rounds keep accepting.
    acc = st["spec_acceptance_sum"] / max(1, st["spec_acceptance_count"])
    assert acc > 0.9


def test_spec_metrics_prometheus_stats_lockstep(params):
    """The four PR-9 Prometheus families move by the batcher's own
    stats() deltas — one instrumentation site, two surfaces."""
    from llm_consensus_tpu.server.metrics import (
        DEVICE_PROGRAMS,
        SPEC_ACCEPTANCE,
        SPEC_ACCEPTED_TOKENS,
        SPEC_DRAFT_TOKENS,
        SPEC_VERIFIED_TOKENS,
    )

    before = {
        "drafted": SPEC_DRAFT_TOKENS.value,
        "accepted": SPEC_ACCEPTED_TOKENS.value,
        "acc_count": SPEC_ACCEPTANCE.count,
        "acc_sum": SPEC_ACCEPTANCE.sum,
        "spec": DEVICE_PROGRAMS.labels(kind="spec").value,
        "draft": DEVICE_PROGRAMS.labels(kind="draft").value,
    }
    _, st = _burst(params, (CFG, params), 3)
    assert SPEC_DRAFT_TOKENS.value - before["drafted"] == (
        st["spec_draft_tokens"]
    )
    assert SPEC_ACCEPTED_TOKENS.value - before["accepted"] == (
        st["spec_accepted_tokens"]
    )
    assert SPEC_ACCEPTANCE.count - before["acc_count"] == (
        st["spec_acceptance_count"]
    )
    assert SPEC_ACCEPTANCE.sum - before["acc_sum"] == pytest.approx(
        st["spec_acceptance_sum"]
    )
    assert DEVICE_PROGRAMS.labels(kind="spec").value - before["spec"] == (
        st["device_programs_spec"]
    )
    assert DEVICE_PROGRAMS.labels(kind="draft").value - before["draft"] == (
        st["device_programs_draft"]
    )
    # The gauge is last-write (no delta): both surfaces hold the final
    # spec program's emitted-token count.
    assert SPEC_VERIFIED_TOKENS.value == st["spec_verified_tokens_last"]


def test_spec_stop_sequences_parity(params, dparams):
    """Multi-token string stops landing inside a multi-token emission:
    the fetch scans emitted tokens one at a time, so stop-trim and
    retirement stay byte-identical to spec-off."""
    prompts = [_HEADER + "one", _HEADER + "two", _HEADER + "three"]
    kw = dict(prompts=prompts, temperature=0.9, seed=3, stop=["\x00", "ab"])
    want, _ = _burst(params, None, 0, depth=1, **kw)
    got, _ = _burst(params, (DCFG, dparams), 3, **kw)
    # Sampled rows keep their (seed, index) PRNG addressing, but the
    # accept rule burns keys differently than the plain sampler — only
    # GREEDY rows promise byte parity. temperature=0.9 here exercises
    # the stop machinery under spec; parity is asserted on the greedy
    # variant below.
    assert [len(t) >= 0 for t in got]
    kw_greedy = dict(prompts=prompts, stop=["\x00", "ab"])
    want_g, _ = _burst(params, None, 0, depth=1, **kw_greedy)
    got_g, _ = _burst(params, (DCFG, dparams), 3, **kw_greedy)
    assert got_g == want_g


def test_spec_stream_plan_stale_mirror_skips_fill(params, dparams):
    """The pipeline staleness rule: with a program in flight the host
    mirror lags the device by a data-dependent round, so a donor-
    suffix FILL (off > 0) planned from it would verify at shifted
    positions — the plan must skip it (the mate drafts for itself)
    while delta-0 sharing (donor's fresh proposals, position-free)
    stays planned. With the window empty the catch-up fill plans."""
    b = ContinuousBatcher(
        CFG,
        params,
        config=ContinuousConfig(**_CCFG, spec_k=4),
        draft=(DCFG, dparams),
    )
    try:
        prompts = [_HEADER + "stream plan"] * 3
        _serve(b, prompts, max_new_tokens=4)
        # Rebuild a staggered decode state by hand: three slots over
        # one registered header run, mate 1 in lockstep with the
        # donor, mate 2 two tokens behind.
        for i in range(3):
            b._groups.add(i, b._slots[i].pages[:2] if b._slots[i] else [])
        donor_gen = [7, 8, 9, 10]

        class _S:
            # Neutral phase: the live loop thread scans _slots
            # concurrently (pick-prefill, _decoding, spec catch-up)
            # and must skip these stand-ins, not crash on them.
            phase = "held"

            def __init__(self, gen):
                self.generated = list(gen)

        b._slots[0] = _S(donor_gen)
        b._slots[1] = _S(donor_gen)
        b._slots[2] = _S(donor_gen[:2])
        run = (101, 102)
        b._groups._run_of_seq = {0: run, 1: run, 2: run}
        rows_now = [(0, b._slots[0]), (1, b._slots[1]), (2, b._slots[2])]
        b._inflight.clear()
        src, fill, off, streams, shared = b._spec_stream_plan(rows_now)
        assert list(src[:3]) == [0, 0, 0] and int(off[2]) == 2
        assert shared == 2 and streams == 1
        assert list(fill[2, :2]) == donor_gen[2:]
        b._inflight.append(object())  # a program in flight: stale mirror
        src, fill, off, streams, shared = b._spec_stream_plan(rows_now)
        assert list(src[:3]) == [0, 0, 2]  # the lagging fill is skipped
        assert int(off[1]) == 0 and shared == 1 and streams == 2
        b._inflight.clear()
    finally:
        b._slots = [None] * b.config.max_slots  # drop the stand-ins
        b.close()
