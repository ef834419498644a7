"""SDAR on the serving path: per-head QK norm, the block-causal
attention rule, and block-diffusion decoding — a block of 4 denoised in
place over its steps inside one step program, then committed — each held
to the plain reference (``models/reference/sdar_moe.py``) on the CPU at
``test-tiny-sdar`` (2 layers, 8 experts top-2, heads of 16), float32."""

from __future__ import annotations

import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine import diffusion as D
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
from llm_consensus_tpu.models import transformer as T
from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.paged_cache import (
    DecodeGroupArrays,
    PagedKVCache,
    install_seq,
)
from llm_consensus_tpu.models.reference import sdar_moe as R
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)

PG = 16
B = 4
TOK = ByteTokenizer()
# float32 throughout, pools included: the paged path agrees with the
# reference to rounding.
TOL = 2e-5


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


_MODELS: dict = {}


def _model(**kw):
    """test-tiny-sdar with ``kw``, seeded float32 weights (the tree is
    shared by every variant of one ``head_scale``: the kernel flag
    changes no weight)."""
    head_scale = kw.pop("head_scale", 1.0)
    cfg = get_config("test-tiny-sdar").with_(**kw)
    if head_scale not in _MODELS:
        params = T.init_params(
            get_config("test-tiny-sdar"), jax.random.PRNGKey(0), jnp.float32
        )
        params["lm_head"] = params["lm_head"] * head_scale
        _MODELS[head_scale] = params
    return cfg, _MODELS[head_scale]


def _tokens(n: int, salt: int = 0) -> list[int]:
    rng = np.random.RandomState(salt)
    return [int(t) for t in rng.randint(3, 259, size=n)]


_JITS: dict = {}


def _jit(fn, cfg, **static):
    from functools import partial

    key = (fn, cfg, tuple(sorted(static.items())))
    if key not in _JITS:
        _JITS[key] = jax.jit(partial(fn, cfg, **static))
    return _JITS[key]


def _prefill(cfg, params, cache, ids, table, start=0):
    """``ids`` from ``start`` through one-lane chunk programs of PG."""
    pos = start
    while pos < len(ids):
        m = min(PG, len(ids) - pos)
        toks = np.full((1, PG), 7, np.int32)
        toks[0, :m] = ids[pos : pos + m]
        _, cache, *_ = _jit(T.prefill_chunk_paged, cfg)(
            params, jnp.asarray(toks), jnp.asarray(table[None]),
            jnp.asarray([pos], jnp.int32), cache,
        )
        pos += PG
    return cache


def _table(pages) -> np.ndarray:
    table = np.zeros((8,), np.int32)
    table[: len(pages)] = pages
    return table


# -- the model ---------------------------------------------------------------


def test_forward_matches_reference():
    """The full forward (``transformer.forward``, the block-causal rule
    in ``causal_attention``) against the reference's."""
    cfg, params = _model()
    ids = _tokens(37)
    want = np.asarray(R.forward(cfg, params, ids))
    got = np.asarray(T.forward(cfg, params, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.05


def test_qk_norm_and_block_rule_are_each_visible():
    """Each mechanism moves the logits far past the tolerance: the
    reference without its QK norms, and the plain causal rule."""
    cfg, params = _model()
    ids = _tokens(37)
    want = np.asarray(R.forward(cfg, params, ids))
    no_norm = np.asarray(R.forward(cfg, params, ids, qk_norm=False))
    assert np.abs(no_norm - want).max() > 100 * TOL
    causal = np.asarray(
        T.forward(cfg.with_(generation="autoregressive", block_length=1),
                  params, jnp.asarray(ids)[None])
    )[0]
    assert np.abs(causal - want).max() > 100 * TOL


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("lanes", [0, 2])
def test_kernel_block_rule_against_masked_attention(block, lanes):
    """The ragged kernel in interpret mode — verify rows of 4 queries
    and chunk lanes of 16 — against a masked ``jnp`` attention over the
    same pages, at the block rule of ``block``; ``block`` 1 is today's
    program, bit for bit."""
    from llm_consensus_tpu.ops.attention import causal_attention
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    rng = np.random.RandomState(block * 10 + lanes)
    hkv, g, d, nq, rows = 2, 4, 16, 4, 3
    k_pool = jnp.asarray(rng.randn(12, PG, hkv, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(12, PG, hkv, d), jnp.float32)
    tables = np.zeros((rows, 4), np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :2] = [4, 5]
    valid = np.asarray([40, 20, 0], np.int32)  # row 2 idle
    q = jnp.asarray(rng.randn(rows, nq, hkv * g, d), jnp.float32)
    kw = {}
    if lanes:
        ct = np.zeros((lanes, 4), np.int32)
        ct[0, :2] = [6, 7]
        ct[1, :3] = [8, 9, 10]
        kw = dict(
            q_chunk=jnp.asarray(rng.randn(lanes, PG, hkv * g, d), jnp.float32),
            chunk_table=jnp.asarray(ct),
            chunk_start=jnp.asarray([16, 32][:lanes], jnp.int32),
        )
    got = ragged_paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(valid),
        interpret=True, block=block, **kw,
    )
    if block == 1:
        plain = ragged_paged_attention(
            q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(valid),
            interpret=True, **kw,
        )
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(plain)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    out_rows = got[0] if lanes else got

    def dense(qr, table, end):
        """Masked attention of queries at [end - n, end) over the
        table's first ``end`` slots."""
        k = k_pool[table].reshape(-1, hkv, d)[:end]
        v = v_pool[table].reshape(-1, hkv, d)[:end]
        n = qr.shape[0]
        qfull = jnp.zeros((end, hkv * g, d)).at[end - n:].set(qr)
        out = causal_attention(qfull[None], k[None], v[None], block=block)
        return out[0, end - n:]

    for r in range(2):
        want = dense(q[r], tables[r], int(valid[r]))
        assert np.abs(np.asarray(out_rows[r]) - np.asarray(want)).max() < 1e-5
    for lane in range(lanes):
        start = [16, 32][lane]
        want = dense(kw["q_chunk"][lane], kw["chunk_table"][lane], start + PG)
        assert np.abs(np.asarray(got[1][lane]) - np.asarray(want)).max() < 1e-5


def _denoise_run(cfg, params, cache, rows, steps_blocks, groups=None):
    """Blocks through ``denoise_step_paged`` for the ``rows`` dict
    {row: (committed ids, tail)}: each block's steps by the unmask rule,
    then its commit forward; every step's logits of every row held to
    the reference's full forward. Returns the committed ids a row."""
    seqs = {r: list(ids) for r, (ids, _) in rows.items()}
    tails = {r: list(t) for r, (_, t) in rows.items()}
    worst = 0.0
    for _ in range(steps_blocks):
        block = np.full((4, B), cfg.mask_token_id, np.int32)
        for r, t in tails.items():
            block[r, : len(t)] = t
        masked0 = jnp.asarray((block == cfg.mask_token_id).sum(1), jnp.int32)
        x = jnp.asarray(block)
        for step in range(cfg.denoising_steps):
            logits, _, cache, *_ = _jit(T.denoise_step_paged, cfg)(
                params, x, cache, groups
            )
            for r in rows:
                want = R.forward(
                    cfg, params, seqs[r] + [int(t) for t in x[r]],
                    at=range(len(seqs[r]), len(seqs[r]) + B),
                )
                worst = max(worst, float(jnp.abs(logits[r] - want).max()))
            x = D.unmask(cfg, logits, x, masked0, step)
        _, _, cache, *_ = _jit(T.denoise_step_paged, cfg, unembed=False)(
            params, x, cache, groups
        )
        live = (cache.page_table[:, 0] != 0).astype(jnp.int32)
        cache = replace(cache, length=cache.length + B * live)
        for r in rows:
            seqs[r] += [int(t) for t in x[r]]
        tails = {r: [] for r in rows}
    assert worst < TOL, worst
    return seqs, cache


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("grouped", [False, True], ids=["rows", "grouped"])
def test_paged_prefill_then_blocks_match_reference(use_pallas, grouped):
    """Chunked prefill of two prompts (one with a tail of 3, one
    without), then three blocks through the cache — every denoising
    step's logits against the reference's full forward over the same
    state, each block reaching the cache through its commit forward;
    grouped: the rows share two prompt pages, read once a group."""
    cfg, params = _model(use_pallas=use_pallas)
    head = _tokens(32, 1)
    prompts = {1: head + _tokens(7, 2), 2: head + _tokens(4, 3)}
    cache = PagedKVCache.create(cfg, 24, PG, 4, 8, dtype=jnp.float32)
    tables = {1: _table([3, 4, 5, 6, 7]), 2: _table([3, 4, 9, 10, 11])}
    rows = {}
    for r, ids in prompts.items():
        whole = len(ids) - len(ids) % B
        start = 32 if (r == 2 and grouped) else 0
        if r == 2 and not grouped:
            tables[2] = _table([12, 13, 9, 10, 11])
        cache = _prefill(cfg, params, cache, ids[:whole], tables[r], start)
        cache = install_seq(
            cache, jnp.int32(r), jnp.asarray(tables[r]), jnp.int32(whole)
        )
        rows[r] = (ids[:whole], ids[whole:])
    groups = None
    if grouped:
        groups = DecodeGroupArrays(
            jnp.asarray([-1, 0, 0, -1], jnp.int32),
            jnp.asarray([1, 0], jnp.int32),
            jnp.asarray([2, 0], jnp.int32),
            jnp.asarray([0, 32, 32, 0], jnp.int32),
        )
    _denoise_run(cfg, params, cache, rows, 3, groups)


# -- the unmask rule -----------------------------------------------------------


def test_unmask_rule_matches_the_reference_rule():
    """The traced rule against the reference's host loop over random
    logits, threshold passes and ties included, at every step."""
    cfg, _ = _model()
    rng = np.random.RandomState(0)
    for trial in range(40):
        tail = trial % 4
        block = rng.randint(3, 259, size=B).tolist()
        for i in range(tail, B):
            block[i] = cfg.mask_token_id
        masked0 = B - tail
        step = trial % cfg.denoising_steps
        logits = rng.randn(B, cfg.vocab_size).astype(np.float32)
        if trial % 3 == 0:
            logits[:, 5] += 20.0  # every position confident
        if trial % 5 == 0:
            logits[1] = logits[2]  # a tie in confidence
        want = R.unmask(cfg, block, logits, masked0, step)
        got = D.unmask(
            cfg, jnp.asarray(logits)[None], jnp.asarray([block], jnp.int32),
            jnp.asarray([masked0], jnp.int32), step,
        )
        assert [int(t) for t in got[0]] == want, trial


@pytest.mark.parametrize("masked0", [1, 2, 3, 4])
def test_step_shares_split_the_block(masked0):
    """SDAR's schedule: the shares of the steps add up to the masks,
    the first ``masked0 % steps`` steps one more."""
    shares = [
        int(D.step_share(jnp.asarray([masked0]), s, 4)[0]) for s in range(4)
    ]
    assert sum(shares) == masked0 and shares == sorted(shares, reverse=True)


# -- the batcher -----------------------------------------------------------------


def _batcher(cfg, params, **kw):
    return ContinuousBatcher(cfg, params, config=ContinuousConfig(**{
        **dict(
            max_slots=4, page_size=PG, n_pages=64, pages_per_seq=12,
            seq_buckets=(32, 64, 128), prefill_chunk=PG, max_new_tokens=12,
        ),
        **kw,
    }))


def _ids(text: str) -> list[int]:
    return list(TOK.encode(text))


# Prompts of 26 (tail 2), 33 (tail 1) and 40 (no tail) tokens.
PROMPTS = ["a prompt of twenty-five!!", "another prompt, thirty-two long",
           "and a third one, exactly thirty-nine ch"]


@pytest.mark.parametrize("steps", [4, 2])
def test_served_generation_matches_reference_loop(steps):
    """Greedy block diffusion through the batcher (chunked prefill,
    block programs, commits, the prompt's tail riding the first block)
    against the reference's plain loop, for 4 and 2 denoising steps;
    ``max_new_tokens`` 10 cuts inside a block."""
    cfg, params = _model(denoising_steps=steps)
    b = _batcher(cfg, params)
    try:
        futs = [b.submit(p, max_new_tokens=10) for p in PROMPTS]
        got = [f.result(timeout=300) for f in futs]
    finally:
        b.close()
    for p, r in zip(PROMPTS, got):
        want = R.generate(cfg, params, _ids(p), 10, TOK.eos_id)
        assert r.num_tokens == len(want) == 10
        assert r.text == TOK.decode(
            [t for t in want if t != TOK.eos_id]
        )


def test_threshold_fires_on_peaked_logits():
    """With the head scaled up the greedy probabilities pass 0.9 and a
    step commits several positions: the served run still follows the
    reference loop, and its blocks finish in fewer steps."""
    cfg, params = _model(head_scale=60.0)
    ids = _ids(PROMPTS[2])
    seq = ids
    block = [cfg.mask_token_id] * B
    logits = R.forward(
        cfg, params, seq + block, at=range(len(seq), len(seq) + B)
    )
    fired = sum(
        t != cfg.mask_token_id for t in R.unmask(cfg, block, logits, B, 0)
    )
    assert fired > 1  # more than the step's share of 1
    b = _batcher(cfg, params)
    try:
        got = b.submit(PROMPTS[2], max_new_tokens=9, logits=8).result(300)
    finally:
        b.close()
    want = R.generate(cfg, params, ids, 9, TOK.eos_id)
    assert got.num_tokens == len(want)
    assert got.text == TOK.decode([t for t in want if t != TOK.eos_id])
    have, ref, blocks = R.teacher_forced(cfg, params, ids, got.logits)
    assert blocks == got.logit_ids
    assert R.compare(have, ref)["rel_rms"] < 5e-3


def test_eos_inside_a_block_ends_the_request():
    """An EOS favoured at some positions only: the request ends at the
    first one committed, what follows it in the block is dropped, and
    the count is the reference loop's."""
    cfg, params = _model()
    params = dict(params)
    eos = TOK.eos_id
    params["lm_head"] = params["lm_head"].at[:, eos].multiply(400.0)
    b = _batcher(cfg, params)
    try:
        got = [
            b.submit(p, max_new_tokens=12).result(300) for p in PROMPTS
        ]
    finally:
        b.close()
    ended = 0
    for p, r in zip(PROMPTS, got):
        want = R.generate(cfg, params, _ids(p), 12, eos)
        assert r.num_tokens == len(want)
        ended += want[-1] == eos and len(want) < 12
    assert ended  # some request did end on an EOS


def test_served_logits_teacher_forced_on_the_reference():
    """``"logits": n`` returns each denoising step's B rows and the
    block's ids at it; the reference rebuilds every block from the
    served logits by the same rule, and its logits agree at every
    masked position — through three blocks, so two commits are read."""
    cfg, params = _model()
    b = _batcher(cfg, params)
    try:
        futs = [b.submit(p, max_new_tokens=12, logits=12) for p in PROMPTS]
        got = [f.result(timeout=300) for f in futs]
    finally:
        b.close()
    for p, r in zip(PROMPTS, got):
        assert r.logits.shape == (12 * B, cfg.vocab_size)
        have, want, blocks = R.teacher_forced(cfg, params, _ids(p), r.logits)
        assert blocks == r.logit_ids
        # bf16 pages on the batcher's pool: rounding, not a rule apart.
        assert R.compare(have, want)["rel_rms"] < 5e-3


@pytest.mark.parametrize("prompt", PROMPTS)
def test_packed_states_are_each_states_own_forward(prompt):
    """One forward over the longest context and every state's block,
    padded: each state's logits equal its own full forward over its
    context and block (tails of 2, 1 and 0; three blocks, so finished
    blocks ride the context that earlier states must not see)."""
    cfg, params = _model()
    ids = _ids(prompt)
    rng = np.random.default_rng(len(ids))
    served = rng.standard_normal((12 * B, cfg.vocab_size)).astype(np.float32)
    context, states = R.served_states(cfg, ids, served)
    assert len(context) == len(ids) - len(ids) % B + 2 * B
    width = R.packed_width(cfg, context, states) + 21
    packed = R.states_logits(cfg, params, context, states, width)
    for n, (start, block) in enumerate(states):
        own = np.asarray(R._block_logits(cfg, params, context[:start], block))
        np.testing.assert_allclose(packed[n], own, rtol=0, atol=TOL)


def test_reference_child_ends_with_its_parent(tmp_path):
    """The reference run as a script asks to end with the process that
    started it: a parent killed mid-judgement leaves no child behind."""
    import signal
    import subprocess
    import sys
    import time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = (
        "import sys, time; sys.path.insert(0, %r); "
        "from llm_consensus_tpu.models.reference import sdar_moe as R; "
        "R._die_with_parent(); print('armed', flush=True); time.sleep(120)"
        % root
    )
    parent = (
        "import subprocess, sys, time; "
        "c = subprocess.Popen([sys.executable, '-c', %r], "
        "stdout=subprocess.PIPE, text=True); c.stdout.readline(); "
        "print(c.pid, flush=True); time.sleep(120)" % child
    )
    p = subprocess.Popen([sys.executable, "-c", parent],
                         stdout=subprocess.PIPE, text=True)
    try:
        pid = int(p.stdout.readline())
    finally:
        p.send_signal(signal.SIGKILL)
        p.wait(30)

    def alive() -> bool:
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0] not in "ZX"
        except FileNotFoundError:
            return False

    deadline = time.monotonic() + 20
    while alive() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not alive()


def test_prefix_hit_at_a_page_edge_then_a_block():
    """Two prompts sharing two whole pages and nothing after the edge:
    the second maps them and its first block follows; both equal the
    reference loop, and no page that holds a block is registered."""
    cfg, params = _model()
    head = "0123456789abcdef" * 2  # 32 ids with the leading BOS: 33
    prompts = [head[:-1] + "A tail of its own", head[:-1] + "B other end"]
    b = _batcher(cfg, params)
    try:
        first = b.submit(prompts[0], max_new_tokens=6).result(300)
        second = b.submit(prompts[1], max_new_tokens=6).result(300)
        registry = b._registries[0]
        registered = [n.tokens for n in registry._walk()]
    finally:
        b.close()
    assert second.timing["header_pages_shared"] >= 1
    for p, r in zip(prompts, (first, second)):
        want = R.generate(cfg, params, _ids(p), 6, TOK.eos_id)
        assert r.text == TOK.decode([t for t in want if t != TOK.eos_id])
    for p in prompts:
        ids = _ids(p)
        whole = len(ids) - len(ids) % B
        pages = [tuple(ids[k * PG:(k + 1) * PG]) for k in range(whole // PG)]
        tail_page = tuple(ids[(whole // PG) * PG:][:PG])
        assert all(pg in registered for pg in pages)
        assert tail_page not in registered


@pytest.mark.parametrize("length", [25, 26, 27, 28])
def test_no_page_of_an_unfinished_block_is_registered(length):
    """Whatever the tail, the registry holds the whole pages of the
    prompt's whole blocks and nothing else."""
    cfg, params = _model()
    text = ("x" * 40)[: length - 1]  # + BOS
    b = _batcher(cfg, params, page_size=8, prefill_chunk=8)
    try:
        b.submit(text, max_new_tokens=5).result(300)
        registered = list(b._registries[0]._walk())
    finally:
        b.close()
    whole = length - length % B
    assert len(registered) == whole // 8


def test_refusals_name_the_model():
    cfg, params = _model()
    with pytest.raises(ValueError, match="block-diffusion"):
        _batcher(cfg, params, decode_rounds=2)
    b = _batcher(cfg, params)
    try:
        with pytest.raises(ValueError, match="greedy"):
            b.submit("a prompt", temperature=0.7)
        with pytest.raises(ValueError, match="shorter than one block"):
            b.submit("ab")
    finally:
        b.close()


def test_counters_count_blocks_steps_and_tokens():
    """The batcher's diffusion mirrors: every committed block cost the
    steps and its commit, tokens are what requests were given."""
    cfg, params = _model()
    b = _batcher(cfg, params)
    try:
        got = [b.submit(p, max_new_tokens=10).result(300) for p in PROMPTS]
        st = b.stats()
    finally:
        b.close()
    assert st["diffusion_tokens"] == sum(r.num_tokens for r in got)
    assert st["diffusion_steps"] == st["diffusion_blocks"] * 5
    # 10 tokens after tails of 2, 1, 0: 3 + 3 + 3 blocks, none to spare.
    assert st["diffusion_blocks"] == 9
    assert st["attention_pairs_denoise"] + st.get(
        "attention_pairs_fused", 0) > 0


def test_benchmark_reference_is_the_packages_copy():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/reference/sdar_moe.py")) as f:
        bench = f.read()
    with open(R.__file__) as f:
        assert f.read() == bench
