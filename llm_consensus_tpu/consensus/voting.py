"""Answer aggregation: majority vote, weighted vote, logit pooling.

The reference's only aggregation rule is *unanimity* — every panelist's
feedback must be ``Good`` (``src/main.rs:316-325``), with forced approval
at the round cap (``:308-311``). Per SURVEY.md §7(c) and BASELINE.json,
the rebuild generalizes this to N-way self-consistency:

- :func:`majority_vote` / :func:`weighted_vote` — host-side aggregation
  over canonicalized answers (heterogeneous panels use persona weights).
- :func:`logit_pool` — pool candidates by total probability mass
  (sum of per-candidate sequence probabilities per distinct answer).
- :func:`device_majority_vote` — the on-device reducer from the north
  star: candidates live on the ``data`` mesh axis; the tally is a one-hot
  ``psum`` over that axis + argmax, so the vote rides ICI instead of a
  host gather.
- :func:`self_consistency` — end-to-end: one batched N-way sample on an
  :class:`InferenceEngine`, canonicalize, vote.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Canonicalization
# ---------------------------------------------------------------------------

_NUM_RE = re.compile(r"-?\$?\d[\d,]*(?:\.\d+)?")


def extract_final_number(text: str) -> str | None:
    """Extract a final numeric answer (GSM8K-style EM key).

    Honors an explicit ``#### <answer>`` marker when present, else takes
    the last number in the text. Commas/dollar signs are stripped;
    ``42.0`` canonicalizes to ``42``.
    """
    marker = text.rsplit("####", 1)
    hay = marker[1] if len(marker) == 2 else text
    matches = _NUM_RE.findall(hay)
    if not matches:
        return None
    raw = matches[-1].replace(",", "").replace("$", "")
    try:
        val = float(raw)
    except ValueError:
        return None
    return str(int(val)) if val == int(val) else str(val)


def canonicalize(text: str) -> str:
    """Default answer key: final number when present, else normalized text."""
    num = extract_final_number(text)
    if num is not None:
        return num
    return " ".join(text.strip().lower().split())


# ---------------------------------------------------------------------------
# Host-side voting
# ---------------------------------------------------------------------------


@dataclass
class VoteResult:
    winner: str  # canonical key of the winning answer
    text: str  # a representative raw answer carrying the winning key
    tally: dict[str, float]
    n_candidates: int


def _vote(
    answers: list[str],
    scores: list[float],
    key_fn,
) -> VoteResult:
    if not answers:
        raise ValueError("no answers to vote over")
    tally: dict[str, float] = defaultdict(float)
    rep: dict[str, str] = {}
    for ans, sc in zip(answers, scores):
        k = key_fn(ans)
        tally[k] += sc
        rep.setdefault(k, ans)
    winner = max(tally.items(), key=lambda kv: kv[1])[0]
    return VoteResult(
        winner=winner,
        text=rep[winner],
        tally=dict(tally),
        n_candidates=len(answers),
    )


def majority_vote(answers: list[str], key_fn=canonicalize) -> VoteResult:
    """Uniform one-candidate-one-vote (self-consistency, Wang et al.)."""
    return _vote(answers, [1.0] * len(answers), key_fn)


def weighted_vote(
    answers: list[str], weights: list[float], key_fn=canonicalize
) -> VoteResult:
    """Per-candidate weights — heterogeneous panels vote with persona
    weights (BASELINE.md config[3])."""
    if len(weights) != len(answers):
        raise ValueError("weights and answers must align")
    return _vote(answers, list(weights), key_fn)


def logit_pool(
    answers: list[str], logprobs: list[float], key_fn=canonicalize
) -> VoteResult:
    """Pool by probability mass: each candidate contributes
    ``exp(logprob)`` (normalized over the batch for stability)."""
    if len(logprobs) != len(answers):
        raise ValueError("logprobs and answers must align")
    lp = np.asarray(logprobs, np.float64)
    w = np.exp(lp - lp.max())  # softmax-style stabilization
    return _vote(answers, list(w / w.sum()), key_fn)


def rescore_vote(
    engine,
    prompt: str,
    answers: list[str],
    key_fn=canonicalize,
    normalize: bool = True,
) -> VoteResult:
    """Logit-pool candidates under a JUDGE model's own scores.

    The candidates can come from anywhere — other panel models, debate
    rounds, humans; ``engine.score_texts`` (teacher-forced, one chunk
    forward) assigns each its log-probability given ``prompt``, and the
    pool weights by that mass. This is cross-model reranking: the
    generalization of logit pooling to candidates the judge did not
    sample itself. ``normalize`` length-normalizes so verbose answers
    aren't penalized linearly.
    """
    # Scorability is a TOKEN property, not a string one: an answer that
    # a tokenizer encodes to zero ids (possible with HF tokenizers on
    # e.g. control-char-only text) cannot be teacher-forced any more
    # than "" can. Both pool with ~zero mass instead of erroring.
    tok = getattr(engine, "tokenizer", None)

    def _scorable(a: str) -> bool:
        if not a:
            return False
        if tok is None:
            return True
        return len(tok.encode(a, add_bos=False)) > 0

    scorable = [_scorable(a) for a in answers]
    picked = [a for a, ok in zip(answers, scorable) if ok]
    scored = (
        engine.score_texts(prompt, picked, normalize=normalize)
        if picked
        else []
    )
    it = iter(scored)
    scores = [next(it) if ok else -1e30 for ok in scorable]
    return logit_pool(answers, scores, key_fn)


# ---------------------------------------------------------------------------
# On-device reducer (north-star: all-gather/psum + argmax over candidates)
# ---------------------------------------------------------------------------


# jit cache keys on function identity — a fresh shard_map closure per
# vote would recompile every call. One jitted reducer per
# (mesh, n_classes, axis_name); repeat votes on the same mesh hit it.
# lru_cache bounds retention: a long-lived process churning through
# distinct meshes must not pin every mesh + executable forever.
@lru_cache(maxsize=16)
def _vote_reducer(mesh: Mesh, n_classes: int, axis_name: str):
    def tally(ids, w):
        onehot = jax.nn.one_hot(ids, n_classes, dtype=jnp.float32)
        local = jnp.sum(onehot * w[:, None], axis=0)
        hist = jax.lax.psum(local, axis_name)
        return jnp.argmax(hist).astype(jnp.int32), hist

    spec = P(axis_name)
    return jax.jit(
        jax.shard_map(
            tally,
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=(P(), P()),
        )
    )


def device_majority_vote(
    candidate_ids: jnp.ndarray,
    n_classes: int,
    mesh: Mesh,
    weights: jnp.ndarray | None = None,
    axis_name: str = "data",
) -> tuple[int, np.ndarray]:
    """Tally candidate class-ids across the ``data`` mesh axis on device.

    candidate_ids: [N] int32, sharded over ``axis_name`` (the candidate
    fan-out axis). The tally is a one-hot reduction ``psum``-ed over the
    axis; argmax of the pooled histogram picks the winner. Ties break
    toward the lower id (argmax convention).

    Returns (winner_id, histogram) on host.
    """
    if weights is None:
        weights = jnp.ones_like(candidate_ids, jnp.float32)
    winner, hist = _vote_reducer(mesh, n_classes, axis_name)(
        candidate_ids, weights
    )
    return int(winner), np.asarray(hist)


# ---------------------------------------------------------------------------
# End-to-end self-consistency over an engine
# ---------------------------------------------------------------------------


@dataclass
class PanelVoteResult:
    vote: VoteResult
    per_model: dict[str, list[str]]
    total_tokens: int


def _panel_fanout(
    ordered: list[tuple[str, tuple[object, float]]],
    prompts_for,
    temperature: float,
    seed_for,
    max_new_tokens: int | None,
):
    """Concurrent per-member sampling shared by
    :func:`heterogeneous_panel_vote` and
    :func:`~llm_consensus_tpu.consensus.debate.run_panel_debate`.

    One thread per engine: on a single shared chip the calls still
    serialize on the device queue, but engines on disjoint meshes/hosts
    overlap fully, and even single-chip panels overlap each model's
    host-side tokenize/detokenize work. ``seed_for(member_index)`` gives
    each member its own seed, so results are identical to the
    sequential path regardless of completion order. Returns
    ``[(name, weight, results)]`` in the input (sorted-name) order.
    """
    from concurrent.futures import ThreadPoolExecutor

    def _one(arg):
        mi, (name, (engine, weight)) = arg
        prompts = prompts_for(name)
        results = engine.generate_texts(
            prompts,
            temperatures=[temperature] * len(prompts),
            seed=seed_for(mi),
            max_new_tokens=max_new_tokens,
        )
        return name, weight, results

    with ThreadPoolExecutor(max_workers=max(1, len(ordered))) as ex:
        return list(ex.map(_one, enumerate(ordered)))


def heterogeneous_panel_vote(
    engines: dict[str, tuple[object, float]],
    prompt: str,
    n_per_model: int = 4,
    temperature: float = 0.7,
    seed: int = 0,
    max_new_tokens: int | None = None,
    key_fn=canonicalize,
) -> PanelVoteResult:
    """Weighted vote across DIFFERENT models (BASELINE.md config[3]).

    ``engines``: model name -> (engine, vote weight). Each model samples
    ``n_per_model`` candidates (one batched program per model — models
    have different weights/meshes so they cannot share a batch); every
    candidate votes with its model's weight.

    The per-model calls run CONCURRENTLY via :func:`_panel_fanout`
    (one thread per engine; per-model seeds = seed + model index in
    sorted-name order) — the deployment config[3] describes engines on
    disjoint meshes/hosts, which overlap fully.
    """
    ordered = sorted(engines.items())
    outs = _panel_fanout(
        ordered,
        lambda _name: [prompt] * n_per_model,
        temperature,
        lambda mi: seed + mi,
        max_new_tokens,
    )

    answers: list[str] = []
    weights: list[float] = []
    per_model: dict[str, list[str]] = {}
    total_tokens = 0
    for name, weight, results in outs:  # sorted-name order preserved
        texts = [r.text for r in results]
        per_model[name] = texts
        answers.extend(texts)
        weights.extend([weight] * len(texts))
        total_tokens += sum(r.num_tokens for r in results)
    vote = weighted_vote(answers, weights, key_fn)
    return PanelVoteResult(
        vote=vote, per_model=per_model, total_tokens=total_tokens
    )


def _device_vote(engine, texts: list[str], key_fn) -> VoteResult:
    """North-star reducer end-to-end: canonicalize on host, tally on the
    engine's mesh (one-hot psum over the ``data`` axis + argmax — the
    vote rides ICI instead of a host gather). Requires a mesh-wired
    engine; candidates pad to the data-axis size with zero-weight votes.
    """
    mesh = engine.mesh
    # First-seen class order, so argmax's lowest-index tie-break picks
    # the same winner as the host vote's insertion-ordered max().
    keys = [key_fn(t) for t in texts]
    classes = list(dict.fromkeys(keys))
    ids = [classes.index(k) for k in keys]
    dp = int(mesh.shape.get("data", 1))
    pad = (-len(ids)) % dp
    weights = jnp.asarray([1.0] * len(ids) + [0.0] * pad, jnp.float32)
    ids_arr = jnp.asarray(ids + [0] * pad, jnp.int32)
    winner_id, hist = device_majority_vote(
        ids_arr, len(classes), mesh, weights=weights
    )
    winner = classes[winner_id]
    rep = next(t for t, k in zip(texts, keys) if k == winner)
    tally = {c: float(hist[i]) for i, c in enumerate(classes)}
    return VoteResult(
        winner=winner, text=rep, tally=tally, n_candidates=len(texts)
    )


@dataclass
class SelfConsistencyResult:
    vote: VoteResult
    candidates: list[str]
    logprobs: list[float]
    total_tokens: int


def self_consistency(
    engine,
    prompt: str,
    n: int,
    temperature: float = 0.7,
    seed: int = 0,
    max_new_tokens: int | None = None,
    method: str = "majority",
    key_fn=canonicalize,
) -> SelfConsistencyResult:
    """N-way self-consistency: ONE batched sample of n candidates on the
    engine (the candidate axis is the mesh ``data`` axis when sharded),
    then vote. ``method``: majority | logit_pool | device_majority (the
    on-device psum+argmax reducer; needs a mesh-wired engine).
    """
    if method not in ("majority", "logit_pool", "device_majority"):
        raise ValueError(f"unknown aggregation method {method!r}")
    if method == "device_majority" and getattr(engine, "mesh", None) is None:
        # Fail before the expensive N-way generation, not after.
        raise ValueError("device_majority needs a mesh-wired engine")
    results = engine.generate_texts(
        [prompt] * n,
        temperatures=[temperature] * n,
        seed=seed,
        max_new_tokens=max_new_tokens,
    )
    texts = [r.text for r in results]
    lps = [r.logprob for r in results]
    if method == "majority":
        vote = majority_vote(texts, key_fn)
    elif method == "logit_pool":
        vote = logit_pool(texts, lps, key_fn)
    else:
        vote = _device_vote(engine, texts, key_fn)
    return SelfConsistencyResult(
        vote=vote,
        candidates=texts,
        logprobs=lps,
        total_tokens=sum(r.num_tokens for r in results),
    )
