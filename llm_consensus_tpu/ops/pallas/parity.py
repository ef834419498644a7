"""Kernel-vs-reference comparisons, one body for two callers.

The CPU tests call these at toy shapes with ``interpret=True``;
``chip_smoke.py`` calls them at the smoke model's shapes with
``interpret=False``, which is the only thing that shows a kernel lowers
through Mosaic. Each function builds seeded inputs, runs the kernel and
its ``jax.numpy`` reference (under ``default_matmul_precision
("highest")`` — on a TPU an f32 matmul is otherwise bf16) and returns
the largest absolute error; :func:`check` turns that into a pass/fail
against the tolerance written beside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Attention outputs are bf16 (8 significand bits) convex combinations of
#: unit-normal V rows, |out| <~ 4: one rounding of either side is up to
#: 2^-8 * 4 = 1.6e-2; the online softmax's reassociation is far below it.
ATTENTION_TOL = 2e-2
#: The ragged kernel's FLOAT32 outputs on a bf16 pool against the
#: float64 oracle (:func:`rel_err`): q.k is exact in f32 and the softmax
#: weights meet the values as two bf16 terms, 16 of their 24 bits; an
#: output is a convex combination of values, so 2^-16 of the outputs'
#: scale. Measured 1e-6 - 4e-6 on a v5e; weights rounded to ONE bf16
#: term (what an f32 product at Mosaic's default precision does to
#: them) read 1e-3.
RAGGED_ORACLE_TOL = 2.0**-16
#: RMSNorm on f32 input is f32 end to end in kernel and reference.
NORM_TOL = 2e-5
#: On bf16 input both compute in f32 and round once: |out| <~ 5 with
#: unit-normal rows and weights near 1, so one bf16 ulp is 2^-8 * 4.
NORM_BF16_TOL = 1.6e-2
#: int8 matmul with an f32 result: bf16 x int8 products are exact in
#: f32 on both sides, so the two differ by summation order alone —
#: K * eps_f32 * sum|terms| ~ 1e-4 at K = 14336 with O(1) outputs. A
#: kernel that rounded x or the accumulator to bf16 would miss by 1e-2.
QUANT_MATMUL_TOL = 1e-3


def _finite(got) -> np.ndarray:
    got = np.asarray(got, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("kernel output has non-finite values")
    return got


def max_err(got, want) -> float:
    got = _finite(got)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != reference {want.shape}")
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def check(name: str, err: float, tol: float) -> float:
    if not err <= tol:
        raise AssertionError(f"{name}: max |kernel - reference| {err:.3e} > {tol}")
    return err


def attention_oracle(q, keys, values, qpos, *, scale, window=0):
    """Softmax attention in float64 numpy, the yardstick the kernels'
    float32 outputs are held to: ``q`` [n, H, D] at absolute positions
    ``qpos`` [n] over ``keys`` [S, Hkv, D] / ``values`` [S, Hkv, Dv]
    (slot s at position s), causal, ``window`` > 0 a sliding window.
    Returns [n, H, Dv]."""
    q = np.asarray(q, np.float64)
    keys = np.asarray(keys, np.float64)
    values = np.asarray(values, np.float64)
    n, h, d = q.shape
    hkv = keys.shape[1]
    qpos = np.asarray(qpos)[:, None]
    slot = np.arange(keys.shape[0])[None, :]
    mask = slot <= qpos
    if window > 0:
        mask &= slot > qpos - window
    scores = np.einsum(
        "nkgd,skd->kgns", q.reshape(n, hkv, h // hkv, d), keys
    ) * scale
    scores = np.where(mask, scores, -np.inf)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    out = np.einsum("kgns,skd->nkgd", p, values)
    out /= p.sum(axis=-1).transpose(2, 0, 1)[..., None]
    return out.reshape(n, h, -1)


def ragged_oracle(q, k_pool, v_pool, table, valid_len, *, scale=None,
                  window=0, latent_dv=0):
    """:func:`attention_oracle` for rows of a page table: ``q`` [R, n,
    H, D], row r's n queries ending at its fill ``valid_len[r]`` over
    the pages ``table[r]`` of one layer's pools ([pages, pg, Hkv, D];
    latent: [pages, pg, D], the value its first ``latent_dv`` lanes). A
    row that holds nothing returns zeros. Returns [R, n, H, Dv] float64."""
    q = np.asarray(q, np.float64)
    rows, n, h, d = q.shape
    scale = d**-0.5 if scale is None else scale
    out = np.zeros((rows, n, h, latent_dv or d))
    for r in range(rows):
        fill = int(valid_len[r])
        if fill <= 0:
            continue
        pages = np.asarray(table[r])
        keys = np.asarray(k_pool[pages], np.float64)
        keys = keys.reshape(-1, *keys.shape[2:])[:fill]
        if latent_dv:
            keys = keys[:, None]
            vals = keys[..., :latent_dv]
        else:
            vals = np.asarray(v_pool[pages], np.float64)
            vals = vals.reshape(-1, *vals.shape[2:])[:fill]
        out[r] = attention_oracle(
            q[r], keys, vals, fill - n + np.arange(n), scale=scale,
            window=window,
        )
    return out


def rel_err(got, want) -> float:
    """max |got - want| over max |want|: the error in units of the
    outputs' own scale (an attention output is a convex combination of
    values, so single elements come arbitrarily near zero)."""
    got = _finite(got).astype(np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != oracle {want.shape}")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _ragged_inputs(
    *,
    seed: int,
    pg: int,
    hkv: int,
    g: int,
    d: int,
    p_per: int,
    n_pages: int,
    valid_len: list[int],
    nq: int = 1,
    cq: int = 0,
    chunk_start: int | list[int] = 0,
    group_rows: tuple[int, ...] = (),
    shared_pages: int = 1,
    window: int = 0,
    null_tables: bool = False,
    latent_dv: int = 0,
    dtype=jnp.bfloat16,
):
    """The seeded inputs of one ragged call (the parameters are
    :func:`ragged_attention_error`'s): bfloat16 VALUES, held as
    ``dtype``. Returns (rng, q, k_pool, v_pool, table, valid_len, groups,
    the call's other keywords, the live lanes' mask or None)."""
    rng = np.random.default_rng(seed)
    b, h = len(valid_len), hkv * g

    def normal(*shape):
        return jnp.asarray(
            rng.standard_normal(shape), jnp.bfloat16
        ).astype(dtype)

    if latent_dv:
        kp = normal(n_pages, pg, d)
        vp = jnp.zeros((n_pages, pg, 0), dtype)
    else:
        kp = normal(n_pages, pg, hkv, d)
        vp = normal(n_pages, pg, hkv, d)
    q = normal(*((b, h, d) if nq == 1 else (b, nq, h, d)))
    perm = rng.permutation(np.arange(1, n_pages))
    tbl = np.asarray(perm[: b * p_per].reshape(b, p_per), np.int32)
    if null_tables:
        tbl[:] = 0
    groups = None
    if group_rows:
        rep = group_rows[0]
        for r in group_rows[1:]:
            tbl[r, :shared_pages] = tbl[rep, :shared_pages]
        shared = shared_pages * pg
        member = np.isin(np.arange(b), group_rows)
        groups = (
            jnp.asarray(np.where(member, 0, -1), jnp.int32),  # group_id
            jnp.asarray([rep], jnp.int32),  # group_rep
            jnp.asarray([shared], jnp.int32),  # group_end (tokens)
            jnp.asarray(np.where(member, shared, 0), jnp.int32),
        )
    vl = jnp.asarray(valid_len, jnp.int32)
    kw: dict = {"window": window}
    if latent_dv:
        kw.update(latent_dv=latent_dv, scale=1.3 * d**-0.5)
    lane_live = None
    if cq and isinstance(chunk_start, list):
        lanes = len(chunk_start)
        lane_live = np.asarray(chunk_start) >= 0
        ctbl = np.asarray(
            perm[b * p_per : (b + lanes) * p_per].reshape(lanes, p_per),
            np.int32,
        )
        ctbl[~lane_live] = 0
        kw.update(
            q_chunk=normal(lanes, cq, h, d),
            chunk_table=jnp.asarray(ctbl),
            chunk_start=jnp.asarray(chunk_start, jnp.int32),
        )
    elif cq:
        kw.update(
            q_chunk=normal(cq, h, d),
            chunk_table=jnp.asarray(
                perm[b * p_per : (b + 1) * p_per], jnp.int32
            ),
            chunk_start=jnp.int32(chunk_start),
        )
    return rng, q, kp, vp, jnp.asarray(tbl), vl, groups, kw, lane_live


def ragged_attention_error(
    *,
    layer: tuple[int, int] | None = None,
    interpret: bool | None = None,
    **case,
) -> dict[str, float]:
    """Ragged paged attention kernel vs
    :func:`~llm_consensus_tpu.ops.attention.ragged_paged_attention_reference`.

    ``valid_len``: tokens readable per decode row (mid-page fills are
    the interesting ones; a row of 0 holds nothing, and owes only a
    finite output). ``nq`` > 1: verify rows. ``cq`` > 0: one
    prefill-chunk row of cq queries from ``chunk_start`` — or, where
    ``chunk_start`` is a list, one chunk lane a start, each over a table
    of its own; a start of ``-cq`` is a dead lane (an all-NULL table),
    which owes only a finite output. ``group_rows``:
    these rows share their first ``shared_pages`` pages and ride the
    kernel's group phase (the reference has no groups — grouped output
    must equal ungrouped math). ``null_tables``: all-NULL decode tables
    (an idle batcher's rows next to a live chunk); their output only has
    to be finite. ``layer`` = (l, L): the pools are layer l of stacked
    [L, ...] pools and the kernel indexes the stack (a traced
    ``layer=``), as the step programs' layer scan calls it; its output
    must also equal, bit for bit, the call on the slice ``pool[l]``.
    ``latent_dv`` > 0: the latent pool of an MLA model — one key of
    ``d`` lanes a token for all ``g`` heads (``hkv`` must be 1), no
    value plane, values the key's first ``latent_dv`` lanes.
    Returns {"decode": err[, "chunk": err]}.
    """
    from llm_consensus_tpu.ops.attention import (
        ragged_paged_attention_reference,
    )
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    rng, q, kp, vp, tbl, vl, groups, kw, lane_live = _ragged_inputs(**case)
    valid_len, cq = case["valid_len"], case.get("cq", 0)

    def kernel(k_pool, v_pool, layer_idx=None):
        return ragged_paged_attention(
            q, k_pool, v_pool, tbl, vl, groups=groups,
            layer=layer_idx, interpret=interpret, **kw,
        )

    got = jax.jit(lambda: kernel(kp, vp))()
    if layer is not None:
        at, n_layers = layer
        k_stack = jnp.asarray(
            rng.standard_normal((n_layers, *kp.shape)), kp.dtype
        )
        v_stack = jnp.asarray(
            rng.standard_normal((n_layers, *vp.shape)), vp.dtype
        )
        on_slice, got = got, jax.jit(kernel)(
            k_stack.at[at].set(kp), v_stack.at[at].set(vp), jnp.int32(at)
        )
        for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(on_slice)):
            if not np.array_equal(np.asarray(a), np.asarray(b_)):
                raise AssertionError(
                    f"layer {at} of {n_layers} stacked pools reads other "
                    f"bytes than its slice: max diff {max_err(a, b_)}"
                )
    with jax.default_matmul_precision("highest"):
        ref = ragged_paged_attention_reference(q, kp, vp, tbl, vl, **kw)
    live = np.asarray(valid_len) > 0

    def decode_err(got_dec, ref_dec):
        _finite(got_dec)  # all a dead row owes
        return max_err(np.asarray(got_dec)[live], np.asarray(ref_dec)[live])

    if not cq:
        return {"decode": decode_err(got, ref)}
    got_chunk, ref_chunk = got[1], ref[1]
    if lane_live is not None:
        _finite(got_chunk)  # all a dead lane owes
        got_chunk = np.asarray(got_chunk)[lane_live]
        ref_chunk = np.asarray(ref_chunk)[lane_live]
    if case.get("null_tables"):
        _finite(got[0])
        return {"chunk": max_err(got_chunk, ref_chunk)}
    return {
        "decode": decode_err(got[0], ref[0]),
        "chunk": max_err(got_chunk, ref_chunk),
    }


def ragged_attention_oracle_error(
    *,
    dtype=jnp.bfloat16,
    layer: tuple[int, int] | None = None,
    interpret: bool | None = None,
    **case,
) -> dict[str, float]:
    """The ragged kernel's FLOAT32 outputs (before the cast to the
    queries' dtype) vs :func:`ragged_oracle`, as :func:`rel_err`.

    The case is :func:`ragged_attention_error`'s and the inputs are the
    same bfloat16 values whatever ``dtype`` holds them (pool and queries
    alike): bfloat16 takes the kernel's bf16 operands, float32 the f32
    products — one yardstick under both. ``layer`` = (l, L): read as
    layer l of stacked pools (the other layers zeros). Rows and lanes
    that hold nothing are left out. Returns {"decode": err[, "chunk":
    err]}."""
    from llm_consensus_tpu.ops.pallas.attention import ragged_paged_attention

    _, q, kp, vp, tbl, vl, groups, kw, lane_live = _ragged_inputs(
        **case, dtype=dtype
    )
    k_pool, v_pool, layer_idx = kp, vp, None
    if layer is not None:
        at, n_layers = layer
        layer_idx = jnp.int32(at)
        k_pool = jnp.zeros((n_layers, *kp.shape), kp.dtype).at[at].set(kp)
        v_pool = jnp.zeros((n_layers, *vp.shape), vp.dtype).at[at].set(vp)
    got = jax.jit(
        lambda *pools: ragged_paged_attention(
            q, *pools, tbl, vl, groups=groups, layer=layer_idx,
            out_dtype=jnp.float32, interpret=interpret, **kw,
        )
    )(k_pool, v_pool)
    oracle = dict(
        scale=kw.get("scale"), window=kw["window"],
        latent_dv=kw.get("latent_dv", 0),
    )
    kp64, vp64 = np.asarray(kp, np.float64), np.asarray(vp, np.float64)
    errs = {}
    live = np.asarray(case["valid_len"]) > 0
    if live.any() and not case.get("null_tables"):
        got_dec = got[0] if "q_chunk" in kw else got
        qd = np.asarray(q, np.float64)
        if qd.ndim == 3:
            qd, got_dec = qd[:, None], got_dec[:, None]
        want = ragged_oracle(qd, kp64, vp64, tbl, vl, **oracle)
        errs["decode"] = rel_err(np.asarray(got_dec)[live], want[live])
    if "q_chunk" in kw:
        qc, ct, cs = (
            np.asarray(kw["q_chunk"], np.float64),
            np.asarray(kw["chunk_table"]), np.asarray(kw["chunk_start"]),
        )
        got_chunk = np.asarray(got[1])
        if qc.ndim == 3:
            qc, ct, cs, got_chunk = qc[None], ct[None], cs[None], got_chunk[None]
        if lane_live is None:
            lane_live = np.ones((len(qc),), bool)
        want = ragged_oracle(
            qc, kp64, vp64, ct, cs + qc.shape[1], **oracle
        )
        errs["chunk"] = rel_err(got_chunk[lane_live], want[lane_live])
    return errs


def moe_grouped_matmul_error(
    *, seed: int, rows: list[int], k: int, n: int, n_layers: int = 2,
    interpret: bool | None = None,
) -> float:
    """The grouped int8 expert matmul vs a loop of dequantize + ``jnp``
    dots. ``rows``: rows each expert holds (zeros are experts nobody
    reached, whose matrices must not matter); the experts are those of
    the LAST layer of an [n_layers, E, K, N] stack, as the layer scan
    calls the kernel. Dead tiles' rows are not compared."""
    from llm_consensus_tpu.ops.pallas.moe_matmul import (
        MOE_TILE,
        moe_grouped_matmul,
        n_tiles_for,
    )

    tm, e = MOE_TILE, len(rows)
    key = jax.random.PRNGKey(seed)
    w = jax.random.randint(key, (n_layers, e, k, n), -127, 128, jnp.int8)
    s = (
        jnp.abs(jax.random.normal(key, (n_layers, e, 1, n), jnp.float32))
        * 1e-4 + 5e-5
    )
    n_tiles = n_tiles_for(sum(rows), e, tm)
    tiles_e = [-(-r // tm) for r in rows]
    tile_expert = [i for i, t in enumerate(tiles_e) for _ in range(t)]
    n_live = len(tile_expert)
    tile_expert += [tile_expert[-1]] * (n_tiles - n_live)
    x = jax.random.normal(
        jax.random.fold_in(key, 1), (n_tiles * tm, k), jnp.bfloat16
    )
    layer = n_layers - 1
    got = jax.jit(
        lambda: moe_grouped_matmul(
            x, w.reshape(-1, k, n), s.reshape(-1, 1, n),
            jnp.asarray(tile_expert, jnp.int32) + layer * e,
            jnp.asarray([n_live], jnp.int32),
            out_dtype=jnp.float32, interpret=interpret,
        )
    )()
    err = 0.0
    with jax.default_matmul_precision("highest"):
        for t in range(n_live):
            ex = tile_expert[t]
            ref = jnp.dot(
                x[t * tm : (t + 1) * tm].astype(jnp.float32),
                w[layer, ex].astype(jnp.float32),
            ) * s[layer, ex]
            err = max(err, max_err(got[t * tm : (t + 1) * tm], ref))
    return err


#: The state-space scan is float32 matrix products at "highest" in
#: kernel and reference alike: summation order alone, over T <= 64
#: tokens and N = 128 state values with O(1) operands.
SSM_SCAN_TOL = 2e-4


def ssm_scan_error(
    *, seed: int, rows: int, tokens: int, heads: int, head_dim: int,
    state: int, groups: int, slots: int = 8, layers: int = 2,
    interpret: bool | None = None,
) -> float:
    """The state-carrying scan kernel (``ssm_scan``) vs gather,
    ``ops.ssm.ssd_apply``, scatter: ``rows`` rows of ``tokens`` tokens,
    each from its own slot of a [layers, slots, H, P, N] pool into
    another (row 0 back into its own), the pool aliased through the
    call. The error covers the rows' outputs AND the whole pool after:
    a slot nobody wrote must come back as it went in."""
    from llm_consensus_tpu.ops import ssm
    from llm_consensus_tpu.ops.pallas.ssm_scan import ssm_scan

    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (rows, tokens, heads, head_dim))
    b = jax.random.normal(k[1], (rows, tokens, groups, state))
    c = jax.random.normal(k[2], (rows, tokens, groups, state))
    dt = jax.nn.softplus(jax.random.normal(k[3], (rows, tokens, heads)) - 2)
    a = -jnp.exp(jax.random.normal(k[4], (heads,)))
    pool = jax.random.normal(
        k[5], (layers, slots, heads, head_dim, state), jnp.float32
    )
    slot_in = jnp.arange(1, rows + 1, dtype=jnp.int32) % slots
    slot_out = slot_in.at[1:].set((slot_in[1:] + rows) % slots)
    layer = jnp.int32(layers - 1)
    with jax.default_matmul_precision("highest"):
        terms = ssm.ssd_terms(x, b, c, dt, a)
        want_y, s1 = ssm.ssd_apply(terms, pool[layer, slot_in])
        want_pool = pool.at[layer, slot_out].set(s1)
        got_y, got_pool = jax.jit(
            lambda t, p: ssm_scan(
                t, p, layer, slot_in, slot_out, interpret=interpret
            )
        )(terms, pool)
    return max(max_err(got_y, want_y), max_err(got_pool, want_pool))


def rms_norm_error(
    *, seed: int, shape: tuple[int, ...], dtype=jnp.float32, eps: float = 1e-5,
    blk: int = 256, interpret: bool | None = None,
) -> float:
    """``fused_rms_norm`` vs :func:`~llm_consensus_tpu.ops.norms.rms_norm`."""
    from llm_consensus_tpu.ops.norms import rms_norm
    from llm_consensus_tpu.ops.pallas.norms import fused_rms_norm

    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, shape[-1:]) * 0.1 + 1.0).astype(dtype)
    got = jax.jit(
        lambda: fused_rms_norm(x, w, eps, blk=blk, interpret=interpret)
    )()
    if got.dtype != x.dtype:
        raise AssertionError(f"output dtype {got.dtype} != input {x.dtype}")
    return max_err(got, rms_norm(x, w, eps))


def quant_matmul_error(
    *, seed: int, m: int, k: int, n: int, n_layers: int = 0,
    interpret: bool | None = None,
) -> float:
    """The int8 weight matmul kernel vs dequantize + ``jnp`` dot.

    ``n_layers`` > 0 runs the stacked variant (layer index by scalar
    prefetch) against the same reference on that layer's slice.
    """
    from llm_consensus_tpu.ops.pallas.quant_matmul import (
        quant_matmul_2d,
        quant_matmul_stacked,
    )

    key = jax.random.PRNGKey(seed)
    lead = (n_layers,) if n_layers else ()
    w = jax.random.randint(key, (*lead, k, n), -127, 128, jnp.int8)
    # Scales that keep outputs O(1) at K in the thousands.
    s = jnp.abs(jax.random.normal(key, (*lead, 1, n), jnp.float32)) * 1e-4 + 5e-5
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k), jnp.bfloat16)
    if n_layers:
        layer = n_layers - 1
        got = jax.jit(
            lambda: quant_matmul_stacked(
                x, w, s, jnp.int32(layer), out_dtype=jnp.float32,
                interpret=interpret,
            )
        )()
        w, s = w[layer], s[layer]
    else:
        got = jax.jit(
            lambda: quant_matmul_2d(
                x, w, s, out_dtype=jnp.float32, interpret=interpret
            )
        )()
    with jax.default_matmul_precision("highest"):
        ref = jnp.dot(
            x.astype(jnp.float32), w.astype(jnp.float32)
        ) * s
    return max_err(got, ref)
