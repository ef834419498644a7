"""Stable names and one clock (PR 26).

What a trace reduction and the benchmark's counters lean on, checked on
the CPU with ``test-tiny``:

(a) every step program the batcher jits lowers to an XLA module with a
    fixed name (``jit_decode_step``, ``jit_fused_step``, ...), whatever
    its bucket;
(b) a ``jax.profiler`` trace taken around a burst holds the batcher
    loop's phases (``batcher.admit`` ...) and one ``profile.anchor`` on
    a host plane, the anchor carrying ``perf_counter_ns``;
(c) the counters fed where the work happens add up: generated tokens
    to the summaries' ``new_tokens``, prefill tokens to the prompt
    tokens that were not served from shared pages, and the phases of
    ``gateway_batcher_phase_seconds_total`` to the thread's wall time.

No test here logs or asserts on decoded model text.
"""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models.configs import get_config
from llm_consensus_tpu.models.transformer import init_params
from llm_consensus_tpu.server import metrics as M
from llm_consensus_tpu.serving import continuous
from llm_consensus_tpu.serving.continuous import (
    ContinuousBatcher,
    ContinuousConfig,
)
from llm_consensus_tpu.utils import tracing

CFG = get_config("test-tiny")
PAGE = 16
_CCFG = dict(
    max_slots=4,
    page_size=PAGE,
    n_pages=64,
    pages_per_seq=8,
    max_new_tokens=6,
    seq_buckets=(16, 32, 64),
    prefill_chunk=16,
    share_prefix=True,
)
# Two full pages that the second and third prompt share, then a
# character of their own right at the page edge: whole pages are
# mapped, and no partly shared page is copied.
_HEADER = "0123456789abcdef" * 2
PROMPTS = ["x: a prompt of its own", _HEADER + "A tail", _HEADER + "B tail"]


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


class _Spy:
    """Stands in for one jitted step program: lowers it with the
    arguments of its first real call, keeps the module's name, and
    calls through."""

    def __init__(self, jitted, seen: set):
        self.jitted, self.seen, self.done = jitted, seen, False

    def __call__(self, *args):
        if not self.done:
            self.done = True
            text = self.jitted.lower(*args).as_text()
            self.seen.add(re.match(r"module @(\w+)", text).group(1))
        return self.jitted(*args)

    def build(self, *args, **kwargs):  # the batcher's build-ahead
        return self.jitted.build(*args, **kwargs)


def _spy_on(batcher, seen: set) -> None:
    """Put a spy before every step program of ``batcher``: the three
    jitted at construction, and the per-bucket families behind their
    ``_*_fn`` getters — and the row patch of a fetch (PR 36), whose
    module a trace shows between them."""
    for attr in ("_jit_decode", "_jit_rounds", "_jit_spec", "_jit_apply_rows"):
        if hasattr(batcher, attr):
            setattr(batcher, attr, _Spy(getattr(batcher, attr), seen))
    for getter in ("_chunk_fn", "_fused_fn", "_chunk_fn_d", "_denoise_fn"):
        real, spies = getattr(batcher, getter), {}

        def spied(*key, _real=real, _spies=spies):
            if key not in _spies:
                _spies[key] = _Spy(_real(*key), seen)
            return _spies[key]

        setattr(batcher, getter, spied)


def _modules_of(params, prompts=PROMPTS, draft=None, **cfgkw) -> set:
    seen: set = set()
    b = ContinuousBatcher(
        CFG, params, config=ContinuousConfig(**{**_CCFG, **cfgkw}),
        draft=draft,
    )
    try:
        _spy_on(b, seen)
        for f in [b.submit(p) for p in prompts]:
            f.result(timeout=120)
    finally:
        b.close()
    return seen


@pytest.mark.parametrize(
    "cfgkw, with_draft, expected",
    [
        ({}, False,
         {"jit_decode_step", "jit_fused_step", "jit_prefill_chunk",
          "jit_apply_rows"}),
        ({"decode_rounds": 2}, False, {"jit_rounds_step", "jit_apply_rows"}),
        ({"spec_k": 2}, True,
         {"jit_verify_step", "jit_prefill_chunk_draft", "jit_apply_rows"}),
    ],
    ids=["chunked", "rounds", "draft"],
)
def test_step_programs_lower_to_named_modules(
    params, cfgkw, with_draft, expected
):
    draft = (CFG, params) if with_draft else None
    seen = _modules_of(params, draft=draft, **cfgkw)
    assert expected <= seen, seen
    # No step program is left to jax's default for a partial or a
    # bound method.
    assert not {m for m in seen if "unknown" in m or "sample" in m}, seen


def test_block_diffusion_programs_lower_to_named_modules():
    """A block-diffusion model's blocks run as ``jit_denoise_step``,
    with chunk lanes riding or not, beside the standalone chunks."""
    cfg = get_config("test-tiny-sdar")
    sdar = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    seen: set = set()
    b = ContinuousBatcher(cfg, sdar, config=ContinuousConfig(**_CCFG))
    try:
        _spy_on(b, seen)
        for f in [b.submit(p) for p in PROMPTS]:
            f.result(timeout=120)
    finally:
        b.close()
    assert {"jit_denoise_step", "jit_prefill_chunk", "jit_apply_rows"} <= seen
    assert not {m for m in seen if "unknown" in m or "sample" in m}, seen


def test_one_named_program_a_width_whatever_the_bucket(params):
    """Prompts of three buckets (one, two and four 16-token chunks), sent
    one at a time and then together: the chunk programs are keyed by what
    changes their HLO — chunk width, lanes, MoE pin — so a dense model has
    ONE fused and ONE chunk program object a width (one lane, and the wide
    one), shared by every bucket, and each lowers to the module name a
    trace reduction finds it by (``fused_dev_ms.panel`` reads
    ``jit_fused_step`` whatever L)."""
    by_bucket = ["b16: tiny", "b32: " + "m" * 20, "b64: " + "l" * 50]
    seen: set = set()
    keys: dict[str, set] = {"_chunk_fn": set(), "_fused_fn": set()}
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        _spy_on(b, seen)
        for getter, called in keys.items():
            spied = getattr(b, getter)
            setattr(b, getter, lambda *k, _s=spied, _c=called: (
                _c.add(k), _s(*k))[1])
        for p in by_bucket:
            b.submit(p).result(timeout=120)
        for f in [b.submit("again " + p) for p in by_bucket]:
            f.result(timeout=120)
        wide = b._lanes_for(16)
        fused, chunk = dict(b._jit_fused), dict(b._jit_chunk)
    finally:
        b.close()
    assert seen == {
        "jit_decode_step", "jit_fused_step", "jit_prefill_chunk",
        "jit_apply_rows",
    }
    assert wide == 4
    # Called for three buckets and two widths ...
    assert {k[2] for k in keys["_fused_fn"]} >= {32, 64}
    assert {k[1] for k in keys["_fused_fn"] | keys["_chunk_fn"]} == {1, wide}
    # ... and served by one program object a width.
    pin = CFG.moe_dense_decode_tokens  # the same for every bucket
    assert sorted(fused) == sorted(chunk) == [(16, 1, pin), (16, wide, pin)]


def _counter(name: str, **labels) -> float:
    fam = M.REGISTRY.get(name)
    return fam.labels(**labels).value


def _phase_seconds() -> dict:
    return {
        p: _counter("gateway_batcher_phase_seconds_total", phase=p)
        for p in continuous._PHASES
    }


def _wait_for_flush() -> None:
    """Return right after an idle loop has moved its phase seconds into
    the counter (it does so once per iteration, every 0.1 s when idle):
    a reading taken now lags the thread by almost nothing."""
    idle = _counter("gateway_batcher_phase_seconds_total", phase="idle")
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        time.sleep(0.001)
        if _counter(
            "gateway_batcher_phase_seconds_total", phase="idle"
        ) != idle:
            return
    raise AssertionError("the idle loop flushed no phase seconds in 2 s")


@pytest.fixture(scope="module")
def burst(params, tmp_path_factory):
    """One batcher, one burst under the program's own profile hook;
    what (b) and (c) read."""
    logdir = str(tmp_path_factory.mktemp("profile"))
    b = ContinuousBatcher(CFG, params, config=ContinuousConfig(**_CCFG))
    try:
        # Warm every program first, with prompts that share nothing
        # with the burst: the phases should time serving, not compiles.
        for f in [b.submit(p) for p in
                  ("w: warm one", "y" * 40 + " warm two", "z" * 40)]:
            f.result(timeout=120)
        _wait_for_flush()
        before = {
            "generated": _counter("gateway_generated_tokens_total"),
            "prefill": _counter("gateway_prefill_tokens_total"),
            "shared": _counter("gateway_prefix_pages_shared"),
            "copied": _counter("gateway_prefix_pages_copied"),
            "phases": _phase_seconds(),
        }
        t0 = time.perf_counter()
        with tracing.trace_jax_profile(logdir):
            pc0 = time.perf_counter_ns()
            outs = [f.result(timeout=120)
                    for f in [b.submit(p) for p in PROMPTS]]
            pc1 = time.perf_counter_ns()
        _wait_for_flush()
        wall = time.perf_counter() - t0
        after = {
            "generated": _counter("gateway_generated_tokens_total"),
            "prefill": _counter("gateway_prefill_tokens_total"),
            "shared": _counter("gateway_prefix_pages_shared"),
            "copied": _counter("gateway_prefix_pages_copied"),
            "phases": _phase_seconds(),
        }
    finally:
        b.close()
    return {"logdir": logdir, "outs": outs, "before": before,
            "after": after, "wall": wall, "pc": (pc0, pc1)}


def test_profile_holds_phases_and_anchor(burst):
    found = glob.glob(os.path.join(
        burst["logdir"], "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1, found
    data = jax.profiler.ProfileData.from_file(found[0])
    names: dict[str, list] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("batcher.", "profile.")):
                    names.setdefault(ev.name, []).append(ev)
    for want in ("batcher.admit", "batcher.dispatch", "batcher.device_wait",
                 "batcher.retire"):
        assert want in names, sorted(names)
    # The metadata rides as stats and leaves the name alone.
    kinds = {dict(ev.stats).get("kind") for ev in names["batcher.dispatch"]}
    assert kinds & {"prefill", "fused", "decode"}, kinds
    assert len(names["profile.anchor"]) == 1
    anchor = names["profile.anchor"][0]
    stamp = int(dict(anchor.stats)["perf_counter_ns"])
    # The anchor was stamped after start_trace and before the burst.
    assert stamp <= burst["pc"][0]
    # With it, a perf_counter stamp has a place on the profile's clock:
    # every phase of the burst lies between its two ends there.
    offset = anchor.start_ns - stamp
    lo, hi = burst["pc"][0] + offset, burst["pc"][1] + offset
    inside = [ev for ev in names["batcher.dispatch"]
              if lo <= ev.start_ns <= hi]
    assert len(inside) >= len(PROMPTS), (len(inside), lo, hi)


def test_token_counters_add_up(burst):
    outs, before, after = burst["outs"], burst["before"], burst["after"]
    new_tokens = sum(o.timing["new_tokens"] for o in outs)
    assert new_tokens == sum(o.num_tokens for o in outs) > 0
    assert after["generated"] - before["generated"] == new_tokens
    # Whole pages mapped from the registry, none copied: what the chunk
    # programs computed is the rest of the prompts.
    assert after["copied"] == before["copied"]
    shared_pages = sum(o.timing["header_pages_shared"] for o in outs)
    assert shared_pages == after["shared"] - before["shared"] == 2
    prompt_tokens = sum(o.timing["prompt_tokens"] for o in outs)
    assert (after["prefill"] - before["prefill"]
            == prompt_tokens - shared_pages * PAGE)


def test_phases_cover_the_loop(burst):
    before, after = burst["before"]["phases"], burst["after"]["phases"]
    grew = {p: after[p] - before[p] for p in before}
    assert all(v >= 0 for v in grew.values()), grew
    assert abs(sum(grew.values()) - burst["wall"]) <= 0.05 * burst["wall"], (
        grew, burst["wall"])
    # The burst did work in every phase but restore (no host tier), and
    # the loop then idled.
    for p in ("admit", "dispatch", "device_wait", "retire", "idle"):
        assert grew[p] > 0, grew
    assert grew["restore"] == 0.0


def test_device_memory_gauge_filled_at_render(burst, monkeypatch):
    """The gauge is filled by a render hook that the first batcher (the
    ``burst`` fixture's, at the latest) installed, from the allocator's
    numbers: the largest device wins, and a device that reports none
    (the CPU) adds no sample."""
    class Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    fam = M.REGISTRY.get("gateway_device_memory_bytes")
    assert continuous._fill_device_memory in M.REGISTRY._render_hooks
    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_limit": 16}),
        Dev({"bytes_in_use": 7, "peak_bytes_in_use": 8, "bytes_limit": 16}),
        Dev(None),
    ])
    text = M.REGISTRY.render()
    assert 'gateway_device_memory_bytes{kind="in_use"} 7' in text
    assert 'gateway_device_memory_bytes{kind="peak"} 9' in text
    assert 'gateway_device_memory_bytes{kind="limit"} 16' in text
    assert fam.labels(kind="peak").value == 9
