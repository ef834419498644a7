"""Checks of the benchmark's own arithmetic; no server, no jax.

    python3 -m pytest benchmark/tests -q      (or run this file)

Not part of the repo's tier-1 tests (those collect ``tests/`` only).
"""

import os
import random
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import stats  # noqa: E402
from generators import closed_loop_consensus, open_loop_generate  # noqa: E402

CHAT = {
    "rate_per_s": 4.0, "prompt_bytes": [65, 512], "max_new_tokens": [16, 256],
    "payload": {"stream": True, "temperature": 0.7}, "ramp_s": 6, "tail_s": 20,
}


def test_percentile_interpolates_like_numpy():
    xs = [10, 20, 30, 40]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 100) == 40
    assert stats.percentile(xs, 50) == 25
    assert abs(stats.percentile(xs, 95) - 38.5) < 1e-12
    assert stats.percentile([], 50) is None
    assert stats.percentile([7], 95) == 7


def test_filler_text_is_exact_and_starts_with_its_nonce():
    rng = random.Random(1)
    for n in (65, 168, 256, 2300):
        t = stats.filler_text(n, rng, "[ab.1.2]")
        assert len(t.encode()) == n and t.startswith("[ab.1.2]")


def test_every_seed_offers_the_same_work_in_another_order():
    a = open_loop_generate.schedule(CHAT, 1, 45)
    b = open_loop_generate.schedule(CHAT, 2**31 + 12345, 45)
    assert len(a) == len(b) == round(4.0 * 6) + round(4.0 * 45)

    def inside(plan):  # the window is [6, 51)
        return [x for x in plan if 6 <= x["due"] <= 51 + 1e-6]

    wa, wb = inside(a), inside(b)
    assert len(wa) == len(wb) == 180
    size = lambda plan: sorted(len(x["body"]["prompt"]) for x in plan)  # noqa: E731
    out = lambda plan: sorted(x["body"]["max_new_tokens"] for x in plan)  # noqa: E731
    assert size(wa) == size(wb) and out(wa) == out(wb)
    assert [x["due"] for x in wa] != [x["due"] for x in wb]
    # the last request is due exactly as the window shuts
    assert abs(a[-1]["due"] - 51) < 1e-9 and abs(b[-1]["due"] - 51) < 1e-9

    def gaps(plan):
        dues = [6.0] + [x["due"] for x in plan]
        return sorted(round(y - x, 9) for x, y in zip(dues, dues[1:]))

    assert gaps(wa) == gaps(wb)
    assert min(size(a)) >= 65 and max(size(a)) <= 512
    assert min(out(a)) >= 16 and max(out(a)) <= 256
    # no two prompts share a first page
    heads = {x["body"]["prompt"][:16] for x in a}
    assert len(heads) == len(a)


def _run(window, records, summaries, tokens_between_edges, slots=8):
    before = "gateway_tbt_seconds_count 100\n"
    after = f"gateway_tbt_seconds_count {100 + tokens_between_edges}\n"
    return types.SimpleNamespace(
        window=window, records=records, summaries=summaries, slots=slots,
        metrics_before=before, metrics_after=after)


def test_open_loop_counts_a_failed_request_as_the_worst():
    w = types.SimpleNamespace(t0=100.0, t1=110.0, seconds=10.0)
    rec = lambda due, secs, ok, tok: {  # noqa: E731
        "due": due, "t_done": due + secs, "seconds": secs, "ok": ok,
        "status": 200 if ok else 429, "error": "" if ok else "shed",
        "num_tokens": tok}
    records = [rec(100.0 + i * 0.5, 1.0, True, 10) for i in range(19)]
    records += [rec(109.9, 0.5, False, 0)]  # shed
    summaries = [{"new_tokens": 10}] * 18
    out = open_loop_generate.reduce(_run(w, records, summaries, 170))
    assert out["attempted"] == 20 and out["failed"] == 1
    # 170 tokens past a first one between the edges + 18 first tokens
    assert out["tokens_per_s"] == 18.8 and out["token_counts_agree"]
    # the shed one counts as the worst latency seen (1.0 s), not as 0.5
    assert out["request_p95_ms"] == 1000.0
    assert out["halves_p50_ms"] == [1000.0, 1000.0]


def test_closed_loop_counts_what_finished_inside_the_window():
    w = types.SimpleNamespace(t0=10.0, t1=20.0, seconds=10.0)
    rec = lambda done, secs, ok=True: {  # noqa: E731
        "t_start": done - secs, "t_done": done, "seconds": secs, "ok": ok,
        "status": 200, "error": ""}
    records = [rec(9.0, 5.0), rec(12.0, 4.0), rec(15.0, 6.0), rec(19.0, 8.0),
               rec(21.0, 5.0)]
    summaries = [{"new_tokens": 64}] * 10
    out = closed_loop_consensus.reduce(_run(w, records, summaries, 650))
    assert out["attempted"] == 3 and out["failed"] == 0
    assert out["question_p50_s"] == 6.0
    assert out["tokens_per_s"] == 66.0 and out["token_counts_agree"]
    # a counter that stopped counting does not pass for a slow server
    out = closed_loop_consensus.reduce(_run(w, records, summaries, 0))
    assert not out["token_counts_agree"]


if __name__ == "__main__":
    for name, fn in list(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
