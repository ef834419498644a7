"""Tests for logging setup and span tracing."""

import json
import logging

from llm_consensus_tpu.utils.logging import setup_logging
from llm_consensus_tpu.utils.tracing import (
    TraceStore,
    request_span,
    use_trace,
)


def test_setup_logging_levels():
    setup_logging("debug")
    assert logging.getLogger().level == logging.DEBUG
    setup_logging("warning,llm_consensus_tpu.consensus=debug")
    assert logging.getLogger().level == logging.WARNING
    assert (
        logging.getLogger("llm_consensus_tpu.consensus").level == logging.DEBUG
    )
    setup_logging("bogus-level")  # falls back to info, no crash
    assert logging.getLogger().level == logging.INFO


def test_request_spans_tree_and_summary():
    """Nested ``request_span``s under ``use_trace`` land on the trace as
    a tree (was: the flat Tracer's records/summary/dump_json)."""
    trace = TraceStore().start("question")
    with use_trace(trace):
        with request_span("evaluate", round=1):
            with request_span("decode"):
                pass
        with request_span("decode"):
            pass
    trace.finish()
    assert trace.n_spans == 3
    names = [s.name for s in trace.spans()]
    assert names.count("decode") == 2 and names.count("evaluate") == 1
    assert all(s.duration >= 0.0 for s in trace.spans())

    doc = json.loads(json.dumps(trace.to_dict()))  # what /debug/traces sends
    assert doc["n_spans"] == 3 and doc["finished"]
    top = {n["name"]: n for n in doc["spans"]}
    assert set(top) == {"evaluate", "decode"}
    assert top["evaluate"]["meta"] == {"round": 1}
    assert [c["name"] for c in top["evaluate"]["children"]] == ["decode"]
    # Outside use_trace a span is a silent no-op.
    with request_span("orphan"):
        pass
    assert trace.n_spans == 3


def _tiny_engine(draft_seed=None):
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.engine.engine import EngineConfig, InferenceEngine
    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.transformer import init_params

    cfg = get_config("test-tiny")

    def weights(seed):
        return init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)

    return InferenceEngine(
        cfg,
        weights(0),
        engine_config=EngineConfig(
            max_new_tokens=3, seq_buckets=(16,), batch_buckets=(1, 2)
        ),
        draft=None if draft_seed is None else (cfg, weights(draft_seed)),
    )


def test_engine_records_generate_spans():
    """The engine's one instrumentation site reports to the caller's
    request trace (was: to an ``InferenceEngine(tracer=...)``)."""
    eng = _tiny_engine()
    trace = TraceStore().start("request")
    with use_trace(trace):
        eng.generate_texts(["one", "two"])
    spans = [s for s in trace.spans() if s.name == "engine.generate"]
    assert len(spans) == 1
    assert spans[0].meta["n_real"] == 2
    assert spans[0].duration > 0
    eng.generate_texts(["three"])  # untraced: nothing recorded, no error
    assert len(trace.spans()) == len(spans)


def test_engine_records_speculative_spans():
    eng = _tiny_engine(draft_seed=7)
    trace = TraceStore().start("request")
    with use_trace(trace):
        eng.generate_texts_speculative(["one"])
    spans = [
        s for s in trace.spans() if s.name == "engine.generate_speculative"
    ]
    assert len(spans) == 1
    assert spans[0].meta["k_spec"] == 4


def test_earliest_stop_cut_and_tail_window():
    """The shared stop rules (utils/stops): earliest occurrence wins
    across stops; window covers worst-case one-byte-per-token emission
    even when the tokenizer's own encoding is shorter."""
    from llm_consensus_tpu.engine.tokenizer import ByteTokenizer
    from llm_consensus_tpu.utils.stops import (
        earliest_stop_cut,
        stop_tail_window,
    )

    assert earliest_stop_cut("abcdef", ["cd", "ef"]) == 2
    assert earliest_stop_cut("abcdef", ["ef", "cd"]) == 2  # order-free
    assert earliest_stop_cut("abcdef", ["zz"]) == -1
    assert earliest_stop_cut("", ["a"]) == -1

    tok = ByteTokenizer()
    assert stop_tail_window(tok, []) == 0
    # Byte tokenizer: window = byte length + slack.
    assert stop_tail_window(tok, ["\n\n"]) == 2 + 8
    assert stop_tail_window(tok, ["ab", "abcd"]) == 4 + 8

    class MergeTok:
        """Stub merge-based tokenizer: whole string -> one id."""

        def encode(self, s, add_bos=True):
            return [7]

    # Even though the tokenizer encodes the stop as ONE id, a model can
    # emit it one byte-ish token at a time: the byte length must win.
    assert stop_tail_window(MergeTok(), ["Final answer"]) == 12 + 8


def test_visible_id_filter_sizes_window_by_visible_count():
    """VisibleIdFilter: the tail window counts ids that actually decode
    to characters — empty-decoding ids (special/byte-fallback pieces)
    and skip_ids (EOS) must not consume window slots, or a stop
    stretched across them escapes the incremental check (r4 advisor).
    The returned slice stays CONTIGUOUS (empty ids kept, only skip_ids
    removed): byte-fallback fragments decode to nothing alone but
    contribute bytes in context."""
    from llm_consensus_tpu.utils.stops import VisibleIdFilter

    class Tok:
        """ids >= 100 decode to nothing (special pieces); 99 is EOS."""

        eos_id = 99

        def __init__(self):
            self.decode_calls = 0

        def decode(self, ids):
            self.decode_calls += 1
            return "".join(chr(ord("a") + i) for i in ids if i < 99)

    tok = Tok()
    f = VisibleIdFilter(tok, skip_ids=(tok.eos_id,))
    # Window of 2 over a tail full of empty/skip ids extends past them
    # to reach 2 visible tokens; empty ids stay in the slice, EOS out.
    assert f.visible_tail([0, 100, 101, 99, 1], 2) == [0, 100, 101, 1]
    assert f.visible_tail([], 3) == []
    assert f.visible_tail([0, 1, 2], 0) == []
    # Exactly `window` visible ids bound the slice from the left.
    assert f.visible_tail([3, 4, 0, 100, 1], 2) == [0, 100, 1]
    # Memoized: a second pass over the same ids does no new decodes.
    before = tok.decode_calls
    f.visible_tail([0, 100, 101, 99, 1], 2)
    assert tok.decode_calls == before
    # Scan bound: at most 8 * window raw ids are examined, so a
    # pathological all-empty tail degrades (under-covers) instead of
    # scanning the whole history.
    ids = [5] + [100] * 100 + [6]
    assert f.visible_tail(ids, 2) == [100] * 15 + [6]


def test_visible_id_filter_keeps_fragment_assembly():
    """Contiguity matters: a stop character split across byte-fallback
    fragments (each decoding to "" alone) must still assemble in the
    window decode — dropping empty ids would make the stop invisible
    to the incremental check."""
    from llm_consensus_tpu.utils.stops import VisibleIdFilter

    class FragTok:
        """50 and 51 decode to nothing alone; the PAIR decodes to the
        em dash. Other ids are ascii letters."""

        eos_id = 99

        def decode(self, ids):
            out, i = [], 0
            while i < len(ids):
                if ids[i] == 50 and i + 1 < len(ids) and ids[i + 1] == 51:
                    out.append("—")
                    i += 2
                    continue
                if ids[i] not in (50, 51, 99):
                    out.append(chr(ord("a") + ids[i]))
                i += 1
            return "".join(out)

    tok = FragTok()
    f = VisibleIdFilter(tok, skip_ids=(tok.eos_id,))
    tail = f.visible_tail([0, 1, 50, 51, 2], 3)
    assert tail == [0, 1, 50, 51, 2]
    assert "—" in tok.decode(tail)
