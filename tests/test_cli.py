"""CLI / REPL driver tests (reference L4, ``src/main.rs:428-471``)."""

import json

import pytest

from llm_consensus_tpu.cli import build_parser, main


def test_parser_defaults():
    args = build_parser().parse_args([])
    assert args.backend == "fake"
    assert args.max_rounds == 5  # reference hard-codes 5 (src/main.rs:299)
    assert args.question is None


def test_one_shot_question_fake_backend(capsys):
    rc = main(["--backend", "fake", "--question", "What is 2+2?", "--seed", "0"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "What is 2+2?" in out  # FakeBackend echoes the question


def test_panel_file_roundtrip(tmp_path, capsys):
    from llm_consensus_tpu.consensus.personas import default_panel, save_panel

    panel_file = tmp_path / "panel.json"
    save_panel(default_panel()[:2], panel_file)
    rc = main(
        ["--backend", "fake", "--panel", str(panel_file), "--question", "hi"]
    )
    assert rc == 0


def test_hf_checkpoint_backend(tmp_path, capsys):
    """--backend local --hf-checkpoint loads real HF weights end-to-end."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    config = transformers.LlamaConfig(
        vocab_size=384,  # >= ByteTokenizer's 259 ids
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    transformers.LlamaForCausalLM(config).save_pretrained(
        tmp_path, safe_serialization=True
    )
    rc = main(
        [
            "--backend",
            "local",
            "--cpu",
            "--hf-checkpoint",
            str(tmp_path),
            "--quant",
            "int8",
            "--question",
            "hi",
            "--max-new-tokens",
            "4",
            "--seed",
            "0",
        ]
    )
    assert rc == 0


def test_eval_requires_local_backend(capsys):
    rc = main(["--backend", "fake", "--eval-gsm8k", "synthetic"])
    assert rc == 2


def test_repl_loop_exit(monkeypatch, capsys):
    """REPL parity: prompts 'Enter a question: ', answers, 'exit' quits."""
    import asyncio
    import io

    from llm_consensus_tpu.backends.fake import FakeBackend
    from llm_consensus_tpu.cli import repl
    from llm_consensus_tpu.consensus.coordinator import (
        Coordinator,
        CoordinatorConfig,
    )
    from llm_consensus_tpu.consensus.personas import default_panel

    answers = iter(["What is up?\n", "exit\n"])
    monkeypatch.setattr(
        "sys.stdin", type("S", (), {"readline": lambda self: next(answers)})()
    )
    coord = Coordinator(
        default_panel(), FakeBackend(), CoordinatorConfig(seed=0)
    )
    asyncio.run(repl(coord))
    out = capsys.readouterr().out
    assert out.count("Enter a question: ") == 2
    assert "What is up?" in out


def test_eval_bundled_dataset_with_local_backend(capsys):
    """--eval-gsm8k bundled runs the harness on the packaged dataset
    through a (random-weight) local engine, emitting the JSON report."""
    import json

    from llm_consensus_tpu.cli import main

    rc = main(
        [
            "--backend", "local", "--cpu",
            "--model", "test-tiny",
            "--eval-gsm8k", "bundled",
            "--eval-n", "2",
            "--eval-limit", "2",
            "--max-new-tokens", "4",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["n_problems"] == 2
    assert report["n_candidates"] == 2


def test_eval_synthetic2_hard_task(capsys):
    """--eval-gsm8k synthetic2 runs the multi-step arith2 task through
    a (random-weight) local engine — CLI surface for the hard corpus."""
    import json

    from llm_consensus_tpu.cli import main

    rc = main(
        [
            "--backend", "local", "--cpu",
            "--model", "test-tiny",
            "--eval-gsm8k", "synthetic2",
            "--eval-n", "2",
            "--eval-limit", "2",
            "--max-new-tokens", "4",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["n_problems"] == 2
    assert report["n_candidates"] == 2


def test_cli_mesh_flag_shards_engine(capsys):
    """--mesh data=8 answers a one-shot question on a sharded engine."""
    from llm_consensus_tpu.cli import main

    rc = main(
        [
            "--backend", "local", "--cpu",
            "--model", "test-tiny",
            "--mesh", "data=8",
            "--question", "What is 2+2?",
            "--max-new-tokens", "4",
            "--max-rounds", "1",
            "--seed", "0",
        ]
    )
    assert rc == 0
    assert capsys.readouterr().out.strip()


def test_debate_mode_one_shot(capsys):
    from llm_consensus_tpu.cli import main

    rc = main(
        [
            "--backend", "local", "--cpu",
            "--model", "test-tiny",
            "--question", "What is 2+2?",
            "--debate", "4",
            "--max-rounds", "2",
            "--max-new-tokens", "4",
            "--seed", "0",
        ]
    )
    assert rc == 0
    assert capsys.readouterr().out.strip()


def test_debate_requires_local_and_question(capsys):
    from llm_consensus_tpu.cli import main

    assert main(["--debate", "4", "--question", "q"]) == 2  # fake backend
    assert main(["--backend", "local", "--cpu", "--model", "test-tiny", "--debate", "4"]) == 2


def test_debate_rejects_bad_n(capsys):
    from llm_consensus_tpu.cli import main

    rc = main([
        "--backend", "local", "--cpu", "--model", "test-tiny",
        "--question", "q", "--debate", "-1",
    ])
    assert rc == 2


def test_cli_stream_prints_completion(capsys):
    """--stream emits a single-model streamed completion."""
    from llm_consensus_tpu.cli import main

    rc = main(
        [
            "--backend", "local", "--cpu",
            "--model", "test-tiny",
            "--question", "hello there",
            "--stream",
            "--max-new-tokens", "6",
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert out.endswith("\n")


def test_cli_stream_requires_local_backend():
    from llm_consensus_tpu.cli import main

    assert main(["--stream", "--question", "q"]) == 2
    assert main(["--backend", "local", "--cpu", "--model", "test-tiny", "--stream"]) == 2


def test_plan_capacity_command(capsys):
    """--plan prints a config-only HBM plan and exits 1 when the config
    cannot fit the budget (scripting-friendly capacity checks)."""
    import json

    from llm_consensus_tpu.cli import main

    rc = main(
        [
            "--plan", "--model", "mixtral-8x7b", "--plan-n", "64",
            "--plan-context", "256", "--max-new-tokens", "128",
            "--plan-mesh", "expert=4,model=2",
        ]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["fits"] is True
    assert out["params_gib"] > out["kv_cache_gib"] > 0

    rc = main(
        [
            "--plan", "--model", "mixtral-8x7b", "--plan-n", "64",
            "--plan-context", "256", "--max-new-tokens", "128",
        ]
    )
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["fits"] is False  # 44.7 GiB on one chip


def test_serve_parser_defaults_and_dispatch(monkeypatch):
    """`serve` owns its parser (shared backend flags, gateway knobs) and
    main() dispatches to it before the main parser sees the argv."""
    from llm_consensus_tpu import cli

    args = cli.build_serve_parser().parse_args([])
    assert args.backend == "fake"
    assert args.port == 8080
    assert args.queue_bound == 64
    assert args.max_inflight == 8
    assert args.default_deadline_s is None

    seen = {}

    def fake_run(argv):
        seen["argv"] = argv
        return 0

    monkeypatch.setattr(cli, "_run_serve", fake_run)
    assert main(["serve", "--port", "0"]) == 0
    assert seen["argv"] == ["--port", "0"]


def test_serve_refuses_prefill_chunk_zero(capsys):
    """`serve --prefill-chunk 0` is an argparse error that says why:
    there is no prefill path but the chunked one."""
    from llm_consensus_tpu import cli

    with pytest.raises(SystemExit) as exc:
        cli.build_serve_parser().parse_args(["--prefill-chunk", "0"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "--prefill-chunk" in err
    assert "chunked prefill is the only prefill path" in err
    args = cli.build_serve_parser().parse_args(["--prefill-chunk", "16"])
    assert args.prefill_chunk == 16


def test_serve_subcommand_boots_and_drains_on_sigterm(tmp_path):
    """End-to-end `serve` process: ephemeral port, fake backend, one
    consensus request over HTTP, then SIGTERM -> graceful exit 0."""
    import json as _json
    import os
    import re
    import signal
    import subprocess
    import sys
    import time
    import urllib.request

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "llm_consensus_tpu", "serve", "--port", "0"],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )
    try:
        # Read the boot log in a thread: a bare readline() would block
        # past the deadline if the process stays alive but never prints
        # the listening line, turning one quiet server into a whole
        # tier-1 gate timeout instead of a clean assertion here.
        import queue as _queue
        import threading

        lines: _queue.Queue = _queue.Queue()
        threading.Thread(
            target=lambda: [lines.put(ln) for ln in proc.stdout],
            daemon=True,
        ).start()
        port, deadline = None, time.time() + 60
        while port is None and time.time() < deadline:
            try:
                line = lines.get(timeout=1.0)
            except _queue.Empty:
                assert proc.poll() is None, "serve process died before binding"
                continue
            m = re.search(r"listening on 127\.0\.0\.1:(\d+)", line)
            if m:
                port = int(m.group(1))
        assert port is not None, "never saw the listening log line"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/consensus",
            data=_json.dumps({"question": "What is 2+2?"}).encode(),
            headers={"Content-Type": "application/json"},
        )
        doc = _json.load(urllib.request.urlopen(req, timeout=30))
        assert doc["endorsed"] is True and doc["rounds"] >= 1
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()
