"""CLI / REPL driver — parity with the reference's L4 layer.

The reference REPL (``src/main.rs:428-471``): prompt ``"Enter a question: "``,
read a line, ``exit`` terminates, ask the Coordinator, poll readiness
every 500 ms (a hot spin, ``src/main.rs:448-459``), print the final
answer, reset. Differences here, per SURVEY.md §7 step 5:

- the readiness poll is a real ``await`` on the protocol task — no spin;
- panel/backends/round-cap come from flags and JSON config instead of
  hard-coded literals (reference ``src/main.rs:359-426`` + TODO at
  ``:299``);
- a missing API key cannot happen: the default substrate is local. A
  ``fake`` backend stands in where the reference required
  ``GEMINI_API_KEY`` or died (``src/main.rs:354-357``);
- ``--eval-gsm8k`` runs the batch GSM8K harness instead of the REPL.

Run: ``python -m llm_consensus_tpu [--backend fake|local] ...``
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import sys

from llm_consensus_tpu.backends.base import SamplingParams
from llm_consensus_tpu.backends.fake import FakeBackend
from llm_consensus_tpu.consensus.coordinator import Coordinator, CoordinatorConfig
from llm_consensus_tpu.consensus.personas import default_panel, load_panel

log = logging.getLogger("llm_consensus_tpu")


def _init_logging() -> None:
    """env_logger parity (reference ``src/main.rs:352``): level comes from
    the ``LLM_CONSENSUS_LOG`` env var (RUST_LOG convention, default info)."""
    level = os.environ.get("LLM_CONSENSUS_LOG", "info").upper()
    logging.basicConfig(
        level=getattr(logging, level, logging.INFO),
        format="[%(asctime)s %(levelname)s %(name)s] %(message)s",
    )


def _load_checkpoint_params(cfg, path: str):
    """Load params from either checkpoint layout.

    A training-run directory (training.loop's LATEST-pointer layout,
    incl. a concrete ``step_N``/legacy flat dir holding ``state``)
    restores params-for-inference — train with this repo, serve the
    same dir with no export step. Anything else is a ``save_params``
    directory. Applies to --checkpoint and --draft-checkpoint alike.
    """
    from pathlib import Path

    from llm_consensus_tpu.checkpoint.io import (
        load_params,
        restore_params_for_inference,
    )

    root = Path(path)
    is_train_dir = (
        (root / "LATEST").exists()
        or (root / "state").exists()
        or any(root.glob("step_*/state"))
    )
    if is_train_dir:
        import jax.numpy as _jnp

        params, step = restore_params_for_inference(cfg, root, _jnp.bfloat16)
        log.info("loaded train checkpoint %s (step %s)", root, step)
        return params
    return load_params(path)



def _printable(text: str) -> str:
    """Model output for stdout: lone surrogates (the ByteTokenizer's
    reversible stand-ins for invalid bytes) render as U+FFFD instead of
    crashing the terminal's strict UTF-8 encoder. Display-only — the
    protocol/engine surfaces keep the exact reversible text."""
    return "".join(
        "\ufffd" if 0xD800 <= ord(ch) <= 0xDFFF else ch for ch in text
    )

def require_tpu(cpu_pinned: bool) -> None:
    """Device programs run on a TPU or, pinned there on purpose
    (``--cpu``: tests, debugging), on the CPU — never on whatever JAX
    fell back to."""
    import jax

    platform = jax.default_backend()
    if not cpu_pinned and platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX's default backend here is {platform!r}. Pass "
            "--cpu to run on the CPU on purpose."
        )


def random_params(cfg, key, quant: str):
    """Random weights for a preset served without a checkpoint; with
    ``quant`` they are born quantized (the bf16 tree of a 7B preset does
    not fit a 16 GB chip beside its int8 copy)."""
    from llm_consensus_tpu.models.transformer import (
        init_params,
        init_params_quantized,
    )

    if quant == "none":
        return init_params(cfg, key)
    return init_params_quantized(cfg, key, bits=8 if quant == "int8" else 4)


def _build_backend(args):
    if args.backend == "fake":
        return FakeBackend()
    # Local on-device inference ("local" = engine whole-batch programs,
    # "continuous" = token-level continuous batching over the paged
    # cache with shared-prefix CoW page tables + chunked prefill).
    # Import lazily: jax/device init is heavy
    # and the fake path must stay instant.
    import jax

    require_tpu(args.cpu)

    from llm_consensus_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    from llm_consensus_tpu.backends.local import LocalBackend
    from llm_consensus_tpu.engine.engine import EngineConfig, InferenceEngine
    from llm_consensus_tpu.engine.tokenizer import load_tokenizer
    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.transformer import init_params

    if getattr(args, "model_spec", None):
        if args.backend != "continuous":
            raise SystemExit(
                "--model-spec needs --backend continuous (the "
                "multi-model plane is built from continuous engines)"
            )
        return _build_modelset_backend(args)
    if args.hf_checkpoint:
        from llm_consensus_tpu.models.hf_loader import (
            config_from_hf,
            load_hf_params,
        )

        cfg = config_from_hf(args.hf_checkpoint, name=args.model)
        params = load_hf_params(cfg, args.hf_checkpoint)
    elif args.checkpoint:
        cfg = get_config(args.model)
        params = _load_checkpoint_params(cfg, args.checkpoint)
    else:
        cfg = get_config(args.model)
        if args.layers:
            cfg = cfg.with_layers(args.layers)
        log.warning(
            "No --checkpoint given: using RANDOM weights for %s "
            "(protocol/e2e plumbing only; text will be gibberish).",
            cfg.name,
        )
        params = random_params(cfg, jax.random.PRNGKey(0), args.quant)
    if args.layers and args.layers != cfg.n_layers:
        from llm_consensus_tpu.models.transformer import first_layers

        cfg, params = first_layers(cfg, params, args.layers)
    draft = None
    if args.draft_checkpoint and not args.draft_model:
        raise SystemExit(
            "--draft-checkpoint requires --draft-model (which preset "
            "should load those weights?)"
        )
    if args.draft_model:
        dcfg = get_config(args.draft_model)
        if args.draft_checkpoint:
            dparams = _load_checkpoint_params(dcfg, args.draft_checkpoint)
        else:
            log.warning(
                "No --draft-checkpoint: random draft weights for %s "
                "(speculation stays exact but accepts ~nothing).",
                dcfg.name,
            )
            dparams = init_params(dcfg, jax.random.PRNGKey(1))
        draft = (dcfg, dparams)
    mesh = None
    if args.mesh:
        from llm_consensus_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(**_parse_axes(args.mesh)))
        if mesh.shape.get("seq", 1) > 1:
            cfg = cfg.with_(use_ring=True)
        # Shard now and drop the one-device copy: while the engine or
        # batcher builds its cache beside them, device 0 must hold its
        # share of the weights, not all of them.
        from llm_consensus_tpu.parallel.partitioning import shard_params

        params = shard_params(params, mesh)
        if draft is not None:
            draft = (draft[0], shard_params(draft[1], mesh))
    if args.backend == "continuous":
        from llm_consensus_tpu.serving.continuous import (
            ContinuousBackend,
            ContinuousBatcher,
            ContinuousConfig,
        )

        if args.quant != "none":
            # Same weight-only quantization the engine path applies
            # (paged decode + chunk prefill read QuantizedTensor leaves
            # through ops.quant.matmul exactly like the dense programs).
            # Checkpoint weights quantize here; random ones were born
            # quantized and pass through untouched.
            from llm_consensus_tpu.ops.quant import quantize_params

            params = quantize_params(
                params, bits=8 if args.quant == "int8" else 4
            )
        if draft is not None and args.spec_k <= 0:
            raise SystemExit(
                "--draft-model on --backend continuous needs --spec-k > 0 "
                "(draft tokens proposed per verify round)"
            )
        from llm_consensus_tpu.serving.control import (
            AdaptiveController,
            ControlConfig,
            resolve_hbm_gbps,
        )

        serve_config = ContinuousConfig(
            max_slots=args.serve_slots,
            max_new_tokens=args.max_new_tokens,
            prefill_chunk=args.prefill_chunk,
            share_prefix=not args.no_share_prefix,
            host_cache_bytes=args.host_cache_mb << 20,
            pipeline_depth=args.pipeline_depth,
            ragged_attention=not args.no_ragged_attention,
            spec_k=args.spec_k if draft is not None else 0,
            decode_rounds=args.decode_rounds,
            # "auto" resolves the roofline peak from the per-platform
            # table (PR 15); a number passes through unchanged.
            hbm_gbps=resolve_hbm_gbps(args.hbm_gbps),
        )
        control = ControlConfig() if args.adaptive else None
        if args.replicas > 1:
            # Prefix-affinity replica fleet (PR 14): K batchers behind
            # the one gateway, routed by resident-chain affinity with
            # preempt-to-host-tier under overload. --host-cache-mb
            # budgets the ONE fleet-shared store.
            from llm_consensus_tpu.serving.fleet import (
                FleetBackend,
                FleetConfig,
                ReplicaSet,
            )

            role = args.role
            if "," in role:
                role = tuple(r.strip() for r in role.split(","))
            host_store = None
            if args.host_store:
                # Remote page-store tier (PR 16): the fleet's shared
                # host tier lives in another process; --host-cache-mb
                # still gates tier ENGAGEMENT (the budget itself is
                # the server's).
                from llm_consensus_tpu.serving.remote_store import (
                    RemotePageStore,
                )

                host_store = RemotePageStore(args.host_store)
            return FleetBackend(
                ReplicaSet(
                    cfg,
                    params,
                    tokenizer=load_tokenizer(args.tokenizer),
                    config=serve_config,
                    host_store=host_store,
                    fleet=FleetConfig(
                        replicas=args.replicas,
                        role=role,
                        # Keep the router's wedged-replica threshold in
                        # lockstep with the gateway's /readyz one: two
                        # independent defaults would let /readyz report
                        # a replica wedged while the router still
                        # routes to it (or vice versa). The main
                        # parser has no --ready-stall-s; fall back to
                        # the serve default.
                        ready_stall_s=getattr(
                            args, "ready_stall_s", 10.0
                        ),
                    ),
                    mesh=mesh,
                    draft=draft,
                    control=control,
                )
            )
        single_kw = {}
        if args.host_store:
            from llm_consensus_tpu.serving.remote_store import (
                RemotePageStore,
            )

            single_kw["host_store"] = RemotePageStore(args.host_store)
        batcher = ContinuousBatcher(
            cfg,
            params,
            tokenizer=load_tokenizer(args.tokenizer),
            config=serve_config,
            mesh=mesh,
            draft=draft,
            controller=(
                AdaptiveController(control) if control is not None else None
            ),
            **single_kw,
        )
        return ContinuousBackend(batcher)
    engine = InferenceEngine(
        cfg,
        params,
        tokenizer=load_tokenizer(args.tokenizer),
        engine_config=EngineConfig(
            max_new_tokens=args.max_new_tokens, quant=args.quant
        ),
        mesh=mesh,
        draft=draft,
    )
    return LocalBackend(engine)


def _parse_model_spec(raw: str) -> dict[str, str]:
    """``"name=large,preset=llama-1b,draft_from=small"`` -> dict.
    Validates keys at parse time so a typo is argparse-style usage
    feedback, not a KeyError mid-engine-build."""
    allowed = {
        "name", "preset", "checkpoint", "tokenizer", "slots",
        "spec_k", "replicas", "adaptive", "draft_from",
    }
    kv: dict[str, str] = {}
    for part in raw.split(","):
        k, sep, v = part.partition("=")
        k = k.strip()
        if not sep or not k or not v.strip():
            raise SystemExit(
                f"bad --model-spec entry {part!r} (want KEY=VAL,...)"
            )
        if k not in allowed:
            raise SystemExit(
                f"unknown --model-spec key {k!r} (have {sorted(allowed)})"
            )
        kv[k] = v.strip()
    for req in ("name", "preset"):
        if req not in kv:
            raise SystemExit(f"--model-spec needs {req}= (got {raw!r})")
    return kv


def _build_modelset_backend(args):
    """Build the multi-model serving plane (PR 18) from --model-spec
    flags: one engine per member, cross-model draft pairings resolved
    through vocab alignment, one ModelSetBackend behind the gateway.
    Global continuous-serving flags (--prefill-chunk, --host-cache-mb,
    --decode-rounds, ...) set every member's baseline; per-member keys
    (slots, spec_k, replicas, adaptive) override. Each member gets its
    OWN ContinuousConfig instance — the live-knob aliasing contract is
    per model, never across models."""
    import jax

    from llm_consensus_tpu.engine.tokenizer import load_tokenizer
    from llm_consensus_tpu.models.configs import get_config
    from llm_consensus_tpu.models.transformer import init_params
    from llm_consensus_tpu.serving.continuous import ContinuousConfig
    from llm_consensus_tpu.serving.control import (
        ControlConfig,
        resolve_hbm_gbps,
    )
    from llm_consensus_tpu.serving.fleet import FleetConfig
    from llm_consensus_tpu.serving.modelset import (
        ModelSet,
        ModelSetBackend,
        ModelSpec,
    )

    specs = []
    for i, raw in enumerate(args.model_spec):
        kv = _parse_model_spec(raw)
        cfg = get_config(kv["preset"])
        if kv.get("checkpoint"):
            params = _load_checkpoint_params(cfg, kv["checkpoint"])
        else:
            log.warning(
                "member %r: no checkpoint — RANDOM weights for %s "
                "(plumbing only; text will be gibberish).",
                kv["name"],
                cfg.name,
            )
            # Distinct seed per member: two members of the same preset
            # must not alias weights (their store scopes and consensus
            # roles differ).
            params = init_params(cfg, jax.random.PRNGKey(i))
        pairs = bool(kv.get("draft_from"))
        config = ContinuousConfig(
            max_slots=int(kv.get("slots", args.serve_slots)),
            max_new_tokens=args.max_new_tokens,
            prefill_chunk=args.prefill_chunk,
            share_prefix=not args.no_share_prefix,
            host_cache_bytes=args.host_cache_mb << 20,
            pipeline_depth=args.pipeline_depth,
            ragged_attention=not args.no_ragged_attention,
            spec_k=int(kv.get("spec_k", args.spec_k)) if pairs else 0,
            decode_rounds=args.decode_rounds,
            hbm_gbps=resolve_hbm_gbps(args.hbm_gbps),
        )
        replicas = int(kv.get("replicas", 1))
        fleet = None
        if replicas > 1:
            fleet = FleetConfig(
                replicas=replicas,
                ready_stall_s=getattr(args, "ready_stall_s", 10.0),
            )
        adaptive = kv.get("adaptive")
        control = None
        if adaptive == "1" or (adaptive is None and args.adaptive):
            control = ControlConfig()
        specs.append(
            ModelSpec(
                name=kv["name"],
                cfg=cfg,
                params=params,
                tokenizer=load_tokenizer(
                    kv.get("tokenizer") or args.tokenizer
                ),
                config=config,
                fleet=fleet,
                draft_from=kv.get("draft_from"),
                control=control,
            )
        )
    return ModelSetBackend(ModelSet(specs, default=args.model_default))


def _prefill_chunk_width(text: str) -> int:
    """``--prefill-chunk``'s value: a width of at least one token."""
    width = int(text)
    if width < 1:
        raise argparse.ArgumentTypeError(
            f"must be >= 1 (got {width}): chunked prefill is the only "
            "prefill path"
        )
    return width


def _add_backend_args(p: argparse.ArgumentParser) -> None:
    """Backend-construction flags — the ONE definition of everything
    `_build_backend` reads, shared by the main parser and `serve` so the
    two cannot drift apart."""
    p.add_argument(
        "--backend", choices=["fake", "local", "continuous"], default="fake"
    )
    p.add_argument(
        "--serve-slots",
        type=int,
        default=8,
        help="continuous backend: decode slots (batch width of the "
        "one compiled decode program)",
    )
    p.add_argument(
        "--replicas",
        type=int,
        default=1,
        help="continuous backend: batcher replicas behind the one "
        "gateway (PR 14) — requests route by prefix affinity (a "
        "request lands on the replica whose registry/host-tier "
        "already holds its prompt's chain; consensus panels make "
        "that the common case), fall back to least modeled cost, "
        "and under overload the fleet preempts resident chains to "
        "the shared host tier (--host-cache-mb, fleet-wide budget) "
        "instead of shedding 429s. 1 = a single batcher (the classic "
        "path)",
    )
    p.add_argument(
        "--role",
        default="mixed",
        help="continuous backend with --replicas > 1: replica roles "
        "(PR 16) — 'mixed' (default, uniform fleet), or a comma list "
        "naming each replica's role, e.g. 'prefill,decode': prefill "
        "replicas run admission + chunked prefill only (spec and "
        "R-round windows off) and hand finished chains through the "
        "fleet page store; decode replicas restore them and stream "
        "tokens. At least one replica must be decode-capable",
    )
    p.add_argument(
        "--host-store",
        default=None,
        metavar="ENDPOINT",
        help="continuous backend: serve the host KV tier from a REMOTE "
        "page-store server (PR 16) instead of an in-process one — "
        "'tcp://host:port' or 'uds:///path' of a running "
        "`python -m llm_consensus_tpu.serving.remote_store`. Requires "
        "--host-cache-mb > 0 (the tier must be engaged); store "
        "outages degrade to local recompute, never wedge serving",
    )
    p.add_argument(
        "--prefill-chunk",
        type=_prefill_chunk_width,
        default=64,
        help="continuous backend: prefill-chunk tokens (>= 1) "
        "interleaved between decode steps",
    )
    p.add_argument(
        "--no-share-prefix",
        action="store_true",
        help="continuous backend: disable copy-on-write shared-prefix "
        "page dedup",
    )
    p.add_argument(
        "--host-cache-mb",
        type=int,
        default=0,
        help="continuous backend: host-RAM KV offload tier budget in "
        "MiB (0 = off) — evicted prefix-registry pages demote to host "
        "buffers and restore at the next same-prefix admission instead "
        "of re-prefilling",
    )
    p.add_argument(
        "--no-ragged-attention",
        action="store_true",
        help="continuous backend: disable the fused scheduler step "
        "(PR 8) — prefill chunks run as standalone device programs "
        "between decode steps instead of riding the decode dispatch "
        "as ragged-kernel rows (outputs are identical either way)",
    )
    p.add_argument(
        "--pipeline-depth",
        type=int,
        default=2,
        help="continuous backend: decode programs in flight at once — "
        "the host loop enqueues program n+1 before fetching program "
        "n's tokens, hiding scheduling work behind device compute "
        "(1 = the serialized loop; outputs are identical either way)",
    )
    p.add_argument(
        "--decode-rounds",
        type=int,
        default=1,
        help="continuous backend: decode rounds folded into one "
        "device program (PR 12) — stop scan, sampling, and emit/"
        "length bookkeeping run on device and a row hitting a stop "
        "or its token budget mid-window freezes (no further KV "
        "writes or PRNG folds) while neighbors keep decoding; the "
        "host fetches once per R rounds. Text is byte-identical to "
        "1 (the default); engages with steps-per-sync 1 on every "
        "topology (meshes included since PR 13), "
        "and requests whose stop sequences have no bounded device "
        "screen collapse the window to 1 while they decode",
    )
    def _hbm_gbps_arg(v: str) -> str:
        # Validate at parse time (argparse's clean usage error, not a
        # traceback mid-backend-build) but RETURN the string:
        # resolving "auto" needs jax.devices(), which must not run
        # before --cpu has had its chance to pin the platform.
        if v.strip().lower() != "auto":
            float(v)  # raises ValueError -> argparse "invalid value"
        return v

    p.add_argument(
        "--hbm-gbps",
        type=_hbm_gbps_arg,
        default="0",
        help="continuous backend: the device's peak HBM bandwidth in "
        "GB/s for roofline attribution — > 0 publishes "
        "gateway_program_mbu{kind} (modeled program HBM bytes / "
        "measured wall time / this peak; ~1.0 = at the weights+KV "
        "roofline). 'auto' looks the device kind up in the table of "
        "published peaks (serving/control.py; a kind that is not in "
        "it is an error — pass the number). 0 = gauge off; "
        "the modeled-bytes and measured-seconds sums still "
        "accumulate in the batcher's stats()",
    )
    p.add_argument(
        "--adaptive",
        action="store_true",
        help="continuous backend: roofline-adaptive runtime control "
        "(PR 15) — auto-tune effective spec_k from measured per-group "
        "acceptance, decode-round windows from modeled MBU + token "
        "budgets, prefill-chunk width and pipeline depth from "
        "un-overlapped scheduler overhead, and pace preempt-to-host-"
        "tier demotions by modeled restore debt. Decisions ride "
        "gateway_autotune_* and the flight recorder; text stays "
        "byte-identical to any fixed knob setting (default off = "
        "every knob static)",
    )
    p.add_argument(
        "--cpu",
        action="store_true",
        help="run on the CPU on purpose (tests, debugging). Without it "
        "a device backend refuses to start unless JAX's default backend "
        "is a TPU",
    )
    p.add_argument("--model", default="llama-1b", help="model preset name")
    p.add_argument(
        "--layers",
        type=int,
        default=0,
        help="serve the FIRST N layers of the model (leading dense "
        "layers count): one stage of a pipeline, or a deep model cut to "
        "one chip. 0 = all. Random weights are born at that depth; a "
        "checkpoint's stacks are cut after loading",
    )
    p.add_argument("--checkpoint", default=None, help="orbax checkpoint dir")
    p.add_argument(
        "--hf-checkpoint",
        default=None,
        help="HF safetensors checkpoint dir (config.json derives the "
        "model config; overrides --model/--checkpoint)",
    )
    p.add_argument(
        "--quant",
        choices=["none", "int8", "int4"],
        default="none",
        help="weight-only quantization for the local engine",
    )
    p.add_argument("--tokenizer", default=None, help="local HF tokenizer dir")
    p.add_argument(
        "--draft-model",
        default=None,
        help="model preset for a speculative-decoding draft (greedy "
        "requests then ride draft-and-verify; output is unchanged)",
    )
    p.add_argument(
        "--draft-checkpoint",
        default=None,
        help="orbax checkpoint dir for the draft model's weights",
    )
    p.add_argument(
        "--spec-k",
        type=int,
        default=4,
        help="continuous backend: draft tokens proposed per speculative "
        "verify round (with --draft-model; the batcher drafts once per "
        "shared-prefix panel group, verifies all slots' drafts in one "
        "ragged device program, and rolls back rejected tokens by "
        "count bookkeeping — greedy output is byte-identical to "
        "spec-off)",
    )
    p.add_argument(
        "--mesh",
        default=None,
        metavar="AXIS=N[,AXIS=N...]",
        help="shard the local engine over a device mesh, e.g. "
        "'data=4,model=2' (axes: data/model/expert/seq/pipe; product "
        "must equal the device count; seq>1 enables ring attention)",
    )
    p.add_argument(
        "--model-spec",
        action="append",
        default=None,
        metavar="KEY=VAL[,KEY=VAL...]",
        help="continuous backend: one multi-model SET member per flag "
        "(PR 18) — repeat to add members; overrides --model/"
        "--draft-model. Keys: name (required), preset (required "
        "model-config preset), checkpoint, tokenizer, slots, spec_k, "
        "replicas, adaptive=0/1, draft_from=<member> (mount that "
        "member's weights as this member's speculative draft across "
        "the tokenizer boundary via exact-match vocab alignment). "
        "Example: --model-spec name=large,preset=llama-1b,"
        "draft_from=small --model-spec name=small,preset=llama-debug",
    )
    p.add_argument(
        "--model-default",
        default=None,
        help="multi-model: member serving untagged requests (default: "
        "the first --model-spec)",
    )
    p.add_argument(
        "--model-lanes",
        action="store_true",
        help="multi-model: add one model:<name> admission lane per "
        "member — requests tagged with a model queue behind their own "
        "bound instead of the shared interactive lane",
    )


def _add_protocol_args(p: argparse.ArgumentParser) -> None:
    """Panel-protocol defaults shared by the REPL and `serve`."""
    p.add_argument("--panel", default=None, help="panel JSON file")
    p.add_argument(
        "--max-rounds",
        type=int,
        default=5,
        help="evaluation-round cap (the reference hard-codes 5, "
        "src/main.rs:299-300)",
    )
    p.add_argument("--max-new-tokens", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.7)
    p.add_argument("--seed", type=int, default=None)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="llm_consensus_tpu",
        description="Multi-persona LLM consensus on local TPU inference.",
    )
    _add_backend_args(p)
    _add_protocol_args(p)
    p.add_argument(
        "--question", default=None, help="answer one question and exit"
    )
    p.add_argument(
        "--debate",
        type=int,
        default=None,
        metavar="N",
        help="answer --question via N-candidate multi-round debate "
        "(consensus/debate.py) instead of the panel protocol "
        "(needs --backend local)",
    )
    p.add_argument(
        "--debate-method",
        default="majority",
        choices=("majority", "logit_pool", "rescore"),
        help="per-round debate vote: head count, pool by sampling "
        "logprob, or teacher-forced judge re-scoring",
    )
    p.add_argument(
        "--stream",
        action="store_true",
        help="stream a single-model completion of --question token by "
        "token (bypasses the panel protocol; needs --backend local)",
    )
    p.add_argument(
        "--eval-gsm8k",
        default=None,
        metavar="JSONL|bundled|synthetic|synthetic2",
        help="run the GSM8K EM harness on a JSONL file, the bundled "
        "50-problem dataset (eval/data/gsm8k_mini.jsonl), 'synthetic' "
        "(single-template arithmetic), or 'synthetic2' (the hard "
        "multi-step multi-template task, eval/arith2.py)",
    )
    p.add_argument("--eval-n", type=int, default=8, help="candidates per problem")
    p.add_argument("--eval-limit", type=int, default=20)
    p.add_argument(
        "--plan",
        action="store_true",
        help="print the HBM capacity plan for --model at --plan-n/"
        "--plan-context (config-only, nothing is allocated): does the "
        "config fit one chip, and what does a mesh buy? Honors "
        "--plan-quant/--plan-mesh (e.g. 'data=4,model=2').",
    )
    p.add_argument("--plan-n", type=int, default=64)
    p.add_argument("--plan-context", type=int, default=2048)
    p.add_argument(
        "--plan-quant", default="int8", choices=("none", "int8", "int4")
    )
    p.add_argument(
        "--plan-kv",
        default="int8",
        choices=("none", "int8"),
        help="KV-cache quantization the plan assumes (bf16 doubles the "
        "cache term)",
    )
    p.add_argument("--plan-mesh", default="", metavar="AXIS=N,...")
    p.add_argument(
        "--plan-hbm-gib", type=float, default=16.0, help="per-chip HBM"
    )
    return p


def _parse_axes(spec: str) -> dict[str, int]:
    """``"data=4,model=2"`` -> ``{"data": 4, "model": 2}`` — the one
    parser behind both ``--mesh`` and ``--plan-mesh``."""
    sizes: dict[str, int] = {}
    for part in spec.split(","):
        axis, sep, n = part.partition("=")
        if not sep or not axis.strip() or not n.strip():
            raise SystemExit(
                f"bad mesh axis spec {part!r} (want AXIS=N,...)"
            )
        sizes[axis.strip()] = int(n)
    return sizes


def _run_plan(args) -> int:
    """Capacity planning without touching a device (``--plan``)."""
    import json as _json

    from llm_consensus_tpu.engine.engine import plan_memory
    from llm_consensus_tpu.models.configs import get_config

    mesh_shape = _parse_axes(args.plan_mesh) if args.plan_mesh else {}
    prompt = max(1, args.plan_context - args.max_new_tokens)
    plan = plan_memory(
        get_config(args.model),
        quant=args.plan_quant,
        kv_quant=args.plan_kv == "int8",
        n_candidates=args.plan_n,
        prompt_len=prompt,
        new_tokens=args.max_new_tokens,
        mesh_shape=mesh_shape or None,
        hbm_bytes=int(args.plan_hbm_gib * (1 << 30)),
    )
    gib = 1 << 30
    out = {
        "model": args.model,
        "quant": args.plan_quant,
        "kv_quant": args.plan_kv,
        "n_candidates": args.plan_n,
        "context": args.plan_context,
        "mesh": mesh_shape or "single chip",
        "params_gib": round(plan["params_bytes"] / gib, 2),
        "kv_cache_gib": round(plan["kv_cache_bytes"] / gib, 2),
        "total_gib": round(plan["total_bytes"] / gib, 2),
        "hbm_gib": args.plan_hbm_gib,
        "fits": plan["fits"],
    }
    print(_json.dumps(out, indent=2))
    return 0 if plan["fits"] else 1


async def repl(coord: Coordinator, stream=None) -> None:
    """Interactive loop with reference UX parity (``src/main.rs:428-471``)."""
    out = stream or sys.stdout
    while True:
        out.write("Enter a question: ")
        out.flush()
        line = await asyncio.to_thread(sys.stdin.readline)
        if not line:
            break
        question = line.strip()
        if question == "exit":
            break
        if not question:
            continue
        await coord.ask_question(question)
        answer = await coord.wait_for_answer()
        log.info("Final answer: %s", answer)
        out.write(f"\n{_printable(answer)}\n\n")
        coord.reset()


def build_serve_parser() -> argparse.ArgumentParser:
    """Parser for the ``serve`` subcommand (the serving gateway).

    Shares the backend-construction flags with the main parser so
    ``serve`` can front any substrate the REPL can (fake for tests,
    local engines incl. mesh/quant/draft for real serving).
    """
    p = argparse.ArgumentParser(
        prog="llm_consensus_tpu serve",
        description="HTTP serving gateway: /v1/generate, /v1/consensus, "
        "/metrics, /healthz (SIGTERM drains gracefully).",
    )
    _add_backend_args(p)
    _add_protocol_args(p)
    # Gateway flags.
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument(
        "--port",
        type=int,
        default=8080,
        help="TCP port (0 = ephemeral; the bound port is logged)",
    )
    p.add_argument(
        "--queue-bound",
        type=int,
        default=64,
        help="per-priority admission queue bound (full => 429 + "
        "Retry-After)",
    )
    p.add_argument(
        "--max-inflight",
        type=int,
        default=8,
        help="concurrent in-flight executions across priorities",
    )
    p.add_argument(
        "--admission-cost-budget-mb",
        type=int,
        default=0,
        help="cost-budget admission (PR 15): switch every queue bound "
        "from request counts to MODELED BYTES — each request charges "
        "its modeled KV schedule (the same unit the fleet router's "
        "load_cost compares), so a 32k-context request is no longer "
        "one unit of work and the overflow hard cap is bytes too. "
        "0 = classic request-count bounds (--queue-bound)",
    )
    p.add_argument(
        "--default-deadline-s",
        type=float,
        default=None,
        help="deadline applied to requests that do not carry one",
    )
    # Observability (PR 5): request-scoped tracing + profiler bridge.
    p.add_argument(
        "--no-trace",
        action="store_true",
        help="disable request-scoped tracing (trace ids, /debug/traces "
        "span trees, and the span-derived histograms' trace side; "
        "default ON)",
    )
    p.add_argument(
        "--trace-max-traces",
        type=int,
        default=256,
        help="bounded trace-store ring: retained request traces "
        "(evict-oldest; drops counted in gateway_trace_dropped_total)",
    )
    p.add_argument(
        "--trace-max-spans",
        type=int,
        default=2048,
        help="span budget per trace (excess spans dropped + counted)",
    )
    # Observability (PR 10): the serving flight recorder.
    p.add_argument(
        "--no-flight",
        action="store_true",
        help="disable the serving flight recorder (typed scheduler "
        "events at GET /debug/flight incl. the Perfetto-loadable "
        "?format=chrome export; default ON)",
    )
    p.add_argument(
        "--flight-events",
        type=int,
        default=8192,
        help="bounded flight-recorder ring: retained scheduler events "
        "(evict-oldest; drops counted in gateway_flight_dropped_total)",
    )
    p.add_argument(
        "--profile-dir",
        default=None,
        help="enable the X-Profile: 1 request header: capture a JAX "
        "device profile (TensorBoard format) into this directory for "
        "the flagged request, aligned with its host trace spans",
    )
    p.add_argument(
        "--ready-stall-s",
        type=float,
        default=10.0,
        help="GET /readyz returns 503 when the backend serving loop's "
        "heartbeat is older than this (wedged loop)",
    )
    p.add_argument(
        "--peer",
        action="append",
        default=None,
        metavar="URL",
        help="cross-host peer tier (PR 16, repeatable): run this "
        "gateway as a routing FRONT over peer gateways at these base "
        "URLs ('http://host:port') — each /v1/* request is forwarded "
        "to the peer whose GET /debug/chains probe shows the longest "
        "resident chain for its prompt (move the query, not the "
        "cache). The local backend still serves /healthz, /metrics "
        "and debug routes; use --backend fake for a pure front",
    )
    # Fleet observability (PR 20).
    p.add_argument(
        "--no-fleet-obs",
        action="store_true",
        help="disable fleet observability federation (PR 20): "
        "X-Trace-Id propagation/adoption across peer forwards, the "
        "per-hop meta['hops'] breakdown on /v1/* responses, and the "
        "/metrics?fleet=1 + /debug/flight?fleet=1 merged views "
        "(default ON)",
    )
    # Fleet control plane (PR 19).
    p.add_argument(
        "--fleet-control",
        action="store_true",
        help="fleet control plane (PR 19): run one FleetController "
        "over the --replicas fleet — SLO-aware admission (requests "
        "carry an optional 'slo' payload field; at a full queue the "
        "request that WILL miss its target is shed, never simply the "
        "newest), tenant weighted fair queueing over the 'tenant' "
        "field, router load-weight steering from live queue-cost "
        "signals, group/restore sizing, and elastic replica "
        "spawn/retire (--elastic-max). Requires --replicas > 1",
    )
    p.add_argument(
        "--slo-target",
        action="append",
        default=None,
        metavar="CLASS=SECONDS",
        help="fleet control: SLO class -> queue-wait target seconds "
        "(repeatable; default interactive=2,batch=30). Defines the "
        "classes the /v1/generate 'slo' payload field accepts",
    )
    p.add_argument(
        "--slo-class",
        default="interactive",
        help="fleet control: default SLO class for untagged requests "
        "('none' = untagged requests stay SLO-blind)",
    )
    p.add_argument(
        "--tenant-weight",
        action="append",
        default=None,
        metavar="TENANT=WEIGHT",
        help="fleet control: tenant fair-share weight (repeatable; "
        "unlisted tenants weigh 1.0)",
    )
    p.add_argument(
        "--elastic-max",
        type=int,
        default=0,
        help="fleet control: elastic replica ceiling (0 = fixed "
        "fleet; above --replicas the controller spawns batchers "
        "against sustained queue depth and retires them when the "
        "fleet idles, draining through the shared host tier)",
    )
    return p


def _parse_fleet_control(args):
    """``serve --fleet-control`` flags -> :class:`FleetControlConfig`
    (None when the flag is off)."""
    if not getattr(args, "fleet_control", False):
        return None
    from llm_consensus_tpu.serving.fleet_control import FleetControlConfig

    cfg = FleetControlConfig()
    if args.slo_target:
        classes = {}
        for spec in args.slo_target:
            name, _, secs = spec.partition("=")
            if not name or not secs:
                raise SystemExit(
                    f"--slo-target expects CLASS=SECONDS, got {spec!r}"
                )
            classes[name] = float(secs)
        cfg.slo_classes = classes
    default = args.slo_class
    cfg.default_slo_class = None if default in (None, "none", "") else default
    if (
        cfg.default_slo_class is not None
        and cfg.default_slo_class not in cfg.slo_classes
    ):
        raise SystemExit(
            f"--slo-class {cfg.default_slo_class!r} is not one of the "
            f"--slo-target classes {sorted(cfg.slo_classes)}"
        )
    if args.tenant_weight:
        weights = {}
        for spec in args.tenant_weight:
            name, _, w = spec.partition("=")
            if not name or not w:
                raise SystemExit(
                    f"--tenant-weight expects TENANT=WEIGHT, got {spec!r}"
                )
            weights[name] = float(w)
        cfg.tenant_weights = weights
    if args.elastic_max:
        cfg.elastic_min = max(1, args.replicas)
        cfg.elastic_max = args.elastic_max
        if cfg.elastic_max < cfg.elastic_min:
            raise SystemExit(
                f"--elastic-max {cfg.elastic_max} is below "
                f"--replicas {cfg.elastic_min}"
            )
    return cfg


def _run_serve(argv: list[str]) -> int:
    """The ``serve`` subcommand: build backend + panel, run the gateway
    until SIGTERM/SIGINT, then drain (stop admitting, finish in-flight)."""
    import signal

    from llm_consensus_tpu.server.admission import AdmissionConfig
    from llm_consensus_tpu.server.gateway import Gateway, GatewayConfig

    args = build_serve_parser().parse_args(argv)
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    from llm_consensus_tpu.utils import tracing as _tracing

    if args.no_trace:
        _tracing.set_enabled(False)
    _tracing.trace_store().configure(
        max_traces=args.trace_max_traces, max_spans=args.trace_max_spans
    )
    from llm_consensus_tpu.serving import flight as _flight

    if args.no_flight:
        _flight.set_enabled(False)
    _flight.flight_recorder().configure(capacity=args.flight_events)
    panel = load_panel(args.panel) if args.panel else default_panel()
    fleet_cfg = _parse_fleet_control(args)
    backend = _build_backend(args)
    # Fleet control plane (PR 19): one controller over the replica
    # fleet. Its config also seeds the gateway's SLO classes and
    # tenant weights (admission_kwargs below) so the two layers agree.
    fleet_controller = None
    if fleet_cfg is not None:
        replicas = getattr(backend, "replicas", None)
        if replicas is None:
            raise SystemExit(
                "--fleet-control requires the replica fleet backend "
                "(--backend continuous --replicas 2+)"
            )
        from llm_consensus_tpu.serving.fleet_control import FleetController

        fleet_controller = FleetController(replicas, fleet_cfg)
    # Per-model admission lanes (PR 18): a multi-model backend adds one
    # ``model:<name>`` priority lane per member behind the base pair —
    # a request tagged with a model defaults into its own lane (the
    # gateway's _lane_for), so one member's burst queues behind its own
    # bound instead of starving the panel's other models.
    priorities: tuple[str, ...] = ("interactive", "batch")
    modelset = getattr(backend, "modelset", None)
    if modelset is not None and args.model_lanes:
        priorities = priorities + modelset.admission_lanes()
    admission_kw = fleet_cfg.admission_kwargs() if fleet_cfg else {}
    gateway = Gateway(
        backend,
        panel=panel,
        config=GatewayConfig(
            host=args.host,
            port=args.port,
            admission=AdmissionConfig(
                priorities=priorities,
                max_queue=args.queue_bound,
                max_inflight=args.max_inflight,
                default_deadline_s=args.default_deadline_s,
                cost_budget_bytes=float(
                    args.admission_cost_budget_mb << 20
                ),
                **admission_kw,
            ),
            sampling=SamplingParams(
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
            ),
            max_rounds=args.max_rounds,
            consensus_seed=args.seed,
            ready_stall_s=args.ready_stall_s,
            profile_dir=args.profile_dir,
            peers=tuple(args.peer or ()),
            fleet_obs=not args.no_fleet_obs,
        ),
    )
    if fleet_controller is not None:
        # Burn-rate pressure (PR 20): give the controller a live view
        # of the admission tier's per-class SLO burn so _steer_elastic
        # can spawn on sustained burn even before queues deepen.
        fleet_controller.attach_admission(gateway.admission)

    async def _serve() -> None:
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:  # non-Unix event loops
                pass
        await gateway.run_until(stop)

    if fleet_controller is not None:
        fleet_controller.start()
    try:
        asyncio.run(_serve())
    finally:
        if fleet_controller is not None:
            fleet_controller.stop()
    return 0


def main(argv: list[str] | None = None) -> int:
    _init_logging()
    if argv is None:
        argv = sys.argv[1:]
    if argv[:1] == ["serve"]:
        return _run_serve(argv[1:])
    args = build_parser().parse_args(argv)

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    if args.plan:
        return _run_plan(args)
    if args.eval_gsm8k is not None:
        return _run_eval(args)
    if args.debate is not None:
        return _run_debate(args)
    if args.stream:
        return _run_stream(args)

    panel = load_panel(args.panel) if args.panel else default_panel()
    backend = _build_backend(args)
    coord = Coordinator(
        panel,
        backend,
        CoordinatorConfig(
            max_rounds=args.max_rounds,
            seed=args.seed,
            sampling=SamplingParams(
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
            ),
        ),
    )
    if args.question is not None:
        result = asyncio.run(coord.run(args.question))
        print(_printable(result.answer))
        return 0
    asyncio.run(repl(coord))
    return 0


def _run_stream(args) -> int:
    if args.backend != "local":
        print("--stream needs --backend local", file=sys.stderr)
        return 2
    if not args.question:
        print("--stream needs --question", file=sys.stderr)
        return 2
    backend = _build_backend(args)
    for piece in backend.engine.generate_stream(
        args.question,
        temperature=args.temperature,
        seed=args.seed if args.seed is not None else 0,
        max_new_tokens=args.max_new_tokens,
    ):
        print(_printable(piece), end="", flush=True)
    print()
    return 0


def _run_debate(args) -> int:
    from llm_consensus_tpu.consensus.debate import DebateConfig, run_debate

    if args.backend == "fake":
        print("--debate needs --backend local", file=sys.stderr)
        return 2
    if not args.question:
        print("--debate needs --question", file=sys.stderr)
        return 2
    if args.debate < 1:
        print(f"--debate needs N >= 1, got {args.debate}", file=sys.stderr)
        return 2
    backend = _build_backend(args)
    result = run_debate(
        backend.engine,
        args.question,
        DebateConfig(
            n_candidates=args.debate,
            max_rounds=args.max_rounds,
            temperature=args.temperature,
            max_new_tokens=args.max_new_tokens,
            seed=args.seed or 0,
            method=args.debate_method,
        ),
    )
    log.info(
        "Debate: %d rounds, %d candidate-tokens, winner tally %s",
        result.n_rounds,
        result.total_tokens,
        result.vote.tally,
    )
    print(_printable(result.answer))
    return 0


def _run_eval(args) -> int:
    import json

    from llm_consensus_tpu.eval.gsm8k import (
        evaluate_self_consistency,
        load_gsm8k,
        synthetic_problems,
    )

    if args.backend == "fake":
        print("GSM8K eval needs --backend local", file=sys.stderr)
        return 2
    backend = _build_backend(args)
    if args.eval_gsm8k == "synthetic":
        problems = synthetic_problems(args.eval_limit)
    elif args.eval_gsm8k == "synthetic2":
        # The hard offline task (eval/arith2.py): multi-step chains,
        # six narrative frames, distractors — serve an arith2-trained
        # checkpoint (--checkpoint runs/arith25m --model arith-25m)
        # and measure EM-vs-N from the same CLI the REPL uses.
        from llm_consensus_tpu.eval.arith2 import eval_problems

        problems, _ = eval_problems(args.eval_limit)
    elif args.eval_gsm8k == "bundled":
        import llm_consensus_tpu.eval as _eval_pkg

        bundled = os.path.join(
            os.path.dirname(_eval_pkg.__file__), "data", "gsm8k_mini.jsonl"
        )
        problems = load_gsm8k(bundled, limit=args.eval_limit)
    else:
        problems = load_gsm8k(args.eval_gsm8k, limit=args.eval_limit)
    report = evaluate_self_consistency(
        backend.engine,
        problems,
        n=args.eval_n,
        temperature=args.temperature,
        max_new_tokens=args.max_new_tokens,
    )
    print(json.dumps(report.to_dict()))
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
