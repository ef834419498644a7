#!/usr/bin/env python3
"""The quickest proof that the system still starts and answers on the chip.

    python3 chip_smoke.py            # on a machine with a TPU
    python3 chip_smoke.py --dry-run  # CPU, tiny preset: exercises this
                                     # script's own phases, never a pass

Drives the main path once, through the entry points a user would call,
at the full width of ``mistral-7b`` (32 layers, random int8 weights from
``PRNGKey(0)``, the default 512 x 64-token bf16 page pool):

  A  ``python -m llm_consensus_tpu serve --backend continuous --quant
     int8`` answers one generate, one streamed generate, a concurrent
     burst of 8 (six sharing a two-page header, two unique) and one
     four-persona consensus question; the server must report a TPU and
     the compiled Pallas attention path, have dispatched fused, decode
     and prefix-sharing work, and drain with rc 0 on SIGTERM.
  B  every kernel family the main path uses, compiled at the smoke
     model's shapes, agrees with its ``jax.numpy`` reference
     (``llm_consensus_tpu/ops/pallas/parity.py`` holds the comparisons
     and the tolerances with their reasons).
  C  where phase A saw four or more devices: the same server on
     ``--mesh data=2,model=2``, same burst, same checks, kernel under
     ``jax.shard_map``.

This process is standard library only and never imports jax: a chip
belongs to one process at a time, so each phase is one child, run one
after another. Children inherit ``JAX_COMPILATION_CACHE_DIR`` untouched;
unset, they resolve ``<checkout>/.jax_cache`` themselves. Any phase that
fails, hangs past its limit or is skipped for a reason other than the
device count makes the exit status non-zero, and then no result line is
printed. On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Start-up and compile times are printed as set-up seconds, never under a
metric's name.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

MODEL = "mistral-7b"
# The smoke model's kernel-facing shapes (models/configs.py, mistral-7b;
# serving defaults of ContinuousConfig).
HKV, G, D, PAGE, WINDOW = 8, 4, 128, 64, 4096
D_MODEL, D_FF, VOCAB = 4096, 14336, 32000
SLOTS, CHUNK, PAGES_PER_SEQ = 8, 64, 32

START_LIMIT_S = 900  # weights + pool + "gateway listening"
FIRST_REQUEST_LIMIT_S = 600  # carries the cold compiles
REQUEST_LIMIT_S = 300
KERNELS_LIMIT_S = 900
DRAIN_LIMIT_S = 60

# >= 2 full 64-token pages under the byte tokenizer (1 token per byte).
HEADER = (
    "You are one member of a four-person review panel. Read the question "
    "below with care, reason step by step inside your own head, and then "
    "reply with your final answer only, in a single short sentence. "
)
assert len(HEADER) >= 2 * PAGE


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("LLM_CONSENSUS_LOG", "info")
    env.update(extra or {})
    return env


def cache_entries(cache_dir: str | None) -> int | None:
    """Files under the compile cache (None: the server named none)."""
    if not cache_dir:
        return None
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def http(method: str, url: str, body: dict | None, timeout: float):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def post_ok(base: str, route: str, body: dict, timeout: float) -> dict:
    status, text = http("POST", base + route, body, timeout)
    if status != 200:
        raise SmokeFailure(f"POST {route} -> {status}: {text[:400]}")
    return json.loads(text)


def metric(text: str, name: str, labels: str = "") -> float:
    """Sum of the samples of ``name`` whose label set contains ``labels``."""
    total, seen = 0.0, False
    for line in text.splitlines():
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$", line)
        if m and m.group(1) == name and labels in (m.group(2) or ""):
            total += float(m.group(3))
            seen = True
    if not seen:
        raise SmokeFailure(f"/metrics has no sample {name}{{{labels}}}")
    return total


class Server:
    """One ``serve`` child: started with the documented command line,
    found through its own log, stopped with SIGTERM."""

    def __init__(self, name: str, args: list[str], env: dict | None = None):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self.cmd = [sys.executable, "-m", "llm_consensus_tpu", "serve", *args]
        say(f"{name}: {' '.join(self.cmd[1:])}")
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            self.cmd, cwd=ROOT, env=child_env(env),
            stdout=self._log, stderr=subprocess.STDOUT,
        )
        self.base = ""
        self.cache_dir: str | None = None

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def fail(self, why: str) -> SmokeFailure:
        tail = "\n".join(self.log_text().splitlines()[-40:])
        return SmokeFailure(f"{self.name}: {why}\n--- {self.log_path} ---\n{tail}")

    def wait_listening(self) -> float:
        while True:
            text = self.log_text()
            m = re.search(r"gateway listening on ([\w.\-]+):(\d+)", text)
            if m:
                self.base = f"http://{m.group(1)}:{m.group(2)}"
                c = re.search(r"compile cache: (\S+)", text)
                self.cache_dir = c.group(1) if c else None
                return time.monotonic() - self.t0
            rc = self.proc.poll()
            if rc is not None:
                raise self.fail(f"server exited with rc {rc} before listening")
            if time.monotonic() - self.t0 > START_LIMIT_S:
                raise self.fail(f"not listening after {START_LIMIT_S}s")
            time.sleep(0.5)

    def drain(self) -> None:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=DRAIN_LIMIT_S)
        except subprocess.TimeoutExpired:
            raise self.fail(f"no exit {DRAIN_LIMIT_S}s after SIGTERM") from None
        if rc != 0:
            raise self.fail(f"rc {rc} after SIGTERM (want a clean drain, 0)")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._log.close()


def drive_traffic(base: str) -> None:
    """The four request kinds, each checked. ``num_tokens``, not text:
    the byte tokenizer's decode drops ids >= 259, so a random model with
    a 32k vocabulary prints almost nothing."""
    gen = {"max_new_tokens": 16, "temperature": 0}
    r = post_ok(
        base, "/v1/generate", {"prompt": "What is 17 + 25?", **gen},
        FIRST_REQUEST_LIMIT_S,
    )
    if not r["num_tokens"] > 0:
        raise SmokeFailure(f"generate returned no tokens: {r}")
    say(f"generate: 200, {r['num_tokens']} tokens")

    status, text = http(
        "POST", base + "/v1/generate",
        {"prompt": "Name a prime above 100.", "stream": True, **gen},
        REQUEST_LIMIT_S,
    )
    events = [
        json.loads(line[6:])
        for line in text.splitlines()
        if line.startswith("data: {")
    ]
    done = [e for e in events if e.get("done")]
    if status != 200 or not done or not done[-1]["num_tokens"] > 0:
        raise SmokeFailure(f"stream -> {status}: {text[:400]}")
    say(f"stream: 200, {done[-1]['num_tokens']} tokens, [DONE] seen: "
        f"{'data: [DONE]' in text}")

    # Six share the header (the first two are identical prompts: the
    # mates), two share nothing — standalone chunks, chunks riding
    # decode dispatches, plain decode steps and grouped shared-prefix
    # reads all get traced.
    tails = ["Is 91 prime?", "Is 91 prime?", "Is 97 prime?",
             "What is 12 * 12?", "Spell 'lattice' backwards.",
             "Which is larger, 2^10 or 10^3?"]
    prompts = [HEADER + t for t in tails] + [
        "Unrelated: list three colours of the rainbow in order.",
        "Another stranger: how many legs do two spiders have?",
    ]
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futs = [
            pool.submit(
                post_ok, base, "/v1/generate", {"prompt": p, **gen},
                FIRST_REQUEST_LIMIT_S,
            )
            for p in prompts
        ]
        burst = [f.result() for f in futs]
    if not all(b["num_tokens"] > 0 for b in burst):
        raise SmokeFailure(f"burst member without tokens: {burst}")
    a, b = burst[0], burst[1]
    if (a["text"], a["num_tokens"]) != (b["text"], b["num_tokens"]):
        raise SmokeFailure(f"identical greedy prompts diverged: {a} vs {b}")
    say(f"burst: 8 x 200, tokens {[x['num_tokens'] for x in burst]}, "
        "mates byte-identical")

    c = post_ok(
        base, "/v1/consensus",
        {"question": "Is 221 a prime number?", "max_rounds": 2,
         "max_new_tokens": 8, "temperature": 0, "seed": 0},
        FIRST_REQUEST_LIMIT_S,
    )
    if not c["rounds"] >= 1 or len(c["feedback"]) < 1:
        raise SmokeFailure(f"consensus ran no round: {c}")
    say(f"consensus: 200, {c['rounds']} round(s), "
        f"{len(c['feedback'])} evaluations, endorsed={c['endorsed']}")


def serve_phase(
    name: str, args: list[str], *, want_platform: str,
    want_kernels: str, mesh: dict | None = None, env: dict | None = None,
) -> dict:
    srv = Server(name, args, env)
    try:
        start_s = srv.wait_listening()
        before = cache_entries(srv.cache_dir)
        status, text = http("GET", srv.base + "/readyz", None, 30)
        if status != 200:
            raise srv.fail(f"/readyz -> {status}: {text[:400]}")
        backend = json.loads(text)["backend"]
        dev, kernels = backend["device"], backend["kernels"]
        say(f"{name}: listening after {start_s:.1f} set-up seconds; device "
            f"{dev['platform']} / {dev['kind']} x {dev['count']}, pool on "
            f"{dev['pool_on']}; attention kernels: {kernels}")
        if dev["platform"] != want_platform:
            raise srv.fail(
                f"server runs on platform {dev['platform']!r}, "
                f"not {want_platform!r}"
            )
        if kernels != want_kernels:
            raise srv.fail(
                f"attention path is {kernels!r}, want {want_kernels!r}"
            )
        t0 = time.monotonic()
        try:
            drive_traffic(srv.base)
        except (SmokeFailure, OSError, KeyError, ValueError) as e:
            raise srv.fail(f"traffic failed: {e!r}") from e
        traffic_s = time.monotonic() - t0
        _, mtext = http("GET", srv.base + "/metrics", None, 30)
        counts = {
            "fused": metric(
                mtext, "gateway_device_programs_total", 'kind="fused"'
            ),
            "decode": metric(
                mtext, "gateway_device_programs_total", 'kind="decode"'
            ),
            "prefix_pages_shared": metric(
                mtext, "gateway_prefix_pages_shared"
            ),
        }
        for axis, n in (mesh or {}).items():
            counts[f"mesh_{axis}"] = metric(
                mtext, "gateway_mesh_shards", f'axis="{axis}"'
            )
            if counts[f"mesh_{axis}"] != n:
                raise srv.fail(f"gateway_mesh_shards{{{axis}}} != {n}: {counts}")
        say(f"{name}: counters {counts}")
        if not all(
            counts[k] > 0 for k in ("fused", "decode", "prefix_pages_shared")
        ):
            raise srv.fail(f"a program kind never ran: {counts}")
        srv.drain()
        after = cache_entries(srv.cache_dir)
        say(f"{name}: drained rc 0; traffic incl. compiles took "
            f"{traffic_s:.1f} set-up seconds; compile cache {srv.cache_dir}: "
            f"{before} -> {after} entries ({(after or 0) - (before or 0)} new)")
        return {"device": dev, "start_s": start_s}
    finally:
        srv.kill()


def kernels_phase(dry_run: bool) -> None:
    os.makedirs(LOG_DIR, exist_ok=True)
    cmd = [sys.executable, os.path.abspath(__file__), "--child-kernels"]
    if dry_run:
        cmd.append("--dry-run")
    t0 = time.monotonic()
    try:
        r = subprocess.run(
            cmd, cwd=ROOT, env=child_env(), timeout=KERNELS_LIMIT_S,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired as e:
        raise SmokeFailure(
            f"kernels: no result after {KERNELS_LIMIT_S}s\n{e.stdout}\n{e.stderr}"
        ) from None
    with open(os.path.join(LOG_DIR, "kernels.log"), "w") as f:
        f.write(r.stdout + "\n--- stderr ---\n" + r.stderr)
    sys.stdout.write(r.stdout)
    sys.stdout.flush()
    if r.returncode != 0:
        raise SmokeFailure(
            f"kernels: rc {r.returncode}\n" + r.stderr[-6000:]
        )
    say(f"kernels: all within tolerance, {time.monotonic() - t0:.1f} "
        "set-up seconds incl. compiles")


def kernels_child(dry_run: bool) -> int:
    """Phase B's body — the one place in this file that touches jax."""
    sys.path.insert(0, ROOT)
    import jax

    from llm_consensus_tpu.ops.pallas import parity
    from llm_consensus_tpu.utils.compile_cache import enable_compilation_cache

    dev = jax.devices()[0]
    if dry_run:
        # Toy shapes through the interpreter: checks this harness.
        interpret, hkv, g, d, pg, p_per = True, 2, 2, 32, 8, 6
        d_model, d_ff, vocab, window, cq = 128, 256, 384, 9, 16
    else:
        if dev.platform != "tpu":
            print(f"kernels: platform {dev.platform!r} is not a TPU",
                  file=sys.stderr)
            return 1
        interpret, hkv, g, d, pg, p_per = False, HKV, G, D, PAGE, PAGES_PER_SEQ
        d_model, d_ff, vocab, window, cq = D_MODEL, D_FF, VOCAB, WINDOW, CHUNK
    enable_compilation_cache()
    b = SLOTS
    cap = p_per * pg
    # Mid-page fills, an empty-ish row, a full row, rows past the window.
    fills = [cap, 1, pg + 3, cap // 2 + 5, 3 * pg - 1, cap - 7, 2 * pg, pg // 2]
    win_small = 2 * pg + 5  # binds inside these short test contexts
    base = dict(seed=0, pg=pg, hkv=hkv, g=g, d=d, p_per=p_per,
                n_pages=(b + 1) * p_per + 1, interpret=interpret)
    cases: list[tuple[str, float, object]] = []

    def ragged(name, **kw):
        """Two lines a variant: its outputs against the XLA reference,
        and its float32 outputs (before the cast to the queries' dtype)
        against the float64 oracle."""
        kw = {**base, **kw}
        cases.append((
            f"ragged_attention[{name}]", parity.ATTENTION_TOL,
            lambda: max(parity.ragged_attention_error(**kw).values()),
        ))
        cases.append((
            f"ragged_attention[{name}] f32 out / float64 oracle",
            parity.RAGGED_ORACLE_TOL,
            lambda: max(parity.ragged_attention_oracle_error(**kw).values()),
        ))

    grouped = [max(f, 2 * pg + 5) for f in fills]  # members past the run
    for wname, w in (("full", 0), (f"window={win_small}", win_small)):
        ragged(f"decode,{wname}", valid_len=fills, window=w)
        ragged(f"decode+chunk,{wname}", valid_len=fills, cq=cq,
               chunk_start=pg + 11, window=w)
        ragged(f"grouped+chunk,{wname}", valid_len=grouped, cq=cq,
               chunk_start=pg + 11, group_rows=(0, 2, 3, 5), shared_pages=2,
               window=w)
        ragged(f"verify nq=5,{wname}", valid_len=[max(f, 5) for f in fills],
               nq=5, window=w)
        ragged(f"verify nq=5 grouped,{wname}", valid_len=grouped, nq=5,
               group_rows=(1, 4), shared_pages=2, window=w)
    # The model's own window, as the server compiles it (it cannot bind
    # inside one sequence's pages here; the small one above does).
    ragged(f"grouped+chunk,window={window}", valid_len=grouped, cq=cq,
           chunk_start=pg + 11, group_rows=(0, 2, 3, 5), shared_pages=2,
           window=window)
    ragged("chunk only, idle decode rows", valid_len=[0] * b, cq=cq,
           chunk_start=0, null_tables=True)
    # The step programs' call: layer l of the stacked pools, indexed by
    # the kernel. Bit-equal to the call on the slice, or the case raises.
    for at in (0, 2):
        ragged(f"grouped+chunk, layer {at} of 3 stacked", valid_len=grouped,
               cq=cq, chunk_start=pg + 11, group_rows=(0, 2, 3, 5),
               shared_pages=2, window=window, layer=(at, 3))
    ragged("verify nq=5, layer 2 of 3 stacked",
           valid_len=[max(f, 5) for f in fills], nq=5, window=win_small,
           layer=(2, 3))
    # Three chunk lanes (PR 31), each over a table of its own at its own
    # fill, one of them dead, beside grouped rows on the stacked pools.
    lanes = dict(cq=cq, chunk_start=[pg + 11, -cq, 2 * pg],
                 n_pages=(b + 3) * p_per + 1)
    ragged("grouped+3 lanes, layer 2 of 3 stacked", valid_len=grouped,
           group_rows=(0, 2, 3, 5), shared_pages=2, window=window,
           layer=(2, 3), **lanes)
    # A latent (MLA) pool: one 640-lane key a token for 16 heads, the
    # value its first 512 lanes (DeepSeek-V2-Lite's, padded; toy sizes
    # in the dry run), on the stacked pool as the layer scan calls it.
    lat = (dict(d=64, latent_dv=32, g=4) if dry_run
           else dict(d=640, latent_dv=512, g=16))
    for name, kw in (
        ("decode", dict(valid_len=fills)),
        ("grouped+chunk, layer 1 of 2 stacked", dict(
            valid_len=grouped, cq=cq, chunk_start=pg + 11,
            group_rows=(0, 2, 3, 5), shared_pages=2, layer=(1, 2))),
        ("grouped+3 lanes, layer 1 of 2 stacked", dict(
            valid_len=grouped, group_rows=(0, 2, 3, 5), shared_pages=2,
            layer=(1, 2), **lanes)),
    ):
        ragged(f"latent {name}", hkv=1, **lat, **kw)
    # The grouped expert matmul at DeepSeek-V2-Lite's two shapes: 8
    # experts of which two hold no row and one holds three tiles.
    for k_, n_ in (((128, 256), (256, 128)) if dry_run
                   else ((2048, 1408), (1408, 2048))):
        cases.append((
            f"moe_grouped_matmul[{k_}x{n_}]", parity.QUANT_MATMUL_TOL,
            lambda k_=k_, n_=n_: parity.moe_grouped_matmul_error(
                seed=4, rows=[5, 0, 40, 1, 16, 0, 17, 2], k=k_, n=n_,
                interpret=interpret),
        ))
    # The state-carrying scan (a recurrent model's layers): decode rows
    # of one token padded to a sublane tile, chunk lanes of a whole chunk.
    for rows, tokens in ((b, 8), (3, cq)):
        ssm = (dict(heads=4, head_dim=16, state=16, groups=2) if interpret
               else dict(heads=64, head_dim=64, state=128, groups=8))
        cases.append((
            f"ssm_scan[{rows} rows x {tokens} tokens]", parity.SSM_SCAN_TOL,
            lambda rows=rows, tokens=tokens, ssm=ssm: parity.ssm_scan_error(
                seed=5, rows=rows, tokens=tokens, slots=2 * rows + 2,
                interpret=interpret, **ssm),
        ))
    for rows in (1, b, b + cq):
        cases.append((
            f"fused_rms_norm[{rows}x{d_model} bf16]", parity.NORM_BF16_TOL,
            lambda rows=rows: parity.rms_norm_error(
                seed=1, shape=(rows, d_model), dtype="bfloat16",
                interpret=interpret),
        ))
    cases.append((
        f"fused_rms_norm[{b}x{d_model} f32]", parity.NORM_TOL,
        lambda: parity.rms_norm_error(
            seed=2, shape=(b, d_model), interpret=interpret),
    ))
    for m, k, n in ((b, d_model, d_model), (b + cq, d_model, d_ff),
                    (b + cq, d_ff, d_model), (1, d_model, vocab),
                    (b, d_model, vocab)):
        cases.append((
            f"quant_matmul[M={m},K={k},N={n}]", parity.QUANT_MATMUL_TOL,
            lambda m=m, k=k, n=n: parity.quant_matmul_error(
                seed=3, m=m, k=k, n=n, interpret=interpret),
        ))
    cases.append((
        f"quant_matmul_stacked[L=2,M={b},K={d_model},N={d_ff}]",
        parity.QUANT_MATMUL_TOL,
        lambda: parity.quant_matmul_error(
            seed=4, m=b, k=d_model, n=d_ff, n_layers=2, interpret=interpret),
    ))
    print(f"[smoke] kernels on {dev.platform} / {dev.device_kind}, "
          f"interpret={interpret}: Hkv {hkv}, G {g}, D {d}, page {pg}, "
          f"{p_per} pages/seq, K in {{{d_model}, {d_ff}}}", flush=True)
    failed = 0
    for name, tol, run in cases:
        try:
            err = parity.check(name, run(), tol)
            print(f"[smoke]   ok   {name}: max err {err:.2e} (tol {tol})",
                  flush=True)
        except Exception as e:  # noqa: BLE001 - report every variant
            failed += 1
            msg = str(e).strip().splitlines()
            print(f"[smoke]   FAIL {name}: {type(e).__name__}: "
                  f"{' | '.join(msg[:6])[:1500]}", flush=True)
    return 1 if failed else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--dry-run", action="store_true",
        help="tiny preset on the CPU (--cpu, Pallas interpreter): runs "
        "every phase to test this script; its result line says "
        '"ok": false, "device": "cpu" — it is never a pass',
    )
    ap.add_argument("--child-kernels", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "llm_consensus_tpu")):
        print(f"chip_smoke: no llm_consensus_tpu/ beside {__file__}: this "
              "script proves the program, it is not the program",
              file=sys.stderr)
        return 2
    if args.child_kernels:
        return kernels_child(args.dry_run)

    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            versions[pkg] = None
    say(f"versions: {versions}; JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '<unset>')}")
    if args.dry_run:
        model, platform, kern = "test-tiny", "cpu", "reference"
        common = ["--cpu"]
    else:
        model, platform, kern = MODEL, "tpu", "pallas"
        common = []
    common += ["--backend", "continuous", "--model", model, "--quant", "int8",
               "--port", "0"]
    t0 = time.monotonic()
    try:
        a = serve_phase("A-serve", common, want_platform=platform,
                        want_kernels=kern)
        kernels_phase(args.dry_run)
        if args.dry_run or a["device"]["count"] >= 4:
            # The dry run fakes four CPU devices so phase C's code runs.
            env = (
                {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
                if args.dry_run else None
            )
            serve_phase(
                "C-mesh", common + ["--mesh", "data=2,model=2"],
                want_platform=platform,
                want_kernels=kern if args.dry_run else kern + "/shard_map",
                mesh={"data": 2, "model": 2}, env=env,
            )
        else:
            say(f"C-mesh: skipped, {a['device']['count']} device(s) < 4")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED after {time.monotonic() - t0:.0f}s: {e}",
              file=sys.stderr)
        return 1
    say(f"all phases passed in {time.monotonic() - t0:.0f} set-up seconds "
        f"({model}, {versions})")
    if args.dry_run:
        print(json.dumps({"ok": False, "dry_run": True, "device": "cpu",
                          "phases_passed": True}))
        return 0
    dev = a["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
