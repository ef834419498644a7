"""Persistent XLA compilation cache, and the store of executables beside it.

A cold start compiles every serving program; a persistent cache makes the
next start of the same code read them back. JAX keys an entry on the
cache directory's path among other things, so the directory must not
move: it is wherever ``JAX_COMPILATION_CACHE_DIR`` says — JAX reads that
variable itself and this module then sets nothing — and otherwise a fixed
path inside the checkout. Never ``$HOME``, a temp name, a pid or a time.

JAX can key its own entries only on the lowered module, so each process
still traces and lowers every program it runs. The store skips that: a
program built once (:class:`StoredJit`) is serialized into the
``executables`` subdirectory of the compile cache, keyed on what can
change it — the package's source, the versions and the backend, the
devices, ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``, the jax configuration,
the caller's key (the model and batcher configuration, the program's own
key) and the arguments' tree, shapes, dtypes and shardings — and a later
process with the same key loads it and never traces it. The store is on
wherever the compile cache is; deleting the directory is always safe.
"""

from __future__ import annotations

import atexit
import functools
import hashlib
import logging
import math
import os
import pickle
import tempfile
import threading
import time
import weakref
from pathlib import Path

from llm_consensus_tpu.utils.tracing import EXECUTABLE_EVENT, LOAD_EVENT

log = logging.getLogger(__name__)

_PACKAGE = Path(__file__).resolve().parents[1]
_CHECKOUT_CACHE = _PACKAGE.parent / ".jax_cache"
STORE = "executables"


def enable_compilation_cache() -> str:
    """Turn the persistent compile cache on (idempotent); returns the
    directory in use. Every entry point that compiles for the device —
    the CLI, ``chip_smoke.py``'s children — calls this one
    function before its first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        log.info("compile cache: %s (JAX_COMPILATION_CACHE_DIR)", placed)
        return placed
    import jax

    cache_dir = str(_CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    log.info("compile cache: %s", cache_dir)
    return cache_dir


def store_dir() -> Path | None:
    """The store of executables: ``executables`` under the compile cache
    directory in use; None where the compile cache is off."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not jax.config.jax_enable_compilation_cache:
        return None
    return Path(cache_dir) / STORE


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------


class _Traced(Exception):
    """An argument is a tracer: the call is inside another trace."""


@functools.cache
def _kinds() -> tuple:
    import jax
    import numpy as np
    from jax._src.array import ArrayImpl

    return (
        ArrayImpl, jax.core.Tracer, jax.Array, jax.ShapeDtypeStruct,
        (np.ndarray, np.generic),
    )


def _leaf(x, kinds) -> tuple:
    """One argument leaf as a call signature holds it: shape, dtype, weak
    type and — for an array placed on purpose (committed) — sharding; a
    Python scalar is its type alone (its value is data)."""
    concrete, tracer, array, abstract, host = kinds
    if type(x) is concrete or (
        isinstance(x, array) and not isinstance(x, tracer)
    ):
        return (
            x.shape, x.dtype, x.weak_type, x.sharding if x.committed else None
        )
    if isinstance(x, tracer):
        raise _Traced
    if isinstance(x, abstract):
        return (x.shape, x.dtype, x.weak_type, x.sharding)
    if isinstance(x, host):
        return (x.shape, x.dtype, False, None)
    return (type(x),)


def _signature(dynamic: tuple, kwargs: dict, static: tuple) -> tuple:
    """What selects one executable of a jitted function: the tree of the
    dynamic arguments, each leaf's :func:`_leaf`, and the static
    arguments' values. Arrays and their ``ShapeDtypeStruct``s (sharding
    only where the array is committed) give the same signature. Raises
    :class:`_Traced` for a tracer."""
    import jax

    kinds = _kinds()
    leaves, tree = jax.tree_util.tree_flatten((dynamic, kwargs))
    return tree, tuple([_leaf(x, kinds) for x in leaves]), static


def _source_digest(root: Path) -> str:
    """sha256 of every file under ``root`` (bytecode caches aside), path
    and contents: any edit to the package changes it."""
    h = hashlib.sha256()
    for path in sorted(root.rglob("*")):
        if path.is_dir() or "__pycache__" in path.parts or path.suffix == ".pyc":
            continue
        h.update(str(path.relative_to(root)).encode() + b"\0")
        h.update(path.read_bytes() + b"\0")
    return h.hexdigest()


def _version(dist: str) -> str:
    import importlib.metadata

    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "none"


@functools.cache
def _environment() -> str:
    """The part of every key that is fixed for a process: the package's
    source, the versions, the backend and its devices, the flags given
    to XLA and libtpu."""
    import jax
    import jaxlib

    devices = jax.devices()
    return "\n".join([
        f"source {_source_digest(_PACKAGE)}",
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {_version('libtpu')}",
        f"platform {devices[0].platform} {devices[0].client.platform_version}",
        f"devices {[(d.id, d.device_kind) for d in devices]}",
        f"XLA_FLAGS {os.environ.get('XLA_FLAGS', '')}",
        f"LIBTPU_INIT_ARGS {os.environ.get('LIBTPU_INIT_ARGS', '')}",
    ])


def _config() -> str:
    """Every jax configuration value as this thread sees it (a context
    such as ``jax.default_matmul_precision`` included)."""
    import jax

    return repr(sorted(
        (name, repr(getattr(jax.config, name, value)))
        for name, value in jax.config.values.items()
    ))


def _sharding(s) -> str:
    if s is None:
        return "follows"
    mesh = getattr(s, "mesh", None)
    ids = (
        [d.id for d in mesh.devices.flat] if mesh is not None
        else sorted(d.id for d in s.device_set)
    )
    return f"{s!r} on {ids}"


def entry_name(program: str, key, sig: tuple) -> str:
    """The store's file name for one executable: sha256 of the
    environment, the jax configuration, the program's name, the caller's
    ``key`` and the call :func:`signature`."""
    tree, leaves, static = sig
    lines = [
        _environment(), _config(), f"program {program}", f"key {key!r}",
        f"tree {tree}", f"static {static!r}",
    ]
    for leaf in leaves:
        if len(leaf) == 1:
            lines.append(f"scalar {leaf[0].__name__}")
        else:
            shape, dtype, weak, sharding = leaf
            lines.append(f"{dtype}{list(shape)} weak={weak} {_sharding(sharding)}")
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


# ---------------------------------------------------------------------------
# The store
# ---------------------------------------------------------------------------

_WARNED: set = set()
_WARNED_LOCK = threading.Lock()


def _warn_once(what: str, message: str, *args) -> None:
    with _WARNED_LOCK:
        if what in _WARNED:
            return
        _WARNED.add(what)
    log.warning(message, *args)


def _load(path: Path, program: str):
    """The executable stored at ``path``, loaded onto the devices it was
    built for; None where there is none or it cannot be read."""
    from jax import monitoring

    try:
        blob = path.read_bytes()
    except FileNotFoundError:
        return None
    except OSError as e:
        _warn_once(str(path), "executable store: %s: %s", path, e)
        return None
    monitoring.record_scalar(LOAD_EVENT, time.perf_counter(), fun_name=program)
    t0 = time.perf_counter()
    try:
        import jax
        from jax._src.compilation_cache import decompress_executable
        from jax.experimental.serialize_executable import deserialize_and_load

        serialized, in_tree, out_tree, ids = pickle.loads(
            decompress_executable(blob)
        )
        by_id = {d.id: d for d in jax.devices()}
        return deserialize_and_load(
            serialized, in_tree, out_tree,
            execution_devices=[by_id[i] for i in ids],
        )
    except Exception as e:  # noqa: BLE001 - any unreadable entry is rebuilt
        _warn_once(
            str(path), "executable store: %s (%s) unreadable, rebuilding: "
            "%s: %s", path.name, program, type(e).__name__, e,
        )
        return None
    finally:
        monitoring.record_event_duration_secs(
            LOAD_EVENT, time.perf_counter() - t0, fun_name=program
        )


def _write(path: Path, compiled, program: str) -> None:
    """``compiled`` into the store at ``path``: a temporary file renamed
    over the entry, so a reader (or a second writer of the same key)
    never sees part of one. Compressed as jax compresses its own
    entries. A program that cannot be serialized is logged once."""
    tmp = None
    try:
        from jax._src.compilation_cache import compress_executable
        from jax.experimental.serialize_executable import serialize

        ids = [d.id for d in compiled.runtime_executable().local_devices()]
        blob = compress_executable(pickle.dumps((*serialize(compiled), ids)))
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=".tmp-")
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        tmp = None
    except Exception as e:  # noqa: BLE001 - the program runs unstored
        _warn_once(
            f"write {program}", "executable store: %s not stored: %s: %s",
            program, type(e).__name__, e,
        )
    finally:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _compile(lowered):
    """``lowered.compile()`` for a program the store keeps. On XLA:CPU
    outside jax's persistent cache — neither read nor written —, since
    XLA:CPU cannot serialize again an executable it loaded from there
    (the copy runs, its serialized form lacks its kernels): a key that
    holds the module's metadata is one no plain compile writes, and the
    threshold keeps this compile from writing it. Elsewhere through jax's
    cache as any compile, so a new source tree still finds there what an
    earlier one compiled."""
    import jax
    from jax._src import config as jax_config

    if jax.default_backend() != "cpu":
        return lowered.compile()
    with jax_config.compilation_cache_include_metadata_in_key(True), \
            jax_config.persistent_cache_min_compile_time_secs(math.inf):
        return lowered.compile()


_HOLDERS: "weakref.WeakSet[StoredJit]" = weakref.WeakSet()


def _release_all() -> None:
    """Drop every held executable before jax's own exit hook takes the
    backends down (``atexit`` runs hooks last-registered first, and this
    one is registered after jax's): an XLA:CPU executable loaded from
    the store and destroyed after that aborts the process on its way
    out."""
    for holder in list(_HOLDERS):
        holder._compiled.clear()
        holder._last.clear()


@functools.cache
def _release_at_exit() -> None:
    atexit.register(_release_all)


class StoredJit:
    """``jax.jit(fun, ...)`` whose executables come from the store: one a
    call :func:`_signature`, loaded where a process wrote it under the
    same key (:func:`entry_name`), else traced, lowered, compiled and
    written there. ``program`` names it in the store and the metrics
    (``fun``'s name by default); ``key`` is what ``fun`` reads besides
    its arguments.

    A call costs no signature: it goes to the executable last used with
    the same static arguments, which checks the rest itself before it
    runs (tree, shapes and dtypes), and only a call that executable
    refuses looks its own up. A call inside another trace goes to the
    jitted function itself."""

    def __init__(self, fun, *, program: str | None = None, key=(),
                 static_argnums=(), **jit_kwargs):
        import jax

        self.program = program or fun.__name__
        self._key = key
        self._static = tuple(sorted(static_argnums))
        self._jit = jax.jit(fun, static_argnums=self._static, **jit_kwargs)
        self._compiled: dict = {}  # signature -> executable
        self._last: dict = {}  # static arguments -> executable
        _release_at_exit()  # after ``import jax``, so before jax's hook
        _HOLDERS.add(self)

    def _split(self, args) -> tuple[tuple, tuple]:
        """(static arguments, dynamic arguments)."""
        if not self._static:
            return (), args
        return (
            tuple(args[i] for i in self._static if i < len(args)),
            tuple(a for i, a in enumerate(args) if i not in self._static),
        )

    def entry(self, *args, **kwargs) -> str:
        """The store's file name for these arguments."""
        static, dynamic = self._split(args)
        return entry_name(
            self.program, self._key, _signature(dynamic, kwargs, static)
        )

    def _get(self, args, kwargs, static, dynamic):
        """The executable for this call's signature: held, loaded or
        built. Raises :class:`_Traced` for a tracer."""
        sig = _signature(dynamic, kwargs, static)
        compiled = self._compiled.get(sig)
        if compiled is None:
            compiled = self._compiled[sig] = self._build(args, kwargs, sig)
        self._last[static] = compiled
        return compiled

    def _build(self, args, kwargs, sig):
        """Loaded from the store, else built and written; counted once as
        ``loaded`` or ``built`` (``utils.tracing.EXECUTABLE_EVENT``).
        Nothing here raises for the store: an entry that cannot be read
        is rebuilt and written again."""
        from jax import monitoring

        path = None
        try:
            store = store_dir()
            if store is not None:
                path = store / entry_name(self.program, self._key, sig)
        except Exception as e:  # noqa: BLE001 - the program runs unstored
            _warn_once(
                f"key {self.program}", "executable store: no key for %s: "
                "%s: %s", self.program, type(e).__name__, e,
            )
        if path is not None:
            compiled = _load(path, self.program)
            if compiled is not None:
                monitoring.record_event(
                    EXECUTABLE_EVENT, program=self.program, source="loaded"
                )
                return compiled
        lowered = self._jit.lower(*args, **kwargs)
        if path is None:
            compiled = lowered.compile()
        else:
            compiled = _compile(lowered)
            _write(path, compiled, self.program)
        monitoring.record_event(
            EXECUTABLE_EVENT, program=self.program, source="built"
        )
        return compiled

    def __call__(self, *args, **kwargs):
        static, dynamic = self._split(args)
        last = self._last.get(static)
        if last is not None:
            try:
                return last(*dynamic, **kwargs)
            except (TypeError, ValueError) as e:
                refused = e  # checked before it runs: nothing was donated
        try:
            compiled = self._get(args, kwargs, static, dynamic)
        except _Traced:
            return self._jit(*args, **kwargs)
        if compiled is last:
            raise refused  # the call's own executable: a real error
        return compiled(*dynamic, **kwargs)

    def build(self, *args, **kwargs):
        """The executable for these arguments (``ShapeDtypeStruct``s will
        do), got ahead of its first call; runs nothing."""
        static, dynamic = self._split(args)
        return self._get(args, kwargs, static, dynamic)

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def trace(self, *args, **kwargs):
        return self._jit.trace(*args, **kwargs)

    def _cache_size(self) -> int:
        """Executables held, one a signature (as ``jax.jit``'s)."""
        return len(self._compiled)
