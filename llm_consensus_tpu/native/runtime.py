"""ctypes surface over libconsensus_rt.so.

Three native components (see native/src/consensus_rt.cpp):
batch byte tokenizer, bounded MPMC request ring, mmap token data loader.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

_REPO_ROOT = Path(__file__).resolve().parents[2]
_NATIVE_DIR = _REPO_ROOT / "native"
_LIB_PATH = _NATIVE_DIR / "build" / "libconsensus_rt.so"

_lib = None
_lib_lock = threading.Lock()


def _build() -> bool:
    try:
        subprocess.run(
            ["make", "-C", str(_NATIVE_DIR)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return _LIB_PATH.exists()
    except Exception:  # noqa: BLE001 - no toolchain / build failure
        return False


def load():
    """Load (building if needed) the native library, or return None."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not _LIB_PATH.exists() and not _build():
            return None
        lib = ctypes.CDLL(str(_LIB_PATH))

        lib.rt_byte_encode_batch.restype = ctypes.c_int
        lib.rt_byte_encode_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        lib.rt_byte_decode.restype = ctypes.c_int64
        lib.rt_byte_decode.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
            ctypes.c_char_p,
            ctypes.c_int64,
        ]
        lib.rt_ring_create.restype = ctypes.c_void_p
        lib.rt_ring_create.argtypes = [ctypes.c_int64]
        lib.rt_ring_destroy.argtypes = [ctypes.c_void_p]
        lib.rt_ring_push.restype = ctypes.c_int
        lib.rt_ring_push.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.rt_ring_pop.restype = ctypes.c_int
        lib.rt_ring_pop.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
        ]
        lib.rt_ring_size.restype = ctypes.c_int64
        lib.rt_ring_size.argtypes = [ctypes.c_void_p]
        lib.rt_ring_close.argtypes = [ctypes.c_void_p]
        lib.rt_loader_create.restype = ctypes.c_void_p
        lib.rt_loader_create.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_uint64,
        ]
        lib.rt_loader_next.restype = ctypes.c_int
        lib.rt_loader_next.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.rt_loader_destroy.argtypes = [ctypes.c_void_p]
        lib.rt_loader_n_tokens.restype = ctypes.c_int64
        lib.rt_loader_n_tokens.argtypes = [ctypes.c_void_p]
        lib.rt_loader_skip.restype = ctypes.c_int
        lib.rt_loader_skip.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------------
# Tokenizer
# ---------------------------------------------------------------------------


def batch_encode(
    texts: list[str], max_len: int, add_bos: bool = True
) -> tuple[np.ndarray, np.ndarray]:
    """Encode texts into a right-padded [n, max_len] int32 batch + lengths.

    Same id scheme as :class:`llm_consensus_tpu.engine.tokenizer.ByteTokenizer`
    (0/1/2 pad/bos/eos, byte+3), same tail-keeping truncation.
    """
    lib = load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    raw = [t.encode("utf-8") for t in texts]
    n = len(raw)
    arr = (ctypes.c_char_p * n)(*raw)
    lens = (ctypes.c_int64 * n)(*[len(r) for r in raw])
    out = np.zeros((n, max_len), np.int32)
    out_lens = np.zeros((n,), np.int32)
    rc = lib.rt_byte_encode_batch(
        arr,
        lens,
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        max_len,
        out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        1 if add_bos else 0,
    )
    if rc != 0:
        raise RuntimeError(f"rt_byte_encode_batch failed: {rc}")
    return out, out_lens


def batch_decode(ids: np.ndarray) -> list[str]:
    """Decode each row of an int32 id array (stops at EOS per row)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    ids = np.ascontiguousarray(ids, np.int32)
    out = []
    cap = ids.shape[-1] + 8
    buf = ctypes.create_string_buffer(cap)
    for row in ids.reshape(-1, ids.shape[-1]):
        n = lib.rt_byte_decode(
            row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            row.shape[0],
            buf,
            cap,
        )
        if n < 0:
            raise RuntimeError("rt_byte_decode overflow")
        out.append(buf.raw[:n].decode("utf-8", errors="replace"))
    return out


# ---------------------------------------------------------------------------
# Request ring
# ---------------------------------------------------------------------------


class NativeRing:
    """Bounded MPMC byte-payload queue (the serving scheduler's spine)."""

    def __init__(self, capacity: int, max_item: int = 1 << 20):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.rt_ring_create(capacity)
        if not self._h:
            raise ValueError("bad ring capacity")
        self._max_item = max_item

    def push(self, payload: bytes, timeout: float | None = None) -> bool:
        """True on success; False on timeout. Raises if closed."""
        t = -1 if timeout is None else int(timeout * 1000)
        buf = (ctypes.c_uint8 * len(payload)).from_buffer_copy(payload)
        rc = self._lib.rt_ring_push(self._h, buf, len(payload), t)
        if rc == 2:
            raise RuntimeError("ring closed")
        return rc == 0

    def pop(self, timeout: float | None = None) -> bytes | None:
        """Payload, or None on timeout/closed-and-drained."""
        t = -1 if timeout is None else int(timeout * 1000)
        buf = (ctypes.c_uint8 * self._max_item)()
        out_len = ctypes.c_int64()
        rc = self._lib.rt_ring_pop(
            self._h, buf, self._max_item, ctypes.byref(out_len), t
        )
        if rc in (1, 2):
            return None
        if rc == 3:
            raise RuntimeError("payload exceeds max_item")
        return bytes(buf[: out_len.value])

    def __len__(self) -> int:
        return int(self._lib.rt_ring_size(self._h))

    def close(self) -> None:
        self._lib.rt_ring_close(self._h)

    def __del__(self):  # pragma: no cover - best effort
        try:
            if getattr(self, "_h", None):
                self._lib.rt_ring_destroy(self._h)
                self._h = None
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Data loader
# ---------------------------------------------------------------------------


class NativeLoader:
    """mmap'd token-shard loader with a native prefetch thread.

    ``path`` is a raw little-endian int32 token file; yields random
    [batch, seq] windows (the standard LM pretraining sampler) without
    holding the GIL during copy/shuffle.
    """

    def __init__(self, path: str | os.PathLike, batch: int, seq: int, seed: int = 0):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self.batch, self.seq = batch, seq
        self._h = lib.rt_loader_create(
            str(path).encode(), batch, seq, seed
        )
        if not self._h:
            raise FileNotFoundError(f"cannot open token shard {path}")

    @property
    def n_tokens(self) -> int:
        return int(self._lib.rt_loader_n_tokens(self._h))

    def next(self) -> np.ndarray:
        out = np.empty((self.batch, self.seq), np.int32)
        rc = self._lib.rt_loader_next(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        )
        if rc != 0:
            raise RuntimeError("loader stopped")
        return out

    def skip(self, n: int) -> None:
        """Discard n batches in C (checkpoint-resume fast-forward)."""
        if n <= 0:
            return
        if self._lib.rt_loader_skip(self._h, n) != 0:
            raise RuntimeError("loader stopped")

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.rt_loader_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass
