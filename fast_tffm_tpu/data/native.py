"""ctypes wrapper around the C++ batch parser (lazy-built with g++).

The shared library is compiled on first use into the package directory and
cached under a name that carries a hash of the source's content.
``NativeParser.parse_batch`` is the drop-in fast path for the Python
oracle's parse+batch (:func:`fast_tffm_tpu.data.libsvm.parse_lines` +
``make_batch``); tests enforce bit-exact agreement between the two.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from fast_tffm_tpu.data.libsvm import Batch

log = logging.getLogger(__name__)


class OutOfRangeIdsError(ValueError):
    """Batch ids fall outside [0, vocabulary_size).

    This is a data / vocabulary_size integrity bug, not a transient
    native failure: the device-sort fallback would silently drop updates
    for the out-of-range ids, so callers must keep surfacing it instead
    of degrading once and going quiet (ADVICE r5).
    """


_SRC_DIR = os.path.join(os.path.dirname(__file__), "_src")
_SRC = os.path.join(_SRC_DIR, "fm_parser.cc")
# Plain -O3, no -march=native: measured FASTER here (819k vs 705k
# lines/s — native's wider vectorization loses on this workload), and a
# baseline-ISA .so stays safe if the built artifact ever moves to a
# different CPU.
_CXX = ("g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread")
_build_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _lib_path() -> str:
    """The artifact's path, keyed on the CONTENT of the source and the
    compile command: a binary built from another source (a copied tree
    with arbitrary mtimes, an edited fm_parser.cc) has another name and
    can never be loaded in place of the current one."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_SRC_DIR, f"libfm_parser.{h.hexdigest()[:16]}.so")


def _build() -> str:
    with _build_lock:
        lib = _lib_path()
        if os.path.exists(lib):
            return lib
        # pid-suffixed temp + atomic rename: concurrent first builds
        # (test workers, parse processes) never load a half-written file.
        tmp = f"{lib}.{os.getpid()}.tmp"
        cmd = [*_CXX, _SRC, "-o", tmp]
        log.info("building native parser: %s", " ".join(cmd))
        try:
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, lib)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
        return lib


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(_build())
    lib.fm_parser_create.restype = ctypes.c_void_p
    lib.fm_parser_create.argtypes = [
        ctypes.c_uint64, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    lib.fm_parser_destroy.argtypes = [ctypes.c_void_p]
    lib.fm_parser_parse.restype = ctypes.c_int64
    lib.fm_parser_parse.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_void_p,
    ]
    lib.fm_parser_murmur64.restype = ctypes.c_uint64
    lib.fm_parser_murmur64.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.fm_parser_find_lines.restype = ctypes.c_int64
    lib.fm_parser_find_lines.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ]
    lib.fm_parser_parse_raw.restype = ctypes.c_int64
    lib.fm_parser_parse_raw.argtypes = [
        ctypes.c_void_p,
        # buf: void* instead of char* so callers can pass either bytes
        # or a raw address into a shared-memory ring slot (ctypes
        # converts bytes to a pointer for c_void_p params too).
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),  # starts
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),  # ends
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_void_p,
    ]
    lib.fm_sort_meta.restype = ctypes.c_int64
    lib.fm_sort_meta.argtypes = [
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # ids
        ctypes.c_int64,  # n
        ctypes.c_int64,  # n_pad
        ctypes.c_int64,  # vocab
        ctypes.c_int64,  # chunk
        ctypes.c_int64,  # tile
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # perm
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # upos
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # starts
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # firsts
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # ends
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # tile_start
    ]
    _lib = lib
    return lib


def sort_meta(ids, vocab: int, chunk: int, tile: int):
    """Host-side sparse-apply prep for one batch's flat ids.

    Mirrors ops/sparse_apply._prep's id-derived outputs exactly (stable
    sort, sentinel padding to a CHUNK multiple); parity is test-enforced.
    Returns a :class:`fast_tffm_tpu.data.libsvm.SortMeta`.
    """
    from fast_tffm_tpu.data.libsvm import SortMeta

    lib = _load()
    ids = np.ascontiguousarray(np.asarray(ids).reshape(-1), np.int32)
    n = ids.shape[0]
    if n:
        # The C++ side also rejects out-of-range ids (it would corrupt
        # its bucket scatter) but folds them into the same rc as bad
        # arguments; pre-checking here gives the caller a typed error to
        # tell the integrity bug apart from a transient failure.
        lo, hi = int(ids.min()), int(ids.max())
        if lo < 0 or hi >= vocab:
            raise OutOfRangeIdsError(
                f"out-of-range batch ids (outside [0, {vocab})): "
                f"min={lo} max={hi} — input data and vocabulary_size "
                "disagree"
            )
    n_pad = -(-n // chunk) * chunk
    n_chunks = n_pad // chunk
    n_tiles = vocab // tile
    perm = np.empty((n_pad,), np.int32)
    upos = np.empty((n_pad,), np.int32)
    lrow_last = np.empty((n_pad,), np.float32)
    starts = np.empty((n_chunks,), np.int32)
    firsts = np.empty((n_chunks + 1,), np.int32)
    ends = np.empty((n_chunks,), np.int32)
    tile_start = np.empty((n_tiles + 1,), np.int32)
    rc = lib.fm_sort_meta(
        ids, n, n_pad, vocab, chunk, tile,
        perm, upos, lrow_last, starts, firsts, ends, tile_start,
    )
    if rc < 0:
        raise ValueError(
            f"fm_sort_meta rejected arguments or out-of-range ids: n={n} "
            f"vocab={vocab} chunk={chunk} tile={tile}"
        )
    return SortMeta(perm, upos, lrow_last, starts, firsts, ends, tile_start)


def find_line_offsets(
    buf: bytes, length: Optional[int] = None, guess: Optional[int] = None
) -> np.ndarray:
    """Line-start offsets in buf[:length] (C++ memchr scan, no copies).

    ``guess`` is the expected line count (callers streaming a file pass the
    previous buffer's count — line density is stable, avoiding a rescan).
    """
    lib = _load()
    n_len = len(buf) if length is None else length
    guess = max(16, n_len // 64 if guess is None else guess)
    while True:
        out = np.empty((guess,), np.int64)
        n = lib.fm_parser_find_lines(buf, n_len, out, guess)
        if n <= guess:
            return out[:n]
        guess = n


def murmur64_native(data: bytes) -> int:
    return _load().fm_parser_murmur64(data, len(data))


class NativeParser:
    """Multi-threaded libsvm batch parser backed by the C++ extension."""

    def __init__(
        self,
        vocabulary_size: int,
        max_features: int,
        hash_feature_id: bool = False,
        field_num: int = 0,
        num_threads: int = 4,
    ):
        self._lib = _load()
        self.max_features = max_features
        self.truncated_features = 0  # running count, like reference warnings
        self._trunc_lock = threading.Lock()  # parser threads share self
        self._handle = self._lib.fm_parser_create(
            vocabulary_size, max_features, int(hash_feature_id), field_num,
            num_threads,
        )
        if not self._handle:
            raise ValueError(
                f"vocabulary_size {vocabulary_size} out of range (must be "
                "in [1, 2^59) for the native parser)"
            )

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.fm_parser_destroy(handle)
            self._handle = None

    def parse_batch(
        self,
        lines: Sequence[str],
        batch_size: int,
        weights: Optional[Sequence[float]] = None,
    ) -> Batch:
        n = len(lines)
        if n > batch_size:
            raise ValueError(f"{n} lines > batch_size {batch_size}")
        encoded = [s.encode("utf-8") for s in lines]
        buf = b"\n".join(encoded)
        lens = np.fromiter((len(e) for e in encoded), np.int64, count=n)
        offsets = np.zeros((n + 1,), np.int64)
        np.cumsum(lens + 1, out=offsets[1:])  # +1 for the joining '\n'
        if n:
            offsets[n] -= 1  # last line has no trailing separator

        labels = np.zeros((batch_size,), np.float32)
        ids = np.zeros((batch_size, self.max_features), np.int32)
        vals = np.zeros((batch_size, self.max_features), np.float32)
        fields = np.zeros((batch_size, self.max_features), np.int32)
        w = np.zeros((batch_size,), np.float32)

        weights_in = None
        weights_ptr = None
        if weights is not None:
            weights_in = np.ascontiguousarray(weights, np.float32)
            if weights_in.shape != (n,):
                raise ValueError("weights must have one entry per line")
            weights_ptr = weights_in.ctypes.data_as(ctypes.c_void_p)

        dropped = self._lib.fm_parser_parse(
            self._handle, buf, offsets, n, labels, ids, vals, fields, w,
            weights_ptr,
        )
        if dropped < 0:
            bad = -int(dropped) - 1
            raise ValueError(
                f"malformed libsvm input at batch line {bad}: {lines[bad]!r}"
            )
        if dropped:
            with self._trunc_lock:
                self.truncated_features += int(dropped)
        return Batch(labels, ids, vals, fields, w)

    def parse_raw(
        self,
        buf: bytes,
        starts: np.ndarray,  # [n] int64 line-start offsets into buf
        ends: np.ndarray,  # [n] int64 line-end offsets (exclusive)
        batch_size: int,
    ) -> Batch:
        """Zero-copy fast path: parse lines straight out of a raw file
        chunk (no Python string per line).  Lines may be non-contiguous
        and in any order — the pipeline's line-level shuffle passes a
        permuted view of a scanned window.  Blank/comment lines become
        weight-0 rows.

        ``buf`` may be bytes or a buffer (a memoryview of a shared-
        memory ring slot): parse workers read straight out of the
        mapped segment, no bytes() copy."""
        buf_arg = buf
        holder = None
        if not isinstance(buf, (bytes, bytearray)):
            # Pass non-bytes buffers by raw address (the argtype is
            # void*).  A numpy view — not a ctypes from_buffer/cast
            # pair, whose internal _objects cycle keeps the buffer
            # exported until a cycle collection and makes the segment's
            # mmap unclosable at worker exit — pins the exporter for
            # the call's duration.
            holder = np.frombuffer(buf, np.uint8)
            buf_arg = holder.ctypes.data
        n = len(starts)
        if n > batch_size:
            raise ValueError(f"{n} lines > batch_size {batch_size}")
        if len(ends) != n:
            raise ValueError(f"starts/ends length mismatch: {n}/{len(ends)}")
        starts = np.ascontiguousarray(starts, np.int64)
        ends = np.ascontiguousarray(ends, np.int64)
        labels = np.zeros((batch_size,), np.float32)
        ids = np.zeros((batch_size, self.max_features), np.int32)
        vals = np.zeros((batch_size, self.max_features), np.float32)
        fields = np.zeros((batch_size, self.max_features), np.int32)
        w = np.zeros((batch_size,), np.float32)
        dropped = self._lib.fm_parser_parse_raw(
            self._handle, buf_arg, starts, ends, n, labels, ids, vals,
            fields, w, None,
        )
        if dropped < 0:
            bad = -int(dropped) - 1
            text = bytes(buf[starts[bad]:ends[bad]])
            raise ValueError(
                f"malformed libsvm input at chunk line {bad}: {text!r}"
            )
        if dropped:
            with self._trunc_lock:
                self.truncated_features += int(dropped)
        return Batch(labels, ids, vals, fields, w)
