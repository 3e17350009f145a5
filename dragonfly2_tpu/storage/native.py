"""ctypes bindings to the C++ hot-path library (``native/``).

The native library accelerates what the reference's Rust client (`client-rs`)
and Go hot loops do natively: piece hashing (sha256/md5/crc32c) and aligned
file piece IO. Loading is best-effort — every caller has a pure-Python
fallback, so the framework runs (slower) without the .so. Build with
``make -C native`` (see native/Makefile).
"""

from __future__ import annotations

import ctypes
import os
import threading

_LIB_NAMES = ("libdfnative.so",)
_lib = None
_lib_lock = threading.Lock()
_load_attempted = False


def _candidate_paths():
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    for name in _LIB_NAMES:
        yield os.path.join(repo, "native", "build", name)
        yield os.path.join(repo, "native", name)
        yield name  # system path


def load():
    """Load the native library once; returns None if unavailable."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        for path in _candidate_paths():
            try:
                lib = ctypes.CDLL(path)
            except OSError:
                continue
            try:
                _bind(lib)
            except AttributeError:
                continue
            _lib = lib
            break
    return _lib


def _bind(lib) -> None:
    # int df_hash(const char* algo, const uint8_t* data, size_t n, char* hex_out, size_t hex_cap)
    lib.df_hash.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
                            ctypes.c_char_p, ctypes.c_size_t]
    lib.df_hash.restype = ctypes.c_int
    # uint32 df_crc32c(const uint8_t* data, size_t n, uint32 seed) — chainable
    lib.df_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
    lib.df_crc32c.restype = ctypes.c_uint32
    # Newer exports bind OPTIONALLY: a stale .so built before they existed
    # must keep its working hash path (losing ALL native acceleration to an
    # AttributeError here would silently drop crc32c to the pure-Python
    # fallback fleet-wide).
    try:
        # int df_piece_write(path, offset, data, n, uint32* crc_out)
        lib.df_piece_write.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                       ctypes.c_char_p, ctypes.c_size_t,
                                       ctypes.POINTER(ctypes.c_uint32)]
        lib.df_piece_write.restype = ctypes.c_int
        # int64 df_piece_read(path, offset, uint8* out, n)
        lib.df_piece_read.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                      ctypes.c_char_p, ctypes.c_size_t]
        lib.df_piece_read.restype = ctypes.c_int64
        lib._df_has_piece_io = True
    except AttributeError:
        lib._df_has_piece_io = False
    try:
        # int df_span_write(fd, offset, data, uint64* piece_sizes,
        #                   n_pieces, uint32* crcs_out) — fused span landing
        # over a cached fd; bound separately so a pre-span .so keeps its
        # working piece IO
        lib.df_span_write.argtypes = [ctypes.c_int, ctypes.c_uint64,
                                      ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_uint64),
                                      ctypes.c_size_t,
                                      ctypes.POINTER(ctypes.c_uint32)]
        lib.df_span_write.restype = ctypes.c_int
        lib._df_has_span_io = True
    except AttributeError:
        lib._df_has_span_io = False
    try:
        # int df_span_write_staged(fd, offset, data, uint64* piece_sizes,
        #                          n_pieces, uint32* crcs_out,
        #                          int64* expect, uint8* stage_dst,
        #                          uint64* stage_ns_out) — df_span_write
        # that also copies each verified piece into a device sink's host
        # buffer; bound separately for the same reason
        lib.df_span_write_staged.argtypes = [
            ctypes.c_int, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.df_span_write_staged.restype = ctypes.c_int
        lib._df_has_span_stage = True
    except AttributeError:
        lib._df_has_span_stage = False


def available() -> bool:
    return load() is not None


def _buf_arg(data) -> tuple:
    """(c_char_p-compatible pointer, length) WITHOUT copying writable
    buffers: bytes pass through; bytearray / writable memoryview expose
    their storage via from_buffer. Only readonly views pay a copy. The
    download path hands 4-16 MiB bytearrays here — a per-piece bytes()
    conversion would re-copy every P2P byte."""
    if isinstance(data, bytes):
        return data, len(data)
    mv = data if isinstance(data, memoryview) else memoryview(data)
    if mv.readonly or not mv.contiguous:
        b = mv.tobytes()
        return b, len(b)
    n = mv.nbytes
    return ctypes.cast((ctypes.c_char * n).from_buffer(mv),
                       ctypes.c_char_p), n


def crc32c_update(data: bytes | bytearray | memoryview, seed: int) -> int | None:
    """Chainable crc32c via the native lib, or None to signal fallback."""
    lib = load()
    if lib is None:
        return None
    ptr, n = _buf_arg(data)
    return int(lib.df_crc32c(ptr, n, seed))


def hash_bytes(algo: str, data: bytes | bytearray | memoryview) -> str | None:
    """Hex digest via native lib, or None to signal fallback."""
    lib = load()
    if lib is None:
        return None
    ptr, n = _buf_arg(data)
    out = ctypes.create_string_buffer(129)
    rc = lib.df_hash(algo.encode(), ptr, n, out, len(out))
    if rc != 0:
        return None
    return out.value.decode()


def piece_write(path: str, offset: int, data: bytes | memoryview
                ) -> str | None:
    """Fused write+hash: pwrite ``data`` at ``offset`` while computing its
    crc32c in the same pass (one memory traversal instead of Python's
    hash-then-write two). Returns the crc32c hex, or None to signal
    fallback to the pure-Python path. Raises OSError on IO failure."""
    lib = load()
    if lib is None or not getattr(lib, "_df_has_piece_io", False):
        return None
    ptr, n = _buf_arg(data)
    crc = ctypes.c_uint32(0)
    rc = lib.df_piece_write(path.encode(), offset, ptr, n,
                            ctypes.byref(crc))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    return f"{crc.value:08x}"


def span_write(fd: int, offset: int, data: bytes | bytearray | memoryview,
               piece_sizes: list[int]) -> list[str] | None:
    """Fused span landing: ONE pwrite traversal of ``data`` at ``offset``
    through an already-open ``fd``, folding per-piece crc32c as it goes.
    Returns the per-piece crc32c hex list, or None to signal fallback to
    the pure-Python path (no .so, or a stale .so without the export).
    Raises OSError on IO failure."""
    lib = load()
    if lib is None or not getattr(lib, "_df_has_span_io", False):
        return None
    ptr, n = _buf_arg(data)
    if n != sum(piece_sizes):
        raise ValueError(f"span buffer {n} != sum(piece_sizes) "
                         f"{sum(piece_sizes)}")
    sizes = (ctypes.c_uint64 * len(piece_sizes))(*piece_sizes)
    crcs = (ctypes.c_uint32 * len(piece_sizes))()
    rc = lib.df_span_write(fd, offset, ptr, sizes, len(piece_sizes), crcs)
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return [f"{c:08x}" for c in crcs]


def span_write_staged(fd: int, offset: int,
                      data: bytes | bytearray | memoryview,
                      piece_sizes: list[int], expect: list[int],
                      stage_addr: int) -> tuple[list[str], float] | None:
    """``span_write`` that also stages: in the same call, on the same
    thread and with the GIL dropped, each piece whose crc32c equals
    ``expect[i]`` (-1: the piece carries no digest) is copied to
    ``stage_addr + (its offset in data)``, AFTER its crc is known, so a
    corrupt piece's bytes never arrive there. ``stage_addr`` is the
    address of ``len(data)`` writable bytes that the caller keeps alive
    across the call (``StageLease.address``). Returns the crc hex list
    and the seconds the copies took, or None to signal fallback (no .so,
    or one built before the export)."""
    lib = load()
    if lib is None or not getattr(lib, "_df_has_span_stage", False):
        return None
    ptr, n = _buf_arg(data)
    if n != sum(piece_sizes) or len(expect) != len(piece_sizes):
        raise ValueError(f"span buffer {n}, piece_sizes {piece_sizes} and "
                         f"{len(expect)} expected crcs do not agree")
    sizes = (ctypes.c_uint64 * len(piece_sizes))(*piece_sizes)
    want = (ctypes.c_int64 * len(expect))(*expect)
    crcs = (ctypes.c_uint32 * len(piece_sizes))()
    stage_ns = ctypes.c_uint64(0)
    rc = lib.df_span_write_staged(fd, offset, ptr, sizes, len(piece_sizes),
                                  crcs, want, stage_addr,
                                  ctypes.byref(stage_ns))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return [f"{c:08x}" for c in crcs], stage_ns.value / 1e9


def piece_read(path: str, offset: int, length: int) -> bytes | None:
    """pread a piece straight into a fresh buffer via the native lib, or
    None to signal fallback. Raises OSError on IO failure; short reads
    past EOF return the available bytes.

    LEGACY: the store's hot read path moved to plain os.pread on the
    cached per-task fd (store._data_fd) — same zero-copy profile without
    a ctypes hop. Kept for external tooling against the path-based ABI
    (exercised by tests/test_storage.py)."""
    lib = load()
    if lib is None or not getattr(lib, "_df_has_piece_io", False):
        return None
    # one allocation, no zero-fill pass, no .raw copy: pread fills the
    # bytearray in place and full reads (the normal case) return it as-is
    buf = bytearray(length)
    got = lib.df_piece_read(path.encode(), offset,
                            (ctypes.c_char * length).from_buffer(buf),
                            length)
    if got < 0:
        raise OSError(-got, os.strerror(-got), path)
    return bytes(buf) if got == length else bytes(buf[:got])
