"""ctypes loader for the C++ native layer (libhm_native.so).

The reference leans on four native npm addons — sodium (ed25519/blake2b),
iltorb (brotli), better-sqlite3, utp-native (SURVEY.md §2.4). This module
loads our C++ equivalent for the crypto + codec surface and exposes it to
Python; every capability degrades to a pure-Python fallback at the call
site (utils/crypto.py, storage/block.py), so the framework runs — slower
— on machines without a toolchain or the shared libraries.

The shared object builds on demand: first import runs `make` in this
directory when `libhm_native.so` is absent and a compiler is available.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading
from typing import Optional

from ..analysis.lockdep import make_lock

CAP_SODIUM = 1
CAP_BROTLI = 2
CAP_ZLIB = 4

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libhm_native.so")

_lock = make_lock("native.load")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    if shutil.which("make") is None or shutil.which("g++") is None:
        return False
    try:
        subprocess.run(
            ["make", "-C", _DIR],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (subprocess.SubprocessError, OSError):
        return False
    return os.path.exists(_SO)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.hm_caps.restype = ctypes.c_int
    lib.hm_ed25519_public.restype = ctypes.c_int
    lib.hm_ed25519_public.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.hm_ed25519_sign.restype = ctypes.c_int
    lib.hm_ed25519_sign.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.hm_ed25519_verify.restype = ctypes.c_int
    lib.hm_ed25519_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.hm_blake2b.restype = ctypes.c_int
    lib.hm_blake2b.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.hm_merkle_root.restype = ctypes.c_int
    lib.hm_merkle_root.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.hm_x25519_base.restype = ctypes.c_int
    lib.hm_x25519_base.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.hm_x25519.restype = ctypes.c_int
    lib.hm_x25519.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.hm_aead_encrypt.restype = ctypes.c_long
    lib.hm_aead_encrypt.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.hm_aead_decrypt.restype = ctypes.c_long
    lib.hm_aead_decrypt.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.hm_compress_bound.restype = ctypes.c_size_t
    lib.hm_compress_bound.argtypes = [ctypes.c_size_t]
    lib.hm_compress.restype = ctypes.c_long
    lib.hm_compress.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.hm_decompress.restype = ctypes.c_long
    lib.hm_decompress.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    # columnar pack entry points are OPTIONAL: a prebuilt .so from an
    # older tree (no compiler to rebuild with) must keep serving crypto
    # + codec rather than disabling the whole native layer.
    #
    # GIL contract: the library is loaded with ctypes.CDLL (never
    # PyDLL), so every foreign call — hm_pack_prefix, hm_pack_gather
    # and hm_prefix_gate included — RUNS WITH THE GIL RELEASED for the
    # duration of the C call. The streaming slab pipeline
    # (backend/pipeline.py) depends
    # on this: its pack worker thread spends its time inside them
    # while the io thread reads the next slab's sidecars and the
    # dispatch thread feeds the device. The pack entries touch only
    # caller-owned buffers (no Python objects, no allocation through
    # CPython), which is what makes the GIL-free call sound; pinned by
    # tests/test_native_pack.py::test_pack_releases_gil.
    try:
        ll = ctypes.c_longlong
        lib.hm_pack_value_minmax.restype = ctypes.c_int
        lib.hm_pack_value_minmax.argtypes = [ll] + [ctypes.c_void_p] * 12
        lib.hm_pack_prefix.restype = ctypes.c_int
        lib.hm_pack_prefix.argtypes = [ll, ll, ll] + [ctypes.c_void_p] * 16
        lib.hm_pack_gather.restype = ctypes.c_int
        lib.hm_pack_gather.argtypes = [ll] + [ctypes.c_void_p] * 8
        lib.hm_prefix_gate.restype = ctypes.c_int
        lib.hm_prefix_gate.argtypes = [ll] + [ctypes.c_void_p] * 6
        lib._has_pack = True
    except AttributeError:
        lib._has_pack = False
    # change-frame codec entry points are OPTIONAL for the same
    # prebuilt-.so reason; same GIL contract as the pack entries
    # (caller-owned buffers only — pinned by codec_drops_gil()).
    try:
        buf = ctypes.c_char_p
        lib.hm_change_encode.restype = ctypes.c_long
        lib.hm_change_encode.argtypes = [
            buf, ctypes.c_size_t, buf, ctypes.c_size_t,
        ]
        lib.hm_change_decode.restype = ctypes.c_long
        lib.hm_change_decode.argtypes = [
            buf, ctypes.c_size_t, buf, ctypes.c_size_t,
        ]
        lib._has_codec = True
    except AttributeError:
        lib._has_codec = False
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The bound library, building it first if needed; None when
    unavailable (no compiler and no prebuilt .so)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("HM_NO_NATIVE"):
            return None
        src = os.path.join(_DIR, "src", "hm_native.cpp")
        stale = os.path.exists(_SO) and os.path.exists(src) and (
            os.path.getmtime(src) > os.path.getmtime(_SO)
        )
        if (not os.path.exists(_SO) or stale) and not _build():
            if not os.path.exists(_SO):
                return None
        try:
            _lib = _bind(ctypes.CDLL(_SO))
        except (OSError, AttributeError):
            # unloadable, or a stale prebuilt .so missing newer symbols
            # (rebuild failed): fall back to pure Python
            _lib = None
        return _lib


def caps() -> int:
    lib = load()
    return lib.hm_caps() if lib is not None else 0


def pack_lib() -> Optional[ctypes.CDLL]:
    """The library handle iff it carries the columnar pack entry points
    (ops/columnar.py native fast path); None otherwise."""
    lib = load()
    if lib is None or not getattr(lib, "_has_pack", False):
        return None
    return lib


def pack_drops_gil() -> bool:
    """True when the pack entry points are bound through a plain
    ctypes.CDLL, whose foreign calls release the GIL — the property the
    bulk loader's pipelined pack stage relies on to overlap packing
    with sidecar IO and device dispatch. (ctypes.PyDLL would hold the
    GIL; we never load through it.)"""
    lib = pack_lib()
    return lib is not None and not isinstance(lib, ctypes.PyDLL)


def pack_parallel_ok() -> bool:
    """True when hm_pack_prefix / hm_pack_value_minmax / hm_pack_gather /
    hm_prefix_gate may be called from SEVERAL threads at once — the pack
    pool's contract (HM_PACK_WORKERS > 1, backend/pipeline.py).

    The entry points are stateless C loops: every pointer they touch
    (source planes, LUTs, output buffers) is a caller-owned argument,
    there are no globals, no allocation, and no errno-style side
    channels, so concurrent calls with DISTINCT output buffers are
    safe by construction. Distinctness is the caller's obligation and
    holds trivially for the pool: each worker packs a different slab
    into buffers it just allocated (hm_prefix_gate's only output is the
    caller's fresh verdict array; two workers gating one feed read the
    same immutable planes). Combined with the GIL release
    (pack_drops_gil) this is what makes N pack workers N-core real
    rather than time-sliced."""
    return pack_drops_gil()


def codec_lib() -> Optional[ctypes.CDLL]:
    """The library handle iff it carries the change-frame codec entry
    points (crdt/codec.py native fast path); None otherwise."""
    lib = load()
    if lib is None or not getattr(lib, "_has_codec", False):
        return None
    return lib


def codec_drops_gil() -> bool:
    """True when the change-codec entry points are bound through a
    plain ctypes.CDLL, whose foreign calls release the GIL — the
    property the sharded write daemon relies on to parse frames from N
    connections on real threads. (ctypes.PyDLL would hold the GIL; we
    never load through it.)"""
    lib = codec_lib()
    return lib is not None and not isinstance(lib, ctypes.PyDLL)


def _codec_call(fn, data: bytes, guess: int) -> Optional[bytes]:
    """Counting-writer protocol shared by encode/decode: the entry
    point always returns the size it NEEDS and only writes what fits
    in cap, so one retry with the returned size always lands."""
    out = ctypes.create_string_buffer(guess)
    n = fn(data, len(data), out, guess)
    if n < 0:
        return None
    if n > guess:
        out = ctypes.create_string_buffer(n)
        n = fn(data, len(data), out, n)
        if n < 0 or n > len(out):
            return None
    return out.raw[:n]


def change_encode(raw: bytes) -> Optional[bytes]:
    """Canonical change JSON -> binary change frame; None when the
    native layer is absent or the input is off-canon (caller falls
    back to the Python twin / raw JSON block)."""
    lib = codec_lib()
    if lib is None:
        return None
    return _codec_call(lib.hm_change_encode, raw, len(raw) + 16)


def change_decode(frame: bytes) -> Optional[bytes]:
    """Binary change frame -> canonical change JSON; None when the
    native layer is absent or the frame is malformed."""
    lib = codec_lib()
    if lib is None:
        return None
    return _codec_call(lib.hm_change_decode, frame, 2 * len(frame) + 64)


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------
# typed wrappers (None / raise on unavailable capability — callers that
# want graceful degradation go through utils/crypto.py)


def ed25519_public(seed: bytes) -> Optional[bytes]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    out = ctypes.create_string_buffer(32)
    if lib.hm_ed25519_public(seed, out) != 0:
        return None
    return out.raw


def ed25519_sign(seed: bytes, msg: bytes) -> Optional[bytes]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    sig = ctypes.create_string_buffer(64)
    if lib.hm_ed25519_sign(seed, msg, len(msg), sig) != 0:
        return None
    return sig.raw


def ed25519_verify(pub: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    return bool(lib.hm_ed25519_verify(pub, msg, len(msg), sig))


def blake2b(
    data: bytes, key: bytes = b"", outlen: int = 32
) -> Optional[bytes]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    out = ctypes.create_string_buffer(outlen)
    if lib.hm_blake2b(data, len(data), key or None, len(key), out, outlen) != 0:
        return None
    return out.raw


def merkle_root(leaves: bytes) -> Optional[bytes]:
    """Root over concatenated 32-byte leaf hashes."""
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    if len(leaves) % 32:
        raise ValueError("leaves must be a multiple of 32 bytes")
    out = ctypes.create_string_buffer(32)
    if lib.hm_merkle_root(leaves, len(leaves) // 32, out) != 0:
        return None
    return out.raw


def x25519_base(sk: bytes) -> Optional[bytes]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    out = ctypes.create_string_buffer(32)
    if lib.hm_x25519_base(sk, out) != 0:
        return None
    return out.raw


def x25519(sk: bytes, pk: bytes) -> Optional[bytes]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    out = ctypes.create_string_buffer(32)
    if lib.hm_x25519(sk, pk, out) != 0:
        return None
    return out.raw


def aead_encrypt(key: bytes, nonce: bytes, msg: bytes) -> Optional[bytes]:
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    out = ctypes.create_string_buffer(len(msg) + 16)
    n = lib.hm_aead_encrypt(key, nonce, msg, len(msg), out)
    if n < 0:
        return None
    return out.raw[:n]


_AEAD_FAIL = object()


def aead_decrypt(key: bytes, nonce: bytes, ct: bytes):
    """None = native unavailable; _AEAD_FAIL = authentication failed."""
    lib = load()
    if lib is None or not (lib.hm_caps() & CAP_SODIUM):
        return None
    if len(ct) < 16:
        return _AEAD_FAIL
    out = ctypes.create_string_buffer(max(len(ct) - 16, 1))
    n = lib.hm_aead_decrypt(key, nonce, ct, len(ct), out)
    if n == -2:
        return None
    if n < 0:
        return _AEAD_FAIL
    return out.raw[:n]


CODEC_BROTLI = 1
CODEC_ZLIB = 2


def compress(codec: int, data: bytes, quality: int = 5) -> Optional[bytes]:
    lib = load()
    if lib is None:
        return None
    cap = lib.hm_compress_bound(len(data))
    out = ctypes.create_string_buffer(cap)
    n = lib.hm_compress(codec, quality, data, len(data), out, cap)
    if n < 0:
        return None
    return out.raw[:n]


def decompress(codec: int, data: bytes, raw_len: int) -> Optional[bytes]:
    lib = load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(max(raw_len, 1))
    n = lib.hm_decompress(codec, data, len(data), out, raw_len)
    if n < 0:
        return None
    return out.raw[:n]
