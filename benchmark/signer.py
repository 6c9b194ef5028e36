"""The signer of the harness's own actors: the n-1 peers and the node's
validator client sign their partials with it, and the cluster's public
keys and public shares (the node's INPUT, its lock file) are made with it.
It is the C++ library the repo builds from native/ (a copy, commit
dd23c5f) — fast, and releasing the GIL while it signs. It decides
nothing: what `correct` compares comes from reference.py, which shares no
code with it."""

from __future__ import annotations

import ctypes
from pathlib import Path

_LIB = Path(__file__).resolve().parent / "libpeersigner.so"


def _load():
    lib = ctypes.CDLL(str(_LIB))
    c, sz = ctypes.c_char_p, ctypes.c_size_t
    lib.ctpu_sign.restype = ctypes.c_int
    lib.ctpu_sign.argtypes = [c, c, sz, c]
    lib.ctpu_sk_to_pk.restype = ctypes.c_int
    lib.ctpu_sk_to_pk.argtypes = [c, c]
    lib.ctpu_threshold_aggregate.restype = ctypes.c_int
    lib.ctpu_threshold_aggregate.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint64), c, c]
    return lib


_lib = _load()


def secret_to_public_key(secret: bytes) -> bytes:
    out = ctypes.create_string_buffer(48)
    if len(secret) != 32 or not _lib.ctpu_sk_to_pk(secret, out):
        raise ValueError("sk_to_pk failed")
    return out.raw


def sign(secret: bytes, data: bytes) -> bytes:
    out = ctypes.create_string_buffer(96)
    if len(secret) != 32 or not _lib.ctpu_sign(secret, data, len(data), out):
        raise ValueError("sign failed")
    return out.raw


def recombine_unchecked(partials: dict[int, bytes]) -> bytes:
    """Lagrange recombination at zero of (share index -> partial), with
    no check of anything: what tests/control.py puts in the node's place."""
    idx = sorted(partials)
    arr = (ctypes.c_uint64 * len(idx))(*idx)
    out = ctypes.create_string_buffer(96)
    if not _lib.ctpu_threshold_aggregate(len(idx), arr, b"".join(partials[i] for i in idx), out):
        raise ValueError("recombination failed")
    return out.raw
