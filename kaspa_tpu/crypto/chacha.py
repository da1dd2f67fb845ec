"""Vectorised ChaCha20 keystream (djb variant, 64-bit counter, nonce 0).

Matches rand_chacha's ChaCha20Rng::from_seed(key).fill_bytes(..) used for
muhash element expansion (crypto/muhash/src/lib.rs:152-168): keystream
blocks from counter 0 with stream id 0.  numpy-vectorised over a batch of
keys — this is the host-side element-generation throughput path feeding the
TPU U3072 reduction.
"""

from __future__ import annotations

import ctypes

from kaspa_tpu.crypto import hostcrypto

import numpy as np

_CONSTANTS = np.array([0x61707865, 0x3320646E, 0x79622D32, 0x6B206574], dtype=np.uint32)


def _rotl(x, n):
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


def _quarter(s, a, b, c, d):
    s[a] += s[b]
    s[d] = _rotl(s[d] ^ s[a], 16)
    s[c] += s[d]
    s[b] = _rotl(s[b] ^ s[c], 12)
    s[a] += s[b]
    s[d] = _rotl(s[d] ^ s[a], 8)
    s[c] += s[d]
    s[b] = _rotl(s[b] ^ s[c], 7)


def keystream(keys: np.ndarray, n_bytes: int) -> np.ndarray:
    """keys: [N, 32] uint8 -> [N, n_bytes] uint8 keystream (counter from 0).

    Uses the native C path when available (the per-element host hot loop of
    muhash element expansion); falls back to the vectorised numpy rounds.
    """
    assert keys.ndim == 2 and keys.shape[1] == 32
    n = keys.shape[0]
    lib = hostcrypto.lib()
    if lib is not None and n > 0:
        keys_u8 = np.ascontiguousarray(keys, dtype=np.uint8)
        out = np.empty((n, n_bytes), dtype=np.uint8)
        lib.chacha20_keystream_batch(
            keys_u8.ctypes.data_as(ctypes.c_char_p), n, out.ctypes.data_as(ctypes.c_void_p), n_bytes
        )
        return out
    key_words = keys.view("<u4").reshape(n, 8).astype(np.uint32)
    n_blocks = (n_bytes + 63) // 64
    out = np.empty((n, n_blocks * 64), dtype=np.uint8)
    with np.errstate(over="ignore"):
        for blk in range(n_blocks):
            init = np.empty((16, n), dtype=np.uint32)
            init[0:4] = _CONSTANTS[:, None]
            init[4:12] = key_words.T
            init[12] = np.uint32(blk)  # 64-bit LE counter, low word
            init[13] = 0
            init[14] = 0  # nonce / stream id 0
            init[15] = 0
            s = init.copy()
            for _ in range(10):
                _quarter(s, 0, 4, 8, 12)
                _quarter(s, 1, 5, 9, 13)
                _quarter(s, 2, 6, 10, 14)
                _quarter(s, 3, 7, 11, 15)
                _quarter(s, 0, 5, 10, 15)
                _quarter(s, 1, 6, 11, 12)
                _quarter(s, 2, 7, 8, 13)
                _quarter(s, 3, 4, 9, 14)
            s += init
            out[:, blk * 64 : (blk + 1) * 64] = (
                np.ascontiguousarray(s.T, dtype="<u4").view(np.uint8).reshape(n, 64)
            )
    return out[:, :n_bytes]
