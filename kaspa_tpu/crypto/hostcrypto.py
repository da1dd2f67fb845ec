"""The one loader of ``native/hostcrypto``: the host's native hot loops.

Two entries, each with a Python path its caller keeps for a checkout that
cannot build the library (no ``g++``): ``chacha20_keystream_batch``
(crypto/chacha.py; numpy rounds otherwise) and ``secp_lift_x_batch``
(crypto/secp.py's batch builders; ``eclib.lift_x`` otherwise).  What the
code can observe is whether the library loaded — no flag, no environment
variable.

The keystream call releases the GIL (it expands kilobytes a key).  The lift
is entered with the GIL held: it is ≈ 5 µs a key and its callers chunk it,
so a call is shorter than the interpreter's switch interval, and giving the
GIL up for that long costs more than it frees — under catch-up load the
thread waited ≈ 0.4 ms a batch to get the interpreter back (PERF.md §6,
PR 30).
"""

from __future__ import annotations

import ctypes
import os

from kaspa_tpu.utils import nativebuild
from kaspa_tpu.utils.sync import ranked_lock

_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "native", "hostcrypto", "hostcrypto.cc")
_LOCK = ranked_lock("chacha.build")
_LIB = None
_LIB_FAILED = False


def lib():
    """Build (once per source digest) and load the library; None if it
    cannot be built or loaded here."""
    global _LIB, _LIB_FAILED
    if _LIB is not None or _LIB_FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        try:
            path = nativebuild.build(_SRC, "hostcrypto", opt="-O3")
            loaded = ctypes.CDLL(path)
            # the same file through the handle whose calls keep the GIL
            loaded.secp_lift_x_batch = ctypes.PyDLL(path).secp_lift_x_batch
            loaded.chacha20_keystream_batch.argtypes = [
                ctypes.c_char_p,
                ctypes.c_uint64,
                ctypes.c_void_p,
                ctypes.c_uint64,
            ]
            loaded.chacha20_keystream_batch.restype = None
            loaded.secp_lift_x_batch.argtypes = [
                ctypes.c_char_p,  # xs: n x 32 B big-endian
                ctypes.c_uint64,
                ctypes.c_char_p,  # odd: n flags, or None for the even root everywhere
                ctypes.c_char_p,  # ys out: n x 32 B
                ctypes.c_char_p,  # ok out: n
            ]
            loaded.secp_lift_x_batch.restype = None
            _LIB = loaded
        except Exception:  # noqa: BLE001 - no toolchain, failed compile, unloadable file: the Python paths serve
            _LIB_FAILED = True
    return _LIB
