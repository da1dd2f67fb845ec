"""Host-side batched signature verification front-end.

This is the framework's "communication backend" between the validation
pipeline and the TPU: it marshals (pubkey, msg, sig) triples into fixed
shape device arrays, dispatches the jitted kernels, and hands back a
validity bitmask the validator consumes unchanged — mirroring the role of
libsecp256k1 calls inside the reference's script engine
(crypto/txscript/src/lib.rs:885-935) but batched across a whole block/DAG
slice instead of per-input.

Host preparation is per batch wherever the arithmetic is big: the keys of
a batch are lifted to curve points by one native call (``_lift_keys``:
native/hostcrypto's ``secp_lift_x_batch``, entered with the GIL held, or
``eclib.lift_x`` per key where the library cannot be built) and ECDSA's ``s`` are inverted together
(``_batch_inverse``).  Native is the lift alone; the tagged hash is
hashlib's, the range checks and the scalar products are Python ints, and
eclib stays the oracle of every verdict (the host lane, the tests).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import threading
from dataclasses import dataclass, field

import numpy as np

from kaspa_tpu.crypto import eclib, hostcrypto
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import PERCENT_BUCKETS, REGISTRY, SIZE_BUCKETS
from kaspa_tpu.ops.secp256k1.verify import ecdsa_verify, schnorr_verify
from kaspa_tpu.resilience import supervisor
from kaspa_tpu.resilience.breaker import HUNG, device_breaker
from kaspa_tpu.resilience.faults import FAULTS

# batch shape telemetry: occupancy here is jobs over the `_bucket` width the
# batch is padded to on the host — not the width of the program the device
# launches (the Pallas ladder pads the bucket on to a multiple of 256:
# `secp_device_lanes` in ops/secp256k1/verify.py counts that); dispatched
# shapes proxy XLA recompiles — every new bucket is a fresh jit trace
_BATCH_SIZE = REGISTRY.histogram("secp_batch_size", SIZE_BUCKETS, help="logical verify jobs per device batch")
_OCCUPANCY = REGISTRY.histogram(
    "secp_batch_occupancy_pct", PERCENT_BUCKETS,
    help="logical batch size / _bucket width * 100 (the launched width is secp_device_lanes)",
)
_PADDED_LANES = REGISTRY.counter(
    "secp_padded_lanes", help="lanes added by pad-to-bucket on the host (_bucket width - jobs; not the launched width)"
)
_NEW_SHAPES = REGISTRY.counter_family(
    "secp_dispatch_shapes", "kernel", help="distinct padded bucket sizes dispatched (jit recompile proxy)"
)
_COLD_SPLITS = REGISTRY.counter_family(
    "secp_cold_bucket_splits", "kernel",
    help="batches split into warm-bucket sub-dispatches to dodge a cold jit compile",
)
# (kernel name, padded bucket, mesh width) shapes compiled in this process.
# The mesh width is part of the key: mesh > 1 dispatches a different
# (shard_map-wrapped) program per width, so a bucket that is warm at mesh 1
# still pays a full compile — and needs the compile-tier watchdog deadline —
# the first time it is dispatched under another width.
_seen_shapes: set = set()


def _shape_key(kernel_name: str, bucket: int) -> tuple:
    from kaspa_tpu.ops import mesh

    return (kernel_name, bucket, mesh.active_size())


def _largest_warm_below(kernel_name: str, bucket: int) -> int | None:
    width = _shape_key(kernel_name, bucket)[-1]
    return max((bk for k, bk, w in _seen_shapes if k == kernel_name and w == width and bk < bucket), default=None)

# thread-local escape hatch: pretrace_bucket() deliberately compiles a
# cold bucket, so it must bypass the warm-bucket split
_force_tls = threading.local()


def _cold_split_enabled() -> bool:
    """Warm-bucket splitting: a batch whose padded bucket was never
    compiled is split into sub-dispatches at the largest already-warm
    bucket instead of paying the compile wall inline.  The verify-kernel
    jit cost grows superlinearly with batch width on the XLA formulation
    (~3 min for bucket 16 on CPU), so crossing into a cold bucket
    mid-pipeline can stall the commit lock for minutes.
    `KASPA_TPU_COLD_BUCKET_SPLIT=0` restores pad-up-and-compile — a run
    that deliberately measures one bucket shape needs that."""
    return os.environ.get("KASPA_TPU_COLD_BUCKET_SPLIT", "1") not in ("0", "off", "false")

# degraded-lane occupancy: how much of the verify workload is riding the
# host oracle instead of the device (breaker open, or a dispatch died) —
# the quantity the hostile-load sustain run reports
_DEGRADED_DISPATCHES = REGISTRY.counter(
    "secp_degraded_dispatches", help="batches routed to the host degraded lane (breaker open / dispatch failed)"
)
_DEGRADED_JOBS = REGISTRY.counter("secp_degraded_jobs", help="verify jobs executed on the host degraded lane")
# the other side of the same ledger: every job handed to a guarded dispatch
# ends in exactly one of secp_device_jobs / secp_degraded_jobs
_DEVICE_JOBS = REGISTRY.counter("secp_device_jobs", help="verify jobs answered by the device lane")
_DEVICE_ECDSA_JOBS = REGISTRY.counter(
    "secp_device_ecdsa_jobs", help="of secp_device_jobs, those the ECDSA ladder answered (secp_device_jobs stays the total)"
)

# which way the builders' keys were lifted: the second equals the first
# wherever native/hostcrypto built; under it, eclib's pow() answered the rest
_HOST_LIFT_JOBS = REGISTRY.counter(
    "secp_host_lift_jobs", help="public keys lifted to curve points by the verify batch builders, whichever way"
)
_NATIVE_LIFT_JOBS = REGISTRY.counter(
    "secp_native_lift_jobs", help="of secp_host_lift_jobs, those the native batched lift_x call answered"
)

_CHALLENGE_MID = hashlib.sha256(
    hashlib.sha256(b"BIP0340/challenge").digest() * 2
)  # pre-tagged sha256 state


def _bucket(n: int) -> int:
    """Pad batch sizes to powers of two (min 8) to bound jit recompiles."""
    b = 8
    while b < n:
        b <<= 1
    return b


def schnorr_challenge(r32: bytes, px32: bytes, msg32: bytes) -> int:
    h = _CHALLENGE_MID.copy()
    h.update(r32 + px32 + msg32)
    return int.from_bytes(h.digest(), "big") % eclib.N


_ZERO32 = b"\x00" * 32
# equal-length big-endian strings order as the numbers they spell
_P_BE = eclib.P.to_bytes(32, "big")
_N_BE = eclib.N.to_bytes(32, "big")


@dataclass
class _Batch:
    """Collects verification jobs as byte columns and scalars, one entry a
    job, and hands them to the kernel entry at a bucket width.

    What layout the device wants is the entry's business
    (ops/secp256k1/verify.py marshals for the lane it takes: one packed
    byte array for the fused ladder, limb / window-digit arrays for the
    XLA and mesh ladders), numpy-vectorised there without per-item loops.
    """

    px: list = field(default_factory=list)  # 32B BE x-coordinates
    py: list = field(default_factory=list)
    rc: list = field(default_factory=list)  # canonical target (r or r mod n)
    d1: list = field(default_factory=list)  # s / u1 scalars (python ints mod n)
    d2: list = field(default_factory=list)  # e / u2 scalars (python ints mod n)
    ok: list = field(default_factory=list)

    def push_invalid(self):
        self.px.append(_ZERO32)
        self.py.append(_ZERO32)
        self.rc.append(_ZERO32)
        self.d1.append(0)
        self.d2.append(0)
        self.ok.append(False)

    def push(self, px: int, py: int, rc: int, s1: int, s2: int):
        self.push_wire(px.to_bytes(32, "big"), py.to_bytes(32, "big"), rc.to_bytes(32, "big"), s1, s2)

    def push_wire(self, px: bytes, py: bytes, rc: bytes, s1, s2):
        """push() for a job whose coordinates are already the 32 big-endian
        bytes the columns hold (a key's x and a signature's r as the wire has
        them, y as the batched lift returns it)."""
        self.px.append(px)
        self.py.append(py)
        self.rc.append(rc)
        self.d1.append(s1)
        self.d2.append(s2)
        self.ok.append(True)

    def run(self, kernel):
        n = len(self.ok)
        if n == 0:
            return np.zeros(0, dtype=bool)
        b = _bucket(n)
        shape_key = _shape_key(kernel.__name__, b)
        new_shape = shape_key not in _seen_shapes
        if new_shape and _cold_split_enabled() and not getattr(_force_tls, "on", False):
            warm = _largest_warm_below(kernel.__name__, b)
            if warm is not None:
                _COLD_SPLITS.inc(kernel.__name__)
                return self._run_split(kernel, warm)
        _BATCH_SIZE.observe(n)
        _OCCUPANCY.observe(100.0 * n / b)
        _PADDED_LANES.inc(b - n)
        if new_shape:
            _seen_shapes.add(shape_key)
            _NEW_SHAPES.inc(kernel.__name__)
        # the flags carry the bucket width; the columns stay one entry a job
        ok = np.zeros(b, dtype=bool)
        ok[:n] = self.ok
        args = (self.px, self.py, self.rc, self.d1, self.d2, ok)
        if new_shape:
            # first dispatch of a (kernel, bucket) shape pays the XLA
            # trace+compile; surfacing it as a span is what lets a wedge
            # dossier / flight trace say *where* a probe stalled
            try:
                with trace.span("secp.jit_compile", kernel=kernel.__name__, bucket=b):
                    FAULTS.fire("device.jit_compile")
                    mask = kernel(*args)
            except BaseException:
                # a compile that failed (or was abandoned by the watchdog)
                # must not leave the shape marked warm — the next dispatch
                # would skip the split and pay a surprise compile wall
                _seen_shapes.discard(shape_key)
                raise
            supervisor.note_shape(kernel.__name__, b)
        else:
            mask = kernel(*args)
        return np.asarray(mask)[:n]

    def _run_split(self, kernel, warm: int) -> np.ndarray:
        """Dispatch this batch as sub-batches of the given warm bucket
        size — several known-compiled round trips instead of one cold
        compile.  Sub-batches recurse through run(): a full slice reuses
        the warm shape, the tail pads into a smaller (also warm) bucket."""
        n = len(self.ok)
        out = np.empty(n, dtype=bool)
        for off in range(0, n, warm):
            end = min(off + warm, n)
            sub = _Batch(
                px=self.px[off:end],
                py=self.py[off:end],
                rc=self.rc[off:end],
                d1=self.d1[off:end],
                d2=self.d2[off:end],
                ok=self.ok[off:end],
            )
            out[off:end] = sub.run(kernel)
        return out


def _dispatch_tier(kernel, n: int) -> str:
    """Watchdog tier: a never-seen (kernel, bucket) shape legitimately
    pays an XLA compile, so it gets the long deadline."""
    return "dispatch" if _shape_key(kernel.__name__, _bucket(n)) in _seen_shapes else "compile"


def _run_guarded(batch: _Batch, kernel, items: list, host_verify) -> np.ndarray:
    """Dispatch through the watchdog and the device circuit breaker.

    CLOSED/probing: the device runs the batch on a supervised worker
    thread; a dispatch exception (wedged chip, XLA error, injected fault)
    counts toward a trip, while a watchdog deadline trips immediately
    with cause ``hung`` and the batch — never lost, never double-resolved
    — requeues below.  OPEN: the host degraded lane verifies each raw
    triple with the eclib oracle — same acceptance decisions, host
    throughput — until a canary probe succeeds and the breaker re-arms.
    """
    n = len(batch.ok)
    if n == 0:
        return np.zeros(0, dtype=bool)
    br = device_breaker()
    if br.allow():
        try:
            mask = supervisor.run_supervised(
                lambda: batch.run(kernel),
                tier=_dispatch_tier(kernel, n),
                kernel=kernel.__name__,
                jobs=n,
            )
        except supervisor.DeviceHangError:
            br.record_failure(cause=HUNG)
            supervisor.note_requeue(n)
        except Exception:  # noqa: BLE001 - device boundary: any failure trips
            br.record_failure()
        else:
            br.record_success()
            _DEVICE_JOBS.inc(n)
            if kernel.__name__ == "ecdsa_verify":
                _DEVICE_ECDSA_JOBS.inc(n)
            return mask
    return _host_lane(batch, kernel.__name__, items, host_verify)


def _host_lane(batch: _Batch, kernel_name: str, items: list, host_verify) -> np.ndarray:
    """The bit-identical host degraded lane: same prechecks as the device
    path (already folded into ``batch.ok``), per-item eclib oracle verify
    for the survivors.  Shared by the breaker-open path above and the
    fabric balancer's last failover tier."""
    n = len(batch.ok)
    _DEGRADED_DISPATCHES.inc()
    _DEGRADED_JOBS.inc(n)
    with trace.span("secp.degraded_dispatch", kernel=kernel_name, jobs=n):
        mask = np.zeros(n, dtype=bool)
        for i, (pub, msg, sig) in enumerate(items):
            if batch.ok[i]:  # host-precheck failures stay False
                mask[i] = bool(host_verify(pub, msg, sig))
    return mask


# keys a native call: the call keeps the GIL (crypto/hostcrypto.py), so this
# bounds the hold at ≈ 3 ms, under the interpreter's 5 ms switch interval
_LIFT_CHUNK = 512


def _lift_keys(xs: list, odd: bytes | None = None) -> list:
    """The y of each x (32 big-endian bytes each), as 32 big-endian bytes, or
    None where x >= p or no curve point has that x: the even root (BIP340
    lift_x), or the odd one where ``odd[i]`` (a 0x03 compressed key).

    One native call for the whole batch (native/hostcrypto
    ``secp_lift_x_batch``; one per ``_LIFT_CHUNK`` keys past that) where the
    library loaded; ``eclib.lift_x``, a modular exponentiation in the
    interpreter per key, where it did not.  Same answer either way: eclib is the oracle
    tests/test_secp_native_lift.py holds the native entry to.
    """
    n = len(xs)
    if n == 0:
        return []
    _HOST_LIFT_JOBS.inc(n)
    lib = hostcrypto.lib()
    if lib is None:
        ys = []
        for i, x in enumerate(xs):
            point = eclib.lift_x(int.from_bytes(x, "big"))
            if point is None:
                ys.append(None)
            else:
                y = eclib.P - point[1] if odd is not None and odd[i] else point[1]
                ys.append(y.to_bytes(32, "big"))
        return ys
    joined = b"".join(xs)
    if len(joined) != 32 * n or (odd is not None and len(odd) != n):
        raise ValueError("lift_keys: every x is 32 bytes, one parity flag a key")
    out = ctypes.create_string_buffer(32 * _LIFT_CHUNK)
    flags = ctypes.create_string_buffer(_LIFT_CHUNK)
    ys = []
    for off in range(0, n, _LIFT_CHUNK):
        m = min(_LIFT_CHUNK, n - off)
        lib.secp_lift_x_batch(
            joined[32 * off : 32 * (off + m)], m, None if odd is None else odd[off : off + m], out, flags
        )
        raw, ok = out.raw, flags.raw
        ys += [raw[32 * i : 32 * i + 32] if ok[i] else None for i in range(m)]
    _NATIVE_LIFT_JOBS.inc(n)
    return ys


def _build_schnorr_batch(items: list) -> _Batch:
    """Two passes: the encoding and range checks pick the jobs whose key is
    worth lifting, one ``_lift_keys`` call lifts them all, then challenge
    and columns in item order."""
    # BIP340 allows arbitrary-length messages (matching eclib oracle);
    # kaspa consensus always passes 32-byte sighash digests.
    live = [
        i
        for i, (pub, _msg, sig) in enumerate(items)
        if len(pub) == 32 and len(sig) == 64 and sig[:32] < _P_BE and sig[32:] < _N_BE
    ]
    ys = dict(zip(live, _lift_keys([bytes(items[i][0]) for i in live])))
    batch = _Batch()
    for i, (pub, msg, sig) in enumerate(items):
        y = ys.get(i)
        if y is None:
            batch.push_invalid()
            continue
        e = schnorr_challenge(sig[:32], pub, msg)
        # s rides as its canonical 32-byte wire encoding (range-checked
        # above): _scalars_to_digits takes it with zero per-item int work
        batch.push_wire(bytes(pub), y, sig[:32], sig[32:], e)
    return batch


def schnorr_verify_batch(items) -> np.ndarray:
    """items: iterable of (pubkey32, msg32, sig64) -> bool mask.

    Encoding/range checks and lift_x run on host (failures short-circuit to
    False without occupying useful device lanes beyond padding); the lift is
    one native call for the batch (``_lift_keys``).
    """
    items = list(items)
    with trace.span("secp.host_prepare", kernel="schnorr_verify", jobs=len(items)):
        batch = _build_schnorr_batch(items)
    return _run_guarded(batch, schnorr_verify, items, eclib.schnorr_verify)


def _batch_inverse(values: list, m: int) -> list:
    """Every value's inverse mod the prime m for one pow(): Montgomery's
    trick (running products up, the one inverse peeled back down).  Every
    value is in [1, m)."""
    prefix, acc = [], 1
    for v in values:
        prefix.append(acc)
        acc = acc * v % m
    inv = pow(acc, -1, m)
    out = [0] * len(values)
    for i in range(len(values) - 1, -1, -1):
        out[i] = inv * prefix[i] % m
        inv = inv * values[i] % m
    return out


def _build_ecdsa_batch(items: list) -> _Batch:
    """Two passes, as _build_schnorr_batch: checks, one ``_lift_keys`` call
    (the key's prefix says which root), one modular inversion for every s
    of the batch, then u1 / u2 and columns in item order."""
    half_n = eclib.N // 2
    live, rs, ss = [], [], []
    for i, (pub, msg, sig) in enumerate(items):
        if len(sig) != 64 or len(msg) != 32 or len(pub) != 33 or pub[0] not in (2, 3):
            continue
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        if 1 <= r < eclib.N and 1 <= s <= half_n:  # high-S is rejected (eclib.ecdsa_verify)
            live.append(i)
            rs.append(r)
            ss.append(s)
    ys = _lift_keys([bytes(items[i][0][1:]) for i in live], bytes(items[i][0][0] & 1 for i in live))
    rows = {i: (y, r, si) for i, y, r, si in zip(live, ys, rs, _batch_inverse(ss, eclib.N)) if y is not None}
    batch = _Batch()
    for i, (pub, msg, sig) in enumerate(items):
        if i not in rows:
            batch.push_invalid()
            continue
        y, r, si = rows[i]
        z = int.from_bytes(msg, "big") % eclib.N
        # r < n < p: the signature's first half is the target as the column holds it
        batch.push_wire(bytes(pub[1:]), y, sig[:32], z * si % eclib.N, r * si % eclib.N)
    return batch


def ecdsa_verify_batch(items) -> np.ndarray:
    """items: iterable of (pubkey33, msg32, sig64_compact) -> bool mask."""
    items = list(items)
    with trace.span("secp.host_prepare", kernel="ecdsa_verify", jobs=len(items)):
        batch = _build_ecdsa_batch(items)
    return _run_guarded(batch, ecdsa_verify, items, eclib.ecdsa_verify)


def verify_batch(kind: str, items) -> np.ndarray:
    """Kind-dispatching batched verify ("schnorr" | "ecdsa") — the entry
    the verify fabric's slice workers, the coalescing dispatcher, and the
    legacy synchronous txscript lane all route through."""
    if kind == "schnorr":
        return schnorr_verify_batch(items)
    return ecdsa_verify_batch(items)


def host_verify_batch(kind: str, items) -> np.ndarray:
    """Host-only verify for one super-batch: the same precheck + eclib
    oracle lane the breaker-open path runs, callable directly.  This is
    the fabric balancer's final failover tier — every slice dead or hung
    still yields bit-identical acceptance decisions, just at host
    throughput, and it can never touch a (possibly wedged) device."""
    items = list(items)
    if kind == "schnorr":
        return _host_lane(_build_schnorr_batch(items), "schnorr_verify", items, eclib.schnorr_verify)
    return _host_lane(_build_ecdsa_batch(items), "ecdsa_verify", items, eclib.ecdsa_verify)


# --- supervision hooks ----------------------------------------------------

_CANARY_SECKEY = int.from_bytes(hashlib.sha256(b"kaspa-tpu canary").digest(), "big") % eclib.N or 1


def _canary_items(count: int = 2) -> list:
    """Tiny known-answer workload (fixed key, distinct messages): every
    signature is valid, so a canary dispatch must return an all-True mask."""
    pub = eclib.schnorr_pubkey(_CANARY_SECKEY)
    out = []
    for i in range(count):
        msg = hashlib.sha256(b"canary-msg-%d" % i).digest()
        out.append((pub, msg, eclib.schnorr_sign(msg, _CANARY_SECKEY)))
    return out


def canary_probe() -> bool:
    """One supervised device dispatch of the known-answer batch — the
    prober's HALF_OPEN probe.  Bypasses the breaker gate (the prober holds
    the probe slot) and runs with fault injection suppressed so drills
    keep their requeued==injected accounting.  True iff the device
    answered correctly within the watchdog deadline."""
    from kaspa_tpu.resilience import faults as faults_mod

    items = _canary_items()
    batch = _build_schnorr_batch(items)

    def _dispatch():
        with faults_mod.suppress():
            return batch.run(schnorr_verify)

    mask = supervisor.run_supervised(
        _dispatch,
        tier=_dispatch_tier(schnorr_verify, len(items)),
        kernel="schnorr_verify",
        jobs=len(items),
    )
    return bool(np.asarray(mask).all())


_PRETRACE_KERNELS = {"schnorr_verify": schnorr_verify, "ecdsa_verify": ecdsa_verify}


def pretrace_bucket(kernel_name: str, bucket: int) -> str:
    """Compile one (kernel, bucket) shape ahead of traffic (warm-manifest
    restart path).  Dispatches an all-invalid batch of exactly ``bucket``
    jobs with the warm-split bypassed so the target shape itself compiles;
    runs under the watchdog's compile tier.  Returns "warm" (already
    compiled this process), "traced", or "error:...".
    """
    if kernel_name == "muhash_tree":
        from kaspa_tpu.ops import muhash_ops

        def _dispatch():
            return muhash_ops.pretrace_bucket(bucket)

        try:
            return supervisor.run_supervised(_dispatch, tier="compile", kernel=kernel_name, jobs=bucket)
        except Exception as e:  # noqa: BLE001 - pretrace is best-effort
            return f"error:{type(e).__name__}"
    kernel = _PRETRACE_KERNELS.get(kernel_name)
    if kernel is None or bucket < 8:
        return f"error:unknown {kernel_name}/{bucket}"
    if _shape_key(kernel_name, bucket) in _seen_shapes:
        return "warm"
    batch = _Batch()
    for _ in range(bucket):
        batch.push_invalid()

    def _dispatch():
        from kaspa_tpu.resilience import faults as faults_mod

        _force_tls.on = True
        try:
            with faults_mod.suppress():
                return batch.run(kernel)
        finally:
            _force_tls.on = False

    try:
        supervisor.run_supervised(_dispatch, tier="compile", kernel=kernel_name, jobs=bucket)
    except Exception as e:  # noqa: BLE001 - pretrace is best-effort
        return f"error:{type(e).__name__}"
    return "traced"
