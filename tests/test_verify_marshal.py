"""What crosses the host/device boundary of a verify call, on the CPU.

The fused Pallas ladder takes one packed byte array and lays limbs and window
digits out inside the jit; the XLA and mesh ladders take the [B, 16] limbs and
[B, 64] digits they always took.  The numpy helpers the Pallas marshal was
made of before (limbs -> transposed radix 2**8, digits, padding) are kept here
as the reference the in-jit unpack is held to, bit for bit.  Nothing is timed.
"""

import random

import jax
import numpy as np
import pytest

from kaspa_tpu.crypto import eclib, secp
from kaspa_tpu.observability import trace
from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.ops import bigint as bi
from kaspa_tpu.ops.secp256k1 import ladder_pallas as lp
from kaspa_tpu.ops.secp256k1 import verify


# --- the reference: the host marshal of the Pallas lane before the packed call


def _to_radix8_T(limbs16):
    a = np.asarray(limbs16, dtype=np.int32)
    out = np.empty((lp.W8, a.shape[0]), dtype=np.int32)
    out[0::2] = (a & 0xFF).T
    out[1::2] = (a >> 8).T
    return out


def _full_digits(scalars):
    b = len(scalars)
    raw = b"".join([k if type(k) is bytes else int(k).to_bytes(32, "big") for k in scalars])
    arr = np.frombuffer(raw, dtype=np.uint8).reshape(b, 32)
    dig = np.empty((b, 64), np.uint8)
    dig[:, 0::2] = arr >> 4
    dig[:, 1::2] = arr & 0x0F
    return dig.astype(np.int32).T.copy()


def _reference_operands(batch, b, lanes):
    n = len(batch.ok)
    ok = np.zeros(b, dtype=bool)
    ok[:n] = batch.ok
    pad = [0] * (b - n)
    planes = [_to_radix8_T(verify._be32_to_limbs(c, b)) for c in (batch.px, batch.py, batch.rc)]
    planes += [_full_digits(batch.d1 + pad), _full_digits(batch.d2 + pad)]
    planes.append(np.broadcast_to(np.asarray(ok, dtype=np.int32), (8, b)).copy())
    return [np.pad(p, ((0, 0), (0, lanes - b))) for p in planes], ok


def _batch(n, seed):
    """n jobs as _Batch holds them: random field elements, the first scalar
    column a mix of ints and 32-byte strings (the schnorr s column's wire
    form), every seventh job a push_invalid row."""
    rng = random.Random(seed)
    batch = secp._Batch()
    for i in range(n):
        if i % 7 == 3:
            batch.push_invalid()
            continue
        s = rng.randrange(eclib.N)
        batch.push(
            rng.randrange(eclib.P), rng.randrange(eclib.P), rng.randrange(eclib.P),
            s.to_bytes(32, "big") if i % 2 else s, rng.randrange(eclib.N),
        )
    return batch


@pytest.fixture(scope="module")
def unpack():
    return jax.jit(lp.unpack_lanes)


SIZES = [1, 10, 255, 256, 257]


@pytest.mark.parametrize("n", SIZES)
def test_in_jit_unpack_equals_the_host_marshal_it_replaced(unpack, n):
    batch = _batch(n, seed=n)
    b = secp._bucket(n)
    lanes = lp.launched_lanes(b)
    want, ok = _reference_operands(batch, b, lanes)
    packed = lp.pack_lanes(batch.px, batch.py, batch.rc, batch.d1, batch.d2, ok, lanes)
    got = [np.asarray(a) for a in unpack(packed)]
    assert [a.shape for a in got] == [(32, lanes)] * 3 + [(64, lanes)] * 2 + [(8, lanes)]
    assert all(a.dtype == np.int32 for a in got)
    for name, g, w in zip(("px", "py", "rc", "k1", "k2", "valid"), got, want):
        assert np.array_equal(g, w), name
    # padding lanes and push_invalid rows read valid = 0, whatever else they hold
    valid = got[5][0]
    assert not valid[n:].any() and valid[:n].tolist() == [int(v) for v in batch.ok]


@pytest.mark.parametrize("n", SIZES)
def test_packed_lanes_hold_the_wire_bytes(n):
    batch = _batch(n, seed=100 + n)
    lanes = lp.launched_lanes(secp._bucket(n))
    packed = lp.pack_lanes(batch.px, batch.py, batch.rc, batch.d1, batch.d2, batch.ok, lanes)
    assert packed.dtype == np.uint8 and packed.shape == (lp.LANE_BYTES, lanes) and packed.nbytes == 161 * lanes
    as_bytes = lambda k: k if type(k) is bytes else k.to_bytes(32, "big")  # noqa: E731
    for lane in (0, n // 2, n - 1):
        fields = (batch.px[lane], batch.py[lane], batch.rc[lane], as_bytes(batch.d1[lane]), as_bytes(batch.d2[lane]))
        assert packed[:, lane].tobytes() == b"".join(fields) + bytes([batch.ok[lane]])
    assert not packed[:, n:].any()


def test_pack_lanes_of_no_jobs_is_all_padding():
    assert not lp.pack_lanes([], [], [], [], [], np.zeros(8, bool), 256).any()


def test_scalars_to_digits_bytes_match_ints():
    ks = [0, 1, eclib.N - 1, 0x1234567890ABCDEF]
    as_int = verify._scalars_to_digits(ks, 6)
    as_bytes = verify._scalars_to_digits([k.to_bytes(32, "big") for k in ks], 6)
    assert (as_int == as_bytes).all()


# --- which marshal a dispatch takes, and what it hands over ------------------


def _schnorr_items(n, seed=5):
    rng = random.Random(seed)
    items = []
    for i in range(n):
        sk = rng.randrange(1, eclib.N)
        msg = rng.randbytes(32)
        sig = eclib.schnorr_sign(msg, sk, b"\x07" * 32)
        if i == 1:
            sig = b"\xff" * 32 + sig[32:]  # r >= p: refused on the host, a zero row
        items.append((eclib.schnorr_pubkey(sk), msg, sig))
    return items


def _moved(before, name):
    a, b = REGISTRY.snapshot()["counters"].get(name, 0), before.get(name, 0)
    if isinstance(a, dict):
        return {k: v - (b or {}).get(k, 0) for k, v in a.items() if v - (b or {}).get(k, 0)}
    return a - b


def _inside(inner, outer):
    return outer["start_ns"] <= inner["start_ns"] and inner["end_ns"] <= outer["end_ns"]


@pytest.fixture
def warm_shapes(monkeypatch):
    """A stubbed kernel compiles nothing: keep what it marks warm out of the
    process's table (and of the warm manifest)."""
    warm = {(k, b, 1) for k in secp._PRETRACE_KERNELS for b in (8, 16)}
    monkeypatch.setattr(secp, "_seen_shapes", set(secp._seen_shapes) | warm)


def test_pallas_lane_hands_the_device_one_array(monkeypatch, warm_shapes):
    """The built call replaced by a stub that answers with the valid byte:
    everything else (prechecks, _Batch.run, _verify, verify_batch_pallas, the
    spans and counters) is the program's."""
    import jax.numpy as jnp

    handed = []

    def built_call(n_padded, ecdsa, interpret):
        def run(*args):
            handed.append((n_padded, ecdsa, args))
            return jnp.asarray(args[0][160].astype(np.int32))

        return run

    monkeypatch.setattr(verify, "_use_pallas", lambda: True)
    monkeypatch.setattr(lp, "_build_call", built_call)
    items = _schnorr_items(5)
    trace.set_capture(1 << 12)
    trace.drain()
    before = REGISTRY.snapshot()["counters"]
    try:
        mask = secp.schnorr_verify_batch(items)
        spans = trace.drain()
    finally:
        trace.set_capture(0)
    assert mask.tolist() == [True, False, True, True, True]  # the stub's answer: the host's prechecks
    (n_padded, ecdsa, args), = handed
    assert (n_padded, ecdsa) == (256, False) and len(args) == 1
    assert type(args[0]) is np.ndarray and args[0].dtype == np.uint8 and args[0].shape == (161, 256)
    assert args[0][:, 0].tobytes()[96:128] == items[0][2][32:] and not args[0][:, 1].any() and not args[0][:, 5:].any()
    assert _moved(before, "secp_device_uploads") == 1
    assert _moved(before, "secp_device_dispatches") == {"schnorr_pallas": 1}
    assert _moved(before, "secp_device_buckets") == {"8": 1}
    assert _moved(before, "secp_device_lanes") == 256 and _moved(before, "secp_device_jobs") == 5
    by = lambda name: [s for s in spans if s["name"] == name]  # noqa: E731
    names = ("device_dispatch", "host_marshal", "device_call", "readback")
    (dispatch,), (marshal,), (call,), (read,) = (by("secp." + n) for n in names)
    assert all(_inside(s, dispatch) and s["thread"] == dispatch["thread"] for s in (marshal, call, read))
    assert marshal["end_ns"] <= call["start_ns"] and call["end_ns"] <= read["start_ns"]
    assert marshal["attrs"] == {"kernel": "schnorr_pallas", "batch": 8, "lanes": 256}
    assert call["attrs"] == {"kernel": "schnorr_pallas", "lanes": 256, "bytes": 161 * 256}


def _expected_xla_args(batch, b):
    """The six arrays _Batch.run built for the XLA ladder before the columns
    were handed over, and the limbs once more from the integers themselves."""
    n = len(batch.ok)
    ok = np.zeros(b, dtype=bool)
    ok[:n] = batch.ok
    pad = [0] * (b - n)
    want = [verify._be32_to_limbs(c, b) for c in (batch.px, batch.py, batch.rc)]
    want += [verify._scalars_to_digits(batch.d1 + pad, b), verify._scalars_to_digits(batch.d2 + pad, b), ok]
    for col, limbs in zip((batch.px, batch.py, batch.rc), want):
        for i in (0, n - 1):
            assert limbs[i].tolist() == bi.int_to_limbs(int.from_bytes(col[i], "big"), 16).tolist()
        assert not limbs[n:].any()
    return want


@pytest.mark.parametrize("kind", ["schnorr", "ecdsa"])
@pytest.mark.parametrize("n", [3, 8, 11])
def test_xla_lane_receives_the_arrays_it_always_did(monkeypatch, warm_shapes, kind, n):
    import jax.numpy as jnp

    received = []

    def kernel(*args):
        received.append(args)
        return jnp.asarray(args[5])

    monkeypatch.setattr(verify, f"{kind}_verify_kernel", kernel)
    batch = _batch(n, seed=200 + n)
    b = secp._bucket(n)
    before = REGISTRY.snapshot()["counters"]
    mask = batch.run(getattr(secp, f"{kind}_verify"))
    assert mask.tolist() == batch.ok
    (got,) = received
    want = _expected_xla_args(batch, b)
    assert len(got) == 6
    for g, w in zip(got, want):
        assert type(g) is np.ndarray and g.dtype == w.dtype and np.array_equal(g, w)
    assert _moved(before, "secp_device_uploads") == 6
    assert _moved(before, "secp_device_dispatches") == {kind: 1} and _moved(before, "secp_device_lanes") == b


def test_mesh_lane_receives_the_arrays_it_always_did(monkeypatch, warm_shapes):
    from kaspa_tpu.ops import mesh

    received = []
    monkeypatch.setattr(mesh, "active_size", lambda: 2)
    monkeypatch.setattr(mesh, "dispatch_verify", lambda kind, *args: received.append((kind, args)) or np.asarray(args[5]))
    monkeypatch.setattr(secp, "_seen_shapes", {("schnorr_verify", 16, 2)})
    batch = _batch(11, seed=7)
    before = REGISTRY.snapshot()["counters"]
    assert batch.run(secp.schnorr_verify).tolist() == batch.ok
    ((kind, got),) = received
    assert kind == "schnorr" and all(np.array_equal(g, w) for g, w in zip(got, _expected_xla_args(batch, 16)))
    assert _moved(before, "secp_device_uploads") == 6 and _moved(before, "secp_device_dispatches") == {"schnorr_mesh": 1}


# --- which back end a dispatch takes -----------------------------------------


@pytest.mark.parametrize("kind", ["schnorr", "ecdsa"])
@pytest.mark.parametrize(
    "backend,mesh_n,lane,suffix",
    [("cpu", 1, "xla", ""), ("cpu", 4, "mesh", "_mesh"), ("tpu", 1, "pallas", "_pallas")],
    ids=["cpu-mesh1", "mesh4", "tpu-mesh1"],
)
def test_verify_selects_its_back_end_from_platform_and_mesh(monkeypatch, kind, backend, mesh_n, lane, suffix):
    """`_verify` picks the Pallas ladder (a TPU at mesh 1), the shard_map XLA
    ladder (mesh > 1) or the XLA ladder (anything else) from
    `jax.default_backend()` and `mesh.active_size()`, read here from
    `secp_device_dispatches{kernel}`.  All three back ends are stubbed to
    answer with the valid flags, so nothing compiles: the kernels have their
    own tests."""
    import jax.numpy as jnp

    from kaspa_tpu.ops import mesh

    taken = []
    monkeypatch.delenv("KASPA_TPU_NO_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(mesh, "active_size", lambda: mesh_n)
    monkeypatch.setattr(
        lp, "verify_batch_pallas",
        lambda px, py, rc, k1, k2, valid_in, *, ecdsa: taken.append(("pallas", ecdsa)) or (np.asarray(valid_in), 1),
    )
    monkeypatch.setattr(
        mesh, "dispatch_verify", lambda k, *args: taken.append(("mesh", k == "ecdsa")) or np.asarray(args[5])
    )
    monkeypatch.setattr(
        verify, f"{kind}_verify_kernel", lambda *args: taken.append(("xla", kind == "ecdsa")) or jnp.asarray(args[5])
    )
    monkeypatch.setattr(secp, "_seen_shapes", {(f"{kind}_verify", 8, mesh_n)})
    batch = _batch(5, seed=31)
    before = REGISTRY.snapshot()["counters"]
    assert batch.run(getattr(secp, f"{kind}_verify")).tolist() == batch.ok
    assert taken == [(lane, kind == "ecdsa")]
    assert _moved(before, "secp_device_dispatches") == {kind + suffix: 1}
    assert _moved(before, "secp_device_uploads") == (1 if lane == "pallas" else 6)
