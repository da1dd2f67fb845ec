"""The native batched ``lift_x`` (native/hostcrypto ``secp_lift_x_batch``)
against the eclib oracle, and the two verify batch builders with and without
the library: the columns ``_Batch`` hands to the kernels are the same bytes
either way.  CPU only; nothing here is timed."""

import hashlib
import random
import sys
import threading

import numpy as np
import pytest

from kaspa_tpu.crypto import chacha, eclib, hostcrypto, secp
from kaspa_tpu.observability.core import REGISTRY

P, N = eclib.P, eclib.N


@pytest.fixture
def native():
    """The loaded library; a checkout that cannot build it (no g++) skips the
    tests of the native entry alone and still runs those of the builders."""
    lib = hostcrypto.lib()
    if lib is None:
        pytest.skip("native/hostcrypto did not build here")
    return lib


def _be(x: int) -> bytes:
    return x.to_bytes(32, "big")


def _lift_counters():
    c = REGISTRY.snapshot()["counters"]
    return c.get("secp_host_lift_jobs", 0), c.get("secp_native_lift_jobs", 0)


def _non_residues(rng, count):
    out = []
    while len(out) < count:
        x = rng.randrange(P)
        if eclib.lift_x(x) is None:
            out.append(x)
    return out


def _xs(seed=30):
    """Seeded random x (about half are no point's x), the edges of the field,
    values at and past p, and x known to be non-residues."""
    rng = random.Random(seed)
    xs = [rng.randrange(P) for _ in range(300)] + [0, 1, 2, 3, P - 1, P - 2, eclib.GX] + _non_residues(rng, 8)
    return [_be(x) for x in xs] + [_be(P), _be(P + 1), b"\xff" * 32]


def _eclib_y(x32: bytes, prefix: int):
    pt = eclib.parse_compressed(bytes([prefix]) + x32)
    return None if pt is None else _be(pt[1])


# --- the native entry against the oracle -------------------------------------


def test_native_lift_x_matches_eclib_lift_x(native):
    xs = _xs()
    got = secp._lift_keys(xs)
    want = []
    for x in xs:
        pt = eclib.lift_x(int.from_bytes(x, "big"))
        want.append(None if pt is None else _be(pt[1]))
    assert got == want
    assert sum(y is None for y in got) > 100 and sum(y is not None for y in got) > 100
    assert got[-3:] == [None, None, None]  # x >= p
    assert all(y is None or y[-1] % 2 == 0 for y in got)  # the even root


@pytest.mark.parametrize("prefix", [2, 3])
def test_native_lift_matches_parse_compressed(native, prefix):
    xs = _xs(31)
    got = secp._lift_keys(xs, bytes([prefix & 1]) * len(xs))
    assert got == [_eclib_y(x, prefix) for x in xs]
    assert all(y is None or y[-1] % 2 == prefix & 1 for y in got)


def test_native_lift_takes_a_parity_a_key_across_its_chunks(native):
    xs = _xs(32) * 4  # past two chunk boundaries: a chunk is one native call
    assert len(xs) > 2 * secp._LIFT_CHUNK
    odd = bytes(random.Random(33).randrange(2) for _ in xs)
    assert secp._lift_keys(xs, odd) == [_eclib_y(x, 2 + o) for x, o in zip(xs, odd)]


def test_the_lift_keeps_the_gil_and_the_keystream_gives_it_up(native):
    """A call of ≈ 5 µs a key is shorter than the interpreter's switch
    interval; giving the GIL up for it costs a wait to get it back."""
    import ctypes

    assert native.secp_lift_x_batch._flags_ & ctypes._FUNCFLAG_PYTHONAPI
    assert not native.chacha20_keystream_batch._flags_ & ctypes._FUNCFLAG_PYTHONAPI


def test_native_lift_refuses_columns_of_the_wrong_size(native):
    with pytest.raises(ValueError):
        secp._lift_keys([b"\x01" * 31])
    with pytest.raises(ValueError):
        secp._lift_keys([_be(1), _be(2)], b"\x00")


def test_ecdsa_builder_refuses_a_bad_prefix_like_parse_compressed():
    key = 77
    msg = hashlib.sha256(b"prefix").digest()
    sig = eclib.ecdsa_sign(msg, key, 1234567)
    pub = eclib.ecdsa_pubkey(key)
    for bad in (bytes([4]) + pub[1:], bytes([0]) + pub[1:], pub[1:], pub + b"\x00"):
        assert eclib.parse_compressed(bad) is None
        assert secp._build_ecdsa_batch([(bad, msg, sig)]).ok == [False]
    assert secp._build_ecdsa_batch([(pub, msg, sig)]).ok == [True]
    # the other root of the same x is another key: on the curve, so it is lifted, to the other y
    other = bytes([pub[0] ^ 1]) + pub[1:]
    b_pub, b_other = secp._build_ecdsa_batch([(pub, msg, sig)]), secp._build_ecdsa_batch([(other, msg, sig)])
    assert b_other.ok == [True] and b_other.px == b_pub.px
    assert int.from_bytes(b_other.py[0], "big") + int.from_bytes(b_pub.py[0], "big") == P


def test_batch_inverse_is_the_modular_inverse_of_each():
    rng = random.Random(34)
    values = [1, N - 1, 2] + [rng.randrange(1, N) for _ in range(50)]
    assert secp._batch_inverse(values, N) == [pow(v, -1, N) for v in values]
    assert secp._batch_inverse([], N) == []


# --- the builders, with the library and without -------------------------------

_SECKEYS = [int.from_bytes(hashlib.sha256(b"native-lift-key-%d" % i).digest(), "big") % N or 1 for i in range(6)]


def _schnorr_items(n, seed):
    rng = random.Random(seed)
    items = []
    for i in range(n):
        sk = _SECKEYS[i % len(_SECKEYS)]
        msg = rng.randbytes(32)
        items.append((eclib.schnorr_pubkey(sk), msg, eclib.schnorr_sign(msg, sk)))
    return items


def _ecdsa_items(n, seed):
    rng = random.Random(seed)
    items = []
    for i in range(n):
        sk = _SECKEYS[i % len(_SECKEYS)]
        msg = rng.randbytes(32)
        items.append((eclib.ecdsa_pubkey(sk), msg, eclib.ecdsa_sign(msg, sk, rng.randrange(1, N))))
    return items


def _spoil_schnorr(item, how, rng):
    pub, msg, sig = item
    return {
        "short_pub": (pub[:31], msg, sig),
        "short_sig": (pub, msg, sig[:63]),
        "x_past_p": (_be(P + 3), msg, sig),
        "non_residue": (_be(_non_residues(rng, 1)[0]), msg, sig),
        "r_past_p": (pub, msg, _be(P) + sig[32:]),
        "s_past_n": (pub, msg, sig[:32] + _be(N)),
        "wrong_sig": (pub, msg, sig[:32] + _be((int.from_bytes(sig[32:], "big") + 1) % N)),  # builds fine, verifies False
    }[how]


def _spoil_ecdsa(item, how, rng):
    pub, msg, sig = item
    r, s = sig[:32], sig[32:]
    return {
        "bad_prefix": (bytes([5]) + pub[1:], msg, sig),
        "short_pub": (pub[:32], msg, sig),
        "short_msg": (pub, msg[:31], sig),
        "short_sig": (pub, msg, sig[:63]),
        "x_past_p": (bytes([2]) + _be(P), msg, sig),
        "non_residue": (bytes([3]) + _be(_non_residues(rng, 1)[0]), msg, sig),
        "r_zero": (pub, msg, _be(0) + s),
        "r_past_n": (pub, msg, _be(N) + s),
        "s_zero": (pub, msg, r + _be(0)),
        "high_s": (pub, msg, r + _be(N - int.from_bytes(s, "big"))),
        "other_root": (bytes([pub[0] ^ 1]) + pub[1:], msg, sig),  # builds fine, verifies False
    }[how]


_SPOILS = {
    "schnorr": (_schnorr_items, _spoil_schnorr, secp._build_schnorr_batch,
                ["short_pub", "short_sig", "x_past_p", "non_residue", "r_past_p", "s_past_n", "wrong_sig"]),
    "ecdsa": (_ecdsa_items, _spoil_ecdsa, secp._build_ecdsa_batch,
              ["bad_prefix", "short_pub", "short_msg", "short_sig", "x_past_p", "non_residue", "r_zero", "r_past_n",
               "s_zero", "high_s", "other_root"]),
}


def _items(kind, n, seed):
    """n signed jobs with invalid rows at the first, the middle and the last
    position (every kind of invalid row in turn), the rest valid."""
    make, spoil, _build, hows = _SPOILS[kind]
    items = make(n, seed)
    rng = random.Random(seed + 1)
    for k, pos in enumerate(sorted({0, n // 2, n - 1} & set(range(n)))):
        items[pos] = spoil(items[pos], hows[(seed + k) % len(hows)], rng)
    return items


def _columns(batch):
    return (batch.px, batch.py, batch.rc, batch.d1, batch.d2, batch.ok)


@pytest.mark.parametrize("n", [0, 1, 10, 257])
@pytest.mark.parametrize("kind", ["schnorr", "ecdsa"])
def test_builders_give_the_same_columns_with_and_without_the_library(kind, n, monkeypatch):
    build = _SPOILS[kind][2]
    items = _items(kind, n, seed=40 + n)
    # how many keys the cheap checks let through to the lift: what both counters must say
    host0, native0 = _lift_counters()
    with_lib = build(items)
    host1, native1 = _lift_counters()
    lifted = host1 - host0
    assert native1 - native0 == (lifted if hostcrypto.lib() is not None else 0)

    monkeypatch.setattr(hostcrypto, "lib", lambda: None)
    without = build(items)
    host2, native2 = _lift_counters()
    assert host2 - host1 == lifted and native2 == native1  # the same keys, none of them natively

    assert _columns(with_lib) == _columns(without)
    assert len(with_lib.ok) == n and lifted <= n
    # byte for byte what the kernels are handed: strings of 32, the scalar columns' types too
    for col in (with_lib.px, with_lib.py, with_lib.rc):
        assert all(type(v) is bytes and len(v) == 32 for v in col)
    assert [type(v) for v in with_lib.d1] == [type(v) for v in without.d1]
    assert [type(v) for v in with_lib.d2] == [type(v) for v in without.d2]
    # and what they say is the oracle's: a row is ok exactly where eclib finds a key and ranges to verify with
    verify = eclib.schnorr_verify if kind == "schnorr" else eclib.ecdsa_verify
    for item, ok in zip(items, with_lib.ok):
        if not ok:
            assert not verify(*item)


@pytest.mark.parametrize("kind", ["schnorr", "ecdsa"])
def test_every_kind_of_invalid_row_is_flagged_alike(kind, monkeypatch):
    make, spoil, build, hows = _SPOILS[kind]
    rng = random.Random(50)
    items = make(len(hows) + 2, 51)
    for k, how in enumerate(hows):
        items[k + 1] = spoil(items[k + 1], how, rng)
    with_lib = build(items)
    monkeypatch.setattr(hostcrypto, "lib", lambda: None)
    without = build(items)
    assert _columns(with_lib) == _columns(without)
    builds_fine = {"wrong_sig", "other_root"}
    assert with_lib.ok == [True] + [how in builds_fine for how in hows] + [True]


@pytest.mark.parametrize("kind", ["schnorr", "ecdsa"])
def test_host_lane_answers_alike_with_and_without_the_library(kind, monkeypatch):
    """``host_verify_batch`` (breaker open, fabric failover) goes through the
    same builders: the mask is eclib's either way."""
    verify = eclib.schnorr_verify if kind == "schnorr" else eclib.ecdsa_verify
    items = _items(kind, 10, seed=60)
    want = [bool(verify(*item)) for item in items]
    assert secp.host_verify_batch(kind, items).tolist() == want
    monkeypatch.setattr(hostcrypto, "lib", lambda: None)
    assert secp.host_verify_batch(kind, items).tolist() == want
    assert want.count(True) >= 7 and want.count(False) >= 1


def test_two_threads_lifting_at_once_agree_with_the_serial_answer(native):
    xs = _xs(70) * 8
    odd = bytes(random.Random(71).randrange(2) for _ in xs)
    serial = secp._lift_keys(xs, odd)
    results, errors = {}, []

    def work(name):
        try:
            results[name] = [secp._lift_keys(xs, odd) for _ in range(6)]
        except Exception as e:  # noqa: BLE001 - reported by the assertion below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(results) == 4 and all(r == serial for rs in results.values() for r in rs)


# --- the library's other tenant -----------------------------------------------


def test_keystream_still_takes_its_native_path_from_the_rebuilt_library(native, monkeypatch):
    """The source's digest changed with the new entry, so ChaCha loads the new
    file too: it must still be the native path, and say what numpy says."""
    assert hasattr(native, "secp_lift_x_batch") and hasattr(native, "chacha20_keystream_batch")
    calls = []
    real = native.chacha20_keystream_batch

    class Spy:

        def chacha20_keystream_batch(self, *args):
            calls.append(args[1])
            return real(*args)

    keys = np.frombuffer(hashlib.sha256(b"ks").digest() * 5, dtype=np.uint8).reshape(5, 32)
    monkeypatch.setattr(hostcrypto, "lib", lambda: Spy())
    native = chacha.keystream(keys, 384)
    assert calls == [5]
    monkeypatch.setattr(hostcrypto, "lib", lambda: None)
    assert np.array_equal(native, chacha.keystream(keys, 384)) and calls == [5]
