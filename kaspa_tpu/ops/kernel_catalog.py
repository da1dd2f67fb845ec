"""Static kernel catalog: every family × bucket × mesh signature, audited.

The runtime's deterministic-execution discipline hangs on a closed world
of compiled shapes: ``crypto/secp._bucket`` pads batches to powers of two,
``ops/mesh`` shards the batch axis, and the warm manifest replays exactly
those (kernel, bucket) pairs on restart.  Nothing checked that the world
actually closes — that every reachable signature traces cleanly, keeps
its dtype contract, and is covered by a pretrace rule.  This module is
that check's data half:

- ``FAMILIES``: each kernel family's manifest kernel name, reachable
  bucket ladder, and shardable mesh sizes (the static mirror of
  ``secp._dispatch_tier`` + ``ops/mesh.dispatch_*``).
- ``enumerate_signatures()``: the closed world, one row per reachable
  (family, bucket, mesh) with the per-shard batch.
- ``audit_signature(row)``: ``jax.eval_shape`` on the real jitted kernel
  at that signature — no compile, no device — failing on shape/dtype
  drift.
- ``WARM_COVERAGE``: committed pretrace-coverage rules reconciled by the
  ``kernel-shape`` lint checker (``analysis/shapes.py``): every reachable
  shape must match a rule, every rule must match a reachable shape.

Heavy imports (jax, the kernels) stay inside functions: importing the
catalog is free, so lint tooling can read the static tables without
touching a backend.
"""

from __future__ import annotations

from dataclasses import dataclass

LIMBS = 16  # 256-bit field elements: 16 x 16-bit limbs (ops/secp256k1)
DIGITS = 64  # 4-bit MSB window digits per 256-bit scalar
MUHASH_LIMBS = 192  # 3072-bit muhash elements: 192 x 16-bit limbs

# secp._bucket pads to powers of two, min 8; the dispatch tiers cap
# coalesced batches at 1024
VERIFY_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)
MUHASH_BUCKETS = (64, 1024)  # mirrors ops.muhash_ops.BUCKETS
MESH_SIZES = (1, 2, 4, 8)  # KASPA_TPU_MESH values the partition rules serve


@dataclass(frozen=True)
class Family:
    name: str
    kernel: str  # warm-manifest "kernel"
    buckets: tuple
    mesh_sizes: tuple


FAMILIES: dict[str, Family] = {
    "ladder": Family("ladder", "schnorr_verify", VERIFY_BUCKETS, MESH_SIZES),
    "ecdsa": Family("ecdsa", "ecdsa_verify", VERIFY_BUCKETS, MESH_SIZES),
    # the 3072-bit tree product shards whole buckets, not lanes: audit the
    # fixed buckets at mesh 1 (mesh dispatch reuses the same bucket shapes)
    "muhash": Family("muhash", "muhash_tree", MUHASH_BUCKETS, (1,)),
}

# Pretrace coverage rules: (family, min_bucket, max_bucket) — a reachable
# (family, bucket) is covered iff some rule brackets it.  The lint gate
# fails on uncovered reachable shapes AND on dead rules, so this table
# can't silently rot when a bucket ladder or family changes.
WARM_COVERAGE: tuple[tuple[str, int, int], ...] = (
    ("ladder", 8, 1024),
    ("ecdsa", 8, 1024),
    ("muhash", 64, 1024),
)


def covered(family: str, bucket: int) -> bool:
    return any(f == family and lo <= bucket <= hi for f, lo, hi in WARM_COVERAGE)


def enumerate_signatures() -> list[dict]:
    """One row per reachable (family, bucket, mesh): mesh must divide the
    bucket and leave at least the minimum (8-lane) per-shard batch."""
    rows = []
    for fam in FAMILIES.values():
        for b in fam.buckets:
            for m in fam.mesh_sizes:
                if b % m != 0 or b // m < 8:
                    continue
                rows.append(
                    {
                        "family": fam.name,
                        "kernel": fam.kernel,
                        "bucket": b,
                        "mesh": m,
                        "shard": b // m,
                    }
                )
    return rows


def _i32(*shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _b(*shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.bool_)


def audit_signature(row: dict) -> str | None:
    """eval_shape the row's kernel(s); an error string on drift, else
    None.  Runs entirely abstractly — no compile, no device memory."""
    import jax
    import jax.numpy as jnp

    fam, shard, mesh = row["family"], row["shard"], row["mesh"]
    try:
        if fam in ("ladder", "ecdsa"):
            from kaspa_tpu.ops.secp256k1 import verify

            kern = verify.schnorr_verify_kernel if fam == "ladder" else verify.ecdsa_verify_kernel
            out = jax.eval_shape(
                kern,
                _i32(shard, LIMBS), _i32(shard, LIMBS), _i32(shard, LIMBS),
                _i32(shard, DIGITS), _i32(shard, DIGITS), _b(shard),
            )
            if out.shape != (shard,) or out.dtype != jnp.bool_:
                return f"verify mask drifted: got {out.shape}/{out.dtype}, want ({shard},)/bool"
        elif fam == "muhash":
            from kaspa_tpu.ops import muhash_ops

            levels = shard.bit_length() - 1  # shard is a power of two
            out = jax.eval_shape(
                lambda x: muhash_ops._tree_product(x, levels), _i32(shard, MUHASH_LIMBS)
            )
            if out.shape != (MUHASH_LIMBS,) or out.dtype != jnp.int32:
                return f"muhash product drifted: got {out.shape}/{out.dtype}, want ({MUHASH_LIMBS},)/int32"
        else:
            return f"unknown family {fam!r}"
    except Exception as e:  # noqa: BLE001 - the audit reports, never crashes lint
        return f"eval_shape failed: {type(e).__name__}: {e}"
    return None


def audit_all(rows: list[dict]) -> tuple[list[tuple[dict, str]], int]:
    """Audit every row with a minimal set of eval_shape traces:
    ``([(representative_row, error)...], traces_performed)``.

    Tracing a verify kernel costs seconds (the window ladders unroll at
    trace time) and its graph — so any dtype drift in it — is identical
    across batch widths: the kernels take no static arguments, only the
    batch axis changes.  One representative trace per kernel therefore
    validates the whole bucket ladder.  The exception re-traces:
    ``_tree_product``'s ``levels`` static argument changes the graph per
    muhash bucket (one trace per bucket).
    """
    errors: list[tuple[dict, str]] = []
    traces = 0
    for fam in ("ladder", "ecdsa"):
        frows = [r for r in rows if r["family"] == fam]
        if not frows:
            continue
        rep = min(frows, key=lambda r: (r["shard"], r["mesh"]))
        traces += 1
        err = audit_signature(rep)
        if err is not None:
            errors.append((rep, err))
    for row in (r for r in rows if r["family"] == "muhash"):
        traces += 1
        err = audit_signature(row)
        if err is not None:
            errors.append((row, err))
    return errors, traces
