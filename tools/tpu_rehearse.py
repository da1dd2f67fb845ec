#!/usr/bin/env python
"""Ask the TPU's compiler, with no chip attached, for every kernel the
chip smoke dispatches at the shape it dispatches it (on-chip-measurement
guide, section 2, third rehearsal).

    JAX_PLATFORMS=cpu python tools/tpu_rehearse.py            # everything
    JAX_PLATFORMS=cpu python tools/tpu_rehearse.py pallas     # one group

Groups: ``pallas`` (the default single-chip ladder, Schnorr + ECDSA at the
padded widths 256/512/1024 the served buckets map to), ``muhash`` (tree
product at 64 and 1024),
``mesh`` (the shard_map-wrapped XLA ladder on a 4-device mesh built from
the described topology, shard 256 = bucket 1024 / 4, plus the sharded
muhash tree).  One JSON line per compile: seconds, generated-code and temp
bytes, whether a Mosaic kernel is in the program.

A compile that passes here is not a chip run and is never reported as one:
nothing executes, so this says nothing about results or times.  The case
builders take the described topology as an argument —
``tests/test_tpu_compile.py`` calls them from its own fixture.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"


def describe_topology():
    """The described (not attached) v5e 2x2 topology.  Loads the TPU
    library into this process: call it from a script's main or a test
    fixture, never at import."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def pallas_ladder(topo, kind: str, n_padded: int):
    """Lower + compile the fused Mosaic ladder for one described chip."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kaspa_tpu.ops.secp256k1 import ladder_pallas as lp

    chip = SingleDeviceSharding(topo.devices[0])
    # the one packed byte array of a call; limbs and digits are laid out
    # inside the program (ladder_pallas.unpack_lanes)
    run = lp._build_call(n_padded, kind == "ecdsa", False)
    return run.lower(_shape((lp.LANE_BYTES, n_padded), jnp.uint8, chip)).compile()


def muhash_tree(topo, bucket: int):
    """Lower + compile the single-chip muhash tree product at one bucket."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kaspa_tpu.ops import muhash_ops

    chip = SingleDeviceSharding(topo.devices[0])
    x = _shape((bucket, muhash_ops.F.W), jnp.int32, chip)
    return muhash_ops._tree_product.lower(x, levels=bucket.bit_length() - 1).compile()


def _described_mesh(topo, n: int):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:n]), axis_names=("shard",))


def mesh_ladder(topo, kind: str, shard: int, n: int = 4):
    """Lower + compile the shard_map-wrapped XLA ladder (what mesh > 1
    dispatches) over n described chips at ``shard`` lanes per chip."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from kaspa_tpu.ops import mesh

    dmesh = _described_mesh(topo, n)
    in_specs = tuple(mesh.partition_spec_for(nm, flat=True) for nm in mesh._VERIFY_ARG_NAMES)
    entry = mesh._sharded_jit(
        mesh._verify_kernel(kind), dmesh, in_specs, mesh.partition_spec_for("mask", flat=True)
    )
    b = shard * n
    widths = (16, 16, 16, 64, 64)
    args = [
        _shape((b, w), jnp.int32, NamedSharding(dmesh, spec)) for w, spec in zip(widths, in_specs)
    ]
    args.append(_shape((b,), jnp.bool_, NamedSharding(dmesh, in_specs[5])))
    return entry.lower(*args).compile()


def mesh_muhash_tree(topo, bucket: int, n: int = 4):
    """Lower + compile the sharded muhash tree (per-chip bucket) over n chips."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kaspa_tpu.ops import mesh
    from kaspa_tpu.ops.muhash_ops import F

    dmesh = _described_mesh(topo, n)
    spec = P("shard", None)
    entry = mesh._sharded_jit(mesh._local_tree(bucket.bit_length() - 1), dmesh, spec, spec)
    return entry.lower(_shape((bucket * n, F.W), jnp.int32, NamedSharding(dmesh, spec))).compile()


def report(compiled) -> dict:
    """What a passed compile is allowed to say: sizes and kernel presence."""
    ma = compiled.memory_analysis()
    return {
        "code_bytes": int(ma.generated_code_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "mosaic_kernel": "tpu_custom_call" in compiled.as_text(),
    }


def _cases(groups: set) -> list:
    cases = []
    if "pallas" in groups:
        for kind in ("schnorr", "ecdsa"):
            for n in (256, 512, 1024):
                cases.append((f"pallas/{kind}/n{n}", lambda t, k=kind, n=n: pallas_ladder(t, k, n)))
    if "muhash" in groups:
        for bucket in (64, 1024):
            cases.append((f"muhash/tree/b{bucket}", lambda t, b=bucket: muhash_tree(t, b)))
    if "mesh" in groups:
        for kind in ("schnorr", "ecdsa"):
            cases.append((f"mesh4/{kind}/shard256", lambda t, k=kind: mesh_ladder(t, k, 256)))
        cases.append(("mesh4/muhash/b64", lambda t: mesh_muhash_tree(t, 64)))
    return cases


def main() -> int:
    groups = set(sys.argv[1:]) or {"pallas", "muhash", "mesh"}
    import jax

    # a described-device compile is written to the persistent cache but can
    # never be read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    topo = describe_topology()
    failed = 0
    for name, build in _cases(groups):
        t0 = time.perf_counter()
        try:
            row = {"case": name, "ok": True, **report(build(topo))}
        except Exception as e:  # noqa: BLE001 - report what the compiler refused, go on
            row = {"case": name, "ok": False, "error": f"{type(e).__name__}: {str(e)[:400]}"}
            failed += 1
        row["compile_seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
    print(json.dumps({"topology": TOPOLOGY, "failed": failed, "note": "compiles only - nothing ran on a chip"}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
