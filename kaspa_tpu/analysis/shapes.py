"""kernel-shape audit: the closed world of compiled shapes, checked.

Gated project checker (``--shapes`` / ``options={"kernel-shape": True}``)
— it imports jax and the real kernels, so it only runs when the caller
asks (the repo lint wrapper turns it on; the generic
``python -m kaspa_tpu.analysis`` CLI leaves it off for arbitrary trees).

Three failure classes, all anchored to committed source so pragmas and
the ratchet apply:

1. **dtype/shape drift** — every reachable (family, bucket, mesh)
   signature from ``ops/kernel_catalog.py`` is audited via
   ``jax.eval_shape`` on a minimal representative set of traces (see
   ``kernel_catalog.audit_all``: tracing is seconds per kernel, and the
   graph is identical across batch widths); a verify kernel that stops
   returning a ``[b] bool`` mask fails lint before it fails a device
   batch.
2. **coverage holes** — a reachable signature matched by no
   ``WARM_COVERAGE`` rule: the shape would compile cold in production
   with no pretrace replaying it.
3. **dead rules** — a coverage rule matching no reachable signature:
   the rule (or the bucket ladder) rotted.

The audit is abstract evaluation only: no kernel compiles, no device
memory, which is what keeps ``roundcheck --only lint`` inside its 60 s
wall.
"""

from __future__ import annotations

from kaspa_tpu.analysis.core import Finding, Project, register_project_checker

_CATALOG_REL = "kaspa_tpu/ops/kernel_catalog.py"

_FAMILY_OWNERS = {
    "ladder": "kaspa_tpu/ops/secp256k1/verify.py",
    "ecdsa": "kaspa_tpu/ops/secp256k1/verify.py",
    "muhash": "kaspa_tpu/ops/muhash_ops.py",
}


def _anchor(project: Project, rel: str, symbol: str) -> tuple[str, int]:
    """(rel, line) of ``symbol`` in ``rel`` when it's in the lint set,
    else line 1 — findings stay pragma-able where possible."""
    f = project.by_rel(rel)
    if f is not None:
        for i, raw in enumerate(f.lines, start=1):
            if symbol in raw:
                return f.rel, i
        return f.rel, 1
    return rel, 1


@register_project_checker(
    "kernel-shape",
    "every reachable kernel family x bucket x mesh signature eval_shapes "
    "cleanly (dtype/shape drift) and is matched by a WARM_COVERAGE "
    "pretrace rule, with no dead rules (gated: imports jax)",
    gated=True,
)
def check_kernel_shapes(project: Project):
    from kaspa_tpu.ops import kernel_catalog as cat

    findings: list[Finding] = []
    rows = cat.enumerate_signatures()
    drift, traces = cat.audit_all(rows)
    for row, err in drift:
        rel, line = _anchor(
            project, _FAMILY_OWNERS.get(row["family"], _CATALOG_REL), "_kernel"
        )
        findings.append(
            Finding(
                rel, line, "kernel-shape",
                f"{row['family']}/{row['kernel']} bucket={row['bucket']} "
                f"mesh={row['mesh']}: {err}",
            )
        )
    for row in rows:
        if not cat.covered(row["family"], row["bucket"]):
            rel, line = _anchor(project, _CATALOG_REL, "WARM_COVERAGE")
            findings.append(
                Finding(
                    rel, line, "kernel-shape",
                    f"reachable shape {row['family']}/{row['kernel']} "
                    f"bucket={row['bucket']} is matched by no WARM_COVERAGE "
                    "rule — it would compile cold with no pretrace",
                )
            )
    reachable = {(r["family"], r["bucket"]) for r in rows}
    for fam, lo, hi in cat.WARM_COVERAGE:
        if not any(f == fam and lo <= b <= hi for f, b in reachable):
            rel, line = _anchor(project, _CATALOG_REL, "WARM_COVERAGE")
            findings.append(
                Finding(
                    rel, line, "kernel-shape",
                    f"dead WARM_COVERAGE rule ({fam!r}, {lo}, {hi}): matches "
                    "no reachable signature",
                )
            )
    payload = {
        "signatures": len(rows),
        "families": sorted({r["family"] for r in rows}),
        "audited": len(rows),
        "traces": traces,
        "drift_errors": len(drift),
        "coverage_rules": len(cat.WARM_COVERAGE),
    }
    return findings, payload
