"""The program's own stage histograms and counters, read as totals.

The verify pipeline (cess_proof_stage_*_seconds, cess_proof_checks,
cess_proofs_verified) and the RS data plane (cess_rs_*_seconds,
cess_rs_bytes_total) keep process-wide registries; a snapshot before and
after the window gives each stage's seconds and each count over it.
"""

from __future__ import annotations


def _registries() -> list:
    from cess_tpu.ops.rs import rs_stage_registry
    from cess_tpu.proof.xla_backend import proof_stage_registry

    return [proof_stage_registry(), rs_stage_registry()]


def snapshot() -> dict[str, float]:
    """{sample name: value} of every unlabelled sample: counters, and
    each histogram's _sum and _count."""
    out: dict[str, float] = {}
    for reg in _registries():
        for metric in reg.metrics():
            for name, labels, value in metric.samples():
                if not labels:
                    out[name] = float(value)
    return out


def delta(before: dict[str, float], after: dict[str, float]):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}
