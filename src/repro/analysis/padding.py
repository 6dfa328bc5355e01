"""Padding-inertness checker: noninterference by self-composition.

The masking contract (`kernels/ops.py` docstring, DESIGN.md §12) says the
*only* trusted padding indicator is a weight/mask of zero — padded index
slots may hold any valid id, because a sentinel like ``n_pad-1`` can alias
a real row when a dim lands exactly on its bucket.  The contract therefore
has a precise semantic reading: **the real slots of every output are a
function of the real slots of the inputs alone.**

That is a noninterference property, and the checker proves it per entry by
self-composition over the *traced* program: evaluate the entry's
ClosedJaxpr twice — once on the canonical inputs, once with deterministic
garbage written into exactly the padding slots (the entry's `PaddingSpec`
perturbation: zero-weight edges re-aimed at random vertices, masked pins
re-aimed at random nets, padding-vertex labels scrambled, padding batch
rows scrambled) — and require the projections onto real slots to be
**bit-identical**.  Any divergence means padding flowed into an accepted
move, an objective value, or a balance total, and the location is reported
with the differing output index.

Running the traced jaxpr (not the python fn) means the property is checked
for the exact program the engine ships, after jit inlining and
constant-folding.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.analysis.findings import Finding
from repro.analysis.tracing import TracedEntry

_SEED = 0xA11A


def _eval_closed(closed, flat_args):
    from jax.extend import core
    return core.jaxpr_as_fun(closed)(*flat_args)


def check_padding(traced: TracedEntry, entry) -> List[Finding]:
    if entry.padding is None:
        return []
    import jax
    rng = np.random.default_rng(_SEED)
    perturbed = entry.padding.perturb(traced.args, rng)
    base_flat = traced.flat_args
    pert_flat = jax.tree_util.tree_leaves(perturbed)
    if len(pert_flat) != len(base_flat):
        return [Finding(
            checker="padding", severity="error", entry=entry.name,
            code="bad-perturbation", location="spec",
            message=f"{entry.name}: PaddingSpec.perturb changed the arg "
                    f"tree ({len(base_flat)} -> {len(pert_flat)} leaves)")]
    out_a = _eval_closed(traced.closed, base_flat)
    out_b = _eval_closed(traced.closed, pert_flat)
    proj_a = entry.padding.project(out_a)
    proj_b = entry.padding.project(out_b)
    findings: List[Finding] = []
    for i, (a, b) in enumerate(zip(proj_a, proj_b)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or not np.array_equal(a, b):
            diff = (int(np.count_nonzero(a != b))
                    if a.shape == b.shape else -1)
            findings.append(Finding(
                checker="padding", severity="error", entry=entry.name,
                code="padding-flows-into-output",
                location=f"output[{i}]",
                message=f"{entry.name}: garbage in padding slots changed "
                        f"real output {i} ({diff} differing elements) — "
                        f"padding leaked into accepted moves, objective "
                        f"values, or balance totals",
                detail={"output": i, "differing": diff}))
    return findings
