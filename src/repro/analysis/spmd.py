"""SPMD replication checker: a race detector for distributed refinement.

Walks every `shard_map` equation in an entry's jaxpr (the `parhyp` rounds
of `hypergraph/dist.py`, the memetic ring migration) and runs a forward
dataflow analysis over the body, computing for every intermediate the set
of mesh axes it is *shard-varying* over:

  * body inputs seed from ``in_specs`` (an input split over axis a is
    varying over a; a replicated input over nothing);
  * ``psum``/``pmin``/``pmax``/``all_gather`` over axis a *remove* a
    (the value becomes replicated over a);
  * ``pvary`` over axis a (inserted by ``check_vma=True`` tracing)
    *introduces* a, as ``axis_index`` does;
  * ``ppermute``/``all_to_all`` keep the varying set (data moves between
    shards but stays shard-dependent);
  * ``axis_index`` *introduces* its axis;
  * everything else unions its inputs; scan/while carries run to fixpoint;
    a shard-varying cond predicate taints every branch output.

Violations: a body output whose varying set exceeds what ``out_specs``
claims (the protocol requires replication there — with ``check_vma=False``
jax itself won't catch it and each shard would silently hold a different
value), and any collective whose axis name is not an axis of the mesh.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, List, Sequence

from jax.extend import core

from repro.analysis.findings import Finding
from repro.analysis.tracing import TracedEntry, eqn_label, iter_eqns

EMPTY: FrozenSet[str] = frozenset()

#: collectives that make their output replicated over the named axes
_REDUCING = ("psum", "psum_invariant", "pmin", "pmax", "all_gather",
             "all_gather_invariant")
#: collectives that permute shard-varying data (varying in, varying out)
_PERMUTING = ("ppermute", "pshuffle", "all_to_all")


def _flat_axes(spec) -> FrozenSet[str]:
    """Mesh axes a ``PartitionSpec`` splits over (entries are None, an
    axis name, or a tuple of axis names)."""
    return frozenset(ax for entry in spec for ax in _named_axes(entry))


def _named_axes(value) -> List[str]:
    if isinstance(value, str):
        return [value]
    if isinstance(value, (tuple, list)):
        return [a for a in value if isinstance(a, str)]
    return []


class _Dataflow:
    def __init__(self, mesh_axes: FrozenSet[str], entry_name: str):
        self.mesh_axes = mesh_axes
        self.entry_name = entry_name
        self.findings: List[Finding] = []

    def _bad_axis(self, axes: Sequence[str], prim: str, path: str) -> None:
        for ax in axes:
            if ax not in self.mesh_axes:
                self.findings.append(Finding(
                    checker="spmd", severity="error", entry=self.entry_name,
                    code="bad-collective-axis", location=path,
                    message=f"{prim} over axis {ax!r} which is not an axis "
                            f"of the shard_map mesh "
                            f"{sorted(self.mesh_axes)}"))

    def run(self, jaxpr: core.Jaxpr,
            env: Dict[core.Var, FrozenSet[str]], path: str) -> None:
        read = lambda a: (EMPTY if isinstance(a, core.Literal)  # noqa: E731
                          else env.get(a, EMPTY))
        for i, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            here = f"{path}/{eqn_label(eqn, i)}"
            ins = [read(a) for a in eqn.invars]
            union = frozenset().union(*ins) if ins else EMPTY
            if prim in _REDUCING:
                axes = _named_axes(eqn.params.get(
                    "axes", eqn.params.get("axis_name", ())))
                self._bad_axis(axes, prim, here)
                res = union - frozenset(axes)
            elif prim in _PERMUTING:
                axes = _named_axes(eqn.params.get("axis_name", ()))
                self._bad_axis(axes, prim, here)
                res = union
            elif prim in ("axis_index", "pvary"):
                axes = _named_axes(eqn.params.get(
                    "axes", eqn.params.get("axis_name", ())))
                self._bad_axis(axes, prim, here)
                res = union | (frozenset(axes) & self.mesh_axes)
            elif prim == "scan":
                self._scan(eqn, ins, env, here)
                continue
            elif prim == "while":
                self._while(eqn, ins, env, here)
                continue
            elif prim == "cond":
                self._cond(eqn, ins, env, here)
                continue
            elif prim == "jit":
                body = eqn.params["jaxpr"].jaxpr
                sub: Dict[core.Var, FrozenSet[str]] = {}
                for var, ax in zip(body.invars, ins):
                    sub[var] = ax
                self.run(body, sub, here)
                for var, bout in zip(eqn.outvars, body.outvars):
                    env[var] = (EMPTY if isinstance(bout, core.Literal)
                                else sub.get(bout, EMPTY))
                continue
            else:
                res = union
            for v in eqn.outvars:
                env[v] = res

    # -- structured control flow -------------------------------------------
    def _scan(self, eqn, ins, env, path) -> None:
        nc = eqn.params["num_consts"]
        ncarry = eqn.params["num_carry"]
        body = eqn.params["jaxpr"].jaxpr
        carry = list(ins[nc:nc + ncarry])
        sub: Dict[core.Var, FrozenSet[str]] = {}
        for _ in range(16):
            sub = {}
            seed = ins[:nc] + carry + ins[nc + ncarry:]
            for var, ax in zip(body.invars, seed):
                sub[var] = ax
            saved = list(self.findings)
            self.findings = []
            self.run(body, sub, path + ".body")
            new_findings = self.findings
            self.findings = saved
            outs = [EMPTY if isinstance(v, core.Literal) else sub.get(v, EMPTY)
                    for v in body.outvars]
            new_carry = [c | o for c, o in zip(carry, outs[:ncarry])]
            if new_carry == carry:
                self.findings.extend(new_findings)
                break
            carry = new_carry
        outs = [EMPTY if isinstance(v, core.Literal) else sub.get(v, EMPTY)
                for v in body.outvars]
        for var, ax in zip(eqn.outvars, carry + outs[ncarry:]):
            env[var] = ax

    def _while(self, eqn, ins, env, path) -> None:
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond = eqn.params["cond_jaxpr"].jaxpr
        body = eqn.params["body_jaxpr"].jaxpr
        carry = list(ins[cn + bn:])
        for _ in range(16):
            csub: Dict[core.Var, FrozenSet[str]] = {}
            for var, ax in zip(cond.invars, ins[:cn] + carry):
                csub[var] = ax
            saved = list(self.findings)
            self.findings = []
            self.run(cond, csub, path + ".cond")
            pred = (EMPTY if isinstance(cond.outvars[0], core.Literal)
                    else csub.get(cond.outvars[0], EMPTY))
            bsub: Dict[core.Var, FrozenSet[str]] = {}
            for var, ax in zip(body.invars, ins[cn:cn + bn] + carry):
                bsub[var] = ax
            self.run(body, bsub, path + ".body")
            new_findings = self.findings
            self.findings = saved
            outs = [EMPTY if isinstance(v, core.Literal)
                    else bsub.get(v, EMPTY) for v in body.outvars]
            # a shard-varying loop predicate taints every carry
            new_carry = [c | o | pred for c, o in zip(carry, outs)]
            if new_carry == carry:
                self.findings.extend(new_findings)
                break
            carry = new_carry
        for var, ax in zip(eqn.outvars, carry):
            env[var] = ax

    def _cond(self, eqn, ins, env, path) -> None:
        pred = ins[0]
        outs = None
        for bi, branch in enumerate(eqn.params["branches"]):
            bj = branch.jaxpr
            sub: Dict[core.Var, FrozenSet[str]] = {}
            for var, ax in zip(bj.invars, ins[1:]):
                sub[var] = ax
            self.run(bj, sub, f"{path}.branch[{bi}]")
            bouts = [EMPTY if isinstance(v, core.Literal)
                     else sub.get(v, EMPTY) for v in bj.outvars]
            outs = bouts if outs is None else [a | b for a, b
                                               in zip(outs, bouts)]
        for var, ax in zip(eqn.outvars, outs or []):
            env[var] = ax | pred


def check_spmd(traced: TracedEntry, entry) -> List[Finding]:
    findings: List[Finding] = []
    for site in iter_eqns(traced.closed.jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "shard_map":
            continue
        mesh = eqn.params["mesh"]
        mesh_axes = frozenset(getattr(mesh, "axis_names", ()))
        body = eqn.params["jaxpr"]
        flow = _Dataflow(mesh_axes, entry.name)
        env: Dict[core.Var, FrozenSet[str]] = {}
        for var, spec in zip(body.invars, eqn.params["in_specs"]):
            env[var] = _flat_axes(spec)
        flow.run(body, env, site.path)
        findings.extend(flow.findings)
        for i, (var, spec) in enumerate(zip(body.outvars,
                                            eqn.params["out_specs"])):
            if isinstance(var, core.Literal):
                continue
            claimed = _flat_axes(spec)
            extra = env.get(var, EMPTY) - claimed
            if extra:
                findings.append(Finding(
                    checker="spmd", severity="error", entry=entry.name,
                    code="varying-as-replicated",
                    location=f"{site.path}.out[{i}]",
                    message=f"shard_map output {i} of {entry.name} is "
                            f"shard-varying over {sorted(extra)} but "
                            f"out_specs claims it replicated "
                            f"(axes {sorted(claimed)}) — with "
                            f"check_vma=False each shard silently holds a "
                            f"different value",
                    detail={"varying": sorted(extra),
                            "claimed": sorted(claimed)}))
    # structured control flow can re-walk bodies during fixpoint; dedupe
    seen = set()
    unique = []
    for f in findings:
        if f.key not in seen:
            seen.add(f.key)
            unique.append(f)
    return unique
