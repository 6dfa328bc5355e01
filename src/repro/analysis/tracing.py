"""Tracing entry points to jaxprs and walking them.

`trace_entry` runs `jax.make_jaxpr` on an entry point's canonical inputs
(registry.py) — abstract evaluation only, nothing is compiled or
executed.  The walker yields every equation in the program together with
a human-readable path ("scan[3].body/jit[0]{_refine_scan}") and the
loop/shard_map context the checkers key off.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.core as jcore
from jax.extend import core

#: primitives whose body jaxprs execute repeatedly (hot-loop context)
LOOP_PRIMITIVES = ("scan", "while")


@dataclasses.dataclass
class TracedEntry:
    name: str
    closed: core.ClosedJaxpr       # the traced program
    flat_args: List[Any]           # concrete leaves, make_jaxpr arg order
    fn: Any                        # the callable that was traced
    args: Tuple[Any, ...]          # original (pytree) arguments


def trace_entry(entry) -> TracedEntry:
    """Abstractly evaluate one registry entry to a ClosedJaxpr."""
    fn, args = entry.build()
    closed = jax.make_jaxpr(fn)(*args)
    flat = jax.tree_util.tree_leaves(args)
    return TracedEntry(name=entry.name, closed=closed, flat_args=flat,
                       fn=fn, args=args)


def _jaxpr_of(value) -> Optional[core.Jaxpr]:
    if isinstance(value, core.ClosedJaxpr):
        return value.jaxpr
    if isinstance(value, core.Jaxpr):
        return value
    return None


def sub_jaxprs(eqn: core.JaxprEqn) -> Iterator[Tuple[str, core.Jaxpr]]:
    """Yield (param name, body jaxpr) for every sub-jaxpr of an equation.

    Covers jit/scan/while (`jaxpr` as ClosedJaxpr or Jaxpr), cond
    (`branches` tuple), and custom-call params that carry jaxprs.
    """
    for pname, value in eqn.params.items():
        j = _jaxpr_of(value)
        if j is not None:
            yield pname, j
            continue
        if isinstance(value, (tuple, list)):
            for i, item in enumerate(value):
                j = _jaxpr_of(item)
                if j is not None:
                    yield f"{pname}[{i}]", j


def eqn_label(eqn: core.JaxprEqn, index: int) -> str:
    name = eqn.params.get("name")
    prim = eqn.primitive.name
    return f"{prim}[{index}]" + (f"{{{name}}}" if name else "")


@dataclasses.dataclass(frozen=True)
class EqnSite:
    eqn: core.JaxprEqn
    path: str                 # "scan[2].body/jit[0]{foo}"
    in_loop: bool             # inside a scan/while body
    loop_depth: int


def iter_eqns(jaxpr: core.Jaxpr, path: str = "", in_loop: bool = False,
              loop_depth: int = 0) -> Iterator[EqnSite]:
    """Depth-first walk over every equation, including all sub-jaxprs."""
    for i, eqn in enumerate(jaxpr.eqns):
        label = eqn_label(eqn, i)
        here = f"{path}/{label}" if path else label
        yield EqnSite(eqn=eqn, path=here, in_loop=in_loop,
                      loop_depth=loop_depth)
        body_is_loop = eqn.primitive.name in LOOP_PRIMITIVES
        for pname, sub in sub_jaxprs(eqn):
            sub_path = f"{here}.{pname}"
            yield from iter_eqns(
                sub, sub_path,
                in_loop=in_loop or body_is_loop,
                loop_depth=loop_depth + (1 if body_is_loop else 0))


def scan_carry_avals(eqn: core.JaxprEqn) -> Sequence[jcore.AbstractValue]:
    """Carry avals of a scan equation (body-jaxpr invars, post-consts)."""
    assert eqn.primitive.name == "scan"
    nc = eqn.params["num_consts"]
    ncarry = eqn.params["num_carry"]
    body = eqn.params["jaxpr"].jaxpr
    return [v.aval for v in body.invars[nc:nc + ncarry]]


def all_avals(jaxpr: core.Jaxpr) -> Iterator[Tuple[str, jcore.AbstractValue]]:
    """Every aval in the program with a location tag (recursive)."""
    for v in jaxpr.invars:
        yield "invar", v.aval
    for site in iter_eqns(jaxpr):
        for v in site.eqn.outvars:
            if isinstance(v, jcore.DropVar):
                continue
            yield site.path, v.aval
