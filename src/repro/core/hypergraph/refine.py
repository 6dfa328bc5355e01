"""Size-constrained LP uncoarsening refinement for hypergraphs — device side.

Batch-synchronous k-way LP with exact move gains for both objectives:

  * connectivity (λ−1):  moving v from a to b removes w(e) for every net
    where v is a's sole pin, and adds w(e) for every net with no pin in b:
       gain(v, b) = R(v) − W(v) + A(v, b)
    with R(v) = Σ_{e∋v} w(e)·[cnt(e, a) = 1],  W(v) = Σ_{e∋v} w(e),
    A(v, b) = Σ_{e∋v} w(e)·[cnt(e, b) ≥ 1]  — so argmax_b A is the best
    target, exactly the pin-affinity the Pallas kernel computes.
  * cut-net:  gain(v, b) = Σ_{e∋v} w(e)·[cnt(e, b) = |e|−1]
                         − Σ_{e∋v} w(e)·[cnt(e, a) = |e|].

Moves are applied with the same capped acceptance (hard balance guarantee)
and undo-to-best semantics as the graph refiner (core/lp.py, core/refine.py).
Per-net pin counts come either from the Pallas pin-affinity kernel (ELL
path) or a COO scatter (oracle / CPU path); both views share pow2 padding so
jit caches hit across multilevel levels.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import lp as lp_mod
from repro.core.hypergraph import metrics as M
from repro.core.hypergraph.container import (EllHypergraph, Hypergraph,
                                             PinCoo, to_ell_h, to_pincoo)

_NEG = -1e30
_NOISE = 1e-4
_GAIN_EPS = 1e-3


def _hyper_refine_scan(hc: PinCoo, labels0: jax.Array, cap: jax.Array,
                       key: jax.Array, k: int, rounds: int,
                       objective: str, force_balance,
                       use_kernel: bool,
                       ell: Optional[EllHypergraph] = None):
    """One candidate's scan (unjitted; vmapped by `_hyper_refine_scan_batch`
    — single refines ride the batched program at the medium's batch floor,
    DESIGN.md §12) → (labels, objective, moves): ``moves`` (rounds,)
    counts the vertices each round moved.  The round's steps carry the
    named scopes of the graph scan: ``affinity`` (the pin counts), ``cut``,
    ``gain``, ``accept`` and ``sizes``."""
    n = hc.n_pad
    vw = hc.vwgt
    w_pin = hc.mask * hc.netw[hc.pe]                      # (p_pad,)
    wtot = jnp.zeros((n,), jnp.float32).at[hc.pv].add(w_pin)

    if use_kernel and ell is not None:
        from repro.kernels import ops as kops

        def cnt_fn(labels):
            cnt, _ = kops.pin_count(ell.pins, ell.pin_mask, ell.netw,
                                    labels, k)
            return cnt
    else:
        def cnt_fn(labels):
            return M.pin_counts_device(hc, labels, k)

    obj_fn = M.km1_device if objective == "km1" else M.cut_net_device

    def gains(labels, cnt):
        cnt_e = cnt[hc.pe]                                # (p_pad, k)
        cnt_own = cnt_e[jnp.arange(hc.p_pad),
                        labels[hc.pv].astype(jnp.int32)]  # (p_pad,)
        if objective == "km1":
            pres = (cnt_e > 0).astype(jnp.float32)
            aff = jnp.zeros((n, k), jnp.float32).at[hc.pv].add(
                w_pin[:, None] * pres)
            rem = jnp.zeros((n,), jnp.float32).at[hc.pv].add(
                w_pin * (cnt_own == 1))
            return rem[:, None] - wtot[:, None] + aff
        makes = (cnt_e == (hc.esize[hc.pe] - 1.0)[:, None])
        joins = jnp.zeros((n, k), jnp.float32).at[hc.pv].add(
            w_pin[:, None] * makes.astype(jnp.float32))
        breaks = jnp.zeros((n,), jnp.float32).at[hc.pv].add(
            w_pin * (cnt_own == hc.esize[hc.pe]))
        return joins - breaks[:, None]

    def body(carry, key_r):
        labels, sizes, best_obj, best_labels, parity = carry
        with jax.named_scope("affinity"):
            cnt = cnt_fn(labels)
        # track best feasible state seen (undo-to-best)
        with jax.named_scope("cut"):
            obj = obj_fn(cnt, hc.netw)
            feas = jnp.max(sizes - cap) <= 1e-6
            better = feas & (obj < best_obj)
            best_obj = jnp.where(better, obj, best_obj)
            best_labels = jnp.where(better, labels, best_labels)
        # propose + accept moves
        with jax.named_scope("gain"):
            gain = gains(labels, cnt)
            gain = gain + jax.random.uniform(key_r, (n, k), jnp.float32,
                                             0.0, _NOISE)
            gain = gain.at[jnp.arange(n), labels].set(_NEG)
            room = sizes[None, :] + vw[:, None] <= cap[None, :]
            gain = jnp.where(room, gain, _NEG)
            best_gain = jnp.max(gain, axis=1)
            best_tgt = jnp.argmax(gain, axis=1).astype(labels.dtype)
            want = best_gain > _GAIN_EPS
            # overweight blocks push nodes out regardless of gain (forced)
            over = sizes[labels] > cap[labels]
            want = want | (jnp.asarray(force_balance)
                           & over & (best_gain > _NEG / 2) & (vw > 0))
            node_par = (jnp.arange(n) + parity) % 2 == 0
            want = want & node_par
            proposal = jnp.where(want, best_tgt, labels)
        with jax.named_scope("accept"):
            new_labels = lp_mod.capped_accept(
                labels, proposal, vw, sizes, cap,
                jnp.where(want, best_gain, _NEG))
        with jax.named_scope("sizes"):
            new_sizes = jnp.zeros((k,), jnp.float32).at[new_labels].add(vw)
        return (new_labels, new_sizes, best_obj, best_labels,
                parity + 1), jnp.sum((new_labels != labels)
                                     .astype(jnp.int32))

    sizes0 = jnp.zeros((k,), jnp.float32).at[labels0].add(vw)
    keys = jax.random.split(key, rounds)
    carry0 = (labels0, sizes0, jnp.float32(jnp.inf), labels0, jnp.int32(0))
    (labels, sizes, best_obj, best_labels, _), moves = jax.lax.scan(
        body, carry0, keys)
    # evaluate the final state too
    obj = obj_fn(cnt_fn(labels), hc.netw)
    feas = jnp.max(sizes - cap) <= 1e-6
    better = feas & (obj < best_obj)
    best_obj = jnp.where(better, obj, best_obj)
    best_labels = jnp.where(better, labels, best_labels)
    have = jnp.isfinite(best_obj)
    return jnp.where(have, best_labels, labels), best_obj, moves


def _caps_for(hg: Hypergraph, k: int, eps: float) -> np.ndarray:
    lmax = np.ceil(hg.total_vwgt() / k)
    return np.full(k, (1.0 + eps) * lmax)


def k_bucket(k: int) -> int:
    """pow2 block-count bucket with floor 4 (DESIGN.md §12): scans for
    k=2..4 (and 5..8, ...) share one compiled program per shape bucket.
    Fake blocks get zero capacity, so no vertex ever moves into one."""
    from repro.core.csr import _pow2_pad
    return _pow2_pad(max(k, 4), 1)


def _pad_caps(cap: np.ndarray, k_pad: int) -> np.ndarray:
    out = np.zeros(k_pad, np.float32)
    out[:len(cap)] = cap
    return out


@functools.partial(jax.jit, static_argnames=("k", "rounds", "objective",
                                             "use_kernel"))
def _hyper_refine_scan_batch(hc: PinCoo, labels0: jax.Array, cap: jax.Array,
                             keys: jax.Array, force: jax.Array, k: int,
                             rounds: int, objective: str,
                             use_kernel: bool,
                             ell: Optional[EllHypergraph] = None):
    """THE hypergraph refinement program: everything routes through here.
    → (labels, objectives, moves), one row each per candidate."""
    def one(lab0, key, f):
        return _hyper_refine_scan(hc, lab0, cap, key, k, rounds, objective,
                                  f, use_kernel, ell=ell)
    return jax.vmap(one)(labels0, keys, force)


def _run_hyper_scan_batch(hc, cap_np, labs, keys, force, k, rounds,
                          objective, use_kernel, ell, batch_floor,
                          recorder=None):
    """Pad the batch to its bucket and run the one program; an enabled
    ``recorder`` (default: the ambient one) gets its ``refine/*`` round
    counters (`lp.count_round_moves`), read back after the labels."""
    from repro.core import multilevel as ML
    from repro.core.refine import _pad_rows, batch_bucket
    b = labs.shape[0]
    b_pad = batch_bucket(b, batch_floor)
    k_pad = k_bucket(k)
    ML.note_bucket_pad(b_pad - b)
    ML.note_program("hyper", hc.n_pad, hc.e_pad, hc.p_pad, k_pad, rounds,
                    objective, b_pad, use_kernel)
    outs, _, moves = _hyper_refine_scan_batch(
        hc, jnp.asarray(_pad_rows(labs, b_pad)),
        jnp.asarray(_pad_caps(np.asarray(cap_np), k_pad)),
        jnp.asarray(_pad_rows(keys, b_pad)),
        jnp.asarray(_pad_rows(force, b_pad)),
        k_pad, rounds, objective, use_kernel, ell=ell)
    outs = np.asarray(outs, dtype=np.int64)[:b]
    rec = recorder if recorder is not None else obs.current()
    if rec.enabled:
        lp_mod.count_round_moves(rec, "refine/", moves, b)
    return outs


def refine_hypergraph(hg: Hypergraph, part: np.ndarray, k: int,
                      eps: float = 0.03, rounds: int = 12, seed: int = 0,
                      objective: str = "km1",
                      force_balance: bool = False,
                      use_kernel: Optional[bool] = None,
                      hc: Optional[PinCoo] = None,
                      ell: Optional[EllHypergraph] = None,
                      batch_floor: int = 1,
                      recorder=None) -> np.ndarray:
    """Polish ``part``; never returns a worse feasible objective.

    ``use_kernel=None`` resolves to the backend default (Pallas pin counts
    on TPU, COO scatter elsewhere); ``hc``/``ell`` accept cached views.
    ``batch_floor`` pads the batch dim up to the medium's bucket so this
    single call reuses the tournament's compiled program.  ``recorder``
    gets the round counters (`_run_hyper_scan_batch`).
    """
    if k <= 1 or hg.n == 0:
        return np.asarray(part, dtype=np.int64)
    from repro.core.refine import default_use_kernel
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    hc = hc if hc is not None else to_pincoo(hg)
    if use_kernel and ell is None:
        ell = to_ell_h(hg)
    labs = np.zeros((1, hc.n_pad), dtype=np.int32)
    labs[0, :hg.n] = part
    keys = np.asarray(jax.random.PRNGKey(seed))[None]
    outs = _run_hyper_scan_batch(hc, _caps_for(hg, k, eps), labs, keys,
                                 np.asarray([force_balance]), k, rounds,
                                 objective, use_kernel, ell, batch_floor,
                                 recorder)
    out = outs[0][:hg.n]
    score = M.connectivity if objective == "km1" else M.cut_net
    # paranoia: keep the better of (in, out) among feasible options
    if score(hg, out) <= score(hg, part) or force_balance:
        return out
    return np.asarray(part, dtype=np.int64)


def refine_hypergraph_batch(hg: Hypergraph, parts: list, k: int,
                            eps: float = 0.03, rounds: int = 12,
                            seed: int = 0, objective: str = "km1",
                            use_kernel: Optional[bool] = None,
                            hc: Optional[PinCoo] = None,
                            ell: Optional[EllHypergraph] = None,
                            keys: Optional[np.ndarray] = None,
                            batch_floor: int = 1,
                            recorder=None) -> list:
    """Refine several candidate partitions in one vmapped device call (the
    initial-partition tournament shares a single compile).  ``keys``
    overrides the per-candidate PRNG keys (shape ``(b, 2)``) — the memetic
    sweep passes per-island keys so each island's trajectory is independent
    of how many islands are batched together."""
    if k <= 1 or hg.n == 0 or not parts:
        return [np.asarray(p, dtype=np.int64) for p in parts]
    from repro.core.refine import default_use_kernel
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    hc = hc if hc is not None else to_pincoo(hg)
    if use_kernel and ell is None:
        ell = to_ell_h(hg)
    labs = np.zeros((len(parts), hc.n_pad), dtype=np.int32)
    for i, p in enumerate(parts):
        labs[i, :hg.n] = p
    force = np.asarray([not M.is_feasible(hg, p, k, eps) for p in parts])
    if keys is None:
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                           len(parts)))
    outs = _run_hyper_scan_batch(hc, _caps_for(hg, k, eps), labs,
                                 np.asarray(keys), force, k, rounds,
                                 objective, use_kernel, ell, batch_floor,
                                 recorder)
    outs = outs[:, :hg.n]
    score = M.connectivity if objective == "km1" else M.cut_net
    result = []
    for i, p in enumerate(parts):
        if score(hg, outs[i]) <= score(hg, p) or force[i]:
            result.append(outs[i])
        else:
            result.append(np.asarray(p, dtype=np.int64))
    return result
