"""Mixture-of-Experts FFN (deepseek-v2: 2 shared + 160 routed top-6;
llama4-scout: shared + 16 routed top-1).

GShard-style capacity dispatch: einsum one-hot dispatch/combine tensors keep
the graph static-shaped and shardable; expert weight stacks shard over the
``model`` mesh axis (expert parallelism inside the TP plane).

KaHIP integration (DESIGN.md §3): ``expert_placement`` partitions the expert
co-activation graph (node weight = expert load, edge weight = co-routing
frequency) with the *node+edge balanced* objective, yielding a permutation
that places co-activated experts on the same shard — ``place_experts``
applies it to the weight stacks, minimizing cross-shard all-to-all traffic.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.layers import normal


def _buffers(x):
    from repro.models import shardings as SH
    return SH.constrain_moe_buffers(x)


# ---------------------------------------------------------------------------
# gate observation (serve-path telemetry, DESIGN.md §13)
# ---------------------------------------------------------------------------

#: When set, every ``moe_ffn`` forward reports its routed expert indices
#: (host numpy, shape (..., k)) — the live signal `obs.live`'s
#: `TrafficAccumulator` folds into the traffic hypergraph.
_gate_observer = None


def _dispatch_gates(gate_idx) -> None:
    fn = _gate_observer
    if fn is not None:
        fn(np.asarray(gate_idx))


def _emit_gates(gate_idx) -> None:
    """Tap the routing decision.  With no observer installed at trace time
    this is a pure no-op (nothing is staged into the computation); with
    one installed, a `jax.debug.callback` ships the indices to the host.
    The runtime double-check in `_dispatch_gates` makes *clearing* the
    observer effective even for already-compiled programs; *installing*
    one only affects computations traced afterwards (e.g. a fresh
    `ContinuousBatcher`, whose jitted steps are per-instance)."""
    if _gate_observer is not None:
        jax.debug.callback(_dispatch_gates, gate_idx)


@contextlib.contextmanager
def observe_gates(sink):
    """Install a gate observer for the duration of the context.

    ``sink`` is either a callable taking an (..., k) int array or an
    object with an ``observe`` method (`obs.live.TrafficAccumulator`).
    """
    global _gate_observer
    fn = sink.observe if hasattr(sink, "observe") else sink
    prev = _gate_observer
    _gate_observer = fn
    try:
        yield sink
    finally:
        _gate_observer = prev


def init_moe(key, cfg, dtype):
    d = cfg.d_model
    dff = cfg.d_ff_expert or cfg.d_ff
    e = cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": normal(ks[0], (d, e), 0.02, jnp.float32),
        "w_gate": normal(ks[1], (e, d, dff), 0.02, dtype),
        "w_up": normal(ks[2], (e, d, dff), 0.02, dtype),
        "w_down": normal(ks[3], (e, dff, d), 0.02, dtype),
    }
    if cfg.n_shared_experts:
        sdff = cfg.n_shared_experts * dff
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p.update({
            "ws_gate": normal(k1, (d, sdff), 0.02, dtype),
            "ws_up": normal(k2, (d, sdff), 0.02, dtype),
            "ws_down": normal(k3, (sdff, d), 0.02, dtype),
        })
    return p


def moe_ffn(params, x, cfg):
    """x: (B,S,d) → (B,S,d).

    Sort-based grouped dispatch (memory O(T·k·d), FLOPs ∝ active experts):
    (token, choice) pairs are sorted by expert id, scattered into per-expert
    capacity buffers (E, cap, d) — sharded over the ``model`` axis, so the
    scatter lowers to the expert-parallel all-to-all — then three batched
    expert matmuls, then a weighted gather back.  Tokens beyond an expert's
    capacity are dropped (capacity_factor headroom), as in GShard/Switch.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt @ params["router"]).astype(jnp.float32)        # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # (T,k)
    _emit_gates(gate_idx)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(np.ceil(t * k * cfg.capacity_factor / e)))
    if t >= 4096:       # shardability: capacity divisible by (pod,data)
        cap = int(np.ceil(cap / 512) * 512)
    # flatten (token, choice) pairs and sort by expert
    pair_e = gate_idx.reshape(-1)                                # (T*k,)
    pair_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pair_g = gate_vals.reshape(-1)
    order = jnp.argsort(pair_e)
    pe, pt, pg = pair_e[order], pair_t[order], pair_g[order]
    # position within expert group = index − first index of that expert
    first = jnp.searchsorted(pe, jnp.arange(e), side="left")     # (E,)
    pos = jnp.arange(t * k) - first[pe]
    keep = pos < cap
    slot = jnp.where(keep, pe * cap + pos, 0)                    # drop → w=0
    val = jnp.where(keep[:, None], xt[pt], 0.0)
    buf = jnp.zeros((e * cap, d), xt.dtype).at[slot].add(val)
    expert_in = _buffers(buf.reshape(e, cap, d))
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"]))
         * jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"]))
    expert_out = _buffers(jnp.einsum("ecf,efd->ecd", _buffers(h),
                                     params["w_down"]))
    flat_out = expert_out.reshape(e * cap, d)
    contrib = jnp.where(keep[:, None],
                        flat_out[slot] * pg[:, None].astype(xt.dtype), 0.0)
    y = jnp.zeros((t, d), xt.dtype).at[pt].add(contrib)
    if cfg.n_shared_experts:
        y = y + (jax.nn.silu(xt @ params["ws_gate"])
                 * (xt @ params["ws_up"])) @ params["ws_down"]
    return y.reshape(b, s, d)


# ---------------------------------------------------------------------------
# expert-parallel dispatch via explicit all_to_all (hillclimb, EXPERIMENTS.md
# §Perf): the jnp scatter above lets GSPMD close the token→expert movement
# with full-buffer all-reduces over the model axis (O(E·cap·d) per layer!).
# The shard_map form moves exactly the routed tokens twice: send + return.
# ---------------------------------------------------------------------------

def moe_ffn_a2a(params, x, cfg):
    """Expert-parallel MoE with manual all_to_all over the ``model`` axis.

    Requires an active mesh with E % model == 0; falls back to moe_ffn
    otherwise (CPU tests).  Tokens stay sharded (pod, data)×batch and
    model×sequence exactly like the residual stream, so entering/leaving the
    shard_map needs no resharding.  Gate observation (`observe_gates`)
    covers only the fallback path — callbacks inside the shard_map body
    would serialise the all-to-all.
    """
    from repro.models import shardings as SH
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = SH.current_mesh()
    if mesh is None:
        return moe_ffn(params, x, cfg)
    sizes = dict(mesh.shape)
    m = sizes.get("model", 1)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    fs = SH.fsdp_axes(mesh.axis_names)
    dsz = 1
    for a in fs:
        dsz *= sizes[a]
    if m == 1 or e % m or s % m or b % dsz:
        return moe_ffn(params, x, cfg)
    e_loc = e // m
    t_loc = (b // dsz) * (s // m)
    # per-(source-shard → dest-shard) expert capacity
    cap = max(8, int(np.ceil(t_loc * k * cfg.capacity_factor / e)))

    def body(xb, router, w_gate, w_up, w_down):
        # xb: (b/dsz, s/m, d); expert stacks: (e_loc, d, f)
        bl, sl, _ = xb.shape
        xt = xb.reshape(bl * sl, d)
        logits = (xt @ router).astype(jnp.float32)            # (t_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
        pair_e = gate_idx.reshape(-1)
        pair_t = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
        pair_g = gate_vals.reshape(-1)
        order = jnp.argsort(pair_e)
        pe, pt, pg = pair_e[order], pair_t[order], pair_g[order]
        first = jnp.searchsorted(pe, jnp.arange(e), side="left")
        pos = jnp.arange(t_loc * k) - first[pe]
        keep = pos < cap
        slot = jnp.where(keep, pe * cap + pos, 0)
        val = jnp.where(keep[:, None], xt[pt], 0.0)
        send = jnp.zeros((e * cap, d), xt.dtype).at[slot].add(val)
        send = send.reshape(m, e_loc * cap, d)
        # exchange: dest shard j receives every source's block j
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (m_src, e_loc*cap, d) → experts see m_src×cap rows each
        expert_in = (recv.reshape(m, e_loc, cap, d)
                     .transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d))
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate))
             * jnp.einsum("ecd,edf->ecf", expert_in, w_up))
        out = jnp.einsum("ecf,efd->ecd", h, w_down)
        back = (out.reshape(e_loc, m, cap, d)
                .transpose(1, 0, 2, 3).reshape(m, e_loc * cap, d))
        ret = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        flat = ret.reshape(e * cap, d)
        contrib = jnp.where(keep[:, None],
                            flat[slot] * pg[:, None].astype(xt.dtype), 0.0)
        y = jnp.zeros((t_loc, d), xt.dtype).at[pt].add(contrib)
        return y.reshape(bl, sl, d)

    bspec = fs if len(fs) > 1 else fs[0]
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(bspec, "model", None), P(None, None),
                             P("model", None, None), P("model", None, None),
                             P("model", None, None)),
                   out_specs=P(bspec, "model", None),
                   check_vma=False)
    y = fn(x, params["router"].astype(x.dtype), params["w_gate"],
           params["w_up"], params["w_down"])
    if cfg.n_shared_experts:
        xt = x.reshape(b * s, d)
        y = y + ((jax.nn.silu(xt @ params["ws_gate"])
                  * (xt @ params["ws_up"])) @ params["ws_down"]) \
            .reshape(b, s, d)
    return y


# ---------------------------------------------------------------------------
# KaHIP-driven expert placement
# ---------------------------------------------------------------------------

def coactivation_graph(gate_idx: np.ndarray, n_experts: int,
                       load: Optional[np.ndarray] = None):
    """Build the expert co-activation graph from routing decisions.

    gate_idx: (T, k) int — per token, its routed experts.  Edge (a, b) weight
    = number of tokens routed to both a and b; node weight = expert load.
    """
    from repro.core.csr import Graph
    t, k = gate_idx.shape
    cnt = np.zeros((n_experts, n_experts), dtype=np.int64)
    for i in range(k):
        for j in range(i + 1, k):
            np.add.at(cnt, (gate_idx[:, i], gate_idx[:, j]), 1)
    cnt = cnt + cnt.T
    if load is None:
        load = np.bincount(gate_idx.reshape(-1), minlength=n_experts)
    u, v = np.triu_indices(n_experts, 1)
    w = cnt[u, v]
    keep = w > 0
    return Graph.from_edges(n_experts, u[keep], v[keep], w[keep],
                            vwgt=np.maximum(load, 1))


def expert_placement(gate_idx: np.ndarray, n_experts: int, n_shards: int,
                     seed: int = 0) -> np.ndarray:
    """Partition experts into shards (node+edge balanced KaFFPa, §1) and
    return a permutation: perm[new_slot] = old_expert_id, where slots are
    contiguous per shard."""
    from repro.core.kaffpa import kaffpa
    g = coactivation_graph(gate_idx, n_experts)
    part = kaffpa(g, n_shards, 0.03, "fast", seed=seed, balance_edges=True,
                  enforce_balance=False)
    per = n_experts // n_shards
    # exact-size packing: overflow experts spill to underfull shards
    order = []
    buckets = [list(np.flatnonzero(part == s)) for s in range(n_shards)]
    spill = []
    for s in range(n_shards):
        if len(buckets[s]) > per:
            spill.extend(buckets[s][per:])
            buckets[s] = buckets[s][:per]
    for s in range(n_shards):
        while len(buckets[s]) < per and spill:
            buckets[s].append(spill.pop())
        order.extend(buckets[s])
    return np.asarray(order, dtype=np.int64)


def place_experts(params: dict, perm: np.ndarray) -> dict:
    """Apply a placement permutation to the stacked expert weights + router."""
    out = dict(params)
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = params[k][perm]
    out["router"] = params["router"][:, perm]
    return out
