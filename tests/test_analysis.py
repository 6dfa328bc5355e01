"""`repro.analysis` — the jaxpr contract checkers (DESIGN.md §14).

Each checker gets a deliberately-broken fixture it must flag (unbucketed
batch, padding vertex force-moved into balance totals, shard-varying Φ
consumed as replicated, callback in a scan body, weak-typed carry), plus a
clean-tree regression: the full registry must produce zero findings above
the committed baseline.  The pin tests at the bottom anchor the real
violations this PR fixed (weak `jnp.inf` scan carries, the fori_loop
weak-int carry inside the Pallas kernels, position-dependent tie-break
noise in `_segment_affinity`).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.analysis import (analyze, analyze_entry, default_registry,
                            load_baseline, partition_by_baseline,
                            write_findings_jsonl)
from repro.analysis import checkers, lint, padding as padmod, spmd, tracing
from repro.analysis.findings import Finding
from repro.analysis.registry import (DRIVER_ENTRIES, EntryPoint, PaddingSpec,
                                     _perturb_coo, _ring_graph)


def _codes(findings):
    return sorted(f.code for f in findings)


def _entry(name, fn, args, tags, **kw):
    return EntryPoint(name=name, build=lambda: (fn, args),
                      tags=frozenset(tags), **kw)


# ---------------------------------------------------------------------------
# bucket checker
# ---------------------------------------------------------------------------

def test_bucket_flags_unbucketed_batch():
    """A vmapped scan over a non-pow2 batch dim violates DESIGN §12."""
    x = np.ones((3, 8), np.float32)       # batch 3: not a pow2 bucket

    def fn(x):
        def body(c, row):
            return c + row.sum(), None
        return jax.vmap(lambda r: jax.lax.scan(
            body, jnp.float32(0.0), r[:, None])[0])(x)

    e = _entry("fixture/unbucketed", fn, (x,), {"bucket"},
               bucket_dims=lambda args: {"batch": args[0].shape[0],
                                         "cols": args[0].shape[1]})
    found = analyze_entry(e)
    assert "non-pow2-dim" in _codes(found)
    assert any(f.detail == {"dim": "batch", "size": 3} for f in found)


def test_bucket_program_registry_cross_check():
    bad = checkers.check_program_registry(
        [("kway", 100, 256, 2, 8, 3, False)])
    assert _codes(bad).count("non-pow2-signature-field") == 2  # 100 and 3
    # two distinct signatures at one bucket projection: recompile hazard
    coll = checkers.check_program_registry(
        [("kway", 128, 256, 2, 8, 4, False),
         ("kway", 100, 256, 2, 8, 3, False)])
    assert "bucket-collision" in _codes(coll)
    # identical pow2 signatures share one program: clean
    ok = checkers.check_program_registry(
        [("kway", 128, 256, 2, 8, 4, False),
         ("hyper", 128, 128, 256, 4, 6, "km1", 2, False),
         ("sep", 256, 256, 6, 2, False)])
    assert ok == []


# ---------------------------------------------------------------------------
# padding-inertness checker
# ---------------------------------------------------------------------------

def _broken_refine_entry():
    """The PR-7 bug class, seeded deliberately: balance totals count
    *vertices* instead of vertex weight (so zero-weight padding rows enter
    the totals) and the overweight push lacks the ``vw > 0`` gate (so
    padding vertices are force-moved)."""
    from repro.core.csr import to_coo
    g = _ring_graph()
    coo = to_coo(g)
    n = g.n
    labels0 = (np.arange(coo.n_pad) % 2).astype(np.int32)

    def fn(coo, labels0):
        k = 2

        def body(labels, _):
            # BUG: .add(1.0) counts padding vertices into balance totals
            sizes = jnp.zeros((k,), jnp.float32).at[labels].add(1.0)
            aff = jnp.zeros((coo.n_pad, k), jnp.float32).at[
                coo.src, labels[coo.dst]].add(coo.w)
            own = jnp.take_along_axis(
                aff, labels[:, None].astype(jnp.int32), 1)[:, 0]
            gain = aff - own[:, None]
            gain = gain.at[jnp.arange(coo.n_pad), labels].set(-1e30)
            best = jnp.argmax(gain, 1).astype(labels.dtype)
            # BUG: force-move from the overweight block without vw > 0
            over = sizes[labels] > sizes.sum() / k
            return jnp.where(over, best, labels), None

        labels, _ = jax.lax.scan(body, labels0, None, length=3)
        sizes = jnp.zeros((2,), jnp.float32).at[labels].add(1.0)
        return labels, sizes

    def perturb(args, rng):
        coo, labels = args
        labs = np.array(labels)
        labs[n:] = rng.integers(0, 2, size=labs[n:].shape, dtype=labs.dtype)
        return (_perturb_coo(coo, rng), labs)

    return _entry("fixture/padding_force_move", fn, (coo, labels0),
                  {"padding"},
                  padding=PaddingSpec(
                      perturb, lambda outs: [np.asarray(outs[0])[:n],
                                             np.asarray(outs[1])]))


def test_padding_flags_force_moved_padding_vertex():
    found = analyze_entry(_broken_refine_entry())
    assert "padding-flows-into-output" in _codes(found)


# ---------------------------------------------------------------------------
# SPMD replication checker
# ---------------------------------------------------------------------------

def _phi_entry(reduce_phi: bool):
    """A miniature parhyp Φ histogram round.  With ``reduce_phi=False`` the
    per-shard partial is returned through ``out_specs=P()`` — claimed
    replicated while still shard-varying (check_vma=False hides it from
    jax itself)."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("nets",))
    pv = np.zeros((1, 8), np.int32)
    pe = np.zeros((1, 8), np.int32)
    mask = np.ones((1, 8), np.float32)
    labels = np.zeros(16, np.int32)

    def local(pv, pe, mask, labels):
        cnt = jnp.zeros((4, 2), jnp.float32).at[
            pe.reshape(-1), labels[pv.reshape(-1)]].add(mask.reshape(-1))
        if reduce_phi:
            cnt = jax.lax.psum(cnt, "nets")
        return cnt

    def fn(pv, pe, mask, labels):
        return shard_map(local, mesh=mesh,
                         in_specs=(P("nets", None), P("nets", None),
                                   P("nets", None), P()),
                         out_specs=P(), check_vma=False)(pv, pe, mask,
                                                         labels)

    return _entry(f"fixture/phi_{reduce_phi}", fn, (pv, pe, mask, labels),
                  {"spmd"})


def test_spmd_flags_unreduced_phi_as_replicated():
    found = analyze_entry(_phi_entry(reduce_phi=False))
    assert "varying-as-replicated" in _codes(found)
    assert any(f.detail["varying"] == ["nets"] for f in found)


def test_spmd_accepts_psummed_phi():
    assert analyze_entry(_phi_entry(reduce_phi=True)) == []


def test_spmd_axis_index_introduces_varyingness():
    mesh = Mesh(np.array(jax.devices()[:1]), ("islands",))

    def fn(x):
        def local(x):
            return x.sum() + jax.lax.axis_index("islands").astype(jnp.float32)
        return shard_map(local, mesh=mesh, in_specs=P("islands"),
                         out_specs=P(), check_vma=False)(x)

    found = analyze_entry(_entry("fixture/axis_index", fn,
                                 (np.ones(4, np.float32),), {"spmd"}))
    assert "varying-as-replicated" in _codes(found)


# ---------------------------------------------------------------------------
# purity / dtype hygiene checker
# ---------------------------------------------------------------------------

def _callback_entry(allow=()):
    def fn(x):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c + x.sum(), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=4)
        return out

    return _entry("fixture/callback", fn, (np.ones(3, np.float32),),
                  {"hygiene"}, allow_callbacks=allow)


def test_hygiene_flags_callback_in_scan_body():
    found = analyze_entry(_callback_entry())
    assert "callback-in-loop" in _codes(found)


def test_hygiene_allowlist_admits_observe_gates_style_tap():
    found = analyze_entry(_callback_entry(allow=("debug_callback",)))
    assert "callback-in-loop" not in _codes(found)


def test_hygiene_flags_weak_carry():
    def fn(x):
        def body(c, _):
            return (c[0] + 1, jnp.minimum(c[1], 0.5)), None
        (a, b), _ = jax.lax.scan(body, (jnp.int32(0), jnp.inf), None,
                                 length=3)
        return a, b + x.sum()

    found = analyze_entry(_entry("fixture/weak", fn,
                                 (np.ones(3, np.float32),), {"hygiene"}))
    assert "weak-carry" in _codes(found)


# ---------------------------------------------------------------------------
# lints
# ---------------------------------------------------------------------------

def test_host_sync_lint(tmp_path):
    bad = tmp_path / "glue.py"
    bad.write_text(
        "_HOST_SYNC_OK = (\"designed\",)\n"
        "def hot(xs):\n"
        "    total = 0\n"
        "    for x in xs:\n"
        "        total += x.item()\n"
        "        y = np.asarray(x)\n"
        "    return total\n"
        "def designed(x):\n"
        "    return int(np.asarray(x))\n")
    found = lint.check_host_sync(serve_dir=str(tmp_path))
    codes = _codes(found)
    assert "sync-item" in codes
    assert "materialize-in-loop" in codes
    # the allowlisted designed sync point (line 9) is not flagged
    assert not any(f.location.endswith(":9") for f in found)


def test_serve_tree_passes_host_sync_lint():
    assert lint.check_host_sync() == []


def test_driver_registry_lint_clean_and_complete():
    assert lint.check_driver_registry() == []
    # every mapped entry must exist in the registry
    reg = default_registry()
    for entries in DRIVER_ENTRIES.values():
        for name in entries:
            assert name in reg


def test_driver_registry_lint_flags_unregistered_driver():
    incomplete = {k: v for k, v in DRIVER_ENTRIES.items() if k != "kaffpa"}
    found = lint.check_driver_registry(driver_entries=incomplete)
    assert any(f.code == "driver-unregistered" and f.entry == "kaffpa"
               for f in found)


# ---------------------------------------------------------------------------
# findings plumbing: JSONL obs-compat, baseline gate, counters
# ---------------------------------------------------------------------------

def test_findings_jsonl_readable_by_obs(tmp_path):
    f1 = Finding(checker="bucket", severity="error", entry="e", code="c",
                 location="l", message="m", detail={"x": 1})
    f2 = Finding(checker="spmd", severity="warning", entry="e2", code="c2",
                 location="l2", message="m2")
    path = str(tmp_path / "findings.jsonl")
    write_findings_jsonl(path, [f1, f2])
    headers, events = obs.read_jsonl(path)
    assert headers[0]["name"] == "analysis"
    assert headers[0]["counters"] == {"analysis/bucket": 1,
                                      "analysis/spmd": 1}
    assert [e["key"] for e in events] == [f1.key, f2.key]
    assert events[0]["severity"] == "error"


def test_baseline_partition(tmp_path):
    f = Finding(checker="bucket", severity="error", entry="e", code="c",
                location="l", message="m")
    base = tmp_path / "base.json"
    base.write_text(json.dumps(
        {"version": 1, "allow": [{"key": f.key, "reason": "known"}]}))
    new, allowed = partition_by_baseline([f], load_baseline(str(base)))
    assert new == [] and allowed == [f]
    new2, _ = partition_by_baseline([f], load_baseline(str(base) + ".nope"))
    assert new2 == [f]


def test_analyze_increments_obs_counters():
    before = obs.metrics.get("analysis/violations")
    found = analyze(entries=["kernels/ssd_scan"], lints=False,
                    program_registry=False)
    assert found == []
    # clean entry: counter unchanged; broken fixture path covered above
    assert obs.metrics.get("analysis/violations") == before
    reg = {"fixture/callback": _callback_entry()}
    found = analyze(entries=["fixture/callback"], registry=reg,
                    lints=False, program_registry=False)
    assert found
    assert obs.metrics.get("analysis/violations") > before
    assert obs.metrics.get("analysis/hygiene") >= 1


# ---------------------------------------------------------------------------
# clean-tree regression + pins for the violations fixed in this PR
# ---------------------------------------------------------------------------

def test_clean_tree_zero_findings_above_baseline():
    """The acceptance gate, in-process: every registered entry point plus
    the lints produce no findings beyond ANALYSIS_BASELINE.json."""
    findings = analyze()
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    baseline = load_baseline(os.path.join(root, "ANALYSIS_BASELINE.json"))
    new, _ = partition_by_baseline(findings, baseline)
    assert new == [], [f.key for f in new]


def test_pin_no_weak_carry_in_hyper_refine():
    """Pins the jnp.float32(jnp.inf) fix in hypergraph/refine.py and
    hypergraph/dist.py: weak f32 carries came from bare jnp.inf."""
    reg = default_registry()
    for name in ("engine/hyper_refine_km1", "dist/parhyp_round"):
        found = analyze_entry(reg[name])
        assert not [f for f in found if f.code == "weak-carry"], name


def test_pin_no_weak_carry_in_pallas_kernels():
    """Pins the fori_loop → strong-counter-scan fix in kernels/: the
    python-int fori_loop bounds seeded a weak int32 carry."""
    reg = default_registry()
    for name in ("kernels/lp_affinity", "kernels/pin_count",
                 "engine/kway_refine_kernel"):
        found = analyze_entry(reg[name])
        assert not [f for f in found if f.code == "weak-carry"], name


def test_pin_cluster_lp_padding_inert():
    """Pins the _segment_affinity fix: tie-break noise is now drawn per
    original edge id and zeroed on padding edges, so garbage in zero-weight
    edges cannot perturb real clustering decisions."""
    reg = default_registry()
    assert analyze_entry(reg["engine/cluster_lp"]) == []


def test_pin_cluster_lp_noise_still_tiebreaks():
    """The fix must not have killed the tie-break: two runs with different
    keys still produce valid (and generally different) clusterings."""
    from repro.core import lp as L
    from repro.core.csr import to_coo
    g = _ring_graph()
    coo = to_coo(g)
    labs = np.arange(coo.n_pad, dtype=np.int32)
    cap = np.full(coo.n_pad, 6.0 * g.n, np.float32)
    out1, _ = L._cluster_lp(coo, jnp.asarray(labs), jnp.asarray(cap),
                            jax.random.PRNGKey(0), 4)
    out1 = np.asarray(out1)[:g.n]
    # every vertex joined a cluster led by a real vertex
    assert out1.min() >= 0 and out1.max() < coo.n_pad
    # clustering is non-trivial: fewer clusters than vertices
    assert len(np.unique(out1)) < g.n
