"""chip_smoke.py off the chip: it refuses to run without a TPU, its plain
references agree with the library's metrics, and its one-chip phases pass
their own checks at small sizes on the CPU (kernels interpreted)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

SMALL = {"kaffpa_grid": 16, "kaffpa_k": 4, "hyper_scale": 11, "hyper_k": 4,
         "sep_grid": 16}


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_references_match_library_metrics():
    from repro.core.hypergraph import connectivity
    from repro.core.partition import edge_cut
    from repro.io.generators import grid2d, rmat_hypergraph
    rng = np.random.default_rng(0)
    g = grid2d(12, 9)
    part = rng.integers(0, 3, g.n)
    assert cs.ref_edge_cut(g, part) == edge_cut(g, part)
    hg = rmat_hypergraph(9, seed=1)
    part = rng.integers(0, 5, hg.n)
    assert cs.ref_km1(hg, part) == connectivity(hg, part)
    assert cs.ref_balanced(np.ones(8), np.arange(8) % 4, 4, 0.0)
    assert not cs.ref_balanced(np.ones(8), np.zeros(8, int), 4, 0.5)


def test_separator_split_rejects_a_non_separator():
    from repro.io.generators import grid2d
    g = grid2d(6, 6)
    _, ncomp, _ = cs.ref_separator_split(g, np.array([0, 7]))
    assert ncomp == 1
    part, ncomp, load = cs.ref_separator_split(g, np.arange(12, 18))
    assert ncomp == 2 and sorted(load) == [12.0, 18.0]
    assert set(part[:12]) != set(part[18:])


def test_one_chip_phases_small(monkeypatch, capsys):
    """All four phases on the chip's code path — the kernels on
    (interpreted on the CPU) and the sort-free forms; parhyp's
    device-resident floor is lowered to this small instance."""
    from repro.core import lp, refine, sorting
    from repro.core.hypergraph import dist
    monkeypatch.setattr(refine, "default_use_kernel", lambda: True)
    monkeypatch.setattr(lp, "sort_free", lambda: True)
    monkeypatch.setattr(sorting, "sort_free", lambda: True)
    monkeypatch.setattr(dist, "_DEVICE_MIN_N", 1024)
    cs.run_one_chip(SMALL)
    phases = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [p["phase"] for p in phases] == ["kaffpa", "node_separator",
                                             "kahypar", "parhyp"]
    for p in phases:
        assert p["compiles_warm"] == 0, p
        assert p["cold_s"] > 0 and p["warm_s"] > 0


def test_check_kernel_flags_a_wrong_kernel():
    import jax.numpy as jnp
    x = (jnp.arange(8, dtype=jnp.float32),)
    with pytest.raises(cs.CheckFailed):
        cs.check_kernel("wrong", lambda a: a + 1, lambda a: a, x)
