"""Frontier-proportional sweeps (DESIGN.md §10): masked ≡ full, bit-for-bit.

Property suite for the change-propagation update path: on random
connected graphs with random mixed batches (insert / delete /
re-weight, weighted and unweighted), an engine with frontier tracking
on must produce *exactly* the labelling of the full-sweep reference —
same planes, same affected set — on both backends. The density
threshold is swept across its boundary behaviours: a threshold so small
that every wave overflows ``rows_cap`` and takes the full-sweep
fallback branch, the default 0.25, and 1.0 (the masked branch whenever
the frontier is nonempty). Bit-identity is the whole contract — the
frontier is a performance mode, never an approximation — so every
assertion here is exact array equality, not allclose.

The pipelined chunked updater is checked the same way, its chunk
programs threading the bitmap across dispatches; a slow-marked
subprocess repeats that on a forced 8-device host mesh (the
`shard_*` chunk twins), against the unsharded full-sweep reference.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # optional dep: the drawn-case layer; the seeded grid always runs
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.graphs import generators as gen
from repro.graphs.coo import apply_batch, from_edges, make_batch
from repro.core.batch import batchhl_update
from repro.core.construct import build_labelling, select_landmarks_by_degree
from repro.core.engine import RelaxEngine
from repro.core.snapshot import (Snapshot, pipelined_update,
                                 run_pipelined_update)

BACKENDS = ("jnp", "pallas")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True, scope="module")
def _fresh_compile_caches():
    """The parity grid compiles many frontier fixpoints (per backend ×
    threshold × batch mix). Bracket the module with cache drops — same
    hygiene as test_weighted.py — so those executables neither sit on a
    few hundred accumulated ones nor stay live under the rest of the
    suite (the single XLA CPU client has segfaulted a later shard_map
    compile when the process-wide executable count climbed too far)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _assert_same(ref, got, context):
    g_ref, lab_ref, aff_ref = ref
    g_got, lab_got, aff_got = got
    np.testing.assert_array_equal(np.asarray(aff_ref), np.asarray(aff_got),
                                  err_msg=f"aff {context}")
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(
            np.asarray(getattr(lab_ref, f)), np.asarray(getattr(lab_got, f)),
            err_msg=f"{f} {context}")


def _one_tick(g, batch, lab, g_next, engine):
    plan = engine.prepare(g_next) if engine is not None else None
    return batchhl_update(g, batch, lab, plan=plan, g_new=g_next)


def _check_case(backend, n, seed, n_ins, n_del, n_rew, max_w, threshold,
                improved, pipelined=False):
    edges = gen.random_connected(n, extra_edges=n // 2, seed=seed)
    g = from_edges(n, edges, edges.shape[0] + 16)
    lab = build_labelling(g, select_landmarks_by_degree(g, 3))
    ups = gen.random_batch_updates(edges, n, n_ins, n_del, seed=seed + 1,
                                   n_rew=n_rew, max_weight=max_w)
    batch = make_batch(ups, pad_to=max(len(ups), 1) + 2)
    if not ups:  # all-padding batch: a no-op update
        batch = dataclasses.replace(batch, valid=jnp.zeros_like(batch.valid))
    g_next = apply_batch(g, batch)

    ref_engine = (None if backend == "jnp"
                  else RelaxEngine(backend="pallas", block_v=16))
    ref = batchhl_update(g, batch, lab, improved,
                         plan=(ref_engine.prepare(g_next)
                               if ref_engine else None),
                         g_new=g_next)
    fr_engine = RelaxEngine(backend=backend, block_v=16, frontier=True,
                            frontier_threshold=threshold, frontier_block=8)
    fr_plan = fr_engine.prepare(g_next)
    if pipelined:
        nxt, aff = run_pipelined_update(pipelined_update(
            Snapshot(0, g, lab, None), batch, plan=fr_plan, g_new=g_next,
            improved=improved, chunk_sweeps=2))
        got = (nxt.graph, nxt.labelling, aff)
    else:
        got = batchhl_update(g, batch, lab, improved, plan=fr_plan,
                             g_new=g_next)
    _assert_same(ref, got,
                 f"[backend={backend} th={threshold} improved={improved} "
                 f"pipelined={pipelined}]")


# Representative corners, one per row: pure inserts, pure deletes, pure
# re-weights, a weighted mixed batch, the empty batch, masked-always
# (th=1.0), and fallback-always (th=0.01). Runs in every environment —
# the hypothesis layer below widens the net when the dep is present.
CASES = [
    # (n, seed, n_ins, n_del, n_rew, max_w, threshold, improved)
    (24, 3, 3, 0, 0, 1, 0.25, True),
    (24, 4, 0, 3, 0, 1, 0.25, True),
    (24, 5, 0, 0, 2, 4, 0.25, True),
    (36, 6, 2, 2, 2, 3, 0.25, False),
    (18, 7, 0, 0, 0, 1, 0.25, True),
    (30, 8, 2, 2, 1, 2, 1.0, True),
    (30, 9, 2, 2, 1, 2, 0.01, True),
]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", CASES,
                         ids=[f"n{c[0]}s{c[1]}" for c in CASES])
def test_frontier_update_bit_identical(backend, case):
    """Masked ≡ full across mixed batches, backends, and the threshold's
    boundary behaviours (fallback-always / default / masked-always)."""
    _check_case(backend, *case)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", CASES,
                         ids=[f"n{c[0]}s{c[1]}" for c in CASES])
def test_frontier_pipelined_bit_identical(backend, case):
    """The pipelined update's chunk programs, carrying the changed-block
    bitmap across dispatches, drain to the full-sweep `batchhl_update`
    bit for bit on the same corners."""
    _check_case(backend, *case, pipelined=True)


if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("backend", BACKENDS)
    @given(data=st.data())
    @settings(deadline=None, max_examples=8,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.differing_executors])
    def test_frontier_update_bit_identical_drawn(backend, data):
        _check_case(
            backend,
            n=data.draw(st.integers(12, 36), label="n"),
            seed=data.draw(st.integers(0, 10_000), label="seed"),
            n_ins=data.draw(st.integers(0, 3), label="n_ins"),
            n_del=data.draw(st.integers(0, 3), label="n_del"),
            n_rew=data.draw(st.integers(0, 2), label="n_rew"),
            max_w=data.draw(st.integers(1, 4), label="max_weight"),
            threshold=data.draw(st.sampled_from((0.01, 0.25, 1.0)),
                                label="frontier_threshold"),
            improved=data.draw(st.booleans(), label="improved"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_threshold_fallback_boundary(backend):
    """rows_cap boundary: thresholds straddling the exact active-row
    count flip between the masked branch and the full-sweep fallback —
    both must be bit-identical to the reference (the cond is a routing
    decision, not a semantic one)."""
    n = 40
    edges = gen.random_connected(n, extra_edges=20, seed=7)
    g = from_edges(n, edges, edges.shape[0] + 16)
    lab = build_labelling(g, select_landmarks_by_degree(g, 3))
    ups = gen.random_batch_updates(edges, n, n_ins=2, n_del=2, seed=8,
                                   n_rew=1, max_weight=3)
    batch = make_batch(ups, pad_to=8)
    g_next = apply_batch(g, batch)
    ref = _one_tick(g, batch, lab, g_next,
                    None if backend == "jnp"
                    else RelaxEngine(backend="pallas", block_v=16))
    nrows = RelaxEngine(backend=backend, block_v=16, frontier=True,
                        frontier_block=8).prepare(g_next).frontier.nrows
    # One threshold per achievable rows_cap regime around the boundary:
    # cap=1 (overflow on any multi-row wave), cap≈half, cap=nrows.
    for th in (1.0 / nrows, 0.5, 1.0):
        eng = RelaxEngine(backend=backend, block_v=16, frontier=True,
                          frontier_threshold=th, frontier_block=8)
        got = _one_tick(g, batch, lab, g_next, eng)
        _assert_same(ref, got, f"[backend={backend} th={th}]")


_MESH_SCRIPT = textwrap.dedent("""
    import numpy as np, jax.numpy as jnp
    from repro.graphs import generators as gen
    from repro.graphs.coo import from_edges, make_batch, apply_batch
    from repro.core.construct import (build_labelling,
                                      select_landmarks_by_degree)
    from repro.core.engine import RelaxEngine
    from repro.core.batch import batchhl_update
    from repro.core.snapshot import (Snapshot, pipelined_update,
                                     run_pipelined_update)
    from repro.launch.mesh import make_host_mesh

    import jax
    assert len(jax.devices()) == 8, jax.devices()
    n, deg = 300, 3
    edges = gen.barabasi_albert(n, deg, seed=0)
    g = from_edges(n, edges, edges.shape[0] + 64)
    lab = build_labelling(g, select_landmarks_by_degree(g, 8))
    ups = gen.random_batch_updates(edges, n, n_ins=3, n_del=3, seed=2,
                                   n_rew=1, max_weight=3)
    batch = make_batch(ups, pad_to=8)
    g_new = apply_batch(g, batch)
    _, labref, affref = batchhl_update(g, batch, lab, True, None)
    mesh = make_host_mesh(model=2)
    for backend in ("jnp", "pallas"):
        eng = RelaxEngine(backend=backend, block_v=64, frontier=True)
        plan = eng.prepare(g_new)
        snap = Snapshot(0, g, lab, plan)
        s1, aff = run_pipelined_update(pipelined_update(
            snap, batch, plan=plan, g_new=g_new, mesh=mesh,
            improved=True, chunk_sweeps=2))
        assert bool(jnp.all(aff == affref)), backend
        for f in ("dist", "hub", "highway"):
            assert bool(jnp.all(getattr(s1.labelling, f)
                                == getattr(labref, f))), (backend, f)
    print("MESH FRONTIER PARITY OK")
""")


@pytest.mark.slow
def test_frontier_mesh_multidevice_parity(tmp_path):
    """Masked ≡ full through the sharded pipelined updater on a forced
    8-device host mesh, both backends."""
    script = tmp_path / "mesh_frontier_parity.py"
    script.write_text(_MESH_SCRIPT)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, str(script)], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MESH FRONTIER PARITY OK" in out.stdout, out.stdout
