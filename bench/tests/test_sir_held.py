"""The measles-SIR configuration is held out of BENCHMARK.json: the
program's infection departs from the model (PERF.md, Open questions).  These
tests keep the reference's model semantics and the departure in view; when
the program is repaired, the second one fails and the cell can come back."""

import jax.numpy as jnp
import numpy as np

import reference as ref
import run as harness


def test_search_wraps_across_faces_on_a_torus():
    """Two agents 1 unit apart across the x face: close on a torus, far in
    a closed box."""
    pos = jnp.asarray([[0.5, 50.0, 50.0], [99.5, 50.0, 50.0],
                       [50.0, 50.0, 50.0]], jnp.float32)
    flag = jnp.asarray([False, True, False])
    alive = jnp.ones(3, bool)
    args = (pos, pos, flag, alive, 0.0, 100.0, 25, 3.24, jnp.float32)
    assert np.asarray(ref.any_close(*args, True)).tolist() == [True, False, False]
    assert np.asarray(ref.any_close(*args, False)).tolist() == [False] * 3


def test_program_departs_from_the_model_and_matches_its_own_search():
    cell = harness.Cell.load("sir_measles.uniform", held=True)
    r = harness.run_cell(cell, 2**31 + 21, 0.0, False, agents=4096,
                         keep_states=True)
    (before, got, want), = r["states"]
    assert r["checks"]["kind_mismatch"]["value"] > 0
    own = cell.check.compare(r["cfg"], got,
                             cell.check.witness(r["cfg"], before, 16))
    assert own["kind_mismatch"] == 0 and own["layout_mismatch"] == 0
