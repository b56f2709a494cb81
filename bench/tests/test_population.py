"""The population generator: components drawn by shape files found by
name, the same counts on every seed, and kinds from the configuration."""

import jax.numpy as jnp
import numpy as np
import pytest

import population
from configs import sir_measles, soma_clustering

CFG = {"agents": 1000, "space": [0.0, 100.0], "margin": 10.0,
       "kind_share": 0.5, "infected_share": 0.01}


def test_counts_take_each_share_and_the_rest():
    comps = [{"share": 0.3}, {"share": 0.3}, {"share": 0.4}]
    assert population.counts(1001, comps) == [300, 300, 401]
    with pytest.raises(ValueError):
        population.counts(10, [{"share": 0.5}])


def test_unknown_shape_is_refused():
    with pytest.raises(ValueError):
        population.shape("no_such_shape")


@pytest.mark.parametrize("traffic", [
    {"components": [{"shape": "uniform", "share": 1.0}]},
    {"components": [{"shape": "uniform", "share": 0.25},
                    {"shape": "uniform", "share": 0.75}]},
])
def test_positions_inside_and_seeded(traffic):
    a = population.agents(CFG, traffic, 2**31 + 5, soma_clustering.kinds)
    b = population.agents(CFG, traffic, 2**31 + 5, soma_clustering.kinds)
    c = population.agents(CFG, traffic, 2**31 + 6, soma_clustering.kinds)
    pos = np.asarray(a["position"])
    assert pos.shape == (1000, 3) and pos.dtype == np.float32
    assert pos.min() >= 10.0 and pos.max() <= 90.0
    assert np.array_equal(pos, np.asarray(b["position"]))
    assert not np.array_equal(pos, np.asarray(c["position"]))
    assert np.array_equal(np.asarray(a["tag"]), np.arange(1000))


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_kinds_are_exact_on_every_seed(seed):
    key = population.seed_key(seed)
    soma = np.asarray(soma_clustering.kinds(CFG, key, 1000))
    sir = np.asarray(sir_measles.kinds(CFG, key, 1000))
    assert soma.sum() == 500 and set(np.unique(soma)) == {0, 1}
    assert (sir == sir_measles.INFECTED).sum() == 10
    assert set(np.unique(sir)) == {0, sir_measles.INFECTED}
    assert jnp.asarray(sir).dtype == jnp.int32
