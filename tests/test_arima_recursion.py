"""The ARIMA residual recursion runs once over time for all refit
groups of a slab of series (ops/arima.py `_css_reads`): its forecasts
against a plain numpy walk that runs one sequential recursion per
(series, group); what the traced program holds and how often its loop
turns at the benchmark's shape (traced, never run); and the slab rule.

The suite runs in float64; program and walk do the same arithmetic in
another order (the walk's fits come from running sums), so forecasts
agree to REL relative."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import arima_reference as ref
from theia_tpu.ops import arima
from theia_tpu.ops.arima import arima_scores, arima_walk_forward

REL = 1e-9


def series(n_series, n_steps, seed):
    """Box-Cox-scale series: a slow walk, noise, a spike now and then;
    row 1 with a masked tail, row 2 of 4 points."""
    rng = np.random.default_rng(seed)
    y = (rng.normal(0, 0.02, (n_series, n_steps)).cumsum(1)
         + rng.normal(0, 0.05, (n_series, n_steps)))
    y = np.where(rng.random(y.shape) < 0.02, y + 3.0, y)
    mask = np.ones(y.shape, bool)
    mask[1, max(5, n_steps * 2 // 3):] = False
    mask[2, 4:] = False
    return np.where(mask, y, 0.0), mask


def plain_walk(y, k):
    """Forecasts of one series y [n]: for every group its own fit and
    its own recursion from eps_0 = 0 up to the last step it reads."""
    n = len(y)
    d = np.diff(y)
    pred = y.copy()
    groups = np.arange(-(-n // k))
    phis, thetas = ref.fits(d[None, :], np.maximum(groups * k, 3) - 2)
    for g in groups:
        phi, theta = phis[0, g], thetas[0, g]
        eps = np.zeros(n - 1)
        for t in range(1, min(g * k + k - 2, n - 1)):
            eps[t] = d[t] - phi * d[t - 1] - theta * eps[t - 1]
        for m in range(max(g * k, 3), min(g * k + k, n)):
            pred[m] = y[m - 1] + phi * d[m - 2] + theta * eps[m - 2]
    return pred


@pytest.mark.parametrize("n_steps,k", [
    (37, 1), (300, 4), (512, 21), (4200, 2),
    (1000, 21),          # T not a multiple of k
    (10, 21),            # T < k: one group, fitted on 3 points
    (700, 128),          # k beyond a block: two blocks a group
    (300, 97),           # k with no divisor up to a block: one step a turn
])
def test_forecasts_are_a_plain_walks(n_steps, k):
    y, mask = series(3, n_steps, seed=n_steps + k)
    pred = np.asarray(arima_walk_forward(y, mask, refit_every=k))
    np.testing.assert_array_equal(pred[:, :3], y[:, :3])
    for s, n in enumerate(mask.sum(1)):
        np.testing.assert_allclose(pred[s, :n], plain_walk(y[s, :n], k),
                                   rtol=REL, atol=REL)
    assert (mask.sum(1) == [n_steps, max(5, n_steps * 2 // 3), 4]).all()
    n_slabs, slab, turns, block = arima.css_plan(3, n_steps, k)
    assert (n_slabs, slab) == (1, 3) and k % block == 0
    assert block == {128: 64, 97: 1}.get(k, k)
    assert turns == -(-n_steps // k) * (k // block) \
        == arima.css_loop_iterations(3, n_steps, k)


# -- what the traced program holds ---------------------------------------

def _inner(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def walk(jaxpr, scope=""):
    """(scope, equation) of every equation, sub-programs included; an
    equation's scope is its own name stack under its callers'."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield here, eqn
        for sub in _inner(eqn):
            yield from walk(sub, here)


def css_loops(jaxpr, scope="", turns=1):
    """Total trip count of each innermost loop under scope `css`: its
    own length times those of the loops around it."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        assert eqn.primitive.name != "while" or "css" not in here
        loop = eqn.primitive.name == "scan"
        n = turns * (eqn.params["length"] if loop and "css" in here
                     else 1)
        below = [t for sub in _inner(eqn)
                 for t in css_loops(sub, here, n)]
        found += below or ([n] if loop and "css" in here else [])
    return found


def traced(n_series, n_steps, k, fn=arima_scores):
    """The program as the chip gets it: float32, and no float64 for
    the Box-Cox grid to promote it to."""
    with jax.enable_x64(False):
        return jax.make_jaxpr(functools.partial(fn, refit_every=k))(
            jax.ShapeDtypeStruct((n_series, n_steps), jnp.float32),
            jax.ShapeDtypeStruct((n_series, n_steps), jnp.bool_)).jaxpr


def test_the_program_at_the_cells_shape_holds_no_stack_and_one_loop():
    """f32[20, 43200] at k = 21, traced only: outside the fits (scope
    `fit`, whose masked sums over [S, chunk, T-1] are why they are
    chunked) and Box-Cox's likelihood over its grid of 161 lambdas
    (scope `boxcox`, [161, S, T] as traced, reduced as it is made), no
    value is larger than one padded [S, T] array, where a chunk's stack
    of residuals was [T-1, S, 77], 266 MB; and the recursion is one
    loop of as many turns as the module says."""
    S, T, k = 20, 43200, 21
    jaxpr = traced(S, T, k)
    largest, fit_largest = 0, 0
    for scope, eqn in walk(jaxpr):
        for var in eqn.outvars:
            size = int(np.prod(getattr(var.aval, "shape", ())))
            if "fit" in scope:
                fit_largest = max(fit_largest, size)
            elif "boxcox" not in scope:
                largest = max(largest, size)
                assert size <= S * (T + 2 * k), (
                    scope, eqn.primitive.name, var.aval.shape)
    assert largest >= S * T
    assert fit_largest == S * 77 * (T - 1)           # unchanged
    assert css_loops(jaxpr) == [2058] \
        == [arima.css_loop_iterations(S, T, k)]
    assert arima.css_plan(S, T, k) == (1, 20, 2058, 21)
    assert {var.aval.dtype for _, eqn in walk(jaxpr)
            for var in eqn.outvars} <= {
        np.dtype(t) for t in (np.float32, np.int32, np.bool_)}


@pytest.mark.parametrize("shape,plan", [
    # the REST default: k = 1, every step a group
    ((8000, 128, 1), (1, 8000, 128, 1)),
    # BASELINE.json's 10,000 x 24 h at the auto cadence 42: 20.6 M
    # elements of carry, five slabs
    ((10000, 86400, 42), (5, 2000, 2058, 42)),
    ((16, 1 << 20, 512), (1, 16, 2048 * 8, 64)),
    ((3, 1 << 23, 1), (3, 1, 1 << 23, 1)),     # one series' carry too long
])
def test_the_plan_follows_from_the_shape(shape, plan):
    assert arima.css_plan(*shape) == plan
    assert arima.css_loop_iterations(*shape) == plan[0] * plan[2]


# -- the slab rule --------------------------------------------------------

def test_slabs_of_series_give_one_slabs_forecasts(monkeypatch):
    S, T, k = 7, 300, 4
    y, mask = series(S, T, seed=3)
    one = np.asarray(arima_walk_forward(y, mask, refit_every=k))
    assert arima.css_plan(S, T, k)[0] == 1
    monkeypatch.setattr(arima, "CSS_CARRY_ELEMENTS", 2 * 75)
    arima_walk_forward.clear_cache()
    try:
        assert arima.css_plan(S, T, k) == (4, 2, 75, 4)
        many = np.asarray(arima_walk_forward(y, mask, refit_every=k))
        assert css_loops(traced(S, T, k, arima_walk_forward)) \
            == [4 * 75] == [arima.css_loop_iterations(S, T, k)]
    finally:
        monkeypatch.undo()
        arima_walk_forward.clear_cache()
    np.testing.assert_array_equal(many, one)


def test_sharded_over_8_devices_equals_one_device_bit_for_bit(
        eight_devices):
    """Each device runs the recursion over its own slab of series; a
    series' forecast is the same sequence of multiply-adds wherever it
    runs."""
    from theia_tpu.parallel import (make_mesh, make_sharded_arima,
                                    shard_arrays)
    mesh = make_mesh(8, time_shards=1)
    rng = np.random.default_rng(8)
    x = 1e7 * np.clip(rng.normal(1, 0.05, (16, 90)), 0.1, None)
    x[3, 40] *= 50
    mask = np.ones(x.shape, bool)
    mask[5, 60:] = False
    x = np.where(mask, x, 0.0)
    for k in (1, 4):
        calc, std, anom = make_sharded_arima(mesh, refit_every=k)(
            *shard_arrays(mesh, x, mask))
        want = arima_scores(x, mask, refit_every=k)
        for got, ref_ in zip((calc, std, anom), want):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(ref_))
