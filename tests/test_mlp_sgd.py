"""Local SGD on the flat weight vector (`mlp_mnist.sgd_steps_flat`).

The fleet engine trains each agent's row of the (A, N) weight matrix. The
row is unflattened once, the parameter tree is carried through the step
scan, and the result is flattened once: the flat<->tree re-layouts are paid
once a call, whatever the step count, and the answer equals the tree scan of
`sgd_steps` to the bit.
"""

import jax
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.flatten_util import ravel_pytree

from repro.core.partition import flatten_params, unflatten_params
from repro.models import mlp_mnist

LR = 0.05
BATCH = 16
AGENTS = 4


@pytest.fixture(scope="module")
def setup():
    w, layout = flatten_params(mlp_mnist.init_params(0))
    layout_t = tuple((name, tuple(shape)) for name, shape in layout)
    rng = np.random.default_rng(1)
    W = np.stack([w + 0.01 * rng.standard_normal(w.shape).astype(np.float32)
                  for _ in range(AGENTS)])
    X = rng.standard_normal((AGENTS, BATCH, 784)).astype(np.float32)
    Y = rng.integers(0, 10, size=(AGENTS, BATCH)).astype(np.int32)
    return W, X, Y, layout_t


def _tree_reference(w, x, y, num_iters, layout):
    """flatten(sgd_steps(unflatten(w))), flattened by `ravel_pytree`."""
    params = mlp_mnist.sgd_steps(unflatten_params(w, layout), x, y, LR, num_iters)
    return ravel_pytree(params)[0]


def _flat(num_iters, layout):
    return lambda w, x, y: mlp_mnist.sgd_steps_flat(w, x, y, LR, num_iters, layout)


def test_ravel_order_is_layout_order(setup):
    params = mlp_mnist.init_params(3)
    np.testing.assert_array_equal(ravel_pytree(params)[0], flatten_params(params)[0])


@pytest.mark.parametrize("num_iters", [1, 10])
def test_flat_equals_tree_scan_unbatched(setup, num_iters):
    W, X, Y, layout = setup
    got = _flat(num_iters, layout)(W[0], X[0], Y[0])
    want = jax.jit(_tree_reference, static_argnums=(3, 4))(W[0], X[0], Y[0], num_iters, layout)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), W[0])


@pytest.mark.parametrize("num_iters", [1, 10])
def test_flat_equals_tree_scan_vmapped(setup, num_iters):
    W, X, Y, layout = setup
    got = jax.vmap(_flat(num_iters, layout))(W, X, Y)
    want = jax.jit(jax.vmap(lambda w, x, y: _tree_reference(w, x, y, num_iters, layout)))(
        W, X, Y
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(j, ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, Jaxpr):
                yield j


def _eqns(jaxpr, into_scans):
    """Every equation of `jaxpr` and its sub-jaxprs; a scan's body is
    entered only if `into_scans`."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "scan" and not into_scans:
            continue
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub, into_scans)


def _scans(jaxpr):
    return [e for e in _eqns(jaxpr, into_scans=False) if e.primitive.name == "scan"]


def _vmapped_jaxpr(setup, num_iters):
    W, X, Y, layout = setup
    return jax.make_jaxpr(jax.vmap(_flat(num_iters, layout)))(W, X, Y).jaxpr


@pytest.mark.parametrize("num_iters", [1, 10])
def test_scan_body_never_touches_the_flat_vector(setup, num_iters):
    """The step scan carries the tree: no equation in its body slices,
    reshapes or concatenates an array of the flat vector's length."""
    W = setup[0]
    n = W.shape[1]
    (scan,) = _scans(_vmapped_jaxpr(setup, num_iters))
    assert scan.params["length"] == num_iters
    for eqn in _eqns(scan.params["jaxpr"].jaxpr, into_scans=True):
        assert eqn.primitive.name != "concatenate"
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape")]
        assert not any(n in s for s in shapes), (eqn.primitive.name, shapes)


def test_conversions_once_per_call(setup):
    """The flat<->tree re-layouts sit outside the scan, so their count does
    not grow with the step count: at least one reshape into each 2-D leaf
    and one back, for 1 step as for 10."""
    layout = setup[3]
    matrices = sum(len(shape) == 2 for _, shape in layout)

    def outside(num_iters):
        return sorted(
            e.primitive.name
            for e in _eqns(_vmapped_jaxpr(setup, num_iters), into_scans=False)
            if e.primitive.name in ("reshape", "slice", "concatenate")
        )

    once, ten = outside(1), outside(10)
    assert once == ten
    assert once.count("reshape") >= 2 * matrices
    assert once.count("concatenate") == 1
