"""The mesh reference's rows against ``jax.grad`` of a whole row on the CPU,
for a model reference whose layers return ``x`` alone or ``(x, a)``: a
row's loss is the head's plus every layer's ``a``, each leaf's gradient the
participation-weighted sum of the rows' gradients of that loss, and an
absent row adds nothing to either; a layer that returns anything else is
refused when the programs are built."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.reference import mesh_step as ref
from benchmarks.chip.reference.lm_ops import head_loss

V, D, E, L, S = 32, 16, 4, 2, 8
EPS = 1e-6


def _toy(aux: bool):
    """A model reference of ``L`` layers, each a tanh residual step and a
    router whose softmax feeds only a balance term ``a`` per row."""

    def leaf_shapes(model):
        return {
            "embed/table": ((V, D), 0.3), "final_norm/scale": ((D,), 0.0),
            "g0/w": ((L, D, D), 0.3), "g0/router": ((L, D, E), 0.3),
            "lm_head/table": ((V, D), 0.3),
        }

    def layer_fwd(group, w, x, model, fp8=False):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        y = x + jnp.tanh(x @ w["w"])
        if not aux:
            return y
        p = jax.nn.softmax(x @ w["router"], axis=-1)
        return y, 0.1 * E * jnp.sum(jnp.mean(p, axis=0) ** 2)

    def loss(fnorm, table, x, tokens, model, fp8=False):
        return head_loss(fnorm, table, x, tokens, EPS, fp8)

    return types.SimpleNamespace(
        EMBED="embed/table", FINAL_NORM="final_norm/scale", HEAD="lm_head/table",
        leaf_shapes=leaf_shapes, groups=lambda model: [("g0", L, {"w": "g0/w", "router": "g0/router"})],
        layer_fwd=layer_fwd, loss=loss,
    )


def _whole_row(mref, p: dict, tokens):
    """A row's loss from the nested parameters ``p``, in one function."""
    x = p["embed"]["table"][tokens]
    total = 0.0
    for l in range(L):
        out = mref.layer_fwd(0, {"w": p["g0"]["w"][l], "router": p["g0"]["router"][l]}, x, {})
        x, *a = jax.tree.leaves(out)
        total = total + sum(a)
    return mref.loss(p["final_norm"]["scale"], p["lm_head"]["table"], x, tokens, {}) + total


@pytest.mark.parametrize("present", [(1.0, 1.0), (1.0, 0.0)], ids=["both", "row1_absent"])
@pytest.mark.parametrize("aux", [True, False], ids=["x_and_a", "x_only"])
def test_rows_match_grad_of_the_whole_row(aux, present):
    mref = _toy(aux)
    devices = jax.devices()[:1]
    prog = ref.RowPrograms(mref, {}, devices, fp8=False)
    # float32 leaves, so that no gradient is rounded to bfloat16 on either side
    params = jax.tree.map(lambda v: v.astype(jnp.float32), prog.init(ref.seed_key(2**31 + 5)))
    units = {}
    for name, x in ref.flatten(params).items():
        if name.startswith("g0/"):
            units.update({f"{name}#{l}": x[l] for l in range(L)})
        else:
            units[name] = x
    owner = {k: devices[0] for k in units}
    tokens = np.random.default_rng(3).integers(0, V, (2, S)).astype(np.int32)
    mask = np.asarray(present)
    wl = mask / mask.sum()
    grads, losses = ref._row_grads(prog, mref, {}, units, owner, tokens,
                                   wl[:, None].astype(np.float32), lambda name: (1, 0, 0))

    with jax.default_matmul_precision("highest"):
        want_loss = [float(_whole_row(mref, params, tokens[b])) for b in range(2)]
        row_grads = [jax.grad(_whole_row, argnums=1)(mref, params, tokens[b]) for b in range(2)]
    np.testing.assert_allclose(losses, want_loss, rtol=1e-6)
    assert np.dot(wl, losses) == pytest.approx(sum(w * l for w, l in zip(wl, want_loss)
                                                   if w), rel=1e-6)
    want = jax.tree.map(lambda *g: sum(w * x for w, x in zip(wl, g) if w), *row_grads)
    for name, g in ref.flatten(want).items():
        if name.startswith("g0/"):
            for l in range(L):
                np.testing.assert_allclose(grads[f"{name}#{l}"], g[l], rtol=1e-5, atol=1e-7,
                                           err_msg=f"{name}#{l}")
        else:
            np.testing.assert_allclose(grads[name], g, rtol=1e-5, atol=1e-7, err_msg=name)
    router = np.asarray(grads["g0/router#0"])
    assert (np.abs(router).max() > 1e-4) == aux  # the router learns from `a` alone


@pytest.mark.parametrize("ret", [
    lambda y, a: {"x": y, "a": a},
    lambda y, a: (y, a, a),
    lambda y, a: [y, a],
    lambda y, a: (y, jnp.full((E,), a)),
    lambda y, a: (y, a.astype(jnp.bfloat16)),
    lambda y, a: (y[:-1], a),
    lambda y, a: y.astype(jnp.bfloat16),
], ids=["dict", "three", "list", "vector_a", "bf16_a", "short_x", "bf16_x"])
def test_row_programs_refuse_a_layer_outside_the_contract(ret):
    mref = _toy(True)
    layer = mref.layer_fwd
    mref.layer_fwd = lambda group, w, x, model, fp8=False: ret(*layer(group, w, x, model, fp8))
    with pytest.raises(ValueError, match="layer_fwd of group g0"):
        ref.RowPrograms(mref, {}, jax.devices()[:1], fp8=False)
