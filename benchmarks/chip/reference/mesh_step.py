"""Plain reference of a cell on the mesh path: the IPLS round as
``core/sharded.py``'s docstring and the paper state it, around a model's
plain float32 reference (matrix products at HIGHEST), the module under this
directory that the configuration's ``reference_model`` names. It imports
nothing of the program.

The weights are made here from the seed (``init_params``); the entry hands
the same function's output to the program, so both start from the same
bfloat16 values, which the reference upcasts.

One round, from the agents' batches and participation:

  gradients   each row's loss and gradient, one row per device, layer by
              layer (the layers' inputs are kept, each layer's backward
              recomputes its forward)
  UpdateModel the masked mean over the participants' rows; agent a owns
              partition a of every leaf: the a-th slice of the leaf's first
              dimension that the agent count divides
  update      global-norm clip, AdamW, and the staleness weight:
              eps <- alpha * eps + (1 - alpha) / r over the r participants,
              applied as eps * r to the update of the mean, in float32; the
              stored parameters are rounded to bfloat16
  LoadModel   every agent holds the new parameters

The reference keeps each leaf's parameters and optimizer state on one of
the devices and copies a layer's weights to all of them while the rows use
it: a placement of its own, so that 1.89 B parameters and their float32
moments fit on four chips, and no device's work leaves it.

``Variant`` plants a fault in that round, or computes its matrix products
in a lower precision than the configuration states (the control); the
benchmark's runs use the plain one.

A model reference is a module here that gives, for the configuration's
``model`` dict:

  EMBED, FINAL_NORM, HEAD   the names of the embedding table, the final
                            norm's weight and the output head's table
  leaf_shapes(model)        {leaf name: (shape, std)} in the program's
                            parameter layout, the layers of a group stacked
                            on a leading axis under the group's prefix
  groups(model)             [(group prefix, layers in it, {weight: leaf})]
  layer_fwd(group, w, x, model, fp8)
                            one layer of the group on one row ``x`` (S, d):
                            the new ``x``, or ``(x, a)`` where ``a`` is a
                            float32 scalar added to the row's loss as it is
                            (an auxiliary loss, weighted by the model
                            reference itself); ``check_layers`` refuses
                            any other output when the programs are built
  loss(fnorm, table, x, tokens, model, fp8)
                            the row's loss through the final norm and head
  check(arch, model)        optional: raises where the program's
                            ``ArchConfig`` departs from the configuration in
                            what its leaves do not show

A row's loss is the head's loss plus every ``a`` of its layers; the
participation weights mask it whole, so an absent agent's row adds nothing
to the loss or to a gradient.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

F32, BF16 = jnp.float32, jnp.bfloat16


def model_ref(config: dict):
    """The model reference that the configuration names."""
    return importlib.import_module(f"benchmarks.chip.reference.{config['reference_model']}")


# ------------------------------------------------------------ weights ---
def nest(flat: dict) -> dict:
    out: dict = {}
    for name, v in flat.items():
        *path, last = name.split("/")
        node = out
        for k in path:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "/"))
        else:
            out[name] = v
    return out


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def init_leaf(shape: tuple, std: float, key, index):
    """Leaf ``index`` (of the sorted leaf names) of the initial weights,
    bfloat16: uniform on [-a, a), a the power of two nearest sqrt(3) * std,
    or zeros for a std of 0. The values are made from integer bits and one
    exact scaling, so every program that makes them gets the same bits.
    A program that converts them back to float32 must take them as an
    argument: the compiler may drop a bfloat16 rounding that is followed by
    a widening in the same program."""
    if std == 0.0:
        return jnp.zeros(shape, BF16)
    k = int(np.round(np.log2(std * np.sqrt(3.0))))
    bits = jax.random.bits(jax.random.fold_in(key, index), shape, jnp.uint32)
    ints = (bits >> 8).astype(jnp.int32) - (1 << 23)
    return (ints.astype(F32) * np.float32(2.0 ** (k - 23))).astype(BF16)


def init_params(shapes: Dict[str, tuple], key) -> dict:
    """The initial weights from ``key``: each leaf of ``shapes`` ({name:
    (shape, std)}, the model reference's ``leaf_shapes``) by ``init_leaf``.
    Traceable: the entry makes them on the devices in one jitted call."""
    return nest({name: init_leaf(shape, std, key, i)
                 for i, (name, (shape, std)) in enumerate(sorted(shapes.items()))})


def part_dim(shape, n: int) -> Optional[int]:
    """The dimension along which ``n`` agents split a leaf into their
    partitions: the first that ``n`` divides."""
    return next((i for i, s in enumerate(shape) if s >= n and s % n == 0), None)


# ---------------------------------------------------- device programs ---
def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)
    return wrapped


def check_layers(mref, model: dict, fp8: bool) -> None:
    """Each group's ``layer_fwd`` keeps to the contract: it returns ``x``,
    float32 of the input's shape, or ``(x, a)`` with ``a`` a float32 scalar.
    Anything else would enter the row's loss and its backward unseen.
    Traced on shapes alone, on a row of 8 positions."""
    shapes = mref.leaf_shapes(model)
    x = jax.ShapeDtypeStruct((8, shapes[mref.EMBED][0][1]), F32)

    def f32(v, shape) -> bool:
        return isinstance(v, jax.ShapeDtypeStruct) and v.shape == shape and v.dtype == F32

    for g, (prefix, _, keys) in enumerate(mref.groups(model)):
        w = {k: jax.ShapeDtypeStruct(shapes[name][0][1:], BF16) for k, name in keys.items()}
        y = jax.eval_shape(lambda ww, xx: mref.layer_fwd(g, ww, xx, model, fp8), w, x)
        pair = isinstance(y, tuple) and len(y) == 2 and f32(y[0], x.shape) and f32(y[1], ())
        if not (f32(y, x.shape) or pair):
            raise ValueError(f"layer_fwd of group {prefix} returns {y}: not x, float32 "
                             f"{x.shape}, or (x, a) with a a float32 scalar")


class RowPrograms:
    """The jitted programs of a round: rows sharded over the devices (one
    row's work on each), weights replicated, everything per row; one layer
    program per group of layers."""

    def __init__(self, mref, model: dict, devices, fp8: bool):
        check_layers(mref, model, fp8)
        self.mesh = Mesh(np.asarray(devices), ("rows",))
        self.rows = NamedSharding(self.mesh, P("rows"))
        self.repl = NamedSharding(self.mesh, P())
        rows, repl, m = self.rows, self.repl, model

        def fwd(g, w, x):
            return jax.vmap(lambda xr: mref.layer_fwd(g, w, xr, m, fp8))(x)

        def bwd(g, w, x, dy):
            def one(xr, dyr):
                y, vjp = jax.vjp(lambda ww, xx: mref.layer_fwd(g, ww, xx, m, fp8), w, xr)
                # x's cotangent dy; an ``a`` enters the row's loss as it is: 1
                _, *a = jax.tree.leaves(y)
                return vjp(jax.tree.unflatten(jax.tree.structure(y),
                                              [dyr] + [jnp.ones_like(v) for v in a]))
            dw, dx = jax.vmap(one)(x, dy)
            return dx, dw

        def head(fnorm, table, x, tokens):
            vg = jax.value_and_grad(
                lambda f, t, xx, tk: mref.loss(f, t, xx, tk, m, fp8), argnums=(0, 1, 2)
            )
            return jax.vmap(vg, in_axes=(None, None, 0, 0))(fnorm, table, x, tokens)

        def embed_bwd(tokens, dx):
            V, d = mref.leaf_shapes(m)[mref.EMBED][0]
            return jax.vmap(lambda tk, g: jnp.zeros((V, d), F32).at[tk].add(g))(tokens, dx)

        groups = range(len(mref.groups(m)))
        self.init = jax.jit(functools.partial(init_params, mref.leaf_shapes(m)), out_shardings=repl)
        self.embed = jax.jit(lambda table, tokens: table[tokens].astype(F32), out_shardings=rows)
        self.fwd = [jax.jit(_highest(functools.partial(fwd, g)), out_shardings=rows) for g in groups]
        self.bwd = [jax.jit(_highest(functools.partial(bwd, g)), out_shardings=(rows, rows))
                    for g in groups]
        self.head = jax.jit(_highest(head), out_shardings=(rows, (rows, rows, rows)))
        self.embed_bwd = jax.jit(embed_bwd, out_shardings=rows)

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def combine(self, g, wp, axis):
        """sum over rows b of wp[b, k] * (partition k of g[b]), the leaf
        split into wp.shape[1] parts along ``axis`` (of the leaf)."""
        B, parts = wp.shape
        s = g.shape[1:]
        gs = g.reshape((B,) + s[:axis] + (parts, s[axis] // parts) + s[axis + 1:])
        wb = wp.reshape((B,) + (1,) * axis + (parts, 1) + (1,) * (len(s) - axis - 1))
        return jnp.sum(gs * wb, axis=0).reshape(s)


@functools.lru_cache(maxsize=4)
def _row_programs(name: str, model_items: tuple, devices: tuple, fp8: bool) -> RowPrograms:
    """One set of programs per model, devices and precision in a process."""
    mref = importlib.import_module(f"benchmarks.chip.reference.{name}")
    return RowPrograms(mref, dict(model_items), devices, fp8)


# ------------------------------------------------------------- update ---
@functools.partial(jax.jit, donate_argnums=(2, 3))
def _adamw(p, g, m, v, scal):
    """AdamW on one leaf (or layer of a leaf) and the eps-weighted apply,
    in float32; the result stored as bfloat16."""
    clip, lr, wd, b1, b2, eps, bc1, bc2, scale = (scal[i] for i in range(9))
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p32 = p.astype(F32)
    u = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps) + lr * wd * p32
    return (p32 - scale * u).astype(BF16), m, v


@jax.jit
def _sumsq(g):
    """Sum of squares in float32, in blocks of 4,096 so that the error stays
    near a float32 rounding."""
    x = g.astype(F32).ravel()
    x = jnp.pad(x, (0, -x.shape[0] % 4096)).reshape(-1, 4096)
    return jnp.sum(jnp.sum(x * x, axis=1))


@dataclasses.dataclass(frozen=True)
class Variant:
    """A fault planted in the round, or a lower precision (the control)."""
    lr_scale: float = 1.0        # "lr_halved": 0.5
    use_eps: bool = True         # "eps_off": False
    count_absent: bool = False   # absent agents' rows counted in the mean and in r
    half_batch: bool = False     # the second half of the rows left out of the mean
    drop_layer: Optional[int] = None  # this layer's gradient dropped
    no_exchange: bool = False    # each partition updated from its owner's rows alone
    fp8: bool = False            # control: matrix products' inputs in float8


VARIANTS = {
    "program": Variant(),
    "control_fp8": Variant(fp8=True),
    "lr_halved": Variant(lr_scale=0.5),
    "eps_off": Variant(use_eps=False),
    "absent_counted": Variant(count_absent=True),
    "half_batch": Variant(half_batch=True),
    "layer_dropped": Variant(drop_layer=0),
    "no_exchange": Variant(no_exchange=True),
}


def lr_at(opt: dict, step: int) -> float:
    """The cosine schedule with linear warm-up at ``step`` (0-based)."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * min(1.0, (step + 1.0) / max(warm, 1))
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (opt["min_ratio"] + (1 - opt["min_ratio"]) * 0.5 * (1 + np.cos(np.pi * prog)))


def _weights(mask: np.ndarray, agent: np.ndarray, n: int, v: Variant) -> tuple:
    """(loss weights over rows, gradient weights (rows, partitions), r)."""
    B = mask.shape[0]
    keep = np.ones(B, bool)
    if v.half_batch:
        keep[B // 2:] = False
    m = (np.ones(B) if v.count_absent else mask) * keep
    wl = m / max(m.sum(), 1.0)
    if v.no_exchange:
        wp = np.zeros((B, n))
        for a in range(n):
            own = (agent == a) * m
            wp[:, a] = own / max(own.sum(), 1.0)
    else:
        wp = np.repeat(wl[:, None], n, axis=1)
    present = np.zeros(n)
    np.add.at(present, agent, mask)
    r = float(n if v.count_absent else max((present > 0).sum(), 1))
    return wl, wp.astype(np.float32), r


def _layer_leaves(mref, model: dict) -> Dict[str, tuple]:
    """{leaf name per layer ``name#l``, or leaf name: (shape, group repeat
    or None)}: the units whose parameters and state live on one device."""
    stacked = {}
    for prefix, repeat, _ in mref.groups(model):
        stacked[prefix] = repeat
    out = {}
    for name, (shape, _) in mref.leaf_shapes(model).items():
        repeat = stacked.get(name.split("/")[0])
        if repeat is None:
            out[name] = (shape, None)
        else:
            out.update({f"{name}#{l}": (shape, repeat) for l in range(repeat)})
    return out


def _owners(units: Dict[str, tuple], n: int) -> Dict[str, int]:
    """The device that keeps each unit and its optimizer state: the largest
    first, each to the device that holds the fewest elements so far."""
    size = {k: int(np.prod(s[1:] if rep else s)) for k, (s, rep) in units.items()}
    load = [0] * n
    out = {}
    for k in sorted(size, key=lambda k: (-size[k], k)):
        d = min(range(n), key=lambda i: load[i])
        out[k] = d
        load[d] += size[k]
    return out


def _on(arr, device):
    """The copy of a replicated array that lives on ``device``."""
    return next(s.data for s in arr.addressable_shards if s.device == device)


def run(config: dict, traffic: dict, seed: int, batches: List[dict], devices,
        variant: Variant = Variant()) -> dict:
    """The reference over ``batches`` (one per round, as ``round_batch``
    gives them) from the seed's weights. Returns per round the loss, r and
    eps; the norm of each leaf's gradient as the optimizer gets it in the
    first round (``grad``); the norm of each leaf's change over all the
    rounds (``change``). Stacked leaves count per layer: ``name#l``."""
    model, opt, ipls = config["model"], config["optimizer"], config["ipls"]
    mref = model_ref(config)
    n = config["mesh"]["data"]
    devices = tuple(devices)
    prog = _row_programs(config["reference_model"], tuple(sorted(model.items())), devices,
                         variant.fp8)
    units = _layer_leaves(mref, model)
    owner = {k: devices[d] for k, d in _owners(units, len(devices)).items()}

    def units_of(tree) -> Dict[str, jax.Array]:
        """Each unit of a replicated tree, on its owner."""
        out = {}
        for name, x in flatten(tree).items():
            if name in units:
                out[name] = _on(x, owner[name])
                continue
            for l in range(x.shape[0]):
                out[f"{name}#{l}"] = _on(x, owner[f"{name}#{l}"])[l]
        return out

    def part(name):
        """(parts, axis within the unit, owner of a whole layer)."""
        shape, repeat = units[name]
        d = part_dim(shape, n)
        if d is None:
            return 1, 0, 0
        if repeat is None:
            return n, d, None
        if d == 0:
            return 1, 0, int(name.split("#")[1]) // (repeat // n)
        return n, d - 1, None

    key = seed_key(seed)
    params = units_of(prog.init(key))
    mom = {k: [jnp.zeros(p.shape, F32, device=p.device) for _ in range(2)]
           for k, p in params.items()}
    eps_state = 1.0
    out = {"loss": [], "r": [], "eps": []}
    for step, batch in enumerate(batches):
        mask = np.asarray(batch["participation"], np.float64)
        B = mask.shape[0]
        agent = np.arange(B) // (B // n)
        wl, wp, r = _weights(mask, agent, n, variant)
        grads, losses = _row_grads(prog, mref, model, params, owner, batch["tokens"], wp, part)
        if variant.drop_layer is not None:
            for k in grads:
                if k.endswith(f"#{variant.drop_layer}"):
                    grads[k] = jnp.zeros_like(grads[k])
        sq = {k: _sumsq(g) for k, g in grads.items()}
        sq = {k: float(v) for k, v in sq.items()}
        gnorm = float(np.sqrt(sum(sq.values())))
        clip = min(1.0, ipls["grad_clip"] / max(gnorm, 1e-12)) if ipls["grad_clip"] else 1.0
        if variant.use_eps and ipls["use_eps"]:
            new_eps = ipls["alpha"] * eps_state + (1 - ipls["alpha"]) / r
            scale = new_eps * r
        else:
            new_eps, scale = eps_state, 1.0
        count = step + 1.0
        scal = np.asarray([
            clip, lr_at(opt, step) * variant.lr_scale, opt["weight_decay"], opt["b1"], opt["b2"],
            opt["eps"], 1 - opt["b1"] ** count, 1 - opt["b2"] ** count, scale,
        ], np.float32)
        if step == 0:
            out["grad"] = {k: float(np.sqrt(v)) * clip for k, v in sq.items()}
        for k in list(grads):
            params[k], *mom[k] = _adamw(params[k], grads.pop(k), *mom[k], scal)
        out["loss"].append(float(np.dot(wl, losses)))
        out["r"].append(r)
        out["eps"].append(float(new_eps))
        eps_state = new_eps
    first = units_of(prog.init(key))
    out["change"] = {k: float(np.sqrt(_sumsq(_delta(params[k], first[k])))) for k in params}
    return out


@jax.jit
def _delta(a, b):
    return a.astype(F32) - b.astype(F32)


def _row_grads(prog: RowPrograms, mref, model: dict, params: dict, owner: dict,
               tokens: np.ndarray, wp: np.ndarray, part):
    """Each row's loss (the head's and its layers' ``a``) and gradient at
    ``params``, one row per device, combined into each partition's mean by
    ``wp`` and kept on the unit's owner; and the rows' losses."""
    repl, rows = prog.repl, prog.rows
    tok = jax.device_put(np.asarray(tokens, np.int32), rows)
    wpd = jax.device_put(wp, repl)
    grads: dict = {}

    def everywhere(name):
        return jax.device_put(params[name], repl)

    def put(name, g_rows):
        parts, axis, whole = part(name)
        w = wpd if parts > 1 else wpd[:, whole:whole + 1]
        grads[name] = _on(prog.combine(g_rows, w, axis), owner[name])

    layers = [(g, l, keys) for g, (_, repeat, keys) in enumerate(mref.groups(model))
              for l in range(repeat)]

    def weights(keys, l):
        return {k: everywhere(f"{name}#{l}") for k, name in keys.items()}

    x = prog.embed(everywhere(mref.EMBED), tok)
    xs, aux = [], []
    for g, l, keys in layers:
        xs.append(x)
        x, *a = jax.tree.leaves(prog.fwd[g](weights(keys, l), x))
        aux += a
    loss, (g_fn, g_head, dx) = prog.head(everywhere(mref.FINAL_NORM), everywhere(mref.HEAD), x, tok)
    loss = np.asarray(loss, np.float64) + sum(np.asarray(a, np.float64) for a in aux)
    del x
    put(mref.FINAL_NORM, g_fn)
    put(mref.HEAD, g_head)
    del g_fn, g_head
    for i in reversed(range(len(layers))):
        g, l, keys = layers[i]
        dx, dw = prog.bwd[g](weights(keys, l), xs[i], dx)
        xs[i] = None
        for k, name in keys.items():
            put(f"{name}#{l}", dw[k])
        del dw
    put(mref.EMBED, prog.embed_bwd(tok, dx))
    return grads, loss


# ------------------------------------------------------------ compare ---
def numbers(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``, program against reference over
    the same rounds:

      loss_gap          largest gap of a round's loss, relative
      grad_gap          worst leaf (per layer) of the gap between the norms
                        of the first round's gradient as the optimizer gets
                        it, over the reference's norm of that leaf or of the
                        median leaf, whichever is larger
      change_gap        the same for the parameters' change over the rounds;
                        leaves whose reference gradient is under a
                        thousandth of the median leaf's are left out
      participants_gap  largest gap of a round's participant count: exact
      eps_gap           largest gap of a round's staleness weight eps
    """
    def worst(p: dict, r: dict, keys) -> float:
        med = float(np.median([r[k] for k in keys]))
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keys)

    g_med = float(np.median(list(ref["grad"].values())))
    moved = [k for k in ref["grad"] if ref["grad"][k] >= 1e-3 * g_med]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": worst(prog["grad"], ref["grad"], list(ref["grad"])),
        "change_gap": worst(prog["change"], ref["change"], moved),
        "participants_gap": max(abs(a - b) for a, b in zip(prog["r"], ref["r"])),
        "eps_gap": max(abs(a - b) for a, b in zip(prog["eps"], ref["eps"])),
    }


def worst_leaves(prog: dict, ref: dict) -> dict:
    """For a reading's record: the leaf behind ``grad_gap`` and
    ``change_gap``, with the program's and the reference's norms."""
    out = {}
    for what in ("grad", "change"):
        med = float(np.median(list(ref[what].values())))
        k = max(ref[what], key=lambda k: abs(prog[what][k] - ref[what][k]) / max(ref[what][k], med))
        out[what] = [k, prog[what][k], ref[what][k], med]
    return out
