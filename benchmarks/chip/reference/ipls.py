"""Plain reference of an IPLS fleet (arXiv:2101.01901), written from the
protocol and imported by nothing but the benchmark.

One agent per fleet member, one Python object per message. Every round is

  fetch   agents that lack a partition ask one of its holders for it
  train   each agent loads its model, runs local SGD on its own shard and
          sends each partition's delta slice to one holder
  merge   each holder applies ``w -= eps * sum(deltas)`` with
          ``eps <- alpha*eps + (1-alpha)/r``, replies the new value to every
          contributor and publishes it to the partition's other holders,
          which average it with their own

over a network that loses or delays each message by a keyed hash of its
coordinates (channel, round, sender, partition, peer), four ticks a round.
Partitions are assigned by the paper's join rule (pi per agent, rho per
partition). The wire is raw float32 or block int8 with power-of-two scales
(error feedback on deltas). Local SGD and evaluation run in plain
``jax.numpy``; the float32 reference runs its matrix products at
``Precision.HIGHEST``. ``sgd_dtype=bfloat16`` gives the control: the same
fleet with local SGD computed in bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TICKS = 4
CH_FETCH, CH_FETCH_REPLY, CH_UPDATE, CH_UPDATE_REPLY, CH_REPLICA, CH_MEMBER = range(6)
UPDATE, REPLY, REPLICA, MEMBER, FETCH = range(5)
FETCH_BYTES = 16
MEMBER_BYTES = 64
BLOCK = 1024
M64 = (1 << 64) - 1
GOLDEN, MIX = 0x9E3779B97F4A7C15, 0xD1B54A32D192ED03
C1, C2 = 0xBF58476D1CE4E5B9, 0x94D049BB133111EB


# ---------------------------------------------------------------- fates ---
def _splitmix(x: int) -> int:
    z = (x + GOLDEN) & M64
    z = ((z ^ (z >> 30)) * C1) & M64
    z = ((z ^ (z >> 27)) * C2) & M64
    return z ^ (z >> 31)


def keyed_uniform(*key: int) -> float:
    """Uniform in [0, 1) that is a pure function of the integer key."""
    h = 0
    for c in key:
        h = _splitmix(h ^ ((c * MIX) & M64))
    return (h >> 11) * 2.0**-53


def message_fate(net: dict, seed: int, *key: int) -> Tuple[bool, int]:
    """(delivered, delay in ticks): lost with ``loss_prob``; else delayed by a
    geometric number of ticks, ``delay_prob`` per tick, capped."""
    loss, delay_p, cap = net["loss_prob"], net["delay_prob"], net["max_delay_ticks"]
    if loss > 0 and keyed_uniform(seed, *key, 0) < loss:
        return False, 0
    delay = 0
    if delay_p > 0:
        for slot in range(1, cap + 1):
            if keyed_uniform(seed, *key, slot) < delay_p and delay == slot - 1:
                delay += 1
    return True, delay


# ----------------------------------------------------------------- wire ---
def wire_bytes(n: int, wire: str) -> int:
    return n + 4 * -(-n // BLOCK) if wire == "int8" else 4 * n


def quantize(x: np.ndarray, err: np.ndarray):
    """Block int8: per block of BLOCK values the scale is 2**(E-6) for the
    block's absmax ``m * 2**E`` (absmax/scale in [64, 128)); codes are
    round-half-even and clipped to +-127. Blocks under 2**-120 send zeros.
    Returns (codes, scales, new residual)."""
    n = x.shape[0]
    xb = np.zeros(-(-n // BLOCK) * BLOCK, np.float32)
    xb[:n] = x + err
    xb = xb.reshape(-1, BLOCK)
    absmax = np.max(np.abs(xb), axis=1)
    exp = absmax.view(np.int32) >> 23  # biased exponent
    zero = exp <= 6
    expc = np.maximum(exp, 7)
    scale = np.where(zero, 0.0, np.ldexp(1.0, expc - 133)).astype(np.float32)
    inv = np.where(zero, 0.0, np.ldexp(1.0, 133 - expc)).astype(np.float32)
    q = np.clip(np.round(xb * inv[:, None]), -127, 127)
    deq = (q * scale[:, None]).astype(np.float32)
    return q.astype(np.int8).reshape(-1)[:n], scale, (xb - deq).reshape(-1)[:n]


def dequantize(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    n = q.shape[0]
    qb = np.zeros(scale.shape[0] * BLOCK, np.float32)
    qb[:n] = q
    return (qb.reshape(-1, BLOCK) * scale[:, None]).reshape(-1)[:n]


# ------------------------------------------------------------ the model ---
def model_layout(layers) -> List[Tuple[str, Tuple[int, ...]]]:
    """Flat order of the parameter vector: leaves sorted by name
    (b0, b1, ..., w0, w1, ...)."""
    shapes = {}
    for i, (fi, fo) in enumerate(layers):
        shapes[f"w{i}"] = (fi, fo)
        shapes[f"b{i}"] = (fo,)
    return [(name, shapes[name]) for name in sorted(shapes)]


def init_weights(layers, seed: int) -> np.ndarray:
    """Glorot-uniform weights, zero biases, drawn layer by layer from
    ``numpy.random.default_rng(seed)``; flattened in ``model_layout`` order."""
    rng = np.random.default_rng(seed)
    leaves = {}
    for i, (fi, fo) in enumerate(layers):
        bound = float(np.sqrt(6.0 / (fi + fo)))
        leaves[f"w{i}"] = rng.uniform(-bound, bound, size=(fi, fo)).astype(np.float32)
        leaves[f"b{i}"] = np.zeros((fo,), np.float32)
    return np.concatenate([leaves[name].reshape(-1) for name, _ in model_layout(layers)])


def leaf_slices(layers) -> Dict[str, slice]:
    out, off = {}, 0
    for name, shape in model_layout(layers):
        n = math.prod(shape)
        out[name] = slice(off, off + n)
        off += n
    return out


def _unflatten(w, layers):
    leaves, off = {}, 0
    for name, shape in model_layout(layers):
        n = math.prod(shape)
        leaves[name] = w[off : off + n].reshape(shape)
        off += n
    return leaves


def _logits(leaves, x, n_layers: int, precision):
    h = x
    for i in range(n_layers):
        h = jnp.matmul(h, leaves[f"w{i}"], precision=precision) + leaves[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def _nll(leaves, x, y, n_layers: int, precision):
    logp = jax.nn.log_softmax(_logits(leaves, x, n_layers, precision).astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def make_local_sgd(layers, lr: float, iters: int, dtype=jnp.float32):
    """jit fn (W (n, N) f32, X (n, B, d), Y (n, B)) -> deltas (n, N) f32:
    each row runs ``iters`` plain SGD steps on its batch (mean cross-entropy)
    and returns start minus end. ``dtype`` is the arithmetic of the steps."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    n_layers = len(layers)

    def one(w, x, y):
        w = w.astype(dtype)
        x = x.astype(dtype)

        def loss(v):
            return _nll(_unflatten(v, layers), x, y, n_layers, precision)

        v = w
        for _ in range(iters):
            v = v - jnp.asarray(lr, dtype) * jax.grad(loss)(v).astype(dtype)
        return w.astype(jnp.float32) - v.astype(jnp.float32)

    return jax.jit(jax.vmap(one))


def make_eval(layers, max_chunk: int = 2500):
    """jit fn (W (n, N), X (T, d), Y (T,)) -> (accuracy (n,), mean nll (n,)),
    over the test rows in chunks of at most ``max_chunk``."""
    n_layers = len(layers)
    hi = jax.lax.Precision.HIGHEST

    def one(w, x, y):
        leaves = _unflatten(w, layers)
        chunk = math.gcd(x.shape[0], max_chunk)
        xs = x.reshape(-1, chunk, x.shape[-1])
        ys = y.reshape(-1, chunk)

        def part(args):
            xc, yc = args
            lg = _logits(leaves, xc, n_layers, hi)
            logp = jax.nn.log_softmax(lg)
            nll = -jnp.sum(jnp.take_along_axis(logp, yc[:, None], axis=1))
            return jnp.sum(jnp.argmax(lg, axis=1) == yc), nll

        hits, nll = jax.lax.map(part, (xs, ys))
        return jnp.sum(hits) / y.shape[0], jnp.sum(nll) / y.shape[0]

    return jax.jit(jax.vmap(one, in_axes=(0, None, None)))


def eval_rows(evaluate, W: np.ndarray, x_te, y_te, block: int = 10):
    """Accuracy and mean nll of each row of W, ``block`` rows per call."""
    accs, nlls = [], []
    for lo in range(0, W.shape[0], block):
        a, n = evaluate(jnp.asarray(W[lo : lo + block]), x_te, y_te)
        accs.append(np.asarray(a))
        nlls.append(np.asarray(n))
    return np.concatenate(accs), np.concatenate(nlls)


# ------------------------------------------------------- the partitions ---
def assign_partitions(num_agents: int, K: int, pi: int, rho: int):
    """The paper's join rule. Agent 0 starts with every partition; each later
    agent takes up to ``pi``: from the most loaded agent holding more than pi
    (its least replicated partition, lowest id first), else a new replica of
    the least replicated partition under rho. Returns, in join order, the
    events ``(agent, partition, donor or None)`` and the final holders."""
    pi = min(pi, K)
    holders: List[List[int]] = [[0] for _ in range(K)]
    held: Dict[int, List[int]] = {0: list(range(K))}
    events = []
    for a in range(1, num_agents):
        held[a] = []
        for _ in range(pi):
            mine = set(held[a])
            donors = sorted(
                (d for d in held if d != a and len(held[d]) > pi),
                key=lambda d: (-len(held[d]), d),
            )
            got = None
            for d in donors:
                cands = sorted(
                    (p for p in held[d] if p not in mine),
                    key=lambda p: (len(holders[p]), p),
                )
                if cands:
                    got = (cands[0], d)
                    held[d].remove(cands[0])
                    holders[cands[0]].remove(d)
                    break
            if got is None:
                cands = sorted(
                    (p for p in range(K) if p not in mine and len(holders[p]) < rho),
                    key=lambda p: (len(holders[p]), p),
                )
                if not cands:
                    break
                got = (cands[0], None)
            held[a].append(got[0])
            held[a].sort()
            holders[got[0]].append(a)
            events.append((a, got[0], got[1]))
    return events, holders


class _Owned:
    __slots__ = ("value", "eps", "version", "pending", "payload")

    def __init__(self, value, eps=1.0, version=0):
        self.value, self.eps, self.version = value, eps, version
        self.pending: List[np.ndarray] = []
        self.payload = None


class Fleet:
    """The whole fleet's protocol state; ``round(r, deltas_fn)`` runs one
    round, ``models()`` gives each agent's assembled parameter vector."""

    def __init__(self, fleet: dict, net: dict, seed: int, w0: np.ndarray):
        self.A, self.K = fleet["num_agents"], fleet["num_partitions"]
        self.alpha = fleet["alpha"]
        self.wire = fleet["wire_dtype"]
        self.net, self.seed = net, seed
        N = w0.shape[0]
        base, rem = divmod(N, self.K)
        self.sizes = [base + (1 if k < rem else 0) for k in range(self.K)]
        self.offs = [sum(self.sizes[:k]) for k in range(self.K)]
        self.N = N
        self.tick_no = 0
        self.inflight: List[tuple] = []
        self.inbox: Dict[int, List[tuple]] = {a: [] for a in range(self.A)}
        self.n_sent = self.n_dropped = self.n_bytes = 0
        self.owned: Dict[int, Dict[int, _Owned]] = {a: {} for a in range(self.A)}
        self.cache: Dict[int, Dict[int, np.ndarray]] = {a: {} for a in range(self.A)}
        self.residual: Dict[int, Dict[int, np.ndarray]] = {a: {} for a in range(self.A)}
        self.requesters: Dict[int, Dict[int, List[int]]] = {a: {} for a in range(self.A)}
        self.member_subs: List[int] = []

        # bootstrap and joins, with their membership traffic
        for k in range(self.K):
            self.owned[0][k] = _Owned(w0[self.offs[k] : self.offs[k] + self.sizes[k]].copy())
        self.member_subs.append(0)
        self._publish(MEMBER, 0, [], None, MEMBER_BYTES, CH_MEMBER, 0)
        events, self.holders = assign_partitions(
            self.A, self.K, fleet["pi"], fleet["rho"]
        )
        self.replica_subs = [[] for _ in range(self.K)]
        for k in range(self.K):
            self.replica_subs[k].append(0)
        by_agent: Dict[int, list] = {}
        for a, k, donor in events:
            by_agent.setdefault(a, []).append((k, donor))
        for a in range(1, self.A):
            self.member_subs.append(a)
            for k, donor in by_agent.get(a, []):
                src = donor if donor is not None else self._first_holder(k, a)
                st = self.owned[src][k]
                self.owned[a][k] = _Owned(st.value.copy(), st.eps, st.version)
                if donor is not None:
                    self.cache[donor][k] = self.owned[donor].pop(k).value
                    self.replica_subs[k].remove(donor)
                self.replica_subs[k].append(a)
                others = [s for s in self.member_subs if s != a]
                self._publish(
                    MEMBER, a, others, None, MEMBER_BYTES + 4 * self.sizes[k], CH_MEMBER, k
                )

    def _first_holder(self, k: int, joiner: int) -> int:
        return min(a for a in range(joiner) if k in self.owned[a])

    # -- transport --------------------------------------------------------
    def _rnd(self) -> int:
        return self.tick_no // TICKS

    def _queue(self, topic, sender, recipient, payload, delay):
        self.inflight.append((self.tick_no + delay, topic, sender, recipient, payload))

    def _send(self, topic, sender, recipient, payload, size, key):
        self.n_sent += 1
        self.n_bytes += size
        ok, delay = message_fate(self.net, self.seed, *key)
        if not ok:
            self.n_dropped += 1
            return
        self._queue(topic, sender, recipient, payload, delay)

    def _publish(self, topic, sender, subscribers, payload, size, channel, part):
        self.n_sent += 1
        self.n_bytes += size
        for r in subscribers:
            if r == sender:
                continue
            ok, delay = message_fate(self.net, self.seed, channel, self._rnd(), sender, part, r)
            if not ok:
                self.n_dropped += 1
                continue
            self._queue(topic, sender, r, payload, delay)

    def tick(self) -> None:
        still = []
        for msg in self.inflight:
            if msg[0] > self.tick_no:
                still.append(msg)
            else:
                self.inbox[msg[3]].append((msg[1], msg[2], msg[4]))
        self.inflight = still
        self.tick_no += 1

    def _drain(self, a: int, topic: int) -> List[tuple]:
        box = self.inbox[a]
        self.inbox[a] = [m for m in box if m[0] != topic]
        return [m for m in box if m[0] == topic]

    # -- wire ---------------------------------------------------------------
    def _encode_value(self, st: _Owned):
        if st.payload is None:
            if self.wire == "int8":
                q, s, _ = quantize(st.value, np.zeros_like(st.value))
                st.payload = (q, s)
            else:
                st.payload = st.value.copy()
        return st.payload

    def _decode(self, payload) -> np.ndarray:
        if self.wire == "int8":
            return dequantize(*payload)
        return payload

    def _reply(self, holder: int, requester: int, k: int, channel: int) -> None:
        st = self.owned[holder].get(k)
        if st is None:
            return
        self._send(
            REPLY, holder, requester, (k, self._encode_value(st)),
            wire_bytes(self.sizes[k], self.wire),
            (channel, self._rnd(), requester, k, holder),
        )

    # -- one round ----------------------------------------------------------
    def models(self) -> np.ndarray:
        W = np.zeros((self.A, self.N), np.float32)
        for a in range(self.A):
            for k in range(self.K):
                sl = slice(self.offs[k], self.offs[k] + self.sizes[k])
                if k in self.owned[a]:
                    W[a, sl] = self.owned[a][k].value
                elif k in self.cache[a]:
                    W[a, sl] = self.cache[a][k]
        return W

    def round(self, r: int, deltas_fn) -> None:
        """One round; ``deltas_fn(W)`` maps the (A, N) models to the (A, N)
        local deltas (start minus end of local SGD)."""
        agents = range(self.A)
        for a in agents:
            for k in range(self.K):
                if k in self.owned[a] or k in self.cache[a]:
                    continue
                hs = self.holders[k]
                self._send(
                    FETCH, a, hs[(r + a) % len(hs)], k, FETCH_BYTES,
                    (CH_FETCH, self._rnd(), a, k, 0),
                )
        self.tick()
        for a in agents:
            for _, sender, k in self._drain(a, FETCH):
                self._reply(a, sender, k, CH_FETCH_REPLY)
        self.tick()
        self._receive_replies()

        D = deltas_fn(self.models())
        for a in agents:
            for k in range(self.K):
                sl = D[a, self.offs[k] : self.offs[k] + self.sizes[k]]
                if k in self.owned[a]:
                    self.owned[a][k].pending.append(sl)
                    continue
                hs = self.holders[k]
                if self.wire == "int8":
                    err = self.residual[a].get(k)
                    if err is None:
                        err = np.zeros(self.sizes[k], np.float32)
                    q, s, self.residual[a][k] = quantize(sl, err)
                    payload = (q, s)
                else:
                    payload = sl.copy()
                self._send(
                    UPDATE, a, hs[(r + a) % len(hs)], (k, payload),
                    wire_bytes(self.sizes[k], self.wire),
                    (CH_UPDATE, self._rnd(), a, k, 0),
                )
        self.tick()

        for a in agents:
            for _, sender, (k, payload) in self._drain(a, UPDATE):
                if k in self.owned[a]:
                    self.owned[a][k].pending.append(self._decode(payload))
                    self.requesters[a].setdefault(k, []).append(sender)
        for a in agents:
            for st in self.owned[a].values():
                if not st.pending:
                    continue
                st.eps = self.alpha * st.eps + (1.0 - self.alpha) / len(st.pending)
                total = np.sum(np.stack(st.pending), axis=0, dtype=np.float32)
                st.value = (st.value - np.float32(st.eps) * total).astype(np.float32)
                st.version += 1
                st.pending = []
                st.payload = None
        for a in agents:
            for k, reqs in self.requesters[a].items():
                for q in reqs:
                    self._reply(a, q, k, CH_UPDATE_REPLY)
            self.requesters[a] = {}
            for k, st in self.owned[a].items():
                if len(self.holders[k]) > 1:
                    self._publish(
                        REPLICA, a, self.replica_subs[k],
                        (k, self._encode_value(st), st.version),
                        wire_bytes(self.sizes[k], self.wire), CH_REPLICA, k,
                    )
        self.tick()
        self._receive_replies()
        for a in agents:
            incoming: Dict[int, List[np.ndarray]] = {}
            for _, _, (k, payload, ver) in self._drain(a, REPLICA):
                if k in self.owned[a] and ver >= self.owned[a][k].version:
                    incoming.setdefault(k, []).append(self._decode(payload))
            for k, vals in incoming.items():
                st = self.owned[a][k]
                st.value = np.mean(np.stack([st.value] + vals), axis=0).astype(np.float32)
                st.payload = None

    def _receive_replies(self) -> None:
        for a in range(self.A):
            for _, _, (k, payload) in self._drain(a, REPLY):
                self.cache[a][k] = self._decode(payload)


# ------------------------------------------------------------- the run ---
def eval_subset(num_agents: int, eval_agents: int) -> List[int]:
    """Stride-spread subset of at most ``eval_agents`` agents (0 = all)."""
    live = list(range(num_agents))
    if eval_agents and num_agents > eval_agents:
        live = live[:: max(num_agents // eval_agents, 1)][:eval_agents]
    return live


def run_fleet(fleet: dict, traffic: dict, seed: int, shards, x_te, y_te, rounds: int,
              sgd_dtype=jnp.float32) -> dict:
    """Run ``rounds`` rounds from the seed. Returns the (A, N) models after the
    last round, the start vector, the traffic counters and the accuracy of
    the evaluated agents after the last round."""
    layers = [tuple(p) for p in zip(fleet["layers"][:-1], fleet["layers"][1:])]
    A = fleet["num_agents"]
    w0 = init_weights(layers, seed)
    fl = Fleet(fleet, traffic["network"], seed, w0)
    sgd = make_local_sgd(layers, fleet["lr"], traffic["local_iters"], sgd_dtype)
    rngs = [np.random.default_rng(seed + 1000 * (a + 1)) for a in range(A)]

    def deltas(W):
        xs, ys = [], []
        for a in range(A):
            x, y = shards[a]
            sel = rngs[a].choice(len(x), size=min(traffic["batch_size"], len(x)), replace=False)
            xs.append(x[sel])
            ys.append(y[sel])
        return np.asarray(sgd(jnp.asarray(W), jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))))

    for r in range(rounds):
        fl.round(r, deltas)
    W = fl.models()
    idx = eval_subset(A, traffic["eval_agents"])
    evaluate = make_eval(layers)
    acc, _ = eval_rows(evaluate, W[idx], jnp.asarray(x_te), jnp.asarray(y_te))
    return {
        "models": W, "w0": w0, "acc_mean": float(np.mean(acc)),
        "bytes_total": fl.n_bytes, "messages_sent": fl.n_sent,
        "messages_dropped": fl.n_dropped, "layers": layers,
    }
