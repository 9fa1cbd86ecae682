"""Vectorized IPLS round engine: whole-round batching across agents/partitions.

The scalar engine (`fl/rounds.py`) dispatches one jitted SGD call per agent,
one numpy slice-copy per (agent, partition) message and one tiny numpy
reduction per partition — Python overhead linear in A*K per round. This
engine reproduces the *same* per-round dataflow as ONE fused device call
that batches the three round phases:

  1. local SGD for all A agents at once — agents' flat weights assembled
     into an (A, N) matrix inside the call and trained with `jax.vmap` over
     `mlp_mnist.sgd_steps_flat` (each agent's row unflattened once, the tree
     scan of `sgd_steps` run on it, the result flattened once);
  2. aggregation of every (partition, replica-slot) instance: on TPU one
     partition-batched Pallas launch (`kernels/ipls_aggregate`) with deltas
     laid out (K_inst, R, S) + a per-instance (mask, r, eps) table; on
     CPU/GPU the same contributor rows reduced by a sequential masked sum
     in the scalar oracle's pending order — followed by replica consensus
     (segment mean);
  3. evaluation of the (sub-sampled) agents in one vmapped call.

Only the small per-instance value tables (V_pre, V_merged, eps) cross the
device-call boundary between rounds; the (A, N) matrices live and die
inside the fused call.

Exactness: under PERFECT network conditions with a fixed membership the
scalar engine is fully deterministic — every agent sends each non-owned
partition's delta to holder `H(k)[(round + agent) % rho_k]`, holders
aggregate `w -= eps * sum(deltas)` with the eps recursion, replicas mean-
merge AFTER replies are served (so caches hold pre-merge per-replica
values), and agents assemble owned->merged / cached->pre-merge views. The
engine replicates exactly that, including per-agent data batch RNG streams,
so the two engines agree to float tolerance round by round (tested in
tests/test_vectorized.py).

LOSSY conditions (loss_prob/delay_prob > 0) run batched too: per-message
fates come from the keyed counter-based stream (`fl/rounds.MessageFates`)
that the scalar engine's pubsub reads one message at a time, so both
engines see identical loss/delay decisions by construction. The engine
pre-draws each round's fates as (A, K) mask/delay tensors and folds them
into the contribution masks; delayed deltas ride a small ring buffer of
in-flight delta windows (depth = max delay in rounds) that feeds the
per-instance (mask, r, eps) table of the batched aggregation; lost/late
replies become cache-update masks over an explicit (A, K, S) cache plane,
so stale caches persist exactly as in the scalar engine. A tiny host-side
state machine (pure integer/boolean numpy) mirrors the scalar fetch
warm-up protocol so `bytes_total` / `messages_dropped` match the pubsub
counters exactly. See docs/ENGINE.md.

Churn: membership schedules run here too, via event-boundary re-snapshot.
Rounds between membership events run fused; each event round replays on
the embedded scalar oracle (whose `_apply_churn` implements the
leave/crash/join handoff rules), and every membership-dependent dense
structure — instance tables, `_slot_inst`/`_widx`, the value/cache/ring
planes, contribution and merge layouts, trainer buckets — is rebuilt from
the scalar state at the boundary. In-flight protocol messages cross the
boundary in both directions: harvested from the pubsub into the queue
rings and a span-constant mail plane on entry, re-injected as pubsub
messages on exit. See docs/ENGINE.md "Churn re-snapshot".
Traffic accounting is computed in closed form (PERFECT) or by the mask
stream (LOSSY) and matches the scalar engine's pubsub counters exactly.

Multi-round fusion: each path (PERFECT, LOSSY) has exactly one device
program, its scanned window. With ``SimConfig(scan_rounds=W)`` the engine
runs windows of W rounds as ONE ``lax.scan``-driven device call each;
``scan_rounds=0`` and ``run_round`` run one-round windows. Batch
row indices, fate tensors (via the windowed batch draw
``MessageFates.draw_window``) and the host control plane are pre-drawn for
the whole window; the images stay in a device-resident training set that
the scanned body gathers each round's batches from; the device
state — weights plane, cache plane, delta ring, value-history rings —
lives in the fixed-shape scan carry, and the ring rotations/cache-event
gathers run as in-carry dynamic indices inside the scanned body. Per-round
bytes/messages/drops come back as stacked per-round values, still exactly
equal to the scalar pubsub counters. With ``scan_rounds >= 1``,
evaluation is gated by ``eval_cadence`` so it can move to window
boundaries. See docs/ENGINE.md "Multi-round fused scan".
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import unflatten_params
from repro.core.wire import BLOCK as WBLOCK
from repro.core.wire import dequantize_rows, qdq_rows, quantize_rows, wire_size
from repro.kernels.ipls_aggregate.ops import aggregate_batched, aggregate_batched_q
from repro.models import mlp_mnist
from repro.telemetry.device import metric_pair

# cache-event value sources (see _control_one_round)
_KIND_START = 0  # holder value at the start of the serve round (fetch reply)
_KIND_AGG = 1  # holder value after aggregation, pre-merge (UpdateModel reply)
_KIND_MAIL = 2  # harvested in-flight reply payload (span-constant mail plane)


def partition_plane(D, *, offsets, sizes, S: int):
    """(rows, N) -> (rows, K, S): row r, partition k holds D's columns
    [offsets[k], offsets[k] + sizes[k]) of row r, zero-padded to S: K static
    column slices, stacked."""
    rows = D.shape[0]
    return jnp.stack(
        [
            jnp.pad(
                jax.lax.slice(D, (0, int(o)), (rows, int(o) + int(s))),
                ((0, 0), (0, S - int(s))),
            )
            for o, s in zip(offsets, sizes)
        ],
        axis=1,
    )


def contributor_rows(D, idx, *, inst_of_k, offsets, sizes, S: int):
    """The aggregation kernel's (K_inst, R, S) contributor layout: row j of
    instance i is row idx[i, j] of D restricted to the instance's partition
    columns, zero-padded to S. Instances are k-major, so partition k's
    instances are the contiguous rows inst_of_k[k] of idx.

    Per partition one static column slice of D and one whole-row gather of
    it. On a TPU v5e an element-indexed (row, column) gather of the same
    layout runs at ~18 ns per element, and a (row, partition) gather of the
    stacked `partition_plane` inside the scanned window program did not
    finish one 8-round window of a 100-agent fleet in 10 minutes."""
    n = D.shape[0]
    parts = []
    for rows, o, s in zip(inst_of_k, offsets, sizes):
        if len(rows) == 0:
            continue
        Dk = jax.lax.slice(D, (0, int(o)), (n, int(o) + int(s)))
        Gk = Dk[idx[int(rows[0]) : int(rows[-1]) + 1]]
        parts.append(jnp.pad(Gk, ((0, 0), (0, 0), (0, S - int(s)))))
    return jnp.concatenate(parts, axis=0)


def gather_batches(x_tab, y_tab, idx):
    """Each batch bucket's (rows, bs, ...) images and labels, gathered from
    the device-resident training set by the staged global row indices
    ``idx`` (one (rows, bs) int32 array per bucket). Whole-row gathers of the
    flat (n, 784) table: the host stages 4 bytes a sample, not 3,136."""
    with jax.named_scope("batch_gather"):
        take = lambda t, i: jnp.take(t, i, axis=0, mode="clip")
        return (
            tuple(take(x_tab, i) for i in idx),
            tuple(take(y_tab, i) for i in idx),
        )


def sgd_all(W, Xs, Ys, *, buckets, lr, iters, layout_t):
    """All rows' local SGD on the (rows, N) weight matrix; Xs/Ys are
    per-bucket stacked batches (a single bucket unless array_split handed
    out two shard sizes). Each row is unflattened once, trained by the tree
    scan and flattened once: the tree<->vector passes are paid once a call,
    not once a step, bit-identical to the scalar engine's tree scan."""
    step = lambda w, x, y: mlp_mnist.sgd_steps_flat(w, x, y, lr, iters, layout_t)
    if len(buckets) == 1:
        return jax.vmap(step)(W, Xs[0], Ys[0])
    parts = [
        jax.vmap(step)(W[lo:hi], Xs[b], Ys[b]) for b, (lo, hi, _) in enumerate(buckets)
    ]
    return jnp.concatenate(parts, axis=0)


def eval_block(W, x_te, y_te, layout):
    """Test accuracy of every row of a (rows, N) weight block."""
    return jax.vmap(
        lambda w: mlp_mnist.evaluate(unflatten_params(w, layout), x_te, y_te)
    )(W)


class _HarvestDeferred(Exception):
    """A span-boundary harvest met an in-flight message shape the dense
    planes cannot represent (possible only when max_delay_rounds exceeds
    one round of ticks, e.g. a straggler whose sender has since left).
    The caller replays one more round on the scalar oracle and retries —
    stragglers drain within max_delay, so the retry converges."""


class _FateWindow:
    """Per-round slices of windowed fate draws (`MessageFates.draw_window`).

    The request-side channels (fetch / UpdateModel / replica publish) have
    fixed per-round keys, so a whole scan window's (W, A, K) mask/delay
    tensors can be materialized in one hashing pass up front; the reply
    channels stay per-event draws inside the control plane (their keys
    depend on which messages actually arrived). Slices equal the per-round
    draws exactly — fates are pure hashes of their coordinates."""

    def __init__(self, fates, r0, W, a_col, k_row, rep_src_agent, rep_k, rep_dst_agent):
        from repro.fl.rounds import CH_FETCH, CH_REPLICA, CH_UPDATE

        rounds = np.arange(r0, r0 + W)
        self.r0 = r0
        self.fetch = fates.draw_window(CH_FETCH, rounds, a_col, k_row)
        self.update = fates.draw_window(CH_UPDATE, rounds, a_col, k_row)
        self.replica = (
            fates.draw_window(CH_REPLICA, rounds, rep_src_agent, rep_k, rep_dst_agent)
            if len(rep_src_agent)
            else None
        )

    def slice(self, name: str, t: int):
        de, dl = getattr(self, name)
        w = t - self.r0
        return de[w], dl[w]


class VectorizedIPLSSimulation:
    """Drop-in engine for `IPLSSimulation`: any network conditions, wire
    dtype and churn schedule.

    Construction delegates to the scalar engine so the bootstrap/join
    protocol (partition transfers, donor caches, membership traffic) is
    byte-for-byte identical; the resulting state is then snapshotted into
    dense arrays and all rounds run batched.
    """

    def __init__(self, cfg, shards, x_test, y_test, use_kernel: bool | None = None):
        from repro.fl.rounds import IPLSSimulation

        # aggregation backend: the partition-batched Pallas kernel natively
        # on TPU; the identical-math XLA masked-matmul elsewhere (running the
        # kernel through the interpreter in the hot loop would be pure
        # emulation overhead — interpret mode is for correctness tests)
        self._use_kernel = (
            jax.default_backend() == "tpu" if use_kernel is None else use_kernel
        )
        # int8 wire mode: route through the general event-driven path even
        # under PERFECT conditions — quantized replica consensus makes each
        # holder's merged value differ (raw self + qdq of the others), which
        # the phase-table PERFECT path cannot represent; under PERFECT the
        # fate stream degenerates to (delivered, delay 0) so the event path
        # reproduces the scalar engine exactly
        self._int8 = getattr(cfg, "wire_dtype", "f32") == "int8"
        # imperfect connectivity runs batched through the mask-stream path
        # (same gate as the scalar engine's keyed-fates installation); churn
        # routes there too — membership-event rounds replay on the scalar
        # oracle and the spans between re-snapshot, which only the
        # event-driven path's queue rings can represent
        self._lossy = (
            cfg.conditions.loss_prob > 0
            or cfg.conditions.delay_prob > 0
            or self._int8
            or bool(cfg.churn)
        )
        self.cfg = cfg
        # multi-round fusion: run() executes windows of `scan_rounds` rounds
        # as one lax.scan device call each (0 = one-round windows)
        self.scan_rounds = int(getattr(cfg, "scan_rounds", 0) or 0)
        if self.scan_rounds < 0:
            raise ValueError("scan_rounds must be >= 0")
        # eval_cadence thins evaluation of scanned windows only: with
        # scan_rounds=0 every round is evaluated
        self._eval_cadence = (
            max(1, int(getattr(cfg, "eval_cadence", 1) or 1)) if self.scan_rounds else 1
        )
        # jitted-call counter: benchmarks report dispatches/round (the scan
        # path's whole point is driving this to 1/W)
        self.device_dispatches = 0
        # bytes the host has handed the device in window and round staging
        # (batch row indices + routing/control tables); the training set
        # itself is uploaded at construction, or at a re-snapshot that
        # brings a shard the table lacks, and is not counted
        self.staged_bytes = 0
        self._tab_off: dict = {}
        self._last_accs: np.ndarray | None = None
        self.x_test, self.y_test = x_test, y_test
        # exact init state + init-phase traffic via the scalar constructor
        seed_sim = IPLSSimulation(cfg, shards, x_test, y_test)
        self.net = seed_sim.net
        # telemetry handoff: this engine emits the same per-round stream
        # through the seed's recorder, but feeds it from the control plane /
        # closed-form traffic instead of the pubsub taps — detach the pubsub
        # hook so nothing double-counts (rounds never touch the pubsub here)
        self.recorder = seed_sim.recorder
        self._pt = seed_sim._pt
        self.net.pubsub.telemetry = None
        self.spec = seed_sim.spec
        self.table = seed_sim.table
        self.layout = seed_sim.layout
        self.history: List[dict] = []

        A = cfg.num_agents
        K = self.spec.num_partitions
        sizes = np.asarray(self.spec.sizes, np.int64)
        offsets = np.asarray(self.spec.offsets(), np.int64)
        N = self.spec.total
        self.A, self.K, self.N = A, K, N
        # membership rows: live agents in scalar `active`-iteration (dict)
        # order; full fixed membership outside the churn path. The embedded
        # scalar sim stays attached as the churn replay oracle.
        self._seed = seed_sim
        self._ids: List[int] = list(range(A))
        self._n_act = A
        self._on_device = True
        self._replay: List[int] = []
        self._replay_set: frozenset = frozenset()

        # ---- instance plane: one row per (partition, replica-slot) --------
        holders: List[List[int]] = [self.table.holders_of(k) for k in range(K)]
        inst_k: List[int] = []
        inst_owner: List[int] = []
        inst_id: Dict[Tuple[int, int], int] = {}
        for k in range(K):
            for j, h in enumerate(holders[k]):
                inst_id[(k, j)] = len(inst_k)
                inst_k.append(k)
                inst_owner.append(h)
        self.K_inst = len(inst_k)
        self._inst_k = np.asarray(inst_k, np.int32)
        self._inst_owner = np.asarray(inst_owner, np.int32)
        rho = np.asarray([len(h) for h in holders], np.int64)
        self._rho = rho
        self._holder_ids = holders
        # (K, max_rho) instance id per (partition, replica slot); -1 pad
        self._slot_inst = np.full((K, int(rho.max())), -1, np.int32)
        for (k, j), i in inst_id.items():
            self._slot_inst[k, j] = i

        # padded instance size: tail zeros flow through the batched kernel
        # untouched (0 - eps*0), so one shared width serves all partitions.
        # int8 wire: round up to whole quantization blocks so each (agent,
        # partition) row of the (A, K, S) planes is an integral number of
        # scale blocks; the zero tail quantizes to zero blocks, matching the
        # scalar codec's per-slice padding exactly
        self.S = int(sizes.max())
        if self._int8:
            self.S = -(-self.S // WBLOCK) * WBLOCK
        self._sizes = sizes
        self._offsets = offsets
        # per-partition wire payload bytes (4*s for f32; s + 4*ceil(s/BLOCK)
        # for int8) — every closed-form byte count below derives from these
        self._wsizes = np.asarray(
            [wire_size(int(s), getattr(cfg, "wire_dtype", "f32")) for s in sizes],
            np.int64,
        )

        # ---- snapshot values / eps / caches from the scalar init ----------
        V_pre = np.zeros((self.K_inst, self.S), np.float32)
        eps = np.ones((self.K_inst,), np.float32)
        for k in range(K):
            for j, h in enumerate(holders[k]):
                st = seed_sim.agents[h].owned[k]
                V_pre[inst_id[(k, j)], : sizes[k]] = st.value
                eps[inst_id[(k, j)]] = st.eps
        # per-INSTANCE merged table (all replicas equal at init); each
        # holder's own sequential merge can differ by ULP at rho >= 3, so a
        # per-partition row cannot represent the scalar oracle's state
        V_merged = V_pre.copy()
        owner_col = np.zeros((A, K), bool)
        for k in range(K):
            for h in holders[k]:
                owner_col[h, k] = True
        self._owner_col = owner_col
        self._bytes_total = self.net.pubsub.total_bytes()
        # message counters mirroring the scalar pubsub (init-phase membership
        # traffic included via the snapshot; the LOSSY path keeps them exact)
        self.messages_sent = self.net.pubsub.messages_sent
        self.messages_dropped = self.net.pubsub.messages_dropped

        # ---- trainers: the scalar constructor's LocalTrainer objects own
        # the per-agent RNG streams; drawing batch rows through their
        # draw_indices() keeps both engines' SGD inputs identical by
        # construction ----
        self._trainers = [seed_sim.trainers[a] for a in range(A)]
        self._act_trainers = self._trainers
        self._act_off = self._train_table(self._trainers, self._act_trainers)
        bs = [min(cfg.batch_size, len(shards[a][0])) for a in range(A)]
        # contiguous buckets of equal batch size (array_split shard sizes
        # differ by at most one, so there are at most two)
        self._buckets: List[Tuple[int, int, int]] = []
        start = 0
        for a in range(1, A + 1):
            if a == A or bs[a] != bs[start]:
                self._buckets.append((start, a, bs[start]))
                start = a

        # eval subset: shared stride helper => same agents as the scalar engine
        from repro.fl.rounds import eval_subset

        self._eval_idx = np.asarray(eval_subset(list(range(A)), cfg.eval_agents), np.int32)

        if self._lossy:
            self._init_lossy(seed_sim)
            return

        # round-0 warm-up traffic (agents fetch partitions absent from both
        # their owned set and the donor caches left behind by joins)
        fetch_bytes = fetch_msgs = 0
        fetch_pairs = fetch_rep_bytes = 0
        for a in range(A):
            ag = seed_sim.agents[a]
            for k in range(K):
                if k not in ag.owned and k not in ag.cache:
                    fetch_bytes += 16 + int(self._wsizes[k])
                    fetch_msgs += 2  # the fetch and its reply
                    fetch_pairs += 1
                    fetch_rep_bytes += int(self._wsizes[k])
        self._round0_fetch_bytes = fetch_bytes
        self._round0_fetch_msgs = fetch_msgs
        # per-channel split of the same closed forms (telemetry stream)
        self._tel_r0_fetch_n = fetch_pairs
        self._tel_r0_fetch_rep_bytes = fetch_rep_bytes

        # steady-state per-round traffic: every agent updates every non-owned
        # partition (one wire payload up + one reply) and each replica of a
        # rho_k>1 partition publishes once for consensus
        upd = int(np.sum((A - rho) * self._wsizes))
        replica = int(np.sum(np.where(rho > 1, rho * self._wsizes, 0)))
        self._round_bytes = 2 * upd + replica
        self._round_msgs = 2 * int(np.sum(A - rho)) + int(np.sum(np.where(rho > 1, rho, 0)))
        # per-channel steady-state traffic (telemetry stream): one UpdateModel
        # up + one reply back per (agent, non-owned partition); each replica
        # of a rho_k>1 partition publishes once, fanning out to the rho_k-1
        # other subscribers of the partition topic
        self._tel_upd_msgs = int(np.sum(A - rho))
        self._tel_upd_bytes = upd
        self._tel_rep_msgs = int(np.sum(np.where(rho > 1, rho, 0)))
        self._tel_rep_bytes = replica
        self._tel_rep_deliv = int(np.sum(np.where(rho > 1, rho * (rho - 1), 0)))

        # ---- per-phase routing tables (period = lcm of replication) -------
        # non-owner a targets H(k)[(round + a) % rho_k]; the pattern repeats
        # with period lcm(rho_k), so all gather/scatter index tensors are
        # precomputed once
        self._period = int(np.lcm.reduce(rho)) if len(rho) else 1
        agents_arr = np.arange(A)
        self._t_inst: List[np.ndarray] = []
        self._contrib_idx: List[np.ndarray] = []
        self._contrib_mask: List[np.ndarray] = []
        R_cap = 1
        for p in range(self._period):
            contrib: List[List[int]] = [[] for _ in range(self.K_inst)]
            t_inst = np.zeros((A, K), np.int32)
            for k in range(K):
                rk = len(holders[k])
                jsel = (p + agents_arr) % rk
                for a in range(A):
                    if owner_col[a, k]:
                        # owners read their OWN replica's post-consensus value:
                        # index into the merged section of the concatenated
                        # [V_pre; V_merged] value table the W-rebuild gathers
                        # from. Merged values are per-instance, not per-
                        # partition: the scalar oracle's mean starts at the
                        # holder's own value, so at rho >= 3 each holder's
                        # merged row differs by association order.
                        t_inst[a, k] = self.K_inst + inst_id[(k, holders[k].index(a))]
                    else:
                        i = inst_id[(k, int(jsel[a]))]
                        t_inst[a, k] = i
                        contrib[i].append(a)
            # owner contributes first (matches scalar pending-row order)
            rows = [[self._inst_owner[i]] + contrib[i] for i in range(self.K_inst)]
            R_cap = max(R_cap, max(len(r) for r in rows))
            self._t_inst.append(t_inst)
            self._contrib_idx.append(rows)  # ragged; padded below
        self.R_cap = R_cap
        for p in range(self._period):
            idx = np.zeros((self.K_inst, R_cap), np.int32)
            msk = np.zeros((self.K_inst, R_cap), np.float32)
            for i, row in enumerate(self._contrib_idx[p]):
                idx[i, : len(row)] = row
                msk[i, : len(row)] = 1.0
            self._contrib_idx[p] = idx
            self._contrib_mask.append(msk)

        # ---- replica-merge order (static under PERFECT) -------------------
        # scalar merge: np.mean over [own post-agg value] + arrivals; under
        # PERFECT the arrivals drain in publish order = holder agent
        # ascending. The sequential-sum merge must associate in exactly that
        # order, starting from the instance's own row.
        max_rho = int(rho.max()) if len(rho) else 1
        morder = np.zeros((self.K_inst, max_rho), np.int32)
        mmask = np.zeros((self.K_inst, max_rho), np.float32)
        for k in range(K):
            ids = [inst_id[(k, j)] for j in range(len(holders[k]))]
            by_agent = sorted(ids, key=lambda i: int(self._inst_owner[i]))
            for i in ids:
                row = [i] + [o for o in by_agent if o != i]
                morder[i, : len(row)] = row
                mmask[i, : len(row)] = 1.0
        self._morder_perf = morder
        self._mmask_perf = mmask

        # ---- state carried across rounds ---------------------------------
        # only the small per-instance value tables persist; the (A, N)
        # weight matrix is an INTERNAL tensor of the fused round call (never
        # a device-call boundary buffer — at 32 agents it is ~57 MB and the
        # allocation alone costs more than the round's math)
        self._V_pre = jnp.asarray(V_pre)
        self._V_merged = jnp.asarray(V_merged)
        self._eps = jnp.asarray(eps)
        self._last_phase = self._period - 1  # any phase: all replicas equal at init

        if self.recorder is not None:
            # eps replay on the host in float64: the scalar engine's eps is a
            # python float, and the device's f32 recursion drifts by an ULP —
            # the telemetry stream must carry the scalar's exact values. The
            # PERFECT contributor counts are static per routing phase.
            self._tel_eps64 = np.asarray(
                [
                    seed_sim.agents[int(self._inst_owner[i])]
                    .owned[int(self._inst_k[i])]
                    .eps
                    for i in range(self.K_inst)
                ],
                np.float64,
            )
            self._tel_r = [
                self._contrib_mask[p].sum(axis=1).astype(np.int64)
                for p in range(self._period)
            ]

        self._build_jitted()

    # -- jitted batched phases ---------------------------------------------
    def _local_sgd(self):
        """`sgd_all` bound to this fleet's batch buckets and SGD settings."""
        cfg = self.cfg
        return functools.partial(
            sgd_all, buckets=self._buckets, lr=float(cfg.lr),
            iters=int(cfg.local_iters),
            layout_t=tuple((name, tuple(shape)) for name, shape in self.layout),
        )

    def _build_jitted(self):
        cfg, layout = self.cfg, self.layout
        A, K, S = self.A, self.K, self.S
        offsets, sizes = self._offsets, self._sizes
        alpha = float(cfg.alpha)
        sgd = self._local_sgd()
        use_kernel = self._use_kernel
        # instance rows grouped by partition (k-major, so contiguous)
        inst_of_k = [np.nonzero(self._inst_k == k)[0] for k in range(K)]
        x_te = jnp.asarray(self.x_test)
        y_te = jnp.asarray(self.y_test)
        E = len(self._eval_idx)

        def build_W(V_pre, V_merged, t_inst, rows: int):
            """Assemble ``rows`` agents' flat weights from the concatenated
            value table: owners' t_inst entries point past K_inst into the
            merged section, everyone else's at the pre-merge value of the
            replica that served their UpdateModel reply. One concatenate =
            one output pass (a dynamic_update_slice chain copies the whole
            (rows, N) buffer K times on the CPU backend)."""
            with jax.named_scope("weight_build"):
                V_all = jnp.concatenate([V_pre, V_merged], axis=0)
                return jnp.concatenate(
                    [V_all[t_inst[:, k], : sizes[k]] for k in range(K)], axis=1
                )

        morder = jnp.asarray(self._morder_perf)
        mmask_m = jnp.asarray(self._mmask_perf)
        max_rho = int(self._morder_perf.shape[1])
        rho_inst = jnp.asarray(
            np.bincount(self._inst_k, minlength=K).astype(np.float32)[self._inst_k]
        )
        R_cap = int(self.R_cap)

        def agg_merge(V_merged, eps, W, W2, contrib_idx, contrib_mask):
            """Aggregation + replica consensus, given the pre/post local-SGD
            weight matrices. The contributor gather + sequential masked sum
            reduces each instance's deltas in the scalar oracle's pending
            order (own push first, then arrivals agent-ascending), so the
            f32 associations match the scalar engine bit-for-bit."""
            # eps recursion refreshed from r BEFORE applying (paper §2.2)
            r = jnp.sum(contrib_mask, axis=1)
            eps_new = jnp.where(
                r > 0, alpha * eps + (1.0 - alpha) / jnp.maximum(r, 1.0), eps
            )
            base = V_merged
            D = W - W2
            with jax.named_scope("contrib_gather"):
                G = contributor_rows(
                    D, contrib_idx, inst_of_k=inst_of_k, offsets=offsets,
                    sizes=sizes, S=S,
                )
            if use_kernel:
                # TPU: aggregate every (partition, replica-slot) instance in
                # ONE kernel launch. The kernel computes w - eps*masked_sum,
                # exactly the scalar engine's update (the 1/r lives in the
                # eps recursion).
                V_pre = aggregate_batched(base, G, contrib_mask, eps_new)
            else:
                # CPU/GPU: a sequential masked sum over the contributor
                # slots in scalar pending order, then one FMA-contracted
                # update
                agg = jnp.zeros((G.shape[0], S), jnp.float32)
                for j in range(R_cap):
                    agg = jnp.where(contrib_mask[:, j, None] > 0, agg + G[:, j], agg)
                V_pre = base - eps_new[:, None] * agg
            # pin ONE materialization of V_pre: without the barrier XLA may
            # recompute it at the merge's gather site with a different FMA
            # contraction than the direct use, skewing merged rows by an ULP
            V_pre = jax.lax.optimization_barrier(V_pre)
            # replica consensus: each instance averages [self] + the other
            # replicas in arrival (holder agent ascending) order — the
            # scalar engine's np.mean associates exactly this way
            with jax.named_scope("replica_merge"):
                acc = V_pre
                for j in range(1, max_rho):
                    acc = jnp.where(
                        mmask_m[:, j, None] > 0, acc + V_pre[morder[:, j]], acc
                    )
                # barrier the (constant) divisor too: XLA folds division by a
                # constant into multiply-by-reciprocal, off by an ULP for
                # rho=3 — scalar np.mean does a true divide
                V_merged_new = acc / jax.lax.optimization_barrier(rho_inst)[:, None]
            return V_pre, V_merged_new, eps_new

        def eval_rows(V_pre, V_merged_new, t_eval):
            # evaluate ONLY the sub-sampled agents: their assembled rows are
            # a few MB, so the full (A, N) matrix never leaves the program
            return eval_block(build_W(V_pre, V_merged_new, t_eval, E), x_te, y_te, layout)

        # telemetry: a python-bool trace-time gate — False adds nothing to
        # the jitted program (no extra outputs in the jaxpr); True adds one
        # (2,) aux output per round with the f32 norm metrics
        tel = self.recorder is not None

        def make_scan(gate_eval: bool):
            """The PERFECT device program: a window of W rounds as ONE
            device call, `lax.scan` over per-round xs (batch row indices +
            routing tables), carry = the small value tables (V_pre,
            V_merged, eps); the training set is an argument, gathered from
            in the body. A per-round call is a one-round window."""

            def body(x_tab, y_tab, carry, xs):
                V_pre, V_merged, eps = carry
                Ir, t_prev, idx, mask, t_eval, de = xs
                W = build_W(V_pre, V_merged, t_prev, A)
                W2 = sgd(W, *gather_batches(x_tab, y_tab, Ir))
                V_pre2, V_m2, eps2 = agg_merge(V_merged, eps, W, W2, idx, mask)
                if gate_eval:
                    accs = jax.lax.cond(
                        de,
                        lambda: eval_rows(V_pre2, V_m2, t_eval),
                        lambda: jnp.full((E,), jnp.nan, jnp.float32),
                    )
                else:
                    accs = eval_rows(V_pre2, V_m2, t_eval)
                if tel:
                    return (V_pre2, V_m2, eps2), (accs, metric_pair(W - W2, V_m2))
                return (V_pre2, V_m2, eps2), accs

            def scan_window(V_pre, V_merged, eps, x_tab, y_tab, xs_all):
                carry, ys = jax.lax.scan(
                    functools.partial(body, x_tab, y_tab), (V_pre, V_merged, eps), xs_all
                )
                return carry + (ys if tel else (ys,))

            return jax.jit(scan_window, donate_argnums=(0, 1, 2))

        self._scan_window_j = make_scan(self._eval_cadence > 1)

    # ===================== LOSSY (mask-stream) path ========================
    def _init_lossy(self, seed_sim):
        """State for the lossy-network batched path.

        The protocol's per-parameter math stays in one scanned device
        program a window; what loss/delay add is a tiny host-side
        control plane (integer/boolean numpy over (A, K)): the keyed fate
        stream shared with the scalar pubsub, the fetch warm-up state
        machine, and event queues for in-flight serves/arrivals/merges/
        cache updates. Delayed deltas and the value tables late messages
        read from live in small device-side history rings.

        Only membership-independent constants live here; everything shaped
        by the current membership is built by `_snapshot_from_scalar`, which
        also re-runs after every replayed membership-event round.
        """
        from repro.fl.rounds import TICKS_PER_ROUND

        cfg = self.cfg
        cond = cfg.conditions
        self._ticks = TICKS_PER_ROUND
        # delays are in tick units; a message delayed d ticks lands
        # ceil(d / TICKS) rounds late at its drain point
        self._Lu = (
            -(-cond.max_delay_rounds // TICKS_PER_ROUND) if cond.delay_prob > 0 else 0
        )
        self._HD = self._Lu + 1  # history ring depth (value ages 0..Lu)
        # in-flight event queues: bounded-depth rings indexed by
        # (consuming round) mod depth. Nothing stays in flight longer than
        # Lu rounds (delays are capped), so depth Lu+1 suffices; every slot
        # is drained exactly once per depth rounds. The window runner stacks
        # each round's drained events into dense per-round tensors that ride
        # the lax.scan as xs (the device state itself lives in the carry).
        self._qdepth = self._Lu + 1
        # int8/churn under PERFECT conditions also run this path; the scalar
        # engine never installed a fate stream there, so build one — every
        # draw degenerates to (delivered, delay 0), i.e. default delivery
        if seed_sim.fates is None:
            from repro.fl.rounds import MessageFates

            self._fates = MessageFates(cond, cfg.seed)
        else:
            self._fates = seed_sim.fates
        # membership-event rounds replay on the embedded scalar oracle; the
        # dense planes re-snapshot at each boundary (docs/ENGINE.md
        # "Churn re-snapshot")
        self._replay = sorted(
            {int(r) for r in (cfg.churn or {}) if 0 <= int(r) < cfg.rounds}
        )
        self._replay_set = frozenset(self._replay)
        # delivered-fate pubsub messages harvested at a boundary whose
        # recipient is offline: they drop at their delivery tick (round key)
        self._pending_drop_msgs: Dict[int, list] = {}
        # harvested in-flight replica values pending a version-filtered
        # merge, keyed by their merge round
        self._mail_merges: Dict[int, list] = {}
        # the constructor's membership broadcasts are still in flight; the
        # scalar ticks would deliver them during round 0, so deliver them
        # inert now — otherwise a later oracle replay would re-deliver them
        # mid-run (and drop any addressed to a then-offline agent)
        ps = seed_sim.net.pubsub
        for _i, msg in sorted(
            enumerate(ps._inflight), key=lambda e: (e[1].deliver_round, e[0])
        ):
            ps._inbox[msg.recipient].append(msg)
            ps.bytes_recv[msg.recipient] += msg.nbytes
        ps._inflight = []
        self._snapshot_from_scalar(0, harvest=False)

    def _snapshot_from_scalar(self, r0: int, harvest: bool) -> None:
        """Rebuild every membership-dependent dense structure from the
        scalar state — rows, instance tables, `_slot_inst`/`_widx`, the
        value/eps/version/cache/residual planes, closed-form traffic masks,
        replica pair tables, trainer buckets — and re-jit the span's device
        programs. Runs once at construction (harvest=False: the init-phase
        membership broadcasts were already delivered inert) and again after
        each replayed membership-event round
        (harvest=True: in-flight protocol messages are harvested into the
        delta ring / queue rings / a span-constant mail plane, so the fused
        span consumes them exactly where the scalar engine would)."""
        sim = self._seed
        ps = sim.net.pubsub
        cfg = self.cfg
        K, S = self.K, self.S
        sizes = self._sizes

        # ---- membership rows: live agents in scalar `active` (dict) order
        self._ids = [a for a, ag in sim.agents.items() if ag.live]
        A = len(self._ids)
        self.A = A
        self._row_of = {a: r for r, a in enumerate(self._ids)}
        self._ids_arr = np.asarray(self._ids, np.int64)
        self._ids_col = self._ids_arr[:, None]
        act = np.asarray([not ps.is_offline(a) for a in self._ids], bool)
        self._act = act
        self._act_idx = np.nonzero(act)[0].astype(np.int32)
        self._n_act = int(act.sum())
        self._full_active = bool(act.all())

        # ---- instance plane: one row per (partition, replica-slot) --------
        holders: List[List[int]] = [self.table.holders_of(k) for k in range(K)]
        inst_k: List[int] = []
        inst_owner_id: List[int] = []
        inst_id: Dict[Tuple[int, int], int] = {}
        for k in range(K):
            for j, h in enumerate(holders[k]):
                inst_id[(k, j)] = len(inst_k)
                inst_k.append(k)
                inst_owner_id.append(h)
        self.K_inst = len(inst_k)
        K_inst = self.K_inst
        self._inst_k = np.asarray(inst_k, np.int32)
        self._inst_owner_id = np.asarray(inst_owner_id, np.int64)
        # owner ROWS (scalar active-iteration order), the sort keys of every
        # ordered drain — after churn, dict order need not be id order
        self._inst_owner = np.asarray(
            [self._row_of[h] for h in inst_owner_id], np.int32
        )
        rho = np.asarray([len(h) for h in holders], np.int64)
        self._rho = rho
        max_rho = int(rho.max()) if len(rho) and int(rho.max()) > 0 else 1
        self._slot_inst = np.full((K, max_rho), -1, np.int32)
        for (k, j), i in inst_id.items():
            self._slot_inst[k, j] = i
        owner_col = np.zeros((A, K), bool)
        for i in range(K_inst):
            owner_col[self._inst_owner[i], self._inst_k[i]] = True
        self._owner_col = owner_col

        # sequential-reduction capacities for the ordered gather paths:
        # each other replica of a partition has at most one value in flight
        # per send round (ages 0..Lu), and each non-owner at most one
        # UpdateModel delta per in-flight send round
        self._mw = max(1, (max_rho - 1) * self._HD)
        self._cw = 1 + self._HD * (A - 1)
        # kernel-path contributor cap: owner + every other agent once per
        # delta-age window. The quantized kernel takes the owner's raw delta
        # through a dedicated input, so its contributor table holds only the
        # remote (wire) rows.
        if self._int8 and self._use_kernel:
            self.R_cap = max(1, (A - 1) * (self._Lu + 1))
        else:
            self.R_cap = 1 + (A - 1) * (self._Lu + 1)

        # per-round send counts/bytes are closed-form over ACTIVE senders:
        # loss only affects delivery, never whether a message is sent, and
        # offline agents send nothing (the scalar round skips them)
        send_mask = act[:, None] & ~owner_col & (rho > 0)[None, :]
        self._upd_send_mask = send_mask
        self._upd_msgs = int(send_mask.sum())
        self._upd_bytes = int((send_mask * self._wsizes[None, :]).sum())
        # ordered (source -> destination) instance pairs for replica sync.
        # Sources are instances whose owner is ACTIVE (offline holders skip
        # sync_replicas); destinations include offline holders — the pubsub
        # fans a publish out to every subscriber, drawing a fate each, and
        # a delivered fate to an offline holder is an offline drop at the
        # send round.
        src, dst = [], []
        for k in range(K):
            insts = np.nonzero(self._inst_k == k)[0]
            if len(insts) <= 1:
                continue
            for i in insts:
                if not act[self._inst_owner[i]]:
                    continue
                for j in insts:
                    if i != j:
                        src.append(int(i))
                        dst.append(int(j))
        self._rep_src = np.asarray(src, np.int32)
        self._rep_dst = np.asarray(dst, np.int32)
        self._rep_src_agent = self._inst_owner_id[self._rep_src]
        self._rep_dst_agent = self._inst_owner_id[self._rep_dst]
        self._rep_k = self._inst_k[self._rep_src]
        self._rep_dst_act = (
            act[self._inst_owner[self._rep_dst]]
            if len(dst)
            else np.zeros(0, bool)
        )
        pub_inst = sorted({int(i) for i in src})
        self._pub_msgs = len(pub_inst)
        self._pub_bytes = (
            int(np.sum(self._wsizes[self._inst_k[pub_inst]])) if pub_inst else 0
        )

        # W-assembly index into concat([V (K_inst rows), C (A*K rows)]):
        # owners read their own instance value, everyone else their cache row
        widx = np.zeros((A, K), np.int32)
        inst_of = {
            (int(self._inst_owner[i]), int(self._inst_k[i])): i
            for i in range(K_inst)
        }
        for r in range(A):
            for k in range(K):
                widx[r, k] = inst_of.get((r, k), K_inst + r * K + k)
        self._widx = widx

        # ---- value / eps / version / cache / residual planes --------------
        V = np.zeros((K_inst, S), np.float32)
        # eps lives on the HOST in float64: the scalar engine's per-partition
        # eps is a python float, and its recursion must be replayed in the
        # same precision (f32 replay drifts by an ULP, which the int8 codec
        # amplifies to a full quantization step).
        eps64 = np.ones(K_inst, np.float64)
        ver = np.zeros(K_inst, np.int64)
        for i in range(K_inst):
            st = sim.agents[int(self._inst_owner_id[i])].owned[int(self._inst_k[i])]
            V[i, : sizes[self._inst_k[i]]] = st.value
            eps64[i] = st.eps
            ver[i] = st.version
        self._Vl = jnp.asarray(V)
        self._eps64 = eps64
        self._ver = ver
        # explicit cache plane + fetch warm-up state. A slot stays at its
        # last successfully delivered value — exactly the scalar
        # cache-staleness semantics under loss.
        C = np.zeros((A, K, S), np.float32)
        has = np.zeros((A, K), bool)
        for r, a in enumerate(self._ids):
            for k, val in sim.agents[a].cache.items():
                C[r, k, : sizes[k]] = val
                has[r, k] = True
        self._has_cache = has
        self._C = jnp.asarray(C)
        # error-feedback residuals, one per (sender, partition) wire slice.
        # Owner positions carry the agent's (frozen, never again read)
        # residual from any pre-ownership sends — matching the scalar
        # _delta_err dict, which keeps stale entries across handoffs.
        if self._int8:
            E = np.zeros((A, K, S), np.float32)
            for r, a in enumerate(self._ids):
                for k, err in sim.agents[a]._delta_err.items():
                    if err is not None:
                        E[r, k, : len(err)] = err
        # delta ring: in-flight delta windows, one entry per delay age.
        # f32 (and the int8 CPU path) carry the (A, N) plane — for int8 the
        # rows hold the DEQUANTIZED wire values with the owner's own slices
        # kept raw; the int8 kernel path instead rings the int8 codes + the
        # per-block scale planes and dequantizes inside the fused kernel.
        if self._int8 and self._use_kernel:
            nb = S // WBLOCK
            ring_np = (
                np.zeros((self._Lu, A, K, S), np.int8),
                np.zeros((self._Lu, A, K, nb), np.float32),
            )
        else:
            ring_np = np.zeros((self._Lu, A, self.N), np.float32)
        self._serve_ring: List[list] = [[] for _ in range(self._qdepth)]
        self._arr_ring: List[list] = [[] for _ in range(self._qdepth)]
        self._cache_ring: List[list] = [[] for _ in range(self._qdepth)]
        self._merge_ring: List[list] = [[] for _ in range(self._qdepth)]
        self._seq = 0
        self._t = r0
        self._mail_merges = {}
        self._pending_drop_msgs = {}
        mail_vals: List[np.ndarray] = []
        if harvest:
            # may raise _HarvestDeferred; pubsub mutations are deferred to
            # the commit step inside, so a raise leaves the pubsub intact
            self._harvest_pubsub(r0, inst_of, ring_np, mail_vals)
        if self._int8 and self._use_kernel:
            self._ring = (jnp.asarray(ring_np[0]), jnp.asarray(ring_np[1]))
        else:
            self._ring = jnp.asarray(ring_np)
        if self._int8:
            self._E = jnp.asarray(E)
        else:
            self._E = jnp.zeros((1,), jnp.float32)
        self._Vagg_hist = jnp.zeros((self._HD, K_inst, S), jnp.float32)
        self._Vstart_hist = jnp.zeros((self._HD, K_inst, S), jnp.float32)
        # span-constant mail plane: wire images of harvested in-flight
        # reply/replica payloads, referenced by _KIND_MAIL cache events and
        # mail merge entries (the value histories the span rings start empty,
        # so pre-span values must travel alongside)
        self._V_mail = (
            np.stack(mail_vals).astype(np.float32) if mail_vals else None
        )

        # ---- trainers / batch buckets / eval rows -------------------------
        # the scalar constructor's (and _apply_churn's) LocalTrainer objects
        # own the per-agent RNG streams; drawing batch rows through their
        # draw_indices() keeps both engines' SGD inputs identical. Only
        # ACTIVE agents train — offline agents' streams freeze, like the
        # scalar round skipping them.
        self._trainers = [sim.trainers[a] for a in self._ids]
        self._act_trainers = [
            tr for tr, on in zip(self._trainers, act) if on
        ]
        self._act_off = self._train_table(self._trainers, self._act_trainers)
        bs = [min(cfg.batch_size, len(tr.x)) for tr in self._act_trainers]
        self._buckets = []
        start = 0
        n_act = len(bs)
        for i in range(1, n_act + 1):
            if i == n_act or bs[i] != bs[start]:
                self._buckets.append((start, i, bs[start]))
                start = i
        from repro.fl.rounds import eval_subset

        self._eval_idx = np.asarray(
            [self._row_of[a] for a in eval_subset(list(self._ids), cfg.eval_agents)],
            np.int32,
        )

        # ---- counters / telemetry handoff --------------------------------
        self.messages_sent = ps.messages_sent
        self.messages_dropped = ps.messages_dropped
        self._bytes_total = ps.total_bytes()
        ps.telemetry = None
        if harvest and (self.recorder is not None or self._eval_cadence > 1):
            # scan-gated rounds reuse the last computed accuracies; refresh
            # from the replayed round's evaluation so the reuse crosses the
            # boundary intact
            if self.recorder is not None and self.recorder.rows:
                self._last_accs = np.asarray(
                    self.recorder.rows[-1]["accs"], np.float32
                )
            else:
                self._last_accs = np.asarray(sim._eval_accs(), np.float32)
        self._build_jitted_lossy()

    def _harvest_pubsub(self, r0, inst_of, ring_np, mail_vals) -> None:
        """Convert the scalar pubsub's delivered-but-undrained inbox
        messages and its in-flight queue into span state: UpdateModel
        payloads into the delta ring + arrival entries, fetches into serve
        entries, reply/replica values into the mail plane, membership
        broadcasts delivered inert, and delivered-fate messages to offline
        recipients into pending tick-of-delivery drops.

        Classification is read-only; pubsub mutations commit at the end, so
        an unsupported straggler (`_HarvestDeferred`, only reachable when
        max_delay_rounds > TICKS_PER_ROUND) leaves the pubsub untouched for
        the scalar retry round. Within one drain slot, harvested inbox
        entries precede in-flight entries in delivery order — exactly the
        inbox fill order for max_delay_rounds <= TICKS_PER_ROUND; beyond
        that, stragglers from different source rounds may interleave with
        in-span arrivals in send order rather than delivery order."""
        from repro.core.api import (
            FETCH_TOPIC,
            REPLY_TOPIC,
            REPLICA_TOPIC,
            UPDATE_TOPIC,
        )

        sim = self._seed
        ps = sim.net.pubsub
        TICKS = self._ticks
        wire = sim.wire
        sizes, offsets = self._sizes, self._offsets
        row_of = self._row_of
        act = self._act
        Lu = self._Lu

        arr_items: list = []    # (deliver_tick, order, drain_round, entry)
        serve_items: list = []
        new_inboxes: Dict[int, list] = {}
        deliveries: list = []   # messages delivered whole (dead/member)
        order = 0

        def active_row(aid):
            r = row_of.get(aid)
            return r if (r is not None and act[r]) else None

        def pad_val(wp):
            val = np.zeros(self.S, np.float32)
            dec = wire.decode(wp)
            val[: len(dec)] = dec
            return val

        def ring_write(age, a_row, k, wp):
            if not (0 <= age < Lu):
                raise _HarvestDeferred
            if self._int8 and self._use_kernel:
                q, sc = wp
                ring_np[0][age, a_row, k, : len(q)] = q
                ring_np[1][age, a_row, k, : len(sc)] = sc
            else:
                ring_np[age, a_row, offsets[k] : offsets[k] + sizes[k]] = (
                    wire.decode(wp)
                )

        def take_update(msg, order, u):
            h_row = row_of[msg.recipient]
            k, wp = msg.payload
            i = inst_of.get((h_row, int(k)))
            if i is None:
                return  # recipient no longer owns k: scalar collect drops it
            a_row = active_row(msg.sender)
            if a_row is None:
                raise _HarvestDeferred  # sender left/offline mid-flight
            send_r = msg.sent_round // TICKS
            ring_write(r0 - send_r - 1, a_row, int(k), wp)
            arr_items.append(
                (msg.deliver_round, order, u, (send_r, a_row, int(k), int(i)))
            )

        def take_fetch(msg, order, u):
            a_row = active_row(msg.sender)
            if a_row is None:
                raise _HarvestDeferred  # requester left/offline mid-flight
            (k,) = msg.payload
            i = inst_of.get((row_of[msg.recipient], int(k)))
            if i is None:
                return  # holder lost k: scalar serve_reply returns silently
            send_r = msg.sent_round // TICKS
            serve_items.append(
                (msg.deliver_round, order, u, (send_r, a_row, int(k), int(i)))
            )

        def take_reply(msg):
            a_row = row_of[msg.recipient]
            h_row = row_of.get(msg.sender)
            if h_row is None:
                raise _HarvestDeferred  # serving holder left mid-flight
            k, wp = msg.payload
            m = len(mail_vals)
            mail_vals.append(pad_val(wp))
            dv = max(msg.deliver_round, TICKS * r0)
            self._cache_ring[(dv // TICKS) % self._qdepth].append(
                (dv, msg.sent_round, h_row, self._seq, a_row, int(k),
                 _KIND_MAIL, r0, m)
            )
            self._seq += 1

        def take_replica(msg):
            d_row = row_of[msg.recipient]
            s_row = row_of.get(msg.sender)
            if s_row is None:
                raise _HarvestDeferred  # publishing holder left mid-flight
            k, wp, ver = msg.payload
            di = inst_of.get((d_row, int(k)))
            if di is None:
                return  # no longer an owner: scalar merge filter drops it
            m = len(mail_vals)
            mail_vals.append(pad_val(wp))
            dv = max(msg.deliver_round, TICKS * r0)
            self._mail_merges.setdefault(dv // TICKS, []).append(
                (dv - 1, s_row, int(ver), int(di), m, msg.sent_round)
            )

        def lat(d):
            return -(-d // TICKS)

        # -- delivered-but-undrained inboxes of ACTIVE agents. Offline
        # agents' inboxes stay in the pubsub untouched — the scalar engine
        # would not drain them either until they come back online, which is
        # itself a membership event that replays through the oracle.
        for r, aid in enumerate(self._ids):
            if not act[r]:
                continue
            keep = []
            for msg in ps._inbox.get(aid, []):
                order += 1
                if msg.topic == UPDATE_TOPIC:
                    take_update(msg, order, r0)
                elif msg.topic == FETCH_TOPIC:
                    take_fetch(msg, order, r0)
                elif msg.topic == REPLY_TOPIC:
                    take_reply(msg)
                elif msg.topic.startswith(REPLICA_TOPIC):
                    take_replica(msg)
                else:
                    keep.append(msg)  # membership traffic: inert
            new_inboxes[aid] = keep

        # -- in-flight messages, in delivery order (ties broken by queue
        # position — the order the scalar tick appends them to an inbox)
        for _idx, msg in sorted(
            enumerate(ps._inflight), key=lambda e: (e[1].deliver_round, e[0])
        ):
            order += 1
            rrow = row_of.get(msg.recipient)
            if rrow is None:
                # dead recipient: deliver into its (never-drained) inbox
                deliveries.append(msg)
                continue
            if not act[rrow]:
                # delivered-fate message to an offline recipient: the scalar
                # tick drops it at its delivery tick
                self._pending_drop_msgs.setdefault(
                    msg.deliver_round // TICKS, []
                ).append(msg)
                continue
            send_r = msg.sent_round // TICKS
            d = msg.deliver_round - msg.sent_round
            if msg.topic == UPDATE_TOPIC:
                take_update(msg, order, send_r + lat(d))
            elif msg.topic == FETCH_TOPIC:
                take_fetch(msg, order, send_r + lat(d))
            elif msg.topic == REPLY_TOPIC:
                take_reply(msg)
            elif msg.topic.startswith(REPLICA_TOPIC):
                take_replica(msg)
            else:
                deliveries.append(msg)  # membership traffic: deliver inert

        # -- commit (no raises past this point) -----------------------------
        for aid, keep in new_inboxes.items():
            ps._inbox[aid] = keep
        for msg in deliveries:
            ps._inbox[msg.recipient].append(msg)
            ps.bytes_recv[msg.recipient] += msg.nbytes
        ps._inflight = []
        for _dv, _o, u, entry in sorted(serve_items, key=lambda e: (e[0], e[1])):
            self._serve_ring[u % self._qdepth].append(entry)
        for _dv, _o, u, entry in sorted(arr_items, key=lambda e: (e[0], e[1])):
            self._arr_ring[u % self._qdepth].append(entry)

    def _has_active(self) -> bool:
        sim = self._seed
        ps = sim.net.pubsub
        return any(
            ag.live and not ps.is_offline(a) for a, ag in sim.agents.items()
        )

    def _scalar_to_device(self, r0: int) -> bool:
        """Enter a fused span at round r0: snapshot + harvest from the
        scalar state. Returns False (staying in scalar mode for this round)
        when no agent is active or a straggler defers the harvest."""
        if not self._has_active():
            return False
        try:
            self._snapshot_from_scalar(r0, harvest=True)
        except _HarvestDeferred:
            return False
        self._on_device = True
        return True

    def _device_to_scalar(self, rnd: int) -> None:
        """Leave the fused span before replaying round `rnd` on the scalar
        oracle: write the dense device state back into the scalar agents
        (via the core/api snapshot hooks) and re-inject every pending queue
        entry as a pubsub message, so the oracle resumes from exactly the
        state the span produced."""
        from repro.core.api import (
            FETCH_TOPIC,
            REPLY_TOPIC,
            REPLICA_TOPIC,
            UPDATE_TOPIC,
        )
        from repro.fl.rounds import CH_FETCH, CH_UPDATE
        from repro.p2p.ipfs_sim import Message

        sim = self._seed
        ps = sim.net.pubsub
        TICKS = self._ticks
        wire = sim.wire
        sizes, offsets, wsizes = self._sizes, self._offsets, self._wsizes
        K, K_inst = self.K, self.K_inst
        Vl = np.asarray(self._Vl)
        Cpl = np.asarray(self._C)
        Vagg = np.asarray(self._Vagg_hist)
        Vstart = np.asarray(self._Vstart_hist)
        int8_kernel = self._int8 and self._use_kernel
        if int8_kernel:
            ring_q = np.asarray(self._ring[0])
            ring_s = np.asarray(self._ring[1])
            ring_f = None
        else:
            ring_f = np.asarray(self._ring)
        E = np.asarray(self._E) if self._int8 else None

        # ---- protocol-state writeback ------------------------------------
        for r, aid in enumerate(self._ids):
            owned = {}
            for k in range(K):
                i = self._widx[r, k]
                if i < K_inst:  # owner rows index into the instance table
                    owned[k] = (Vl[i, : sizes[k]], self._eps64[i], self._ver[i])
            cache = {
                k: Cpl[r, k, : sizes[k]]
                for k in range(K)
                if self._has_cache[r, k]
            }
            derr = (
                {k: E[r, k, : sizes[k]] for k in range(K)}
                if E is not None
                else None
            )
            sim.agents[aid].import_state(owned, cache, derr)

        # ---- pubsub clock / counters / telemetry -------------------------
        ps.round = TICKS * rnd
        ps.messages_sent = self.messages_sent
        ps.messages_dropped = self.messages_dropped
        delta_b = self._bytes_total - ps.total_bytes()
        if delta_b:
            # per-round engine traffic is tracked in aggregate; only the
            # total is observable (total_bytes sums the per-sender dict)
            ps.bytes_sent[self._ids[0]] += delta_b
        ps.telemetry = self.recorder

        # ---- re-inject pending queue entries as pubsub messages ----------
        # sort key = (send tick, phase rank, scalar within-tick order): the
        # _inflight list must hold messages in send order so the tick scan
        # delivers same-tick arrivals exactly like the scalar rounds did
        f = self._fates
        out = []
        for s in range(self._qdepth):
            for send_r, a, k, inst in self._serve_ring[s]:
                aid_req = int(self._ids_arr[a])
                _de, d = f.draw_one(CH_FETCH, send_r, aid_req, k)
                st = TICKS * send_r
                out.append(
                    ((st, 0, int(a), int(k)), st + int(d),
                     Message(FETCH_TOPIC, aid_req, (int(k),), st, st + int(d),
                             16, int(self._inst_owner_id[inst])))
                )
            for send_r, a, k, inst in self._arr_ring[s]:
                aid_snd = int(self._ids_arr[a])
                _de, d = f.draw_one(CH_UPDATE, send_r, aid_snd, k)
                st = TICKS * send_r + 2
                age = rnd - send_r - 1
                if int8_kernel:
                    # codes/scales ride the ring verbatim — re-injection is
                    # bitwise, no decode/re-encode round trip
                    nb = -(-int(sizes[k]) // WBLOCK)
                    payload = (
                        ring_q[age, a, k, : sizes[k]].copy(),
                        ring_s[age, a, k, :nb].copy(),
                    )
                else:
                    img = ring_f[age, a, offsets[k] : offsets[k] + sizes[k]]
                    payload = wire.encode_value(img)[0]
                out.append(
                    ((st, 1, int(a), int(k)), st + int(d),
                     Message(UPDATE_TOPIC, aid_snd, (int(k), payload), st,
                             st + int(d), int(wsizes[k]),
                             int(self._inst_owner_id[inst])))
                )
            for ctr, sc, holder, seq, a, k, kind, src_r, inst in self._cache_ring[s]:
                if kind == _KIND_MAIL:
                    img = self._V_mail[inst, : sizes[k]]
                elif kind == _KIND_START:
                    img = Vstart[rnd - 1 - src_r, inst, : sizes[k]]
                else:
                    img = Vagg[rnd - 1 - src_r, inst, : sizes[k]]
                out.append(
                    ((sc, 2, int(holder), seq), ctr,
                     Message(REPLY_TOPIC, int(self._ids_arr[holder]),
                             (int(k), wire.encode_value(img)[0]), sc, ctr,
                             int(wsizes[k]), int(self._ids_arr[a])))
                )
            for send_r, si, di, ver_sent, dl in self._merge_ring[s]:
                k = int(self._inst_k[si])
                img = Vagg[rnd - 1 - send_r, si, : sizes[k]]
                st = TICKS * send_r + 3
                out.append(
                    ((st, 3, int(self._inst_owner[si]), int(si)), st + int(dl),
                     Message(f"{REPLICA_TOPIC}/{k}",
                             int(self._inst_owner_id[si]),
                             (k, wire.encode_value(img)[0], int(ver_sent)),
                             st, st + int(dl), int(wsizes[k]),
                             int(self._inst_owner_id[di])))
                )
        for _u, entries in sorted(self._mail_merges.items()):
            for key_tick, src_row, ver_sent, di, m, sent_tick in entries:
                k = int(self._inst_k[di])
                img = self._V_mail[m, : sizes[k]]
                out.append(
                    ((sent_tick, 3, int(src_row), int(di)), key_tick + 1,
                     Message(f"{REPLICA_TOPIC}/{k}",
                             int(self._ids_arr[src_row]),
                             (k, wire.encode_value(img)[0], int(ver_sent)),
                             sent_tick, key_tick + 1, int(wsizes[k]),
                             int(self._inst_owner_id[di])))
                )
        for _u in sorted(self._pending_drop_msgs):
            for msg in self._pending_drop_msgs[_u]:
                out.append(((msg.sent_round, 4, 0, 0), msg.deliver_round, msg))
        out.sort(key=lambda e: e[0])
        for _key, dv, msg in out:
            if dv < TICKS * rnd:
                # already due: the scalar tick would have delivered it
                ps._inbox[msg.recipient].append(msg)
                ps.bytes_recv[msg.recipient] += msg.nbytes
            else:
                ps._inflight.append(msg)
        for ring in (self._serve_ring, self._arr_ring,
                     self._cache_ring, self._merge_ring):
            for slot in ring:
                slot.clear()
        self._mail_merges = {}
        self._pending_drop_msgs = {}
        self._on_device = False

    def _live_ids(self) -> List[int]:
        """Live agent ids in scalar iteration order — the row order of
        `agent_weights()`. Reads the oracle directly while in scalar mode
        (between an event replay and the next span)."""
        if self._lossy and not self._on_device:
            return [a for a, ag in self._seed.agents.items() if ag.live]
        return list(self._ids)

    def agent_ids(self) -> List[int]:
        return self._live_ids()

    def _build_jitted_lossy(self):
        layout = self.layout
        A, K, N, S, K_inst = self.A, self.K, self.N, self.S, self.K_inst
        Lu, HD = self._Lu, self._HD
        sizes, offsets = self._sizes, self._offsets
        sgd = self._local_sgd()
        LA = (Lu + 1) * A
        use_kernel = self._use_kernel
        int8 = self._int8
        CW = int(self._cw)   # contributor slots (CPU sequential-sum path)
        MW = int(self._mw)   # replica-merge slots (ordered sequential merge)
        owner3 = jnp.asarray(self._owner_col)[:, :, None]
        inst_k_j = jnp.asarray(self._inst_k)
        inst_owner_j = jnp.asarray(self._inst_owner)
        WNB = S // WBLOCK if int8 else 0
        widx_eval = self._widx[self._eval_idx]
        # active-row structures: SGD runs over ONLINE rows only; python-level
        # branches keep every jaxpr byte-identical to the fixed-membership
        # programs when the whole membership is online
        full_active = self._full_active
        act_idx_j = jnp.asarray(self._act_idx)
        widx_act = self._widx if full_active else self._widx[self._act_idx]
        act3 = jnp.asarray(self._act)[:, None, None]
        # span-constant mail plane (harvested in-flight reply/replica wire
        # values); appended to each gather table only when non-empty so
        # churn-free spans keep their exact jaxprs
        MAIL = 0 if self._V_mail is None else int(self._V_mail.shape[0])
        V_mail_j = jnp.asarray(self._V_mail) if MAIL else None
        inst_of_k = [np.nonzero(self._inst_k == k)[0] for k in range(K)]
        x_te = jnp.asarray(self.x_test)
        y_te = jnp.asarray(self.y_test)

        def row_slices(idx):
            """Static one-row slices that assemble the flat weights of the
            rows of ``idx``, a (rows, K) index into [V; C as (A*K, S)] fixed
            for the span (owners read their instance row of V, everyone else
            their (row, k) cache slot): per partition, (plane, start, limit)
            per row. A row gather of the (A*K, S) cache plane compiles to a
            loop of one dynamic slice per row on a TPU, about six device ops
            a row."""
            plan = []
            for k in range(K):
                s, col = int(sizes[k]), []
                for i in idx[:, k].tolist():
                    if i < K_inst:
                        col.append((0, (i, 0), (i + 1, s)))
                    else:
                        r, kk = divmod(i - K_inst, K)
                        col.append((1, (r, kk, 0), (r + 1, kk + 1, s)))
                plan.append(col)
            return plan

        plan_act, plan_eval = row_slices(widx_act), row_slices(widx_eval)

        def build_W(V, C, plan):
            with jax.named_scope("weight_build"):
                planes = (V, C)
                return jnp.concatenate(
                    [
                        jnp.concatenate(
                            [jax.lax.slice(planes[p], a, b).reshape(1, -1) for p, a, b in col],
                            axis=0,
                        )
                        for col in plan
                    ],
                    axis=1,
                )

        def pre(V, C, Vstart_hist, Vagg_hist, c0_mask, c0_src):
            """Phase 0: roll the start-of-round value ring, apply the cache
            updates the scalar engine would drain before LoadModel, and
            assemble all agents' flat weights. The value rings store WIRE
            values — every consumer (fetch/UpdateModel-reply cache writes,
            replica merges) saw the payload after one trip over the wire, so
            under int8 the authoritative V stays raw while the ring entry is
            its quantize->dequantize image."""
            with jax.named_scope("wire_codec"):
                V0 = qdq_rows(V) if int8 else V
            with jax.named_scope("ring_roll"):
                Vstart_new = jnp.concatenate([V0[None], Vstart_hist[:-1]], axis=0)
            with jax.named_scope("weight_build"):
                parts0 = [
                    Vstart_new.reshape(HD * K_inst, S),
                    Vagg_hist.reshape(HD * K_inst, S),
                ]
                if MAIL:
                    parts0.append(V_mail_j)
                T0 = jnp.concatenate(parts0, axis=0)
                C0 = jnp.where(c0_mask[:, :, None], T0[c0_src], C)
            W = build_W(V, C0, plan_act)
            return Vstart_new, C0, W

        def core_main(V, C0, D_now, ring, Vagg_hist, Vstart_new, E,
                      msrc, eps_new, mmask, merge_cnt, c2_mask, c2_src, kidx, kmask):
            """Phases 2-3: aggregate every (partition, replica-slot) instance
            from the current + in-flight delta windows, run the
            version-filtered replica consensus, reply-driven cache updates,
            and roll the history rings. `eps_new` is the post-recursion
            staleness weight, computed on the HOST in float64 by the control
            plane (`_control_round`) — the scalar engine's eps is a python
            float, and replaying its recursion in device f32 drifts by an
            ULP, which quantization then amplifies to a full scale step.

            int8 wire: every non-owner (a, k) delta slice is quantized (with
            the per-slice error-feedback residual E, updated at send time —
            loss-independent, exactly like the scalar encode) before joining
            the delta ring; owner slices never transit and stay raw. On the
            kernel path the ring carries the int8 codes + scale planes and
            dequantize fuses into the aggregation kernel's masked-sum; on
            the CPU path the ring carries the dequantized (A, N) plane with
            raw owner slices mixed in."""
            if int8:
                # row (a, k) is agent a's slice of partition k, zero beyond
                # s_k (whole zero blocks quantize to zero)
                with jax.named_scope("contrib_gather"):
                    Dplane = partition_plane(D_now, offsets=offsets, sizes=sizes, S=S)
                with jax.named_scope("wire_codec"):
                    qn, scn, ne = quantize_rows(Dplane, E)
                    # offline agents never send, so their error-feedback
                    # residuals must freeze exactly like the scalar dict entries
                    keep3 = owner3 if full_active else (owner3 | ~act3)
                    E_new = jnp.where(keep3, E, ne)
            else:
                E_new = E
            if int8 and use_kernel:
                # fused path: gather contributor CODES + SCALES per instance
                # (owner excluded from kidx by the control plane; its raw
                # delta enters through the kernel's dedicated own-input)
                Q_hist, S_hist = ring
                with jax.named_scope("contrib_gather"):
                    Q_all = jnp.concatenate([qn[None], Q_hist], axis=0).reshape(LA, K, S)
                    S_all = jnp.concatenate([scn[None], S_hist], axis=0).reshape(LA, K, WNB)
                    G_q = Q_all[kidx, inst_k_j[:, None]]     # (K_inst, R, S)
                    G_s = S_all[kidx, inst_k_j[:, None]]     # (K_inst, R, WNB)
                    d_own = Dplane[inst_owner_j, inst_k_j]   # (K_inst, S)
                V_agg = aggregate_batched_q(
                    V, d_own, G_q, G_s, kmask,
                    jnp.ones((K_inst,), jnp.float32), eps_new,
                )
                with jax.named_scope("ring_roll"):
                    ring_new = (
                        jnp.concatenate([qn[None], Q_hist], axis=0)[:Lu],
                        jnp.concatenate([scn[None], S_hist], axis=0)[:Lu],
                    )
            else:
                if int8:
                    # wire image of this round's delta plane: dequantized
                    # slices for remote readers, raw slices at owner positions
                    with jax.named_scope("wire_codec"):
                        deq = dequantize_rows(qn, scn)
                    D_use = jnp.concatenate(
                        [
                            jnp.where(
                                owner3[:, k],
                                jax.lax.dynamic_slice(
                                    D_now, (0, int(offsets[k])), (A, int(sizes[k]))
                                ),
                                deq[:, k, : sizes[k]],
                            )
                            for k in range(K)
                        ],
                        axis=1,
                    )
                else:
                    D_use = D_now
                D_all = jnp.concatenate([D_use[None], ring], axis=0).reshape(LA, N)
                # the contributor rows (current + ring-buffer ages) in the
                # (K_inst, R, S) layout, in scalar DELIVERY order (kidx, own
                # delta first), so the sequential masked sum (the kernel's,
                # or the slot loop's off-TPU) associates exactly like the
                # scalar oracle's np.sum over pending deltas
                with jax.named_scope("contrib_gather"):
                    G = contributor_rows(
                        D_all, kidx, inst_of_k=inst_of_k, offsets=offsets,
                        sizes=sizes, S=S,
                    )
                if use_kernel:
                    V_agg = aggregate_batched(V, G, kmask, eps_new)
                else:
                    agg = jnp.zeros((G.shape[0], S), jnp.float32)
                    for j in range(CW):
                        agg = jnp.where(kmask[:, j, None] > 0, agg + G[:, j], agg)
                    V_agg = V - eps_new[:, None] * agg
                with jax.named_scope("ring_roll"):
                    ring_new = jnp.concatenate([D_use[None], ring], axis=0)[:Lu]
            # pin ONE materialization before the merge gathers the wire
            # image: a recompute at the gather site may pick a different FMA
            # contraction than the direct use (see the PERFECT-path barrier)
            V_agg = jax.lax.optimization_barrier(V_agg)
            # everything a post-aggregate value feeds (UpdateModel-reply
            # cache writes, replica publishes) crossed the wire: ring/table
            # the wire image, keep the authoritative V_agg raw
            with jax.named_scope("wire_codec"):
                V_aggw = qdq_rows(V_agg) if int8 else V_agg
            # replica consensus: mean of self + version-kept arrived values
            # (late values read the post-aggregate ring at their send age).
            # Sequential adds in the control plane's landing-tick order keep
            # the association identical to the scalar np.mean over
            # [self] + arrivals.
            with jax.named_scope("replica_merge"):
                Vm_flat = jnp.concatenate(
                    [V_aggw[None], Vagg_hist[: HD - 1]], axis=0
                ).reshape(HD * K_inst, S)
                if MAIL:
                    Vm_flat = jnp.concatenate([Vm_flat, V_mail_j], axis=0)
                acc = V_agg
                for j in range(MW):
                    acc = jnp.where(mmask[:, j, None] > 0, acc + Vm_flat[msrc[:, j]], acc)
                V_new = acc / (1.0 + merge_cnt)[:, None]
                # phase-2 cache updates (may reference this round's post-agg table)
                parts2 = [
                    Vstart_new.reshape(HD * K_inst, S),
                    Vagg_hist.reshape(HD * K_inst, S),
                    V_aggw,
                ]
                if MAIL:
                    parts2.append(V_mail_j)
                T2 = jnp.concatenate(parts2, axis=0)
                C2 = jnp.where(c2_mask[:, :, None], T2[c2_src], C0)
            with jax.named_scope("ring_roll"):
                Vagg_hist_new = jnp.concatenate([V_aggw[None], Vagg_hist[:-1]], axis=0)
            return V_new, C2, ring_new, Vagg_hist_new, E_new

        def eval_lossy(V_new, C2):
            # evaluate the sub-sampled agents on end-of-round state
            return eval_block(build_W(V_new, C2, plan_eval), x_te, y_te, layout)

        # telemetry: python-bool trace-time gate — False keeps the jitted
        # program's jaxpr unchanged; True adds the (2,) f32 norm-metric aux
        # output (deltas RAW pre-quantize, values the authoritative plane)
        tel = self.recorder is not None

        def expand_rows(D_act):
            # scatter the online rows' deltas into the full (A, N) plane;
            # offline rows stay zero (they neither send nor contribute)
            if full_active:
                return D_act
            return jnp.zeros((A, N), jnp.float32).at[act_idx_j].set(D_act)

        E = len(self._eval_idx)

        def make_scan(gate_eval: bool):
            """The LOSSY device program: pre / SGD / core in one scanned
            body, one device call per W-round window (a per-round call is a
            one-round window). The carry is the fixed-shape device state
            (weights plane, cache plane, delta ring, value-history rings);
            the host control plane's per-round dense tensors ride as scan
            xs."""

            def body(x_tab, y_tab, carry, xs):
                V, C, ring, Vagg_hist, Vstart_hist, Eres = carry
                (Ir, c0_mask, c0_src, msrc, eps, mmask, cnt,
                 c2_mask, c2_src, kidx, kmask, de) = xs
                Vstart_new, C0, W = pre(V, C, Vstart_hist, Vagg_hist, c0_mask, c0_src)
                W2 = sgd(W, *gather_batches(x_tab, y_tab, Ir))
                D_act = W - W2
                V_new, C2, ring_new, Vagg_hist_new, E_new = core_main(
                    V, C0, expand_rows(D_act), ring, Vagg_hist, Vstart_new, Eres,
                    msrc, eps, mmask, cnt, c2_mask, c2_src, kidx, kmask,
                )
                if gate_eval:
                    accs = jax.lax.cond(
                        de,
                        lambda: eval_lossy(V_new, C2),
                        lambda: jnp.full((E,), jnp.nan, jnp.float32),
                    )
                else:
                    accs = eval_lossy(V_new, C2)
                carry_new = (V_new, C2, ring_new, Vagg_hist_new, Vstart_new, E_new)
                if tel:
                    # delta metrics over the TRAINED rows only — the scalar
                    # emission stacks exactly the active agents' deltas
                    return carry_new, (accs, metric_pair(D_act, V_new))
                return carry_new, accs

            def scan_window(V, C, ring, Vagg_hist, Vstart_hist, Eres, x_tab, y_tab, xs_all):
                return jax.lax.scan(
                    functools.partial(body, x_tab, y_tab),
                    (V, C, ring, Vagg_hist, Vstart_hist, Eres), xs_all,
                )

            return jax.jit(scan_window, donate_argnums=(0, 1, 2, 3, 4, 5))

        self._scan_window_j = make_scan(self._eval_cadence > 1)

    def _push_cache_event(self, deliver_ctr, send_ctr, a, k, kind, src_round, inst):
        """Schedule a cache write for the round whose drain sees the message.
        The sort key (deliver_ctr, send_ctr, serving holder id, seq)
        reproduces the scalar inbox order — messages delivered at the same
        tick sit in send order, and within one send phase the scalar engine
        loops holders in agent-id order — so when several replies race for
        one (agent, partition) cache slot the same one wins in both engines.
        (Replies from the SAME holder in the same phase carry identical
        values, so their relative order is immaterial.)"""
        holder = int(self._inst_owner[inst])
        self._cache_ring[(deliver_ctr // self._ticks) % self._qdepth].append(
            (deliver_ctr, send_ctr, holder, self._seq, a, k, kind, src_round, inst)
        )
        self._seq += 1

    def _control_round(self, r0: int, W: int, wf: _FateWindow) -> list:
        """Rounds r0 .. r0+W-1 of the host-side control plane, in order: one
        call a window, so that a scan window runs its W control rounds up
        front and stacks their dense tensors as `lax.scan` xs."""
        return [self._control_one_round(r0 + w, wf) for w in range(W)]

    def _control_one_round(self, rnd: int, wf: _FateWindow) -> dict:
        """One round of the host-side control plane: fate draws, queue-ring
        drains, fetch warm-up state machine, traffic counters. Pure
        integer/boolean numpy over the fixed-shape event space — no device
        data. Returns the per-round control tensors plus (msgs, drops,
        nbytes), which are exactly the scalar pubsub's counters for the
        round by construction."""
        from repro.fl.rounds import CH_FETCH_REPLY, CH_UPDATE_REPLY

        t = self._t
        TICKS = self._ticks
        f = self._fates
        rec = self.recorder
        A, K, K_inst = self.A, self.K, self.K_inst
        Lu, HD = self._Lu, self._HD
        owner = self._owner_col
        rho = self._rho
        act = self._act
        act_col = act[:, None]
        msgs = drops = nbytes = 0
        k_row = np.arange(K)[None, :]
        # routing: non-owner a targets replica slot (rnd + id_a) % rho_k —
        # keyed by the agent's ID (the scalar target rule), while every
        # dense index below runs over membership ROWS
        slot = (rnd + self._ids_col) % np.maximum(rho, 1)[None, :]
        tgt_inst = self._slot_inst[np.broadcast_to(k_row, (A, K)), slot]
        # target liveness per (a, k): a delivered-fate message to an offline
        # holder is an offline drop at the send tick (pubsub send semantics)
        tgt_act = np.zeros((A, K), bool)
        has_tgt = np.broadcast_to(rho[None, :] > 0, (A, K))
        tgt_act[has_tgt] = act[self._inst_owner[tgt_inst[has_tgt]]]

        def lat_rounds(d):
            return -(-d // TICKS)

        # ---- in-flight messages whose recipient went offline at the span
        # boundary: the scalar tick drops them at their delivery tick
        for msg in self._pending_drop_msgs.pop(t, []):
            drops += 1
            if rec is not None:
                rec.on_offline_drop(msg.deliver_round)

        # ---- phase 0: fetch requests for partitions never yet cached ------
        need = act_col & (~owner) & (~self._has_cache) & has_tgt
        n_need = int(need.sum())
        if n_need:
            de, dl = wf.slice("fetch", t)
            lost = need & ~de
            offl = need & de & ~tgt_act
            live = need & de & tgt_act
            msgs += n_need
            nbytes += 16 * n_need
            drops += int(lost.sum()) + int(offl.sum())
            if rec is not None:
                rec.on_channel(rnd, "fetch", n_need, 16 * n_need, int(lost.sum()))
                rec.on_offline_drops(rnd, int(offl.sum()))
                rec.on_delays(rnd, dl[live])
            lat = lat_rounds(dl)
            for a, k in np.argwhere(live):
                self._serve_ring[(t + int(lat[a, k])) % self._qdepth].append(
                    (t, int(a), int(k), int(tgt_inst[a, k]))
                )

        # ---- phase 1: holders serve the fetches that arrived --------------
        serves, self._serve_ring[t % self._qdepth] = (
            self._serve_ring[t % self._qdepth], []
        )
        sv_bytes = sv_drops = 0
        sv_delays: List[int] = []
        for send_r, a, k, inst in serves:
            de1, d1 = f.draw_one(
                CH_FETCH_REPLY, t, int(self._ids_arr[a]), k,
                int(self._inst_owner_id[inst]),
            )
            msgs += 1
            nbytes += int(self._wsizes[k])
            sv_bytes += int(self._wsizes[k])
            if de1:
                self._push_cache_event(
                    TICKS * t + 1 + d1, TICKS * t + 1, a, k, _KIND_START, t, inst
                )
                sv_delays.append(d1)
            else:
                drops += 1
                sv_drops += 1
        if rec is not None and serves:
            rec.on_channel(rnd, "fetch_reply", len(serves), sv_bytes, sv_drops)
            rec.on_delays(rnd, sv_delays)

        # ---- phase 2: UpdateModel sends -----------------------------------
        de_u, dl_u = wf.slice("update", t)
        send_u = self._upd_send_mask
        msgs += self._upd_msgs
        nbytes += self._upd_bytes
        lost_u = send_u & ~de_u
        offl_u = send_u & de_u & ~tgt_act
        drops += int(lost_u.sum()) + int(offl_u.sum())
        lat_u = lat_rounds(dl_u)
        # ring appends must mirror the scalar inbox, which fills in delivery-
        # TICK order: a message delayed d ticks lands at tick TICKS*t+2+d, so
        # same-send-round arrivals drain delay-ascending first, then publish
        # (a, k) order. np.unique gives the delays sorted ascending.
        live_u = send_u & de_u & tgt_act
        if rec is not None:
            rec.on_channel(
                rnd, "update", self._upd_msgs, self._upd_bytes, int(lost_u.sum())
            )
            rec.on_offline_drops(rnd, int(offl_u.sum()))
            rec.on_delays(rnd, dl_u[live_u])
        for d in np.unique(dl_u[live_u]):
            for a, k in np.argwhere(live_u & (dl_u == d)):
                self._arr_ring[(t + int(lat_u[a, k])) % self._qdepth].append(
                    (t, int(a), int(k), int(tgt_inst[a, k]))
                )

        # ---- arrivals => contribution masks + UpdateModel replies ---------
        arrivals, self._arr_ring[t % self._qdepth] = (
            self._arr_ring[t % self._qdepth], []
        )
        M_all = np.zeros((K_inst, (Lu + 1) * A), np.float32)
        # owner self-delta — only when the owner is ONLINE (offline holders
        # neither train nor aggregate, so their r stays 0 and eps freezes)
        M_all[np.arange(K_inst), self._inst_owner] = act[self._inst_owner].astype(
            np.float32
        )
        # per-instance contributor columns in scalar DELIVERY order: the
        # arrivals list drains the ring in append order = (send round
        # ascending, then tick-delay ascending, then (a, k) send order),
        # exactly the scalar pubsub's FIFO inbox — the order the
        # sequential-sum kernel must reduce in
        contrib_cols: List[List[int]] = [[] for _ in range(K_inst)]
        for send_r, a, k, inst in arrivals:
            M_all[inst, (t - send_r) * A + a] = 1.0
            contrib_cols[inst].append((t - send_r) * A + a)
        r_vec = M_all.sum(axis=1)
        # eps recursion in float64 on the host — bit-identical to the scalar
        # engine's python-float `eps = alpha*eps + (1-alpha)/r`; the device
        # consumes only the f32 image of the post-recursion value
        r64 = np.maximum(r_vec, 1.0).astype(np.float64)  # weak f32 promotion would downgrade the divide
        self._eps64 = np.where(
            r_vec > 0,
            self.cfg.alpha * self._eps64 + (1.0 - self.cfg.alpha) / r64,
            self._eps64,
        )
        if arrivals:
            arr = np.asarray([(a, k, i) for (_, a, k, i) in arrivals], np.int64)
            de_r, d_r = f.draw(
                CH_UPDATE_REPLY, t, self._ids_arr[arr[:, 0]], arr[:, 1],
                self._inst_owner_id[arr[:, 2]],
            )
            msgs += len(arrivals)
            nbytes += int(np.sum(self._wsizes[arr[:, 1]]))
            drops += int((~de_r).sum())
            if rec is not None:
                rec.on_channel(
                    rnd, "update_reply", len(arrivals),
                    int(np.sum(self._wsizes[arr[:, 1]])), int((~de_r).sum()),
                )
                rec.on_delays(rnd, d_r[de_r])
            for j in np.nonzero(de_r)[0]:
                self._push_cache_event(
                    TICKS * t + 3 + int(d_r[j]), TICKS * t + 3,
                    int(arr[j, 0]), int(arr[j, 1]), _KIND_AGG, t, int(arr[j, 2]),
                )

        # version bumps where anything aggregated (owner always contributes
        # under fixed membership; keep the general rule anyway)
        ver_after = self._ver + (r_vec > 0).astype(np.int64)

        # ---- replica publishes --------------------------------------------
        if len(self._rep_src):
            msgs += self._pub_msgs
            nbytes += self._pub_bytes
            de_p, dl_p = wf.slice("replica", t)
            lost_p = ~de_p
            offl_p = de_p & ~self._rep_dst_act
            live_p = de_p & self._rep_dst_act
            drops += int(lost_p.sum()) + int(offl_p.sum())
            if rec is not None:
                rec.on_channel(
                    rnd, "replica", self._pub_msgs, self._pub_bytes,
                    int(lost_p.sum()),
                )
                rec.on_offline_drops(rnd, int(offl_p.sum()))
                rec.on_delays(rnd, dl_p[live_p])
            lat_p = lat_rounds(dl_p)
            for j in np.nonzero(live_p)[0]:
                si, di = int(self._rep_src[j]), int(self._rep_dst[j])
                self._merge_ring[(t + int(lat_p[j])) % self._qdepth].append(
                    (t, si, di, int(ver_after[si]), int(dl_p[j]))
                )

        # ---- merge set: version-filtered replica values due this round ----
        # ordered columns into the flattened (HD*K_inst) value-history table,
        # sorted by landing tick (then source agent) = the scalar inbox's
        # FIFO drain order, so the device's sequential merge associates
        # exactly like the scalar oracle's np.mean over [self] + arrivals
        MW = self._mw
        msrc = np.zeros((K_inst, MW), np.int32)
        mmsk = np.zeros((K_inst, MW), np.float32)
        cnt = np.zeros(K_inst, np.float32)
        merges, self._merge_ring[t % self._qdepth] = (
            self._merge_ring[t % self._qdepth], []
        )
        # unified landing-order key over in-span and harvested (mail) merge
        # entries: (landing tick - 1, send tick, source row). In-span
        # entries publish at tick TICKS*send_r + 3 and land at +3 + dl;
        # under max_delay <= TICKS the send-tick component is a no-op (all
        # same-landing-tick entries share the send round), beyond that it
        # keeps stragglers in scalar send order.
        entries = [
            (
                e[0] * TICKS + 2 + e[4], e[0] * TICKS + 3,
                int(self._inst_owner[e[1]]), int(e[2]), int(e[3]),
                (t - e[0]) * K_inst + int(e[1]),
            )
            for e in merges
        ] + [
            (int(kt), int(st_), int(sr), int(di), int(vs), HD * K_inst + int(m))
            for kt, sr, vs, di, m, st_ in self._mail_merges.pop(t, [])
        ]
        entries.sort(key=lambda e: (e[0], e[1], e[2]))
        for _kt, _st, _sr, di, ver_sent, col_src in entries:
            if ver_sent >= ver_after[di]:
                col = int(cnt[di])
                msrc[di, col] = col_src
                mmsk[di, col] = 1.0
                cnt[di] += 1.0
        self._ver = ver_after

        # ---- cache-update batches (phase-0 / phase-2 drains) --------------
        c0_mask = np.zeros((A, K), bool)
        c0_src = np.zeros((A, K), np.int32)
        c2_mask = np.zeros((A, K), bool)
        c2_src = np.zeros((A, K), np.int32)
        cache_events, self._cache_ring[t % self._qdepth] = (
            self._cache_ring[t % self._qdepth], []
        )
        for ctr, _sc, _holder, _seq, a, k, kind, src_r, inst in sorted(cache_events):
            is_c0 = ctr % TICKS <= 1
            if kind == _KIND_MAIL:
                # harvested reply payload: `inst` is a mail-plane row; the
                # mail block sits after the value sections of each gather
                # table (T0: 2 history rings; T2: rings + this round's
                # post-agg table)
                idx = (
                    2 * HD * K_inst + inst
                    if is_c0
                    else (2 * HD + 1) * K_inst + inst
                )
            elif kind == _KIND_START:
                idx = (t - src_r) * K_inst + inst
            elif src_r < t:
                idx = HD * K_inst + (t - src_r - 1) * K_inst + inst
            else:
                idx = 2 * HD * K_inst + inst
            if is_c0:
                c0_mask[a, k] = True
                c0_src[a, k] = idx
            else:
                c2_mask[a, k] = True
                c2_src[a, k] = idx
            self._has_cache[a, k] = True  # suppresses fetches from round t+1

        # ---- contributor gathers (kernel + CPU sequential-sum paths) ------
        # slot order IS reduction order for the sequential sum, so it must
        # be the scalar pending order: own delta first (the local push
        # precedes the inbox drain), then arrivals in delivery order. The
        # quantized kernel takes the owner's raw delta through a dedicated
        # input summed first, so its table holds only the remote rows; the
        # CPU path gathers from the wire-image delta plane, where the
        # owner's raw slice is already mixed in.
        if self._use_kernel:
            width = self.R_cap
            add_owner = not self._int8
        else:
            width = self._cw
            add_owner = True
        kidx = np.zeros((K_inst, width), np.int32)
        kmask = np.zeros((K_inst, width), np.float32)
        for i in range(K_inst):
            rows = contrib_cols[i]
            # offline owners contribute nothing (their D row is zero anyway,
            # but keeping the mask exact keeps the sequential-sum shape
            # aligned with the scalar pending order)
            if add_owner and act[self._inst_owner[i]]:
                rows = [int(self._inst_owner[i])] + rows
            kidx[i, : len(rows)] = rows
            kmask[i, : len(rows)] = 1.0

        self._t = t + 1
        ctl = dict(
            rnd=rnd, c0_mask=c0_mask, c0_src=c0_src, c2_mask=c2_mask,
            c2_src=c2_src, msrc=msrc, eps=self._eps64.astype(np.float32),
            mmask=mmsk, cnt=cnt, kidx=kidx, kmask=kmask,
            msgs=msgs, drops=drops, nbytes=nbytes,
        )
        if rec is not None:
            # snapshots for the round's finish_round emission: contributor
            # counts and the post-recursion f64 eps (self._eps64 mutates
            # every round, so the window runner needs per-round copies)
            ctl["r_vec"] = r_vec.astype(np.int64)
            ctl["eps64"] = self._eps64.copy()
        return ctl

    def _run_window_lossy(self, r0: int, W: int) -> None:
        """W LOSSY rounds as one lax.scan device call: run the host control
        plane W times up front (windowed fate draws where the keys are
        fixed), stack its dense per-round tensors as scan xs, and scan the
        fused pre+SGD+core body over them with the device state in the
        carry."""
        K = self.K
        pt = self._pt
        with pt.phase("fate_draw"):
            wf = _FateWindow(
                self._fates, self._t, W, self._ids_col, np.arange(K)[None, :],
                self._rep_src_agent, self._rep_k, self._rep_dst_agent,
            )
        with pt.phase("control"):
            ctls = self._control_round(r0, W, wf)
        with pt.phase("batches"):
            idx = self._draw_batches(W)
        with pt.phase("stage"):
            Is = self._stage_batches(idx)
            stack = lambda key: self._put(np.stack([c[key] for c in ctls]))
            des = self._put(np.asarray([self._do_eval(r0 + w) for w in range(W)]))
            xs_all = (
                Is, stack("c0_mask"), stack("c0_src"), stack("msrc"),
                stack("eps"), stack("mmask"), stack("cnt"), stack("c2_mask"),
                stack("c2_src"), stack("kidx"), stack("kmask"), des,
            )
        with pt.phase("device_window"):
            carry, ys = self._scan_window_j(
                self._Vl, self._C, self._ring, self._Vagg_hist,
                self._Vstart_hist, self._E, self._x_tab, self._y_tab, xs_all,
            )
            if pt.sync:
                jax.block_until_ready(ys)
        (
            self._Vl, self._C, self._ring, self._Vagg_hist,
            self._Vstart_hist, self._E,
        ) = carry
        self.device_dispatches += 1
        with pt.phase("wait"):
            mets = None
            if self.recorder is not None:
                accs, mets = ys
                mets = np.asarray(mets, np.float32)
            else:
                accs = ys
            accs = np.asarray(accs, np.float32)
        with pt.phase("account"):
            for w in range(W):
                c = ctls[w]
                self.messages_sent += c["msgs"]
                self.messages_dropped += c["drops"]
                self._bytes_total += c["nbytes"]
                self.history.append(self._metrics_entry(r0 + w, accs[w]))
                if self.recorder is not None:
                    self._emit_row(r0 + w, c["r_vec"], c["eps64"], mets[w])

    # -- one round ----------------------------------------------------------
    def _train_table(self, trainers, act_trainers) -> np.ndarray:
        """Keep the fleet's training set on the device as one flat table
        (``_x_tab`` (n, 784), ``_y_tab`` (n,) in the labels' dtype), the
        trainers' shards concatenated, and return each active trainer's row
        offset into it. Uploaded at construction, and again only when a
        re-snapshot meets a shard the table lacks (a joiner's own data)."""
        shards = {}
        for tr in trainers:
            shards.setdefault((id(tr.x), id(tr.y)), (tr.x, tr.y))
        if not shards.keys() <= self._tab_off.keys():
            self._tab_off, n = {}, 0
            for key, (x, _) in shards.items():
                self._tab_off[key] = n
                n += len(x)
            # the shards stay referenced, so their ids stay unique
            self._tab_shards = list(shards.values())
            self._x_tab = jnp.asarray(np.concatenate([x for x, _ in self._tab_shards]))
            self._y_tab = jnp.asarray(np.concatenate([y for _, y in self._tab_shards]))
        return np.asarray(
            [self._tab_off[(id(tr.x), id(tr.y))] for tr in act_trainers], np.int64
        )

    def _put(self, a) -> jax.Array:
        """Hand one staged host array to the device, counting its bytes."""
        out = jnp.asarray(a)
        self.staged_bytes += out.nbytes
        return out

    def _stage_batches(self, idx):
        """Drawn batch row indices as device arrays, one per batch bucket:
        the scan xs' batch part (the images stay on the device)."""
        return tuple(self._put(a) for a in idx)

    def _draw_batches(self, W: int):
        """W rounds of every online agent's batch as global row indices into
        the device-resident training set: one (W, rows, bs) int32 array per
        batch bucket. Round-major, so each trainer's stream advances exactly
        as in W single rounds. Only the ONLINE agents' RNG streams advance —
        the scalar train phase skips offline agents, so their trainers must
        not draw."""
        rounds = [
            [off + tr.draw_indices() for tr, off in zip(self._act_trainers, self._act_off)]
            for _ in range(W)
        ]
        return [
            np.asarray([I[lo:hi] for I in rounds], np.int32)
            for lo, hi, _ in self._buckets
        ]

    def _scalar_round(self, rnd: int) -> dict:
        """One round on the embedded scalar oracle: membership-event rounds
        (and the rare spans the dense planes cannot host, e.g. zero active
        agents) replay there, then the next fused round re-snapshots."""
        if self._on_device:
            self._device_to_scalar(rnd)
        met = self._seed.run_round(rnd)
        # keep the mirrored counters live even if the run ends on the oracle
        ps = self.net.pubsub
        self.messages_sent = ps.messages_sent
        self.messages_dropped = ps.messages_dropped
        self._bytes_total = ps.total_bytes()
        self._n_act = met["active"]
        self.history.append(met)
        return met

    def run_round(self, rnd: int) -> dict:
        """One round: a one-round window of the path's device program
        (membership-event rounds, and rounds no fused span can host, replay
        on the scalar oracle). Returns the round's history entry."""
        if self._lossy:
            if rnd in self._replay_set:
                return self._scalar_round(rnd)
            if not self._on_device and not self._scalar_to_device(rnd):
                return self._scalar_round(rnd)
            self._run_window_lossy(rnd, 1)
        else:
            self._run_window_perfect(rnd, 1)
        return self.history[-1]

    def _perfect_traffic(self, rnd: int) -> None:
        self._bytes_total += self._round_bytes + (
            self._round0_fetch_bytes if rnd == 0 else 0
        )
        # keep the pubsub-mirroring counters live on the PERFECT path too
        # (nothing drops under PERFECT conditions)
        self.messages_sent += self._round_msgs + (
            self._round0_fetch_msgs if rnd == 0 else 0
        )

    def _emit_row(self, rnd: int, contrib, eps, met) -> None:
        """The engine's single telemetry emission site: one schema-ordered
        finish_round per round, from the device aux metrics + control-plane
        snapshots. Shapes/float paths mirror the scalar engine exactly
        (rows byte-identical but for the f32 norms, which agree within 2
        ULP; tests/test_telemetry.py)."""
        m = np.asarray(met, np.float32)
        self.recorder.finish_round(
            round=rnd,
            active=self._n_act,
            contrib=[int(x) for x in contrib],
            eps=[float(x) for x in eps],
            delta_normsq=float(m[0]),
            value_normsq=float(m[1]),
            accs=self._last_accs,
            bytes_total=self._bytes_total,
            msgs_total=self.messages_sent,
            drops_total=self.messages_dropped,
        )

    def _emit_perfect(self, rnd: int, met) -> None:
        """PERFECT-path telemetry: the closed-form traffic split by channel
        (everything delivered, delay 0; replica publishes fan out rho_k-1
        ways), plus the host-f64 eps replay of the scalar recursion."""
        rec = self.recorder
        if rnd == 0 and self._tel_r0_fetch_n:
            n = self._tel_r0_fetch_n
            rec.on_channel(rnd, "fetch", n, 16 * n, 0)
            rec.on_delivered(rnd, 0, n)
            rec.on_channel(rnd, "fetch_reply", n, self._tel_r0_fetch_rep_bytes, 0)
            rec.on_delivered(rnd, 0, n)
        rec.on_channel(rnd, "update", self._tel_upd_msgs, self._tel_upd_bytes, 0)
        rec.on_delivered(rnd, 0, self._tel_upd_msgs)
        rec.on_channel(
            rnd, "update_reply", self._tel_upd_msgs, self._tel_upd_bytes, 0
        )
        rec.on_delivered(rnd, 0, self._tel_upd_msgs)
        if self._tel_rep_msgs:
            rec.on_channel(
                rnd, "replica", self._tel_rep_msgs, self._tel_rep_bytes, 0
            )
            rec.on_delivered(rnd, 0, self._tel_rep_deliv)
        r = self._tel_r[rnd % self._period]
        self._tel_eps64 = (
            self.cfg.alpha * self._tel_eps64 + (1.0 - self.cfg.alpha) / r
        )
        self._emit_row(rnd, r, self._tel_eps64, met)

    def _do_eval(self, rnd: int) -> bool:
        """Eval gate: every `eval_cadence`-th round plus the final round of
        the run (every round when scan_rounds=0, see `_eval_cadence`)."""
        return (rnd + 1) % self._eval_cadence == 0 or rnd == self.cfg.rounds - 1

    def _metrics_entry(self, rnd: int, accs: np.ndarray) -> dict:
        """History entry for one round; rounds the scanned path skipped
        (eval_cadence > 1 => NaN accs out of the cond) reuse the last
        computed accuracies, so the history schema never changes."""
        if np.isnan(accs).all():
            accs = (
                self._last_accs
                if self._last_accs is not None
                else np.zeros_like(accs)
            )
        else:
            self._last_accs = accs
        return {
            "acc_mean": float(accs.mean()),
            "acc_std": float(accs.std()),
            "acc_max": float(accs.max()),
            "round": rnd,
            "active": self._n_act,
            "bytes_total": self._bytes_total,
        }

    def _run_window_perfect(self, r0: int, W: int) -> None:
        """W PERFECT rounds as one lax.scan device call: batch row indices
        and the phase-cycled routing tables are stacked as (W, ...) scan xs,
        the value tables (V_pre, V_merged, eps) ride the carry."""
        pt = self._pt
        with pt.phase("batches"):
            idx = self._draw_batches(W)
        with pt.phase("stage"):
            Is = self._stage_batches(idx)
            prev = self._last_phase
            t_prev_l, idx_l, mask_l, t_eval_l, de_l = [], [], [], [], []
            for w in range(W):
                rnd = r0 + w
                p = rnd % self._period
                t_prev_l.append(self._t_inst[prev])
                idx_l.append(self._contrib_idx[p])
                mask_l.append(self._contrib_mask[p])
                t_eval_l.append(self._t_inst[p][self._eval_idx])
                de_l.append(self._do_eval(rnd))
                prev = p
            put = self._put
            xs_all = (
                Is,
                put(np.stack(t_prev_l)), put(np.stack(idx_l)),
                put(np.stack(mask_l)), put(np.stack(t_eval_l)),
                put(np.asarray(de_l, bool)),
            )
        with pt.phase("device_window"):
            out = self._scan_window_j(
                self._V_pre, self._V_merged, self._eps, self._x_tab, self._y_tab,
                xs_all,
            )
            if pt.sync:
                jax.block_until_ready(out)
        mets = None
        if self.recorder is not None:
            self._V_pre, self._V_merged, self._eps, accs, mets = out
        else:
            self._V_pre, self._V_merged, self._eps, accs = out
        self.device_dispatches += 1
        self._last_phase = prev
        with pt.phase("wait"):
            if mets is not None:
                mets = np.asarray(mets, np.float32)
            accs = np.asarray(accs, np.float32)
        with pt.phase("account"):
            for w in range(W):
                self._perfect_traffic(r0 + w)
                self.history.append(self._metrics_entry(r0 + w, accs[w]))
                if self.recorder is not None:
                    self._emit_perfect(r0 + w, mets[w])

    def run_window(self, start_rnd: int, window: int) -> List[dict]:
        """Run `window` consecutive rounds as ONE lax.scan-driven device
        call (the multi-round fused path; see docs/ENGINE.md). Returns the
        new history entries — one per round, bytes/messages/drops accounted
        per round exactly as the scalar pubsub would."""
        if window < 1:
            raise ValueError("window must be >= 1")
        n0 = len(self.history)
        with self._pt.phase("run_window"):
            if not self._lossy:
                self._run_window_perfect(start_rnd, window)
                return self.history[n0:]
            # a window may not span a membership event: fall back to
            # round-at-a-time (which replays event rounds on the oracle)
            ok = not any(
                (start_rnd + w) in self._replay_set for w in range(window)
            )
            if ok and not self._on_device:
                ok = self._scalar_to_device(start_rnd)
            if ok:
                self._run_window_lossy(start_rnd, window)
            else:
                for w in range(window):
                    self.run_round(start_rnd + w)
        return self.history[n0:]

    def run(self) -> List[dict]:
        W = self.scan_rounds or 1
        R = self.cfg.rounds
        rnd = 0
        while rnd < R:
            if rnd in self._replay_set:
                # membership event: replay this round on the oracle, then
                # resume fused windows after the re-snapshot
                self.run_round(rnd)
                rnd += 1
                continue
            nxt = next((r for r in self._replay if r > rnd), R)
            step = min(W, nxt - rnd)
            self.run_window(rnd, step)
            rnd += step
        return self.history

    # -- introspection (tests / benchmarks) ---------------------------------
    def agent_weights(self) -> np.ndarray:
        """The (A, N) matrix of per-agent assembled models, equal to what
        each scalar agent's `load_model()` would return (reconstructed from
        the value tables and the last round's routing)."""
        if self._lossy:
            if not self._on_device:
                # state currently lives on the scalar oracle (mid-churn)
                ids = self._live_ids()
                W = np.zeros((len(ids), self.N), np.float32)
                for r, a in enumerate(ids):
                    W[r] = self._seed.agents[a].load_model()
                return W
            tbl = np.concatenate(
                [
                    np.asarray(self._Vl),
                    np.asarray(self._C).reshape(self.A * self.K, self.S),
                ],
                axis=0,
            )
            W = np.zeros((self.A, self.N), np.float32)
            for k in range(self.K):
                off, s = self._offsets[k], self._sizes[k]
                W[:, off : off + s] = tbl[self._widx[:, k], :s]
            return W
        V_all = np.concatenate(
            [np.asarray(self._V_pre), np.asarray(self._V_merged)], axis=0
        )
        t_inst = self._t_inst[self._last_phase]
        W = np.zeros((self.A, self.N), np.float32)
        for k in range(self.K):
            off, s = self._offsets[k], self._sizes[k]
            W[:, off : off + s] = V_all[t_inst[:, k], :s]
        return W
