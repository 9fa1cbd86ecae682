"""Fused IPLS partition aggregation — Pallas TPU kernel.

One pass over HBM: reads the R replica/contributor deltas tile-by-tile into
VMEM, reduces them with the participation mask, and applies the eps-weighted
update to the partition value. Replaces (R reads + 1 reduce + 1 axpy) XLA
ops with a single fused kernel; on TPU this is HBM-bandwidth-bound, so the
fusion removes R+1 extra round-trips of the partition through HBM.

Semantics are the scalar engine's: ``w - eps * masked_SUM(deltas)``. The
1/r normalization lives entirely in the eps recursion
(``eps <- alpha*eps + (1-alpha)/r``), so the kernel never divides by the
contributor count — that division (a mean inside, undone by ``eps*r`` at the
call site) is not bitwise invertible in f32 and broke engine equivalence at
r=3. Summation is strictly sequential in slot order, within a chunk and
across R_TILE chunks, so the reduction associates exactly like the scalar
oracle's ``np.sum(axis=0)`` over deltas in delivery order. An all-zero mask
row (zero-contributor round) naturally passes w through unchanged.

Three variants:

  * ``ipls_aggregate``       — one partition:  w (N,), deltas (R, N);
  * ``ipls_aggregate_batched`` — all K partitions a holder owns in ONE
    launch: w (K, N), deltas (K, R, N), with a per-partition
    ``[mask(R), eps]`` table, grid spanning (K, row-tiles, R-tiles).
    The vectorized round engine flattens every (partition, replica-slot)
    instance of a training round into this layout, so a whole round's
    aggregation is a single kernel call instead of K numpy reductions.
  * ``ipls_aggregate_batched_q`` — int8-wire variant: remote deltas arrive
    as int8 codes + per-block scales and dequantize INSIDE the reduction;
    the holder's own delta (never on the wire) joins raw, first — matching
    the scalar pending order (local push before inbox drain).

Tiling: the flat partition is viewed as (rows, 128) lanes; each grid step
owns a (BR, 128) tile (BR=256 rows => 128 KiB f32 per delta in VMEM; with
R<=16 contributors the working set stays ~2 MiB << 16 MiB VMEM). The batched
variants use BR=128 to cut per-partition padding waste, and tile the
contributor axis in chunks of R_TILE so variable-r instance tables (lossy
rounds can carry 1 + (A-1) * (1 + max_delay) contributor slots) neither
unroll into huge kernel bodies nor blow the VMEM budget: the grid's last
axis walks R-chunks sequentially, carrying the running sum through the
revisited output block, and applies ``w - eps * acc`` on the final chunk.

``interpret`` defaults to auto-detection: interpret-mode (CPU emulation of
the kernel body) everywhere except on a real TPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BR = 256  # tile rows; lanes fixed at 128
BR_BATCHED = 128  # smaller tile for the partition-batched grid (less padding)
LANES = 128
R_TILE = 8  # contributor-slot chunk per grid step of the batched variant
# quantization block of the int8 wire format (must equal kernels/quantize
# BLOCK; asserted in tests — quantize imports default_interpret from here,
# so importing back would be circular). One BR_BATCHED row-tile spans
# exactly BR_BATCHED*LANES/QBLOCK = 16 scale blocks, each 8 row-groups.
QBLOCK = 1024


def default_interpret() -> bool:
    """Run the kernel body via the Pallas interpreter except on real TPUs."""
    return jax.default_backend() != "tpu"


def _kernel(mask_eps_ref, w_ref, deltas_ref, out_ref):
    # mask_eps_ref: (R+1,) SMEM-ish small vector: [mask(R), eps]
    # w_ref: (BR, 128); deltas_ref: (R, BR, 128)
    me = mask_eps_ref[...]
    R = deltas_ref.shape[0]
    mask = me[:R]
    eps = me[R]
    acc = jnp.zeros(w_ref.shape, jnp.float32)
    for r in range(R):  # static unroll: R is a compile-time constant
        acc = acc + mask[r] * deltas_ref[r].astype(jnp.float32)
    out_ref[...] = (w_ref[...].astype(jnp.float32) - eps * acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ipls_aggregate(w, deltas, mask, eps, interpret: bool | None = None):
    """w: (N,), deltas: (R,N), mask: (R,), eps: (). N padded to BR*128."""
    if interpret is None:
        interpret = default_interpret()
    N = w.shape[0]
    R = deltas.shape[0]
    tile = BR * LANES
    pad = (-N) % tile
    wp = jnp.pad(w, (0, pad))
    dp = jnp.pad(deltas, ((0, 0), (0, pad)))
    rows = (N + pad) // LANES
    w2 = wp.reshape(rows, LANES)
    d2 = dp.reshape(R, rows, LANES)
    grid = (rows // BR,)
    mask_f = mask.astype(jnp.float32)
    me = jnp.concatenate([mask_f, eps.astype(jnp.float32)[None]])

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R + 1,), lambda i: (0,)),
            pl.BlockSpec((BR, LANES), lambda i: (i, 0)),
            pl.BlockSpec((R, BR, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((BR, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), w.dtype),
        interpret=interpret,
    )(me, w2, d2)
    return out.reshape(-1)[:N]


def _kernel_batched(table_ref, w_ref, deltas_ref, out_ref, *, rp):
    # table_ref: SMEM (K*(rp+1),) scalar-prefetched flat table, partition
    # k's row [mask(rp), eps] at offset k*(rp+1); rp is the R_TILE-padded
    # contributor count. w_ref: (1, BR_BATCHED, 128); deltas_ref:
    # (1, R_TILE, BR_BATCHED, 128) — one R-chunk per grid step.
    # The grid's last axis walks the R-chunks sequentially; the running sum
    # is carried through the revisited output block so the reduction order
    # is strictly slot 0,1,2,... — bit-identical to the scalar oracle's
    # sequential np.sum (masked-out slots add an exact +0.0).
    k = pl.program_id(0)
    rt = pl.program_id(2)
    n_rt = pl.num_programs(2)
    RT = deltas_ref.shape[1]
    row = k * (rp + 1)
    eps = table_ref[row + rp]

    @pl.when(rt == 0)
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    acc = out_ref[0].astype(jnp.float32)
    for r in range(RT):  # static unroll of one chunk
        acc = acc + table_ref[row + rt * RT + r] * deltas_ref[0, r].astype(jnp.float32)

    @pl.when(rt < n_rt - 1)
    def _():
        out_ref[0] = acc.astype(out_ref.dtype)

    @pl.when(rt == n_rt - 1)
    def _():
        out_ref[0] = (w_ref[0].astype(jnp.float32) - eps * acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ipls_aggregate_batched(w, deltas, mask, eps, interpret: bool | None = None):
    """Per-partition masked-sum update for K partitions in one launch.

    w: (K, N), deltas: (K, R, N), mask: (K, R), eps: (K,). Each partition k
    gets ``w[k] - eps[k] * sum_r mask[k,r] * deltas[k,r]``; partitions with
    an all-zero mask row (zero-contributor rounds — possible under lossy
    networks) pass through unchanged. R is variable at the call site (lossy
    rounds shrink/grow the contributor table per round) and is padded to a
    multiple of R_TILE with zero mask rows; the grid walks R-chunks so large
    contributor tables neither unroll into huge kernel bodies nor exceed
    VMEM. Partitions of unequal true size share the padded N; callers
    zero-pad tails (the padded lanes compute garbage-free zeros since
    pad(w)=pad(deltas)=0). The ``[mask, eps]`` table is scalar-prefetched
    into SMEM (K*(Rp+1) f32 words; SMEM holds 1 MiB on v5e) and read as
    scalars, so no table block has to meet the (8, 128) tiling rule.
    """
    if interpret is None:
        interpret = default_interpret()
    K, N = w.shape
    R = deltas.shape[1]
    rpad = (-R) % R_TILE
    tile = BR_BATCHED * LANES
    pad = (-N) % tile
    wp = jnp.pad(w, ((0, 0), (0, pad)))
    dp = jnp.pad(deltas, ((0, 0), (0, rpad), (0, pad)))
    rows = (N + pad) // LANES
    Rp = R + rpad
    w3 = wp.reshape(K, rows, LANES)
    d4 = dp.reshape(K, Rp, rows, LANES)
    mask_f = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, rpad)))
    table = jnp.concatenate([mask_f, eps.astype(jnp.float32)[:, None]], axis=1)

    out = pl.pallas_call(
        functools.partial(_kernel_batched, rp=Rp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K, rows // BR_BATCHED, Rp // R_TILE),
            in_specs=[
                pl.BlockSpec((1, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, i, 0)),
                pl.BlockSpec(
                    (1, R_TILE, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, rt, i, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((K, rows, LANES), w.dtype),
        interpret=interpret,
    )(table.reshape(-1), w3, d4)
    return out.reshape(K, -1)[:, :N]


# Scale blocks spanned by one (BR_BATCHED, LANES) row-tile of the quantized
# variant: 128*128/1024 = 16 per-block scales, each covering 8 row-groups.
SB_TILE = BR_BATCHED * LANES // QBLOCK


def _kernel_batched_q(table_ref, w_ref, own_ref, q_ref, s_ref, out_ref, *, rp):
    # Quantized contributor rows: deltas arrive as int8 codes q plus per-
    # QBLOCK f32 scales; dequantize (q * scale — exact, scales are powers of
    # two or 0) fuses into the masked-sum accumulation, so the f32 deltas
    # never materialize in HBM. The owner's own delta never crossed the wire
    # and stays raw f32 (own_ref), gated by the own_mask table slot and
    # summed FIRST — the scalar oracle pushes the local delta into pending
    # before draining the inbox, and sum order must match bit for bit.
    # table_ref: SMEM (K*(rp+2),), row k = [mask(rp), own_mask, eps];
    # q_ref: (1, R_TILE, BR_BATCHED, 128) int8;
    # s_ref: (1, 1, SB_TILE, R_TILE) f32 — the row-tile's SB_TILE scale
    # blocks (sublanes) for the chunk's R_TILE contributors (lanes).
    k = pl.program_id(0)
    rt = pl.program_id(2)
    n_rt = pl.num_programs(2)
    RT = q_ref.shape[1]
    row = k * (rp + 2)
    own_mask = table_ref[row + rp]
    eps = table_ref[row + rp + 1]
    rows = w_ref.shape[1]
    rows_per_block = QBLOCK // LANES  # 8 contiguous lane-rows share a scale
    s_blk = s_ref[0, 0]  # (SB_TILE, R_TILE)

    @pl.when(rt == 0)
    def _():
        out_ref[0] = (own_mask * own_ref[0].astype(jnp.float32)).astype(out_ref.dtype)

    acc = out_ref[0].astype(jnp.float32)
    for r in range(RT):  # static unroll of one chunk
        s_col = s_blk[:, r : r + 1]  # (SB_TILE, 1)
        s_full = jnp.broadcast_to(
            s_col[:, None, :], (SB_TILE, rows_per_block, 1)
        ).reshape(rows, 1)
        m = table_ref[row + rt * RT + r]
        acc = acc + m * (q_ref[0, r].astype(jnp.float32) * s_full)

    @pl.when(rt < n_rt - 1)
    def _():
        out_ref[0] = acc.astype(out_ref.dtype)

    @pl.when(rt == n_rt - 1)
    def _():
        out_ref[0] = (w_ref[0].astype(jnp.float32) - eps * acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ipls_aggregate_batched_q(
    w, own, q, scales, mask, own_mask, eps, interpret: bool | None = None
):
    """Quantized-input variant of ``ipls_aggregate_batched``.

    w: (K, N) f32; own: (K, N) f32 — the holder's OWN delta (never quantized:
    it doesn't cross the wire); q: (K, R, N) int8 wire codes of the remote
    contributor deltas; scales: (K, R, ceil(N/QBLOCK)) f32 per-block
    power-of-two scales; mask: (K, R) remote-contributor mask; own_mask:
    (K,) 1.0 where the holder's own delta participates; eps: (K,). Computes
    ``w - eps * (own_mask*own + sum_r mask[r]*deq(q[r]))`` with
    deq(q) = q * scale fused into the R_TILE accumulation, own summed first.
    Zero-contributor rows (own_mask and mask all zero) pass through
    unchanged. The ``[mask, own_mask, eps]`` table is scalar-prefetched into
    SMEM; the scales are laid out (K, Rp/R_TILE, blocks, R_TILE) so each
    grid step reads one (SB_TILE, R_TILE) block whose last dim spans the
    array's.
    """
    if interpret is None:
        interpret = default_interpret()
    K, N = w.shape
    R = q.shape[1]
    rpad = (-R) % R_TILE
    tile = BR_BATCHED * LANES
    pad = (-N) % tile
    wp = jnp.pad(w, ((0, 0), (0, pad)))
    op = jnp.pad(own, ((0, 0), (0, pad)))
    qp = jnp.pad(q, ((0, 0), (0, rpad), (0, pad)))
    rows = (N + pad) // LANES
    nbp = (N + pad) // QBLOCK  # padded scale-block count (multiple of SB_TILE)
    sp = jnp.pad(
        scales, ((0, 0), (0, rpad), (0, nbp - scales.shape[2]))
    )  # pad blocks carry scale 0 -> dequantize to exact zeros
    Rp = R + rpad
    w3 = wp.reshape(K, rows, LANES)
    o3 = op.reshape(K, rows, LANES)
    q4 = qp.reshape(K, Rp, rows, LANES)
    s4 = sp.reshape(K, Rp // R_TILE, R_TILE, nbp).transpose(0, 1, 3, 2)
    mask_f = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, rpad)))
    own_f = own_mask.astype(jnp.float32)[:, None]
    table = jnp.concatenate([mask_f, own_f, eps.astype(jnp.float32)[:, None]], axis=1)

    out = pl.pallas_call(
        functools.partial(_kernel_batched_q, rp=Rp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K, rows // BR_BATCHED, Rp // R_TILE),
            in_specs=[
                pl.BlockSpec((1, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, i, 0)),
                pl.BlockSpec((1, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, i, 0)),
                pl.BlockSpec(
                    (1, R_TILE, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, rt, i, 0)
                ),
                # lanes: all R_TILE contributors of the chunk, the array's
                # whole last dim
                pl.BlockSpec(
                    (1, 1, SB_TILE, s4.shape[3]), lambda k, i, rt, tbl: (k, rt, i, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, BR_BATCHED, LANES), lambda k, i, rt, tbl: (k, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((K, rows, LANES), w.dtype),
        interpret=interpret,
    )(table.reshape(-1), w3, o3, q4, s4)
    return out.reshape(K, -1)[:, :N]
