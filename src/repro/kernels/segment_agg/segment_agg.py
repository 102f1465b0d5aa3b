"""Pallas TPU kernel: sorted-segment sum — the GNN message-passing scatter.

Message passing  out[dst] += msg[e]  is a scatter-add; TPUs have no scatter
unit, but they have an MXU.  With edges sorted by destination, each node tile
[t*TN, (t+1)*TN) owns a contiguous edge range, and the scatter becomes a
*one-hot matmul*:

    onehot[n, e] = (seg[e] - t*TN == n)          (TN x KB, built with iota)
    acc         += onehot @ msg_block            (MXU, TN x KB x D MACs)

Grid is over node tiles; per-tile edge ranges arrive via scalar prefetch
(host-side searchsorted).  Edge blocks are staged HBM->VMEM with explicit
async copies (double-buffer depth 2), so DMA of block k+1 overlaps the MXU
work of block k.  This is the TPU re-derivation of GE-SpMM-style row-parallel
SpMM, and also the spill path of the hierarchical accumulator when values are
feature vectors rather than scalars.

TN and KB default to 128 to align the one-hot matmul with the 128x128 MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The double-buffered HBM->VMEM fetch addresses seg/msg with pl.ds over
# traced offsets; ops.segment_sum pads both to E_pad = ceil(E/KB)*KB + KB,
# one full spare block past the last tile_starts entry, so every KB-wide
# window a grid step can request stays in bounds on both backends.
# palkit: allow(K005) kernel=segment_agg.* ops pads E to ceil(E/KB)*KB+KB so every ds window is in bounds


def _segment_kernel(starts_ref,            # scalar prefetch [num_tiles+1]
                    seg_ref, msg_ref,      # ANY (HBM): [E_pad], [E_pad, D]
                    out_ref,               # VMEM block (TN, D)
                    seg_buf, msg_buf, sems,  # scratch: VMEM + DMA semaphores
                    *, tn: int, kb: int, d: int):
    t = pl.program_id(0)
    start = starts_ref[t]
    end = starts_ref[t + 1]
    nb = (end - start + kb - 1) // kb

    def fetch(slot, block_ix):
        off = start + block_ix * kb
        seg_cp = pltpu.make_async_copy(
            seg_ref.at[pl.ds(off, kb)], seg_buf.at[slot], sems.at[slot, 0])
        msg_cp = pltpu.make_async_copy(
            msg_ref.at[pl.ds(off, kb)], msg_buf.at[slot], sems.at[slot, 1])
        seg_cp.start()
        msg_cp.start()
        return seg_cp, msg_cp

    @pl.when(nb > 0)
    def _prologue():
        fetch(0, 0)

    def body(k, acc):
        slot = jax.lax.rem(k, 2)
        off = start + k * kb
        # wait for this block
        pltpu.make_async_copy(seg_ref.at[pl.ds(off, kb)], seg_buf.at[slot],
                              sems.at[slot, 0]).wait()
        pltpu.make_async_copy(msg_ref.at[pl.ds(off, kb)], msg_buf.at[slot],
                              sems.at[slot, 1]).wait()

        # prefetch next block into the other slot
        @pl.when(k + 1 < nb)
        def _():
            fetch(1 - slot, k + 1)

        seg_local = seg_buf[slot] - t * tn                     # [KB]
        in_range = (jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
                    + off) < end
        node_ids = jax.lax.broadcasted_iota(jnp.int32, (tn, kb), 0)
        onehot = ((node_ids == seg_local[None, :]) & in_range
                  ).astype(jnp.float32)                        # [TN, KB]
        return acc + jax.lax.dot(
            onehot, msg_buf[slot].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)

    acc = jnp.zeros((tn, d), jnp.float32)
    acc = jax.lax.fori_loop(0, nb, body, acc)
    out_ref[...] = acc


def segment_sum_pallas(messages, seg_ids, tile_starts, num_tiles: int, *,
                       tn: int = 128, kb: int = 128, interpret: bool = True):
    """messages [E_pad, D] sorted by seg id; seg_ids [E_pad] int32 ascending
    (padding rows carry seg id >= num_tiles*tn); tile_starts [num_tiles+1]
    edge offsets per node tile.  Returns [num_tiles*tn, D] float32."""
    e_pad, d = messages.shape
    kernel = functools.partial(_segment_kernel, tn=tn, kb=kb, d=d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # seg ids stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),     # messages stay in HBM
        ],
        out_specs=pl.BlockSpec((tn, d), lambda t, starts: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kb), jnp.int32),
            pltpu.VMEM((2, kb, d), messages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles * tn, d), jnp.float32),
        interpret=interpret,
    )(tile_starts, seg_ids, messages)
