"""Pallas TPU kernel: MXU-formulated TM clause evaluation.

Tiled integer matmul ``violations = A @ (1 - L)`` with the K (literal)
dimension streamed through VMEM (the classic K-loop: grid =
(clause tiles, batch tiles, literal tiles), accumulator scratch persists
across the K tiles), followed by the ==0 test in the epilogue.

MXU alignment: tiles are multiples of (128, 128); inputs are cast to the
matmul dtype (bf16 is exact here — violation counts are < 2^8 per tile and
accumulation happens in fp32 on the MXU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _clause_matmul_kernel(a_ref, nl_ref, nonempty_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    acc_ref[...] += jax.lax.dot(
        a_ref[...].astype(jnp.bfloat16),
        nl_ref[...].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        viol = acc_ref[...]
        fired = (viol < 0.5) & (nonempty_ref[...] > 0)
        out_ref[...] = fired.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_b", "block_k", "interpret")
)
def clause_matmul(
    actions: jax.Array,  # {0,1}[NC, L2]
    lits: jax.Array,  # {0,1}[L2, B]
    *,
    block_c: int = 128,
    block_b: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """-> int32[NC, B] clause outputs via MXU matmul."""
    nc, l2 = actions.shape
    _, b = lits.shape
    bc, bb, bk = (min(block_c, nc), min(block_b, b), min(block_k, l2))
    ncp, bp, l2p = (-(-nc // bc) * bc, -(-b // bb) * bb, -(-l2 // bk) * bk)
    a = jnp.pad(actions.astype(jnp.int32), ((0, ncp - nc), (0, l2p - l2)))
    nl = jnp.pad(
        1 - lits.astype(jnp.int32), ((0, l2p - l2), (0, bp - b))
    )  # pad rows are 0 = no violation contribution
    nonempty = jnp.sum(a, axis=1, keepdims=True)  # [NCp, 1]
    nonempty = jnp.broadcast_to(nonempty, (ncp, bp))

    out = pl.pallas_call(
        _clause_matmul_kernel,
        grid=(ncp // bc, bp // bb, l2p // bk),
        in_specs=[
            pl.BlockSpec((bc, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bb), lambda i, j, k: (k, j)),
            pl.BlockSpec((bc, bb), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bc, bb), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ncp, bp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bc, bb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, nl, nonempty)
    return out[:nc, :b]
