"""Pallas TPU kernel: popcount bitplane inference over the decoded plan.

The paper's pitch is that compressed-TM inference is nothing but bitwise
AND/NOT plus popcount-style summation — yet the interpreter kernel
(``tm_interp``) expands the packed clause accumulator into ``int32[1, B]``
bit vectors on EVERY instruction and read-modify-writes the class-sum bank
with a ``dynamic_slice``/``dynamic_update_slice`` pair per step.  This
kernel keeps everything packed until one popcount reduction per
instruction block:

  1. The sequential sweep only ANDs packed ``uint32`` words: per
     instruction, ``acc &= lits[lit_idx[t]]`` (32 datapoints/lane) and, on
     a clause boundary, the emitted clause word is stored into a
     block-local emit buffer (zero when the instruction does not emit).
     No bit expansion, no sum-bank scatter inside the loop.
  2. Once per instruction block, the ``[bi, BW]`` emit buffer is
     bit-transposed in 32x32 tiles (5 masked shift/XOR rounds — the
     classic bitplane transpose), yielding per-datapoint words whose bit j
     is clause-output bit of instruction ``32c+j``.
  3. Class routing is scatter-free: the program is compiled (host-side,
     at program time) into per-class *polarity-bank* selection bitplanes
     ``mask_pos/mask_neg[m_cap, I/32]`` — bit j of chunk c selects
     instruction ``32c+j`` iff it emits a +/- clause of that class.  Class
     sums are then
         sums[m, b] += popcount(T[c, b] & mask_pos[m, c])
                     - popcount(T[c, b] & mask_neg[m, c])
     via ``jax.lax.population_count`` — the Fig 4.6 accumulate stage as
     32-way popcounts instead of 32 scalar adds.

Layout: grid = (batch-word blocks [parallel], instruction blocks
[arbitrary]).  The operand vectors ``lit_idx``/``last`` sit in SMEM
(scalar prefetch); the packed clause accumulator is VMEM scratch and the
class-sum bank is the output block, both persisting across instruction
blocks; the packed-literal panel (Feature Memory, Fig 4.5) stays
VMEM-resident per batch block.  Every block is a whole array dim or whole
8x128 tiles, as the TPU compiler requires (``tests/test_tpu_compile.py``).
Block shapes default to ``kernels.tuning.choose_blocks`` (a per-capacity
synthesis-time choice, never a runtime recompile).

Positions (the Convolutional TM, ``core.tm.PatchGeometry``): packed
literals ``uint32[L2, P, W]`` carry a position axis.  Each literal of the
panel then holds the P x bw words of a word block position-major (word
``p * bw + w``) in dense (8, 128) tiles, ``[S, 128]`` with S =
ceil(P * bw / 128) rounded up to a multiple of 8: row r, lane l holds
word ``128 r + l``, which is word ``l % bw`` of its position.  Padded
words are zero.  The sweep ANDs the literal's ``[S, 128]`` tile per
include and ORs the clause word over its S rows (whole-vreg ORs, then
sublane rolls) into the one ``[1, 128]`` row it stores in the emit
buffer, so the buffer is ``[bi, 128]``; before step 2 the lanes a
multiple of bw apart are ORed together (lane rolls), leaving in lane
``w < bw`` the OR over positions of word w (a clause fires on a
datapoint iff it fires at some position).  Padded positions hold zero in
both polarities, so they never fire.  With 2-D literals (one position)
the kernel is the plain one, shape for shape.

``tm_popcount_xla`` is the same algorithm phrased as pure XLA ops (gather +
segmented AND scan + bit transpose + popcount): the portable fast path the
serving executors use on CPU/GPU, bit-exact with the Pallas kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tuning import choose_blocks

ONES = 0xFFFFFFFF  # python int: safe to close over in kernels

# (shift, mask) rounds of the 32x32 bitplane transpose (Hacker's Delight
# 7-3, little-endian: the rounds swap the off-diagonal s x s sub-blocks of
# every 2s x 2s block, so no bit or word reversal is needed)
_TRANSPOSE_ROUNDS = (
    (16, 0x0000FFFF),
    (8, 0x00FF00FF),
    (4, 0x0F0F0F0F),
    (2, 0x33333333),
    (1, 0x55555555),
)


def bit_transpose32_rows(x: jax.Array, roll=jnp.roll) -> jax.Array:
    """Transpose the 32x32 bit tile of every 32-row group of
    ``uint32[R, W]`` (R % 32 == 0): out row ``32g + b`` holds, at bit j,
    bit b of in row ``32g + j``.

    Each round pairs row ``r`` with row ``r ^ s`` through two sublane
    rolls and a row-parity select — whole-array ops only, no reshape,
    stack or reversal, so the same body lowers inside a Mosaic kernel
    (``roll=pltpu.roll``) and in XLA (``jnp.roll``).
    """
    n = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    for s, m in _TRANSPOSE_ROUNDS:
        m = jnp.uint32(m)
        up = roll(x, n - s, 0)  # up[r] = x[r + s]
        down = roll(x, s, 0)  # down[r] = x[r - s]
        lo = x ^ ((((x >> s) ^ up) & m) << s)  # rows with bit s clear
        hi = x ^ (((down >> s) ^ x) & m)  # their partners
        x = jnp.where((rows & s) == 0, lo, hi)
    return x


def bit_transpose32(x: jax.Array, axis: int) -> jax.Array:
    """Transpose 32x32 bit tiles held along ``axis`` (size 32) of uint32.

    ``out[..., b, ...]`` has bit j equal to bit b of ``x[..., j, ...]``,
    vectorized over all other axes (``bit_transpose32_rows`` on the
    flattened tile columns).
    """
    y = jnp.moveaxis(x, axis, 0)
    out = bit_transpose32_rows(y.reshape(32, -1)).reshape(y.shape)
    return jnp.moveaxis(out, 0, axis)


def popcount_reduce(
    emit_words: jax.Array,  # uint32[I, W], I % 32 == 0; 0 unless emitting
    mask_pos: jax.Array,  # uint32[m_cap, I//32] or uint32[P, m_cap, I//32]
    mask_neg: jax.Array,  # same shape as mask_pos
) -> jax.Array:
    """Emit buffer + polarity-bank bitplanes -> int32[m_cap, W*32] sums.

    2-D masks are the classic unit-weight banks.  3-D masks are the
    repro.prune weighted form: plane ``b`` selects emitting instructions
    whose clause weight has bit ``b`` set, and the reduction becomes

        sums = sum_b ((pop(T & pos[b]) - pop(T & neg[b])) << b)

    — shifted popcounts, NO multiplies, so the weighted engine keeps the
    paper's bitwise-only execution contract.  Plane 0 of an all-ones
    weight vector reproduces the unit-weight banks bit-exactly."""
    i, w = emit_words.shape
    planes = bit_transpose32_rows(emit_words).reshape(i // 32, 32, w)
    # planes[c, b, w] bit j = clause-output bit b (datapoint 32w+b) of
    # instruction 32c+j; select per class with one AND, count with popcount
    if mask_pos.ndim == 2:
        pos = jax.lax.population_count(
            planes[None] & mask_pos[:, :, None, None]
        )
        neg = jax.lax.population_count(
            planes[None] & mask_neg[:, :, None, None]
        )
        sums = (pos.astype(jnp.int32) - neg.astype(jnp.int32)).sum(axis=1)
        return sums.transpose(0, 2, 1).reshape(mask_pos.shape[0], w * 32)
    p, m_cap, _ = mask_pos.shape
    pos = jax.lax.population_count(
        planes[None, None] & mask_pos[:, :, :, None, None]
    )  # [P, m, chunks, 32, W]
    neg = jax.lax.population_count(
        planes[None, None] & mask_neg[:, :, :, None, None]
    )
    per_plane = (pos.astype(jnp.int32) - neg.astype(jnp.int32)).sum(axis=2)
    shifts = jnp.arange(p, dtype=jnp.int32)[:, None, None, None]
    sums = jnp.left_shift(per_plane, shifts).sum(axis=0)  # [m, 32, W]
    return sums.transpose(0, 2, 1).reshape(m_cap, w * 32)


def _or_rows(words: jax.Array) -> jax.Array:
    """``[S, 128]`` (S a multiple of 8) -> ``[1, 128]``, the OR of its
    rows: the (8, 128) tiles are ORed together, then each sublane with
    the others (sublane rolls)."""
    fold = words[0:8]
    for r in range(8, words.shape[0], 8):
        fold = fold | words[r:r + 8]
    for shift in (4, 2, 1):
        fold = fold | pltpu.roll(fold, shift, 0)
    return fold[0:1]


def _or_lanes(emitted: jax.Array, bw: int) -> jax.Array:
    """``[bi, 128]`` clause words ORed over rows (lane l holds word
    ``l % bw`` of some positions; ``bw`` a power of two up to 128) -> lane
    ``w < bw`` holding the OR over positions of word ``w``: each lane is
    ORed with the lanes a multiple of ``bw`` away (lane rolls)."""
    fold = emitted
    shift = 64
    while shift >= bw:
        fold = fold | pltpu.roll(fold, shift, 1)
        shift //= 2
    return fold


def _tm_popcount_kernel(
    lit_idx_ref, last_ref,  # SMEM (scalar prefetch): int32[I_pad]
    # (with stream_blocks: one int32[1, 1, bi] block per instruction block)
    mask_pos_ref, mask_neg_ref,  # VMEM uint32[bi, banks], one row per inst
    lits_ref,  # VMEM uint32[L2, bw] — Feature Memory panel ([L2, S, 128]
    # with positions: P x bw words, position-major, in (8, 128) tiles)
    out_ref,  # VMEM int32[banks, 32, bw] — the class-sum bank ([.., 128])
    acc_ref, emit_ref,  # VMEM scratch uint32[1, bw], uint32[bi, bw]
    # (uint32[S, 128], uint32[bi, 128] with positions)
    *,
    positions_bw: int | None = None,  # bw when the words carry positions
    stream_blocks: bool = False,  # the operand vectors come per block
):
    bi = emit_ref.shape[0]
    if stream_blocks:  # one SMEM block of the stream per instruction block

        def operand(ref, t):
            return ref[0, 0, t]
    else:  # the whole stream is prefetched
        base = pl.program_id(1) * bi

        def operand(ref, t):
            return ref[base + t]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, ONES, jnp.uint32)
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    def body(t, acc):
        # Literal Select + Clause Compute: one packed AND per include
        acc = acc & lits_ref[pl.ds(operand(lit_idx_ref, t), 1), :]
        emit = operand(last_ref, t) == 1
        emit_ref[pl.ds(t, 1), :] = jnp.where(emit, acc, jnp.uint32(0))
        return jnp.where(emit, jnp.uint32(ONES), acc)

    def position_body(t, acc):
        # one AND of the literal's [S, 128] position words per include; the
        # clause word is ORed over its rows as it is stored
        acc = acc & lits_ref[operand(lit_idx_ref, t)]
        emit = operand(last_ref, t) == 1
        emit_ref[pl.ds(t, 1), :] = jnp.where(emit, _or_rows(acc),
                                             jnp.uint32(0))
        return jnp.where(emit, jnp.uint32(ONES), acc)

    sweep = body if positions_bw is None else position_body
    acc_ref[...] = jax.lax.fori_loop(0, bi, sweep, acc_ref[...])
    emitted = emit_ref[...]
    if positions_bw is not None:
        emitted = _or_lanes(emitted, positions_bw)
    # one bitplane transpose + popcount reduction per instruction block:
    # row 32c+b of ``planes`` holds, at bit j, datapoint b's output of
    # instruction 32c+j; each mask row selects the same 32 instructions
    planes = bit_transpose32_rows(emitted, pltpu.roll)
    words = planes.shape[1]
    for m in range(out_ref.shape[0]):
        d = jax.lax.population_count(
            planes & mask_pos_ref[:, m : m + 1]
        ).astype(jnp.int32) - jax.lax.population_count(
            planes & mask_neg_ref[:, m : m + 1]
        ).astype(jnp.int32)
        out_ref[m] += d.reshape(bi // 32, 32, words).sum(axis=0)


def kernel_blocks(
    i_cap: int,
    n_words: int,
    block_instructions: int | None = None,
    block_words: int | None = None,
    positions: int = 1,
) -> tuple[int, int]:
    """Validated ``(bi, bw)`` for the kernel at one capacity point.

    Unset blocks come from ``kernels.tuning.choose_blocks``.
    ``block_instructions`` must be a positive multiple of 32 (the class
    masks pack 32 instructions per word) and is clipped to the 32-aligned
    instruction depth; ``block_words`` is clipped to the word count and
    must then be all of it or a multiple of 128 (a block's last dim is a
    whole array dim or whole 128-lane tiles).  With ``positions > 1`` the
    word block is at most 128 and rounded up to a power of two, which the
    OR over positions needs (the words are padded to it)."""
    if block_instructions is None or block_words is None:
        auto_bi, auto_bw = choose_blocks(i_cap, n_words)
        if block_instructions is None:
            block_instructions = auto_bi
        if block_words is None:
            block_words = auto_bw
    if block_instructions <= 0 or block_instructions % 32:
        raise ValueError(
            f"block_instructions must be a positive multiple of 32, got "
            f"{block_instructions}"
        )
    bw = min(block_words, n_words)
    if positions > 1 and bw > 0:
        bw = 1 << (min(bw, 128) - 1).bit_length()
    elif bw <= 0 or (bw != n_words and bw % 128):
        raise ValueError(
            f"block_words must cover all {n_words} words or be a positive "
            f"multiple of 128, got {block_words}"
        )
    return min(block_instructions, -(-i_cap // 32) * 32), bw


def kernel_operands(lit_idx, last_flag, mask_pos, mask_neg, block_instructions):
    """Program operands -> the kernel's layout (numpy or jax arrays).

    The operand vectors are padded to ``I_pad``, a whole number of
    instruction blocks (padded instructions AND row 0 and never emit).
    The class masks (``[banks, chunks]``, or ``[P, m_cap, chunks]`` with
    the planes flattened into banks) become one row per instruction,
    ``[I_pad, banks]`` with row t = chunk t // 32, so an instruction block
    is a sublane-aligned row block of them.  A serving engine builds this
    once per program and keeps it resident."""
    xp = np if isinstance(mask_pos, np.ndarray) else jnp
    i_cap = lit_idx.shape[0]
    i_pad = -(-i_cap // block_instructions) * block_instructions

    def rows(m):
        m = m.reshape(-1, m.shape[-1])
        m = xp.pad(m, ((0, 0), (0, i_pad // 32 - m.shape[1])))
        return xp.repeat(m.T, 32, axis=0)

    return (
        xp.pad(lit_idx, (0, i_pad - i_cap)),
        xp.pad(last_flag, (0, i_pad - i_cap)),
        rows(mask_pos),
        rows(mask_neg),
    )


def sum_weight_planes(sums: jax.Array) -> jax.Array:
    """int32[P, m_cap, B] per-plane sums -> int32[m_cap, B] with shifted
    adds (``<< b``), keeping the weighted path multiply-free."""
    shifts = jnp.arange(sums.shape[0], dtype=jnp.int32)[:, None, None]
    return jnp.left_shift(sums, shifts).sum(axis=0)


@functools.partial(
    jax.jit, static_argnames=("block_instructions", "block_words", "interpret")
)
def tm_popcount(
    lit_idx: jax.Array,  # int32[I_cap]  absolute literal slot (padded: 0)
    last_flag: jax.Array,  # int32[I_cap] 1 = last include of its clause
    mask_pos: jax.Array,  # uint32[m_cap, ceil(I_cap/32)] +clause selectors
    mask_neg: jax.Array,  # uint32[m_cap, ceil(I_cap/32)] -clause selectors
    packed_lits: jax.Array,  # uint32[L2, W], or uint32[L2, P, W]
    *,
    block_instructions: int | None = None,
    block_words: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Popcount-bitplane inference -> int32[m_cap, W*32] class sums.

    Blocks are checked and defaulted by ``kernel_blocks``; the operands
    are laid out by ``kernel_operands`` on every call (a serving engine
    does that once per program and calls ``tm_popcount_resident``).

    3-D masks (``[P, m_cap, chunks]``, repro.prune weighted clauses) run
    the SAME kernel with the plane axis flattened into the class axis —
    the kernel popcounts ``P * m_cap`` banks — and the per-plane sums are
    combined outside by ``sum_weight_planes``, keeping the kernel body
    untouched and the whole path multiply-free.
    """
    bi, bw = kernel_blocks(
        lit_idx.shape[0], packed_lits.shape[-1], block_instructions,
        block_words, positions=lit_positions(packed_lits),
    )
    sums = tm_popcount_resident.__wrapped__(
        *kernel_operands(lit_idx, last_flag, mask_pos, mask_neg, bi),
        packed_lits, block_instructions=bi, block_words=bw,
        interpret=interpret,
    )
    if mask_pos.ndim == 3:
        p = mask_pos.shape[0]
        return sum_weight_planes(sums.reshape(p, -1, sums.shape[1]))
    return sums


# the longest instruction stream whose operand vectors (two int32 each) are
# prefetched whole into SMEM (1 MiB on a v5e), half of it; a longer one
# comes one SMEM block per instruction block
PREFETCH_INSTRUCTIONS = 65536


@functools.partial(
    jax.jit, static_argnames=("block_instructions", "block_words", "interpret")
)
def tm_popcount_resident(
    lit_idx: jax.Array,  # int32[I_pad]
    last_flag: jax.Array,  # int32[I_pad]
    mask_pos: jax.Array,  # uint32[I_pad, banks]
    mask_neg: jax.Array,  # uint32[I_pad, banks]
    packed_lits: jax.Array,  # uint32[L2, W]
    *,
    block_instructions: int,
    block_words: int,
    interpret: bool = False,
) -> jax.Array:
    """The kernel on operands already in ``kernel_operands``' layout, with
    blocks from ``kernel_blocks`` -> int32[banks, W*32] class sums.

    ``lit_idx``/``last_flag`` ride in SMEM as scalar prefetch (read once
    per instruction), or, for a stream longer than
    ``PREFETCH_INSTRUCTIONS``, one ``(1, 1, bi)`` SMEM block per
    instruction block; each grid step reads a ``(bi, banks)`` row block
    of the masks.  3-D ``packed_lits`` (``[L2, P, W]``) carry positions:
    each word block's P x bw words are laid out position-major in whole
    (8, 128) tiles, ``[L2, n_blocks * S, 128]``, and each clause word is
    ORed over positions (rows at the include, lanes once a block)."""
    bi, bw = block_instructions, block_words
    i_pad, banks = mask_pos.shape
    l2, w = packed_lits.shape[0], packed_lits.shape[-1]
    w_pad = -(-w // bw) * bw
    n_blocks = w_pad // bw
    options, n_prefetch, operand_specs = {}, 2, []
    if i_pad > PREFETCH_INSTRUCTIONS:
        options["stream_blocks"] = True
        n_prefetch = 0
        operand_specs = [
            pl.BlockSpec((1, 1, bi), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
        ] * 2
        lit_idx = lit_idx.reshape(i_pad // bi, 1, bi)
        last_flag = last_flag.reshape(i_pad // bi, 1, bi)
    out_words = bw
    if packed_lits.ndim == 3:
        p = packed_lits.shape[1]
        rows = -(-p * bw // 1024) * 8  # a block's 128-word rows, whole tiles
        panel = jnp.pad(packed_lits, ((0, 0), (0, 0), (0, w_pad - w)))
        panel = panel.reshape(l2, p, n_blocks, bw).transpose(0, 2, 1, 3)
        panel = jnp.pad(panel.reshape(l2, n_blocks, p * bw),
                        ((0, 0), (0, 0), (0, rows * 128 - p * bw)))
        panel = panel.reshape(l2, n_blocks * rows, 128)
        options["positions_bw"] = bw
        out_words = 128
        lits_spec = pl.BlockSpec((l2, rows, 128), lambda j, i, *_: (0, j, 0))
        acc_shape = (rows, 128)
    else:
        panel = jnp.pad(packed_lits, ((0, 0), (0, w_pad - w)))
        lits_spec = pl.BlockSpec((l2, bw), lambda j, i, *_: (0, j))
        acc_shape = (1, bw)

    kernel = _tm_popcount_kernel
    if options:
        kernel = functools.partial(_tm_popcount_kernel, **options)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(n_blocks, i_pad // bi),
            in_specs=operand_specs + [
                pl.BlockSpec((bi, banks), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((bi, banks), lambda j, i, *_: (i, 0)),
                lits_spec,
            ],
            out_specs=pl.BlockSpec(
                (banks, 32, out_words), lambda j, i, *_: (0, 0, j)
            ),
            scratch_shapes=[
                # packed clause accumulator, block emit buffer
                pltpu.VMEM(acc_shape, jnp.uint32),
                pltpu.VMEM((bi, out_words), jnp.uint32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (banks, 32, n_blocks * out_words), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="tm_popcount",
    )(lit_idx, last_flag, mask_pos, mask_neg, panel)
    if out_words != bw:  # word w of a block is in lane w of its 128
        out = out.reshape(banks, 32, n_blocks, out_words)[..., :bw]
        out = out.reshape(banks, 32, w_pad)
    # out[m, b, w] is datapoint 32w + b
    return out[:, :, :w].transpose(0, 2, 1).reshape(banks, w * 32)


def lit_positions(packed_lits: jax.Array) -> int:
    """Positions of a packed literal panel: ``[L2, P, W]`` carries P,
    ``[L2, W]`` one."""
    return packed_lits.shape[1] if packed_lits.ndim == 3 else 1


def _segmented_and_scan(sel: jax.Array, start: jax.Array) -> jax.Array:
    """Inclusive AND scan over axis 0 with resets where ``start`` is True.

    Standard segmented-scan combine — associative, so XLA evaluates it in
    log2(I) parallel rounds instead of the interpreter's I sequential ones.
    """

    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb[:, None], vb, va & vb)

    _, acc = jax.lax.associative_scan(combine, (start, sel), axis=0)
    return acc


@jax.jit
def tm_popcount_xla(
    lit_idx: jax.Array,  # int32[I_cap]
    last_flag: jax.Array,  # int32[I_cap]
    mask_pos: jax.Array,  # uint32[m_cap, ceil(I_cap/32)]
    mask_neg: jax.Array,  # uint32[m_cap, ceil(I_cap/32)]
    packed_lits: jax.Array,  # uint32[L2, W], or uint32[L2, P, W]
) -> jax.Array:
    """The popcount bitplane algorithm as pure XLA -> int32[m_cap, W*32].

    Bit-exact with ``tm_popcount``; this is what the serving executors run
    off-TPU (Pallas interpret mode emulates the grid and is far slower than
    native XLA on CPU).  With positions the clause words are ORed over
    them before the popcount, as in the kernel.
    """
    i_cap = lit_idx.shape[0]
    i_pad = -(-i_cap // 32) * 32
    lit_idx = jnp.pad(lit_idx, (0, i_pad - i_cap))
    last_flag = jnp.pad(last_flag, (0, i_pad - i_cap))
    pad_chunks = i_pad // 32 - mask_pos.shape[-1]
    lead = ((0, 0),) * (mask_pos.ndim - 1)
    mask_pos = jnp.pad(mask_pos, lead + ((0, pad_chunks),))
    mask_neg = jnp.pad(mask_neg, lead + ((0, pad_chunks),))

    l2, w = packed_lits.shape[0], packed_lits.shape[-1]
    panel = packed_lits.reshape(l2, -1)  # [L2, P*W]
    sel = jnp.take(panel, lit_idx, axis=0)  # [I, P*W] literal select
    emit = last_flag == 1
    start = jnp.concatenate([jnp.ones((1,), bool), emit[:-1]])
    acc = _segmented_and_scan(sel, start)  # packed clause outputs
    emit_words = jnp.where(emit[:, None], acc, jnp.uint32(0))
    if packed_lits.ndim == 3:  # OR over positions
        emit_words = jax.lax.reduce(
            emit_words.reshape(i_pad, -1, w), jnp.uint32(0),
            jax.lax.bitwise_or, (1,))
    return popcount_reduce(emit_words, mask_pos, mask_neg)
