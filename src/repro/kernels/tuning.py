"""Static block-size autotuner for the compressed-TM Pallas kernels.

The kernels in this package tile their grids with two knobs:

  * ``block_instructions`` — instruction-memory rows per grid step (the
    sequential "K-loop" depth; must be a multiple of 32 for the popcount
    bitplane reduction, whose class masks are packed 32 instructions/word);
  * ``block_words``        — 32-datapoint feature words per grid step (the
    parallel batch tile).

The right choice depends only on the *capacity* point (instruction depth x
batch words) — a synthesis-time property, never on runtime model contents —
so no search at trace time and no cache misses at serve time.  Today one
shape serves every capacity point: it must be one the TPU compiler accepts
(a word block is all of the words or a multiple of 128 lanes, an
instruction block a multiple of 32 rows), and ``tests/test_tpu_compile.py``
compiles the kernel for a described TPU v5e at the capacity points the repo
serves.  It is compile-checked, not timed; ``measure_blocks`` times
candidates on a TPU (``python -m repro.kernels.tuning``) once a benchmark
needs a second shape.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BlockChoice = Tuple[int, int]  # (block_instructions, block_words)

# block_words clips to the word count, so up to 128 words the word axis is
# one block; wider batches tile in whole 128-lane blocks
BLOCK_INSTRUCTIONS, BLOCK_WORDS = 512, 128


def _ceil32(n: int) -> int:
    return max(32, -(-n // 32) * 32)


def choose_blocks(n_instructions: int, n_words: int) -> BlockChoice:
    """Pick ``(block_instructions, block_words)`` for a capacity point,
    clipped to the (32-aligned) instruction depth and to the word count,
    so the caller can pass the choice straight to the kernel."""
    if n_instructions <= 0 or n_words <= 0:
        raise ValueError(
            f"capacity must be positive, got {n_instructions} instructions "
            f"x {n_words} words"
        )
    return (
        min(BLOCK_INSTRUCTIONS, _ceil32(n_instructions)),
        min(BLOCK_WORDS, n_words),
    )


def measure_blocks(
    n_instructions: int,
    n_words: int,
    *,
    candidates: Iterable[BlockChoice] = (
        (128, 128), (256, 128), (512, 128), (1024, 128),
    ),
    m_cap: int = 16,
    l2: int = 256,
    repeats: int = 10,
    interpret: bool = False,
    seed: int = 0,
) -> Tuple[BlockChoice, dict]:
    """Time the tm_popcount kernel per candidate block shape at one
    capacity point -> (best choice, {choice: median_seconds}).

    Used offline to choose ``choose_blocks``' shapes; not called on any
    hot path.  Run it on a TPU: an interpret-mode timing measures the CPU
    emulation, not the kernel.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from .tm_popcount.kernel import tm_popcount

    rng = np.random.default_rng(seed)
    i_cap = _ceil32(n_instructions)
    lit_idx = rng.integers(0, l2, i_cap).astype(np.int32)
    last = (rng.random(i_cap) < 0.25).astype(np.int32)
    n_chunks = i_cap // 32
    mask_pos = rng.integers(0, 2**32, (m_cap, n_chunks), dtype=np.uint32)
    mask_neg = (~mask_pos).astype(np.uint32)
    lits = rng.integers(0, 2**32, (l2, n_words), dtype=np.uint32)
    args = tuple(
        jnp.asarray(a) for a in (lit_idx, last, mask_pos, mask_neg, lits)
    )

    timings: dict = {}
    for bi, bw in candidates:
        if bi > i_cap:
            continue
        fn = lambda: tm_popcount(  # noqa: E731
            *args, block_instructions=bi, block_words=bw, interpret=interpret
        )
        jax.block_until_ready(fn())  # compile outside the window
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        timings[(bi, bw)] = float(np.median(ts))
    if not timings:
        raise ValueError(
            f"no candidate block shape fits {n_instructions} instructions "
            f"x {n_words} words"
        )
    best = min(timings, key=timings.get)
    return best, timings


def _main() -> None:  # pragma: no cover - offline block timing
    points = [(256, 1), (256, 4), (1024, 2), (1024, 8), (4096, 4)]
    print("capacity (instructions x words) -> best (bi, bw)  [median us]")
    for i_cap, w in points:
        best, timings = measure_blocks(i_cap, w)
        print(
            f"  ({i_cap:5d}, {w}) -> {best}  "
            f"[{', '.join(f'{k}={v * 1e6:.0f}' for k, v in sorted(timings.items()))}]"
        )


if __name__ == "__main__":  # pragma: no cover
    _main()
