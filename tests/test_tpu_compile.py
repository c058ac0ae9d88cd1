"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib; it compiles for a topology that
is described (``v5e:2x2``) rather than attached.  These compiles refuse
what CPU interpret mode accepts — block shapes off the 8x128 tiling,
vector ops Mosaic cannot lower, kernels that overflow on-chip memory — so
they guard every later change at the shapes the chip run serves:

* ``tm_popcount`` (Pallas) at the MNIST capacity point, at the
  ``CapacityPlan()`` default, and with two clause-weight planes;
* ``tm_popcount_xla`` and the packed train step at the MNIST shape;
* the popcount engine's serve step at the Convolutional TM cell's point
  (200,000 instructions, 136 features a patch, 784-bit images), with one
  position and with 361: a stream that long reaches the kernel in SMEM
  blocks, the patch literals are formed in the same step, and the
  kernel's scoped VMEM shows the ``[bi, 128]`` emit buffer.

Nothing runs; a compile that passes is not a chip run.  The topology is
described inside a fixture (never at import: only one process at a time
may load the TPU library), and the persistent compilation cache is off
around these compiles, since an entry written for a described chip cannot
be read back without one.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tm import TMConfig
from repro.kernels.tm_popcount.kernel import (
    kernel_blocks,
    tm_popcount,
    tm_popcount_xla,
)
from repro.kernels.tm_train import fused_train_batch

# The envelope CapacityPlan.for_models negotiates for the two MNIST-scale
# models chip_smoke.py serves (benchmarks.tm_bench_common
# .synthetic_mnist_scale, seeds 0 and 1), with batch_words=4.
MNIST = dict(i_cap=17152, m_cap=10, f_cap=784, words=4)
DEFAULT = dict(i_cap=4096, m_cap=16, f_cap=256, words=4)  # CapacityPlan()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _popcount_args(one_chip, i_cap, m_cap, f_cap, words, planes):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    masks = (planes, m_cap, -(-i_cap // 32))  # the engine's 3-D banks
    return (
        s((i_cap,), jnp.int32), s((i_cap,), jnp.int32),
        s(masks, jnp.uint32), s(masks, jnp.uint32),
        s((2 * f_cap, words), jnp.uint32),
    )


@pytest.mark.parametrize(
    "point,planes",
    [(MNIST, 1), (DEFAULT, 1), (MNIST, 2)],
    ids=["mnist", "default", "mnist-weighted"],
)
def test_tm_popcount_compiles_for_v5e(one_chip, point, planes):
    args = _popcount_args(one_chip, planes=planes, **point)
    compiled = jax.jit(tm_popcount).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA


def test_tm_popcount_kernel_keeps_its_name_on_v5e(one_chip):
    """The serving step's kernel op is named ``tm_popcount`` whatever jit
    calls it, so a profiler trace finds it by that name."""
    from repro.accel.engines import _popcount_engine_pallas

    i_cap, m_cap, f_cap, words = (DEFAULT[k] for k in
                                  ("i_cap", "m_cap", "f_cap", "words"))
    bi, bw = kernel_blocks(i_cap, words)
    i_pad = -(-i_cap // bi) * bi  # kernel_operands' resident layout

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def serve_step(*args):
        return _popcount_engine_pallas(
            *args, weight_planes=1, block_instructions=bi,
            block_words=bw, interpret=False)

    args = (s((i_pad,), jnp.int32), s((i_pad,), jnp.int32),
            s((i_pad, m_cap), jnp.uint32), s((i_pad, m_cap), jnp.uint32),
            s((32 * words, f_cap), jnp.uint8))
    text = jax.jit(serve_step).lower(*args).compile().as_text()
    kernel = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert kernel and all(
        ln.lstrip().startswith("%tm_popcount") for ln in kernel)


# the benchmark's Convolutional TM cell (bench/configs/tm_ctm_mnist.json),
# as CapacityPlan.for_models negotiates it with batch_words=4
CTM = dict(i_cap=200000, m_cap=10, f_cap=144, words=4, x_cap=784)


@pytest.mark.parametrize("positions", [1, 361],
                         ids=["one-position", "ctm-361"])
def test_serve_step_compiles_for_v5e_at_the_ctm_point(one_chip, positions):
    from repro.accel.engines import _popcount_engine_pallas

    i_cap, m_cap, f_cap, words, x_cap = (CTM[k] for k in
                                         ("i_cap", "m_cap", "f_cap", "words",
                                          "x_cap"))
    bi, bw = kernel_blocks(i_cap, words, positions=positions)
    i_pad = -(-i_cap // bi) * bi

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def serve_step(*args):
        return _popcount_engine_pallas(
            *args, weight_planes=1, block_instructions=bi,
            block_words=bw, interpret=False)

    args = [s((i_pad,), jnp.int32), s((i_pad,), jnp.int32),
            s((i_pad, m_cap), jnp.uint32), s((i_pad, m_cap), jnp.uint32)]
    if positions > 1:  # images, and the geometry's patch operands
        args += [s((32 * words, x_cap), jnp.uint8),
                 s((f_cap, positions), jnp.int32),
                 s((positions,), jnp.uint32)]
    else:  # one position: the patch is the whole row
        args += [s((32 * words, f_cap), jnp.uint8)]
    text = jax.jit(serve_step).lower(*args).compile().as_text()
    kernel = [ln.lstrip() for ln in text.splitlines()
              if "tpu_custom_call" in ln]
    # the benchmark finds the kernel's device events by this prefix
    assert len(kernel) == 1 and kernel[0].startswith("%tm_popcount.1 = ")
    if positions > 1:
        # the emit buffer holds one 128-lane row an instruction, so the
        # kernel's scoped VMEM stays under what a row of all 361 x 4
        # position words (12 lane tiles) would take for the buffer alone
        scoped = re.search(r'"used_scoped_memory_configs":\[\{[^}]*'
                           r'"size":"(\d+)"', kernel[0])
        assert scoped and int(scoped.group(1)) < bi * 12 * 128 * 4


def test_tm_popcount_xla_compiles_for_v5e_mnist(one_chip):
    args = _popcount_args(one_chip, planes=1, **MNIST)
    jax.jit(tm_popcount_xla).lower(*args).compile()


def test_fused_train_batch_compiles_for_v5e_mnist(one_chip):
    cfg = TMConfig(n_classes=10, n_clauses=200, n_features=784)
    batch = 32
    key = jax.eval_shape(lambda: jax.random.key(0))
    args = (
        jax.ShapeDtypeStruct(
            (cfg.n_classes, cfg.n_clauses, cfg.n_features, 2), jnp.int8,
            sharding=one_chip,
        ),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((batch, cfg.n_features), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
    )
    fused_train_batch.lower(cfg, *args).compile()
