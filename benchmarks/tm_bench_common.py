"""Shared benchmark plumbing: trained models per dataset + timing helpers.

Hardware-model constants (paper Table 1 / Fig 5): the base accelerator
executes one include instruction in 4 clock cycles at 200 MHz on the A7035;
energy uses the paper's reported base-config power envelope (~0.35 W for
the Artix-7 class device).  These are MODELED numbers — the real
measurements in the paper came from the FPGA; we reproduce the evaluation
structure and report the model inputs explicitly.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TMConfig, accuracy, fit, include_actions, init_state
from repro.core.compress import CompressedModel, encode
from repro.data.pipeline import TM_DATASETS, booleanized_tm_dataset

CYCLES_PER_INSTRUCTION = 4  # Fig 5 pipeline
BASE_FREQ_HZ = 200e6  # Table 1, base config
BASE_POWER_W = 0.35  # modeled Artix-7 class envelope
BATCH_WORDS = 1  # 32 datapoints per pass (paper batching)


@dataclass
class TrainedTM:
    name: str
    cfg: TMConfig
    state: jax.Array
    model: CompressedModel
    accuracy: float
    x_test: np.ndarray
    y_test: np.ndarray


@lru_cache(maxsize=None)
def trained_tm(dataset: str, n_clauses: int = 60, epochs: int = 8) -> TrainedTM:
    spec = TM_DATASETS[dataset]
    xb, y, booler = booleanized_tm_dataset(spec, 1500, seed=0)
    xt, yt, _ = booleanized_tm_dataset(spec, 512, seed=1, booleanizer=booler)
    cfg = TMConfig(
        n_classes=spec.n_classes, n_clauses=n_clauses,
        n_features=booler.n_boolean_features,
    )
    state = init_state(cfg, jax.random.key(0))
    state = fit(cfg, state, jax.random.key(1), jnp.asarray(xb), jnp.asarray(y),
                epochs=epochs, batch=250)
    acc = accuracy(cfg, state, jnp.asarray(xt), jnp.asarray(yt))
    model = encode(cfg, np.asarray(include_actions(cfg, state)))
    return TrainedTM(dataset, cfg, state, model, acc, xt, yt)


def synthetic_mnist_scale(seed: int = 0) -> tuple[TMConfig, CompressedModel]:
    """Paper's MNIST numbers: 10 classes x 200 clauses x 1568 literals,
    ~17k includes (0.54% density); random includes drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    cfg = TMConfig(n_classes=10, n_clauses=200, n_features=784)
    acts = rng.random((10, 200, 1568)) < 17000 / 3136000
    return cfg, encode(cfg, acts)


def time_call(fn, *args, repeats: int = 20, warmup: int = 3) -> float:
    """-> median seconds per call (blocks on jax outputs)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def modeled_efpga_latency_s(n_instructions: int) -> float:
    return n_instructions * CYCLES_PER_INSTRUCTION / BASE_FREQ_HZ


def modeled_efpga_energy_j(n_instructions: int) -> float:
    return modeled_efpga_latency_s(n_instructions) * BASE_POWER_W
