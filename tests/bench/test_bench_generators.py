"""The yardstick's generators: models and traffic repeat exactly for a
seed, and the rows do what their parameters say."""

import numpy as np
import pytest

from bench.configs import models
from bench.configs.models import include_actions, load_config, make_rows
from bench.traffic import closed, open_poisson

TINY = {"n_classes": 3, "n_clauses": 8, "n_features": 16, "n_includes": 40}


@pytest.mark.parametrize("rule", ["literal", "feature"])
def test_include_actions_repeat_and_count_exactly(rule):
    config = dict(TINY, include_rule=rule)
    a = include_actions(config, 2**31 + 17)
    assert a.shape == (3, 8, 32) and a.sum() == 40
    assert np.array_equal(a, include_actions(config, 2**31 + 17))
    assert not np.array_equal(a, include_actions(config, 5))
    if rule == "feature":  # never a literal and its negation together
        assert not (a[..., 0::2] & a[..., 1::2]).any()


@pytest.mark.parametrize("name", ["tm_mnist", "tm_emg"])
def test_full_size_configs_give_the_stated_include_count(name):
    config = load_config(name)
    a = include_actions(config, 3)
    assert a.sum() == config["n_includes"]
    assert a.shape == (config["n_classes"], config["n_clauses"],
                       2 * config["n_features"])


def test_rows_repeat_and_satisfy_planted_clauses():
    acts = include_actions(dict(TINY, include_rule="feature"), 1)
    x1 = make_rows(acts, 64, 1, np.random.default_rng([9, 2]))
    x2 = make_rows(acts, 64, 1, np.random.default_rng([9, 2]))
    assert np.array_equal(x1, x2) and x1.dtype == np.uint8
    lits = np.stack([x1, 1 - x1], axis=-1).reshape(64, -1)
    flat = acts.reshape(-1, acts.shape[-1])
    fires = ((lits[:, None, :] >= flat[None]) | ~flat[None]).all(-1)
    fires &= flat.any(-1)[None]
    assert fires.any(axis=1).all()  # every row satisfies its planted clause


def _closed(seed):
    model = models.build(dict(TINY, include_rule="literal"), 0)
    params = {"clients": 2, "rows": 4, "lane": "normal",
              "pool_requests": 16, "satisfy_clauses": 2,
              "warmup_seconds": 0.1}
    return closed.build(params, model, np.random.default_rng([seed, 2]))


def test_closed_schedule_repeats_for_a_seed():
    a, b = _closed(7), _closed(7)
    assert np.array_equal(a.pools[0], b.pools[0])
    assert np.array_equal(a.schedule(40), b.schedule(40))
    assert not np.array_equal(a.schedule(40), _closed(8).schedule(40))


def _open(seed):
    model = models.build(dict(TINY, include_rule="literal"), 0)
    params = {"rate_per_s": 500.0, "satisfy_clauses": 1,
              "warmup_seconds": 0.1,
              "mix": [{"share": 0.75, "rows": 1, "lane": "critical",
                       "pool_requests": 32},
                      {"share": 0.25, "rows": 4, "lane": "normal",
                       "pool_requests": 8}]}
    return open_poisson.build(params, model,
                              np.random.default_rng([seed, 2]))


def test_open_schedule_repeats_for_a_seed_and_keeps_its_rate():
    a, b = _open(3), _open(3)
    for u, v in zip(a.schedule(4.0), b.schedule(4.0)):
        assert np.array_equal(u, v)
    due, kind, index = _open(3).schedule(4.0)
    assert 0 <= due.min() and due.max() < 4.0
    assert abs(due.size / 4.0 - 500) < 5 * np.sqrt(2000) / 4.0
    assert abs(kind.mean() - 0.25) < 0.05
    assert index[kind == 1].max() < 8 and index[kind == 0].max() < 32
    assert not np.array_equal(due, _open(4).schedule(4.0)[0][:due.size])


def test_open_mix_shares_must_sum_to_one():
    model = models.build(dict(TINY, include_rule="literal"), 0)
    params = {"rate_per_s": 10.0, "satisfy_clauses": 0,
              "warmup_seconds": 0.1,
              "mix": [{"share": 0.5, "rows": 1, "lane": "critical",
                       "pool_requests": 2}]}
    with pytest.raises(ValueError, match="shares"):
        open_poisson.build(params, model, np.random.default_rng(0))
