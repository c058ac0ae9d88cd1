"""The model seam moved no input of the three cells: at seeds 0 and 1 the
include actions, every row of every pool, the served artifact's bytes and
the schedule of sends hash to the digests the benchmark's own code gave
before the seam existed, and the work counts and the readers built on
them read as they did.

A digest is SHA-256 over each array's dtype and shape, then its bytes;
the artifact's is over ``Accelerator.compile(model).to_bytes()``.  A
closed schedule is its first 1000 pool indices; an open one its due
offsets, classes and indices over 2 s.
"""

import hashlib

import numpy as np
import pytest

from bench import harness, work
from bench.configs.models import load_config
from bench.traffic.common import TRAFFIC_STREAM, Records, load_traffic

DIGESTS = {
    ("tm_mnist", "upload32_closed", 0): {
        "actions":
            "26066cd0ab267b92901475b4a3936905650e445063bb70f8b1444a70c3b2de7e",
        "pools":
            "b00f3a39f05e017b6ede64478d400d9a68129989d239797a6152552d82a9d3b1",
        "artifact":
            "ca5bbb375ccb23cb37a9cbaf1742f91d88dbdeea04b4a0502deabc60b5edc6dc",
        "schedule":
            "6f41996d9eef9405629d97e30b21ba2a06a97691bb55f52f7b4da89939602a3d",
    },
    ("tm_mnist", "upload32_closed", 1): {
        "actions":
            "d61d205d6932d92d34eee77f304190da97095548199641dae7cd947ba0278328",
        "pools":
            "a64d3361b34444fee33a1f2f4c8e44c32c590ac679bfd4aa3bb3fe3df07db765",
        "artifact":
            "49b2b68ed567429c35876c5e3520e53ccbc8ffbd3d6316d2e4cbf0fad75c45c1",
        "schedule":
            "3d5b1548d0909526e4146091bba2e5b5a8e21e2f4adf233b319e224782dfd745",
    },
    ("tm_mnist", "mixed_open", 0): {
        "actions":
            "26066cd0ab267b92901475b4a3936905650e445063bb70f8b1444a70c3b2de7e",
        "pools":
            "f3a2a62499e3962242a86eacaf939cf651be8efdd53f59db16c110490da2b034",
        "artifact":
            "ca5bbb375ccb23cb37a9cbaf1742f91d88dbdeea04b4a0502deabc60b5edc6dc",
        "schedule":
            "cf4c97b1caebd78661e40fabd69862e500c60e4e325baa8f00908cb3dbc3c42a",
    },
    ("tm_mnist", "mixed_open", 1): {
        "actions":
            "d61d205d6932d92d34eee77f304190da97095548199641dae7cd947ba0278328",
        "pools":
            "5ce8086e7bfe2458afa5855d42ec3dc626c707b9d4e279a2dc7ea8f28b1a756e",
        "artifact":
            "49b2b68ed567429c35876c5e3520e53ccbc8ffbd3d6316d2e4cbf0fad75c45c1",
        "schedule":
            "039e8e03572f34e272bb2b85be158654c573c63b8d2fcfd73790536095b7bb44",
    },
    ("tm_emg", "sensor1_closed", 0): {
        "actions":
            "b9b7d42f023832249467f1c8b32bd063a5ccea1aee8202e82668db1352af3cae",
        "pools":
            "b9b3bdf3e699f5db3c3c7a65149c362c590bf67d9931470c6c595358596cc7a5",
        "artifact":
            "c959c30348df1e2f038a4d351d61bd70e5a2b6077002c7d620b038cb5399e12f",
        "schedule":
            "b21a31b2ff432c236c5ced2d4776aa102953ab058ceff4b4c7a1ea71fe2a94b0",
    },
    ("tm_emg", "sensor1_closed", 1): {
        "actions":
            "56ccd94efe2b7b36d1536e672240e488e658f5ce66deefde9223c592f48d9d54",
        "pools":
            "43559c4ded73aceca98d3c3d57d6acaf0b1d6142920b1d5458832aa549828cae",
        "artifact":
            "d0df1585ad68a5390ac9c0fb7ed15be5c4f414d6ed3db84d94d21bcf8be3bd9b",
        "schedule":
            "ae470307771a464133867a8b38b97e23b81ec6290d7e871bad98cc51fc787311",
    },
}
# ops and bytes of 128 rows in one batch
WORK = {"tm_mnist": (2432000, 64208.0), "tm_emg": (948992, 18124.0)}
# (popcount_roofline, serve_mfu_pct) of ``_run``
READERS = {"tm_mnist": (0.016764016764016763, 1.5470737913486004e-07),
           "tm_emg": (0.00475939675939676, 6.036844783715013e-08)}


def _digest(*arrays) -> str:
    d = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        d.update(str((a.dtype.str, a.shape)).encode())
        d.update(a.tobytes())
    return d.hexdigest()


@pytest.mark.parametrize("name,traffic_name,seed", list(DIGESTS))
def test_inputs_match_the_digests(name, traffic_name, seed):
    from repro.accel import Accelerator

    config, traffic = load_config(name), load_traffic(traffic_name)
    model = harness.load_model(config).build(config, seed)
    gen = harness.load_generator(traffic["kind"]).build(
        traffic, model, np.random.default_rng([seed, TRAFFIC_STREAM]))
    acc = Accelerator.for_models([model.program_model])
    artifact = acc.compile(model.program_model).to_bytes()
    if traffic["kind"] == "closed":
        schedule = np.asarray(gen.schedule(1000), np.int64)
    else:
        schedule = np.concatenate(
            [np.asarray(a, np.float64) for a in gen.schedule(2.0)])
    assert {"actions": _digest(model.truth), "pools": _digest(*gen.pools),
            "artifact": hashlib.sha256(artifact).hexdigest(),
            "schedule": _digest(schedule)} == DIGESTS[name, traffic_name, seed]


@pytest.mark.parametrize("name", list(WORK))
def test_work_counts_are_unchanged(name):
    config = load_config(name)
    model = harness.load_model(config).build(config, 0)
    assert (model.ops(128), model.bytes(128, 1)) == WORK[name]


def _run(config, model):
    n = 5
    records = Records(
        pool=np.zeros(n, np.int32), index=np.arange(n),
        due=np.full(n, np.nan), sent=np.linspace(0.1, 0.5, n),
        queue_delay=np.zeros(n), done=np.array([0.2, 0.4, 0.6, 0.9, 1.5]),
        failed=np.array([False, False, True, False, False]),
        sums=[None] * n, preds=[None] * n,
        rows=np.array([32, 1, 32, 7, 32]), t_start=0.0, t_end=1.0)
    return harness.Run(
        config=config, model=model, setup_s=1.0, window_s=1.25,
        records=records, counters={"batches": 800, "rows": 100000,
                                   "padded_rows": 102400, "engine_s": 1.0},
        peak=work.peak("TPU v5 lite"),
        trace={"kernel_s": {model.kernels["popcount"]: 0.37}})


@pytest.mark.parametrize("name", list(READERS))
def test_work_readers_read_as_before(name):
    config = load_config(name)
    run = _run(config, harness.load_model(config).build(config, 0))
    got = tuple(harness.load_reader(m)(run)
                for m in ("popcount_roofline", "serve_mfu_pct"))
    assert got == READERS[name]
