"""What the four benchmark cells read does not move when the harness
does: the data every configuration's ``data`` block makes, and the
numbers the binary reference gives on a fixed ``Produced``, equal to
the bit what the harness gave before generators and objectives became
files of their own (``golden_parent.json``, recorded from that commit:
sha256 of ``x`` and ``y`` at 20,000 rows for two seeds, and
``compare``'s numbers for the reference put in the trainer's place on
the ``tiny`` configuration, exact, as the control and with each
fault).  Nothing here touches a JAX backend."""
from __future__ import annotations

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from harness import datagen, reference                     # noqa: E402

with open(os.path.join(HERE, "golden_parent.json")) as _f:
    GOLDEN = json.load(_f)
CONFIGS = ("higgs28", "criteo67", "criteo67x4", "epsilon2000")
RUNS = {"exact": {},
        "control": {"hist_precision": "int4", "leaf_precision": "bfloat16"},
        "state_unchanged": {"fault": "state_unchanged"},
        "half_batch": {"fault": "half_batch"},
        "altered_answer": {"fault": "altered_answer"}}


def _config(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", GOLDEN["seeds"])
@pytest.mark.parametrize("name", CONFIGS)
def test_data_bytes_are_the_parents(name, seed):
    cfg = _config(f"benchmark/configs/{name}.json")
    x, y, group = datagen.make(GOLDEN["rows"], cfg["features"], cfg["data"],
                               seed)
    want = GOLDEN["data"][f"{name}/{seed}"]
    assert group is None
    assert hashlib.sha256(x.tobytes()).hexdigest() == want["x"]
    assert hashlib.sha256(y.tobytes()).hexdigest() == want["y"]


@pytest.fixture(scope="module")
def tiny_data():
    cfg = _config("tests/benchmark/files/configs/tiny.json")
    seed = GOLDEN["seeds"][0]
    x, y, _ = datagen.make(cfg["rows"], cfg["features"], cfg["data"], seed)
    return cfg["params"], seed, x, y


@pytest.mark.parametrize("who", list(RUNS))
def test_binary_reference_numbers_are_the_parents(tiny_data, who):
    params, seed, x, y = tiny_data
    produced = reference.train_in_place(x, y, params, 3, seed, **RUNS[who])
    assert reference.compare(produced, x, y, params, seed, 3) == \
        GOLDEN["compare"][who]
