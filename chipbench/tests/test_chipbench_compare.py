"""The training numbers on synthetic first steps: one leaf's gradient
swinging, as a ReLU gate at a pre-activation within rounding of zero makes
it, reads at the median leaf as the steady leaves do; an error in every
leaf reads in full."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.lib import compare

LIMITS = json.loads((Path(__file__).resolve().parents[1] / "workloads"
                     / "gcn-blogcatalog.train.json").read_text())["limits"]


def _steps(seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    grads = {"w0": rng.normal(size=(128, 16)), "w1": rng.normal(size=(16, 39))}
    params0 = {k: rng.normal(size=v.shape) for k, v in grads.items()}
    return {"losses": [3.66, 3.65, 3.64], "first_grad": grads,
            "params0": params0,
            "params3": {k: v - 0.03 * np.sign(grads[k])
                        for k, v in params0.items()}}


def _perturbed(side: dict, leaves, rel: float, seed: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    grads = dict(side["first_grad"])
    for k in leaves:
        g = grads[k]
        d = rng.normal(size=g.shape)
        grads[k] = g + rel * np.linalg.norm(g) * d / np.linalg.norm(d)
    return {**side, "first_grad": grads}


@pytest.mark.parametrize("rel", [1e-4, 1.7e-3])
def test_one_leaf_swinging_reads_at_the_median_leaf(rel):
    ref = _steps()
    numbers = compare.train_numbers(_perturbed(ref, ["w0"], rel), ref)
    assert numbers["grad_diff"] == 0.0 and numbers["grad_norm_gap"] == 0.0
    detail = compare.train_detail(_perturbed(ref, ["w0"], rel), ref)
    assert detail["grad_diffs"]["w0"] > 0.5 * rel
    assert compare.judge(numbers, LIMITS)[0]


def test_every_leaf_off_reads_in_full():
    ref = _steps()
    numbers = compare.train_numbers(_perturbed(ref, ["w0", "w1"], 1e-3), ref)
    assert numbers["grad_diff"] > 5e-4
    assert not compare.judge(numbers, LIMITS)[0]
