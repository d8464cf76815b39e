"""The runner: no result without a TPU; and, past the look for a chip, a
whole run at a CPU size whose `correct` follows the timed path: true when
it is sound, false when it is broken underneath."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cell

from chipbench.lib import serve

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("workload", ["gcn-blogcatalog.train",
                                      "no-such.cell"])
def test_exits_non_zero_with_no_result_off_the_chip(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("name", ["gcn-blogcatalog.train", "gin-dd.train"])
def test_sound_train_run_is_correct(cpu_run, name):
    out = cpu_run(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(out["metrics"]) == {"setup_s", "train_step_ms"}
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_traced_train_run_reads_its_layers(cpu_run):
    out = cpu_run(tiny_cell("gcn-blogcatalog.train"), trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert {"plan_s", "compile_s", "padded_slots_per_edge",
            "train_mfu"} <= set(m)
    assert m["padded_slots_per_edge"]["value"] >= 1.0
    # no chip planes in a CPU trace: the device readers find nothing
    assert "agg_roofline" not in m and "agg_kernel_ms" not in m
    assert list(out)[-2:] == ["breakdown", "checks"]


def _unchanged_state(step):
    return jax.jit(lambda state, batch: (state, step(state, batch)[1]))


def _half_batch(step):
    def half(state, batch):
        n = batch["labels"].shape[0]
        mask = (jnp.arange(n) < n // 2).astype(jnp.float32)
        return step(state, {**batch, "mask": mask})
    return jax.jit(half)


def _one_leaf(times):
    """The last leaf moved ``times`` its update; the others as computed."""
    def fault(step):
        def broken(state, batch):
            new, m = step(state, batch)
            k = sorted(new[0])[-1]
            old = state[0][k]
            return ({**new[0], k: old + times * (new[0][k] - old)},
                    new[1]), m
        return jax.jit(broken)
    return fault


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _one_leaf(0.0), _one_leaf(2.0)],
                         ids=["state_unchanged", "half_batch",
                              "one_leaf_unmoved", "one_leaf_double"])
def test_broken_train_step_is_not_correct(cpu_run, fault):
    out = cpu_run(tiny_cell("gcn-blogcatalog.train"), hooks={"step": fault})
    assert not out["correct"], out["checks"]


def test_bf16_program_path_is_not_correct(cpu_run):
    cell = tiny_cell("gcn-blogcatalog.train")
    cell.config = {**cell.config, "dtype": "bfloat16"}
    out = cpu_run(cell)
    assert not out["correct"], out["checks"]


def test_sound_serve_run_is_correct(cpu_run):
    out = cpu_run(tiny_cell("gin-dd.serve"), seconds=1.0, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 40 and out["failed"] == 0
    assert set(out["checks"]) == set(serve.CHECKS)
    m = out["metrics"]
    assert {"extract_ms.serve", "plan_ms.serve", "compute_ms.serve",
            "batch_size.serve", "plan_cache_hit_rate.serve",
            "gen_late_ms.serve"} <= set(m)


def _altered_answer(serve):
    def altered(seeds):
        out = np.array(serve(seeds), copy=True)
        out[0, 0] += 1e-2 * max(1.0, float(np.abs(out[0]).max()))
        return out
    return altered


def test_altered_answer_is_not_correct(cpu_run):
    out = cpu_run(tiny_cell("gin-dd.serve"), seconds=1.0,
                  hooks={"serve": _altered_answer})
    assert not out["correct"], out["checks"]
