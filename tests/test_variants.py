"""The kernel on bipartite (unwritten-node-block) blocks and in interpret
mode inside shard_map, the plan's jit statics and npz round trip, and the
profile gauges' labels.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.model import AggConfig
from repro.core.partition import partition_graph, transpose_graph
from repro.graphs.csr import random_power_law
from repro.kernels.ops import DeviceSchedule, aggregate

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _scheds(g, *, gs=8, gpt=8, ont=8, src_win=64, edge_vals=None, seed=0):
    p = partition_graph(g, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                        edge_vals=edge_vals)
    gT, vals_t, perm = transpose_graph(g, edge_vals)
    pT = partition_graph(gT, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                         edge_vals=vals_t)
    return DeviceSchedule(p), DeviceSchedule(pT, edge_perm=perm)


# ------------------------------------------ bipartite / unwritten blocks


def test_bipartite_unvisited_blocks_read_zero(rng):
    from repro.graphs.subgraph import pad_to_nodes
    g = random_power_law(60, 4.0, seed=16)
    gp = pad_to_nodes(g, 256)            # rows 60..255 have no edges
    ev = np.ones(gp.num_edges, np.float32)
    p = partition_graph(gp, gs=8, gpt=8, ont=8, src_win=64, edge_vals=ev)
    sched = DeviceSchedule(p)
    feat = jnp.asarray(rng.standard_normal((gp.num_nodes, 16)), jnp.float32)
    out = np.asarray(aggregate(feat, sched, dt=16,
                               backend="pallas_interpret"))
    ref = np.asarray(aggregate(feat, sched, dt=16, backend="xla"))
    assert np.all(out[g.num_nodes:] == 0.0)
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


# ---------------------------------------------- interpret-mode shard_map


def test_kernel_in_shard_map_interpret():
    """The kernel runs inside the halo-exchange shard_map body under
    interpret mode, forward + grad."""
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.advisor import plan_for
        from repro.core.aggregate import PlanExecutor
        from repro.core.model import AggConfig
        from repro.distributed.graph_shard import ShardedExecutor
        from repro.graphs.csr import random_power_law
        from repro.models.gnn import gcn_edge_values

        g, vals = gcn_edge_values(random_power_law(300, 5.0, seed=7))
        cfg = AggConfig(gs=8, gpt=8, ont=8, src_win=64, dt=16)
        plan = plan_for(g, arch="gcn", in_dim=16, edge_vals=vals,
                        config=cfg, with_backward=True)
        feat = jnp.asarray(np.random.default_rng(0).standard_normal(
            (g.num_nodes, 16)).astype(np.float32))
        ref_ex = PlanExecutor(plan, backend="xla")
        ref = np.asarray(ref_ex(feat))
        gref = np.asarray(jax.grad(lambda f: (ref_ex(f) ** 2).sum())(feat))
        ex = ShardedExecutor(plan.shards(2), backend="pallas_interpret")
        assert np.abs(np.asarray(ex(feat)) - ref).max() < 1e-4
        gsh = np.asarray(jax.grad(lambda f: (ex(f) ** 2).sum())(feat))
        assert np.abs(gsh - gref).max() < 1e-4 * (1 + np.abs(gref).max())
        print("OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


# ----------------------------------------------- plan statics and npz


def test_config_in_jit_statics_and_npz_roundtrip(tmp_path):
    from repro.core.advisor import plan_for
    from repro.core.plan import Plan
    from repro.kernels.ops import sched_statics
    g = random_power_law(150, 5.0, seed=18)
    cfg = AggConfig(gs=8, gpt=8, ont=8, src_win=64, dt=16,
                    feat_dtype="bfloat16")
    plan = plan_for(g, arch="gcn", in_dim=16, config=cfg,
                    feat_dtype="bfloat16")
    # cached executables key on jit_statics: the schedule geometry, the
    # dim tile and the dtype policy
    assert plan.jit_statics() == (sched_statics(plan.sched()), None, 16,
                                  "bfloat16")
    path = str(tmp_path / "plan.npz")
    plan.save(path)
    assert Plan.load(path).config == cfg


def test_plan_load_ignores_retired_variant_field(tmp_path):
    """Plans saved while the kernel had several gather variants carry a
    ``cfg_variant`` field; they still load, to the same config."""
    from repro.core.advisor import plan_for
    from repro.core.plan import Plan
    g = random_power_law(150, 5.0, seed=19)
    cfg = AggConfig(gs=8, gpt=8, ont=8, src_win=64, dt=16)
    plan = plan_for(g, arch="gcn", in_dim=16, config=cfg)
    path = str(tmp_path / "plan.npz")
    plan.save(path)
    data = dict(np.load(path))
    data["cfg_variant"] = np.frombuffer(b"direct", dtype=np.uint8)
    legacy = str(tmp_path / "legacy.npz")
    np.savez_compressed(legacy, **data)
    loaded = Plan.load(legacy)
    assert loaded.config == cfg
    feat = jnp.asarray(np.random.default_rng(0).standard_normal(
        (g.num_nodes, 16)), jnp.float32)
    np.testing.assert_allclose(np.asarray(loaded.executor("xla")(feat)),
                               np.asarray(plan.executor("xla")(feat)),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------- bench_compare: new rows


def test_bench_compare_new_rows_exit_zero(tmp_path, capsys):
    """Rows present in the run but absent from the committed baseline are
    'new' — informational, NOT gate failures (a benchmark's new rows land
    before the baseline refresh)."""
    import importlib.util
    from repro.obs.baseline import make_baseline, save_baseline
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)

    base_rows = [{"name": "agg/x/group", "us_per_call": 100.0,
                  "p50_us": 100.0, "p90_us": 105.0}]
    cur_rows = base_rows + [{"name": "agg/x/group_bf16",
                             "us_per_call": 40.0}]
    bench_dir = tmp_path / "bench"
    base_dir = tmp_path / "baselines"
    bench_dir.mkdir()
    base_dir.mkdir()
    with open(bench_dir / "BENCH_bench_t.json", "w") as f:
        json.dump({"schema": "repro.bench/v1", "section": "t", "module": "m",
                   "ok": True, "wall_s": 1.0, "context": {"git_sha": "abc"},
                   "rows": cur_rows}, f)
    save_baseline(make_baseline("bench_t", base_rows,
                                context={"git_sha": "abc"}),
                  str(base_dir / "bench_t.json"))
    rc = bc.main(["--bench-dir", str(bench_dir),
                  "--baseline-dir", str(base_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "new" in out and "agg/x/group_bf16" in out
