"""Quickstart: the GNNAdvisor loop in five steps.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import advise, PlanExecutor
from repro.graphs.csr import random_community_graph
from repro.kernels import ref

# 1. an input graph (here: synthetic community graph — the structure §4.1.3
#    exploits; swap in your own CSRGraph)
g = random_community_graph(24, 32, p_intra=0.3, p_inter_edges_per_node=0.5,
                           seed=0)
print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges, "
      f"avg degree {g.avg_degree:.1f}")

# 2. run the advisor: input extractor -> modeling & estimating -> renumbering
#    -> group partitioning (paper Fig. 1 pipeline, one call)
plan = advise(g, arch="gcn", in_dim=128, hidden_dim=64)
print(f"advisor picked: gs={plan.config.gs} gpt={plan.config.gpt} "
      f"dt={plan.config.dt} src_win={plan.config.src_win} "
      f"renumbered={plan.perm is not None}")
print(f"schedule: {plan.stats['tiles']} tiles, "
      f"occupancy {plan.stats['slot_occupancy']:.2f}, "
      f"{plan.stats['flushes']} output flushes")

# 3. bind the plan to an executor.  The backend follows the platform: the
#    compiled Pallas kernel on a TPU, the XLA reference (same semantics)
#    elsewhere; backend="pallas_interpret" runs the kernel body on CPU.
ex = PlanExecutor(plan)

# 4. aggregate: out[v] = sum of neighbor embeddings
feat = jnp.asarray(np.random.default_rng(0).standard_normal(
    (g.num_nodes, 128)), jnp.float32)
out = ex.aggregate_original_order(feat)

# 5. verify against the reference segment-sum
rows, cols = g.to_coo()
want = ref.segment_aggregate_ref(feat, jnp.asarray(cols), jnp.asarray(rows),
                                 jnp.ones(g.num_edges), g.num_nodes)
print("matches segment-sum oracle:", bool(np.allclose(out, want, atol=1e-3)))
