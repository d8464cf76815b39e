"""The yardstick holds still: the plain reference, the inputs drawn from a
seed, the work counts and the graphs of the two configurations reproduce,
bit for bit, the values recorded into ``data/golden.npz`` and
``data/golden.json`` on the CPU by the harness as it stood before the
architectures moved into ``lib/arch/``.

    python3 chipbench/tests/test_chipbench_golden.py --record

writes those two files from the harness it is run in; it is how they were
made, not a step of any test.
"""
import hashlib
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.lib import counts, graphs, train  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 33 + 77                 # over 32 bits, as the driver's are
CONFIGS = ("gcn-blogcatalog", "gin-dd")
# a small graph of each generator, a model of each architecture; the specs
# carry both the generator and the graph type, which older harnesses read
CASES = {
    "gcn": ({"arch": "gcn", "num_layers": 2, "hidden_dim": 16},
            {"generator": "power_law", "type": "III", "num_nodes": 300,
             "num_edges": 2400, "exponent": 2.1, "seed": 1}, 32, 7),
    "gin": ({"arch": "gin", "num_layers": 3, "hidden_dim": 16,
             "gin_eps": 0.1},
            {"generator": "community", "type": "II", "num_nodes": 400,
             "num_edges": 2000, "seed": 2}, 24, 2),
}
OPT = json.loads((ROOT / "chipbench" / "mixes" / "train_full.json")
                 .read_text())["optimizer"]


def _sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _counts(model: dict, nodes: int, edges: int, in_dim: int,
            classes: int) -> dict:
    passes = counts.train_agg_passes(model, nodes, edges, in_dim, classes)
    return {
        "agg_passes": [[p.nodes, p.edges, p.dim, p.weighted]
                       for p in passes],
        "agg_flops": sum(counts.agg_flops(p) for p in passes),
        "agg_bytes": sum(counts.agg_bytes(p) for p in passes),
        "dense_train_flops": counts.dense_train_flops(model, nodes, in_dim,
                                                      classes),
        "train_step_flops": counts.train_step_flops(model, nodes, edges,
                                                    in_dim, classes),
    }


def reference_values() -> dict:
    """Inputs from ``SEED`` and three reference AdamW steps at each
    precision, for each small case: ``{"<arch>/<part>/<leaf>": array}``."""
    out = {}
    for arch, (model, spec, in_dim, classes) in CASES.items():
        indptr, indices = graphs.make_graph(spec)
        inputs = train.make_inputs(SEED, model, len(indptr) - 1, in_dim,
                                   classes)
        params, feat, labels = inputs
        out[f"{arch}/inputs/feat"] = np.asarray(feat)
        out[f"{arch}/inputs/labels"] = np.asarray(labels)
        for k, v in params.items():
            out[f"{arch}/inputs/{k}"] = np.asarray(v)
        cell = types.SimpleNamespace(config={"model": model},
                                     mix={"optimizer": OPT})
        for prec in ("highest", "high"):
            ref = train.reference_steps(cell, indptr, indices, inputs, prec)
            out[f"{arch}/{prec}/losses"] = np.asarray(ref["losses"])
            for part in ("first_grad", "params3"):
                for k, v in ref[part].items():
                    out[f"{arch}/{prec}/{part}/{k}"] = np.asarray(v)
    return out


def count_values() -> dict:
    """The counts of each small case, and each configuration's full graph
    (its size and hash) with the counts of a step on it."""
    out = {}
    for arch, (model, spec, in_dim, classes) in CASES.items():
        indptr, indices = graphs.make_graph(spec)
        out[arch] = _counts(model, len(indptr) - 1, len(indices), in_dim,
                            classes)
    for name in CONFIGS:
        cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                         .read_text())
        g = cfg["graph"]
        indptr, indices = graphs.make_graph(g)
        n, e = len(indptr) - 1, len(indices)
        out[name] = {"nodes": n, "edges": e, "sha256": _sha(indptr, indices),
                     **_counts(cfg["model"], n, e, g["feat_dim"],
                               g["num_classes"])}
    return out


@pytest.fixture(scope="module")
def golden():
    with np.load(DATA / "golden.npz") as z:
        arrays = {k: z[k] for k in z.files}
    return arrays, json.loads((DATA / "golden.json").read_text())


def test_reference_and_inputs_reproduce_bit_for_bit(golden):
    want, _ = golden
    got = reference_values()
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_counts_and_full_graphs_reproduce(golden):
    _, want = golden
    got = count_values()
    assert got == want
    assert want["gcn-blogcatalog"]["edges"] == 3_265_502
    assert want["gin-dd"]["edges"] == 1_535_862


if __name__ == "__main__" and sys.argv[1:] == ["--record"]:
    DATA.mkdir(exist_ok=True)
    np.savez_compressed(DATA / "golden.npz", **reference_values())
    (DATA / "golden.json").write_text(
        json.dumps(count_values(), indent=1, sort_keys=True) + "\n")
