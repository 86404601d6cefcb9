"""The paper's systems payoff end-to-end: BuffCut as the placement service
for distributed GNN training.  The placement service dispatches through
`repro.api.partition`, so any driver registered there can back it.

 1. Stream-partition a graph into 8 'device' blocks with BuffCut,
 2. quantify the halo-exchange bytes a GNN layer would move vs
    random/hash placement,
 3. sample minibatch neighborhoods on the placed graph, with and without
    the partition-aware bias, and compare the fraction of feature gathers
    that cross shards.

    PYTHONPATH=src python examples/gnn_partition_pipeline.py
"""
import numpy as np

from repro.graphs import (
    rgg_graph, apply_order, random_order, sample_multihop, cross_block_fraction,
)
from repro.distributed.gnn_placement import place_graph, placement_report, reorder_for_shards

N_SHARDS = 8
D_FEAT = 32
FANOUTS = (8, 4)

g = apply_order(rgg_graph(2048, seed=3), random_order(rgg_graph(2048, seed=3), 1))
print(f"graph n={g.n} m={g.m}")

# --- 1+2: placement quality
report = placement_report(g, N_SHARDS, D_FEAT)
for method, r in report.items():
    print(f"{method:8s} halo={r['halo_MB_per_layer']:.3f} MB/layer "
          f"imbalance={r['load_imbalance']:.3f}")
assert report["buffcut"]["halo_MB_per_layer"] < report["random"]["halo_MB_per_layer"]

placement = place_graph(g, N_SHARDS, method="buffcut")
perm = reorder_for_shards(g, placement)
assert (np.diff(placement.block[perm]) >= 0).all()  # shard-major contiguous
print("shard sizes:", np.bincount(placement.block).tolist())

# --- 3: cross-shard gathers of a sampled minibatch
rng = np.random.default_rng(0)
seeds = rng.integers(0, g.n, 256)
cross = {}
for name, block_of in (("plain", None), ("aware", placement.block)):
    layers = sample_multihop(g, seeds, FANOUTS, seed=0, block_of=block_of)
    cross[name] = cross_block_fraction(g, layers, placement.block)
print(f"cross-shard gather fraction: plain {cross['plain']:.3f}, "
      f"partition-aware {cross['aware']:.3f}")
assert cross["aware"] < cross["plain"]
print("OK")
