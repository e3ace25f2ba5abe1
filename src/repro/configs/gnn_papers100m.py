"""gnn-papers100m-like  [gnn] — BONUS config: the paper's own system at
production scale, included in the dry-run matrix beyond the assigned 10.

Mirrors ogbn-papers100M's regime scaled to fit the dry-run mesh:
16M nodes, 128-dim features, 172 classes, GraphSAGE-mean 2-layer,
fan-out (15, 10) / batch 8192 for mini-batch; ELL max_degree=32 for
full-graph.  [paper: Liu et al. 2026; dataset: Hu et al. 2020]
"""
from repro.configs.base import GNNConfig


def full_config() -> GNNConfig:
    return GNNConfig(
        name="gnn-papers100m",
        model="graphsage",
        n_nodes=16_777_216,
        feat_dim=128,
        hidden=256,
        n_classes=172,
        n_layers=2,
        fanout=(15, 10),
        batch_size=8192,
        max_degree=32,
        dtype="bfloat16",   # aggregation traffic dtype (§Perf H1)
        # The batch-tiled, double-buffered Pallas gather: compiled by
        # Mosaic on a TPU (interpret mode only off-TPU), and mesh-ready
        # through the shard_map partitioning over the NODES axis.
        # chip_smoke.py trains and serves this config on a v5e;
        # tests/test_tpu_compile.py compiles its steps for a described
        # v5e.  The 512-device launch/dryrun.py runs on virtual CPU
        # devices and takes the einsum path instead.
        use_agg_kernel=True,
        source="Liu et al. 2026 / ogbn-papers100M (Hu et al. 2020)",
    )


def smoke_config() -> GNNConfig:
    return GNNConfig(
        name="gnn-papers100m",
        model="graphsage",
        n_nodes=512,
        feat_dim=32,
        hidden=64,
        n_classes=8,
        n_layers=2,
        fanout=(5, 3),
        batch_size=32,
        max_degree=16,
        source="(reduced)",
    )
