from .sample import (
    NeighborOutput, sample_neighbors, sample_neighbors_weighted,
    neighbor_probs,
)
from .unique import ordered_unique
from .negative import edge_in_csr, random_negative_sample, NegativeOutput
from .subgraph import induced_subgraph, SubGraph
from .stitch import stitch_rows
from .superstep import superstep, scan_consume
from .delta import delta_one_hop, tombstone_mask

__all__ = [
    'NeighborOutput', 'sample_neighbors', 'sample_neighbors_weighted',
    'neighbor_probs',
    'ordered_unique',
    'edge_in_csr', 'random_negative_sample', 'NegativeOutput',
    'induced_subgraph', 'SubGraph',
    'stitch_rows',
    'superstep', 'scan_consume',
    'delta_one_hop', 'tombstone_mask',
]
