from .conv import SAGEConv, GATConv, GCNConv, segment_mean
from .sage import GraphSAGE

__all__ = ['SAGEConv', 'GATConv', 'GCNConv', 'segment_mean', 'GraphSAGE']
from .rgnn import RGNN, HeteroConvLayer
from .hgt import HGT, HGTConv

__all__ += ['RGNN', 'HeteroConvLayer', 'HGT', 'HGTConv']
from .bipartite_sage import BipartiteSAGE

__all__ += ['BipartiteSAGE']
