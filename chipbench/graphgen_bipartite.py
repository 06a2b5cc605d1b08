"""The user-item benchmark's own data: a bipartite behaviour-log graph with
an item-item relation beside it, and the link model's weights, all from
``--seed``. ``chipbench/graphgen_hetero.py``'s laws, relation by relation
(its ``relation_csr`` and ``transpose`` are imported): a user's
interactions by Pareto out-degrees (shape 4/3, capped, scaled and topped
up to the configuration's count exactly; mean 81.1 in the training
split), the items of row ``i`` of degree ``k`` at ``floor(n_item *
u_j**2)`` for the stratified uniforms ``u_j = (j + r_j) / k``: low ids are
popular. Two neighbouring strata can land on one item, so a user may hold
an item twice: a multi-edge, as a behaviour log has one for every repeated
view or purchase. A relation that names ``reverse_of`` is the transpose
of that relation, edge for edge. The item-item relation is drawn by the
same laws at the configuration's mean degree, and is NOT computed as the
recipe computes it (``A^T A >= 3``: a 4 M x 4 M sparse product inside
``setup_s``); it may hold an item beside itself, as the product's
diagonal does.

The nodes have no features: a node is its id. Every array has the same
shape for every seed.
"""
import numpy as np

# a seeded choice of positive edges over a CSR is the link cell's
from chipbench.drivers.link_fused import positive_edges  # noqa: F401
from chipbench.graphgen import jax_key
from chipbench.graphgen_hetero import relation_csr, transpose


def relations(cfg):
  """Stored (traversal) relations in the configuration's order."""
  return [(r['src'], r['name'], r['dst']) for r in cfg['relations']]


def graph(cfg, seed):
  """{(s, r, d): (indptr int64 [n_s + 1], indices int32 [E])} for every
  relation of ``cfg``."""
  nodes, csr = cfg['num_nodes'], {}
  for k, rel in enumerate(cfg['relations']):
    key = (rel['src'], rel['name'], rel['dst'])
    if 'reverse_of' in rel:
      fwd = tuple(rel['reverse_of'])
      assert (fwd[0], fwd[2]) == (rel['dst'], rel['src']), rel
      csr[key] = transpose(*csr[fwd], nodes[rel['src']])
    else:
      csr[key] = relation_csr(nodes[rel['src']], nodes[rel['dst']],
                              rel['num_edges'], seed, k)
    assert csr[key][1].shape[0] == rel['num_edges'], rel
  return csr


def leaves(cfg):
  """``[(path, shape, scale)]`` of ``BipartiteSAGE``'s parameter tree:
  the tables N(0, 1) as ``torch.nn.Embedding`` starts them, kernels
  normal with variance 1 / fan_in, biases at a tenth."""
  n, d = cfg['num_nodes'], cfg['embedding_dim']
  hidden, out_dim = cfg['hidden_dim'], cfg['out_dim']
  dense = lambda at, a, b: [(at + ('kernel',), (a, b), a ** -0.5),
                            (at + ('bias',), (b,), 0.1)]
  conv = lambda at, a, b: (dense(at + ('lin_root',), a, b)
                           + [(at + ('lin_nbr', 'kernel'), (a, b),
                               a ** -0.5)])
  out = [(('embed_user', 'embedding'), (n['user'], d), 1.0),
         (('embed_item', 'embedding'), (n['item'], d), 1.0)]
  out += conv(('item_encoder', 'conv1'), d, hidden)
  out += conv(('item_encoder', 'conv2'), hidden, hidden)
  out += dense(('item_encoder', 'lin'), hidden, out_dim)
  out += conv(('user_encoder', 'conv1'), d, hidden)
  out += conv(('user_encoder', 'conv2'), d, hidden)
  out += conv(('user_encoder', 'conv3'), hidden, hidden)
  out += dense(('user_encoder', 'lin'), hidden, out_dim)
  out += dense(('decoder', 'lin1'), 2 * out_dim, out_dim)
  return out + dense(('decoder', 'lin2'), out_dim, 1)


def num_weights(cfg):
  return sum(int(np.prod(shape, dtype=np.int64))
             for _, shape, _ in leaves(cfg))


def tree_of(cfg, draw):
  """``{'params': tree}``; ``draw(i, shape)`` gives leaf ``i``'s unit
  normals."""
  tree = {}
  for i, (path, shape, scale) in enumerate(leaves(cfg)):
    node = tree
    for k in path[:-1]:
      node = node.setdefault(k, {})
    node[path[-1]] = draw(i, shape) * np.float32(scale)
  return {'params': tree}


def weights(seed, cfg):
  """The model's weights from ``seed``, made on the device in one jitted
  call from three normal draws: one a table (one draw for all would hold
  a third of a billion normals twice) and one cut into every other leaf
  (a draw a leaf is a Threefry program a leaf in the TPU's compiler)."""
  import jax
  import jax.numpy as jnp
  shapes = [shape for _, shape, _ in leaves(cfg)]
  sizes = [int(np.prod(shape)) for shape in shapes[2:]]
  starts = np.concatenate([[0], np.cumsum(sizes)])

  def make(key):
    rest = jax.random.normal(jax.random.fold_in(key, 2), (sum(sizes),),
                             jnp.float32)

    def draw(i, shape):
      if i < 2:
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
      return rest[starts[i - 2]:starts[i - 1]].reshape(shape)

    return tree_of(cfg, draw)

  return jax.jit(make)(jax_key(seed, 0))
