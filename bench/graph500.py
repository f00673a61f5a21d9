"""The Graph500 Kronecker graph as LDBC Graphalytics publishes it, made on the
device from a seed.

The edge list follows the Graph500 reference generator (``kronecker_generator``
of the specification, version 3): ``edge_factor * 2**scale`` edges, each
placed by ``scale`` independent quadrant draws with probabilities A, B, C and
D = 1 - A - B - C, then every vertex label scrambled by one random
permutation. Graphalytics' ``graph500-*`` data sets make that list a simple
undirected graph: self-loops and duplicate edges removed, and only the
vertices with an edge kept. Here each undirected edge becomes two arcs, one
each way, and the kept vertices are numbered 0 .. V - 1. The structure and the
scramble come from the configuration's fixed ``graph_seed``: a configuration
is one data set.

``--seed`` then relabels the vertices, each into another vertex of the same
degree. The degree of every vertex id is unchanged, so SPE cuts the same tiles
with the same edge and row counts on every seed, and every algorithm follows
the same trajectory up to the relabelling (the graphs are isomorphic): every
seed does the same work, laid out differently in the tiles.

The draws are made in chunks of :data:`CHUNK_EDGES` by one jitted function
(each chunk folds its index into the key, so the chunking is part of the
graph's definition); the whole list then stays on the device for the sort
that removes duplicates.

A weighted configuration (``"weights": "uniform01"``) adds Graph500 kernel
3's edge weights: float32, uniform on [0, 1), drawn from the ``graph_seed``'s
key folded with :data:`WEIGHT_TAG`, so the arc draws, the scramble and the
relabelling are the same as without weights. There is one weight per kept
simple edge, in the sorted order of the distinct edges, and both arcs of the
edge carry it. Matching a weight to a simple edge, and not to a drawn edge
before duplicates are removed, is an assumption: the specification draws a
weight for each drawn edge and leaves duplicates to the kernels. ``--seed``
moves no weight off its edge, so every seed still does the same work.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: edges drawn per jitted call (the whole list when it is shorter)
CHUNK_EDGES = 1 << 22
#: folded into the graph's key for the weights: neither the scramble's
#: ``1 << 31`` nor a chunk index
WEIGHT_TAG = (1 << 31) + 1
#: a configuration's ``graph.weights`` values
WEIGHTS = (None, "uniform01")


class Graph(NamedTuple):
    """Host copies of the arcs, as SPE and the reference read them."""

    num_vertices: int
    src: np.ndarray                # int32 [E]: both arcs of every edge
    dst: np.ndarray                # int32 [E]
    relabel: np.ndarray            # int32 [V]: base vertex id -> vertex id
    weight: Optional[np.ndarray] = None   # float32 [E], or None: unweighted


def prng_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number below 2**64 (the low and high 32 bits
    are both used, so large seeds do not wrap onto small ones)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64 - 1")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, np.uint32(seed >> 32))


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "a", "b", "c"))
def _draws(graph_key, index, scramble, *, scale, chunk, a, b, c):
    """Chunk ``index`` of the edge list, in scrambled ids."""
    key = jax.random.fold_in(graph_key, index)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(lvl, ij):
        i, j = ij
        k_i, k_j = jax.random.split(jax.random.fold_in(key, lvl))
        i_bit = jax.random.uniform(k_i, (chunk,)) > ab
        j_bit = jax.random.uniform(k_j, (chunk,)) > jnp.where(i_bit, c_norm,
                                                              a_norm)
        return (i | (i_bit.astype(jnp.int32) << lvl),
                j | (j_bit.astype(jnp.int32) << lvl))

    zero = jnp.zeros(chunk, jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return scramble[i], scramble[j]


@functools.partial(jax.jit, static_argnames=("num_vertices",))
def _simple(parts, *, num_vertices):
    """The distinct edges (lo < hi) of the drawn list, moved to its front in
    sorted order; their count; and every vertex's degree."""
    i = jnp.concatenate([p[0] for p in parts])
    j = jnp.concatenate([p[1] for p in parts])
    lo, hi = jax.lax.sort((jnp.minimum(i, j), jnp.maximum(i, j)), num_keys=2)
    new = jnp.concatenate([jnp.ones(1, bool),
                           (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = new & (lo != hi)
    slot = jnp.where(keep, jnp.cumsum(keep) - 1, lo.shape[0])
    lo_kept = jnp.zeros_like(lo).at[slot].set(lo, mode="drop")
    hi_kept = jnp.zeros_like(hi).at[slot].set(hi, mode="drop")
    ones = keep.astype(jnp.int32)
    degree = (jnp.zeros(num_vertices, jnp.int32).at[lo].add(ones)
              .at[hi].add(ones))
    return lo_kept, hi_kept, keep.sum(), degree


def _relabel(degree, noise):
    # the k-th member of a degree class in id order becomes the k-th member
    # of the same class in the order of ``noise``
    by_id = jnp.argsort(degree, stable=True)
    by_noise = jnp.lexsort((noise, degree))
    return jnp.zeros(degree.shape[0], jnp.int32).at[by_id].set(
        by_noise.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_edges", "num_vertices"))
def _arcs(lo, hi, degree, seed_key, *, num_edges, num_vertices):
    """Both arcs of each kept edge in the seed's labels, and the relabelling
    of the kept vertices (numbered in base-id order)."""
    present = degree > 0
    number = jnp.cumsum(present) - 1          # base id -> kept vertex
    kept = jnp.nonzero(present, size=num_vertices)[0]
    relabel = _relabel(degree[kept],
                       jax.random.bits(seed_key, (num_vertices,), jnp.uint32))
    u = relabel[number[lo[:num_edges]]]
    v = relabel[number[hi[:num_edges]]]
    return jnp.concatenate([u, v]), jnp.concatenate([v, u]), relabel


@functools.partial(jax.jit, static_argnames=("num_edges",))
def _weights(weight_key, *, num_edges):
    """One weight per kept edge, given to both of its arcs (as ``_arcs``
    orders them)."""
    w = jax.random.uniform(weight_key, (num_edges,), jnp.float32)
    return jnp.concatenate([w, w])


def generate(scale: int, edge_factor: int, graph_seed: int, seed: int,
             a: float = 0.57, b: float = 0.19, c: float = 0.19,
             weights: Optional[str] = None) -> Graph:
    """Make the configuration's graph (``graph_seed``) relabelled by ``seed``
    on the default device, with ``weights`` as :data:`WEIGHTS` names them,
    and copy it to the host."""
    if weights not in WEIGHTS:
        raise ValueError(f"unknown edge weights {weights!r}; known: "
                         f"{WEIGHTS}")
    nv = 1 << scale
    ne = edge_factor * nv
    chunk = min(CHUNK_EDGES, ne)
    if ne % chunk:
        raise ValueError(f"{ne} edges do not split into chunks of {chunk}")
    graph_key = prng_key(graph_seed)
    scramble = jax.random.permutation(jax.random.fold_in(graph_key, 1 << 31),
                                      nv).astype(jnp.int32)
    parts = [_draws(graph_key, k, scramble, scale=scale, chunk=chunk,
                    a=a, b=b, c=c) for k in range(ne // chunk)]
    lo, hi, count, degree = _simple(parts, num_vertices=nv)
    del parts
    num_vertices = int(jnp.count_nonzero(degree))
    src, dst, relabel = _arcs(lo, hi, degree, prng_key(seed),
                              num_edges=int(count), num_vertices=num_vertices)
    del lo, hi
    weight = None
    if weights is not None:
        weight_key = jax.random.fold_in(graph_key, WEIGHT_TAG)
        weight = np.asarray(_weights(weight_key, num_edges=int(count)))
    return Graph(num_vertices, np.asarray(src), np.asarray(dst),
                 np.asarray(relabel), weight)


def of_config(graph: dict, seed: int) -> Graph:
    """The graph of a configuration file's ``graph`` entry, relabelled by
    ``seed``."""
    return generate(graph["scale"], graph["edge_factor"], graph["seed"], seed,
                    graph["a"], graph["b"], graph["c"], graph.get("weights"))
