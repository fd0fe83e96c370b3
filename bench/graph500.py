"""Graph500 Kronecker edge generator (specification, kernel 1 input), on the device.

Follows the specification's reference generator: for each of ``scale`` bit
levels every edge picks a quadrant of the adjacency matrix with
probabilities a, b, c, d (row bit first, then the column bit conditioned on
it), then the vertex labels go through one random permutation and the edge
list through another.  Duplicates and self loops stay, as generated.  The
specification's graph is undirected, so each generated edge {u, v} becomes
the two arcs u->v and v->u (a self loop twice, as it counts twice in its
vertex's degree): the pull-mode engine and the reference both read arcs.
A weighted configuration (``"weighted": true``, Graph500 kernel 3) gives
each generated edge a float32 weight uniform in [0, 1), drawn from a key
the arcs do not use, so its arcs are the unweighted graph's; both arcs of
an edge carry its weight.

The edge shuffle's key always comes from ``--seed``.  The edges, the
vertex permutation and the weights come from ``--seed`` too, unless the
configuration fixes them with ``"graph_seed"``: then every ``--seed`` gives
the same graph, shards and weights, its edge list in another order.

Everything is made in one jitted call; only the finished arrays come back
to the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed: ``jax.random.key`` keeps only
    the low 32 bits of a seed past 2**32, so the high bits are folded in."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("scale", "m", "a", "b", "c"))
def quadrant_bits(key, *, scale: int, m: int, a: float, b: float, c: float):
    """Unpermuted (row, column) ids of ``m`` edges: bit i of each is the
    quadrant drawn at level i."""
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)

    def level(i, carry):
        ii, jj = carry
        k_row, k_col = jax.random.split(jax.random.fold_in(key, i))
        ii_bit = jax.random.uniform(k_row, (m,)) > ab
        jj_bit = jax.random.uniform(k_col, (m,)) > jnp.where(
            ii_bit, c_norm, a_norm)
        return (ii | (ii_bit.astype(jnp.int32) << i),
                jj | (jj_bit.astype(jnp.int32) << i))

    zeros = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zeros, zeros))


# the fold_in tag of the weights' key, which the arcs' keys do not use: under
# threefry, fold_in(key, t) encrypts the counter pair (0, t) and the arcs'
# split(key, 3) the pairs (0, 3), (1, 4), (2, 5)
_WEIGHT_STREAM = 0xB0A7


@functools.partial(jax.jit,
                   static_argnames=("scale", "m", "a", "b", "c", "weighted"))
def _kronecker(key, order_key, *, scale, m, a, b, c, weighted=False):
    """The arcs (and weights) of the graph ``key`` draws, in the edge order
    ``order_key`` draws."""
    k_bits, k_vperm, _ = jax.random.split(key, 3)
    ii, jj = quadrant_bits(k_bits, scale=scale, m=m, a=a, b=b, c=c)
    vperm = jax.random.permutation(k_vperm, 1 << scale).astype(jnp.int32)
    eperm = jax.random.permutation(order_key, m)
    src, dst = vperm[ii][eperm], vperm[jj][eperm]
    arcs = (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]))
    if not weighted:
        return arcs
    # one weight per generated edge, moved with it by the shuffle
    w = jax.random.uniform(jax.random.fold_in(key, _WEIGHT_STREAM), (m,),
                           jnp.float32)[eperm]
    return arcs + (jnp.concatenate([w, w]),)


def _keys(seed: int, graph_seed: int | None):
    """(the graph's key, the edge shuffle's key): the shuffle's is always
    ``seed``'s, the graph's ``graph_seed``'s where one is given."""
    key = seed_key(seed)
    order_key = jax.random.split(key, 3)[2]
    return (key if graph_seed is None else seed_key(graph_seed)), order_key


def kronecker_edges(seed: int, scale: int, edge_factor: int, a: float,
                    b: float, c: float, graph_seed: int | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) int32 host arrays of the 2m arcs of the ``m = edge_factor
    * 2**scale`` generated edges: the edges as generated, then each
    reversed."""
    src, dst = _kronecker(*_keys(seed, graph_seed), scale=scale,
                          m=edge_factor << scale, a=float(a), b=float(b),
                          c=float(c))
    return np.asarray(src), np.asarray(dst)


def config_arcs(config: dict, seed: int
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """(src, dst, weights or None) of a configuration's graph
    (``bench/configs``): weights with ``"weighted": true``, the same graph
    for every seed with ``"graph_seed"``."""
    if config["directed"]:
        raise ValueError("the Graph500 graph is undirected; a configuration "
                         "cannot state \"directed\": true")
    scale = config["scale"]
    out = _kronecker(*_keys(seed, config.get("graph_seed")), scale=scale,
                     m=config["edge_factor"] << scale, a=float(config["a"]),
                     b=float(config["b"]), c=float(config["c"]),
                     weighted=bool(config["weighted"]))
    src, dst = np.asarray(out[0]), np.asarray(out[1])
    return src, dst, (np.asarray(out[2]) if config["weighted"] else None)
