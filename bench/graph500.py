"""Graph500 Kronecker edge generator (specification, kernel 1 input), on the device.

Follows the specification's reference generator: for each of ``scale`` bit
levels every edge picks a quadrant of the adjacency matrix with
probabilities a, b, c, d (row bit first, then the column bit conditioned on
it), then the vertex labels go through one random permutation and the edge
list through another.  Duplicates and self loops stay, as generated.  The
specification's graph is undirected, so each generated edge {u, v} becomes
the two arcs u->v and v->u (a self loop twice, as it counts twice in its
vertex's degree): the pull-mode engine and the reference both read arcs.

Everything runs in one jitted call from ``--seed``; only the two finished
int32 arrays come back to the host.
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


@functools.partial(jax.jit, static_argnames=("scale", "m", "a", "b", "c"))
def _kronecker(key, *, scale, m, a, b, c):
    k_bits, k_vperm, k_eperm = jax.random.split(key, 3)
    ii, jj = quadrant_bits(k_bits, scale=scale, m=m, a=a, b=b, c=c)
    vperm = jax.random.permutation(k_vperm, 1 << scale).astype(jnp.int32)
    eperm = jax.random.permutation(k_eperm, m)
    src, dst = vperm[ii][eperm], vperm[jj][eperm]
    return jnp.concatenate([src, dst]), jnp.concatenate([dst, src])


def kronecker_edges(seed: int, scale: int, edge_factor: int, a: float,
                    b: float, c: float) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) int32 host arrays of the 2m arcs of the ``m = edge_factor
    * 2**scale`` generated edges: the edges as generated, then each
    reversed."""
    src, dst = _kronecker(seed_key(seed), scale=scale, m=edge_factor << scale,
                          a=float(a), b=float(b), c=float(c))
    return np.asarray(src), np.asarray(dst)


def config_arcs(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The arcs of a configuration's graph (``bench/configs``)."""
    if config["directed"]:
        raise ValueError("the Graph500 graph is undirected; a configuration "
                         "cannot state \"directed\": true")
    return kronecker_edges(seed, config["scale"], config["edge_factor"],
                           config["a"], config["b"], config["c"])
