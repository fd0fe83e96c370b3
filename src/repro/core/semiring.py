"""Semirings for vertex-centric pull-mode updates (Algorithm 3, vectorized).

A GraphMP ``Update`` function factors into three pieces:

  partial[v] = REDUCE_{(u,v) in shard}  COMBINE(edge_val(u,v), src[u])
  dst[v]     = POST(partial[v], old[v], aux)

PageRank : REDUCE=+,   COMBINE=(w, s) -> s            POST = 0.15/n + 0.85*p
SSSP     : REDUCE=min, COMBINE=(w, s) -> s + w        POST = min(p, old)
CC       : REDUCE=min, COMBINE=(w, s) -> s            POST = min(p, old)
BFS      : REDUCE=min, COMBINE=(w, s) -> s + 1        POST = min(p, old)
LP       : REDUCE=max, COMBINE=(w, s) -> s            POST = max(p, old)

The semiring is the device-side contract shared by the pure-jnp reference
(`kernels/spmv/ref.py`), the Pallas kernels (`kernels/spmv/spmv.py`) and the
VSW engine.  ``identity`` is the REDUCE identity and is what padded (sentinel)
ELL slots must contribute.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class Semiring:
    name: str
    # reduce(a, b) -> elementwise monoid used to fold a virtual row
    reduce: Callable[[Array, Array], Array]
    # combine(edge_val, src_val) -> contribution of one edge
    combine: Callable[[Array, Array], Array]
    # identity element of `reduce` (what masked slots contribute)
    identity: float
    # whether `reduce` is `+` (enables the one-hot MXU SpMV variant)
    is_plus: bool = False
    # whether `reduce` is `max` (the non-plus default is `min`, the
    # propagation direction of sssp/bfs/cc; label propagation flips it)
    is_max: bool = False

    def fold(self, edge_vals: Array, src_vals: Array, mask: Array, axis: int = -1) -> Array:
        """Reduce COMBINE(edge, src) over `axis`, treating ~mask as identity."""
        contrib = self.combine(edge_vals, src_vals)
        contrib = jnp.where(mask, contrib, jnp.asarray(self.identity, contrib.dtype))
        if self.is_plus:
            return jnp.sum(contrib, axis=axis)
        if self.is_max:
            return jnp.max(contrib, axis=axis)
        return jnp.min(contrib, axis=axis)


PLUS_TIMES = Semiring(
    name="plus_times",
    reduce=jnp.add,
    combine=lambda w, s: w * s,
    identity=0.0,
    is_plus=True,
)

# PageRank pulls src/out_deg along in-edges; the division is folded into the
# gather-transform, so on the shard the combine is just "take the source".
PLUS_SRC = Semiring(
    name="plus_src",
    reduce=jnp.add,
    combine=lambda w, s: s,
    identity=0.0,
    is_plus=True,
)

MIN_PLUS = Semiring(
    name="min_plus",
    reduce=jnp.minimum,
    combine=lambda w, s: w + s,
    identity=float("inf"),
)

MIN_SRC = Semiring(
    name="min_src",
    reduce=jnp.minimum,
    combine=lambda w, s: s,
    identity=float("inf"),
)

# Label propagation pulls the neighbor's label and keeps the largest; -inf is
# the identity so sentinel ELL slots (and vertices with no in-edges) never win.
MAX_SRC = Semiring(
    name="max_src",
    reduce=jnp.maximum,
    combine=lambda w, s: s,
    identity=float("-inf"),
    is_max=True,
)

SEMIRINGS = {s.name: s for s in (PLUS_TIMES, PLUS_SRC, MIN_PLUS, MIN_SRC, MAX_SRC)}
