"""Communication backends for the fault-tolerant butterfly collectives.

The engine in :mod:`repro.collective.engine` (and therefore every consumer:
TSQR, ``ft_allreduce``, PowerSGD orthogonalization) is written once against
this small interface and executes on either backend:

  * :class:`ShardMapComm` — the production path: SPMD inside
    ``shard_map``, exchanges are ``lax.ppermute`` (XLA
    ``collective-permute`` on ICI).  Per-rank values are scalars / local
    blocks.
  * :class:`SimComm` — a single-device simulation where every per-rank value
    carries a leading ``(P,)`` axis and exchanges are gathers.  This is what
    the CPU test-suite and the hypothesis robustness sweeps run on: it is
    bit-identical in algorithm structure (same plans, same combine order)
    but needs no multi-device runtime.

Both backends fill non-receiving ranks with zeros, matching XLA
``collective-permute`` semantics (a rank absent from the permutation's
destination list receives zeros — the moral equivalent of ULFM's error
return, which the validity bits then adjudicate).

``exchange`` maps over pytrees, so the engine can route whole gradient
trees (the trainer's BLANK-mode all-reduce) as easily as a single R factor.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import dispatch as _dispatch

__all__ = ["Comm", "SimComm", "ShardMapComm"]

Pair = tuple[int, int]


class Comm:
    """Interface: per-rank SPMD values or (P,)-leading simulated values."""

    n_ranks: int

    def ranks(self):  # rank id: scalar (SPMD) or (P,) vector (sim)
        raise NotImplementedError

    def take(self, host_vec):  # per-rank slice of a host (P,) vector
        raise NotImplementedError

    def exchange(self, x, perm: Sequence[Pair]):
        """Permute per-rank payloads; non-receivers get zeros."""
        raise NotImplementedError

    def bwhere(self, cond, a, b):
        """`where` with a per-rank scalar condition, broadcast over payload."""
        raise NotImplementedError

    def leaf_nbytes(self, leaf) -> int:
        """Per-rank wire bytes of one payload leaf — the hook the
        per-round byte counters (:mod:`repro.collective.instrument`) use.
        Backends differ: a ``SimComm`` leaf carries the whole (P,)-leading
        array, a ``ShardMapComm`` leaf is already the local block."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class SimComm(Comm):
    """Single-device simulation: leading (P,) axis on every per-rank value."""

    n_ranks: int

    def ranks(self):
        return jnp.arange(self.n_ranks)

    def take(self, host_vec):
        arr = jnp.asarray(host_vec)
        assert arr.shape[0] == self.n_ranks
        return arr

    def exchange(self, x, perm: Sequence[Pair]):
        def go(leaf):
            out = jnp.zeros_like(leaf)
            if not perm:
                return out
            src = jnp.array([s for s, _ in perm], dtype=jnp.int32)
            dst = jnp.array([d for _, d in perm], dtype=jnp.int32)
            return out.at[dst].set(leaf[src])

        with _dispatch.span(_dispatch.EXCHANGE, messages=len(perm)):
            return jax.tree.map(go, x)

    def bwhere(self, cond, a, b):
        a, b = jnp.broadcast_arrays(a, b)
        extra = a.ndim - cond.ndim
        return jnp.where(cond.reshape(cond.shape + (1,) * extra), a, b)

    def leaf_nbytes(self, leaf) -> int:
        # leading (P,) axis: one rank's slice is 1/P of the array
        return int(np.prod(leaf.shape[1:], dtype=np.int64)) * leaf.dtype.itemsize


@dataclasses.dataclass(frozen=True)
class ShardMapComm(Comm):
    """SPMD inside ``shard_map``: exchanges lower to ``collective-permute``."""

    n_ranks: int
    axis: str

    def ranks(self):
        return lax.axis_index(self.axis)

    def take(self, host_vec):
        arr = jnp.asarray(np.asarray(host_vec))
        assert arr.shape[0] == self.n_ranks
        return arr[lax.axis_index(self.axis)]

    def exchange(self, x, perm: Sequence[Pair]):
        def go(leaf):
            if not perm:
                return jnp.zeros_like(leaf)
            return lax.ppermute(leaf, self.axis, [tuple(p) for p in perm])

        with _dispatch.span(_dispatch.EXCHANGE, messages=len(perm)):
            return jax.tree.map(go, x)

    def bwhere(self, cond, a, b):
        return jnp.where(cond, a, b)

    def leaf_nbytes(self, leaf) -> int:
        # SPMD: the leaf is already one rank's local block
        return int(np.prod(leaf.shape, dtype=np.int64)) * leaf.dtype.itemsize
