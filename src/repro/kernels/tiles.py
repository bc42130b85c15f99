"""In-kernel helpers shared by the Pallas kernels.

Mosaic refuses a plain reshape between a [1, n] row (one value per lane)
and an [n, 1] column (one value per sublane). Per-row statistics such as
flash attention's lse/delta and the GLA scan's gate cumsums travel in HBM
as rows or columns, so the kernels convert them in VMEM with these exact
diagonal moves: broadcast, keep the diagonal, reduce (one value plus
zeros).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _diag(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) ==
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def row_to_col(row):
    """[1, n] -> [n, 1]: keep the diagonal of the sublane-broadcast tile
    and reduce across lanes."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_diag(n), row, 0.0), axis=1, keepdims=True)


def col_to_row(col):
    """[n, 1] -> [1, n], the inverse of :func:`row_to_col`."""
    n = col.shape[0]
    return jnp.sum(jnp.where(_diag(n), col, 0.0), axis=0, keepdims=True)
