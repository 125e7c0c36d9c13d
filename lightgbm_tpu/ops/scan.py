"""Prefix scans of long vectors in blocks: a scan inside each block, then
one short scan over the blocks' totals. A single scan over the whole
length is slow on the TPU (it lowers to a reduce-window of that length);
two short ones are not (PERF.md section 6, PR 35)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_SCANS = {"sum": (jax.lax.cumsum, jnp.add),
          "max": (jax.lax.cummax, jnp.maximum),
          "min": (jax.lax.cummin, jnp.minimum)}


def _identity(op: str, dtype) -> np.generic:
    if op == "sum":
        return np.zeros((), dtype)[()]
    big = (np.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
           else np.inf)
    return np.asarray(-big if op == "max" else big, dtype)[()]


def blocked_scan(x: jax.Array, op: str = "sum", block: int = 2048,
                 reverse: bool = False) -> jax.Array:
    """Inclusive prefix ``op`` (``sum``, ``max`` or ``min``) of the 1-D
    ``x``, from the end where ``reverse``."""
    scan, combine = _SCANS[op]
    n = x.shape[0]
    ident = _identity(op, x.dtype)
    xb = jnp.pad(x, (0, -n % block),
                 constant_values=ident).reshape(-1, block)
    inner = scan(xb, axis=1, reverse=reverse)
    whole = scan(inner[:, 0] if reverse else inner[:, -1], axis=0,
                 reverse=reverse)
    # what the blocks before (after, in reverse) hand each block
    fill = jnp.full((1,), ident, x.dtype)
    offs = (jnp.concatenate([whole[1:], fill]) if reverse
            else jnp.concatenate([fill, whole[:-1]]))
    return combine(inner, offs[:, None]).reshape(-1)[:n]
