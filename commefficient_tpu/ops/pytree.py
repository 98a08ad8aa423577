"""Flatten model parameter pytrees to the single contiguous fp32 vector the
framework operates on, and back.

Reference equivalent: CommEfficient/utils.py:261-297 (`get_param_vec` /
`set_param_vec` / `get_grad_vec`), which loop over ``model.parameters()`` and
``torch.cat`` the pieces. In JAX the canonical tool is
``jax.flatten_util.ravel_pytree``; the unravel closure it returns is traceable,
so flatten/unflatten happen *inside* the jitted round step with no host trips
(the reference pays a host↔device copy per round, fed_worker.py:41).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


def ravel_params(params: Any, pad_to: Optional[int] = None
                 ) -> Tuple[jax.Array, Callable[[jax.Array], Any]]:
    """Return (flat fp32 vector, unravel closure); the vector is a new
    array, never a leaf of ``params``. With ``pad_to`` the
    vector comes at that length, zeros past the last leaf, out of the
    ravel's own ``concatenate``: no unpadded vector exists beside it.
    ``unravel`` takes the unpadded length either way."""
    leaves = jax.tree_util.tree_leaves(params)
    n_tail = 0 if pad_to is None else pad_to - sum(l.size for l in leaves)
    if n_tail == 0:
        # the plain ravel's executable, not a second one with an empty
        # operand
        flat, unravel = ravel_pytree(params)
    else:
        # in the leaves' own promoted dtype, so the first d elements are
        # the unpadded ravel to the bit
        tail = jnp.zeros((n_tail,), jnp.result_type(*leaves))
        flat = ravel_pytree((params, tail))[0]
        unravel = make_unraveler(params)[1]
    flat = flat.astype(jnp.float32)
    if any(flat is leaf for leaf in leaves):
        # a tree of one flat fp32 leaf ravels to that leaf itself, and a
        # round that donates the vector would delete the caller's leaf
        flat = jnp.copy(flat)
    return flat, unravel


def make_unraveler(params: Any) -> Tuple[int, Callable[[jax.Array], Any]]:
    """Return (grad_size, unravel closure) for a parameter pytree, from
    its shapes alone: nothing is allocated."""
    unravel = []

    def flat(tree):
        vec, fn = ravel_pytree(tree)
        # the closure holds sizes, shapes and dtypes, no traced value
        unravel.append(fn)
        return vec

    return int(jax.eval_shape(flat, params).size), unravel[0]
