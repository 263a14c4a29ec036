"""The weights a serving engine holds: each leaf in the dtype its served
programs round it to.

A GPT computes in ``cfg.dtype`` (bfloat16) from float32 parameters:
every dense kernel and bias is converted to bfloat16 inside the program,
before its product (``nn.Dense(dtype=..., param_dtype=float32)``).  A
decode tick or prefill wave that is handed the float32 tree therefore
reads 4 bytes a weight to use 2.  Holding the rounded copy changes no
bit any program computes: the conversion is the same rounding whether it
runs inside the program or once, here, and a conversion to a leaf's own
dtype is no operation at all.

Which leaves each layer kind rounds is declared beside the layers, in
``models/gpt.ROUNDED_PARAMS`` (``served_param_dtypes``); the tier-1
tests hold the declaration to the serving forward's own conversions and
every program kind to the same bits from the held tree as from the
given one.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import numpy as np


def held_avals(params, dtypes):
    """``params``' shapes in the dtypes they are held in."""
    return jax.tree_util.tree_map(
        lambda x, d: jax.ShapeDtypeStruct(np.shape(x), d), params, dtypes
    )


@functools.partial(jax.jit, static_argnums=1)
def hold_weights(leaves, dtypes):
    return [x.astype(d) for x, d in zip(leaves, dtypes)]


def hold(params, dtypes, device):
    """``params`` on ``device``, each leaf in its held dtype: the leaves
    to narrow are rounded on the device, by the conversion their
    programs would apply, in one program (``jit_hold_weights``; one
    compile a stage's shapes, kept by the persistent compile cache)."""
    leaves, tree = jax.tree_util.tree_flatten(jax.device_put(params, device))
    want = jax.tree_util.tree_leaves(dtypes)
    narrow = [i for i, (x, d) in enumerate(zip(leaves, want))
              if x.dtype != d]
    if narrow:
        rounded = hold_weights([leaves[i] for i in narrow],
                               tuple(want[i] for i in narrow))
        for i, x in zip(narrow, rounded):
            leaves[i] = x
    return jax.tree_util.tree_unflatten(tree, leaves)


def weight_bytes(params_list, held_list) -> Tuple[int, int]:
    """(bytes held, bytes of the leaves held narrower than given) over
    parallel lists of given and held trees (arrays or avals)."""
    served = narrowed = 0
    for given, held in zip(params_list, held_list):
        for x, y in zip(jax.tree_util.tree_leaves(given),
                        jax.tree_util.tree_leaves(held)):
            n = int(np.prod(np.shape(y))) * np.dtype(y.dtype).itemsize
            served += n
            narrowed += n if np.dtype(x.dtype) != np.dtype(y.dtype) else 0
    return served, narrowed


__all__ = ["hold", "held_avals", "weight_bytes"]
