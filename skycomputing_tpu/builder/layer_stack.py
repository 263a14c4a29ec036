"""LayerStack: the tuple-threading sequential container.

Functional analog of the reference's ``SequentialWrapper``
(``scaelum/builder/sequential_wrapper.py:8-20``): a chain of layer modules
where each layer consumes the *tuple* of outputs of the previous one (BERT
units pass ``(hidden, mask, ...)`` tuples).  Because JAX separates modules
from parameters, the stack holds linen module instances and threads a
*list of per-layer param pytrees* alongside the data tuple.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax


def as_tuple(x) -> Tuple:
    return x if isinstance(x, tuple) else (x,)


class LayerStack:
    """An ordered chain of linen modules with tuple-threading semantics."""

    def __init__(self, modules: Sequence[Any]):
        self.modules = list(modules)

    def __len__(self) -> int:
        return len(self.modules)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerStack(self.modules[idx])
        return self.modules[idx]

    def init(self, rng: jax.Array, *inputs) -> List[Any]:
        """Initialize every layer by threading example inputs through.

        Returns a list of per-layer param pytrees (each the layer's full
        variable dict ``{'params': ...}`` collapsed to its ``params`` tree).
        """
        params_list = []
        data = tuple(inputs)
        for i, module in enumerate(self.modules):
            layer_rng, dropout_rng, rng = jax.random.split(
                jax.random.fold_in(rng, i), 3
            )
            variables = module.init(
                {"params": layer_rng, "dropout": dropout_rng}, *data
            )
            params_list.append(variables["params"])
            data = as_tuple(
                module.apply(
                    {"params": variables["params"]},
                    *data,
                    rngs={"dropout": dropout_rng},
                )
            )
        return params_list

    def apply(
        self,
        params_list: Sequence[Any],
        *inputs,
        dropout_rng: Optional[jax.Array] = None,
        counters: bool = False,
    ):
        """Forward the tuple of inputs through every layer.

        Returns the final layer's raw output (tensor or tuple), matching the
        reference where the last stage's output lands in the loss.  With
        ``counters=True`` returns ``(output, [what each layer sowed into its
        "counters" collection])``: an auxiliary output beside the data (a
        layer that sows nothing gives ``{}``).
        """
        if len(params_list) != len(self.modules):
            raise ValueError(
                f"got {len(params_list)} param trees for {len(self.modules)} layers"
            )
        data = tuple(inputs)
        out = data if len(data) > 1 else data[0]
        sown = []
        for i, (module, params) in enumerate(zip(self.modules, params_list)):
            rngs = None
            if dropout_rng is not None:
                rngs = {"dropout": jax.random.fold_in(dropout_rng, i)}
            if counters:
                out, state = module.apply({"params": params}, *data,
                                          rngs=rngs, mutable=["counters"])
                sown.append(dict(state.get("counters", {})))
            else:
                out = module.apply({"params": params}, *data, rngs=rngs)
            data = as_tuple(out)
        return (out, sown) if counters else out

__all__ = ["LayerStack", "as_tuple"]
