"""Between the reference's layout (one leaf per kind of weight, the layers
stacked) and the program's (one named parameter per layer). A family gives
``NAMES``: reference leaf -> the program's name, ``{i}`` for the layer."""
from __future__ import annotations

import jax.numpy as jnp


def to_program(names: dict, specs: dict, params: dict) -> dict:
    out = {}
    for leaf, template in names.items():
        if specs[leaf][2]:
            for i in range(specs[leaf][0][0]):
                out[template.format(i=i)] = params[leaf][i]
        else:
            out[template] = params[leaf]
    return out


def from_program(names: dict, specs: dict, named: dict, leaf: str):
    """One reference leaf, gathered from the program's named arrays."""
    template = names[leaf]
    if not specs[leaf][2]:
        return named[template]
    return jnp.stack([named[template.format(i=i)]
                      for i in range(specs[leaf][0][0])])
