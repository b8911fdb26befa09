"""Seeded weights, made on the device from ``--seed``.

A configuration's reference module lists its tensors (``leaves``): a name
per tensor in the served program's layout (``attn/wq``, ``ffn/w_up``,
``ffn/shared/w_down``, ...), its shape, its type as served and its init.
Each tensor is drawn from a key that depends only on the seed, the layer
and the name, so the program's set-up (one jitted call for the whole
model) and the reference (one layer at a time, after the window) draw the
same numbers without sharing any array.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Spec = Dict[str, Tuple[tuple, str, object]]   # name -> (shape, dtype, init)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits and beyond."""
    k = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return k


def leaf(key, layer: int, name: str, shape, dtype, init) -> jax.Array:
    """One tensor: ``"ones"``, ``"zeros"``, or normal with standard
    deviation ``init``."""
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           zlib.crc32(name.encode()))
    return (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)


def layer_tensors(key, layer: int, spec: Spec) -> Dict[str, jax.Array]:
    return {n: leaf(key, layer, n, s, d, i) for n, (s, d, i) in spec.items()}


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for name, v in flat.items():
        node = out
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def _stack_groups(layers: List[Spec], first_dense: int,
                 period: int) -> List[List[int]]:
    """The layer indices of each ``sub_j`` of the program's stack: layer
    ``first_dense + b*period + j`` is block ``b`` of ``sub_j``.  Layers of
    one ``sub_j`` must have the same names, shapes and types."""
    rest = len(layers) - first_dense
    if rest < 0 or rest % period:
        raise ValueError(f"{len(layers)} layers do not fill {first_dense} "
                         f"prefix layers and blocks of {period}")
    groups = [list(range(first_dense + j, len(layers), period))
              for j in range(period)] if rest else []
    kinds = [{n: (tuple(s), d) for n, (s, d, _) in spec.items()}
             for spec in layers]
    for l in range(first_dense, len(layers)):
        j = (l - first_dense) % period
        first = groups[j][0]
        a, b = kinds[first], kinds[l]
        if a != b:
            diff = sorted(n for n in a.keys() | b.keys()
                          if a.get(n) != b.get(n))
            raise ValueError(f"layer {l} differs from layer {first}, which "
                             f"shares sub_{j} with it, in {diff}")
    return groups


def program_params(seed: int, glob: Spec, layers: List[Spec],
                   first_dense: int, period: int = 1):
    """The whole model in the program's pytree, in one jitted call:
    ``{"embed", "final_norm", "lm_head", "decoder": {"prefix": [dense
    layers], "stack": {"sub_j": layers first_dense + b*period + j stacked
    over b on a leading axis}}}``, as ``transformer.stack_layout`` lays
    out a period of ``period`` layer kinds."""
    groups = _stack_groups(layers, first_dense, period)

    def make(key):
        p = _nest({n: leaf(key, -1, n, s, d, i)
                   for n, (s, d, i) in glob.items()})
        per = [layer_tensors(key, l, spec) for l, spec in enumerate(layers)]
        p["decoder"] = {
            "prefix": [_nest(t) for t in per[:first_dense]],
            "stack": ({f"sub_{j}": _nest({n: jnp.stack([per[l][n] for l in ls])
                                          for n in per[ls[0]]})
                       for j, ls in enumerate(groups)}
                      if groups else None)}
        return p

    return jax.jit(make)(seed_key(seed))


def reference_layer(seed: int, layer: int, spec: Spec) -> Dict[str, jax.Array]:
    """One layer's tensors as float32, for the reference."""
    f = jax.jit(lambda k: {n: v.astype(jnp.float32) for n, v in
                           layer_tensors(k, layer, spec).items()})
    return f(seed_key(seed))


def reference_globals(seed: int, glob: Spec) -> Dict[str, jax.Array]:
    return reference_layer(seed, -1, glob)
