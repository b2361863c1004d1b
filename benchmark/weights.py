"""Weights made from ``--seed``, the same for the program and for the
plain reference.

The benchmark makes the weights itself: one jitted call on the device,
from the seed, in the dtype the leaf is stored in. The program's own
initialiser still runs (it is part of the set-up a user pays) and its
parameter tree gives the *shapes*; the values are replaced by these
before the first step (``hooks.py``), and the reference gets the very
same values from the same function without touching the program.

A leaf is named by its path in the parameter tree, keys joined with
``/`` (``BasicBlock_0/Conv_0/kernel``). The rule for a leaf depends on
its last key only:

- ``scale``  -> 1 + 0.1 * normal   (norm gains; never the zero-init the
  program gives a block's last BatchNorm, so every leaf has a gradient)
- ``bias``   -> 0.1 * normal
- anything else -> 0.02 * normal   (kernels, embeddings, positions)
"""

import zlib


def seed_key(seed: int):
    """A PRNG key from any whole number a little over 2**31."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def leaf_key(key, path: str):
    import jax
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def leaf_value(key, path: str, shape, dtype):
    import jax
    import jax.numpy as jnp
    noise = jax.random.normal(leaf_key(key, path), shape, jnp.float32)
    last = path.rsplit('/', 1)[-1]
    if last == 'scale':
        value = 1.0 + 0.1 * noise
    elif last == 'bias':
        value = 0.1 * noise
    else:
        value = 0.02 * noise
    return value.astype(dtype)


def make_params(seed: int, spec: dict, shardings: dict = None) -> dict:
    """``{path: array}`` for ``spec = {path: (shape, dtype)}`` in ONE
    jitted call. ``shardings`` ({path: sharding}) places each leaf
    where the program keeps it."""
    import jax
    paths = sorted(spec)

    def make(key):
        return {p: leaf_value(key, p, tuple(spec[p][0]), spec[p][1])
                for p in paths}

    kwargs = {}
    if shardings is not None:
        kwargs['out_shardings'] = {p: shardings[p] for p in paths}
    with jax.threefry_partitionable(True):
        return jax.jit(make, **kwargs)(seed_key(seed))


# --------------------------------------------------- the program's tree
def flat_paths(tree):
    """[(path, leaf)] of a flax parameter tree, boxed leaves
    (``nn.Partitioned``) named by their dict keys alone."""
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(k.key) for k in path
                if isinstance(k, jax.tree_util.DictKey)]
        out.append(('/'.join(keys), leaf))
    return out


def tree_spec(tree) -> dict:
    return {p: (tuple(leaf.shape), leaf.dtype)
            for p, leaf in flat_paths(tree)}


def replace_leaves(tree, values: dict):
    """``tree`` with every leaf replaced by ``values[path]``."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    paths = [p for p, _ in flat_paths(tree)]
    assert len(paths) == len(leaves)
    return jax.tree_util.tree_unflatten(
        treedef, [values[p] for p in paths])
