"""The one traffic generator: a cell's ``data`` parameters and the seed
in, the files the program's registered datasets read out.

Two kinds of rows, chosen by ``data.kind`` in the cell's file:

- ``images``: ``train_rows`` + ``valid_rows`` uint8 images of
  ``image_size``² x ``channels`` in ``num_classes`` classes (a class
  prototype plus noise, so every row differs), written in the layout
  the program's ``cifar10`` dataset loads from ``path``;
- ``tokens``: ``train_rows`` + ``valid_rows`` sequences of ``seq_len``
  ids drawn over the whole ``vocab_size``, written for the program's
  ``npz`` dataset (``x``, a ``y`` it wants and ``lm_ce`` ignores) with
  a fold file that makes exactly the last ``valid_rows`` the
  validation split.

The same seed gives the same bytes; every seed gives the same sizes,
so the seed changes the values and never the work.
"""

import os

import numpy as np


def _rng(seed: int, stream: int):
    return np.random.Generator(np.random.PCG64([int(seed), int(stream)]))


def make_images(data: dict, seed: int):
    size, ch = int(data['image_size']), int(data['channels'])
    classes = int(data['num_classes'])
    protos = _rng(seed, 0).integers(
        64, 192, (classes, size, size, ch), dtype=np.int16)

    def rows(n, stream):
        r = _rng(seed, stream)
        y = r.integers(0, classes, n, dtype=np.int32)
        noise = r.integers(-64, 64, (n, size, size, ch), dtype=np.int16)
        return (protos[y] + noise).astype(np.uint8), y

    x_train, y_train = rows(int(data['train_rows']), 1)
    x_valid, y_valid = rows(int(data['valid_rows']), 2)
    return {'x_train': x_train, 'y_train': y_train,
            'x_test': x_valid, 'y_test': y_valid}


def make_tokens(data: dict, seed: int):
    n = int(data['train_rows']) + int(data['valid_rows'])
    x = _rng(seed, 0).integers(
        0, int(data['vocab_size']), (n, int(data['seq_len'])),
        dtype=np.int32)
    folds = np.ones(n, np.int32)
    folds[int(data['train_rows']):] = 0      # fold 0 is validation
    return {'x': x, 'y': np.zeros(n, np.int32)}, folds


def write(data: dict, seed: int, folder: str) -> dict:
    """Write the rows under ``folder`` and return the ``dataset:`` spec
    of the ``jax_train`` executor that reads them."""
    os.makedirs(folder, exist_ok=True)
    kind = data['kind']
    path = os.path.join(folder, f'{kind}.npz')
    if kind == 'images':
        np.savez(path, **make_images(data, seed))
        return {'name': 'cifar10', 'path': path,
                'n_train': int(data['train_rows']),
                'n_valid': int(data['valid_rows'])}
    if kind == 'tokens':
        arrays, folds = make_tokens(data, seed)
        np.savez(path, **arrays)
        fold_path = os.path.join(folder, 'folds.npy')
        np.save(fold_path, folds)
        return {'name': 'npz', 'path': path, 'fold_path': fold_path,
                'fold': 0}
    raise ValueError(f'unknown data kind {kind!r}')
