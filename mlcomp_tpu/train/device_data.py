"""Device-resident dataset path: the TPU-native input pipeline for
datasets that fit in HBM.

Put the WHOLE dataset in HBM once (CIFAR-10 as uint8 = 150 MB vs 16 GB
HBM); each step then ships only a [B] int32 index vector (8 KB at batch
2,048, against a 6 MB batch) and does the batch gather, augmentation
and dequantization ON DEVICE inside the jitted step. On the v5e trace
(PERF.md) the host's share of a 60 ms ResNet-18 step at batch 2,048 is
0.3 ms; how much of a per-step batch transfer a prefetch would have
hidden is not measured.

The set is held FLAT, ``[N, prod(row_shape)]`` (``place_dataset``), and
only the gathered batch is given its row shape back
(``gather_rows``). The chip's default layout for a resident
``uint8[N,32,32,3]`` puts N minor-most, and the compiler answered the
row gather by copying all N rows into a row-major layout in EVERY
step: 3.8 ms of a 63.5 ms step whatever the batch, 0.9 ms of each
validation step. From ``uint8[N,3072]`` the gather reads the rows where
they lie (0.07 ms for 2,048 of them) and the step holds no other
operation over the set.

The on-device augmentations mirror contrib/transform/numpy_aug.py's
pad-crop/flip/cutout semantics, expressed as vectorized lax ops under
``jax.random`` so they trace once and shard over dp.
"""

from typing import Optional, Sequence

import numpy as np


def quantize_dataset(x: np.ndarray):
    """(array, dequant) — uint8-pack float images in [0,1] to cut the
    one-time host→device transfer 4x; anything else ships as-is."""
    x = np.asarray(x)
    if x.dtype == np.uint8:
        return x, True
    if np.issubdtype(x.dtype, np.floating) and x.size \
            and 0.0 <= float(x.min()) and float(x.max()) <= 1.0:
        return np.round(x * 255.0).astype(np.uint8), True
    return x, False


#: augmentation names the device path understands
DEVICE_AUGMENTS = ('pad_crop', 'hflip', 'vflip', 'cutout')


def normalize_augment_spec(specs) -> Optional[list]:
    """Parse a config augment list into [(name, params)] if every entry
    is device-expressible, else None (caller falls back to host path)."""
    out = []
    for spec in specs or ():
        if isinstance(spec, str):
            name, params = spec, {}
        else:
            params = dict(spec)
            name = params.pop('name')
        if name not in DEVICE_AUGMENTS:
            return None
        out.append((name, params))
    return out


def make_device_augment(augments: Sequence, image_shape):
    """Build ``augment(x, rng) -> x`` for [B,H,W,C] device batches."""
    import jax
    import jax.numpy as jnp

    h, w = image_shape[0], image_shape[1]

    def augment(x, rng):
        # integer pixels augmented BEFORE dequantization: 1-byte dtypes
        # ride bf16 (0..255 exact → full MXU rate). Wider integer
        # dtypes stay in their native dtype throughout — flips/cutout
        # are dtype-agnostic and pad_crop takes an exact gather path
        # (no float dtype can hold e.g. int32 > 2^24 exactly)
        if not jnp.issubdtype(x.dtype, jnp.floating) \
                and x.dtype.itemsize == 1:
            x = x.astype(jnp.bfloat16)
        for i, (name, params) in enumerate(augments):
            key = jax.random.fold_in(rng, i)
            if name == 'pad_crop':
                pad = int(params.get('pad', 4))
                xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                             mode='reflect')
                k1, k2 = jax.random.split(key)
                n = x.shape[0]
                dy = jax.random.randint(k1, (n,), 0, 2 * pad + 1)
                dx = jax.random.randint(k2, (n,), 0, 2 * pad + 1)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    # crop expressed as one-hot row/col selection
                    # MATMULS: the natural gather lowers to a slow
                    # general gather on TPU (+4.3 ms/step measured on
                    # the ResNet bench); two batched einsums ride the
                    # MXU and make the crop free (25.3k -> 32.0k
                    # img/s). One-hot rows have a single nonzero, so
                    # the selection is an EXACT pixel copy at any
                    # matmul precision; HIGHEST additionally keeps f32
                    # [0,1] floats un-rounded
                    dtype = x.dtype
                    ry = jax.nn.one_hot(dy[:, None] + jnp.arange(h),
                                        h + 2 * pad, dtype=dtype)
                    rx = jax.nn.one_hot(dx[:, None] + jnp.arange(w),
                                        w + 2 * pad, dtype=dtype)
                    t_sel = jnp.einsum(
                        'bqr,brwc->bqwc', ry, xp,
                        precision=jax.lax.Precision.HIGHEST)
                    x = jnp.einsum(
                        'bkw,bqwc->bqkc', rx, t_sel,
                        precision=jax.lax.Precision.HIGHEST)
                else:
                    # wide integer dtypes: exact gather crop
                    # (correctness over MXU speed on this rare path)
                    rows = dy[:, None] + jnp.arange(h)
                    cols = dx[:, None] + jnp.arange(w)
                    xg = jnp.take_along_axis(
                        xp, rows[:, :, None, None], axis=1)
                    x = jnp.take_along_axis(
                        xg, cols[:, None, :, None], axis=2)
            elif name == 'hflip':
                p = float(params.get('p', 0.5))
                flip = jax.random.bernoulli(key, p, (x.shape[0],))
                x = jnp.where(flip[:, None, None, None],
                              x[:, :, ::-1, :], x)
            elif name == 'vflip':
                p = float(params.get('p', 0.5))
                flip = jax.random.bernoulli(key, p, (x.shape[0],))
                x = jnp.where(flip[:, None, None, None],
                              x[:, ::-1, :, :], x)
            elif name == 'cutout':
                size = int(params.get('size', 8))
                p = float(params.get('p', 0.5))
                k1, k2, k3 = jax.random.split(key, 3)
                n = x.shape[0]
                cy = jax.random.randint(k1, (n,), 0, h)
                cx = jax.random.randint(k2, (n,), 0, w)
                pick = jax.random.bernoulli(k3, p, (n,))
                s = size // 2
                yy = jnp.arange(h)[None, :, None]
                xx = jnp.arange(w)[None, None, :]
                # [c-s, c+s) window — exactly the host Cutout's slice
                dy = yy - cy[:, None, None]
                dx_ = xx - cx[:, None, None]
                hole = ((dy >= -s) & (dy < s) & (dx_ >= -s) & (dx_ < s)
                        & pick[:, None, None])
                x = jnp.where(hole[..., None], jnp.zeros_like(x), x)
        return x

    return augment


def place_dataset(x: np.ndarray, y: Optional[np.ndarray], mesh):
    """Put the full dataset on device, replicated across the mesh (each
    device gathers its batch shard by index — replication keeps the
    gather local, and HBM-resident uint8 CIFAR is 150 MB/device). Rows
    of rank > 1 are held flat, ``[N, prod(row_shape)]``: the step
    makers take the ``row_shape`` and restore it on the batch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    # a view on the host; why flat: the module docstring
    x_dev = jax.device_put(x.reshape(len(x), -1) if x.ndim > 2 else x,
                           rep)
    y_dev = jax.device_put(y, rep) if y is not None else None
    return x_dev, y_dev


def gather_rows(x_all, idx, row_shape=None):
    """Rows ``idx`` of the set ``place_dataset`` holds, as a
    ``[B, *row_shape]`` batch, inside a jitted step: the gather is the
    step's only operation over the set, the reshape touches the batch
    alone."""
    import jax.numpy as jnp
    x = jnp.take(x_all, idx, axis=0)
    if row_shape is not None:
        x = x.reshape((x.shape[0],) + tuple(row_shape))
    return x


def dataset_fits_hbm(x: np.ndarray, budget_bytes: int = 2 << 30,
                     extra_bytes: int = 0) -> bool:
    return x.nbytes + extra_bytes <= budget_bytes


__all__ = ['quantize_dataset', 'normalize_augment_spec',
           'make_device_augment', 'place_dataset', 'gather_rows',
           'dataset_fits_hbm', 'DEVICE_AUGMENTS']
