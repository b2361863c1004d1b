"""Plain reference for the ``resnet`` family: ResNet-18 with the CIFAR
stem (He et al., arXiv:1512.03385, Table 1, 18-layer; 3x3 stem without
max-pool), its input path, loss, backward pass and update, in float32.

Covers what the timed step covers: the gather from the resident uint8
set, pad-crop and flip (drawn as the configuration's seed says), the
dequantisation, the network in training mode (batch statistics), the
softmax cross-entropy and the gradient. Imports nothing of the program.

Departures from the paper, as the configuration states them: a
BatchNorm gain and bias after every convolution, a 1x1 projection with
its own norm where the shape changes, global average pooling and a
biased linear head over ``num_classes``.
"""

import jax
import jax.numpy as jnp

from . import common

STAGES = (2, 2, 2, 2)
EPS = 1e-5


def blocks(model: dict):
    """(name, c_in, c_out, stride) of each basic block."""
    width = int(model.get('num_filters', 64))
    out, c_in, i = [], width, 0
    for stage, n in enumerate(STAGES):
        for j in range(n):
            c_out = width * 2 ** stage
            out.append((f'BasicBlock_{i}', c_in, c_out,
                        2 if stage > 0 and j == 0 else 1))
            c_in, i = c_out, i + 1
    return out


def param_spec(model: dict) -> dict:
    """{path: (shape, dtype)} of every parameter the configuration
    gives the model."""
    f32 = jnp.float32
    width = int(model.get('num_filters', 64))
    spec = {'conv_stem/kernel': ((3, 3, 3, width), f32)}

    def norm(name, c):
        spec[f'{name}/scale'] = ((c,), f32)
        spec[f'{name}/bias'] = ((c,), f32)

    norm('norm_stem', width)
    c_last = width
    for name, c_in, c_out, stride in blocks(model):
        spec[f'{name}/Conv_0/kernel'] = ((3, 3, c_in, c_out), f32)
        norm(f'{name}/BatchNorm_0', c_out)
        spec[f'{name}/Conv_1/kernel'] = ((3, 3, c_out, c_out), f32)
        norm(f'{name}/BatchNorm_1', c_out)
        if stride != 1 or c_in != c_out:
            spec[f'{name}/conv_proj/kernel'] = ((1, 1, c_in, c_out), f32)
            norm(f'{name}/norm_proj', c_out)
        c_last = c_out
    classes = int(model['num_classes'])
    spec['head/kernel'] = ((c_last, classes), f32)
    spec['head/bias'] = ((classes,), f32)
    return spec


def forward(params: dict, x, model: dict, rnd):
    """Logits of ``x`` [B,H,W,3] float32 in training mode."""

    def conv(x, name, stride=1):
        return jax.lax.conv_general_dilated(
            rnd(x), rnd(params[f'{name}/kernel']), (stride, stride),
            'SAME', dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            precision=common.HIGHEST)

    def norm(x, name):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.maximum(jnp.mean(x * x, (0, 1, 2)) - mean * mean, 0.0)
        y = (x - mean) * jax.lax.rsqrt(var + EPS)
        return y * params[f'{name}/scale'] + params[f'{name}/bias']

    def block(x, name, c_in, c_out, stride):
        y = jax.nn.relu(norm(conv(x, f'{name}/Conv_0', stride),
                             f'{name}/BatchNorm_0'))
        y = norm(conv(y, f'{name}/Conv_1'), f'{name}/BatchNorm_1')
        if stride != 1 or c_in != c_out:
            x = norm(conv(x, f'{name}/conv_proj', stride),
                     f'{name}/norm_proj')
        return jax.nn.relu(x + y)

    x = jax.nn.relu(norm(conv(x, 'conv_stem'), 'norm_stem'))
    for name, c_in, c_out, stride in blocks(model):
        # batch statistics tie the rows together, so the batch cannot go
        # in blocks of rows; one block's activations at a time instead
        x = jax.checkpoint(block, static_argnums=(1, 2, 3, 4))(
            x, name, c_in, c_out, stride)
    x = jnp.mean(x, (1, 2))
    return jnp.dot(rnd(x), rnd(params['head/kernel']),
                   precision=common.HIGHEST) + params['head/bias']


# -------------------------------------------------------------- input path
def job_key(seed: int):
    """The key a job keeps for its steps: the second half of the split
    of the key its ``seed`` makes."""
    return jax.random.split(jax.random.PRNGKey(int(seed)))[1]


def step_key(key, step):
    """The key of step ``step``'s augmentation: the job's key folded
    with the step, then with 1 (the augmentation's stream)."""
    return jax.random.fold_in(jax.random.fold_in(key, step), 1)


def augment(x, key, specs):
    """``pad_crop`` (reflect pad, a uniform offset per row) and
    ``hflip`` on [B,H,W,C]; ``specs`` as the configuration lists them."""
    n, h, w = x.shape[0], x.shape[1], x.shape[2]
    for i, spec in enumerate(specs or ()):
        name = spec if isinstance(spec, str) else spec['name']
        k = jax.random.fold_in(key, i)
        if name == 'pad_crop':
            pad = int(spec.get('pad', 4))
            xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                         mode='reflect')
            k1, k2 = jax.random.split(k)
            dy = jax.random.randint(k1, (n,), 0, 2 * pad + 1)
            dx = jax.random.randint(k2, (n,), 0, 2 * pad + 1)
            rows = dy[:, None] + jnp.arange(h)
            cols = dx[:, None] + jnp.arange(w)
            xg = jnp.take_along_axis(xp, rows[:, :, None, None], axis=1)
            x = jnp.take_along_axis(xg, cols[:, None, :, None], axis=2)
        elif name == 'hflip':
            p = 0.5 if isinstance(spec, str) else float(spec.get('p', 0.5))
            flip = jax.random.bernoulli(k, p, (n,))
            x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
        else:
            raise ValueError(f'no reference for augment {name!r}')
    return x


def batch(job: dict, feed, step):
    """Rows and labels of one step: gather by the step's indices from
    the resident uint8 set, augment, dequantise."""
    x = jnp.take(feed['x_all'], feed['feed'], axis=0).astype(jnp.float32)
    y = jnp.take(feed['y_all'], feed['feed'], axis=0)
    x = augment(x, step_key(feed['key'], step), job.get('augment'))
    return x / 255.0, y


def resident(dataset: dict) -> dict:
    """The resident uint8 set, read from the benchmark's own file (not
    from the program's copy on the device)."""
    import numpy as np
    with np.load(dataset['path']) as data:
        return {'x_all': jnp.asarray(data['x_train']),
                'y_all': jnp.asarray(data['y_train'])}


def train_flops_per_sample(model: dict, data: dict) -> float:
    """FLOPs the forward and backward passes of one image require
    (``flops.py``): every convolution and the head, backward twice the
    forward; norms, activations and the pooling are not counted."""
    from benchmark import flops
    size = int(data['image_size'])
    width = int(model.get('num_filters', 64))
    total = flops.conv2d(size, size, 3, 3, int(data['channels']), width)
    for _, c_in, c_out, stride in blocks(model):
        out = size // stride
        total += flops.conv2d(out, out, 3, 3, c_in, c_out)
        total += flops.conv2d(out, out, 3, 3, c_out, c_out)
        if stride != 1 or c_in != c_out:
            total += flops.conv2d(out, out, 1, 1, c_in, c_out)
        size = out
    total += flops.matmul(1, width * 8, int(model['num_classes']))
    return 3.0 * total


# ------------------------------------------------------------------- train
def train(job: dict, params: dict, feeds, operands='float32',
          fault=None, steps=3) -> dict:
    """Follow the first ``steps`` steps of the job. ``feeds[i]`` holds
    step i's index vector (``feed``) and the resident set.
    ``fault='half_batch'`` leaves the second half of every batch out
    and takes the mean over the rest."""
    rnd = common.rounder(operands)
    model = job['model']

    def loss_fn(params, x, y):
        logits = forward(params, x, model, rnd)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    @jax.jit
    def loss_and_grads_jit(params, feed, step):
        x, y = batch(job, feed, step)
        if fault == 'half_batch':
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        return jax.value_and_grad(loss_fn)(params, x, y)

    # the job's key goes in as an argument: as a constant it would make
    # every seed a program of its own to compile
    key = job_key(job['seed'])
    return common.follow(loss_and_grads_jit, job['optimizer'], params,
                         [dict(f, key=key) for f in feeds], steps)
