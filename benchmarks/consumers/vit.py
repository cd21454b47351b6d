"""The ViT consumer: the program's ``models.vit`` train step under
``optax.adamw``, fed by ``ops.normalize_images``, with the benchmark's
own weights and its own reference (``benchmarks/reference/vit.py``).

A configuration names this file by ``"consumer": "vit"``; its dataset
delivers ``image`` (uint8 NHWC) and ``label`` columns.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np

from reference import vit as reference

from lib import flops

#: the programs' names on the trace's ``XLA Modules`` line (``jit_`` and
#: the jitted function's name): the feed's normalize, one chip or a mesh,
#: and the train step (``models.vit.vit_train_step`` jits ``step``)
NORMALIZE_PROGRAMS = ('jit_bench_normalize', 'jit_bench_normalize_mesh')
STEP_PROGRAMS = ('jit_step',)


def _key(seed):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, seed // 2 ** 32 % 2 ** 31)


def init_params(seed, config, sharding):
    """The weights, in float32 as the step holds them, made on the device
    in one jitted call from ``seed``, in the program's layout. The head is
    drawn at random (a zero head makes every first loss ln(n_classes) and
    hides the forward pass)."""
    w = config['widths']
    d, dff, layers = w['d_model'], w['d_ff'], w['n_layers']
    patch_dim = w['patch_size'] ** 2 * w['channels']
    n_patches = (w['image_size'] // w['patch_size']) ** 2

    def make(key):
        keys = iter(jax.random.split(key, 3 + 4 * layers))

        def normal(shape, scale):
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

        blocks = [{'qkv': normal((d, 3 * d), d ** -0.5),
                   'attn_out': normal((d, d), d ** -0.5),
                   'mlp_in': normal((d, dff), d ** -0.5),
                   'mlp_out': normal((dff, d), dff ** -0.5),
                   'ln1': jnp.ones((d,), jnp.float32),
                   'ln2': jnp.ones((d,), jnp.float32)}
                  for _ in range(layers)]
        return {'patch_embed': normal((patch_dim, d), patch_dim ** -0.5),
                'pos_embed': normal((n_patches, d), 0.02),
                'blocks': blocks,
                'ln_f': jnp.ones((d,), jnp.float32),
                'head': normal((d, w['n_classes']), d ** -0.5)}

    return jax.jit(make, out_shardings=sharding)(_key(seed))


def check_config(config):
    """Raise ``ValueError`` where ``config`` is not one this consumer can
    run: patches that do not tile the image, or images that are not RGB."""
    w = config['widths']
    if w['image_size'] % w['patch_size']:
        raise ValueError('patch_size %d does not tile image_size %d'
                         % (w['patch_size'], w['image_size']))
    if w['channels'] != 3:
        raise ValueError('the normalize takes RGB images, not %d channels'
                         % w['channels'])


def tiny(config):
    """``config`` cut to a size a CPU test run holds: a two-layer ViT of
    width 32 over 32px images in 8px patches, eight rows a chip, the plain
    normalize formula, and the reference four rows at a time."""
    config = copy.deepcopy(config)
    config['widths'].update(image_size=32, patch_size=8, d_model=32,
                            n_heads=2, n_layers=2, d_ff=64)
    config['batch_per_chip'] = 8
    config['normalize']['impl'] = 'jnp'
    config['reference_chunk'] = 4
    return config


def model_config(widths):
    from petastorm_tpu.models.vit import ViTConfig
    return ViTConfig(image_size=widths['image_size'],
                     patch_size=widths['patch_size'],
                     channels=widths['channels'],
                     n_classes=widths['n_classes'],
                     d_model=widths['d_model'], n_heads=widths['n_heads'],
                     n_layers=widths['n_layers'], d_ff=widths['d_ff'],
                     dtype=jnp.dtype(widths['dtype']),
                     attn_impl=widths['attention'])


def optimizer(spec):
    import optax
    return optax.adamw(spec['learning_rate'], b1=spec['b1'], b2=spec['b2'],
                       eps=spec['eps'], weight_decay=spec['weight_decay'])


def train_step(config):
    """The program's jitted ``(params, opt_state, images, labels) ->
    (params, opt_state, loss)``."""
    from petastorm_tpu.models.vit import vit_train_step
    return vit_train_step(model_config(config['widths']),
                          optimizer(config['optimizer']))


def feed_fn(config, mesh):
    """``batch -> (images, labels)``, the train step's inputs: the
    program's Pallas normalize kernel on the batch's uint8 images."""
    normalize = _normalize_fn(config, mesh)

    def feed(batch):
        return normalize(batch['image']), batch['label']
    return feed


def _normalize_fn(config, mesh):
    """The program's Pallas normalize kernel, run on each chip's own rows
    under a mesh (a bare ``pallas_call`` on a sharded batch would be
    gathered onto every chip)."""
    from petastorm_tpu.ops import normalize_images
    norm = config['normalize']

    def bench_normalize(images):
        return normalize_images(images, tuple(norm['mean']),
                                tuple(norm['std']),
                                out_dtype=jnp.dtype(config['widths']['dtype']),
                                impl=norm['impl'])

    if mesh is None:
        return jax.jit(bench_normalize)
    from jax.sharding import PartitionSpec as P
    per_chip = jax.shard_map(bench_normalize, mesh=mesh, in_specs=P('data'),
                             out_specs=P('data'), check_vma=False)

    def bench_normalize_mesh(images):  # the program's name in the trace
        return per_chip(images)

    return jax.jit(bench_normalize_mesh)


def flops_per_row(config):
    w = config['widths']
    return flops.vit_train_flops_per_image(
        patch_dim=w['patch_size'] ** 2 * w['channels'], d_model=w['d_model'],
        n_layers=w['n_layers'], d_ff=w['d_ff'],
        n_patches=(w['image_size'] // w['patch_size']) ** 2,
        n_classes=w['n_classes'])


def loss_fn(config):
    """The program's ``(params, images, labels) -> loss``."""
    from petastorm_tpu.models.vit import vit_loss
    model = model_config(config['widths'])

    def loss(params, images, labels):
        return vit_loss(params, images, labels, model)
    return loss


def reference_train(seed, samples, config, devices):
    """The reference's readings on the checked batches (host ``samples``)
    from the seed's weights, made anew, its row chunks spread over
    ``devices``: ``(losses, first gradient, parameters before, parameters
    after)``."""
    from jax.sharding import SingleDeviceSharding
    params = init_params(seed, config, SingleDeviceSharding(devices[0]))
    norm = config['normalize']
    losses, grad, after = reference.train(
        params, [(s['image'], s['label']) for s in samples],
        config['widths'], norm['mean'], norm['std'], config['optimizer'],
        config['reference_chunk'], devices)
    return losses, grad, params, after


def feed_numbers(samples, config):
    """``normalize_gap``: the largest gap between the kernel's output (the
    feed's first input, ``fed[0]``) and the plain formula on the same
    pixels."""
    norm = config['normalize']
    scale = (1.0 / (255.0 * np.asarray(norm['std'], np.float64))).astype(
        np.float32)
    bias = (-np.asarray(norm['mean'], np.float64)
            / np.asarray(norm['std'], np.float64)).astype(np.float32)
    gap = 0.0
    for sample in samples:
        want = sample['image'].astype(np.float32) * scale + bias
        gap = max(gap, float(np.max(np.abs(
            np.asarray(sample['fed'][0], np.float32) - want))))
    return {'normalize_gap': (gap, None)}


#: the control's precision, float8 e4m3 as (exponent, mantissa) bits: the
#: nearest below the bfloat16 that the configurations state
CONTROL_BITS = (4, 3)


def control_feed_fn(config, mesh):
    """The control's feed: the plain formula, rounded to float8."""
    norm = config['normalize']
    out = jnp.dtype(config['widths']['dtype'])

    @jax.jit
    def control_normalize(images):
        x = reference.normalize(images, norm['mean'], norm['std'])
        return reference.round_to(x, CONTROL_BITS).astype(out)

    def feed(batch):
        return control_normalize(batch['image']), batch['label']
    return feed


def control_train_step(config):
    """The control's step: the reference's loss and gradient with every
    matmul operand rounded to float8, under the same AdamW; the gradient
    is summed over chunks of ``reference_chunk`` rows (the float32
    reference holds a whole batch's activations in no chip's memory)."""
    import optax
    widths, opt = dict(config['widths']), optimizer(config['optimizer'])
    chunk = config['reference_chunk']

    def chunk_loss(params, x, labels):
        return reference.loss_sum(params, x, labels, widths, CONTROL_BITS)

    @jax.jit
    def step(params, opt_state, images, labels):
        n = images.shape[0]
        xs = images.reshape((n // chunk, chunk) + images.shape[1:])
        ys = labels.reshape(n // chunk, chunk)

        def add(carry, xy):
            value, grads = jax.value_and_grad(chunk_loss)(params, *xy)
            return jax.tree_util.tree_map(jnp.add, carry,
                                          (value, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        (total, grads), _ = jax.lax.scan(add, zero, (xs, ys))
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, total / n

    return step
