"""Plain reference for the `resnet` kind: bottleneck ResNet v1.5, its loss,
gradients and SGD steps, in `jax.numpy` / `lax.conv`, float32, under
`jax.default_matmul_precision("highest")`.

Written from He et al. (arXiv:1512.03385, Table 1) with the stride in the
3x3 convolution ("v1.5"), and imports nothing of the program.  Departures
from the paper, each because the configuration says the program does so:
`SAME` padding in the strided 3x3 and in the max-pool (one pixel on the far
side only), BatchNorm eps 1e-5 with the biased batch variance, label
smoothing, Nesterov momentum.  Parameter names are `/`-joined paths
(`stage2_block1/conv2/kernel`, HWIO); the builder maps them onto the
program's tree.

The weights are the benchmark's input, made here from the seed: He-normal
kernels, BatchNorm scales 1 + 0.1 n and biases 0.1 n, and each block's last
scale a tenth of that.  A zero last scale, the usual initialisation, would
leave most first gradients exactly zero and the comparison blind to them;
a last scale of one makes sixteen unit-variance branches pile up, and the
gradients of the early BatchNorm leaves then differ by 20-40% between
bfloat16 and float32 on sound code (my chip run, PR 23), which no limit
can tell from a fault.  At a tenth every branch carries signal and sound
bfloat16 stays within a few percent.

`rounding` goes around every convolution and matmul (`benchmarks/precision.py`):
the identity gives the reference, fp8 the lower-precision control.
"""

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.precision import ROUNDINGS, Rounding
from benchmarks.sketch import sketches


# Their gradient is the pooled features (the whole forward pass) times the
# loss's derivative: no backward pass through the network.
HEAD_LEAVES = ("head/kernel", "head/bias")


def param_table(cfg: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, role) of every parameter, in a fixed order."""
    f = int(cfg["num_filters"])
    rows: list[tuple[str, tuple[int, ...], str]] = []

    def conv(name, kh, cin, cout):
        rows.append((f"{name}/kernel", (kh, kh, cin, cout), "conv"))

    def bn(name, c):
        last = name.endswith("/bn3")
        rows.append((f"{name}/scale", (c,), "last_scale" if last else "scale"))
        rows.append((f"{name}/bias", (c,), "bias"))

    conv("conv_init", 7, 3, f)
    bn("bn_init", f)
    cin = f
    for i, blocks in enumerate(cfg["stage_sizes"]):
        width = f * 2**i
        for j in range(blocks):
            scope = f"stage{i + 1}_block{j + 1}"
            conv(f"{scope}/conv1", 1, cin, width)
            bn(f"{scope}/bn1", width)
            conv(f"{scope}/conv2", 3, width, width)
            bn(f"{scope}/bn2", width)
            conv(f"{scope}/conv3", 1, width, 4 * width)
            bn(f"{scope}/bn3", 4 * width)
            if cin != 4 * width or (i > 0 and j == 0):
                conv(f"{scope}/conv_proj", 1, cin, 4 * width)
                bn(f"{scope}/bn_proj", 4 * width)
            cin = 4 * width
    rows.append(("head/kernel", (cin, int(cfg["num_classes"])), "dense"))
    rows.append(("head/bias", (int(cfg["num_classes"]),), "bias"))
    return rows


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    """Float32 weights from the seed, one fold of the key per row."""
    out = {}
    for i, (name, shape, role) in enumerate(param_table(cfg)):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if role == "conv":
            out[name] = n * math.sqrt(2.0 / (shape[0] * shape[1] * shape[2]))
        elif role == "dense":
            out[name] = n * math.sqrt(1.0 / shape[0])
        elif role == "scale":
            out[name] = 1.0 + 0.1 * n
        elif role == "last_scale":
            out[name] = 0.1 * (1.0 + 0.1 * n)
        else:
            out[name] = 0.1 * n
    return out


def _conv(x, w, stride, padding, rounding):
    return rounding.result(lax.conv_general_dilated(
        rounding.operand(x), rounding.operand(w), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    ))


def _bn(x, p, name):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + 1e-5) * p[f"{name}/scale"] + p[f"{name}/bias"]


def _block(x, p, scope, stride, rounding):
    y = _conv(x, p[f"{scope}/conv1/kernel"], 1, "SAME", rounding)
    y = jax.nn.relu(_bn(y, p, f"{scope}/bn1"))
    y = _conv(y, p[f"{scope}/conv2/kernel"], stride, "SAME", rounding)
    y = jax.nn.relu(_bn(y, p, f"{scope}/bn2"))
    y = _conv(y, p[f"{scope}/conv3/kernel"], 1, "SAME", rounding)
    y = _bn(y, p, f"{scope}/bn3")
    if f"{scope}/conv_proj/kernel" in p:
        x = _conv(x, p[f"{scope}/conv_proj/kernel"], stride, "SAME", rounding)
        x = _bn(x, p, f"{scope}/bn_proj")
    return jax.nn.relu(x + y)


def logits(params: dict, images: jax.Array, cfg: dict, rounding=Rounding()) -> jax.Array:
    """uint8 NHWC images -> float32 logits, BatchNorm on batch statistics."""
    mean = jnp.asarray(cfg["input_mean"], jnp.float32)
    std = jnp.asarray(cfg["input_std"], jnp.float32)
    x = (images.astype(jnp.float32) / 255.0 - mean) / std
    x = _conv(x, params["conv_init/kernel"], 2, [(3, 3), (3, 3)], rounding)
    x = jax.nn.relu(_bn(x, params, "bn_init"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for i, blocks in enumerate(cfg["stage_sizes"]):
        for j in range(blocks):
            scope = f"stage{i + 1}_block{j + 1}"
            stride = 2 if i > 0 and j == 0 else 1
            sub = {k: v for k, v in params.items() if k.startswith(scope + "/")}
            # Recompute each block in the backward pass: the float32 batch
            # has to fit beside nothing but itself.
            x = jax.checkpoint(
                partial(_block, scope=scope, stride=stride, rounding=rounding)
            )(x, sub)
    x = jnp.mean(x, axis=(1, 2))
    head = jnp.dot(
        rounding.operand(x), rounding.operand(params["head/kernel"]),
        precision=lax.Precision.HIGHEST,
    )
    return rounding.result(head) + params["head/bias"]


def loss(params, images, labels, cfg, rounding=Rounding()) -> jax.Array:
    z = logits(params, images, cfg, rounding)
    classes = z.shape[-1]
    s = float(cfg["label_smoothing"])
    target = jax.nn.one_hot(labels, classes) * (1.0 - s) + s / classes
    return -jnp.mean(jnp.sum(target * jax.nn.log_softmax(z, axis=-1), axis=-1))


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


@lru_cache(maxsize=4)
def _programs(cfg_json: str, precision: str):
    """The jitted parts of `follow` for one configuration and precision,
    kept so that a process that follows many seeds traces them once."""
    cfg = json.loads(cfg_json)
    rounding = ROUNDINGS[precision]
    lr, momentum = float(cfg["learning_rate"]), float(cfg["momentum"])

    @jax.jit
    def step(params, trace, images, labels, key):
        value, grads = jax.value_and_grad(loss)(params, images, labels, cfg, rounding)
        trace = {k: grads[k] + momentum * trace[k] for k in grads}
        # Nesterov: the step looks one momentum application ahead.
        params = {
            k: params[k] - lr * (grads[k] + momentum * trace[k]) for k in params
        }
        return value, (_norms(grads), sketches(grads, key)), params, trace

    start = jax.jit(partial(init_params, cfg=cfg))
    moved = jax.jit(lambda a, b: _norms({k: a[k] - b[k] for k in a}))
    return start, step, moved


def follow(key, cfg: dict, batches, steps: int, *, precision: str = "float32",
           batch_sharding=None) -> dict:
    """Follow the first `steps` SGD steps from the seeded weights.

    Returns each step's loss, the norm and the seeded projection
    (`sketch.py`) of the first gradient per leaf and the norm of the
    parameters' change after the last step per leaf, as Python floats.  `batch_sharding`, if given, spreads the batch over the
    cell's chips; the arithmetic is that of the whole batch either way.
    """
    start, step, moved = _programs(json.dumps(cfg, sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        seeded = params = start(key)
        trace = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first = [], None
        for i in range(steps):
            images, labels = batches[i]
            if batch_sharding is not None:
                images = jax.device_put(images, batch_sharding)
                labels = jax.device_put(labels, batch_sharding)
            value, of_grads, params, trace = step(params, trace, images, labels, key)
            losses.append(float(value))
            if first is None:
                first = (
                    {k: float(v) for k, v in of_grads[0].items()},
                    {k: [float(x) for x in v] for k, v in of_grads[1].items()},
                )
        return {
            "loss": losses,
            "grad_norm": first[0],
            "grad_sketch": first[1],
            "head_leaves": list(HEAD_LEAVES),
            "update_norm": {k: float(v) for k, v in moved(params, seeded).items()},
        }
