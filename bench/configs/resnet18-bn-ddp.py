"""resnet18-bn-ddp as the benchmark runs it: the published ResNet-18
(torchvision's resnet18: 7x7 stride-2 stem, max-pool, BatchNorm) built
through the program from ``resnet18-bn-ddp.json``, its weights, running
statistics and images made from the seed, the operations and bytes a step
needs, and a plain float32 reference of the step under DDP's semantics.

The reference imports nothing of the program and makes its weights and
batches again from the seed.  It takes the whole global batch on one
device (the check gives it the host's CPU) and computes BatchNorm's
statistics over each chip's group of rows, as DDP does (each chip
normalises its own shard); the running statistics it keeps are the first
group's, which DDP broadcasts from chip 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import seeding

HIGHEST = jax.lax.Precision.HIGHEST
PUBLISHED_STEM = "conv7x7_stride2_maxpool"


def _program_resnet():
    """The program's ResNet module; refuses a program that has no
    published layout (it then cannot run this configuration)."""
    import inspect

    from repro.models import resnet
    if "published" not in inspect.signature(resnet.ResNet18).parameters:
        raise ImportError("the program's ResNet-18 has no published stem "
                          "and BatchNorm layout")
    return resnet


_program_resnet()


def build_model(c: dict):
    """The program's published ResNet-18; refuses a file whose shape it
    does not run."""
    resnet = _program_resnet()
    if (tuple(c["stages"]), tuple(c["widths"])) != (resnet.STAGES,
                                                    resnet.WIDTHS):
        raise ValueError(f"{c['name']}: the program's ResNet-18 has stages "
                         f"{resnet.STAGES} and widths {resnet.WIDTHS}")
    if ((c["stem"], c["norm"], c["bn_momentum"], c["bn_eps"], c["dtype"])
            != (PUBLISHED_STEM, "batchnorm", resnet.BN_MOMENTUM,
                resnet.BN_EPS, "float32")):
        raise ValueError(f"{c['name']}: the program's published ResNet-18 "
                         f"has a {PUBLISHED_STEM} stem and BatchNorm with "
                         f"momentum {resnet.BN_MOMENTUM}, eps "
                         f"{resnet.BN_EPS}, float32")
    return resnet.ResNet18(c["num_classes"], published=True,
                           precision=c["matmul_precision"])


# ---------------------------------------------------------------------------
# shapes, weights, state and batches from the seed
# ---------------------------------------------------------------------------
def blocks(c: dict):
    """``(stage, block, c_in, width, stride, has_proj)`` in order."""
    out, cin = [], c["widths"][0]
    for si, (n, w) in enumerate(zip(c["stages"], c["widths"])):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            out.append((si, bi, cin, w, stride, stride != 1 or cin != w))
            cin = w
    return out


def norms(c: dict):
    """``(path, channels)`` of every BatchNorm, ``path`` its keys in the
    parameter tree (stem first, then block by block)."""
    out = [(("stem", "bn"), c["widths"][0])]
    for si, bi, cin, w, stride, proj in blocks(c):
        for key in ("bn1", "bn2") + (("bn_proj",) if proj else ()):
            out.append((("stages", si, bi, key), w))
    return out


def _tree(c: dict, leaf) -> dict:
    """The stem / stages tree with ``leaf(path, channels)`` at every
    BatchNorm."""
    t = {"stem": {}, "stages": [[{} for _ in range(n)]
                                for n in c["stages"]]}
    for path, ch in norms(c):
        node = t
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = leaf(path, ch)
    return t


def make_weights(c: dict, root):
    """float32 parameters in the program's tree (nested dicts and lists)."""
    def conv(name, k, cin, cout):
        return seeding.normal(root, name, (k, k, cin, cout),
                              (2.0 / (k * k * cout)) ** 0.5)

    p = _tree(c, lambda path, ch: {"scale": jnp.ones((ch,), jnp.float32),
                                   "bias": jnp.zeros((ch,), jnp.float32)})
    p["stem"]["conv"] = conv("stem/conv", 7, c["in_channels"],
                             c["widths"][0])
    for si, bi, cin, w, stride, proj in blocks(c):
        blk, tag = p["stages"][si][bi], f"stages/{si}/{bi}"
        blk["conv1"] = conv(f"{tag}/conv1", 3, cin, w)
        blk["conv2"] = conv(f"{tag}/conv2", 3, w, w)
        if proj:
            blk["proj"] = conv(f"{tag}/proj", 1, cin, w)
    last, bound = c["widths"][-1], c["widths"][-1] ** -0.5

    def uniform(name, shape):
        return jax.random.uniform(seeding.leaf_key(root, name), shape,
                                  jnp.float32, -bound, bound)

    p["fc"] = {"w": uniform("fc/w", (last, c["num_classes"])),
               "b": uniform("fc/b", (c["num_classes"],))}
    return p


def make_state(c: dict):
    """The running statistics before the first step: mean 0, variance 1."""
    return _tree(c, lambda path, ch: {"mean": jnp.zeros((ch,), jnp.float32),
                                      "var": jnp.ones((ch,), jnp.float32)})


def make_params(c: dict, root, sharding):
    """The weights and the running statistics on the device, in one jitted
    call."""
    return jax.jit(lambda r: (make_weights(c, r), make_state(c)),
                   out_shardings=sharding)(root)


def make_batch(c: dict, root, index):
    """Batch ``index`` of the feed: unit-normal float32 images and uniform
    labels, (global_batch, side, side, channels) and (global_batch,)."""
    b, side = c["global_batch"], c["image_size"]
    return {"images": seeding.normal(root, "feed/images",
                                     (b, side, side, c["in_channels"]), 1.0,
                                     index),
            "labels": jax.random.randint(
                seeding.leaf_key(root, "feed/labels", index), (b,), 0,
                c["num_classes"])}


# ---------------------------------------------------------------------------
# operations and bytes a step needs
# ---------------------------------------------------------------------------
def forward_flops_per_sample(c: dict) -> float:
    """Multiply-adds of every convolution and the classifier, times 2;
    normalisation, activations and pooling are not counted."""
    side = c["image_size"] // 2                     # after the 7x7/2 stem
    macs = side * side * 49 * c["in_channels"] * c["widths"][0]
    side //= 2                                      # after the max-pool
    for si, bi, cin, w, stride, proj in blocks(c):
        out = side // (2 ** si)
        macs += out * out * 9 * cin * w + out * out * 9 * w * w
        if proj:
            macs += out * out * cin * w
    macs += c["widths"][-1] * c["num_classes"]
    return 2.0 * macs


def train_flops_per_sample(c: dict) -> float:
    """Forward and backward: the backward pass takes twice the forward's
    multiply-adds (gradients of the activations and of the weights)."""
    return 3.0 * forward_flops_per_sample(c)


def parameter_count(c: dict) -> int:
    leaves = jax.eval_shape(lambda: make_weights(c, seeding.root_key(0)))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(leaves))


def allreduce_payload_bytes(c: dict) -> int:
    """Bytes all-reduced per step: every float32 gradient and the loss."""
    return 4 * (parameter_count(c) + 1)


def broadcast_payload_bytes(c: dict) -> int:
    """Bytes of the buffers broadcast from chip 0 per step: a float32
    running mean and variance per BatchNorm channel."""
    return 4 * 2 * sum(ch for _, ch in norms(c))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _cast(precision):
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def _conv(x, w, stride, dt):
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x.astype(dt), w.astype(dt), (stride, stride), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        preferred_element_type=dt)


def _batchnorm(x, p, group, eps, dt):
    """Train-mode BatchNorm with statistics over each ``group`` rows (one
    chip's shard); returns the output and the groups' (mean, unbiased
    variance), each (groups, channels)."""
    rows, hh, ww, ch = x.shape
    xg = x.astype(dt).reshape(rows // group, group, hh, ww, ch)
    mean = xg.mean(axis=(1, 2, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 3), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    n = group * hh * ww
    stats = (mean.reshape(-1, ch), var.reshape(-1, ch) * (n / (n - 1)))
    return y * p["scale"].astype(dt) + p["bias"].astype(dt), stats


def reference_forward(c: dict, params, images, precision: str = "f32"):
    """Logits of the plain forward pass, and every BatchNorm's per-group
    statistics in :func:`norms` order.  ``precision="bf16"`` computes every
    layer in bfloat16 (the control); "f32" is the reference, float32 with
    convolutions at HIGHEST."""
    dt = _cast(precision)
    group = c["global_batch"] // c["data_parallel"]
    stats = []

    def bn(x, p):
        y, s = _batchnorm(x, p, group, c["bn_eps"], dt)
        stats.append(s)
        return y

    relu = lambda t: jnp.maximum(t, 0)  # noqa: E731
    x = relu(bn(_conv(images, params["stem"]["conv"], 2, dt),
                params["stem"]["bn"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for si, bi, cin, w, stride, proj in blocks(c):
        bp = params["stages"][si][bi]
        y = relu(bn(_conv(x, bp["conv1"], stride, dt), bp["bn1"]))
        y = bn(_conv(y, bp["conv2"], 1, dt), bp["bn2"])
        r = bn(_conv(x, bp["proj"], stride, dt), bp["bn_proj"]) if proj else x
        x = relu(y + r)
    x = x.mean(axis=(1, 2))
    logits = (jnp.dot(x.astype(dt), params["fc"]["w"].astype(dt),
                      precision=HIGHEST, preferred_element_type=dt)
              + params["fc"]["b"].astype(dt)).astype(jnp.float32)
    return logits, stats


def reference_loss(c: dict, params, batch, precision: str = "f32"):
    """Mean cross-entropy over the batch (each chip's mean, averaged over
    the chips, as DDP's gradients are), and the per-group statistics."""
    logits, stats = reference_forward(c, params, batch["images"], precision)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.take_along_axis(logp, batch["labels"][:, None], -1).mean()
    return loss, stats


def update_state(c: dict, state, stats, buffers: str = "first"):
    """The running statistics after one step: each BatchNorm's moved
    towards group 0's statistics (``buffers="first"``, DDP's broadcast
    from chip 0) or towards the groups' mean (``"mean"``, a fault)."""
    m = c["bn_momentum"]
    by_path = dict(zip((path for path, _ in norms(c)), stats))

    def pick(s):
        s = s[0] if buffers == "first" else s.mean(axis=0)
        return s.astype(jnp.float32)

    def leaf(path, ch):
        old = state
        for k in path:
            old = old[k]
        mean, var = by_path[path]
        return {"mean": (1 - m) * old["mean"] + m * pick(mean),
                "var": (1 - m) * old["var"] + m * pick(var)}
    return _tree(c, leaf)


def reference_steps(c: dict, root, steps: int, device,
                    precision: str = "f32", rows=None,
                    buffers: str = "first"):
    """``steps`` plain SGD steps from the seed's weights on the seed's
    first batches, on one ``device``, one jitted step at a time (the step
    compiles once).  Returns ``(losses, first_grads, params_before,
    params_after, state_before, state_after)`` as numpy trees.

    ``rows`` (a slice) keeps only those rows of every batch: the readings
    of a step that leaves part of the batch out.  ``buffers`` is
    :func:`update_state`'s.
    """
    lr = c["lr"]

    @jax.jit
    def step(root, params, state, i):
        batch = make_batch(c, root, i)
        if rows is not None:
            batch = jax.tree.map(lambda t: t[rows], batch)
        (loss, stats), grads = jax.value_and_grad(
            reference_loss, argnums=1, has_aux=True)(c, params, batch,
                                                     precision)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, update_state(c, state, stats, buffers), loss, grads

    with jax.default_matmul_precision("highest"), jax.default_device(device):
        root = jax.device_put(root, device)
        p0, s0 = jax.jit(lambda r: (make_weights(c, r), make_state(c)))(root)
        p, s, losses, first = p0, s0, [], None
        for i in range(steps):
            p, s, loss, grads = step(root, p, s, i)
            losses.append(loss)
            first = grads if first is None else first
        return jax.device_get((jnp.stack(losses), first, p0, p, s0, s))
