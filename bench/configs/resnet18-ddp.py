"""resnet18-ddp as the benchmark runs it: the program's ResNet-18 built
from ``resnet18-ddp.json``, its weights and images made from the seed, the
operations a training step needs, and a plain float32 reference of the
whole step on one chip (the single-worker baseline of the same task).

The reference imports nothing of the program and makes its weights and
batches again from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import seeding

HIGHEST = jax.lax.Precision.HIGHEST


def build_model(c: dict):
    """The program's ResNet-18; refuses a file whose shape it does not run."""
    from repro.configs import paper
    from repro.models import resnet
    if (tuple(c["stages"]), tuple(c["widths"])) != (resnet.STAGES,
                                                    resnet.WIDTHS):
        raise ValueError(f"{c['name']}: the program's ResNet-18 has stages "
                         f"{resnet.STAGES} and widths {resnet.WIDTHS}")
    if (c["stem"], c["norm"], c["dtype"]) != ("conv3x3_stride1",
                                              "groupnorm8", "float32"):
        raise ValueError(f"{c['name']}: the program runs a 3x3 stride-1 "
                         f"stem, GroupNorm(8) and float32")
    return paper.resnet18_model(c["num_classes"])


# ---------------------------------------------------------------------------
# shapes, weights and batches from the seed
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


def make_weights(c: dict, root):
    """float32 parameters in the program's tree (nested dicts and lists)."""
    def conv(name, k, cin, cout):
        return seeding.normal(root, name, (k, k, cin, cout),
                              (2.0 / (k * k * cin)) ** 0.5)

    def gn(w):
        return {"scale": jnp.ones((w,), jnp.float32),
                "bias": jnp.zeros((w,), jnp.float32)}

    w0 = c["widths"][0]
    stages = [[] for _ in c["stages"]]
    for si, bi, cin, w, stride, proj in blocks(c):
        tag = f"stages/{si}/{bi}"
        blk = {"conv1": conv(f"{tag}/conv1", 3, cin, w), "gn1": gn(w),
               "conv2": conv(f"{tag}/conv2", 3, w, w), "gn2": gn(w)}
        if proj:
            blk["proj"] = conv(f"{tag}/proj", 1, cin, w)
        stages[si].append(blk)
    last = c["widths"][-1]
    return {
        "stem": {"conv": conv("stem/conv", 3, c["in_channels"], w0),
                 "gn": gn(w0)},
        "stages": stages,
        "fc": {"w": seeding.normal(root, "fc/w", (last, c["num_classes"]),
                                   last ** -0.5),
               "b": jnp.zeros((c["num_classes"],), jnp.float32)},
    }


def make_params(c: dict, root, sharding):
    """The weights on the device, in one jitted call."""
    return jax.jit(lambda r: make_weights(c, r), out_shardings=sharding)(root)


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
# operations a step needs
# ---------------------------------------------------------------------------
def forward_flops_per_sample(c: dict) -> float:
    """Multiply-adds of every convolution and the classifier, times 2;
    normalisation, activations and pooling are not counted."""
    side = c["image_size"]
    macs = side * side * 9 * c["in_channels"] * c["widths"][0]
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


def allreduce_payload_bytes(c: dict) -> int:
    """Bytes all-reduced per step: every float32 gradient and the loss."""
    leaves = jax.eval_shape(lambda: make_weights(c, seeding.root_key(0)))
    return 4 * (sum(int(np.prod(x.shape)) for x in jax.tree.leaves(leaves))
                + 1)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _cast(precision):
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


def _conv(x, w, stride, dt):
    return jax.lax.conv_general_dilated(
        x.astype(dt), w.astype(dt), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        preferred_element_type=dt)


def _groupnorm(x, p, dt, groups=8):
    b, hh, ww, ch = x.shape
    g = min(groups, ch)
    xg = x.astype(dt).reshape(b, hh, ww, g, ch // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + 1e-5)
    return (xg.reshape(b, hh, ww, ch) * p["scale"].astype(dt)
            + p["bias"].astype(dt))


def reference_loss(c: dict, params, batch, precision: str = "f32"):
    """Mean cross-entropy of the plain forward pass.  ``precision="bf16"``
    computes every layer in bfloat16 (the control); "f32" is the
    reference, float32 with convolutions at HIGHEST."""
    dt = _cast(precision)
    relu = lambda t: jnp.maximum(t, 0)  # noqa: E731
    x = batch["images"]
    x = relu(_groupnorm(_conv(x, params["stem"]["conv"], 1, dt),
                        params["stem"]["gn"], dt))
    for (si, bi, cin, w, stride, proj), bp in zip(
            blocks(c), [b for st in params["stages"] for b in st]):
        y = relu(_groupnorm(_conv(x, bp["conv1"], stride, dt), bp["gn1"], dt))
        y = _groupnorm(_conv(y, bp["conv2"], 1, dt), bp["gn2"], dt)
        r = _conv(x, bp["proj"], stride, dt) if proj else x
        x = relu(y + r)
    x = x.mean(axis=(1, 2))
    logits = (jnp.dot(x.astype(dt), params["fc"]["w"].astype(dt),
                      precision=HIGHEST, preferred_element_type=dt)
              + params["fc"]["b"].astype(dt)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], -1).mean()


def reference_steps(c: dict, root, steps: int, device,
                    precision: str = "f32", rows=None):
    """``steps`` plain SGD steps from the seed's weights on the seed's
    first batches, on one ``device``.  Returns ``(losses, first_grads,
    params_before, params_after)`` as numpy trees.

    ``rows`` (a slice) keeps only those rows of every batch: the readings
    of a step that leaves part of the batch out.
    """
    lr = c["lr"]

    def step(root, params, i):
        batch = make_batch(c, root, i)
        if rows is not None:
            batch = jax.tree.map(lambda t: t[rows], batch)
        loss, grads = jax.value_and_grad(reference_loss, argnums=1)(
            c, params, batch, precision)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, (loss, grads)

    def run(root):
        p0 = make_weights(c, root)
        p, losses, first = p0, [], None
        for i in range(steps):
            p, (loss, grads) = step(root, p, i)
            losses.append(loss)
            first = grads if first is None else first
        return jnp.stack(losses), first, p0, p

    with jax.default_matmul_precision("highest"), jax.default_device(device):
        out = jax.jit(run)(jax.device_put(root, device))
    return jax.device_get(out)
