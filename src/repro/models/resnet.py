"""ResNet-18 in pure JAX — the paper's image-classification evaluation app.

The paper (§4.2) profiles a data-parallel PyTorch ResNet-18 on 64x64
ImageNet-subset images and shows how gradient bucketing changes the
AllReduce call count (Table 3).  We reproduce that experiment with this
model + repro.train's bucketed DDP gradient sync + the monitor.

Two layouts, chosen by the constructor's ``published``:

* the default (False): a 3x3 stride-1 stem without max-pool (the usual stem
  for small images) and GroupNorm over 8 groups, which keeps no state, so a
  training step carries parameters only (:meth:`ResNet18.loss_fn`);
* the published torchvision ``resnet18`` (He et al., arXiv:1512.03385):
  a 7x7 stride-2 stem padded 3, a 3x3 stride-2 max-pool padded 1, every 3x3
  convolution padded 1 on each side, and BatchNorm after every convolution,
  the 1x1 projection shortcut's included (:meth:`ResNet18.stateful_loss_fn`).

``precision`` (a ``jax.lax.Precision`` name such as ``"highest"``) is the
matmul precision of every convolution and of the classifier; None leaves
the backend's default, which on a TPU is one bfloat16 pass.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import Spec, init_params, param_axes, param_shapes

STAGES = (2, 2, 2, 2)                      # ResNet-18 basic blocks
WIDTHS = (64, 128, 256, 512)
BN_MOMENTUM, BN_EPS = 0.1, 1e-5


def _conv_spec(cin, cout, k):
    return Spec((k, k, cin, cout), (None, None, None, "mlp"),
                scale=jnp.sqrt(2.0))


def _gn_spec(c):
    return {"scale": Spec((c,), ("mlp",), init="ones"),
            "bias": Spec((c,), ("mlp",), init="zeros")}


def _blocks():
    """``(stage, block, c_in, width, stride, has_proj)`` in order."""
    out, cin = [], WIDTHS[0]
    for si, (n, w) in enumerate(zip(STAGES, WIDTHS)):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            out.append((si, bi, cin, w, stride, stride != 1 or cin != w))
            cin = w
    return out


def resnet18_specs(num_classes: int = 200, in_ch: int = 3,
                   published: bool = False):
    k, n = (7, "bn") if published else (3, "gn")
    specs = {
        "stem": {"conv": _conv_spec(in_ch, WIDTHS[0], k),
                 n: _gn_spec(WIDTHS[0])},
        "stages": [[] for _ in STAGES],
        "fc": {"w": Spec((WIDTHS[-1], num_classes), (None, "mlp")),
               "b": Spec((num_classes,), ("mlp",), init="zeros")},
    }
    for si, bi, cin, w, stride, proj in _blocks():
        block = {"conv1": _conv_spec(cin, w, 3), n + "1": _gn_spec(w),
                 "conv2": _conv_spec(w, w, 3), n + "2": _gn_spec(w)}
        if proj:
            block["proj"] = _conv_spec(cin, w, 1)
            if published:
                block["bn_proj"] = _gn_spec(w)
        specs["stages"][si].append(block)
    return specs


def resnet18_state(specs):
    """BatchNorm running statistics for every ``bn*`` entry of ``specs``:
    mean 0, variance 1, float32 (an empty tree for GroupNorm)."""
    def walk(tree):
        if isinstance(tree, list):
            subs = [walk(t) for t in tree]
            return subs if any(subs) else []
        out = {}
        for k, v in tree.items():
            if k.startswith("bn"):
                c = v["scale"].shape[0]
                out[k] = {"mean": jnp.zeros((c,), jnp.float32),
                          "var": jnp.ones((c,), jnp.float32)}
            elif isinstance(v, (dict, list)):
                sub = walk(v)
                if sub:
                    out[k] = sub
        return out
    return walk(specs)


def _conv(x, w, stride=1, precision=None, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _gn(x, p, groups=8):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g).astype(jnp.float32)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    x = xg.reshape(b, h, w, c).astype(x.dtype)
    return x * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _bn(x, p, s):
    """Train-mode BatchNorm over (batch, height, width); returns the output
    and the updated running statistics ``s``."""
    xf = x.astype(jnp.float32)
    n = xf.shape[0] * xf.shape[1] * xf.shape[2]
    mean = xf.mean(axis=(0, 1, 2))
    var = xf.var(axis=(0, 1, 2))
    y = (xf - mean) * jax.lax.rsqrt(var + BN_EPS)
    y = (y * p["scale"] + p["bias"]).astype(x.dtype)
    m = BN_MOMENTUM
    return y, {"mean": (1 - m) * s["mean"] + m * mean,
               "var": (1 - m) * s["var"] + m * var * (n / (n - 1))}


def _max_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))


def resnet18_forward(params, images, state=None, *,
                     precision: Optional[str] = None):
    """images: (B, H, W, 3) -> (logits (B, num_classes), new state).

    With ``state`` (BatchNorm running statistics, :func:`resnet18_state`)
    the published layout, which returns the updated statistics; without it
    the default layout, which returns None."""
    published = state is not None
    new = None if state is None else {"stem": {}, "stages": [
        [{} for _ in blocks] for blocks in params["stages"]]}

    def conv(x, w, stride=1):
        k = w.shape[0]
        pad = ((k // 2, k // 2),) * 2 if published else "SAME"
        return _conv(x, w.astype(x.dtype), stride, precision, pad)

    def norm(x, p, key, where):
        """GroupNorm ``gn<key>``, or BatchNorm ``bn<key>`` with the state
        and new state at ``where`` (``("stem",)`` or ``("stages", si,
        bi)``)."""
        if not published:
            return _gn(x, p["gn" + key])
        s, out = state, new
        for k in where:
            s, out = s[k], out[k]
        y, out["bn" + key] = _bn(x, p["bn" + key], s["bn" + key])
        return y

    x = conv(images, params["stem"]["conv"], 2 if published else 1)
    x = jax.nn.relu(norm(x, params["stem"], "", ("stem",)))
    if published:
        x = _max_pool(x)
    for si, bi, cin, w, stride, proj in _blocks():
        bp, at = params["stages"][si][bi], ("stages", si, bi)
        y = jax.nn.relu(norm(conv(x, bp["conv1"], stride), bp, "1", at))
        y = norm(conv(y, bp["conv2"]), bp, "2", at)
        r = x
        if proj:
            r = conv(x, bp["proj"], stride)
            if published:
                r = norm(r, bp, "_proj", at)
        x = jax.nn.relu(y + r)
    x = x.mean(axis=(1, 2))                                 # global avg pool
    logits = jnp.dot(x, params["fc"]["w"].astype(x.dtype),
                     precision=precision) + params["fc"]["b"].astype(x.dtype)
    return logits, new


def resnet18_apply(params, images, shd=None, precision=None):
    """images: (B, H, W, 3) -> logits (B, num_classes), default layout."""
    return resnet18_forward(params, images, precision=precision)[0]


def _nll(logits, labels):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    return nll, {"acc": (logits.argmax(-1) == labels).mean()}


def resnet18_loss(params, batch, shd=None, precision=None):
    return _nll(resnet18_apply(params, batch["images"], shd, precision),
                batch["labels"])


class ResNet18:
    def __init__(self, num_classes: int = 200, *, published: bool = False,
                 precision: Optional[str] = None):
        self.num_classes = num_classes
        self.published, self.precision = published, precision

    def specs(self):
        return resnet18_specs(self.num_classes, published=self.published)

    def init(self, rng):
        return init_params(self.specs(), rng)

    def init_state(self):
        return resnet18_state(self.specs())

    def shapes(self):
        return param_shapes(self.specs())

    def axes(self):
        return param_axes(self.specs())

    def loss_fn(self, params, batch, shd=None, remat=None):
        """Mean cross-entropy of the default layout, which keeps no state."""
        if self.published:
            raise ValueError("the published layout keeps BatchNorm state: "
                             "use stateful_loss_fn")
        return resnet18_loss(params, batch, shd, self.precision)

    def stateful_loss_fn(self, params, state, batch):
        """``(loss, (metrics, new_state))`` of the published layout: the
        train-mode loss and the running statistics updated from this
        batch."""
        if not self.published:
            raise ValueError("the default layout keeps no state: use loss_fn")
        logits, new = resnet18_forward(params, batch["images"], state,
                                       precision=self.precision)
        loss, metrics = _nll(logits, batch["labels"])
        return loss, (metrics, new)
