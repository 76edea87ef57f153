"""The published ResNet-18 (7x7 stride-2 stem, max-pool, BatchNorm) and its
DDP step with the buffer broadcast, against a plain reference written
here: BatchNorm's statistics over each chip's rows, the running statistics
taken from chip 0's, as PyTorch DDP computes them."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import MonitorSession
from repro.models.resnet import STAGES, WIDTHS, ResNet18, resnet18_forward
from repro.train import ddp

pytestmark = pytest.mark.compile

HI = jax.lax.Precision.HIGHEST
PUBLISHED = dict(published=True, precision="highest")
CLASSES, SIDE, CHIPS = 10, 32, 4
# float32 on both sides, summed in different orders.  Measured at these
# sizes: logits 2.2e-5 of their largest value (the last stage's BatchNorm
# divides by the spread of 4 rows of one pixel, which magnifies round-off),
# loss 1.5e-6, the worst gradient leaf 1.0e-4 (BatchNorm's backward
# subtracts two means of the upstream gradient, which cancels most of it),
# running statistics 2.4e-6.  Each tolerance is about ten times that; a
# broadcast that averages or is left out misses chip 0's statistics by
# more than 0.8.
LOGITS_TOL, LOSS_TOL, GRAD_TOL, STATS_TOL = 2e-4, 1e-5, 1e-3, 2e-5


def _blocks():
    out, cin = [], WIDTHS[0]
    for si, (n, w) in enumerate(zip(STAGES, WIDTHS)):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            out.append((si, bi, stride, stride != 1 or cin != w))
            cin = w
    return out


def _conv(x, w, stride):
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _pool(x):
    """3x3 stride-2 max-pool, padded 1: the max of nine shifted views."""
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)),
                 constant_values=-jnp.inf)
    h, w = x.shape[1] // 2, x.shape[2] // 2
    return jnp.stack([xp[:, i:i + 2 * h:2, j:j + 2 * w:2]
                      for i in range(3) for j in range(3)]).max(axis=0)


def reference(params, state, images, group, pick="first"):
    """Logits and the running statistics after one step.  BatchNorm takes
    its statistics over each ``group`` rows; the running statistics move
    (momentum 0.1, unbiased variance) towards group 0's (``pick="first"``)
    or the groups' mean (``"mean"``)."""
    new = {"stem": {}, "stages": [[{} for _ in range(n)] for n in STAGES]}

    def bn(x, p, s, out, key):
        b, h, w, c = x.shape
        xg = x.reshape(b // group, group, h, w, c)
        mean = xg.mean(axis=(1, 2, 3), keepdims=True)
        var = ((xg - mean) ** 2).mean(axis=(1, 2, 3), keepdims=True)
        y = ((xg - mean) / jnp.sqrt(var + 1e-5)).reshape(x.shape)
        n = group * h * w
        m, v = mean.reshape(-1, c), var.reshape(-1, c) * n / (n - 1)
        m, v = (m[0], v[0]) if pick == "first" else (m.mean(0), v.mean(0))
        out[key] = {"mean": 0.9 * s[key]["mean"] + 0.1 * m,
                    "var": 0.9 * s[key]["var"] + 0.1 * v}
        return y * p[key]["scale"] + p[key]["bias"]

    relu = lambda t: jnp.maximum(t, 0.0)  # noqa: E731
    x = relu(bn(_conv(images, params["stem"]["conv"], 2), params["stem"],
                state["stem"], new["stem"], "bn"))
    x = _pool(x)
    for si, bi, stride, proj in _blocks():
        bp, bs = params["stages"][si][bi], state["stages"][si][bi]
        out = new["stages"][si][bi]
        y = relu(bn(_conv(x, bp["conv1"], stride), bp, bs, out, "bn1"))
        y = bn(_conv(y, bp["conv2"], 1), bp, bs, out, "bn2")
        r = (bn(_conv(x, bp["proj"], stride), bp, bs, out, "bn_proj")
             if proj else x)
        x = relu(y + r)
    logits = jnp.dot(x.mean(axis=(1, 2)), params["fc"]["w"],
                     precision=HI) + params["fc"]["b"]
    return logits, new


def reference_loss(params, state, batch, group, pick="first"):
    logits, new = reference(params, state, batch["images"], group, pick)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.take_along_axis(logp, batch["labels"][:, None], -1).mean()
    return loss, new


def gap(a, b):
    """Largest relative gap between two trees, leaf by leaf."""
    return max(float(np.linalg.norm(np.asarray(x, np.float64) - y)
                     / np.linalg.norm(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.fixture(scope="module")
def setup():
    model = ResNet18(CLASSES, **PUBLISHED)
    params = model.init(jax.random.PRNGKey(0))
    # scales and shifts away from 1 and 0, so that each one matters
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (x + 0.3 * jax.random.normal(next(keys), x.shape)
                         if path[-1].key in ("scale", "bias") else x),
        params)
    state = model.init_state()
    batch = {"images": jax.random.normal(jax.random.PRNGKey(2),
                                         (16, SIDE, SIDE, 3)),
             "labels": jnp.arange(16) % CLASSES}
    return model, params, state, batch


def test_published_counts():
    """torchvision's resnet18 at 200 classes: 11,279,112 parameters (the
    default layout's 11,269,640, plus 7,680 for the 7x7 stem and 1,792 for
    the shortcuts' BatchNorm); 20 BatchNorms over 4,800 channels, whose
    float32 running mean and variance are 38,400 B."""
    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    pub, default = ResNet18(200, **PUBLISHED), ResNet18(200)
    assert count(pub.shapes()) == 11_279_112
    assert count(default.shapes()) == 11_269_640
    state = pub.init_state()
    assert len(jax.tree.leaves(state)) == 2 * 20
    assert sum(x.nbytes for x in jax.tree.leaves(state)) == 38_400
    assert default.init_state() == {}


def test_published_model_matches_plain_reference(setup):
    model, params, state, batch = setup
    b4 = jax.tree.map(lambda t: t[:4], batch)
    (loss, (_, new)), grads = jax.jit(jax.value_and_grad(
        model.stateful_loss_fn, has_aux=True))(params, state, b4)
    with jax.default_matmul_precision("highest"):
        (want, want_new), want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, state, b4, 4), has_aux=True))(params)
        want_logits = reference(params, state, b4["images"], 4)[0]
    logits = jax.jit(lambda p, s, x: resnet18_forward(
        p, x, s, precision="highest")[0])(
        params, state, b4["images"])
    assert float(jnp.abs(logits - want_logits).max()
                 / jnp.abs(want_logits).max()) < LOGITS_TOL
    assert abs(float(loss) - float(want)) / float(want) < LOSS_TOL
    assert gap(grads, want_grads) < GRAD_TOL
    assert gap(new, want_new) < STATS_TOL


def test_each_layout_has_one_loss(setup):
    """The published layout trains only through its stateful loss, the
    default only through the stateless one: neither runs the other's
    network by mistake."""
    model, params, state, batch = setup
    with pytest.raises(ValueError, match="stateful_loss_fn"):
        model.loss_fn(params, batch)
    default = ResNet18(CLASSES)
    with pytest.raises(ValueError, match="loss_fn"):
        default.stateful_loss_fn(default.init(jax.random.PRNGKey(0)), {},
                                 batch)


@pytest.mark.parametrize("published", [False, True])
def test_precision_reaches_every_matmul(published):
    """``precision`` is the precision of every convolution and of the
    classifier, in both layouts."""
    model = ResNet18(CLASSES, published=published, precision="highest")
    params = model.shapes()
    batch = {"images": jax.ShapeDtypeStruct((2, SIDE, SIDE, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((2,), jnp.int32)}
    if published:
        fn = lambda p, b: model.stateful_loss_fn(  # noqa: E731
            p, model.init_state(), b)[0]
    else:
        fn = lambda p, b: model.loss_fn(p, b)[0]  # noqa: E731
    jaxpr = str(jax.make_jaxpr(fn)(params, batch))
    convs = jaxpr.count("conv_general_dilated[")
    assert convs == 20 and jaxpr.count("dot_general[") == 1
    assert jaxpr.count("precision=(Precision.HIGHEST") == convs + 1


def _mesh():
    return Mesh(np.array(jax.devices()[:CHIPS]), ("data",),
                axis_types=(AxisType.Auto,))


def _ddp_step(model, params, state, batch, lr=1.0):
    """One stateful DDP step on four chips; returns the loss, the gradient
    (read back from the parameters) and each chip's running statistics."""
    mesh = _mesh()
    step = ddp.make_ddp_train_step(model.stateful_loss_fn, mesh, lr=lr,
                                   stateful=True)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    p = jax.device_put(params, repl)
    new_p, new_s, _, loss = step(p, jax.device_put(state, repl),
                                 ddp.init_error_feedback(p),
                                 jax.device_put(batch, split))
    grads = jax.tree.map(lambda a, b: (np.asarray(a, np.float64) - b) / lr,
                         params, new_p)
    chips = [jax.tree.map(lambda x: np.asarray(x.addressable_shards[i].data),
                          new_s) for i in range(CHIPS)]
    return float(loss), grads, chips


def test_stateful_ddp_step_matches_per_chip_reference(setup):
    """Four chips of four images each: the loss and gradient of the
    per-chip-BatchNorm reference, and on every chip the running statistics
    updated from chip 0's images."""
    model, params, state, batch = setup
    loss, grads, chips = _ddp_step(model, params, state, batch)
    with jax.default_matmul_precision("highest"):
        (want, want_new), want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, state, batch, 16 // CHIPS),
            has_aux=True))(params)
    assert abs(loss - float(want)) / float(want) < LOSS_TOL
    assert gap(grads, want_grads) < GRAD_TOL
    for chip in chips:
        assert gap(chip, want_new) < STATS_TOL


@pytest.mark.parametrize("fault", ["averaged", "left_out"])
def test_a_broadcast_that_averages_or_is_left_out_fails(setup, monkeypatch,
                                                         fault):
    model, params, state, batch = setup
    if fault == "averaged":
        monkeypatch.setattr(ddp, "broadcast_from_first",
                            lambda t, axis, n: jax.lax.pmean(t, axis))
    else:
        monkeypatch.setattr(ddp, "broadcast_from_first",
                            lambda t, axis, n: t)
    _, _, chips = _ddp_step(model, params, state, batch)
    with jax.default_matmul_precision("highest"):
        _, want_new = reference_loss(params, state, batch, 16 // CHIPS)
    assert max(gap(chip, want_new) for chip in chips) > 100 * STATS_TOL


def test_report_counts_the_gradients_and_the_buffer_broadcast():
    """The captured step at 200 classes: one bucketed all-reduce of every
    gradient and the loss, 45,116,452 B, and the 38,400-byte buffer
    forwarded from chip 0 over three collective-permutes; compiled equal
    to traced, and each chip's bytes in the matrices."""
    model = ResNet18(200, **PUBLISHED)
    mesh = _mesh()
    step = ddp.make_ddp_train_step(model.stateful_loss_fn, mesh,
                                   bucket_mb=25.0, stateful=True)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = lambda t, s: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), t)
    params = shapes(model.shapes(), repl)
    state = shapes(jax.eval_shape(model.init_state), repl)
    batch = {"images": jax.ShapeDtypeStruct((8, SIDE, SIDE, 3), jnp.float32,
                                            sharding=split),
             "labels": jax.ShapeDtypeStruct((8,), jnp.int32, sharding=split)}
    sess = MonitorSession(mesh=mesh, name="ddp-resnet18-bn")
    cap = sess.capture(step, params, state, params, batch)
    compiled, traced = {}, {}
    for op in cap.ops:
        compiled[op.kind] = compiled.get(op.kind, 0) + op.payload_bytes
    for ev in cap.traced:
        traced[ev.primitive] = traced.get(ev.primitive, 0) + ev.payload_bytes
    assert compiled == {"all-reduce": 45_116_452,
                        "collective-permute": 3 * 38_400}
    assert traced == {"psum": 45_116_452, "ppermute": 3 * 38_400}
    assert sorted(op.source_target_pairs[0] for op in cap.ops
                  if op.kind == "collective-permute") == [(0, 1), (1, 2),
                                                          (2, 3)]
    rep = sess.report()
    chain = np.zeros((CHIPS + 1, CHIPS + 1))
    for k in range(1, CHIPS):
        chain[k, k + 1] = 38_400
    assert np.array_equal(rep.per_primitive["collective-permute"], chain)
    ar = np.asarray(rep.per_primitive["all-reduce"])
    assert np.all(ar[1:, 1:].sum(axis=1) == 2 * 3 / 4 * 45_116_452)
    assert np.array_equal(rep.matrix, ar + chain)
