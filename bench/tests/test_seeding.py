"""Draws from the seed: the reference can make again, layer by layer, the
weights the benchmark made in one call, and large seeds are whole keys."""
import jax
import jax.numpy as jnp
import numpy as np

import harness
import seeding
import tiny


def test_stacked_draw_equals_each_layer_drawn_alone():
    stacked = jax.jit(lambda: seeding.stacked_normal(seeding.root_key(7), "w", 3, (4, 5),
                                                     0.5))()
    for i in range(3):
        one = jax.jit(lambda i: seeding.normal(seeding.root_key(7), "w", (4, 5), 0.5, i))(i)
        np.testing.assert_array_equal(np.asarray(stacked[i]),
                                      np.asarray(one))


def test_seeds_beyond_32_bits_differ():
    a = seeding.normal(seeding.root_key(2**31 + 5), "w", (8,), 1.0)
    b = seeding.normal(seeding.root_key(5), "w", (8,), 1.0)
    c = seeding.normal(seeding.root_key(2**32 + 5), "w", (8,), 1.0)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(b), np.asarray(c))
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(seeding.normal(seeding.root_key(2**31 + 5), "w", (8,), 1.0)))


def test_granite_reference_weights_are_the_served_ones():
    cell = tiny.tiny_cell("granite-3-2b.decode-b32")
    mod, c = cell.config_module, cell.config
    model = mod.build_model(c)
    params = mod.make_params(c, seeding.root_key(11), None)
    assert jax.tree.structure(params) == jax.tree.structure(model.shapes())
    for layer in range(c["num_hidden_layers"]):
        w = mod.layer_weights(c, seeding.root_key(11), layer)
        np.testing.assert_array_equal(
            np.asarray(params["layers"]["attn"]["wq"][layer], np.float32),
            np.asarray(w["attn/wq"]))
        np.testing.assert_array_equal(
            np.asarray(params["layers"]["mlp"]["wi"][layer], np.float32),
            np.asarray(w["mlp/wi"]))
    assert params["layers"]["mlp"]["wi"].dtype == jnp.bfloat16


def test_resnet_weights_have_the_program_tree():
    cell = tiny.load_cell("resnet18-ddp.4chip")
    mod = cell.config_module
    shapes = jax.eval_shape(lambda: mod.make_weights(cell.config, seeding.root_key(0)))
    model = mod.build_model(cell.config)
    assert jax.tree.structure(shapes) == jax.tree.structure(model.shapes())
    assert ([x.shape for x in jax.tree.leaves(shapes)]
            == [x.shape for x in jax.tree.leaves(model.shapes())])
