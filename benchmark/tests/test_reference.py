"""The plain reference against the program's Flax model at a tiny size, in
float32 on the CPU: logits, loss and gradient norm, for both parameter
layouts (stacked by `scan_blocks`, and one tree a block)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import vit as reference

SHAPE = dict(image_size=32, patch_size=8, embed_dim=64, num_heads=2,
             num_blocks=3, num_classes=10)


@pytest.mark.parametrize("scan_blocks", [True, False])
def test_reference_equals_the_flax_model(scan_blocks):
    import optax
    from vitax.config import Config
    from vitax.models import build_model
    from vitax.train.step import prepare_images
    cfg = Config(**SHAPE, dtype="float32", scan_blocks=scan_blocks,
                 batch_size=4).validate()
    model = build_model(cfg)
    images = jax.random.bits(jax.random.key(1), (4, 32, 32, 3), jnp.uint8)
    labels = jnp.array([1, 7, 3, 3], jnp.int32)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 32, 32, 3), jnp.float32), True)
    # biases and LayerNorm offsets start at zero: move them, or a reference
    # that dropped one would still agree
    leaves, tree = jax.tree.flatten(variables)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    variables = jax.tree.unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])

    def program_loss(v):
        logits = model.apply(v, prepare_images(images), True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), logits

    (want_loss, want_logits), grads = jax.value_and_grad(
        program_loss, has_aux=True)(variables)
    want_norm = optax.global_norm(grads)
    shape = reference.shape_of(SHAPE)
    got_logits = reference.logits(variables, images, **shape)
    got_loss, got_norm = reference.loss_and_grad_norm(variables, images,
                                                      labels, **shape)
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(got_norm, want_norm, rtol=1e-4)
    np.testing.assert_allclose(
        reference.loss(variables, images, labels, **shape), want_loss,
        rtol=1e-5)
