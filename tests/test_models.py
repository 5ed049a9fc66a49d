"""Golden shape/dtype tests for the Flax model zoo (reference zoos:
NESTED/model/cifar_resnet.py, imagenet_resnet.py, vgg.py)."""

import jax
import jax.numpy as jnp
import pytest

from ddp_classification_pytorch_tpu.config import ModelConfig
from ddp_classification_pytorch_tpu.models import (
    FEAT_DIMS, build_model, resnet18, resnet50, vgg19_bn,
)
from ddp_classification_pytorch_tpu.models.factory import (
    ArcFaceModel, ClassifierModel, NestedModel,
)


def _init_and_apply(model, *inputs, apply_inputs=None):
    """(variables, output) as shapes and dtypes, which is all these tests
    assert: traced with eval_shape, nothing is drawn and no forward runs."""
    def both():
        variables = model.init(jax.random.key(0), *inputs, train=False)
        return variables, model.apply(variables, *(apply_inputs or inputs),
                                      train=False)
    return jax.eval_shape(both)


@pytest.mark.parametrize("factory,feat", [(resnet18, 512), (resnet50, 2048)])
def test_resnet_imagenet_feature_shapes(factory, feat):
    x = jnp.zeros((2, 64, 64, 3))  # small spatial for test speed
    model = factory(num_classes=0, variant="imagenet", dtype=jnp.float32)
    _, out = _init_and_apply(model, x)
    assert out.shape == (2, feat)
    assert out.dtype == jnp.float32


def test_resnet_cifar_stem_keeps_resolution():
    x = jnp.zeros((2, 32, 32, 3))
    model = resnet18(num_classes=10, variant="cifar", dtype=jnp.float32)
    variables, out = _init_and_apply(model, x)
    assert out.shape == (2, 10)
    # cifar stem: no /2 stem stride and no maxpool → layer1 sees 32×32
    stem_bn = variables["batch_stats"]["bn_stem"]["mean"]
    assert stem_bn.shape == (64,)


def test_resnet_classifier_logits():
    x = jnp.zeros((2, 64, 64, 3))
    model = resnet18(num_classes=7, variant="imagenet", dtype=jnp.float32)
    _, out = _init_and_apply(model, x)
    assert out.shape == (2, 7)


def test_batch_stats_update_in_train_mode():
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    model = resnet18(num_classes=0, variant="cifar", dtype=jnp.float32)
    variables = jax.jit(lambda: model.init(jax.random.key(0), x, train=False))()
    out, mutated = jax.jit(lambda v: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables)
    before = variables["batch_stats"]["bn_stem"]["mean"]
    after = mutated["batch_stats"]["bn_stem"]["mean"]
    assert not jnp.allclose(before, after)


def test_freeze_bn_no_stat_update():
    """NESTED freeze-BN (model/model.py:44-55): train forward must use running
    stats and leave them unchanged."""
    from ddp_classification_pytorch_tpu.models.resnet import resnet18 as r18

    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    model = r18(num_classes=0, variant="cifar", dtype=jnp.float32, freeze_bn=True)
    variables = jax.jit(lambda: model.init(jax.random.key(0), x, train=False))()
    _, mutated = jax.jit(lambda v: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables)
    before = variables["batch_stats"]["bn_stem"]["mean"]
    after = mutated.get("batch_stats", {}).get("bn_stem", {}).get("mean", before)
    assert jnp.allclose(before, after)


def test_vgg19_bn_feature_and_logits():
    x = jnp.zeros((2, 32, 32, 3))
    model = vgg19_bn(num_classes=0, dtype=jnp.float32)
    _, out = _init_and_apply(model, x)
    assert out.shape == (2, 4096)


def test_build_model_fc_head():
    cfg = ModelConfig(arch="resnet18", dtype="float32")
    model = build_model(cfg, num_classes=11)
    assert isinstance(model, ClassifierModel)
    x = jnp.zeros((2, 64, 64, 3))
    _, out = _init_and_apply(model, x)
    assert out.shape == (2, 11)


def test_build_model_arcface_head():
    cfg = ModelConfig(arch="resnet18", head="arcface", dtype="float32")
    model = build_model(cfg, num_classes=11)
    assert isinstance(model, ArcFaceModel)
    x = jnp.zeros((2, 64, 64, 3))
    labels = jnp.zeros((2,), jnp.int32)
    _, out = _init_and_apply(model, x, labels)
    assert out.shape == (2, 11)
    _, scores = _init_and_apply(model, x, labels, apply_inputs=(x, None))
    assert scores.shape == (2, 11)


def test_build_model_nested_head():
    cfg = ModelConfig(arch="resnet18", head="nested", dtype="float32", freeze_bn=True)
    model = build_model(cfg, num_classes=11)
    assert isinstance(model, NestedModel)
    x = jnp.zeros((2, 32, 32, 3))
    mask = jnp.ones((1, FEAT_DIMS["resnet18"]))
    _, out = _init_and_apply(model, x, apply_inputs=(x, mask))
    assert out.shape == (2, 11)
