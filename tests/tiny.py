"""The tiny Config the end-to-end tests start from (tests/README.md: a test
builds the smallest program on which its assertion can fail). One step costs
about half a second on the virtual 8-device mesh whatever the image size, and
one compiled program a few seconds, so the defaults are few steps of the
shallowest ResNet: a test overrides, beside its assertion, only what that
assertion needs (more steps, a head, a mesh axis)."""

import jax
import numpy as np

from ddp_classification_pytorch_tpu.config import get_preset
from ddp_classification_pytorch_tpu.models import factory, resnet, vit

# The shallow nets, under names of their own (importing this module registers
# them; the published names keep their meaning): ResNet-18's blocks at one a
# stage, not two, and ViT-T at a depth of 4, not 12. Every block of a kind is
# the same program text, so what a test asserts of the ring, the pipeline
# stages (4 = 2 x 2 = 4 x 1), the experts, remat, a head, a mesh or the loop
# around the step, it asserts of the shallow net at a third less compile.
# Not for a test that holds a network to torch's, to a baseline or to a
# parameter count: those say `resnet18` / `vit_t16` and get them.
resnet._DEPTHS["resnet10"] = (resnet.BasicBlock, (1, 1, 1, 1))
resnet.FEAT_DIMS["resnet10"] = resnet.FEAT_DIMS["resnet18"]
factory._RESNETS["resnet10"] = resnet._factory("resnet10")
vit.VIT_CONFIGS["vit_t16_d4"] = (16, 192, 4, 3)
vit.FEAT_DIMS["vit_t16_d4"] = 192


def tiny_cfg(workload: str = "baseline", out_dir=None, epochs: int = 1):
    """`workload`'s preset cut to `resnet10` (CIFAR stem) in float32 on 32
    synthetic 32 px images of 4 classes: two steps of 16 an epoch, nothing
    written unless the test asks."""
    cfg = get_preset(workload)
    cfg.data.dataset = "synthetic"
    cfg.data.image_size = 32
    cfg.data.num_classes = 4
    cfg.data.synthetic_size = 32
    cfg.data.batch_size = 16
    cfg.data.num_workers = 1
    cfg.model.arch = "resnet10"
    cfg.model.variant = "cifar"
    cfg.model.dtype = "float32"
    cfg.optim.warmup_iters = 0
    cfg.run.epochs = epochs
    cfg.run.log_every = 4
    cfg.run.write_records = False
    cfg.run.save_every_epoch = False
    cfg.run.save_best_only = False
    if out_dir is not None:
        cfg.run.out_dir = str(out_dir)
    return cfg


def zero_variables(model, *init_args, **init_kw):
    """`model.init(...)`'s variables as host zeros of the same shapes and
    dtypes, from a trace: nothing is drawn and no forward runs. For the tests
    that overwrite every leaf (the torch importers': a leaf the converter
    misses then stays 0 and shows)."""
    shapes = jax.eval_shape(lambda: model.init(*init_args, **init_kw))
    return jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype), shapes)
