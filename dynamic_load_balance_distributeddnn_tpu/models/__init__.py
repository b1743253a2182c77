"""Flax model zoo.

One family per reference architecture (Net/ directory): MnistNet, ResNet,
DenseNet, GoogLeNet, RegNet, Transformer LM. All CNNs use GroupNorm — the
reference's deliberate choice (Net/Resnet.py:11 et al.) because BatchNorm
statistics would be skewed by unequal per-worker batch sizes; on TPU this also
avoids cross-replica batch-stat sync. Layout is NHWC (TPU-native).

``build_model(name)`` mirrors the reference's model selection switch
(dbs.py:345-362): resnet -> ResNet-101, densenet -> DenseNet-121,
googlenet -> GoogLeNet, regnet -> RegNetY-400MF, plus mnistnet and
transformer.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import flax.linen as nn


# model_type of a published config.json -> the module's class in models/<model_type>.py
PUBLISHED_FAMILIES = {"afmoe": "AFMoELM", "qwen3_next": "Qwen3NextLM"}


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    module: nn.Module
    # "logits" -> softmax cross-entropy; "log_probs" -> NLL (dbs.py:371-374)
    output_kind: str
    # "image" (NHWC uint8 pipeline) or "tokens" (LM bptt pipeline)
    input_kind: str
    # non-empty: with train=True the module returns (outputs, a float32 array
    # of this shape that the step carries out beside the loss): the routed
    # model's arrivals, [expert layers, held experts + 1]
    aux_shape: tuple = ()
    # substrings of parameter paths the step's compute-dtype cast leaves in
    # float32 (a router, whose product the published code keeps in float32)
    f32_leaves: tuple = ()
    # the module places its own checkpoints under --remat (one a block); the
    # step then adds none around the whole forward
    own_remat: bool = False
    # the scanned superstep ties each worker's step to the one before it, as
    # workers that share a chip run: left free, XLA:TPU runs the workers'
    # linear-attention layers side by side and holds every worker's
    # activations at once (15.1 GB of temporaries against 8.0 GB at the
    # Qwen3-Next cell's size, compiled for a described v5e; PERF.md, PR 32)
    serial_workers: bool = False

    @property
    def train_aux(self) -> bool:
        return bool(self.aux_shape)


def _cnn_constructor(name: str) -> Callable[..., nn.Module] | None:
    """Family-default names match the reference switch (dbs.py:345-362);
    explicit variants expose every constructor the reference's Net/ files
    define (Net/Resnet.py:91-108, Net/Densenet.py:87-100, Net/RegNet.py:108-141)."""
    from dynamic_load_balance_distributeddnn_tpu.models import (
        densenet,
        googlenet,
        mnistnet,
        regnet,
        resnet,
    )

    table = {
        "mnistnet": mnistnet.MnistNet,
        "resnet": resnet.ResNet101,
        "resnet18": resnet.ResNet18,
        "resnet34": resnet.ResNet34,
        "resnet50": resnet.ResNet50,
        "resnet101": resnet.ResNet101,
        "resnet152": resnet.ResNet152,
        "densenet": densenet.DenseNet121,
        "densenet121": densenet.DenseNet121,
        "densenet169": densenet.DenseNet169,
        "densenet201": densenet.DenseNet201,
        "densenet161": densenet.DenseNet161,
        "googlenet": googlenet.GoogLeNet,
        "regnet": regnet.RegNetY_400MF,
        "regnetx200mf": regnet.RegNetX_200MF,
        "regnetx400mf": regnet.RegNetX_400MF,
        "regnety400mf": regnet.RegNetY_400MF,
    }
    return table.get(name)


def build_model(name: str, num_classes: int = 10, **kw) -> ModelSpec:
    ctor = _cnn_constructor(name)
    if ctor is not None:
        return ModelSpec(name, ctor(num_classes=num_classes), "logits", "image")
    if name in PUBLISHED_FAMILIES:
        # a published decoder, by the `model_type` of its config.json
        from dynamic_load_balance_distributeddnn_tpu.models import afmoe

        family = importlib.import_module(f"{__name__}.{name}")
        pub = afmoe.published(kw["arch"])
        if pub.get("model_type") != name:
            raise ValueError(f"{kw['arch']!r}: its model_type is not {name!r}")
        cfg = family.cut_config(pub, kw["ntoken"], kw.get("layers", ()), kw.get("experts_held"))
        module = getattr(family, PUBLISHED_FAMILIES[name])(cfg, remat=bool(kw.get("remat", False)))
        return ModelSpec(name, module, "logits", "tokens",
                         aux_shape=(family.expert_layers(cfg), cfg.experts_held + 1),
                         f32_leaves=family.F32_LEAVES, own_remat=True,
                         serial_workers=family.SERIAL_WORKERS)
    if name == "transformer":
        from dynamic_load_balance_distributeddnn_tpu.models.transformer import (
            TransformerLM,
        )

        # logits + softmax-xent == the reference's log_softmax + NLL
        # (dbs.py:371-372) — same math, fused-kernel-friendly
        return ModelSpec(name, TransformerLM(**kw), "logits", "tokens")
    raise ValueError(f"unknown model {name!r}")
