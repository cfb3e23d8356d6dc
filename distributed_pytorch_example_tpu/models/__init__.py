"""Model zoo: the string registry the CLI builds its model from.

- ``mlp``, ``simplenet``: ``mlp.SimpleNet``, 784-256-256-10 MLP, exact parity
  with the reference model (reference train.py:32-50).
- ``resnet18``, ``resnet50``: ``resnet.ResNet18/50``, CIFAR-10 / ImageNet.
- ``vit-b16``, ``vit``: ``vit.ViTB16``.
- ``bert-base``, ``bert``: ``bert.BertBase`` with MLM head.
- ``gpt2``, ``gpt2-124m``: ``gpt2.GPT2`` decoder LM (optional MoE MLPs,
  pipeline stages).
- ``llama``, ``llama-tiny``: ``llama.Llama``, RMSNorm / RoPE / SwiGLU / GQA
  decoder (optional Mixtral-style MoE).
- ``lfm2-8b-a1b``: ``lfm2.Lfm2``, gated short convolutions and grouped-query
  attention in one stack, dropless sigmoid top-4 experts; a deployment's
  share by ``layers_kept``, ``experts_first`` / ``experts_held`` and
  ``vocab_size``.
- ``joyai-llm-flash``: ``joyai.JoyaiLlmFlash``, latent attention (MLA) at
  head dims 192 / 128, a shared expert beside sigmoid top-8 of 256 routed
  ones, a multi-token-prediction module on the untied head; the same three
  fields say a deployment's share (``--layers-kept``, ``--experts-held``,
  ``--vocab-slice``), the prediction module stays with the head.

All models are flax ``nn.Module``s taking NHWC images or int32 token ids and
routing attention through ``ops.attention`` so kernel/parallelism dispatch is
centralized. ``models/moe.py`` (capacity and dropless expert layers),
``models/stacked.py`` (layer-stacked pipelined decoders) and
``models/transformer.py`` are their shared parts.

``get_model(name, **overrides)`` builds one; ``model_has(name, attribute)``
answers a caller that takes a model by what it has (a field such as
``moe_experts`` or ``layers_kept``, a method such as ``head_params``) and
not by its name.
"""

from __future__ import annotations

from typing import Any

from distributed_pytorch_example_tpu.models.mlp import SimpleNet  # noqa: F401


def model_class(name: str):
    """The registry: a model's class (or its family's factory) by name."""
    name = name.lower().replace("_", "-")
    if name in ("mlp", "simplenet"):
        return SimpleNet
    if name in ("resnet18", "resnet-18"):
        from distributed_pytorch_example_tpu.models.resnet import ResNet18

        return ResNet18
    if name in ("resnet50", "resnet-50"):
        from distributed_pytorch_example_tpu.models.resnet import ResNet50

        return ResNet50
    if name in ("vit-b16", "vit-b-16", "vit"):
        from distributed_pytorch_example_tpu.models.vit import ViTB16

        return ViTB16
    if name in ("bert-base", "bert"):
        from distributed_pytorch_example_tpu.models.bert import BertBase

        return BertBase
    if name in ("gpt2", "gpt-2", "gpt2-124m"):
        from distributed_pytorch_example_tpu.models.gpt2 import GPT2

        return GPT2
    if name in ("llama", "llama-tiny"):
        from distributed_pytorch_example_tpu.models.llama import Llama

        return Llama
    if name in ("lfm2-8b-a1b", "lfm2"):
        from distributed_pytorch_example_tpu.models.lfm2 import Lfm2

        return Lfm2
    if name in ("joyai-llm-flash", "joyai"):
        from distributed_pytorch_example_tpu.models.joyai import JoyaiLlmFlash

        return JoyaiLlmFlash
    raise ValueError(f"Unknown model: {name!r}")


def get_model(name: str, **overrides: Any):
    """Build a model by registry name."""
    return model_class(name)(**overrides)


def model_has(name: str, attribute: str) -> bool:
    """Whether the model of this name has the field or method."""
    cls = model_class(name)
    return (
        attribute in getattr(cls, "__dataclass_fields__", {})
        or hasattr(cls, attribute)
    )
