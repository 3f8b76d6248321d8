"""Functional model zoo.

Every model is a lightweight stateless object with two methods:

- ``init(rng) -> params``   — build the parameter pytree.
- ``apply(params, x) -> logits`` — pure forward pass, safe under
  ``jax.jit`` / ``jax.grad`` / ``shard_map``.

Models carry no parameters themselves (params are explicit pytrees),
so the same model object can be used for training, checkpointing, and
serving, and params can be sharded over a mesh without the model
object knowing.

Registry: ``get_model(name, **kwargs)`` builds a model by config name.
"""

from __future__ import annotations

from mlapi_tpu.utils.registry import Registry

_REGISTRY: Registry = Registry("model")
register_model = _REGISTRY.register


def get_model(name: str, **kwargs):
    """Build a model by registry name (e.g. ``linear``, ``mlp``)."""
    return _REGISTRY.get(name)(**kwargs)


def model_registered(name: str) -> bool:
    return name in _REGISTRY


def registered_models() -> list[str]:
    return _REGISTRY.names()


# Import model modules for their registration side effects.
from mlapi_tpu.models import linear as _linear  # noqa: E402,F401
from mlapi_tpu.models import mlp as _mlp  # noqa: E402,F401
from mlapi_tpu.models import wide_deep as _wide_deep  # noqa: E402,F401
from mlapi_tpu.models import bert as _bert  # noqa: E402,F401
from mlapi_tpu.models import gpt as _gpt  # noqa: E402,F401
from mlapi_tpu.models import llama as _llama  # noqa: E402,F401
from mlapi_tpu.models import kimi_linear as _kimi_linear  # noqa: E402,F401
from mlapi_tpu.models import laguna as _laguna  # noqa: E402,F401
from mlapi_tpu.models.bert import BertClassifier  # noqa: E402,F401
from mlapi_tpu.models.gpt import GptLM  # noqa: E402,F401
from mlapi_tpu.models.kimi_linear import KimiLinearLM  # noqa: E402,F401
from mlapi_tpu.models.laguna import LagunaLM  # noqa: E402,F401
from mlapi_tpu.models.lora import LoraModel  # noqa: E402,F401
from mlapi_tpu.models.quantized import QuantizedModel  # noqa: E402,F401
from mlapi_tpu.models.linear import LinearClassifier  # noqa: E402,F401
from mlapi_tpu.models.llama import LlamaLM  # noqa: E402,F401
from mlapi_tpu.models.mlp import MLPClassifier  # noqa: E402,F401
from mlapi_tpu.models.wide_deep import WideDeepClassifier  # noqa: E402,F401
