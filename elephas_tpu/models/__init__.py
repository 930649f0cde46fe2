"""Model zoo + registry.

The reference ships no models — users hand it compiled Keras models, and
its examples build MNIST MLP/CNN, IMDB LSTM, CIFAR ResNet (BASELINE.md
configs). The rebuild provides those architectures as flax modules so the
five benchmark configs are runnable out of the box, plus a *registry* so
architectures serialize by name (the TPU-native analogue of Keras's
``model_to_json`` arch string — SURVEY.md §2.1 serialization row).

Three of the registered families are language models that
``serving.InferenceEngine`` serves: ``transformer_lm`` (GPT-2's block),
``jamba_lm`` (Mamba-1 mixers with an attention layer every few; per-slot
state beside paged K/V; the engine refuses it prefix adoption, fork,
handoff, speculation and ``shard_serving``) and ``latent_moe_lm`` (latent
attention with rotary positions and a routed expert layer; it takes
``experts_held=(first, count)`` beside ``n_routed_experts`` and computes
the held experts' part, ``(0, n_routed_experts)`` being the uncut layer;
the engine refuses it speculation and ``shard_serving``), and
``minicpm_sala_lm`` (lightning linear-attention layers with a matrix state a
slot beside block-sparse attention that selects whole pages of the pool;
per-slot state, so refused as ``jamba_lm`` is), and ``granite_hybrid_lm``
(Mamba-2 mixers whose matrix state decays by a step each token sets, beside
grouped attention, a routed layer told which experts it holds and a shared
expert on every layer; per-slot state, so refused as ``jamba_lm`` is).
"""

from __future__ import annotations

from typing import Callable, Dict

_REGISTRY: Dict[str, Callable] = {}


def register_model(name: str):
    """Register a module builder under ``name`` for arch serialization."""

    def wrap(builder: Callable) -> Callable:
        _REGISTRY[name] = builder
        return wrap.__wrapped__ if hasattr(wrap, "__wrapped__") else builder

    return wrap


def get_model(name: str, **kwargs):
    """Build a registered module; tags it so its arch serializes by name."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    module = _REGISTRY[name](**kwargs)
    config = {"name": name, "kwargs": kwargs}
    try:
        object.__setattr__(module, "_elephas_config", config)
    except AttributeError:  # exotic Module subclass with __slots__
        pass
    return module


def registered_models():
    return sorted(_REGISTRY)


# Import for side effect: populate the registry.
from elephas_tpu.models import (  # noqa: E402,F401
    mlp, cnn, resnet, lstm, transformer, jamba, latent_moe, minicpm_sala, granite_hybrid,
)
from elephas_tpu.models.mlp import MLP  # noqa: E402,F401
from elephas_tpu.models.cnn import SimpleCNN  # noqa: E402,F401
from elephas_tpu.models.resnet import ResNet18  # noqa: E402,F401
from elephas_tpu.models.lstm import LSTMClassifier  # noqa: E402,F401
from elephas_tpu.models.jamba import JambaLM  # noqa: E402,F401
from elephas_tpu.models.latent_moe import LatentMoELM  # noqa: E402,F401
from elephas_tpu.models.minicpm_sala import MiniCPMSALA  # noqa: E402,F401
from elephas_tpu.models.granite_hybrid import GraniteHybridLM  # noqa: E402,F401
from elephas_tpu.models.transformer import (  # noqa: E402,F401
    TransformerLM,
    generate,
)
