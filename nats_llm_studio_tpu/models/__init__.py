"""Model architectures in pure functional JAX.

The reference's "model zoo" is LM Studio's external catalog — models are
opaque GGUF ids shelled out to `lms get` (/root/reference/nats_llm_studio.go:51)
and executed by llama.cpp. Here the architectures the north-star configs name
(BASELINE.md: Llama-3 8B/70B, Granite-3.0-2B, Mixtral-8x7B) are in-tree.

Params are pytrees with all per-layer weights stacked on a leading [L] axis so
the layer stack runs as one compiled ``lax.scan`` block (one XLA compilation
unit regardless of depth) and sharding rules address whole stacks at once.
"""

from .config import ModelConfig
from .llama import forward, init_params

__all__ = ["ModelConfig", "forward", "init_params"]
