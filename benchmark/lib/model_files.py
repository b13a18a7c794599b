"""The header-only GGUF the program finds in its models dir: geometry and a
byte-level tokenizer in the metadata, no tensors. ``LocalRegistry`` reads
both from metadata only, so HBM admission, pool sizing and every batcher
argument stay the program's own; the tensors come from ``weights.py``.

Copied: ``bench.py:2702`` ``byte_level_tokenizer_md`` and the metadata half
of ``chip_smoke.py:75-92``.
"""

from __future__ import annotations

from pathlib import Path

from .traffic import CHAT_TEMPLATE


def byte_level_tokenizer_md(vocab_size: int) -> dict:
    """gpt2-family tokenizer metadata covering all 256 bytes (any text
    encodes, one token per byte), padded with filler tokens to the model's
    vocab; the last id is the eos/control token."""
    from nats_llm_studio_tpu.gguf.constants import TokenType
    from nats_llm_studio_tpu.gguf.tokenizer import _byte_to_unicode

    b2u = _byte_to_unicode()
    tokens = [b2u[b] for b in range(256)]
    while len(tokens) < vocab_size - 1:
        tokens.append(f"<filler_{len(tokens)}>")
    tokens.append("<|eot|>")
    return {
        "tokenizer.ggml.model": "gpt2",
        "tokenizer.ggml.tokens": tokens,
        "tokenizer.ggml.token_type": (
            [int(TokenType.NORMAL)] * (vocab_size - 1) + [int(TokenType.CONTROL)]),
        "tokenizer.ggml.merges": [],
        "tokenizer.ggml.eos_token_id": vocab_size - 1,
        "tokenizer.ggml.add_bos_token": False,
        "tokenizer.chat_template": CHAT_TEMPLATE,
    }


def write_header_gguf(cfg, model_id: str, models_dir: Path) -> Path:
    """``models_dir/<publisher>/<model>/m.gguf`` with metadata only."""
    from nats_llm_studio_tpu.gguf.writer import GGUFWriter
    from nats_llm_studio_tpu.models.export import config_metadata

    path = models_dir / model_id / "m.gguf"
    path.parent.mkdir(parents=True, exist_ok=True)
    w = GGUFWriter(path)
    w.add_dict(config_metadata(cfg, model_id))
    w.add_dict(byte_level_tokenizer_md(cfg.vocab_size))
    w.write()
    return path
