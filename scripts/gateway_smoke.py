"""Gateway e2e smoke: embedded broker + one real-model worker + the OpenAI
HTTP gateway, exercised with raw sockets — one streaming SSE chat and one
JSON-schema constrained completion. Exits non-zero on any broken contract.

CI runs this as its own step; locally:

    JAX_PLATFORMS=cpu python scripts/gateway_smoke.py
"""

import asyncio
import json
import sys
import tempfile
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.lib.model_files import byte_level_tokenizer_md  # noqa: E402
from nats_llm_studio_tpu.config import WorkerConfig  # noqa: E402
from nats_llm_studio_tpu.gateway import Gateway  # noqa: E402
from nats_llm_studio_tpu.models.config import ModelConfig  # noqa: E402
from nats_llm_studio_tpu.models.export import export_params_to_gguf  # noqa: E402
from nats_llm_studio_tpu.models.llama import init_params  # noqa: E402
from nats_llm_studio_tpu.serve import Worker  # noqa: E402
from nats_llm_studio_tpu.serve.registry import LocalRegistry  # noqa: E402
from nats_llm_studio_tpu.store.manager import ModelStore  # noqa: E402
from nats_llm_studio_tpu.transport import EmbeddedBroker, connect  # noqa: E402

MODEL = "ci/gw-smoke"

# integer/enum-only properties: the compiled language is length-bounded, so
# max_tokens can never truncate the document — validity is guaranteed
SCHEMA = {
    "type": "object",
    "properties": {
        "age": {"type": "integer"},
        "tag": {"enum": ["alpha", "beta"]},
    },
}


async def post_chat(port: int, body: dict) -> tuple[int, dict, bytes]:
    """Raw-socket POST /v1/chat/completions; the gateway answers with
    ``Connection: close``, so the body is simply everything until EOF."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        raw = json.dumps(body).encode()
        writer.write(
            (
                "POST /v1/chat/completions HTTP/1.1\r\nHost: smoke\r\n"
                f"Content-Length: {len(raw)}\r\n\r\n"
            ).encode()
            + raw
        )
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        lines = head.decode("latin-1").split("\r\n")[1:]
        headers = {}
        for line in lines:
            if ":" in line:
                k, v = line.split(":", 1)
                headers[k.strip().lower()] = v.strip()
        payload = b""
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                break
            payload += chunk
        return status, headers, payload
    finally:
        writer.close()


async def main() -> None:
    with tempfile.TemporaryDirectory() as td:
        models_dir = Path(td) / "models"
        cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
        (models_dir / MODEL).mkdir(parents=True)
        export_params_to_gguf(
            models_dir / MODEL / "m.gguf", init_params(cfg, jax.random.PRNGKey(5)), cfg,
            name=MODEL, tokenizer_md=byte_level_tokenizer_md(cfg.vocab_size))
        broker = await EmbeddedBroker().start()
        worker = Worker(
            WorkerConfig(nats_url=broker.url),
            LocalRegistry(ModelStore(models_dir), dtype="float32"),
        )
        await worker.start()
        nc = await connect(broker.url)
        gw = await Gateway(nc, port=0).start()
        try:
            # 1. streaming SSE chat
            status, headers, payload = await post_chat(gw.port, {
                "model": MODEL,
                "messages": [{"role": "user", "content": "smoke test"}],
                "max_tokens": 8, "temperature": 0.0, "stream": True,
            })
            assert status == 200, (status, payload[:200])
            assert headers.get("content-type") == "text/event-stream", headers
            events = [
                e[len("data: "):]
                for e in payload.decode().split("\n\n")
                if e.startswith("data: ")
            ]
            assert events[-1] == "[DONE]", events[-1]
            chunks = [json.loads(e) for e in events[:-1]]
            text = "".join(
                c["choices"][0]["delta"].get("content", "") for c in chunks
            )
            assert text, "streaming produced no content"
            # random tiny weights rarely emit EOS inside 8 tokens
            fin = chunks[-1]["choices"][0]["finish_reason"]
            assert fin in ("stop", "length"), chunks[-1]
            print(f"streaming ok: {len(chunks)} chunks, {len(text)} chars")

            # 2. constrained (json_schema) completion at temperature > 0:
            # the response MUST be a schema-valid document
            status, _, payload = await post_chat(gw.port, {
                "model": MODEL,
                "messages": [{"role": "user", "content": "emit a person"}],
                "max_tokens": 80, "temperature": 0.9, "seed": 5,
                "response_format": {
                    "type": "json_schema",
                    "json_schema": {"name": "person", "schema": SCHEMA},
                },
            })
            assert status == 200, (status, payload[:200])
            resp = json.loads(payload)
            doc = json.loads(resp["choices"][0]["message"]["content"])
            assert isinstance(doc, dict), doc
            assert isinstance(doc["age"], int), doc
            assert doc["tag"] in ("alpha", "beta"), doc
            assert resp["choices"][0]["finish_reason"] == "stop", resp
            print(f"constrained ok: {resp['choices'][0]['message']['content']}")
        finally:
            await gw.stop()
            await nc.close()
            await worker.drain()
            await broker.stop()
    print("gateway smoke passed")


if __name__ == "__main__":
    asyncio.run(main())
