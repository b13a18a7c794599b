"""The grouped form through the live batcher, on the CPU: the toy of the
latent-attention / routed-expert family served on TWO slots with a prefill
chunk of 16 tokens, so that every prefill dispatch (16 rows x top-2 = 32
picks over 8 experts) takes the grouped form and a decode burst (2 rows x
top-2 = 4 picks) the hit list. A prompt of three chunks, then decoded, must
give the token ids of the same engine with ``expert_path`` answering "dense"
for every call (the dense branch itself; the program has no switch for it),
and so must a run whose verify bundles (2 slots x (3 drafts + 1) = 8 rows)
go through the grouped form."""

import json
from pathlib import Path

import jax
import pytest

from benchmark import run
from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models import mla_moe
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.obs import spans
from nats_llm_studio_tpu.serve.batcher import ContinuousBatcher

from conftest import async_test

ROOT = Path(__file__).resolve().parents[1]
CHUNK, SEQ, NEW = 16, 128, 12
# three chunks (16 + 16 + 8), and text that repeats so that prompt-lookup drafts
PROMPT = [40 + i % 5 for i in range(2 * CHUNK + 8)]


@pytest.fixture(scope="module")
def model():
    ref = run.load_module(ROOT / "benchmark/references/mla_moe_mhc.py")
    conf = json.loads((ROOT / "benchmark/tests/rehearsal/configs/tiny-mla.json").read_text())
    cfg = ref.model_config(conf, SEQ).with_(dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


async def serve(model, spec_k: int = 0):
    cfg, params = model
    spans.clear()
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=SEQ, prefill_chunk=CHUNK,
                          decode_burst=4, spec_decode_k=spec_k)
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=NEW)
        tokens = [t async for t in b.submit(PROMPT, sp)]
    finally:
        b.stop()
    admits = [attrs for _, _, _, attrs in spans.records(name="batcher.admit")]
    programs = {name: h.snapshot().count for name, h in b.stats.program_histograms().items()}
    return tokens, b.stats, admits, programs, page_of(b)


def page_of(b) -> str:
    """The worker's page with ``b`` as its one loaded engine's batcher."""
    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.serve.worker import Worker

    class Engine:
        batcher = b

    class Registry:
        def stats(self):
            return {}

        def loaded_engines(self):
            return {"toy/latent": Engine()}

    return Worker(WorkerConfig(), Registry()).render_prometheus()


@async_test(timeout=240.0)  # 59 s beside five workers on an empty compile cache
async def test_a_prompt_of_three_chunks_through_the_grouped_form_is_dense_dispatch(
        model, monkeypatch):
    tokens, stats, admits, programs, page = await serve(model)
    assert len(tokens) == NEW
    assert admits and all(a["experts"] == "grouped" for a in admits), admits
    assert stats.expert_path == "hit_list"
    # the counter's own definition: the static [B, T] of every prefill
    # dispatch, padding included: two whole chunks and the last one padded
    assert programs["prefill1"] == 3 and stats.expert_prefill_rows_grouped == 3 * CHUNK
    assert stats.expert_prefill_rows_dense == 0 and stats.expert_prefill_rows_hit_list == 0
    rows = {line.split('path="')[1].split('"')[0]: int(line.rsplit(" ", 1)[1])
            for line in page.splitlines() if line.startswith("lmstudio_moe_prefill_rows_total{")}
    assert rows == stats.expert_prefill_rows() == {"grouped": 3 * CHUNK, "dense": 0, "hit_list": 0}

    # one verify bundle at least through the grouped form: the same tokens
    spec_tokens, spec_stats, _, spec_programs, _ = await serve(model, spec_k=3)
    assert spec_stats.spec_verifies > 0, spec_programs
    assert mla_moe.expert_path(model[0], 2 * 4, model[1]["blocks"]["moe"]) == "grouped"
    assert spec_tokens == tokens

    # the same engine, every call answered "dense"
    from nats_llm_studio_tpu.models import experts

    for module in (experts, mla_moe):   # the batcher asks the first, the model file the second
        monkeypatch.setattr(module, "expert_path", lambda *a, **k: "dense")
    dense_tokens, dense_stats, dense_admits, _, _ = await serve(model)
    assert dense_stats.expert_path == "dense"
    assert all(a["experts"] == "dense" for a in dense_admits)
    assert dense_stats.expert_prefill_rows_dense == stats.expert_prefill_rows_grouped
    assert dense_stats.expert_prefill_rows_grouped == 0
    assert dense_tokens == tokens
