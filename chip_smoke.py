"""The quickest proof that the system still starts on the chip.

Serves Llama-3-8B (full width and depth, seeded random weights) through the
program's own entry points, in this one process: a Q8_0 GGUF written from
``--seed`` -> ``main.start_serve`` (what ``python -m nats_llm_studio_tpu
serve --embedded-broker`` runs: WorkerConfig from the environment,
configure_jax, embedded broker, serving mesh, ModelStore, LocalRegistry,
Worker) -> ``lmstudio.list_models`` / ``health`` / ``chat_model`` over a NATS
socket. WQUANT=int8, bf16 paged KV, every other knob at its default unless
the environment says otherwise.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --chips 4       # tp=4 against MESH_SHAPE=off, nothing else

Every line of stdout is one JSON object. The last is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed; any failure raises and the process exits non-zero. Times printed
here are smoke observations of one cold run, not benchmark results.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
MODEL_ID = "smoke/llama3-8b-q8_0"
# Meta-Llama-3-8B-Instruct's published geometry (ModelConfig's fields): 32
# layers, d=4096, ff=14336, GQA 32q/8kv, head_dim 128, vocab 128256, rope 500k
LLAMA3_8B = dict(
    arch="llama",
    vocab_size=128256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    rope_theta=500000.0,
    max_seq_len=8192,
    dtype="bfloat16",
)
MAX_NEW = 32
# first-token top-k logprobs of tp=4 vs one chip: the same int8 weights and
# bf16 activations, but tp turns every row-sharded contraction (wo, w_down)
# into four partial sums rounded to bf16 before the all-reduce; attention is
# per head, so the heads split changes nothing inside it. The bounds come
# from scripts/tp_reorder_noise.py, a CPU simulation of that reorder alone
# through a random 32-layer model built like this GGUF (logit std 10), made
# independently of any chip reading: per request median 0.4-0.7, p90
# 0.8-1.2, max 1.75 of 128, no growth with prompt length — while a heads
# split that reads the wrong K/V shares no top-5 token at all, and one that
# drops 35 of 547 keys on one shard lands at a median of 2.1. So: every
# request within LOGPROB_TOL; the median of the short-prompt requests and
# the median of the long-prompt ones (flash prefill) each within
# LOGPROB_MEDIAN_TOL; the argmax inside the other side's top-5.
LOGPROB_TOL = 2.5
LOGPROB_MEDIAN_TOL = 1.0
TOP_LOGPROBS = 5
# a prompt this long spans more than one prefill chunk (256): chunked prefill
# and the flash kernels serve it
LONG_TOKENS = 300

SHORT_PROMPTS = [
    "Explain in one sentence what NATS request-reply is.",
    "one two three four " * 6,  # repetition: prompt-lookup spec decode drafts
    "List three uses of a message broker.",
    "What does a continuous batcher do?",
]
LONG_PROMPT = (
    "You are a helpful assistant running on a TPU worker behind a NATS "
    "subject. Summarize the following operating notes. "
    + "The worker loads a GGUF model, quantizes weights to int8, keeps a "
      "paged KV block pool, and answers chat requests in a shared decode "
      "step. " * 3
)


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def generate_gguf(cfg, path: Path, seed: int) -> dict:
    """Write a Q8_0 GGUF of ``cfg``'s geometry from ``seed``: one seeded
    block's bytes queued under all ``n_layers`` names (values do not matter;
    widths, depth and tensor names do), so host memory is one block plus the
    embedding, and the file is the real size."""
    import numpy as np

    from benchmark.lib.model_files import byte_level_tokenizer_md
    from nats_llm_studio_tpu.gguf.constants import GGMLType
    from nats_llm_studio_tpu.gguf.quants import quantize
    from nats_llm_studio_tpu.gguf.writer import GGUFWriter
    from nats_llm_studio_tpu.models.export import config_metadata

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    path.parent.mkdir(parents=True, exist_ok=True)
    w = GGUFWriter(path)
    w.add_dict(config_metadata(cfg, MODEL_ID))
    w.add_dict(byte_level_tokenizer_md(cfg.vocab_size))

    def q8(*shape: int, ascii_rows: float = 1.0) -> tuple:
        # stored [out, in] like llama.cpp writes; N(0, 0.02) like init_params
        x = rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)
        if ascii_rows != 1.0:
            x[32:127] *= np.float32(ascii_rows)  # token id == byte value
        return shape, GGMLType.Q8_0, quantize(x, GGMLType.Q8_0)

    d, ff = cfg.d_model, cfg.d_ff
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ones = (d,), GGMLType.F32, quantize(np.ones((d,), np.float32), GGMLType.F32)
    tensors = {
        "token_embd.weight": q8(cfg.vocab_size, d),
        "output_norm.weight": ones,
        # the head's printable-ASCII rows are louder, so the random model
        # emits text as a trained byte-level model would: streams deliver
        # chunk by chunk, and the output can repeat something the prompt
        # holds, which is all prompt-lookup speculation needs to draft (so
        # spec verify runs)
        "output.weight": q8(cfg.vocab_size, d, ascii_rows=8.0),
    }
    block = {
        "attn_norm": ones, "ffn_norm": ones,
        "attn_q": q8(hq, d), "attn_k": q8(hkv, d), "attn_v": q8(hkv, d),
        "attn_output": q8(d, hq),
        "ffn_gate": q8(ff, d), "ffn_up": q8(ff, d), "ffn_down": q8(d, ff),
    }
    for i in range(cfg.n_layers):
        tensors |= {f"blk.{i}.{key}.weight": enc for key, enc in block.items()}
    for name, (shape, ggml_type, data) in tensors.items():
        w.add_encoded(name, shape, ggml_type, data)
    w.write()
    return {"seconds": round(time.perf_counter() - t0, 2),
            "bytes": path.stat().st_size, "tensors": len(tensors)}


class CompileClock:
    """Seconds XLA spent compiling, per jitted program (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self.by_program: dict[str, float] = {}
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **kw) -> None:
        if event == self.EVENT:
            name = str(kw.get("fun_name", "?"))
            self.by_program[name] = self.by_program.get(name, 0.0) + seconds

    def report(self, floor_s: float = 1.0) -> dict:
        big = {k: round(v, 1) for k, v in sorted(
            self.by_program.items(), key=lambda kv: -kv[1]) if v >= floor_s}
        return {"programs": len(self.by_program), "over_1s": big,
                "total_s": round(sum(self.by_program.values()), 1)}


def memory_by_device() -> list[dict]:
    import jax

    out = []
    for dev in jax.local_devices():
        ms = dev.memory_stats() or {}
        out.append({"id": dev.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                    "bytes_limit": ms.get("bytes_limit")})
    return out


def prom_value(text: str, family: str) -> float | None:
    """First sample of ``family`` in a Prometheus exposition."""
    for line in text.splitlines():
        if line.startswith(family) and line[len(family)] in " {":
            return float(line.rsplit(" ", 1)[1])
    return None


def chat_body(prompt: str, *, stream: bool = False, logprobs: bool = False) -> dict:
    body = {
        "model": MODEL_ID,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": MAX_NEW,
        "temperature": 0.0,
        "stream": stream,
    }
    if logprobs:
        body |= {"logprobs": True, "top_logprobs": TOP_LOGPROBS}
    return body


def check_reply(env: dict, n_prompt: int) -> dict:
    """An ``ok:true`` envelope with non-empty text and the tokenizer's own
    prompt count — anything else fails the run."""
    if not env.get("ok"):
        raise RuntimeError(f"error envelope: {env.get('error')!r}")
    resp = env["data"]["response"]
    choice = resp["choices"][0]
    text = choice["message"]["content"]
    if not text:
        raise RuntimeError(f"empty completion: {resp}")
    usage = resp["usage"]
    if usage["prompt_tokens"] != n_prompt:
        raise RuntimeError(
            f"usage.prompt_tokens {usage['prompt_tokens']} != tokenizer's {n_prompt}")
    stats = resp.get("stats", {})
    return {
        "prompt_tokens": usage["prompt_tokens"],
        "completion_tokens": usage["completion_tokens"],
        "finish_reason": choice["finish_reason"],
        "ttft_s": stats.get("time_to_first_token"),
        "tokens_per_s": stats.get("tokens_per_second"),
        "text_chars": len(text),
        "logprobs": choice.get("logprobs"),
    }


async def serve_and_drive(cfg, out_dir: Path, seed: int, *, logprobs: bool = False) -> dict:
    """Generate the GGUF, start the worker exactly as ``serve`` does, drive
    it over a NATS socket, stop it. Returns what was observed; raises on
    the first thing that is wrong. Which device this ran on is the
    caller's business."""
    import jax

    from nats_llm_studio_tpu import native
    from nats_llm_studio_tpu.gguf.reader import open_gguf
    from nats_llm_studio_tpu.gguf.tokenizer import GGUFTokenizer
    from nats_llm_studio_tpu.main import start_serve
    from nats_llm_studio_tpu.serve.template import render_chat_template
    from nats_llm_studio_tpu.transport import connect

    out: dict = {}
    models_dir = out_dir / "models"
    gguf = models_dir / MODEL_ID / "m.gguf"
    if not gguf.exists():
        out["gguf"] = generate_gguf(cfg, gguf, seed)
        emit(phase="gguf", path=str(gguf), seed=seed, **out["gguf"])
    os.environ["LMSTUDIO_MODELS_DIR"] = str(models_dir)
    os.environ["WQUANT"] = "int8"

    # the tokenizer's own count of each prompt, independent of the engine
    with open_gguf(str(gguf)) as reader:
        meta = dict(reader.metadata)
    tokenizer = GGUFTokenizer.from_metadata(meta)

    def n_tokens(prompt: str) -> int:
        return len(tokenizer.encode(render_chat_template(
            meta, [{"role": "user", "content": prompt}], add_generation_prompt=True)))

    emit(phase="about", note="times below are smoke observations of one run "
         "(cold unless the compile cache hits), not benchmark results")
    clock = CompileClock()
    worker, shutdown = await start_serve(embedded_broker=True, port=0)
    wcfg = worker.config
    emit(phase="serve", nats_url=wcfg.nats_url, mesh_shape=wcfg.mesh_shape,
         max_seq_len=wcfg.max_seq_len, max_batch_slots=wcfg.max_batch_slots,
         wquant=wcfg.quant_mode, kv_quant=wcfg.kv_quant_mode,
         kv_paged=wcfg.kv_paged, kv_block_tokens=wcfg.kv_block_tokens,
         spec_decode_k=wcfg.spec_decode_k,
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         native_dequant=native.available())
    nc = await connect(wcfg.nats_url, name="chip-smoke")
    after_load: dict = {}

    async def watch_load() -> None:
        # device memory the moment the engine exists: the peak of the load
        while not worker.registry.loaded_engines():
            await asyncio.sleep(0.05)
        after_load["memory"] = memory_by_device()

    watcher = asyncio.ensure_future(watch_load())
    try:
        async def req(op: str, payload: dict, timeout: float = 30.0) -> dict:
            msg = await nc.request(f"lmstudio.{op}", json.dumps(payload).encode(),
                                   timeout=timeout)
            return json.loads(msg.payload)

        async def chat(prompt: str, **kw) -> dict:
            t0 = time.perf_counter()
            # the first request loads the model and compiles its programs
            env = await req("chat_model", chat_body(prompt, **kw), timeout=1100.0)
            r = check_reply(env, n_tokens(prompt))
            r["wall_s"] = round(time.perf_counter() - t0, 2)
            return r

        async def chat_stream(prompt: str, started: asyncio.Event | None = None, **kw) -> dict:
            t0 = time.perf_counter()
            parts, final = [], None
            async for msg in nc.request_stream(
                "lmstudio.chat_model",
                json.dumps(chat_body(prompt, stream=True, **kw)).encode(), timeout=1100.0,
            ):
                body = json.loads(msg.payload)
                if (msg.headers or {}).get("Nats-Stream-Done"):
                    final = body
                    break
                parts.append(body["data"]["chunk"]["choices"][0]["delta"].get("content", ""))
                if started is not None:
                    started.set()
            if final is None:
                raise RuntimeError("stream ended without a terminal message")
            r = check_reply(final, n_tokens(prompt))
            agg = final["data"]["response"]["choices"][0]["message"]["content"]
            if "".join(parts) != agg:
                raise RuntimeError("streamed chunks do not add up to the final text")
            r["chunks"] = len(parts)
            r["wall_s"] = round(time.perf_counter() - t0, 2)
            return r

        listed = await req("list_models", {})
        ids = [m["id"] for m in listed["data"]["models"]["data"]]
        if not listed.get("ok") or MODEL_ID not in ids:
            raise RuntimeError(f"list_models does not list {MODEL_ID}: {listed}")
        health = await req("health", {})
        if not health.get("ok") or health["data"]["status"] != "ok":
            raise RuntimeError(f"health: {health}")
        out["devices"] = health["data"]["devices"]
        emit(phase="health", devices=out["devices"], models=ids)

        replies: dict = {}
        replies["first"] = await chat(SHORT_PROMPTS[0], logprobs=logprobs)
        await watcher
        events = await req("events", {"kind": "engine_load"})
        out["load"] = {
            "seconds": events["data"]["events"][-1]["seconds"],
            "memory_after_load": after_load["memory"],
        }
        emit(phase="load", **out["load"])
        emit(phase="chat", kind="non_streaming_cold", **_public(replies["first"]))
        replies["stream"] = await chat_stream(SHORT_PROMPTS[1], logprobs=logprobs)
        emit(phase="chat", kind="streaming", **_public(replies["stream"]))
        # four at once, one of them longer than a prefill chunk, while the
        # batched-admit and chunked-prefill programs are still cold: three
        # of the four queue behind the compiles of the first
        t0 = time.perf_counter()
        four = await asyncio.gather(*(
            chat(p, logprobs=logprobs) for p in [LONG_PROMPT, *SHORT_PROMPTS[1:]]))
        wave_s = time.perf_counter() - t0
        for i, r in enumerate(four):
            replies[f"concurrent_{i}"] = r
            emit(phase="chat", kind=f"concurrent_{i}", **_public(r))
        emit(phase="chat", kind="concurrent_wave", wall_s=round(wave_s, 2),
             tokens_per_s=round(sum(r["completion_tokens"] for r in four) / wave_s, 1))
        if four[0]["prompt_tokens"] < LONG_TOKENS:
            raise RuntimeError(f"the long prompt is shorter than {LONG_TOKENS} tokens")
        # a long prompt alone: an idle engine takes it in one flash dispatch
        replies["long"] = await chat(LONG_PROMPT.swapcase(), logprobs=logprobs)
        emit(phase="chat", kind="long_prompt", **_public(replies["long"]))
        # a long prompt that arrives while a stream is decoding: its prefill
        # goes in chunks interleaved with the live decode
        decoding = asyncio.Event()

        async def long_once_decoding() -> dict:
            await decoding.wait()
            return await chat(LONG_PROMPT[::-1], logprobs=logprobs)

        replies["stream_under_prefill"], replies["long_under_decode"] = await asyncio.gather(
            chat_stream(SHORT_PROMPTS[1], decoding, logprobs=logprobs),
            long_once_decoding(),
        )
        for kind in ("stream_under_prefill", "long_under_decode"):
            emit(phase="chat", kind=kind, **_public(replies[kind]))
        # warm repeat of the first request: what a request costs once every
        # program it needs is compiled
        replies["warm"] = await chat(SHORT_PROMPTS[0], logprobs=logprobs)
        emit(phase="chat", kind="non_streaming_warm", **_public(replies["warm"]))
        out["replies"] = replies

        prom = (await nc.request("lmstudio.metrics.prom", b"", timeout=30.0)).payload.decode()
        dev = jax.devices()[0]
        out["engine"] = {
            "decode_kernel_pallas": prom_value(prom, "lmstudio_decode_kernel_pallas"),
            "mesh_tp": prom_value(prom, "lmstudio_mesh_tp"),
            "spec_verifies": prom_value(prom, "lmstudio_spec_verifies_total"),
            "kv_pool_blocks_total": prom_value(prom, "lmstudio_kv_pool_blocks_total"),
            "brownout_level": prom_value(prom, "lmstudio_brownout_level"),
            "shed_by_cause": {
                ln.split('cause="', 1)[1].split('"', 1)[0]: float(ln.rsplit(" ", 1)[1])
                for ln in prom.splitlines()
                if ln.startswith("lmstudio_batcher_shed_by_cause_total{")
            },
            "compile_cache_hits": prom_value(prom, "lmstudio_compile_cache_hits_total"),
            "compile_cache_misses": prom_value(prom, "lmstudio_compile_cache_misses_total"),
            "programs": sorted({
                ln.split('program="', 1)[1].split('"', 1)[0]
                for ln in prom.splitlines()
                if ln.startswith("lmstudio_program_ms_count")
            }),
            "roofline_device_kind": dev.device_kind,
        }
        emit(phase="engine", **out["engine"])
        out["compile"] = clock.report()
        emit(phase="compile", **out["compile"])
        out["weight_bytes"] = _weight_bytes(worker.registry)
        out["memory_end"] = memory_by_device()
        emit(phase="memory", at="end", weight_bytes=out["weight_bytes"],
             devices=out["memory_end"])
    finally:
        watcher.cancel()
        await nc.close()
        await shutdown()
    return out


def _public(reply: dict) -> dict:
    return {k: v for k, v in reply.items() if k != "logprobs"}


def _weight_bytes(registry) -> dict[str, int]:
    """Bytes of the loaded engine's weights on each device, read from the
    arrays' own shards."""
    import jax

    eng = next(iter(registry.loaded_engines().values()))
    per: dict[int, int] = {}
    for leaf in jax.tree.leaves(eng.batcher.params):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return {str(d): n for d, n in sorted(per.items())}


def _first_top(reply: dict) -> dict[str, float]:
    first = reply["logprobs"]["content"][0]
    return {t["token"]: t["logprob"] for t in first["top_logprobs"]}


def compare_tp(one: dict, tp: dict, n_dev: int) -> dict:
    """tp=N against one chip: the same requests must stream the same number
    of tokens with the same finish reason, the first token's top-k logprobs
    must agree within LOGPROB_TOL (median LOGPROB_MEDIAN_TOL), and each device must hold about 1/N of the
    weight and KV bytes — from the devices, not from the mesh object."""
    diffs = {}
    for key, a in one["replies"].items():
        b = tp["replies"][key]
        if (a["completion_tokens"], a["finish_reason"]) != (
                b["completion_tokens"], b["finish_reason"]):
            raise RuntimeError(f"{key}: stream length/finish differ: {a} vs {b}")
        ta, tb = _first_top(a), _first_top(b)
        shared = set(ta) & set(tb)
        if max(ta, key=ta.get) not in tb or len(shared) < 2:
            raise RuntimeError(f"{key}: first-token top-k disagree: {ta} vs {tb}")
        diffs[key] = round(max(abs(ta[t] - tb[t]) for t in shared), 4)
    medians = {}
    for case, is_long in (("short_prompts", False), ("long_prompts", True)):
        group = sorted(d for key, d in diffs.items()
                       if (one["replies"][key]["prompt_tokens"] >= LONG_TOKENS) == is_long)
        medians[case] = group[len(group) // 2]
    emit(phase="tp_logprobs", max_abs_diff_on_shared_top5=diffs, medians=medians,
         tolerance=LOGPROB_TOL, median_tolerance=LOGPROB_MEDIAN_TOL)
    if max(diffs.values()) > LOGPROB_TOL or max(medians.values()) > LOGPROB_MEDIAN_TOL:
        raise RuntimeError(
            f"first-token logprobs differ by more than {LOGPROB_TOL} (medians "
            f"{medians}, allowed {LOGPROB_MEDIAN_TOL}): {diffs}")
    # what a device holds besides its weights is the KV pool (and small
    # change): allocator bytes in use, less the weight shards counted above
    in_use = {str(d["id"]): d["bytes_in_use"] for d in tp["memory_end"]}
    one_dev, one_w = next(iter(one["weight_bytes"].items()))
    one_in_use = {str(d["id"]): d["bytes_in_use"] for d in one["memory_end"]}[one_dev]
    whole = {"weights": one_w, "kv_pool": one_in_use - one_w}
    if len(tp["weight_bytes"]) != n_dev:
        raise RuntimeError(f"weights live on {len(tp['weight_bytes'])} devices, not {n_dev}")
    shares = {
        "weights": {d: round(n / whole["weights"], 3) for d, n in tp["weight_bytes"].items()},
        "kv_pool": {d: round((in_use[d] - n) / whole["kv_pool"], 3)
                    for d, n in tp["weight_bytes"].items()},
    }
    for kind, per_dev in shares.items():
        # embeddings and norms replicate, so a shard is a little over 1/N
        if not all(0.8 / n_dev <= s <= 1.6 / n_dev for s in per_dev.values()):
            raise RuntimeError(f"{kind} shares per device are not ~1/{n_dev}: {per_dev}")
    return {"requests": len(one["replies"]), "max_first_token_logprob_diff": max(diffs.values()),
            "median_first_token_logprob_diff": medians,
            "tolerance": LOGPROB_TOL, "share_of_one_chip_bytes": shares,
            "one_chip_bytes": whole, "bytes_in_use_per_device": in_use}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", type=Path, default=REPO / ".chip_smoke",
                    help="output directory (generated GGUF; removed on exit)")
    args = ap.parse_args(argv)

    import jax

    from nats_llm_studio_tpu.models.config import ModelConfig

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] != args.chips:
        sys.exit(f"chip_smoke: needs {args.chips} TPU device(s), JAX found {device}")
    # the published context is 8192; MAX_SEQ_LEN (default 4096) clamps serving
    cfg = ModelConfig(**LLAMA3_8B)
    try:
        if args.chips == 1:
            run = asyncio.run(serve_and_drive(cfg, args.out, args.seed))
            runs = [run]
        else:
            os.environ["MESH_SHAPE"] = "off"
            one = asyncio.run(serve_and_drive(cfg, args.out, args.seed, logprobs=True))
            gc.collect()  # the one-chip engine's arrays go before tp loads
            emit(phase="memory", at="between_runs", devices=memory_by_device())
            os.environ["MESH_SHAPE"] = "auto"
            run = asyncio.run(serve_and_drive(cfg, args.out, args.seed, logprobs=True))
            emit(phase="tp_compare", **compare_tp(one, run, args.chips))
            runs = [one, run]
    finally:
        shutil.rmtree(args.out, ignore_errors=True)
    for r in runs:
        if any(d["platform"] != "tpu" for d in r["devices"]):
            sys.exit(f"chip_smoke: health reports a non-TPU device: {r['devices']}")
        if r["engine"]["decode_kernel_pallas"] != 1:
            sys.exit("chip_smoke: the engine did not resolve decode_kernel=pallas")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
