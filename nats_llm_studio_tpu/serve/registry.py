"""LocalRegistry: the in-process replacement for LM Studio + the `lms` CLI.

Wires the four reference capabilities (list/pull/delete/chat —
/root/reference/nats_llm_studio.go:22-179) to the in-tree stack: ModelStore
(cache + Object Store), GGUF loader, and the JAX Generator. Model listings
are LM-Studio-shaped (README.md:66-80) so existing clients keep working.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from dataclasses import replace as dc_replace
from typing import Any, AsyncIterator

import jax

from ..engine.generator import GenStats, SamplingParams
from ..gguf.reader import open_gguf
from ..gguf.tokenizer import GGUFTokenizer
from ..models.config import ModelConfig
from ..obs import FlightRecorder, HbmLedger, LogHistogram, efficiency_enabled
from ..obs import emit as obs_emit
from ..parallel.sharding import validate_mesh_for_config
from ..store.manager import ModelStore, StoreError
from ..utils.nuid import next_nuid
from . import constrain as constrain_mod
from .api import ChatEngine, EngineError, ModelNotFound, Registry
from .batcher import (
    BatcherOverloaded,
    BatcherStopped,
    ContinuousBatcher,
)
from .brownout import BrownoutConfig
from .constrain import ConstraintError, compile_token_dfa, validate_response_format
from .programs import LOGPROBS_K
from .qos import ANON_TENANT, DEFAULT_PRIORITY, parse_priority_header
from .template import render_chat_template, stop_token_ids

log = logging.getLogger(__name__)


def _hbm_budget_bytes() -> int | None:
    """Per-device memory budget for admission (None = unknown, no check).
    TPU backends report ``bytes_limit`` via memory_stats(); the env override
    exists for CPU-backed tests and for operators reserving headroom."""
    env = os.environ.get("TPU_HBM_BUDGET_BYTES", "").strip()
    if env:
        return int(env) or None
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend without memory stats
        return None
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return None


def _prefix_cache_blocks_env(default: int = 64) -> int:
    """Per-engine prefix-cache budget in blocks (serve/prefix_cache.py).
    ``PREFIX_CACHE=0`` (or false/off) is the hard off-switch; otherwise
    ``PREFIX_CACHE_BLOCKS`` sizes the radix cache (0 also disables)."""
    if os.environ.get("PREFIX_CACHE", "").strip().lower() in ("0", "false", "off"):
        return 0
    env = os.environ.get("PREFIX_CACHE_BLOCKS", "").strip()
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            log.warning("ignoring non-integer PREFIX_CACHE_BLOCKS=%r", env)
    return default


def _kv_paged_env(default: bool = True) -> bool:
    """Paged-KV master switch: one refcounted block pool instead of
    contiguous per-slot rings (serve/block_pool.py). Default ON;
    ``KV_PAGED=0`` (or false/off) restores the pre-paged layout."""
    env = os.environ.get("KV_PAGED", "").strip().lower()
    if not env:
        return default
    return env not in ("0", "false", "off")


def _kv_block_tokens_env(default: int = 16) -> int:
    """Tokens per pool block (``KV_BLOCK_TOKENS``). The batcher snaps this
    down (pow2 halving) until it divides the serving prefill chunk."""
    env = os.environ.get("KV_BLOCK_TOKENS", "").strip()
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            log.warning("ignoring non-integer KV_BLOCK_TOKENS=%r", env)
    return default


def _kv_pool_blocks_env(default: int = 0) -> int:
    """Pool population override (``KV_POOL_BLOCKS``). 0 = auto: every slot
    at max_seq plus the whole prefix-cache budget (zero starvation).
    Deployments under-provision here to pack more slots into the same HBM
    — blocks only materialize per-token, which is the point of paging."""
    env = os.environ.get("KV_POOL_BLOCKS", "").strip()
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            log.warning("ignoring non-integer KV_POOL_BLOCKS=%r", env)
    return default


def _kv_host_pool_bytes_env(default: int = 256 << 20) -> int:
    """Host-RAM KV tier budget in bytes (serve/kv_tiers.py,
    ``KV_HOST_POOL_BYTES``). 0 disables tiering — demoted prefix chunks
    are dropped instead of swapped to host memory."""
    env = os.environ.get("KV_HOST_POOL_BYTES", "").strip()
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            log.warning("ignoring non-integer KV_HOST_POOL_BYTES=%r", env)
    return default


def _kv_tier_policy_env() -> tuple[int, float, int]:
    """(promote_chunks, demote_free_frac, spill_max_objects) from the env
    — the KVTierManager policy knobs (KV_PROMOTE_CHUNKS /
    KV_DEMOTE_FREE_FRAC / KV_SPILL_MAX_OBJECTS)."""
    try:
        promote = max(1, int(os.environ.get("KV_PROMOTE_CHUNKS", "").strip() or 64))
    except ValueError:
        promote = 64
    try:
        frac = float(os.environ.get("KV_DEMOTE_FREE_FRAC", "").strip() or 0.10)
    except ValueError:
        frac = 0.10
    try:
        max_obj = max(1, int(os.environ.get("KV_SPILL_MAX_OBJECTS", "").strip() or 512))
    except ValueError:
        max_obj = 512
    return promote, min(max(frac, 0.0), 0.9), max_obj


def _spec_decode_env(default_k: int = 6) -> tuple[int, int]:
    """(spec_decode_k, spec_max_active) from the env (serve/spec.py).
    ``SPEC_DECODE=0`` (or false/off) is the hard off-switch; otherwise
    ``SPEC_DECODE_K`` sizes the draft (0 also disables) and
    ``SPEC_DECODE_MAX_ACTIVE`` bounds the occupancy at which verify
    dispatches still run."""
    k = default_k
    if os.environ.get("SPEC_DECODE", "").strip().lower() in ("0", "false", "off"):
        k = 0
    else:
        env = os.environ.get("SPEC_DECODE_K", "").strip()
        if env:
            try:
                k = max(0, int(env))
            except ValueError:
                log.warning("ignoring non-integer SPEC_DECODE_K=%r", env)
    max_active = 4
    env = os.environ.get("SPEC_DECODE_MAX_ACTIVE", "").strip()
    if env:
        try:
            max_active = max(1, int(env))
        except ValueError:
            log.warning("ignoring non-integer SPEC_DECODE_MAX_ACTIVE=%r", env)
    return k, max_active


def _env_float(name: str, default: float) -> float:
    env = os.environ.get(name, "").strip()
    if env:
        try:
            return float(env)
        except ValueError:
            log.warning("ignoring non-numeric %s=%r", name, env)
    return default


def _brownout_env(enabled: bool | None = None) -> BrownoutConfig | None:
    """Adaptive-brownout config from the env (serve/brownout.py), or None
    when disabled. ``BROWNOUT=0`` (or false/off) is the hard off-switch
    (default on); the BROWNOUT_* threshold knobs tune the hysteresis."""
    if enabled is None:
        enabled = os.environ.get("BROWNOUT", "").strip().lower() not in (
            "0", "false", "off",
        )
    if not enabled:
        return None
    return BrownoutConfig(
        depth_hi=_env_float("BROWNOUT_DEPTH_HI", 0.75),
        depth_lo=_env_float("BROWNOUT_DEPTH_LO", 0.40),
        age_hi_ms=_env_float("BROWNOUT_AGE_HI_MS", 1500.0),
        age_lo_ms=_env_float("BROWNOUT_AGE_LO_MS", 500.0),
        hbm_lo_frac=_env_float("BROWNOUT_HBM_LO", 0.05),
        dwell_s=_env_float("BROWNOUT_DWELL_S", 2.0),
    )


def _pull_precompile_env(default: bool = True) -> bool:
    v = os.environ.get("PULL_PRECOMPILE", "").strip().lower()
    if not v:
        return default
    return v not in ("0", "false", "off")


def _compile_cache_dir_configured() -> bool:
    """Whether a persistent XLA compile cache is active in this process
    (WorkerConfig.configure_jax or JAX_COMPILATION_CACHE_DIR). Pull-time
    precompile only pays off when the compiled grid lands somewhere a
    replacement worker can replay it from."""
    return bool(jax.config.jax_compilation_cache_dir)


def _deadline_min_tokens_env(default: int = 1) -> int:
    """Feasibility floor for deadline-aware admission: a request that cannot
    deliver this many tokens before its deadline skips prefill and is shed
    retryably (DEADLINE_MIN_TOKENS, default 1 = just the first token)."""
    env = os.environ.get("DEADLINE_MIN_TOKENS", "").strip()
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            log.warning("ignoring non-integer DEADLINE_MIN_TOKENS=%r", env)
    return default


class JaxChatEngine(ChatEngine):
    """One loaded model: tokenizer + continuous batcher. Concurrent chats
    join the shared fixed-width decode step; the batcher's dedicated owner
    thread is the only mutator of device state (SURVEY.md §5)."""

    def __init__(
        self,
        model_id: str,
        batcher: ContinuousBatcher,
        tokenizer: GGUFTokenizer,
        cfg: ModelConfig,
        meta: dict[str, Any],
        quantization: str = "",
    ):
        self.model_id = model_id
        self.batcher = batcher
        self.tokenizer = tokenizer
        self.cfg = cfg
        self.meta = meta
        self.quantization = quantization
        self._stop_ids = stop_token_ids(tokenizer)

    # -- internals -----------------------------------------------------------

    def _sampling(self, payload: dict) -> SamplingParams:
        return SamplingParams(
            temperature=float(payload.get("temperature", 0.8)),
            top_p=float(payload.get("top_p", 1.0)),
            # DEFAULT_TOP_K: what a request that names no top_k samples from
            # (0 = the whole vocabulary; llama.cpp, which LM Studio runs,
            # defaults to 40)
            top_k=int(payload.get("top_k", os.environ.get("DEFAULT_TOP_K") or 0)),
            max_tokens=int(payload.get("max_tokens") or payload.get("max_completion_tokens") or 256),
            seed=payload.get("seed"),
            stop_ids=self._stop_ids,
        )

    def _encode_prompt(self, payload: dict) -> list[int]:
        messages = payload.get("messages") or []
        prompt = render_chat_template(self.meta, messages, add_generation_prompt=True)
        return self.tokenizer.encode(prompt)

    def _completion(self, text: str, n_prompt: int, n_out: int, finish: str,
                    stats=None, logprobs=None) -> dict:
        """OpenAI-style body with LM Studio's stats block
        (/root/reference/README.md:208-231)."""
        choice: dict[str, Any] = {
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": finish,
        }
        if logprobs is not None:
            choice["logprobs"] = logprobs
        out: dict[str, Any] = {
            "id": f"chatcmpl-{next_nuid()[:12].lower()}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.model_id,
            "choices": [choice],
            "usage": {
                "prompt_tokens": n_prompt,
                "completion_tokens": n_out,
                "total_tokens": n_prompt + n_out,
            },
        }
        if stats is not None:
            out["stats"] = {
                "tokens_per_second": round(stats.decode_tok_s, 2),
                "time_to_first_token": round(stats.ttft_s, 4),
                "generation_time": round(stats.total_s, 4),
            }
        return out

    def _lp_entry(self, item: tuple, top_n: int) -> dict:
        """One OpenAI ``logprobs.content`` element from a batcher
        (tok, logprob, top_ids, top_logprobs) tuple."""
        tok, lp, top_ids, top_lps = item
        s = self.tokenizer.decode([int(tok)])
        entry: dict[str, Any] = {
            "token": s,
            "logprob": float(lp) if lp is not None else 0.0,
            "bytes": list(s.encode("utf-8")),
            "top_logprobs": [],
        }
        if top_n and top_ids:
            for tid, tlp in list(zip(top_ids, top_lps))[:top_n]:
                ts = self.tokenizer.decode([int(tid)])
                entry["top_logprobs"].append({
                    "token": ts,
                    "logprob": float(tlp),
                    "bytes": list(ts.encode("utf-8")),
                })
        return entry

    # -- ChatEngine ----------------------------------------------------------

    async def chat(self, payload: dict) -> dict:
        parts = []
        final = None
        async for chunk in self.chat_stream(payload):
            if chunk.get("object") == "chat.completion":
                final = chunk
            else:
                parts.append(chunk["choices"][0]["delta"].get("content", ""))
        return final if final is not None else self._completion("".join(parts), 0, 0, "stop")

    def _parse_ext(self, payload: dict):
        """Parse the engine-layer OpenAI extensions out of the payload:
        returns (token_dfa, want_logprobs, top_logprobs, n_choices).
        Raises EngineError with a client-facing message on bad values —
        the worker envelope carries it back as a 400-shaped error."""
        try:
            schema = validate_response_format(payload.get("response_format"))
        except ValueError as e:
            raise EngineError(f"invalid response_format: {e}") from e
        dfa = None
        if schema is not None:
            if not constrain_mod.enabled():
                raise EngineError(
                    "invalid response_format: constrained decoding is "
                    "disabled on this worker (CONSTRAIN=0)"
                )
            try:
                dfa = compile_token_dfa(
                    schema, self.tokenizer, self.cfg.vocab_size,
                    eos_ids=self._stop_ids,
                )
            except ConstraintError as e:
                raise EngineError(f"invalid response_format: {e}") from e
        try:
            top_n = int(payload.get("top_logprobs") or 0)
            n_choices = int(payload.get("n") or 1)
        except (TypeError, ValueError) as e:
            raise EngineError(f"invalid request: {e}") from e
        if not 0 <= top_n <= LOGPROBS_K:
            raise EngineError(
                f"invalid top_logprobs: must be between 0 and {LOGPROBS_K}"
            )
        want_lp = bool(payload.get("logprobs")) or top_n > 0
        if not 1 <= n_choices <= self.batcher.max_slots:
            raise EngineError(
                f"invalid n: must be between 1 and {self.batcher.max_slots}"
            )
        return dfa, want_lp, top_n, n_choices

    async def _stream_one(
        self, index: int, prompt_ids: list[int], sp: SamplingParams,
        trace, deadline, dfa, want_lp: bool, top_n: int, result: dict,
        waste_tag: str | None = None, qos: tuple | None = None,
    ) -> AsyncIterator[dict]:
        """Drive ONE choice through the batcher: yields OpenAI chunk dicts
        tagged with choice ``index`` and fills ``result`` with the
        aggregate (text / finish / stats / logprobs) on clean completion."""
        stats = GenStats(prompt_tokens=len(prompt_ids))
        t0 = time.perf_counter()
        toks: list[int] = []
        lp_entries: list[dict] = []
        pending_lp: list[dict] = []  # entries held with incomplete UTF-8 text
        emitted = 0
        end_info: dict = {}
        # batched iteration: a decode burst's tokens land as ONE chunk
        # message (the delta simply carries more text) — per-message
        # publish overhead is a real share of throughput at 64+ streams
        tenant, priority, weight = qos or (ANON_TENANT, DEFAULT_PRIORITY, 0.0)
        async for tok_batch in self.batcher.submit_batched(
            prompt_ids, sp, info=end_info, trace=trace, deadline=deadline,
            constrain=dfa, want_logprobs=want_lp, top_logprobs=top_n,
            waste_tag=waste_tag, tenant=tenant, priority=priority,
            weight=weight,
        ):
            if not toks:
                stats.ttft_s = time.perf_counter() - t0
            if want_lp:
                # ext deliveries are (tok, logprob, top_ids, top_lps) tuples
                entries = [self._lp_entry(t, top_n) for t in tok_batch]
                lp_entries.extend(entries)
                pending_lp.extend(entries)
                tok_batch = [t[0] for t in tok_batch]
            toks.extend(tok_batch)
            stats.completion_tokens += len(tok_batch)
            # decode incrementally; emit only completed UTF-8 text
            text = self.tokenizer.decode(toks)
            if len(text) > emitted and not text.endswith("�"):
                choice: dict[str, Any] = {
                    "index": index,
                    "delta": {"role": "assistant", "content": text[emitted:]},
                    "finish_reason": None,
                }
                if want_lp:
                    choice["logprobs"] = {"content": pending_lp}
                    pending_lp = []
                yield {
                    "object": "chat.completion.chunk",
                    "model": self.model_id,
                    "choices": [choice],
                }
                emitted = len(text)
        stats.total_s = time.perf_counter() - t0
        text = self.tokenizer.decode(toks)
        if len(text) > emitted or pending_lp:
            # flush text held back by the incomplete-UTF-8 guard so the chunk
            # stream concatenates to exactly the aggregate completion
            choice = {
                "index": index,
                "delta": {"role": "assistant", "content": text[emitted:]},
                "finish_reason": None,
            }
            if want_lp:
                choice["logprobs"] = {"content": pending_lp}
            yield {
                "object": "chat.completion.chunk",
                "model": self.model_id,
                "choices": [choice],
            }
        # the batcher's end reason covers max_tokens *and* cache-capacity
        # terminations ("length"); a worker-drain truncation surfaces as an
        # error when nothing was generated, or an explicit "shutdown"
        # finish_reason on a partial completion — never as a clean "stop"
        reason = end_info.get("finish_reason", "stop")
        if reason == "shutdown" and not toks:
            raise EngineError("worker draining, retry on another worker")
        result.update(
            text=text,
            n_out=len(toks),
            finish=reason if reason in ("length", "shutdown") else "stop",
            stats=stats,
            logprobs={"content": lp_entries} if want_lp else None,
        )

    async def chat_stream(self, payload: dict) -> AsyncIterator[dict]:
        # trace context injected by the worker (serve/worker.py): popped so
        # the engine-facing payload stays the verbatim OpenAI body, handed
        # to the batcher so its owner thread stamps the admit/prefill/
        # first-token transitions on the same record
        trace = payload.pop("_trace", None)
        # monotonic deadline injected by the worker from the client's
        # X-Deadline-Ms header, capped by the per-op timeout ladder; popped
        # for the same stays-verbatim reason as the trace
        deadline = payload.pop("_deadline", None)
        # waste attribution tag injected by the worker (e.g. a failed
        # disagg KV prefetch forcing a local re-prefill): popped so the
        # engine-facing payload stays the verbatim OpenAI body, handed to
        # the batcher which charges this request's prefill device-ms to
        # that category instead of "served"
        waste_tag = payload.pop("_waste_tag", None)
        # tenant identity + priority class injected by the worker from the
        # gateway-stamped X-Tenant/X-Priority bus headers: popped for the
        # same stays-verbatim reason; raw-NATS callers that set neither
        # serve as the anonymous tenant at standard priority (backcompat)
        tenant = str(payload.pop("_tenant", None) or ANON_TENANT)
        priority, weight = parse_priority_header(payload.pop("_priority", None))
        qos = (tenant, priority, weight)
        prompt_ids = self._encode_prompt(payload)
        sp = self._sampling(payload)
        dfa, want_lp, top_n, n_choices = self._parse_ext(payload)
        results = [dict() for _ in range(n_choices)]
        try:
            if n_choices == 1:
                async for chunk in self._stream_one(
                    0, prompt_ids, sp, trace, deadline, dfa, want_lp, top_n,
                    results[0], waste_tag=waste_tag, qos=qos,
                ):
                    yield chunk
            else:
                async for chunk in self._stream_n(
                    prompt_ids, sp, trace, deadline, dfa, want_lp, top_n,
                    results, waste_tag=waste_tag, qos=qos,
                ):
                    yield chunk
        except BatcherOverloaded as e:
            # honest overload envelope: the client (or the bus) retries on a
            # queue-group peer instead of waiting out an invisible queue
            raise EngineError(f"overloaded: {e}") from e
        except BatcherStopped as e:
            # raced a drain or an idle-eviction (HBM admission): same
            # retry-on-another-worker shape, not a generic crash envelope
            raise EngineError(str(e)) from e
        except ValueError as e:  # e.g. prompt longer than max_seq
            raise EngineError(str(e)) from e
        r0 = results[0]
        out = self._completion(
            r0["text"], len(prompt_ids),
            sum(r["n_out"] for r in results), r0["finish"],
            r0["stats"], logprobs=r0.get("logprobs"),
        )
        for i, r in enumerate(results[1:], start=1):
            choice: dict[str, Any] = {
                "index": i,
                "message": {"role": "assistant", "content": r["text"]},
                "finish_reason": r["finish"],
            }
            if r.get("logprobs") is not None:
                choice["logprobs"] = r["logprobs"]
            out["choices"].append(choice)
        yield out

    async def _stream_n(
        self, prompt_ids, sp, trace, deadline, dfa, want_lp, top_n, results,
        waste_tag: str | None = None, qos: tuple | None = None,
    ) -> AsyncIterator[dict]:
        """n>1 fan-out: each choice is its own batcher request. Choice 0
        launches alone; the rest launch after its first chunk, so choice
        0's admit has harvested the prompt into the radix prefix cache —
        under paged KV the siblings' identical prompts then admit as
        zero-copy block SHARES (copy-on-write on divergence) instead of n
        prefills and n block sets. Chunks from all choices interleave on
        one stream, tagged by ``choices[0].index``."""
        done = object()
        queue: asyncio.Queue = asyncio.Queue()
        started = asyncio.Event()

        def sp_for(i: int) -> SamplingParams:
            # distinct per-choice seeds keep choices distinct AND replayable;
            # with no seed every choice draws its own random stream anyway
            if i == 0 or sp.seed is None:
                return sp
            return dc_replace(sp, seed=sp.seed + i)

        async def drive(i: int) -> None:
            try:
                async for chunk in self._stream_one(
                    i, prompt_ids, sp_for(i), trace if i == 0 else None,
                    deadline, dfa, want_lp, top_n, results[i],
                    waste_tag=waste_tag if i == 0 else None, qos=qos,
                ):
                    await queue.put(chunk)
                    if i == 0:
                        started.set()
            except Exception as e:  # noqa: BLE001 — re-raised by the merger
                results[i]["error"] = e
            finally:
                if i == 0:
                    started.set()
                await queue.put(done)

        tasks = [asyncio.ensure_future(drive(0))]
        try:
            await started.wait()
            tasks += [
                asyncio.ensure_future(drive(i)) for i in range(1, len(results))
            ]
            finished = 0
            while finished < len(results):
                item = await queue.get()
                if item is done:
                    finished += 1
                    continue
                yield item
        finally:
            for t in tasks:
                t.cancel()
        for r in results:
            if "error" in r:
                # a missing choice makes the whole completion wrong: fail
                # the request honestly rather than return a short n
                raise r["error"]

    # -- disaggregated prefill/decode (serve/kv_transfer.py) -----------------

    async def export_prefix(self, payload: dict) -> dict | None:
        """Prefill-role half of disaggregated serving: ensure this chat
        payload's prompt KV is prefilled and harvested into the local
        radix prefix cache, then gather the cached blocks to host memory
        for shipment to a decode peer. Returns the ``serve.kv_transfer``
        export dict, or None when there is nothing chunk-aligned worth
        shipping (short prompt, harvest paused under brownout, cache
        pressure) — the decode side then serves with local prefill,
        which is always correct."""
        payload = dict(payload)
        trace = payload.pop("_trace", None)
        deadline = payload.pop("_deadline", None)
        prompt_ids = self._encode_prompt(payload)
        C = self.batcher.prefill_chunk
        if len(prompt_ids) < C:
            return None
        n_cover = (len(prompt_ids) // C) * C
        export = await asyncio.to_thread(
            self.batcher.export_prefix_blocks, prompt_ids
        )
        if export is None or len(export["token_ids"]) < n_cover:
            # cold cache: run the chunked prefill HERE (that is this
            # worker's whole job) — admit harvests the blocks into the
            # prefix cache, the single greedy token is discarded — then
            # re-gather. The decode peer samples the real first token
            # from the shipped chunk-end logits with the request's own
            # sampling params, so the throwaway settings don't leak.
            sp = SamplingParams(temperature=0.0, max_tokens=1)
            async for _ in self.batcher.submit(
                prompt_ids, sp, trace=trace, deadline=deadline
            ):
                pass
            export = await asyncio.to_thread(
                self.batcher.export_prefix_blocks, prompt_ids
            )
        return export

    async def import_prefix(self, export: dict) -> dict:
        """Decode-role half: drop transferred blocks into the local block
        pool and seed the prefix cache, so the chat that triggered the
        transfer admits as a prefix hit (full hit ⇒ zero prefill work).
        Raises on pool exhaustion or layout mismatch; the worker counts
        the failure and falls back to local prefill."""
        return await asyncio.to_thread(
            self.batcher.import_prefix_blocks, export
        )

    def info(self) -> dict:
        return {
            "id": self.model_id,
            "object": "model",
            "type": "llm",
            "publisher": self.model_id.split("/")[0] if "/" in self.model_id else "local",
            "arch": self.cfg.arch,
            "quantization": self.quantization,
            "state": "loaded",
            "max_context_length": self.cfg.max_seq_len,
            "loaded_context_length": self.batcher.max_seq,
            "batch_slots": self.batcher.max_slots,
        }

    async def unload(self) -> None:
        await asyncio.to_thread(self.batcher.stop)


class LocalRegistry(Registry):
    """Model lifecycle over a ModelStore + JAX engines."""

    def __init__(
        self,
        store: ModelStore,
        mesh=None,
        dtype: str | None = None,
        max_seq_len: int | None = None,
        max_batch_slots: int = 8,
        quant: str = "none",
        kv_quant: str = "none",
        wquant_group: int = 32,
        admit_queue_limit: int = 0,
        admit_max_age_ms: float = 0.0,
        prefix_cache_blocks: int | None = None,
        spec_decode_k: int | None = None,
        spec_max_active: int | None = None,
        restart_backoff_s: float = 0.5,
        restart_backoff_max_s: float = 30.0,
        max_restarts: int = 3,
        restart_window_s: float = 120.0,
        brownout: bool | None = None,
        deadline_min_tokens: int | None = None,
        kv_paged: bool | None = None,
        kv_block_tokens: int | None = None,
        kv_pool_blocks: int | None = None,
        prefill_chunk: int | None = None,
        obs_recorder: bool | None = None,
        obs_recorder_interval_ms: float | None = None,
        obs_dump_dir: str | None = None,
        worker_id: str = "",
        pull_precompile: bool | None = None,
        kv_host_pool_bytes: int | None = None,
        kv_spill_factory=None,
        qos_quantum_tokens: int | None = None,
        qos_preempt: bool | None = None,
    ):
        self.store = store
        self.mesh = mesh
        self.dtype = dtype or ("float32" if jax.default_backend() == "cpu" else "bfloat16")
        self.max_seq_len = max_seq_len
        self.max_batch_slots = max_batch_slots
        self.quant = quant
        # rows per int4 scale/zero-point group (only read when quant="int4")
        self.wquant_group = wquant_group
        # "int8": store the serving KV cache quantized (ops/kvcache.py) —
        # halves decode cache traffic and per-slot HBM, so the same chip
        # serves ~2x the concurrent slots
        self.kv_quant = kv_quant
        # overload bounds handed to every batcher (0 = off): depth sheds at
        # submit, age sheds at admit — see ContinuousBatcher.max_queue
        self.admit_queue_limit = admit_queue_limit
        self.admit_max_age_ms = admit_max_age_ms
        # per-engine prefix KV cache budget in chunk blocks (0 = off);
        # None = read PREFIX_CACHE / PREFIX_CACHE_BLOCKS from the env
        # speculative decoding knobs handed to every batcher (k 0 = off);
        # None = read SPEC_DECODE / SPEC_DECODE_K / SPEC_DECODE_MAX_ACTIVE
        env_k, env_ma = _spec_decode_env()
        self.spec_decode_k = spec_decode_k if spec_decode_k is not None else env_k
        self.spec_max_active = (
            spec_max_active if spec_max_active is not None else env_ma
        )
        self.prefix_cache_blocks = (
            prefix_cache_blocks
            if prefix_cache_blocks is not None
            else _prefix_cache_blocks_env()
        )
        # paged KV (serve/block_pool.py): one refcounted block pool shared
        # by live slots, the prefix cache, and spec decode. HBM admission
        # prices the POOL (not per-slot worst-case rows + a separate prefix
        # budget) — see _estimate_load_bytes. None = read KV_PAGED /
        # KV_BLOCK_TOKENS / KV_POOL_BLOCKS from the env.
        self.kv_paged = kv_paged if kv_paged is not None else _kv_paged_env()
        self.kv_block_tokens = (
            kv_block_tokens
            if kv_block_tokens is not None
            else _kv_block_tokens_env()
        )
        self.kv_pool_blocks = (
            kv_pool_blocks
            if kv_pool_blocks is not None
            else _kv_pool_blocks_env()
        )
        # hierarchical KV tiers (serve/kv_tiers.py): host-RAM tier budget
        # under the HBM block pool (0 disables tiering entirely).
        # kv_spill_factory() returns a SpillStore adapter for the cold
        # Object Store tier — the worker injects one over its JetStream
        # connection; None keeps the host tier terminal (no cold spill).
        self.kv_host_pool_bytes = (
            kv_host_pool_bytes
            if kv_host_pool_bytes is not None
            else _kv_host_pool_bytes_env()
        )
        self.kv_spill_factory = kv_spill_factory
        (self.kv_promote_chunks, self.kv_demote_free_frac,
         self.kv_spill_max_objects) = _kv_tier_policy_env()
        # prefill chunk size handed to every batcher (None = the batcher
        # default, clamped to max_seq_len). Tiny serving setups — tests and
        # the disagg bench — need small chunks so a short prompt still
        # covers whole chunks for KV export (serve/kv_transfer.py)
        self.prefill_chunk = prefill_chunk
        # adaptive brownout (serve/brownout.py) handed to every batcher;
        # None reads BROWNOUT from the env (default on), the BROWNOUT_*
        # threshold knobs tune the hysteresis. The HBM-headroom signal is
        # this registry's admission accounting, injected as a probe.
        self.brownout_cfg = _brownout_env(brownout)
        self.deadline_min_tokens = (
            deadline_min_tokens
            if deadline_min_tokens is not None
            else _deadline_min_tokens_env()
        )
        # multi-tenant QoS (serve/qos.py) handed to every batcher: the DRR
        # quantum (prompt tokens per fair-share round) and the premium
        # preempt-to-host-tier toggle. None reads QOS_QUANTUM_TOKENS here;
        # the batcher itself resolves a None qos_preempt from QOS_PREEMPT.
        self.qos_quantum_tokens = (
            qos_quantum_tokens
            if qos_quantum_tokens is not None
            else int(os.environ.get("QOS_QUANTUM_TOKENS", "256") or 256)
        )
        self.qos_preempt = qos_preempt
        self._engines: dict[str, JaxChatEngine] = {}
        self._load_lock = asyncio.Lock()
        # model id -> the task loading it (get_engine): a load belongs to
        # the registry, not to the request that happened to ask first
        self._loading: dict[str, asyncio.Task] = {}
        self._requests = 0
        # HBM admission bookkeeping: estimated per-device bytes committed by
        # each loaded engine, and last-use times for idle-eviction order.
        # evict_grace_s: a recently-targeted engine is never evicted (see
        # _pick_idle_victim)
        self._hbm_committed: dict[str, int] = {}
        self._last_used: dict[str, float] = {}
        # slice of each engine's committed bytes that is its prefix cache's
        # budget — reclaimable under pressure WITHOUT unloading the engine
        # (_shrink_prefix_caches), unlike the weights/serving cache
        self._prefix_bytes: dict[str, int] = {}
        self.evict_grace_s = 1.0
        # engine supervision (serve/worker.py watchdog → restart_engine):
        # capped exponential restart backoff; > max_restarts crashes inside
        # restart_window_s marks the engine POISONED — further get_engine
        # calls are refused (retryable) until an operator delete/pull resets
        # it, reusing the refuse-until-reset shape of the failed-load path
        # in get_engine
        self.restart_backoff_s = restart_backoff_s
        self.restart_backoff_max_s = restart_backoff_max_s
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        self._crash_times: dict[str, list[float]] = {}
        self._poisoned: dict[str, str] = {}  # model_id -> reason
        self.engine_restarts_total = 0
        # harvested from crashed batchers' stats at restart/teardown so the
        # Prometheus total survives the batcher object being dropped
        self.inflight_failed_retryable = 0
        self.restart_latency_ms = LogHistogram()
        # flight recorder (obs/recorder.py): per-engine frame rings sampled
        # by each batcher's owner loop; None ctor args read OBS_RECORDER /
        # OBS_RECORDER_INTERVAL_MS / OBS_DUMP_DIR from the env
        self.obs_recorder = (
            obs_recorder
            if obs_recorder is not None
            else os.environ.get("OBS_RECORDER", "1").strip().lower()
            not in ("0", "false", "off")
        )
        self.obs_recorder_interval_ms = (
            obs_recorder_interval_ms
            if obs_recorder_interval_ms is not None
            else float(os.environ.get("OBS_RECORDER_INTERVAL_MS", "").strip() or "250")
        )
        self.obs_dump_dir = (
            obs_dump_dir
            if obs_dump_dir is not None
            else os.environ.get("OBS_DUMP_DIR", "").strip()
        )
        # process-level counters merged into every recorder frame so
        # restart/reconnect counts sit on the same timeline as queue depth;
        # the worker registers its transport's reconnect counter here
        # cluster identity (serve/router.py): stamped on recorder frames and
        # anomaly dumps so N workers sharing one dump dir stay attributable
        self.worker_id = worker_id
        self.recorder_counters: dict[str, Any] = {
            "engine_restarts": lambda: self.engine_restarts_total,
        }
        # HBM ledger (obs/roofline.py): reconcile the admission accounting
        # against the allocator's bytes_in_use on every recorder tick — the
        # committed estimate already folds block pool + prefix budget, so
        # components split it for the breakdown rather than re-pricing.
        # HBM_WORKSPACE_SLACK_BYTES prices XLA scratch/workspace the
        # admission model deliberately ignores; the ledger's baseline
        # absorbs whatever constant slack remains unpriced.
        try:
            _slack = int(os.environ.get("HBM_WORKSPACE_SLACK_BYTES", "0") or 0)
        except ValueError:
            _slack = 0
        self.hbm_ledger = HbmLedger(
            {
                "engines": lambda: (
                    sum(self._hbm_committed.values())
                    - sum(self._prefix_bytes.values())
                ),
                "prefix_cache": lambda: sum(self._prefix_bytes.values()),
                "workspace_slack": lambda: _slack,
            },
            emit_fn=obs_emit,
        )
        # ticking inside the counter fn puts each reconciliation sample on
        # the recorder frame timeline for free (and into anomaly dumps).
        # EFFICIENCY=0 kills the whole plane: no ticks, no hbm_drift
        # events, and (per the worker's gates) no exposition families
        if efficiency_enabled():
            self.recorder_counters["hbm_drift_bytes"] = self.hbm_ledger.tick
        # pull-time precompile (ISSUE 15): at pull_model, compile the full
        # jit grid into the persistent compile cache so a replacement
        # worker's first request replays warm compiles. Only active when a
        # compile cache dir is configured — warming a process-local cache
        # would just tax the pull. None = read PULL_PRECOMPILE (default on).
        self.pull_precompile = (
            pull_precompile
            if pull_precompile is not None
            else _pull_precompile_env()
        )
        # elastic-drain flag (serve/worker.py begin_drain → set_draining):
        # while set, restart_engine refuses to relaunch engines — a worker
        # being scaled down must never be resurrected mid-teardown, even by
        # a supervisor restart already sleeping out its backoff
        self.draining = False

    # -- Registry ------------------------------------------------------------

    async def list_models(self) -> dict:
        entries = []
        for cm in self.store.cached():
            eng = self._engines.get(cm.model_id)
            if eng is not None:
                entries.append(eng.info())
            else:
                entries.append(
                    {
                        "id": cm.model_id,
                        "object": "model",
                        "type": "llm",
                        "publisher": cm.publisher,
                        "state": "not-loaded",
                        "size_bytes": cm.size,
                    }
                )
        return {"object": "list", "data": entries}

    async def pull(self, identifier: str) -> str:
        try:
            path, transcript = await self.store.pull(identifier)
        except StoreError as e:
            raise EngineError(str(e)) from None
        # a fresh pull is the other operator reset path for a poisoned model
        self._poisoned.pop(identifier, None)
        self._crash_times.pop(identifier, None)
        # mesh gate at pull time: a model whose head layout this worker's
        # mesh cannot shard is reported unservable NOW, in a retryable
        # cause-tagged envelope, instead of crashing the first chat_model.
        # The file stays cached — a mesh reconfig makes it servable later.
        reason = await asyncio.to_thread(self._mesh_unservable, str(path))
        if reason is not None:
            raise EngineError(
                f"pulled {identifier}, but it is {reason} — retry on "
                f"another worker"
            )
        if self.pull_precompile and _compile_cache_dir_configured():
            # reported via the pull_precompile event and the log, NOT the
            # transcript: the reply text is wire contract ("pulled")
            await self._precompile(identifier)
        return transcript

    async def _precompile(self, model_id: str) -> int:
        """Best-effort jit-grid warm at pull time: load the engine and
        compile every chunk/full-prefill program, populating the persistent
        compile cache a seconds-cold replacement worker will replay
        (PR 6/7's lmstudio_compile_cache_* counters measure the replay).
        Never fails the pull — the model IS pulled; precompile is a
        cold-start optimization. The engine load serves only the compile:
        when the model was not already resident it is unloaded again on the
        way out, so pull leaves it cached-not-loaded (the programs persist
        on disk either way)."""
        was_loaded = model_id in self._engines
        try:
            eng = await self.get_engine(model_id)
        except (EngineError, ModelNotFound) as e:
            log.warning("pull precompile skipped for %s: %s", model_id, e)
            return 0
        n = 0
        try:
            warm = getattr(
                getattr(eng, "batcher", None), "warm_chunk_programs", None
            )
            if warm is None:
                return 0
            t0 = time.perf_counter()
            try:
                n = await asyncio.to_thread(warm)
            except Exception as e:  # noqa: BLE001 — precompile is best-effort
                log.warning("pull precompile failed for %s: %s", model_id, e)
                return 0
            obs_emit("pull_precompile", model=model_id, programs=n,
                     seconds=round(time.perf_counter() - t0, 2))
            log.info("pull precompile: %d programs for %s in %.2fs",
                     n, model_id, time.perf_counter() - t0)
            return n
        finally:
            if not was_loaded and self._engines.get(model_id) is eng:
                self._engines.pop(model_id, None)
                self._hbm_committed.pop(model_id, None)
                self._prefix_bytes.pop(model_id, None)
                self._last_used.pop(model_id, None)
                await eng.unload()
                obs_emit("engine_unload", model=model_id,
                         reason="pull_precompile")

    async def delete(self, model_id: str) -> str:
        eng = self._engines.pop(model_id, None)
        self._hbm_committed.pop(model_id, None)
        self._prefix_bytes.pop(model_id, None)
        self._last_used.pop(model_id, None)
        # operator reset path for a poisoned engine
        self._poisoned.pop(model_id, None)
        self._crash_times.pop(model_id, None)
        if eng is not None:
            await eng.unload()
            obs_emit("engine_unload", model=model_id, reason="delete")
        try:
            return self.store.delete_local(model_id)
        except StoreError as e:
            err = EngineError(str(e))
            err.dir = e.dir  # surfaced in the error envelope (go :304-313)
            raise err from None

    async def sync_from_bucket(self, name: str, model_id: str | None = None) -> str:
        try:
            path, _ = await self.store.pull(name, model_id=model_id)
        except StoreError as e:
            raise EngineError(str(e)) from None
        return str(path)

    async def get_engine(self, model_id: str) -> ChatEngine:
        self._requests += 1
        poisoned = self._poisoned.get(model_id)
        if poisoned is not None:
            # refuse-until-reset: delete or pull the model to clear. The
            # message carries the retryable marker so a queue-group peer
            # (whose copy may be healthy) gets the retry.
            raise EngineError(
                f"model {model_id} is poisoned ({poisoned}); delete or pull "
                f"it to reset — retry on another worker"
            )
        eng = self._engines.get(model_id)
        if eng is not None:
            self._last_used[model_id] = time.monotonic()
            return eng
        # one load per model, in a task of its own. A caller whose deadline
        # fires meanwhile is cancelled out of the wait, not out of the load:
        # the load thread cannot be stopped, so a load abandoned with its
        # caller would finish anyway, drop an engine's worth of device
        # memory on the floor, and let the retry start a second load next to
        # it. The retry joins this one instead, or finds the engine loaded.
        task = self._loading.get(model_id)
        if task is None:
            task = asyncio.ensure_future(self._load_engine(model_id))
            self._loading[model_id] = task

            def done(t: asyncio.Task) -> None:
                self._loading.pop(model_id, None)
                if not t.cancelled():
                    t.exception()  # every waiter may be gone; not a leak

            task.add_done_callback(done)
        return await asyncio.shield(task)

    async def _load_engine(self, model_id: str) -> ChatEngine:
        async with self._load_lock:
            eng = self._engines.get(model_id)
            if eng is not None:
                self._last_used[model_id] = time.monotonic()
                return eng
            cm = self.store.lookup(model_id)
            if cm is None:
                raise ModelNotFound(model_id)
            paths = [str(f) for f in cm.files]
            await self._admit_hbm(cm.model_id, paths)
            try:
                eng = await asyncio.to_thread(self._load, cm.model_id, paths)
            except BaseException:
                # release the reservation: a failed load (corrupt file,
                # device OOM) must not leave phantom committed bytes that
                # refuse every future load until restart
                self._hbm_committed.pop(cm.model_id, None)
                self._prefix_bytes.pop(cm.model_id, None)
                raise
            self._engines[cm.model_id] = eng
            self._last_used[cm.model_id] = time.monotonic()
            return eng

    # -- HBM admission (VERDICT r4 missing #3) -------------------------------

    async def _admit_hbm(self, model_id: str, paths: list[str]) -> None:
        """Refuse (or free room for) a load that would blow the per-device
        HBM budget — BEFORE touching the device, so a second model cannot
        OOM mid-serving and take the first engine's dispatches with it. The
        reference delegates this to LM Studio's loader
        (/root/reference/nats_llm_studio.go:46-59 shells out); in-process
        it is ours. Estimates come from parallel.memory.estimate_device_bytes
        (the same math the 70B budget test pins); idle engines are evicted
        LRU-first to make room; an engine actively serving is never evicted."""
        budget = _hbm_budget_bytes()
        if budget is None:
            return
        evictable = True
        try:
            need = await asyncio.to_thread(self._estimate_load_bytes, paths)
        except Exception:  # noqa: BLE001 — keep admitting with a floor, not blind
            # an unexpected estimator failure must not silently disable
            # admission (the engine would serve with ZERO committed bytes
            # and the next load could OOM live serving). Fall back to the
            # file sizes — a floor on the real footprint — and log loudly.
            # Such a load may well fail outright in _load, so it is never
            # allowed to EVICT a healthy engine to make its room.
            need = sum(os.path.getsize(p) for p in paths if os.path.exists(p))
            evictable = False
            log.warning(
                "HBM estimate failed for %s; admitting with file-size floor "
                "%d MiB (no eviction)", model_id, need >> 20, exc_info=True,
            )
        pbytes = 0
        # paged mode: the prefix cache holds POOL block ids — its HBM is the
        # pool's, already inside _estimate_load_bytes; pricing it separately
        # would double-count (and _shrink_prefix_caches would then credit
        # bytes the pool never gives back to the OS)
        if self.prefix_cache_blocks > 0 and not self.kv_paged:
            try:
                pbytes = await asyncio.to_thread(self._estimate_prefix_bytes, paths)
            except Exception:  # noqa: BLE001 — cache stays block-bounded anyway
                log.warning(
                    "prefix-cache estimate failed for %s; admitting its cache "
                    "unpriced", model_id, exc_info=True,
                )
        need += pbytes
        self._hbm_committed.pop(model_id, None)  # reloading: don't double count
        self._prefix_bytes.pop(model_id, None)
        while sum(self._hbm_committed.values()) + need > budget:
            # cheapest eviction tier first: dropping another engine's prefix
            # cache frees its whole block budget without unloading anything
            if evictable and self._shrink_prefix_caches(exclude=model_id):
                continue
            victim = self._pick_idle_victim() if evictable else None
            if victim is None and evictable:
                # an idle engine inside the eviction grace may become
                # evictable within a second — wait a short remainder out
                # rather than bounce the load with a hard error
                wait = self._grace_remaining_s()
                if wait is not None and wait <= 1.5:
                    await asyncio.sleep(wait + 0.05)
                    victim = self._pick_idle_victim()
            if victim is None:
                committed = sum(self._hbm_committed.values())
                raise EngineError(
                    f"insufficient device memory to load {model_id}: needs "
                    f"~{need >> 20} MiB, {committed >> 20} MiB committed to "
                    f"{sorted(self._hbm_committed)} of {budget >> 20} MiB "
                    f"budget, and no loaded engine is idle to evict"
                )
            log.info("evicting idle engine %s to fit %s", victim, model_id)
            freed = self._hbm_committed.pop(victim, 0)
            self._prefix_bytes.pop(victim, None)
            eng = self._engines.pop(victim)
            self._last_used.pop(victim, None)
            await eng.unload()
            obs_emit("engine_evict", model=victim, for_model=model_id,
                     freed_bytes=freed)
        self._hbm_committed[model_id] = need
        if pbytes:
            self._prefix_bytes[model_id] = pbytes

    def _estimate_load_bytes(self, paths: list[str]) -> int:
        """Per-device estimate for serving this file with the registry's
        settings (mesh sharding, weight/KV quant, slot count, seq len).
        Paged KV replaces the per-slot worst-case cache term with the ONE
        pool's footprint (blocks x kv_pool_block_bytes) — the prefix cache
        lives inside the same pool and is not priced separately."""
        from ..gguf.reader import is_split_shard
        from ..parallel.memory import estimate_device_bytes

        split = sorted(p for p in paths if is_split_shard(p))
        with open_gguf(split[0] if split else paths[0]) as reader:
            cfg = ModelConfig.from_gguf_metadata(reader.metadata).with_(dtype=self.dtype)
        mesh_shape = dict(self.mesh.shape) if self.mesh is not None else {}
        seq = min(self.max_seq_len or cfg.max_seq_len, cfg.max_seq_len)
        est = estimate_device_bytes(
            cfg, mesh_shape, quant=self.quant, batch=self.max_batch_slots,
            seq_len=seq, cache_dtype_bytes=1 if self.kv_quant == "int8" else None,
            group=self.wquant_group,
        )
        if not self.kv_paged:
            return est["total"]
        from ..parallel.memory import kv_pool_block_bytes, state_slot_bytes
        from .prefix_cache import serving_chunk

        # mirror the batcher's block-size snap (T | serving chunk) and its
        # auto pool population, +1 for the permanent null block
        chunk = serving_chunk(seq)
        T = max(1, self.kv_block_tokens)
        while T > 1 and chunk % T:
            T //= 2
        nb = 1 + (
            self.kv_pool_blocks
            if self.kv_pool_blocks > 0
            else self.max_batch_slots * max(1, seq // T)
            + max(0, self.prefix_cache_blocks)
        )
        pool = nb * kv_pool_block_bytes(
            cfg, T, kv_quant=self.kv_quant, tp=self._kv_tp(cfg)
        )
        # beside the blocks, a slot's recurrent state (state-space layers):
        # priced whole a slot, whatever the slot's context
        pool += self.max_batch_slots * state_slot_bytes(cfg)
        return est["total"] - est["kv_cache"] + pool

    def _mesh_unservable(self, path: str) -> str | None:
        """Reason this worker's mesh cannot serve the GGUF at ``path``
        (the validate_mesh_for_config message), or None when servable or
        the check cannot run. Best-effort: a failure to *check* is not a
        failure to *serve* — _load retells any real problem."""
        if self.mesh is None:
            return None
        from pathlib import Path

        from ..gguf.reader import is_split_shard

        p = Path(path)
        paths = sorted(str(f) for f in p.glob("*.gguf")) if p.is_dir() else [str(p)]
        if not paths:
            return None
        split = sorted(q for q in paths if is_split_shard(q))
        try:
            with open_gguf(split[0] if split else paths[0]) as reader:
                cfg = ModelConfig.from_gguf_metadata(reader.metadata)
            validate_mesh_for_config(self.mesh, cfg)
        except ValueError as e:
            return str(e)
        except Exception:  # noqa: BLE001 — gate is best-effort
            return None
        return None

    def _kv_tp(self, cfg: ModelConfig) -> int:
        """The tp factor actually applied to KV rings and prefix blocks:
        the mesh's tp when it divides the KV heads, else 1 (the
        replicated-KV GQA fallback keeps whole KV per chip)."""
        if self.mesh is None:
            return 1
        tp = dict(self.mesh.shape).get("tp", 1)
        return tp if tp > 1 and cfg.n_kv_heads % tp == 0 else 1

    def _shrink_prefix_caches(self, exclude: str | None = None) -> bool:
        """Reclaim HBM by dropping the least-recently-used engine's prefix
        cache — no unload, serving state untouched; blocks pinned by an
        in-flight admit are freed when that admit releases them (the
        refcount contract in serve/prefix_cache.py). Returns True when
        committed bytes decreased, so the admit loop retries the budget
        check before escalating to whole-engine eviction."""
        cands = [
            mid for mid in self._engines
            if mid != exclude and self._prefix_bytes.get(mid, 0) > 0
        ]
        if not cands:
            return False
        mid = min(cands, key=lambda m: self._last_used.get(m, 0.0))
        eng = self._engines[mid]
        freed = self._prefix_bytes.pop(mid, 0)
        self._hbm_committed[mid] = max(0, self._hbm_committed.get(mid, 0) - freed)
        dropped = eng.batcher.drop_prefix_cache() if eng.batcher is not None else 0
        log.info(
            "dropped %s prefix cache under HBM pressure (%d blocks, ~%d MiB)",
            mid, dropped, freed >> 20,
        )
        obs_emit("prefix_cache_drop", model=mid, freed_bytes=freed, blocks=dropped)
        return True

    def _estimate_prefix_bytes(self, paths: list[str]) -> int:
        """Worst-case device bytes of this engine's prefix-cache budget:
        blocks x the block footprint at the chunk size the batcher will
        actually serve with (serve/prefix_cache.serving_chunk mirrors the
        batcher's chunk halving)."""
        from .prefix_cache import prefix_block_bytes, serving_chunk

        from ..gguf.reader import is_split_shard

        split = sorted(p for p in paths if is_split_shard(p))
        with open_gguf(split[0] if split else paths[0]) as reader:
            cfg = ModelConfig.from_gguf_metadata(reader.metadata).with_(dtype=self.dtype)
        seq = min(self.max_seq_len or cfg.max_seq_len, cfg.max_seq_len)
        chunk = serving_chunk(seq)
        return self.prefix_cache_blocks * prefix_block_bytes(
            cfg, chunk, kv_quant=self.kv_quant, tp=self._kv_tp(cfg)
        )

    def _pick_idle_victim(self) -> str | None:
        # grace window: an engine targeted within the last second is never
        # evicted even if its batcher looks idle — get_engine bumps
        # _last_used BEFORE the caller submits, so this closes the
        # check-then-act gap where a request is in flight toward a
        # momentarily-idle batcher (and damps mutual-eviction loops when
        # two models alternate under a one-model budget)
        now = time.monotonic()
        idle = [
            mid for mid, eng in self._engines.items()
            if eng.batcher is not None and eng.batcher.idle
            and now - self._last_used.get(mid, 0.0) > self.evict_grace_s
        ]
        if not idle:
            return None
        return min(idle, key=lambda mid: self._last_used.get(mid, 0.0))

    def _grace_remaining_s(self) -> float | None:
        """Shortest time until some currently-idle engine exits the
        eviction grace (None when no idle engine is inside it)."""
        now = time.monotonic()
        waits = [
            self.evict_grace_s - (now - self._last_used.get(mid, 0.0))
            for mid, eng in self._engines.items()
            if eng.batcher is not None and eng.batcher.idle
        ]
        waits = [w for w in waits if w > 0]
        return min(waits) if waits else None

    def _hbm_headroom_frac(self) -> float | None:
        """Free fraction of the HBM admission budget (brownout signal),
        or None when no budget is known. Called from batcher owner threads:
        one dict sum under the GIL, no lock needed for a pressure signal."""
        budget = _hbm_budget_bytes()
        if not budget:
            return None
        committed = sum(self._hbm_committed.values())
        return max(0.0, (budget - committed) / budget)

    def _load(self, model_id: str, paths: list[str]) -> JaxChatEngine:
        t0 = time.perf_counter()
        from ..gguf.reader import is_split_shard

        split = sorted(p for p in paths if is_split_shard(p))
        # a -NNNNN-of-MMMMM split set loads as one model (open_gguf verifies
        # every sibling exists, so a partial download fails loudly instead of
        # serving a third of the weights); otherwise keep the long-standing
        # behavior of serving the first .gguf in the dir
        reader = open_gguf(split[0] if split else paths[0])
        cfg = ModelConfig.from_gguf_metadata(reader.metadata)
        tp = dict(self.mesh.shape).get("tp", 1) if self.mesh is not None else 1
        cfg = cfg.with_(
            dtype=self.dtype,
            # prefill TTFT. Under tp the kernels are shard_mapped over heads
            # (models/llama.py _on_mesh), which needs whole GQA groups per
            # shard; the replicated-KV fallback prefills on the XLA path
            # (a latent-attention model prefills through XLA attention in
            # query blocks, models/mla_moe.py: no flash kernel at its widths
            # yet, so none of the idle-engine single-dispatch shortcuts)
            use_flash_attention=(
                jax.default_backend() == "tpu" and self._kv_tp(cfg) == tp
                and cfg.family in ("llama", "swa_moe", "gdn_moe", "sala")
            ),
            use_routed_moe=True,  # sparse dispatch (parallel/moe.py)
            kv_quant=self.kv_quant,
        )
        tokenizer = GGUFTokenizer.from_metadata(reader.metadata)
        quant = {t.ggml_type.name for t in reader.tensors.values()}
        # stream tensors straight onto the device(s): each is dequantized,
        # cast or re-quantized on the host and placed at its final sharding,
        # so peak host memory is one tensor (70B-class files load on
        # small-RAM workers) and peak device memory is the final tree plus
        # one layer slice (an int8 8B tree loads on one 16 GB chip). The
        # one loader serves every placement: unsharded serving is a
        # one-device mesh here, and the batcher still gets mesh=None.
        from ..parallel.loader import load_params_sharded
        from ..parallel.mesh import build_mesh, dp_submeshes

        if self.mesh is None:
            submeshes: list[Any] = [None]
            load_mesh = build_mesh({"tp": 1}, devices=jax.local_devices()[:1])
        else:
            validate_mesh_for_config(self.mesh, cfg)
            # a dp axis means batcher REPLICAS: one submesh per dp slice
            # (disjoint devices, ep/sp/tp intact). The GGUF streams onto
            # slice 0; the other slices get device-to-device re-placements
            # of the same tree below — weights replicated ALONG dp, sharded
            # WITHIN each slice, one host read total
            submeshes = dp_submeshes(self.mesh)
            load_mesh = submeshes[0]
        params = load_params_sharded(
            reader, cfg, load_mesh, quant=self.quant, group=self.wquant_group
        )
        meta = dict(reader.metadata)
        reader.close()
        n_dp = len(submeshes)
        replicas = []
        for i, sub in enumerate(submeshes):
            counters = dict(self.recorder_counters)
            if n_dp > 1:
                # every recorder frame of this replica carries its dp index
                # (frames already carry the replica-local queue_depth), so
                # a merged dump timeline stays attributable per slice
                counters["dp_replica"] = lambda _i=i: _i
            recorder = FlightRecorder(
                enabled=self.obs_recorder,
                interval_ms=self.obs_recorder_interval_ms,
                dump_dir=self.obs_dump_dir,
                engine=model_id if n_dp == 1 else f"{model_id}#dp{i}",
                worker_id=self.worker_id,
                counter_fns=counters,
            )
            if i == 0:
                rep_params = params
            else:
                from ..parallel.sharding import shard_params

                rep_params = shard_params(params, sub, cfg)
            b = ContinuousBatcher(
                rep_params, cfg, max_slots=self.max_batch_slots,
                max_seq_len=self.max_seq_len,
                mesh=sub, max_queue=self.admit_queue_limit,
                max_queue_age_ms=self.admit_max_age_ms,
                prefix_cache_blocks=self.prefix_cache_blocks,
                spec_decode_k=self.spec_decode_k,
                spec_max_active=self.spec_max_active,
                brownout=self.brownout_cfg,
                hbm_headroom_fn=self._hbm_headroom_frac,
                deadline_min_tokens=self.deadline_min_tokens,
                paged=self.kv_paged,
                kv_block_tokens=self.kv_block_tokens,
                kv_pool_blocks=self.kv_pool_blocks,
                recorder=recorder,
                qos_quantum_tokens=self.qos_quantum_tokens,
                qos_preempt=self.qos_preempt,
                **({"prefill_chunk": self.prefill_chunk}
                   if self.prefill_chunk else {}),
            )
            # hierarchical KV tier manager, attached AFTER construction so
            # chunk_tokens matches the batcher's (possibly halved) prefill
            # chunk exactly — the tier is keyed by whole prefix-cache
            # chunks, and a mismatch would poison every demote/promote.
            # Per-replica managers: demote/promote stay owner-thread-local,
            # and per-replica spill namespaces keep the Object Store index
            # single-writer.
            if cfg.slot_state and self.kv_host_pool_bytes > 0:
                b.refusals["kv_tiers"] = (
                    "off: the host/Object-Store tiers hold KV blocks and no "
                    + ("recurrent state" + (" nor pooled keys" if cfg.is_sala else "")
                       if cfg.recurrent else "ring of the window layers")
                    + " (KV_HOST_POOL_BYTES=0 says the same)")
            if (
                self.kv_host_pool_bytes > 0
                and b.paged
                and b.prefix_cache is not None
            ):
                if cfg.is_mla:
                    b.stop()
                    raise ValueError(
                        f"{cfg.arch}: the host/Object-Store KV tiers spill "
                        "blocks as KVX1, which holds one shape for keys and "
                        "values; a latent cache's pair differs: set "
                        "KV_HOST_POOL_BYTES=0 to serve this model")
                from .kv_tiers import KVTierManager

                spill = None
                if self.kv_spill_factory is not None:
                    try:
                        spill = self.kv_spill_factory()
                    except Exception:  # noqa: BLE001
                        log.warning(
                            "kv spill store unavailable for %s; host tier "
                            "only", model_id, exc_info=True,
                        )
                ns = f"kv/{model_id}" if n_dp == 1 else f"kv/{model_id}/dp{i}"
                b.kv_tiers = KVTierManager(
                    self.kv_host_pool_bytes,
                    chunk_tokens=b.prefill_chunk,
                    spill=spill,
                    namespace=ns,
                    max_spill_objects=self.kv_spill_max_objects,
                    promote_chunks=self.kv_promote_chunks,
                    demote_free_frac=self.kv_demote_free_frac,
                )
            replicas.append(b)
        if n_dp > 1:
            from .dp import DataParallelBatcher

            batcher = DataParallelBatcher(replicas)
        else:
            batcher = replicas[0]
        if os.environ.get("TPU_WARM_ON_LOAD", "").strip() in ("1", "true"):
            # opt-in: compile every chunk/full-prefill program at load time
            # instead of pairing multi-second XLA compiles with the first
            # unlucky long requests (adds ~minutes to an 8B load on TPU,
            # which is why it is not the default)
            n_warm = batcher.warm_chunk_programs()
            log.info("warmed %d prefill programs for %s", n_warm, model_id)
        batcher.start()
        # restart-with-warm-cache: the Object Store tier survived the old
        # process, so re-import the deepest spilled chains without a live
        # donor. Best-effort — a full pool or a torn blob just means this
        # engine starts cold, exactly like before tiering existed.
        for r in replicas:
            tier = getattr(r, "kv_tiers", None)
            if tier is None:
                continue
            warmed = 0
            for export in tier.warm_exports(limit=4):
                try:
                    warmed += int(r.import_prefix_blocks(export).get("tokens", 0))
                except Exception:  # noqa: BLE001
                    break
            if warmed:
                log.info("warm-imported %d cached prefix tokens for %s",
                         warmed, model_id)
                obs_emit("kv_warm_import", model=model_id, tokens=warmed)
        load_s = time.perf_counter() - t0
        log.info("loaded %s in %.1fs (%s, %s)", model_id, load_s, cfg.arch, self.dtype)
        obs_emit("engine_load", model=model_id, seconds=round(load_s, 2),
                 arch=cfg.arch, dtype=self.dtype)
        return JaxChatEngine(
            model_id, batcher, tokenizer, cfg, meta, quantization="/".join(sorted(quant))
        )

    # -- engine supervision ---------------------------------------------------

    async def restart_engine(self, model_id: str, reason: str = "crash") -> str:
        """Tear down and relaunch one engine (the worker supervisor's action
        on a crashed or hung batcher). Returns "restarted", "poisoned" (too
        many crashes inside the window — refuse-until-reset), or "gone" (the
        engine was already unloaded by a concurrent delete/evict). A reload
        failure propagates as EngineError after the teardown."""
        if self.draining:
            return "draining"
        t0 = time.monotonic()
        async with self._load_lock:
            eng = self._engines.pop(model_id, None)
            if eng is None:
                return "gone"
            self._hbm_committed.pop(model_id, None)
            self._prefix_bytes.pop(model_id, None)
            self._last_used.pop(model_id, None)
            b = eng.batcher
            recorder = None
            if b is not None:
                from .dp import batcher_replicas

                # keep the Prometheus total alive past this batcher object
                # (summed over dp replicas — each keeps its own stats)
                self.inflight_failed_retryable += sum(
                    getattr(r.stats, "inflight_failed_retryable", 0)
                    for r in batcher_replicas(b)
                )
                # the dying batcher's flight recorder holds the pre-crash
                # timeline; keep it past unload so the restart dump below
                # can write it out
                recorder = getattr(b, "recorder", None)
            await eng.unload()
            obs_emit("engine_unload", model=model_id, reason=reason)
            now = time.monotonic()
            times = [
                t for t in self._crash_times.get(model_id, [])
                if now - t <= self.restart_window_s
            ]
            times.append(now)
            self._crash_times[model_id] = times
            if len(times) > self.max_restarts:
                why = (
                    f"{len(times)} crashes in {self.restart_window_s:.0f}s "
                    f"(last: {reason})"
                )
                self._poisoned[model_id] = why
                log.error("engine %s poisoned: %s", model_id, why)
                obs_emit("engine_poisoned", model=model_id, reason=why)
                return "poisoned"
            backoff = min(
                self.restart_backoff_s * (2 ** (len(times) - 1)),
                self.restart_backoff_max_s,
            )
        # backoff + reload OUTSIDE the load lock: a long XLA reload must not
        # block unrelated loads, and get_engine takes the lock itself
        await asyncio.sleep(backoff)
        if self.draining:
            # the drain began while we slept out the backoff — a worker
            # being scaled down must not resurrect its engine mid-teardown
            return "draining"
        await self.get_engine(model_id)
        self.engine_restarts_total += 1
        latency_ms = (time.monotonic() - t0) * 1e3
        self.restart_latency_ms.record(latency_ms)
        log.info("engine %s restarted in %.0f ms (reason: %s)",
                 model_id, latency_ms, reason)
        obs_emit("engine_restart", model=model_id, reason=reason,
                 ms=round(latency_ms, 1))
        if recorder is not None:
            # after the engine_restart emit, so the dump's event tail
            # contains the restart itself; force past the rate limiter —
            # the crash dump seconds earlier must not suppress this one
            recorder.dump(
                "engine_restart",
                force=True,
                extra={"model": model_id, "restart_reason": reason,
                       "restart_ms": round(latency_ms, 1)},
            )
        return "restarted"

    def engine_health(self) -> dict[str, dict[str, Any]]:
        """Per-engine liveness/readiness for the health subject: ``alive``
        (owner thread running, no crash), ``ready`` (alive and accepting
        submits), ``heartbeat_age_s`` (staleness; only meaningful when the
        batcher is not idle — an idle owner blocks on its inbox)."""
        mesh_shape = dict(self.mesh.shape) if self.mesh is not None else {}
        out: dict[str, dict[str, Any]] = {}
        for mid, eng in self._engines.items():
            b = eng.batcher
            if b is None or not hasattr(b, "alive"):
                continue
            out[mid] = {
                "alive": bool(b.alive),
                "ready": bool(b.alive and not b._stopping),
                "idle": bool(b.idle),
                "heartbeat_age_s": round(b.heartbeat_age_s(), 3),
                "brownout_level": int(getattr(b, "brownout_level", 0)),
            }
            reps = getattr(b, "replicas", None)
            if reps:
                # dp facade: aggregates above (alive=all, brownout=max,
                # heartbeat=min) plus per-replica routed load for the
                # health subject's drill-down
                out[mid]["dp"] = len(reps)
                out[mid]["replica_loads"] = b.replica_loads()
            if mesh_shape:
                out[mid]["mesh"] = mesh_shape
        return out

    def set_draining(self, flag: bool = True) -> None:
        """Raise (or clear) the elastic-drain flag: while set,
        ``restart_engine`` refuses to relaunch engines, so a supervisor
        restart racing a scale-down drain cannot resurrect the worker."""
        self.draining = bool(flag)

    def poisoned_models(self) -> dict[str, str]:
        return dict(self._poisoned)

    def loaded_engines(self) -> dict[str, Any]:
        return dict(self._engines)

    def stats(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "models_cached": len(self.store.cached()),
            "models_loaded": len(self._engines),
            "engine_requests": self._requests,
            "backend": jax.default_backend(),
            "hbm_committed_bytes": sum(self._hbm_committed.values()),
            "hbm_ledger": self.hbm_ledger.last_sample(),
        }
        if self.mesh is not None:
            out["mesh"] = dict(self.mesh.shape)
        if self.engine_restarts_total:
            out["engine_restarts"] = self.engine_restarts_total
        if self._poisoned:
            out["poisoned"] = dict(self._poisoned)
        from .dp import batcher_replicas

        batchers: dict[str, Any] = {}
        prefix: dict[str, Any] = {}
        for mid, eng in self._engines.items():
            if eng.batcher is None:
                continue
            reps = batcher_replicas(eng.batcher)
            for i, r in enumerate(reps):
                # dp>1 snapshots key per replica so per-slice load shows
                key = mid if len(reps) == 1 else f"{mid}#dp{i}"
                batchers[key] = r.stats.snapshot()
                if r.prefix_cache is not None:
                    prefix[key] = r.prefix_cache.stats()
        if batchers:
            out["batcher"] = batchers
        if prefix:
            out["prefix_cache"] = prefix
        return out
