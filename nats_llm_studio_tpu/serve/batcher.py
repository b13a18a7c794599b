"""Continuous batcher: concurrent requests share one fixed-width decode step.

SURVEY.md §7 puts this on the critical perf path (hard part #5): single-stream
decode is HBM-bound on reading the weights once *per token*; batching B
requests reads them once per B tokens. Design:

* one decode program compiled at a fixed ``[B, 1]`` batch width (no shape
  churn); empty slots run masked (token 0, pos 0, greedy) and are ignored
* requests prefill into a single-row cache (bucketed lengths) and are
  scattered into the shared ``[B, L, Hkv, S, D]`` cache at their slot index —
  joining and leaving never recompiles the decode step
* one dedicated owner thread drives the device (the decode loop is the one
  shared-mutable structure — SURVEY.md §5); asyncio callers talk to it
  through thread-safe queues
"""

from __future__ import annotations

import asyncio
import collections
import logging
import math
import os
import queue as _queue
import random
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import AsyncIterator

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.generator import SamplingParams, default_buckets
from ..engine.sampling import row_class, sample_rows
from ..models.config import ModelConfig
from ..models.llama import make_cache
from ..obs import LogHistogram, Trace
from ..obs import emit as obs_emit
from ..obs import spans as obs_spans
from ..obs.roofline import (
    SPEC_PROGRAMS,
    WASTE_CATEGORIES,
    classify_program,
    dispatch_shape_key,
    efficiency_enabled,
    note_programs,
    program_base,
)
from ..transport import faults as _faults
from ..ops.kvcache import (
    KVQ,
    has_state,
    is_quantized,
    kv_gather_block,
    kv_pool_read_blocks,
    kv_pool_write_row,
    kv_pool_zeros,
    state_row,
    table_rows_in_use,
)
from .block_pool import BlockPool, StatePool
from .brownout import LEVEL_NAMES, SHED_ONLY, BrownoutConfig, BrownoutController
from .prefix_cache import PrefixCache
from .programs import build_programs, prompt_tokens_arg, recorded_name, ring_name
from .qos import (
    ANON_TENANT,
    DEFAULT_PRIORITY,
    DrrScheduler,
    TenantStats,
    class_rank,
    class_weight,
)
from .spec import SpecConfig, SpecSlot, make_slot

# obs/ stays import-light: the span primitive gets the profiler's annotation
# from here, where JAX is imported anyway
obs_spans.use_annotation(jax.profiler.TraceAnnotation)

log = logging.getLogger(__name__)

# placeholder occupying a slot that a batched chunked admit has reserved but
# not yet written: decode steps during the chunk loop must neither deliver
# tokens for it nor let another admit claim the slot
_RESERVED = object()

# a decode burst that hands over more tokens than this (live rows x steps)
# gives each row's tokens to its stream as one event; 64 = 8 slots x a burst
# of 8, serve's defaults, whose streams keep a hand-over a token
_TOKEN_HANDOVERS_A_BURST = 64


# why a family whose slots keep something beside their KV blocks does not get
# a serving feature, by what the slot keeps: a recurrent state
# (models/ssm_hybrid.py) or the window layers' ring (models/swa_moe.py)
_SLOT_STATE_CAUSES = {
    "state": {
        "paged": "state-space models are served on the paged pool only (unset "
                 "KV_PAGED=0): the shared ring rolls and shifts rows "
                 "(compact_ring), and a recurrent state cannot be shifted",
        "kv_quant": "TPU_KV_QUANT=int8 is not implemented for state-space models "
                    "(two kv heads share a cache row; one scale a row cannot "
                    "serve both): unset TPU_KV_QUANT",
        "kv_tiers": "the host/Object-Store KV tiers hold KV blocks and no state: "
                    "a prefix promoted from a tier could not be decoded from: "
                    "set KV_HOST_POOL_BYTES=0",
        "prefix_cache": "off: the cache holds KV blocks and no snapshot of the "
                        "recurrent state at a block's end, so a hit could not be "
                        "decoded from",
        "spec_decode": "off: a verify would advance the state past rejected "
                       "drafts and the pool keeps no snapshot to go back to",
        "decode_xla": "DECODE_KERNEL=xla gathers a slot's blocks into a view and "
                      "scatters them back; state-space models decode on the pool "
                      "in place only: unset DECODE_KERNEL",
        "kv_transfer": "KVX1 carries KV blocks and no recurrent state: a prefix "
                       "transferred without its state could not be decoded from "
                       "(state-space models prefill where they decode)",
    },
    "ring": {
        "paged": "window-attention models are served on the paged pool only "
                 "(unset KV_PAGED=0): the shared-ring cache holds every layer's "
                 "whole context, and a window layer keeps a ring of its window "
                 "a slot",
        "kv_quant": "TPU_KV_QUANT=int8 is not implemented for window-attention "
                    "models (the ring has no scale leaf and its kernel reads plain "
                    "rows): unset TPU_KV_QUANT",
        "kv_tiers": "the host/Object-Store KV tiers hold KV blocks and no ring: a "
                    "prefix promoted from a tier would lack the window layers' "
                    "keys: set KV_HOST_POOL_BYTES=0",
        "prefix_cache": "off: the cache shares KV blocks, and a slot's ring of the "
                        "window layers' last keys cannot be shared by block nor "
                        "rebuilt from the full layers' blocks",
        "spec_decode": "off: a verify would write its drafts over ring places "
                       "whose keys are still in the window if a draft is "
                       "rejected, and the ring keeps nothing to go back to",
        "decode_xla": "DECODE_KERNEL=xla gathers a slot's blocks into a view; "
                      "window-attention models decode on the pool and the ring in "
                      "place only: unset DECODE_KERNEL",
        "kv_transfer": "KVX1 carries KV blocks and no ring: a prefix transferred "
                       "without the window layers' keys could not be decoded from "
                       "(window-attention models prefill where they decode)",
    },
}


# a linear-attention family (models/gdn_moe.py) keeps a recurrent state as the
# state-space family does: the same causes, but for what holds int8 KV back
_SLOT_STATE_CAUSES["linear"] = _SLOT_STATE_CAUSES["state"] | {
    "kv_quant": "TPU_KV_QUANT=int8 is not implemented for linear-attention models "
                "(the caches ride with a float32 state that has no scale leaf): "
                "unset TPU_KV_QUANT",
}


# a lightning / block-sparse family (models/sala.py) keeps a recurrent state
# and, for its sparse layers, a slot's pooled keys: the linear family's causes,
# with the pooled keys named where KV blocks alone would not do either
_SLOT_STATE_CAUSES["sparse"] = _SLOT_STATE_CAUSES["linear"] | {
    "kv_tiers": "the host/Object-Store KV tiers hold KV blocks, no state and no "
                "pooled keys: a prefix promoted from a tier could not be decoded "
                "from: set KV_HOST_POOL_BYTES=0",
    "prefix_cache": "off: the cache holds KV blocks and no snapshot of the "
                    "recurrent state at a block's end nor the sparse layers' "
                    "pooled keys, so a hit could not be decoded from",
}


def _slot_state_causes(cfg: ModelConfig) -> dict[str, str]:
    return _SLOT_STATE_CAUSES[
        "state" if cfg.n_ssm_layers else "sparse" if cfg.is_sala
        else "linear" if cfg.n_lin_layers else "ring"]


class BatcherStopped(RuntimeError):
    """Submit raced a shutdown (drain, or idle-eviction by the registry's
    HBM admission): the request was never queued. Callers map this to a
    retry-on-another-worker error envelope, same as a shed."""


class BatcherOverloaded(RuntimeError):
    """The admit queue is past its configured depth/age bound. Raised (or
    emitted) instead of queueing silently so NATS queue-group peers can
    absorb the overflow — a worker that hoards requests defeats the bus's
    load balancing (/root/reference/README.md:478-484). The r4 bench
    measured a silent 38.6 s p95 admit delay without this."""


class _PoolExhausted(BatcherOverloaded):
    """The paged-KV block pool ran dry (after reclaiming unpinned prefix
    cache blocks). Raised BEFORE any device dispatch touches the donated
    pool arrays, so the owner loop sheds just the one request instead of
    resetting the whole cache."""


class _ControlOp:
    """An owner-thread errand riding the request inbox.

    Disaggregated serving needs to read (export) and write (import) the
    paged KV pool and prefix cache, but those live as ``_run()`` locals
    owned by the batcher thread — the inbox is the only thread-safe way
    in. A control op is executed inline at intake (it never occupies a
    slot and never enters the waitlist); the submitting thread blocks on
    ``done`` and reads ``result``/``error``."""

    __slots__ = ("kind", "args", "done", "result", "error", "cancelled")

    def __init__(self, kind: str, args: dict):
        self.kind = kind  # "export" | "import" | "suspend_harvest"
        self.args = args
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        # set by a timed-out submitter: the owner skips the work and the
        # (already-gone) caller never reads the result
        self.cancelled = False

    def finish(self, result=None, error: BaseException | None = None) -> None:
        self.result = result
        self.error = error
        self.done.set()

    def emit(self, kind: str, value) -> None:
        """Duck-typed with _Request so the shutdown/crash drain paths
        (_drain_all, _fail_inflight_retryable) fail a queued control op
        instead of stranding its waiter until timeout."""
        if kind == "err":
            self.finish(error=value)
        else:
            self.finish(error=BatcherStopped(
                f"batcher stopped ({value}) before kv {self.kind} ran; "
                f"retry on another worker"
            ))


class _Suspended:
    """A slot parked on the host tier (swap-don't-shed). Holds the host
    copies of the slot's KV blocks plus everything resume needs to be
    bit-identical under greedy: position, rng step/seed, the spec-decode
    n-gram state (by reference — its history already includes every
    delivered token), and the request itself (whose ``emitted`` tail
    re-seeds the device carry token). Owner thread only."""

    __slots__ = ("req", "k", "v", "st", "n_blocks", "min_blocks", "pos",
                 "steps", "seed", "spec", "t_suspend", "reason")

    def __init__(self, req, k, v, n_blocks, pos, steps, seed, spec,
                 t_suspend, reason, min_blocks=None, st=None):
        self.req = req
        self.k = k
        self.v = v
        # host copies of the slot's recurrent state (K's and V's leaves, a
        # [1, ...] row each) for a family that keeps one beside its KV
        self.st = st
        self.n_blocks = n_blocks
        # resume gate: don't re-admit until this many blocks are free. For
        # a slot parked by a FAILED mid-decode growth this covers n_blocks
        # plus the growth it could not take — resuming at exactly n_blocks
        # would re-fail the same growth and park again, a livelock that
        # starves the slots the parking was meant to unblock.
        self.min_blocks = n_blocks if min_blocks is None else min_blocks
        self.pos = pos
        self.steps = steps
        self.seed = seed
        self.spec = spec
        self.t_suspend = t_suspend
        self.reason = reason


@dataclass
class _Request:
    prompt_ids: list[int]
    sp: SamplingParams
    loop: asyncio.AbstractEventLoop
    out: asyncio.Queue  # (kind, value): ("tok", id) | ("toks", [id, ..]) | ("end", reason) | ("err", exc)
    slot: int = -1
    pos: int = 0
    generated: int = 0
    t_enq: float = 0.0  # monotonic enqueue time (queue-delay metric)
    t_admit: float = 0.0  # monotonic admit-dispatch time (prefill metric)
    trace: Trace | None = None  # per-request span record (obs/trace.py)
    # set (from any thread; plain bool is GIL-safe) when the consumer is
    # gone — the owner thread frees the slot/queue entry at its next check
    # instead of decoding to max_tokens for nobody (VERDICT r4 missing #1)
    cancelled: bool = False
    # absolute monotonic deadline propagated from the client's budget
    # (None = no deadline); past it the request is shed before prefill or
    # cooperatively aborted mid-decode instead of burning device time for
    # a caller that has already given up
    deadline: float | None = None
    # distinguishes a deadline abort from a consumer-gone cancel when the
    # owner thread frees the slot (cause tag in cancel_causes/prometheus)
    deadline_hit: bool = False
    # -- constrained decoding / logprobs (the "ext" regime) ---------------
    # TokenDFA (serve/constrain.py) when response_format demands schema-
    # constrained output; cstate is the current DFA state, advanced on the
    # host at readback (the device only sees the per-state vocab mask)
    constrain: object | None = None
    cstate: int = 0
    want_logprobs: bool = False
    top_logprobs: int = 0
    # the rewind trick: an ext admit suppresses the fused-admit first token,
    # steps pos back one, and re-processes prompt[-1] through the masked ext
    # program — so token 0 obeys the mask and carries logprobs like every
    # later token, without a separate masked-prefill program family
    rewound: bool = False
    # -- device-time ledger (obs/roofline.py) -----------------------------
    # dispatch ms accrued on behalf of this request, split by program class;
    # finalized into BatcherStats.device_ms under an outcome category when
    # the request leaves (served / cancelled / deadline_abort / shed / ...)
    dev_prefill_ms: float = 0.0
    dev_decode_ms: float = 0.0
    # this request's share of its most recent spec-verify dispatch, so the
    # readback can move the rejected-draft fraction to "spec_rejected"
    dev_spec_ms: float = 0.0
    # outcome tag for prefill work that only exists because an upstream step
    # failed (disaggregated KV pull fell back to a local re-prefill): the
    # prefill share of a served request lands here instead of "served"
    waste_tag: str | None = None
    # token ids actually delivered to the consumer, in order. prompt_ids +
    # emitted is the slot's exact token history; slot suspend relies on it
    # (resume re-seeds the device carry token from the tail, and suspend
    # refuses a slot whose history length disagrees with its position)
    emitted: list = field(default_factory=list)
    # -- multi-tenant QoS (serve/qos.py) ----------------------------------
    # identity resolved by the gateway's API-key auth and carried on the
    # X-Tenant/X-Priority bus headers; raw-NATS callers default to the
    # anonymous standard tenant, so pre-QoS traffic schedules exactly as
    # before. ``weight`` overrides the class weight in DRR when the key
    # spec sets one (0 = derive from class).
    tenant: str = ANON_TENANT
    priority: str = DEFAULT_PRIORITY
    weight: float = 0.0

    @property
    def rank(self) -> int:
        """0 = batch (shed/preempt first) .. 2 = premium (shed last)."""
        return class_rank(self.priority)

    @property
    def drr_weight(self) -> float:
        return self.weight if self.weight > 0 else float(class_weight(self.priority))

    @property
    def is_ext(self) -> bool:
        return self.constrain is not None or self.want_logprobs

    def emit(self, kind: str, value) -> None:
        self.loop.call_soon_threadsafe(self.out.put_nowait, (kind, value))


@dataclass
class BatcherStats:
    requests: int = 0
    tokens: int = 0
    steps: int = 0
    peak_active: int = 0
    grouped_admits: int = 0  # requests admitted via the batched-admit path
    chunked_group_admits: int = 0  # long prompts admitted via batched chunking
    # a chunked group's launches: the rows each computed (its width), and
    # those of them that held tokens of their prompt; the times a group
    # went on at a smaller width, and the rows finished before their
    # group's last chunk
    chunk_rows_computed: int = 0
    chunk_rows_real: int = 0
    chunked_group_narrowings: int = 0
    chunked_group_early_finishes: int = 0
    ring_compactions: int = 0  # wrapped ring re-rolled to restore windows
    cancelled: int = 0  # consumer-gone requests whose slot/queue entry was freed
    shed: int = 0  # requests rejected at the depth bound or dropped at the age bound
    # in-flight requests failed with a retryable envelope by a pump-loop
    # crash (the supervisor's restart path harvests this into the registry
    # accumulator behind lmstudio_inflight_failed_retryable_total)
    inflight_failed_retryable: int = 0
    # first-seen (program, static-args) combos on the decode/verify paths —
    # each one is a fresh XLA compile (the pow2 window ladder is the
    # classic source; the Pallas decode kernel's whole-table grid keeps
    # this flat). Exposed as lmstudio_decode_recompiles_total.
    decode_recompiles: int = 0
    # speculative decoding (serve/spec.py): drafted = n-gram tokens sent to
    # verify dispatches, accepted = drafts the model's own distribution kept
    spec_verifies: int = 0  # width-(k+1) verify dispatches
    spec_drafted: int = 0
    spec_accepted: int = 0
    # rows admitted to a slot by what they ask of the sampler
    # (engine/sampling.py row_class): a greedy or a restricted row never
    # needs the whole-vocabulary draw, and a decode step runs it only while
    # an unrestricted row is live
    rows_greedy: int = 0
    rows_restricted: int = 0
    rows_unrestricted: int = 0
    # routed-expert layers (models/mla_moe.py), summed over decode steps and
    # expert layers: distinct experts the live rows hit, the most rows on one
    # expert, the live rows, and how many (step, layer) samples that is
    experts_hit: int = 0
    expert_rows_max: int = 0
    expert_rows: int = 0
    expert_steps: int = 0
    # the (row, pick) pairs the live rows routed, and those whose expert this
    # chip holds (all of them unless the chip holds a share of a layer's
    # experts: models/experts.py)
    moe_picks: int = 0
    moe_picks_held: int = 0
    # state-space layers (models/ssm_hybrid.py) and linear-attention layers
    # (models/gdn_moe.py): rows whose recurrent state a
    # decode step advanced (live rows x steps of every burst), the steps, the
    # slots whose state a step moved (the slots the launch's block table
    # lists x steps: over slots x steps it is the share of the state pool a
    # step reads and writes), and the admits by how their state was made (from
    # zeros in one dispatch, or carried from chunk to chunk of a prompt over
    # one chunk)
    state_rows: int = 0
    # block-sparse layers (models/sala.py), summed over decode steps, live
    # rows and sparse layers: the keys a row could see (live), the keys of the
    # blocks it picked and walked (picked: all of them while it is under the
    # dense length), and the rows still under it
    sparse_tokens_live: int = 0
    sparse_tokens_picked: int = 0
    sparse_rows_dense: int = 0
    state_steps: int = 0
    state_slots_moved: int = 0
    state_admits_fresh: int = 0
    state_admits_carried: int = 0
    # window layers beside full ones (models/swa_moe.py): the keys the live
    # rows of every decode burst attended to in ONE window layer and in ONE
    # full layer, summed over its steps, the steps, and the tokens admits
    # left in the rings of one window layer
    win_tokens: int = 0
    full_tokens: int = 0
    win_steps: int = 0
    ring_tokens: int = 0
    # the form the expert layers of a decode burst take: "hit_list" (only the
    # experts the live rows hit are read), "grouped" (a burst of 16 slots and
    # more: each pick computed on its own expert) or "dense" (models/mla_moe.py
    # expert_path); "" for a family without expert layers
    expert_path: str = ""
    # prompt rows through the expert layers, by the form expert_path gave
    # their dispatch: the static [B, T] of every prefill and fused admit
    # (padding rows and warm-up dispatches included: what the device
    # computed, not what a prompt held), counted on the host at dispatch
    expert_prefill_rows_grouped: int = 0
    expert_prefill_rows_dense: int = 0
    expert_prefill_rows_hit_list: int = 0
    # bounded log-bucket histograms (obs/histogram.py): O(1) record on the
    # batcher owner thread, O(buckets) snapshot from the asyncio metrics
    # handlers, fixed memory for the life of the worker. A reader that
    # wants one phase's samples subtracts two snapshots (``s1 - s0``).
    admit_delay_ms: LogHistogram = field(default_factory=LogHistogram)
    ttft_ms: LogHistogram = field(default_factory=LogHistogram)  # enqueue -> first token
    prefill_ms: LogHistogram = field(default_factory=LogHistogram)  # admit -> first token
    decode_step_ms: LogHistogram = field(default_factory=LogHistogram)  # per burst step
    tokens_per_step: LogHistogram = field(
        default_factory=lambda: LogHistogram(lo=1.0, hi=4096.0, growth=1.25)
    )
    # per-verify fraction of drafted tokens accepted; 0 is clamped to the
    # bottom bucket (LogHistogram needs lo > 0)
    spec_accept_rate: LogHistogram = field(
        default_factory=lambda: LogHistogram(lo=0.01, hi=1.0, growth=1.25)
    )
    # "depth" | "age" | "deadline" | "brownout" -> count
    shed_causes: dict = field(default_factory=dict)
    cancel_causes: dict = field(default_factory=dict)  # where the cancel landed
    # per-program device telemetry: one histogram per jit-grid program
    # (prefill1, decode_pos_paged, spec_verify, ...) of host dispatch wall
    # ms, plus tokens moved per dispatch. decode_step_ms stays the
    # readback-inclusive stream-experienced number; these decompose WHERE
    # the device time goes (a first call's entry includes its XLA compile,
    # which is exactly the spike worth seeing). Keys materialize on first
    # record; exposition copies the dict under the lock.
    program_ms: dict = field(default_factory=dict)  # name -> LogHistogram
    program_tokens: dict = field(default_factory=dict)  # name -> LogHistogram
    # -- device-time ledger (obs/roofline.py) -----------------------------
    # outcome category -> accumulated dispatch ms, and
    # tokens delivered (tokens accrue only under "served")
    device_ms: dict = field(default_factory=dict)
    device_tokens: dict = field(default_factory=dict)
    # exact sum of every dispatch's ms (the same samples program_ms buckets
    # approximately): reconciliation denominator for the ledger
    # (tests/test_efficiency.py holds the category sums to it within 10%)
    dispatch_ms_total: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def record_program(self, name: str, ms: float, tokens: float | None = None) -> None:
        """One jit-grid dispatch of ``name`` took ``ms`` (host wall: on an
        async backend this is dispatch time — execution may still be in
        flight — but a cold call's trace+compile is fully in here)."""
        self.dispatch_ms_total += ms  # owner-thread single writer
        h = self.program_ms.get(name)
        if h is None:
            with self._lock:
                h = self.program_ms.setdefault(name, LogHistogram())
        h.record(ms)
        if tokens is not None and tokens > 0:
            ht = self.program_tokens.get(name)
            if ht is None:
                with self._lock:
                    ht = self.program_tokens.setdefault(
                        name, LogHistogram(lo=1.0, hi=1e6, growth=1.25)
                    )
            ht.record(float(tokens))

    def program_histograms(self) -> dict[str, LogHistogram]:
        with self._lock:
            return dict(self.program_ms)

    def program_token_histograms(self) -> dict[str, LogHistogram]:
        with self._lock:
            return dict(self.program_tokens)

    def attribute_device_time(self, category: str, ms: float, tokens: int = 0) -> None:
        """Ledger entry: ``ms`` of device dispatch time resolved to an outcome
        ``category`` (roofline.WASTE_CATEGORIES, plus "failed" for crash
        paths). Tokens count only toward goodput ("served")."""
        with self._lock:
            self.device_ms[category] = self.device_ms.get(category, 0.0) + ms
            if tokens:
                self.device_tokens[category] = self.device_tokens.get(category, 0) + tokens

    def device_time_snapshot(self) -> dict:
        """{"ms": {category: ms}, "tokens": {category: n}} — the standard
        categories are always present (zero-filled) so exposition and the
        cluster rollup see stable families."""
        with self._lock:
            ms = {c: 0.0 for c in WASTE_CATEGORIES}
            ms.update(self.device_ms)
            tok = {c: 0 for c in WASTE_CATEGORIES}
            tok.update(self.device_tokens)
        return {"ms": ms, "tokens": tok}

    def goodput_tokens_per_device_s(self) -> float:
        """Served tokens per second of TOTAL attributed device time — waste
        in any category drags this below raw decode throughput."""
        with self._lock:
            total_ms = sum(self.device_ms.values())
            served = self.device_tokens.get("served", 0)
        return served / (total_ms / 1e3) if total_ms > 0 else 0.0

    def record_admit_delay(self, ms: float) -> None:
        """Queue delay (enqueue -> admit DISPATCH), ms — the scheduling
        half of TTFT the worker controls (the other half is the prefill
        itself, tracked separately in prefill_ms)."""
        self.admit_delay_ms.record(ms)

    def record_shed(self, cause: str = "depth", waited_ms: float | None = None) -> None:
        """Sheds happen on TWO threads (depth bound: submitter's event
        loop; age bound: batcher owner) — a bare ``+= 1`` can lose counts
        between them, and the bench asserts exact shed totals."""
        with self._lock:
            self.shed += 1
            self.shed_causes[cause] = self.shed_causes.get(cause, 0) + 1
        ev = {"cause": cause}
        if waited_ms is not None:
            ev["waited_ms"] = round(waited_ms, 1)
        obs_emit("shed", **ev)

    def record_cancel(self, where: str = "active") -> None:
        """Consumer-gone request reclaimed; all sites run on the owner
        thread, but the event ring wants the *where* for diagnosis."""
        self.cancelled += 1
        self.cancel_causes[where] = self.cancel_causes.get(where, 0) + 1
        obs_emit("cancel", where=where)

    def shed_cause_counts(self) -> dict[str, int]:
        with self._lock:
            return dict(self.shed_causes)

    def histograms(self) -> dict[str, LogHistogram]:
        """Name -> histogram, for Prometheus exposition (serve/worker.py)."""
        return {
            "admit_queue_delay_ms": self.admit_delay_ms,
            "ttft_ms": self.ttft_ms,
            "prefill_ms": self.prefill_ms,
            "decode_step_ms": self.decode_step_ms,
            "tokens_per_step": self.tokens_per_step,
            "spec_accept_rate": self.spec_accept_rate,
        }

    def record_moe(self, counters) -> dict[str, int]:
        """One burst's expert counters ([3 x layers, steps] ints: per layer
        the experts hit, the most rows on one expert, the live rows). Returns
        what the readback span carries: the burst's sums, and ``expert_path``
        where the batcher has named it."""
        c = np.asarray(counters).reshape(-1, 3, counters.shape[-1])
        burst = {"experts_hit": int(c[:, 0].sum()), "expert_rows_max": int(c[:, 1].sum()),
                 "expert_rows": int(c[:, 2].sum()), "expert_steps": int(c[:, 0].size)}
        for k, v in burst.items():
            setattr(self, k, getattr(self, k) + v)
        return burst | ({"expert_path": self.expert_path} if self.expert_path else {})

    def record_picks(self, rows: int, used: int, held=None) -> dict[str, int]:
        """One burst's (row, pick) pairs: its live rows over steps and expert
        layers (``expert_rows``) routed ``used`` picks each, and ``held``
        [layers, steps] of them landed on an expert this chip holds (None:
        the chip holds every expert). Returns what the readback span carries."""
        picks = rows * used
        burst = {"moe_picks": picks,
                 "moe_picks_held": picks if held is None else int(np.asarray(held).sum())}
        for k, v in burst.items():
            setattr(self, k, getattr(self, k) + v)
        return burst

    def picks_counters(self) -> dict[str, int]:
        """Exposed by serve/worker.py as lmstudio_moe_picks{,_held}_total."""
        return {"picks": self.moe_picks, "picks_held": self.moe_picks_held}

    def record_state(self, rows: int, steps: int, listed: int) -> dict[str, int]:
        """One decode burst of a family with a recurrent state: ``rows`` live
        rows advanced their state ``steps`` times, and the launch's block
        table listed ``listed`` slots for the state kernel to move. Returns
        what the readback span carries."""
        burst = {"state_rows": rows * steps, "state_steps": steps,
                 "state_slots_moved": listed * steps}
        for k, v in burst.items():
            setattr(self, k, getattr(self, k) + v)
        return burst

    def record_window(self, starts: list[int], steps: int, window: int) -> dict[str, int]:
        """One decode burst of a family with window layers beside full ones:
        its live rows begin at positions ``starts`` and take ``steps`` steps.
        Counts the keys they attend to in ONE layer of each kind (the row at
        position p sees p + 1 keys in a full layer and at most ``window`` of
        them in a window layer). Returns what the readback span carries."""
        full = sum(steps * (p + 1) + steps * (steps - 1) // 2 for p in starts)
        win = sum(min(p + j + 1, window) for p in starts for j in range(steps))
        burst = {"win_tokens": win, "full_tokens": full, "win_steps": steps}
        for k, v in burst.items():
            setattr(self, k, getattr(self, k) + v)
        return burst

    def record_sparse(self, starts: list[int], steps: int, cfg) -> dict[str, int]:
        """One decode burst of a family with block-sparse layers: its live
        rows begin at positions ``starts`` and take ``steps`` steps. A row at
        position p sees p + 1 keys a sparse layer; past the dense length it
        walks ``sparse_topk`` blocks, the last up to its own key. Returns what
        the readback span carries."""
        blk, layers = cfg.sparse_block, cfg.n_kv_layers
        live = picked = dense = 0
        for p in starts:
            for n in range(p + 1, p + steps + 1):
                live += n
                if n <= cfg.sparse_dense_len:
                    picked, dense = picked + n, dense + 1
                else:
                    picked += (cfg.sparse_topk - 1) * blk + (n - 1) % blk + 1
        burst = {"sparse_tokens_live": live * layers, "sparse_tokens_picked": picked * layers,
                 "sparse_rows_dense": dense * layers}
        for k, v in burst.items():
            setattr(self, k, getattr(self, k) + v)
        return burst

    def sparse_counters(self) -> dict[str, int]:
        """Exposed by serve/worker.py as lmstudio_sparse_*_total."""
        return {"tokens_live": self.sparse_tokens_live, "tokens_picked": self.sparse_tokens_picked,
                "rows_dense": self.sparse_rows_dense}

    def window_counters(self) -> dict[str, int]:
        """Exposed by serve/worker.py as lmstudio_swa_*_total."""
        return {"win_tokens": self.win_tokens, "full_tokens": self.full_tokens,
                "win_steps": self.win_steps, "ring_tokens": self.ring_tokens}

    def state_counters(self) -> dict[str, int]:
        """Exposed by serve/worker.py as lmstudio_ssm_*_total."""
        return {"state_rows": self.state_rows, "state_steps": self.state_steps,
                "state_slots_moved": self.state_slots_moved,
                "state_admits_fresh": self.state_admits_fresh,
                "state_admits_carried": self.state_admits_carried}

    def record_expert_prefill(self, form: str, rows: int) -> None:
        """One prefill dispatch of ``rows`` rows whose expert layers took
        ``form`` (models/mla_moe.py expert_path)."""
        name = f"expert_prefill_rows_{form}"
        setattr(self, name, getattr(self, name) + rows)

    def expert_prefill_rows(self) -> dict[str, int]:
        """Prompt rows through the expert layers by form, exposed by
        serve/worker.py as lmstudio_moe_prefill_rows_total{path=...}."""
        return {form: getattr(self, f"expert_prefill_rows_{form}")
                for form in ("grouped", "dense", "hit_list")}

    def moe_counters(self) -> dict[str, int]:
        """Expert-layer counters, exposed by serve/worker.py as
        lmstudio_moe_*_total (all zero for a family without expert layers)."""
        return {"experts_hit": self.experts_hit, "expert_rows_max": self.expert_rows_max,
                "expert_rows": self.expert_rows, "expert_steps": self.expert_steps}

    def spec_counters(self) -> dict[str, int]:
        """Speculative-decoding counters, exposed by serve/worker.py as the
        dedicated lmstudio_spec_*_total metric families."""
        return {
            "verifies": self.spec_verifies,
            "drafted": self.spec_drafted,
            "accepted": self.spec_accepted,
        }

    def count_admitted(self, sp, vocab_size: int) -> None:
        """One more request in a slot, and its row's class for the sampler."""
        self.requests += 1
        name = "rows_" + row_class(sp.temperature, sp.top_k, sp.top_p, vocab_size)
        setattr(self, name, getattr(self, name) + 1)

    def sampler_counters(self) -> dict[str, int]:
        """Rows admitted by sampler class, exposed by serve/worker.py as
        lmstudio_sampler_rows_total{class}."""
        return {"greedy": self.rows_greedy, "restricted": self.rows_restricted,
                "unrestricted": self.rows_unrestricted}

    def counters(self) -> dict[str, int]:
        """Monotonic counters, for Prometheus exposition."""
        return {
            "requests": self.requests,
            "tokens": self.tokens,
            "decode_steps": self.steps,
            "grouped_admits": self.grouped_admits,
            "chunked_group_admits": self.chunked_group_admits,
            "chunk_rows_computed": self.chunk_rows_computed,
            "chunk_rows_real": self.chunk_rows_real,
            "chunked_group_narrowings": self.chunked_group_narrowings,
            "chunked_group_early_finishes": self.chunked_group_early_finishes,
            "ring_compactions": self.ring_compactions,
            "cancelled": self.cancelled,
            "shed": self.shed,
            "inflight_failed_retryable": self.inflight_failed_retryable,
            "decode_recompiles": self.decode_recompiles,
        }

    def snapshot(self) -> dict:
        adm = self.admit_delay_ms.snapshot()
        ttft = self.ttft_ms.snapshot()
        pre = self.prefill_ms.snapshot()
        dec = self.decode_step_ms.snapshot()
        with self._lock:
            shed_causes = dict(self.shed_causes)
        return {
            "requests": self.requests,
            "tokens": self.tokens,
            "decode_steps": self.steps,
            "peak_active_slots": self.peak_active,
            "grouped_admits": self.grouped_admits,
            "chunked_group_admits": self.chunked_group_admits,
            "chunk_rows_computed": self.chunk_rows_computed,
            "chunk_rows_real": self.chunk_rows_real,
            "chunked_group_narrowings": self.chunked_group_narrowings,
            "chunked_group_early_finishes": self.chunked_group_early_finishes,
            "ring_compactions": self.ring_compactions,
            "cancelled": self.cancelled,
            "shed": self.shed,
            "inflight_failed_retryable": self.inflight_failed_retryable,
            "decode_recompiles": self.decode_recompiles,
            "spec_verifies": self.spec_verifies,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "rows_greedy": self.rows_greedy,
            "rows_restricted": self.rows_restricted,
            "rows_unrestricted": self.rows_unrestricted,
            "shed_causes": shed_causes,
            "tokens_per_step_avg": round(self.tokens / self.steps, 2) if self.steps else 0.0,
            "admit_queue_delay_p50_ms": round(adm.percentile(0.5), 1),
            "admit_queue_delay_p95_ms": round(adm.percentile(0.95), 1),
            "admit_queue_delay_max_ms": round(adm.vmax or 0.0, 1),
            "ttft_p50_ms": round(ttft.percentile(0.5), 1),
            "ttft_p95_ms": round(ttft.percentile(0.95), 1),
            "prefill_p50_ms": round(pre.percentile(0.5), 1),
            "prefill_p95_ms": round(pre.percentile(0.95), 1),
            "decode_step_p50_ms": round(dec.percentile(0.5), 1),
            "decode_step_p95_ms": round(dec.percentile(0.95), 1),
            "goodput_tokens_per_device_s": round(self.goodput_tokens_per_device_s(), 2),
            "device_ms": {
                k: round(v, 1) for k, v in self.device_time_snapshot()["ms"].items()
            },
        }


class ContinuousBatcher:
    """Owns the device loop for one loaded model."""

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        max_slots: int = 8,
        max_seq_len: int | None = None,
        buckets: list[int] | None = None,
        mesh=None,
        prefill_chunk: int = 256,
        decode_burst: int = 8,
        admit_coalesce_ms: float = 3.0,
        max_group_admit: int = 8,
        max_group_long: int = 4,
        max_queue: int = 0,
        max_queue_age_ms: float = 0.0,
        prefix_cache_blocks: int = 0,
        spec_decode_k: int = 0,
        spec_max_active: int = 4,
        brownout: BrownoutConfig | None = None,
        hbm_headroom_fn=None,
        deadline_min_tokens: int = 1,
        paged: bool | None = None,
        kv_block_tokens: int = 16,
        kv_pool_blocks: int = 0,
        recorder=None,
        kv_tiers=None,
        kv_suspend: bool | None = None,
        qos_quantum_tokens: int = 256,
        qos_preempt: bool | None = None,
    ):
        from ..models.llama import ensure_lm_head

        self.params = ensure_lm_head(params)
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = min(max_seq_len or cfg.max_seq_len, cfg.max_seq_len)
        self.buckets = buckets or default_buckets(self.max_seq)
        self.mesh = mesh
        # prompts longer than this prefill in chunks, with one shared decode
        # step interleaved between chunks so active streams' inter-token gap
        # is bounded by ~one chunk's prefill, not the whole prompt's
        # (VERDICT round-1 weak #4: head-of-line blocking on admit).
        # The chunk must divide max_seq: the final zero-padded [1, C] chunk
        # would otherwise write past the cache end, where dynamic-update-
        # slice clamps the start and corrupts earlier prefix slots.
        self.prefill_chunk = max(8, prefill_chunk)
        while self.max_seq % self.prefill_chunk and self.prefill_chunk > 8:
            self.prefill_chunk //= 2
        if self.max_seq % self.prefill_chunk:
            raise ValueError(
                f"max_seq_len={self.max_seq} must be divisible by a prefill "
                f"chunk >= 8; use a power-of-two max_seq_len"
            )
        # decode runs ``decode_burst`` steps per dispatch (one on-device
        # lax.scan), so the host dispatch + token readback is paid once per
        # N steps and tokens stream in bursts of N. 1 = token-by-token. The
        # default of 8 is not re-measured on a local chip.
        self.decode_burst = max(1, decode_burst)
        # how long an idle worker waits after the FIRST arrival for more
        # requests before admitting: a few ms turns a concurrent burst into
        # one batched admit dispatch instead of 1 + (m-1)
        self.admit_coalesce_ms = max(0.0, admit_coalesce_ms)
        # cap on one batched admit: bounds the set of compiled admit widths
        # (mpad in powers of two up to this) and one admit dispatch's
        # latency. Default 8 favors TTFT at light load; throughput-tuned
        # deployments raise it (a 96-client wave at 32 is 3 pipelined
        # [32, bucket] prefills instead of 12 [8, bucket] — bigger MXU
        # tiles, ~the dominant term in wave ramp time).
        self.max_group_admit = max(1, max_group_admit)
        # cap on one batched CHUNKED admit (long prompts): bounds the
        # [m, L, Hkv, S, D] transient row-cache pair the group prefills
        # into (HBM: m x 2 full-length rows) and the compiled widths.
        # Concurrent long prompts otherwise serialize one full chunked
        # prefill each — B=1 chunks at poor MXU utilization, measured ~4x
        # the wall time of one [4, C]-chunked pass on the chip (round 4).
        self.max_group_long = max(1, max_group_long)
        # overload bounds (0 = off). Depth: submit fails fast past this many
        # queued-not-yet-admitted requests. Age: the owner thread sheds
        # waiters older than this at admit time. Either bound turns silent
        # queueing into an immediate BatcherOverloaded the caller can route
        # to a queue-group peer (VERDICT r4 missing #2).
        self.max_queue = max(0, max_queue)
        self.max_queue_age_ms = max(0.0, max_queue_age_ms)
        # paged KV: one refcounted fixed-size-block pool replaces the
        # contiguous per-slot rings — live decode slots, the radix prefix
        # cache, and spec decode's positional layout all read/write through
        # per-slot block tables (vLLM PagedAttention + RadixAttention
        # sharing). Default ON; KV_PAGED=0 keeps the pre-paged contiguous
        # paths byte-for-byte (the equivalence baseline).
        if paged is None:
            paged = os.environ.get("KV_PAGED", "1").strip().lower() not in (
                "0", "false", "off"
            )
        self.paged = bool(paged)
        if cfg.is_mla:
            # what the latent-attention family does not serve yet, refused
            # here with its cause and its off-switch, never half-served
            if not self.paged:
                raise ValueError(
                    f"{cfg.arch}: latent-attention models are served on the "
                    "paged pool only (unset KV_PAGED=0): the shared-ring cache "
                    "has no latent form")
            if cfg.kv_quant == "int8":
                raise ValueError(
                    f"{cfg.arch}: TPU_KV_QUANT=int8 is not implemented for a "
                    "latent cache (the latent is key and value at once; one "
                    "scale a row cannot serve both): unset TPU_KV_QUANT")
            if kv_tiers is not None:
                raise ValueError(
                    f"{cfg.arch}: the host/Object-Store KV tiers spill blocks "
                    "as KVX1, which holds one shape for keys and values; a "
                    "latent cache's pair differs: set KV_HOST_POOL_BYTES=0")
        # what was asked for and this family does not serve, by feature: the
        # cause an operator reads on the metrics page and in health
        self.refusals: dict[str, str] = {}
        if cfg.slot_state:
            # a slot keeps something beside its KV blocks that no table
            # describes: a recurrent state (models/ssm_hybrid.py) or the
            # window layers' ring (models/swa_moe.py). What cannot be served
            # is refused with its cause: an error where the knob contradicts
            # the family, a feature turned off (and said so) where it is an
            # optimisation
            why = _slot_state_causes(cfg)
            if not self.paged:
                raise ValueError(f"{cfg.arch}: {why['paged']}")
            if cfg.kv_quant == "int8":
                raise ValueError(f"{cfg.arch}: {why['kv_quant']}")
            if kv_tiers is not None:
                raise ValueError(f"{cfg.arch}: {why['kv_tiers']}")
            if prefix_cache_blocks > 0:
                self.refusals["prefix_cache"] = why["prefix_cache"]
                prefix_cache_blocks = 0
            if spec_decode_k > 0:
                self.refusals["spec_decode"] = why["spec_decode"]
                spec_decode_k = 0
        if cfg.n_expert_only_layers and not all(
                isinstance(params["blocks"]["moe"][k], jax.Array)
                for k in ("w_up_e", "w_down_e")):
            raise ValueError(
                f"{cfg.arch}: WQUANT=int8 is not implemented for two-matrix experts "
                "in a latent (the hit-list and the grouped kernel read plain stacks, "
                "and the dense dispatch would stream every held expert every step): "
                "unset WQUANT")
        self._pool: BlockPool | None = None
        # the books of what a slot keeps beside its blocks: a state-space
        # family's state pool, a window-attention family's rings
        self._state_pool = None
        self._window_pool = None
        if self.paged:
            # block size: the requested tokens-per-block snapped down (pow2
            # halving) until it divides the prefill chunk — cached chunks
            # are then whole blocks, so a prefix-cache hit is a refcount
            # bump with no re-blocking. T | C | max_seq by construction.
            T = max(1, int(kv_block_tokens))
            while T > 1 and self.prefill_chunk % T:
                T //= 2
            self.kv_block_tokens = T
            self.blocks_per_row = self.max_seq // T
            # pool population (usable blocks; +1 for the permanently-
            # referenced null block 0). The default sizes for zero
            # starvation — every slot at max_seq plus the whole prefix
            # cache budget; serving deployments under-provision via
            # KV_POOL_BLOCKS to pack more slots in the same HBM (blocks
            # only materialize per-token, the whole point of paging).
            usable = (
                int(kv_pool_blocks)
                if kv_pool_blocks > 0
                else max_slots * self.blocks_per_row + max(0, prefix_cache_blocks)
            )
            self._pool = BlockPool(usable + 1, T)
            if cfg.slot_state:
                from ..parallel.memory import state_slot_bytes

                books = StatePool(
                    max_slots, state_slot_bytes(cfg),
                    lambda: sum(r is not None for r in self._slots))
                if cfg.recurrent:
                    self._state_pool = books
                else:
                    self._window_pool = books
        else:
            self.kv_block_tokens = 0
            self.blocks_per_row = 0
        # pow2 window-ladder cap: every distinct (program, window) pair on
        # the XLA decode path is a fresh jit compile. Bounding the ladder to
        # DECODE_LADDER_RUNGS rungs (max_seq halved rung-1 times, floor 8)
        # caps compiles per program; short contexts just read a larger
        # masked window (position masking keeps numerics identical).
        rungs = max(1, int(os.environ.get("DECODE_LADDER_RUNGS", "6")))
        f = max(8, self.max_seq >> (rungs - 1))
        self._win_floor = 1 << max(0, f - 1).bit_length()
        # first-seen static-arg combos per decode-path program (owner thread
        # only) — the proxy behind stats.decode_recompiles
        self._compiled_keys: set[tuple] = set()
        # decode-kernel selection (ops/paged_attention.py): "pallas" streams
        # pool blocks straight through each slot's table inside the
        # attention kernel; "xla" is the gather-view fallback; "auto"
        # (default) picks pallas only where Mosaic can tile the pool layout
        # AND a real TPU backend is attached (off-TPU the kernel runs under
        # the Pallas interpreter — right for equivalence tests, far too
        # slow for serving).
        self.decode_kernel = self._resolve_decode_kernel()
        # automatic prefix KV cache (serve/prefix_cache.py): chunk size IS
        # the (possibly halved) prefill chunk, so every cached block is a
        # boundary the chunked-prefill program can resume from. 0 = off,
        # and the admit paths are then byte-for-byte the uncached ones.
        # Paged mode: capacity is denominated in POOL BLOCKS, nodes hold
        # (epoch, block-id) payloads, and harvest/eviction are refcount
        # bumps/drops on the shared pool instead of block copies.
        if prefix_cache_blocks > 0 and self.paged:
            _pool = self._pool

            def _pc_acquire(payload):
                ep, ids = payload
                if ep == _pool.epoch:
                    _pool.incref(ids)

            def _pc_release(payload):
                ep, ids = payload
                _pool.decref(ids, epoch=ep)

            self.prefix_cache: PrefixCache | None = PrefixCache(
                self.prefill_chunk, prefix_cache_blocks,
                node_blocks=self.prefill_chunk // self.kv_block_tokens,
                acquire_fn=_pc_acquire, free_fn=_pc_release,
            )
        else:
            self.prefix_cache = (
                PrefixCache(self.prefill_chunk, prefix_cache_blocks)
                if prefix_cache_blocks > 0
                else None
            )
        # speculative decoding (serve/spec.py): k > 0 turns it on AND flips
        # the whole cache to POSITIONAL layout (slot = sequence position,
        # the ring_slot=None path of models.llama.forward). Per-slot
        # acceptance counts differ, so the shared-ring invariant ("every
        # row's history ends at one common head") cannot survive a verify;
        # positional layout has no shared head, and a rejected draft needs
        # no KV rollback — stale entries above the accepted length are
        # masked by position and overwritten by that row's next writes.
        # Tradeoff: positional decode writes via a per-row scatter (the
        # serialized-row cost the ring path exists to avoid), which is why
        # spec is worth it at LOW occupancy (the memory-bound regime) and
        # verify dispatches auto-disable above ``spec_max_active`` live
        # slots. 0 keeps the ring hot path byte-for-byte unchanged.
        self.spec_cfg: SpecConfig | None = (
            SpecConfig(k=spec_decode_k, max_active=max(1, spec_max_active))
            if spec_decode_k > 0
            else None
        )
        # adaptive brownout (serve/brownout.py): ticked by the owner thread
        # each main-loop iteration; None = off (every lever stays nominal).
        # ``hbm_headroom_fn`` is injected by the registry (the batcher has
        # no handle on HBM accounting) and returns the free-fraction of the
        # HBM budget, or None when no budget is configured.
        self.brownout: BrownoutController | None = (
            BrownoutController(brownout) if brownout is not None else None
        )
        self.hbm_headroom_fn = hbm_headroom_fn
        # wall spans the owner thread spent in a program's FIRST dispatch of
        # a shape (trace + XLA compile, or the persistent-cache load —
        # seconds each for an 8B model). A request that queued behind one
        # waited for a one-time cost, not for load, so the load signals
        # leave that part out (_warm_s). Appended by the owner thread only,
        # in time order; bounded, the oldest spans fall off.
        self._cold_spans: collections.deque = collections.deque(maxlen=64)
        # deadline feasibility floor: a request that cannot produce at least
        # min(deadline_min_tokens, its max_tokens) before its deadline —
        # estimated from the live prefill/decode rate EWMAs — is shed before
        # prefill instead of admitted to be aborted mid-stream
        self.deadline_min_tokens = max(1, deadline_min_tokens)
        # live rate EWMAs (owner thread only): prefill tokens/s measured at
        # first token, decode seconds/token measured per burst readback.
        # 0.0 = no sample yet (feasibility then only sheds the already-expired)
        self._prefill_rate_ewma = 0.0
        self._decode_spt_ewma = 0.0
        # per-verify draft acceptance EWMA (owner thread only) — the
        # recorder frame's one-number answer to "is spec still paying?"
        self._spec_accept_ewma = 0.0
        self.stats = BatcherStats()
        if cfg.n_moe_layers:
            from ..models.experts import expert_path, stats_width

            self._moe_stats_width = stats_width(cfg)

            # the form the expert layers of a call of ``rows`` rows take
            self._expert_form = lambda rows: expert_path(
                cfg, rows, self.params["blocks"]["moe"], mesh)
            # a decode burst is max_slots rows of one token
            self.stats.expert_path = self._expert_form(max_slots)
        # the forms the prefill dispatches of the open batcher.admit span
        # gave their expert layers (owner thread; _timed adds, _admit_span reads)
        self._admit_experts: set[str] = set()
        # the device-time ledger (obs/roofline.py): EFFICIENCY=0 turns it
        # off and _timed is then the plain timer
        self._efficiency = efficiency_enabled()
        # the requests the in-progress dispatch works for (owner thread
        # only); _timed splits each dispatch's ms across this context, and
        # dispatches with no context are ledgered as "other" (warmup,
        # compaction, CoW copies)
        self._charge_ctx: tuple | None = None
        # the group widths whose row takes are built (_build_takes)
        self._takes_built: set[int] = set()
        # flight recorder (obs/recorder.py): the owner loop samples one
        # frame per interval and the anomaly paths (crash, pool
        # exhaustion, SHED_ONLY entry) dump through it; None = off
        self.recorder = recorder
        # hierarchical KV tiers (serve/kv_tiers.py KVTierManager): host-RAM
        # spill + Object Store behind the paged prefix cache. Only
        # meaningful with paged KV AND a radix cache — the cache is both
        # the demotion source (evicted-not-discarded chunks) and the
        # promotion target. The manager holds host/Object-Store bytes only;
        # every device transfer stays on the owner thread.
        self.kv_tiers = (
            kv_tiers if (self.paged and self.prefix_cache is not None) else None
        )
        # slot suspend/resume (swap-don't-shed): on pool exhaustion or the
        # SHED_ONLY edge a victim slot's blocks + full resume state move to
        # host RAM and the slot resumes later, bit-identical under greedy.
        # None → KV_SUSPEND env; "0" is the kill switch that restores the
        # pre-tier shed-on-exhaustion behavior exactly.
        if kv_suspend is None:
            kv_suspend = os.environ.get("KV_SUSPEND", "1").strip().lower() not in (
                "0", "false", "off"
            )
        self.kv_suspend = bool(kv_suspend) and self.paged
        # suspended-slot records (owner thread mutates; len() is read
        # cross-thread for metrics/adverts — list ref swap + len are
        # GIL-safe) and lifetime suspend counters, kept off BatcherStats so
        # the stats snapshot shape stays a stable contract
        self._suspended: list = []
        self._suspend_stats = {
            "suspended_total": 0,
            "resumed_total": 0,
            "suspend_failures": 0,
            "suspended_deadline_expired": 0,
        }
        # multi-tenant QoS (serve/qos.py): admission is deficit round-robin
        # over per-tenant queues weighted by priority class — the owner loop
        # re-orders the waitlist through the scheduler before each admission
        # pass (single-tenant traffic degenerates to exact FIFO), brownout
        # sheds strictly batch < standard < premium at _enqueue, and with
        # ``qos_preempt`` a higher-class admit that finds the pool full
        # parks the lowest strictly-lower-class victim via the suspend path
        # (resumed bit-identically when pressure clears) before ever
        # shedding. QOS_PREEMPT=0 restores class-blind victim selection;
        # preemption rides the suspend machinery, so it needs paged KV.
        if qos_preempt is None:
            qos_preempt = os.environ.get("QOS_PREEMPT", "1").strip().lower() not in (
                "0", "false", "off"
            )
        self.qos_preempt = bool(qos_preempt) and self.kv_suspend
        self._drr = DrrScheduler(quantum=max(1, int(qos_quantum_tokens)))
        self.tenant_stats = TenantStats()
        # owner-maintained snapshot of the live slots for debug_snapshot()
        # (the real tables/host_pos are _run locals): slot -> {pos,
        # generated, blocks, ...}. Replaced wholesale each loop iteration
        # and entries popped at finish_slot, so an idle (inbox-blocked)
        # owner never leaves freed slots visible. Read from any thread —
        # plain dict ref swap is atomic under the GIL.
        self._slot_view: dict[int, dict] = {}

        # the device programs (serve/programs.py), each behind the dispatch
        # timer as ``self._<table name>``
        table = build_programs(
            cfg, mesh, max_seq=self.max_seq, paged=self.paged,
            kv_block_tokens=self.kv_block_tokens, sample_rows=sample_rows,
        )
        note_programs(table)  # the page's lmstudio_program_kind lines
        for name, fn in table.items():
            setattr(self, "_" + name, self._timed(name, fn))
        # per-dispatch ``_name=`` override: the ``_ring`` tag of a prefill
        # whose width takes the sp ring-attention path
        self._ring_name = partial(ring_name, cfg, mesh)

        self._inbox: _queue.Queue[_Request | None] = _queue.Queue()
        # cancel notices for the owner thread (consumer-gone requests); the
        # flag on the request is the source of truth, the queue is the wakeup
        self._cancels: _queue.Queue[_Request] = _queue.Queue()
        # owner-maintained mirror of len(waitlist) so _enqueue's depth bound
        # can see waiters that already left the inbox (approximate by a few
        # requests during an admit — fine for an overload guard)
        self._wl_len = 0
        self._slots: list[_Request | None] = [None] * max_slots
        self._thread: threading.Thread | None = None
        self._started = False
        self._stopping = False
        # serializes submit's stopped-check+enqueue against stop's
        # stopping-flag+sentinel so no request can slip into the inbox after
        # the final drain (submit would otherwise hang forever)
        self._submit_lock = threading.Lock()
        # supervision surface (serve/worker.py watchdog): the owner thread
        # stamps `heartbeat` once per main-loop iteration; `crashed` holds
        # the exception that killed the pump loop, if any. The waitlist is
        # an instance attr so a crash handler can fail waiters too.
        self.heartbeat = time.monotonic()
        self.crashed: BaseException | None = None
        self._waitlist: list[_Request] = []

    def _timed(self, name: str, fn):
        """Wrap one program of the table so every dispatch lands in
        stats.program_ms[name] (and, when the caller passes ``_tokens=``,
        tokens-per-dispatch in program_tokens[name]). Times the host-side
        call only — it never blocks on the result, so the depth-2 decode
        pipeline is untouched; decode_step_ms remains the
        readback-inclusive per-step number. ``name`` is the table's; what
        is recorded carries the family tag (programs.recorded_name).

        The first dispatch per shape-bucket is the one that traces and
        compiles: on the owner thread its wall span goes to ``_cold_spans``
        (see ``_warm_s``). Nothing builds the program ahead of that call.

        With the efficiency plane on, every dispatch is also charged, via
        the owner thread's charge context, to the per-request device-time
        ledger."""
        name = recorded_name(self.cfg, name)
        stats = self.stats
        ledger = self._efficiency
        seen: set = set()
        is_prefill = classify_program(name) == "prefill"
        is_spec = program_base(name) in SPEC_PROGRAMS
        # where a program of an expert family takes its [B, T] prompt tokens
        tokens_at = prompt_tokens_arg(fn) if self.cfg.n_moe_layers else None

        def run(*args, _tokens=None, _name=None, _chunk=None, **kwargs):
            key = dispatch_shape_key(args, kwargs)
            form = None
            if tokens_at is not None:
                rows = math.prod(args[tokens_at].shape)
                form = self._expert_form(rows)
                self._admit_experts.add(form)
                stats.record_expert_prefill(form, rows)
            t0 = time.monotonic()
            out = fn(*args, **kwargs)
            t1 = time.monotonic()
            ms = (t1 - t0) * 1e3
            if key not in seen:
                seen.add(key)
                if threading.current_thread() is self._thread:
                    # first dispatch of this shape: the call traced and
                    # compiled on the owner thread, which served nobody
                    # meanwhile
                    self._cold_spans.append((t0, t1))
            # _name: per-dispatch family tag (e.g. "prefill_full_ring" when
            # this bucket's program takes the sp ring path) — same jit, same
            # classification, distinct metrics row
            stats.record_program(_name or name, ms, _tokens)
            if _chunk is not None:
                # a chunk launch of a latent or a linear-attention family: a
                # record of its own in the ring (no annotation: the admit's
                # span is open around it), so that a reader prices the launches
                # of a traced span by their own rows and keys
                p1 = time.perf_counter()
                obs_spans.record("batcher.admit", p1 - (t1 - t0), p1, dict(
                    _chunk, program="chunk", **({"experts": form} if form else {})))
            if ledger:
                ctx = self._charge_ctx
                if ctx:
                    share = ms / len(ctx)
                    for r in ctx:
                        if is_prefill:
                            r.dev_prefill_ms += share
                        else:
                            r.dev_decode_ms += share
                            if is_spec:
                                r.dev_spec_ms = share
                else:
                    stats.attribute_device_time("other", ms)
            return out

        run.__name__ = f"timed_{name}"
        return run

    @contextmanager
    def _admit_span(self, **attrs):
        """A ``batcher.admit`` span. For a family with expert layers it also
        carries ``experts``: the form (``models/mla_moe.py expert_path``) its
        prefill dispatches gave their expert layers, from their static
        shapes ("grouped", "dense", "hit_list"; joined by "+" where an admit's
        dispatches differed)."""
        self._admit_experts.clear()
        if self._state_pool is not None:
            # how the slot's recurrent state is made: from zeros in one
            # dispatch, or carried from chunk to chunk of a longer prompt
            carried = attrs.get("tokens", 0) > self.prefill_chunk
            attrs["state"] = "carried" if carried else "fresh"
            if carried:
                self.stats.state_admits_carried += attrs.get("width", 1)
            else:
                self.stats.state_admits_fresh += attrs.get("width", 1)
        if self._window_pool is not None:
            # tokens the admit leaves in the rings of ONE window layer: a
            # ring holds a prompt's last ``window`` positions
            attrs["ring"] = attrs.get("width", 1) * min(
                attrs.get("tokens", 0), self.cfg.window)
            self.stats.ring_tokens += attrs["ring"]
        with obs_spans.span("batcher.admit", **attrs) as spn:
            try:
                yield spn
            finally:
                if self._admit_experts:
                    spn.attrs["experts"] = "+".join(sorted(self._admit_experts))

    def _chunk_attrs(self, start: int, lens: list[int], width: int = 1) -> dict | None:
        """What a chunk launch's ``batcher.admit`` record carries, for a
        latent or a linear-attention family or a state-space family with
        layers of experts (None otherwise). ``lens``: each
        row's real tokens from ``start`` on; ``width``: the rows the launch
        computes. ``rows`` are
        those that hold tokens of their prompt in this chunk, ``tokens``
        theirs (at most a chunk a row), ``live_keys`` the keys a row's chunk
        attends over (its prefix through this chunk) summed over the rows,
        ``pairs`` the (query, key) pairs of their causal attention."""
        if not (self.cfg.is_mla or self.cfg.n_lin_layers or self.cfg.n_expert_only_layers):
            return None
        real = [min(n, self.prefill_chunk) for n in lens if n > 0]
        return {"rows": len(real), "width": width, "tokens": sum(real),
                "live_keys": sum(start + t for t in real),
                "pairs": sum(t * start + t * (t + 1) // 2 for t in real)}

    def _ledger_finalize(self, req, category: str) -> None:
        """Resolve a request's accrued device time into an outcome category.

        ``category`` is one of roofline.WASTE_CATEGORIES (or "failed" for
        crash paths). A served request with a ``waste_tag`` (disaggregated
        KV-pull fallback) books its prefill share under the tag — that work
        only happened because the transfer failed. Tolerates duck-typed
        inbox entries (_ControlOp): they never accrue."""
        if not self._efficiency:
            return
        pre = getattr(req, "dev_prefill_ms", 0.0)
        dec = getattr(req, "dev_decode_ms", 0.0)
        if pre <= 0.0 and dec <= 0.0:
            return
        req.dev_prefill_ms = req.dev_decode_ms = req.dev_spec_ms = 0.0
        st = self.stats
        if category == "served":
            if req.waste_tag and pre > 0.0:
                st.attribute_device_time(req.waste_tag, pre)
                st.attribute_device_time("served", dec, req.generated)
            else:
                st.attribute_device_time("served", pre + dec, req.generated)
        else:
            st.attribute_device_time(category, pre + dec)

    def _tenant_served(self, req) -> None:
        """Per-tenant completion accounting for the QoS metrics plane:
        generated tokens (the billable unit) and queue age (admit wait —
        the fairness signal a starved tenant shows first)."""
        try:
            age_ms = max(0.0, (req.t_admit - req.t_enq) * 1e3)
            self.tenant_stats.record_served(req.tenant, req.generated, age_ms)
        except Exception:  # noqa: BLE001 — metrics must never kill the pump
            pass

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._thread = threading.Thread(
            target=self._run_guarded, name="batcher", daemon=True
        )
        self._thread.start()

    def _run_guarded(self) -> None:
        """Owner-thread entry: a pump-loop escape (device fault, injected
        chaos exception, bug) must not strand in-flight requests until their
        client timeouts — capture it, fail every in-flight/queued request
        with a *retryable* error, and leave the crash visible for the
        worker's supervisor to restart this engine."""
        try:
            self._run()
        except BaseException as e:  # noqa: BLE001 — watchdogs need everything
            self.crashed = e
            log.exception("batcher pump loop crashed")
            n = self._fail_inflight_retryable(e)
            obs_emit(
                "engine_crash", error=f"{type(e).__name__}: {e}",
                inflight_failed=n,
            )
            if self.recorder is not None:
                # the pre-crash timeline is exactly what the recorder is
                # for; the supervisor's restart writes a second (forced)
                # dump whose event tail includes the restart itself
                self.recorder.dump(
                    "engine_crash",
                    extra={
                        "error": f"{type(e).__name__}: {e}",
                        "inflight_failed": n,
                        "device_ms": self.stats.device_time_snapshot()["ms"],
                    },
                )

    def _fail_inflight_retryable(self, cause: BaseException) -> int:
        """Fail every in-flight and queued request with a BatcherStopped
        (its message carries the retryable marker, so clients with a
        RetryPolicy re-issue to a queue-group peer). Returns the count."""
        with self._submit_lock:
            self._stopping = True  # no new submits past this point
        err = BatcherStopped(
            f"engine crashed ({type(cause).__name__}: {cause}); "
            f"retry on another worker"
        )
        n = 0

        def fail(req: _Request) -> None:
            # count BEFORE emit: the emit wakes the consumer, which may read
            # the stats counter (health/metrics scrape) immediately
            nonlocal n
            n += 1
            self._ledger_finalize(req, "failed")
            self.stats.inflight_failed_retryable += 1
            req.emit("err", err)

        for req in self._waitlist:
            fail(req)
        self._waitlist.clear()
        self._wl_len = 0
        for i, req in enumerate(self._slots):
            if isinstance(req, _Request):
                fail(req)
            self._slots[i] = None
        self._slot_view = {}
        while True:
            try:
                req = self._inbox.get_nowait()
            except _queue.Empty:
                break
            if req is not None:
                fail(req)
        return n

    @property
    def brownout_level(self) -> int:
        """Current degradation level (0 normal / 1 brownout / 2 shed-only);
        0 when the controller is off. Plain int read — safe cross-thread."""
        return self.brownout.level if self.brownout is not None else 0

    @property
    def queue_depth(self) -> int:
        """Admitted-but-unscheduled work: waitlist + unread inbox. Two
        GIL-atomic reads — safe from any thread; the advert/router load
        signal (worker.build_advert, serve/dp.py replica pick)."""
        return self._wl_len + self._inbox.qsize()

    def _recorder_frame(self, depth: int, n_active: int) -> dict:
        """One compact flight-recorder frame (owner thread). Everything in
        here must be O(1)-ish: this runs once per OBS_RECORDER_INTERVAL_MS
        inside the pump loop."""
        st = self.stats
        fr = {
            "queue_depth": depth,
            "active_slots": n_active,
            "brownout_level": self.brownout_level,
            "decode_spt_ewma_ms": round(self._decode_spt_ewma * 1e3, 3),
            "spec_accept_ewma": round(self._spec_accept_ewma, 3),
            "requests": st.requests,
            "tokens": st.tokens,
            "shed": st.shed,
            "cancelled": st.cancelled,
            "inflight_failed_retryable": st.inflight_failed_retryable,
        }
        if self.hbm_headroom_fn is not None:
            try:
                hr = self.hbm_headroom_fn()
            except Exception:  # noqa: BLE001 — probe is best-effort
                hr = None
            if hr is not None:
                fr["hbm_headroom_frac"] = round(hr, 4)
        if self._pool is not None:
            ps = self._pool.stats()
            fr["pool_blocks_free"] = ps["blocks_free"]
            fr["pool_blocks_live"] = ps["blocks_live"]
            fr["pool_blocks_shared"] = ps["blocks_shared"]
        if self._suspended or self._suspend_stats["suspended_total"]:
            fr["suspended_slots"] = len(self._suspended)
            fr["suspended_total"] = self._suspend_stats["suspended_total"]
        if self.kv_tiers is not None:
            ts = self.kv_tiers.stats()
            fr["tier_host_bytes"] = ts["host_bytes"]
            fr["tier_host_entries"] = ts["host_entries"]
            fr["tier_demoted_chunks"] = ts["demoted_chunks"]
            fr["tier_promoted_chunks"] = ts["promoted_chunks"]
        if self._efficiency:
            dt = st.device_time_snapshot()["ms"]
            # only nonzero categories: frames are size-sensitive
            fr["device_ms"] = {k: round(v, 1) for k, v in dt.items() if v}
            fr["goodput_tokens_per_device_s"] = round(
                st.goodput_tokens_per_device_s(), 1
            )
        return fr

    def debug_snapshot(self) -> dict:
        """Deep live-state view for ``lmstudio.debug.snapshot``: the slot
        table (per-slot positions and block tables with refcounts), pool
        and prefix-cache summaries, brownout controller state, and the
        recorder ring tail. Safe from any thread — reads the owner's
        wholesale-replaced ``_slot_view`` plus the pool's locked stats."""
        pool = self._pool
        view = self._slot_view  # one GIL-atomic ref read
        slots: dict[int, dict] = {}
        for i, ent in sorted(view.items()):
            e = dict(ent)
            if pool is not None and e.get("blocks"):
                e["block_refcounts"] = [pool.refcount(b) for b in e["blocks"]]
            slots[i] = e
        snap: dict = {
            "max_slots": self.max_slots,
            "max_seq": self.max_seq,
            "paged": self.paged,
            "decode_kernel": self.decode_kernel,
            "kv_block_tokens": self.kv_block_tokens,
            "queue_depth": self._wl_len + self._inbox.qsize(),
            "slots": slots,
            "decode_spt_ewma_ms": round(self._decode_spt_ewma * 1e3, 3),
            "spec_accept_ewma": round(self._spec_accept_ewma, 3),
        }
        bo = self.brownout
        if bo is not None:
            snap["brownout"] = {
                "level": bo.level,
                "level_name": LEVEL_NAMES[bo.level],
                "transitions": bo.transitions,
            }
        if pool is not None:
            snap["pool"] = pool.stats()
        if self.prefix_cache is not None:
            snap["prefix_cache"] = self.prefix_cache.stats()
        if self.recorder is not None:
            snap["recorder_tail"] = self.recorder.tail(20)
            snap["recorder_frames_sampled"] = self.recorder.frames_sampled
        return snap

    def _note_prefill_rate(self, tokens: int, seconds: float) -> None:
        if seconds <= 0 or tokens <= 0:
            return
        rate = tokens / seconds
        prev = self._prefill_rate_ewma
        self._prefill_rate_ewma = rate if prev == 0.0 else 0.8 * prev + 0.2 * rate

    def _note_decode_spt(self, step_seconds: float) -> None:
        if step_seconds <= 0:
            return
        prev = self._decode_spt_ewma
        self._decode_spt_ewma = (
            step_seconds if prev == 0.0 else 0.8 * prev + 0.2 * step_seconds
        )

    def _estimate_serve_s(self, req: _Request) -> float:
        """Seconds to prefill ``req`` and decode its feasibility floor of
        tokens, from the live rate EWMAs (0.0 while cold — no sample means
        no informed shed, only already-expired ones)."""
        est = 0.0
        if self._prefill_rate_ewma > 0.0:
            est += len(req.prompt_ids) / self._prefill_rate_ewma
        min_tok = max(1, min(self.deadline_min_tokens, req.sp.max_tokens))
        est += min_tok * self._decode_spt_ewma
        return est

    def heartbeat_age_s(self) -> float:
        """Seconds since the owner thread last topped its main loop. Only
        meaningful while the batcher is NOT idle: a fully idle owner blocks
        on the inbox and legitimately stops stamping."""
        return time.monotonic() - self.heartbeat

    @property
    def alive(self) -> bool:
        """True while the owner thread is running and has not crashed."""
        return (
            self.crashed is None
            and self._thread is not None
            and self._thread.is_alive()
        )

    def stop(self) -> None:
        if not self._started or self._stopping:
            return
        with self._submit_lock:
            self._stopping = True
            self._inbox.put(None)
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        # anything enqueued between the owner thread's final drain and here
        self._drain_all("shutdown")
        if self.kv_tiers is not None:
            # flush pending spills so the Object Store tier is complete for
            # the restart-with-warm-cache path, then stop the spill thread
            self.kv_tiers.close()

    @property
    def idle(self) -> bool:
        """True when nothing is being served or queued (approximate snapshot,
        safe to read from any thread) — the registry's idle-eviction test.
        Consults the owner's waitlist mirror too: during the admit-coalesce
        window a request sits in neither the inbox nor a slot."""
        return (
            not any(s is not None for s in self._slots)
            and self._inbox.qsize() == 0
            and self._wl_len == 0
        )

    def warm_chunk_programs(self, widths: tuple[int, ...] | None = None) -> int:
        """Compile every (group-width, attention-window) chunked-prefill
        program this engine can reach, deterministically. Chunk windows are
        a pow2 ladder (``_win_bucket``), so one long admit touches several
        distinct programs; warming them by racing concurrent requests is
        timing-fragile — a missed width x window pairs a multi-second XLA
        compile with some unlucky request's TTFT. Call while the engine is idle; safe from any
        thread (pure jitted fns over fresh transient caches — serving K/V
        state is untouched). Returns the number of programs exercised."""
        C = self.prefill_chunk
        wins = sorted({self._win_bucket(s + C) for s in range(0, self.max_seq, C)})
        if widths is None:
            widths = (1,) + tuple(
                2 ** i for i in range(1, max(1, (self.max_group_long - 1).bit_length() + 1))
            )
        n = 0
        for m in widths:
            if m == 1:
                k1, v1 = self._make_row_cache(1, self.max_seq)
                for w in wins:
                    logits, k1, v1 = self._prefill1(
                        self.params, jnp.zeros((1, C), jnp.int32), k1, v1,
                        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), w,
                    )
                    n += 1
                # idle-engine full-prefill programs: every bucket an admit
                # length n in (C, max_seq) can map to — the pow2 ladder
                # PLUS the clamped max_seq bucket (a non-pow2 max_seq like
                # 4608 clamps there; sampling every C catches each edge).
                # Flash-gated like the serving shortcut itself: without the
                # kernel these programs are the dense-score blowup the
                # chunked path exists to avoid, and serving never runs them
                if self.cfg.whole_prompt_prefill:
                    full_buckets = sorted(
                        {self._win_bucket(x) for x in range(C + 1, self.max_seq + 1, C)}
                    )
                    for b_ in full_buckets:
                        logits, k1, v1 = self._prefill_full(
                            self.params, jnp.zeros((1, b_), jnp.int32), k1, v1,
                            jnp.int32(1),
                            _name=self._ring_name("prefill_full", b_),
                        )
                        n += 1
            else:
                km, vm = self._make_row_cache(m, self.max_seq)
                for w in wins:
                    logits, km, vm = self._prefill_chunk_group(
                        self.params, jnp.zeros((m, C), jnp.int32), km, vm,
                        jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.int32), w,
                    )
                    n += 1
                final = self._select_end(
                    jnp.zeros_like(logits, jnp.float32), logits,
                    jnp.asarray([False] * m, jnp.bool_),
                )
                n += self._build_takes(km, vm, final)
            jax.block_until_ready(logits)
        return n

    def _build_takes(self, km, vm, final) -> int:
        """Build the row takes from this group width to every narrower one
        (``take_rows``'s shapes depend on the two widths alone), on a cache
        pair and end logits as a chunk launch of the width leaves them: a
        group that narrows later, perhaps inside a measured window, then
        builds nothing. Called where a width's group program first runs and
        by ``warm_chunk_programs``. Returns the number of programs."""
        m = final.shape[0]
        widths = [m >> i for i in range(1, m.bit_length())]
        for w in widths:
            self._take_rows(km, vm, final, jnp.asarray([0] * w, jnp.int32))
        self._takes_built.add(m)
        return len(widths)

    def pool_stats(self) -> dict | None:
        """Paged-KV block pool counters for metrics/bench (None when the
        batcher runs the legacy contiguous layout). Thread-safe snapshot."""
        if self._pool is None:
            return None
        out = self._pool.stats()
        if self._state_pool is not None:
            out["state"] = self._state_pool.stats()
        if self._window_pool is not None:
            from ..parallel.memory import kv_pool_block_bytes

            # the rings' books, and beside them what the full layers' paged
            # pool takes: the two kinds of cache priced apart
            out["window"] = self._window_pool.stats() | {
                "kv_pool_bytes": self._pool.n_blocks * kv_pool_block_bytes(
                    self.cfg, self.kv_block_tokens)}
        return out

    def drop_prefix_cache(self) -> int:
        """Evict every cached prefix block and zero the budget (the
        registry's HBM-pressure hook). Safe from any thread: blocks pinned
        by an admit in flight are detached now and freed when that admit
        releases them. Returns the number of blocks evicted."""
        pc = self.prefix_cache
        return pc.resize(0) if pc is not None else 0

    def tier_stats(self) -> dict | None:
        """KV tier + slot-suspend counters for metrics/bench (None when
        neither tiering nor suspend is on). Thread-safe snapshot."""
        if self.kv_tiers is None and not self.kv_suspend:
            return None
        out = dict(self._suspend_stats)
        out["suspended"] = len(self._suspended)
        if self.kv_tiers is not None:
            out.update(self.kv_tiers.stats())
        if self.prefix_cache is not None:
            c = self.prefix_cache.counters()
            out["demoted_blocks"] = c.get("demoted_blocks", 0)
            out["demote_failures"] = (
                out.get("demote_failures", 0) + c.get("demote_failures", 0)
            )
        return out

    def suspend_harvest_to_cache(self, timeout: float = 30.0) -> dict:
        """Suspend every active slot and fold its full token history
        (prompt + generated, whole chunks) into the radix prefix cache,
        then fail the request with a retryable envelope. The drain path
        calls this at its deadline so a warm handoff ships *in-progress*
        work too: the survivor serves the client's retry as a prefix hit
        instead of re-prefilling from scratch (zero-lost-work preemption).
        Returns {"slots": n, "tokens": cached_tokens}."""
        return self._control(_ControlOp("suspend_harvest", {}), timeout)

    def _make_row_cache(self, batch: int, seq_len: int):
        """Fresh transient prefill cache, committed with the row sharding
        when a mesh is live (heads on tp — parallel.sharding.row_cache_spec)
        so the prefill jits compile against per-chip heads instead of
        inferring replication from an unsharded host array."""
        k, v = make_cache(self.cfg, batch, seq_len)
        if self.mesh is not None:
            from ..parallel.sharding import row_cache_spec, shard_cache

            k, v = shard_cache(
                k, v, self.mesh, spec=row_cache_spec(self.mesh, self.cfg)
            )
        return k, v

    def _shard_block(self, kb, vb):
        """Commit a gathered prefix-cache block pair to the row sharding
        (heads on tp). ``kv_gather_block`` slices eagerly; on a tp-only
        mesh the slice usually inherits the head sharding, but a dp/sp
        mesh's slice can land gathered on one device — the device_put
        makes per-chip residency deterministic, so a later hit's copy-in
        never pays an all-gather."""
        if self.mesh is None:
            return kb, vb
        from ..parallel.sharding import row_cache_spec, shard_cache

        return shard_cache(
            kb, vb, self.mesh, spec=row_cache_spec(self.mesh, self.cfg)
        )

    # -- client API ----------------------------------------------------------

    def _enqueue(
        self,
        prompt_ids: list[int],
        sp: SamplingParams,
        trace: Trace | None = None,
        deadline: float | None = None,
        constrain=None,
        want_logprobs: bool = False,
        top_logprobs: int = 0,
        waste_tag: str | None = None,
        tenant: str = ANON_TENANT,
        priority: str = DEFAULT_PRIORITY,
        weight: float = 0.0,
    ) -> _Request:
        if not prompt_ids:
            raise ValueError("empty prompt")
        if len(prompt_ids) >= self.max_seq:
            raise ValueError(f"prompt of {len(prompt_ids)} tokens >= max_seq {self.max_seq}")
        if (constrain is not None or want_logprobs) and not (
            self.paged or self.spec_cfg is not None
        ):
            # the rewind trick re-processes prompt[-1] at its own sequence
            # position — only the positional layouts can do that; the legacy
            # ring writes at a shared ring head and would corrupt the cache
            raise ValueError(
                "constrained decoding / logprobs require the positional KV "
                "layout (paged KV or spec decode); KV_PAGED=0 without spec "
                "cannot serve them"
            )
        req = _Request(
            prompt_ids=list(prompt_ids),
            sp=sp,
            loop=asyncio.get_running_loop(),
            out=asyncio.Queue(),
            t_enq=time.monotonic(),
            trace=trace,
            deadline=deadline,
            constrain=constrain,
            cstate=constrain.start if constrain is not None else 0,
            want_logprobs=want_logprobs or top_logprobs > 0,
            top_logprobs=int(top_logprobs),
            waste_tag=waste_tag,
            tenant=str(tenant or ANON_TENANT),
            priority=priority,
            weight=max(0.0, float(weight)),
        )
        self.tenant_stats.record_request(req.tenant)
        if trace is not None:
            trace.mark("enqueue", req.t_enq)
        # expired before it was even queued: shed at submit, zero device work
        # (the caller's budget is gone — serving it helps nobody)
        if deadline is not None and req.t_enq >= deadline:
            self.stats.record_shed("deadline")
            self.tenant_stats.record_shed(req.tenant)
            raise BatcherOverloaded(
                "deadline already expired at submit (shed_cause=deadline); "
                "retry on another worker"
            )
        bo = self.brownout
        with self._submit_lock:
            if self._stopping:
                raise BatcherStopped("batcher is stopped; retry on another worker")
            if bo is not None and bo.level >= SHED_ONLY and self.idle:
                # the owner loop only ticks the controller while it has work;
                # a fully drained pipeline parks it on the inbox, and a bounce
                # below never wakes it — the level would be stuck at shed-only
                # forever. Tick from the submit path with the current (calm)
                # signals so sustained retry traffic can step the level down.
                headroom = None
                if self.hbm_headroom_fn is not None:
                    try:
                        headroom = self.hbm_headroom_fn()
                    except Exception:  # noqa: BLE001 — probe is best-effort
                        headroom = None
                bo.update(
                    depth_frac=self._inbox.qsize()
                    / (self.max_queue or 4 * self.max_slots),
                    age_p95_ms=0.0,
                    hbm_headroom_frac=headroom,
                )
            if bo is not None and bo.level > req.rank:
                # priority-ordered brownout: the load-shed level IS the
                # lowest class still admitted — BROWNOUT (1) sheds batch
                # (rank 0), SHED_ONLY (2) sheds batch AND standard, and
                # premium (rank 2) is never brownout-shed (only the depth
                # bound below can refuse it). Rank-1 behavior at SHED_ONLY
                # is exactly the pre-QoS bounce every default-class caller
                # already saw.
                self.stats.record_shed("brownout")
                self.tenant_stats.record_shed(req.tenant)
                if bo.level >= SHED_ONLY:
                    msg = (
                        "brownout shed-only: worker saturated "
                        "(shed_cause=brownout); retry on another worker"
                    )
                else:
                    msg = (
                        f"brownout: {req.priority} class shed first "
                        f"(shed_cause=brownout); retry on another worker"
                    )
                raise BatcherOverloaded(msg)
            limit = (
                bo.effective_queue_limit(self.max_queue)
                if bo is not None
                else self.max_queue
            )
            if limit:
                # premium rides a 50% depth grace past the bound: a queue
                # full of lower classes must not bounce it — the owner loop
                # displaces the lowest-fair-share waiters instead (the
                # shed_cause=fair_share path)
                eff = limit + (limit >> 1) + 1 if req.rank >= 2 else limit
                if self._inbox.qsize() + self._wl_len >= eff:
                    self.stats.record_shed("depth")
                    self.tenant_stats.record_shed(req.tenant)
                    raise BatcherOverloaded(
                        f"admit queue full ({limit} waiting) "
                        f"(shed_cause=depth); retry on another worker"
                    )
            self._inbox.put(req)
        return req

    def cancel(self, req: _Request) -> None:
        """Mark a request's consumer as gone. The owner thread frees its
        slot (or drops it from the queue) at the next main-loop check —
        within one decode burst for an active stream. Idempotent."""
        req.cancelled = True
        self._cancels.put(req)

    async def submit(
        self,
        prompt_ids: list[int],
        sp: SamplingParams,
        info: dict | None = None,
        trace: Trace | None = None,
        deadline: float | None = None,
        constrain=None,
        want_logprobs: bool = False,
        top_logprobs: int = 0,
        waste_tag: str | None = None,
        tenant: str = ANON_TENANT,
        priority: str = DEFAULT_PRIORITY,
        weight: float = 0.0,
    ) -> AsyncIterator[int]:
        """Yield generated token ids for one request.

        When ``info`` is given, the batcher's end reason ("stop" / "length" /
        "shutdown") is recorded in ``info["finish_reason"]`` so callers report
        cache-capacity terminations truthfully instead of re-deriving from
        token counts. ``deadline`` is an absolute ``time.monotonic()`` value
        (the client's propagated budget): past it the request is shed before
        prefill or cooperatively aborted mid-decode."""
        async for batch in self.submit_batched(
            prompt_ids, sp, info=info, trace=trace, deadline=deadline,
            constrain=constrain, want_logprobs=want_logprobs,
            top_logprobs=top_logprobs, waste_tag=waste_tag,
            tenant=tenant, priority=priority, weight=weight,
        ):
            for tok in batch:
                yield tok

    async def submit_batched(
        self,
        prompt_ids: list[int],
        sp: SamplingParams,
        info: dict | None = None,
        trace: Trace | None = None,
        deadline: float | None = None,
        constrain=None,
        want_logprobs: bool = False,
        top_logprobs: int = 0,
        waste_tag: str | None = None,
        tenant: str = ANON_TENANT,
        priority: str = DEFAULT_PRIORITY,
        weight: float = 0.0,
    ) -> AsyncIterator[list]:
        """Like ``submit`` but yields LISTS of tokens: everything already
        delivered when the consumer wakes comes out as one batch. A decode
        burst lands on the event loop as ``decode_burst`` tokens at once,
        so the streaming layer can publish one NATS chunk per burst instead
        of per token — at 64+ concurrent streams the per-message publish
        overhead is a measurable share of served throughput.

        ``constrain`` is a serve/constrain.py TokenDFA (schema-constrained
        decoding); ``want_logprobs``/``top_logprobs`` switch each batch item
        from a bare token id to a ``(tok, logprob, top_ids, top_logprobs)``
        tuple. Either option routes the request through the single-step
        masked ext decode program."""
        if not self._started:
            self.start()
        if not prompt_ids:
            return
        req = self._enqueue(
            prompt_ids, sp, trace=trace, deadline=deadline,
            constrain=constrain, want_logprobs=want_logprobs,
            top_logprobs=top_logprobs, waste_tag=waste_tag,
            tenant=tenant, priority=priority, weight=weight,
        )
        done = False
        try:
            while True:
                kind, value = await req.out.get()
                batch: list[int] = []
                while True:
                    if kind == "tok":
                        batch.append(value)
                    elif kind == "toks":  # a decode burst's tokens of this row
                        batch.extend(value)
                    elif kind == "end":
                        done = True
                        if batch:
                            yield batch
                        if info is not None:
                            info["finish_reason"] = value
                        return
                    else:
                        done = True
                        if batch:
                            yield batch
                        raise value
                    try:
                        kind, value = req.out.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                yield batch
        finally:
            # consumer left before the stream ended (handler deadline fired,
            # client disconnected, generator closed): free the slot instead
            # of decoding to max_tokens for nobody. The Go reference gets
            # this from ctx threading into the HTTP call
            # (/root/reference/nats_llm_studio.go:328, :158-167); here the
            # cancel rides a thread-safe queue into the batcher owner.
            if not done:
                self.cancel(req)

    # -- disaggregated prefill/decode (serve/kv_transfer.py) -----------------

    def export_prefix_blocks(self, prompt_ids: list[int],
                             timeout: float = 30.0) -> dict | None:
        """Gather the prompt's cached full-chunk KV blocks to HOST memory.

        Returns the ``serve.kv_transfer`` export dict (token_ids /
        chunk_tokens / per-chunk k, v, logits leaves as numpy arrays or
        KVQ (codes, scales) pairs), or None when the prefix cache holds
        nothing useful for this prompt (short prompt, cache miss, pool
        reset). Thread-safe: marshals onto the owner thread through the
        inbox; blocking — call via ``asyncio.to_thread`` from a loop."""
        self._refuse_kv_transfer()
        return self._control(_ControlOp(
            "export", {"prompt_ids": [int(t) for t in prompt_ids]}
        ), timeout)

    def import_prefix_blocks(self, export: dict,
                             timeout: float = 30.0) -> dict:
        """Write a transferred prefill export into freshly allocated pool
        blocks and seed the radix prefix cache, so the matching request's
        admit becomes a prefix hit (full hit ⇒ zero local prefill work).
        Returns ``{"tokens": covered, "blocks": allocated}``. Raises
        ``BatcherOverloaded`` (cause ``kv_pool``) when the pool cannot
        hold the import — the decode-pool-exhaustion failure mode; the
        caller falls back to local prefill. Thread-safe and blocking,
        like :meth:`export_prefix_blocks`."""
        self._refuse_kv_transfer()
        return self._control(_ControlOp("import", {"export": export}), timeout)

    def _refuse_kv_transfer(self) -> None:
        cfg = self.cfg
        if cfg.slot_state:
            raise ValueError(f"{cfg.arch}: {_slot_state_causes(cfg)['kv_transfer']}")

    def _control(self, op: _ControlOp, timeout: float):
        if not self._started:
            self.start()
        with self._submit_lock:
            if self._stopping:
                raise BatcherStopped(
                    "batcher is stopped; retry on another worker"
                )
            self._inbox.put(op)
        if not op.done.wait(timeout):
            # the owner may still run it later; it checks this flag and
            # skips — nobody is left to read the result
            op.cancelled = True
            raise TimeoutError(
                f"kv {op.kind} control op timed out after {timeout:.1f}s"
            )
        if op.error is not None:
            raise op.error
        return op.result

    # -- device loop (owner thread) ------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_seq

    def _resolve_decode_kernel(self) -> str:
        """DECODE_KERNEL=pallas|xla|auto -> the kernel paged decode uses.

        The Pallas kernel needs the shard_map heads split to work
        (Hkv % tp == 0 — the replicated-KV GQA fallback stays on the XLA
        path) and, on a real TPU, a pool layout Mosaic can tile
        (``paged_decode_eligible``). "auto" picks it where both hold AND the
        TPU backend is attached (off-TPU the kernel only runs under the
        Pallas interpreter, which is what the equivalence tests want and
        what serving throughput does not), and downshifts to "xla"
        otherwise — the resolved kernel is exported as
        ``lmstudio_decode_kernel_pallas``. An explicit "pallas" that cannot
        be met raises: a worker never serves a kernel other than the one it
        was told to."""
        if not self.paged:
            return "xla"
        mode = os.environ.get("DECODE_KERNEL", "auto").strip().lower() or "auto"
        if mode not in ("pallas", "xla", "auto"):
            raise ValueError(
                f"DECODE_KERNEL must be pallas|xla|auto, got {mode!r}"
            )
        from ..ops.paged_attention import paged_decode_eligible

        cfg = self.cfg
        on_tpu = jax.default_backend() == "tpu"
        itemsize = 4 if cfg.dtype == "float32" else 2
        # off-TPU the interpreter runs any layout; Mosaic's tiling rules
        # only bind on the chip
        if cfg.slot_state:
            # the family has one decode path: the kernel over the pool
            # (packed rows in models/ssm_hybrid.py) and, for window layers,
            # the kernel over the ring; through the interpreter off the chip
            if mode == "xla":
                raise ValueError(f"{cfg.arch}: {_slot_state_causes(cfg)['decode_xla']}")
            (hp, width), _ = cfg.kv_cache_dims()
            if on_tpu and not paged_decode_eligible(
                    self.kv_block_tokens, width, itemsize, False, hp, 1):
                raise ValueError(
                    f"{cfg.arch}: the paged decode kernel cannot serve this "
                    f"layout (rows of {width} lanes, T={self.kv_block_tokens}) "
                    "and the family has no gather-view decode path")
            if cfg.n_win_layers and on_tpu:
                from ..ops.paged_attention import window_decode_eligible

                if not window_decode_eligible(cfg.window, cfg.head_dim, itemsize):
                    raise ValueError(
                        f"{cfg.arch}: the ring kernel cannot serve a window of "
                        f"{cfg.window} keys of {cfg.head_dim} lanes, and window "
                        "layers have no other decode path")
            return "pallas"
        if mode == "xla":
            return "xla"
        tp = 1
        if self.mesh is not None:
            from ..parallel.mesh import AXIS_TP

            tp = self.mesh.shape.get(AXIS_TP, 1)
        heads_split = tp <= 1 or cfg.n_kv_heads % tp == 0
        if cfg.is_mla:
            # the absorbed kernel (ops/mla_attention.py): one latent head
            from ..ops.mla_attention import mla_paged_decode_eligible

            eligible = tp <= 1 and (not on_tpu or mla_paged_decode_eligible(
                self.kv_block_tokens, cfg.kv_lora_rank, itemsize))
        else:
            eligible = heads_split and (not on_tpu or paged_decode_eligible(
                self.kv_block_tokens, cfg.head_dim, itemsize,
                cfg.kv_quant == "int8", cfg.n_kv_heads, tp,
            ))
        if mode == "auto":
            return "pallas" if (on_tpu and eligible) else "xla"
        if not eligible:
            raise ValueError(
                f"DECODE_KERNEL=pallas cannot serve this layout (Hkv="
                f"{cfg.n_kv_heads}, tp={tp}, T={self.kv_block_tokens}, D="
                f"{cfg.head_dim}, kv_quant={cfg.kv_quant}): it needs "
                f"Hkv % tp == 0 and a Mosaic-tileable pool block; use "
                f"DECODE_KERNEL=auto to fall back to xla"
            )
        return "pallas"

    def _warm_s(self, since: float, now: float) -> float:
        """Seconds of ``[since, now]`` the owner thread did not spend in a
        program's first dispatch (``_cold_spans``). The load signals read
        this, not the wall clock — the brownout controller's queue age and
        the prefill/decode rate EWMAs behind deadline feasibility: a worker
        that is compiling its first programs is slow once, not saturated,
        and must neither shed the requests that arrive meanwhile nor price
        the next prompt at compile speed."""
        cold = 0.0
        for start, end in reversed(self._cold_spans):
            if end <= since:
                break  # time-ordered: nothing older overlaps either
            cold += max(0.0, min(end, now) - max(start, since))
        return now - since - cold

    def _note_compile(self, program: str, *static) -> None:
        """Count first-seen static-arg combos on the decode/verify paths —
        each is a fresh XLA compile (owner thread only). The counter makes
        the pow2 ladder's compile cost visible next to the Pallas kernel's
        flat one (lmstudio_decode_recompiles_total)."""
        key = (program, *static)
        if key not in self._compiled_keys:
            self._compiled_keys.add(key)
            self.stats.decode_recompiles += 1

    def _win_bucket(self, n: int) -> int:
        """Power-of-two attention window >= n, clamped to max_seq — the
        chunked-prefill read bound. Independent of the (often coarse)
        prompt-length buckets: with buckets like [512, 2048, 16k] a
        bucket-based window reads the full 16k slab from chunk 3 on
        (exactly the r4 O(T^2) tail), while the pow2 ladder keeps reads
        proportional to the live prefix at a log-bounded compile count.
        The floor caps the ladder at DECODE_LADDER_RUNGS rungs total."""
        w = 1 << max(0, n - 1).bit_length()
        return min(max(w, self._win_floor), self.max_seq)

    def _run(self) -> None:
        cfg = self.cfg
        B = self.max_slots
        # speculative decoding OR paged KV: the WHOLE cache runs in
        # positional layout (see __init__) — ring head bookkeeping stays
        # frozen at the cold state and every shift/offset below is forced
        # to 0 so admitted prefixes land at sequence positions [0, n)
        spec = self.spec_cfg
        paged = self.paged
        use_pallas = paged and self.decode_kernel == "pallas"
        pool = self._pool
        T = self.kv_block_tokens
        MB = self.blocks_per_row
        positional = spec is not None or paged
        # per-slot n-gram index over prompt + generated tokens (owner-thread
        # state, created at the admit record's readback, dropped with the slot)
        spec_slots: list[SpecSlot | None] = [None] * B
        # ring head: the shared cache slot the next decode step writes; rows'
        # validity is "my last pos+1 ring slots", see models.llama.forward
        self._ring_next = 0
        self._ring_wrapped = False  # once True, windowed reads are unsafe

        def make_pool():
            """The device block pool pair [NB, L, Hkv, T, D] (KVQ under
            int8) — ONE allocation serves live slots, the prefix cache,
            and spec decode; per-slot worst-case rows are gone."""
            quant = cfg.kv_quant == "int8"
            dt = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
            # K and V alike for GQA; (latent, rotary key), one head each, for
            # MLA: nothing below this line looks into the pair
            KP, VP = (
                kv_pool_zeros((pool.n_blocks, cfg.n_kv_layers, h, T, width),
                              dtype=dt, quant=quant)
                for h, width in cfg.kv_cache_dims()
            )
            if cfg.slot_state:
                # the state pool (or the rings) beside the blocks: row i is
                # slot i's
                from ..models.llama import family_module
                from ..ops.kvcache import WithState

                KP, VP = (WithState(p, st, ax) for p, (st, ax) in zip(
                    (KP, VP), family_module(cfg).make_state(cfg, B)))
            if self.mesh is not None:
                from ..parallel.sharding import pool_spec, shard_cache

                KP, VP = shard_cache(
                    KP, VP, self.mesh, cfg=cfg,
                    spec=pool_spec(self.mesh, cfg),
                )
            return KP, VP

        if paged:
            K, V = make_pool()
        else:
            K, V = make_cache(cfg, B, self.max_seq)
            if self.mesh is not None:
                from ..parallel.sharding import shard_cache

                K, V = shard_cache(K, V, self.mesh, cfg=cfg)

        # paged-KV host bookkeeping (owner thread only): per-slot block
        # tables mirrored to a device [B, MB] int32 on table_dirty. Entries
        # past a slot's allocation are 0 (the null block).
        tables: list[list[int]] = [[] for _ in range(B)]
        tbl_dev = jnp.zeros((B, max(MB, 1)), jnp.int32)
        # rows of tbl_dev that name a block: the slots a decode launch of a
        # family with a recurrent state lists for its state kernel
        # (models/ssm_hybrid.py makes its list from the same table by the
        # same rule)
        tbl_rows_in_use = 0
        table_dirty = False

        # hierarchical KV tiers + slot suspend (owner-thread handles)
        tier = self.kv_tiers
        suspend_on = self.kv_suspend and paged

        def alloc_blocks(k: int, suspend_ok: bool = True,
                         internal: bool = False,
                         for_req: _Request | None = None) -> list[int]:
            """Take k fresh pool blocks; on shortage, reclaim unpinned
            prefix-cache blocks (the evictable tier — demoted to the host
            tier when one is attached, discarded otherwise), then suspend
            victim slots (swap-don't-shed), and only shed when every lever
            is exhausted. Raises _PoolExhausted BEFORE any device dispatch
            so the caller sheds one request instead of resetting the cache.

            ``suspend_ok=False`` marks decode-time growth (ensure_blocks/
            ensure_private): those run mid-burst-preparation over a frozen
            active-slot list, where removing a slot would corrupt the
            dispatch. ``internal=True`` marks opportunistic allocations
            (tier promotion, slot resume) — they must neither suspend
            another slot (thrash cycles) nor count a shed (the caller just
            defers the work), so exhaustion raises a quiet _PoolExhausted.

            ``for_req`` is the ADMITTING request (QoS preemption): a
            higher-class admit that finds the pool full first preempts
            strictly-lower-class victims (lowest class, largest table
            first) to the host tier — reason "preempted", resumed
            bit-identically when pressure clears — before falling back to
            the class-blind swap-don't-shed sweep."""
            got = pool.alloc(k)
            if got is None and pc is not None:
                pc.reclaim(k - pool.free_blocks, demote=tier is not None)
                got = pool.alloc(k)
            if got is None and suspend_on and suspend_ok and not internal:
                if (
                    self.qos_preempt
                    and for_req is not None
                    and for_req.rank > 0
                ):
                    # preempt-to-host-tier: only strictly-lower classes are
                    # eligible, so a premium admit never parks a premium peer
                    while got is None and suspend_victim(
                        below_rank=for_req.rank, reason="preempted"
                    ):
                        if pc is not None and pool.free_blocks < k:
                            pc.reclaim(
                                k - pool.free_blocks, demote=tier is not None
                            )
                        got = pool.alloc(k)
                # swap-don't-shed: demote whole victim slots (blocks + full
                # resume state) to the host tier until the allocation fits
                while got is None and suspend_victim():
                    if pc is not None and pool.free_blocks < k:
                        pc.reclaim(
                            k - pool.free_blocks, demote=tier is not None
                        )
                    got = pool.alloc(k)
            if got is None:
                if internal:
                    raise _PoolExhausted(
                        f"pool busy ({k} blocks needed, "
                        f"{pool.free_blocks} free); deferred"
                    )
                if suspend_ok:
                    # decode-time growth (suspend_ok=False) does NOT count
                    # a shed here: grow_for_burst may park the slot instead
                    # of shedding it, and records the shed itself when not
                    self.stats.record_shed("kv_pool")
                    if for_req is not None:
                        self.tenant_stats.record_shed(for_req.tenant)
                if self.recorder is not None:
                    # rate-limited (not forced): a starved pool sheds every
                    # admit attempt, one dump per window tells the story
                    self.recorder.dump(
                        "kv_pool_exhausted",
                        extra={"needed": k, "free": pool.free_blocks,
                               "device_ms": self.stats.device_time_snapshot()["ms"]},
                    )
                raise _PoolExhausted(
                    f"kv block pool exhausted ({k} blocks needed, "
                    f"{pool.free_blocks} free) (shed_cause=kv_pool); "
                    f"retry on another worker"
                )
            return got

        def ensure_blocks(i: int, upto: int) -> None:
            """Grow slot i's table to cover positions [0, min(upto,
            max_seq)) — decode/spec writes must land in owned blocks."""
            nonlocal table_dirty
            need = min(-(-min(upto, self.max_seq) // T), MB)
            tbl = tables[i]
            if len(tbl) < need:
                tbl.extend(alloc_blocks(need - len(tbl), suspend_ok=False))
                table_dirty = True

        def ensure_private(i: int, lo: int, hi: int) -> None:
            """Copy-on-write safety net: any block slot i is about to write
            in [lo, hi) that is still shared (refs > 1) gets a private
            copy first. Chunk-aligned sharing (T | C) means decode writes
            normally start past every shared block, so this almost never
            fires — but it keeps correctness independent of that layout
            argument."""
            nonlocal K, V, table_dirty
            tbl = tables[i]
            if not tbl:
                return
            b0 = lo // T
            b1 = min((min(hi, self.max_seq) - 1) // T, len(tbl) - 1)
            for b in range(b0, b1 + 1):
                bid = tbl[b]
                if bid != 0 and pool.refcount(bid) > 1:
                    nid = alloc_blocks(1, suspend_ok=False)[0]
                    K, V = self._pool_copy_block(
                        K, V, jnp.int32(nid), jnp.int32(bid)
                    )
                    pool.decref([bid])
                    pool.cow_copies += 1
                    tbl[b] = nid
                    table_dirty = True

        def grow_for_burst(act, upto_of, prev_ctx) -> bool:
            """Grow every active row's table (plus CoW privatization) ahead
            of a burst dispatch. ``ensure_blocks`` deliberately never
            suspends (the active-slot list is frozen mid-preparation), so
            exhaustion lands here — BEFORE any dispatch, device buffers
            intact. Resolve it by aborting the round and removing just the
            overflowing slot: PARK it on the host tier when parking can
            ever succeed (zero lost work — it resumes and regrows once
            blocks free up), shed it retryably when it cannot (its full
            extent exceeds the pool, or no other slot will ever release
            blocks, so resume would re-fail the same growth forever). The
            other streams keep their tokens either way; without this the
            escape used to reach the blanket dispatch handler and reset
            the whole cache. Returns False when the caller must skip the
            round (the slot list is stale)."""
            i = -1
            try:
                for i in act:
                    ensure_blocks(i, upto_of(i))
                    ensure_private(i, host_pos[i], upto_of(i))
                return True
            except _PoolExhausted as e:
                self._charge_ctx = prev_ctx
                r = self._slots[i]
                fits = isinstance(r, _Request) and min(
                    -(-(len(r.prompt_ids) + r.sp.max_tokens) // T), MB
                ) <= pool.n_blocks - 1
                others = any(
                    j != i and isinstance(self._slots[j], _Request)
                    for j in range(B)
                )
                need = min(-(-min(upto_of(i), self.max_seq) // T), MB)
                if (fits and others and suspend_on
                        and suspend_slot(i, "growth", min_blocks=need)):
                    return False
                r = self._slots[i]  # the suspend drain may have finished it
                if isinstance(r, _Request):
                    self.stats.record_shed("kv_pool")
                    self._ledger_finalize(r, "shed_after_prefill")
                    r.emit("err", e)
                    finish_slot(i)
                return False

        def refresh_tables() -> None:
            """Mirror the host block tables to the device [B, MB] array the
            paged decode/verify programs gather through."""
            nonlocal tbl_dev, tbl_rows_in_use, table_dirty
            if not table_dirty:
                return
            arr = np.zeros((B, max(MB, 1)), np.int32)
            for i, t in enumerate(tables):
                arr[i, : len(t)] = t
            tbl_dev = jnp.asarray(arr)
            tbl_rows_in_use = int(table_rows_in_use(arr).sum())
            table_dirty = False

        def paged_window(top: int) -> int:
            """Table-block count covering positions [0, top): the pow2
            window ladder in units of T (so gather-view extents match the
            contiguous path's attention windows program-for-program)."""
            w = min(max(self._win_bucket(top), T), self.max_seq)
            return w // T
        # device-resident next-token carry: burst k+1's input comes straight
        # from burst k's output ON DEVICE, so the host can dispatch k+1
        # before reading k's tokens back (the depth-2 pipeline below) — the
        # readback overlaps with compute instead of serializing after
        # every burst. Depth 2 is not re-measured on a local chip.
        tok_dev = jnp.zeros((B,), jnp.int32)
        # per-slot sampling tensors AND position/step/seed carries, rebuilt
        # only when membership changes (dirty); pos/steps advance ON DEVICE
        # as decode carries, so steady-state bursts upload nothing but the
        # ring scalar instead of three [B] transfers per burst
        temp = jnp.zeros((B,), jnp.float32)
        topk = jnp.zeros((B,), jnp.int32)
        topp = jnp.ones((B,), jnp.float32)
        pos_dev = jnp.zeros((B,), jnp.int32)
        steps_dev = jnp.zeros((B,), jnp.int32)
        seeds_dev = jnp.zeros((B,), jnp.int32)
        dirty = False

        # host-side OPTIMISTIC per-slot counters, advanced at DISPATCH time
        # (the device will have executed that many steps whether or not the
        # host has read the tokens yet): write position, rng step counter
        host_pos = [0] * B
        host_steps = [0] * B
        host_seed = [0] * B

        # in-flight dispatches whose results have not been read back:
        # ("decode", toks_ref, n, [(slot, req), ...], t, slots listed) |
        # ("ext", toks, lps, top_ids, top_lps, [(slot, req), ...], t, listed) |
        # ("admit", firsts_ref, [(row_in_firsts, slot, req), ...])
        inflight: collections.deque = collections.deque()

        def active() -> list[int]:
            # reserved (mid-chunked-admit) slots are excluded: the decode
            # program still computes their rows (fixed width, masked junk),
            # but no tokens are delivered and host bookkeeping stays frozen
            # until the group's finish dispatch writes them
            return [
                i for i, r in enumerate(self._slots) if isinstance(r, _Request)
            ]

        def ext_live() -> bool:
            # any live constrained/logprob slot forces the ext regime: the
            # burst/spec programs advance the device pos carry for EVERY
            # row, so an ext slot cannot sit out a normal dispatch — all
            # decode goes through the masked single-step program until the
            # last ext slot finishes
            return any(
                isinstance(r, _Request) and r.is_ext for r in self._slots
            )

        def finish_slot(i: int) -> None:
            self._slots[i] = None
            host_pos[i] = 0
            host_steps[i] = 0
            spec_slots[i] = None
            # keep the cross-thread slot view honest even when the loop is
            # about to block idle on the inbox (no rebuild tick follows)
            self._slot_view.pop(i, None)
            nonlocal dirty, table_dirty
            dirty = True
            if paged and tables[i]:
                # return only this slot's references — blocks still pinned
                # by the prefix cache (or another slot) stay live
                pool.decref(tables[i])
                tables[i] = []
                table_dirty = True

        def rebuild_slot_view() -> None:
            """Refresh the cross-thread slot snapshot (debug_snapshot's
            data source) from the owner-local tables/positions. Replaced
            wholesale — readers see one consistent dict via the GIL-atomic
            ref swap; block lists are copies, never the live tables."""
            view: dict[int, dict] = {}
            for i, r in enumerate(self._slots):
                if not isinstance(r, _Request):
                    continue
                ent = {
                    "pos": host_pos[i],
                    "prompt_tokens": len(r.prompt_ids),
                    "generated": r.generated,
                    "max_tokens": r.sp.max_tokens,
                    "cancelled": r.cancelled,
                }
                if r.trace is not None:
                    ent["trace_id"] = r.trace.trace_id
                if paged:
                    ent["blocks"] = list(tables[i])
                view[i] = ent
            self._slot_view = view

        def process_record(rec) -> None:
            """Block on one in-flight dispatch's readback, deliver tokens.

            A per-request delivery failure (e.g. the client's event loop was
            torn down mid-stream, so emit raises) only finishes THAT slot —
            it must not escape to the dispatch-failure reset and kill every
            healthy stream (the K/V buffers are fine; only np.asarray
            readback errors mean poisoned device state)."""
            nonlocal tok_dev, dirty
            if rec[0] == "decode":
                _, toks_ref, n, rows, t_disp, listed = rec
                with obs_spans.span("batcher.readback", program="decode") as spn:
                    ids = np.asarray(toks_ref)  # ONE [B, n] readback per burst
                    if ids.shape[0] > B:
                        # an expert family's burst: the rows past B are its
                        # counters (decode_pos_moe)
                        # (at width 4, a chip with a share of the experts,
                        # each layer's fourth row is the picks held here)
                        c = ids[B:].reshape(-1, self._moe_stats_width, ids.shape[-1])
                        burst = self.stats.record_moe(c[:, :3].reshape(-1, ids.shape[-1]))
                        spn.attrs.update(burst)
                        spn.attrs.update(self.stats.record_picks(
                            burst["expert_rows"], cfg.n_experts_used,
                            c[:, 3] if c.shape[1] > 3 else None))
                    if self._state_pool is not None:
                        spn.attrs.update(self.stats.record_state(len(rows), n, listed))
                    if self._window_pool is not None:
                        spn.attrs.update(self.stats.record_window(
                            [req.pos for _, req in rows], n, cfg.window))
                    if cfg.is_sala:
                        spn.attrs.update(self.stats.record_sparse(
                            [req.pos for _, req in rows], n, cfg))
                    if cfg.is_mla:
                        # the live rows' positions at the burst's first step:
                        # the latents the absorbed kernel reads, by row
                        spn.attrs["live_tokens"] = sum(req.pos for _, req in rows)
                # observed per-step latency (dispatch -> tokens readable);
                # includes pipeline wait, i.e. what a stream experiences
                now = time.monotonic()
                step_s = (now - t_disp) / n
                self.stats.decode_step_ms.record(step_s * 1e3)
                self._note_decode_spt(self._warm_s(t_disp, now) / n)
                # a wide burst's tokens of a row go to its stream in ONE
                # hand-over: a wake-up of the event loop a token (a GIL
                # release each) made the owner thread's way from here to its
                # next intake as long as the loop thread's way round a closed
                # loop at 24 live rows, and which request made an intake was
                # a race. A burst of up to _TOKEN_HANDOVERS_A_BURST tokens
                # keeps a hand-over a token: its streams' chunks, and so the
                # gaps a client measures between them, stay as they were
                one_event = len(rows) * n > _TOKEN_HANDOVERS_A_BURST
                with obs_spans.span("batcher.deliver") as spn:
                    tok0 = self.stats.tokens
                    for slot, req in rows:
                        if self._slots[slot] is not req:
                            continue  # finished at an earlier record; zombie rows
                        if req.cancelled:
                            self._ledger_finalize(
                                req, "deadline_abort" if req.deadline_hit else "cancelled"
                            )
                            finish_slot(slot)
                            self.stats.record_cancel(
                                "deadline" if req.deadline_hit else "decode"
                            )
                            continue
                        st = spec_slots[slot]
                        held: list[int] | None = [] if one_event else None
                        try:
                            for j in range(n):
                                req.pos += 1
                                t = int(ids[slot, j])
                                if st is not None:
                                    st.index.append(t)
                                reason = self._deliver(req, t, into=held)
                                if reason is not None:
                                    self._ledger_finalize(req, "served")
                                    self._tenant_served(req)
                                    finish_slot(slot)  # free BEFORE the end event
                                    if held:
                                        req.emit("toks", held)
                                        held = []
                                    req.emit("end", reason)
                                    break
                            if held:
                                req.emit("toks", held)
                        except Exception:  # noqa: BLE001 — dead client
                            log.exception("delivery failed; dropping slot %d", slot)
                            self._ledger_finalize(req, "cancelled")
                            finish_slot(slot)
                    spn.attrs["tokens"] = self.stats.tokens - tok0
            elif rec[0] == "spec":
                _, out_ref, nacc_ref, rows, t_disp = rec
                with obs_spans.span("batcher.readback", program="spec"):
                    ids = np.asarray(out_ref)  # [B, k+1]
                    nacc = np.asarray(nacc_ref)  # [B] emitted counts (a + 1)
                self.stats.decode_step_ms.record((time.monotonic() - t_disp) * 1e3)
                with obs_spans.span("batcher.deliver") as spn:
                    tok0 = self.stats.tokens
                    for slot, req, dlen in rows:
                        if self._slots[slot] is not req:
                            continue  # spec is depth-0, but stay defensive
                        n_emit = int(nacc[slot])
                        # host pos catches up to the device carry HERE (spec is
                        # the one dispatch whose advance is data-dependent);
                        # host_steps advanced by k+1 at dispatch
                        host_pos[slot] += n_emit
                        if dlen > 0:
                            self.stats.spec_drafted += dlen
                            self.stats.spec_accepted += n_emit - 1
                            rate = (n_emit - 1) / dlen
                            self.stats.spec_accept_rate.record(max(rate, 0.01))
                            prev = self._spec_accept_ewma
                            self._spec_accept_ewma = (
                                rate if prev == 0.0 else 0.8 * prev + 0.2 * rate
                            )
                            if self._efficiency and req.dev_spec_ms > 0.0:
                                # ledger: the rejected-draft fraction of this
                                # verify's cost moves out of the request's
                                # accrual immediately — it can never serve a
                                # token, whatever the request's outcome
                                waste = min(
                                    req.dev_spec_ms * (dlen + 1 - n_emit) / (dlen + 1),
                                    req.dev_decode_ms,
                                )
                                if waste > 0.0:
                                    req.dev_decode_ms -= waste
                                    self.stats.attribute_device_time(
                                        "spec_rejected", waste
                                    )
                                req.dev_spec_ms = 0.0
                        if req.cancelled:
                            self._ledger_finalize(
                                req, "deadline_abort" if req.deadline_hit else "cancelled"
                            )
                            finish_slot(slot)
                            self.stats.record_cancel(
                                "deadline" if req.deadline_hit else "decode"
                            )
                            continue
                        st = spec_slots[slot]
                        try:
                            for j in range(n_emit):
                                req.pos += 1
                                t = int(ids[slot, j])
                                if st is not None:
                                    st.index.append(t)
                                reason = self._deliver(req, t)
                                if reason is not None:
                                    self._ledger_finalize(req, "served")
                                    self._tenant_served(req)
                                    finish_slot(slot)  # free BEFORE the end event
                                    req.emit("end", reason)
                                    break
                        except Exception:  # noqa: BLE001 — dead client
                            log.exception("delivery failed; dropping slot %d", slot)
                            self._ledger_finalize(req, "cancelled")
                            finish_slot(slot)
                    spn.attrs["tokens"] = self.stats.tokens - tok0
            elif rec[0] == "ext":
                _, toks_ref, lp_ref, topids_ref, toplps_ref, rows, t_disp, listed = rec
                with obs_spans.span("batcher.readback", program="ext") as spn:
                    if self._state_pool is not None:
                        spn.attrs.update(self.stats.record_state(len(rows), 1, listed))
                    if self._window_pool is not None:
                        spn.attrs.update(self.stats.record_window(
                            [req.pos for _, req in rows], 1, cfg.window))
                    if cfg.is_sala:
                        spn.attrs.update(self.stats.record_sparse(
                            [req.pos for _, req in rows], 1, cfg))
                    ids = np.asarray(toks_ref)  # [B]
                    lps = np.asarray(lp_ref)  # [B]
                    tis = np.asarray(topids_ref)  # [B, LOGPROBS_K]
                    tls = np.asarray(toplps_ref)  # [B, LOGPROBS_K]
                now = time.monotonic()
                step_s = now - t_disp
                self.stats.decode_step_ms.record(step_s * 1e3)
                self._note_decode_spt(self._warm_s(t_disp, now))
                with obs_spans.span("batcher.deliver") as spn:
                    tok0 = self.stats.tokens
                    for slot, req in rows:
                        if self._slots[slot] is not req:
                            continue
                        if req.cancelled:
                            self._ledger_finalize(
                                req, "deadline_abort" if req.deadline_hit else "cancelled"
                            )
                            finish_slot(slot)
                            self.stats.record_cancel(
                                "deadline" if req.deadline_hit else "decode"
                            )
                            continue
                        st = spec_slots[slot]
                        try:
                            req.pos += 1
                            t = int(ids[slot])
                            if st is not None:
                                st.index.append(t)  # normal slot riding along
                            dead = False
                            if req.constrain is not None:
                                nstate = req.constrain.advance(req.cstate, t)
                                if nstate is not None:
                                    # (None only for an EOS outside an accept
                                    # state, which the mask already forbids —
                                    # _deliver maps stop ids to "stop" below)
                                    req.cstate = nstate
                                dead = not req.constrain.live(req.cstate)
                            if req.want_logprobs:
                                reason = self._deliver(
                                    req, t, logprob=float(lps[slot]),
                                    top_ids=tis[slot].tolist(),
                                    top_lps=tls[slot].tolist(),
                                )
                            else:
                                reason = self._deliver(req, t)
                            if reason is None and dead:
                                # the DFA can extend the document no further:
                                # the constrained output is complete
                                reason = "stop"
                            if reason is not None:
                                self._ledger_finalize(req, "served")
                                self._tenant_served(req)
                                finish_slot(slot)  # free BEFORE the end event
                                req.emit("end", reason)
                        except Exception:  # noqa: BLE001 — dead client
                            log.exception("delivery failed; dropping slot %d", slot)
                            self._ledger_finalize(req, "cancelled")
                            finish_slot(slot)
                    spn.attrs["tokens"] = self.stats.tokens - tok0
            else:
                _, firsts_ref, rows = rec
                with obs_spans.span("batcher.readback", program="admit"):
                    ids = np.asarray(firsts_ref)
                with obs_spans.span("batcher.deliver") as spn:
                    tok0 = self.stats.tokens
                    for row, slot, req in rows:
                        if self._slots[slot] is not req:
                            continue
                        if req.cancelled:
                            self._ledger_finalize(
                                req, "deadline_abort" if req.deadline_hit else "cancelled"
                            )
                            finish_slot(slot)
                            self.stats.record_cancel(
                                "deadline" if req.deadline_hit else "admit"
                            )
                            continue
                        if req.is_ext and not req.rewound:
                            # the rewind trick: the fused admit sampled token 0
                            # without mask or logprob readback — drop it, step
                            # the slot back one position, and put prompt[-1]
                            # back on the device carry. The next ext step
                            # re-processes prompt[-1] at position n-1 (the KV
                            # write repeats identical values; CoW privatizes any
                            # shared block first) and samples the REAL first
                            # token under the mask. host_steps resets to 0 so
                            # the delivered token 0 consumes rng (seed, step 0)
                            # exactly like an unconstrained first token would.
                            req.rewound = True
                            host_pos[slot] -= 1
                            host_steps[slot] = 0
                            tok_dev = tok_dev.at[slot].set(
                                jnp.int32(req.prompt_ids[-1])
                            )
                            dirty = True
                            continue
                        try:
                            first = int(ids[row])
                            reason = self._deliver(req, first)
                            if reason is not None:
                                self._ledger_finalize(req, "served")
                                self._tenant_served(req)
                                finish_slot(slot)  # free BEFORE the end event
                                req.emit("end", reason)
                            elif spec is not None:
                                # history = prompt + the first sampled token
                                # (still riding the device carry, unwritten)
                                spec_slots[slot] = make_slot(
                                    req.prompt_ids, first, spec
                                )
                        except Exception:  # noqa: BLE001 — dead client
                            log.exception("delivery failed; dropping slot %d", slot)
                            self._ledger_finalize(req, "cancelled")
                            finish_slot(slot)
                    spn.attrs["tokens"] = self.stats.tokens - tok0

        def pump(depth: int = 1) -> None:
            """Process oldest readbacks until at most ``depth`` dispatches
            remain in flight (depth 1 = one burst computing while the host
            delivers the previous one; depth 0 = fully drained)."""
            while len(inflight) > depth or (inflight and not active()):
                process_record(inflight.popleft())

        def drain_cancels(waitlist: list[_Request]) -> None:
            """Free slots / queue entries of consumer-gone requests. Runs
            once per main-loop iteration, so an active stream's slot is
            reclaimed within ~one decode burst of the cancel. Requests still
            in the inbox are dropped at intake via their flag; a request
            cancelled mid-group-admit is caught at first delivery (both
            paths count stats.cancelled exactly once — each checks the slot
            ownership before freeing)."""
            while True:
                try:
                    req = self._cancels.get_nowait()
                except _queue.Empty:
                    return
                if 0 <= req.slot < B and self._slots[req.slot] is req:
                    self._ledger_finalize(
                        req, "deadline_abort" if req.deadline_hit else "cancelled"
                    )
                    finish_slot(req.slot)
                    self.stats.record_cancel("active")
                elif req in waitlist:
                    waitlist.remove(req)
                    self.stats.record_cancel("waitlist")

        def maybe_compact() -> None:
            """Re-roll a wrapped ring when the live window is small enough
            that bounded reads pay for the one-off 2x-cache HBM roll. After
            the roll the head sits at max(live pos) and windowed attention
            resumes; re-triggering needs another full wrap, so the cost is
            amortized over >= (max_seq - head) decode steps."""
            nonlocal K, V
            if not self._ring_wrapped:
                return
            act = active()
            if not act:
                return
            head = max(host_pos[i] for i in act)
            if self._bucket(head + self.decode_burst) > self.max_seq // 2:
                return  # window too wide to be worth the roll yet
            shift = (head - self._ring_next) % self.max_seq
            K, V = self._compact_ring(K, V, jnp.int32(shift))
            self._ring_next = head
            self._ring_wrapped = False
            self.stats.ring_compactions += 1
            obs_emit("ring_compaction", shift=shift, head=head, active=len(act))

        def refresh_rows() -> None:
            """Re-upload the per-slot sampling tensors and pos/step/seed
            carries after a membership change (``dirty``)."""
            nonlocal temp, topk, topp, pos_dev, steps_dev, seeds_dev, dirty
            if not dirty:
                return
            live = [r if isinstance(r, _Request) else None for r in self._slots]
            temp = jnp.asarray(
                [r.sp.temperature if r else 0.0 for r in live], jnp.float32
            )
            topk = jnp.asarray([r.sp.top_k if r else 0 for r in live], jnp.int32)
            topp = jnp.asarray([r.sp.top_p if r else 1.0 for r in live], jnp.float32)
            pos_dev = jnp.asarray(host_pos, jnp.int32)
            steps_dev = jnp.asarray(host_steps, jnp.int32)
            seeds_dev = jnp.asarray(host_seed, jnp.int32)
            dirty = False

        def decode_once() -> None:
            """Dispatch one decode burst (decode_burst steps) for every
            active slot. Does NOT read the tokens back — the record goes on
            the in-flight queue and pump() delivers it while the next burst
            computes."""
            nonlocal K, V, tok_dev, dirty
            nonlocal pos_dev, steps_dev, seeds_dev
            act = active()
            if not act:
                return
            # charge this burst (and its CoW/alloc side dispatches) to the
            # active requests; restore the previous context because decode
            # interleaves inside admit chunk loops
            with obs_spans.span("batcher.dispatch", program="decode",
                                rows=len(act)) as spn:
                prev_ctx = self._charge_ctx
                self._charge_ctx = tuple(
                    r for r in (self._slots[i] for i in act) if isinstance(r, _Request)
                )
                refresh_rows()
                # cap the burst so no active row can run past the cache capacity.
                # n is a static jit arg: snap to single steps near capacity
                # instead of counting down through n-1 fresh compiles.
                # NOTE: with the depth-2 pipeline, host_pos may TRANSIENTLY sit at
                # or past max_seq for a row whose terminal burst is still awaiting
                # readback (the delivery in process_record ends it with "length").
                # Those zombie steps are safe: the ring mask's mod-S arithmetic
                # degrades to full-window attention once start_pos >= max_seq, so
                # the extra decode computes a token nobody delivers — headroom
                # may be <= 0 here and n=1 covers it.
                headroom = self.max_seq - 1 - max(host_pos[i] for i in act)
                # brownout shrinks the burst (shorter dispatch windows → faster
                # shed/abort reaction under pressure); n stays a static jit arg
                # from a tiny set {burst, burst//2, 1}, so compiles stay bounded
                burst = (
                    self.brownout.effective_burst(self.decode_burst)
                    if self.brownout is not None
                    else self.decode_burst
                )
                n = burst if headroom >= burst else 1
                spn.attrs["steps"] = n
                if paged:
                    # grow each row's table to cover its writes, privatize any
                    # still-shared block in the write range (CoW), then decode
                    # through the gathered block-table view. The view extent
                    # nb*T rides the SAME pow2 ladder as the contiguous
                    # positional window, so softmax reduction extents match
                    # bit-for-bit.
                    if not grow_for_burst(act, lambda i: host_pos[i] + n, prev_ctx):
                        return
                    refresh_tables()
                    if use_pallas:
                        self._note_compile("decode_pallas", n)
                        toks, K, V, tok_dev, pos_dev, steps_dev = (
                            self._decode_pallas(
                                self.params, tok_dev, K, V, tbl_dev, pos_dev,
                                seeds_dev, steps_dev, temp, topk, topp, n,
                                _tokens=len(act) * n,
                            )
                        )
                    else:
                        nb = paged_window(max(host_pos[i] for i in act) + n + 1)
                        self._note_compile("decode_pos_paged", n, nb)
                        toks, K, V, tok_dev, pos_dev, steps_dev = (
                            self._decode_pos_paged(
                                self.params, tok_dev, K, V, tbl_dev, pos_dev,
                                seeds_dev, steps_dev, temp, topk, topp, n, nb,
                                _tokens=len(act) * n,
                            )
                        )
                elif positional:
                    # writes land at each row's own position: the window only
                    # needs to cover the highest live position after the burst
                    # (pow2 ladder, same bounded-compile argument as prefill)
                    w = self._win_bucket(max(host_pos[i] for i in act) + n + 1)
                    window = w if w < self.max_seq else None
                    self._note_compile("decode_pos", n, window)
                    toks, K, V, tok_dev, pos_dev, steps_dev = self._decode_pos(
                        self.params, tok_dev, K, V, pos_dev,
                        seeds_dev, steps_dev, temp, topk, topp, n, window,
                        _tokens=len(act) * n,
                    )
                else:
                    # until the ring wraps, every live slot index is < ring_next:
                    # attention can read just a bucket covering the head (static
                    # windows come from self.buckets, so compiles stay bounded)
                    window = None
                    if not self._ring_wrapped:
                        w = self._bucket(self._ring_next + n)
                        if w < self.max_seq:
                            window = w
                    self._note_compile("decode", n, window)
                    toks, K, V, tok_dev, pos_dev, steps_dev = self._decode(
                        self.params, tok_dev, K, V, pos_dev, jnp.int32(self._ring_next),
                        seeds_dev, steps_dev, temp, topk, topp, n, window,
                        _tokens=len(act) * n,
                    )
                    if self._ring_next + n >= self.max_seq:
                        self._ring_wrapped = True
                    self._ring_next = (self._ring_next + n) % self.max_seq
                self.stats.steps += n
                self.stats.tokens_per_step.record(float(len(act)))
                for i in act:
                    host_pos[i] += n
                    host_steps[i] += n
                inflight.append(
                    ("decode", toks, n, [(i, self._slots[i]) for i in act], time.monotonic(),
                     tbl_rows_in_use)
                )
                self._charge_ctx = prev_ctx

        def decode_ext_once() -> None:
            """Dispatch ONE masked single-step decode covering every active
            slot (the ext regime). Constrained rows carry their DFA state's
            vocab mask; every other row gets all-True (a bitwise no-op
            inside _pick). Single-step because the mask for step i+1 is a
            host-side DFA walk over the token chosen at step i — the caller
            runs depth-0 (pump(0) before and after) for the same reason."""
            nonlocal K, V, tok_dev, dirty
            nonlocal pos_dev, steps_dev, seeds_dev
            act = active()
            if not act:
                return
            with obs_spans.span("batcher.dispatch", program="ext", rows=len(act),
                                steps=1):
                prev_ctx = self._charge_ctx
                self._charge_ctx = tuple(
                    r for r in (self._slots[i] for i in act) if isinstance(r, _Request)
                )
                refresh_rows()
                mask = np.ones((B, cfg.vocab_size), dtype=bool)
                for i in act:
                    r = self._slots[i]
                    if isinstance(r, _Request) and r.constrain is not None:
                        dm = r.constrain.mask(r.cstate)
                        mask[i, :] = False
                        mask[i, : dm.shape[0]] = dm
                mask_dev = jnp.asarray(mask)
                if paged:
                    if not grow_for_burst(act, lambda i: host_pos[i] + 1, prev_ctx):
                        return
                    refresh_tables()
                    if use_pallas:
                        self._note_compile("decode_pallas_ext")
                        (toks, lps, top_ids, top_lps, K, V, tok_dev, pos_dev,
                         steps_dev) = self._decode_pallas_ext(
                            self.params, tok_dev, K, V, tbl_dev, pos_dev,
                            seeds_dev, steps_dev, temp, topk, topp, mask_dev,
                            _tokens=len(act),
                        )
                    else:
                        nb = paged_window(max(host_pos[i] for i in act) + 2)
                        self._note_compile("decode_pos_paged_ext", nb)
                        (toks, lps, top_ids, top_lps, K, V, tok_dev, pos_dev,
                         steps_dev) = self._decode_pos_paged_ext(
                            self.params, tok_dev, K, V, tbl_dev, pos_dev,
                            seeds_dev, steps_dev, temp, topk, topp, mask_dev, nb,
                            _tokens=len(act),
                        )
                else:
                    w = self._win_bucket(max(host_pos[i] for i in act) + 2)
                    window = w if w < self.max_seq else None
                    self._note_compile("decode_pos_ext", window)
                    (toks, lps, top_ids, top_lps, K, V, tok_dev, pos_dev,
                     steps_dev) = self._decode_pos_ext(
                        self.params, tok_dev, K, V, pos_dev,
                        seeds_dev, steps_dev, temp, topk, topp, mask_dev, window,
                        _tokens=len(act),
                    )
                self.stats.steps += 1
                self.stats.tokens_per_step.record(float(len(act)))
                for i in act:
                    host_pos[i] += 1
                    host_steps[i] += 1
                inflight.append(
                    ("ext", toks, lps, top_ids, top_lps,
                     [(i, self._slots[i]) for i in act], time.monotonic(),
                     tbl_rows_in_use)
                )
                self._charge_ctx = prev_ctx

        def spec_once() -> bool:
            """Dispatch ONE verify forward when at least one live slot has a
            prompt-lookup draft. Returns False (caller runs a plain burst)
            when nothing drafted, a row is too close to the cache end for a
            width-(k+1) write, or there are no active slots. The caller must
            have DRAINED the pipeline first (proposals read each slot's full
            token history, which is only current after every readback) and
            must drain again right after (host pos catches up at readback)."""
            nonlocal K, V, tok_dev, dirty, pos_dev, steps_dev, seeds_dev
            act = active()
            if not act:
                return False
            kspec = spec.k
            if max(host_pos[i] for i in act) + kspec + 1 >= self.max_seq:
                # the per-row cache write would clamp past the end; the
                # plain burst path's n=1 capacity snap handles the tail
                return False
            drafts = np.zeros((B, kspec), np.int32)
            dlens = [0] * B
            total = 0
            for i in act:
                st = spec_slots[i]
                if st is None:
                    continue  # admit readback pending (caller drains first)
                d = st.index.propose(kspec)
                if d:
                    drafts[i, : len(d)] = d
                    dlens[i] = len(d)
                    total += len(d)
            if total == 0:
                return False  # nothing to verify: a plain burst is cheaper
            with obs_spans.span("batcher.dispatch", program="spec", rows=len(act),
                                steps=kspec + 1):
                prev_ctx = self._charge_ctx
                self._charge_ctx = tuple(
                    r for r in (self._slots[i] for i in act) if isinstance(r, _Request)
                )
                refresh_rows()
                if paged:
                    if not grow_for_burst(
                        act, lambda i: host_pos[i] + kspec + 1, prev_ctx
                    ):
                        return False  # slot list is stale; plain burst re-scans
                    refresh_tables()
                    if use_pallas:
                        self._note_compile("spec_verify_pallas", kspec)
                        out, nacc, K, V, tok_dev, pos_dev, steps_dev = (
                            self._spec_verify_pallas(
                                self.params, tok_dev, K, V, tbl_dev, pos_dev,
                                jnp.asarray(drafts), jnp.asarray(dlens, jnp.int32),
                                seeds_dev, steps_dev, temp, topk, topp,
                                _tokens=len(act) * (kspec + 1),
                            )
                        )
                    else:
                        nb = paged_window(max(host_pos[i] for i in act) + kspec + 1)
                        self._note_compile("spec_verify_paged", nb)
                        out, nacc, K, V, tok_dev, pos_dev, steps_dev = (
                            self._spec_verify_paged(
                                self.params, tok_dev, K, V, tbl_dev, pos_dev,
                                jnp.asarray(drafts), jnp.asarray(dlens, jnp.int32),
                                seeds_dev, steps_dev, temp, topk, topp, nb,
                                _tokens=len(act) * (kspec + 1),
                            )
                        )
                else:
                    w = self._win_bucket(max(host_pos[i] for i in act) + kspec + 1)
                    window = w if w < self.max_seq else None
                    self._note_compile("spec_verify", window)
                    out, nacc, K, V, tok_dev, pos_dev, steps_dev = self._spec_verify(
                        self.params, tok_dev, K, V, pos_dev,
                        jnp.asarray(drafts), jnp.asarray(dlens, jnp.int32),
                        seeds_dev, steps_dev, temp, topk, topp, window,
                        _tokens=len(act) * (kspec + 1),
                    )
                self.stats.steps += 1
                self.stats.spec_verifies += 1
                self.stats.tokens_per_step.record(float(len(act)))
                for i in act:
                    # rng streams advance by the verify width for every row
                    # (deterministic, matches the device carry); host_pos
                    # advances at READBACK — acceptance is data-dependent
                    host_steps[i] += kspec + 1
                inflight.append((
                    "spec", out, nacc,
                    [(i, self._slots[i], dlens[i]) for i in act],
                    time.monotonic(),
                ))
                self._charge_ctx = prev_ctx
                return True

        pc = self.prefix_cache

        def harvest_prefix(prompt_ids, kc, vc, row, chunk_logits,
                           skip_chunks: int = 0,
                           slot: int | None = None) -> None:
            """Insert the prompt's full-chunk KV blocks into the prefix
            cache, gathered from the transient row cache ``kc``/``vc`` at
            ``row``. MUST run before the donating finish dispatch consumes
            the transient (program order on the single device stream keeps
            the eager gather slices ahead of it). Insertion happens at
            ADMIT time, not completion — the blocks exist right here in
            un-rolled chunk-aligned layout, and a same-prefix burst already
            hits on its second member; gathering at completion would mean
            un-rolling them back out of the shared ring. ``skip_chunks``
            leading chunks were themselves cache hits: their nodes already
            exist, so None placeholders skip the gather."""
            if pc is None:
                return
            if self.brownout is not None and self.brownout.pause_prefix_harvest:
                return  # browned out: admits stop paying the block copy-out
            C = self.prefill_chunk
            n_full = len(prompt_ids) // C
            if n_full <= skip_chunks:
                return
            if paged and slot is not None:
                # zero-copy harvest: the cache nodes hold pool BLOCK IDS
                # (refcount bumps in acquire_fn), not device copies — the
                # KV bytes already live in the slot's blocks. Epoch-tagged
                # so payloads from before a pool reset free as no-ops.
                nbc = C // T
                tbl = tables[slot]
                payloads: list = [None] * skip_chunks
                for j in range(skip_chunks, n_full):
                    ids = tbl[j * nbc : (j + 1) * nbc]
                    payloads.append(
                        (pool.epoch, list(ids)) if len(ids) == nbc else None
                    )
                pc.insert(
                    list(prompt_ids[: n_full * C]), payloads, chunk_logits
                )
                return
            blocks: list = [None] * skip_chunks
            for j in range(skip_chunks, n_full):
                blocks.append(self._shard_block(
                    kv_gather_block(kc, row, j * C, C),
                    kv_gather_block(vc, row, j * C, C),
                ))
            pc.insert(list(prompt_ids[: n_full * C]), blocks, chunk_logits)

        def _host_kv(x):
            """Device block view -> host leaves (KVQ ships as a pair)."""
            if is_quantized(x):
                return (np.asarray(x.q), np.asarray(x.s))
            return np.asarray(x)

        def _dev_kv(leaf):
            """Host leaves -> the row shape kv_pool_write_row wants."""
            if isinstance(leaf, tuple):
                q, s = leaf
                return KVQ(q=jnp.asarray(np.asarray(q)),
                           s=jnp.asarray(np.asarray(s)))
            return jnp.asarray(np.asarray(leaf))

        if tier is not None and pc is not None and paged:
            def _demote_chunk(token_ids, payload, logits) -> bool:
                """Prefix-cache eviction hook (owner thread, pc lock held):
                read the evicted node's pool blocks back to host in one
                batched gather and hand them to the tier manager — LRU
                eviction becomes demotion. False (plain eviction) for
                payloads that survived a pool reset: their ids reference
                recycled blocks."""
                ep, ids = payload
                if ep != pool.epoch:
                    return False
                bids = jnp.asarray(ids, jnp.int32)
                k_host = _host_kv(kv_pool_read_blocks(K, bids))
                v_host = _host_kv(kv_pool_read_blocks(V, bids))
                return tier.demote(token_ids, k_host, v_host, logits)

            pc.demote_fn = _demote_chunk

        def suspend_slot(i: int, reason: str,
                         min_blocks: int | None = None) -> bool:
            """Demote slot i (KV blocks + full resume state) to the host
            side and free the slot — swap-don't-shed. Returns False with
            the slot untouched when it is not suspendable (mid-admit, no
            tier-consistent state, readback failure); the caller falls back
            to the existing shed path. Chaos hook: a ``raise`` rule at
            SUSPEND is a worker dying mid-suspend (pump crash, supervisor
            restart); any other kind aborts the suspend before any state
            has moved."""
            if not suspend_on:
                return False
            req = self._slots[i]
            if not isinstance(req, _Request) or req.cancelled:
                return False
            if _faults.ACTIVE is not None:
                f = _faults.ACTIVE.check(_faults.SUSPEND)
                if f is not None:
                    self._suspend_stats["suspend_failures"] += 1
                    if f.kind == "raise":
                        raise f.exception()
                    return False
            # drain every in-flight dispatch first: delivered tokens,
            # positions and rng step counters must agree before the state
            # is frozen (a pending burst would deliver tokens the captured
            # state does not cover)
            pump(0)
            req = self._slots[i]
            if not isinstance(req, _Request) or req.cancelled:
                return False  # finished or cancelled during the drain
            hist = len(req.prompt_ids) + len(req.emitted)
            if hist != host_pos[i] + 1 or not tables[i]:
                # a state the resume path cannot rebuild exactly (e.g. a
                # reserved/partial admit): refuse rather than resume wrong
                self._suspend_stats["suspend_failures"] += 1
                return False
            try:
                bids = jnp.asarray(tables[i], jnp.int32)
                st_host = None
                kv_k, kv_v = K, V
                if has_state(K):
                    # the slot's state goes to the host with its KV: what
                    # resume writes back is the state after host_pos tokens
                    st_host = jax.device_get(
                        (state_row(K, i), state_row(V, i)))
                    kv_k, kv_v = K.kv, V.kv
                k_host = _host_kv(kv_pool_read_blocks(kv_k, bids))
                v_host = _host_kv(kv_pool_read_blocks(kv_v, bids))
            except Exception:  # noqa: BLE001 — readback failed; keep in HBM
                log.exception("suspend readback failed; slot %d stays", i)
                self._suspend_stats["suspend_failures"] += 1
                return False
            srec = _Suspended(
                req=req, k=k_host, v=v_host, n_blocks=len(tables[i]),
                pos=host_pos[i], steps=host_steps[i], seed=host_seed[i],
                spec=spec_slots[i], t_suspend=time.monotonic(),
                reason=reason, min_blocks=min_blocks, st=st_host,
            )
            finish_slot(i)  # decrefs the blocks; the host copy owns the KV
            self._suspended.append(srec)
            self._suspend_stats["suspended_total"] += 1
            if reason == "preempted":
                # the victim is parked, not lost — this counts preemption
                # events per tenant (noisy-neighbor diagnosis), not sheds
                self.tenant_stats.record_preempted(req.tenant)
            obs_emit(
                "slot_suspend", slot=i, reason=reason, pos=srec.pos,
                generated=req.generated, blocks=srec.n_blocks,
            )
            return True

        def suspend_victim(below_rank: int | None = None,
                           reason: str = "kv_pool") -> bool:
            """Suspend the victim slot whose demotion frees the most pool
            blocks (falling through candidates a drain disqualifies),
            lowest priority class first — under uniform class this is
            exactly the pre-QoS largest-table-first sweep. ``below_rank``
            restricts candidates to strictly-lower classes (preemption on
            behalf of a higher-class admit). False when nothing is
            suspendable."""
            cand = sorted(
                (i for i, r in enumerate(self._slots)
                 if isinstance(r, _Request) and not r.cancelled and tables[i]
                 and (below_rank is None or r.rank < below_rank)),
                key=lambda i: (self._slots[i].rank, -len(tables[i])),
            )
            for i in cand:
                if suspend_slot(i, reason):
                    return True
            return False

        def resume_suspended() -> None:
            """Re-admit suspended slots (oldest first) while free slots and
            pool blocks allow. Bit-identical resume: the host KV copies are
            written into freshly allocated blocks, the pos/rng-step/seed
            mirrors are restored, and the device carry token is re-seeded
            from the delivered-token tail — the next decode step computes
            exactly what it would have without the suspension."""
            nonlocal K, V, tok_dev, dirty, table_dirty
            if not self._suspended:
                return
            bo = self.brownout
            if bo is not None and bo.level >= SHED_ONLY:
                return  # still inside the incident window that parked them
            pending = self._suspended
            while pending and None in self._slots:
                rec = pending[0]
                req = rec.req
                if req.cancelled:
                    pending.pop(0)
                    self._ledger_finalize(
                        req,
                        "deadline_abort" if req.deadline_hit else "cancelled",
                    )
                    self.stats.record_cancel("active")
                    continue
                if pool.free_blocks < rec.min_blocks:
                    # growth-parked slots wait for headroom beyond their
                    # own tables (see _Suspended.min_blocks); reclaim the
                    # evictable cache toward it like alloc_blocks would
                    if pc is not None:
                        pc.reclaim(
                            rec.min_blocks - pool.free_blocks,
                            demote=tier is not None,
                        )
                    if pool.free_blocks < rec.min_blocks:
                        return  # pool still tight; retry next tick
                try:
                    # internal: a resume must never suspend another slot to
                    # make room (thrash), and a full pool is a deferral, not
                    # a shed
                    ids = alloc_blocks(rec.n_blocks, internal=True)
                except _PoolExhausted:
                    return  # pool still tight; retry next tick
                slot = self._slots.index(None)
                try:
                    bids = jnp.asarray(ids, jnp.int32)
                    if has_state(K):
                        # KV into the fresh blocks and the state into the
                        # slot's row, in one donated dispatch (an eager
                        # write would copy the whole state pool)
                        K, V = self._state_restore(
                            K, V, _dev_kv(rec.k), _dev_kv(rec.v), bids,
                            rec.st, jnp.int32(slot))
                    else:
                        K = kv_pool_write_row(K, _dev_kv(rec.k), bids)
                        V = kv_pool_write_row(V, _dev_kv(rec.v), bids)
                    if self.mesh is not None:
                        # same re-pin as control_import: the eager writes
                        # may lose the pool sharding the donated dispatches
                        # were compiled for
                        from ..parallel.sharding import pool_spec, shard_cache

                        K, V = shard_cache(
                            K, V, self.mesh, cfg=cfg,
                            spec=pool_spec(self.mesh, cfg),
                        )
                except Exception as e:  # noqa: BLE001 — host copy unusable
                    pool.decref(ids)
                    pending.pop(0)
                    self._suspend_stats["suspend_failures"] += 1
                    self._ledger_finalize(req, "failed")
                    try:
                        req.emit("err", BatcherOverloaded(
                            f"resume failed after {req.generated} tokens "
                            f"({e}); retry on another worker"
                        ))
                    except Exception:  # noqa: BLE001 — dead client loop
                        pass
                    continue
                pending.pop(0)
                tables[slot] = list(ids)
                table_dirty = True
                req.slot = slot
                self._slots[slot] = req
                host_pos[slot] = rec.pos
                host_steps[slot] = rec.steps
                host_seed[slot] = rec.seed
                spec_slots[slot] = rec.spec
                carry = req.emitted[-1] if req.emitted else req.prompt_ids[-1]
                tok_dev = tok_dev.at[slot].set(jnp.int32(carry))
                dirty = True
                self._suspend_stats["resumed_total"] += 1
                obs_emit(
                    "slot_resume", slot=slot, reason=rec.reason, pos=rec.pos,
                    generated=req.generated,
                    suspended_ms=round(
                        (time.monotonic() - rec.t_suspend) * 1e3, 1
                    ),
                )

        def promote_from_tier(prompt_ids) -> None:
            """Pull host/spill-tier chunks that EXTEND this prompt's cached
            prefix back into the pool + radix cache (promotion-on-hit), so
            the match that follows resumes from the deepest tier-covered
            chunk. Bounded by ``tier.promote_chunks`` per admit; exhaustion
            or any failure leaves the cache exactly as it was (fresh
            allocations are dropped, survivors are owned by acquire_fn)."""
            nonlocal K, V
            if tier is None or pc is None or tier.promote_chunks <= 0:
                return
            C = self.prefill_chunk
            n_full = len(prompt_ids) // C
            have = pc.peek(prompt_ids) // C
            if n_full <= have:
                return
            nbc = C // T
            token_ids = [int(t) for t in prompt_ids[: n_full * C]]
            payloads: list = [None] * have
            logits_list: list = [None] * have
            alloc: list[int] = []
            found = 0
            try:
                for j in range(have, min(n_full, have + tier.promote_chunks)):
                    ent = tier.lookup(tuple(token_ids[: (j + 1) * C]))
                    if ent is None:
                        break
                    ids = alloc_blocks(nbc, internal=True)
                    alloc.extend(ids)
                    bids = jnp.asarray(ids, jnp.int32)
                    K = kv_pool_write_row(K, _dev_kv(ent.k), bids)
                    V = kv_pool_write_row(V, _dev_kv(ent.v), bids)
                    payloads.append((pool.epoch, list(ids)))
                    logits_list.append(
                        None if ent.logits is None
                        else jnp.asarray(ent.logits, jnp.float32)
                    )
                    found += 1
            except _PoolExhausted:
                pass  # promote what fit; the admit itself decides the rest
            except Exception:  # noqa: BLE001 — promotion is best-effort
                log.exception("tier promotion failed; continuing without")
                if alloc:
                    pool.decref(alloc)
                return
            if found == 0:
                if alloc:
                    pool.decref(alloc)
                return
            if self.mesh is not None:
                from ..parallel.sharding import pool_spec, shard_cache

                K, V = shard_cache(
                    K, V, self.mesh, cfg=cfg,
                    spec=pool_spec(self.mesh, cfg),
                )
            pc.insert(token_ids[: (have + found) * C], payloads, logits_list)
            # acquire_fn holds the surviving refs; these fresh ones drop
            # (mirrors control_import — an insert cut short frees everything)
            pool.decref(alloc)
            tier.note_promoted(found)

        def suspend_harvest() -> dict:
            """Drain-path zero-lost-work: fold every active slot's full
            token history (whole chunks of prompt + generated KV, already
            sitting in pool blocks) into the radix prefix cache, then fail
            the request with the retryable draining envelope. The warm
            handoff that follows (worker.begin_drain) ships these chunks to
            the survivor, so the client's retry admits as a prefix hit that
            covers the generated tokens too — not a from-scratch prefill."""
            pump(0)
            done = 0
            cached_tokens = 0
            C = self.prefill_chunk
            nbc = C // T if (paged and T) else 0
            for i in range(B):
                req = self._slots[i]
                if not isinstance(req, _Request):
                    continue
                if pc is not None and nbc and not req.cancelled:
                    hist = list(req.prompt_ids) + [
                        int(t) for t in req.emitted
                    ]
                    n_full = min(host_pos[i], len(hist)) // C
                    if n_full > 0:
                        tbl = tables[i]
                        payloads: list = []
                        for j in range(n_full):
                            ids = tbl[j * nbc : (j + 1) * nbc]
                            payloads.append(
                                (pool.epoch, list(ids))
                                if len(ids) == nbc else None
                            )
                        try:
                            pc.insert(
                                hist[: n_full * C], payloads, [None] * n_full
                            )
                            cached_tokens += n_full * C
                        except Exception:  # noqa: BLE001 — best-effort
                            log.exception("suspend-harvest insert failed")
                self._ledger_finalize(req, "served")
                finish_slot(i)
                try:
                    req.emit("err", BatcherOverloaded(
                        f"worker draining; {req.generated} generated tokens "
                        f"cached for warm handoff; retry on another worker"
                    ))
                except Exception:  # noqa: BLE001 — dead client loop
                    pass
                done += 1
            return {"slots": done, "tokens": cached_tokens}

        def control_export(args) -> dict | None:
            """Owner-thread half of disaggregated PREFILL: gather the
            prompt's cached full-chunk KV blocks (plus chunk-end logits)
            to host arrays for shipment to a decode peer. None means
            nothing useful is cached — the decode side falls back to
            local prefill, which is always correct."""
            if not paged or pc is None:
                return None
            prompt_ids = args["prompt_ids"]
            C = self.prefill_chunk
            if len(prompt_ids) < C:
                return None
            hit = pc.match(prompt_ids)
            if hit is None:
                return None
            try:
                if any(
                    p2 is None or p2[0] != pool.epoch for p2 in hit.payloads
                ):
                    # survived a pool reset: the ids reference recycled blocks
                    return None
                chunks = []
                for j, (_, ids) in enumerate(hit.payloads):
                    bids = jnp.asarray(ids, jnp.int32)
                    lg = hit.nodes[j].logits
                    chunks.append({
                        "k": _host_kv(kv_pool_read_blocks(K, bids)),
                        "v": _host_kv(kv_pool_read_blocks(V, bids)),
                        "logits": None if lg is None
                        else np.asarray(lg, np.float32).reshape(-1),
                    })
                return {
                    "token_ids": [int(t) for t in prompt_ids[: hit.tokens]],
                    "chunk_tokens": C,
                    "chunks": chunks,
                }
            finally:
                pc.release(hit)

        def control_import(args) -> dict:
            """Owner-thread half of disaggregated DECODE: write the
            transferred chunks into freshly allocated pool blocks and
            seed the prefix cache, so the request that follows admits as
            a prefix hit. The import's own allocation refs are dropped
            once the cache's acquire_fn holds the surviving ones; a
            _PoolExhausted (decode-pool exhaustion) frees everything
            allocated so far and propagates cleanly."""
            nonlocal K, V
            if not paged or pc is None:
                raise ValueError(
                    "kv import requires paged KV and a prefix cache"
                )
            export = args["export"]
            C = self.prefill_chunk
            if int(export["chunk_tokens"]) != C:
                raise ValueError(
                    f"prefill-chunk mismatch: export C="
                    f"{export['chunk_tokens']}, local C={C}"
                )
            token_ids = [int(t) for t in export["token_ids"]]
            n_full = min(len(token_ids) // C, len(export["chunks"]))
            if n_full <= 0:
                return {"tokens": 0, "blocks": 0}
            nbc = C // T
            alloc: list[int] = []
            payloads: list = []
            logits_list: list = []
            try:
                for j in range(n_full):
                    ch = export["chunks"][j]
                    ids = alloc_blocks(nbc)
                    alloc.extend(ids)
                    bids = jnp.asarray(ids, jnp.int32)
                    K = kv_pool_write_row(K, _dev_kv(ch["k"]), bids)
                    V = kv_pool_write_row(V, _dev_kv(ch["v"]), bids)
                    payloads.append((pool.epoch, list(ids)))
                    lg = ch.get("logits")
                    logits_list.append(
                        None if lg is None
                        else jnp.asarray(
                            np.asarray(lg), jnp.float32
                        ).reshape(1, 1, -1)
                    )
            except BaseException:
                if alloc:
                    pool.decref(alloc)
                raise
            if self.mesh is not None:
                # the eager .at[].set updates may lose the pool sharding;
                # re-pin so later donated dispatches see the layout they
                # were compiled for
                from ..parallel.sharding import pool_spec, shard_cache

                K, V = shard_cache(
                    K, V, self.mesh, cfg=cfg,
                    spec=pool_spec(self.mesh, cfg),
                )
            pc.insert(token_ids[: n_full * C], payloads, logits_list)
            # the cache's acquire_fn holds the surviving refs (a chunk
            # whose node already existed stays owned by that node; these
            # fresh blocks free right here)
            pool.decref(alloc)
            return {"tokens": n_full * C, "blocks": len(alloc)}

        def run_control(op: _ControlOp) -> None:
            """Execute one inbox control op inline; failures return to the
            waiting caller and never crash the pump."""
            self.heartbeat = time.monotonic()
            if op.cancelled:  # submitter timed out; nobody reads the result
                op.finish(error=TimeoutError("control op abandoned"))
                return
            try:
                if op.kind == "export":
                    op.finish(result=control_export(op.args))
                elif op.kind == "import":
                    op.finish(result=control_import(op.args))
                elif op.kind == "suspend_harvest":
                    op.finish(result=suspend_harvest())
                else:
                    op.finish(error=ValueError(
                        f"unknown control op {op.kind!r}"
                    ))
            except Exception as e:  # noqa: BLE001 — caller's error, not ours
                op.finish(error=e)

        def admit_paged(req: _Request, slot: int, n: int, seed: int,
                        samp) -> jax.Array:
            """Paged admit: allocate the slot's block table up front (raising
            _PoolExhausted BEFORE any device dispatch), run the same
            short/hit/flash/chunked prefill regimes as the legacy path, and
            land the KV in pool blocks. A FULL prefix hit appends the cached
            blocks to the table with no copy at all — refcount bumps plus
            one sample from the stored prompt-end logits."""
            nonlocal K, V, tok_dev, table_dirty
            C = self.prefill_chunk
            if n <= C:
                bucket = self._bucket(n)
                ids = alloc_blocks(-(-n // T), for_req=req)
                tables[slot] = ids
                table_dirty = True
                bids = ids + [0] * (max(1, bucket // T) - len(ids))
                tokens = jnp.asarray(
                    [req.prompt_ids + [0] * (bucket - n)], jnp.int32
                )
                first, K, V, tok_dev = self._admit_fused_paged(
                    self.params, K, V, tok_dev, tokens, jnp.int32(n),
                    jnp.asarray(bids, jnp.int32), jnp.int32(slot), *samp,
                    _tokens=n, _name=self._ring_name("admit_fused_paged", bucket),
                )
                return first
            # long prompt: same regime choices as the legacy path (see
            # admit_one's comment), but prefix-hit resume references cached
            # POOL blocks instead of copying them into the row
            n_full = n // C
            nbc = C // T
            chunk_logits = [None] * n_full if pc is not None else None
            # promotion-on-hit: chunks the HBM cache evicted to the host /
            # Object Store tiers come back into the pool before the match,
            # so the hit below covers the deepest tier-resident prefix
            promote_from_tier(req.prompt_ids)
            hit = pc.match(req.prompt_ids) if pc is not None else None
            if hit is not None and any(
                p2 is None or p2[0] != pool.epoch for p2 in hit.payloads
            ):
                # survived a pool reset: the ids reference recycled blocks
                pc.release(hit)
                hit = None
            if (
                hit is not None
                and not active()
                and cfg.use_flash_attention
                and 2 * hit.tokens < n
            ):
                pc.release(hit)
                hit = None
            k1 = v1 = None
            try:
                if hit is not None:
                    p = hit.tokens
                    prefix_ids: list[int] = []
                    for _, ids in hit.payloads:
                        prefix_ids.extend(ids)
                    pool.incref(prefix_ids)
                    tables[slot] = list(prefix_ids)
                    table_dirty = True
                    obs_emit(
                        "prefix_hit", tokens=p, prompt=n, full=(p == n),
                    )
                    if p == n:
                        # FULL hit: zero block copies, zero prefill flops
                        first, tok_dev = self._sample_first(
                            tok_dev, hit.end_logits, jnp.int32(slot), *samp,
                        )
                        return first
                    k1, v1 = self._make_row_cache(1, self.max_seq)
                    for j in range(p // C):
                        k1, v1 = self._fill_row_chunk(
                            k1, v1, K, V,
                            jnp.asarray(
                                prefix_ids[j * nbc : (j + 1) * nbc],
                                jnp.int32,
                            ),
                            jnp.int32(j * C),
                        )
                    for start in range(p, n, C):
                        chunk = req.prompt_ids[start : start + C]
                        chunk = chunk + [0] * (C - len(chunk))
                        logits, k1, v1 = self._prefill1(
                            self.params, jnp.asarray([chunk], jnp.int32),
                            k1, v1,
                            jnp.full((1,), start, jnp.int32),
                            jnp.asarray(
                                [min(n - 1 - start, C - 1)], jnp.int32
                            ),
                            self._win_bucket(start + C),
                            _tokens=min(C, n - start),
                            _chunk=self._chunk_attrs(start, [n - start]),
                        )
                        if start + C <= n:
                            chunk_logits[start // C] = logits
                        if start + C < n and not ext_live():
                            decode_once()
                            pump()
                    skip = p // C
                elif not active() and cfg.whole_prompt_prefill:
                    k1, v1 = self._make_row_cache(1, self.max_seq)
                    wb = self._win_bucket(n)
                    toks = req.prompt_ids + [0] * (wb - n)
                    logits, k1, v1 = self._prefill_full(
                        self.params, jnp.asarray([toks], jnp.int32), k1, v1,
                        jnp.int32(n),
                        _tokens=n,
                        _name=self._ring_name("prefill_full", wb),
                    )
                    if chunk_logits is not None and n_full and n % C == 0:
                        chunk_logits[n_full - 1] = logits
                    skip = 0
                else:
                    k1, v1 = self._make_row_cache(1, self.max_seq)
                    for start in range(0, n, C):
                        chunk = req.prompt_ids[start : start + C]
                        chunk = chunk + [0] * (C - len(chunk))
                        logits, k1, v1 = self._prefill1(
                            self.params, jnp.asarray([chunk], jnp.int32),
                            k1, v1,
                            jnp.full((1,), start, jnp.int32),
                            jnp.asarray(
                                [min(n - 1 - start, C - 1)], jnp.int32
                            ),
                            self._win_bucket(start + C),
                            _tokens=min(C, n - start),
                            _chunk=self._chunk_attrs(start, [n - start]),
                        )
                        if chunk_logits is not None and start + C <= n:
                            chunk_logits[start // C] = logits
                        if start + C < n and not ext_live():
                            decode_once()
                            pump()
                    skip = 0
            finally:
                if hit is not None:
                    pc.release(hit)
            # extend the table over the freshly prefilled suffix, THEN
            # harvest (host-only id bookkeeping; the device write below is
            # program-ordered before any later admit's gather of these ids)
            total = -(-n // T)
            bstart = len(tables[slot])
            tables[slot].extend(alloc_blocks(total - bstart, for_req=req))
            table_dirty = True
            harvest_prefix(
                req.prompt_ids, None, None, 0, chunk_logits,
                skip_chunks=skip, slot=slot,
            )
            # full [max_seq/T] bid row: NULL for shared prefix blocks (the
            # write must not touch the cache's copies) and the junk tail
            bids = [0] * MB
            for b in range(bstart, total):
                bids[b] = tables[slot][b]
            first, K, V, tok_dev = self._finish_admit_paged(
                self.params, K, V, tok_dev, k1, v1, logits,
                jnp.asarray(bids, jnp.int32), jnp.int32(slot), *samp,
            )
            return first

        def admit_one(req: _Request) -> None:
            nonlocal K, V, tok_dev, dirty, table_dirty
            # queue delay = enqueue -> admission START (the scheduling half
            # of TTFT); a chunked prefill's seconds are NOT queue delay
            t_admit = time.monotonic()
            req.t_admit = t_admit
            if req.trace is not None:
                req.trace.mark("admit", t_admit)
            self.stats.record_admit_delay((t_admit - req.t_enq) * 1e3)
            # every dispatch until the finish (including interleaved decode's
            # own re-scoped context) charges this request's ledger accrual
            prev_ctx = self._charge_ctx
            self._charge_ctx = (req,)
            slot = self._slots.index(None)
            n = len(req.prompt_ids)
            C = self.prefill_chunk
            sp = req.sp
            seed = sp.seed if sp.seed is not None else random.getrandbits(31)
            samp = (
                jnp.int32(seed), jnp.float32(sp.temperature),
                jnp.int32(sp.top_k), jnp.float32(sp.top_p),
            )
            note_admit(n)
            # reserve AFTER note_admit (whose cold-ring check must see the
            # true all-empty table), BEFORE the prefill: a multi-second
            # chunked/full prefill with every slot still None would read as
            # idle() to the registry's eviction check and the engine could
            # be unloaded mid-admit (admit_group_chunked already does this).
            # The failure path releases via reset_after_failed_dispatch,
            # which clears placeholders too.
            self._slots[slot] = _RESERVED
            if paged:
                try:
                    first = admit_paged(req, slot, n, seed, samp)
                except BaseException:
                    # _PoolExhausted (raised pre-dispatch) must NOT trigger
                    # the cache reset — release just this reservation. Other
                    # exceptions reset via the caller, but returning the
                    # blocks first keeps the pool books exact either way.
                    if tables[slot]:
                        pool.decref(tables[slot])
                        tables[slot] = []
                        table_dirty = True
                    self._slots[slot] = None
                    self._charge_ctx = prev_ctx
                    raise
            elif n <= C:
                # short prompt: the whole admit is one fused dispatch
                bucket = self._bucket(n)
                tokens = jnp.asarray([req.prompt_ids + [0] * (bucket - n)], jnp.int32)
                shift = jnp.int32(
                    0 if positional else (self._ring_next - n) % self.max_seq
                )
                first, K, V, tok_dev = self._admit_fused(
                    self.params, K, V, tok_dev, tokens, jnp.int32(n),
                    jnp.int32(slot), shift, *samp,
                    _tokens=n, _name=self._ring_name("admit_fused", bucket),
                )
            else:
                # long prompt. PREFIX-CACHE hit: copy the cached chunk
                # blocks into the fresh row cache (where a chunked prefill
                # would have written them) and prefill only the uncached
                # suffix — a full-prefix hit skips prefill entirely and
                # samples from the stored prompt-end logits. Miss, IDLE
                # engine: the whole prompt in ONE fresh flash dispatch at a
                # pow2 token bucket — chunking only exists to bound live
                # streams' inter-token gap, and with nothing else decoding
                # it costs ~2x the wall time (measured on the chip at 16k);
                # a hit covering less than half the prompt is released in
                # favor of it. Otherwise: chunked prefill, fixed [1, C]
                # chunks with a shared decode step between chunks, so
                # concurrent streams stall at most ~one chunk's latency,
                # not the whole prompt's. The final chunk's logits row
                # (prompt end) is selected by logit_positions, so only
                # [1, 1, vocab] materializes; with the cache on, every
                # full chunk's END row is kept too — that row is what makes
                # a future full-prefix hit sampleable.
                k1, v1 = self._make_row_cache(1, self.max_seq)
                n_full = n // C
                chunk_logits = [None] * n_full if pc is not None else None
                hit = pc.match(req.prompt_ids) if pc is not None else None
                if (
                    hit is not None
                    and not active()
                    and cfg.use_flash_attention
                    and 2 * hit.tokens < n
                ):
                    # the single flash dispatch beats resuming a SHORT
                    # cached prefix through per-chunk dispatches
                    pc.release(hit)
                    hit = None
                try:
                    if hit is not None:
                        p = hit.tokens
                        for j, (kb, vb) in enumerate(hit.blocks):
                            k1, v1 = self._write_prefix_block(
                                k1, v1, kb, vb, jnp.int32(j * C)
                            )
                        obs_emit(
                            "prefix_hit", tokens=p, prompt=n,
                            full=(p == n),
                        )
                        if p == n:
                            logits = hit.end_logits
                        else:
                            for start in range(p, n, C):
                                chunk = req.prompt_ids[start : start + C]
                                chunk = chunk + [0] * (C - len(chunk))
                                logits, k1, v1 = self._prefill1(
                                    self.params, jnp.asarray([chunk], jnp.int32),
                                    k1, v1,
                                    jnp.full((1,), start, jnp.int32),
                                    jnp.asarray(
                                        [min(n - 1 - start, C - 1)], jnp.int32
                                    ),
                                    self._win_bucket(start + C),
                                    _tokens=min(C, n - start),
                                    _chunk=self._chunk_attrs(start, [n - start]),
                                )
                                if start + C <= n:
                                    chunk_logits[start // C] = logits
                                if start + C < n and not ext_live():
                                    decode_once()
                                    pump()
                        harvest_prefix(
                            req.prompt_ids, k1, v1, 0, chunk_logits,
                            skip_chunks=p // C,
                        )
                    elif not active() and cfg.whole_prompt_prefill:
                        # the shortcut needs the fresh FLASH path: through the
                        # dense fallback a full-bucket prefill would materialize
                        # the [Hq, bucket, S] f32 scores the chunked path exists
                        # to bound (2+ GB at 4k on a flash-off CPU worker)
                        wb = self._win_bucket(n)
                        toks = req.prompt_ids + [0] * (wb - n)
                        logits, k1, v1 = self._prefill_full(
                            self.params, jnp.asarray([toks], jnp.int32), k1, v1,
                            jnp.int32(n),
                            _tokens=n,
                            _name=self._ring_name("prefill_full", wb),
                        )
                        # only the prompt-end row exists here; chunk-end
                        # rows for interior chunks are backfilled if a
                        # later chunked admit recomputes them
                        if chunk_logits is not None and n_full and n % C == 0:
                            chunk_logits[n_full - 1] = logits
                        harvest_prefix(req.prompt_ids, k1, v1, 0, chunk_logits)
                    else:
                        for start in range(0, n, C):
                            chunk = req.prompt_ids[start : start + C]
                            chunk = chunk + [0] * (C - len(chunk))
                            logits, k1, v1 = self._prefill1(
                                self.params, jnp.asarray([chunk], jnp.int32), k1, v1,
                                jnp.full((1,), start, jnp.int32),
                                jnp.asarray([min(n - 1 - start, C - 1)], jnp.int32),
                                self._win_bucket(start + C),
                                _tokens=min(C, n - start),
                                _chunk=self._chunk_attrs(start, [n - start]),
                            )
                            if chunk_logits is not None and start + C <= n:
                                chunk_logits[start // C] = logits
                            if start + C < n and not ext_live():
                                decode_once()
                                pump()
                        harvest_prefix(req.prompt_ids, k1, v1, 0, chunk_logits)
                finally:
                    if hit is not None:
                        pc.release(hit)
                # shift MUST be computed here, after the chunk loop: the
                # interleaved decode_once() calls advanced the ring head,
                # and the prefix has to end at the CURRENT head for the
                # ring-validity mask to see it
                shift = jnp.int32(
                    0 if positional else (self._ring_next - n) % self.max_seq
                )
                first, K, V, tok_dev = self._finish_admit(
                    self.params, K, V, tok_dev, k1, v1, logits,
                    jnp.int32(slot), shift, *samp,
                )
            req.slot = slot
            req.pos = n
            self._slots[slot] = req
            self.stats.count_admitted(req.sp, cfg.vocab_size)
            dirty = True
            host_pos[slot] = n
            host_steps[slot] = 1  # the admit program sampled at rng step 0
            host_seed[slot] = seed
            if req.trace is not None:
                req.trace.mark("prefill")  # prefill dispatched; first token next
            inflight.append(("admit", first, [(0, slot, req)]))
            self._charge_ctx = prev_ctx

        def note_admit(n: int) -> None:
            """Shared cold-ring / wrap bookkeeping for an admit of length n
            (the ring-validity invariant lives in exactly one place)."""
            if positional:
                return  # no shared head: prefixes always land at [0, n)
            if not any(r is not None for r in self._slots):
                self._ring_next = n  # cold ring: the prefix fits below
                self._ring_wrapped = False
            elif self._ring_next < n:
                # the prefix placement wraps to the high slots: windowed
                # reads would miss it from here on
                self._ring_wrapped = True

        def admit_group(reqs: list[_Request], bucket: int) -> bool:
            """Admit m same-bucket short prompts in one fused dispatch.
            Returns False (caller admits individually) when any block would
            wrap around the ring. The first tokens are NOT read back here —
            the record rides the in-flight queue like a decode burst."""
            nonlocal K, V, tok_dev, dirty, table_dirty
            ns = [len(r.prompt_ids) for r in reqs]
            max_n = max(ns)
            note_admit(max_n)
            # every [bucket]-length block [ring_next - n_i, ring_next - n_i
            # + bucket) must lie inside [0, max_seq). Positional mode has no
            # head: blocks land at [0, bucket) and can never wrap.
            if not positional and (
                self._ring_next < max_n
                or self._ring_next - min(ns) + bucket > self.max_seq
            ):
                return False
            if paged:
                # pre-dispatch capacity check: a group alloc is all-or-
                # nothing, so verify (and reclaim toward) the total need
                # BEFORE reserving; a shortfall falls back to per-request
                # admits where _PoolExhausted sheds just the overflow.
                need = sum(-(-n // T) for n in ns)
                if need > pool.free_blocks and pc is not None:
                    pc.reclaim(need - pool.free_blocks)
                if need > pool.free_blocks:
                    return False
            prev_ctx = self._charge_ctx
            self._charge_ctx = tuple(reqs)
            slots: list[int] = []
            try:
                for r in reqs:
                    s = self._slots.index(None)
                    self._slots[s] = r  # reserve so index(None) advances
                    slots.append(s)
                m = len(reqs)
                mpad = 1 << (m - 1).bit_length()  # bound compiles: m in {2,4,8,..}
                idx = list(range(m)) + [0] * (mpad - m)  # pad rows repeat row 0
                seeds = [
                    r.sp.seed if r.sp.seed is not None else random.getrandbits(31)
                    for r in reqs
                ]
                tokens = [
                    reqs[i].prompt_ids + [0] * (bucket - ns[i]) for i in idx
                ]
                if paged:
                    nblk_row = max(1, bucket // T)
                    for j, s in enumerate(slots):
                        tables[s] = alloc_blocks(
                            -(-ns[j] // T), for_req=reqs[j]
                        )
                    table_dirty = True
                    bid_rows = [
                        tables[slots[i]]
                        + [0] * (nblk_row - len(tables[slots[i]]))
                        for i in idx
                    ]
                    firsts, K, V, tok_dev = self._admit_many_fused_paged(
                        self.params, K, V, tok_dev,
                        jnp.asarray(tokens, jnp.int32),
                        jnp.asarray([ns[i] for i in idx], jnp.int32),
                        jnp.asarray(bid_rows, jnp.int32),
                        jnp.asarray([slots[i] for i in idx], jnp.int32),
                        jnp.asarray([seeds[i] for i in idx], jnp.int32),
                        jnp.asarray(
                            [reqs[i].sp.temperature for i in idx], jnp.float32
                        ),
                        jnp.asarray([reqs[i].sp.top_k for i in idx], jnp.int32),
                        jnp.asarray([reqs[i].sp.top_p for i in idx], jnp.float32),
                        _tokens=sum(ns[i] for i in idx),
                        _name=self._ring_name("admit_many_fused_paged", bucket),
                    )
                else:
                    firsts, K, V, tok_dev = self._admit_many_fused(
                        self.params, K, V, tok_dev,
                        jnp.asarray(tokens, jnp.int32),
                        jnp.asarray([ns[i] for i in idx], jnp.int32),
                        jnp.asarray([slots[i] for i in idx], jnp.int32),
                        jnp.asarray(
                            [0 if positional else self._ring_next - ns[i] for i in idx],
                            jnp.int32,
                        ),
                        jnp.asarray([seeds[i] for i in idx], jnp.int32),
                        jnp.asarray([reqs[i].sp.temperature for i in idx], jnp.float32),
                        jnp.asarray([reqs[i].sp.top_k for i in idx], jnp.int32),
                        jnp.asarray([reqs[i].sp.top_p for i in idx], jnp.float32),
                        _tokens=sum(ns[i] for i in idx),
                        _name=self._ring_name("admit_many_fused", bucket),
                    )
            except BaseException:
                for s in slots:  # release reservations; caller emits the error
                    self._slots[s] = None
                    if paged and tables[s]:
                        pool.decref(tables[s])
                        tables[s] = []
                        table_dirty = True
                self._charge_ctx = prev_ctx
                raise
            dirty = True
            self.stats.grouped_admits += len(reqs)
            rows = []
            t_admit = time.monotonic()
            for j, r in enumerate(reqs):
                s = slots[j]
                r.slot = s
                r.pos = ns[j]
                r.t_admit = t_admit
                self.stats.count_admitted(r.sp, cfg.vocab_size)
                self.stats.record_admit_delay((t_admit - r.t_enq) * 1e3)
                if r.trace is not None:
                    r.trace.mark("admit", t_admit)
                    r.trace.mark("prefill")  # the group dispatch just went out
                host_pos[s] = ns[j]
                host_steps[s] = 1  # the admit program sampled at rng step 0
                host_seed[s] = seeds[j]
                rows.append((j, s, r))
            inflight.append(("admit", firsts, rows))
            self._charge_ctx = prev_ctx
            return True

        def admit_group_chunked(reqs: list[_Request]) -> None:
            """Admit m LONG prompts (each > prefill_chunk) through SHARED
            [w, C] chunk dispatches. Serial chunked admits at B=1 leave most
            of the MXU idle and, worse, make waiting long prompts queue a
            whole prefill each; batching divides the chunk-pass count by m.
            A shared decode step still interleaves between chunk dispatches,
            so live streams' inter-token gap stays bounded by ~one [w, C]
            chunk.

            The group narrows as its prompts end. After the chunk in which a
            row's prompt ends, that row is finished (written to the pool or
            the ring, its first token sampled at rng step 0 from the logits
            select_end kept for it) and installed in its slot, so the next
            decode burst of this loop decodes it. The rows still prefilling
            go on at the smallest built width that holds them (4 -> 2 -> 1;
            three live rows stay in the width-4 program), their cache taken
            out of the wider one by take_rows; a last lone row runs the
            program a lone long prompt runs (prefill1).

            The slot of a row not installed yet holds the _RESERVED
            placeholder: the fixed-width decode program computes its row as
            masked junk (same as an empty slot) and nothing is delivered."""
            nonlocal K, V, tok_dev, dirty, table_dirty
            if paged:
                # all-or-nothing capacity check up front; a shortfall routes
                # each request through admit_one, where _PoolExhausted sheds
                # just the requests that truly do not fit
                need = sum(-(-len(r.prompt_ids) // T) for r in reqs)
                if need > pool.free_blocks and pc is not None:
                    pc.reclaim(need - pool.free_blocks)
                if need > pool.free_blocks:
                    for r in reqs:
                        try:
                            admit_one(r)
                        except _PoolExhausted as e:
                            # chunk prefills may have run before the alloc
                            # failed: that device time is shed-after-prefill
                            self._ledger_finalize(r, "shed_after_prefill")
                            r.emit("err", e)
                    return
            prev_ctx = self._charge_ctx
            # queue delay = enqueue -> admission START (scheduling only;
            # the chunk loop's seconds are prefill, not queueing)
            t_start = time.monotonic()
            for r in reqs:
                r.t_admit = t_start
                self.stats.record_admit_delay((t_start - r.t_enq) * 1e3)
                if r.trace is not None:
                    r.trace.mark("admit", t_start)
            C = self.prefill_chunk
            ns = [len(r.prompt_ids) for r in reqs]
            note_admit(max(ns))
            m = len(reqs)
            end_chunk = [(n - 1) // C for n in ns]
            slots: list[int] = []
            # the requests still prefilling, by their place in ``reqs``: a
            # launch's device time is theirs, and their slots are _RESERVED
            waiting = list(range(m))
            try:
                for r in reqs:
                    s = self._slots.index(None)
                    self._slots[s] = _RESERVED
                    slots.append(s)
                seeds = [
                    r.sp.seed if r.sp.seed is not None else random.getrandbits(31)
                    for r in reqs
                ]
                mpad = 1 << (m - 1).bit_length()
                # the request of each cache row; a pad row repeats a live one
                cur = waiting + [0] * (mpad - m)
                km, vm = self._make_row_cache(mpad, self.max_seq)
                final = jnp.zeros((mpad, 1, cfg.vocab_size), jnp.float32)
                # per-chunk [w, 1, vocab] logits with the rows they belong
                # to, kept only while the prefix cache is on: full-chunk END
                # rows become the cached nodes' first-token logits (transient
                # cost ~n_chunks x w x vocab f32, freed with the last harvest)
                glogits: list = [] if pc is not None else None
                # a row whose prompt ended in an earlier chunk (three live
                # rows keep the width-4 cache) reads position 0; a family
                # with a recurrent state is told so (-1: none of this chunk's
                # positions is real), or the padding would run through the
                # row's state
                lo = -1 if cfg.slot_state else 0
                unharvested: list[int] = []

                def harvest(i: int) -> None:
                    """Row i's full-chunk blocks into the prefix cache (the
                    paged harvest records its slot's block ids and reads no
                    row of the pair); jnp.copy detaches each [1, 1, vocab]
                    end row so the [w, ...] chunk buffers can free."""
                    cl = []
                    for lg, at in glogits[: ns[i] // C]:
                        k = at.index(i)
                        cl.append(lg if len(at) == 1 else jnp.copy(lg[k : k + 1]))
                    harvest_prefix(
                        reqs[i].prompt_ids, km, vm,
                        0 if paged else cur.index(i), cl, slot=slots[i],
                    )

                j = 0
                while waiting:
                    start = j * C
                    width = len(cur)
                    self._charge_ctx = tuple(reqs[i] for i in waiting)
                    rows = []
                    for i in cur:
                        chunk = reqs[i].prompt_ids[start : start + C]
                        rows.append(chunk + [0] * (C - len(chunk)))
                    last_pos = jnp.asarray(
                        [min(max(ns[i] - 1 - start, lo), C - 1) for i in cur],
                        jnp.int32,
                    )
                    attrs = self._chunk_attrs(
                        start, [ns[i] - start for i in waiting], width)
                    if width == 1:
                        logits, km, vm = self._prefill1(
                            self.params, jnp.asarray(rows, jnp.int32), km, vm,
                            jnp.full((1,), start, jnp.int32), last_pos,
                            self._win_bucket(start + C),
                            _tokens=min(C, ns[cur[0]] - start), _chunk=attrs,
                        )
                        final = logits  # read once, after the row's last chunk
                    else:
                        logits, km, vm = self._prefill_chunk_group(
                            self.params, jnp.asarray(rows, jnp.int32), km, vm,
                            jnp.full((width,), start, jnp.int32), last_pos,
                            self._win_bucket(start + C),
                            _tokens=width * C, _chunk=attrs,
                        )
                        final = self._select_end(
                            final, logits,
                            jnp.asarray([end_chunk[i] == j for i in cur], jnp.bool_),
                        )
                        if width not in self._takes_built:
                            self._build_takes(km, vm, final)
                    self.stats.chunk_rows_computed += width
                    self.stats.chunk_rows_real += len(waiting)
                    if glogits is not None:
                        glogits.append((logits, cur))
                    ending = [i for i in waiting if end_chunk[i] == j]
                    j += 1
                    if ending:
                        self._charge_ctx = tuple(reqs[i] for i in ending)
                        if paged:
                            # tables BEFORE harvest (the paged harvest records
                            # the rows' pool block ids, not device copies)
                            for i in ending:
                                tables[slots[i]] = alloc_blocks(
                                    -(-ns[i] // T), for_req=reqs[i]
                                )
                            table_dirty = True
                        if glogits is not None:
                            if paged:
                                unharvested.extend(ending)
                            else:
                                # the ring layout's harvest gathers the row's
                                # blocks out of the pair: BEFORE a finish
                                # that takes the pair
                                for i in ending:
                                    harvest(i)
                        if width > 1 and all(i in ending for i in cur):
                            # every row of the cache ends here (a pad row
                            # repeats one that does): one batched finish
                            samp = (
                                jnp.asarray([seeds[i] for i in cur], jnp.int32),
                                jnp.asarray(
                                    [reqs[i].sp.temperature for i in cur], jnp.float32
                                ),
                                jnp.asarray([reqs[i].sp.top_k for i in cur], jnp.int32),
                                jnp.asarray([reqs[i].sp.top_p for i in cur], jnp.float32),
                            )
                            if paged:
                                bid_rows = np.zeros((width, max(MB, 1)), np.int32)
                                for i in ending:
                                    t = tables[slots[i]]
                                    bid_rows[cur.index(i), : len(t)] = t
                                firsts, K, V, tok_dev = self._finish_admit_group_paged(
                                    self.params, K, V, tok_dev, km, vm, final,
                                    jnp.asarray(bid_rows),
                                    jnp.asarray([slots[i] for i in cur], jnp.int32),
                                    *samp,
                                )
                            else:
                                # shifts AFTER the loop's decodes moved the head
                                shifts = [
                                    0 if positional
                                    else (self._ring_next - ns[i]) % self.max_seq
                                    for i in cur
                                ]
                                firsts, K, V, tok_dev = self._finish_admit_group(
                                    self.params, K, V, tok_dev, km, vm, final,
                                    jnp.asarray([slots[i] for i in cur], jnp.int32),
                                    jnp.asarray(shifts, jnp.int32),
                                    *samp,
                                )
                            records = [("admit", firsts, [
                                (cur.index(i), slots[i], reqs[i]) for i in ending])]
                        else:
                            # the others go on (or a row that ended earlier
                            # still lies in this cache): each ending row
                            # through the finish of a lone long prompt
                            records = []
                            for i in ending:
                                k1, v1, l1 = (km, vm, final) if width == 1 else (
                                    self._take_rows(
                                        km, vm, final,
                                        jnp.asarray([cur.index(i)], jnp.int32)))
                                sp = reqs[i].sp
                                samp = (
                                    jnp.int32(seeds[i]), jnp.float32(sp.temperature),
                                    jnp.int32(sp.top_k), jnp.float32(sp.top_p),
                                )
                                if paged:
                                    t = tables[slots[i]]
                                    first, K, V, tok_dev = self._finish_admit_paged(
                                        self.params, K, V, tok_dev, k1, v1, l1,
                                        jnp.asarray(t + [0] * (MB - len(t)), jnp.int32),
                                        jnp.int32(slots[i]), *samp,
                                    )
                                else:
                                    shift = jnp.int32(
                                        0 if positional
                                        else (self._ring_next - ns[i]) % self.max_seq
                                    )
                                    first, K, V, tok_dev = self._finish_admit(
                                        self.params, K, V, tok_dev, k1, v1, l1,
                                        jnp.int32(slots[i]), shift, *samp,
                                    )
                                records.append(
                                    ("admit", first, [(0, slots[i], reqs[i])]))
                        dirty = True
                        for i in ending:
                            r, s = reqs[i], slots[i]
                            r.slot = s
                            r.pos = ns[i]
                            self._slots[s] = r
                            self.stats.count_admitted(r.sp, cfg.vocab_size)
                            if r.trace is not None:
                                r.trace.mark("prefill")  # its chunks + finish dispatched
                            host_pos[s] = ns[i]
                            host_steps[s] = 1  # the finish program sampled at rng step 0
                            host_seed[s] = seeds[i]
                        inflight.extend(records)
                        self.stats.chunked_group_admits += len(ending)
                        waiting = [i for i in waiting if i not in ending]
                        if waiting:
                            self.stats.chunked_group_early_finishes += len(ending)
                        w = 1 << max(0, len(waiting) - 1).bit_length()
                        if waiting and w < width:
                            # the wide pair is dropped as the narrow one is
                            # bound: nothing else is allocated between
                            self._charge_ctx = tuple(reqs[i] for i in waiting)
                            kept = waiting + [waiting[0]] * (w - len(waiting))
                            km, vm, final = self._take_rows(
                                km, vm, final,
                                jnp.asarray([cur.index(i) for i in kept], jnp.int32),
                            )
                            cur = kept
                            self.stats.chunked_group_narrowings += 1
                    if waiting and not ext_live():
                        decode_once()
                        pump()
                    # the paged harvest records block ids and cuts each row's
                    # end logits out of the chunks' buffers, an un-jitted
                    # slice and copy a chunk: behind a running launch that
                    # many small programs make the host wait for the device,
                    # so they go out once the burst is dispatched and what
                    # was ready is delivered (a live stream's tokens stood
                    # ready for a launch's time otherwise)
                    for i in unharvested:
                        harvest(i)
                    unharvested.clear()
            except BaseException:
                # release the reservations of the rows not installed (the
                # caller emits their error); a row installed is a live
                # request like any other
                for s in slots:
                    if self._slots[s] is _RESERVED:
                        self._slots[s] = None
                        if paged and tables[s]:
                            pool.decref(tables[s])
                            tables[s] = []
                            table_dirty = True
                self._charge_ctx = prev_ctx
                raise
            self._charge_ctx = prev_ctx

        def reset_after_failed_dispatch() -> None:
            """A failed admit/decode dispatch may have consumed the donated
            K/V buffers (e.g. device OOM raised after donation); continuing
            would wedge every subsequent dispatch against invalidated
            buffers (round-2 advisor). Fail the active streams honestly and
            rebuild a fresh cache. In-flight records reference the poisoned
            buffers and are discarded."""
            nonlocal K, V, tok_dev, dirty, table_dirty
            inflight.clear()
            self._charge_ctx = None  # drop any context the failed call left
            err = RuntimeError("batcher cache reset after a failed device dispatch")
            for i, r in enumerate(self._slots):
                if isinstance(r, _Request):
                    self._ledger_finalize(r, "failed")
                    r.emit("err", err)
                if r is not None:  # includes _RESERVED placeholders
                    self._slots[i] = None
                    host_pos[i] = 0
                    host_steps[i] = 0
                spec_slots[i] = None
            self._ring_next = 0
            self._ring_wrapped = False
            dirty = True
            if paged:
                # epoch bump: prefix-cache payloads minted before the reset
                # free as no-ops, and stale hits are rejected at match time
                pool.reset()
                for i in range(B):
                    tables[i] = []
                table_dirty = True
                if pc is not None:
                    pc.clear()
                K, V = make_pool()
            else:
                K, V = make_cache(cfg, B, self.max_seq)
                if self.mesh is not None:
                    from ..parallel.sharding import shard_cache

                    K, V = shard_cache(K, V, self.mesh, cfg=cfg)
            tok_dev = jnp.zeros((B,), jnp.int32)

        coalesce_s = self.admit_coalesce_ms / 1e3
        # instance attr (not a local): a pump-loop crash must be able to
        # fail waiters that have left the inbox but not yet won a slot
        waitlist = self._waitlist
        while True:
            self.heartbeat = time.monotonic()  # supervisor liveness stamp
            if _faults.ACTIVE is not None:  # chaos harness; off ⇒ one attr read
                f = _faults.ACTIVE.check(_faults.PUMP)
                if f is not None and f.kind == "raise":
                    raise f.exception()
            act = active()
            self.stats.peak_active = max(self.stats.peak_active, len(act))
            # intake: block when fully idle, otherwise just drain what's
            # queued. Suspended slots keep their deadline clocks running,
            # so with any parked the idle park becomes a bounded poll (the
            # suspended sweep/resume below must keep ticking); when a
            # resume is already possible, don't wait at all.
            bo0 = self.brownout
            can_resume = bool(
                self._suspended
                and None in self._slots
                and (bo0 is None or bo0.level < SHED_ONLY)
            )
            block = (
                not act and not waitlist and not inflight and not can_resume
            )
            poll_s = 0.05 if (block and self._suspended) else None
            first_intake = block
            with obs_spans.span("batcher.intake") as spn:
                n_wl = len(waitlist)
                while True:
                    try:
                        item = self._inbox.get(block=block, timeout=poll_s)
                    except _queue.Empty:
                        break
                    block = False
                    if item is None:
                        self._drain_all("shutdown", waitlist)
                        return
                    if isinstance(item, _ControlOp):
                        run_control(item)
                        continue
                    if item.cancelled:
                        self.stats.record_cancel("inbox")
                        continue
                    waitlist.append(item)
                    self._wl_len = len(waitlist)  # keep idle() honest mid-intake
                    if first_intake and coalesce_s > 0:
                        # the worker was idle and one request just arrived —
                        # concurrent arrivals are usually a few scheduler ticks
                        # apart; waiting a few ms turns 1 + (m-1) admit
                        # dispatches (each a full device round trip) into ONE
                        # batched admit
                        first_intake = False
                        deadline = time.monotonic() + coalesce_s
                        while True:
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            try:
                                nxt = self._inbox.get(timeout=left)
                            except _queue.Empty:
                                break
                            if nxt is None:
                                self._drain_all("shutdown", waitlist)
                                return
                            if isinstance(nxt, _ControlOp):
                                run_control(nxt)
                                continue
                            if nxt.cancelled:
                                self.stats.record_cancel("inbox")
                                continue
                            waitlist.append(nxt)
                            self._wl_len = len(waitlist)
                spn.attrs["items"] = len(waitlist) - n_wl
            with obs_spans.span("batcher.tick"):
                drain_cancels(waitlist)
                now = time.monotonic()
                depth = len(waitlist) + self._inbox.qsize()
                rebuild_slot_view()
                rec = self.recorder
                if rec is not None and rec.due(now):
                    rec.sample(
                        self._recorder_frame(depth=depth, n_active=len(active())),
                        now=now,
                    )
                bo = self.brownout
                lvl_before = bo.level if bo is not None else SHED_ONLY
                if bo is not None:
                    # controller tick: queue depth as a fraction of the
                    # (configured, or nominal 4x-slots) limit, queue-age p95
                    # over the current waiters, HBM headroom via the
                    # registry-injected probe
                    limit = self.max_queue or 4 * self.max_slots
                    ages = sorted(self._warm_s(r.t_enq, now) * 1e3 for r in waitlist)
                    age_p95 = ages[max(0, int(len(ages) * 0.95) - 1)] if ages else 0.0
                    headroom_frac = None
                    if self.hbm_headroom_fn is not None:
                        try:
                            headroom_frac = self.hbm_headroom_fn()
                        except Exception:  # noqa: BLE001 — probe is best-effort
                            headroom_frac = None
                    bo.update(depth_frac=depth / limit, age_p95_ms=age_p95,
                              hbm_headroom_frac=headroom_frac, now=now)
                    if (
                        bo.level == SHED_ONLY
                        and lvl_before < SHED_ONLY
                        and rec is not None
                    ):
                        # entering full shed is an incident, not a metric blip:
                        # capture the ramp that led here (rate-limited)
                        rec.dump(
                            "shed_only_entry",
                            extra={"depth": depth, "age_p95_ms": round(age_p95, 1),
                                   "hbm_headroom_frac": headroom_frac,
                                   "device_ms": self.stats.device_time_snapshot()["ms"]},
                        )
                    if bo.level == SHED_ONLY and lvl_before < SHED_ONLY:
                        # swap-don't-shed on the incident edge: park the
                        # youngest streams on the host tier so the survivors
                        # keep full decode width; they resume once the level
                        # drops back below SHED_ONLY (resume_suspended gates
                        # on it)
                        target = bo.suspend_target(self.max_slots)
                        while suspend_on:
                            live = [
                                i for i, r in enumerate(self._slots)
                                if isinstance(r, _Request)
                            ]
                            if len(live) <= target:
                                break
                            # lowest class first, youngest within a class — a
                            # premium stream is the last to be parked
                            victim = min(
                                live,
                                key=lambda i: (
                                    self._slots[i].rank, -self._slots[i].t_admit
                                ),
                            )
                            if not suspend_slot(victim, "brownout"):
                                break
                if tier is not None and paged:
                    # proactive demotion: keep ~demote_free_frac of the pool
                    # free by demoting cold cache chunks to the host tier
                    # BETWEEN bursts, so admissions stop paying the reclaim at
                    # the worst moment (and the tier fills before pressure
                    # peaks). No-op once the cache holds nothing unpinned.
                    floor_blocks = int(pool.n_blocks * tier.demote_free_frac)
                    if pool.free_blocks < floor_blocks:
                        pc.reclaim(floor_blocks - pool.free_blocks, demote=True)
                # deadline sweep, queued side: waiters whose budget already ran
                # out — or whose remaining budget the live rate EWMAs say cannot
                # cover prefill plus the token floor — are shed BEFORE any
                # prefill work, with a retryable envelope
                if waitlist and any(r.deadline is not None for r in waitlist):
                    kept = []
                    for r in waitlist:
                        left = None if r.deadline is None else r.deadline - now
                        if left is None or (
                            left > 0 and self._estimate_serve_s(r) <= left
                        ):
                            kept.append(r)
                            continue
                        waited_ms = (now - r.t_enq) * 1e3
                        self.stats.record_shed("deadline", waited_ms=waited_ms)
                        self.tenant_stats.record_shed(r.tenant)
                        msg = (
                            f"deadline infeasible (~{self._estimate_serve_s(r) * 1e3:.0f} ms "
                            f"needed, {left * 1e3:.0f} ms left) "
                            f"(shed_cause=deadline); skipped prefill; "
                            if left > 0
                            else f"deadline expired after {waited_ms:.0f} ms "
                            f"queued (shed_cause=deadline); "
                        )
                        try:
                            r.emit("err", BatcherOverloaded(
                                msg + "retry on another worker"
                            ))
                        except Exception:  # noqa: BLE001 — dead client loop
                            pass
                    waitlist[:] = kept
                    self._wl_len = len(waitlist)
                # deadline sweep, active side: a slot past its deadline is
                # cooperatively aborted through the consumer-gone cancel path
                # (freed at the next burst readback, cause-tagged "deadline")
                for r in self._slots:
                    if (
                        isinstance(r, _Request)
                        and r.deadline is not None
                        and not r.cancelled
                        and now > r.deadline
                    ):
                        r.deadline_hit = True
                        r.cancelled = True
                        try:
                            r.emit("err", BatcherOverloaded(
                                f"deadline exceeded mid-decode after {r.generated} "
                                f"tokens (shed_cause=deadline); retry on another "
                                f"worker"
                            ))
                        except Exception:  # noqa: BLE001 — dead client loop
                            pass
                # deadline sweep, suspended side: a parked slot's clock keeps
                # running — an expired one is failed right here with the same
                # retryable deadline cause (it holds no pool blocks, so there
                # is nothing to free), and a cancelled one is dropped
                if self._suspended:
                    kept_s = []
                    for srec in self._suspended:
                        r = srec.req
                        if r.cancelled:
                            self._ledger_finalize(
                                r,
                                "deadline_abort" if r.deadline_hit else "cancelled",
                            )
                            self.stats.record_cancel("active")
                            continue
                        if r.deadline is not None and now > r.deadline:
                            r.deadline_hit = True
                            waited_ms = (now - r.t_enq) * 1e3
                            self.stats.record_shed(
                                "deadline", waited_ms=waited_ms
                            )
                            self._suspend_stats["suspended_deadline_expired"] += 1
                            self._ledger_finalize(r, "deadline_abort")
                            self.tenant_stats.record_shed(r.tenant)
                            try:
                                r.emit("err", BatcherOverloaded(
                                    f"deadline exceeded while suspended after "
                                    f"{r.generated} tokens (shed_cause=deadline); "
                                    f"retry on another worker"
                                ))
                            except Exception:  # noqa: BLE001 — dead client loop
                                pass
                            continue
                        kept_s.append(srec)
                    self._suspended = kept_s
                # resume parked slots BEFORE admitting new waiters: they are
                # strictly older work and already hold their first tokens
                resume_suspended()
                # weighted fair-share admission: reorder the waitlist by
                # deficit round-robin over tenants (FIFO within a tenant,
                # prompt tokens as cost, class/key weight as share). A single
                # tenant degenerates to exact FIFO, so every pre-QoS workload
                # admits in the same order it always did.
                if len(waitlist) > 1:
                    waitlist[:] = self._drr.order(
                        waitlist,
                        tenant_of=lambda r: r.tenant,
                        cost_of=lambda r: len(r.prompt_ids),
                        weight_of=lambda r: r.drr_weight,
                    )
                    # the premium depth grace in _enqueue can leave the queue
                    # over its bound; settle it here by displacing the excess
                    # from the BACK of the DRR order, lowest class first — the
                    # requests weighted fair share says would wait the longest
                    # anyway go retry on a less loaded worker
                    limit = (
                        bo.effective_queue_limit(self.max_queue)
                        if bo is not None else self.max_queue
                    )
                    if limit and len(waitlist) > limit:
                        order = {id(r): i for i, r in enumerate(waitlist)}
                        excess = len(waitlist) - limit
                        victims = sorted(
                            waitlist, key=lambda r: (r.rank, -order[id(r)])
                        )[:excess]
                        vset = {id(r) for r in victims}
                        waitlist[:] = [r for r in waitlist if id(r) not in vset]
                        for r in victims:
                            waited_ms = (now - r.t_enq) * 1e3
                            self.stats.record_shed(
                                "fair_share", waited_ms=waited_ms
                            )
                            self.tenant_stats.record_shed(r.tenant)
                            try:
                                r.emit("err", BatcherOverloaded(
                                    "displaced by weighted fair share "
                                    "(shed_cause=fair_share); retry on another "
                                    "worker"
                                ))
                            except Exception:  # noqa: BLE001 — dead client
                                pass
                self._wl_len = len(waitlist)
            # admit waiters: bursts of short same-bucket prompts go through
            # one batched dispatch; runs of LONG prompts go through one
            # batched CHUNKED dispatch; odd ones admit individually
            while waitlist and None in self._slots:
                self._wl_len = len(waitlist)
                free = self._slots.count(None)
                head_long = len(waitlist[0].prompt_ids) > self.prefill_chunk
                head_bucket = (
                    None if head_long
                    else self._bucket(len(waitlist[0].prompt_ids))
                )
                group: list[_Request] = []

                def _peek_hit(r: _Request) -> bool:
                    # a long prompt with a usable cached prefix is admitted
                    # ALONE: the group-chunked program prefills every row
                    # from position 0, which would throw the hit away (a
                    # peek, not a match — nothing is pinned until admit_one)
                    return (
                        pc is not None
                        and len(r.prompt_ids) > self.prefill_chunk
                        and pc.peek(r.prompt_ids) >= self.prefill_chunk
                    )

                if head_long:
                    cap = min(free, self.max_group_long)
                    head_hit = _peek_hit(waitlist[0])
                    group.append(waitlist.pop(0))
                    while (
                        not head_hit
                        and waitlist
                        and len(group) < cap
                        and len(waitlist[0].prompt_ids) > self.prefill_chunk
                        and not _peek_hit(waitlist[0])
                    ):
                        group.append(waitlist.pop(0))
                    # top-up: a chunked admit costs SECONDS of prefill, so
                    # waiting ~50 ms for co-arriving long prompts (e.g. a
                    # synchronized client wave trickling through the
                    # broker) is always worth one more group row — the
                    # arrival race otherwise serializes them into separate
                    # full prefill passes (and, once, a separate COMPILE
                    # per distinct group width). With live streams the
                    # wait is spent as a decode burst instead of idling
                    # (same wall clock, but the chip works and nobody's
                    # inter-token gap grows).
                    def drain_topup() -> bool:
                        """Pull queued longs; False = stop topping up."""
                        while len(group) < cap:
                            try:
                                nxt = self._inbox.get_nowait()
                            except _queue.Empty:
                                return True
                            if nxt is None:
                                # shutdown sentinel: push back for the
                                # outer intake to see after this admit
                                self._inbox.put(None)
                                return False
                            if isinstance(nxt, _ControlOp):
                                run_control(nxt)
                                continue
                            if nxt.cancelled:
                                self.stats.record_cancel("inbox")
                                continue
                            if (
                                len(nxt.prompt_ids) > self.prefill_chunk
                                and not _peek_hit(nxt)
                            ):
                                group.append(nxt)
                            else:
                                waitlist.append(nxt)
                                return False
                        return False

                    if (
                        not head_hit
                        and len(group) < cap
                        and not waitlist
                        and coalesce_s > 0
                        and not ext_live()
                    ):
                        if active():
                            # guarded like every other dispatch site: a
                            # device failure here must fail the popped group
                            # honestly and reset, not kill the owner thread
                            # with the group's streams hung (r4 advisor)
                            try:
                                decode_once()
                                pump()
                            except Exception as e:  # noqa: BLE001
                                for req in group:
                                    req.emit("err", e)
                                reset_after_failed_dispatch()
                                continue
                            drain_topup()
                        else:
                            deadline = time.monotonic() + max(coalesce_s, 0.05)
                            while len(group) < cap:
                                left = deadline - time.monotonic()
                                if left <= 0:
                                    break
                                try:
                                    nxt = self._inbox.get(timeout=left)
                                except _queue.Empty:
                                    break
                                if nxt is None:
                                    self._inbox.put(None)
                                    break
                                if isinstance(nxt, _ControlOp):
                                    run_control(nxt)
                                    continue
                                if nxt.cancelled:
                                    self.stats.record_cancel("inbox")
                                    continue
                                if (
                                    len(nxt.prompt_ids) > self.prefill_chunk
                                    and not _peek_hit(nxt)
                                ):
                                    group.append(nxt)
                                else:
                                    waitlist.append(nxt)
                                    break
                    # requests popped into the group are being ADMITTED, not
                    # queued: refresh the mirror before the seconds-long
                    # chunked admit so the depth bound doesn't count them
                    # and spuriously shed new submits (measured against the
                    # "queued-not-yet-admitted" semantics _enqueue documents)
                    self._wl_len = len(waitlist)
                    if len(group) > 1:
                        try:
                            with self._admit_span(
                                    path="chunked", width=len(group),
                                    tokens=max(len(r.prompt_ids) for r in group)):
                                admit_group_chunked(group)
                        except _PoolExhausted as e:
                            # raised pre-dispatch: the device pool is intact,
                            # shed the rows not installed (a row whose prompt
                            # ended earlier decodes on) without the cache reset
                            for req in group:
                                if req.slot < 0:
                                    self._ledger_finalize(req, "shed_after_prefill")
                                    req.emit("err", e)
                        except Exception as e:  # noqa: BLE001 — surface to callers
                            for req in group:
                                if req.slot < 0:  # the reset fails the installed
                                    self._ledger_finalize(req, "failed")
                                    req.emit("err", e)
                            reset_after_failed_dispatch()
                        continue
                elif head_bucket is not None:
                    while (
                        waitlist
                        and len(group) < min(free, self.max_group_admit)
                        and len(waitlist[0].prompt_ids) <= self.prefill_chunk
                        and self._bucket(len(waitlist[0].prompt_ids)) == head_bucket
                    ):
                        group.append(waitlist.pop(0))
                self._wl_len = len(waitlist)  # popped-into-group != queued
                if len(group) > 1:  # here only via the short same-bucket path
                    try:
                        with self._admit_span(
                                path="group", width=len(group), bucket=head_bucket,
                                tokens=max(len(r.prompt_ids) for r in group)):
                            handled = admit_group(group, head_bucket)
                    except Exception as e:  # noqa: BLE001 — surface to callers
                        for req in group:
                            self._ledger_finalize(req, "failed")
                            req.emit("err", e)
                        reset_after_failed_dispatch()
                        continue
                    if handled:
                        continue
                    # group placement would wrap the ring (or the block pool
                    # cannot fit the whole group): admit one by one
                for req in group:
                    try:
                        with self._admit_span(path="one", width=1,
                                              tokens=len(req.prompt_ids)):
                            admit_one(req)
                    except _PoolExhausted as e:
                        # pre-dispatch shed: pool state is intact, the other
                        # streams keep decoding; no cache reset — but a long
                        # prompt's chunk prefills may have run before the
                        # suffix alloc failed: that device time was wasted
                        self._ledger_finalize(req, "shed_after_prefill")
                        req.emit("err", e)
                    except Exception as e:  # noqa: BLE001 — surface to the caller
                        self._ledger_finalize(req, "failed")
                        req.emit("err", e)
                        reset_after_failed_dispatch()
            # age bound: requests STILL waiting after admission had its
            # chance (i.e. genuinely slot-starved, not just coalescing) and
            # older than the limit are shed with an honest error instead of
            # queueing invisibly (the r4 bench's silent 38.6 s admit-delay
            # tail) — the reply lets the client retry on a queue-group peer
            if self.max_queue_age_ms and waitlist:
                now = time.monotonic()
                kept = []
                for r in waitlist:
                    waited_ms = (now - r.t_enq) * 1e3
                    if waited_ms > self.max_queue_age_ms:
                        self.stats.record_shed("age", waited_ms=waited_ms)
                        self.tenant_stats.record_shed(r.tenant)
                        try:
                            r.emit("err", BatcherOverloaded(
                                f"shed after {waited_ms:.0f} ms queued "
                                f"(> {self.max_queue_age_ms:.0f} ms bound) "
                                f"(shed_cause=age); retry on another worker"
                            ))
                        except Exception:  # noqa: BLE001 — dead client loop
                            pass
                    else:
                        kept.append(r)
                waitlist[:] = kept
            self._wl_len = len(waitlist)
            # depth-2 pipeline: dispatch the next burst, THEN block on the
            # oldest in-flight readback — the device computes burst k+1
            # while the host delivers burst k's tokens. EXCEPT when an admit
            # is in flight AT LIGHT LOAD: its first-token readback must not
            # queue behind the next burst (a D2H transfer waits for the
            # programs queued ahead of it, which would add a whole burst to
            # TTFT) — drain first, then resume the pipeline. At
            # high occupancy (>= 3/4 of slots live) the trade flips:
            # closed-loop traffic admits every few bursts, and draining the
            # pipeline on each one idles the device for a readback round
            # trip per admit (the 3/4 threshold is not re-measured on a
            # local chip); there TTFT is queue-dominated anyway, so keep the pipeline full and let the
            # admit's first token ride one burst later.
            try:
                if any(rec[0] == "admit" for rec in inflight) and (
                    4 * len(active()) < 3 * self.max_slots
                ):
                    pump(0)
                maybe_compact()
                if ext_live():
                    # ext regime: a constrained/logprob slot advances one
                    # masked step at a time, and the burst/spec programs
                    # would advance the device pos carry of EVERY row —
                    # so while any ext slot is live, all slots decode
                    # through the masked single-step program. pump(0)
                    # first so an ext admit's rewind lands before its
                    # first masked step; pump(0) after so the DFA state
                    # advances before the next mask is built.
                    pump(0)
                    decode_ext_once()
                    pump(0)
                elif (
                    spec is not None
                    and 0 < len(active()) <= spec.max_active
                    and not (bo is not None and bo.pause_spec)
                ):
                    # speculative regime (low occupancy = memory-bound):
                    # drain so proposals see full history and admit records
                    # have installed their n-gram indices, verify, drain
                    # again (host pos only catches up at readback). The
                    # depth-2 pipeline is deliberately given up here — one
                    # verify emits up to k+1 tokens per slot, so the
                    # readback round trip amortizes across the whole burst.
                    pump(0)
                    if spec_once():
                        pump(0)
                    else:
                        decode_once()
                        pump()
                else:
                    decode_once()
                    pump()
            except Exception:  # noqa: BLE001 — K/V were donated; must reset
                reset_after_failed_dispatch()

    def _deliver(
        self,
        req: _Request,
        tok_id: int,
        logprob: float | None = None,
        top_ids: list | None = None,
        top_lps: list | None = None,
        into: list | None = None,
    ) -> str | None:
        """Push one token (or, with ``into``, append it there for the caller
        to hand over as one ``("toks", [...])`` event); returns the end reason
        when the request just finished, else None. The END event is NOT
        emitted here — the caller
        frees the slot first, then emits, so a consumer observing "end" can
        rely on the slot (and the batcher's ``idle`` view) being current
        (the registry's idle-eviction check reads it immediately after a
        chat returns). Requests with ``want_logprobs`` receive
        ``(tok, logprob, top_ids, top_logprobs)`` tuples instead of bare
        ids (the ext readback supplies the extra fields)."""
        if tok_id in req.sp.stop_ids:
            if req.trace is not None:
                req.trace.mark("decode_done")
            return "stop"
        req.generated += 1
        self.stats.tokens += 1
        if req.generated == 1:
            # the first delivered token closes both latency halves: TTFT
            # (enqueue -> token) and prefill (admit dispatch -> token)
            now = time.monotonic()
            self.stats.ttft_ms.record((now - req.t_enq) * 1e3)
            if req.t_admit:
                self.stats.prefill_ms.record((now - req.t_admit) * 1e3)
                self._note_prefill_rate(
                    len(req.prompt_ids), self._warm_s(req.t_admit, now))
            if req.trace is not None:
                req.trace.mark("first_token", now)
        if req.trace is not None:
            # request mark for the reply path's worker.publish lag: set
            # BEFORE the token is handed over, or the loop thread can publish
            # the chunk while the mark still says the token before it (None
            # for a stream's first: tests/test_obs_spans.py under load)
            req.trace.emitted = (time.perf_counter(), req.generated)
        if req.want_logprobs:
            req.emit("tok", (tok_id, logprob, top_ids, top_lps))
        elif into is not None:
            into.append(tok_id)
        else:
            req.emit("tok", tok_id)
        req.emitted.append(int(tok_id))
        if req.generated >= req.sp.max_tokens or req.pos + 1 >= self.max_seq:
            if req.trace is not None:
                req.trace.mark("decode_done")
            return "length"
        return None

    def _drain_all(self, reason: str, waitlist: list[_Request] = ()) -> None:
        # the owner thread is gone (or going): nothing is waiting any more,
        # so zero the waitlist mirror unconditionally — a stopped batcher
        # must read as idle (the registry's eviction check relies on it)
        self._wl_len = 0
        self._slot_view = {}
        for req in waitlist:
            req.emit("end", reason)
        if isinstance(waitlist, list):
            waitlist.clear()  # self._waitlist: a later crash must not re-fail these
        for i, req in enumerate(self._slots):
            if isinstance(req, _Request):
                # whatever streamed before shutdown was served; the ledger
                # keeps its tokens so goodput stays honest across drains
                self._ledger_finalize(req, "served")
                req.emit("end", reason)
            if req is not None:  # includes _RESERVED placeholders
                self._slots[i] = None
        for rec in self._suspended:
            # suspended slots are live requests parked on the host tier;
            # a drain fails them exactly like active slots (their streamed
            # tokens were served, the rest retries elsewhere)
            self._ledger_finalize(rec.req, "served")
            rec.req.emit("end", reason)
        self._suspended = []
        while True:
            try:
                req = self._inbox.get_nowait()
            except _queue.Empty:
                return
            if req is not None:
                req.emit("end", reason)
