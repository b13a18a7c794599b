"""The device programs of the serving path: one table of pure builders.

``build_programs`` makes every jitted program the continuous batcher
dispatches (prefill, admit, decode, spec verify, pool copies), for one model
configuration and mesh, from plain arguments. Nothing here knows the
scheduler: serve/batcher.py imports this module, wraps each entry in its
dispatch timer and calls it; this module imports neither the batcher, the
block pool, the prefix cache nor obs/. A program's source locations are part
of its compile-cache key, so keeping the programs out of the scheduler keeps
a scheduler edit from re-keying them.

The table's keys are the names dispatches are recorded under
(``lmstudio_program_ms{program=...}``); ``recorded_name`` and ``ring_name``
add the ``_moe`` / ``_ring`` family tags. A device trace and the build ledger
know a program by its jitted function's ``__name__`` instead
(``decode_pos_pallas`` for the table's ``decode_pallas``): obs/roofline.py
``program_kind`` is the bridge, by the name alone (``_TABLE_NAME_OF`` holds
the entries whose function is named otherwise: tests/test_scopes.py).

Inside the programs, what draws and writes a token lies under the scope
``head/sample`` (obs/spans.py ``SCOPE_NAMES``; the model files open the
others); pool and ring writes, rolls and table slices are glue.
"""

from __future__ import annotations

import inspect
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..engine.sampling import require_partitionable_threefry, spec_accept_rows
from ..models.config import ModelConfig
from ..models.llama import forward, forward_decode_paged, make_cache
from ..ops.kvcache import (
    KVQ,
    WithState,
    has_state,
    is_quantized,
    kv_copy_slice,
    kv_pool_copy_block,
    kv_pool_gather_view,
    kv_pool_read_blocks,
    kv_pool_scatter_view,
    kv_pool_write_row,
    kv_roll_s,
    kv_slice,
    state_row,
    state_write_row,
)
from ..parallel.ring_attention import use_ring_prefill
from ..parallel.sharding import cache_spec, row_cache_spec, validate_mesh_for_config

# how many top-logprob (id, logprob) pairs the ext decode programs read back
# per step; OpenAI caps top_logprobs requests well below this
LOGPROBS_K = 8

# forward-bearing programs, which record under a "_moe" name suffix when the
# model runs capacity-factor routed experts (roofline.program_family) —
# sampling/bookkeeping programs (finish_admit, select_end, pool copies)
# never touch the FFN and keep their plain names
_MOE_TAGGED_PROGRAMS = frozenset({
    "prefill1", "prefill_full", "prefill_chunk_group",
    "admit_fused", "admit_many_fused",
    "admit_fused_paged", "admit_many_fused_paged",
    "decode", "decode_pos", "decode_pos_ext",
    "decode_pos_paged", "decode_pos_paged_ext",
    "decode_pallas", "decode_pallas_ext",
    "spec_verify", "spec_verify_paged", "spec_verify_pallas",
})


def recorded_name(cfg: ModelConfig, name: str) -> str:
    """The name the dispatches of table entry ``name`` are recorded under:
    forward-bearing programs of a routed-MoE model carry a ``_moe`` suffix —
    same program class (classify_program strips the suffix), distinct
    metrics family."""
    if name in _MOE_TAGGED_PROGRAMS and cfg.is_moe and cfg.use_routed_moe:
        return name + "_moe"
    return name


def ring_name(cfg: ModelConfig, mesh, base: str, t: int) -> str | None:
    """Per-dispatch metrics-name override for a full-prefill of padded
    width ``t``: tagged ``_ring`` when this bucket's program takes the
    sp ring-attention path (parallel.ring_attention.use_ring_prefill —
    t is trace-time static, so the tag matches what the jit compiled).
    None means "use the table name"."""
    if mesh is None or not use_ring_prefill(mesh, t):
        return None
    return recorded_name(cfg, base) + "_ring"


def prompt_tokens_arg(fn: Callable) -> int | None:
    """Where a program of the table takes its [B, T] block of prompt tokens
    (the argument named ``tokens``: the prefills and the fused admits), None
    for a program that takes none (decode and verify feed back ``tok``)."""
    names = list(inspect.signature(fn).parameters)
    return names.index("tokens") if "tokens" in names else None


def build_programs(cfg: ModelConfig, mesh, *, max_seq: int, paged: bool,
                   kv_block_tokens: int, sample_rows: Callable) -> dict[str, Callable]:
    """Every jitted program of the serving path for ``cfg`` on ``mesh``, by
    recorded name. ``max_seq`` is the serving cache's length, ``paged``
    adds the block-pool programs (tables over blocks of ``kv_block_tokens``
    tokens) beside the contiguous-ring ones. ``sample_rows`` is the sampler
    every program draws its tokens with (engine.sampling.sample_rows): the
    caller hands it in because the benchmark's rehearsal of a broken path
    (benchmark/tests/test_rehearsal.py) replaces it in the batcher's module
    to show that ``correct`` catches a wrong token."""
    require_partitionable_threefry()  # the one layout of a draw's bits the sampler reproduces
    fwd = partial(forward, cfg=cfg, mesh=mesh)

    def draw(*args, **kw):
        with jax.named_scope("head/sample"):
            return sample_rows(*args, **kw)

    def put_token(tok, first, slot):
        """A row's first token into the device-resident next-token carry."""
        with jax.named_scope("head/sample"):
            return jax.lax.dynamic_update_slice(tok, first, (slot,))

    def draw_ext(logits, seeds, steps, temp, topk, topp, mask):
        """The masked step of the "ext" programs: the token, its log
        probability and the top ``LOGPROBS_K`` (id, logprob) pairs."""
        with jax.named_scope("head/sample"):
            raw = logits[:, -1, :]
            nxt = sample_rows(raw, seeds, steps, temp, topk, topp, mask=mask)
            logp = jax.nn.log_softmax(raw.astype(jnp.float32), axis=-1)
            chosen = jnp.take_along_axis(logp, nxt[:, None], axis=-1)[:, 0]
            kk = min(LOGPROBS_K, raw.shape[-1])
            top_lp, top_ids = jax.lax.top_k(logp, kk)
            return nxt, chosen, top_ids, top_lp

    def accept(logits, drafts, dlen, seeds, steps, temp, topk, topp):
        """A verify's acceptance rule: the emitted tokens, their count and
        the next carry token."""
        with jax.named_scope("head/sample"):
            out, n_emit = spec_accept_rows(
                logits, drafts, dlen, seeds, steps, temp, topk, topp
            )
            new_tok = jnp.take_along_axis(out, (n_emit - 1)[:, None], axis=1)[:, 0]
            return out, n_emit, new_tok

    # -- explicit cache shardings (tensor-parallel serving) --------------
    # With a mesh, the serving K/V ring arrives in every jit already
    # sharded (heads on tp — shard_cache in _run), but values *created
    # inside* a jit (the fused admits' fresh row caches) and the cache
    # write boundaries would otherwise be left to the partitioner's
    # guess — worst case a replicated transient per chip plus an
    # all-gather at the serving-cache write. ``pin_cache``/``pin_row``
    # pin the KV head axis to tp at creation and at every read/write
    # boundary; the constraint matches the donated inputs' shardings
    # exactly, so buffer donation survives. Both are identity with no
    # mesh — the tp=1 path compiles byte-for-byte unchanged.
    if mesh is not None:
        validate_mesh_for_config(mesh, cfg)
        cache_sh = NamedSharding(mesh, cache_spec(mesh, cfg))
        row_sh = NamedSharding(mesh, row_cache_spec(mesh, cfg))

        def _pin_with(c, sh):
            if is_quantized(c):
                s_sh = NamedSharding(mesh, PartitionSpec(*list(sh.spec)[:-1]))
                return KVQ(
                    q=jax.lax.with_sharding_constraint(c.q, sh),
                    s=jax.lax.with_sharding_constraint(c.s, s_sh),
                )
            return jax.lax.with_sharding_constraint(c, sh)

        def pin_cache(c):
            return _pin_with(c, cache_sh)

        def pin_row(c):
            return _pin_with(c, row_sh)
    else:

        def pin_cache(c):
            return c

        pin_row = pin_cache

    def row_of(c, i):
        """Row i of a transient row cache as a [1, ...] cache of its own.
        The two caches of a pair are sliced each by its own shape: K and
        V alike for GQA, latent and rotary key for MLA."""
        zero = jnp.zeros((), jnp.int32)
        if has_state(c):  # a row's state goes with it
            return WithState(row_of(c.kv, i), state_row(c, i), c.axes)
        return kv_slice(c, (i, zero, zero, zero, zero), (1,) + tuple(c.shape[1:]))

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(6,))
    def prefill1(params, tokens, k1, v1, start, last_pos, window):
        # One [1, C] chunk of a single prompt's chunked admit. Donates the
        # row-cache pair like prefill_chunk_group: the pair is updated in
        # place (every caller rebinds k1, v1 from the result; an undonated
        # pair was copied whole on entry, 335 MB at 40 x 8 x 2048 x 128).
        # lm_head at one position only ([1,1,vocab]); non-final chunks
        # ignore the logits, the final chunk's last_pos is the prompt end.
        # uniform_start: all rows share `start`, so chunk continuations
        # ride the cache-backed flash kernel, not the dense fallback.
        # window (static, bucketed >= start + C): each chunk reads only
        # the live cache prefix instead of the full max_seq slab — the
        # r4 bench measured 16k chunked prefill at 43% of the
        # single-dispatch kernel from the O(T^2) full-window reads
        # (and KVQ dequant transients) this removes.
        logits, k1, v1 = fwd(
            params, tokens=tokens, k_cache=pin_row(k1), v_cache=pin_row(v1),
            start_pos=start,
            logit_positions=last_pos, uniform_start=True, attn_window=window,
        )
        return logits, pin_row(k1), pin_row(v1)

    def _insert_and_sample(params, K, V, tok, k1, v1, logits, slot, shift,
                           seed, temp, topk, topp):
        """Roll the prefilled row onto the ring, write it, sample token 0,
        and write it into the device-resident next-token carry ``tok``.

        The prefix (tokens at [0, n) of k1) must land on the ring slots
        ending at the current ring head, so the whole row is rolled by
        ``shift`` = (ring_next - n) mod S before the row write — decode
        validity is "the start_pos+1 most recent ring slots" and relies
        on every row's tokens being slot-contiguous there.
        """
        zero = jnp.zeros((), jnp.int32)
        k1 = kv_roll_s(k1, shift, s_axis=3)
        v1 = kv_roll_s(v1, shift, s_axis=3)
        K = pin_cache(kv_copy_slice(K, k1, (slot, zero, zero, zero, zero)))
        V = pin_cache(kv_copy_slice(V, v1, (slot, zero, zero, zero, zero)))
        first = draw(
            logits[:, 0], seed[None], jnp.zeros((1,), jnp.int32),
            temp[None], topk[None], topp[None],
        )
        tok = put_token(tok, first, slot)
        return first, K, V, tok

    @partial(jax.jit, donate_argnums=(1, 2, 3))
    def admit_fused(params, K, V, tok, tokens, n, slot, shift, seed, temp,
                    topk, topp):
        """Whole short-prompt admit in ONE dispatch: fresh row cache is
        created on device, prefilled, ring-aligned, written, and the
        first token sampled — host round trips per admit drop from ~5 to
        2 (tokens in, first token out), which bounds TTFT under
        concurrent load."""
        k1, v1 = make_cache(cfg, 1, max_seq)
        k1, v1 = pin_row(k1), pin_row(v1)
        # logit_positions: lm_head at the prompt end only — skips
        # bucket× the lm_head FLOPs and the [1, bucket, vocab] f32
        logits, k1, v1 = fwd(
            params, tokens=tokens, k_cache=k1, v_cache=v1,
            start_pos=jnp.zeros((1,), jnp.int32),
            logit_positions=jnp.reshape(n - 1, (1,)),
            fresh_prefill=True,
        )
        return _insert_and_sample(
            params, K, V, tok, k1, v1, logits, slot, shift, seed, temp,
            topk, topp,
        )

    @partial(jax.jit, donate_argnums=(1, 2, 3))
    def admit_many_fused(params, K, V, tok, tokens, ns, slots, offsets,
                         seeds, temps, topks, topps):
        """Admit m short prompts in ONE dispatch: a single batched
        prefill over [m, bucket] plus per-row insert/sample — concurrent
        arrivals pay one prefill's latency instead of m (the dominant
        term in TTFT p95 under bursty load).

        The transient prefill cache is [m, ..., bucket] long, not
        max_seq (which at m = max_slots would duplicate the whole
        serving cache's HBM). Each bucket-length block lands at
        ``offsets[i]`` = ring_next - n_i so the prefix ends at the ring
        head; the caller guarantees no block wraps (falls back to
        per-request admits otherwise)."""
        m, bucket = tokens.shape
        km, vm = make_cache(cfg, m, bucket)
        km, vm = pin_row(km), pin_row(vm)
        logits, km, vm = fwd(
            params, tokens=tokens, k_cache=km, v_cache=vm,
            start_pos=jnp.zeros((m,), jnp.int32),
            logit_positions=ns - 1,  # [m,1,vocab]: prompt-end rows only
            fresh_prefill=True,
        )
        zero = jnp.zeros((), jnp.int32)
        firsts = draw(
            logits[:, 0], seeds, jnp.zeros((m,), jnp.int32), temps, topks, topps
        )

        def body(carry, i):
            K, V, tok = carry
            k1, v1 = row_of(km, i), row_of(vm, i)
            K = kv_copy_slice(K, k1, (slots[i], zero, zero, offsets[i], zero))
            V = kv_copy_slice(V, v1, (slots[i], zero, zero, offsets[i], zero))
            tok = put_token(
                tok, jax.lax.dynamic_slice_in_dim(firsts, i, 1), slots[i])
            return (K, V, tok), None

        (K, V, tok), _ = jax.lax.scan(
            body, (K, V, tok), jnp.arange(m, dtype=jnp.int32)
        )
        return firsts, pin_cache(K), pin_cache(V), tok

    @partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
    def finish_admit(params, K, V, tok, k1, v1, logits, slot, shift,
                     seed, temp, topk, topp):
        """Chunked-prefill tail: ring-align + write + sample, one dispatch."""
        return _insert_and_sample(
            params, K, V, tok, k1, v1, logits, slot, shift,
            seed, temp, topk, topp,
        )

    @partial(jax.jit, donate_argnums=(0, 1))
    def write_prefix_block(k1, v1, kb, vb, start):
        """Write one CACHED prefix block into a transient row cache at
        S-offset ``start`` (hit-path admit): the block lands exactly
        where the chunked prefill would have written it, so the suffix
        chunks resume through prefill1 unchanged. kb/vb are NOT donated
        — they stay resident in the prefix cache for the next hit."""
        zero = jnp.zeros((), jnp.int32)
        k1 = kv_copy_slice(k1, kb, (zero, zero, zero, start, zero))
        v1 = kv_copy_slice(v1, vb, (zero, zero, zero, start, zero))
        return pin_row(k1), pin_row(v1)

    @jax.jit
    def prefill_full(params, tokens, k1, v1, n):
        """A whole LONG prompt in ONE fresh flash dispatch (idle-engine
        admits). Chunking exists to bound live streams' inter-token
        gap; with nothing else decoding it is pure overhead — measured
        on-chip at 16k: ~110-180 ms per chunk of structural cost
        beyond the matmuls, 5.2 s
        chunked vs 2.3 s for this path. Tokens are right-padded to a
        pow2 bucket (pad keys sit at positions only pad queries can
        see; the rolled-in junk above ``n`` lands on future ring slots
        that decode overwrites before they can become valid)."""
        logits, k1, v1 = fwd(
            params, tokens=tokens, k_cache=pin_row(k1), v_cache=pin_row(v1),
            start_pos=jnp.zeros((1,), jnp.int32),
            logit_positions=jnp.reshape(n - 1, (1,)),
            fresh_prefill=True,
        )
        return logits, pin_row(k1), pin_row(v1)

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(6,))
    def prefill_chunk_group(params, tokens, km, vm, start, last_pos, window):
        """One [m, C] chunk of a BATCHED chunked admit. Donates the
        m-row transient cache pair (reassigned every iteration; without
        donation each chunk would briefly hold 2x the m-row caches).
        ``window`` (static, bucketed >= start + C) bounds reads to the
        live prefix — see prefill1."""
        logits, km, vm = fwd(
            params, tokens=tokens, k_cache=pin_row(km), v_cache=pin_row(vm),
            start_pos=start,
            logit_positions=last_pos, uniform_start=True, attn_window=window,
        )
        return logits, pin_row(km), pin_row(vm)

    @jax.jit
    def select_end(final, logits, is_end):
        """Keep each row's logits from the chunk its prompt ENDS in."""
        return jnp.where(is_end[:, None, None], logits, final)

    def rows_of(c, rows):
        """The rows ``rows`` ([w], traced) of a transient row cache as a
        [w, ...] cache of their own; what belongs to a row goes with it (a
        quantised cache's scales, a family's state and rings)."""
        def take(a, axis=0):
            # a slice a row, each written into its place of the result: XLA:TPU
            # cuts a gather of whole rows into hundreds of loops of its own, and
            # holds every slice of a concatenation as a temporary
            w = rows.shape[0]
            if w == 1:
                return jax.lax.dynamic_slice_in_dim(a, rows[0], 1, axis=axis)
            def put(t, out):
                return jax.lax.dynamic_update_slice_in_dim(
                    out, jax.lax.dynamic_slice_in_dim(a, rows[t], 1, axis=axis), t, axis=axis)

            out = jnp.zeros(a.shape[:axis] + (w,) + a.shape[axis + 1:], a.dtype)
            return jax.lax.fori_loop(0, w, put, out)

        if has_state(c):
            st = tuple(take(a, ax) for a, ax in zip(c.st, c.axes))
            return WithState(rows_of(c.kv, rows), st, c.axes)
        if is_quantized(c):
            return KVQ(q=take(c.q), s=take(c.s))
        return take(c)

    @jax.jit
    def take_rows(km, vm, final, rows):
        """A chunked group admit narrows: the rows ``rows`` of its [m, ...]
        cache pair, and of the end logits kept for them, as a [w, ...] group
        of their own (w = 1: one row, for the single-row finish or
        ``prefill1``). km/vm are NOT donated: no [w, ...] result can take an
        [m, ...] buffer, and the caller drops the wide pair at once."""
        return (pin_row(rows_of(km, rows)), pin_row(rows_of(vm, rows)),
                rows_of(final, rows))

    @partial(jax.jit, donate_argnums=(1, 2, 3))
    def finish_admit_group(params, K, V, tok, km, vm, final_logits,
                           slots, shifts, seeds, temps, topks, topps):
        """Batched chunked-prefill tail: per-row ring-align + write +
        first-token sample for m rows in ONE dispatch. km/vm are NOT
        donated: the AOT compile path double-counts donated buffers
        against the HBM budget, and the m-row transients are the
        largest operands here — donating them would spuriously reject
        configs whose real peak fits comfortably."""
        m = final_logits.shape[0]
        zero = jnp.zeros((), jnp.int32)
        firsts = draw(
            final_logits[:, 0], seeds, jnp.zeros((m,), jnp.int32),
            temps, topks, topps,
        )

        def body(carry, i):
            K, V, tok = carry
            k1 = kv_roll_s(row_of(km, i), shifts[i], s_axis=3)
            v1 = kv_roll_s(row_of(vm, i), shifts[i], s_axis=3)
            K = kv_copy_slice(K, k1, (slots[i], zero, zero, zero, zero))
            V = kv_copy_slice(V, v1, (slots[i], zero, zero, zero, zero))
            tok = put_token(
                tok, jax.lax.dynamic_slice_in_dim(firsts, i, 1), slots[i])
            return (K, V, tok), None

        (K, V, tok), _ = jax.lax.scan(
            body, (K, V, tok), jnp.arange(m, dtype=jnp.int32)
        )
        return firsts, pin_cache(K), pin_cache(V), tok

    @partial(jax.jit, donate_argnums=(0, 1))
    def compact_ring(K, V, shift):
        """Roll every row's S axis so the shared validity window ends at
        a fresh head below max_seq again — the wrapped ring's recovery
        path (VERDICT r2 weak #7: without this, one wrap costs windowed
        attention reads for the rest of the worker's life)."""
        return (
            pin_cache(kv_roll_s(K, shift, s_axis=3)),
            pin_cache(kv_roll_s(V, shift, s_axis=3)),
        )

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(11, 12))
    def decode(params, tok, K, V, pos, ring, seeds, steps, temp, topk, topp,
               n, window):
        """n decode steps in one dispatch (device-side scan): the host
        sees one transfer in and one [B, n] token readback — and the
        next-token carry stays ON DEVICE (returned as ``tok``), so the
        NEXT burst can be dispatched before this one's tokens are read
        back (the depth-2 pipeline in _run). ``pos``/``steps`` are
        device-resident carries too (returned advanced by n): with them
        re-uploaded every burst, every burst would pay three more
        host->device transfers. ``window`` (static) bounds attention reads to the live
        ring prefix while the ring has not wrapped — the dominant HBM
        saving at partial cache occupancy (~35% step time at half-full,
        granite-2b b32)."""

        def body(carry, i):
            tok, K, V = carry
            logits, K, V = fwd(
                params, tokens=tok[:, None], k_cache=K, v_cache=V,
                start_pos=pos + i, ring_slot=(ring + i) % max_seq,
                attn_window=window,
            )
            nxt = draw(logits[:, -1, :], seeds, steps + i, temp, topk, topp)
            return (nxt, K, V), nxt

        (tok, K, V), toks = jax.lax.scan(
            body, (tok, pin_cache(K), pin_cache(V)), jnp.arange(n, dtype=jnp.int32)
        )
        # [B, n] tokens, caches, device-side carries
        return toks.T, pin_cache(K), pin_cache(V), tok, pos + n, steps + n

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(10, 11))
    def decode_pos(params, tok, K, V, pos, seeds, steps, temp, topk, topp,
                   n, window):
        """Positional-layout decode burst: spec mode's fallback when no
        slot has a draft (or occupancy passed spec_max_active). Same
        contract as ``decode`` minus the ring scalar — each row writes
        its fresh KV at its own sequence position ``pos + i`` (per-row
        scatter) and attention masks by ``key_pos <= position``."""

        def body(carry, i):
            tok, K, V = carry
            logits, K, V = fwd(
                params, tokens=tok[:, None], k_cache=K, v_cache=V,
                start_pos=pos + i, attn_window=window,
            )
            nxt = draw(logits[:, -1, :], seeds, steps + i, temp, topk, topp)
            return (nxt, K, V), nxt

        (tok, K, V), toks = jax.lax.scan(
            body, (tok, pin_cache(K), pin_cache(V)), jnp.arange(n, dtype=jnp.int32)
        )
        return toks.T, pin_cache(K), pin_cache(V), tok, pos + n, steps + n

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(11,))
    def decode_pos_ext(params, tok, K, V, pos, seeds, steps, temp, topk,
                       topp, mask, window):
        """Single masked positional decode step with logprob readback —
        the "ext" regime program, dispatched whenever any live slot
        needs constrained decoding or logprobs. ``mask`` [B, V] bans
        tokens before truncation inside sample_rows; all-True rows are
        a bitwise no-op, so normal slots ride along unchanged. n is
        fixed at 1: the mask for step i+1 depends on the token chosen
        at step i (a host-side DFA walk), so bursts cannot scan."""
        logits, K, V = fwd(
            params, tokens=tok[:, None], k_cache=pin_cache(K),
            v_cache=pin_cache(V), start_pos=pos, attn_window=window,
        )
        nxt, chosen, top_ids, top_lp = draw_ext(
            logits, seeds, steps, temp, topk, topp, mask)
        return (nxt, chosen, top_ids, top_lp, pin_cache(K), pin_cache(V),
                nxt, pos + 1, steps + 1)

    @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(12,))
    def spec_verify(params, tok, K, V, pos, drafts, dlen, seeds, steps,
                    temp, topk, topp, window):
        """One width-(k+1) VERIFY dispatch: forward the device carry
        token plus k drafted tokens through the positional decode
        cache-write path in a single program (the weight tree is read
        once for k+1 token positions — the bandwidth conversion the
        whole feature exists for), then run the rejection-sampling
        acceptance rule on device. Only the accepted prefix advances
        the carries; KV written for rejected positions is stale by
        construction (see spec.py: masked by position, overwritten by
        this row's own future writes — no rollback)."""
        toks_in = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B,k+1]
        logits, K, V = fwd(
            params, tokens=toks_in, k_cache=pin_cache(K), v_cache=pin_cache(V),
            start_pos=pos, attn_window=window,
        )
        K, V = pin_cache(K), pin_cache(V)
        out, n_emit, new_tok = accept(
            logits, drafts, dlen, seeds, steps, temp, topk, topp)
        width = toks_in.shape[1]
        return out, n_emit, K, V, new_tok, pos + n_emit, steps + width

    programs = {
        "prefill1": prefill1,
        "prefill_full": prefill_full,
        "write_prefix_block": write_prefix_block,
        "admit_fused": admit_fused,
        "admit_many_fused": admit_many_fused,
        "finish_admit": finish_admit,
        "prefill_chunk_group": prefill_chunk_group,
        "select_end": select_end,
        "take_rows": take_rows,
        "finish_admit_group": finish_admit_group,
        "decode": decode,
        "decode_pos": decode_pos,
        "decode_pos_ext": decode_pos_ext,
        "spec_verify": spec_verify,
        "compact_ring": compact_ring,
    }

    # -- paged-KV jit grid ------------------------------------------------
    # Every program below reads/writes the serving cache THROUGH a block
    # table over the shared pool [NB, L, Hkv, T, D] instead of a
    # contiguous per-slot ring. The pool replaces K/V wholesale in _run
    # when paged; the legacy programs above stay untouched (and are
    # the KV_PAGED=0 equivalence baseline).
    if paged:
        T = kv_block_tokens
        pin_pool = pin_row  # pool [NB, L, Hkv, T, D]: heads at index 2

        def pool_write(P, row, bids, slot):
            """One prefilled row into the pool: its KV into the blocks
            ``bids``, and, for a family that keeps a recurrent state beside
            the KV (ops.kvcache.WithState), its state into row ``slot`` of
            the state pool. A slot's state is whole after this write:
            nothing of the slot's previous request is read again."""
            if has_state(P):
                return WithState(kv_pool_write_row(P.kv, row.kv, bids),
                                 state_write_row(P, row.st, slot), P.axes)
            return kv_pool_write_row(P, row, bids)

        @partial(jax.jit, donate_argnums=(0,))
        def sample_first(tok, logits, slot, seed, temp, topk, topp):
            """Full-prefix-hit admit: ZERO KV copies — the slot's block
            table already references the cached blocks, so all that is
            left on device is sampling token 0 from the stored
            prompt-end logits into the carry."""
            first = draw(
                logits[:, 0], seed[None], jnp.zeros((1,), jnp.int32),
                temp[None], topk[None], topp[None],
            )
            tok = put_token(tok, first, slot)
            return first, tok

        def _write_and_sample(KP, VP, tok, k1, v1, logits, bids, slot,
                              seed, temp, topk, topp):
            KP = pin_pool(pool_write(KP, k1, bids, slot))
            VP = pin_pool(pool_write(VP, v1, bids, slot))
            first = draw(
                logits[:, 0], seed[None], jnp.zeros((1,), jnp.int32),
                temp[None], topk[None], topp[None],
            )
            tok = put_token(tok, first, slot)
            return first, KP, VP, tok

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def admit_fused_paged(params, KP, VP, tok, tokens, n, bids, slot,
                              seed, temp, topk, topp):
            """Short-prompt admit, paged: prefill a bucket-length
            transient row on device and write its blocks straight into
            the pool at ``bids`` (null-padded — bucket junk past the
            prompt's last block lands in block 0 and is never read
            unmasked). No ring roll: paged mode is positional."""
            k1, v1 = make_cache(cfg, 1, tokens.shape[1])
            k1, v1 = pin_row(k1), pin_row(v1)
            logits, k1, v1 = fwd(
                params, tokens=tokens, k_cache=k1, v_cache=v1,
                start_pos=jnp.zeros((1,), jnp.int32),
                logit_positions=jnp.reshape(n - 1, (1,)),
                fresh_prefill=True,
            )
            return _write_and_sample(
                KP, VP, tok, k1, v1, logits, bids, slot, seed, temp,
                topk, topp,
            )

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def admit_many_fused_paged(params, KP, VP, tok, tokens, ns, bids,
                                   slots, seeds, temps, topks, topps):
            """Batched short admit, paged: one [m, bucket] prefill, then
            a scan writes each row's blocks to its own table entries.
            Pad rows carry all-null bids (junk into block 0)."""
            m, bucket = tokens.shape
            km, vm = make_cache(cfg, m, bucket)
            km, vm = pin_row(km), pin_row(vm)
            logits, km, vm = fwd(
                params, tokens=tokens, k_cache=km, v_cache=vm,
                start_pos=jnp.zeros((m,), jnp.int32),
                logit_positions=ns - 1,
                fresh_prefill=True,
            )
            zero = jnp.zeros((), jnp.int32)
            firsts = draw(
                logits[:, 0], seeds, jnp.zeros((m,), jnp.int32), temps,
                topks, topps,
            )
            def body(carry, i):
                KP, VP, tok = carry
                k1, v1 = row_of(km, i), row_of(vm, i)
                KP = pool_write(KP, k1, bids[i], slots[i])
                VP = pool_write(VP, v1, bids[i], slots[i])
                tok = put_token(
                    tok, jax.lax.dynamic_slice_in_dim(firsts, i, 1), slots[i])
                return (KP, VP, tok), None

            (KP, VP, tok), _ = jax.lax.scan(
                body, (KP, VP, tok), jnp.arange(m, dtype=jnp.int32)
            )
            return firsts, pin_pool(KP), pin_pool(VP), tok

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def finish_admit_paged(params, KP, VP, tok, k1, v1, logits, bids,
                               slot, seed, temp, topk, topp):
            """Chunked/flash-prefill tail, paged: scatter the transient
            row into the pool and sample token 0. ``bids`` is a full
            [max_seq/T] row with NULL entries for blocks that must not
            be written — shared prefix blocks (the slot references the
            cache's copies directly) and the junk tail past the
            prompt. k1/v1 are NOT donated: the block re-layout cannot
            alias the row buffer, so donation would only warn."""
            return _write_and_sample(
                KP, VP, tok, k1, v1, logits, bids, slot, seed, temp,
                topk, topp,
            )

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def finish_admit_group_paged(params, KP, VP, tok, km, vm,
                                     final_logits, bids, slots, seeds,
                                     temps, topks, topps):
            """Batched chunked tail, paged. km/vm NOT donated — same
            AOT double-count reasoning as finish_admit_group."""
            m = final_logits.shape[0]
            firsts = draw(
                final_logits[:, 0], seeds, jnp.zeros((m,), jnp.int32),
                temps, topks, topps,
            )

            def body(carry, i):
                KP, VP, tok = carry
                k1, v1 = row_of(km, i), row_of(vm, i)
                KP = pool_write(KP, k1, bids[i], slots[i])
                VP = pool_write(VP, v1, bids[i], slots[i])
                tok = put_token(
                    tok, jax.lax.dynamic_slice_in_dim(firsts, i, 1), slots[i])
                return (KP, VP, tok), None

            (KP, VP, tok), _ = jax.lax.scan(
                body, (KP, VP, tok), jnp.arange(m, dtype=jnp.int32)
            )
            return firsts, pin_pool(KP), pin_pool(VP), tok

        @partial(jax.jit, donate_argnums=(0, 1))
        def fill_row_chunk(k1, v1, KP, VP, bids, start):
            """Copy C//T cached pool blocks into a transient row cache
            at S-offset ``start`` (partial-prefix-hit admit): suffix
            chunks then attend over the prefix exactly as if it had
            been prefilled here. KP/VP are read-only — the cached
            blocks stay shared; only the transient gets a copy."""
            kb = kv_pool_read_blocks(KP, bids)
            vb = kv_pool_read_blocks(VP, bids)
            zero = jnp.zeros((), jnp.int32)
            k1 = kv_copy_slice(k1, kb, (zero, zero, zero, start, zero))
            v1 = kv_copy_slice(v1, vb, (zero, zero, zero, start, zero))
            return pin_row(k1), pin_row(v1)

        def _touched(pos, width, nb):
            """View-block positions a ``width``-token write starting at
            ``pos`` can touch, clipped into the view (zombie rows past
            max_seq clamp into their own last block — always private,
            and their tokens are never delivered)."""
            ntb = min(nb, (width - 1) // T + 2)
            return jnp.clip(
                pos[:, None] // T
                + jnp.arange(ntb, dtype=jnp.int32)[None, :],
                0, nb - 1,
            )

        @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(11, 12))
        def decode_pos_paged(params, tok, KP, VP, tbl, pos, seeds, steps,
                             temp, topk, topp, n, nb):
            """Paged decode burst: gather each slot's first ``nb`` table
            blocks into a contiguous [B, L, Hkv, nb*T, D] view, run the
            same positional scan as decode_pos over it (the view extent
            IS the attention window — nb rides the same pow2 ladder, so
            reduction extents match the contiguous path), then scatter
            back only the blocks this burst could have written."""
            tbl_n = jax.lax.slice_in_dim(tbl, 0, nb, axis=1)
            Kv = pin_row(kv_pool_gather_view(KP, tbl_n))
            Vv = pin_row(kv_pool_gather_view(VP, tbl_n))

            def body(carry, i):
                tok, Kc, Vc = carry
                logits, Kc, Vc = fwd(
                    params, tokens=tok[:, None], k_cache=Kc, v_cache=Vc,
                    start_pos=pos + i,
                )
                nxt = draw(
                    logits[:, -1, :], seeds, steps + i, temp, topk, topp
                )
                return (nxt, Kc, Vc), nxt

            (tok, Kv, Vv), toks = jax.lax.scan(
                body, (tok, Kv, Vv), jnp.arange(n, dtype=jnp.int32)
            )
            vb = _touched(pos, n, nb)
            KP = pin_pool(kv_pool_scatter_view(KP, Kv, tbl_n, vb))
            VP = pin_pool(kv_pool_scatter_view(VP, Vv, tbl_n, vb))
            return toks.T, KP, VP, tok, pos + n, steps + n

        @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(12,))
        def decode_pos_paged_ext(params, tok, KP, VP, tbl, pos, seeds,
                                 steps, temp, topk, topp, mask, nb):
            """Paged twin of decode_pos_ext: one masked step with
            logprob readback through the gather-view / scatter-back
            frame. Same n=1 constraint (next mask needs this token)."""
            tbl_n = jax.lax.slice_in_dim(tbl, 0, nb, axis=1)
            Kv = pin_row(kv_pool_gather_view(KP, tbl_n))
            Vv = pin_row(kv_pool_gather_view(VP, tbl_n))
            logits, Kv, Vv = fwd(
                params, tokens=tok[:, None], k_cache=Kv, v_cache=Vv,
                start_pos=pos,
            )
            nxt, chosen, top_ids, top_lp = draw_ext(
                logits, seeds, steps, temp, topk, topp, mask)
            vb = _touched(pos, 1, nb)
            KP = pin_pool(kv_pool_scatter_view(KP, Kv, tbl_n, vb))
            VP = pin_pool(kv_pool_scatter_view(VP, Vv, tbl_n, vb))
            return (nxt, chosen, top_ids, top_lp, KP, VP, nxt, pos + 1,
                    steps + 1)

        @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(13,))
        def spec_verify_paged(params, tok, KP, VP, tbl, pos, drafts, dlen,
                              seeds, steps, temp, topk, topp, nb):
            """Paged spec verify: the same gather-view / scatter-back
            frame as decode_pos_paged around the width-(k+1) verify
            forward — spec decode's positional layout IS the block
            table, no separate positional cache."""
            tbl_n = jax.lax.slice_in_dim(tbl, 0, nb, axis=1)
            Kv = pin_row(kv_pool_gather_view(KP, tbl_n))
            Vv = pin_row(kv_pool_gather_view(VP, tbl_n))
            toks_in = jnp.concatenate([tok[:, None], drafts], axis=1)
            logits, Kv, Vv = fwd(
                params, tokens=toks_in, k_cache=Kv, v_cache=Vv,
                start_pos=pos,
            )
            out, n_emit, new_tok = accept(
                logits, drafts, dlen, seeds, steps, temp, topk, topp)
            width = toks_in.shape[1]
            vb = _touched(pos, width, nb)
            KP = pin_pool(kv_pool_scatter_view(KP, Kv, tbl_n, vb))
            VP = pin_pool(kv_pool_scatter_view(VP, Vv, tbl_n, vb))
            return out, n_emit, KP, VP, new_tok, pos + n_emit, steps + width

        @partial(jax.jit, donate_argnums=(0, 1))
        def pool_copy_block(KP, VP, dst, src):
            """Copy-on-write: duplicate one shared block before a write."""
            def cp(P):  # blocks are KV; a slot's state is never shared
                if has_state(P):
                    return WithState(kv_pool_copy_block(P.kv, dst, src), P.st, P.axes)
                return kv_pool_copy_block(P, dst, src)

            return pin_pool(cp(KP)), pin_pool(cp(VP))

        # -- Pallas paged-decode twins (ops/paged_attention.py) --------
        # Same signatures and return contracts as the *_paged programs
        # minus the ``nb`` static arg: the kernel walks a slot's table up
        # to its last live block inside one program, so one compile per
        # burst width serves every context length — no gather-view
        # materialization, no scatter-back, no pow2-ladder recompiles.
        # Write-then-attend happens per layer
        # inside forward_decode_paged (the pool is the only KV storage
        # these programs touch).
        fwd_paged = partial(forward_decode_paged, cfg=cfg, mesh=mesh)

        @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(11,))
        def decode_pos_moe(params, tok, KP, VP, tbl, pos, seeds,
                           steps, temp, topk, topp, n):
            """decode_pos_pallas for a family with routed-expert layers:
            the same burst, and per step and expert layer the distinct
            experts hit, the most rows on one expert and the
            live rows, appended to the token array as 3 x layers rows
            ([B + 3 Le, n]) so that they come back in the burst's one
            readback."""
            def body(carry, i):
                tok, KP, VP = carry
                logits, KP, VP, st = fwd_paged(
                    params, tokens=tok[:, None], k_pool=KP, v_pool=VP,
                    tbl=tbl, start_pos=pos + i, moe_stats=True,
                )
                nxt = draw(
                    logits[:, -1, :], seeds, steps + i, temp, topk, topp
                )
                return (nxt, KP, VP), (nxt, st.reshape(-1))

            (tok, KP, VP), (toks, st) = jax.lax.scan(
                body, (tok, KP, VP), jnp.arange(n, dtype=jnp.int32)
            )
            out = jnp.concatenate([toks.T, st.T.astype(toks.dtype)], axis=0)
            return (out, pin_pool(KP), pin_pool(VP), tok, pos + n,
                    steps + n)

        @partial(jax.jit, donate_argnums=(2, 3), static_argnums=(11,))
        def decode_pos_pallas(params, tok, KP, VP, tbl, pos, seeds,
                              steps, temp, topk, topp, n):
            """Pallas decode burst: n single-token paged forwards in one
            on-device scan, pool carried through."""
            def body(carry, i):
                tok, KP, VP = carry
                logits, KP, VP = fwd_paged(
                    params, tokens=tok[:, None], k_pool=KP, v_pool=VP,
                    tbl=tbl, start_pos=pos + i,
                )
                nxt = draw(
                    logits[:, -1, :], seeds, steps + i, temp, topk, topp
                )
                return (nxt, KP, VP), nxt

            (tok, KP, VP), toks = jax.lax.scan(
                body, (tok, KP, VP), jnp.arange(n, dtype=jnp.int32)
            )
            return (toks.T, pin_pool(KP), pin_pool(VP), tok, pos + n,
                    steps + n)

        @partial(jax.jit, donate_argnums=(2, 3))
        def decode_pos_pallas_ext(params, tok, KP, VP, tbl, pos, seeds,
                                  steps, temp, topk, topp, mask):
            """Pallas twin of decode_pos_paged_ext: one masked step with
            logprob readback straight off the pool."""
            logits, KP, VP = fwd_paged(
                params, tokens=tok[:, None], k_pool=KP, v_pool=VP,
                tbl=tbl, start_pos=pos,
            )
            nxt, chosen, top_ids, top_lp = draw_ext(
                logits, seeds, steps, temp, topk, topp, mask)
            return (nxt, chosen, top_ids, top_lp, pin_pool(KP),
                    pin_pool(VP), nxt, pos + 1, steps + 1)

        @partial(jax.jit, donate_argnums=(2, 3))
        def spec_verify_pallas(params, tok, KP, VP, tbl, pos, drafts,
                               dlen, seeds, steps, temp, topk, topp):
            """Pallas spec verify: the width-(k+1) draft bundle rides the
            same kernel (W = k+1 query rows per slot) — rejected drafts'
            pool rows are stale-by-position, overwritten by that slot's
            next writes, exactly the positional-layout contract."""
            toks_in = jnp.concatenate([tok[:, None], drafts], axis=1)
            logits, KP, VP = fwd_paged(
                params, tokens=toks_in, k_pool=KP, v_pool=VP,
                tbl=tbl, start_pos=pos,
            )
            out, n_emit, new_tok = accept(
                logits, drafts, dlen, seeds, steps, temp, topk, topp)
            width = toks_in.shape[1]
            return (out, n_emit, pin_pool(KP), pin_pool(VP), new_tok,
                    pos + n_emit, steps + width)

        if cfg.slot_state:
            @partial(jax.jit, donate_argnums=(0, 1))
            def state_restore(KP, VP, krow, vrow, bids, st, slot):
                """Resume of a suspended slot of a family whose slots keep a
                state or a ring beside their KV: the host copies of its KV
                into fresh blocks and of that into the slot's row, pools
                donated."""
                return (pool_write(KP, WithState(krow, st[0], KP.axes), bids, slot),
                        pool_write(VP, WithState(vrow, st[1], VP.axes), bids, slot))

            programs["state_restore"] = state_restore

        programs.update({
            "sample_first": sample_first,
            "admit_fused_paged": admit_fused_paged,
            "admit_many_fused_paged": admit_many_fused_paged,
            "finish_admit_paged": finish_admit_paged,
            "finish_admit_group_paged": finish_admit_group_paged,
            "fill_row_chunk": fill_row_chunk,
            "decode_pos_paged": decode_pos_paged,
            "decode_pos_paged_ext": decode_pos_paged_ext,
            "spec_verify_paged": spec_verify_paged,
            "pool_copy_block": pool_copy_block,
            # a family with routed-expert layers gets the burst that also
            # reads the expert counters back
            "decode_pallas": decode_pos_moe if cfg.n_moe_layers else decode_pos_pallas,
            "decode_pallas_ext": decode_pos_pallas_ext,
            "spec_verify_pallas": spec_verify_pallas,
        })

    return programs
