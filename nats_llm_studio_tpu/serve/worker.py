"""NATS worker runtime: the handler layer the reference leaves unwritten.

The reference is a library with no ``main()``/``Subscribe`` (SURVEY.md §1);
its README specifies the runtime: connect to ``NATS_URL``, queue-subscribe the
subjects under ``NATS_QUEUE_GROUP`` (/root/reference/README.md:475-494). This
module implements that contract plus the handler semantics of
/root/reference/nats_llm_studio.go:228-364:

* uniform ``{ok, error?, data?}`` envelope (``:186-190``)
* validation branches and error strings (``:254-262, :293-300, :331-345``) —
  with the Portuguese "payload vazio em ChatModel" (``:332``) consciously
  normalized to English (deviation documented in SURVEY.md §2.1)
* per-op deadline ladder: list 30 s / pull 10 min / delete 2 min / chat 2 min
  (``:229, :251, :289, :328``)
* subjects: the four from README.md:17-21, the conceptual
  ``sync_model_from_bucket`` (README.md:286-318) made real, and a ``health``
  subject (SURVEY.md §5 failure-detection gap).

Streaming: when the chat payload sets ``"stream": true``, tokens are published
to the reply inbox as OpenAI-style chunks and the terminal message carries the
full aggregate completion with a ``Nats-Stream-Done`` header — so naive
single-reply clients (``nats req``) still receive a complete response.
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import json
import logging
import os
import time

from ..config import WorkerConfig
from ..obs import spans as obs_spans
from ..obs import (
    EVENTS,
    PromRenderer,
    Trace,
    Span,
    build_ledger,
    compile_cache_counts,
    efficiency_enabled,
    install_compile_cache_listener,
    new_span_id,
    new_trace_id,
    parse_span_context,
    program_kinds,
    span_context_value,
)
from ..transport.client import Msg, NatsClient, connect
from ..transport.envelope import deadline_remaining_s, envelope_error, envelope_ok
from ..transport.jetstream import ObjectStoreError
from ..transport.protocol import (
    ATTEMPT_HEADER,
    DEADLINE_HEADER,
    EXCLUDED_WORKERS_HEADER,
    KV_PREFILL_HEADER,
    PRIORITY_HEADER,
    STREAM_CANCEL_SUFFIX,
    TENANT_HEADER,
    TRACE_HEADER,
    TRACEPARENT_HEADER,
    WORKER_HEADER,
    parse_worker_list,
)
from .api import EngineError, ModelNotFound, Registry
from .kv_transfer import KVTransferFormatError, decode_kv_blob, encode_kv_blob
from .router import ADVERT_SUBJECT, RecentHeads, prompt_head_hash

log = logging.getLogger(__name__)

# model id accompanying a raw KVX1 blob pushed at a peer's kv_import
# subject (warm prefix-cache handoff, ISSUE 15); the Object Store
# reference form carries the model inside its JSON body instead
KV_MODEL_HEADER = "X-KV-Model"


def _devices() -> list[dict]:
    """The JAX devices of this process, as health and metrics report them."""
    import jax

    return [
        {"id": d.id, "platform": d.platform, "kind": d.device_kind}
        for d in jax.devices()
    ]


def _zip_dir(path: str) -> bytes:
    """Zip a directory tree (relative paths) into an in-memory archive —
    runs in a thread from on_profile; trace dirs are tens of MB at most."""
    import io
    import zipfile

    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
        for root, _dirs, files in os.walk(path):
            for f in sorted(files):
                full = os.path.join(root, f)
                zf.write(full, os.path.relpath(full, path))
    return buf.getvalue()


if hasattr(asyncio, "timeout"):
    _timeout = asyncio.timeout  # Python >= 3.11
else:

    @contextlib.asynccontextmanager
    async def _timeout(delay: float):
        """asyncio.timeout backport for 3.10: arm a timer that cancels the
        current task; the cancellation surfaces as TimeoutError at the
        ``async with`` boundary, exactly like the 3.11 primitive."""
        task = asyncio.current_task()
        assert task is not None
        fired = False

        def _fire() -> None:
            nonlocal fired
            fired = True
            task.cancel()

        handle = asyncio.get_running_loop().call_later(delay, _fire)
        try:
            yield
        except asyncio.CancelledError:
            if fired:
                raise asyncio.TimeoutError from None
            raise
        finally:
            handle.cancel()


class _ObjectStoreSpill:
    """Sync ``SpillStore`` adapter over the worker's JetStream Object Store
    (bucket ``kv-tier``) for serve/kv_tiers.py: the tier manager's spill
    thread calls put/get/delete, each marshalled onto the worker's asyncio
    loop with ``run_coroutine_threadsafe``. Unlike ``kv-transfer`` blobs the
    bucket is NOT single-use — it is the cold KV tier that survives process
    death, which is the whole restart-with-warm-cache story."""

    _PROBE_TIMEOUT_S = 2.0

    def __init__(self, nc, loop, timeout: float = 10.0):
        from ..transport.jetstream import ObjectStore

        self._store = ObjectStore(nc, timeout=timeout)
        self._loop = loop
        self._timeout = timeout
        self._bucket = "kv-tier"
        # availability probe, kicked off NOW but never awaited on the hot
        # path: the broker has no no-responders signalling, so a deployment
        # without the object-store module (bare EmbeddedBroker in tests,
        # core-NATS-only brokers) would otherwise stall the full transfer
        # timeout on every call — 10s added to engine load via
        # warm_exports, 10s per spill attempt. One short STREAM.CREATE,
        # latched both ways: ready, or dead for the process (host tier
        # stays, cold tier off).
        probe_t = min(self._PROBE_TIMEOUT_S, timeout)
        probe = ObjectStore(nc, timeout=probe_t)

        async def _probe_once() -> bool:
            try:
                await probe.ensure_bucket(self._bucket)
                return True
            except Exception as e:  # noqa: BLE001 — any failure = no tier
                log.warning(
                    "kv-tier object store unreachable (%s); cold KV spill "
                    "disabled for this process", type(e).__name__,
                )
                return False

        self._probe_fut = asyncio.run_coroutine_threadsafe(_probe_once(), loop)

    def _run(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self._loop).result(
            self._timeout + 5.0
        )

    def _alive(self, wait: bool) -> bool:
        """Probe verdict. ``wait=False`` (read path: engine-load warm
        restore, promotion fetches) treats an unresolved probe as dead-for-
        now so lookups degrade to instant misses; ``wait=True`` (the tier
        manager's background spill thread) blocks for the verdict."""
        try:
            if wait:
                return bool(self._probe_fut.result(self._PROBE_TIMEOUT_S + 5.0))
            return self._probe_fut.done() and bool(self._probe_fut.result(0))
        except Exception:  # noqa: BLE001 — cancelled/timed out probe = dead
            return False

    def put(self, name: str, data: bytes) -> None:
        if not self._alive(wait=True):
            raise ObjectStoreError("kv-tier object store unavailable")
        self._run(self._store.put(self._bucket, name, data))

    def get(self, name: str) -> bytes | None:
        from ..transport.jetstream import ObjectNotFound

        if not self._alive(wait=False):
            return None  # no (confirmed) cold tier: same as a miss
        try:
            return self._run(self._store.get(self._bucket, name))
        except ObjectNotFound:
            return None  # never spilled, or pruned: a clean miss

    def delete(self, name: str) -> None:
        if not self._alive(wait=False):
            return
        with contextlib.suppress(Exception):
            self._run(self._store.delete(self._bucket, name))


class Worker:
    """One serving process: NATS subscriptions + an in-process model registry."""

    def __init__(self, config: WorkerConfig, registry: Registry):
        self.config = config
        self.registry = registry
        self.worker_id = config.worker_id
        self.nc: NatsClient | None = None
        self._started = asyncio.Event()
        self._stop = asyncio.Event()
        self._requests_total = 0
        self._tokens_total = 0
        self._streams_cancelled = 0  # consumer-gone aborts (<inbox>.cancel)
        self._profiling = False
        self._supervisor_task: asyncio.Task | None = None
        self._t0 = time.monotonic()
        # -- cluster state (serve/router.py) ---------------------------------
        self.draining = False
        self._queue_subs: list = []  # dropped on drain; control subs stay
        self._advert_task: asyncio.Task | None = None
        self._advert_seq = 0
        self._recent_heads = RecentHeads()
        self._excluded_bounce_total = 0  # X-Excluded-Workers self-matches
        self._drain_bounce_total = 0  # requests bounced while draining
        # -- disaggregated prefill/decode (ISSUE 13) -------------------------
        # bytes/ms by direction: "export" is KV shipped to decode peers (we
        # are the prefill side), "import" is KV pulled from a prefill peer
        self._kv_transfer_bytes = {"export": 0, "import": 0}
        self._kv_transfer_ms = {"export": 0.0, "import": 0.0}
        self._kv_transfer_failures = 0  # pulls that fell back to local prefill
        # -- warm prefix-cache handoff (ISSUE 15) ----------------------------
        # hot prefixes pushed to a replacement worker at drain/scale-up, and
        # prefixes received+imported from a draining donor
        self._warm_handoff_sent = 0
        self._warm_handoff_received = 0
        # chat requests slower than this end-to-end land in the event ring
        # for post-hoc diagnosis (0 disables)
        self._slow_request_ms = float(
            os.environ.get("OBS_SLOW_REQUEST_MS", "5000").strip() or 0
        )
        # -- cross-process spans (obs/trace.py + obs/aggregator.py) ----------
        # spans emitted in one event-loop tick coalesce into a single batch
        # publish on {prefix}.obs.spans; OBS_SPANS=0 disables emission
        self._span_buf: list[dict] = []
        self._span_flush_task: asyncio.Task | None = None
        self._spans_emitted_total = 0

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> None:
        cfg = self.config
        # count XLA compile-cache hits/misses from the very first engine
        # load (idempotent; surfaces as lmstudio_compile_cache_*_total)
        install_compile_cache_listener()
        self.nc = await connect(
            cfg.nats_url,
            # worker_id in the CONNECT name: the chaos harness's
            # worker-scoped sever rule (faults.sever_worker) keys on it
            name=f"tpu-worker-{self.worker_id}",
            max_reconnects=cfg.max_reconnects,
            reconnect_wait_s=cfg.reconnect_wait_s,
            reconnect_max_wait_s=cfg.reconnect_max_wait_s,
            ping_interval_s=cfg.ping_interval_s,
        )
        # cold KV tier (serve/kv_tiers.py): hand the registry a spill-store
        # factory over this connection so engine loads can give their tier
        # managers an Object Store behind the host-RAM tier. Late-bound —
        # the registry is constructed before the connection exists; a
        # registry without tiering (or tests' fakes) never passes the gate.
        if (
            getattr(cfg, "kv_spill_objstore", True)
            and getattr(self.registry, "kv_host_pool_bytes", 0) > 0
            and getattr(self.registry, "kv_spill_factory", None) is None
        ):
            loop = asyncio.get_running_loop()
            nc, spill_t = self.nc, cfg.kv_transfer_timeout_s
            self.registry.kv_spill_factory = (
                lambda: _ObjectStoreSpill(nc, loop, timeout=spill_t)
            )
        q = cfg.queue_group
        subs = {
            cfg.subject("list_models"): self.on_list_models,
            cfg.subject("pull_model"): self.on_pull_model,
            cfg.subject("delete_model"): self.on_delete_model,
            cfg.subject("chat_model"): self.on_chat_model,
            cfg.subject("sync_model_from_bucket"): self.on_sync_model_from_bucket,
            cfg.subject("health"): self.on_health,
            cfg.subject("metrics"): self.on_metrics,
            cfg.subject("metrics.prom"): self.on_metrics_prom,
            cfg.subject("events"): self.on_events,
            cfg.subject("profile"): self.on_profile,
        }
        if getattr(cfg, "debug_subjects", False):
            # deep-debug surface (DEBUG_SUBJECTS=1 only): slot tables with
            # block refcounts expose request shapes and debug.dump forces
            # disk writes, so the subjects simply don't exist by default
            subs[cfg.subject("debug.snapshot")] = self.on_debug_snapshot
            subs[cfg.subject("debug.dump")] = self.on_debug_dump
        # flight-recorder frames carry worker-level counters too: register
        # them with the registry so every engine's recorder sees them
        # (FakeRegistry in tests has no recorder_counters — guard)
        counters = getattr(self.registry, "recorder_counters", None)
        if counters is not None:
            counters["reconnects"] = lambda: getattr(self.nc, "reconnects", 0)
            counters["requests_total"] = lambda: self._requests_total
            counters["excluded_bounces"] = lambda: self._excluded_bounce_total
            counters["drain_bounces"] = lambda: self._drain_bounce_total
        for subject, handler in subs.items():
            sub = await self.nc.subscribe(subject, queue=q, cb=self._guarded(handler))
            self._queue_subs.append(sub)
        # directed per-worker subjects (plain subs, NOT the queue group):
        # the router steers at .chat_model; .health/.metrics.prom make one
        # specific worker scrapeable (the queue-group subjects route to a
        # random member). These survive a drain — control plane stays up.
        wid_prefix = f"{cfg.subject_prefix}.worker.{self.worker_id}"
        for op, handler in (
            ("chat_model", self.on_chat_model),
            ("health", self.on_health),
            ("metrics.prom", self.on_metrics_prom),
            # every worker serves kv_export (not just prefill-role ones):
            # an engine that cannot export replies no_export gracefully, so
            # a stale role map degrades to local prefill instead of timeout
            ("kv_export", self.on_kv_export),
            # warm prefix-cache handoff (ISSUE 15): kv_import receives a
            # pushed KVX1 blob (or an Object Store reference) from a
            # draining donor; kv_handoff tells THIS worker to push its
            # hottest prefixes to a named recipient (autoscaler control)
            ("kv_import", self.on_kv_import),
            ("kv_handoff", self.on_kv_handoff),
        ):
            await self.nc.subscribe(f"{wid_prefix}.{op}", cb=self._guarded(handler))
        # drain control: broadcast subject, each worker matches on payload
        await self.nc.subscribe(
            cfg.subject("admin.drain"), cb=self._guarded(self.on_admin_drain)
        )
        await self.nc.flush()
        if cfg.supervise_interval_s > 0:
            self._supervisor_task = asyncio.ensure_future(self._supervise())
        if getattr(cfg, "cluster_advert_interval_s", 0) > 0:
            self._advert_task = asyncio.ensure_future(self._advert_loop())
        self._started.set()
        log.info(
            "worker %s serving %s.* (queue=%s)",
            self.worker_id, cfg.subject_prefix, q,
        )

    async def run(self) -> None:
        await self.start()
        await self._stop.wait()
        await self.drain()

    def request_stop(self) -> None:
        self._stop.set()

    async def drain(self) -> None:
        if self._supervisor_task is not None:
            self._supervisor_task.cancel()
            self._supervisor_task = None
        if self._advert_task is not None:
            self._advert_task.cancel()
            self._advert_task = None
        if self.nc is not None:
            await self.nc.drain()

    # -- cluster adverts + graceful drain (ISSUE 10 tentpole) ----------------

    def build_advert(self) -> dict:
        """The compact membership advert ``{prefix}.cluster.adverts`` carries:
        identity, load (queue depth summed over engines, worst brownout
        level, HBM headroom), capacity (``slots`` summed over engines — a
        dp>1 worker really advertises dp x per-replica slots), the named
        mesh shape (routers prefer sp-capable workers for long prompts),
        loaded models, draining flag, and the head hashes of recently
        served prompts (router prefix-locality)."""
        depth = 0
        brownout = 0
        slots = 0
        tier_depth = 0
        for eng in self.registry.loaded_engines().values():
            b = getattr(eng, "batcher", None)
            if b is None:
                continue
            depth += int(getattr(b, "queue_depth", 0) or 0)
            slots += int(getattr(b, "max_slots", 0) or 0)
            brownout = max(brownout, int(getattr(b, "brownout_level", 0) or 0))
            # warm-KV depth (router tiebreak): host-tier entries held by
            # this worker's engines — a deeper tier serves repeat prefixes
            # without recompute, so equal-load routing prefers it
            tier_fn = getattr(b, "tier_stats", None)
            if tier_fn is not None:
                try:
                    ts = tier_fn()
                except Exception:  # noqa: BLE001 — adverts never crash
                    ts = None
                if ts:
                    tier_depth += int(ts.get("host_entries", 0) or 0)
        headroom_fn = getattr(self.registry, "_hbm_headroom_frac", None)
        try:
            headroom = float(headroom_fn()) if headroom_fn is not None else 1.0
        except Exception:  # noqa: BLE001 — an advert must never crash the loop
            headroom = 1.0
        mesh = getattr(self.registry, "mesh", None)
        return {
            "worker_id": self.worker_id,
            "role": getattr(self.config, "worker_role", ""),
            "queue_depth": depth,
            "slots": slots,
            "brownout": brownout,
            "hbm_headroom": round(headroom, 4),
            "mesh": dict(mesh.shape) if mesh is not None else {},
            "models": sorted(self.registry.loaded_engines()),
            "kv_tier_depth": tier_depth,
            "draining": self.draining,
            "heads": self._recent_heads.snapshot(),
            "seq": self._advert_seq,
        }

    async def _publish_advert(self) -> None:
        if self.nc is None:
            return
        self._advert_seq += 1
        try:
            await self.nc.publish(
                self.config.subject(ADVERT_SUBJECT),
                json.dumps(self.build_advert(), separators=(",", ":")).encode(),
            )
        except (ConnectionError, ValueError):
            pass  # reconnect in flight; the next tick re-advertises

    async def _advert_loop(self) -> None:
        try:
            while True:
                await self._publish_advert()
                await asyncio.sleep(self.config.cluster_advert_interval_s)
        except asyncio.CancelledError:
            return

    async def on_admin_drain(self, msg: Msg) -> None:
        """admin.drain {worker_id, deadline_s?} — puts THE NAMED worker (or
        every worker, with ``"*"``) into draining mode. Broadcast subject:
        all workers hear it, only addressees act and reply."""
        try:
            req = json.loads(msg.payload or b"{}")
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in Drain: {e}")
            return
        target = (req.get("worker_id") or "").strip()
        if not target:
            await self._respond_error(
                msg, "'worker_id' is required ('*' drains every worker)"
            )
            return
        if target not in ("*", self.worker_id):
            return  # addressed to a peer; its reply is the reply
        try:
            deadline_s = float(req.get("deadline_s", self.config.drain_deadline_s))
        except (TypeError, ValueError):
            await self._respond_error(msg, "'deadline_s' must be a number")
            return
        handoff_to = (req.get("handoff_to") or "").strip() or None
        result = await self.begin_drain(deadline_s, handoff_to=handoff_to)
        await self._respond_ok(msg, result)

    async def begin_drain(
        self, deadline_s: float | None = None, handoff_to: str | None = None
    ) -> dict:
        """Graceful handoff: stop accepting new queue-group work (drop the
        queue subs — the broker routes around us immediately), advertise the
        draining flag, let in-flight decode finish up to the drain deadline,
        then stop the batchers — which fail the remainder with the existing
        retryable "worker draining, retry on another worker" envelope so the
        client RetryPolicy lands them on a peer. Directed/control subjects
        stay up: a draining worker still answers health and bounces chat.

        With ``handoff_to`` (ISSUE 15), the hottest prefix-cache block sets
        are pushed to the named replacement worker after in-flight work
        settles and before the batchers stop — so the replacement starts
        with a hit rate instead of a cold cache."""
        if deadline_s is None:
            deadline_s = self.config.drain_deadline_s
        if self.draining:
            return {"worker_id": self.worker_id, "draining": True,
                    "already_draining": True}
        self.draining = True
        # suppress the registry's engine-restart path for the whole
        # teardown: a supervisor restart already sleeping out its backoff
        # must not resurrect an engine we are about to stop
        set_drain = getattr(self.registry, "set_draining", None)
        if set_drain is not None:
            set_drain(True)
        EVENTS.emit("worker_drain", worker_id=self.worker_id,
                    deadline_s=deadline_s, handoff_to=handoff_to or "")
        log.info("worker %s draining (deadline %.1fs)", self.worker_id, deadline_s)
        for sub in self._queue_subs:
            await sub.unsubscribe()
        self._queue_subs.clear()
        await self._publish_advert()  # peers + routers see draining NOW
        deadline = time.monotonic() + max(0.0, deadline_s)
        finished_in_time = True
        while True:
            busy = [
                mid for mid, eng in self.registry.loaded_engines().items()
                if getattr(getattr(eng, "batcher", None), "alive", False)
                and not getattr(eng.batcher, "idle", True)
            ]
            if not busy:
                break
            if time.monotonic() >= deadline:
                finished_in_time = False
                log.warning(
                    "worker %s drain deadline: %s still busy; failing the "
                    "remainder retryably", self.worker_id, busy,
                )
                break
            await asyncio.sleep(0.05)
        # zero-lost-work preemption: fold every still-running slot's full
        # token history (prompt + generated so far) into its prefix cache
        # BEFORE the handoff export below, so in-progress work ships to the
        # survivor too and the client's retry resumes as a prefix hit
        # instead of re-prefilling (and re-decoding) from scratch. No-op on
        # idle engines; best-effort — a failure falls back to the plain
        # retryable-drain envelope the stop() below produces anyway.
        harvested = {"slots": 0, "tokens": 0}
        for mid, eng in list(self.registry.loaded_engines().items()):
            b = getattr(eng, "batcher", None)
            harvest = getattr(b, "suspend_harvest_to_cache", None)
            if harvest is None or not getattr(b, "alive", False):
                continue
            try:
                got = await asyncio.to_thread(harvest)
                harvested["slots"] += int(got.get("slots", 0))
                harvested["tokens"] += int(got.get("tokens", 0))
            except Exception:  # noqa: BLE001
                log.warning("suspend-harvest failed for %s", mid, exc_info=True)
        handoff: dict | None = None
        if handoff_to and handoff_to != self.worker_id:
            # after the busy-wait, before the batcher stops: the cache
            # blocks must still be alive to export. Best-effort — a failed
            # handoff degrades the replacement to a cold cache, never
            # blocks the drain.
            handoff = await self.push_warm_handoff(handoff_to)
        stopped = []
        for mid, eng in list(self.registry.loaded_engines().items()):
            b = getattr(eng, "batcher", None)
            if b is not None and getattr(b, "alive", False) and hasattr(b, "stop"):
                # stop() drains in-flight slots with the retryable draining
                # envelope (registry's shutdown finish path); it blocks on
                # the owner thread, so keep the event loop breathing
                await asyncio.to_thread(b.stop)
                stopped.append(mid)
        await self._publish_advert()
        result = {
            "worker_id": self.worker_id,
            "draining": True,
            "finished_in_time": finished_in_time,
            "stopped_engines": stopped,
            "deadline_s": deadline_s,
        }
        if harvested["slots"]:
            result["harvested"] = harvested
        if handoff is not None:
            result["handoff"] = handoff
        return result

    async def _supervise(self) -> None:
        """Engine watchdog: every ``supervise_interval_s`` check each loaded
        batcher's owner thread — crashed (uncaught pump exception; its
        in-flight slots were already failed retryable) or hung (heartbeat
        stale while NOT idle; an idle owner blocks on its inbox and
        legitimately stops stamping) — and hand unhealthy engines to the
        registry's restart path (capped backoff; repeated crashes within the
        window poison the model). The watchdog itself must never die: every
        per-engine action is individually guarded."""
        cfg = self.config
        hb_timeout = cfg.engine_heartbeat_timeout_s
        restart = getattr(self.registry, "restart_engine", None)
        try:
            while True:
                await asyncio.sleep(cfg.supervise_interval_s)
                if self.draining:
                    continue  # drain stops batchers on purpose; no restarts
                for mid, eng in list(self.registry.loaded_engines().items()):
                    b = getattr(eng, "batcher", None)
                    if b is None or not hasattr(b, "alive"):
                        continue  # fake/test engines have no pump loop
                    try:
                        dead = not b.alive
                        hung = (
                            not dead
                            and hb_timeout > 0
                            and not b.idle
                            and b.heartbeat_age_s() > hb_timeout
                        )
                        if not dead and not hung:
                            continue
                        why = "crashed" if dead else (
                            f"hung (heartbeat {b.heartbeat_age_s():.1f}s stale)"
                        )
                        log.warning("supervisor: engine %s %s", mid, why)
                        EVENTS.emit("engine_supervisor", model=mid, state=why)
                        if restart is not None:
                            outcome = await restart(mid, reason=why)
                            log.info("supervisor: engine %s -> %s", mid, outcome)
                    except Exception:  # noqa: BLE001 — watchdog must survive
                        log.exception("supervisor action for %s failed", mid)
        except asyncio.CancelledError:
            return

    def _guarded(self, handler):
        """Last-resort catch-all: the Go reference replies with an error
        envelope on every failure path; an exception escaping a handler must
        not leave the requester waiting out its timeout."""

        async def run(msg: Msg) -> None:
            try:
                await handler(msg)
            except Exception as e:  # noqa: BLE001 — deliberate catch-all seam
                log.exception("handler for %s failed", msg.subject)
                await self._respond_error(msg, f"internal error: {e}")

        return run

    # -- envelope helpers ----------------------------------------------------

    async def _respond_json(self, msg: Msg, payload: bytes, headers=None) -> None:
        # every reply names its worker (X-Worker-Id): the client retry loop
        # reads it to exclude a shedding worker from the next hop, and the
        # router uses it to attribute replies in a multi-worker scrape
        headers = dict(headers) if headers else {}
        headers.setdefault(WORKER_HEADER, self.worker_id)
        try:
            await msg.respond(payload, headers=headers)
        except (ConnectionError, ValueError):
            log.warning("failed to respond on %s", msg.subject)

    async def _respond_ok(self, msg: Msg, data=None) -> None:
        await self._respond_json(msg, envelope_ok(data))

    async def _respond_error(
        self, msg: Msg, error: str, data=None, headers=None, trace_id=None
    ) -> None:
        await self._respond_json(msg, envelope_error(error, data, trace_id=trace_id), headers=headers)

    # -- handlers ------------------------------------------------------------

    async def on_list_models(self, msg: Msg) -> None:
        """list_models → wraps the registry listing as ``data.models`` +
        ``data.http_status`` (nats_llm_studio.go:240-247 shape, status fixed
        at 200 since no HTTP hop exists any more)."""
        self._requests_total += 1
        try:
            async with _timeout(self.config.list_timeout_s):
                models = await self.registry.list_models()
        except asyncio.TimeoutError:
            await self._respond_error(msg, "timeout listing models")
            return
        except EngineError as e:
            await self._respond_error(msg, f"error listing models: {e}")
            return
        await self._respond_ok(msg, {"models": models, "http_status": 200})

    async def on_pull_model(self, msg: Msg) -> None:
        """pull_model {identifier} — nats_llm_studio.go:250-286. On failure the
        data still carries {model, output} (:266-275)."""
        self._requests_total += 1
        try:
            req = json.loads(msg.payload or b"{}")
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in PullModel: {e}")
            return
        identifier = (req.get("identifier") or "").strip()
        if not identifier:
            await self._respond_error(msg, "'identifier' is required")
            return
        try:
            async with _timeout(self.config.pull_timeout_s):
                output = await self.registry.pull(identifier)
        except asyncio.TimeoutError:
            await self._respond_error(
                msg, "error pulling model: deadline exceeded", {"model": identifier}
            )
            return
        except EngineError as e:
            await self._respond_error(
                msg, f"error pulling model: {e}", {"model": identifier, "output": str(e)}
            )
            return
        await self._respond_ok(msg, {"model": identifier, "output": output})

    async def on_delete_model(self, msg: Msg) -> None:
        """delete_model {model_id} — nats_llm_studio.go:288-324. Error
        responses include the attempted dir (:304-313); success returns
        ``deleted_dir`` (:316-323)."""
        self._requests_total += 1
        try:
            req = json.loads(msg.payload or b"{}")
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in DeleteModel: {e}")
            return
        model_id = (req.get("model_id") or "").strip()
        if not model_id:
            await self._respond_error(msg, "'model_id' is required")
            return
        try:
            async with _timeout(self.config.delete_timeout_s):
                deleted_dir = await self.registry.delete(model_id)
        except asyncio.TimeoutError:
            await self._respond_error(msg, "error deleting model: deadline exceeded", {"model": model_id})
            return
        except EngineError as e:
            data = {"model": model_id}
            attempted = getattr(e, "dir", None)
            if attempted:
                data["dir"] = str(attempted)
            await self._respond_error(msg, f"error deleting model: {e}", data)
            return
        await self._respond_ok(msg, {"model": model_id, "deleted_dir": deleted_dir})

    async def on_chat_model(self, msg: Msg) -> None:
        """chat_model — nats_llm_studio.go:327-364. Payload is the OpenAI-style
        body passed through to the engine verbatim (:348); success wraps
        {http_status, response} (:356-362).

        Trace: the client's ``X-Trace-Id`` header (minted one if absent)
        becomes a per-request span record. The batcher stamps its stage
        transitions through ``payload["_trace"]``; the final envelope carries
        ``trace_id`` and the response ``stats.trace`` holds the waterfall —
        no extra round-trip."""
        self._requests_total += 1
        hdrs = msg.headers or {}
        try:
            attempt = int(hdrs[ATTEMPT_HEADER]) if ATTEMPT_HEADER in hdrs else None
        except (TypeError, ValueError):
            attempt = None
        # upstream span context (gateway/router Traceparent header): the
        # serve span this handler emits becomes that hop's child, so the
        # assembled cluster tree stays causally linked across retries
        parent = parse_span_context(hdrs.get(TRACEPARENT_HEADER))
        trace = Trace(hdrs.get(TRACE_HEADER) or new_trace_id(), attempt=attempt,
                      parent_span_id=parent[1] if parent else "")
        trace.mark("recv")
        if self.worker_id in parse_worker_list(hdrs.get(EXCLUDED_WORKERS_HEADER)):
            # a queue-group redelivery landed the retry back on the worker
            # that just shed/failed it: bounce retryably so the next hop
            # (with us in the header) reaches a peer
            self._excluded_bounce_total += 1
            await self._respond_error(
                msg,
                "worker excluded by this request's retry history, "
                "retry on another worker",
                # excluded_bounce marks this as a one-shot deflection: the
                # client drops us from the exclusion list after it, so a
                # single-worker group (or one whose every member already
                # shed once) can still serve the next attempt
                {"worker_id": self.worker_id, "excluded_bounce": True},
                trace_id=trace.trace_id,
            )
            # the bounce is a real hop of the retry story: without its span
            # the assembled tree shows a hole where the redelivery landed
            self._emit_span(trace.to_span("worker.serve", self.worker_id,
                                          attrs={"outcome": "excluded_bounce"}))
            return
        if self.draining:
            self._drain_bounce_total += 1
            await self._respond_error(
                msg,
                "worker draining, retry on another worker",
                {"worker_id": self.worker_id},
                trace_id=trace.trace_id,
            )
            self._emit_span(trace.to_span("worker.serve", self.worker_id,
                                          attrs={"outcome": "drain_bounce"}))
            return
        if not msg.payload:
            await self._respond_error(msg, "empty payload in ChatModel", trace_id=trace.trace_id)
            return
        try:
            payload = json.loads(msg.payload)
            if not isinstance(payload, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(
                msg, f"invalid JSON in ChatModel: {e}", trace_id=trace.trace_id
            )
            return
        model_id = (payload.get("model") or "").strip()
        if not model_id:
            await self._respond_error(
                msg, "'model' is required in ChatModel", trace_id=trace.trace_id
            )
            return
        if payload.get("stream") and not msg.reply:
            return  # fire-and-forget stream request: nowhere to send tokens
        streaming = bool(payload.get("stream"))
        if self.config.router_prefix_head_chars > 0:
            # remember this prompt's head: the advert's ``heads`` set is the
            # router's prefix-cache locality signal (same hash both sides)
            self._recent_heads.add(prompt_head_hash(
                model_id, payload.get("messages"),
                self.config.router_prefix_head_chars,
            ))
        payload["_trace"] = trace  # engines pop it; fakes ignore it
        # tenant identity + priority class from the gateway-stamped bus
        # headers (transport/protocol.py): engines pop them and thread them
        # into the batcher's fair-share admission. Raw-NATS callers that
        # never heard of tenancy set neither — the registry defaults them
        # to the anonymous tenant at standard priority, so pre-QoS clients
        # and tests see unchanged behavior.
        if hdrs.get(TENANT_HEADER):
            payload["_tenant"] = str(hdrs[TENANT_HEADER])
        if hdrs.get(PRIORITY_HEADER):
            payload["_priority"] = str(hdrs[PRIORITY_HEADER])
        try:
            # a chat that finds its model cached-not-loaded loads it first,
            # under the PULL deadline of the ladder: getting a model ready
            # is a pull-class operation (an 8B int8 load is minutes of host
            # requantization), and the chat deadline starts once the
            # engine exists
            async with _timeout(self.config.pull_timeout_s):
                engine = await self.registry.get_engine(model_id)
            if self.config.deadline_propagation:
                # client budget (X-Deadline-Ms, wall ms) → monotonic
                # deadline capped by the per-op ladder; the batcher sheds
                # expired work at submit/admit and aborts mid-decode slots
                # past it. An already-expired budget still flows through:
                # the shed there is a retryable envelope, not a silent drop.
                remaining = deadline_remaining_s(hdrs.get(DEADLINE_HEADER))
                if remaining is not None:
                    payload["_deadline"] = time.monotonic() + min(
                        remaining, self.config.chat_timeout_s
                    )
            async with _timeout(self.config.chat_timeout_s):
                prefill_peer = (hdrs.get(KV_PREFILL_HEADER) or "").strip()
                if prefill_peer and prefill_peer != self.worker_id:
                    # disaggregated two-hop: the router already ran (or is
                    # running) this prompt's prefill on the named peer; pull
                    # its KV blocks into our pool before serving so decode
                    # starts from a full prefix-cache hit. Never fatal — any
                    # failure inside counts itself and we prefill locally.
                    await self._kv_prefetch(engine, model_id, payload,
                                            prefill_peer, trace)
                if streaming:
                    await self._chat_streaming(msg, engine, payload, trace)
                else:
                    response = await engine.chat(payload)
                    usage = response.get("usage") or {}
                    self._tokens_total += usage.get("completion_tokens", 0)
                    trace.mark("publish")
                    self._finish_trace(trace, model_id, response)
                    await self._respond_json(
                        msg,
                        envelope_ok(
                            {"http_status": 200, "response": response},
                            trace_id=trace.trace_id,
                        ),
                    )
        except asyncio.TimeoutError:
            await self._error_terminal(
                msg, "error in chat: deadline exceeded", {"model": model_id}, streaming, trace
            )
        except ModelNotFound as e:
            await self._error_terminal(
                msg, f"model not found: {e}", {"model": model_id}, streaming, trace
            )
        except EngineError as e:
            await self._error_terminal(
                msg, f"error in chat: {e}", {"model": model_id}, streaming, trace
            )
        except Exception as e:  # noqa: BLE001 — mid-stream crash must still terminate the stream
            log.exception("chat handler failed for %s", model_id)
            await self._error_terminal(
                msg, f"internal error: {e}", {"model": model_id}, streaming, trace
            )

    def _finish_trace(self, trace: Trace, model_id: str, response) -> None:
        """Inject the span waterfall into the response stats block and emit
        a slow-request event when the end-to-end time crosses the threshold."""
        report = trace.report()
        if isinstance(response, dict):
            response.setdefault("stats", {})["trace"] = report
        total_ms = report["spans_ms"].get("total_ms", 0.0)
        self._emit_span(trace.to_span(
            "worker.serve", self.worker_id,
            attrs={"model": model_id, "outcome": "ok",
                   "role": getattr(self.config, "worker_role", "") or "monolithic"},
        ))
        if self._slow_request_ms and total_ms > self._slow_request_ms:
            EVENTS.emit(
                "slow_request",
                model=model_id,
                trace_id=trace.trace_id,
                total_ms=total_ms,
                spans_ms=report["spans_ms"],
            )
            # attach the offending request's waterfall to a flight dump so
            # the pre-slowness frames (queue depth, brownout, pool state)
            # land next to the trace that suffered them
            eng = self.registry.loaded_engines().get(model_id)
            recorder = getattr(getattr(eng, "batcher", None), "recorder", None)
            if recorder is not None:
                recorder.dump(
                    "slow_request",
                    trace=report,
                    extra={"model": model_id, "total_ms": round(total_ms, 1)},
                )

    async def _error_terminal(
        self, msg: Msg, error: str, data, streaming: bool, trace: Trace | None = None
    ) -> None:
        """Error reply that, mid-stream, still carries the terminal
        ``Nats-Stream-Done`` header so ``request_stream`` consumers end
        cleanly instead of waiting out their idle timeout."""
        headers = {"Nats-Stream-Done": "1"} if streaming else None
        await self._respond_error(
            msg, error, data, headers=headers,
            trace_id=trace.trace_id if trace is not None else None,
        )
        if trace is not None:
            self._emit_span(trace.to_span(
                "worker.serve", self.worker_id,
                attrs={"outcome": "error", "error": error[:160]},
            ))

    # -- cross-process span emission (obs/aggregator.py consumes) ------------

    def _emit_span(self, span: dict) -> None:
        """Buffer one span for fire-and-forget batch publish on
        ``{prefix}.obs.spans``. Spans emitted in the same event-loop tick
        (serve + kv_pull of one request) coalesce into one message; span
        loss on a dropped connection is acceptable by design — spans are
        diagnostics, never load-bearing."""
        if self.nc is None or not getattr(self.config, "obs_spans", True):
            return
        self._span_buf.append(span)
        self._spans_emitted_total += 1
        if self._span_flush_task is None or self._span_flush_task.done():
            self._span_flush_task = asyncio.ensure_future(self._flush_spans())

    async def _flush_spans(self) -> None:
        await asyncio.sleep(0)  # let same-tick spans join this batch
        batch, self._span_buf = self._span_buf, []
        if not batch or self.nc is None:
            return
        try:
            await self.nc.publish(
                self.config.subject("obs.spans"),
                json.dumps({"spans": batch}, separators=(",", ":")).encode(),
            )
        except (ConnectionError, ValueError):
            pass  # reconnect in flight; these spans are lost, the next batch isn't

    async def _chat_streaming(self, msg: Msg, engine, payload: dict, trace: Trace) -> None:
        assert self.nc is not None
        if not msg.reply:
            return
        final: dict | None = None
        seq = 0
        sent_tokens = 0  # of trace.emitted, already published
        model_id = payload.get("model", "")
        # consumer-gone watcher: request_stream publishes an empty message
        # to <inbox>.cancel when its consumer abandons the stream before the
        # terminal Nats-Stream-Done. Racing each chunk pull against that
        # signal lets this worker close the engine stream (freeing the
        # batcher slot) within one chunk instead of decoding to max_tokens
        # for nobody.
        cancel_sub = None
        cancel_task: asyncio.Task | None = None
        try:
            cancel_sub = await self.nc.subscribe(msg.reply + STREAM_CANCEL_SUFFIX)
            cancel_task = asyncio.ensure_future(cancel_sub.next_msg(timeout=None))
        except Exception:  # noqa: BLE001 — watcher is best-effort
            cancel_sub = None
            cancel_task = None
        gen = engine.chat_stream(payload)
        cancelled = False
        try:
            while True:
                step = asyncio.ensure_future(gen.__anext__())
                if cancel_task is not None:
                    await asyncio.wait(
                        {step, cancel_task},
                        return_when=asyncio.FIRST_COMPLETED,
                    )
                    if cancel_task.done() and not step.done():
                        step.cancel()
                        with contextlib.suppress(
                            BaseException
                        ):
                            await step
                        cancelled = True
                        break
                try:
                    chunk = await step
                except StopAsyncIteration:
                    break
                if chunk.get("object") == "chat.completion":
                    final = chunk  # engines yield the aggregate last
                    continue
                with obs_spans.span("worker.publish") as sp:
                    await self.nc.publish(
                        msg.reply,
                        json.dumps({"ok": True, "data": {"chunk": chunk}}, separators=(",", ":")).encode(),
                        headers={"X-Seq": str(seq)},
                    )
                    emitted = trace.emitted
                    if emitted is not None:
                        # owner thread's emit of the chunk's last token ->
                        # publish returned, across the two threads
                        sp.attrs["tokens"] = emitted[1] - sent_tokens
                        sp.attrs["lag_ms"] = (time.perf_counter() - emitted[0]) * 1e3
                        sent_tokens = emitted[1]
                seq += 1
        finally:
            if cancel_task is not None:
                cancel_task.cancel()
                with contextlib.suppress(BaseException):
                    await cancel_task
            if cancel_sub is not None:
                with contextlib.suppress(Exception):
                    await cancel_sub.unsubscribe()
            if cancelled:
                # aclose() raises GeneratorExit inside chat_stream at its
                # yield point; submit_batched's finally cancels the batcher
                # request, freeing the slot
                with contextlib.suppress(BaseException):
                    await gen.aclose()
        if cancelled:
            self._streams_cancelled += 1
            trace.mark("publish")
            self._emit_span(trace.to_span(
                "worker.serve", self.worker_id,
                attrs={"model": model_id, "outcome": "cancelled"},
            ))
            return
        if final is None:
            # An engine whose stream ends without the terminal chat.completion
            # aggregate is broken: regenerating via engine.chat() here would
            # silently double the cost AND could return a different completion
            # than the chunks already streamed. Fail loudly instead; the
            # caller's handler turns this into a terminal error envelope.
            raise EngineError(
                "engine stream ended without a chat.completion aggregate"
            )
        usage = final.get("usage") or {}
        self._tokens_total += usage.get("completion_tokens", 0)
        trace.mark("publish")
        self._finish_trace(trace, model_id, final)
        await self.nc.publish(
            msg.reply,
            envelope_ok({"http_status": 200, "response": final}, trace_id=trace.trace_id),
            headers={"Nats-Stream-Done": "1", "X-Seq": str(seq),
                     WORKER_HEADER: self.worker_id},
        )

    # -- disaggregated prefill/decode (ISSUE 13 tentpole) --------------------

    async def on_kv_export(self, msg: Msg) -> None:
        """kv_export — directed-only subject ``{prefix}.worker.<id>.kv_export``:
        a decode-role peer sends the chat body ``{model, messages}``; this
        (prefill-role) worker runs/looks-up the prompt's chunked prefill,
        gathers the finished KV blocks to host memory, and streams the
        serialized blob back as raw binary chunk messages followed by a
        terminal ``Nats-Stream-Done`` JSON envelope ``{sha256, bytes,
        chunks}``. Over ``kv_transfer_objstore_bytes`` the blob ships via
        the JetStream Object Store instead and the terminal envelope carries
        ``{bucket, object, sha256, bytes}``.

        An engine that cannot export (fake/test engine, prompt shorter than
        one prefill chunk, dense-only batcher) answers ``{no_export: true}``
        — a graceful skip the peer treats as "prefill locally", never an
        error."""
        self._requests_total += 1
        if not msg.reply:
            return  # nowhere to ship the blob
        t0 = time.monotonic()
        # span context from the pulling decode worker: the kv_export span
        # emitted here is the child of its kv_pull span, which is what makes
        # the two-hop visible in the assembled cluster tree instead of
        # vanishing from the requesting worker's waterfall
        hdrs = msg.headers or {}
        span_parent = parse_span_context(hdrs.get(TRACEPARENT_HEADER))
        span_trace_id = hdrs.get(TRACE_HEADER) or (
            span_parent[0] if span_parent else ""
        )
        span_t0 = time.time()
        span_attrs: dict = {"outcome": "error"}
        try:
            try:
                payload = json.loads(msg.payload or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("payload must be a JSON object")
            except ValueError as e:
                span_attrs["outcome"] = "bad_request"
                await self._error_terminal(
                    msg, f"invalid JSON in KvExport: {e}", None, True
                )
                return
            model_id = (payload.get("model") or "").strip()
            if not model_id:
                span_attrs["outcome"] = "bad_request"
                await self._error_terminal(
                    msg, "'model' is required in KvExport", None, True
                )
                return
            span_attrs["model"] = model_id
            try:
                async with _timeout(self.config.kv_transfer_timeout_s):
                    engine = await self.registry.get_engine(model_id)
                    export_fn = getattr(engine, "export_prefix", None)
                    export = (
                        await export_fn(dict(payload)) if export_fn is not None else None
                    )
            except asyncio.TimeoutError:
                span_attrs["outcome"] = "timeout"
                await self._error_terminal(
                    msg, "error in kv export: deadline exceeded",
                    {"model": model_id}, True,
                )
                return
            except (ModelNotFound, EngineError, ValueError, RuntimeError) as e:
                # ValueError/RuntimeError: the export's internal prefill can hit
                # the same admission guards as a chat (prompt >= max_seq, pool
                # exhaustion). A terminal error lets the puller fall back to
                # local prefill immediately instead of idling out its pull.
                span_attrs["error"] = str(e)[:160]
                await self._error_terminal(
                    msg, f"error in kv export: {e}", {"model": model_id}, True
                )
                return
            if export is None or not export.get("chunks"):
                span_attrs["outcome"] = "no_export"
                await self._respond_json(
                    msg, envelope_ok({"no_export": True}),
                    headers={"Nats-Stream-Done": "1"},
                )
                return
            try:
                blob = encode_kv_blob(export)
            except KVTransferFormatError as e:
                span_attrs["error"] = str(e)[:160]
                await self._error_terminal(
                    msg, f"error in kv export: {e}", {"model": model_id}, True
                )
                return
            digest = hashlib.sha256(blob).hexdigest()
            meta = {"sha256": digest, "bytes": len(blob),
                    "tokens": len(export["token_ids"])}
            sent = await self._ship_blob(msg, blob, meta)
            if sent:
                span_attrs.update(outcome="ok", bytes=len(blob),
                                  tokens=meta["tokens"])
                self._kv_transfer_bytes["export"] += len(blob)
                self._kv_transfer_ms["export"] += (time.monotonic() - t0) * 1000.0
                EVENTS.emit("kv_export", model=model_id, bytes=len(blob),
                            tokens=meta["tokens"], trace_id=span_trace_id or None)
        finally:
            if span_trace_id:
                self._emit_span(Span(
                    trace_id=span_trace_id,
                    span_id=new_span_id(),
                    stage="worker.kv_export",
                    worker_id=self.worker_id,
                    parent_span_id=span_parent[1] if span_parent else "",
                    t0=span_t0,
                    t1=time.time(),
                    attrs=span_attrs,
                ).to_dict())

    async def _ship_blob(self, msg: Msg, blob: bytes, meta: dict) -> bool:
        """Ship an encoded KV blob to ``msg.reply``: Object Store when the
        blob crosses the configured threshold (and JetStream answers),
        otherwise chunked inline publishes. Returns False only when even the
        inline path failed (connection gone)."""
        assert self.nc is not None
        cfg = self.config
        objstore_min = int(getattr(cfg, "kv_transfer_objstore_bytes", 0) or 0)
        if objstore_min > 0 and len(blob) >= objstore_min:
            from ..transport.jetstream import ObjectStore

            bucket = "kv-transfer"
            obj = f"{self.worker_id}-{meta['sha256'][:16]}"
            try:
                store = ObjectStore(self.nc, timeout=cfg.kv_transfer_timeout_s)
                await store.ensure_bucket(bucket)
                await store.put(bucket, obj, blob)
                await self._respond_json(
                    msg,
                    envelope_ok({**meta, "bucket": bucket, "object": obj}),
                    headers={"Nats-Stream-Done": "1"},
                )
                return True
            except Exception as e:  # noqa: BLE001 — objstore is an optimization
                # no JetStream on this broker (or a mid-put hiccup): the
                # inline chunk path below is the degradation, not a failure
                log.warning("kv export object-store path failed (%s); "
                            "falling back to inline chunks", e)
        chunk_bytes = max(1, int(getattr(cfg, "kv_transfer_chunk_bytes", 256 << 10)))
        limit = (getattr(self.nc, "server_info", None) or {}).get("max_payload")
        if limit:
            # leave headroom for the header block within the broker frame
            chunk_bytes = min(chunk_bytes, max(1, int(limit) - 1024))
        try:
            seq = 0
            for off in range(0, len(blob), chunk_bytes):
                await self.nc.publish(
                    msg.reply, blob[off : off + chunk_bytes],
                    headers={"X-KV-Seq": str(seq)},
                )
                seq += 1
            await self._respond_json(
                msg, envelope_ok({**meta, "chunks": seq}),
                headers={"Nats-Stream-Done": "1"},
            )
            return True
        except (ConnectionError, ValueError):
            log.warning("kv export to %s failed mid-ship", msg.reply)
            return False

    async def _kv_prefetch(
        self, engine, model_id: str, payload: dict, peer: str, trace: Trace
    ) -> None:
        """Decode-side pull: fetch the prompt's exported KV blocks from the
        prefill peer's directed ``kv_export`` subject, verify the SHA-256,
        and import them into the local engine's block pool + prefix cache so
        the chat below decodes from a full prefix hit (zero local prefill).

        EVERY failure mode — peer gone, transfer timeout, digest mismatch,
        malformed blob, decode-pool exhaustion on import — lands in
        ``lmstudio_kv_transfer_failures_total`` and returns normally: the
        caller serves with local prefill, bit-identical, just slower."""
        import_fn = getattr(engine, "import_prefix", None)
        if import_fn is None:
            return  # engine can't import (fake/test engine): local prefill
        assert self.nc is not None
        cfg = self.config
        t0 = time.monotonic()
        trace.mark("kv_pull")
        # the pull is its own span (child of this worker's serve span); its
        # id travels to the prefill peer in the Traceparent header so the
        # peer's kv_export span links under it in the assembled tree
        pull_span_id = new_span_id()
        pull_t0 = time.time()
        req = {"model": model_id, "messages": payload.get("messages")}
        subject = f"{cfg.subject_prefix}.worker.{peer}.kv_export"
        try:
            parts: list[bytes] = []
            meta: dict | None = None
            stream = self.nc.request_stream(
                subject,
                json.dumps(req, separators=(",", ":")).encode(),
                timeout=cfg.kv_transfer_timeout_s,
                idle_timeout=cfg.kv_transfer_timeout_s,
                headers={
                    TRACE_HEADER: trace.trace_id,
                    TRACEPARENT_HEADER: span_context_value(
                        trace.trace_id, pull_span_id
                    ),
                },
            )
            async for m in stream:
                if m.headers and "Nats-Stream-Done" in m.headers:
                    env = json.loads(m.payload)
                    if not env.get("ok"):
                        raise ConnectionError(
                            f"kv export failed on {peer}: {env.get('error')}"
                        )
                    meta = env.get("data") or {}
                else:
                    parts.append(m.payload)
            if meta is None:
                raise ConnectionError(f"kv export stream from {peer} ended early")
            if meta.get("no_export"):
                # graceful skip (peer can't export this prompt) — NOT a
                # transfer failure; just prefill locally
                trace.mark("kv_import")
                self._emit_span(Span(
                    trace_id=trace.trace_id, span_id=pull_span_id,
                    stage="worker.kv_pull", worker_id=self.worker_id,
                    parent_span_id=trace.span_id, t0=pull_t0, t1=time.time(),
                    attrs={"model": model_id, "peer": peer,
                           "outcome": "no_export"},
                ).to_dict())
                return
            if meta.get("object"):
                from ..transport.jetstream import ObjectStore

                store = ObjectStore(self.nc, timeout=cfg.kv_transfer_timeout_s)
                blob = await store.get(meta["bucket"], meta["object"])
                # best-effort cleanup: the blob is single-use
                with contextlib.suppress(Exception):
                    await store.delete(meta["bucket"], meta["object"])
            else:
                blob = b"".join(parts)
            if len(blob) != int(meta.get("bytes", -1)) or (
                hashlib.sha256(blob).hexdigest() != meta.get("sha256")
            ):
                raise KVTransferFormatError(
                    f"kv blob from {peer} failed integrity check "
                    f"({len(blob)} bytes)"
                )
            export = decode_kv_blob(blob)
            trace.mark("kv_import")
            imported = await import_fn(export)
            self._kv_transfer_bytes["import"] += len(blob)
            self._kv_transfer_ms["import"] += (time.monotonic() - t0) * 1000.0
            EVENTS.emit(
                "kv_import", model=model_id, peer=peer, bytes=len(blob),
                tokens=(imported or {}).get("tokens", 0),
                trace_id=trace.trace_id,
            )
            self._emit_span(Span(
                trace_id=trace.trace_id, span_id=pull_span_id,
                stage="worker.kv_pull", worker_id=self.worker_id,
                parent_span_id=trace.span_id, t0=pull_t0, t1=time.time(),
                attrs={"model": model_id, "peer": peer, "outcome": "ok",
                       "bytes": len(blob),
                       "tokens": (imported or {}).get("tokens", 0)},
            ).to_dict())
        except Exception as e:  # noqa: BLE001 — transfer failure must never fail the chat
            self._kv_transfer_failures += 1
            self._kv_transfer_ms["import"] += (time.monotonic() - t0) * 1000.0
            # the local re-prefill below is duplicated device work (the peer
            # already prefilled this prompt): tag the request so the batcher's
            # device-time ledger charges its prefill ms to the disagg-fallback
            # waste category instead of counting it as goodput
            payload["_waste_tag"] = "disagg_fallback_reprefill"
            log.warning(
                "kv prefetch from %s failed (%s: %s); serving with local prefill",
                peer, type(e).__name__, e,
            )
            # span context rides the failure event AND the anomaly dump, so
            # a kv_transfer_failed dump joins the assembled cluster trace by
            # trace_id (and this pull's exact hop by span_id)
            EVENTS.emit(
                "kv_transfer_failed", model=model_id, peer=peer,
                cause=type(e).__name__, error=str(e)[:200],
                trace_id=trace.trace_id, span_id=pull_span_id,
                parent_span_id=trace.span_id,
            )
            self._emit_span(Span(
                trace_id=trace.trace_id, span_id=pull_span_id,
                stage="worker.kv_pull", worker_id=self.worker_id,
                parent_span_id=trace.span_id, t0=pull_t0, t1=time.time(),
                attrs={"model": model_id, "peer": peer, "outcome": "failed",
                       "cause": type(e).__name__},
            ).to_dict())
            recorder = getattr(getattr(engine, "batcher", None), "recorder", None)
            if recorder is not None:
                recorder.dump(
                    "kv_transfer_failed",
                    trace=trace.report(),
                    extra={"model": model_id, "peer": peer,
                           "cause": type(e).__name__, "error": str(e)[:200],
                           "span_id": pull_span_id,
                           "parent_span_id": trace.span_id},
                )

    # -- warm prefix-cache handoff (ISSUE 15 tentpole) -----------------------

    async def push_warm_handoff(
        self, recipient: str, limit: int | None = None
    ) -> dict:
        """Push this worker's hottest prefix-cache block sets to
        ``recipient``'s directed ``kv_import`` subject so it starts serving
        with a hit rate instead of a cold cache. Used by a draining worker
        handing off to its replacement, and by the autoscaler to warm a
        fresh spawn from the best live peer. Best-effort throughout: every
        failed prefix is counted and skipped, never raised — a botched
        handoff degrades the recipient to a cold cache, nothing worse."""
        assert self.nc is not None
        cfg = self.config
        if limit is None:
            limit = int(getattr(cfg, "autoscale_handoff_prefixes", 4) or 0)
        if limit <= 0 or recipient == self.worker_id:
            return {"to": recipient, "sent": 0, "failed": 0, "tokens": 0}
        subject = f"{cfg.subject_prefix}.worker.{recipient}.kv_import"
        sent = failed = tokens = 0
        for mid, eng in list(self.registry.loaded_engines().items()):
            b = getattr(eng, "batcher", None)
            pc = getattr(b, "prefix_cache", None)
            export_fn = getattr(b, "export_prefix_blocks", None)
            hot_fn = getattr(pc, "hot_prefixes", None)
            if b is None or hot_fn is None or export_fn is None:
                continue  # fake/test engine or dense-only batcher: nothing to hand
            for path in hot_fn(limit):
                t0 = time.monotonic()
                try:
                    export = await asyncio.to_thread(export_fn, path)
                    if not export or not export.get("chunks"):
                        continue  # evicted between enumeration and gather
                    blob = encode_kv_blob(export)
                    ok = await self._push_kv_blob(subject, mid, blob)
                except Exception as e:  # noqa: BLE001 — handoff must not block the drain
                    log.warning("warm handoff of a %s prefix to %s failed: %s",
                                mid, recipient, e)
                    failed += 1
                    continue
                if ok:
                    sent += 1
                    tokens += len(export["token_ids"])
                    self._warm_handoff_sent += 1
                    self._kv_transfer_bytes["export"] += len(blob)
                    self._kv_transfer_ms["export"] += (
                        time.monotonic() - t0
                    ) * 1000.0
                else:
                    failed += 1
        EVENTS.emit("warm_handoff", worker_id=self.worker_id, to=recipient,
                    sent=sent, failed=failed, tokens=tokens)
        log.info("worker %s warm handoff to %s: %d prefixes (%d tokens), "
                 "%d failed", self.worker_id, recipient, sent, tokens, failed)
        return {"to": recipient, "sent": sent, "failed": failed,
                "tokens": tokens}

    async def _push_kv_blob(
        self, subject: str, model_id: str, blob: bytes
    ) -> bool:
        """One encoded blob to a peer's kv_import: a raw request when it
        fits under the broker frame limit (and the Object Store threshold),
        a JetStream Object Store reference otherwise. True when the peer
        confirms the import."""
        assert self.nc is not None
        cfg = self.config
        digest = hashlib.sha256(blob).hexdigest()
        objstore_min = int(getattr(cfg, "kv_transfer_objstore_bytes", 0) or 0)
        frame = (getattr(self.nc, "server_info", None) or {}).get("max_payload")
        inline_max = max(1, int(frame) - 1024) if frame else None
        via_objstore = (objstore_min > 0 and len(blob) >= objstore_min) or (
            inline_max is not None and len(blob) > inline_max
        )
        if via_objstore:
            from ..transport.jetstream import ObjectStore

            bucket = "kv-transfer"
            obj = f"{self.worker_id}-handoff-{digest[:16]}"
            store = ObjectStore(self.nc, timeout=cfg.kv_transfer_timeout_s)
            await store.ensure_bucket(bucket)
            await store.put(bucket, obj, blob)
            ref = {"model": model_id, "bucket": bucket, "object": obj,
                   "sha256": digest, "bytes": len(blob)}
            reply = await self.nc.request(
                subject, json.dumps(ref, separators=(",", ":")).encode(),
                timeout=cfg.kv_transfer_timeout_s,
            )
        else:
            reply = await self.nc.request(
                subject, blob, timeout=cfg.kv_transfer_timeout_s,
                headers={KV_MODEL_HEADER: model_id},
            )
        env = json.loads(reply.payload or b"{}")
        return bool(env.get("ok")) and bool(
            (env.get("data") or {}).get("imported")
        )

    async def on_kv_import(self, msg: Msg) -> None:
        """kv_import — directed subject ``{prefix}.worker.<id>.kv_import``:
        a draining donor (or the autoscaler's chosen peer) PUSHES a hot
        prefix here. The payload is either the raw KVX1 blob with the model
        id in the ``X-KV-Model`` header, or a JSON Object Store reference
        ``{model, bucket, object, sha256, bytes}`` for blobs over the
        threshold. The blocks land in the local pool + radix cache so the
        next matching prompt admits as a prefix hit. An engine that cannot
        import (fake/test engine) replies ``{imported: false}`` — a graceful
        no-op, never an error."""
        self._requests_total += 1
        payload = msg.payload or b""
        t0 = time.monotonic()
        try:
            if payload[:4] == b"KVX1":
                model_id = (
                    (msg.headers or {}).get(KV_MODEL_HEADER) or ""
                ).strip()
                if not model_id:
                    await self._respond_error(
                        msg,
                        f"'{KV_MODEL_HEADER}' header is required with a raw "
                        f"KV blob",
                    )
                    return
                blob = payload
            else:
                try:
                    ref = json.loads(payload or b"{}")
                    if not isinstance(ref, dict):
                        raise ValueError("payload must be a JSON object")
                except ValueError as e:
                    await self._respond_error(
                        msg, f"invalid JSON in KvImport: {e}"
                    )
                    return
                model_id = (ref.get("model") or "").strip()
                if not model_id or not ref.get("object"):
                    await self._respond_error(
                        msg, "'model' and 'object' are required in KvImport"
                    )
                    return
                from ..transport.jetstream import ObjectStore

                assert self.nc is not None
                store = ObjectStore(
                    self.nc, timeout=self.config.kv_transfer_timeout_s
                )
                blob = await store.get(ref["bucket"], ref["object"])
                # best-effort cleanup: the blob is single-use
                with contextlib.suppress(Exception):
                    await store.delete(ref["bucket"], ref["object"])
                if len(blob) != int(ref.get("bytes", -1)) or (
                    hashlib.sha256(blob).hexdigest() != ref.get("sha256")
                ):
                    raise KVTransferFormatError(
                        "handoff blob failed integrity check"
                    )
            export = decode_kv_blob(blob)
            engine = await self.registry.get_engine(model_id)
            import_fn = getattr(engine, "import_prefix", None)
            if import_fn is None:
                await self._respond_ok(
                    msg, {"imported": False, "reason": "no_import"}
                )
                return
            imported = await import_fn(export)
            self._warm_handoff_received += 1
            self._kv_transfer_bytes["import"] += len(blob)
            self._kv_transfer_ms["import"] += (time.monotonic() - t0) * 1000.0
            EVENTS.emit("warm_handoff_import", model=model_id, bytes=len(blob),
                        tokens=(imported or {}).get("tokens", 0))
            await self._respond_ok(msg, {
                "imported": True, "model": model_id,
                "tokens": (imported or {}).get("tokens", 0),
            })
        except (ModelNotFound, EngineError, KVTransferFormatError,
                ValueError, RuntimeError) as e:
            self._kv_transfer_failures += 1
            await self._respond_error(msg, f"error in kv import: {e}")

    async def on_kv_handoff(self, msg: Msg) -> None:
        """kv_handoff — control subject ``{prefix}.worker.<id>.kv_handoff``:
        ``{"to": worker_id, "limit"?}`` makes THIS worker push its hottest
        cached prefixes to the named peer. The autoscaler uses it to warm a
        freshly spawned worker from the best live donor without waiting for
        anyone to drain."""
        self._requests_total += 1
        try:
            req = json.loads(msg.payload or b"{}")
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in KvHandoff: {e}")
            return
        to = (req.get("to") or "").strip()
        if not to:
            await self._respond_error(msg, "'to' is required in KvHandoff")
            return
        if to == self.worker_id:
            await self._respond_error(msg, "cannot hand off to self")
            return
        limit = req.get("limit")
        try:
            limit = int(limit) if limit is not None else None
        except (TypeError, ValueError):
            await self._respond_error(msg, "'limit' must be an integer")
            return
        result = await self.push_warm_handoff(to, limit=limit)
        await self._respond_ok(msg, result)

    async def on_sync_model_from_bucket(self, msg: Msg) -> None:
        """sync_model_from_bucket {object_name, model_id?} — implements the
        README-only conceptual subject (/root/reference/README.md:286-318):
        object store → local model cache, responds {local_path}."""
        self._requests_total += 1
        try:
            req = json.loads(msg.payload or b"{}")
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in SyncModelFromBucket: {e}")
            return
        name = (req.get("object_name") or req.get("name") or "").strip()
        if not name:
            await self._respond_error(msg, "'object_name' is required")
            return
        try:
            async with _timeout(self.config.pull_timeout_s):
                local_path = await self.registry.sync_from_bucket(name, req.get("model_id"))
        except asyncio.TimeoutError:
            await self._respond_error(msg, "error syncing model: deadline exceeded", {"object": name})
            return
        except EngineError as e:
            await self._respond_error(msg, f"error syncing model: {e}", {"object": name})
            return
        await self._respond_ok(msg, {"object": name, "local_path": str(local_path)})

    async def on_health(self, msg: Msg) -> None:
        """health — heartbeat + counters (SURVEY.md §5: the reference has no
        health subject; client timeout is its only failure detector)."""
        data = {
            "status": "draining" if self.draining else "ok",
            "worker_id": self.worker_id,
            "role": getattr(self.config, "worker_role", ""),
            "draining": self.draining,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "requests_total": self._requests_total,
            "tokens_total": self._tokens_total,
            "streams_cancelled": self._streams_cancelled,
            "queue_group": self.config.queue_group,
            "reconnects": getattr(self.nc, "reconnects", 0),
        }
        data.update(self.registry.stats())
        # the devices this worker serves from: a worker that came up on a
        # backend nobody meant is visible in its heartbeat
        data["devices"] = _devices()
        # per-engine liveness/readiness (additive keys): lets clients and the
        # bench route around a worker whose engine is restarting
        health_fn = getattr(self.registry, "engine_health", None)
        if health_fn is not None:
            engines = health_fn()
            if engines:
                data["engines"] = engines
        poisoned_fn = getattr(self.registry, "poisoned_models", None)
        if poisoned_fn is not None:
            poisoned = poisoned_fn()
            if poisoned:
                data["poisoned"] = sorted(poisoned)
        await self._respond_ok(msg, data)

    async def on_metrics(self, msg: Msg) -> None:
        """metrics — full observability snapshot (SURVEY.md §5: counters on a
        NATS metrics subject): worker totals plus per-engine batcher stats
        (decode steps, tokens/step, peak active slots) and device info."""
        engines = {}
        for mid, eng in self.registry.loaded_engines().items():
            batcher = getattr(eng, "batcher", None)
            if batcher is None or not hasattr(batcher, "stats"):
                continue
            reps = getattr(batcher, "replicas", None) or [batcher]
            for ri, rb in enumerate(reps):
                key = mid if len(reps) == 1 else f"{mid}#dp{ri}"
                engines[key] = rb.stats.snapshot()
        data = {
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "requests_total": self._requests_total,
            "tokens_total": self._tokens_total,
            "queue_group": self.config.queue_group,
            "registry": self.registry.stats(),
            "engines": engines,
            "devices": _devices(),
        }
        await self._respond_ok(msg, data)

    def render_prometheus(self) -> str:
        """Worker totals + registry gauges + per-engine batcher counters and
        histograms in Prometheus text exposition (obs/prom.py)."""
        # worker_id on every family: a multi-worker scrape (or one pushed
        # through a shared gateway) stays attributable per worker
        r = PromRenderer(default_labels={"worker_id": self.worker_id})
        r.gauge("lmstudio_uptime_seconds", round(time.monotonic() - self._t0, 3))
        r.gauge("lmstudio_draining", 1 if self.draining else 0,
                help="1 while this worker is in graceful drain")
        r.counter("lmstudio_excluded_bounce_total", self._excluded_bounce_total,
                  help="chat requests bounced retryably because this worker "
                       "appeared in their X-Excluded-Workers header")
        r.counter("lmstudio_spans_emitted_total", self._spans_emitted_total,
                  help="trace spans published on the obs.spans subject")
        r.counter("lmstudio_drain_bounce_total", self._drain_bounce_total,
                  help="chat requests bounced retryably while draining")
        r.counter("lmstudio_requests_total", self._requests_total,
                  help="NATS requests handled by this worker")
        r.counter("lmstudio_tokens_total", self._tokens_total,
                  help="completion tokens generated")
        r.counter("lmstudio_streams_cancelled_total", self._streams_cancelled,
                  help="streaming chats aborted because the consumer vanished")
        # disaggregated prefill/decode families — ALWAYS present (zero-valued
        # on monolithic workers) so a role dashboard can group the fleet and
        # the disagg bench can scrape transfer volume without existence checks
        r.gauge("lmstudio_worker_role", 1,
                labels={"role": getattr(self.config, "worker_role", "") or "monolithic"},
                help="info gauge: this worker's serving role "
                     "(prefill | decode | monolithic)")
        for direction in ("export", "import"):
            dl = {"direction": direction}
            r.counter("lmstudio_kv_transfer_bytes_total",
                      self._kv_transfer_bytes[direction], labels=dl,
                      help="KV blob bytes moved between prefill and decode "
                           "workers, by direction")
            r.counter("lmstudio_kv_transfer_ms_total",
                      round(self._kv_transfer_ms[direction], 3), labels=dl,
                      help="wall milliseconds spent in KV transfers, by "
                           "direction (export: gather+ship; import: "
                           "pull+verify+pool write)")
        r.counter("lmstudio_kv_transfer_failures_total",
                  self._kv_transfer_failures,
                  help="KV pulls that failed (timeout, corrupt blob, pool "
                       "exhaustion) and fell back to local prefill")
        r.counter("lmstudio_warm_handoff_sent_total",
                  self._warm_handoff_sent,
                  help="hot prefix-cache block sets pushed to a replacement "
                       "worker (drain handoff or autoscaler warm-up)")
        r.counter("lmstudio_warm_handoff_received_total",
                  self._warm_handoff_received,
                  help="hot prefix-cache block sets imported from a donor "
                       "worker at kv_import")
        reg = self.registry.stats()
        for key in ("models_cached", "models_loaded", "engine_requests",
                    "hbm_committed_bytes"):
            v = reg.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                r.gauge(f"lmstudio_registry_{key}", v)
        mesh = reg.get("mesh") or {}
        r.gauge("lmstudio_mesh_tp", int(mesh.get("tp", 1)),
                help="tensor-parallel width of the serving mesh "
                     "(1 = unsharded serving)")
        r.gauge("lmstudio_mesh_dp", int(mesh.get("dp", 1)),
                help="data-parallel batcher replicas per worker "
                     "(1 = single batcher)")
        r.gauge("lmstudio_mesh_ep", int(mesh.get("ep", 1)),
                help="expert-parallel width of the serving mesh "
                     "(1 = experts unsharded)")
        r.gauge("lmstudio_mesh_sp", int(mesh.get("sp", 1)),
                help="sequence-parallel width: ring-attention prefill "
                     "degree for long prompts (1 = off)")
        # HBM ledger (obs/roofline.py, ticked by the flight recorder):
        # priced-component sum vs the allocator's bytes_in_use. Guarded —
        # test fakes implement stats() without the ledger key.
        hbm = reg.get("hbm_ledger")
        if efficiency_enabled() and isinstance(hbm, dict) and hbm:
            r.gauge("lmstudio_hbm_bytes_in_use", hbm.get("bytes_in_use", 0),
                    help="allocator bytes_in_use at the last ledger tick "
                         "(0 on backends without memory_stats)")
            r.gauge("lmstudio_hbm_priced_bytes", hbm.get("priced_bytes", 0),
                    help="sum of priced HBM components (weights+pool, "
                         "prefix cache, workspace slack)")
            r.gauge("lmstudio_hbm_unexplained_bytes",
                    hbm.get("unexplained_bytes", 0),
                    help="bytes_in_use minus priced components")
            r.gauge("lmstudio_hbm_drift_bytes", hbm.get("drift_bytes", 0),
                    help="unexplained-bytes growth above the ledger baseline")
        ledger = getattr(self.registry, "hbm_ledger", None)
        if efficiency_enabled() and ledger is not None:
            r.counter("lmstudio_hbm_drift_events_total",
                      getattr(ledger, "drift_events", 0),
                      help="hbm_drift events fired (unexplained bytes grew "
                           "monotonically past the threshold)")
        r.gauge("lmstudio_events_emitted_total", EVENTS.emitted)
        # XLA persistent-compile-cache effectiveness (obs/compile_cache.py;
        # the listener is installed at worker start). Distinguishes "restart
        # re-jitted from the cache in seconds" from "cache cold, every
        # program paid a full compile" — the r05 e2e_long failure mode.
        cc = compile_cache_counts()
        r.counter("lmstudio_compile_cache_hits_total", cc["hits"],
                  help="XLA persistent compile-cache hits in this process")
        r.counter("lmstudio_compile_cache_misses_total", cc["misses"],
                  help="XLA persistent compile-cache misses in this process")
        # the build ledger: where start-up went, by JAX's own duration events
        for kind, secs in build_ledger(top=0)["seconds"].items():
            r.counter("lmstudio_program_build_seconds_total", round(secs, 6),
                      labels={"kind": kind},
                      help="seconds this process spent building programs "
                           "(trace, lower, compile; cache_load lies inside compile)")
        # a device trace (lmstudio.profile) names a program by its jitted
        # function, this page by its table name: the kinds are the bridge
        for program, kind in sorted(program_kinds().items()):
            r.gauge("lmstudio_program_kind", 1, labels={"program": program, "kind": kind},
                    help="what a device trace's module jit_<program> is: "
                         "prefill, decode, spec or other")
        # fault-tolerance families — ALWAYS present (zero-valued when
        # nothing has failed) so dashboards and the chaos tests can assert
        # their existence, not just their increments
        r.counter("lmstudio_reconnects_total", getattr(self.nc, "reconnects", 0),
                  help="NATS connection re-establishments by this worker")
        r.counter("lmstudio_engine_restarts_total",
                  getattr(self.registry, "engine_restarts_total", 0),
                  help="supervisor-driven engine restarts")
        inflight_failed = getattr(self.registry, "inflight_failed_retryable", 0)
        for eng in self.registry.loaded_engines().values():
            b = getattr(eng, "batcher", None)
            for rb in (getattr(b, "replicas", None) or [b]) if b is not None else []:
                stats = getattr(rb, "stats", None)
                # live batchers' counts (every dp replica); crashed ones were
                # harvested into the registry accumulator at restart, so no
                # double count
                inflight_failed += getattr(stats, "inflight_failed_retryable", 0)
        r.counter("lmstudio_inflight_failed_retryable_total", inflight_failed,
                  help="in-flight requests failed with a retryable envelope "
                       "by an engine crash")
        poisoned_fn = getattr(self.registry, "poisoned_models", None)
        if poisoned_fn is not None:
            r.gauge("lmstudio_engines_poisoned", len(poisoned_fn()))
        restart_hist = getattr(self.registry, "restart_latency_ms", None)
        if restart_hist is not None:
            r.histogram("lmstudio_engine_restart_ms", restart_hist.snapshot())
        per_replica = []
        for mid, eng in self.registry.loaded_engines().items():
            b = getattr(eng, "batcher", None)
            if b is None:
                continue
            reps = getattr(b, "replicas", None) or [b]
            for ri, rb in enumerate(reps):
                per_replica.append((mid, ri if len(reps) > 1 else None, rb))
        for mid, ri, rb in per_replica:
            stats = getattr(rb, "stats", None)
            if stats is None or not hasattr(stats, "histograms"):
                continue
            # a dp>1 engine exposes every per-batcher family once per
            # replica under a "replica" label — the proof that an overload
            # wave actually distributed lives in per-replica
            # lmstudio_batcher_requests_total
            labels = {"model": mid}
            if ri is not None:
                labels["replica"] = str(ri)
            for name, v in stats.counters().items():
                r.counter(f"lmstudio_batcher_{name}_total", v, labels=labels)
            r.gauge("lmstudio_batcher_peak_active_slots", stats.peak_active, labels=labels)
            for cause, v in stats.shed_cause_counts().items():
                r.counter("lmstudio_batcher_shed_by_cause_total", v,
                          labels={**labels, "cause": cause})
            # multi-tenant QoS families (serve/qos.py): per-tenant serving
            # counters under a capped ``tenant`` label — the top-K tenants
            # by volume keep their own rows, the rest roll up into
            # tenant="other" so a key-guessing client cannot mint unbounded
            # label values
            tstats = getattr(rb, "tenant_stats", None)
            if tstats is not None:
                topk = getattr(self.config, "qos_tenant_topk", 8)
                for tenant, row in sorted(tstats.snapshot(topk).items()):
                    tl = {**labels, "tenant": tenant}
                    for key, fam in (
                        ("requests", "lmstudio_tenant_requests_total"),
                        ("served", "lmstudio_tenant_served_total"),
                        ("shed", "lmstudio_tenant_shed_total"),
                        ("preempted", "lmstudio_tenant_preempted_total"),
                        ("tokens", "lmstudio_tenant_tokens_total"),
                    ):
                        r.counter(fam, row.get(key, 0), labels=tl)
                    r.counter("lmstudio_tenant_queue_age_ms_total",
                              round(row.get("queue_age_ms_sum", 0.0), 3),
                              labels=tl,
                              help="summed enqueue->admit wait ms of served "
                                   "requests, by tenant (the fairness "
                                   "signal: divide by served for the mean)")
            # deadline/brownout families — always present (zero-valued when
            # quiet) so overload dashboards can alert on the first increment
            causes = stats.shed_cause_counts()
            r.counter("lmstudio_deadline_shed_total",
                      causes.get("deadline", 0), labels=labels,
                      help="requests shed because the client deadline "
                           "expired or became infeasible before prefill")
            r.counter("lmstudio_deadline_aborted_total",
                      getattr(stats, "cancel_causes", {}).get("deadline", 0),
                      labels=labels,
                      help="mid-decode slots aborted past the client deadline")
            r.gauge("lmstudio_brownout_level",
                    getattr(rb, "brownout_level", 0), labels=labels,
                    help="0=normal 1=brownout 2=shed-only")
            # decode-kernel family: which kernel serves paged decode and how
            # many fresh decode-program compiles the window ladder has cost
            # (flat under DECODE_KERNEL=pallas — its grid is context-length
            # independent)
            r.counter("lmstudio_decode_recompiles_total",
                      getattr(stats, "decode_recompiles", 0), labels=labels,
                      help="first-seen (program, static-args) combos on the "
                           "decode/verify paths — each is a fresh XLA compile")
            r.gauge("lmstudio_decode_kernel_pallas",
                    1 if getattr(rb, "decode_kernel", "xla") == "pallas"
                    else 0, labels=labels,
                    help="1 when the Pallas paged-decode kernel is serving")
            if hasattr(stats, "spec_counters"):
                # speculative decoding: lmstudio_spec_{verifies,drafted,
                # accepted}_total; the lmstudio_spec_accept_rate histogram
                # rides the generic histograms() loop below
                for name, v in stats.spec_counters().items():
                    r.counter(f"lmstudio_spec_{name}_total", v, labels=labels)
            if hasattr(stats, "sampler_counters"):
                for name, v in stats.sampler_counters().items():
                    r.counter("lmstudio_sampler_rows_total", v,
                              labels={**labels, "class": name},
                              help="rows admitted by what they ask of the sampler: "
                                   "only an unrestricted row (temperature > 0, no "
                                   "top-k, top_p 1) makes a decode step draw noise "
                                   "for the whole vocabulary")
            moe = getattr(stats, "moe_counters", None)
            if moe is not None and moe()["expert_steps"]:
                # routed-expert layers (models/mla_moe.py), summed over
                # decode steps x expert layers: hit / steps is the experts a
                # step reads where its path is "hit_list" or "grouped" (every
                # expert where it is "dense"), rows_max / steps against
                # rows x k / experts is the imbalance
                for name, v in moe().items():
                    r.counter(f"lmstudio_moe_{name}_total", v, labels=labels)
                for name, v in getattr(stats, "picks_counters", dict)().items():
                    # the (row, pick) pairs the live rows routed, and those
                    # whose expert this chip holds: held / picks is 1 unless
                    # the chip holds a share of a layer's experts
                    r.counter(f"lmstudio_moe_{name}_total", v, labels=labels)
                r.gauge("lmstudio_moe_expert_path", 1,
                        labels={**labels, "path": getattr(stats, "expert_path", "")},
                        help="the form a decode burst's expert layers take: "
                             "hit_list reads only the experts hit, grouped "
                             "computes each pick on its own expert, dense all")
            ssm = getattr(stats, "state_counters", None)
            pool_fn = getattr(rb, "pool_stats", None)
            pools = (pool_fn() if pool_fn else None) or {}
            state_pool = pools.get("state")
            if ssm is not None and state_pool:
                # state-space layers (models/ssm_hybrid.py) and linear-
                # attention layers (models/gdn_moe.py): rows / steps is
                # the live rows whose recurrent state a decode step advanced,
                # slots_moved / steps the slots whose state it read and wrote
                # (the state kernel skips a slot that holds no request); the
                # pool is indexed by slot, beside the paged KV pool
                for name, v in ssm().items():
                    r.counter(f"lmstudio_ssm_{name}_total", v, labels=labels)
                r.gauge("lmstudio_ssm_state_pool_bytes", state_pool["bytes"],
                        labels=labels,
                        help="device bytes of the per-slot recurrent-state pool")
                r.gauge("lmstudio_ssm_state_pool_slots_live",
                        state_pool["slots_live"], labels=labels)
                r.gauge("lmstudio_ssm_state_pool_slots_total",
                        state_pool["slots_total"], labels=labels)
            sparse = getattr(stats, "sparse_counters", None)
            if sparse is not None and sparse()["tokens_live"]:
                # block-sparse layers (models/sala.py), over decode steps, live
                # rows and sparse layers: picked / live is the share of the
                # keys a row could see that its picked walk read
                for name, v in sparse().items():
                    r.counter(f"lmstudio_sparse_{name}_total", v, labels=labels)
            swa = getattr(stats, "window_counters", None)
            if swa is not None and pools.get("window"):
                # window layers beside full ones (models/swa_moe.py): win /
                # (win + full) tokens is the share of a decode step's keys the
                # window layers read, a layer of each kind; the full layers'
                # KV is the paged pool's, the window layers' a ring a slot
                for name, v in swa().items():
                    r.counter(f"lmstudio_swa_{name}_total", v, labels=labels)
                ring = pools["window"]
                r.gauge("lmstudio_swa_ring_pool_bytes", ring["bytes"], labels=labels,
                        help="device bytes of the window layers' per-slot rings")
                r.gauge("lmstudio_swa_full_pool_bytes", ring["kv_pool_bytes"], labels=labels,
                        help="device bytes of the full layers' paged KV pool")
                r.gauge("lmstudio_swa_ring_pool_slots_live", ring["slots_live"], labels=labels)
                r.gauge("lmstudio_swa_ring_pool_slots_total", ring["slots_total"], labels=labels)
            for feature, cause in sorted(getattr(rb, "refusals", {}).items()):
                # what was asked for and this model's family does not serve
                r.gauge("lmstudio_feature_refused", 1,
                        labels={**labels, "feature": feature, "cause": cause},
                        help="a serving feature turned off for this model, "
                             "with the cause")
            rows_fn = getattr(stats, "expert_prefill_rows", None)
            if rows_fn is not None and any(rows_fn().values()):
                # the prefill side: rows of prompt tokens (padding included)
                # dispatched through the expert layers, by the form
                # expert_path gave the dispatch's shape
                for path, v in rows_fn().items():
                    r.counter("lmstudio_moe_prefill_rows_total", v,
                              labels={**labels, "path": path})
            tier_fn = getattr(rb, "tier_stats", None)
            tier = tier_fn() if tier_fn is not None else None
            if tier:
                # hierarchical KV tier + slot suspend/resume families
                # (serve/kv_tiers.py): gauges describe the host tier's
                # current occupancy, counters the chunk traffic between
                # tiers and the swap-don't-shed slot movements
                for name in ("host_entries", "host_bytes",
                             "host_budget_bytes", "spill_pending"):
                    if name in tier:
                        r.gauge(f"lmstudio_kv_tier_{name}", tier[name],
                                labels=labels)
                r.gauge("lmstudio_kv_tier_suspended_slots",
                        tier.get("suspended", 0), labels=labels,
                        help="slots currently swapped out to the host tier "
                             "awaiting resume")
                for name in ("demoted_chunks", "promoted_chunks",
                             "demote_failures", "host_hits", "host_misses",
                             "spilled_blobs", "fetched_blobs",
                             "spill_failures", "fetch_failures",
                             "demoted_blocks", "suspended_total",
                             "resumed_total", "suspend_failures",
                             "suspended_deadline_expired"):
                    if name in tier:
                        # stat keys like suspended_total already carry the
                        # suffix; strip it so the family never doubles up
                        base = name[:-6] if name.endswith("_total") else name
                        r.counter(f"lmstudio_kv_tier_{base}_total",
                                  tier[name], labels=labels)
            for name, h in stats.histograms().items():
                r.histogram(f"lmstudio_{name}", h.snapshot(), labels=labels)
            if hasattr(stats, "program_histograms"):
                # per-program device dispatch timing: every jit-grid program
                # the batcher launched, as one labeled histogram family —
                # answers "which program got slow" without a profiler run.
                # Host-side dispatch time only (the pump never blocks on the
                # result here); cold entries include XLA compile time.
                for name, h in sorted(stats.program_histograms().items()):
                    r.histogram("lmstudio_program_ms", h.snapshot(),
                                labels={**labels, "program": name})
                for name, h in sorted(stats.program_token_histograms().items()):
                    r.histogram("lmstudio_program_tokens", h.snapshot(),
                                labels={**labels, "program": name})
            if efficiency_enabled() and hasattr(stats, "device_time_snapshot"):
                # the device-time ledger (obs/roofline.py): every
                # dispatch's ms attributed to a request outcome
                dt = stats.device_time_snapshot()
                for cat in sorted(dt["ms"]):
                    cl = {**labels, "category": cat}
                    r.counter("lmstudio_device_ms_total",
                              round(dt["ms"][cat], 3), labels=cl,
                              help="device-dispatch milliseconds attributed "
                                   "to a request outcome category")
                    r.counter("lmstudio_device_tokens_total",
                              dt["tokens"].get(cat, 0), labels=cl,
                              help="tokens delivered, by outcome category "
                                   "of the device time that produced them")
                r.gauge("lmstudio_goodput_tokens_per_device_s",
                        round(stats.goodput_tokens_per_device_s(), 3),
                        labels=labels,
                        help="served tokens per device-second across ALL "
                             "attributed device time (waste included in "
                             "the denominator)")
            pool_stats_fn = getattr(rb, "pool_stats", None)
            pool = pool_stats_fn() if pool_stats_fn is not None else None
            if pool is not None:
                # paged-KV block pool residency: total/free/shared block
                # gauges prove the zero-copy prefix-sharing story (shared >
                # 0 while a hit decodes; free returns to total after drain)
                # and the CoW counter stays 0 under chunk-aligned sharing
                for name in ("blocks_total", "blocks_free", "blocks_shared"):
                    r.gauge(f"lmstudio_kv_pool_{name}", pool[name],
                            labels=labels)
                r.counter("lmstudio_kv_pool_cow_copies_total",
                          pool["cow_copies"], labels=labels,
                          help="copy-on-write block duplications (a shared "
                               "block written by a live slot)")
            pcache = getattr(rb, "prefix_cache", None)
            if pcache is not None:
                # two new families: lmstudio_prefix_cache_*_total counters
                # (hits/misses/full_hits/hit_tokens/inserted/evicted blocks)
                # and the lmstudio_prefix_hit_tokens histogram, plus
                # residency gauges — the cache's whole serving story
                for name, v in pcache.counters().items():
                    r.counter(f"lmstudio_prefix_cache_{name}_total", v, labels=labels)
                r.gauge("lmstudio_prefix_cache_blocks", pcache.blocks, labels=labels)
                r.gauge("lmstudio_prefix_cache_bytes", pcache.bytes, labels=labels)
                r.histogram("lmstudio_prefix_hit_tokens",
                            pcache.hit_tokens_hist.snapshot(), labels=labels)
        return r.render()

    async def on_metrics_prom(self, msg: Msg) -> None:
        """metrics.prom — the same observability surface as ``metrics`` but
        rendered as Prometheus text exposition: point any scraper at
        ``nats req lmstudio.metrics.prom ''`` (or a thin HTTP bridge) and
        the admit-delay/TTFT/prefill/decode-step histograms arrive with
        cumulative ``le`` buckets, per-model labels, and counter families.
        Replies raw text, not a JSON envelope — scrapers want the body."""
        await self._respond_json(msg, self.render_prometheus().encode())

    async def on_events(self, msg: Msg) -> None:
        """events — the structured event ring (obs/events.py): sheds,
        cancels, ring compactions, engine load/evict, slow requests.
        Payload (optional): ``{kind?, limit?}`` filters by event kind and
        caps the reply to the most recent N (default 100)."""
        if not msg.reply:
            # fire-and-forget broadcasts land here too (e.g. the aggregator's
            # slo_burn fan-out on <prefix>.events) — nothing to answer
            return
        try:
            req = json.loads(msg.payload) if msg.payload and msg.payload.strip() else {}
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in Events: {e}")
            return
        kind = req.get("kind")
        try:
            limit = int(req.get("limit", 100))
        except (TypeError, ValueError):
            await self._respond_error(msg, "'limit' must be an integer")
            return
        await self._respond_ok(
            msg,
            {
                "events": EVENTS.snapshot(kind=kind, limit=limit),
                "emitted_total": EVENTS.emitted,
                "dropped": EVENTS.dropped,
                "capacity": EVENTS.capacity,
            },
        )

    async def on_profile(self, msg: Msg) -> None:
        """profile — capture a jax.profiler device trace for ``seconds``
        (default 2) into a worker-chosen directory and reply with the trace
        path. The SURVEY.md §5 profiling endpoint: drive load through
        chat_model while this runs, then inspect the trace with the
        TensorBoard profile plugin.

        The trace directory is always worker-chosen (mkdtemp): bus clients
        are untrusted (see config.py threat model) and a client-supplied
        path would be an arbitrary-directory-write primitive on the worker
        host (round-2 advisor, medium)."""
        import tempfile

        import jax

        import math

        try:
            req = json.loads(msg.payload) if msg.payload.strip() else {}
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in Profile: {e}")
            return
        seconds = float(req.get("seconds", 2.0))
        if not math.isfinite(seconds):
            await self._respond_error(msg, "'seconds' must be finite")
            return
        seconds = max(0.0, min(seconds, 60.0))
        if self._profiling:
            await self._respond_error(msg, "a profile capture is already running")
            return
        self._profiling = True
        trace_dir = tempfile.mkdtemp(prefix="tpu_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
            try:
                await asyncio.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        finally:
            self._profiling = False
        reply: dict = {"trace_dir": trace_dir, "seconds": seconds}
        # a profile captured via a directed subject on a REMOTE worker is
        # useless as a local path: zip the trace and park it in the Object
        # Store (same JetStream plumbing as kv-transfer) so the requester
        # can pull it from anywhere. Best-effort — no JetStream on the
        # broker (or any upload hiccup) keeps the local-path reply.
        try:
            blob = await asyncio.to_thread(_zip_dir, trace_dir)
            digest = hashlib.sha256(blob).hexdigest()
            from ..transport.jetstream import ObjectStore

            assert self.nc is not None
            # short API timeout: on a broker WITHOUT JetStream the $JS.API
            # probe gets no responder and would otherwise stall the reply
            # for the full window — the requester's own timeout loses first
            store = ObjectStore(self.nc, timeout=5.0)
            bucket = "profiles"
            obj = f"{self.worker_id}-{digest[:16]}.zip"
            await store.ensure_bucket(bucket)
            await store.put(bucket, obj, blob)
            reply.update(bucket=bucket, object=obj, sha256=digest,
                         bytes=len(blob))
        except Exception as e:  # noqa: BLE001 — upload is an optimization
            log.warning("profile upload failed (%s: %s); trace stays local "
                        "at %s", type(e).__name__, e, trace_dir)
        await self._respond_ok(msg, reply)

    # -- deep-debug subjects (DEBUG_SUBJECTS=1 only) -------------------------

    async def on_debug_snapshot(self, msg: Msg) -> None:
        """debug.snapshot — live internals of every loaded engine's batcher:
        per-slot positions and block tables (with refcounts), prefix-cache
        radix summary, brownout state, and the flight recorder's frame tail.
        Payload (optional): ``{model?}`` restricts to one engine. Read-only
        and point-in-time consistent per engine (the slot view is swapped
        wholesale by the owner loop), but not across engines."""
        try:
            req = json.loads(msg.payload) if msg.payload and msg.payload.strip() else {}
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in DebugSnapshot: {e}")
            return
        want = (req.get("model") or "").strip() or None
        engines = {}
        for mid, eng in self.registry.loaded_engines().items():
            if want is not None and mid != want:
                continue
            snap_fn = getattr(getattr(eng, "batcher", None), "debug_snapshot", None)
            if snap_fn is not None:
                engines[mid] = snap_fn()
        if want is not None and not engines:
            await self._respond_error(msg, f"model not loaded: {want}")
            return
        await self._respond_ok(msg, {
            "worker_id": self.worker_id,
            "role": getattr(self.config, "worker_role", ""),
            "engines": engines,
        })

    async def on_debug_dump(self, msg: Msg) -> None:
        """debug.dump — force a flight-recorder dump for every loaded engine
        (or ``{model?}``) and reply with the written paths. The dump
        directory is always the worker's OBS_DUMP_DIR — a client-supplied
        path would be an arbitrary-directory-write primitive (same threat
        model as on_profile's mkdtemp)."""
        try:
            req = json.loads(msg.payload) if msg.payload and msg.payload.strip() else {}
            if not isinstance(req, dict):
                raise ValueError("payload must be a JSON object")
        except ValueError as e:
            await self._respond_error(msg, f"invalid JSON in DebugDump: {e}")
            return
        want = (req.get("model") or "").strip() or None
        paths = {}
        for mid, eng in self.registry.loaded_engines().items():
            if want is not None and mid != want:
                continue
            recorder = getattr(getattr(eng, "batcher", None), "recorder", None)
            if recorder is not None:
                path = recorder.dump("debug_request", force=True,
                                     extra={"model": mid})
                if path:
                    paths[mid] = path
        if not paths:
            await self._respond_error(
                msg,
                "no dump written (recorder disabled, OBS_DUMP_DIR unset, "
                "or no engine loaded)",
            )
            return
        await self._respond_ok(msg, {"dumps": paths})
