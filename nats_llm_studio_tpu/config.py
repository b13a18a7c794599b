"""Environment-variable configuration.

The reference's config contract is README-only (env vars ``NATS_URL``,
``LMSTUDIO_BASE_URL``, ``LMSTUDIO_MODELS_DIR``, ``NATS_QUEUE_GROUP`` with
defaults — /root/reference/README.md:489-494, materialized into ``.env`` by
scripts/setup_unix.sh:111-115). This build keeps the same names and defaults,
drops ``LMSTUDIO_BASE_URL`` (no external HTTP engine exists any more), and
adds TPU-mesh settings.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path


# where the persistent XLA compile cache lives when JAX_COMPILATION_CACHE_DIR
# does not place it (``WorkerConfig.configure_jax``): fixed, inside the checkout
DEFAULT_COMPILE_CACHE_DIR = str(Path(__file__).resolve().parent.parent / ".jax_cache")


def _env(name: str, default: str) -> str:
    v = os.environ.get(name, "").strip()
    return v or default


@dataclass
class WorkerConfig:
    # reference-compatible contract (README.md:489-494)
    nats_url: str = field(default_factory=lambda: _env("NATS_URL", "nats://127.0.0.1:4222"))
    models_dir: Path = field(
        default_factory=lambda: Path(
            _env("LMSTUDIO_MODELS_DIR", str(Path.home() / ".lmstudio" / "models"))
        ).expanduser()
    )
    queue_group: str = field(default_factory=lambda: _env("NATS_QUEUE_GROUP", "lmstudio-workers"))
    subject_prefix: str = field(default_factory=lambda: _env("SUBJECT_PREFIX", "lmstudio"))

    # object store (README.md:250-318 pattern)
    bucket: str = field(default_factory=lambda: _env("MODEL_BUCKET", "llm-models"))

    # TPU build additions
    # serving mesh spec (parallel.mesh.serving_mesh): "auto" (default)
    # shards every local device on the tp axis — tensor-parallel serving
    # is the multi-device default; a single-device host serves unsharded.
    # "off"/"none"/"1" force tp=1; explicit specs like "tp=4",
    # "dp=2,tp=4", or the compact named-axis grammar "dp2,ep2,tp2" build
    # exactly that mesh. dp = independent batcher replicas (multiplied
    # slot capacity), ep = MoE expert sharding, sp = ring-attention
    # long-prompt prefill (RING_PREFILL_MIN_TOKENS). MESH_SHAPE is the
    # documented knob; TPU_MESH is honored as the legacy alias.
    mesh_shape: str = field(
        default_factory=lambda: _env("MESH_SHAPE", "") or _env("TPU_MESH", "auto")
    )
    max_batch_slots: int = field(default_factory=lambda: int(_env("MAX_BATCH_SLOTS", "8")))
    max_seq_len: int = field(default_factory=lambda: int(_env("MAX_SEQ_LEN", "4096")))
    # weight-only quantization for serving: "none" (cfg dtype), "int8"
    # (per-output-channel — halves HBM weight traffic, fits 70B-class
    # models on a v5e-8) or "int4" (grouped asymmetric QTensor4,
    # WQUANT_GROUP rows per scale/zero-point — halves it again). WQUANT is
    # the documented knob; TPU_QUANT is honored as the legacy alias.
    quant_mode: str = field(
        default_factory=lambda: _env("WQUANT", "") or _env("TPU_QUANT", "none")
    )
    # rows of the contraction axis per int4 scale/zero-point pair (AWQ-style
    # grouping; degrades automatically when it does not divide the axis)
    wquant_group: int = field(default_factory=lambda: int(_env("WQUANT_GROUP", "32")))
    # "none" or "int8": quantized serving KV cache (ops/kvcache.py) — halves
    # decode cache traffic and per-slot HBM
    kv_quant_mode: str = field(default_factory=lambda: _env("TPU_KV_QUANT", "none"))
    # comma-separated URL schemes pull_model may fetch directly; https-only
    # by default on serving workers (bus clients must not be able to SSRF
    # through the worker or read its local files). Empty string disables.
    url_pull_schemes: str = field(default_factory=lambda: _env("URL_PULL_SCHEMES", "https"))
    # ceiling on a single pull_model URL download (disk-fill guard); default
    # mirrors the reference's 100 GiB JetStream file store (setup_unix.sh:93)
    max_url_pull_bytes: int = field(
        default_factory=lambda: int(_env("MAX_URL_PULL_BYTES", str(100 << 30)))
    )
    # overload bounds on the batcher admit queue (0 disables either).
    # Depth: chat_model sheds immediately past this many queued requests.
    # Age: waiters older than this are shed at admit time. Shedding replies
    # with an honest error envelope so queue-group peers absorb the overflow
    # (/root/reference/README.md:478-484); without bounds the r4 bench
    # measured 38.6 s of silent queueing. Unset ADMIT_QUEUE_LIMIT derives
    # 4 x MAX_BATCH_SLOTS; an explicit 0 disables the depth bound.
    admit_queue_limit: int = field(
        default_factory=lambda: int(_env("ADMIT_QUEUE_LIMIT", "-1"))
    )
    admit_max_age_ms: float = field(
        default_factory=lambda: float(_env("ADMIT_MAX_AGE_MS", "30000"))
    )
    # automatic prefix KV cache (serve/prefix_cache.py): per-engine budget
    # in prefill-chunk blocks, priced against the HBM admission budget.
    # PREFIX_CACHE=0 is the hard off-switch (wins over PREFIX_CACHE_BLOCKS);
    # PREFIX_CACHE_BLOCKS=0 also disables.
    prefix_cache_blocks: int = field(
        default_factory=lambda: int(_env("PREFIX_CACHE_BLOCKS", "64"))
    )
    # paged KV (serve/block_pool.py): ONE refcounted fixed-size-block pool
    # shared by live slots, the prefix cache, and spec decode, addressed
    # through per-slot block tables. Default on; KV_PAGED=0/false/off
    # restores the pre-paged contiguous per-slot rings (the bit-equivalence
    # baseline). KV_BLOCK_TOKENS is tokens per block (snapped down to
    # divide the prefill chunk); KV_POOL_BLOCKS=0 auto-sizes for zero
    # starvation (every slot at max_seq + the prefix budget) — deployments
    # under-provision it to pack more slots into the same HBM.
    kv_paged: bool = field(
        default_factory=lambda: _env("KV_PAGED", "1").strip().lower()
        not in ("0", "false", "off")
    )
    kv_block_tokens: int = field(
        default_factory=lambda: int(_env("KV_BLOCK_TOKENS", "16"))
    )
    kv_pool_blocks: int = field(
        default_factory=lambda: int(_env("KV_POOL_BLOCKS", "0"))
    )
    # -- hierarchical KV tiers (serve/kv_tiers.py) ---------------------------
    # host-RAM tier budget in bytes under the HBM block pool: evicted/
    # reclaimed prefix-cache chunks demote here (and spill onward to the
    # Object Store) instead of being dropped. 0 disables tiering entirely.
    kv_host_pool_bytes: int = field(
        default_factory=lambda: int(_env("KV_HOST_POOL_BYTES", str(256 << 20)))
    )
    # spill host-tier evictions to the JetStream Object Store as KVX1 blobs
    # (bucket "kv-tier"); the cold tier survives process death, so a
    # respawned worker warm-imports its hottest prefixes with no live donor
    kv_spill_objstore: bool = field(
        default_factory=lambda: _env("KV_SPILL_OBJSTORE", "1").strip().lower()
        not in ("0", "false", "off")
    )
    # slot suspend/resume (swap-don't-shed): under pool exhaustion or
    # SHED_ONLY brownout, demote a victim slot's KV + resume state to host
    # RAM and continue it later bit-identically instead of shedding/
    # cancelling. KV_SUSPEND=0 is the kill switch (pre-tier shed behavior).
    kv_suspend: bool = field(
        default_factory=lambda: _env("KV_SUSPEND", "1").strip().lower()
        not in ("0", "false", "off")
    )
    # proactive demotion low-water mark: each owner tick with the pool's
    # free fraction below this, cold cache chunks demote to the host tier
    # ahead of demand (admission then allocates without synchronous swaps)
    kv_demote_free_frac: float = field(
        default_factory=lambda: float(_env("KV_DEMOTE_FREE_FRAC", "0.10"))
    )
    # promotion-on-hit ceiling: at most this many tiered chunks re-enter the
    # pool per admit (bounds the synchronous device_put burst a deep
    # host-tier hit can inject ahead of one prefill)
    kv_promote_chunks: int = field(
        default_factory=lambda: int(_env("KV_PROMOTE_CHUNKS", "64"))
    )
    # cold-tier object-count cap; shallowest chains purge first
    kv_spill_max_objects: int = field(
        default_factory=lambda: int(_env("KV_SPILL_MAX_OBJECTS", "512"))
    )
    # speculative decoding (serve/spec.py): max prompt-lookup draft tokens
    # per slot per verify dispatch. SPEC_DECODE=0 is the hard off-switch
    # (wins over SPEC_DECODE_K); SPEC_DECODE_K=0 also disables. NOTE: k > 0
    # runs the engine cache in positional layout (per-row scatter writes),
    # trading some high-occupancy ring throughput for the low-occupancy
    # speculative win — throughput-tuned high-batch deployments should set
    # SPEC_DECODE=0.
    spec_decode_k: int = field(
        default_factory=lambda: int(_env("SPEC_DECODE_K", "6"))
    )
    # verify dispatches pause above this many live slots (decode turns
    # compute-bound and drafts stop paying); plain decode continues
    spec_max_active: int = field(
        default_factory=lambda: int(_env("SPEC_DECODE_MAX_ACTIVE", "4"))
    )
    # -- transport resilience (transport/client.py) --------------------------
    # reconnect attempts after a lost connection (exp backoff + jitter,
    # base→cap below); 0 disables auto-reconnect (connection loss closes the
    # client, pre-resilience behavior)
    max_reconnects: int = field(
        default_factory=lambda: int(_env("NATS_MAX_RECONNECTS", "60"))
    )
    reconnect_wait_s: float = field(
        default_factory=lambda: float(_env("NATS_RECONNECT_WAIT_S", "0.05"))
    )
    reconnect_max_wait_s: float = field(
        default_factory=lambda: float(_env("NATS_RECONNECT_MAX_WAIT_S", "2.0"))
    )
    # client-originated PING keepalive: a connection that misses two
    # consecutive PONGs is declared stale and dropped into the reconnect
    # path. 0 disables the keepalive task.
    ping_interval_s: float = field(
        default_factory=lambda: float(_env("NATS_PING_INTERVAL_S", "30"))
    )
    # -- engine supervision (serve/worker.py + serve/registry.py) ------------
    # watchdog poll period over loaded batchers; 0 disables supervision
    supervise_interval_s: float = field(
        default_factory=lambda: float(_env("SUPERVISE_INTERVAL_S", "2"))
    )
    # a NON-idle batcher whose owner loop hasn't stamped its heartbeat for
    # this long is declared hung and restarted; generous default because a
    # cold XLA compile of a big prefill program legitimately stalls the
    # loop for minutes. 0 disables the hang check (crash detection stays).
    engine_heartbeat_timeout_s: float = field(
        default_factory=lambda: float(_env("ENGINE_HEARTBEAT_TIMEOUT_S", "120"))
    )
    engine_restart_backoff_s: float = field(
        default_factory=lambda: float(_env("ENGINE_RESTART_BACKOFF_S", "0.5"))
    )
    engine_restart_backoff_max_s: float = field(
        default_factory=lambda: float(_env("ENGINE_RESTART_BACKOFF_MAX_S", "30"))
    )
    # more than this many crashes inside the window poisons the model:
    # get_engine refuses (retryable envelope) until a delete/pull resets it
    engine_max_restarts: int = field(
        default_factory=lambda: int(_env("ENGINE_MAX_RESTARTS", "3"))
    )
    engine_restart_window_s: float = field(
        default_factory=lambda: float(_env("ENGINE_RESTART_WINDOW_S", "120"))
    )
    # -- overload robustness (serve/brownout.py + serve/batcher.py) ----------
    # end-to-end deadline propagation: request()/request_stream() stamp the
    # caller's budget as X-Deadline-Ms; the worker converts it to a monotonic
    # deadline (capped by chat_timeout_s) so the batcher can shed expired
    # requests before prefill and abort mid-decode slots whose caller gave
    # up. DEADLINE_PROPAGATION=0/false/off disables the worker-side half
    # (clients still stamp the cheap header). DEADLINE_MIN_TOKENS and the
    # BROWNOUT_* thresholds parse in serve/registry.py.
    deadline_propagation: bool = field(
        default_factory=lambda: _env("DEADLINE_PROPAGATION", "1").strip().lower()
        not in ("0", "false", "off")
    )
    # adaptive brownout controller: NORMAL → BROWNOUT → SHED_ONLY with
    # hysteresis on queue depth, queue age p95, and HBM headroom.
    # BROWNOUT=0/false/off disables (batcher falls back to the binary
    # depth/age sheds only).
    brownout: bool = field(
        default_factory=lambda: _env("BROWNOUT", "1").strip().lower()
        not in ("0", "false", "off")
    )
    # -- flight recorder + debug subjects (obs/recorder.py) ------------------
    # bounded ring of periodic batcher state frames, sampled by the owner
    # loop; anomaly-triggered dumps (engine restart, pool exhaustion,
    # SHED_ONLY entry, slow requests) write frames + event tail + trace to
    # OBS_DUMP_DIR. OBS_RECORDER=0 disables sampling and dumps entirely;
    # an empty OBS_DUMP_DIR keeps the in-memory ring (debug.snapshot still
    # serves it) but writes nothing to disk.
    obs_recorder: bool = field(
        default_factory=lambda: _env("OBS_RECORDER", "1").strip().lower()
        not in ("0", "false", "off")
    )
    obs_recorder_interval_ms: float = field(
        default_factory=lambda: float(_env("OBS_RECORDER_INTERVAL_MS", "250"))
    )
    obs_dump_dir: str = field(default_factory=lambda: _env("OBS_DUMP_DIR", "").strip())
    # deep-introspection subjects (lmstudio.debug.snapshot / .dump): off by
    # default — they expose slot tables and can force disk writes, so only
    # operators who opt in get them on the bus
    debug_subjects: bool = field(
        default_factory=lambda: _env("DEBUG_SUBJECTS", "0").strip().lower()
        in ("1", "true", "on")
    )
    # -- cluster membership + failover routing (serve/router.py) -------------
    # stable cluster identity: stamped on every reply (X-Worker-Id), in
    # adverts, prom labels, recorder frames, and the CONNECT name
    # (tpu-worker-<id> — the chaos harness's worker-scoped kill switch keys
    # on it). Empty WORKER_ID derives a short random id at startup.
    worker_id: str = field(default_factory=lambda: _env("WORKER_ID", ""))
    # period between lmstudio.cluster.adverts publishes; 0 disables the
    # advert loop (single-worker deployments lose nothing)
    cluster_advert_interval_s: float = field(
        default_factory=lambda: float(_env("CLUSTER_ADVERT_INTERVAL_S", "1.0"))
    )
    # graceful drain (lmstudio.admin.drain): in-flight decode gets this long
    # to finish after the queue subs are dropped; the remainder is failed
    # with the retryable draining envelope so peers absorb it
    drain_deadline_s: float = field(
        default_factory=lambda: float(_env("DRAIN_DEADLINE_S", "30"))
    )
    # router: an advert older than this marks the worker dead (dropped from
    # steering). Must comfortably exceed the advert interval.
    router_stale_after_s: float = field(
        default_factory=lambda: float(_env("ROUTER_STALE_AFTER_S", "5.0"))
    )
    # router: prompt-head chars hashed for prefix-cache locality steering
    # (0 disables locality; load-only steering remains)
    router_prefix_head_chars: int = field(
        default_factory=lambda: int(_env("ROUTER_PREFIX_HEAD_CHARS", "256"))
    )
    # -- disaggregated prefill/decode serving (serve/worker.py + router.py) ---
    # phase role for this worker: "" (monolithic, the default — prefill and
    # decode share the batcher), "prefill" (runs chunked prefill and exports
    # finished KV blocks over lmstudio.worker.<id>.kv_export; the role-aware
    # router never steers chats at it, though it stays in the queue group as
    # a degradation backstop), or "decode" (pulls exported blocks from its
    # paired prefill worker before serving, so the slot starts decoding with
    # zero prefill work; any transfer failure falls back to local prefill)
    worker_role: str = field(default_factory=lambda: _env("WORKER_ROLE", "").strip().lower())
    # wall budget for one KV transfer (the decode worker's pull of the
    # prefill worker's exported blocks); a timeout falls back to local
    # prefill and counts into lmstudio_kv_transfer_failures_total
    kv_transfer_timeout_s: float = field(
        default_factory=lambda: float(_env("KV_TRANSFER_TIMEOUT_S", "10"))
    )
    # per-message chunk size for direct NATS block transfers (must stay
    # under the broker max_payload; 256 KiB leaves generous header room)
    kv_transfer_chunk_bytes: int = field(
        default_factory=lambda: int(_env("KV_TRANSFER_CHUNK_BYTES", str(256 << 10)))
    )
    # blobs at or above this size ship via the JetStream Object Store
    # (one put + an object ref over the bus) instead of chunked publishes;
    # 0 disables the object-store path entirely (always chunked publishes)
    kv_transfer_objstore_bytes: int = field(
        default_factory=lambda: int(_env("KV_TRANSFER_OBJSTORE_BYTES", str(8 << 20)))
    )
    # -- OpenAI-compatible HTTP/SSE gateway (gateway/server.py) ---------------
    # bind address for ``python -m nats_llm_studio_tpu gateway``; loopback by
    # default — exposing the front door beyond the host is an explicit choice
    gateway_host: str = field(default_factory=lambda: _env("GATEWAY_HOST", "127.0.0.1"))
    gateway_port: int = field(default_factory=lambda: int(_env("GATEWAY_PORT", "8080")))
    # concurrent HTTP connections admitted before 503 (streaming responses
    # hold a connection for their whole decode, so this bounds gateway RAM
    # and protects the bus from connection storms)
    gateway_max_conn: int = field(
        default_factory=lambda: int(_env("GATEWAY_MAX_CONN", "256"))
    )
    # -- multi-tenant QoS (serve/qos.py, gateway auth + batcher fair share) ---
    # API-key table: comma-separated ``key:tenant:class[:weight[:rps
    # [:monthly_tokens]]]`` entries (class in batch|standard|premium; rps is
    # a per-key token-bucket rate, monthly_tokens a per-tenant completion
    # quota; 0/omitted = unlimited). Empty (the default) disables auth: the
    # gateway serves everyone as the anonymous standard tenant, exactly the
    # pre-QoS behavior.
    api_keys: str = field(default_factory=lambda: _env("API_KEYS", ""))
    # tenant-label cardinality cap for every Prometheus exposition (worker,
    # gateway, aggregator): the top-K tenants by volume keep their own rows,
    # the rest roll up into tenant="other" — a key-guessing client cannot
    # mint unbounded label values. 0 disables the cap.
    qos_tenant_topk: int = field(
        default_factory=lambda: int(_env("QOS_TENANT_TOPK", "8"))
    )
    # premium preempt-to-host-tier: a premium admit that finds the KV pool
    # full suspends the lowest-class victim slot to host RAM (resumed
    # bit-identically when pressure clears) before ever shedding. Off
    # restores class-blind victim selection (largest slot first).
    qos_preempt: bool = field(
        default_factory=lambda: _env("QOS_PREEMPT", "1").strip().lower()
        not in ("0", "false", "off")
    )
    # deficit-round-robin quantum in prompt tokens per round per unit of
    # class weight: smaller = tighter interleaving (fairness converges
    # faster), larger = longer per-tenant runs (better admit batching)
    qos_quantum_tokens: int = field(
        default_factory=lambda: int(_env("QOS_QUANTUM_TOKENS", "256"))
    )
    # -- cluster observability plane (obs/aggregator.py + obs/trace.py) -------
    # kill switch for cross-process span emission: when off, gateway/router/
    # worker skip publishing span batches to {prefix}.obs.spans entirely
    # (Traceparent headers still flow — they cost nothing)
    obs_spans: bool = field(
        default_factory=lambda: _env("OBS_SPANS", "1").strip().lower()
        not in ("0", "false", "off")
    )
    # fleet aggregator scrape cadence: how often the collector requests each
    # live worker's directed metrics.prom subject
    obs_scrape_interval_s: float = field(
        default_factory=lambda: float(_env("OBS_SCRAPE_INTERVAL_S", "2.0"))
    )
    # embed the fleet aggregator inside ``python -m nats_llm_studio_tpu
    # route`` (one fewer process for small clusters); the standalone
    # ``... obs`` subcommand ignores this knob and always runs one
    obs_aggregator: bool = field(
        default_factory=lambda: _env("OBS_AGGREGATOR", "0").strip().lower()
        in ("1", "true", "on")
    )
    # SLO objectives evaluated by the aggregator over fast/slow burn windows:
    # cluster TTFT p95 target (ms), slow window length (s; the fast window is
    # window/12 clamped to at least two scrape intervals), minimum
    # served-or-retryable ratio, and maximum shed rate
    slo_ttft_p95_ms: float = field(
        default_factory=lambda: float(_env("SLO_TTFT_P95_MS", "2000"))
    )
    slo_window_s: float = field(
        default_factory=lambda: float(_env("SLO_WINDOW_S", "60"))
    )
    slo_served_ratio: float = field(
        default_factory=lambda: float(_env("SLO_SERVED_RATIO", "0.99"))
    )
    slo_shed_ratio: float = field(
        default_factory=lambda: float(_env("SLO_SHED_RATIO", "0.05"))
    )
    # -- elastic autoscaling (serve/autoscaler.py, ISSUE 15) ------------------
    # embed the autoscaler inside ``route``/``obs`` (the standalone
    # ``... autoscale`` subcommand always runs one); spawns/drains local
    # worker subprocesses against the advert + SLO-burn signals
    obs_autoscale: bool = field(
        default_factory=lambda: _env("OBS_AUTOSCALE", "0").strip().lower()
        in ("1", "true", "on")
    )
    # fleet bounds: never drain below min, never spawn past max
    autoscale_min_workers: int = field(
        default_factory=lambda: int(_env("AUTOSCALE_MIN", "1"))
    )
    autoscale_max_workers: int = field(
        default_factory=lambda: int(_env("AUTOSCALE_MAX", "4"))
    )
    # control-loop cadence and hysteresis: pressure (SLO burn, deep queues,
    # brownout) must persist up_dwell before a spawn; calm must persist
    # down_dwell before a drain; cooldown blocks back-to-back actions
    autoscale_interval_s: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_INTERVAL_S", "1.0"))
    )
    autoscale_up_dwell_s: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_UP_DWELL_S", "2.0"))
    )
    autoscale_down_dwell_s: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_DOWN_DWELL_S", "15.0"))
    )
    autoscale_cooldown_s: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_COOLDOWN_S", "5.0"))
    )
    # queue-depth thresholds: mean advert depth at/above up_queue_depth is
    # pressure; total fleet depth at/below down_queue_depth is idle
    autoscale_up_queue_depth: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_UP_QUEUE_DEPTH", "8"))
    )
    autoscale_down_queue_depth: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_DOWN_QUEUE_DEPTH", "1"))
    )
    # spawn supervision: a spawned worker must advertise within grace_s or
    # it counts as a spawn failure; breaker_failures consecutive failures
    # open the circuit breaker for breaker_cooldown_s (no spawn storms)
    autoscale_spawn_grace_s: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_SPAWN_GRACE_S", "20"))
    )
    autoscale_breaker_failures: int = field(
        default_factory=lambda: int(_env("AUTOSCALE_BREAKER_FAILURES", "3"))
    )
    autoscale_breaker_cooldown_s: float = field(
        default_factory=lambda: float(_env("AUTOSCALE_BREAKER_COOLDOWN_S", "30"))
    )
    # hottest prefix-cache paths pushed to a replacement at drain/scale-up
    # (warm handoff); 0 disables handoff entirely
    autoscale_handoff_prefixes: int = field(
        default_factory=lambda: int(_env("AUTOSCALE_HANDOFF_PREFIXES", "4"))
    )

    def __post_init__(self) -> None:
        if self.admit_queue_limit < 0:  # unset: scale with the slot count
            self.admit_queue_limit = 4 * self.max_batch_slots
        if _env("PREFIX_CACHE", "").strip().lower() in ("0", "false", "off"):
            self.prefix_cache_blocks = 0
        if _env("SPEC_DECODE", "").strip().lower() in ("0", "false", "off"):
            self.spec_decode_k = 0
        if not self.worker_id:
            from .utils import next_nuid

            self.worker_id = f"w-{next_nuid()[-8:].lower()}"
        if self.worker_role not in ("", "prefill", "decode"):
            raise ValueError(
                f"WORKER_ROLE must be '', 'prefill' or 'decode', "
                f"got {self.worker_role!r}"
            )

    def configure_jax(self) -> None:
        """Apply process-wide JAX settings. Must run before the first
        compile (main.py calls it ahead of mesh construction); idempotent.

        Persistent XLA compile cache: a restarted worker (or an autoscaled
        replica on identical hardware) replays compiles from disk instead
        of paying the multi-second jit grid again. Where
        ``JAX_COMPILATION_CACHE_DIR`` is set JAX itself honours it and no
        directory is set here; otherwise the cache lives at one fixed path
        inside the checkout (the path is part of the cache key, so a
        directory that moves never hits). Library users who never call this
        lose nothing but the cache."""
        import jax

        from .obs.compile_cache import install_compile_cache_listener

        # the build ledger and the cache counters see every program from the
        # first one on (idempotent; Worker.start asks again for a worker
        # wired by hand)
        install_compile_cache_listener()
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
        # the serving grid is many sub-second programs (per-bucket
        # prefills, per-window chunks); cache all of them, not just
        # the slow ones, so a supervisor bounce replays the whole grid
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    # timeout ladder — mirrors the reference's per-op deadlines
    # (nats_llm_studio.go:229, :251, :289, :328)
    list_timeout_s: float = 30.0
    pull_timeout_s: float = 600.0
    delete_timeout_s: float = 120.0
    chat_timeout_s: float = 120.0

    def subject(self, op: str) -> str:
        return f"{self.subject_prefix}.{op}"
