"""Worker process entrypoint.

The reference is a library with no ``main()`` — its README tells embedders to
wire config/connect/subscribe themselves (SURVEY.md §1 "critical structural
fact"). This CLI is that wiring, made first-class:

    python -m nats_llm_studio_tpu serve            # worker against NATS_URL
    python -m nats_llm_studio_tpu serve --embedded-broker [--port 4222]
    python -m nats_llm_studio_tpu broker --port 4222 [--store-dir ./nats_data]
    python -m nats_llm_studio_tpu route                # standalone cluster router
    python -m nats_llm_studio_tpu gateway [--port 8080]  # OpenAI-compatible HTTP front door
    python -m nats_llm_studio_tpu obs                  # fleet metrics/trace aggregator
    python -m nats_llm_studio_tpu autoscale            # elastic worker autoscaler
    python -m nats_llm_studio_tpu publish <model.gguf> <publisher>/<name>
    python -m nats_llm_studio_tpu chat <model_id> "prompt..."

Env contract (reference README.md:489-494, minus the LM Studio URL):
NATS_URL, LMSTUDIO_MODELS_DIR, NATS_QUEUE_GROUP, plus MESH_SHAPE (legacy
alias TPU_MESH; default "auto" = all local devices on tp), MAX_BATCH_SLOTS,
MAX_SEQ_LEN. The persistent XLA compile cache lives where JAX's own
JAX_COMPILATION_CACHE_DIR says, else at a fixed path inside the checkout
(config.configure_jax). Multi-host meshes initialize through
``jax.distributed`` when JAX_COORDINATOR_ADDRESS is set. One worker process
owns all of a host's chips: ``serve`` refuses to start on a CPU backend that
JAX_PLATFORMS did not ask for.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal
import sys

from .config import WorkerConfig

log = logging.getLogger("nats_llm_studio_tpu")


def _maybe_init_distributed() -> None:
    """Join a multi-host DCN mesh when coordinator env vars are present
    (SURVEY.md §5 distributed-backend: jax.distributed + PJRT over DCN)."""
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not addr:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=int(os.environ.get("JAX_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
    )
    log.info("joined distributed mesh: %d devices", len(jax.devices()))


def _require_requested_backend() -> None:
    """Refuse to serve from a backend nobody asked for. When the
    accelerator's runtime fails to initialise (another process owns the
    chip, a broken install) JAX falls back to the CPU with a warning; a
    worker would then answer requests in float32 with every Pallas kernel
    in interpret mode. Serving from the CPU is a choice: JAX_PLATFORMS
    must name it."""
    import jax

    backend = jax.default_backend()
    asked = (jax.config.jax_platforms or "").lower().split(",")
    if backend == "cpu" and "cpu" not in asked:
        raise SystemExit(
            f"serve: JAX initialised the {backend!r} backend, which was not "
            f"asked for (JAX_PLATFORMS={jax.config.jax_platforms or ''!r}) — "
            "the accelerator is missing or held by another process. Set "
            "JAX_PLATFORMS=cpu to serve from the CPU on purpose."
        )


async def start_serve(embedded_broker: bool = False, port: int = 4222,
                      store_dir: str | None = None):
    """Everything ``serve`` does up to the started worker: config from the
    environment, JAX settings, optional embedded broker, mesh, store,
    registry, ``Worker.start()``. Returns ``(worker, shutdown)``;
    ``await shutdown()`` drains the worker and closes what this opened."""
    from .serve import Worker
    from .serve.registry import LocalRegistry
    from .store import JetStreamStoreModule, ModelStore
    from .transport import EmbeddedBroker, connect
    from .transport import faults
    from .transport.jetstream import ObjectStore

    cfg = WorkerConfig()
    # process-wide JAX knobs (persistent compile cache) must land before
    # the first compile — i.e. before mesh build and any engine load
    cfg.configure_jax()
    # deterministic chaos harness (transport/faults.py): only active when
    # CHAOS_SPEC is set — zero-cost otherwise
    plan = faults.plan_from_env()
    if plan is not None:
        faults.install(plan)
    broker = None
    if embedded_broker:
        broker = await EmbeddedBroker(port=port).start()
        JetStreamStoreModule(broker, store_dir=store_dir).install()
        cfg.nats_url = broker.url
        log.info("embedded broker on %s", broker.url)

    _maybe_init_distributed()
    _require_requested_backend()
    from .parallel import serving_mesh

    mesh = serving_mesh(cfg.mesh_shape)
    if mesh is not None:
        log.info("mesh: %s", dict(mesh.shape))
    else:
        log.info("mesh: none (single device or MESH_SHAPE=off)")

    nc = await connect(cfg.nats_url, name="store-client")
    schemes = tuple(s for s in cfg.url_pull_schemes.split(",") if s)
    store = ModelStore(cfg.models_dir, objstore=ObjectStore(nc), bucket=cfg.bucket,
                       url_schemes=schemes, max_url_pull_bytes=cfg.max_url_pull_bytes)
    registry = LocalRegistry(
        store, mesh=mesh, max_seq_len=cfg.max_seq_len, max_batch_slots=cfg.max_batch_slots,
        quant=cfg.quant_mode, kv_quant=cfg.kv_quant_mode,
        wquant_group=cfg.wquant_group,
        admit_queue_limit=cfg.admit_queue_limit, admit_max_age_ms=cfg.admit_max_age_ms,
        prefix_cache_blocks=cfg.prefix_cache_blocks,
        spec_decode_k=cfg.spec_decode_k, spec_max_active=cfg.spec_max_active,
        brownout=cfg.brownout,
        kv_paged=cfg.kv_paged, kv_block_tokens=cfg.kv_block_tokens,
        kv_pool_blocks=cfg.kv_pool_blocks,
        kv_host_pool_bytes=cfg.kv_host_pool_bytes,
        restart_backoff_s=cfg.engine_restart_backoff_s,
        restart_backoff_max_s=cfg.engine_restart_backoff_max_s,
        max_restarts=cfg.engine_max_restarts,
        restart_window_s=cfg.engine_restart_window_s,
        obs_recorder=cfg.obs_recorder,
        obs_recorder_interval_ms=cfg.obs_recorder_interval_ms,
        obs_dump_dir=cfg.obs_dump_dir,
        worker_id=cfg.worker_id,
        qos_quantum_tokens=cfg.qos_quantum_tokens,
        qos_preempt=cfg.qos_preempt,
    )
    worker = Worker(cfg, registry)
    await worker.start()
    log.info("worker serving %s.* on %s (role: %s, models: %s)",
             cfg.subject_prefix, cfg.nats_url, cfg.worker_role or "monolithic",
             cfg.models_dir)

    async def shutdown() -> None:
        log.info("draining...")
        await worker.drain()
        for eng in registry.loaded_engines().values():
            await eng.unload()  # stop the batcher owner threads
        await nc.close()
        if broker is not None:
            await broker.stop()

    return worker, shutdown


async def _run_serve(args: argparse.Namespace) -> None:
    _, shutdown = await start_serve(args.embedded_broker, args.port, args.store_dir)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await shutdown()


async def _run_broker(args: argparse.Namespace) -> None:
    from .store import JetStreamStoreModule
    from .transport import EmbeddedBroker

    broker = await EmbeddedBroker(port=args.port).start()
    JetStreamStoreModule(broker, store_dir=args.store_dir).install()
    log.info("broker on %s (store: %s)", broker.url, args.store_dir or "memory")
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await broker.stop()


async def _run_route(args: argparse.Namespace) -> None:
    """Standalone cluster router (serve/router.py): subscribes to worker
    adverts and forwards ``{prefix}.route.chat_model`` requests to the best
    live worker. Clients that import this package should prefer the
    in-process ClusterRouter; this process serves everyone else."""
    from .serve.router import RouterProcess
    from .transport import RetryPolicy, connect

    cfg = WorkerConfig()
    nc = await connect(cfg.nats_url, name="tpu-router")
    proc = RouterProcess(
        nc,
        prefix=cfg.subject_prefix,
        stale_after_s=cfg.router_stale_after_s,
        prefix_head_chars=cfg.router_prefix_head_chars,
        chat_timeout_s=cfg.chat_timeout_s,
        retry=RetryPolicy(max_attempts=args.max_attempts, retry_on_timeout=True),
    )
    await proc.start()
    scaler = None
    if cfg.obs_autoscale:
        # OBS_AUTOSCALE=1 embeds the elastic control loop in the router
        # process (serve/autoscaler.py); it shares the connection
        from .serve import Autoscaler

        scaler = Autoscaler.from_config(nc, cfg)
    agg = None
    if cfg.obs_aggregator:
        # OBS_AGGREGATOR=1 embeds the fleet collector in the router process
        # (one fewer process for small clusters); it shares the connection
        from .obs import Aggregator

        agg = Aggregator(
            nc,
            prefix=cfg.subject_prefix,
            scrape_interval_s=cfg.obs_scrape_interval_s,
            stale_after_s=cfg.router_stale_after_s,
            slo_ttft_p95_ms=cfg.slo_ttft_p95_ms,
            slo_window_s=cfg.slo_window_s,
            slo_served_ratio=cfg.slo_served_ratio,
            slo_shed_ratio=cfg.slo_shed_ratio,
            # a co-tenant autoscaler's families ride the cluster exposition
            extra_expositions=(
                [scaler.render_prometheus] if scaler is not None else None
            ),
        )
        await agg.start()
    if scaler is not None:
        await scaler.start()
    log.info("router on %s (prefix %s%s%s)", cfg.nats_url, cfg.subject_prefix,
             ", embedded aggregator" if agg is not None else "",
             ", embedded autoscaler" if scaler is not None else "")
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    if scaler is not None:
        await scaler.stop()
    if agg is not None:
        await agg.stop()
    await proc.stop()
    await nc.close()


async def _run_obs(args: argparse.Namespace) -> None:
    """Standalone fleet observability collector (obs/aggregator.py): ingests
    cluster adverts and span batches, scrapes every live worker's directed
    metrics.prom subject, serves the merged cluster exposition on
    ``{prefix}.cluster.metrics.prom`` and assembled traces on
    ``{prefix}.debug.trace.<trace_id>``, and emits slo_burn events."""
    from .obs import Aggregator
    from .transport import connect

    cfg = WorkerConfig()
    nc = await connect(cfg.nats_url, name="tpu-obs")
    scaler = None
    if cfg.obs_autoscale:
        from .serve import Autoscaler

        scaler = Autoscaler.from_config(nc, cfg)
    agg = Aggregator(
        nc,
        prefix=cfg.subject_prefix,
        scrape_interval_s=cfg.obs_scrape_interval_s,
        stale_after_s=cfg.router_stale_after_s,
        slo_ttft_p95_ms=cfg.slo_ttft_p95_ms,
        slo_window_s=cfg.slo_window_s,
        slo_served_ratio=cfg.slo_served_ratio,
        slo_shed_ratio=cfg.slo_shed_ratio,
        extra_expositions=(
            [scaler.render_prometheus] if scaler is not None else None
        ),
    )
    await agg.start()
    if scaler is not None:
        await scaler.start()
    log.info("aggregator on %s (prefix %s, scrape %.1fs%s)",
             cfg.nats_url, cfg.subject_prefix, cfg.obs_scrape_interval_s,
             ", embedded autoscaler" if scaler is not None else "")
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    if scaler is not None:
        await scaler.stop()
    await agg.stop()
    await nc.close()


async def _run_autoscale(args: argparse.Namespace) -> None:
    """Standalone elastic autoscaler (serve/autoscaler.py): watches worker
    adverts and slo_burn events, spawns/drains local worker subprocesses
    within [AUTOSCALE_MIN, AUTOSCALE_MAX], and serves its decision counters
    on ``{prefix}.autoscale.metrics.prom``. OBS_AGGREGATOR=1 co-hosts the
    fleet collector so one process is a complete control plane."""
    from .serve import Autoscaler
    from .transport import connect

    cfg = WorkerConfig()
    nc = await connect(cfg.nats_url, name="tpu-autoscaler")
    scaler = Autoscaler.from_config(nc, cfg)
    agg = None
    if cfg.obs_aggregator:
        from .obs import Aggregator

        agg = Aggregator(
            nc,
            prefix=cfg.subject_prefix,
            scrape_interval_s=cfg.obs_scrape_interval_s,
            stale_after_s=cfg.router_stale_after_s,
            slo_ttft_p95_ms=cfg.slo_ttft_p95_ms,
            slo_window_s=cfg.slo_window_s,
            slo_served_ratio=cfg.slo_served_ratio,
            slo_shed_ratio=cfg.slo_shed_ratio,
            extra_expositions=[scaler.render_prometheus],
        )
        await agg.start()
    await scaler.start()
    log.info("autoscaler on %s (prefix %s, bounds [%d, %d]%s)",
             cfg.nats_url, cfg.subject_prefix, scaler.min_workers,
             scaler.max_workers,
             ", embedded aggregator" if agg is not None else "")
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await scaler.stop()
    if agg is not None:
        await agg.stop()
    await nc.close()


async def _run_gateway(args: argparse.Namespace) -> None:
    """OpenAI-compatible HTTP/SSE front door (gateway/server.py): serves
    /v1/chat/completions, /v1/models, and /healthz over the steered cluster
    router, so unmodified OpenAI clients reach the worker cluster."""
    from .gateway import Gateway
    from .transport import RetryPolicy, connect

    cfg = WorkerConfig()
    nc = await connect(cfg.nats_url, name="tpu-gateway")
    gw = Gateway(
        nc,
        prefix=cfg.subject_prefix,
        host=args.host or cfg.gateway_host,
        port=cfg.gateway_port if args.port is None else args.port,
        max_conn=cfg.gateway_max_conn,
        chat_timeout_s=cfg.chat_timeout_s,
        retry=RetryPolicy(max_attempts=args.max_attempts, retry_on_timeout=True),
        stale_after_s=cfg.router_stale_after_s,
        prefix_head_chars=cfg.router_prefix_head_chars,
        api_keys=cfg.api_keys,
        tenant_topk=cfg.qos_tenant_topk,
    )
    await gw.start()
    log.info("gateway on http://%s:%d (bus %s, prefix %s)",
             gw.host, gw.port, cfg.nats_url, cfg.subject_prefix)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await gw.stop()
    await nc.close()


async def _run_publish(args: argparse.Namespace) -> None:
    from .store import ModelStore
    from .transport import connect
    from .transport.jetstream import ObjectStore

    cfg = WorkerConfig()
    nc = await connect(cfg.nats_url)
    store = ModelStore(cfg.models_dir, objstore=ObjectStore(nc), bucket=cfg.bucket)
    store.import_file(args.gguf, args.model_id)
    obj = await store.publish_model(args.model_id)
    print(f"published {obj} to bucket {cfg.bucket!r}")
    await nc.close()


async def _run_chat(args: argparse.Namespace) -> None:
    from .transport import connect

    cfg = WorkerConfig()
    nc = await connect(cfg.nats_url)
    payload = {
        "model": args.model_id,
        "messages": [{"role": "user", "content": args.prompt}],
        "max_tokens": args.max_tokens,
        "temperature": args.temperature,
        "stream": args.stream,
    }
    body = json.dumps(payload).encode()
    subject = cfg.subject("chat_model")
    if args.stream:
        async for msg in nc.request_stream(subject, body, timeout=cfg.chat_timeout_s):
            r = json.loads(msg.payload)
            if (msg.headers or {}).get("Nats-Stream-Done"):
                if not r.get("ok"):
                    print(f"\nerror: {r.get('error')}", file=sys.stderr)
                print()
                break
            delta = r["data"]["chunk"]["choices"][0]["delta"].get("content", "")
            print(delta, end="", flush=True)
    else:
        msg = await nc.request(subject, body, timeout=cfg.chat_timeout_s)
        r = json.loads(msg.payload)
        if not r.get("ok"):
            print(f"error: {r.get('error')}", file=sys.stderr)
            sys.exit(1)
        print(r["data"]["response"]["choices"][0]["message"]["content"])
    await nc.close()


def main(argv: list[str] | None = None) -> None:
    logging.basicConfig(
        level=os.environ.get("LOG_LEVEL", "INFO"),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    p = argparse.ArgumentParser(prog="nats-llm-studio-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("serve", help="run a TPU worker")
    sp.add_argument("--embedded-broker", action="store_true")
    sp.add_argument("--port", type=int, default=4222)
    sp.add_argument("--store-dir", default=None)

    bp = sub.add_parser("broker", help="run the embedded NATS broker + object store")
    bp.add_argument("--port", type=int, default=4222)
    bp.add_argument("--store-dir", default="./nats_data")

    rp = sub.add_parser("route", help="run a standalone cluster router")
    rp.add_argument("--max-attempts", type=int, default=3)

    sub.add_parser("obs", help="run the fleet metrics/trace aggregator")

    sub.add_parser("autoscale", help="run the elastic worker autoscaler")

    gw = sub.add_parser("gateway", help="run the OpenAI-compatible HTTP gateway")
    gw.add_argument("--host", default=None)
    gw.add_argument("--port", type=int, default=None)
    gw.add_argument("--max-attempts", type=int, default=3)

    pp = sub.add_parser("publish", help="import a GGUF and upload it to the bucket")
    pp.add_argument("gguf")
    pp.add_argument("model_id")

    cp = sub.add_parser("chat", help="send a chat request over NATS")
    cp.add_argument("model_id")
    cp.add_argument("prompt")
    cp.add_argument("--max-tokens", type=int, default=256)
    cp.add_argument("--temperature", type=float, default=0.8)
    cp.add_argument("--stream", action="store_true")

    args = p.parse_args(argv)
    runner = {
        "serve": _run_serve,
        "broker": _run_broker,
        "route": _run_route,
        "gateway": _run_gateway,
        "obs": _run_obs,
        "autoscale": _run_autoscale,
        "publish": _run_publish,
        "chat": _run_chat,
    }[args.cmd]
    asyncio.run(runner(args))


if __name__ == "__main__":
    main()
