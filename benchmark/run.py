"""One run of one benchmark cell, in one process that holds the chip.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets the cell's serving environment, substitutes seeded weights
(``lib/weights.py``), calls ``nats_llm_studio_tpu.main.start_serve(
embedded_broker=True)`` — the real WorkerConfig -> configure_jax -> broker ->
LocalRegistry -> Worker — connects with ``transport.connect``, checks the
served path against the plain reference, warms up, drives
``lmstudio.chat_model`` with ``"stream": true`` for ``--seconds``, prints one
JSON line, shuts down. Every earlier line of stdout is one JSON object that
says where the time went: a ``{"phase": ..., "begin": true, "t_s": ...}`` line
when a phase begins, a line with its ``seconds`` when it ends, each with the
seconds since process start, and ``run_s`` on the last. The driver stops a
run at 360 s; see ``benchmark/README.md``.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. ``--rehearse`` (never given by the driver)
lets the same code run on the CPU at a toy size: it prints ``"rehearsal"``
lines, no metric line, and exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

REHEARSAL_EXIT = 3


def emit(**kw) -> None:
    """One JSON line. A phase line says where the run stands: ``t_s`` is the
    seconds since the process started, so a run that is killed leaves its
    place behind."""
    if "phase" in kw:
        kw["t_s"] = round(time.perf_counter() - T_START, 3)
    print(json.dumps(kw), flush=True)


def begin(phase: str) -> float:
    """Say that a phase begins (its own line follows when it ends)."""
    emit(phase=phase, begin=True)
    return time.perf_counter()


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"benchfile_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """``BENCHMARK.json`` (or a manifest of the same shape) and the files it
    names. Traffic mixes, references and per-layer readers are found by NAME
    in the benchmark's directories and, for a manifest elsewhere, beside it:
    a new cell is new files plus new entries."""

    def __init__(self, path: Path):
        self.path = path
        self.data = json.loads(path.read_text())
        self.dirs = [BENCH] + ([path.parent] if path.parent not in (ROOT, BENCH) else [])

    def find(self, kind: str, name: str, suffixes: tuple[str, ...]) -> Path:
        for base in self.dirs:
            for suf in suffixes:
                p = base / kind / f"{name}{suf}"
                if p.exists():
                    return p
        raise FileNotFoundError(f"no {kind}/{name}{suffixes} under {[str(d) for d in self.dirs]}")

    def cell(self, workload: str) -> dict:
        cells = {w["name"]: w for w in self.data["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
        return cells[workload]

    def config(self, name: str) -> dict:
        entry = next(c for c in self.data["configs"] if c["name"] == name)
        return json.loads((ROOT / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads(self.find("traffic", name, (".json",)).read_text())

    def metrics(self, group: str, workload: str) -> list[dict]:
        return [m for m in self.data[group]
                if "workloads" not in m or workload in m["workloads"]]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


async def run_cell(args, man: Manifest, cell: dict, conf: dict, mix: dict) -> dict:
    """Everything between process start and the result. Returns the result
    object (without printing it)."""
    import jax

    from benchmark.lib import correct, model_files, weights
    from benchmark.lib.sources import CompileClock, memory_by_device
    from benchmark.lib.spans import RUN_DIR
    from benchmark.lib.stats import finite_ms
    from benchmark.lib.traffic import (Client, Generator, Load, Settle, dist_bounds, quantiles,
                                       reduce_client, warmup_lengths)
    from nats_llm_studio_tpu.main import start_serve
    from nats_llm_studio_tpu.transport import connect

    device = device_info()
    serving = conf["serving"]
    model_id = serving["model_id"]
    reference = load_module(man.find("references", conf["reference"], (".py",)))
    mcfg = reference.model_config(conf, int(serving["env"]["MAX_SEQ_LEN"]))

    # a scratch directory a process, removed at its end: two runs in one
    # checkout share the compile cache and nothing else
    scratch = RUN_DIR
    shutil.rmtree(scratch, ignore_errors=True)
    models_dir = scratch / "models"
    model_files.write_header_gguf(mcfg, model_id, models_dir)
    os.environ["LMSTUDIO_MODELS_DIR"] = str(models_dir)

    clock = CompileClock()
    # the schema is the program's own initialiser, or the one the reference
    # names for its family (``param_shapes``, with ``weight_gains`` beside it)
    builder = weights.install(args.seed, reference, conf.get("fixed_draws"))
    worker, shutdown = await start_serve(embedded_broker=True, port=0,
                                         store_dir=str(scratch / "store"))
    nc = await connect(worker.config.nats_url, name="benchmark")
    result: dict = {}
    try:
        client = Client(nc, model_id, float(mix.get("temperature", 0.8)))
        gen = Generator(mix, args.seed)
        wgen = Generator(mix, args.seed ^ 0x5EED)  # warm-up texts, another stream
        wu = mix.get("warmup", {})

        # -- load: the first request makes the registry load the model ------
        t0 = begin("load")
        first = await client.chat(wgen.make(64, 2))
        if not first.ok:
            raise RuntimeError(f"first request failed: {first.error}")
        eng = worker.registry.loaded_engines()[model_id]
        batcher = eng.batcher
        emit(phase="load", seconds=time.perf_counter() - t0,
             weights_s=builder.last_build.get("seconds"),
             weight_bytes=builder.last_build.get("bytes"),
             decode_kernel=getattr(batcher, "decode_kernel", None),
             max_slots=getattr(batcher, "max_slots", None),
             prefill_chunk=getattr(batcher, "prefill_chunk", None),
             compile_cache_dir=jax.config.jax_compilation_cache_dir, scratch=str(scratch),
             memory=memory_by_device())

        # -- probes (set-up): what the reference check will compare ----------
        # greedy, with logprobs, DECODE_TOKENS each: the first token out of
        # the prefill, the others decoded through the pool. Served now, on an
        # engine that holds nothing else; compared once the window has closed
        t0 = begin("probes")
        probes = [wgen.make(correct.PROBE_TOKENS, correct.DECODE_TOKENS)
                  for _ in range(correct.PROBES)]
        served = list(await asyncio.gather(*(
            client.chat(p, logprobs=correct.TOP_K, temperature=0.0) for p in probes)))
        # one more at the mix's median prompt length where that is more than
        # one prefill chunk of this engine (chunked prefill and a table of many
        # blocks come under the check; a shorter one would add a program to
        # build and nothing to see), after the others, from a stream of its own
        median_prompt = quantiles(mix["prompt_tokens"], 1)[0]
        if median_prompt > int(getattr(batcher, "prefill_chunk", None) or 0):
            probes.append(Generator(mix, args.seed ^ 0x10C6).make(
                median_prompt, correct.DECODE_TOKENS))
            served.append(await client.chat(probes[-1], logprobs=correct.TOP_K, temperature=0.0))
        for r in served:
            if not r.ok or len(r.logprobs) != r.max_tokens:
                raise RuntimeError(f"reference probe failed: {r.error or r.mismatch or 'no logprobs'}")
        emit(phase="probes", seconds=time.perf_counter() - t0,
             prompt_tokens=[p.prompt_tokens for p in probes], tokens=correct.DECODE_TOKENS)

        # -- warm-up 1: a sweep over the mix's own shapes --------------------
        # each length at the mix's widths while ``background`` long streams
        # keep the engine decoding, as it is all through the window: an admit
        # under live decode is another program than one on an idle engine
        t0 = begin("warmup_sweep")
        lo_out = int(wu.get("max_tokens", 9))
        n_sweep = 0

        async def sweep_round(n_prompt: int, width: int) -> None:
            nonlocal n_sweep
            recs = await asyncio.gather(*(
                client.chat(wgen.make(n_prompt, lo_out)) for _ in range(width)))
            n_sweep += len(recs)
            bad = [r.error or r.mismatch for r in recs if not r.ok or r.mismatch]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0]}")

        lengths = warmup_lengths(mix)
        # the background stream is as long as the mix's longest prompt, so
        # that every live round decodes at the widest window the mix reaches
        long_in = lengths[-1]
        long_out = int(serving["env"]["MAX_SEQ_LEN"]) - long_in - 32

        async def keep_live() -> None:
            while True:
                await client.chat(wgen.make(long_in, long_out))

        live = [asyncio.ensure_future(keep_live()) for _ in range(int(wu.get("background", 0)))]
        try:
            if live:
                await asyncio.sleep(0.5)
            for n_prompt in lengths:
                for c in wu.get("concurrency", [1]):
                    await sweep_round(n_prompt, int(c))
        finally:
            for t in live:
                t.cancel()
            if live:
                await asyncio.wait(live, timeout=30.0)
        emit(phase="warmup_sweep", seconds=time.perf_counter() - t0, requests=n_sweep,
             lengths=lengths, programs=len(clock.events))

        # -- warm-up 2: the mix itself, until no new program appears ---------
        # the window starts AT a send of the mix that ``Settle`` picks by its
        # number (lib/traffic.py): the same point of the mix's sequence and
        # the same phase of the decode bursts in every run
        t0 = begin("warmup_settle")
        settle = client.settle = Settle(
            wu, t0, lambda: clock.events[-1][0] if clock.events else 0.0)
        load = Load(client, gen)
        load.start()
        w0 = await settle.wait()
        client.settle = None
        emit(phase="warmup_settle", seconds=time.perf_counter() - t0, **settle.line(),
             programs=len(clock.events), compile=clock.summary())

        # -- the window ------------------------------------------------------
        begin("window")
        stats0 = _stat_snapshot(batcher)
        w1 = w0 + args.seconds
        setup_s = w0 - T_START
        samples: list[dict] = []
        trace_dir = scratch / "trace"
        traced = None
        if args.trace:
            traced = asyncio.ensure_future(
                _traced_window(w0, w1, trace_dir, batcher, samples))
        await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
        stats1 = _stat_snapshot(batcher)
        in_window = clock.between(w0, w1)
        trace_span = await traced if traced is not None else None
        await load.stop(w0)
        mem = memory_by_device()

        # -- reduce ----------------------------------------------------------
        cm = reduce_client(client.records, w0, w1)
        emit(phase="window", setup_s=setup_s, programs_in_window=in_window,
             work=_work(stats0, stats1), **{k: v for k, v in cm.items() if k != "mismatches"})

        # -- reference check: once the window has closed and the peak is read -
        # one float32 forward per probe, and per request of a sample of the
        # greedy ones the window itself finished, over its prompt and served
        # tokens; every forward is padded to one (T, N), so ONE program
        t0 = begin("reference")
        sample = correct.window_sample(client.records, w0, w1, args.seed)
        # [prompt + served tokens but the last, served tokens] of each
        probe_io = [(list(_rendered(p.prompt).encode()) + correct.served_tokens(r.logprobs)[:-1],
                     correct.served_tokens(r.logprobs)) for p, r in zip(probes, served)]
        sample_io = [(list((_rendered(r.prompt) + r.text[:-1]).encode()),
                      list(r.text.encode())) for r in sample]
        hi_prompt, hi_out = (dist_bounds(mix[k])[1] for k in ("prompt_tokens", "output_tokens"))
        longest = max([hi_prompt + hi_out] + [len(toks) for toks, _ in probe_io])
        pad = (-(-longest // 128) * 128, max(correct.DECODE_TOKENS, hi_out))

        def forward(toks, out, **kw):
            return reference.tail_logprobs(batcher.params, conf, toks, len(out), pad_to=pad, **kw)

        probe_ref = [forward(toks, out) for toks, out in probe_io]
        sample_ref = [forward(toks, out) for toks, out in sample_io]
        ref_check = correct.compare_probes(
            [(ref, r.logprobs) for ref, r in zip(probe_ref, served)])
        win_check = correct.compare_window(
            [(ref, out) for ref, (_, out) in zip(sample_ref, sample_io)])
        emit(phase="reference", seconds=time.perf_counter() - t0, pad_to=pad,
             **ref_check, window=dict(
                 win_check, greedy_finished=sum(
                     r.ok and r.temperature == 0.0 and w0 <= (r.t_done or 0) < w1
                     for r in client.records),
                 sampled=[[r.prompt_tokens, r.max_tokens] for r in sample]))
        if args.control:
            # never in the driver's runs: the reference at the precision below
            # the served one, in the served path's place, at the same prompts
            # and tokens; every limit must tell it from the served path
            t0 = begin("control")
            low = [forward(toks, out, lower=args.control) for toks, out in probe_io + sample_io]
            emit(phase="control", seconds=time.perf_counter() - t0, lower=args.control,
                 **correct.compare_probes([
                     (ref, correct.entries_of(lp)) for ref, lp in zip(probe_ref, low)]),
                 window=correct.compare_window([
                     (ref, [int(i) for i in lp.argmax(-1)])
                     for ref, lp in zip(sample_ref, low[len(probe_io):])]))

        problems = list(cm["mismatches"][:3])
        if not ref_check["ok"]:
            problems.append(f"reference check outside its tolerance: {ref_check}")
        if not win_check["ok"]:
            problems.append(f"the window's own requests outside their tolerance: {win_check}")
        if in_window:
            problems.append(f"programs built inside the window: {in_window}")
        want_kernel = serving.get("require_decode_kernel")
        if want_kernel and getattr(batcher, "decode_kernel", None) != want_kernel:
            problems.append(f"decode kernel {getattr(batcher, 'decode_kernel', None)!r}, "
                            f"the configuration requires {want_kernel!r}")
        if cm["attempted"] == 0 or cm["out_tokens"] == 0:
            problems.append("nothing was served inside the window")
        if problems:
            emit(phase="problems", problems=problems)

        values = {
            "out_tok_s": cm["out_tok_s"],
            "ttft_p50_ms": finite_ms(cm["ttft_p50_s"]),
            "gap_p95_ms": finite_ms(cm["gap_p95_s"]),
            "setup_s": setup_s,
        }
        dev_out = dict(device, memory_peak_bytes=max(
            (d["peak_bytes_in_use"] or 0) for d in mem))
        # each number compared beside its limit: main() prints it again as the
        # last lines of standard error, where the driver's record of a run ends
        result = {"correct": not problems, "attempted": cm["attempted"],
                  "failed": cm["failed"], "metrics": {}, "device": dev_out,
                  # where the window started and which condition closed the
                  # settle phase: the file's count and ``count`` in a sound run
                  "settle_sends": settle.sends, "settle_closed_by": settle.closed_by,
                  "compared": correct.compared(ref_check, win_check) + [
                      f"problem: {p}" for p in problems]}
        if not args.trace:
            for m in man.metrics("end_to_end", cell["name"]):
                v = values[m["name"]]
                if v is None or (isinstance(v, float) and math.isnan(v)):
                    raise RuntimeError(f"no value for end-to-end metric {m['name']}")
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            from benchmark.lib import reduce_trace, spans

            begin("trace")
            trace = reduce_trace.reduce(
                reduce_trace.load_planes(reduce_trace.find_xplane(str(trace_dir))))
            if not trace.get("busy_s") and not args.rehearse:
                raise RuntimeError("the trace holds no device operation")
            src = {
                "client": cm, "records": client.records, "window": (w0, w1),
                "span": (trace_span["t_on"], trace_span["t_off"]),
                "stats_before": stats0, "stats_after": stats1, "samples": samples,
                "trace": trace, "config": conf, "traffic": mix, "cell": cell,
                "device": device, "env": dict(serving["env"]),
                "engine": {"decode_burst": getattr(batcher, "decode_burst", None),
                           "decode_kernel": getattr(batcher, "decode_kernel", None),
                           "max_slots": getattr(batcher, "max_slots", None)},
            }
            # ``bursts``: what the decode bursts counted over the window and
            # over the traced span, whose device times the rooflines price
            emit(phase="trace", span=trace_span, bursts={
                "window": spans.readback_sums(src, w0, w1),
                "span": spans.readback_sums(src, *spans.traced_span(src))}, **{
                k: trace.get(k) for k in ("device_planes", "window_s", "busy_s",
                                          "programs", "longest_gap_s")})
            unread = []
            for m in man.metrics("per_layer", cell["name"]):
                reader = load_module(man.find("layer_metrics", m["name"], (".py",)))
                v = reader.read(src)
                if v is not None and not (isinstance(v, float) and math.isnan(v)):
                    result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
                else:
                    unread.append(m["name"])
            if unread:
                # the driver's check wants every metric the cell lists: one
                # that finds nothing to read in a cell does not belong to it
                emit(phase="unread", metrics=unread)
            dev_out |= {"busy_s": trace.get("busy_s"), "window_s": trace.get("window_s")}
            result["breakdown"] = {"device_ops": trace.get("device_ops", []),
                                   "idle_gaps": trace.get("idle_gaps", [])}
    finally:
        t0 = begin("shutdown")
        await nc.close()
        await asyncio.wait_for(shutdown(), timeout=60.0)
        shutil.rmtree(scratch, ignore_errors=True)
        emit(phase="shutdown", seconds=time.perf_counter() - t0,
             run_s=time.perf_counter() - T_START)
    return result


def _rendered(prompt: str) -> str:
    """The prompt as the header's chat template renders it."""
    return f"<|user|>{prompt}<|assistant|>"


WORK_COUNTERS = ("experts_hit", "expert_steps", "state_rows", "state_steps")


def _work(before: dict, after: dict) -> dict:
    """The window's own work by the program's counters: steps, tokens a step,
    and per step the experts a layer streamed and the rows whose state moved."""
    d = {k: after[k] - before[k] for k in ("tokens", "steps")}
    d |= {k: v - before["work"].get(k, 0) for k, v in after["work"].items()}
    out = {"steps": d["steps"], "tokens_per_step": d["tokens"] / d["steps"] if d["steps"] else None}
    if d.get("expert_steps"):
        out["experts_hit_avg"] = d["experts_hit"] / d["expert_steps"]
    if d.get("state_steps"):
        out["rows_live_avg"] = d["state_rows"] / d["state_steps"]
    return out


def _stat_snapshot(batcher) -> dict:
    """The program's counters at one instant: ``BatcherStats`` counts and
    histograms (host clock around work that ends in a readback)."""
    st = batcher.stats
    return {
        "tokens": st.tokens, "steps": st.steps, "requests": st.requests,
        "shed": st.shed,
        # what a family's decode bursts counted, where it counts: whether two
        # seeds did the same work shows in an untraced run too
        "work": {k: getattr(st, k) for k in WORK_COUNTERS if hasattr(st, k)},
        "hist": {name: getattr(st, name).snapshot()
                 for name in ("decode_step_ms", "prefill_ms", "admit_delay_ms")},
    }


async def _traced_window(w0: float, w1: float, trace_dir: Path, batcher, samples: list) -> dict:
    """The traced run's extras: a pool sample each second, and the profiler
    on for a few seconds in the middle of the window."""
    import jax

    span = min(4.0, (w1 - w0) / 3.0)
    t_on = w0 + (w1 - w0 - span) / 2.0
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    loop = asyncio.get_running_loop()
    started = stopped = t_off = None
    while time.perf_counter() < w1:
        now = time.perf_counter()
        samples.append({"t": now, "pool": batcher.pool_stats()})
        if started is None and now >= t_on:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            started = time.perf_counter()
        # wake at the next second, or when the trace is due to start or stop
        wake = [now + 1.0, w1] + ([t_on] if started is None else []) + (
            [started + span] if started is not None and stopped is None else [])
        await asyncio.sleep(max(0.0, min(wake) - time.perf_counter()))
        if started is not None and stopped is None and time.perf_counter() >= started + span:
            # stop_trace writes the file: seconds of host work, so off the loop
            t_off = time.perf_counter()
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            stopped = time.perf_counter()
    if started is not None and stopped is None:
        t_off = time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        stopped = time.perf_counter()
    # ``t_on`` .. ``t_off``: the traced span on the host's clock, for the
    # readers that price a device time by the span's own bursts
    return {"trace_on_s": started - w0 if started else None,
            "trace_len_s": (stopped - started) if started else None,
            "t_on": started, "t_off": t_off}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", type=Path, default=ROOT / "BENCHMARK.json")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU: prints rehearsal lines, no result, exits 3")
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="also put the reference at this lower precision in the served "
                         "path's place and print what every limit reads of it")
    ap.add_argument("--env", action="append", default=[], metavar="KEY=VALUE",
                    help="override the configuration's serving env: the program's own "
                         "lower-precision paths as a control, never a cell")
    args = ap.parse_args(argv)

    man = Manifest(args.manifest.resolve())
    cell = man.cell(args.workload)
    conf = man.config(cell["config"])
    mix = man.traffic(cell["traffic"])
    # the cell's serving environment: the configuration's existing knobs
    conf["serving"]["env"] |= dict(kv.split("=", 1) for kv in args.env)
    for k, v in conf["serving"]["env"].items():
        os.environ[k] = str(v)
    # one fixed compile-cache directory inside the checkout (the path is part
    # of the cache key); the program honours the variable and sets no other
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(BENCH / ".cache" / "jax"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    device = device_info()
    on_chip = device["platform"] == "tpu" and device["count"] == int(cell["chips"])
    if not on_chip and not args.rehearse:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU chip(s), "
              f"JAX found {device}", file=sys.stderr)
        return 2

    result = asyncio.run(run_cell(args, man, cell, conf, mix))
    sys.stdout.flush()
    print("\n".join(result["compared"]), file=sys.stderr, flush=True)
    if not on_chip:
        emit(rehearsal=True, note="CPU run: no number below is a measurement",
             would_print=result)
        return REHEARSAL_EXIT
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code:
            print(e.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 — report, then leave without hanging on threads
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # batcher owner threads and broker tasks are stopped by shutdown();
    # leave without waiting on whatever daemon thread a library keeps
    os._exit(code)
