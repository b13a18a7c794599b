"""The one general traffic generator. A traffic mix is a data file
(``benchmark/traffic/<name>.json``) of parameters; nothing here knows a mix
by name. It drives closed loops, which is what every cell of the benchmark
is; a PR that adds an open-loop, bursty or shared-prefix cell brings that
generator code with its chip runs (PERF.md section 7).

Parameters of a mix:

    loop            "closed": ``callers`` clients, each sends its next request
                    when its reply ends
    callers         number of clients
    prompt_tokens   <dist>: whole prompt as the engine counts it (template included)
    output_tokens   <dist>: max_tokens of each request; a reply must run to it
    temperature     sampling temperature; each request carries its own seed
    greedy_every    optional k: every k-th request of the sequence is sent at
                    temperature 0 (the same positions of the sequence for
                    every seed). ``correct`` holds a sample of the requests a
                    window finished to the plain reference by the served
                    tokens' ids, which is valid for greedy tokens only; the
                    row's temperature is an input of the decode program the
                    others run too, so a greedy row costs what a sampled one
                    does
    deck            how many requests one shuffled deck holds (default 64)
    order_seed      optional: the order of sizes comes from this number, not
                    from --seed, which then decides only the texts, the
                    sampling seeds and the weights. For mixes whose window
                    holds a few tens of requests: there the order alone moved
                    tokens/s by 4-5 % from seed to seed while two runs of one
                    seed agreed to 0.1-0.3 % (PR 23, call 6)
    warmup          {"concurrency": [..], "background": k, "max_tokens": n,
                     "quiet_s": s, "min_settle_s": s, "max_settle_s": s,
                     "settle_requests": n, "sends_in_min_settle": m}: the
                    sweep's widths, how many long streams keep the engine live
                    under it, and where the settle phase ends and the window
                    starts (``Settle``).

Where the window starts (``Settle``; the rule every mix's author has to size):
the window's first send is send number ``settle_requests`` of the mix (that
many went out before it), decided AT that send and not by a clock that polls,
so the window starts at a fixed point of the mix's sequence and at the same
phase of the decode bursts in every run. A count fixes the point of the
sequence, a time does not: with a time binding, one run in five started a
send early or late and read tokens/s 0.5-1.4 % off (PR 28). The send still
has to come after ``min_settle_s`` and after ``quiet_s`` without a new
program; where it comes too early (the program got faster since the count was
sized), the first send after both times starts the window, and timing picks
it. There is no stepping on by whole decks: the decks' orders differ, and a
start a deck on read 2.3 % lower on each of two seeds (PR 39), which between a
parent and a change would be a false loss where a picked start is only noise.

A run says what happened on its ``warmup_settle`` line and in its result
line: ``sends`` / ``settle_sends`` (the index of the window's first send) and
``closed_by`` / ``settle_closed_by``: ``count`` (the count came last: the ONLY
reading of a sound run, with ``sends`` equal to the file's count), ``clock``
or ``quiet`` (that condition came after the count, and timing picked the
start), ``max`` (``max_settle_s`` ran out). ``sends_in_min_settle`` on the
line is how many sends the replay had made when ``min_settle_s`` passed; the
file keeps the figure it was sized from beside the count, and
``benchmark/tests/test_traffic.py`` holds every committed mix to
``settle_requests >= 1.05 x sends_in_min_settle``. To re-size a count after a
gain (a ``benchmark`` PR: the file is the yardstick's): read
``sends_in_min_settle`` off a few runs, set ``settle_requests`` to about 1.4 x
that (a whole number of decks where that is near), write the new figure
beside it, and re-measure the cell: its window is another 30 s of the mix.

A <dist> is {"dist": "loguniform", "min": a, "max": b} or
{"dist": "fixed", "value": v}.

Every seed gets the SAME multiset of sizes — the stratified quantiles of each
distribution, one deck at a time — in another order, so a seed changes the
order of the work and not its amount.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import time
from dataclasses import dataclass, field

# the chat template the header-only GGUF carries: role tags and the text, so
# the benchmark knows a prompt's token count (one token per byte) without
# asking the program
CHAT_TEMPLATE = (
    "{% for m in messages %}<|{{ m['role'] }}|>{{ m['content'] }}{% endfor %}"
    "<|assistant|>"
)
TEMPLATE_OVERHEAD = len("<|user|>") + len("<|assistant|>")
ALPHABET = "abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ 0123456789 .,;:"


def quantiles(dist: dict, n: int) -> list[int]:
    """The n stratified quantiles of a length distribution (whole tokens)."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = float(dist["min"]), float(dist["max"])
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "loguniform":
        return [int(round(lo * (hi / lo) ** u)) for u in us]
    raise ValueError(f"unknown distribution {kind!r}")


def dist_bounds(dist: dict) -> tuple[int, int]:
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


def make_text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(ALPHABET, k=n))


@dataclass
class Request:
    idx: int
    prompt: str
    prompt_tokens: int
    max_tokens: int
    seed: int
    temperature: float | None = None  # None: the mix's


class Generator:
    """Endless seeded stream of requests for one traffic mix."""

    def __init__(self, mix: dict, seed: int):
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}: this generator drives closed loops")
        self.mix = mix
        self.rng = random.Random(seed)
        self.order = random.Random(mix.get("order_seed", seed ^ 0x0DDE))
        self.deck_n = int(mix.get("deck", 64))
        self.greedy_every = int(mix.get("greedy_every", 0))
        self._idx = 0
        self._n = 0
        self._deck: list[tuple[int, int]] = []

    def _next_sizes(self) -> tuple[int, int]:
        if not self._deck:
            p = quantiles(self.mix["prompt_tokens"], self.deck_n)
            o = quantiles(self.mix["output_tokens"], self.deck_n)
            self.order.shuffle(p)
            self.order.shuffle(o)
            self._deck = list(zip(p, o))
        return self._deck.pop()

    def make(self, prompt_tokens: int, max_tokens: int) -> Request:
        text = make_text(self.rng, max(1, prompt_tokens - TEMPLATE_OVERHEAD))
        req = Request(self._idx, text, len(text) + TEMPLATE_OVERHEAD, max_tokens,
                      self.rng.randrange(2**31))
        self._idx += 1
        return req

    def next(self) -> Request:
        req = self.make(*self._next_sizes())
        self._n += 1
        if self.greedy_every and self._n % self.greedy_every == 0:
            req.temperature = 0.0
        return req


def warmup_lengths(mix: dict) -> list[int]:
    """The range's ends and one length just past every power of two inside
    it: whatever ladder of buckets, chunks or windows the program compiles
    by, a doubling ladder with "n <= rung" lands one length in each rung."""
    lo, hi = dist_bounds(mix["prompt_tokens"])
    out = {lo, hi}
    p = 1
    while p < hi:
        if lo <= p + 1 <= hi:
            out.add(p + 1)
        p *= 2
    return sorted(out)


@dataclass
class Record:
    """Everything the client saw of one request, on ``time.perf_counter``."""
    idx: int
    prompt_tokens: int
    max_tokens: int
    t_sent: float
    chunks: list = field(default_factory=list)  # (time, tokens in the chunk)
    t_done: float | None = None
    ok: bool = False
    error: str | None = None
    usage: dict | None = None
    stats: dict | None = None
    logprobs: list = field(default_factory=list)  # one entry per served token
    mismatch: str | None = None
    temperature: float = 0.0
    prompt: str = ""
    text: str = ""  # the streamed reply; one printable byte is one token


class Client:
    """Drives ``lmstudio.chat_model`` with ``"stream": true`` over one NATS
    connection and keeps a Record per request."""

    def __init__(self, nc, model_id: str, temperature: float, timeout_s: float = 300.0):
        self.nc = nc
        self.model_id = model_id
        self.temperature = temperature
        self.timeout_s = timeout_s
        self.records: list[Record] = []
        self.settle: Settle | None = None  # told of every send while the phase lasts

    async def chat(self, req: Request, logprobs: int = 0,
                   temperature: float | None = None) -> Record:
        if temperature is None:
            temperature = self.temperature if req.temperature is None else req.temperature
        body = {
            "model": self.model_id,
            "messages": [{"role": "user", "content": req.prompt}],
            "max_tokens": req.max_tokens,
            "temperature": temperature,
            "seed": req.seed,
            "stream": True,
        }
        if logprobs:
            body |= {"logprobs": True, "top_logprobs": logprobs}
        now = time.perf_counter()
        rec = Record(req.idx, req.prompt_tokens, req.max_tokens, now,
                     temperature=temperature, prompt=req.prompt)
        self.records.append(rec)
        if self.settle is not None:
            self.settle.sent(now)
        stream = self.nc.request_stream(
            "lmstudio.chat_model", json.dumps(body).encode(),
            timeout=self.timeout_s, idle_timeout=self.timeout_s)
        try:
            async for msg in stream:
                t = time.perf_counter()
                if (msg.headers or {}).get("Nats-Stream-Done") is not None:
                    rec.t_done = t
                    self._finish(rec, json.loads(msg.payload))
                    break
                choice = json.loads(msg.payload)["data"]["chunk"]["choices"][0]
                if logprobs and choice.get("logprobs"):
                    rec.logprobs.extend(choice["logprobs"]["content"])
                # byte-level tokenizer: one printable character is one token
                piece = choice["delta"].get("content", "")
                rec.text += piece
                rec.chunks.append((t, len(piece)))
            else:
                rec.error = "stream ended without a terminal message"
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            rec.error = f"{type(e).__name__}: {e}"
        finally:
            await stream.aclose()
        return rec

    @staticmethod
    def _finish(rec: Record, env: dict) -> None:
        if not env.get("ok"):
            rec.error = str(env.get("error"))
            return
        resp = env["data"]["response"]
        rec.usage = resp["usage"]
        rec.stats = resp.get("stats")
        rec.ok = True
        got = (rec.usage["prompt_tokens"], rec.usage["completion_tokens"],
               sum(n for _, n in rec.chunks))
        want = (rec.prompt_tokens, rec.max_tokens, rec.max_tokens)
        if got != want:
            rec.mismatch = (f"request {rec.idx}: prompt/completion/streamed tokens "
                            f"{got}, expected {want}")


class Settle:
    """Where the settle phase ends and the window starts (the module's
    docstring has the rule). ``Client.chat`` calls ``sent`` at every send of
    the mix; the send at which it returns True is the window's first.
    ``last_program()`` is the time the newest program was built or fetched."""

    def __init__(self, warmup: dict, t0: float, last_program=lambda: 0.0):
        self.count = int(warmup.get("settle_requests", 0))
        self.quiet_s = float(warmup.get("quiet_s", 5.0))
        self.min_s = float(warmup.get("min_settle_s", self.quiet_s))
        self.max_s = float(warmup.get("max_settle_s", 60.0))
        self.t0 = t0
        self.last_program = last_program
        self.sends = 0                      # sends of the mix before the window's first
        self.sends_in_min_settle: int | None = None
        self.closed_by: str | None = None
        self.w0: float | None = None
        self.started = asyncio.Event()

    def sent(self, now: float) -> bool:
        if self.w0 is not None:
            return False
        if self.sends_in_min_settle is None and now - self.t0 >= self.min_s:
            self.sends_in_min_settle = self.sends
        clock_at, quiet_at = self.t0 + self.min_s, self.last_program() + self.quiet_s
        if now - self.t0 >= self.max_s:
            self._start(now, "max")
        elif self.sends >= self.count and now >= clock_at and now >= quiet_at:
            self._start(now, self.closed_by or "count")
        else:
            if self.sends >= self.count:
                # the count came before a time: that time closes the phase
                self.closed_by = "clock" if clock_at >= quiet_at else "quiet"
            self.sends += 1
        return self.w0 is not None

    def _start(self, now: float, closed_by: str) -> None:
        self.w0, self.closed_by = now, closed_by
        self.started.set()

    async def wait(self) -> float:
        """The window's start. Where nothing is sent for 30 s past
        ``max_settle_s`` the window starts there, as before PR 39."""
        try:
            await asyncio.wait_for(
                self.started.wait(), max(0.0, self.t0 + self.max_s - time.perf_counter()) + 30.0)
        except asyncio.TimeoutError:
            self._start(time.perf_counter(), "max")
        return self.w0

    def line(self) -> dict:
        return {"sends": self.sends, "closed_by": self.closed_by,
                "sends_in_min_settle": self.sends_in_min_settle}


class Load:
    """Runs a mix's closed loop until stopped; the window is a slice of a
    load that is already steady when it starts."""

    def __init__(self, client: Client, gen: Generator):
        self.client = client
        self.gen = gen
        self.tasks: set[asyncio.Task] = set()
        self.stopping = False

    def start(self) -> None:
        for _ in range(int(self.gen.mix["callers"])):
            t = asyncio.ensure_future(self._caller())
            self.tasks.add(t)
            t.add_done_callback(self.tasks.discard)

    async def _caller(self) -> None:
        while not self.stopping:
            await self.client.chat(self.gen.next())

    async def stop(self, must_have_first_chunk_since: float) -> None:
        """Stop sending. A request sent inside the window is waited for
        until its first chunk came (its TTFT is a result); then the replies
        still in flight are abandoned."""
        self.stopping = True
        hard = time.perf_counter() + 60.0
        while time.perf_counter() < hard and any(
            r.t_done is None and r.error is None and not r.chunks
            and r.t_sent >= must_have_first_chunk_since
            for r in self.client.records
        ):
            await asyncio.sleep(0.05)
        for t in list(self.tasks):
            t.cancel()
        if self.tasks:
            await asyncio.wait(self.tasks, timeout=30.0)


def tokens_in_window(chunks: list, w0: float, w1: float) -> float:
    """Output tokens of one stream that fall into [w0, w1). A chunk carries
    the tokens of a whole decode burst, made over the time since the
    stream's previous chunk, so they are spread evenly over that time and
    the part inside the window counts: a burst that straddles an edge of
    the window is shared out, not won or lost whole. The first chunk (the
    token the prefill sampled) counts at the moment it came."""
    total = 0.0
    prev = None
    for t, n in chunks:
        if prev is None or t <= prev:
            total += n if w0 <= t < w1 else 0.0
        else:
            total += n * max(0.0, min(t, w1) - max(prev, w0)) / (t - prev)
        prev = t
    return total


def reduce_client(records: list[Record], w0: float, w1: float) -> dict:
    """Client-side numbers of one window, from the records alone.

    out_tok_s    output tokens received in [w0, w1), a chunk's tokens spread
                 over the time since its stream's previous chunk
                 (``tokens_in_window``), over the window's seconds
    ttft         sent to the first streamed chunk, over every request sent
                 in the window; a request that failed or never got a chunk
                 is +inf
    gaps         between consecutive chunks of one stream, pooled over all
                 streams, for every chunk received in the window
    """
    from .stats import percentile

    tokens = 0.0
    gaps: list[float] = []
    ttft: list[float] = []
    attempted = failed = completed = 0
    for r in records:
        tokens += tokens_in_window(r.chunks, w0, w1)
        for (prev, _), (t, _) in zip(r.chunks, r.chunks[1:]):
            if w0 <= t < w1:
                gaps.append(t - prev)
        if w0 <= r.t_sent < w1:
            attempted += 1
            if r.error is None and r.chunks:
                ttft.append(r.chunks[0][0] - r.t_sent)
            else:
                failed += 1
                ttft.append(math.inf)
            if r.ok:
                completed += 1
    mid = min(len(ttft) - 1, int(len(ttft) * 0.5))  # the index ``percentile`` takes
    return {
        "window_s": w1 - w0,
        "out_tokens": tokens,
        "out_tok_s": tokens / (w1 - w0),
        "attempted": attempted,
        "failed": failed,
        "completed": completed,
        # the order statistics around the median and the longest tenth of the
        # gaps: how far each percentile jumps when one sample changes sides
        "ttft_near_p50_s": sorted(ttft)[max(0, mid - 4):mid + 5],
        "ttft_p50_s": percentile(ttft, 0.5),
        "gap_n": len(gaps),
        "gap_p50_s": percentile(gaps, 0.5),
        "gap_p95_s": percentile(gaps, 0.95),
        "gap_top_s": sorted(gaps)[-(len(gaps) // 10 + 1):],
        "mismatches": [r.mismatch for r in records if r.mismatch],
    }
