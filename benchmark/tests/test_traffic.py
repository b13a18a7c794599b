import asyncio
import math
import time
from collections import Counter

from benchmark.lib import traffic
from benchmark.lib.stats import INF_MS, finite_ms, hist_mean, hist_percentile, percentile, spread

CLOSED = {"loop": "closed", "callers": 2, "deck": 16,
          "prompt_tokens": {"dist": "loguniform", "min": 64, "max": 1024},
          "output_tokens": {"dist": "loguniform", "min": 16, "max": 128}}


def take(gen, n):
    return [gen.next() for _ in range(n)]


def test_generator_is_seeded_and_counts_template_tokens():
    a, b = take(traffic.Generator(CLOSED, 5), 40), take(traffic.Generator(CLOSED, 5), 40)
    assert [(r.prompt, r.max_tokens, r.seed) for r in a] == [
        (r.prompt, r.max_tokens, r.seed) for r in b]
    for r in a:
        assert r.prompt_tokens == len(f"<|user|>{r.prompt}<|assistant|>")
        assert 64 <= r.prompt_tokens <= 1024 and 16 <= r.max_tokens <= 128


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, b = take(traffic.Generator(CLOSED, 1), 16), take(traffic.Generator(CLOSED, 2**31 + 7), 16)
    assert Counter(r.prompt_tokens for r in a) == Counter(r.prompt_tokens for r in b)
    assert Counter(r.max_tokens for r in a) == Counter(r.max_tokens for r in b)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]


def test_a_mix_the_generator_does_not_drive_is_refused():
    import pytest

    with pytest.raises(ValueError):
        traffic.Generator(dict(CLOSED, loop="open"), 1)
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "uniform", "min": 1, "max": 2}, 4)


def test_closed_loop_keeps_callers_in_flight_and_stops():
    mix = dict(CLOSED, output_tokens={"dist": "fixed", "value": 2})

    async def go():
        client = traffic.Client(FakeNC(), "m", 0.8)
        load = traffic.Load(client, traffic.Generator(mix, 4))
        w0 = time.perf_counter()
        load.start()
        await asyncio.sleep(0.2)
        w1 = time.perf_counter()
        await load.stop(w0)
        return client, load, w0, w1

    client, load, w0, w1 = asyncio.run(go())
    cm = traffic.reduce_client(client.records, w0, w1)
    assert not load.tasks and cm["attempted"] >= 4 and cm["failed"] == 0 and not cm["mismatches"]
    assert cm["ttft_p50_s"] in cm["ttft_near_p50_s"] and cm["ttft_p50_s"] < 0.1


def test_warmup_lengths_land_in_every_rung_of_a_doubling_ladder():
    assert traffic.warmup_lengths(CLOSED) == [64, 65, 129, 257, 513, 1024]


class FakeNC:
    """Streams ``n`` one-token chunks ``delay`` apart, then the terminal
    message; ``stall`` blocks the event loop once (a server-side stall)."""

    def __init__(self, delay=0.01, fail=False):
        self.delay, self.fail = delay, fail

    async def request_stream(self, subject, payload, timeout, idle_timeout):
        import json

        body = json.loads(payload)

        class M:
            def __init__(self, payload, headers=None):
                self.payload, self.headers = payload, headers

        n = body["max_tokens"]
        if self.fail:
            yield M(json.dumps({"ok": False, "error": "shed"}).encode(), {"Nats-Stream-Done": "1"})
            return
        for _ in range(n):
            await asyncio.sleep(self.delay)
            yield M(json.dumps({"ok": True, "data": {"chunk": {"choices": [
                {"delta": {"content": "x"}}]}}}).encode())
        n_prompt = len(f"<|user|>{body['messages'][0]['content']}<|assistant|>")
        yield M(json.dumps({"ok": True, "data": {"response": {
            "usage": {"prompt_tokens": n_prompt, "completion_tokens": n}, "stats": {}}}}).encode(),
            {"Nats-Stream-Done": "1"})


def test_failures_count_as_infinite_and_token_mismatch_is_caught():
    async def go():
        client = traffic.Client(FakeNC(fail=True), "m", 0.8)
        gen = traffic.Generator(CLOSED, 1)
        t0 = time.perf_counter()
        await asyncio.gather(*(client.chat(gen.next()) for _ in range(3)))
        return client, t0

    client, t0 = asyncio.run(go())
    cm = traffic.reduce_client(client.records, t0, time.perf_counter())
    assert cm["attempted"] == 3 and cm["failed"] == 3
    assert math.isinf(cm["ttft_p50_s"]) and finite_ms(cm["ttft_p50_s"]) == INF_MS
    rec = traffic.Record(0, 10, 5, 0.0, chunks=[(0.1, 4)])
    traffic.Client._finish(rec, {"ok": True, "data": {"response": {
        "usage": {"prompt_tokens": 10, "completion_tokens": 5}}}})
    assert rec.mismatch and "streamed" in rec.mismatch


def test_window_slices_gaps_and_shares_out_a_chunk_that_straddles_an_edge():
    r = traffic.Record(0, 10, 9, t_sent=0.5,
                       chunks=[(0.9, 1), (1.1, 2), (1.4, 2), (2.2, 4)])
    cm = traffic.reduce_client([r], 1.0, 2.0)
    # (0.9, 1.1] holds 2 tokens, half of it inside; (1.4, 2.2] holds 4, 0.6 / 0.8 inside
    assert abs(cm["out_tokens"] - (1.0 + 2.0 + 3.0)) < 1e-9 and cm["attempted"] == 0
    assert cm["gap_n"] == 2 and abs(cm["gap_p95_s"] - 0.3) < 1e-9
    # whatever the window's phase, windows laid end to end count every token once
    edges = [0.0, 0.95, 1.3, 2.05, 3.0]
    parts = [traffic.tokens_in_window(r.chunks, a, b) for a, b in zip(edges, edges[1:])]
    assert abs(sum(parts) - 9.0) < 1e-9
    # a shift of the window by 10 ms moves the count by 10 ms of the rate, not by a chunk
    a, b = (traffic.tokens_in_window(r.chunks, 1.0, w1) for w1 in (2.195, 2.205))
    assert 0.0 < b - a < 0.06


def test_first_chunk_counts_where_it_came():
    chunks = [(1.5, 1), (2.0, 7)]
    assert traffic.tokens_in_window(chunks, 1.0, 1.4) == 0.0
    assert traffic.tokens_in_window(chunks, 1.0, 1.75) == 1.0 + 3.5


def test_percentiles_and_histogram_deltas():
    assert percentile([3, 1, 2, math.inf], 0.5) == 3 and math.isinf(percentile([1, math.inf], 0.95))
    assert math.isnan(percentile([], 0.5))
    assert abs(spread([10, 11, 12, 13, 14, 15]) - 3.5 / 12.5) < 1e-12
    h = {"bounds": (1.0, 2.0, 4.0), "counts": (0, 4, 0, 0), "count": 4, "total": 6.0}
    assert hist_mean(h) == 1.5 and 1.0 < hist_percentile(h, 0.5) <= 2.0
    assert hist_percentile({"bounds": (1.0,), "counts": (0, 0), "count": 0, "total": 0.0}, 0.5) is None


def test_order_seed_fixes_the_order_and_leaves_the_texts_to_the_seed():
    mix = dict(CLOSED, order_seed=23)
    a, b = take(traffic.Generator(mix, 1), 20), take(traffic.Generator(mix, 2**31 + 5), 20)
    assert [(r.prompt_tokens, r.max_tokens) for r in a] == [
        (r.prompt_tokens, r.max_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.seed for r in a] != [r.seed for r in b]


def test_every_kth_request_of_the_sequence_is_greedy_whatever_the_seed():
    mix = dict(CLOSED, greedy_every=4, order_seed=23)
    a, b = take(traffic.Generator(mix, 1), 24), take(traffic.Generator(mix, 2**31 + 9), 24)
    greedy = [i for i, r in enumerate(a) if r.temperature == 0.0]
    assert greedy == [3, 7, 11, 15, 19, 23]
    assert greedy == [i for i, r in enumerate(b) if r.temperature == 0.0]
    assert all(r.temperature is None for i, r in enumerate(a) if i not in greedy)
    # with the order pinned, the greedy requests have the same sizes in every run
    assert [(a[i].prompt_tokens, a[i].max_tokens) for i in greedy] == [
        (b[i].prompt_tokens, b[i].max_tokens) for i in greedy]
    # warm-up and probe requests (``make``) are never marked
    assert traffic.Generator(mix, 1).make(64, 4).temperature is None
    assert all(r.temperature is None for r in take(traffic.Generator(CLOSED, 1), 8))


# -- where the window starts (``traffic.Settle``) -----------------------------

WARMUP = {"settle_requests": 6, "min_settle_s": 10, "quiet_s": 7, "max_settle_s": 60}


def replay(warmup, send_times, last_program=0.0, t0=100.0):
    """Drive ``Settle`` with a fake clock and a fake client: ``send_times``
    are the mix's sends, seconds after the phase began, in order. Returns the
    settle line and the index of the window's first send."""
    settle = traffic.Settle(warmup, t0, lambda: t0 + last_program)
    for i, t in enumerate(send_times):
        if settle.sent(t0 + t):
            assert settle.w0 == t0 + t and settle.started.is_set()
            assert not settle.sent(t0 + t + 1.0)        # the phase is over: nothing moves
            return settle.line(), i
    return settle.line(), None


def every(dt, n=200):
    return [dt * (i + 1) for i in range(n)]


def test_the_settle_phase_says_which_condition_came_last():
    import pytest

    # the count last (sends 2 s apart: the 7th, index 6, comes at 14 s): sound
    line, first = replay(WARMUP, every(2.0), last_program=1.0)
    assert (line["closed_by"], line["sends"], first) == ("count", 6, 6)
    assert line["sends_in_min_settle"] == 4             # sends at 2, 4, 6, 8 s
    # the clock last (a send a second: the count is reached at 7 s)
    line, first = replay(WARMUP, every(1.0), last_program=1.0)
    assert (line["closed_by"], line["sends"], first) == ("clock", 9, 9)
    # quiet last (a program built 9 s into the phase: quiet from 16 s)
    line, first = replay(WARMUP, every(1.0), last_program=9.0)
    assert (line["closed_by"], first) == ("quiet", 15)
    # neither comes (a program every few seconds): ``max_settle_s`` runs out
    settle = traffic.Settle(WARMUP, 100.0, lambda: now[0] - 1.0)
    now = [100.0]
    for i in range(100):
        now[0] = 100.0 + i + 1
        if settle.sent(now[0]):
            break
    assert settle.closed_by == "max" and now[0] - 100.0 == pytest.approx(60.0)


def test_a_count_that_comes_last_fixes_the_windows_first_send_whatever_the_timing():
    import random

    def jittered(seed, dt):
        rng = random.Random(seed)
        return sorted(dt * (i + 1) + rng.uniform(-0.4, 0.4) * dt for i in range(200))

    sized = dict(WARMUP, settle_requests=24)
    # sized with room (a send every 0.5 s: 20 in ``min_settle_s``, the count
    # 24): the same send opens the window under +-40 % of jitter on every
    # send's time, and at 0.85 x and 1.15 x the send rate
    for dt in (0.5 / 1.15, 0.5, 0.5 / 0.85):
        runs = [replay(sized, jittered(s, dt), last_program=0.5) for s in range(20)]
        assert {(line["closed_by"], first) for line, first in runs} == {("count", 24)}
    # overtaken (twice the rate): the clock closes the phase, timing picks among
    # several sends, and every such run says so
    runs = [replay(sized, jittered(s, 0.25), last_program=0.5) for s in range(20)]
    assert {line["closed_by"] for line, _ in runs} == {"clock"}
    assert len({first for _, first in runs}) > 1 and all(38 <= f <= 42 for _, f in runs)
    assert all(line["sends_in_min_settle"] == first for line, first in runs)


def test_every_committed_mix_keeps_its_count_last_with_room():
    """``settle_requests`` has to be the LAST condition met; the file keeps
    beside it how many sends the replay made in ``min_settle_s`` when it was
    last sized (a run prints the figure on its settle line)."""
    import json
    from pathlib import Path

    files = sorted((Path(traffic.__file__).parent.parent / "traffic").glob("*.json"))
    assert len(files) >= 5
    for f in files:
        mix = json.loads(f.read_text())
        wu = mix["warmup"]
        assert wu["settle_requests"] >= 1.05 * wu["sends_in_min_settle"], f.name
        assert wu["settle_requests"] >= mix["callers"], f.name


def test_the_client_tells_the_settle_phase_of_every_send_and_the_window_starts_at_one():
    mix = dict(CLOSED, output_tokens={"dist": "fixed", "value": 2})

    async def go():
        client = traffic.Client(FakeNC(delay=0.002), "m", 0.8)
        t0 = time.perf_counter()
        settle = client.settle = traffic.Settle(
            {"settle_requests": 6, "quiet_s": 0.0, "min_settle_s": 0.0}, t0)
        load = traffic.Load(client, traffic.Generator(mix, 4))
        load.start()
        w0 = await settle.wait()
        client.settle = None
        await asyncio.sleep(0.05)
        await load.stop(w0)
        return client, settle, w0

    client, settle, w0 = asyncio.run(go())
    assert (settle.sends, settle.closed_by) == (6, "count")
    # the window's first send is the mix's 7th, to the clock's last digit
    assert client.records[6].t_sent == w0
    assert sum(r.t_sent < w0 for r in client.records) == 6
