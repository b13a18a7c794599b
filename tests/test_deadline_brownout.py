"""End-to-end deadline propagation + adaptive brownout (ISSUE 5).

Deadlines: request()/request_stream() stamp the caller's budget as
``X-Deadline-Ms``; the worker converts it to a monotonic deadline and the
batcher (serve/batcher.py) sheds expired requests BEFORE prefill — at
submit and at admit — and cooperatively aborts mid-decode slots whose
deadline passes, all with retryable envelopes cause-tagged ``deadline``.

Brownout: serve/brownout.py degrades service under overload instead of
falling over — NORMAL → BROWNOUT → SHED_ONLY with hysteresis on queue
depth / queue-age p95 / HBM headroom, pausing spec decode, shrinking the
decode burst, and tightening the admit limit per level.
"""

import asyncio
import contextlib
import time

import jax
import pytest

from nats_llm_studio_tpu.engine.generator import SamplingParams
from nats_llm_studio_tpu.models.config import ModelConfig
from nats_llm_studio_tpu.models.llama import init_params
from nats_llm_studio_tpu.obs import EVENTS
from nats_llm_studio_tpu.serve.batcher import (
    BatcherOverloaded,
    ContinuousBatcher,
    _Request,
)
from nats_llm_studio_tpu.serve.brownout import (
    BROWNOUT,
    NORMAL,
    SHED_ONLY,
    BrownoutConfig,
    BrownoutController,
)
from nats_llm_studio_tpu.transport.envelope import (
    deadline_header_value,
    deadline_remaining_s,
    error_is_retryable,
)

from conftest import async_test


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.tiny(n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


async def _wait_for(pred, timeout=10.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# -- deadline header (transport/envelope.py) ---------------------------------


def test_deadline_header_round_trip():
    """A stamped budget comes back within clock-read slop; garbage or an
    absent header degrades to None (never fails a servable request)."""
    v = deadline_header_value(5.0)
    remaining = deadline_remaining_s(v)
    assert remaining is not None and 4.5 < remaining <= 5.0
    # an already-expired budget parses as negative, not None: the serving
    # path must SEE the expiry to shed it retryably rather than ignore it
    past = deadline_remaining_s(deadline_header_value(-3.0))
    assert past is not None and past < 0
    assert deadline_remaining_s(None) is None
    assert deadline_remaining_s("") is None
    assert deadline_remaining_s("not-a-number") is None


# -- BrownoutController (serve/brownout.py) ----------------------------------


def test_brownout_escalates_immediately_and_deescalates_with_dwell():
    cfg = BrownoutConfig(depth_hi=0.75, depth_lo=0.40, age_hi_ms=1500.0,
                         age_lo_ms=500.0, dwell_s=2.0)
    bo = BrownoutController(cfg, engine="t")
    t = 100.0
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=t) == NORMAL
    # one hot signal escalates on the very next tick (no dwell going up)
    assert bo.update(depth_frac=0.8, age_p95_ms=0.0, now=t + 0.1) == BROWNOUT
    # calm must hold CONTINUOUSLY for dwell_s before stepping back down
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=t + 1.0) == BROWNOUT
    # a hot blip resets the dwell clock
    assert bo.update(depth_frac=0.5, age_p95_ms=0.0, now=t + 2.0) == BROWNOUT
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=t + 3.0) == BROWNOUT
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=t + 4.0) == BROWNOUT
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=t + 5.1) == NORMAL
    assert bo.transitions == 2


def test_brownout_shed_only_edge_and_stepwise_recovery():
    cfg = BrownoutConfig(depth_hi=0.5, shed_only_scale=1.5, dwell_s=1.0)
    bo = BrownoutController(cfg, engine="t")
    # pressure past hi*scale jumps straight to SHED_ONLY
    assert bo.update(depth_frac=0.9, age_p95_ms=0.0, now=10.0) == SHED_ONLY
    # recovery is one level per dwell, not a cliff back to NORMAL
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=11.0) == SHED_ONLY
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=12.1) == BROWNOUT
    assert bo.update(depth_frac=0.1, age_p95_ms=0.0, now=13.2) == NORMAL
    # hbm headroom below the floor is an escalation signal on its own
    # (0.04 is under the 0.05 floor but above the shed-only-scaled 0.033
    # mark, so it browns out without jumping straight to shed-only)
    assert bo.update(depth_frac=0.0, age_p95_ms=0.0,
                     hbm_headroom_frac=0.04, now=14.0) == BROWNOUT
    # headroom through the floor even at the scaled mark: SHED_ONLY
    assert bo.update(depth_frac=0.0, age_p95_ms=0.0,
                     hbm_headroom_frac=0.01, now=15.0) == SHED_ONLY


def test_brownout_levers():
    bo = BrownoutController(BrownoutConfig(tighten_frac=0.5), engine="t")
    assert not bo.pause_spec and not bo.pause_prefix_harvest
    assert bo.effective_burst(8) == 8
    assert bo.effective_queue_limit(32) == 32
    bo.level = BROWNOUT
    assert bo.pause_spec and bo.pause_prefix_harvest
    assert bo.effective_burst(8) == 4
    assert bo.effective_queue_limit(32) == 16
    assert bo.effective_queue_limit(0) == 0  # zero-disables convention holds
    bo.level = SHED_ONLY
    assert bo.effective_burst(8) == 1
    assert bo.effective_queue_limit(1) == 1  # never tightened below 1


def test_brownout_transitions_hit_the_event_ring():
    seq0 = EVENTS.emitted
    bo = BrownoutController(BrownoutConfig(depth_hi=0.5, dwell_s=0.5),
                            engine="ring-test")
    bo.update(depth_frac=0.6, age_p95_ms=0.0, now=1.0)
    bo.update(depth_frac=0.0, age_p95_ms=0.0, now=2.0)
    bo.update(depth_frac=0.0, age_p95_ms=0.0, now=2.6)
    evs = [e for e in EVENTS.snapshot(kind="brownout")
           if e["seq"] >= seq0 and e.get("engine") == "ring-test"]
    assert [e["level_name"] for e in evs] == ["brownout", "normal"]
    assert evs[0]["reasons"] == ["depth"] and evs[0]["prev"] == "normal"


# -- batcher: deadline shed/abort (serve/batcher.py) -------------------------


@async_test
async def test_expired_deadline_shed_at_submit_without_prefill(model):
    """A request whose budget already ran out at submit is shed immediately
    with a retryable message, cause-tagged ``deadline`` — and never admitted,
    so no prefill work is wasted on it."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        with pytest.raises(BatcherOverloaded) as ei:
            async for _ in b.submit([1, 2, 3], sp,
                                    deadline=time.monotonic() - 0.5):
                pass
        assert "deadline" in str(ei.value)
        assert error_is_retryable(str(ei.value))
        assert b.stats.shed_cause_counts().get("deadline") == 1
        assert b.stats.requests == 0  # never admitted → no prefill dispatched
        # a deadline-free request afterwards is unaffected
        out = [t async for t in b.submit([4, 5], SamplingParams(
            temperature=0.0, max_tokens=3))]
        assert len(out) == 3
    finally:
        b.stop()


@async_test
async def test_queued_deadline_expiry_sheds_before_prefill(model):
    """A slot-starved waiter whose deadline passes while queued is shed at
    admit time (the queued-side sweep), before any prefill dispatch."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=1, max_seq_len=64, buckets=[8, 64])
    try:
        first_toks: list[int] = []

        async def occupy():
            sp = SamplingParams(temperature=0.0, max_tokens=56)
            async for t in b.submit([1, 2], sp):
                first_toks.append(t)

        occ = asyncio.create_task(occupy())
        await _wait_for(lambda: b.stats.requests >= 1, what="occupier admitted")

        # valid at submit, expires while waiting for the occupied slot
        with pytest.raises(BatcherOverloaded) as ei:
            async for _ in b.submit([3, 4], SamplingParams(
                    temperature=0.0, max_tokens=4),
                    deadline=time.monotonic() + 0.005):
                pass
        assert "deadline" in str(ei.value)
        assert error_is_retryable(str(ei.value))
        await occ
        assert len(first_toks) == 56  # occupier unaffected by the shed
        assert b.stats.shed_cause_counts().get("deadline") == 1
        assert b.stats.requests == 1  # the shed waiter was never admitted
    finally:
        b.stop()


@async_test
async def test_mid_decode_deadline_abort_frees_slot(model):
    """A slot whose deadline passes mid-decode is cooperatively aborted
    through the consumer-gone cancel path: the consumer gets a retryable
    error, the slot frees within ~one decode burst, and the cancel is
    cause-tagged ``deadline`` (distinct from a client disconnect)."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=60)
        agen = b.submit_batched([1, 2, 3], sp,
                                deadline=time.monotonic() + 300.0)
        poked = False
        with pytest.raises(BatcherOverloaded) as ei:
            async for _batch in agen:
                if poked:
                    continue
                # first delivery: the request is live in a slot — rewrite its
                # deadline to the past so the owner loop's active-side sweep
                # fires deterministically on its next tick
                req = next((s for s in b._slots if isinstance(s, _Request)),
                           None)
                if req is not None:
                    req.deadline = time.monotonic() - 0.001
                    poked = True
        assert "deadline exceeded mid-decode" in str(ei.value)
        assert error_is_retryable(str(ei.value))
        await _wait_for(
            lambda: all(s is None for s in b._slots)
            and b.stats.cancel_causes.get("deadline") == 1,
            what="slot freed with a deadline-tagged cancel",
        )
        assert b.stats.tokens < 40, b.stats.snapshot()  # did not run to 60
        # the batcher still serves afterwards
        out = [t async for t in b.submit([7, 8], SamplingParams(
            temperature=0.0, max_tokens=3))]
        assert len(out) == 3
    finally:
        b.stop()


# -- batcher: brownout under overload ----------------------------------------


@async_test
async def test_brownout_e2e_overload_and_recovery(model):
    """A seeded overload storm against a 1-slot batcher drives the
    controller NORMAL → BROWNOUT (visible in the event ring and the level
    gauge) and back to NORMAL once calm holds for the dwell; every request
    is either served or fails with an honest retryable error."""
    cfg, params = model
    seq0 = EVENTS.emitted
    bo_cfg = BrownoutConfig(
        depth_hi=0.3, depth_lo=0.15, age_hi_ms=1e9, age_lo_ms=1e9,
        dwell_s=0.3, shed_only_scale=100.0,  # keep the storm out of SHED_ONLY
    )
    b = ContinuousBatcher(
        params, cfg, max_slots=1, max_seq_len=64, buckets=[8, 64],
        max_queue=8, brownout=bo_cfg,
    )
    try:
        levels_seen: set[int] = set()

        async def sample_level():
            while True:
                levels_seen.add(b.brownout_level)
                await asyncio.sleep(0.001)

        sampler = asyncio.create_task(sample_level())

        async def client(i: int):
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            return [t async for t in b.submit([i + 1, i + 2], sp)]

        results = await asyncio.gather(
            *[client(i) for i in range(10)], return_exceptions=True
        )
        sampler.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await sampler

        served = [r for r in results if isinstance(r, list)]
        failed = [r for r in results if not isinstance(r, list)]
        assert len(served) + len(failed) == 10  # nobody left unanswered
        assert served and all(len(r) == 6 for r in served)
        for exc in failed:  # every failure is an honest retryable shed
            assert isinstance(exc, BatcherOverloaded), exc
            assert error_is_retryable(str(exc)), exc

        assert max(levels_seen) >= BROWNOUT  # the storm actually browned out
        assert b.brownout.transitions >= 1
        evs = [e for e in EVENTS.snapshot(kind="brownout") if e["seq"] >= seq0]
        assert any(e["level_name"] == "brownout" for e in evs)
        # while browned out the levers were armed: spec paused, burst halved,
        # admit limit tightened (pure functions of the level they reached)
        assert bo_cfg.tighten_frac == 0.5  # default held for this run
        assert b.brownout.effective_queue_limit(8) in (4, 8)

        # recovery: a calm trickle keeps the owner loop ticking (it blocks
        # when fully idle) until the dwell elapses and the level steps down
        t_end = time.monotonic() + 15.0
        while b.brownout_level != NORMAL and time.monotonic() < t_end:
            out = [t async for t in b.submit([1], SamplingParams(
                temperature=0.0, max_tokens=2))]
            assert len(out) == 2
            await asyncio.sleep(0.05)
        assert b.brownout_level == NORMAL
        evs = [e for e in EVENTS.snapshot(kind="brownout") if e["seq"] >= seq0]
        assert any(e["level_name"] == "normal" for e in evs)  # hysteresis ran
        assert not b.brownout.pause_spec  # levers disarm with the level
    finally:
        b.stop()


@async_test
async def test_shed_only_bounces_new_submits_retryably(model):
    """At SHED_ONLY every new submit is shed immediately with a retryable
    message, cause-tagged ``brownout``; already-working requests drain."""
    cfg, params = model
    b = ContinuousBatcher(
        params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
        max_queue=8, brownout=BrownoutConfig(),
    )
    try:
        b.brownout.level = SHED_ONLY  # force the level; the tick would clear
        # it only after a calm dwell, giving this assertion a stable window
        with pytest.raises(BatcherOverloaded) as ei:
            async for _ in b.submit([1, 2], SamplingParams(
                    temperature=0.0, max_tokens=2)):
                pass
        assert "brownout shed-only" in str(ei.value)
        assert error_is_retryable(str(ei.value))
        assert b.stats.shed_cause_counts().get("brownout") == 1
    finally:
        b.stop()


@async_test
async def test_shed_only_recovers_while_idle_via_submit_ticks(model):
    """A drained pipeline parks the owner loop on the inbox, so only the
    submit path can tick the controller: sustained calm retries must step
    SHED_ONLY back down instead of bouncing forever (the stuck-brownout
    regression found driving a live worker)."""
    cfg, params = model
    b = ContinuousBatcher(
        params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
        max_queue=8, brownout=BrownoutConfig(dwell_s=0.2),
    )
    try:
        b.brownout.level = SHED_ONLY  # as if a storm just drained
        served = False
        t0 = time.monotonic()
        while time.monotonic() - t0 < 15.0:
            try:
                async for _ in b.submit([1, 2], SamplingParams(
                        temperature=0.0, max_tokens=2)):
                    pass
                served = True
                break
            except BatcherOverloaded:
                await asyncio.sleep(0.05)
        assert served, "submits still bouncing after 15s of calm retries"
        assert b.brownout.level < SHED_ONLY
    finally:
        b.stop()


def test_queue_age_leaves_out_cold_dispatch_spans(model):
    """The load signals (brownout queue age, rate EWMAs) read a wall span
    less what overlapped a program's first dispatch (trace + compile) on
    the owner thread."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=1, max_seq_len=64, buckets=[8, 64])
    try:
        assert b._warm_s(100.0, 106.0) == pytest.approx(6.0)
        b._cold_spans.append((99.0, 104.0))   # began before the enqueue
        b._cold_spans.append((105.0, 105.5))  # wholly inside the wait
        b._cold_spans.append((105.9, 107.0))  # still running at ``now``
        assert b._warm_s(100.0, 106.0) == pytest.approx(1.4)
        assert b._warm_s(107.0, 108.0) == pytest.approx(1.0)
    finally:
        b.stop()


@async_test
async def test_request_behind_a_cold_compile_is_not_shed(model):
    """A fresh worker's second request queues behind the first one's
    compiles. That wait is a one-time cost, not saturation: the controller
    stays at NORMAL and both requests are served (found on the chip: a cold
    8B worker answered its second request 'brownout shed-only')."""
    cfg, params = model
    b = ContinuousBatcher(
        params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64],
        max_queue=8, brownout=BrownoutConfig(age_hi_ms=100.0, age_lo_ms=50.0),
    )
    try:
        async def client(toks, delay):
            await asyncio.sleep(delay)
            sp = SamplingParams(temperature=0.0, max_tokens=6)
            return [t async for t in b.submit(toks, sp)]

        first, second = await asyncio.gather(
            client([1, 2, 3], 0.0), client([4, 5], 0.05))
        assert len(first) == 6 and len(second) == 6
        # the second request did queue behind a first dispatch longer than
        # the controller's SHED_ONLY mark ...
        assert max(e - s for s, e in b._cold_spans) * 1e3 > 150.0
        # ... and the controller never read it as load
        assert b.brownout.transitions == 0
        assert b.stats.shed_cause_counts().get("brownout") is None
    finally:
        b.stop()


# -- prometheus exposition (serve/worker.py) ---------------------------------


@async_test
async def test_prometheus_deadline_and_brownout_families(model):
    """The worker renders lmstudio_deadline_shed_total /
    lmstudio_deadline_aborted_total / lmstudio_brownout_level for every
    loaded engine — zero-valued when quiet, counting once deadlines fire."""
    from nats_llm_studio_tpu.config import WorkerConfig
    from nats_llm_studio_tpu.serve.worker import Worker

    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=2, max_seq_len=64, buckets=[8, 64])
    try:
        class _Eng:
            batcher = b

        class _Reg:
            def stats(self):
                return {}

            def loaded_engines(self):
                return {"acme/dl": _Eng()}

        w = Worker(WorkerConfig(), _Reg())
        wid = w.worker_id
        text = w.render_prometheus()
        assert (f'\nlmstudio_deadline_shed_total'
                f'{{model="acme/dl",worker_id="{wid}"}} 0\n') in text
        assert (f'\nlmstudio_deadline_aborted_total'
                f'{{model="acme/dl",worker_id="{wid}"}} 0\n') in text
        assert (f'\nlmstudio_brownout_level'
                f'{{model="acme/dl",worker_id="{wid}"}} 0\n') in text

        # fire one submit-side shed and check the counter + cause label move
        with pytest.raises(BatcherOverloaded):
            async for _ in b.submit([1, 2], SamplingParams(
                    temperature=0.0, max_tokens=2),
                    deadline=time.monotonic() - 1.0):
                pass
        text = w.render_prometheus()
        assert (f'\nlmstudio_deadline_shed_total'
                f'{{model="acme/dl",worker_id="{wid}"}} 1\n') in text
        assert (f'\nlmstudio_batcher_shed_by_cause_total'
                f'{{cause="deadline",model="acme/dl",worker_id="{wid}"}} 1\n') in text
    finally:
        b.stop()
