"""Elastic autoscaling: advert-driven worker lifecycle (ROADMAP item 3).

The control loop the fleet observability plane (PR 13) was built to feed:
one :class:`Autoscaler` per cluster watches the same two broadcast streams
every other control component already uses —

* ``{prefix}.cluster.adverts`` — per-worker queue depth, brownout level,
  HBM headroom, draining flag (membership + load),
* ``{prefix}.events`` — the aggregator's ``slo_burn`` alerts (the demand
  signal against the TTFT p95 target),

and changes the fleet's shape instead of letting it shed: sustained
pressure spawns a local worker subprocess, sustained calm drains the
least-loaded member. Every decision is deliberately conservative —

* **hysteresis**: pressure must persist ``up_dwell_s`` before a spawn and
  calm must persist ``down_dwell_s`` before a drain, with a global
  ``cooldown_s`` between actions, so an oscillating load cannot flap the
  fleet;
* **bounds**: never below ``min_workers`` (a dead worker is replaced
  immediately — the kill-and-replace path bypasses the dwell), never
  above ``max_workers`` counting spawns still in flight;
* **circuit breaker**: ``breaker_failures`` consecutive spawn failures
  (the subprocess dies, or never advertises within ``spawn_grace_s``)
  open the breaker for ``breaker_cooldown_s`` — a broken image or full
  host degrades to a reasoned event stream, not a spawn storm;

and every decision — acted on or suppressed — is emitted as a reasoned
``autoscale`` event on ``{prefix}.events`` and counted in the
``lmstudio_autoscale_*`` Prometheus families served on
``{prefix}.autoscale.metrics.prom`` (and merged into the cluster
exposition when embedded next to an :class:`obs.aggregator.Aggregator`).

Cold-start is ~seconds, not minutes, because the rest of ISSUE 15 meets
the spawn halfway: ``pull_model`` precompiled the jit grid into the
persistent XLA compile cache (serve/registry.py), and the replacement's
prefix cache is warmed by a ``kv_handoff`` push from the best live donor
(serve/worker.py) as soon as its first advert lands.

Like ClusterRouter and Aggregator, the class is injected with an
already-connected duck-typed client and never imports jax — the
``tick()``/``plan()`` split takes an explicit clock so tests drive the
loop deterministically.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import subprocess
import sys
import time

from ..obs import PromRenderer
from ..obs import emit as obs_emit
from ..utils.nuid import next_nuid
from .router import ADVERT_SUBJECT

log = logging.getLogger(__name__)

AUTOSCALE_METRICS_SUBJECT = "autoscale.metrics.prom"

_INF = float("inf")


class Autoscaler:
    """The elastic control loop; see the module docstring.

    ``spawn_fn(worker_id)`` and ``drain_fn(worker_id, handoff_to)`` are
    injectable (sync or async): the defaults spawn ``python -m
    nats_llm_studio_tpu serve`` subprocesses and request the existing
    ``admin.drain`` subject; tests substitute in-process workers.
    """

    def __init__(self, nc, *, prefix: str = "lmstudio",
                 nats_url: str = "nats://127.0.0.1:4222",
                 min_workers: int = 1, max_workers: int = 4,
                 interval_s: float = 1.0,
                 up_dwell_s: float = 2.0, down_dwell_s: float = 15.0,
                 cooldown_s: float = 5.0,
                 up_queue_depth: float = 8.0, down_queue_depth: float = 1.0,
                 spawn_grace_s: float = 20.0,
                 breaker_failures: int = 3, breaker_cooldown_s: float = 30.0,
                 burn_hold_s: float = 10.0,
                 handoff_prefixes: int = 4,
                 drain_deadline_s: float = 10.0,
                 stale_after_s: float = 5.0,
                 spawn_fn=None, drain_fn=None):
        self.nc = nc
        self.prefix = prefix
        self.nats_url = nats_url
        self.min_workers = max(0, int(min_workers))
        self.max_workers = max(self.min_workers, int(max_workers))
        self.interval_s = interval_s
        self.up_dwell_s = up_dwell_s
        self.down_dwell_s = down_dwell_s
        self.cooldown_s = cooldown_s
        self.up_queue_depth = up_queue_depth
        self.down_queue_depth = down_queue_depth
        self.spawn_grace_s = spawn_grace_s
        self.breaker_failures = max(1, int(breaker_failures))
        self.breaker_cooldown_s = breaker_cooldown_s
        self.burn_hold_s = burn_hold_s
        self.handoff_prefixes = int(handoff_prefixes)
        self.drain_deadline_s = drain_deadline_s
        self.stale_after_s = stale_after_s
        self.spawn_fn = spawn_fn if spawn_fn is not None else self._default_spawn
        self.drain_fn = drain_fn if drain_fn is not None else self._default_drain
        # membership (aggregator-style: mono-keyed, so a respawned worker
        # reusing an id is simply fresher — no seq guard to trip over)
        self._members: dict[str, dict] = {}  # wid -> {"mono": t, "advert": d}
        # spawns awaiting their first advert: wid -> {"mono": t, "proc": p}
        self._pending: dict[str, dict] = {}
        self._last_burn_mono = -_INF
        self._pressure_since: float | None = None
        self._idle_since: float | None = None
        self._cooldown_until = -_INF
        self._consecutive_failures = 0
        self._breaker_open_until = -_INF
        self._breaker_announced = False
        self._spawn_counter = 0
        self.spawns_total = 0
        self.drains_total = 0
        self.spawn_failures_total = 0
        self._subs: list = []
        self._task: asyncio.Task | None = None
        self._bg_tasks: set[asyncio.Task] = set()

    @classmethod
    def from_config(cls, nc, cfg, **overrides) -> "Autoscaler":
        kw = dict(
            prefix=cfg.subject_prefix,
            nats_url=cfg.nats_url,
            min_workers=cfg.autoscale_min_workers,
            max_workers=cfg.autoscale_max_workers,
            interval_s=cfg.autoscale_interval_s,
            up_dwell_s=cfg.autoscale_up_dwell_s,
            down_dwell_s=cfg.autoscale_down_dwell_s,
            cooldown_s=cfg.autoscale_cooldown_s,
            up_queue_depth=cfg.autoscale_up_queue_depth,
            down_queue_depth=cfg.autoscale_down_queue_depth,
            spawn_grace_s=cfg.autoscale_spawn_grace_s,
            breaker_failures=cfg.autoscale_breaker_failures,
            breaker_cooldown_s=cfg.autoscale_breaker_cooldown_s,
            handoff_prefixes=cfg.autoscale_handoff_prefixes,
            drain_deadline_s=cfg.drain_deadline_s,
        )
        kw.update(overrides)
        return cls(nc, **kw)

    # -- lifecycle -----------------------------------------------------------

    async def start(self, *, control_loop: bool = True) -> None:
        sub = await self.nc.subscribe(
            f"{self.prefix}.{ADVERT_SUBJECT}", cb=self._on_advert
        )
        self._subs.append(sub)
        # plain sub (no queue group): slo_burn alerts are broadcast with no
        # reply; requests on the same subject carry a reply and are the
        # workers' event-ring queries — not ours
        sub = await self.nc.subscribe(f"{self.prefix}.events", cb=self._on_event)
        self._subs.append(sub)
        sub = await self.nc.subscribe(
            f"{self.prefix}.{AUTOSCALE_METRICS_SUBJECT}", cb=self._on_metrics
        )
        self._subs.append(sub)
        if control_loop:
            self._task = asyncio.ensure_future(self._loop())
        log.info(
            "autoscaler up: prefix=%s bounds=[%d,%d] interval=%.1fs",
            self.prefix, self.min_workers, self.max_workers, self.interval_s,
        )

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        for t in list(self._bg_tasks):
            t.cancel()
        self._bg_tasks.clear()
        for sub in self._subs:
            try:
                await sub.unsubscribe()
            except (ConnectionError, ValueError):
                pass
        self._subs.clear()

    async def _loop(self) -> None:
        try:
            # let the advert stream settle before the first decision: every
            # live member adverts within stale_after_s, so a younger member
            # view cannot distinguish "below min" from "not yet heard from"
            # — acting on it would spawn surplus workers at every control
            # plane restart
            await asyncio.sleep(max(self.interval_s, self.stale_after_s))
            while True:
                try:
                    await self.tick()
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — the loop must survive a bad tick
                    log.exception("autoscale tick failed")
                await asyncio.sleep(self.interval_s)
        except asyncio.CancelledError:
            return

    # -- signal ingestion ----------------------------------------------------

    async def _on_advert(self, msg) -> None:
        try:
            d = json.loads(msg.payload or b"{}")
        except ValueError:
            return
        wid = d.get("worker_id") if isinstance(d, dict) else None
        if not wid:
            return
        self.observe_advert(wid, d)

    def observe_advert(self, wid: str, d: dict) -> None:
        """Fold one advert into the member table (also the test seam)."""
        self._members[wid] = {"mono": time.monotonic(), "advert": d}
        pending = self._pending.pop(wid, None)
        if pending is not None:
            self._consecutive_failures = 0
            ready_s = time.monotonic() - pending["mono"]
            self._emit_soon("spawn_live", "first_advert", worker_id=wid,
                            ready_s=round(ready_s, 3))
            log.info("autoscaler: spawned worker %s live after %.1fs",
                     wid, ready_s)
            if self.handoff_prefixes > 0:
                donor = self._pick_donor(exclude=wid)
                if donor is not None:
                    self._spawn_bg(self._request_handoff(donor, wid))

    async def _on_event(self, msg) -> None:
        if getattr(msg, "reply", None):
            return  # event-ring query addressed to the workers, not a broadcast
        try:
            d = json.loads(msg.payload or b"{}")
        except ValueError:
            return
        if isinstance(d, dict) and d.get("kind") == "slo_burn":
            self._last_burn_mono = time.monotonic()

    async def _on_metrics(self, msg) -> None:
        if not getattr(msg, "reply", None):
            return
        try:
            await msg.respond(self.render_prometheus().encode())
        except (ConnectionError, ValueError):
            pass

    # -- membership views ----------------------------------------------------

    def live_workers(self, now: float | None = None) -> list[str]:
        """Non-draining workers advertising within the staleness window —
        the fleet's effective serving capacity."""
        now = time.monotonic() if now is None else now
        return sorted(
            wid for wid, m in self._members.items()
            if now - m["mono"] <= self.stale_after_s
            and not m["advert"].get("draining")
            # gateway adverts are metrics-only membership: zero-depth
            # non-serving entries must not dilute the scaling signals
            and m["advert"].get("role") != "gateway"
        )

    def _prune(self, now: float) -> None:
        for wid in [w for w, m in self._members.items()
                    if now - m["mono"] > 10 * self.stale_after_s]:
            del self._members[wid]

    def _pick_donor(self, exclude: str) -> str | None:
        """The best live peer to warm-hand a fresh worker from: the least
        loaded non-draining member (it can best afford the export work)."""
        candidates = [w for w in self.live_workers() if w != exclude]
        if not candidates:
            return None

        def load(wid: str) -> tuple:
            adv = self._members[wid]["advert"]
            return (int(adv.get("brownout", 0) or 0),
                    int(adv.get("queue_depth", 0) or 0), wid)

        return min(candidates, key=load)

    def _pick_victim(self, live: list[str]) -> str | None:
        """Scale-down target: the least-loaded live member (fewest in-flight
        requests to hand off; ties break on worker_id for determinism)."""
        if not live:
            return None
        return min(
            live,
            key=lambda w: (int(self._members[w]["advert"].get("queue_depth", 0)
                               or 0), w),
        )

    # -- the control loop ----------------------------------------------------

    def plan(self, now: float | None = None) -> dict | None:
        """One planning step against the member table: returns the decision
        (``{"action": "spawn"|"drain", "reason": ...}``) or None. Pure
        policy — no I/O — so tests drive it with a synthetic clock; dwell
        bookkeeping (pressure/idle since) lives here."""
        now = time.monotonic() if now is None else now
        live = self.live_workers(now)
        n_effective = len(live) + len(self._pending)
        # below the floor: replace NOW (the kill-and-replace path) — a dead
        # worker's absence is not "pressure" to dwell on
        if n_effective < self.min_workers:
            return {"action": "spawn", "reason": "below_min",
                    "workers_live": len(live)}
        adverts = [self._members[w]["advert"] for w in live]
        depths = [int(a.get("queue_depth", 0) or 0) for a in adverts]
        total_depth = sum(depths)
        avg_depth = (total_depth / len(depths)) if depths else 0.0
        brownout = max((int(a.get("brownout", 0) or 0) for a in adverts),
                       default=0)
        burn = (now - self._last_burn_mono) <= self.burn_hold_s
        pressure = burn or avg_depth >= self.up_queue_depth or brownout >= 2
        idle = (not burn and brownout == 0
                and total_depth <= self.down_queue_depth)
        if pressure:
            if self._pressure_since is None:
                self._pressure_since = now
        else:
            self._pressure_since = None
        if idle:
            if self._idle_since is None:
                self._idle_since = now
        else:
            self._idle_since = None
        if now < self._cooldown_until:
            return None
        if (self._pressure_since is not None
                and now - self._pressure_since >= self.up_dwell_s):
            if n_effective >= self.max_workers:
                return None  # pressed against the ceiling: shedding handles it
            reason = ("slo_burn" if burn
                      else f"queue_depth avg {avg_depth:.1f}" if
                      avg_depth >= self.up_queue_depth
                      else f"brownout {brownout}")
            return {"action": "spawn", "reason": reason,
                    "workers_live": len(live)}
        if (self._idle_since is not None
                and now - self._idle_since >= self.down_dwell_s):
            if len(live) <= self.min_workers:
                return None
            victim = self._pick_victim(live)
            if victim is None:
                return None
            return {"action": "drain", "reason": "idle", "victim": victim,
                    "workers_live": len(live)}
        return None

    async def tick(self, now: float | None = None) -> dict | None:
        """One control tick: expire overdue spawns, plan, act. Returns the
        decision acted on (or suppressed by the breaker), for tests."""
        now = time.monotonic() if now is None else now
        self._prune(now)
        self._expire_pending(now)
        decision = self.plan(now)
        if decision is None:
            return None
        if decision["action"] == "spawn":
            await self._spawn(now, decision)
        elif decision["action"] == "drain":
            await self._drain(now, decision)
        return decision

    def _expire_pending(self, now: float) -> None:
        """Fail the pending spawns that died or never advertised. A child
        that has already exited is a failure NOW, with its exit code — the
        second worker on a host whose chips another process owns exits at
        start-up (``serve`` refuses an unrequested CPU backend), and must
        not sit out the grace as if it were still compiling."""
        for wid, p in list(self._pending.items()):
            poll = getattr(p.get("proc"), "poll", None)
            try:
                rc = poll() if poll is not None else None
            except Exception:  # noqa: BLE001 — treat as still running
                rc = None
            if rc is not None:
                del self._pending[wid]
                self._record_spawn_failure(now, wid, f"exited rc={rc}")
            elif now - p["mono"] > self.spawn_grace_s:
                del self._pending[wid]
                if poll is not None:
                    try:
                        p["proc"].kill()
                    except Exception:  # noqa: BLE001 — cleanup is best-effort
                        pass
                self._record_spawn_failure(now, wid, "no_advert_within_grace")

    def _record_spawn_failure(self, now: float, wid: str, why: str) -> None:
        self.spawn_failures_total += 1
        self._consecutive_failures += 1
        self._emit_soon("spawn_failed", why, worker_id=wid,
                        consecutive=self._consecutive_failures)
        log.warning("autoscaler: spawn of %s failed (%s; %d consecutive)",
                    wid, why, self._consecutive_failures)
        if self._consecutive_failures >= self.breaker_failures:
            self._breaker_open_until = now + self.breaker_cooldown_s
            self._breaker_announced = False

    def breaker_open(self, now: float | None = None) -> bool:
        now = time.monotonic() if now is None else now
        return now < self._breaker_open_until

    async def _spawn(self, now: float, decision: dict) -> None:
        if self.breaker_open(now):
            if not self._breaker_announced:
                self._breaker_announced = True
                await self._emit(
                    "spawn_suppressed", "breaker_open",
                    wanted=decision["reason"],
                    open_for_s=round(self._breaker_open_until - now, 1),
                )
            return
        self._spawn_counter += 1
        wid = f"w-as{self._spawn_counter}-{next_nuid()[-6:].lower()}"
        try:
            res = self.spawn_fn(wid)
            if asyncio.iscoroutine(res):
                res = await res
        except Exception as e:  # noqa: BLE001 — a failed exec is a spawn failure
            self._record_spawn_failure(now, wid, f"{type(e).__name__}: {e}")
            return
        # stamped with the tick clock (== monotonic in live operation) so
        # grace expiry composes with test-driven synthetic time
        self._pending[wid] = {"mono": now, "proc": res}
        self.spawns_total += 1
        self._cooldown_until = now + self.cooldown_s
        self._pressure_since = None
        await self._emit("spawn", decision["reason"], worker_id=wid,
                         workers_live=decision.get("workers_live", 0),
                         workers_pending=len(self._pending))

    async def _drain(self, now: float, decision: dict) -> None:
        victim = decision["victim"]
        # the drained worker's hot cache should survive on a peer, not die
        # with it: hand off to the least-loaded survivor
        handoff_to = (self._pick_donor(exclude=victim)
                      if self.handoff_prefixes > 0 else None)
        self.drains_total += 1
        self._cooldown_until = now + self.cooldown_s
        self._idle_since = None
        await self._emit("drain", decision["reason"], worker_id=victim,
                         handoff_to=handoff_to or "",
                         workers_live=decision.get("workers_live", 0))
        try:
            res = self.drain_fn(victim, handoff_to)
            if asyncio.iscoroutine(res):
                await res
        except Exception as e:  # noqa: BLE001 — a lost drain ages out via staleness
            log.warning("autoscaler: drain of %s failed: %s", victim, e)

    # -- actions (defaults) --------------------------------------------------

    def _default_spawn(self, wid: str):
        env = {**os.environ, "WORKER_ID": wid, "NATS_URL": self.nats_url}
        # a spawned worker is a worker, not another control plane
        for k in ("OBS_AUTOSCALE", "OBS_AGGREGATOR"):
            env.pop(k, None)
        return subprocess.Popen(
            [sys.executable, "-m", "nats_llm_studio_tpu", "serve"], env=env
        )

    async def _default_drain(self, wid: str, handoff_to: str | None):
        req = {"worker_id": wid, "deadline_s": self.drain_deadline_s}
        if handoff_to:
            req["handoff_to"] = handoff_to
        await self.nc.request(
            f"{self.prefix}.admin.drain",
            json.dumps(req, separators=(",", ":")).encode(),
            timeout=self.drain_deadline_s + 10.0,
        )

    async def _request_handoff(self, donor: str, recipient: str) -> None:
        """Ask ``donor`` to push its hottest prefixes to ``recipient``
        (fire-and-forget warm-up of a fresh spawn)."""
        try:
            await self.nc.request(
                f"{self.prefix}.worker.{donor}.kv_handoff",
                json.dumps({"to": recipient, "limit": self.handoff_prefixes},
                           separators=(",", ":")).encode(),
                timeout=30.0,
            )
        except Exception as e:  # noqa: BLE001 — warm-up is best-effort
            log.warning("autoscaler: warm handoff %s -> %s failed: %s",
                        donor, recipient, e)

    # -- observability -------------------------------------------------------

    def _spawn_bg(self, coro) -> None:
        t = asyncio.ensure_future(coro)
        self._bg_tasks.add(t)
        t.add_done_callback(self._bg_tasks.discard)

    def _emit_soon(self, action: str, reason: str, **extra) -> None:
        """Event emission from sync code paths: ring-buffer immediately,
        bus publish as a background task."""
        self._spawn_bg(self._emit(action, reason, _ring=False, **extra))
        obs_emit("autoscale", action=action, reason=reason, **extra)

    async def _emit(self, action: str, reason: str, _ring: bool = True,
                    **extra) -> None:
        if _ring:
            obs_emit("autoscale", action=action, reason=reason, **extra)
        payload = {"kind": "autoscale", "action": action, "reason": reason,
                   **extra}
        try:
            await self.nc.publish(
                f"{self.prefix}.events",
                json.dumps(payload, separators=(",", ":")).encode(),
            )
        except (ConnectionError, ValueError):
            pass  # reconnect in flight; the decision still sits in the ring

    def render_prometheus(self, now: float | None = None) -> str:
        """The ``lmstudio_autoscale_*`` families — served directly on
        ``{prefix}.autoscale.metrics.prom`` and foldable into the cluster
        exposition via Aggregator(extra_expositions=[...]). All families
        are always present (zero-valued) so dashboards can assert on
        existence."""
        now = time.monotonic() if now is None else now
        r = PromRenderer()
        r.counter("lmstudio_autoscale_spawns_total", self.spawns_total,
                  help="worker spawns initiated by the autoscaler")
        r.counter("lmstudio_autoscale_drains_total", self.drains_total,
                  help="scale-down drains initiated by the autoscaler")
        r.counter("lmstudio_autoscale_spawn_failures_total",
                  self.spawn_failures_total,
                  help="spawns that failed to exec or never advertised "
                       "within the grace window")
        r.gauge("lmstudio_autoscale_workers_live",
                len(self.live_workers(now)),
                help="non-draining workers advertising within the "
                     "staleness window")
        r.gauge("lmstudio_autoscale_workers_pending", len(self._pending),
                help="spawned workers awaiting their first advert")
        r.gauge("lmstudio_autoscale_breaker_open",
                1 if self.breaker_open(now) else 0,
                help="1 while the spawn circuit breaker is open")
        return r.render()
