"""DP serve fleet: N engine replicas behind one admission queue.

The mesh work in ``serve.engine`` scales one engine *down* into shards
(TP: every device holds a slice of the weights and page pools); this
module scales *out* — data parallelism at the request level.  N
independent ``Engine`` replicas (each single-device or TP-meshed on its
own disjoint device group) sit behind a single arrival-gated priority
queue, and a host-side router decides, per arrived request, which
replica serves it:

* **Least-loaded / join-shortest-queue.**  The default placement key is
  a replica's queued plus running request count — the classic JSQ rule,
  which keeps per-replica queues balanced under bursty arrivals without
  any coordination between replicas.
* **Priorities.**  The router pops its queue best-effective-priority
  first (the same aging rule the engine scheduler uses), so a
  high-priority arrival is *placed* before a low-priority one that
  arrived earlier — and keeps its priority inside the replica, where
  the engine's preemptive scheduler takes over.
* **Prefix-cache affinity.**  With ``prefix_cache=True`` the router
  hashes each prompt's page-aligned chunk chain once
  (``paging.hash_chunks`` — the same keys every replica's index uses)
  and probes every replica's ``PrefixCache.match``: the replica holding
  the longest cached prefix wins (ties fall back to JSQ).  Requests
  sharing a system prompt therefore converge on the replica that
  already holds its pages instead of re-prefilling it N times.
* **Fleet stats.**  ``Router.stats`` aggregates ``Engine.stats`` across
  replicas (summed counters, pooled rates weighted by their true
  denominators) and adds placement accounting: per-replica placement
  counts and prefix-affinity hit rates.  The workload driver
  (``serve.workload``) runs unchanged against a ``Router`` — it mirrors
  the engine's ``submit`` / ``run`` / ``reset`` / ``stats`` surface —
  so fleet-level tok/s and p50/p99 TTFT/ITL come from the same
  definitions as single-engine numbers.

One thread drives the whole fleet: ``run`` round-robins
``Engine.step(wait=False)`` over the replicas, so a replica mid-chunk
never blocks another's admission.  Requests keep their submission ids
(the router's global ids), while each replica's internal ids live in a
disjoint range — request stream keys are index-derived from the id, so
two replicas can never draw correlated sampling streams.
"""

from __future__ import annotations

import time

import jax

from repro.serve.engine import Engine, Request, ServeConfig, _PriorityQueue
from repro.serve.paging import hash_chunks

__all__ = ["Router"]

# replica-local request ids live in disjoint blocks so the
# index-derived stream key fold_in(base_key, id) never collides
# across replicas (a collision would correlate two requests' sampled
# streams); 2**20 ids per replica is far beyond any drain cycle
_ID_BLOCK = 1 << 20


class Router:
    """Admission router over ``replicas`` engine replicas.  Mirrors the
    engine's serving surface (``submit`` / ``run`` / ``start`` /
    ``step`` / ``drain`` / ``reset`` / ``stats`` / ``compile_counts``)
    so callers — the workload driver, the launcher, the benchmark —
    drive a fleet exactly like one engine."""

    def __init__(self, cfg, params, scfg: ServeConfig, *,
                 replicas: int = 2, devices=None):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.cfg = cfg
        self.scfg = scfg
        groups = self._device_groups(scfg, replicas, devices)
        self.replicas = [Engine(cfg, params, scfg, devices=g)
                         for g in groups]
        for i, eng in enumerate(self.replicas):
            eng._next_id = i * _ID_BLOCK
        self._prefix = bool(scfg.prefix_cache)
        self._page_size = (self.replicas[0].scfg.page_size
                           if self._prefix else 0)
        self._queue = _PriorityQueue(scfg.priority_aging_s)
        self._next_gid = 0
        self._placed: dict[int, tuple[int, int]] = {}
        self.placements = [0] * replicas
        self.affinity_hits = [0] * replicas
        self.placement_order: list[int] = []

    @staticmethod
    def _device_groups(scfg: ServeConfig, replicas: int, devices):
        """Device slices, one per replica.  Unmeshed engines (tp=1, no
        mesh_shape) take one device each, round-robin: more replicas
        than devices share them (N replicas on one CPU device is the
        functional-testing case) and replicas on one device share its
        weight buffer.  Meshed engines must each get their own full
        complement of devices or the fleet cannot be placed at all."""
        shape = scfg.mesh_shape or (1, scfg.tp)
        per = int(shape[0]) * int(shape[1])
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if per <= 1 and scfg.mesh_shape is None:
            return [[devices[i % len(devices)]] for i in range(replicas)]
        if replicas * per > len(devices):
            raise ValueError(
                f"{replicas} replicas x {per} devices/replica needs "
                f"{replicas * per} devices but only {len(devices)} are "
                f"available")
        return [devices[i * per:(i + 1) * per] for i in range(replicas)]

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, arrival: float = 0.0,
               priority: int = 0) -> int:
        """Queue one request fleet-wide; returns its global id (the key
        of the ``run()`` result).  Validation happens here — an
        unserveable request is rejected at the router's front door, not
        at placement inside a replica."""
        prompt, clamped, truncated = self.replicas[0].validate(
            prompt, max_new_tokens)
        req = Request(id=self._next_gid, prompt=prompt,
                      max_new_tokens=clamped, arrival=arrival,
                      priority=priority, truncated=truncated)
        self._next_gid += 1
        self._queue.push(req)
        return req.id

    def _load(self, eng: Engine) -> int:
        """JSQ key: queued plus running requests on a replica."""
        return len(eng._queue) + len(eng.running())

    def _pick_replica(self, req: Request) -> int:
        """Prefix affinity first (most cached chunks of this prompt),
        then least-loaded, then lowest index — a total order, so
        placement is deterministic for a given fleet state."""
        keys = (hash_chunks(req.prompt, self._page_size)
                if self._prefix else None)
        best, best_key = 0, None
        for i, eng in enumerate(self.replicas):
            hits = (len(eng.prefix_cache.match(keys))
                    if keys else 0)
            key = (-hits, self._load(eng), i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        if best_key[0] < 0:
            self.affinity_hits[best] += 1
        return best

    def _dispatch(self, req: Request, now: float) -> None:
        ri = self._pick_replica(req)
        lid = self.replicas[ri].submit(req.prompt, req.max_new_tokens,
                                       arrival=req.arrival,
                                       priority=req.priority)
        self._placed[req.id] = (ri, lid)
        self.placements[ri] += 1
        self.placement_order.append(req.id)

    # ------------------------------------------------------------------
    # fleet loop
    # ------------------------------------------------------------------

    def start(self, t0: float | None = None) -> None:
        """Anchor one shared run clock across every replica, so fleet
        latency percentiles pool comparable per-request stamps."""
        self._t0 = time.perf_counter() if t0 is None else t0
        for eng in self.replicas:
            eng.start(self._t0)

    def step(self, wait: bool = True) -> bool:
        """One fleet iteration: place every arrived request (best
        effective priority first), then give every replica one
        non-blocking scheduler step.  Returns ``False`` when the whole
        fleet is drained."""
        now = time.perf_counter() - self._t0
        while True:
            req = self._queue.pop(now)
            if req is None:
                break
            self._dispatch(req, now)
        alive = False
        for eng in self.replicas:
            alive = eng.step(wait=False) or alive
        if alive:
            return True
        if not len(self._queue):
            return False
        if wait:                       # fleet idle until the next arrival
            nxt = self._queue.next_arrival()
            wait_s = nxt - (time.perf_counter() - self._t0)
            if wait_s > 0:
                time.sleep(min(wait_s, 0.05))
        return True

    def drain(self) -> dict[int, Request]:
        """Collect finished requests from every replica, re-keyed by
        their global ids."""
        drained = [eng.drain() for eng in self.replicas]
        out = {}
        for gid, (ri, lid) in list(self._placed.items()):
            req = drained[ri].get(lid)
            if req is not None:
                out[gid] = req
                del self._placed[gid]
        return out

    def run(self) -> dict[int, Request]:
        """Drain the fleet; returns {global_id: Request} with the same
        per-request timing contract as ``Engine.run``."""
        self.start()
        while self.step():
            pass
        return self.drain()

    def reset(self, rng=None) -> None:
        for eng in self.replicas:
            eng.reset(rng)
        self._queue = _PriorityQueue(self.scfg.priority_aging_s)
        self._placed = {}
        self.placements = [0] * len(self.replicas)
        self.affinity_hits = [0] * len(self.replicas)
        self.placement_order = []

    # ------------------------------------------------------------------
    # fleet-level reporting
    # ------------------------------------------------------------------

    @property
    def compile_counts(self) -> dict:
        """Element-wise max over replicas: every replica must hold the
        per-stage pins, so the fleet-level count equals the single-
        engine contract ({prefill: 1, ...}) — any replica recompiling
        pushes a key above its pin and the benchmark raises."""
        out: dict[str, int] = {}
        for eng in self.replicas:
            for k, v in eng.compile_counts.items():
                out[k] = max(out.get(k, 0), v)
        return out

    @property
    def stats(self) -> dict:
        """Fleet aggregation of ``Engine.stats``: counters are summed,
        rates are re-derived from their summed numerators and
        denominators (never averaged — a replica that served one
        request must not weigh as much as one that served a hundred),
        occupancy is the mean over replicas (same pool size each), and
        ``per_replica`` carries the placement split: how many requests
        each replica got and what fraction of them hit its prefix
        index (the affinity metric)."""
        per = [eng.stats for eng in self.replicas]
        n = len(self.replicas)
        cached = sum(e._cached_prompt_tokens for e in self.replicas)
        total_p = sum(e._total_prompt_tokens for e in self.replicas)
        proposed = sum(e.spec_proposed for e in self.replicas)
        accepted = sum(e.spec_accepted for e in self.replicas)
        spec_toks = sum(e.spec_tokens for e in self.replicas)
        slot_rounds = sum(e.spec_slot_rounds for e in self.replicas)
        return {
            "preemptions": sum(s["preemptions"] for s in per),
            "occupancy": sum(s["occupancy"] for s in per) / n,
            "concurrency": sum(s["concurrency"] for s in per),
            "pool_pages": sum(s["pool_pages"] for s in per),
            "prefix_hits": sum(s["prefix_hits"] for s in per),
            "prefix_hit_rate": cached / max(1, total_p),
            "prefill_tokens": sum(s["prefill_tokens"] for s in per),
            "cow_copies": sum(s["cow_copies"] for s in per),
            "prefix_pages": sum(s["prefix_pages"] for s in per),
            "spec_rounds": sum(s["spec_rounds"] for s in per),
            "acceptance_rate": accepted / max(1, proposed),
            "tokens_per_step": spec_toks / max(1, slot_rounds),
            "spec_rollback_pages": sum(s["spec_rollback_pages"]
                                       for s in per),
            "prefill_waves": sum(s["prefill_waves"] for s in per),
            "decode_chunks": sum(s["decode_chunks"] for s in per),
            "prefill_rows": sum(s["prefill_rows"] for s in per),
            "decode_rows": sum(s["decode_rows"] for s in per),
            "queue_wait_s": sum(s["queue_wait_s"] for s in per),
            "swap_out": sum(s["swap_out"] for s in per),
            "swap_in": sum(s["swap_in"] for s in per),
            "replay_steps_saved": sum(s["replay_steps_saved"]
                                      for s in per),
            "host_pages": sum(s["host_pages"] for s in per),
            "prefix_cold_pages": sum(s["prefix_cold_pages"] for s in per),
            "prefix_cold_hits": sum(s["prefix_cold_hits"] for s in per),
            "prefix_demotions": sum(s["prefix_demotions"] for s in per),
            "dp_replicas": n,
            "placements": list(self.placements),
            "per_replica": [
                {"replica": i,
                 "placed": self.placements[i],
                 "affinity_hits": self.affinity_hits[i],
                 "affinity_hit_rate": round(
                     self.affinity_hits[i] / max(1, self.placements[i]),
                     3),
                 "prefix_hit_rate": round(per[i]["prefix_hit_rate"], 3),
                 "preemptions": per[i]["preemptions"],
                 "occupancy": round(per[i]["occupancy"], 3),
                 "concurrency": round(per[i]["concurrency"], 2)}
                for i in range(n)],
        }

    @property
    def cache_token_bytes(self) -> int:
        return self.replicas[0].cache_token_bytes

    @property
    def mesh_shape(self) -> tuple:
        """Per-replica (data, model) mesh shape (replicas are uniform)."""
        return self.replicas[0].mesh_shape

    @property
    def device_count(self) -> int:
        """Distinct devices the fleet spans (replicas sharing the
        default device count it once)."""
        return len({d for eng in self.replicas for d in eng.devices})

    def release_prefix_cache(self) -> None:
        for eng in self.replicas:
            eng.release_prefix_cache()

    def leaked_pages(self) -> int:
        """Sum of per-replica leak counters (release the prefix caches
        first; non-zero after a full drain is a bug)."""
        return sum(eng.leaked_pages() for eng in self.replicas)
