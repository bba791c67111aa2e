"""Serving launchers: the LM decode engine and the cohort-selection service.

``Server`` is a continuous-batching LM server: a
:class:`DecodeScheduler` owns a **slot table** (one KV-cache slot per
batch lane, independently resettable) and a request queue.  Finished or
cache-full requests retire their slot *mid-decode* and the next queued
request is admitted into it — a slot-targeted prefill
(``lm_prefill_slot``) fills only that lane — so the decode jit keeps
running at full batch width with per-slot active masking.  Decode runs
with **per-request cache positions**: row i writes its token's KV at
its own ``pos[i]`` and attends only ``[0, pos[i]]``, which makes
heterogeneous prompt lengths *exact* — each request's continuation is
bit-identical to decoding it alone (pad and stale-slot KV can never
leak).  ``serve_batch`` survives as a thin wrapper (submit + drain);
the decode step is exactly the one the dry-run lowers for decode_32k.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --batch 4 --prompt-len 32 --gen-len 32 --requests 12 --mixed

``CohortServer`` is the federated control-plane counterpart: it owns the
live client-embedding table (versioned, copy-on-write, so embedding
updates never tear a concurrent selection) and a
``repro.cohort.CohortEngine``, and answers cohort requests either with a
cluster-stratified draw (``policy="stratified"``) or with the paper's
Algorithm II (``policy="dqn"``): a :class:`repro.policy.ClusterPolicy`
scores the clusters and draws the cohort ε-greedily, trained online from
the accuracy signal reported back via ``observe_round``.  Because the
engine warm-starts and fingerprint-caches between requests, steady-state
selection cost is dominated by the (N, m) cross-affinity — sharded over
the cohort mesh when more than one device is visible.  ``stats()``
exposes the whole serving picture: engine cache/warm/cold counters,
per-phase latencies, table version, and the policy's ε / replay fill.

  PYTHONPATH=src python -m repro.launch.serve --cohort 100000 \
      --cohort-size 64 --landmarks kmeans++ --policy dqn --rounds 5

Multi-tenant serving lives one layer up, in
``repro.launch.frontend.CohortFrontend``: named per-model-family shards
(each a ``CohortServer``) and a coalescing select path that batches
concurrent same-version requests behind one engine solve
(``CohortServer.select_cohorts``).  ``--tenants T`` switches the
``--cohort`` demo to that frontend:

  PYTHONPATH=src python -m repro.launch.serve --cohort 20000 \
      --tenants 4 --concurrency 16 --cohort-size 64 --rounds 5
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import threading
import time
from typing import Deque, List, Optional

import numpy as np

from repro import obs

#: smoothing factor for the decode tokens/sec EMA in DecodeScheduler.stats().
_TOK_S_EMA = 0.2


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int
    generated: Optional[List[int]] = None


class DecodeScheduler:
    """Continuous-batching decode engine: slot table + request queue.

    One KV-cache **slot** per batch lane (``repro.models.transformer
    .init_lm_cache`` — leaves stacked ``(repeats, batch, ...)``, batch
    axis = slot table).  The loop per :meth:`step`:

    1. **admit** — every free slot pops the queue: the new request's
       prompt is prefilled *into that slot only*
       (``lm_prefill_slot`` zeroes the lane and fills it; other slots
       keep decoding state untouched), its first token is sampled from
       its own last-prompt-position logits, and the slot's cache
       position starts at the true (unpadded) prompt length.
    2. **decode** — ONE jitted ``lm_decode_step`` over the full batch
       with per-request positions: row i writes at ``pos[i]`` and
       attends ``[0, pos[i]]``, so pad/stale-slot KV cannot leak and
       mixed-length continuations are exact.  Empty slots ride along
       masked-inactive (their logits are discarded and they generate
       nothing — no wasted "filler" steps are ever accounted).
    3. **retire** — requests that produced ``max_new_tokens`` tokens
       (or filled the cache: ``truncated``) free their slot mid-decode
       for the next admit.

    Sampling is vectorized: greedy argmax, or Gumbel-max for
    temperature sampling (``argmax(logits/T + Gumbel)`` is one exact
    softmax draw per row — no per-row Python ``rng.choice`` loop).
    Everything is deterministic under a fixed seed.

    Prompts are right-padded to a multiple of ``prefill_bucket`` to
    bound jit retraces (one per distinct padded length).  Bucketing
    never changes results: the first token is sampled at the true last
    prompt position (causal attention — pad cannot leak backwards) and
    every padded KV entry is overwritten by the real decode write at
    that position before the mask ever exposes it.

    Thread-safe: ``submit`` may race ``step``/``drain`` from another
    thread.  ``_sched_lock`` (slot table + queue) and ``_stats_lock``
    (counters, innermost) are ranked in
    ``repro.analysis.watchdog.SERVING_LOCK_ORDER``.
    """

    def __init__(self, cfg, params, batch: int, max_seq: int, *,
                 seed: int = 0, temperature: float = 0.0,
                 prefill_bucket: int = 8):
        import jax
        from repro.models import transformer as T

        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self.temperature = temperature
        self.prefill_bucket = max(1, int(prefill_bucket))
        self._rng = np.random.default_rng(seed)
        self._prefill_slot = jax.jit(
            lambda p, t, c, slot, last: T.lm_prefill_slot(
                p, cfg, {"tokens": t}, c, slot, last_pos=last))
        self._decode = jax.jit(
            lambda p, t, c, pos: T.lm_decode_step(p, cfg, t, c, pos))

        # slot table + queue (one writer at a time under _sched_lock;
        # _stats_lock is the innermost leaf for dashboard counters)
        self._sched_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self.caches = T.init_lm_cache(cfg, batch, max_seq)  # guarded-by: _sched_lock
        self._reqs: List[Optional[Request]] = [None] * batch  # guarded-by: _sched_lock
        self._pos = np.zeros(batch, np.int32)       # guarded-by: _sched_lock
        self._tok = np.zeros(batch, np.int32)       # guarded-by: _sched_lock
        self._need = np.zeros(batch, np.int64)      # guarded-by: _sched_lock
        self._queue: Deque[Request] = collections.deque()  # guarded-by: _sched_lock
        self._completed: List[Request] = []         # guarded-by: _sched_lock
        self._counters = {  # guarded-by: _stats_lock
            "admitted": 0, "retired": 0, "truncated": 0, "prefills": 0,
            "decode_steps": 0, "decode_tokens": 0, "tokens_generated": 0}
        self._decode_seconds = 0.0                  # guarded-by: _stats_lock
        self._tok_s_ema = 0.0                       # guarded-by: _stats_lock

    # -- sampling ---------------------------------------------------------
    def _sample(self, logits: np.ndarray) -> np.ndarray:
        """Greedy argmax, or one vectorized Gumbel-max softmax draw per
        row (identical in distribution to ``rng.choice(p=softmax)``)."""
        if self.temperature <= 0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        z = logits / self.temperature
        g = self._rng.gumbel(size=z.shape)
        return np.argmax(z + g, axis=-1).astype(np.int32)

    # -- request intake ---------------------------------------------------
    def submit(self, request: Request) -> None:
        """Enqueue one request; it is admitted when a slot frees up."""
        plen = len(request.prompt)
        if plen < 1:
            raise ValueError(f"request {request.uid}: empty prompt")
        if plen > self.max_seq:
            raise ValueError(
                f"request {request.uid}: prompt length {plen} exceeds "
                f"max_seq {self.max_seq}")
        with self._sched_lock:
            self._queue.append(request)

    # -- scheduler core ---------------------------------------------------
    def step(self) -> bool:
        """One scheduler tick: admit, decode once, retire.

        Admission pops the queue into every free slot (a request with
        ``max_new_tokens <= 0`` completes immediately without touching a
        slot — no filler decode steps, no skewed timing); decode runs
        ONE jitted step over the full batch with inactive slots masked;
        finished or cache-full requests retire their slot mid-decode.
        Returns False only when the engine is fully idle (no queued
        requests, no active slots) — the drain-loop termination signal.
        """
        import jax.numpy as jnp

        with self._sched_lock:
            # -- admit -------------------------------------------------
            worked = False
            for i in range(self.batch):
                if self._reqs[i] is not None:
                    continue
                if not self._queue:
                    break
                req = self._queue.popleft()
                worked = True
                req.generated = []
                if req.max_new_tokens <= 0:
                    self._completed.append(req)
                    with self._stats_lock:
                        self._counters["retired"] += 1
                    continue
                plen = len(req.prompt)
                bucket = self.prefill_bucket
                padded = min(self.max_seq, -(-plen // bucket) * bucket)
                toks = np.zeros((1, padded), np.int32)
                toks[0, :plen] = req.prompt
                logits, self.caches = self._prefill_slot(
                    self.params, jnp.asarray(toks), self.caches,
                    jnp.int32(i), jnp.asarray([plen - 1], np.int32))
                first = int(self._sample(np.asarray(logits))[0])
                req.generated.append(first)
                # done at admit: single-token request, or no cache room
                # left to write the first token's KV for further decode
                done_now = req.max_new_tokens == 1 or plen >= self.max_seq
                with self._stats_lock:
                    self._counters["admitted"] += 1
                    self._counters["prefills"] += 1
                    self._counters["tokens_generated"] += 1
                    if done_now:
                        self._counters["retired"] += 1
                        if req.max_new_tokens > 1:
                            self._counters["truncated"] += 1
                if done_now:
                    self._completed.append(req)
                    continue
                self._reqs[i] = req
                self._pos[i] = plen
                self._tok[i] = first
                self._need[i] = req.max_new_tokens - 1

            # -- decode ------------------------------------------------
            active = np.flatnonzero(self._need > 0)
            if active.size == 0:
                return worked
            t0 = time.perf_counter()
            logits, self.caches = self._decode(
                self.params, jnp.asarray(self._tok[:, None]), self.caches,
                jnp.asarray(self._pos))
            nxt = self._sample(np.asarray(logits))
            dt = time.perf_counter() - t0

            # -- retire ------------------------------------------------
            retired = truncated = 0
            for i in active:
                req = self._reqs[i]
                req.generated.append(int(nxt[i]))
                self._tok[i] = nxt[i]
                self._pos[i] += 1
                self._need[i] -= 1
                if self._need[i] <= 0:
                    self._reqs[i] = None
                    self._need[i] = 0
                    self._completed.append(req)
                    retired += 1
                elif self._pos[i] >= self.max_seq:
                    # cache full: retire mid-decode with what we have
                    self._reqs[i] = None
                    self._need[i] = 0
                    self._completed.append(req)
                    retired += 1
                    truncated += 1
            with self._stats_lock:
                # count only REAL generated tokens — inactive/filler
                # slots produce nothing (the old lockstep loop divided
                # batch*steps by wall time and over-counted)
                self._counters["retired"] += retired
                self._counters["truncated"] += truncated
                self._counters["decode_steps"] += 1
                self._counters["decode_tokens"] += int(active.size)
                self._counters["tokens_generated"] += int(active.size)
                self._decode_seconds += dt
                rate = active.size / max(dt, 1e-9)
                self._tok_s_ema = (
                    rate if self._counters["decode_steps"] == 1
                    else self._tok_s_ema
                    + _TOK_S_EMA * (rate - self._tok_s_ema))
        return True

    def completed(self) -> List[Request]:
        """Harvest requests finished so far without driving the engine
        (streaming callers interleave this with :meth:`step`)."""
        with self._sched_lock:
            done, self._completed = self._completed, []
        return done

    def drain(self) -> List[Request]:
        """Run the scheduler until idle; return newly completed requests."""
        while self.step():
            pass
        return self.completed()

    # -- observability ----------------------------------------------------
    def stats(self) -> dict:
        """Serving dashboard: slot occupancy, queue depth, counters.

        ``admitted`` / ``retired`` / ``truncated`` count requests
        (truncated = retired early because the slot's cache filled);
        ``decode_tokens`` counts only tokens actually generated by
        decode steps (inactive slots contribute nothing);
        ``tokens_generated`` additionally includes each request's first
        token, sampled at prefill; ``tok_s_ema`` smooths the per-step
        decode rate with factor ``_TOK_S_EMA``.
        """
        with self._sched_lock:
            occupied = sum(r is not None for r in self._reqs)
            queue_depth = len(self._queue)
            with self._stats_lock:
                counters = dict(self._counters)
                decode_seconds = self._decode_seconds
                tok_s_ema = self._tok_s_ema
        return {
            **counters,
            "slots": self.batch,
            "occupied": occupied,
            "queue_depth": queue_depth,
            "decode_seconds": decode_seconds,
            "tok_s_ema": tok_s_ema,
        }


class Server:
    """Continuous-batching LM server over a :class:`DecodeScheduler`.

    ``serve_batch`` is the compatibility wrapper around the scheduler:
    submit every request, drain, return them (mutated in place, original
    order).  For streaming workloads use :meth:`submit` /
    :meth:`DecodeScheduler.step` / :meth:`drain` directly.
    """

    def __init__(self, cfg, batch: int, max_seq: int, *, seed: int = 0,
                 temperature: float = 0.0, prefill_bucket: int = 8):
        import jax
        from repro.models import transformer as T

        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self.temperature = temperature
        self.params = T.init_lm(jax.random.PRNGKey(seed), cfg)
        self.scheduler = DecodeScheduler(
            cfg, self.params, batch, max_seq, seed=seed,
            temperature=temperature, prefill_bucket=prefill_bucket)
        self.last_decode_tok_s = 0.0

    def submit(self, request: Request) -> None:
        self.scheduler.submit(request)

    def drain(self) -> List[Request]:
        return self.scheduler.drain()

    def stats(self) -> dict:
        """Scheduler stats plus the last ``serve_batch`` decode rate."""
        return {**self.scheduler.stats(),
                "last_decode_tok_s": self.last_decode_tok_s}

    def serve_batch(self, requests: List[Request]) -> List[Request]:
        """Serve ``requests`` to completion (any count — the queue admits
        them as slots free up) and return them in the original order.

        ``last_decode_tok_s`` counts only real generated tokens over
        the decode wall time of this call — short or absent requests no
        longer inflate the rate, and partial batches run no filler
        decode steps at all.
        """
        if not requests:
            return []
        before = self.scheduler.stats()
        for req in requests:
            self.scheduler.submit(req)
        self.scheduler.drain()
        after = self.scheduler.stats()
        toks = after["decode_tokens"] - before["decode_tokens"]
        secs = after["decode_seconds"] - before["decode_seconds"]
        self.last_decode_tok_s = toks / max(secs, 1e-9)
        return list(requests)


#: smoothing factor for the server's per-phase latency EMAs.
_LATENCY_EMA = 0.2
#: smoothing factor for the per-cluster reward EMAs in the policy state
#: (independent knob from the latency smoothing; they just share a value).
_REWARD_EMA = 0.2


class CohortServer:
    """Cohort-selection service backed by a :class:`CohortEngine`.

    Holds the latest (N, d) client-embedding table (updated as client
    deltas stream in via ``update_embeddings``) and serves
    ``select_cohort(size)`` requests: the engine clusters the table —
    dense, Nyström, or mesh-sharded Nyström depending on N and devices —
    and the cohort is drawn from the clusters by the configured policy:

    * ``policy="stratified"`` — round-robin across clusters, the
      uniform de-biasing draw.
    * ``policy="dqn"`` — the paper's Algorithm II: a
      :class:`repro.policy.ClusterPolicy` (cluster-level Deep-Q agent)
      chooses the cluster for every cohort slot ε-greedily; callers
      report each round's resulting accuracy via :meth:`observe_round`,
      which shapes the reward (FAVOR's ``Ξ^(acc − target) − 1``),
      updates the replay buffer, and takes one TD training step — the
      policy learns online which clusters to favor while serving.

    Concurrency: the embedding table is **versioned copy-on-write with a
    coalesced delta buffer** — ``update_embeddings`` appends the changed
    rows (O(delta), no full-table copy) and bumps the version;
    ``snapshot`` materializes a fresh immutable table only when deltas
    are actually pending, so a million-client table is not re-shipped
    per round and a selection in flight always clusters one internally
    consistent table.  Selections are serialized on ``_select_lock``;
    engine entries (inline or background) are serialized on
    ``_solve_lock`` because the engine's warm-start state is
    single-writer.  Embedding updates only invalidate the engine's
    exact-match cache; small drift keeps the warm-start path, so
    steady-state request latency excludes landmark reselection and cold
    eigensolves.

    Streaming (``streaming=StreamingSpec(...)``): re-clustering moves
    off the select path entirely — every ``update_embeddings`` marks the
    table dirty on a :class:`repro.streaming.BackgroundSolver`, whose
    worker snapshots the freshest table, runs ``engine.prepare`` +
    ``publish`` under ``_solve_lock``, and parks the finished
    ``(version, table, result)`` in the ``_published`` mailbox.  The
    next select swaps the mailbox into ``_served`` and draws from it —
    no solve inline — unless the served version has fallen more than
    ``max_stale_versions`` behind the table, which forces one inline
    solve (bounded staleness).  See docs/ARCHITECTURE.md ("Streaming
    re-clustering").

    Serving state (``policy="dqn"``): the per-solve half of
    ``cluster_policy_state`` (population share and dispersion, one
    O(N·d) pass) is memoized for the solve it describes, keyed on that
    solve's table and ``assign`` objects, both immutable.  A select that
    swaps in or solves a new result rebuilds it; its ``observe_round``
    and every later select on the same solve reuse it (``hit`` on the
    ``policy.state`` span; ``state_stats_hits`` / ``state_stats_builds``
    in :meth:`stats`).

    Cohort draws (both policies) go through a
    :class:`repro.policy.ClusterIndex` of the served ``assign``, the
    clients of each cluster grouped by one O(N) sort.  It is memoized
    the same way, keyed on that ``assign`` object, so every select on
    one served solve reuses it (``hit`` on the ``cohort.pools`` span;
    ``pool_index_hits`` / ``pool_index_builds`` in :meth:`stats`); each
    batch draws through a fresh :class:`repro.policy.ClusterPools` view
    and leaves the index whole.

    Args:
        num_clients:  N, rows of the embedding table.
        embed_dim:    d, embedding width.
        config:       :class:`repro.cohort.CohortConfig` for the engine.
        seed:         seeds the engine, the draw rng, and the Q-network.
        policy:       "stratified" | "dqn".
        target_accuracy: reward pivot for the DQN policy's shaping.
        dqn_overrides: DQNConfig field overrides for ``policy="dqn"``.
        state_features: DQN serving-state layout — ``"rich"`` (default,
            ``5k + 1``: + per-cluster embedding dispersion and
            staleness), ``"system"`` (``7k + 1``: + per-cluster
            availability and mean-latency EMAs fed by
            ``observe_round(outcome=...)`` from the client-realism
            layer, so the policy can learn to avoid slow/flaky
            clusters), or ``"basic"`` (the legacy ``3k + 1``
            participation-only state; keeps replay buffers recorded
            against the narrow shape loadable).
        streaming:    :class:`repro.streaming.StreamingSpec` enabling
            double-buffered background re-clustering (+ admission knobs
            for the singular ``select_cohort`` path); None = solve
            inline as before.
        solver:       share a :class:`repro.streaming.BackgroundSolver`
            across servers (the frontend does); None with ``streaming``
            set creates (and owns) a private one.
        deduper:      share a :class:`repro.streaming.SolveDeduper` so
            identical-fingerprint tenants ride one solve; None disables
            dedupe for this server.
        mesh:         cohort mesh for the engine's sharded path; None
            spans every visible device (``make_cohort_mesh``).
    """

    POLICIES = ("stratified", "dqn")

    def __init__(self, num_clients: int, embed_dim: int, *,
                 config=None, seed: int = 0, policy: str = "stratified",
                 target_accuracy: float = 0.85,
                 dqn_overrides: Optional[dict] = None,
                 state_features: str = "rich",
                 streaming=None, solver=None, deduper=None, mesh=None):
        from repro.cohort import CohortConfig, CohortEngine
        from repro.fed.metrics import serving_state_dim

        if policy not in self.POLICIES:
            raise ValueError(f"unknown policy {policy!r}; "
                             f"expected one of {self.POLICIES}")
        self.config = config or CohortConfig()
        self.engine = CohortEngine(self.config, seed=seed, mesh=mesh)
        self.rng = np.random.default_rng(seed)
        self.policy_name = policy
        self.target_accuracy = target_accuracy
        self.state_features = state_features
        k = self.config.num_clusters
        state_dim = serving_state_dim(k, state_features)  # validates knob
        if policy == "dqn":
            from repro.policy import ClusterPolicy
            # serving state = cluster_policy_state(): per-cluster
            # population / participation / reward EMA (+ dispersion and
            # staleness when "rich") + the last reported global accuracy
            self.policy = ClusterPolicy(k, state_dim=state_dim, seed=seed,
                                        dqn_overrides=dqn_overrides,
                                        state_features=state_features)
        else:
            self.policy = None

        table = np.zeros((num_clients, embed_dim), np.float32)
        table.setflags(write=False)       # snapshots must stay immutable
        self._write_lock = threading.Lock()
        self._select_lock = threading.Lock()
        # serializes engine entries: the inline select path and the
        # background solver's prepare/publish both mutate the engine's
        # warm-start state (ranked between _select_lock and _write_lock
        # in SERVING_LOCK_ORDER)
        self._solve_lock = threading.Lock()
        # mailbox the background solver fills and the select path drains
        self._publish_lock = threading.Lock()
        # leaf lock for dashboard state (innermost — see
        # repro.analysis.watchdog.SERVING_LOCK_ORDER): counters and
        # latency EMAs are mutated from BOTH the update path
        # (_write_lock held) and the select path (_select_lock held)
        # and read by stats(), so they need a lock of their own rather
        # than whichever path's lock happened to be held.
        self._stats_lock = threading.Lock()
        # versioned copy-on-write base + coalesced pending deltas:
        # update_embeddings appends O(delta) rows here and snapshot()
        # materializes base+deltas into a fresh immutable table lazily
        self._version = 0                 # guarded-by: _write_lock
        self._base = table                # guarded-by: _write_lock
        self._delta_ids: List[np.ndarray] = []    # guarded-by: _write_lock
        self._delta_rows: List[np.ndarray] = []   # guarded-by: _write_lock
        self._delta_pending = 0           # guarded-by: _write_lock
        self._materializations = 0        # guarded-by: _write_lock
        self._rows_materialized = 0       # guarded-by: _write_lock

        # streaming double-buffer: _published is the background solver's
        # finished (version, table, result); _served is the pair selects
        # currently draw from
        self._streaming = streaming
        self._published = None            # guarded-by: _publish_lock
        # a select has swapped the mailbox's solve in
        self._published_served = False    # guarded-by: _publish_lock
        self._served = None               # guarded-by: _select_lock
        self._closed = False              # guarded-by: _select_lock
        self._deduper = deduper
        self._own_solver = streaming is not None and solver is None
        if self._own_solver:
            from repro.streaming import BackgroundSolver
            solver = BackgroundSolver(streaming.solver_workers)
        self._solver = solver if streaming is not None else None
        self.admission = None
        if streaming is not None and (streaming.max_queue_depth is not None
                                      or streaming.rate_per_s is not None):
            from repro.streaming import AdmissionController
            self.admission = AdmissionController(
                max_queue_depth=streaming.max_queue_depth,
                rate_per_s=streaming.rate_per_s, burst=streaming.burst)

        self._participation = np.zeros(k, np.float64)   # guarded-by: _select_lock
        self._reward_ema = np.zeros(k, np.float32)      # guarded-by: _select_lock
        # selects since each cluster last contributed a served client
        # (the "rich" state's staleness feature)
        self._staleness = np.zeros(k, np.float64)       # guarded-by: _select_lock
        # client-realism EMAs behind the "system" state: per-cluster
        # completion rate and mean simulated latency, fed by
        # observe_round(outcome=...); availability starts optimistic (1)
        self._avail_ema = np.ones(k, np.float64)        # guarded-by: _select_lock
        self._latency_ema_s = np.zeros(k, np.float64)   # guarded-by: _select_lock
        # cluster assignment of the latest served solve (any policy) —
        # maps an observe_round outcome's client ids back to clusters
        self._last_assign = None                        # guarded-by: _select_lock
        self.prev_accuracy = 0.0                        # guarded-by: _select_lock
        # sequence number of the latest select (spans' ``seq``)
        self._select_seq = 0                            # guarded-by: _select_lock
        # parked (state_vec, actions, assign, table) until observe_round
        self._pending = None                            # guarded-by: _select_lock
        # (table, assign, cluster_solve_stats) of the solve the serving
        # state last described: reused while selects and observes serve
        # that same solve, rebuilt when another is served
        self._state_memo = None                         # guarded-by: _select_lock
        # (assign, ClusterIndex) of the solve last drawn from
        self._index_memo = None                         # guarded-by: _select_lock
        self._latency = {  # guarded-by: _stats_lock
            "solve_s": 0.0, "draw_s": 0.0, "total_s": 0.0}
        # running means per RoundResult.timings phase
        self._round_timings: dict = {}                  # guarded-by: _stats_lock
        self._counters = {  # guarded-by: _stats_lock
            "requests": 0, "batches": 0, "updates": 0,
            "rounds_observed": 0, "dropped_transitions": 0,
            # streaming: background warms landed / selects answered from
            # a warmed result / selects that had to solve inline / warms
            # adopted from another tenant's identical-fingerprint solve /
            # warms replaced in the mailbox before any select served them
            "warm_ahead": 0, "served_warm": 0, "forced_inline": 0,
            "dedupe_hit": 0, "superseded": 0,
            # DQN serving states whose per-solve half came from the memo
            # (the served solve was the one last described) / was built
            "state_stats_hits": 0, "state_stats_builds": 0,
            # selects whose cluster index came from the memo / was built
            "pool_index_hits": 0, "pool_index_builds": 0}
        self.last_select_s = 0.0                        # guarded-by: _select_lock

    # -- embedding table (versioned copy-on-write + delta buffer) --------
    @property
    def embeds(self) -> np.ndarray:
        """Current (read-only) embedding-table snapshot."""
        return self.snapshot()[1]

    @property
    def version(self) -> int:
        """Table version; bumps on every ``update_embeddings``."""
        return self._version

    def snapshot(self):
        """Read a consistent ``(version, table)``; the table is immutable.

        Materializes pending deltas into a fresh copy-on-write table
        only when there are any — repeated snapshots between updates
        return the same frozen array, and readers holding an older
        snapshot are never affected.
        """
        with obs.span("cohort.snapshot") as sp:
            version, table, rows = self._flush()
            sp.set_metadata(rows=rows)
        return version, table

    def _flush(self):
        """Apply pending deltas to the base table (self-locking).

        Returns ``(version, table, rows materialized)``.
        """
        with self._write_lock:
            rows = self._delta_pending
            if rows:
                table = self._base.copy()
                for ids, delta in zip(self._delta_ids, self._delta_rows):
                    table[ids] = delta
                table.setflags(write=False)
                self._base = table
                self._delta_ids = []
                self._delta_rows = []
                self._delta_pending = 0
                self._materializations += 1
                self._rows_materialized += rows
            return self._version, self._base, rows

    def update_embeddings(self, client_ids, new_embeds) -> None:
        """Replace the embedding rows of ``client_ids``.

        O(delta): the rows are appended to a pending-delta buffer and
        the version bumps; the O(N·d) materialization happens at the
        next :meth:`snapshot` (deltas applied in arrival order, so
        later writes to the same client win).  Readers holding a
        previous snapshot are unaffected.  When ``streaming`` is
        enabled the update also marks this server dirty on the
        background solver, so a fresh solve starts warming immediately.
        """
        with obs.span("cohort.update") as sp:
            ids = np.array(client_ids, dtype=np.int64)   # copy: deferred
            rows = np.array(new_embeds, dtype=np.float32)
            n, d = self._base.shape
            if rows.ndim != 2 or rows.shape != (len(ids), d):
                raise ValueError(
                    f"rows shape {rows.shape} != ({len(ids)}, {d})")
            if len(ids) and (ids.min() < -n or ids.max() >= n):
                raise IndexError(f"client_ids out of range for {n} clients")
            flush_now = False
            with self._write_lock:
                self._delta_ids.append(ids)
                self._delta_rows.append(rows)
                self._delta_pending += len(ids)
                self._version += 1
                version = self._version
                # bound the buffer: once pending rows rival the table
                # size a materialization is no longer a saving, only
                # deferred work
                flush_now = self._delta_pending >= n
            sp.set_metadata(version=version, rows=len(ids))
            if flush_now:
                with obs.span("cohort.flush"):
                    self._flush()
            with self._stats_lock:
                self._counters["updates"] += 1
            if self._solver is not None:
                self._solver.submit(id(self), self._background_warm)

    # -- streaming (background warm + shutdown) ---------------------------
    def _background_warm(self) -> None:
        """Solve-ahead task run on a :class:`BackgroundSolver` worker.

        Snapshots the freshest table, computes (or, with dedupe, adopts)
        a :class:`repro.cohort.PreparedSolve` for it, publishes it into
        the engine under ``_solve_lock``, and parks the finished
        ``(version, table, result)`` in the ``_published`` mailbox for
        the next select to swap in.  Never takes ``_select_lock`` — the
        serving path is never blocked behind a background solve.
        """
        with obs.span("cohort.warm", adopted=0) as sp:
            version, table = self.snapshot()
            sp.set_metadata(version=version)
            with self._publish_lock:
                pub = self._published
            if pub is not None and pub[0] >= version:
                return                  # already warmed this generation
            ticket = prep = None
            if self._deduper is not None:
                from repro.cohort import CohortEngine
                # key on (table content, engine config): identical tables
                # under different cluster counts / methods must NOT share
                # a solve — the adopted result's k would be wrong
                ticket, prep = self._deduper.begin(
                    (CohortEngine.fingerprint(table), repr(self.config)))
            if prep is not None:        # adopt another tenant's solve
                sp.set_metadata(adopted=1)
                with self._solve_lock:
                    res = self.engine.publish(prep, count=False)
                with self._stats_lock:
                    self._counters["dedupe_hit"] += 1
            else:
                try:
                    with self._solve_lock:
                        own = self.engine.prepare(table)
                        res = (None if own is None
                               else self.engine.publish(own))
                except BaseException:
                    if ticket is not None:
                        self._deduper.abort(ticket)
                    raise
                if ticket is not None:
                    if own is not None:
                        self._deduper.complete(ticket, own)
                    else:
                        self._deduper.abort(ticket)
                if res is None:
                    return              # engine already current: no-op
            replaced = False
            with obs.span("cohort.mailbox", version=version) as mb, \
                    self._publish_lock:
                if self._published is None or version > self._published[0]:
                    replaced = (self._published is not None
                                and not self._published_served)
                    self._published = (version, table, res)
                    self._published_served = False
                mb.set_metadata(replaced=int(replaced))
            with self._stats_lock:
                self._counters["warm_ahead"] += 1
                self._counters["superseded"] += int(replaced)

    def close(self, timeout: Optional[float] = None) -> None:
        """Stop serving: reject new selects, stop an owned solver.

        New ``select_cohort(s)`` calls raise
        :class:`repro.streaming.ServiceClosedError`; a background solver
        created by this server (not a shared one) is drained and joined.
        Idempotent.
        """
        with self._select_lock:
            self._closed = True
        if self._own_solver and self._solver is not None:
            self._solver.close(timeout)

    # -- serving ----------------------------------------------------------
    def _policy_state(self, assign: np.ndarray, table: np.ndarray):
        """The DQN serving state for the solve ``(assign, table)``.

        Returns ``(state, memo, hit)``.  The per-solve half of the state
        (``cluster_solve_stats``: population share, dispersion) comes
        from the memo when it describes this solve, i.e. holds these very
        ``table`` and ``assign`` objects (both immutable), else is built
        afresh; ``memo`` is the entry describing this solve, which the
        caller, holding ``_select_lock``, stores back.  The O(k) half
        (participation, reward EMA, staleness, availability, latency,
        previous accuracy) is read anew on every call.
        """
        from repro.fed.metrics import cluster_policy_state, cluster_solve_stats
        rich = self.state_features in ("rich", "system")
        system = self.state_features == "system"
        k = self.config.num_clusters
        memo = self._state_memo
        hit = memo is not None and memo[0] is table and memo[1] is assign
        if not hit:
            memo = (table, assign,
                    cluster_solve_stats(assign, table if rich else None, k))
        state = cluster_policy_state(
            assign, k,
            self._participation, self._reward_ema, self.prev_accuracy,
            staleness=self._staleness if rich else None,
            availability=self._avail_ema if system else None,
            latency_s=self._latency_ema_s if system else None,
            features=self.state_features, solve_stats=memo[2])
        return state, memo, hit

    def _cluster_index(self, assign: np.ndarray):
        """The :class:`repro.policy.ClusterIndex` of ``assign``.

        Returns ``(memo, hit)``: ``memo`` is ``(assign, index)``, taken
        from the memo when it holds this very ``assign`` object
        (immutable), else built; the caller, holding ``_select_lock``,
        stores it back.
        """
        from repro.policy import ClusterIndex
        memo = self._index_memo
        hit = memo is not None and memo[0] is assign
        if not hit:
            memo = (assign, ClusterIndex(assign, self.config.num_clusters))
        return memo, hit

    def select_cohort(self, cohort_size: int):
        """Serve one cohort; returns ``(client_ids, CohortResult)``.

        ``client_ids`` has ``cohort_size`` entries unless the table has
        fewer clients.  With ``policy="dqn"`` the draw's (state,
        actions) pair is parked until :meth:`observe_round` reports the
        round's accuracy.  When the streaming spec sets admission knobs
        this path sheds with a typed
        :class:`repro.streaming.ShedError` before touching the engine.
        """
        if self.admission is not None:
            self.admission.try_admit()
            try:
                return self.select_cohorts([cohort_size])[0]
            finally:
                self.admission.release()
        return self.select_cohorts([cohort_size])[0]

    def select_cohorts(self, cohort_sizes: Optional[List[int]] = None, *,
                       sizes_fn=None):
        """Serve a batch of cohort requests from ONE engine solve.

        This is the coalesced entry point the
        :class:`repro.launch.frontend.CohortFrontend` batches concurrent
        ``select_cohort`` calls into: the embedding table is snapshotted
        once, the engine runs once (``select_batched``), and every
        request draws from the **same shared cluster pools** — one
        :class:`repro.policy.ClusterPools` view of the solve's cluster
        index, drawn without replacement across the whole batch, so no
        client is served to two cohorts of the same batch.  Returns one
        ``(client_ids, CohortResult)`` pair per requested size; the
        ``CohortResult`` is the single solve shared by the batch.

        ``sizes_fn`` (exclusive with ``cohort_sizes``) defers the batch
        membership decision until the select lock is actually held: the
        frontend passes a callback that seals its in-flight batch at
        that moment, so requests arriving while an earlier solve holds
        the lock still coalesce into this one — natural batching with
        zero added latency for uncontended callers.

        With ``policy="dqn"`` the batch parks ONE combined transition
        (the shared pre-draw state with every slot's cluster action
        across the batch); the next :meth:`observe_round` credits them
        all — the batch is one logical round of the serve contract.
        """
        if (cohort_sizes is None) == (sizes_fn is None):
            raise ValueError(
                "select_cohorts takes exactly one of cohort_sizes or "
                "sizes_fn")
        if cohort_sizes is not None and not len(cohort_sizes):
            return []
        t_enter = time.perf_counter()
        with obs.span("cohort.select") as sp, self._select_lock:
            if self._closed:
                from repro.streaming import ServiceClosedError
                raise ServiceClosedError("CohortServer is closed")
            sizes = [int(s) for s in (cohort_sizes if sizes_fn is None
                                      else sizes_fn())]
            if not sizes:
                return []
            t0 = time.perf_counter()
            self._select_seq += 1
            version, table = self.snapshot()
            res = None
            served_warm = forced_inline = dropped = False
            state_hit = None
            if self._streaming is not None:
                with obs.span("cohort.swap") as sw:
                    # drain the background solver's mailbox: swap in
                    # the warmed (version, table, result) if it is
                    # newer than what we're serving
                    with self._publish_lock:
                        pub = self._published
                        if pub is not None and (
                                self._served is None
                                or pub[0] > self._served[0]):
                            self._served = pub
                            self._published_served = True
                    if self._served is not None:
                        sw.set_metadata(served=self._served[0])
                        max_stale = self._streaming.max_stale_versions
                        if (max_stale is None
                                or version - self._served[0] <= max_stale):
                            _, table, res = self._served
                            served_warm = True
            if res is None:
                # non-streaming, or nothing warmed yet / served version
                # too stale: solve inline
                with obs.span("cohort.inline_solve"), self._solve_lock:
                    res = self.engine.select_batched(
                        table, requests=len(sizes))
                if self._streaming is not None:
                    self._served = (version, table, res)
                    forced_inline = True
            t_solve = time.perf_counter()
            self._last_assign = res.assign
            with obs.span("cohort.pools") as cp:
                self._index_memo, index_hit = self._cluster_index(
                    res.assign)
                cp.set_metadata(hit=int(index_hit))
                pools = self._index_memo[1].pools()
            cohorts: List[np.ndarray] = []
            if self.policy is not None:
                with obs.span("policy.state") as ps:
                    state, self._state_memo, state_hit = self._policy_state(
                        res.assign, table)
                    ps.set_metadata(hit=int(state_hit))
                all_actions: List[int] = []
                with obs.span("policy.draw"):
                    for size in sizes:
                        picked, actions = self.policy.draw(
                            self.rng, state, pools, size)
                        cohorts.append(np.asarray(picked[:size], np.int64))
                        all_actions.extend(actions[: len(picked)])
                # the serve contract is select -> observe_round ->
                # select; a second select (or batch) before the round
                # report replaces the parked transition, and the earlier
                # draw is never learned from — count it so the
                # dashboard can see mis-sequenced callers
                dropped = self._pending is not None
                self._pending = (state, all_actions, res.assign, table)
            else:
                with obs.span("policy.draw"):
                    left = pools.left
                    for size in sizes:
                        # round-robin over the clusters with members left
                        slots: List[int] = []
                        while len(slots) < size and any(left[:res.k]):
                            for c in range(res.k):
                                if left[c] and len(slots) < size:
                                    left[c] -= 1
                                    slots.append(c)
                        cohorts.append(np.asarray(
                            pools.take(self.rng, slots), np.int64))
            with obs.span("cohort.account"):
                flat = (np.concatenate(cohorts) if cohorts
                        else np.empty(0, np.int64))
                if len(flat):
                    np.add.at(self._participation, res.assign[flat], 1.0)
                # staleness: every cluster ages one select; those that just
                # contributed a client reset to fresh
                self._staleness += 1.0
                if len(flat):
                    self._staleness[np.unique(res.assign[flat])] = 0.0
            t1 = time.perf_counter()
            with self._stats_lock:
                self._counters["served_warm"] += int(served_warm)
                self._counters["forced_inline"] += int(forced_inline)
                self._counters["dropped_transitions"] += int(dropped)
                if state_hit is not None:
                    self._counters["state_stats_hits" if state_hit
                                   else "state_stats_builds"] += 1
                self._counters["pool_index_hits" if index_hit
                               else "pool_index_builds"] += 1
                first = self._counters["requests"] == 0
                for name, value in (("solve_s", t_solve - t0),
                                    ("draw_s", t1 - t_solve),
                                    ("total_s", t1 - t0)):
                    prev = self._latency[name]
                    self._latency[name] = (
                        value if first
                        else prev + _LATENCY_EMA * (value - prev))
                self._counters["requests"] += len(sizes)
                self._counters["batches"] += 1
            self.last_select_s = t1 - t0
            served = version if self._served is None else self._served[0]
            sp.set_metadata(seq=self._select_seq, requests=len(sizes),
                            version=version, served=served,
                            lock_wait_ns=int((t0 - t_enter) * 1e9))
            return [(picked, res) for picked in cohorts]

    def _outcome_cluster_rates(self, outcome):
        """Per-cluster completion/latency rates from a realism outcome.

        Maps ``outcome.selected`` through the last solve's assignment
        and bins the completed/dropped split and simulated round-trips
        per cluster.  Returns ``(seen, avail, latency)`` — a boolean
        mask of clusters observed this round plus this round's
        completion-rate and mean-latency vectors (the "system" state
        features) — or ``None`` when nothing maps.  Pure; the caller
        holds ``_select_lock`` (reads ``_last_assign``) and applies the
        EMA updates itself.
        """
        assign = self._last_assign
        if assign is None or not len(outcome.selected):
            return None
        k = self.config.num_clusters
        sel = np.asarray(outcome.selected)
        lat = np.asarray(outcome.latencies_s)
        in_table = (sel >= 0) & (sel < len(assign))
        sel, lat = sel[in_table], lat[in_table]
        if not len(sel):
            return None
        clusters = assign[sel]
        completed = np.isin(sel, np.asarray(outcome.completed))
        counts = np.bincount(clusters, minlength=k)[:k].astype(np.float64)
        hits = np.bincount(clusters, weights=completed.astype(np.float64),
                           minlength=k)[:k]
        lat_sum = np.bincount(clusters, weights=lat, minlength=k)[:k]
        seen = counts > 0
        avail = np.zeros(k)
        latency = np.zeros(k)
        avail[seen] = hits[seen] / counts[seen]
        latency[seen] = lat_sum[seen] / counts[seen]
        return seen, avail, latency

    def observe_round(self, accuracy: float, timings: Optional[dict] = None,
                      outcome=None) -> float:
        """Report a completed round back to the server; returns the reward.

        ``accuracy`` is the post-aggregation global-model accuracy of
        the round trained on the last served cohort; the reward is the
        paper's shaping ``Ξ^(acc − target) − 1``.  With ``policy="dqn"``
        this is the online learning step: the parked (state, actions)
        from :meth:`select_cohort` plus the new state go into the replay
        buffer and one TD minibatch runs.  ``timings`` (e.g.
        ``RoundResult.timings`` from ``repro.fed.rounds``) is folded
        into the per-phase running means reported by :meth:`stats`.
        ``outcome`` (a ``repro.fed.realism.RoundOutcome``) feeds the
        per-cluster availability/latency EMAs behind
        ``state_features="system"`` and, when present, blends the
        reward with deadline attainment (``repro.fed.realism
        .blended_reward``) so slow/flaky clusters are penalized.
        """
        from repro.core.selection import favor_reward

        if outcome is not None:
            from repro.fed.realism import blended_reward
            reward = blended_reward(accuracy, self.target_accuracy,
                                    outcome.attainment)
        else:
            reward = favor_reward(accuracy, self.target_accuracy)
        # same lock as select_cohort: a racing selection must not park a
        # new (state, actions) transition between our read of _pending
        # and its clear, or that round's learning step would be dropped
        with obs.span("cohort.observe") as sp, self._select_lock:
            if outcome is not None:
                rates = self._outcome_cluster_rates(outcome)
                if rates is not None:
                    seen, avail, latency = rates
                    self._avail_ema[seen] += _REWARD_EMA * (
                        avail[seen] - self._avail_ema[seen])
                    self._latency_ema_s[seen] += _REWARD_EMA * (
                        latency[seen] - self._latency_ema_s[seen])
            sp.set_metadata(seq=self._select_seq)
            state_hit = None
            if self.policy is not None and self._pending is not None:
                state, actions, assign, table = self._pending
                for c in set(actions):
                    self._reward_ema[c] += _REWARD_EMA * (
                        reward - self._reward_ema[c])
                self.prev_accuracy = accuracy
                with obs.span("policy.state") as ps:
                    next_state, self._state_memo, state_hit = \
                        self._policy_state(assign, table)
                    ps.set_metadata(hit=int(state_hit))
                with obs.span("policy.observe"):
                    self.policy.observe(state, actions, reward, next_state)
                with obs.span("policy.train"):
                    self.policy.train(self.rng)
                self._pending = None
            else:
                self.prev_accuracy = accuracy
            with self._stats_lock:
                if timings:
                    n = self._counters["rounds_observed"]
                    for phase, seconds in timings.items():
                        prev = self._round_timings.get(phase, 0.0)
                        self._round_timings[phase] = (
                            prev + (seconds - prev) / (n + 1))
                self._counters["rounds_observed"] += 1
                if state_hit is not None:
                    self._counters["state_stats_hits" if state_hit
                                   else "state_stats_builds"] += 1
        return reward

    def stats(self) -> dict:
        """One dict for the serving dashboard: engine, latency, policy.

        Keys: ``requests`` / ``batches`` (engine entries — ``requests /
        batches`` is the realized coalescing factor) / ``updates`` /
        ``rounds_observed`` / ``dropped_transitions`` counters (the last
        counts DQN draws replaced by a second ``select_cohort`` before
        their round was reported — mis-sequenced callers),
        ``table_version``, ``num_clients``, ``state_features``,
        ``engine`` (cache hits, warm/cold starts, solves, probes,
        batched-select counters, autotuned ``auto_m`` when enabled),
        ``latency_s`` (EMA solve/draw/total), ``round_timings_s``
        (running means of ingested ``RoundResult.timings`` phases),
        ``last_select`` (method/source/drift/k of the latest solve), and
        ``policy`` (kind plus ε / state dim / steps / replay fill for
        "dqn").

        ``state_stats_hits`` / ``state_stats_builds`` count the DQN
        serving states (one per select, one per observe that credits a
        draw) whose per-solve half was reused from the memo / built;
        ``pool_index_hits`` / ``pool_index_builds`` count the selects
        (either policy) whose cluster index was reused / built.

        Streaming adds the flat ``warm_ahead`` / ``served_warm`` /
        ``forced_inline`` / ``dedupe_hit`` / ``superseded`` counters
        (always present, zero when disabled; ``superseded`` counts warm
        solves replaced in the mailbox before any select served them),
        ``shed`` (selects rejected by admission control), and a
        ``streaming`` sub-dict: enabled flag, ``max_stale_versions``,
        the version currently served vs the table version, delta-buffer
        ``materializations`` and the ``rows_materialized`` they applied,
        and the admission/solver breakdowns.
        """
        last = self.engine.state.result
        policy = {"kind": self.policy_name}
        if self.policy is not None:
            policy.update(self.policy.stats())
        # one consistent snapshot of the dashboard state; the copies
        # also keep callers from mutating the live dicts
        with self._stats_lock:
            counters = dict(self._counters)
            latency = dict(self._latency)
            round_timings = dict(self._round_timings)
        admission = (None if self.admission is None
                     else self.admission.stats())
        shed = (0 if admission is None
                else admission["shed_queue"] + admission["shed_rate"])
        spec = self._streaming
        streaming = {
            "enabled": spec is not None,
            "max_stale_versions": (None if spec is None
                                   else spec.max_stale_versions),
            "served_version": (None if self._served is None
                               else self._served[0]),
            "materializations": self._materializations,
            "rows_materialized": self._rows_materialized,
            "admission": admission,
        }
        if self._own_solver and self._solver is not None:
            streaming["solver"] = dict(self._solver.stats)
        return {
            **counters,
            "shed": shed,
            "table_version": self.version,
            "num_clients": self._base.shape[0],
            "state_features": self.state_features,
            "engine": dict(self.engine.stats),
            "streaming": streaming,
            "latency_s": latency,
            "round_timings_s": round_timings,
            "last_select": None if last is None else {
                "method": last.method, "source": last.source,
                "drift": last.drift, "k": last.k,
                "seconds": last.seconds},
            "policy": policy,
        }


def planted_table(n: int, k: int, d: int = 8, seed: int = 0):
    """Synthetic (n, d) client table around k planted centers.

    Returns ``(embeds (n, d) f32, labels (n,))``: centers ~ N(0, 6²),
    unit-variance scatter — the population the cohort demo and the chip
    smoke cluster, made from ``seed``.
    """
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32) * 6
    labels = rng.integers(0, k, n)
    embeds = centers[labels] + rng.normal(size=(n, d)).astype(np.float32)
    return embeds, labels


def _cohort_main(args) -> None:
    """Cohort-service demo loop: N synthetic clients, drifting embeddings.

    With ``--policy dqn`` the loop also synthesizes a reward signal:
    clients of true cluster 0 are "stale" (contribute nothing), so round
    accuracy rises with the fraction of the cohort drawn outside it —
    over a few dozen rounds the policy's draw weights visibly shift away
    from the engine cluster covering that group.
    """
    from repro.cohort import CohortConfig
    from repro.streaming import StreamingSpec

    d = 8
    embeds, assign_true = planted_table(args.cohort, args.num_clusters, d,
                                        args.seed)
    rng = np.random.default_rng(args.seed + 1)
    num_landmarks = args.num_landmarks
    if num_landmarks not in (None, "auto"):
        num_landmarks = int(num_landmarks)
    streaming = (StreamingSpec(max_stale_versions=args.max_stale)
                 if args.streaming else None)
    server = CohortServer(
        args.cohort, d, seed=args.seed, policy=args.policy,
        target_accuracy=0.85, streaming=streaming,
        config=CohortConfig(num_clusters=args.num_clusters,
                            landmarks=args.landmarks,
                            num_landmarks=num_landmarks))
    server.update_embeddings(np.arange(args.cohort), embeds)
    for r in range(args.rounds):
        ids, res = server.select_cohort(args.cohort_size)
        # synthetic round outcome: cohort quality = share of non-stale
        # clients (true cluster 0 is stale), reported back to the policy
        useful = float(np.mean(assign_true[ids] != 0)) if len(ids) else 0.0
        reward = server.observe_round(0.5 + 0.4 * useful)
        # the selected cohort trains and drifts; everyone else is static
        server.update_embeddings(
            ids, server.embeds[ids]
            + 0.01 * rng.normal(size=(len(ids), d)).astype(np.float32))
        print(f"round {r}: {len(ids)} clients from {res.k} clusters "
              f"({res.method}/{res.source}) in {server.last_select_s:.3f}s "
              f"({args.cohort / max(server.last_select_s, 1e-9):,.0f} "
              f"clients/s, reward {reward:+.3f})")
    server.close()
    import json
    stats = server.stats()
    print("server stats:", json.dumps(stats, indent=2, default=float))
    solver = stats["streaming"].get("solver")
    if solver and solver["errors"]:
        raise SystemExit(f"{solver['errors']} background solve(s) failed")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=0, metavar="R",
                    help="total LM requests to serve (default: one per "
                         "batch slot); R > batch exercises the "
                         "admit/retire scheduler")
    ap.add_argument("--mixed", action="store_true",
                    help="draw mixed prompt/generation lengths instead "
                         "of uniform --prompt-len/--gen-len")
    ap.add_argument("--cohort", type=int, default=0, metavar="N",
                    help="serve cohort selection for N clients instead "
                         "of the LM loop")
    ap.add_argument("--cohort-size", type=int, default=64)
    ap.add_argument("--num-clusters", type=int, default=8)
    ap.add_argument("--num-landmarks", default=None,
                    help="Nyström landmark count: an int, or 'auto' to "
                         "autotune from the eigengap/drift history")
    ap.add_argument("--landmarks", default="uniform",
                    choices=["uniform", "leverage", "kmeans++"])
    ap.add_argument("--policy", default="stratified",
                    choices=["stratified", "dqn"],
                    help="cohort draw: uniform stratified, or the "
                         "paper's cluster-level DQN (Algorithm II)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tenants", type=int, default=0, metavar="T",
                    help="with --cohort: serve T model-family tenants "
                         "through the coalescing CohortFrontend instead "
                         "of one CohortServer")
    ap.add_argument("--concurrency", type=int, default=16,
                    help="concurrent select workers in --tenants mode")
    ap.add_argument("--batch-window", type=float, default=0.0,
                    help="extra coalescing wait (s) in --tenants mode; "
                         "0 = natural batching only")
    ap.add_argument("--streaming", action="store_true",
                    help="double-buffered background re-clustering: "
                         "serve version v while a BackgroundSolver "
                         "warms v+1 (repro.streaming)")
    ap.add_argument("--max-stale", type=int, default=None, metavar="V",
                    help="with --streaming: force an inline solve when "
                         "the served version falls more than V table "
                         "versions behind (default: never)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.cohort:
        if args.tenants:
            from repro.launch.frontend import run_demo
            run_demo(args)
        else:
            _cohort_main(args)
        return

    from repro.configs import get_config

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(args.seed)
    server = Server(cfg, args.batch, args.prompt_len + args.gen_len,
                    temperature=args.temperature, seed=args.seed)
    n_reqs = args.requests or args.batch
    reqs = []
    for i in range(n_reqs):
        if args.mixed:
            plen = int(rng.integers(1, args.prompt_len + 1))
            gen = int(rng.integers(1, args.gen_len + 1))
        else:
            plen, gen = args.prompt_len, args.gen_len
        reqs.append(Request(i, rng.integers(0, cfg.vocab_size,
                                            plen).astype(np.int32), gen))
    t0 = time.time()
    done = server.serve_batch(reqs)
    stats = server.stats()
    print(f"served {len(done)} requests in {time.time()-t0:.1f}s "
          f"({server.last_decode_tok_s:,.1f} decode tok/s)")
    print(f"scheduler: admitted={stats['admitted']} "
          f"retired={stats['retired']} truncated={stats['truncated']} "
          f"decode_steps={stats['decode_steps']} "
          f"decode_tokens={stats['decode_tokens']} "
          f"tok_s_ema={stats['tok_s_ema']:,.1f}")
    for r in done[:2]:
        print(f"req {r.uid}: first 10 generated tokens {r.generated[:10]}")


if __name__ == "__main__":
    main()
