"""CohortEngine — the select–cluster–cache lifecycle, in one place.

Before this subsystem existed the lifecycle was smeared across
``core/selection.py`` (fingerprint cache, implicit PRNG threading,
auto-k double-compute) and ``core/spectral.py`` (landmark sampling baked
into the embedding).  The engine owns all of it:

* **method resolution** — ``dense`` below ``dense_cutoff`` clients,
  ``sharded`` (distributed Nyström over a client mesh — a jitted 1-way
  mesh when only one device is visible) above it; ``nystrom`` is the
  eager single-device reference path.  Pin any of them explicitly.
* **landmark quality** — pluggable ``uniform | leverage | kmeans++``
  strategies (``cohort/landmarks.py``).
* **determinism** — every solve's PRNG key is ``fold_in(base_key,
  fingerprint(embeds))``, a pure function of the engine seed and the
  embedding content.  Re-clustering the same embeddings is bit-identical
  no matter what happened in between (the PR 1 key stream mutated per
  call, so it wasn't).
* **caching and warm starts** — an exact content fingerprint short-
  circuits repeated solves within a round; between rounds, a cheap
  moment/sign-weighted sketch measures embedding drift against the
  last cold solve, and while cumulative drift stays under
  ``drift_threshold`` the engine reuses that solve's landmarks +
  bandwidth and warm-starts the blocked subspace solvers from the
  persisted eigenbases in ``CohortState``; once accumulated drift
  crosses the threshold, the next solve is cold and the baseline
  refreshes.

Public API: ``CohortEngine(config, seed=...)``, ``engine.select(embeds)
-> CohortResult``, ``engine.reset()``, ``engine.stats``.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cohort.landmarks import LANDMARK_STRATEGIES, select_landmarks
from repro.cohort.nystrom import nystrom_from_landmarks
from repro.core import spectral as _spectral
from repro.core.kmeans import kmeans, pairwise_sq_dists
from repro.core.spectral import row_normalize

_METHODS = ("auto", "dense", "nystrom", "sharded")
_SKETCH_EPS = 1e-12
# autotuning only ever reads the last two gaps; keep a short tail for
# debugging but never let a long-running server grow the list unboundedly
_GAP_HIST_MAX = 32

# landmark-count autotuning (num_landmarks="auto"): relative eigengap
# g = (λ_{k+1} − λ_k) / (λ_{k+1} − λ_1) — the share of the approximate
# L_norm spectral spread concentrated in the k -> k+1 gap.  Empirically
# (see docs/ARCHITECTURE.md) well-separated cohorts sit around 0.1 at
# any sufficient m while unstructured/under-resolved kernels sit below
# 0.01, so: below _GAP_WEAK the landmark set is judged too coarse (m
# doubles); above _GAP_STRONG twice in a row, with only moderate drift,
# it is judged wasteful (m halves toward the base).
_GAP_WEAK = 0.02
_GAP_STRONG = 0.08
_AUTO_M_MAX_FACTOR = 8     # cap: 8x the static default, clipped to n
_AUTO_M_DRIFT_FACTOR = 4   # shrink only when drift <= 4x drift_threshold


@dataclasses.dataclass
class CohortConfig:
    """Knobs of the cohort-selection engine (see module docstring).

    num_clusters     — k: spectral-embedding width and DQN action count.
    method           — "auto" | "dense" | "nystrom" | "sharded".
    num_landmarks    — m for the Nyström paths: an int pins it, None
                       uses the static default max(8k, 64), "auto"
                       autotunes m between that default and 8x it from
                       the drift sketch + relative-eigengap history
                       (weak gap doubles m; two consecutive strong gaps
                       under moderate drift halve it).
    landmarks        — "uniform" | "leverage" | "kmeans++" strategy.
    solver           — landmark eigenproblems: "auto" picks dense eigh
                       for m <= eigh_cutoff, blocked subspace iteration
                       above; "eigh" / "subspace" pin it.
    dense_solver     — dense-path eigensolver ("eigh" | "subspace").
    auto_k           — eigengap heuristic caps the cluster count k̂ <= k.
    warm_start       — enable drift-gated incremental re-clustering.
    drift_threshold  — relative sketch distance below which the previous
                       round's landmarks/bandwidth/eigenbases are reused.
    cold_iters/warm_iters — subspace sweeps from random / persisted q0.
    dense_cutoff     — "auto" method: largest N solved densely.
    eigh_cutoff      — "auto" solver: largest m factored with dense eigh.
    w_rank           — rank of the blocked W^{-1/2} (default max(8k, 64)).
    block_rows       — row-panel height inside the blocked eigensolver.
    use_pallas       — route the landmark paths through the streaming
                       fused Pallas pipeline (the (N, m) cross-affinity
                       is never materialized) and the dense path's
                       affinity kernels through Pallas.
    affinity_dtype   — "f32" | "bf16" | "int8": tile precision of the
                       fused affinity passes (per-row quantization
                       scales, f32/int32 MXU accumulation).  Non-f32
                       requires use_pallas=True — the jnp reference
                       path is the exact f32 oracle.
    """
    num_clusters: int = 8
    method: str = "auto"
    num_landmarks: Optional[object] = None     # int | None | "auto"
    landmarks: str = "uniform"
    solver: str = "auto"
    dense_solver: str = "eigh"
    auto_k: bool = False
    warm_start: bool = True
    drift_threshold: float = 0.05
    cold_iters: int = 40
    warm_iters: int = 8
    dense_cutoff: int = 2048
    eigh_cutoff: int = 2048
    w_rank: Optional[int] = None
    block_rows: int = 2048
    use_pallas: bool = False
    affinity_dtype: str = "f32"

    def __post_init__(self):
        if self.affinity_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"unknown affinity_dtype {self.affinity_dtype!r}; "
                f"expected one of ('f32', 'bf16', 'int8')")
        if self.affinity_dtype != "f32" and not self.use_pallas:
            raise ValueError(
                f"affinity_dtype={self.affinity_dtype!r} requires "
                f"use_pallas=True (quantized tiles only exist in the "
                f"fused Pallas pipeline)")
        if self.method not in _METHODS:
            raise ValueError(f"unknown method {self.method!r}; "
                             f"expected one of {_METHODS}")
        if self.landmarks not in LANDMARK_STRATEGIES:
            raise ValueError(
                f"unknown landmark strategy {self.landmarks!r}; "
                f"expected one of {LANDMARK_STRATEGIES}")
        if self.solver not in ("auto", "eigh", "subspace"):
            raise ValueError(f"unknown solver {self.solver!r}")
        m = self.num_landmarks
        if not (m is None or m == "auto"
                or (isinstance(m, (int, np.integer)) and m > 0)):
            raise ValueError(
                f"num_landmarks={m!r} must be a positive int, None, "
                f"or \"auto\"")
        if isinstance(m, (int, np.integer)):
            _check_fused_landmarks(int(m), self.use_pallas)


def _check_fused_landmarks(m: int, use_pallas: bool) -> None:
    """Refuse a landmark count the fused kernels cannot compile for."""
    from repro.kernels.nystrom_pallas import MAX_LANDMARKS
    if use_pallas and m > MAX_LANDMARKS:
        raise ValueError(
            f"num_landmarks={m} exceeds the fused Pallas pipeline's limit "
            f"of {MAX_LANDMARKS} landmarks (VMEM-sized kernels); use "
            f"fewer landmarks or use_pallas=False")


@dataclasses.dataclass
class CohortState:
    """Engine-owned per-round memory: the warm-start payload.

    ``fingerprint`` short-circuits exact re-clustering; ``sketch`` is the
    drift baseline (the embedding sketch at the last COLD solve);
    ``landmark_idx``/``gamma`` pin the kernel between warm rounds;
    ``w_basis``/``mm_basis`` seed the subspace solvers.
    """
    fingerprint: Optional[bytes] = None
    sketch: Optional[np.ndarray] = None
    num_clients: int = 0
    landmark_idx: Optional[np.ndarray] = None
    gamma: Optional[float] = None
    w_basis: Optional[np.ndarray] = None
    mm_basis: Optional[np.ndarray] = None
    result: Optional["CohortResult"] = None


@dataclasses.dataclass
class CohortResult:
    """One cohort clustering: assignments plus provenance."""
    assign: np.ndarray            # (n,) cluster ids in [0, k)
    k: int                        # clusters actually used (k̂ if auto_k)
    embedding: np.ndarray         # (n, k) row-normalized spectral embedding
    evals: np.ndarray             # approximate L_norm spectrum, ascending
    method: str                   # resolved: dense | nystrom | sharded
    source: str                   # "cold" | "warm" | "cache"
    drift: float                  # relative sketch drift vs last cold baseline
    seconds: float                # wall time of this solve (0 on cache hit)


@dataclasses.dataclass
class PreparedSolve:
    """A finished solve staged for publication (the solve-ahead payload).

    :meth:`CohortEngine.prepare` computes one of these **without**
    touching any serving-visible engine state — no fingerprint-cache
    entry, no warm-start baseline, no counters.  A later
    :meth:`CohortEngine.publish` installs it atomically (from the
    caller's locking point of view: publish is a handful of reference
    assignments).  This is what lets a background solver warm version
    v+1 while the serving path keeps replaying version v's result from
    the cache, then swap.
    """
    fingerprint: bytes
    sketch: np.ndarray
    num_clients: int
    result: CohortResult
    landmark_idx: Optional[np.ndarray]
    gamma: Optional[float]
    w_basis: Optional[np.ndarray]
    mm_basis: Optional[np.ndarray]
    warm: bool                    # warm-started off the state it saw
    drift: float
    # k+1-wide L_norm spectrum for the landmark autotuner (only set for
    # cold landmark solves under num_landmarks="auto")
    auto_m_evals: Optional[np.ndarray] = None


class CohortEngine:
    """Owns the full select–cluster–cache lifecycle for cohort selection.

    ``select(embeds)`` clusters the (N, d) client embeddings and returns
    a :class:`CohortResult`; policies sample their cohort from
    ``result.assign``.  Determinism contract: every COLD solve is a pure
    function of ``(seed, embeds)`` — the PRNG key is derived from the
    content fingerprint, never from call history, so re-clustering the
    same embeddings cold is bit-identical.  Warm starts deliberately
    trade that for speed (they reuse the previous round's landmarks);
    they only fire below ``drift_threshold`` and can be disabled with
    ``warm_start=False`` for strict reproducibility.
    """

    def __init__(self, config: Optional[CohortConfig] = None, *,
                 seed: int = 0, mesh=None):
        self.config = config or CohortConfig()
        self.base_key = jax.random.PRNGKey(seed)
        self._sketch_sign: Optional[np.ndarray] = None
        self._sketch_seed = seed ^ 0x5EED
        self._mesh = mesh
        self.state = CohortState()
        self._auto_m: Optional[int] = None     # autotuned landmark count
        # relative eigengaps of recent cold solves (bounded: a server
        # calling select every round forever must not leak memory here)
        self._gap_hist: "collections.deque" = collections.deque(
            maxlen=_GAP_HIST_MAX)
        self.stats = {"solves": 0, "cache_hits": 0, "warm_starts": 0,
                      "cold_starts": 0, "probes": 0,
                      "batched_selects": 0, "coalesced_requests": 0}

    # -- state ----------------------------------------------------------
    def reset(self) -> None:
        """Drop all cached/warm-start state (e.g. on client churn)."""
        self.state = CohortState()

    @staticmethod
    def fingerprint(embeds: np.ndarray) -> bytes:
        """Content fingerprint of an embedding table (shape-qualified).

        Public because the streaming layer keys cross-tenant solve
        dedupe on it: two tenants whose tables hash identically can ride
        one background solve (``repro.streaming.SolveDeduper``).
        """
        with obs.span("engine.fingerprint"):
            h = hashlib.sha1(np.ascontiguousarray(embeds).tobytes())
            h.update(str(embeds.shape).encode())
            return h.digest()

    _fingerprint = fingerprint                   # pre-streaming spelling

    def _sketch(self, embeds: np.ndarray) -> np.ndarray:
        """O(n·d) drift probe: column moments + a sign-weighted row sum.

        The fixed ±1 row weighting keeps the probe sensitive to
        per-client movement that leaves the global moments unchanged
        (e.g. two clients swapping embeddings).
        """
        n = embeds.shape[0]
        if self._sketch_sign is None or len(self._sketch_sign) != n:
            rng = np.random.default_rng(self._sketch_seed)
            self._sketch_sign = rng.choice(
                np.array([-1.0, 1.0], np.float32), size=n)
        return np.concatenate([
            embeds.mean(axis=0), embeds.std(axis=0),
            (self._sketch_sign[:, None] * embeds).mean(axis=0)])

    # -- resolution -----------------------------------------------------
    def _resolve_method(self, n: int) -> str:
        if self.config.method != "auto":
            return self.config.method
        if n <= self.config.dense_cutoff:
            return "dense"
        # above the dense cutoff, always the mesh path — on a single
        # device it degenerates to the same math on a 1-way mesh, but
        # runs fully jitted (the eager "nystrom" path pays ~1.8x
        # dispatch/materialization overhead at N=100k; it remains the
        # bit-identical-to-interpret-Pallas reference path).
        return "sharded"

    def _resolve_solver(self, m: int) -> str:
        if self.config.solver != "auto":
            return self.config.solver
        return "eigh" if m <= self.config.eigh_cutoff else "subspace"

    def _cohort_mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_cohort_mesh
            self._mesh = make_cohort_mesh()
        return self._mesh

    # -- solve ----------------------------------------------------------
    def select(self, embeds, *, key=None) -> CohortResult:
        """Cluster the (N, d) client embeddings; cache- and drift-aware.

        ``key`` overrides the content-derived PRNG key (advanced; the
        default already makes repeat calls bit-identical).  An explicit
        key makes the call a one-off probe: it bypasses the fingerprint
        cache AND leaves the engine's cache/warm-start state untouched,
        so the default stream's (seed, embeds) purity is preserved.
        Probes are also invisible to the persistent serving counters
        (``solves`` / ``cold_starts`` / ``warm_starts``) — a dashboard
        reading :attr:`stats` sees only real serving traffic; probes
        count under ``stats["probes"]``.
        """
        embeds = np.ascontiguousarray(np.asarray(embeds, np.float32))
        st = self.state
        persist = key is None
        with self._prepare_span(embeds) as sp:
            fp = self.fingerprint(embeds)
            if persist and st.fingerprint == fp and st.result is not None:
                sp.set_metadata(cached=1)
                self.stats["cache_hits"] += 1
                cached = st.result
                return dataclasses.replace(
                    cached, source="cache", seconds=0.0,
                    # copies: the cached arrays back every future replay,
                    # a caller mutating its result must not corrupt them
                    assign=cached.assign.copy(),
                    embedding=cached.embedding.copy(),
                    evals=cached.evals.copy())
            prep = self._prepare(embeds, fp, key=key, warm_ok=persist)
            sp.set_metadata(warm=int(prep.warm))
        if persist:
            self.publish(prep)
        else:
            self.stats["probes"] += 1
        return prep.result

    # -- solve-ahead (the streaming double-buffer entry points) ----------
    def prepare(self, embeds) -> Optional[PreparedSolve]:
        """Solve without mutating serving-visible caches.

        Returns the staged :class:`PreparedSolve` for a later
        :meth:`publish`, or ``None`` when the engine's cache is already
        current for these exact embeddings (nothing to warm).  Warm-start
        eligibility is read from the state the engine holds *now* — the
        canonical caller (``repro.streaming.BackgroundSolver``) serializes
        all engine entries on the server's ``_solve_lock``, so the state
        it sees is the last published solve.
        """
        embeds = np.ascontiguousarray(np.asarray(embeds, np.float32))
        with self._prepare_span(embeds) as sp:
            fp = self.fingerprint(embeds)
            if (self.state.fingerprint == fp
                    and self.state.result is not None):
                sp.set_metadata(cached=1)
                return None
            prep = self._prepare(embeds, fp, key=None, warm_ok=True)
            sp.set_metadata(warm=int(prep.warm))
            return prep

    def _prepare_span(self, embeds: np.ndarray):
        """The ``engine.prepare`` span: fingerprint to staged result."""
        n = embeds.shape[0]
        return obs.span("engine.prepare", method=self._resolve_method(n),
                        n=n, cached=0)

    def publish(self, prep: PreparedSolve, *, count: bool = True,
                ) -> CohortResult:
        """Install a staged solve as the engine's current state.

        This is the only place a :meth:`prepare` output becomes visible
        to the fingerprint cache and the warm-start baseline.  ``count=
        False`` installs without bumping the solve counters — used when a
        deduped solve computed by another tenant's engine is adopted, so
        "exactly one engine solve" stays true on dashboards.
        """
        with obs.span("engine.publish", warm=int(prep.warm)):
            st = self.state
            st.fingerprint, st.num_clients = prep.fingerprint, prep.num_clients
            if not prep.warm:
                st.sketch = prep.sketch          # new cold baseline
            st.landmark_idx = prep.landmark_idx
            st.gamma = prep.gamma
            st.w_basis = prep.w_basis
            st.mm_basis = prep.mm_basis
            st.result = prep.result
            if count:
                self.stats["warm_starts" if prep.warm else "cold_starts"] += 1
                self.stats["solves"] += 1
                if prep.auto_m_evals is not None:
                    self._update_auto_m(prep.num_clients,
                                        self.config.num_clusters,
                                        prep.drift, prep.auto_m_evals)
            return prep.result

    def _prepare(self, embeds: np.ndarray, fp: bytes, *, key,
                 warm_ok: bool) -> PreparedSolve:
        """The full solve, staged: reads engine state, never writes it.

        Every matmul of the solve runs at full f32 precision.  A TPU's
        default f32 matmul is one bf16 pass, which blurs the
        ‖x‖² + ‖z‖² − 2·x·zᵀ affinity and the ill-conditioned W⁻¹ᐟ²
        products enough to scramble the partition (purity 0.35 on a
        planted 8-cluster table of 10⁶ clients).
        """
        with jax.default_matmul_precision("highest"):
            return self._prepare_f32(embeds, fp, key=key, warm_ok=warm_ok)

    def _prepare_f32(self, embeds: np.ndarray, fp: bytes, *, key,
                     warm_ok: bool) -> PreparedSolve:
        cfg = self.config
        st = self.state
        t0 = time.perf_counter()
        n = embeds.shape[0]
        method = self._resolve_method(n)
        if key is None:
            key = jax.random.fold_in(
                self.base_key, int.from_bytes(fp[:4], "little"))
        land_key, solve_key, km_key = jax.random.split(key, 3)

        # drift is measured against the sketch of the last COLD solve,
        # not the previous round: warm rounds do not advance the
        # baseline, so slow per-round drift ACCUMULATES and eventually
        # forces a cold refresh of landmarks + bandwidth (otherwise the
        # round-0 kernel would be reused forever under steady drift).
        with obs.span("engine.sketch"):
            sketch = self._sketch(embeds)
            drift = float("inf")
            if st.sketch is not None and st.num_clients == n:
                drift = float(np.linalg.norm(sketch - st.sketch)
                              / (np.linalg.norm(st.sketch) + _SKETCH_EPS))

        with obs.span("engine.upload"):
            x = jnp.asarray(embeds)
        k = cfg.num_clusters
        # auto_k and landmark autotuning both need the lambda_k /
        # lambda_{k+1} gap, but the subspace solvers only return as many
        # eigenvalues as the embedding width — so solve one wider and
        # slice back after the gap is read off.
        widen = cfg.auto_k or (self._autotune_m and method != "dense")
        solve_k = k + 1 if widen else k
        with obs.span("engine.landmarks"):
            if method == "dense":
                y, evals = self._solve_dense(x, solve_k)
                warm = False
                idx = gamma = w_basis = mm_basis = None
            else:
                y, evals, warm, idx, gamma, w_basis, mm_basis = \
                    self._solve_landmarks(x, solve_k, method, drift,
                                          land_key, solve_key,
                                          warm_ok=warm_ok)
        auto_m_evals = None
        if self._autotune_m and method != "dense" and not warm:
            with obs.span("engine.wait", what="evals"):
                auto_m_evals = np.asarray(evals)

        k_hat = k
        if cfg.auto_k:
            with obs.span("engine.wait", what="evals"):
                k_hat = int(np.clip(
                    int(_spectral.eigengap_k(evals, k)), 2, k))
            y = row_normalize(y[:, :k_hat])
        elif widen:
            y = row_normalize(y[:, :k])
        with obs.span("engine.kmeans"):
            assign, _ = kmeans(km_key, y, k_hat)

        with obs.span("engine.wait", what="result"):
            assign = np.asarray(assign)
            y, evals = np.asarray(y), np.asarray(evals)
        result = CohortResult(
            assign=assign, k=k_hat, embedding=y, evals=evals,
            method=method, source="warm" if warm else "cold", drift=drift,
            seconds=time.perf_counter() - t0)
        with obs.span("engine.wait", what="state"):
            return PreparedSolve(
                fingerprint=fp, sketch=sketch, num_clients=n, result=result,
                landmark_idx=None if idx is None else np.asarray(idx),
                gamma=None if gamma is None else float(gamma),
                w_basis=None if w_basis is None else np.asarray(w_basis),
                mm_basis=None if mm_basis is None else np.asarray(mm_basis),
                warm=warm, drift=drift, auto_m_evals=auto_m_evals)

    def select_batched(self, embeds, *, requests: int = 1) -> CohortResult:
        """One solve serving ``requests`` coalesced select calls.

        The batched serving path (``CohortServer.select_cohorts`` /
        ``CohortFrontend``) funnels every concurrent request against one
        embedding-table version through a single engine entry; this
        wrapper is that entry.  The clustering work is identical to
        :meth:`select` — same cache, same warm-start state, same
        determinism contract — but the ``batched_selects`` /
        ``coalesced_requests`` counters record the coalescing so
        ``requests / batched_selects`` reads as the realized batch
        factor on a dashboard.
        """
        if requests < 1:
            raise ValueError(f"requests={requests} must be >= 1")
        result = self.select(embeds)
        self.stats["batched_selects"] += 1
        self.stats["coalesced_requests"] += requests
        return result

    def _solve_dense(self, x, k: int):
        a = _spectral.affinity_matrix(x, use_pallas=self.config.use_pallas)
        return _spectral.spectral_embedding(
            a, k, solver=self.config.dense_solver)

    @property
    def _autotune_m(self) -> bool:
        return self.config.num_landmarks == "auto"

    def _num_landmarks(self, n: int, k: int) -> int:
        if self._autotune_m:
            # base off the configured cluster count, NOT the (possibly
            # k+1-widened) solve width, so the recorded _auto_m always
            # equals the m actually solved with — otherwise the next
            # round's warm-start size check can never match
            m = self._auto_m or _spectral.default_num_landmarks(
                n, self.config.num_clusters)
        else:
            m = (self.config.num_landmarks
                 or _spectral.default_num_landmarks(n, k))
        m = min(int(m), n)
        if m < k:
            raise ValueError(f"num_landmarks={m} must be >= k={k}")
        _check_fused_landmarks(m, self.config.use_pallas)
        return m

    def _update_auto_m(self, n: int, k: int, drift: float,
                       evals: np.ndarray) -> None:
        """Adapt the landmark count from eigengap + drift evidence.

        Called after every COLD landmark solve (warm solves must keep m
        fixed — the warm-start check requires the persisted landmark set
        to match).  The solve is run one eigenvector wide (k+1) so the
        relative gap  g = (λ_{k+1} − λ_k)/(λ_{k+1} − λ_1)  of the
        approximate L_norm spectrum is observable: a weak gap means the
        Nyström approximation is not resolving the k-cluster structure,
        so m doubles (up to 8x the static default); two consecutive
        strong gaps under moderate sketch drift mean the kernel is over-
        resolved, so m halves back toward the default.
        """
        evals = np.asarray(evals)
        if len(evals) <= k:           # no λ_{k+1}: nothing to measure
            return
        lo, hi = float(evals[k - 1]), float(evals[k])
        gap = max(hi - lo, 0.0) / max(hi - float(evals[0]), _SKETCH_EPS)
        self._gap_hist.append(gap)
        base = _spectral.default_num_landmarks(n, k)
        cap = min(n, _AUTO_M_MAX_FACTOR * base)
        if self.config.use_pallas:
            from repro.kernels.nystrom_pallas import MAX_LANDMARKS
            cap = min(cap, MAX_LANDMARKS)
        m = self._auto_m or base
        if gap < _GAP_WEAK:
            m = min(cap, 2 * m)
        elif (len(self._gap_hist) >= 2
              and min(list(self._gap_hist)[-2:]) > _GAP_STRONG
              and np.isfinite(drift)
              and drift <= _AUTO_M_DRIFT_FACTOR
              * self.config.drift_threshold):
            m = max(base, m // 2)
        self._auto_m = m
        self.stats["auto_m"] = m

    def _solve_landmarks(self, x, k: int, method: str, drift: float,
                         land_key, solve_key, *, warm_ok: bool = True):
        cfg, st = self.config, self.state
        n = x.shape[0]
        m = self._num_landmarks(n, k)
        solver = self._resolve_solver(m)
        # warm = reuse the previous round's landmarks + bandwidth; with
        # subspace solvers the persisted eigenbases additionally seed q0
        # and the iteration count drops to warm_iters.  Keyed probes
        # (warm_ok=False) never warm-start: the caller's key must fully
        # determine the solve, not the persisted landmark state.  Reads
        # the persisted state, never writes it — publication of the
        # landmark set is CohortEngine.publish's job.
        warm = (warm_ok and cfg.warm_start
                and drift <= cfg.drift_threshold
                and st.landmark_idx is not None
                and len(st.landmark_idx) == m and st.gamma is not None)
        warm_basis = (warm and solver == "subspace"
                      and st.mm_basis is not None
                      and st.w_basis is not None)
        if warm:
            idx = jnp.asarray(st.landmark_idx)
            gamma = st.gamma
        else:
            idx = select_landmarks(land_key, x, m, cfg.landmarks)
            rows = x[:min(n, _spectral._GAMMA_SAMPLE_ROWS)]
            gamma = _spectral.auto_gamma(pairwise_sq_dists(rows, x[idx]))
            with obs.span("engine.wait", what="gamma"):
                gamma = float(gamma)
        w_rank = (None if solver == "eigh"
                  else min(m, cfg.w_rank or max(8 * k, 64)))
        kwargs = dict(
            w_solver=solver, w_rank=w_rank, mm_solver=solver,
            iters=cfg.warm_iters if warm_basis else cfg.cold_iters,
            w_q0=jnp.asarray(st.w_basis) if warm_basis else None,
            mm_q0=jnp.asarray(st.mm_basis) if warm_basis else None,
            key=solve_key, block_rows=cfg.block_rows)
        # use_pallas routes the landmark solve through the streaming
        # fused pipeline: C is recomputed tile-by-tile in VMEM (never
        # materialized), at the configured affinity_dtype precision.
        if method == "sharded":
            from repro.cohort.sharded import sharded_nystrom_from_landmarks
            y, evals, mm_basis, w_basis = sharded_nystrom_from_landmarks(
                x, idx, k, gamma, self._cohort_mesh(),
                use_pallas=cfg.use_pallas, fused=cfg.use_pallas,
                affinity_dtype=cfg.affinity_dtype, **kwargs)
        else:
            y, evals, mm_basis, w_basis = nystrom_from_landmarks(
                x, idx, k, gamma, use_pallas=cfg.use_pallas,
                fused=cfg.use_pallas, affinity_dtype=cfg.affinity_dtype,
                **kwargs)
        return y, evals, warm, idx, gamma, w_basis, mm_basis
