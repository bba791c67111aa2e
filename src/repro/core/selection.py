"""Client-selection policies: FedAvg(random), K-Center, FAVOR, DQRE-SCnet.

The paper's baselines (Table 2) and its contribution, behind one
interface.  A policy sees a ``RoundState`` (client weight-delta embeddings
+ global-model embedding) and returns the cohort for the next
communication round; learning policies also consume a reward after the
round (FAVOR-style  r = Ξ^(acc − target) − 1,  Ξ = 64).

DQRE-SCnet (the paper, Algorithm II): spectrally cluster the client
embeddings (Algorithm I), then a Deep-Q agent (current + target nets)
chooses *clusters*; clients are drawn without replacement from the chosen
clusters ("rewarded users"), de-biasing the cohort under non-IID skew.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax

from repro.core.dqn import DQNAgent, DQNConfig
from repro.core.kmeans import pairwise_sq_dists


@dataclasses.dataclass
class RoundState:
    round_idx: int
    client_embeds: np.ndarray          # (N, dim)
    global_embed: np.ndarray           # (dim,)
    prev_accuracy: float


@dataclasses.dataclass
class Feedback:
    accuracy: float
    reward: float
    selected: np.ndarray


class SelectionPolicy:
    name = "base"

    def __init__(self, num_clients: int, clients_per_round: int,
                 embed_dim: int, seed: int = 0):
        self.num_clients = num_clients
        self.clients_per_round = clients_per_round
        self.embed_dim = embed_dim
        self.rng = np.random.default_rng(seed)

    def select(self, state: RoundState) -> np.ndarray:
        raise NotImplementedError

    def update(self, state: RoundState, next_state: RoundState,
               feedback: Feedback) -> None:
        pass


class RandomSelection(SelectionPolicy):
    """FedAvg: uniform random cohort (McMahan et al.)."""
    name = "fedavg"

    def select(self, state: RoundState) -> np.ndarray:
        return self.rng.choice(self.num_clients, self.clients_per_round,
                               replace=False)


class KCenterSelection(SelectionPolicy):
    """Greedy k-center (farthest-point) over client embeddings."""
    name = "kcenter"

    def select(self, state: RoundState) -> np.ndarray:
        x = state.client_embeds
        n, k = self.num_clients, self.clients_per_round
        chosen = [int(self.rng.integers(n))]
        d2 = np.asarray(pairwise_sq_dists(x, x[chosen]))[:, 0]
        while len(chosen) < k:
            nxt = int(np.argmax(d2))
            chosen.append(nxt)
            d2 = np.minimum(
                d2, np.asarray(pairwise_sq_dists(x, x[nxt:nxt + 1]))[:, 0])
        return np.asarray(chosen)


class FavorSelection(SelectionPolicy):
    """FAVOR (Wang et al. 2020): per-client DQN, no clustering.

    State = [global embed ‖ all client embeds]; the Q head scores each
    client; the cohort is the top-K by Q with ε-greedy exploration.
    """
    name = "favor"

    def __init__(self, num_clients, clients_per_round, embed_dim, seed=0,
                 dqn_overrides: Optional[dict] = None):
        super().__init__(num_clients, clients_per_round, embed_dim, seed)
        cfg = DQNConfig(state_dim=(num_clients + 1) * embed_dim,
                        num_actions=num_clients,
                        **(dqn_overrides or {}))
        self.agent = DQNAgent(jax.random.PRNGKey(seed), cfg)

    def _state_vec(self, state: RoundState) -> np.ndarray:
        return np.concatenate([state.global_embed.ravel(),
                               state.client_embeds.ravel()]).astype(np.float32)

    def select(self, state: RoundState) -> np.ndarray:
        s = self._state_vec(state)
        self.agent.steps += 1
        q = self.agent.q_values(s)
        k = self.clients_per_round
        eps = self.agent.epsilon()
        n_rand = int(round(eps * k))
        top = np.argsort(-q)
        picked = list(top[: k - n_rand])
        if n_rand:
            rest = np.setdiff1d(np.arange(self.num_clients), picked)
            picked += list(self.rng.choice(rest, n_rand, replace=False))
        return np.asarray(picked[:k])

    def update(self, state, next_state, feedback):
        s, s2 = self._state_vec(state), self._state_vec(next_state)
        for a in feedback.selected:
            self.agent.observe(s, int(a), feedback.reward, s2)
        self.agent.train_step(self.rng)


def _make_cohort_config(num_clusters, approx_method, num_landmarks,
                        landmarks, use_pallas, auto_k, warm_start):
    """Engine config shared by the cluster-based policies (stratified +
    dqre_sc).  approx_method maps 1:1 onto engine methods ("dense",
    "nystrom", "sharded", "auto"); "dense" stays the default so small
    simulated cohorts keep the exact Algorithm I path."""
    from repro.cohort import CohortConfig
    return CohortConfig(num_clusters=num_clusters, method=approx_method,
                        num_landmarks=num_landmarks, landmarks=landmarks,
                        use_pallas=use_pallas, auto_k=auto_k,
                        warm_start=warm_start)


class StratifiedSelection(SelectionPolicy):
    """Cluster-stratified uniform draw: Algorithm I without Algorithm II.

    Clusters the client embeddings through the same
    :class:`repro.cohort.CohortEngine` as DQRE-SCnet, then draws the
    cohort round-robin across clusters (pools shuffled, popped without
    replacement) — the serving path's ``policy="stratified"`` baseline,
    here for the simulation so the realism benchmarks can isolate what
    the *learned* cluster choice adds under system heterogeneity.
    """
    name = "stratified"

    def __init__(self, num_clients, clients_per_round, embed_dim, seed=0,
                 num_clusters: int = 8, use_pallas: bool = False,
                 auto_k: bool = False, approx_method: str = "dense",
                 num_landmarks: Optional[int] = None,
                 landmarks: str = "uniform", warm_start: bool = True):
        super().__init__(num_clients, clients_per_round, embed_dim, seed)
        from repro.cohort import CohortEngine
        self.num_clusters = num_clusters
        self.engine = CohortEngine(
            _make_cohort_config(num_clusters, approx_method, num_landmarks,
                                landmarks, use_pallas, auto_k, warm_start),
            seed=seed + 1)

    def select(self, state: RoundState) -> np.ndarray:
        assign = self.engine.select(state.client_embeds).assign
        pools = [list(np.flatnonzero(assign == c))
                 for c in range(self.num_clusters)]
        for pool in pools:
            self.rng.shuffle(pool)
        picked: list = []
        while len(picked) < self.clients_per_round and any(pools):
            for pool in pools:
                if pool and len(picked) < self.clients_per_round:
                    picked.append(pool.pop())
        return np.asarray(picked)


class DQREScSelection(SelectionPolicy):
    """DQRE-SCnet (the paper): spectral clustering + cluster-level DQN.

    Algorithm I (clustering) is delegated wholesale to the cohort
    subsystem: a :class:`repro.cohort.CohortEngine` owns method
    resolution (dense / Nyström / mesh-sharded Nyström), landmark
    strategy, the per-round fingerprint cache, and drift-gated
    warm-started re-clustering.  Algorithm II (the cluster-level DQN
    and the ε-greedy cohort draw) is delegated to
    :class:`repro.policy.ClusterPolicy` — the same component the
    serving path (``launch/serve.CohortServer``) runs online — fed here
    with the simulation state [global embed ‖ cluster centroids].
    """
    name = "dqre_sc"

    def __init__(self, num_clients, clients_per_round, embed_dim, seed=0,
                 num_clusters: int = 8, use_pallas: bool = False,
                 auto_k: bool = False, approx_method: str = "dense",
                 num_landmarks: Optional[int] = None,
                 landmarks: str = "uniform", warm_start: bool = True,
                 cohort_config=None,
                 dqn_overrides: Optional[dict] = None):
        super().__init__(num_clients, clients_per_round, embed_dim, seed)
        from repro.cohort import CohortEngine
        self.num_clusters = num_clusters
        if cohort_config is None:
            cohort_config = _make_cohort_config(
                num_clusters, approx_method, num_landmarks, landmarks,
                use_pallas, auto_k, warm_start)
        else:
            if cohort_config.num_clusters != num_clusters:
                # the DQN action space, the pool loop in select(), and
                # the engine's assignment range must agree — a mismatch
                # would silently make clusters >= num_clusters
                # unselectable
                raise ValueError(
                    f"cohort_config.num_clusters="
                    f"{cohort_config.num_clusters} must equal the "
                    f"policy's num_clusters={num_clusters}")
            overlapping = dict(approx_method=(approx_method, "dense"),
                               num_landmarks=(num_landmarks, None),
                               landmarks=(landmarks, "uniform"),
                               use_pallas=(use_pallas, False),
                               auto_k=(auto_k, False),
                               warm_start=(warm_start, True))
            clash = [name for name, (got, default) in overlapping.items()
                     if got != default]
            if clash:
                raise ValueError(
                    f"pass {clash} inside cohort_config, not alongside "
                    f"it — an explicit cohort_config replaces those "
                    f"constructor arguments entirely")
        self.engine = CohortEngine(cohort_config, seed=seed + 1)
        from repro.policy import ClusterPolicy
        self.cluster_policy = ClusterPolicy(
            num_clusters, state_dim=(num_clusters + 1) * embed_dim,
            seed=seed, dqn_overrides=dqn_overrides)
        self.agent = self.cluster_policy.agent   # back-compat alias
        self._last_assign: Optional[np.ndarray] = None
        self._last_state_vec: Optional[np.ndarray] = None
        self._last_actions: Optional[list] = None

    @property
    def cluster_computes(self) -> int:
        """Algorithm I solves actually executed (engine cache hits excluded)."""
        return self.engine.stats["solves"]

    # -- Algorithm I: cluster the client embeddings -------------------------
    def _cluster(self, embeds: np.ndarray):
        return self.engine.select(embeds).assign

    def _state_vec(self, state: RoundState, assign: np.ndarray) -> np.ndarray:
        cents = np.zeros((self.num_clusters, self.embed_dim), np.float32)
        for c in range(self.num_clusters):
            m = assign == c
            if m.any():
                cents[c] = state.client_embeds[m].mean(axis=0)
        return np.concatenate([state.global_embed.ravel(),
                               cents.ravel()]).astype(np.float32)

    # -- Algorithm II: DQN chooses clusters, clients drawn from them --------
    def select(self, state: RoundState) -> np.ndarray:
        assign = self._cluster(state.client_embeds)
        s = self._state_vec(state, assign)
        self._last_assign, self._last_state_vec = assign, s
        from repro.policy import ClusterIndex
        pools = ClusterIndex(assign, self.num_clusters).pools()
        picked, actions = self.cluster_policy.draw(
            self.rng, s, pools, self.clients_per_round)
        self._last_actions = actions
        return np.asarray(picked)

    def update(self, state, next_state, feedback):
        assign2 = self._cluster(next_state.client_embeds)
        s2 = self._state_vec(next_state, assign2)
        self.cluster_policy.observe(self._last_state_vec,
                                    self._last_actions or [],
                                    feedback.reward, s2)
        self.cluster_policy.train(self.rng)


POLICIES = {
    "fedavg": RandomSelection,
    "kcenter": KCenterSelection,
    "favor": FavorSelection,
    "stratified": StratifiedSelection,
    "dqre_sc": DQREScSelection,
}


def make_policy(name: str, num_clients: int, clients_per_round: int,
                embed_dim: int, seed: int = 0, **kw) -> SelectionPolicy:
    return POLICIES[name](num_clients, clients_per_round, embed_dim,
                          seed=seed, **kw)


def favor_reward(accuracy: float, target: float, xi: float = 64.0) -> float:
    """FAVOR's reward shaping — also used by DQRE-SC (paper §3.3)."""
    return float(xi ** (accuracy - target) - 1.0)
