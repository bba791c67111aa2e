"""Algorithm II as a reusable component: a Deep-Q policy over clusters.

The paper's hybrid loop is  *cluster the clients spectrally (Algorithm
I), then let a Deep-Q agent decide which clusters this round's cohort is
drawn from (Algorithm II)*.  Before this module existed, Algorithm II
lived inline in ``core/selection.DQREScSelection`` and could only run
inside a simulated :class:`repro.fed.FederatedRunner`; the serving path
(``launch/serve.CohortServer``) fell back to uniform stratified draws.

:class:`ClusterPolicy` extracts the DQN half into a state-agnostic
component shared by both callers:

* ``DQREScSelection`` feeds it the *simulation* state (global-model
  embedding ‖ cluster centroids) each round.
* ``CohortServer`` feeds it the *serving* state (per-cluster
  population / participation / reward statistics built by
  :func:`repro.fed.metrics.cluster_policy_state` — ``"basic"``/
  ``"rich"``, or ``"system"`` which adds the client-realism
  availability + latency EMAs from ``repro.fed.realism`` round
  outcomes) and trains it online from the accuracy signal of completed
  rounds.  Under a deadline the reward may be the deadline-blended
  shaping (:func:`repro.fed.realism.blended_reward`) instead of the
  pure accuracy signal.

The action space is the cluster index: one ε-greedy cluster choice per
cohort slot, so a round's recorded ``actions`` are the per-slot cluster
draws and the induced per-cluster draw weights are
``ε/k + (1-ε)·1[argmax Q]`` (see :meth:`ClusterPolicy.draw_weights`).
The reward is the paper's accuracy-delta signal
``Ξ^(acc − target) − 1`` (FAVOR shaping, §3.3), computed by the caller.

Cohort members come from a :class:`ClusterIndex` (the clients of each
cluster, built once per assignment) through a per-batch
:class:`ClusterPools` view: the slot loop counts what each cluster has
left, and the members are then drawn by position, uniformly without
replacement, so no pool of N client ids is ever listed or shuffled.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.core.dqn import DQNAgent, DQNConfig


class ClusterIndex:
    """The clients of each of ``num_clusters`` clusters, for one assignment.

    One O(N) pass: a stable counting sort of ``assign`` groups the client
    ids by cluster (``members(c)`` is a slice of that order) and
    ``counts[c]`` is each cluster's size.  Immutable and never consumed:
    every batch draws through its own :meth:`pools` view, so a cached
    index serves any number of selects on the solve it describes.

    Args:
        assign:       (N,) cluster id of each client, in
                      ``[0, num_clusters)``.
        num_clusters: k, the number of clusters (and of actions).
    """

    def __init__(self, assign: np.ndarray, num_clusters: int):
        assign = np.asarray(assign)
        # a stable sort of a <= 16-bit key is a radix sort: O(N)
        key = assign.astype(np.min_scalar_type(num_clusters))
        self._order = np.argsort(key, kind="stable")
        self.counts = np.bincount(assign, minlength=num_clusters)[
            :num_clusters]
        self._starts = np.concatenate([[0], np.cumsum(self.counts)[:-1]])
        for a in (self._order, self.counts, self._starts):
            a.setflags(write=False)

    def members(self, c: int) -> np.ndarray:
        """Client ids of cluster ``c``, ascending (a read-only view)."""
        start = self._starts[c]
        return self._order[start:start + self.counts[c]]

    def pools(self) -> "ClusterPools":
        """A fresh per-batch view holding every member of every cluster."""
        return ClusterPools(self)


class ClusterPools:
    """One batch's draw from a :class:`ClusterIndex`.

    ``left[c]`` is what cluster ``c`` still has for this batch: a slot
    loop counts its choices off it, then :meth:`take` hands out members
    for those slots.  Members taken by one request of the batch are out
    of reach of the next, so the batch's cohorts are disjoint; the index
    itself is left as it was.
    """

    def __init__(self, index: ClusterIndex):
        self.index = index
        self.left = index.counts.tolist()
        # positions (within members(c)) this batch has taken, ascending
        self._taken = [np.empty(0, np.int64)] * len(self.left)

    def take(self, rng: np.random.Generator,
             actions: Sequence[int]) -> List[int]:
        """Client ids for slots drawn from clusters ``actions``.

        The slots must already be counted off :attr:`left`.  Each
        cluster's share is drawn in one ``rng.choice`` (Floyd's method,
        O(slots)) among the positions this batch has not yet taken, so
        members come uniformly without replacement, and are handed to
        that cluster's slots in order.
        """
        actions = np.asarray(actions, np.int64)
        picked = np.empty(len(actions), np.int64)
        for c in np.unique(actions):
            slots = np.flatnonzero(actions == c)
            taken = self._taken[c]
            free = int(self.index.counts[c]) - len(taken)
            ranks = rng.choice(free, len(slots), replace=False)
            # the r-th free position is r plus the taken ones at or
            # below it: taken[i] - i free positions precede taken[i]
            pos = ranks + np.searchsorted(
                taken - np.arange(len(taken)), ranks, side="right")
            self._taken[c] = np.sort(np.concatenate([taken, pos]))
            picked[slots] = self.index.members(c)[pos]
        return picked.tolist()


class ClusterPolicy:
    """Deep-Q policy over ``num_clusters`` discrete cluster actions.

    Wraps a :class:`repro.core.dqn.DQNAgent` (current + target nets,
    uniform replay, ε-greedy) with the cohort-draw loop of Algorithm II.
    The policy is state-agnostic: callers build their own ``(state_dim,)``
    float32 state vectors and pass them to :meth:`draw` / :meth:`observe`.

    Args:
        num_clusters: size of the action space (k of Algorithm I).
        state_dim:    length of the caller's state vectors.
        seed:         PRNG seed for the Q-network init and the fallback rng.
        dqn_overrides: optional :class:`~repro.core.dqn.DQNConfig` field
            overrides (e.g. ``{"eps_decay_steps": 50, "hidden": (32,)}``).
        state_features: optional label of the state layout this policy
            was built for (``"basic"`` 3k+1 / ``"rich"`` 5k+1 /
            ``"system"`` 7k+1 of
            :func:`repro.fed.metrics.cluster_policy_state`).  Purely
            descriptive — reported by :meth:`stats` and echoed in the
            shape-mismatch error — the policy stays state-agnostic.
    """

    def __init__(self, num_clusters: int, state_dim: int, *, seed: int = 0,
                 dqn_overrides: Optional[dict] = None,
                 state_features: Optional[str] = None):
        self.num_clusters = num_clusters
        self.state_dim = state_dim
        self.state_features = state_features
        cfg = DQNConfig(state_dim=state_dim, num_actions=num_clusters,
                        **(dqn_overrides or {}))
        self.agent = DQNAgent(jax.random.PRNGKey(seed), cfg)
        self.rng = np.random.default_rng(seed)
        self._last_loss = 0.0              # device scalar after train()

    def _check_state(self, state_vec: np.ndarray, caller: str) -> np.ndarray:
        """Fail fast on a wrong-length state with a readable error.

        Without this, a mis-built state (e.g. per-cluster stats shorter
        than k, or a "rich" state fed to a policy built for "basic")
        only dies inside the Q-network's first matmul with an opaque
        shape message.
        """
        s = np.asarray(state_vec, np.float32).reshape(-1)
        if len(s) != self.state_dim:
            layout = (f" (policy built for state_features="
                      f"{self.state_features!r})" if self.state_features
                      else "")
            raise ValueError(
                f"ClusterPolicy.{caller}: state vector has length "
                f"{len(s)} but the policy expects state_dim="
                f"{self.state_dim}{layout}")
        return s

    # -- acting -----------------------------------------------------------
    def epsilon(self) -> float:
        """Current exploration rate of the underlying agent's schedule."""
        return self.agent.epsilon()

    def draw_weights(self, state_vec: np.ndarray) -> np.ndarray:
        """Expected per-cluster draw distribution at the current ε.

        Returns the (num_clusters,) marginal probability that a single
        cohort slot is drawn from each cluster, ignoring pool depletion:
        ``ε/k`` everywhere plus ``1-ε`` on the greedy (argmax-Q) cluster.
        Pure readout — does not advance the ε schedule.
        """
        q = self.agent.q_values(self._check_state(state_vec, "draw_weights"))
        k = self.num_clusters
        eps = self.agent.epsilon()
        w = np.full(k, eps / k, np.float64)
        w[int(np.argmax(q))] += 1.0 - eps
        return w

    def draw(self, rng: np.random.Generator, state_vec: np.ndarray,
             pools: ClusterPools, cohort_size: int,
             ) -> Tuple[List[int], List[int]]:
        """Draw a cohort: one ε-greedy cluster choice per slot.

        Args:
            rng:       caller's generator (exploration + member draws).
            state_vec: (state_dim,) state the Q function scores.
            pools:     the batch's :class:`ClusterPools` over
                       ``num_clusters`` clusters; the drawn clients are
                       taken from it (no replacement across the batch).
                       A cluster with nothing left (e.g. above the
                       engine's eigengap k̂) is never chosen.
            cohort_size: number of clients to draw.

        Returns:
            ``(picked, actions)`` — client ids (≤ cohort_size if the
            pools run dry) and the cluster chosen for each slot.
            Advances the agent's ε schedule by one step.
        """
        self.agent.steps += 1
        # Q values to host: waits for a TD step still running on the device
        with obs.span("policy.q"):
            q = self.agent.q_values(self._check_state(state_vec, "draw"))
        eps = self.agent.epsilon()
        left = pools.left
        order = np.argsort(-q)
        actions: List[int] = []
        while len(actions) < cohort_size:
            if rng.random() < eps:
                c = int(rng.integers(self.num_clusters))
            else:
                c = int(next((c for c in order if left[c]), order[0]))
            if not left[c]:
                nonempty = [cc for cc in range(self.num_clusters)
                            if left[cc]]
                if not nonempty:
                    break
                c = int(rng.choice(nonempty))
            left[c] -= 1
            actions.append(c)
        return pools.take(rng, actions), actions

    # -- learning ---------------------------------------------------------
    def observe(self, state_vec: np.ndarray, actions: Sequence[int],
                reward: float, next_state_vec: np.ndarray) -> None:
        """Record one round: every slot's cluster choice shares the
        round's scalar reward (the paper credits all "rewarded users")."""
        s = self._check_state(state_vec, "observe")
        s2 = self._check_state(next_state_vec, "observe")
        for a in actions:
            self.agent.observe(s, int(a), reward, s2)

    def train(self, rng: Optional[np.random.Generator] = None):
        """One TD minibatch step; returns (and remembers) the loss.

        The return value is a DEVICE scalar — ``CohortServer`` calls
        this under its select lock, so forcing a host sync here would
        stall concurrent selects.  :attr:`last_loss` materializes it
        lazily when the stats endpoint asks.
        """
        self._last_loss = self.agent.train_step(
            rng if rng is not None else self.rng)
        return self._last_loss

    @property
    def last_loss(self) -> float:
        """Most recent TD loss, materialized on demand (syncs here)."""
        return float(self._last_loss)

    def stats(self) -> dict:
        """Serving-dashboard counters: ε, steps, replay fill, last loss."""
        buf = self.agent.buffer
        return {"epsilon": self.agent.epsilon(),
                "state_dim": self.state_dim,
                "state_features": self.state_features,
                "steps": self.agent.steps,
                "train_calls": self.agent.train_calls,
                "buffer_fill": buf.size / buf.capacity,
                "buffer_size": buf.size,
                "last_loss": self.last_loss}
