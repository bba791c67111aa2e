"""Policy-serving subsystem: the paper's Algorithm II, reusable.

``ClusterPolicy`` is the Deep-Q half of DQRE-SCnet (cluster-level
actions, ε-greedy cohort draws, replay + TD training) factored out of
the simulation-only ``DQREScSelection`` so the serving path
(``repro.launch.serve.CohortServer``) can run the learned policy online.
See docs/ARCHITECTURE.md ("The DQN policy loop") for the round-trip.
"""

from repro.policy.cluster_policy import (ClusterIndex, ClusterPolicy,
                                         ClusterPools)

__all__ = ["ClusterIndex", "ClusterPolicy", "ClusterPools"]
