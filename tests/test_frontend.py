"""Multi-tenant frontend: coalescing, tenant isolation, rich DQN state."""

import threading

import numpy as np
import pytest

from repro.cohort import CohortConfig
from repro.fed.metrics import cluster_policy_state, serving_state_dim
from repro.launch.frontend import (CohortFrontend, TenantSpec,
                                   make_demo_frontend)
from repro.launch.serve import CohortServer

FAST_DQN = {"hidden": (32,), "eps_decay_steps": 30, "buffer_size": 512,
            "batch_size": 64}


def blob_table(n=120, k=3, d=8, sep=8.0, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32) * sep
    true = rng.integers(0, k, n)
    x = (centers[true] + rng.normal(size=(n, d)).astype(np.float32))
    return x, true


def mk_frontend(tenants=2, n=120, k=3, d=8, policy="stratified", seed=0,
                window=0.0):
    fe = make_demo_frontend(tenants, n, d,
                            config=CohortConfig(num_clusters=k),
                            seed=seed, policy=policy, batch_window_s=window)
    for i, name in enumerate(fe.tenant_names):
        x, _ = blob_table(n, k, d, seed=seed + i)
        fe.update_embeddings(name, np.arange(n), x)
    return fe


# -- coalescing -----------------------------------------------------------

def test_concurrent_selects_coalesce_to_one_solve_disjoint_cohorts():
    """16 concurrent selects on one table version: exactly one engine
    solve for that version, every request served, and the batch's
    cohorts pairwise disjoint (shared pools, drawn without
    replacement)."""
    n, workers = 200, 16
    fe = mk_frontend(tenants=1, n=n, k=4, window=0.5)
    name = fe.tenant_names[0]
    server = fe.tenant(name)

    results = [None] * workers
    barrier = threading.Barrier(workers)

    def worker(i):
        barrier.wait()
        results[i] = fe.select_cohort(name, 8)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert all(r is not None for r in results)
    # one engine solve total for this table version — every other entry
    # was either coalesced into the batch or a fingerprint-cache replay
    assert server.engine.stats["solves"] == 1
    assert server.engine.stats["cold_starts"] == 1
    # the generous window + barrier coalesce the full herd into one batch
    st = fe.stats()
    assert st["frontend"]["requests"] == workers
    assert st["frontend"]["max_batch"] == workers
    assert st["frontend"]["batches"] == 1
    # disjoint cohorts: no client served twice across the batch
    all_ids = np.concatenate([ids for ids, _ in results])
    assert len(all_ids) == workers * 8
    assert len(np.unique(all_ids)) == len(all_ids)
    # every waiter sees the same solve (single CohortResult fanned out)
    versions = {id(res) for _, res in results}
    assert len(versions) == 1


def test_batched_select_counters_and_dashboard_factor():
    fe = mk_frontend(tenants=1, n=90, k=3, window=0.0)
    name = fe.tenant_names[0]
    server = fe.tenant(name)
    out = server.select_cohorts([5, 5, 5])
    assert len(out) == 3
    assert server.engine.stats["batched_selects"] == 1
    assert server.engine.stats["coalesced_requests"] == 3
    assert server.stats()["requests"] == 3
    assert server.stats()["batches"] == 1
    ids = np.concatenate([i for i, _ in out])
    assert len(np.unique(ids)) == 15
    assert server.select_cohorts([]) == []


def test_new_table_version_does_not_coalesce_with_old_batch():
    """Requests racing a table update still get a consistent solve: a
    version bump opens a new batch rather than joining the stale one."""
    fe = mk_frontend(tenants=1, n=90, k=3, window=0.0)
    name = fe.tenant_names[0]
    ids1, res1 = fe.select_cohort(name, 6)
    x, _ = blob_table(90, 3, 8, seed=99)
    fe.update_embeddings(name, np.arange(90), x)
    ids2, res2 = fe.select_cohort(name, 6)
    assert res2 is not res1
    assert fe.tenant(name).engine.stats["solves"] == 2


def test_frontend_select_error_fans_out_and_unknown_tenant():
    fe = mk_frontend(tenants=1, n=60, k=3)
    with pytest.raises(KeyError, match="unknown tenant"):
        fe.select_cohort("no-such-family", 4)
    name = fe.tenant_names[0]

    def boom(*a, **kw):
        raise RuntimeError("engine exploded")

    fe.tenant(name).select_cohorts = boom
    with pytest.raises(RuntimeError, match="coalesced select failed"):
        fe.select_cohort(name, 4)


# -- tenant isolation -----------------------------------------------------

def test_tenants_are_isolated_seeds_policies_stats():
    """Each tenant shard owns its table/engine/policy: updates and
    selects against one never move another's version, counters, or
    policy state; per-tenant seeds decorrelate the draws."""
    fe = mk_frontend(tenants=2, n=120, k=3, policy="dqn", seed=0)
    a, b = fe.tenant_names
    assert fe.tenant(a) is not fe.tenant(b)
    assert fe.tenant(a).engine is not fe.tenant(b).engine
    assert fe.tenant(a).policy is not fe.tenant(b).policy

    v_b = fe.tenant(b).version
    ids_a, _ = fe.select_cohort(a, 10)
    fe.observe_round(a, 0.7)
    st = fe.stats()["tenants"]
    assert st[a]["requests"] == 1 and st[b]["requests"] == 0
    assert st[a]["rounds_observed"] == 1 and st[b]["rounds_observed"] == 0
    assert fe.tenant(b).version == v_b
    assert st[b]["policy"]["buffer_size"] == 0
    assert st[a]["policy"]["buffer_size"] > 0

    # independent seeds: the two shards' Q-networks differ at init
    qa = fe.tenant(a).policy.agent
    qb = fe.tenant(b).policy.agent
    import jax
    leaves_a = jax.tree_util.tree_leaves(qa.params)
    leaves_b = jax.tree_util.tree_leaves(qb.params)
    assert any(not np.array_equal(np.asarray(la), np.asarray(lb))
               for la, lb in zip(leaves_a, leaves_b))


def test_each_tenant_keeps_its_own_state_memo():
    """One memo per tenant server: a tenant's select builds its own
    serving state's per-solve half, and another tenant's traffic never
    replaces or counts against it."""
    fe = mk_frontend(tenants=2, n=120, k=3, policy="dqn", seed=0)
    a, b = fe.tenant_names
    fe.select_cohort(a, 10)
    fe.observe_round(a, 0.7)
    fe.select_cohort(b, 10)
    st = fe.stats()["tenants"]
    assert (st[a]["state_stats_builds"], st[a]["state_stats_hits"]) == (1, 1)
    assert (st[b]["state_stats_builds"], st[b]["state_stats_hits"]) == (1, 0)
    memo_a, memo_b = fe.tenant(a)._state_memo, fe.tenant(b)._state_memo
    assert memo_a[0] is not memo_b[0] and memo_a[1] is not memo_b[1]


def test_duplicate_tenant_rejected():
    fe = CohortFrontend()
    fe.add_tenant("fam", TenantSpec("fam", 40, 4,
                                    config=CohortConfig(num_clusters=2)))
    with pytest.raises(ValueError, match="already registered"):
        fe.add_tenant("fam", CohortServer(40, 4))


# -- rich (5k+1) serving state --------------------------------------------

def test_rich_state_round_trip_through_observe_round():
    """The widened 5k+1 state flows select -> observe_round -> replay:
    the policy is built for 5k+1, draws and learns on it, and the
    buffer's stored transitions have the widened shape."""
    n, k, d = 120, 3, 8
    x, _ = blob_table(n, k, d)
    srv = CohortServer(n, d, seed=0, policy="dqn",
                       config=CohortConfig(num_clusters=k),
                       dqn_overrides=FAST_DQN)     # default rich
    srv.update_embeddings(np.arange(n), x)
    dim = serving_state_dim(k, "rich")
    assert dim == 5 * k + 1
    assert srv.policy.state_dim == dim
    for _ in range(3):
        ids, res = srv.select_cohort(10)
        assert len(ids) == 10
        srv.observe_round(0.6)
    assert srv.policy.agent.buffer.s.shape[1] == dim
    assert srv.policy.agent.buffer.size > 0
    st = srv.stats()
    assert st["state_features"] == "rich"
    assert st["policy"]["state_dim"] == dim
    assert st["policy"]["state_features"] == "rich"
    # dispersion features live in [0, 1) and are not all zero for a
    # real blob table; staleness starts fresh after serving
    state = srv._policy_state(res.assign, srv.embeds)[0]
    disp = state[3 * k: 4 * k]
    stale = state[4 * k: 5 * k]
    assert np.all((disp >= 0) & (disp < 1)) and disp.max() > 0
    assert np.all((stale >= 0) & (stale < 1))


def test_basic_state_features_backcompat():
    """state_features='basic' keeps the legacy 3k+1 replay shape."""
    n, k, d = 90, 3, 8
    x, _ = blob_table(n, k, d)
    srv = CohortServer(n, d, seed=0, policy="dqn",
                       config=CohortConfig(num_clusters=k),
                       dqn_overrides=FAST_DQN, state_features="basic")
    srv.update_embeddings(np.arange(n), x)
    assert srv.policy.state_dim == 3 * k + 1
    ids, _ = srv.select_cohort(8)
    srv.observe_round(0.6)
    assert srv.policy.agent.buffer.s.shape[1] == 3 * k + 1
    with pytest.raises(ValueError, match="unknown state features"):
        CohortServer(n, d, state_features="extra")


def test_staleness_ages_unserved_clusters():
    """Clusters that stop contributing clients age in the staleness
    feature; clusters just served read fresh (0)."""
    n, k, d = 120, 3, 8
    x, _ = blob_table(n, k, d)
    srv = CohortServer(n, d, seed=0, policy="stratified",
                       config=CohortConfig(num_clusters=k))
    srv.update_embeddings(np.arange(n), x)
    ids, res = srv.select_cohort(n)          # everyone served: all fresh
    assert np.all(srv._staleness == 0.0)
    # serve only cluster 0's clients by hand-picking sizes of 0 from
    # the others: a tiny cohort will only touch some clusters
    ids, res = srv.select_cohort(1)
    served = np.unique(res.assign[ids])
    unserved = [c for c in range(k) if c not in served]
    assert np.all(srv._staleness[served] == 0.0)
    assert all(srv._staleness[c] == 1.0 for c in unserved)


def test_cluster_policy_state_validates_short_stats():
    """Per-cluster stats shorter than k must fail loudly, not emit a
    silently wrong-length state (the old [:k] slice bug)."""
    assign = np.array([0, 1, 2, 0])
    with pytest.raises(ValueError, match="participation has length 2"):
        cluster_policy_state(assign, 3, np.zeros(2), np.zeros(3), 0.5,
                             features="basic")
    with pytest.raises(ValueError, match="reward_ema has length 1"):
        cluster_policy_state(assign, 3, np.zeros(3), np.zeros(1), 0.5,
                             features="basic")
    # rich without its inputs is a clear error too
    with pytest.raises(ValueError, match="embeds"):
        cluster_policy_state(assign, 3, np.zeros(3), np.zeros(3), 0.5)
    # longer arrays (historical k̂ > k) still slice cleanly
    s = cluster_policy_state(assign, 3, np.zeros(5), np.zeros(5), 0.5,
                             features="basic")
    assert s.shape == (3 * 3 + 1,)


def test_cluster_policy_wrong_length_state_clear_error():
    from repro.policy import ClusterPolicy
    pol = ClusterPolicy(3, state_dim=16, seed=0, dqn_overrides=FAST_DQN,
                        state_features="rich")
    with pytest.raises(ValueError, match="state_dim=16"):
        pol.draw_weights(np.zeros(10, np.float32))
    with pytest.raises(ValueError, match="ClusterPolicy.observe"):
        pol.observe(np.zeros(16, np.float32), [0], 1.0,
                    np.zeros(9, np.float32))


def test_watchdog_instrumented_stack_obeys_declared_lock_order():
    """Satellite of the repro-lint lock rules: run the serving stack
    with every lock swapped for a rank-asserting
    :class:`repro.analysis.OrderedLock` and hammer it from selector /
    updater / observer / stats threads.  This covers the one edge the
    static analyzer cannot see — ``select_cohorts`` (holding
    ``_select_lock``) calling back into the frontend's ``seal`` closure,
    which takes the tenant lock — and turns any future inversion into a
    deterministic :class:`LockOrderError` instead of a rare deadlock.
    """
    from repro.analysis import instrument

    fe = mk_frontend(tenants=2, n=120, k=3, policy="dqn", window=0.0)
    assert instrument(fe) == ["_registry_lock"]
    for name in fe.tenant_names:
        tenant = fe._tenants[name]
        assert instrument(tenant, prefix=f"{name}:") == ["lock"]
        assert sorted(instrument(tenant.server, prefix=f"{name}:")) == [
            "_publish_lock", "_select_lock", "_solve_lock",
            "_stats_lock", "_write_lock"]

    errors, done = [], []
    rng = np.random.default_rng(1)

    def hammer(i):
        name = fe.tenant_names[i % len(fe.tenant_names)]
        server = fe.tenant(name)
        try:
            for r in range(4):
                ids, _ = fe.select_cohort(name, 6)
                server.observe_round(0.5 + 0.01 * len(ids),
                                     timings={"train": 0.01})
                server.update_embeddings(
                    ids, rng.normal(size=(len(ids), 8)).astype(np.float32))
                fe.stats()
            done.append(i)
        except Exception as exc:        # pragma: no cover - failure path
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert errors == []
    assert len(done) == 8
