"""Policy serving: ClusterPolicy learning + the hardened CohortServer."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.cohort import CohortConfig
from repro.fed.metrics import (cluster_dispersion, cluster_policy_state,
                               cluster_solve_stats)
from repro.launch.serve import CohortServer
from repro.policy import ClusterIndex, ClusterPolicy
from repro.streaming import StreamingSpec

FAST_DQN = {"hidden": (32,), "eps_decay_steps": 30, "buffer_size": 512,
            "batch_size": 64}


def blob_table(n=120, k=3, d=8, sep=8.0, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32) * sep
    true = rng.integers(0, k, n)
    x = (centers[true] + rng.normal(size=(n, d)).astype(np.float32))
    return x, true


def mk_server(n=120, k=3, d=8, policy="dqn", seed=0, **cfg_kw):
    x, true = blob_table(n, k, d, seed=seed)
    srv = CohortServer(n, d, seed=seed, policy=policy,
                       config=CohortConfig(num_clusters=k, **cfg_kw),
                       dqn_overrides=FAST_DQN if policy == "dqn" else None)
    srv.update_embeddings(np.arange(n), x)
    return srv, true


# -- ClusterPolicy (Algorithm II in isolation) ---------------------------

def test_cluster_policy_learns_to_avoid_zero_reward_cluster():
    """Acceptance: trained on synthetic rewards where cluster 0 pays
    nothing, the policy's draw weights shift away from cluster 0."""
    k = 3
    pol = ClusterPolicy(k, state_dim=4, seed=0, dqn_overrides=FAST_DQN)
    rng = np.random.default_rng(0)
    s = np.ones(4, np.float32)
    for _ in range(120):
        for a in range(k):
            pol.observe(s, [a], 0.0 if a == 0 else 1.0, s)
        pol.train(rng)
    pol.agent.steps = 10_000            # decay ε to eps_end
    w = pol.draw_weights(s)
    assert w.shape == (k,) and abs(w.sum() - 1.0) < 1e-9
    assert w[0] < 1.0 / k               # shifted away from zero reward
    assert int(np.argmax(w)) != 0


def test_cluster_policy_draw_contract():
    """draw() honors pools: unique clients, no empty-cluster picks,
    actions aligned with picked slots."""
    k = 4
    pol = ClusterPolicy(k, state_dim=3, seed=0, dqn_overrides=FAST_DQN)
    rng = np.random.default_rng(0)
    # cluster 0: clients 0-4, 1: 5-9, 2: none, 3: 10-11
    pools = ClusterIndex(np.repeat([0, 1, 3], [5, 5, 2]), k).pools()
    picked, actions = pol.draw(rng, np.zeros(3, np.float32), pools, 8)
    assert len(picked) == 8 == len(actions)
    assert len(set(picked)) == 8
    assert 2 not in actions             # empty cluster never credited
    # pool exhaustion: asking for more than exists returns what's there
    pools = ClusterIndex(np.array([0, 0]), k).pools()
    picked, actions = pol.draw(rng, np.zeros(3, np.float32), pools, 8)
    assert sorted(picked) == [0, 1]


@pytest.mark.parametrize("earlier", [0, 3], ids=["fresh", "after_take"])
def test_pool_draws_are_uniform_within_a_cluster(earlier):
    """Many draws of 3 from a 10-member cluster, first in a batch or
    after an earlier take from the same cluster, give every member a
    frequency within 5% of uniform (4.6 sd at 20,000 batches): a draw
    that kept to a fixed prefix of the index would give 3 members all
    of it."""
    members, draws, batches = 10, 3, 20_000
    index = ClusterIndex(np.zeros(members, np.int64), 1)
    rng = np.random.default_rng(7)
    seen = np.zeros(members, np.int64)
    for _ in range(batches):
        pools = index.pools()
        before = pools.take(rng, [0] * earlier)
        picked = pools.take(rng, [0] * draws)
        assert len(set(before + picked)) == earlier + draws
        seen[picked] += 1
    expected = batches * draws / members
    assert np.all(np.abs(seen - expected) < 0.05 * expected), seen


POLICIES = CohortServer.POLICIES


def planted_server(policy, n=120, k=4):
    """A server whose engine hands back one fixed solve: cluster c holds
    the clients i with i % k == c, but cluster 2 is empty (its clients
    are in cluster 3).  The same ``assign`` object on every select."""
    assign = np.arange(n) % k
    assign[assign == 2] = 3
    assign.setflags(write=False)
    res = SimpleNamespace(assign=assign, k=k)
    srv = CohortServer(n, 8, seed=0, policy=policy,
                       config=CohortConfig(num_clusters=k),
                       dqn_overrides=FAST_DQN if policy == "dqn" else None)
    srv.engine.select_batched = lambda table, requests: res
    return srv, assign


@pytest.mark.parametrize("policy", POLICIES)
def test_batch_cohorts_are_disjoint_and_full(policy):
    srv, _ = planted_server(policy)
    cohorts = [ids for ids, _ in srv.select_cohorts([20, 20, 20])]
    assert [len(ids) for ids in cohorts] == [20, 20, 20]
    assert len(np.unique(np.concatenate(cohorts))) == 60


@pytest.mark.parametrize("policy", POLICIES)
def test_exhausted_pools_return_what_exists(policy):
    """The batch asks for more than the 120 clients: the first cohort is
    full, the second gets the 20 left, and together they are all."""
    srv, _ = planted_server(policy)
    (a, _), (b, _) = srv.select_cohorts([100, 100])
    assert (len(a), len(b)) == (100, 20)
    assert np.array_equal(np.sort(np.concatenate([a, b])), np.arange(120))
    (c, _), = srv.select_cohorts([130])
    assert np.array_equal(np.sort(c), np.arange(120))


@pytest.mark.parametrize("policy", POLICIES)
def test_empty_cluster_is_never_drawn(policy):
    srv, assign = planted_server(policy)
    ids, _ = srv.select_cohort(30)
    assert len(ids) == 30 and 2 not in assign[ids]
    if policy == "dqn":
        actions = srv._pending[1]
        assert len(actions) == 30 and 2 not in actions
        assert np.array_equal(assign[ids], actions)
    else:                               # round-robin over 0, 1 and 3
        assert np.bincount(assign[ids], minlength=4).tolist() == [
            10, 10, 0, 10]


@pytest.mark.parametrize("policy", POLICIES)
def test_selects_on_one_solve_draw_from_the_whole_partition(policy):
    """The cached index is never consumed: a second select on the same
    solve again gets a full 100 of the 120 clients."""
    srv, _ = planted_server(policy)
    for _ in range(2):
        ids, _ = srv.select_cohort(100)
        assert len(ids) == 100 == len(np.unique(ids))
        if policy == "dqn":
            srv.observe_round(0.6)
    st = srv.stats()
    assert (st["pool_index_builds"], st["pool_index_hits"]) == (1, 1)


@pytest.mark.parametrize("policy", POLICIES)
def test_cached_index_is_unchanged_by_draws(policy):
    srv, assign = planted_server(policy)
    srv.select_cohort(10)
    memo_assign, index = srv._index_memo
    assert memo_assign is assign
    before = [index.members(c).copy() for c in range(4)]
    counts = index.counts.copy()
    srv.select_cohorts([50, 50, 50])
    assert srv._index_memo[1] is index
    np.testing.assert_array_equal(index.counts, counts)
    for c in range(4):
        np.testing.assert_array_equal(index.members(c), before[c])
        np.testing.assert_array_equal(index.members(c),
                                      np.flatnonzero(assign == c))
    assert not index.members(0).flags.writeable


# -- CohortServer: DQN-policy serving ------------------------------------

def test_cohort_server_dqn_shifts_draws_from_stale_cluster():
    """Acceptance criterion: serving with --policy dqn, a synthetic
    reward that pays nothing for 'stale' clients (true cluster 0) pushes
    the learned draw weights away from the engine cluster covering them."""
    srv, true = mk_server()
    k = srv.config.num_clusters
    for _ in range(60):
        ids, res = srv.select_cohort(12)
        useful = float(np.mean(true[ids] != 0)) if len(ids) else 0.0
        srv.observe_round(0.5 + 0.4 * useful)
    # engine cluster holding the majority of true-cluster-0 clients
    assign = srv.engine.state.result.assign
    stale = int(np.argmax(np.bincount(assign[true == 0], minlength=k)))
    srv.policy.agent.steps = 10_000     # read weights at ε = eps_end
    w = srv.policy.draw_weights(srv._policy_state(assign, srv.embeds)[0])
    assert w[stale] < 1.0 / k
    assert int(np.argmax(w)) != stale


def test_cohort_server_dqn_roundtrip_counters():
    """stats() reports advancing engine/policy/latency counters."""
    srv, true = mk_server()
    for r in range(3):
        ids, res = srv.select_cohort(10)
        assert len(ids) == 10 and len(set(ids.tolist())) == 10
        srv.observe_round(0.6, timings={"select": 0.01, "train": 0.2})
    st = srv.stats()
    assert st["requests"] == 3
    assert st["rounds_observed"] == 3
    assert st["engine"]["solves"] >= 1
    assert st["engine"]["cache_hits"] == 2       # same table, cached
    assert st["latency_s"]["total_s"] > 0
    assert st["round_timings_s"]["train"] == pytest.approx(0.2)
    assert st["last_select"]["method"] == "dense"
    assert 0.0 <= st["policy"]["epsilon"] <= 1.0
    assert st["policy"]["buffer_size"] > 0
    assert st["policy"]["train_calls"] == 3
    assert st["dropped_transitions"] == 0
    # a second select before the round report replaces the parked
    # transition — observable, not silent
    srv.select_cohort(10)
    srv.select_cohort(10)
    assert srv.stats()["dropped_transitions"] == 1


def test_cohort_server_stratified_unchanged_contract():
    """The default policy still serves de-biased round-robin cohorts."""
    srv, _ = mk_server(policy="stratified")
    ids, res = srv.select_cohort(9)
    assert len(ids) == 9 and len(set(ids.tolist())) == 9
    # round-robin over k=3 clusters -> 3 from each
    counts = np.bincount(res.assign[ids], minlength=res.k)
    assert counts.max() - counts.min() <= 1
    st = srv.stats()
    assert st["policy"] == {"kind": "stratified"}


def test_cohort_server_rejects_unknown_policy():
    with pytest.raises(ValueError, match="unknown policy"):
        CohortServer(10, 4, policy="bandit")


# -- CohortServer: versioned copy-on-write table -------------------------

def test_cohort_server_snapshot_versioning_and_immutability():
    srv, _ = mk_server(policy="stratified")
    v0, table0 = srv.snapshot()
    with pytest.raises(ValueError):
        table0[0, 0] = 1.0              # snapshots are frozen
    srv.update_embeddings([0], np.ones((1, 8), np.float32))
    v1, table1 = srv.snapshot()
    assert v1 == v0 + 1
    assert table1 is not table0         # copy-on-write, not in-place
    assert table0[0, 0] != 1.0          # old snapshot untouched
    assert table1[0, 0] == 1.0


def test_cohort_server_concurrent_update_select_no_torn_reads():
    """Interleaved update_embeddings/select_cohort: the table a solve
    clusters must be one consistent version, never a half-written mix."""
    n, d = 96, 4
    base, _ = blob_table(n=n, k=3, d=d, seed=1)
    srv = CohortServer(n, d, seed=0, policy="stratified",
                       config=CohortConfig(num_clusters=3))
    srv.update_embeddings(np.arange(n), base)

    torn = []
    orig_select = srv.engine.select

    def spy(embeds, **kw):
        before = np.array(embeds, copy=True)
        time.sleep(0.01)                 # widen the race window
        if not np.array_equal(before, np.asarray(embeds)):
            torn.append("snapshot mutated under reader")
        # version consistency: the table must be bit-identical to ONE
        # writer version base + 0.001*v (same float32 op as the writer),
        # never a mix of rows from different versions
        offsets = np.asarray(embeds) - base
        v_est = int(round(float(offsets.mean()) / 0.001))
        if not np.array_equal(np.asarray(embeds),
                              base + np.float32(0.001 * v_est)):
            torn.append("mixed-version table")
        return orig_select(before, **kw)

    srv.engine.select = spy
    stop = threading.Event()

    def writer():
        v = 0
        while not stop.is_set():
            v += 1
            srv.update_embeddings(np.arange(n),
                                  base + np.float32(0.001 * v))

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(5):
            ids, res = srv.select_cohort(6)
            assert len(ids) == 6
            assert res.assign.shape == (n,)
    finally:
        stop.set()
        th.join()
    assert not torn, torn
    assert srv.version > 0
    assert srv.stats()["updates"] == srv.version


def test_cluster_policy_train_returns_device_scalar_lazy_loss():
    """Regression (repro-lint jax-blocking-sync): train() must not force
    a host sync under the server's select lock; stats() materializes
    the loss lazily through the last_loss property."""
    pol = ClusterPolicy(3, state_dim=10, seed=0, dqn_overrides=FAST_DQN)
    rng = np.random.default_rng(0)
    s = rng.normal(size=10).astype(np.float32)
    for _ in range(16):
        pol.observe(s, [int(rng.integers(3))], 1.0, s)
    out = pol.train(rng)
    assert not isinstance(out, float)          # device scalar
    assert isinstance(pol.last_loss, float)    # lazy materialization
    assert pol.stats()["last_loss"] == pol.last_loss


def test_cohort_server_stats_are_lock_protected_snapshots():
    """Regression (repro-lint lock-guarded-by): dashboard counters live
    behind their own _stats_lock, and stats() hands back copies —
    mutating the returned dicts must not corrupt the live state."""
    server, _ = mk_server(policy="stratified")
    server.select_cohort(8)
    st = server.stats()
    st["latency_s"]["total_s"] = -1.0
    st["round_timings_s"]["bogus"] = 1.0
    st["requests"] = 10**6
    st2 = server.stats()
    assert st2["latency_s"]["total_s"] >= 0.0
    assert "bogus" not in st2["round_timings_s"]
    assert st2["requests"] == 1
    # counters shared by the update path and the select path still agree
    server.update_embeddings(np.arange(4), np.zeros((4, 8), np.float32))
    assert server.stats()["updates"] == 2      # mk_server seeded 1 update


# -- serving state: the per-solve half, built once per served solve -------

def two_pass_dispersion(embeds, assign, k):
    """Reference: a centred float64 pass for the global spread, then one
    boolean-mask gather and a centred pass per cluster."""
    embeds = np.asarray(embeds, np.float64)
    assign = np.asarray(assign)
    global_var = float(np.mean(np.sum(
        (embeds - embeds.mean(axis=0)) ** 2, axis=1)))
    out = np.zeros(k, np.float64)
    if global_var <= 0.0:
        return out
    for c in range(k):
        members = embeds[assign == c]
        if len(members) == 0:
            continue
        var = float(np.mean(np.sum(
            (members - members.mean(axis=0)) ** 2, axis=1)))
        ratio = var / global_var
        out[c] = ratio / (1.0 + ratio)
    return out


def dispersion_case(name):
    """(embeds, assign, k): 3,000 rows, so several partial-sum blocks."""
    x, true = blob_table(3000, 5, 8, seed=3)
    if name == "blobs":
        return x, true, 5
    if name == "empty_clusters":               # clusters 5..7 hold no one
        return x, true, 8
    if name == "single_member_clusters":
        assign = true.copy()
        assign[[0, 1500, 2999]] = [5, 6, 7]
        return x, assign, 8
    if name == "constant_table":               # global spread 0
        return np.full((500, 8), 2.5, np.float32), true[:500], 5
    if name == "offset_1e4":                   # a large common offset
        return x + np.float32(1e4), true, 5
    if name == "ids_outside_0_k":              # belong to no cluster
        assign = true.copy()
        assign[::7], assign[3::11] = 9, -1
        return x, assign, 5
    raise ValueError(name)


@pytest.mark.parametrize("case", [
    "blobs", "empty_clusters", "single_member_clusters", "constant_table",
    "offset_1e4", "ids_outside_0_k"])
def test_one_pass_dispersion_matches_two_pass_reference(case):
    """Same numbers as the per-cluster gathers, to summation order:
    1e-12 relative (1e-15 absolute where the reference reads exactly 0,
    as a one-member or empty cluster does)."""
    x, assign, k = dispersion_case(case)
    want = two_pass_dispersion(x, assign, k)
    got = cluster_dispersion(x, assign, k)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-15)
    if case == "constant_table":
        assert not got.any()
    if case == "empty_clusters":
        assert not got[5:].any() and got[:5].all()


def test_cluster_solve_stats_is_population_and_dispersion():
    x, assign, k = dispersion_case("empty_clusters")
    pop, disp = cluster_solve_stats(assign, x, k)
    np.testing.assert_array_equal(pop, np.bincount(assign, minlength=k) / 3000)
    np.testing.assert_array_equal(disp, cluster_dispersion(x, assign, k))
    pop_basic, none = cluster_solve_stats(assign, None, k)
    assert none is None
    np.testing.assert_array_equal(pop_basic, pop)
    # a rich state cannot be read from the basic layout's stats
    zeros = np.zeros(k)
    with pytest.raises(ValueError, match="dispersion in solve_stats"):
        cluster_policy_state(assign, k, zeros, zeros, 0.5, staleness=zeros,
                             solve_stats=(pop_basic, none))


@pytest.mark.parametrize("features", ["basic", "rich", "system"])
def test_state_memo_hit_is_bit_identical_to_a_fresh_build(features):
    """After a select and its observe, the memo describes the served
    solve; a state read through it equals cluster_policy_state built
    from scratch on the same inputs, bit for bit."""
    n, k, d = 120, 3, 8
    x, _ = blob_table(n, k, d)
    srv = CohortServer(n, d, seed=0, policy="dqn",
                       config=CohortConfig(num_clusters=k),
                       dqn_overrides=FAST_DQN, state_features=features)
    srv.update_embeddings(np.arange(n), x)
    _, res = srv.select_cohort(10)
    srv.observe_round(0.7)
    table = srv.embeds
    state, memo, hit = srv._policy_state(res.assign, table)
    assert hit and memo is srv._state_memo
    rich, system = features != "basic", features == "system"
    fresh = cluster_policy_state(
        res.assign, k, srv._participation, srv._reward_ema,
        srv.prev_accuracy, embeds=table if rich else None,
        staleness=srv._staleness if rich else None,
        availability=srv._avail_ema if system else None,
        latency_s=srv._latency_ema_s if system else None,
        features=features)
    assert state.dtype == fresh.dtype == np.float32
    np.testing.assert_array_equal(state, fresh)
    assert (memo[2][1] is None) == (features == "basic")


def test_state_memo_is_keyed_on_the_solve_objects():
    """Inline solves hand each select a fresh assign (the engine's cache
    copies it), so every select builds and its observe reuses; another
    table or assign object, even equal in value, misses."""
    srv, _ = mk_server()
    for _ in range(2):
        srv.select_cohort(10)
        srv.observe_round(0.6)
    st = srv.stats()
    assert (st["state_stats_builds"], st["state_stats_hits"]) == (2, 2)
    table, assign, _ = srv._state_memo
    assert srv._policy_state(assign, table)[2]
    assert not srv._policy_state(assign.copy(), table)[2]
    assert not srv._policy_state(assign, table.copy())[2]
    # a lookup alone stores nothing: only select and observe do
    assert srv._state_memo[0] is table and srv._state_memo[1] is assign


def test_state_memo_rebuilds_only_when_the_served_solve_changes():
    """Streaming: a select that swaps in a new solve builds; its observe
    and every select still serving that solve reuse the memo."""
    n, k, d = 120, 3, 8
    x, _ = blob_table(n, k, d)
    srv = CohortServer(n, d, seed=0, policy="dqn",
                       config=CohortConfig(num_clusters=k),
                       dqn_overrides=FAST_DQN, streaming=StreamingSpec())

    def publish(ids, rows):
        warmed = srv.stats()["warm_ahead"]
        srv.update_embeddings(ids, rows)
        deadline = time.monotonic() + 60
        while srv.stats()["warm_ahead"] <= warmed:
            assert time.monotonic() < deadline, "no warm solve landed"
            time.sleep(0.005)

    def counts():
        st = srv.stats()
        return st["state_stats_builds"], st["state_stats_hits"]

    try:
        publish(np.arange(n), x)
        srv.select_cohort(10)                       # new solve: build
        assert counts() == (1, 0)
        srv.observe_round(0.6)                      # same solve: hit
        srv.select_cohort(10)                       # still served: hit
        srv.observe_round(0.6)
        assert counts() == (1, 3)
        memo = srv._state_memo
        publish(np.arange(8), x[:8] + 0.5)
        srv.select_cohort(10)                       # swapped in: build
        srv.observe_round(0.6)
        assert counts() == (2, 4)
        assert srv._state_memo[1] is not memo[1]
        assert srv._state_memo[0] is not memo[0]
        assert srv.stats()["served_warm"] == 3
    finally:
        srv.close(timeout=60)


def test_stratified_server_builds_no_state():
    srv, _ = mk_server(policy="stratified")
    srv.select_cohort(8)
    srv.observe_round(0.5)
    st = srv.stats()
    assert (st["state_stats_builds"], st["state_stats_hits"]) == (0, 0)
    assert srv._state_memo is None


@pytest.mark.parametrize("policy", POLICIES)
def test_pool_index_builds_once_per_served_solve(policy):
    """Streaming: a static table is one build, however many selects
    serve it; an update whose solve is swapped in is a second."""
    n, k, d = 120, 3, 8
    x, _ = blob_table(n, k, d)
    srv = CohortServer(n, d, seed=0, policy=policy,
                       config=CohortConfig(num_clusters=k),
                       dqn_overrides=FAST_DQN if policy == "dqn" else None,
                       streaming=StreamingSpec())

    def publish(ids, rows):
        warmed = srv.stats()["warm_ahead"]
        srv.update_embeddings(ids, rows)
        deadline = time.monotonic() + 60
        while srv.stats()["warm_ahead"] <= warmed:
            assert time.monotonic() < deadline, "no warm solve landed"
            time.sleep(0.005)

    def counts():
        st = srv.stats()
        return st["pool_index_builds"], st["pool_index_hits"]

    try:
        publish(np.arange(n), x)
        for _ in range(3):
            ids, _ = srv.select_cohort(10)
            assert len(ids) == 10
            srv.observe_round(0.6)
        assert counts() == (1, 2)
        index = srv._index_memo[1]
        publish(np.arange(8), x[:8] + 0.5)
        srv.select_cohort(10)
        assert counts() == (2, 2)
        assert srv._index_memo[1] is not index
    finally:
        srv.close(timeout=60)
