"""One run of one cell: set-up, an open-loop window, the comparison.

Set-up builds the planted table from the seed, a streaming
``CohortServer`` with the cell's engine settings and the DQN policy, and
warms exactly the shapes the mix uses: the cold solve always; a select
and its ``observe_round`` where the mix selects; a warm solve where it
updates.  Then the window opens.

In the window three threads of this process drive the service through
its public entry points: selects (each followed by its
``observe_round``) due on the cell's schedule and timed from when they
were due, update batches due on a fixed grid, and a poller that notes
each solve the engine publishes.  Selects due in the window are all
answered, up to ``GRACE_S`` after it.  Then the service is closed, its
last solve drained, and that solve and a seeded sample of the solves
served in the window are held against the reference (``compare.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

from bench import compare, traffic

GRACE_S = 60.0
SAMPLED_SOLVES = 2
POLL_S = 0.005
#: longest wait for the solve in flight when the window closes
CLOSE_S = 120.0
#: seconds of the window the profiler records in a traced run
TRACE_S = 8.0


@dataclasses.dataclass
class Run:
    """What readers in ``bench/metrics/`` read."""
    config: dict
    device_kind: str
    setup_s: float
    window_s: float                  # the length due work was issued over
    close_s: float                   # when the last due answer came
    selects: List[dict]
    observes: List[float]            # seconds of each observe_round
    updates: List[dict]
    solves: List[dict]               # published solves: t, seconds
    trace: Optional[object] = None   # bench.trace.Summary of a traced run


class _CompileCount:
    """Backend compilations while ``active`` (a persistent-cache load
    counts too: either way a program was built on the timed path)."""

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _wait(pred, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout}s: {what}")
        time.sleep(0.01)


def engine_config(config: dict):
    from repro.cohort import CohortConfig
    return CohortConfig(num_clusters=int(config["num_clusters"]),
                        num_landmarks=int(config["num_landmarks"]),
                        **config.get("engine", {}))


class _Window:
    """The threads of one window and what they record."""

    def __init__(self, srv, cohort: int, schedule, accs, batches,
                 table: np.ndarray, labels: np.ndarray, seconds: float,
                 sample_seed: int, annotate: bool, observe: bool):
        self.srv = srv
        self.cohort = cohort
        self.schedule = schedule
        self.accs = accs
        self.batches = batches
        self.table = table               # own copy, kept current
        self.labels = labels             # planted labels, kept current
        self.log: List[tuple] = []       # (version, batch index)
        self.seconds = seconds
        self.annotate = annotate
        self.observe = observe
        self.n = len(table)
        self.selects: List[dict] = []
        self.observes: List[float] = []
        self.updates: List[dict] = []
        self.solves: List[dict] = []
        # [attempted, failed], each list written by one thread only
        self.select_counts = [0, 0]
        self.update_counts = [0, 0]
        self.cohort_faults = 0
        self.sampled: List[tuple] = []   # (served version, CohortResult)
        self._distinct = 0
        self._last_version = None
        self._rng = np.random.default_rng(sample_seed)
        self._stop = threading.Event()
        self.t0 = 0.0
        self.errors: List[str] = []

    def _now(self) -> float:
        return time.perf_counter() - self.t0

    def _sleep_until(self, due: float) -> None:
        lag = due - self._now()
        if lag > 0:
            time.sleep(lag)

    def _sample(self, version: int, res) -> None:
        if version == self._last_version:
            return
        self._last_version = version
        self._distinct += 1
        self.cohort_faults += int(compare.partition_fault(res.assign, res.k,
                                                          self.n))
        if len(self.sampled) < SAMPLED_SOLVES:
            self.sampled.append((version, res))
        else:
            j = int(self._rng.integers(self._distinct))
            if j < SAMPLED_SOLVES:
                self.sampled[j] = (version, res)

    def select_loop(self) -> None:
        srv = self.srv
        free = 0.0                       # when the previous round ended
        for j, due in enumerate(self.schedule):
            self._sleep_until(due)
            issue = self._now()
            ready = max(float(due), free)
            if issue > self.seconds + GRACE_S:
                left = len(self.schedule) - j
                self.select_counts[0] += (1 + self.observe) * left
                self.select_counts[1] += (1 + self.observe) * left
                self.errors.append(f"{left} selects unanswered by "
                                   f"{GRACE_S}s past the window")
                return
            self.select_counts[0] += 1 + self.observe
            try:
                with _annotate("bench.select", self.annotate):
                    ids, res = srv.select_cohort(self.cohort)
                done = self._now()
                front = srv.last_select_s
            except Exception as e:  # a failed select is counted, not fatal
                self.select_counts[1] += 1 + self.observe
                self.errors.append(f"select: {e!r}")
                free = self._now()
                continue
            version = srv.stats()["streaming"]["served_version"]
            self.cohort_faults += int(compare.cohort_fault(
                ids, self.cohort, self.n, res.assign, res.k))
            self.selects.append(dict(due=float(due), ready=ready,
                                     issue=issue, done=done,
                                     front_s=front, version=version))
            self._sample(version, res)
            if not self.observe:
                free = self._now()
                continue
            t = time.perf_counter()
            try:
                with _annotate("bench.observe", self.annotate):
                    srv.observe_round(float(self.accs[j]))
                self.observes.append(time.perf_counter() - t)
            except Exception as e:
                self.select_counts[1] += 1
                self.errors.append(f"observe_round: {e!r}")
            free = self._now()

    def update_loop(self) -> None:
        srv = self.srv
        for j, b in enumerate(self.batches):
            self._sleep_until(b.due)
            issue = self._now()
            self.update_counts[0] += 1
            try:
                with _annotate("bench.update", self.annotate):
                    srv.update_embeddings(b.ids, b.rows)
            except Exception as e:
                self.update_counts[1] += 1
                self.errors.append(f"update_embeddings: {e!r}")
                continue
            ack = self._now()
            version = srv.version
            self.table[b.ids] = b.rows
            self.labels[b.ids] = b.labels
            self.log.append((version, j))
            self.updates.append(dict(due=b.due, issue=issue, ack=ack,
                                     version=version))

    def poll_loop(self) -> None:
        engine = self.srv.engine
        last = engine.stats["solves"]
        while not self._stop.is_set():
            count = engine.stats["solves"]
            if count != last:
                res = engine.state.result
                self.solves.append(dict(t=self._now(), count=count - last,
                                        seconds=res.seconds))
                last = count
            time.sleep(POLL_S)

    def run(self, trace_dir: Optional[str]) -> float:
        threads = []
        if len(self.schedule):
            threads.append(threading.Thread(target=self.select_loop,
                                            name="bench-select"))
        if self.batches:
            threads.append(threading.Thread(target=self.update_loop,
                                            name="bench-update"))
        poller = threading.Thread(target=self.poll_loop, name="bench-poll")
        self.t0 = time.perf_counter()
        for t in threads + [poller]:
            t.start()
        if trace_dir is not None:
            self._trace(trace_dir)
        for t in threads:
            t.join()
        close = max([self.seconds] + [s["done"] for s in self.selects]
                    + [u["ack"] for u in self.updates])
        self._stop.set()
        poller.join()
        return close

    def _trace(self, trace_dir: str) -> None:
        import jax
        length = min(TRACE_S, self.seconds)
        start = (self.seconds - length) / 2
        self._sleep_until(start)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._sleep_until(start + length)
        jax.profiler.stop_trace()

    def table_at(self, version: int, table0: np.ndarray,
                 labels0: np.ndarray):
        """The table and its planted labels as of ``version``."""
        table, labels = table0.copy(), labels0.copy()
        for v, j in self.log:
            if v <= version:
                b = self.batches[j]
                table[b.ids] = b.rows
                labels[b.ids] = b.labels
        return table, labels


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             process_start: float, trace_dir: str = None) -> dict:
    """One run; returns what ``run.py`` prints, plus the ``Run``."""
    from repro.launch.mesh import make_cohort_mesh
    from repro.launch.serve import CohortServer
    from repro.streaming import StreamingSpec

    cfg, mix = cell.config, cell.traffic
    n, d = int(cfg["num_clients"]), int(cfg["embed_dim"])
    k = int(cfg["num_clusters"])
    (s_table, s_updates, s_sched, s_acc, s_server, s_sample,
     s_warm) = traffic.sub_seeds(seed, 7)
    pop = traffic.planted_table(n, k, d, s_table,
                                cluster_zipf=float(cfg["cluster_zipf"]),
                                center_scale=float(cfg["center_scale"]))
    updates = mix.get("updates")
    rate = (float(cell.cell["select_rate_per_s"]) if mix.get("selects")
            else None)
    warm_batch = (traffic.update_stream(pop, updates, 1.0 / float(
        updates["rate_per_s"]), s_warm)[0] if updates else None)
    labels0 = pop.labels.copy()          # as of the warm solve
    batches = (traffic.update_stream(pop, updates, seconds, s_updates)
               if updates else [])
    schedule = (traffic.select_schedule(rate, seconds, s_sched,
                                        mix["selects"]["gaps"])
                if rate else np.empty(0))
    observe = bool(rate) and bool(mix["selects"]["observe"])
    accs = traffic.accuracies(len(schedule) + 2, s_acc)
    cohort = int(cfg["cohort_size"])

    mesh = make_cohort_mesh(cell.chips)
    srv = CohortServer(n, d, seed=s_server, config=engine_config(cfg),
                       policy=cfg["policy"],
                       state_features=cfg["state_features"],
                       streaming=StreamingSpec(), mesh=mesh)
    own = pop.table
    srv.update_embeddings(np.arange(n), own)
    _wait(lambda: srv.stats()["warm_ahead"] >= 1, 1200, "cold solve")
    if rate:
        srv.select_cohort(cohort)
        if observe:
            srv.observe_round(float(accs[-1]))
    if warm_batch is not None:
        srv.update_embeddings(warm_batch.ids, warm_batch.rows)
        own[warm_batch.ids] = warm_batch.rows
        _wait(lambda: srv.stats()["warm_ahead"] >= 2, 1200, "warm solve")
        if rate:
            srv.select_cohort(cohort)
            if observe:
                srv.observe_round(float(accs[-2]))
    table0 = own.copy()
    cold0 = srv.engine.stats["cold_starts"]
    compiles = _CompileCount()

    window = _Window(srv, cohort, schedule, accs, batches, own,
                     labels0.copy(), seconds, s_sample, annotate=trace,
                     observe=observe)
    setup_s = time.perf_counter() - process_start
    compiles.active = True
    close_s = window.run(trace_dir if trace else None)
    compiles.active = False

    devices = mesh.devices.reshape(-1).tolist()
    peak = 0
    for dev in devices:
        ms = dev.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)),
                   int(ms.get("peak_bytes_reserved", 0)))
    srv.close(timeout=CLOSE_S)
    st = srv.stats()
    solver = st["streaming"].get("solver") or {}
    versions = [s["version"] for s in window.selects]
    regressions = int(sum(b < a for a, b in zip(versions, versions[1:])))
    served_table = srv.snapshot()[1]
    unread = int(np.any(served_table != own, axis=1).sum())
    del served_table
    state = srv.engine.state
    stale = int(state.fingerprint != srv.engine.fingerprint(own))
    loss = st["policy"].get("last_loss", 0.0)
    nonfinite = int(not np.isfinite(loss))

    # the solves compared: the last one, and a seeded sample of those
    # served in the window where the landmark set provably stayed put
    t_compare = time.perf_counter()
    compared = [(state.result, (own, window.labels))]
    if srv.engine.stats["cold_starts"] == cold0:
        compared += [(res, window.table_at(version, table0, labels0))
                     for version, res in window.sampled
                     if res is not state.result]
    numbers = [compare.compare_solve(res, table, labels, state.landmark_idx,
                                     state.gamma, k, seed=s_sample)
               for res, (table, labels) in compared]
    checks = [compare.Check(name, max(x[name] for x in numbers),
                            float(cell.cell["limits"][name]))
              for name in compare.SOLVE_NUMBERS]
    checks += compare.serving_checks(
        window.cohort_faults, regressions, int(solver.get("errors", 0)),
        unread, stale, nonfinite)
    compare_s = time.perf_counter() - t_compare

    run = Run(config=cfg, device_kind=devices[0].device_kind,
              setup_s=setup_s, window_s=float(seconds),
              close_s=close_s, selects=window.selects,
              observes=window.observes, updates=window.updates,
              solves=window.solves)
    if trace:
        from bench import trace as trace_mod
        run.trace = trace_mod.summarize(trace_dir, chips=cell.chips)
    return dict(run=run, checks=checks,
                attempted=window.select_counts[0] + window.update_counts[0],
                failed=window.select_counts[1] + window.update_counts[1],
                errors=window.errors,
                compiles_in_window=compiles.count, memory_peak_bytes=peak,
                compared_solves=len(numbers), solve_numbers=numbers,
                compare_s=compare_s, device=devices[0])
