"""Smoke check: the cohort-selection service, its kernels and the FL loop
on a TPU chip.

Drives the entry points a user calls, in one process:

  (a) device   — a TPU or nothing; the cohort mesh spans exactly one chip;
  (b) cohort   — ``CohortServer(policy="dqn")`` over a planted table of
                 N = 10⁶ clients (d = 8, k = 8, default landmarks): a cold
                 select plus 3 rounds of select → observe_round → update
                 churn, on the jnp reference path and on the fused Pallas
                 path in f32, bf16 and int8, each checked against the
                 reference and the planted labels;
  (c) stream   — a streaming ``CohortServer`` over churned table versions,
                 with zero background-solve errors;
  (d) fl       — ``FederatedRunner(policy="dqre_sc")`` for 3 rounds.

``--chips 4`` runs only the sharded cohort path on a four-chip mesh and
what it is compared with: the 10⁶ table on 4 chips against 1 chip, and a
10⁷ table on 4 chips against its planted labels.

Times, compile seconds and device bytes printed here are smoke readings,
not benchmark metrics.  Any failed check raises, so the script exits
non-zero; the last line of a passing run is one JSON object naming the
device.  There is no CPU branch: without a TPU it exits 1 before any
phase.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # sharded cohort path, four chips
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

import numpy as np  # noqa: E402

SEED = 0
N_CLIENTS = 1_000_000
N_CLIENTS_4CHIP = 10_000_000
DIM, K = 8, 8
COHORT = 64
ROUNDS = 3


class SmokeFailure(AssertionError):
    """A smoke check that did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  check ok: {what}", flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, process-wide."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


def purity(assign, labels) -> float:
    """Share of clients in their cluster's majority planted label."""
    table = np.zeros((assign.max() + 1, labels.max() + 1), np.int64)
    np.add.at(table, (assign, labels), 1)
    return float(table.max(axis=1).sum() / len(labels))


def agreement(a, b) -> float:
    """Share of clients on which two partitions agree, up to relabeling."""
    from scipy.optimize import linear_sum_assignment
    table = np.zeros((a.max() + 1, b.max() + 1), np.int64)
    np.add.at(table, (a, b), 1)
    rows, cols = linear_sum_assignment(-table)
    return float(table[rows, cols].sum() / len(a))


def peak_bytes(devices) -> list:
    """Per device: (peak_bytes_in_use, peak_bytes_reserved).  Buffers
    count as in use; compiled programs' temp space only as reserved."""
    stats = [d.memory_stats() for d in devices]
    return [(s.get("peak_bytes_in_use"), s.get("peak_bytes_reserved"))
            for s in stats]


def wait_until(predicate, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise SmokeFailure(f"timed out after {timeout}s: {what}")
        time.sleep(0.05)


def cohort_config(variant: str):
    from repro.cohort import CohortConfig
    if variant == "jnp":
        return CohortConfig(num_clusters=K)
    return CohortConfig(num_clusters=K, use_pallas=True,
                        affinity_dtype=variant)


def phase_cohort(mesh, clock, n: int = N_CLIENTS) -> dict:
    """(b): DQN cohort service, jnp reference then fused f32/bf16/int8."""
    from repro.launch.serve import CohortServer, planted_table

    embeds, labels = planted_table(n, K, DIM, SEED)
    rng = np.random.default_rng(SEED + 1)
    ref = None
    landmarks = {}
    for variant in ("jnp", "f32", "bf16", "int8"):
        print(f"[cohort:{variant}] N={n} d={DIM} k={K}", flush=True)
        srv = CohortServer(n, DIM, seed=SEED, policy="dqn",
                           config=cohort_config(variant), mesh=mesh)
        srv.update_embeddings(np.arange(n), embeds)
        c0, t0 = clock.seconds, time.perf_counter()
        ids, res = srv.select_cohort(COHORT)
        cold_s = time.perf_counter() - t0
        print(f"  smoke reading: cold select {cold_s:.3f}s wall "
              f"(engine solve {res.seconds:.3f}s, "
              f"compile {clock.seconds - c0:.3f}s)", flush=True)
        check(res.method == "sharded" and res.source == "cold",
              f"cold solve on the sharded path ({res.method}/{res.source})")
        check(len(ids) == COHORT and len(set(ids.tolist())) == COHORT,
              f"{COHORT} distinct clients drawn")
        p = purity(res.assign, labels)
        check(p >= 0.95, f"purity vs planted labels {p:.4f} >= 0.95")
        if ref is None:
            ref = res
        else:
            agree = agreement(res.assign, ref.assign)
            lead = float(np.max(np.abs(res.evals[:K] - ref.evals[:K])))
            print(f"  smoke reading: agreement with jnp {agree:.6f}, "
                  f"leading-{K} eval max diff {lead:.3e}", flush=True)
            if variant == "f32":
                check(agree >= 0.99, f"f32 partition agreement with the "
                      f"jnp reference {agree:.6f} >= 0.99")
                check(lead <= 1e-3, f"f32 leading {K} evals within 1e-3 "
                      f"of the jnp reference ({lead:.3e})")
        warm_s = []
        for r in range(ROUNDS):
            srv.observe_round(0.5 + 0.1 * r)
            srv.update_embeddings(
                ids, embeds[ids]
                + 0.01 * rng.normal(size=(len(ids), DIM)).astype(np.float32))
            t0 = time.perf_counter()
            ids, res = srv.select_cohort(COHORT)
            warm_s.append(time.perf_counter() - t0)
        srv.observe_round(0.9)
        st = srv.stats()
        print(f"  smoke reading: warm selects {[round(s, 4) for s in warm_s]}"
              f"s wall", flush=True)
        check(st["engine"]["warm_starts"] >= 1,
              f"churn rounds warm-started ({st['engine']['warm_starts']})")
        check(st["policy"]["train_calls"] == ROUNDS + 1
              and math.isfinite(st["policy"]["last_loss"]),
              f"DQN took {ROUNDS + 1} TD steps, finite loss "
              f"({st['policy']['last_loss']:.4g})")
        landmarks[variant] = len(srv.engine.state.landmark_idx)
        srv.close()
    return landmarks


def phase_kernels(n: int, m: int) -> None:
    """(b): the fused passes compile to TPU kernels; memory figures."""
    import functools

    import jax
    import jax.numpy as jnp

    from benchmarks.kernel_bench import analytic_peak_hbm_mb
    from repro.kernels import ops

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    passes = {
        "colsum": (ops.nystrom_colsum,
                   (f32(n, DIM), f32(m, DIM), f32(), f32(n))),
        "gram": (ops.nystrom_gram,
                 (f32(n, DIM), f32(m, DIM), f32(), f32(m), f32(m, m),
                  f32(n))),
        "extension": (ops.nystrom_extension,
                      (f32(n, DIM), f32(m, DIM), f32(), f32(m), f32(m, K),
                       f32(n))),
    }
    analytic = analytic_peak_hbm_mb(n, m, DIM, K, "fused")
    print(f"[kernels] N={n} m={m}: analytic peak_hbm_mb (fused) "
          f"{analytic:.1f}", flush=True)
    for dtype in ("f32", "bf16", "int8"):
        for name, (fn, shapes) in passes.items():
            compiled = jax.jit(functools.partial(
                fn, affinity_dtype=dtype)).lower(*shapes).compile()
            temp = compiled.memory_analysis().temp_size_in_bytes
            print(f"  smoke reading: {name}/{dtype} compiler temp "
                  f"{temp / 1e6:.1f} MB", flush=True)
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}/{dtype} runs as a compiled TPU kernel")


def phase_streaming(mesh, n: int = N_CLIENTS, versions: int = 3) -> None:
    """(c): background re-clustering over churned versions, no errors."""
    from repro.launch.serve import CohortServer, planted_table
    from repro.streaming import StreamingSpec

    print(f"[stream] N={n}, {versions} churned versions", flush=True)
    embeds, labels = planted_table(n, K, DIM, SEED)
    rng = np.random.default_rng(SEED + 2)
    srv = CohortServer(n, DIM, seed=SEED, config=cohort_config("f32"),
                       streaming=StreamingSpec(), mesh=mesh)
    try:
        srv.update_embeddings(np.arange(n), embeds)
        for v in range(versions):
            wait_until(lambda: srv.stats()["warm_ahead"] >= v + 1, 600,
                       f"background warm of version {v + 1}")
            ids, res = srv.select_cohort(COHORT)
            check(len(ids) == COHORT, f"version {v + 1}: cohort served")
            srv.update_embeddings(
                ids, embeds[ids]
                + 0.01 * rng.normal(size=(len(ids), DIM)).astype(np.float32))
    finally:
        srv.close(timeout=600)
    st = srv.stats()
    solver = st["streaming"]["solver"]
    print(f"  smoke reading: solver {solver}, served_warm "
          f"{st['served_warm']}, forced_inline {st['forced_inline']}",
          flush=True)
    check(solver["errors"] == 0, "zero background-solve errors")
    check(st["served_warm"] >= versions, "every select served warmed")
    p = purity(srv.engine.state.result.assign, labels)
    check(p >= 0.95, f"streamed partition purity {p:.4f} >= 0.95")


def phase_fl() -> None:
    """(d): the FL round loop with DQRE-SC cohort selection."""
    from repro.fed import FederatedRunner, RunnerConfig

    print(f"[fl] dqre_sc, {ROUNDS} rounds", flush=True)
    runner = FederatedRunner(RunnerConfig(
        dataset="mnist", num_clients=24, clients_per_round=6,
        local_steps=10, train_size=800, eval_size=128, policy="dqre_sc",
        num_clusters=3, embed_dim=4, seed=SEED))
    t0 = time.perf_counter()
    history = runner.run(ROUNDS)
    acc = [h.accuracy for h in history]
    print(f"  smoke reading: accuracy per round {acc}, "
          f"{time.perf_counter() - t0:.3f}s wall", flush=True)
    chance = 1.0 / runner.spec.num_classes
    check(len(acc) == ROUNDS and all(math.isfinite(a) for a in acc),
          f"{ROUNDS} rounds with finite accuracy")
    check(acc[-1] > chance, f"final accuracy {acc[-1]:.4f} > {chance:.3f}")


def phase_four_chips(clock) -> None:
    """--chips 4: the sharded cohort path on a 4-way mesh vs 1 chip."""
    import jax

    from repro.launch.mesh import make_cohort_mesh
    from repro.launch.serve import CohortServer, planted_table

    check(len(jax.devices()) == 4, f"4 devices ({len(jax.devices())})")
    mesh4, mesh1 = make_cohort_mesh(4), make_cohort_mesh(1)

    def solve(n, embeds, variant, mesh):
        srv = CohortServer(n, DIM, seed=SEED, config=cohort_config(variant),
                           mesh=mesh)
        srv.update_embeddings(np.arange(n), embeds)
        c0, t0 = clock.seconds, time.perf_counter()
        ids, res = srv.select_cohort(COHORT)
        print(f"  smoke reading: {mesh.devices.size}-way cold select "
              f"{time.perf_counter() - t0:.3f}s wall (compile "
              f"{clock.seconds - c0:.3f}s)", flush=True)
        check(res.method == "sharded" and len(ids) == COHORT,
              f"{mesh.devices.size}-way sharded select served a cohort")
        srv.close()
        return res

    embeds, labels = planted_table(N_CLIENTS, K, DIM, SEED)
    for variant in ("jnp", "f32"):
        print(f"[4chip:{variant}] N={N_CLIENTS}: 4-way vs 1-way", flush=True)
        r4 = solve(N_CLIENTS, embeds, variant, mesh4)
        r1 = solve(N_CLIENTS, embeds, variant, mesh1)
        agree = agreement(r4.assign, r1.assign)
        lead = float(np.max(np.abs(r4.evals[:K] - r1.evals[:K])))
        print(f"  smoke reading: 4-way vs 1-way agreement {agree:.6f}, "
              f"leading-{K} eval max diff {lead:.3e}", flush=True)
        check(agree >= 0.99, f"4-way vs 1-way agreement {agree:.6f} >= 0.99")
        check(lead <= 1e-3, f"leading {K} evals within 1e-3 ({lead:.3e})")
        check(purity(r4.assign, labels) >= 0.95, "4-way purity >= 0.95")
    del embeds, labels

    print(f"[4chip:f32] N={N_CLIENTS_4CHIP} on the 4-way mesh", flush=True)
    embeds, labels = planted_table(N_CLIENTS_4CHIP, K, DIM, SEED)
    res = solve(N_CLIENTS_4CHIP, embeds, "f32", mesh4)
    p = purity(res.assign, labels)
    check(p >= 0.95, f"N={N_CLIENTS_4CHIP} purity {p:.4f} >= 0.95")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded cohort path on a 4-chip mesh")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_cohort_mesh

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(clock)
    else:
        mesh = make_cohort_mesh(1)
        check(mesh.devices.size == 1, "cohort mesh spans exactly one chip")
        m = phase_cohort(mesh, clock)
        phase_kernels(N_CLIENTS, m["f32"])
        phase_streaming(mesh)
        phase_fl()
    devices = jax.devices()
    print(f"[done] {time.perf_counter() - t0:.1f}s wall, compile "
          f"{clock.seconds:.1f}s, (peak_bytes_in_use, peak_bytes_reserved) "
          f"per device {peak_bytes(devices)} (smoke readings)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
