"""Kernel micro-benchmarks: jnp reference path timings (the production
CPU path) + interpret-mode Pallas validation cost.  On TPU the same
harness times the compiled kernels.

Standalone entry for the CI gate on the fused Nyström pipeline:

  PYTHONPATH=src python -m benchmarks.kernel_bench --small --check

--small shrinks the fused sweep to CI size (and skips rewriting
``BENCH_cohort.json``); --check fails the process unless the fused
pipeline matches the unfused oracle (partition + leading evals) and the
quantized tile precisions hold the purity floor."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(fn, *args, iters=5):
    fn(*args)                      # compile / warm
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def _bench_spectral_selection(csv_rows, key):
    """Dense Algorithm I vs the Nyström landmark path.

    At n = 4096 the dense path pays O(n²d) affinity + O(n³) eigh; the
    Nyström path with m = n/8 landmarks is O(n·m·d + m³) and should be
    >= 10x faster wall-clock.  The 100k row demonstrates the cohort scale
    the dense path cannot reach at all (10¹⁰-entry affinity matrix).
    """
    from repro.core.spectral import spectral_cluster

    n, d, k, m = 4096, 8, 8, 512
    x = jax.random.normal(key, (n, d), jnp.float32) * 4.0

    us_dense = _time(
        lambda a: spectral_cluster(key, a, k, method="dense"), x, iters=1)
    us_nys = _time(
        lambda a: spectral_cluster(key, a, k, method="nystrom",
                                   num_landmarks=m), x, iters=1)
    csv_rows.append((f"spectral/dense/n{n}", us_dense, ""))
    csv_rows.append((f"spectral/nystrom_m{m}/n{n}", us_nys,
                     f"speedup={us_dense / us_nys:.1f}x"))

    n_big = 100_000
    xb = jax.random.normal(jax.random.fold_in(key, 7), (n_big, d)) * 4.0
    us_big = _time(
        lambda a: spectral_cluster(key, a, k, method="nystrom",
                                   num_landmarks=m), xb, iters=1)
    csv_rows.append((f"spectral/nystrom_m{m}/n{n_big}", us_big,
                     f"clients_per_sec={n_big / (us_big / 1e6):.0f}"))


def _bench_cohort(csv_rows, key):
    """Dense vs Nyström vs sharded-Nyström cohort selection wall time.

    Engine-level end-to-end timings (landmarks + eigensolve + k-means)
    at the three cohort scales; dense is only feasible at n = 4096 (the
    32k/100k affinity matrices are 4/40 GB).  Emits ``BENCH_cohort.json``
    alongside the CSV rows so the sweep is machine-readable.
    """
    import json

    from repro.cohort import CohortConfig, CohortEngine

    k, d, m = 8, 8, 512
    devices = len(jax.devices())
    records = []
    for n in (4096, 32768, 100_000):
        x = jax.random.normal(jax.random.fold_in(key, n), (n, d),
                              jnp.float32) * 4.0
        x = jax.device_get(x)
        row = {"n": n, "devices": devices, "num_landmarks": m,
               "dense_us": None, "nystrom_us": None, "sharded_us": None}
        methods = (["dense"] if n <= 4096 else []) + ["nystrom", "sharded"]
        for method in methods:
            cfg = CohortConfig(
                num_clusters=k, method=method,
                num_landmarks=None if method == "dense" else m)

            def run_once(a, cfg=cfg):
                # fresh engine per call: the fingerprint cache would
                # otherwise turn the timed call into a no-op
                return CohortEngine(cfg, seed=0).select(a).assign

            us = _time(run_once, x, iters=1)
            row[f"{method}_us"] = us
            csv_rows.append((f"cohort/{method}/n{n}", us,
                             f"clients_per_sec={n / (us / 1e6):.0f}"))
        records.append(row)
    with open("BENCH_cohort.json", "w") as fh:
        json.dump({"unit": "us_per_select", "records": records}, fh,
                  indent=2)


def analytic_peak_hbm_mb(n: int, m: int, d: int, k: int,
                         variant: str) -> float:
    """Analytic peak-HBM estimate (f32 bytes) of each select variant.

    Counts the arrays that must coexist in device memory during the
    landmark solve: the dense path holds the n×n affinity; the unfused
    Nyström path holds C and its degree-scaled copy S (both (n, m))
    side by side; the fused streaming path holds NO (n, m) array — just
    the (n, d) input, the (n, k) output, and the m-sized replicated
    blocks, with each (block_m, m) affinity tile living only in VMEM.

    A count from shapes, not a measurement: for a v5e chip the
    compiler's ``memory_analysis()`` gives each fused pass ~1.5 GB of
    temp at n = 10⁶, d = 8 (the (n, d) rows and (n, 1) mask laid out at
    128 lanes), orders of magnitude above this estimate.
    """
    f32 = 4
    if variant == "dense":
        total = n * n + n * d
    elif variant == "unfused":
        total = 2 * n * m + n * d + n * k + 3 * m * m
    else:  # fused (any affinity_dtype: tiles are quantized in-register)
        total = n * d + n * k + 3 * m * m + m * k
    return total * f32 / 1e6


def _bench_fused(csv_rows, key, *, small: bool = False,
                 check: bool = False):
    """Fused streaming pipeline vs the materialized paths + CI gate.

    Timings follow the ``_bench_cohort`` convention (fresh engine per
    timed call, jit caches warm from the untimed first call).  On this
    CPU container the kernels run in interpret mode, so the fused path
    trades the eliminated (n, m) HBM traffic for a 3× recompute of the
    affinity tile.  Neither these CPU timings nor the analytic
    peak-memory column says anything about the chip (see
    docs/BENCHMARKS.md caveats).  ``check=True`` enforces the
    correctness gates: fused-f32 must reproduce the unfused partition
    and leading spectrum, and bf16/int8 must hold the purity floor on
    a non-IID fixture.
    """
    import json
    import os

    import numpy as np

    from repro.cohort import CohortConfig, CohortEngine

    k, d = 8, 8
    m = 256 if small else 512
    sizes = (4096,) if small else (4096, 100_000)
    variants = [
        ("unfused", dict()),
        ("fused_f32", dict(use_pallas=True)),
        ("fused_bf16", dict(use_pallas=True, affinity_dtype="bf16")),
        ("fused_int8", dict(use_pallas=True, affinity_dtype="int8")),
    ]
    records = []
    for n in sizes:
        x = jax.device_get(jax.random.normal(
            jax.random.fold_in(key, 31 * n), (n, d), jnp.float32) * 4.0)
        row = {"n": n, "num_landmarks": m, "dense_us": None,
               "peak_hbm_mb": {
                   v: round(analytic_peak_hbm_mb(n, m, d, k, v), 2)
                   for v in ("dense", "unfused", "fused")}}
        if n <= 4096:
            cfg = CohortConfig(num_clusters=k, method="dense")
            row["dense_us"] = _time(
                lambda a, cfg=cfg: CohortEngine(cfg, seed=0).select(a).assign,
                x, iters=1)
            csv_rows.append((f"fused/dense/n{n}", row["dense_us"], ""))
        for name, overrides in variants:
            cfg = CohortConfig(num_clusters=k, method="sharded",
                               num_landmarks=m, **overrides)
            us = _time(
                lambda a, cfg=cfg: CohortEngine(cfg, seed=0).select(a).assign,
                x, iters=1)
            row[f"{name}_us"] = us
            note = (f"peak_hbm_mb="
                    f"{row['peak_hbm_mb']['fused' if 'fused' in name else 'unfused']}")
            csv_rows.append((f"fused/{name}/n{n}", us, note))
        records.append(row)

    if not small:
        # fold the sweep into BENCH_cohort.json as the "fused" section
        # (additive: _bench_cohort owns "records")
        payload = {}
        if os.path.exists("BENCH_cohort.json"):
            with open("BENCH_cohort.json") as fh:
                payload = json.load(fh)
        payload["fused"] = {"unit": "us_per_select", "records": records}
        with open("BENCH_cohort.json", "w") as fh:
            json.dump(payload, fh, indent=2)

    if not check:
        return

    # -- correctness gates (the CI contract) ----------------------------
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(4, d)) * 8.0
    sizes_sk = [1500, 180, 180, 140]          # skewed non-IID population
    labels = np.repeat(np.arange(4), sizes_sk)
    xg = (centers[labels]
          + rng.normal(size=(len(labels), d))).astype(np.float32)

    def solve(**overrides):
        cfg = CohortConfig(num_clusters=4, method="sharded",
                           num_landmarks=128, **overrides)
        return CohortEngine(cfg, seed=0).select(xg)

    def purity(assign):
        assign = np.asarray(assign)
        return sum(np.bincount(labels[assign == c]).max()
                   for c in np.unique(assign)) / len(labels)

    r_jnp = solve()
    r_f32 = solve(use_pallas=True)
    same = bool(np.all(
        (np.asarray(r_jnp.assign)[:, None] == np.asarray(r_jnp.assign)[None])
        == (np.asarray(r_f32.assign)[:, None]
            == np.asarray(r_f32.assign)[None])))
    ev_gap = float(np.max(np.abs(np.asarray(r_jnp.evals)[:4]
                                 - np.asarray(r_f32.evals)[:4])))
    p_f32 = purity(r_f32.assign)
    failures = []
    if not same:
        failures.append("fused f32 partition != unfused partition")
    if ev_gap > 1e-3:
        failures.append(f"fused f32 leading evals off by {ev_gap:.2e} "
                        f"(tolerance 1e-3)")
    for dtype in ("bf16", "int8"):
        p_q = purity(solve(use_pallas=True, affinity_dtype=dtype).assign)
        csv_rows.append((f"fused/purity_{dtype}", 0.0, f"purity={p_q:.4f}"))
        if p_q < 0.95 or p_q < p_f32 - 1e-3:
            failures.append(
                f"{dtype} purity {p_q:.4f} under the floor "
                f"(0.95 and f32 {p_f32:.4f} - 1e-3)")
    if failures:
        raise SystemExit("fused gate FAILED: " + "; ".join(failures))
    print(f"fused gate OK: partition match, leading-evals gap "
          f"{ev_gap:.2e}, f32 purity {p_f32:.4f}")


def run(csv_rows: list) -> None:
    key = jax.random.PRNGKey(0)
    on_tpu = jax.default_backend() == "tpu"

    # pairwise distances (spectral clustering hotspot): n clients
    for n in (128, 512):
        x = jax.random.normal(key, (n, 16))
        us_ref = _time(jax.jit(ref.pairwise_sq_dists_ref), x, x)
        csv_rows.append((f"kernel/pairwise_ref/n{n}", us_ref,
                         f"bytes={n*n*4}"))
        if on_tpu:
            us_k = _time(lambda a, b: ops.pairwise_sq_dists(a, b), x, x)
            csv_rows.append((f"kernel/pairwise_pallas/n{n}", us_k, ""))
            z = x[:n // 8]
            us_c = _time(lambda a, b: ops.rbf_cross_affinity(a, b, 0.5),
                         x, z)
            csv_rows.append((f"kernel/cross_rbf_pallas/n{n}", us_c, ""))

    _bench_spectral_selection(csv_rows, key)
    _bench_cohort(csv_rows, key)
    _bench_fused(csv_rows, key)

    # flash attention jnp-blocked vs naive at growing S
    from repro.models.attention import blocked_attention
    for S in (256, 1024):
        q = jax.random.normal(key, (1, S, 4, 64), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, 2, 64))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, 2, 64))
        us_naive = _time(jax.jit(lambda a, b, c: ref.attention_ref(
            a, b, c, causal=True)), q, k, v)
        us_block = _time(jax.jit(lambda a, b, c: blocked_attention(
            a, b, c, causal=True)), q, k, v)
        csv_rows.append((f"kernel/attn_naive/S{S}", us_naive, ""))
        csv_rows.append((f"kernel/attn_blocked/S{S}", us_block,
                         f"vs_naive={us_block/us_naive:.2f}x"))

    # SSD chunked vs per-token scan cost proxy
    from repro.models import mamba as M
    from repro.configs import get_config
    cfg = get_config("mamba2-2.7b").reduced()
    p = M.mamba_init(key, cfg)
    x = jax.random.normal(key, (2, 128, cfg.d_model))
    us_ssd = _time(jax.jit(lambda a: M.mamba_apply(p, a, cfg)[0]), x)
    csv_rows.append(("kernel/ssd_chunked/S128", us_ssd, ""))


def main() -> None:
    """Standalone fused-pipeline sweep + CI gate (see module docstring)."""
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--small", action="store_true",
                    help="CI-sized fused sweep (n=4096, m=256); does not "
                         "rewrite BENCH_cohort.json")
    ap.add_argument("--check", action="store_true",
                    help="fail unless fused==unfused (partition + leading "
                         "evals) and bf16/int8 hold the purity floor")
    args = ap.parse_args()
    csv_rows: list = []
    _bench_fused(csv_rows, jax.random.PRNGKey(0), small=args.small,
                 check=args.check)
    for name, us, note in csv_rows:
        print(f"{name},{us:.0f},{note}")


if __name__ == "__main__":
    main()
