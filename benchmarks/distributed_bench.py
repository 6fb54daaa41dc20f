"""Distributed SpTRSV scaling bench (experiments/BENCH_distributed.json).

Measures the sharded execution path (repro.solver.engines.ShardedEngine,
docs/distributed.md) on the paper analogues:

* a scaling curve over mesh sizes (1/2/4/8 forced host devices by
  default): per-solve wall time of the sharded sweep, transformed vs.
  untransformed, with correctness checked against the sequential
  reference at every size;
* the steps-vs-all_gathers table: `count_all_gathers` audits that every
  schedule issues exactly ONE all_gather family (synchronization barrier)
  per step, so the transformation's step reduction IS the barrier
  reduction the paper headlines.

The full run (`run()`, wired into `python -m benchmarks.run`) executes the
sweep, on the CPU backend, in a subprocess with XLA_FLAGS forcing 8 host
devices, keeping the parent's single-device view intact.  On an
accelerator it runs in-process over `jax.devices()`: a chip belongs to
one process, so no JAX child is started, and with a single device the
section is skipped with its reason printed.  `smoke_record()` runs
in-process at reduced scale on whatever devices the current process has
(the tier-1 / CI form — under the CI distributed job the process itself
is started with 8 forced host devices).

Timings are the sharded sweep only (the any-b preamble is a host/device
charge shared with the single-device path and benchmarked by
operator_bench); `transformed_not_slower` compares the two sweeps at
equal mesh size.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def _measure_record(scales=(0.08, 0.06), device_counts=(1, 2, 4, 8),
                    iters: int = 3, chunk: int = 64,
                    max_deps: int = 8) -> dict:
    """The in-process measurement pass (jax must already be initialized
    with however many devices the caller arranged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import AvgLevelCost, transform
    from repro.solver import (schedule_for_csr, schedule_for_transformed,
                              solve_csr_seq)
    from repro.solver.distributed import count_all_gathers, default_mesh
    from repro.solver.engines import sharded_engine
    from repro.sparse import build_levels, generators

    devs = jax.devices()
    counts = [d for d in device_counts if d <= len(devs)]
    rec = {
        "config": {"scales": list(scales), "device_counts": counts,
                   "iters": iters, "chunk": chunk, "max_deps": max_deps,
                   "backend": devs[0].platform, "num_devices": len(devs)},
        "matrices": {},
    }
    for name, L in (
            (f"lung2_like@{scales[0]}", generators.lung2_like(scales[0])),
            (f"torso2_like@{scales[1]}", generators.torso2_like(scales[1]))):
        b = np.random.default_rng(0).standard_normal(L.n_rows)
        x_ref = solve_csr_seq(L, b)
        xscale = max(1.0, float(np.abs(x_ref).max()))
        s0 = schedule_for_csr(L, build_levels(L), chunk=chunk,
                              max_deps=max_deps)
        ts = transform(L, AvgLevelCost(), validate=False, codegen=False)
        s1 = schedule_for_transformed(ts, chunk=chunk, max_deps=max_deps)
        c1 = ts.preamble(b)
        g0, g1 = count_all_gathers(s0), count_all_gathers(s1)
        entry = {
            "n": L.n_rows, "nnz": L.nnz,
            "steps": {"no_rewriting": s0.num_steps,
                      "transformed": s1.num_steps},
            # one all_gather family (synchronization barrier) per step —
            # the invariant tests assert on the committed artifact
            "all_gathers": {"no_rewriting": g0["families"],
                            "transformed": g1["families"]},
            "all_gather_calls": {"no_rewriting": g0["calls"],
                                 "transformed": g1["calls"]},
            "curve": [],
        }

        def timed(fn, c):
            x = np.asarray(fn(c))               # compile outside the timer
            best = float("inf")
            for _ in range(iters):
                t0 = time.perf_counter()
                jax_block(fn(c))
                best = min(best, time.perf_counter() - t0)
            return x, best * 1e6

        jax_block = jax.block_until_ready
        for d in counts:
            mesh = default_mesh(devices=devs[:d])
            eng = sharded_engine(mesh)
            fn0, fn1 = eng.compile(s0), eng.compile(s1)
            x0, us0 = timed(fn0, jnp.asarray(b, s0.dtype))
            x1, us1 = timed(fn1, jnp.asarray(c1, s1.dtype))
            entry["curve"].append({
                "devices": d,
                "no_rewriting_us": round(us0, 1),
                "transformed_us": round(us1, 1),
                "err_no_rewriting": float(np.abs(x0 - x_ref).max() / xscale),
                "err_transformed": float(np.abs(x1 - x_ref).max() / xscale),
            })
        entry["transformed_not_slower"] = any(
            p["transformed_us"] <= p["no_rewriting_us"]
            for p in entry["curve"])
        rec["matrices"][name] = entry
    rec["transformed_not_slower_any"] = any(
        m["transformed_not_slower"] for m in rec["matrices"].values())
    return rec


def smoke_record(scales=(0.02, 0.02), iters: int = 1) -> dict:
    """Reduced-scale in-process pass over the available devices (the
    `distributed_smoke` section of benchmarks/run.py --smoke)."""
    return _measure_record(scales=scales, device_counts=(1, 2, 4, 8),
                           iters=iters, chunk=32, max_deps=4)


def run(out_path="experiments/BENCH_distributed.json", scales=(0.08, 0.06),
        device_counts=(1, 2, 4, 8), iters: int = 3,
        forced_devices: int = 8, timeout: int = 1200) -> dict:
    """Full sweep; writes the artifact when `out_path` is given.  On the
    CPU backend it runs in a subprocess with `forced_devices` forced host
    devices (the parent process keeps its own device view); on an
    accelerator in-process over `jax.devices()` (module doc), returning
    None when there is only one device."""
    import jax
    if jax.default_backend() != "cpu":
        devs = jax.devices()
        if len(devs) < 2:
            print(f"distributed section skipped: the sharded sweep needs "
                  f"2+ devices, this process has one "
                  f"{devs[0].platform} device ({devs[0].device_kind})")
            return None
        rec = _measure_record(scales=scales, device_counts=device_counts,
                              iters=iters)
    else:
        rec = _forced_host_record(scales, device_counts, iters,
                                  forced_devices, timeout)
    if out_path:
        p = Path(out_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(rec, indent=2) + "\n")
    _print_summary(rec)
    return rec


def _forced_host_record(scales, device_counts, iters: int,
                        forced_devices: int, timeout: int) -> dict:
    """`_measure_record` in a CPU-only child with `forced_devices` forced
    host devices."""
    payload = {"scales": list(scales), "device_counts": list(device_counts),
               "iters": iters}
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        [env.get("XLA_FLAGS", ""),
         f"--xla_force_host_platform_device_count={forced_devices}"]).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.distributed_bench", "--worker",
         json.dumps(payload)],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env,
        timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"distributed bench worker failed:\n"
                           f"{out.stderr[-4000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _print_summary(rec: dict) -> None:
    for name, m in rec["matrices"].items():
        st, ag = m["steps"], m["all_gathers"]
        print(f"{name}: steps {st['no_rewriting']} -> {st['transformed']}, "
              f"all_gather families {ag['no_rewriting']} -> "
              f"{ag['transformed']} "
              f"(-{1 - st['transformed'] / st['no_rewriting']:.0%} barriers)")
        for p in m["curve"]:
            print(f"  devices={p['devices']}: no_rewriting "
                  f"{p['no_rewriting_us']:.0f}us, transformed "
                  f"{p['transformed_us']:.0f}us")


def main() -> None:
    if "--worker" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--worker") + 1])
        rec = _measure_record(scales=tuple(cfg["scales"]),
                              device_counts=tuple(cfg["device_counts"]),
                              iters=cfg["iters"])
        print(json.dumps(rec))
        return
    run()


if __name__ == "__main__":
    main()
