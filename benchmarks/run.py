"""Benchmark harness — one section per paper table/figure + the system
benches. Prints ``name,us_per_call,derived`` CSV to stdout (one row per
bench; a failing section emits a ``<title>/ERROR`` row and the harness
keeps going, then exits non-zero). Invoke from the repo root:

  PYTHONPATH=src:. python benchmarks/run.py        # or: make bench

Sections:
  fig2/*      paper Fig. 2  (accuracy vs epochs per train-set size)
  fig3/*      paper Fig. 3  (per-epoch time / memory vs train-set size)
  fig4/*      paper Fig. 4  (float64 vs float32)
  fl/*        federated rounds (fedsgd/fedavg), loop-vs-cohort scaling
              curve (DESIGN.md §9), paper Eq. (1) per tier, datacenter
              tier-scanned step per arch family
  kernels/*   Pallas kernels (interpret) vs jnp oracle
  roofline/*  dominant-bottleneck census over the dry-run sweep — needs
              ``PYTHONPATH=src python -m repro.launch.dryrun`` run first
              to populate experiments/dryrun/
"""
from __future__ import annotations


def _roofline_rows() -> list[tuple]:
    from benchmarks.roofline import load_records, terms
    recs = load_records()
    if not recs:
        return [("roofline/missing", 0.0,
                 "run PYTHONPATH=src python -m repro.launch.dryrun first")]
    rows = []
    census: dict[str, int] = {}
    for r in recs:
        t = terms(r)
        census[t["dominant"]] = census.get(t["dominant"], 0) + 1
        if r["mesh"] == "16x16" and r["shape"] == "train_4k":
            step_s = max(t["compute_s"], t["memory_s"], t["collective_s"])
            rows.append((f"roofline/{r['arch']}_train4k", step_s * 1e6,
                         f"dominant={t['dominant']};"
                         f"frac={t['roofline_frac']:.3f};"
                         f"6ND/HLO={t['model_over_hlo']:.2f}"))
    rows.append(("roofline/census", float(len(recs)),
                 ";".join(f"{k}={v}" for k, v in sorted(census.items()))))
    return rows


def main() -> int:
    from benchmarks import fl_bench, kernels_bench
    from benchmarks.paper_figs import fig2, fig3, fig4

    from benchmarks import ablation_agg, format_sweep
    sections = [
        ("paper figures", lambda: fig2() + fig3() + fig4()),
        ("format sweep (paper §7.1)", format_sweep.run),
        ("aggregation ablation (paper §3.2)", ablation_agg.run),
        ("federated system", fl_bench.run),
        ("kernels", kernels_bench.run),
        ("roofline", _roofline_rows),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for title, fn in sections:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            print(f"{title}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            failed += 1
    return 1 if failed else 0


if __name__ == "__main__":
    import sys

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
