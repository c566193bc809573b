# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure (see DESIGN.md §6).

  PYTHONPATH=src python -m benchmarks.run              # all
  PYTHONPATH=src python -m benchmarks.run fig9 table4  # subset
"""
from __future__ import annotations

import sys
import time
import traceback

MODULES = ["fig2_metric_pk", "fig3_k_quartiles", "fig46_fit",
           "fig9_effectiveness", "table4_efficiency", "table5_memory",
           "fig10_scalability", "roofline", "bench_service"]


def main() -> int:
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    want = [a for a in sys.argv[1:] if not a.startswith("-")]
    mods = [m for m in MODULES if not want or any(w in m for w in want)]
    print("name,us_per_call,derived")
    failed = []
    for mod_name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            for row in mod.run():
                print(",".join(str(x) for x in row), flush=True)
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            print(f"# {mod_name} FAILED:", flush=True)
            traceback.print_exc()
            failed.append(mod_name)
    if failed:
        print(f"# failed: {', '.join(failed)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
