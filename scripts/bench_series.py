"""One cell of the benchmark as ``benchmark/run.py`` runs it, and beside its
result the window's reading of series that the program exports and no metric
reads yet (a per-layer metric waits for a ``benchmark`` issue; until then a
PR reads its new counter over exactly the window through this):

    chiprun -- python3 scripts/bench_series.py \\
        --series app_tpu_prefill_attn_visit_ratio -- \\
        --workload openpangu-ultra-moe-718b-ep16.longdoc --seed 7 \\
        --seconds 51 --trace 1

Everything after ``--`` is ``benchmark/run.py``'s own command line; its result
line stays the last on standard output. Before it, one ``series`` fact: for
each name the window's delta, and for a histogram its count and mean (delta
of ``_sum`` over delta of ``_count``), summed over label sets. The same goes
to ``chiprun_out/benchmark/<cell>/series.json``. Nothing of the benchmark is
changed: the window's two scrapes are the ones ``run.py`` takes already.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    cut = sys.argv.index("--") if "--" in sys.argv else len(sys.argv)
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--series", required=True,
                        help="names with commas: counters, or histograms "
                             "without their _sum / _count suffix")
    args = parser.parse_args(sys.argv[1:cut])
    sys.argv = [os.path.join(CHECKOUT, "benchmark", "run.py"), *sys.argv[cut + 1:]]
    spec = importlib.util.spec_from_file_location("bench_run", sys.argv[0])
    run = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = run  # its dataclasses look their module up
    spec.loader.exec_module(run)
    measure = run.serve_and_measure

    def measure_and_read(cell, cli):
        got = measure(cell, cli)
        start, end = got.run.prom_start, got.run.prom_end
        delta = lambda s: run.prom.total(end, s) - run.prom.total(start, s)  # noqa: E731
        read = {}
        for name in args.series.split(","):
            count = delta(f"{name}_count")
            read[name] = (
                {"count": count, "mean": delta(f"{name}_sum") / count}
                if count else {"delta": delta(name)}
            )
        run.fact("series", workload=cell.name, **read)
        os.makedirs(run.out_dir(cell.name), exist_ok=True)
        with open(os.path.join(run.out_dir(cell.name), "series.json"), "w") as fh:
            json.dump(read, fh, indent=1)
        return got

    run.serve_and_measure = measure_and_read
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
