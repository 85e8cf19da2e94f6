"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus per-row extras).  Scale note:
CPU container, batch 2^13-2^14 vs the paper's 2^28 on a GV100; the curves'
*shapes* (who wins where, how throughput scales with density/multiplicity/
shards) are the reproduction target — see EXPERIMENTS.md §Paper-claims.

Usage::

    python -m benchmarks.run [fig5|...|fig9|fig10 ...] [--csv PATH] [--json PATH]

Any number of figures may be named (e.g. ``fig7 fig8``); none means all.

``--csv PATH`` mirrors every CSV row (header + data, comments excluded)
into PATH; ``--json PATH`` writes the parsed rows — name, us_per_call and
ops/s — as a perf-trajectory JSON (BENCH_<pr>.json files), so the
trajectory is machine-readable instead of empty shell redirections.
Set ``REPRO_BENCH_SMOKE=1`` for the small smoke config (CI).
"""

from __future__ import annotations

import argparse
import json
import time


#: non-numeric extras lifted into first-class (string) JSON fields;
#: everything else non-numeric stays in the joined ``extra`` string only
STRING_FIELDS = ("geometry",)


def parse_row(line: str):
    """CSV row -> {name, us_per_call, ops_per_s, extra?} (None if header/na).

    Numeric ``k=v`` extras (``probe_len_p99=4``, ``spread=0.03``, ...) are
    lifted into first-class fields of the JSON row; non-numeric ones stay
    in the joined ``extra`` string only, except the declared
    ``STRING_FIELDS`` (``geometry=p8191xW32``), which are lifted verbatim.
    """
    parts = line.split(",")
    if len(parts) < 3 or parts[0] == "name":
        return None
    try:
        us = float(parts[1])
    except ValueError:
        return None
    entry = {"name": parts[0], "us_per_call": us}
    if parts[2].endswith("Mops/s"):
        entry["ops_per_s"] = float(parts[2][:-len("Mops/s")]) * 1e6
    extras = [p for p in parts[3:] if p]
    if extras:
        entry["extra"] = ",".join(extras)
        for p in extras:
            k, sep, v = p.partition("=")
            if sep and k and k not in entry:
                try:
                    entry[k] = float(v)
                except ValueError:
                    if k in STRING_FIELDS:
                        entry[k] = v
    return entry


def main(argv=None) -> None:
    from benchmarks import (fig5_single_value, fig6_weak_scaling,
                            fig7_multi_value, fig8_metagenomics,
                            fig9_relational, fig10_churn, fig11_stream,
                            fig12_serve)
    figures = {
        "fig5": fig5_single_value.run,
        "fig6": fig6_weak_scaling.run,
        "fig7": fig7_multi_value.run,
        "fig8": fig8_metagenomics.run,
        "fig9": fig9_relational.run,
        "fig10": fig10_churn.run,
        "fig11": fig11_stream.run,
        "fig12": fig12_serve.run,
    }
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="*", choices=sorted(figures),
                    help="run only the named figure(s); default: all")
    ap.add_argument("--csv", metavar="PATH",
                    help="also write the CSV rows to PATH")
    ap.add_argument("--json", metavar="PATH",
                    help="write parsed rows (ops/s per figure) to PATH")
    ap.add_argument("--iters", type=int, metavar="N",
                    help="override timing iterations for every row "
                         "(util.ITERS_OVERRIDE)")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    print(f"# compile cache: {compile_cache.configure()}")
    if args.iters:
        from benchmarks import util
        util.ITERS_OVERRIDE = args.iters

    sink = open(args.csv, "w") if args.csv else None
    records: dict[str, list] = {}
    current = [None]

    def out(line: str) -> None:
        print(line, flush=True)
        if sink and not line.startswith("#"):
            sink.write(line + "\n")
            sink.flush()
        entry = parse_row(line)
        if entry is not None and current[0] is not None:
            records.setdefault(current[0], []).append(entry)

    try:
        out("name,us_per_call,derived,extra")
        for name, fn in figures.items():
            if args.only and name not in args.only:
                continue
            current[0] = name
            t0 = time.time()
            fn(out)
            out(f"# {name} done in {time.time() - t0:.1f}s")
    finally:
        if sink:
            sink.close()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {sum(map(len, records.values()))} rows to {args.json}")


if __name__ == "__main__":
    main()
