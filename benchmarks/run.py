"""Benchmark entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Scale knobs:
BENCH_RELEASES (default 2000 releases ~ 100k nodes), BENCH_REPEATS.

``--smoke`` shrinks every knob (tiny corpus, one repeat, sub-second service
sweep) so CI and local sanity checks share this entry point and finish in
seconds; it must stay fast enough to run on every push.  (The cluster
section keeps its own corpus floor — sharding a toy corpus measures
nothing — so it dominates smoke wall time.)

``--json PATH`` additionally writes every section's rows to a machine-
readable file (CI uploads it as a workflow artifact, so perf history is
diffable across runs).  Sections that print a CSV header also get
``records``: each row parsed into a dict keyed by the header columns — the
cluster section's rows carry their ``transport`` there, so thread vs
process trajectories stay comparable across PRs without re-parsing CSV.
"""
import argparse
import json
import os
import sys
import time


class _Tee:
    """Mirror stdout while collecting lines for the JSON report."""

    def __init__(self, stream):
        self.stream = stream
        self.lines: list[str] = []
        self._buf = ""

    def write(self, text: str) -> int:
        self.stream.write(text)
        self._buf += text
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line:
                self.lines.append(line)
        return len(text)

    def flush(self) -> None:
        self.stream.flush()


def _records(lines: list[str]) -> list[dict]:
    """Parse a section's CSV rows into dicts (first data line = header).

    Comment lines (``#``) and non-CSV chatter are skipped; ragged rows keep
    the columns both sides agree on (zip is deliberately non-strict).
    """
    header: list[str] | None = None
    records: list[dict] = []
    for line in lines:
        if line.startswith("#") or "," not in line:
            continue
        parts = [p.strip() for p in line.split(",")]
        if header is None:
            header = parts
            continue
        records.append(dict(zip(header, parts)))
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny sizes: seconds, not minutes (CI and sanity checks)",
    )
    ap.add_argument(
        "--section", default=None,
        help="run only sections whose title contains this substring",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the rows of every section to a JSON report",
    )
    args = ap.parse_args(argv)

    if args.smoke:
        # must happen before the sections (and benchmarks.common) import
        os.environ.setdefault("BENCH_RELEASES", "60")
        os.environ.setdefault("BENCH_REPEATS", "1")
        os.environ.setdefault("BENCH_SERVICE_SMOKE", "1")

    from . import (
        bench_algorithms,
        bench_category,
        bench_cluster,
        bench_db_size,
        bench_index_size,
        bench_kernels,
        bench_query_length,
        bench_search_hillclimb,
        bench_service,
        bench_table_properties,
        bench_vectorized,
    )

    sections = [
        ("tables II/III (query & keyword properties)", bench_table_properties),
        ("fig 8 / experiment I (categories)", bench_category),
        ("fig 9 / experiment II (query length)", bench_query_length),
        ("fig 10 / experiment III (database size)", bench_db_size),
        ("fig 11 / experiment IV (algorithms)", bench_algorithms),
        ("§IV-F (index size)", bench_index_size),
        ("beyond-paper: vectorized backends", bench_vectorized),
        ("beyond-paper: per-kernel microbench", bench_kernels),
        ("beyond-paper: search perf hillclimb", bench_search_hillclimb),
        ("beyond-paper: query service throughput", bench_service),
        ("beyond-paper: cluster scatter-gather throughput", bench_cluster),
    ]
    if args.section:
        sections = [(t, m) for t, m in sections if args.section in t]
    t0 = time.time()
    report = {"smoke": bool(args.smoke), "sections": []}
    for title, mod in sections:
        print(f"# --- {title} ---", flush=True)
        tee = _Tee(sys.stdout)
        sys.stdout = tee
        try:
            t_sec = time.time()
            mod.run()
        finally:
            sys.stdout = tee.stream
        report["sections"].append(
            {
                "title": title,
                "rows": tee.lines,
                "records": _records(tee.lines),
                "elapsed_s": round(time.time() - t_sec, 2),
            }
        )
    report["elapsed_s"] = round(time.time() - t0, 2)
    print(f"# done in {report['elapsed_s']}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {args.json}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
