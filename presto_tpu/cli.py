"""Interactive SQL console.

Reference analog: ``presto-cli`` (``cli/Console.java`` — jline REPL
with aligned table output and \\-commands).  Runs either in-process
(embedded QueryRunner over the TPC-H catalog) or against a coordinator
via --server.

Usage:
  python -m presto_tpu.cli [--server URI] [--sf 0.01] [-e "SQL"]
"""

from __future__ import annotations

import argparse
import sys
import time


def format_table(names, rows, max_rows: int = 200) -> str:
    cols = [str(n) for n in names]
    shown = rows[:max_rows]
    cells = [[("NULL" if v is None else str(v)) for v in r] for r in shown]
    widths = [
        max(len(cols[i]), *(len(r[i]) for r in cells)) if cells else len(cols[i])
        for i in range(len(cols))
    ]
    sep = "-+-".join("-" * w for w in widths)
    out = [" | ".join(c.ljust(w) for c, w in zip(cols, widths)), sep]
    for r in cells:
        out.append(" | ".join(c.rjust(w) for c, w in zip(r, widths)))
    if len(rows) > max_rows:
        out.append(f"... ({len(rows) - max_rows} more rows)")
    return "\n".join(out)


def format_output(names, rows, fmt: str) -> str:
    """ALIGNED (default) | CSV | TSV | JSON — the reference CLI's
    --output-format set (cli/OutputFormat subset)."""
    if fmt == "ALIGNED":
        return format_table(names, rows)
    if fmt in ("CSV", "TSV"):
        import csv
        import io

        buf = io.StringIO()
        w = csv.writer(buf, delimiter="," if fmt == "CSV" else "\t",
                       lineterminator="\n")
        w.writerow(names)
        for r in rows:
            w.writerow(["" if v is None else v for v in r])
        return buf.getvalue().rstrip("\n")
    if fmt == "JSON":
        import json

        return "\n".join(
            json.dumps(dict(zip(names, r)), default=str) for r in rows)
    raise SystemExit(f"unknown output format {fmt!r}")


def _progress_text(stats: dict) -> str:
    """One-line render of statement-protocol progress stats (the
    reference CLI's status bar): queue position while waiting for
    admission, then percentage + the busiest stage."""
    parts = []
    if stats.get("state") == "QUEUED":
        pos = stats.get("queuePosition")
        parts.append(f"queued #{pos}" if pos is not None else "queued")
    pct = stats.get("progressPercentage")
    if pct is not None:
        parts.append(f"{pct:5.1f}%")
    stages = stats.get("stages") or []
    running = [s for s in stages if s.get("state") == "RUNNING"]
    show = (running or stages)[-1:]
    for s in show:
        tot = s.get("splitsTotal")
        parts.append(f"{s['stage']} {s['splitsDone']}/{tot if tot is not None else '?'}")
    return " ".join(parts)


class _ProgressLine:
    """Carriage-return progress line on stderr (suppressed when stderr
    is not a terminal unless --progress forces it)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._width = 0

    def update(self, stats: dict) -> None:
        if not self.enabled:
            return
        text = _progress_text(stats)
        pad = max(self._width - len(text), 0)
        sys.stderr.write("\r" + text + " " * pad)
        sys.stderr.flush()
        self._width = len(text)

    def clear(self) -> None:
        if self.enabled and self._width:
            sys.stderr.write("\r" + " " * self._width + "\r")
            sys.stderr.flush()
            self._width = 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="presto-tpu")
    ap.add_argument("--server", help="coordinator URI (default: embedded engine)")
    ap.add_argument("--sf", type=float, default=0.01, help="embedded TPC-H scale factor")
    ap.add_argument("-e", "--execute", help="run one statement and exit")
    ap.add_argument("--output-format", default="ALIGNED",
                    choices=["ALIGNED", "CSV", "TSV", "JSON"],
                    help="result rendering (reference --output-format)")
    ap.add_argument("--progress", action="store_true",
                    help="render a live progress line even when stderr "
                         "is not a terminal")
    ap.add_argument("--platform", default=None,
                    help="force the jax backend (e.g. cpu)")
    ap.add_argument("--doctor", action="store_true",
                    help="print the query doctor's ranked bottleneck "
                         "findings after each statement")
    args = ap.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    show_progress = args.progress or sys.stderr.isatty()

    if args.server:
        from presto_tpu.client import StatementClient

        client = StatementClient(args.server)

        def run(sql, line):
            columns, rows = client.execute(
                sql, on_progress=line.update if line.enabled else None)
            findings = None
            if args.doctor and client.last_query_id:
                try:
                    findings = client.doctor(
                        client.last_query_id).get("findings")
                except Exception:
                    findings = None  # no telemetry (DDL, old server)
            return [c["name"] for c in columns], rows, findings
    else:
        from presto_tpu.catalog import Catalog
        from presto_tpu.connectors.tpch import Tpch
        from presto_tpu.runner import QueryRunner

        catalog = Catalog()
        catalog.register("tpch", Tpch(sf=args.sf))
        runner = QueryRunner(catalog)

        def run(sql, line):
            if not line.enabled:
                res = runner.execute(sql)
                return res.names, res.rows, getattr(res, "findings", None)
            # embedded: execute on a worker thread and poll the
            # process progress registry from here (the same numbers
            # the statement protocol serves)
            import threading
            import uuid

            from presto_tpu import obs

            qid = "cli_" + uuid.uuid4().hex[:12]
            box = {}

            def go():
                try:
                    box["res"] = runner.execute(sql, query_id=qid)
                except BaseException as e:
                    box["err"] = e

            t = threading.Thread(target=go, daemon=True,
                                 name=f"cli-query-{qid}")
            t.start()
            while t.is_alive():
                t.join(timeout=0.1)
                prog = obs.progress_for(qid)
                if prog is not None:
                    line.update(prog.snapshot())
            if "err" in box:
                raise box["err"]
            res = box["res"]
            return res.names, res.rows, getattr(res, "findings", None)

    def run_one(sql: str) -> int:
        t0 = time.perf_counter()
        line = _ProgressLine(show_progress)
        try:
            names, rows, findings = run(sql, line)
        except Exception as e:
            line.clear()
            print(f"error: {e}", file=sys.stderr)
            return 1
        line.clear()
        print(format_output(names, rows, args.output_format))
        if args.output_format == "ALIGNED":
            print(f"({len(rows)} rows, {time.perf_counter() - t0:.2f}s)")
        if args.doctor and findings is not None:
            from presto_tpu.obs.doctor import format_findings

            print(format_findings(findings), file=sys.stderr)
        return 0

    if args.execute:
        return run_one(args.execute)

    print(f"presto-tpu console ({'server ' + args.server if args.server else f'embedded tpch sf={args.sf}'})")
    buf = ""
    while True:
        try:
            line = input("... " if buf else "presto-tpu> ")
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        if not buf and line.strip().lower() in ("quit", "exit", "\\q"):
            return 0
        buf = (buf + "\n" + line) if buf else line
        if buf.strip().endswith(";") or line == "":
            sql = buf.strip().rstrip(";")
            buf = ""
            if sql:
                run_one(sql)


if __name__ == "__main__":
    sys.exit(main())
